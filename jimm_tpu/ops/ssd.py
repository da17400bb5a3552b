"""The selective state-space scan of Mamba-2 (state-space duality), in chunks.

Per head ``h`` with a state ``S (P, N)`` that starts at zero, a scalar decay
``exp(dt_t A_h)`` (``dt_t >= 0``, ``A_h <= 0``) and inputs ``B_t, C_t`` shared
by the heads of a group:

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t

`chunk_ssd` computes it in chunks of ``L`` tokens. With ``a_t = dt_t A_h``
and every sum over the tokens of one chunk:

    E_rj    = sum_{j < s <= r} a_s               j <= r, else -inf
    y_r     = sum_{j <= r} exp(E_rj) (C_r . B_j) dt_j x_j      within the chunk
              + exp(sum_{s <= r} a_s) S C_r                   from the state
    S'      = exp(sum_s a_s) S + sum_j exp(sum_{s > j} a_s) dt_j x_j B_j^T

``C B^T`` is one ``(L, L)`` product a chunk for all heads of a group.

**Every exponent is a segment sum of ``a`` over tokens, never a difference of
two running sums.** At the family's start a head loses up to 84 nats a token
and a chunk's running sum reaches 2 x 10^4, where float32's step is 2e-3: a
difference of two such sums would carry that error into every exponent. Here
a chunk is cut into sub-chunks of `_SUB` tokens, and a segment is the sum of
at most three parts, each a sum of same-signed terms (`_segment_sums`): the
tail of the key's sub-chunk, the whole sub-chunks between, and the head of the
row's sub-chunk; inside one sub-chunk the sum is taken term by term.

Three phases, the middle one the only sequential one:

1. per chunk, what it writes into the state from a state of zeros
   (`_writes`) and its whole decay (`_totals`);
2. a scan over the chunks: the state each chunk is entered with;
3. per chunk, ``y`` from its own tokens and from the state it was entered
   with (`_outputs`).

Phases 1 and 3 walk SLABS of chunks (`jax.lax.map`): no ``chunks x heads x L
x L`` intermediate is held across the sequence, only a slab's, under
`_PAIR_BYTES`. The backward is the scan's own (`_ssd`'s rule): it keeps the
inputs and the state each chunk is entered with (``ssm_states``: 134 MB a
layer at (1, 16384, 64, 64) with ``N`` 128 in chunks of 256), runs phase 3's
derivative by slabs, the state's cotangent backwards over the chunks, and
phase 1's derivative by slabs. A remat policy that keeps ``ssm_states`` and the
caller's copy of the output (`nn/transformer.py::Transformer._remat_policy`)
runs no second scan in a layer's backward. float32 inside, every product at
`_PRECISION`. This is XLA code: the oracle that a later kernel is held to.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

#: tokens of a sub-chunk: inside one the segment sums are taken term by term
#: (``_SUB^3`` a sub-chunk)
_SUB = 16
_PRECISION = jax.lax.Precision.HIGHEST
#: the most that one slab's ``(heads, L, L)`` matrices may take: two chunks of
#: 256 a slab at (1, 16384, 64 heads)
_PAIR_BYTES = 32 << 20


def _mm(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.einsum(spec, a, b, precision=_PRECISION,
                      preferred_element_type=jnp.float32)


def _segment_sums(a: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """For ``a (..., L)``, all of one sign: ``into_r = sum_{s <= r} a_s``,
    ``out_of_j = sum_{s > j} a_s`` and ``E (..., L, L)``, ``E_rj = sum_{j < s
    <= r} a_s`` on and below the diagonal and ``-inf`` above it, each a sum of
    same-signed parts."""
    *lead, L = a.shape
    k = math.gcd(L, _SUB)
    n = L // k
    parts = a.reshape(*lead, n, k)

    def masked_sum(mask: jax.Array, x: jax.Array) -> jax.Array:
        return jnp.sum(jnp.where(mask, x, 0.0), axis=-1)

    tri = jnp.arange(k)
    head = masked_sum(tri[None, :] <= tri[:, None], parts[..., None, :])
    tail = masked_sum(tri[None, :] > tri[:, None], parts[..., None, :])
    whole = jnp.sum(parts, axis=-1)                            # (..., n)
    blocks = jnp.arange(n)
    before = masked_sum(blocks[None, :] < blocks[:, None], whole[..., None, :])
    after = masked_sum(blocks[None, :] > blocks[:, None], whole[..., None, :])
    into = (head + before[..., None]).reshape(*lead, L)
    out_of = (tail + after[..., None]).reshape(*lead, L)
    # between the key's sub-chunk q and the row's p: the whole ones q < m < p
    between = masked_sum((blocks[None, :, None] < blocks[None, None, :])
                         & (blocks[None, None, :] < blocks[:, None, None]),
                         whole[..., None, None, :])             # (..., p, q)
    # inside one sub-chunk: the terms j < s <= r
    inside = masked_sum((tri[None, :, None] < tri[None, None, :])
                        & (tri[None, None, :] <= tri[:, None, None]),
                        parts[..., None, None, :])              # (..., n, r, j)
    off = (head[..., :, :, None, None] + between[..., :, None, :, None]
           + tail[..., None, None, :, :])                       # (..., p, r, q, j)
    same = (blocks[:, None] == blocks[None, :])[:, None, :, None]
    below = (blocks[:, None] > blocks[None, :])[:, None, :, None] | (
        same & (tri[:, None] >= tri[None, :])[None, :, None, :])
    pair = jnp.where(below, jnp.where(same, inside[..., :, :, None, :], off),
                     -jnp.inf)
    return into, out_of, pair.reshape(*lead, L, L)


def _grouped(x: jax.Array, groups: int) -> jax.Array:
    """``(..., H, rest...)`` at the heads' axis -> ``(..., G, H / G, ...)``."""
    return x.reshape(*x.shape[:2], groups, x.shape[2] // groups, *x.shape[3:])


def _totals(dt: jax.Array, A: jax.Array) -> jax.Array:
    """Each chunk's whole decay exponent, ``(slab, B, H)``."""
    return jnp.sum(dt * A[:, None], axis=-1)


def _writes(x, dt, B, A) -> jax.Array:
    """What each chunk of a slab writes into the state from zeros: ``sum_j
    exp(sum_{s > j} a_s) dt_j x_j B_j^T``, ``(slab, b, H, P, N)``."""
    _, out_of, _ = _segment_sums(dt * A[:, None])
    g = B.shape[2]
    xs = _grouped((jnp.exp(out_of) * dt)[..., None] * x.astype(jnp.float32), g)
    w = _mm("...gkjp,...gjn->...gkpn", xs, B.astype(jnp.float32))
    return w.reshape(*w.shape[:2], -1, *w.shape[-2:])


def _outputs(x, dt, B, C, A, entered) -> jax.Array:
    """``y (slab, b, H, L, P)`` of a slab's chunks from their own tokens and
    from the state each was entered with."""
    into, _, pair = _segment_sums(dt * A[:, None])
    g = B.shape[2]
    f32 = jnp.float32
    # one (L, L) product a chunk for all heads of a group, broadcast over them
    cb = _mm("...grn,...gjn->...grj", C.astype(f32), B.astype(f32))
    w = jnp.exp(_grouped(pair, g)) * cb[:, :, :, None] \
        * _grouped(dt, g)[..., None, :]
    y = _mm("...rj,...jp->...rp", w, _grouped(x.astype(f32), g))
    y = y + jnp.exp(_grouped(into, g))[..., None] * _mm(
        "...gkpn,...grn->...gkrp", _grouped(entered, g), C.astype(f32))
    return y.reshape(*y.shape[:2], -1, *y.shape[-2:])


def _enter(written: jax.Array, totals: jax.Array) -> jax.Array:
    """The state each chunk is entered with, ``(chunks, b, H, P, N)``."""
    def step(state, args):
        w, t = args
        return jnp.exp(t)[..., None, None] * state + w, state

    return jax.lax.scan(step, jnp.zeros_like(written[0]), (written, totals))[1]


def _unslab(x: jax.Array) -> jax.Array:
    return x.reshape(-1, *x.shape[2:])


def _forward(x, dt, B, C, A) -> tuple[jax.Array, jax.Array]:
    written = jax.lax.map(lambda s: _writes(s[0], s[1], s[2], A), (x, dt, B))
    totals = jax.lax.map(lambda d: _totals(d, A), dt)
    entered = _enter(_unslab(written), _unslab(totals)).reshape(written.shape)
    y = jax.lax.map(lambda s: _outputs(*s[:4], A, s[4]),
                    (x, dt, B, C, entered))
    return y, entered


@jax.custom_vjp
def _ssd(x, dt, B, C, A) -> jax.Array:
    return _forward(x, dt, B, C, A)[0]


def _ssd_fwd(x, dt, B, C, A):
    y, entered = _forward(x, dt, B, C, A)
    return y, (x, dt, B, C, A, checkpoint_name(entered, "ssm_states"))


def _ssd_bwd(residuals, dy):
    x, dt, B, C, A, entered = residuals
    f32 = jnp.float32

    # the parts of a gradient are summed in float32, then cast once
    def outputs_back(s):
        _, back = jax.vjp(_outputs, *(t.astype(f32) for t in s[:4]), A, s[4])
        return back(s[5])

    dx, ddt, dB, dC, dA, d_entered = jax.lax.map(
        outputs_back, (x, dt, B, C, entered, dy))
    dA = jnp.sum(dA, axis=0)
    totals = jax.lax.map(lambda d: _totals(d, A), dt)

    def state_back(g_next, args):
        # entered[c + 1] = exp(t_c) entered[c] + written[c]; g_next is the
        # cotangent of entered[c + 1] (zero after the last chunk)
        d_entered_c, entered_c, t = args
        decay = jnp.exp(t)[..., None, None]
        return d_entered_c + decay * g_next, (
            g_next, jnp.sum(g_next * decay * entered_c, axis=(-2, -1)))

    _, (d_written, d_totals) = jax.lax.scan(
        state_back, jnp.zeros_like(entered[0, 0]),
        (_unslab(d_entered), _unslab(entered), _unslab(totals)), reverse=True)
    d_written = d_written.reshape(entered.shape)
    d_totals = d_totals.reshape(totals.shape)

    def writes_back(s):
        _, back_w = jax.vjp(_writes, s[0].astype(f32), s[1],
                            s[2].astype(f32), A)
        _, back_t = jax.vjp(_totals, s[1], A)
        dx, ddt, dB, dA = back_w(s[3])
        ddt_t, dA_t = back_t(s[4])
        return dx, ddt + ddt_t, dB, dA + dA_t

    dx1, ddt1, dB1, dA1 = jax.lax.map(writes_back,
                                      (x, dt, B, d_written, d_totals))
    return ((dx + dx1).astype(x.dtype), (ddt + ddt1).astype(dt.dtype),
            (dB + dB1).astype(B.dtype), dC.astype(C.dtype),
            (dA + jnp.sum(dA1, axis=0)).astype(A.dtype))


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def chunk_ssd(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
              C: jax.Array, *, chunk: int = 256) -> jax.Array:
    """``y (B, S, H, P)`` float32 of the recurrence above, without a ``D``
    term, for ``x (B, S, H, P)``, ``dt (B, S, H)`` float32 (after the
    softplus, at least 0), ``A (H,)`` at most 0, and ``B, C (B, S, G, N)``
    with ``G`` dividing ``H`` (head ``h`` reads group ``h // (H / G)``). A
    length that is no multiple of ``chunk`` is padded with tokens of ``dt =
    0``, which leave the state alone."""
    b, s, h, _ = x.shape
    g = B.shape[2]
    if h % g:
        raise ValueError(f"{g} groups do not divide {h} heads")
    n = -(-s // chunk)
    from jimm_tpu.obs.registry import get_registry
    registry = get_registry("jimm_ssm")
    registry.counter("calls_total").inc()
    registry.counter("chunks_total").inc(n)
    slab = min(n, max(1, _PAIR_BYTES // (b * h * chunk * chunk * 4)))
    while n % slab:
        slab -= 1

    def slabs(t: jax.Array) -> jax.Array:
        # (B, S, heads, ...) -> (slabs, slab, B, heads, L, ...)
        t = jnp.pad(t, [(0, 0), (0, n * chunk - s)] + [(0, 0)] * (t.ndim - 2))
        t = jnp.moveaxis(t.reshape(b, n, chunk, *t.shape[2:]), 3, 2)
        return jnp.moveaxis(t, 1, 0).reshape(n // slab, slab, b,
                                             t.shape[2], chunk, *t.shape[4:])

    y = _ssd(slabs(x), slabs(dt.astype(jnp.float32)), slabs(B), slabs(C),
             A.astype(jnp.float32))
    y = jnp.moveaxis(y.reshape(n, b, h, chunk, -1), (0, 2), (1, 3))
    return y.reshape(b, n * chunk, h, -1)[:, :s]
