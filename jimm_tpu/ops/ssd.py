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
`_PRECISION`.

Two paths compute it, and `chunk_ssd` chooses between them from what it can
observe (`kernel_takes`): on a TPU, with a head count a step whose ``x``
lanes are whole 128-lane tiles, ``N`` a whole number of tiles and a chunk of
whole 128-row tiles, the Pallas kernels; everywhere else (the CPU, the tiny
preset's heads of 16 with a state of 16 in chunks of 16, any other width) the
XLA path above, which is also the tests' oracle. No flag picks one.

**The kernels** (``ssd_fwd``, ``ssd_bwd``) read x in the model's ``(B, S,
H * P)`` layout, B and C as ``(B, S, G * N)`` (the index map picks the group
of the step's heads) and ``dt`` and ``a = dt A`` as ``(B, H, 1, S)`` rows, and
write y (and dx) in x's layout: no pad, transpose or reshape of an x-sized
array around them. The grid is ``(B, H / heads, chunks)``: a step holds
`_heads_a_step` heads, each a ``(heads, R, .)`` batch of every product, and
walks the chunks in order (the last axis ``"arbitrary"``), each head's
``(P, N)`` float32 state in a VMEM scratch, zeroed at the first. A chunk is
cut into tiles of `_ROWS` = 128 rows (`_parts`): inside a tile ``E`` is a
product of the triangle of ones with ``a`` (``(U * a) V``: every term of one
sign); a pair across tiles sums the row's head, the whole tiles between and
the key's tail; ``into``, ``out_of`` and the chunk's total sum a tile's part
and whole tiles. So the rule above holds: no exponent is a difference. The
exact operands stay in the inputs' dtype inside (x, a row tile of C, B, and
the triangle of ones, built in x's dtype), and a product takes as many MXU
passes as its operands' dtypes need (`_mx`): ``C B^T`` of bfloat16 operands
is one pass (every product exact in float32); a product with one bfloat16
operand and one float32 is one pass of the float32 operand's three bfloat16
pieces side by side along the contraction against the exact operand three
times (the float32 operand cut once where it enters several: the state in
every row tile), the terms `_PRECISION`'s six passes sum less the three that
multiply the exact operand's zero low pieces, so the same numbers but for
the order of a float32 sum; a product of two float32 operands (``dyi S``,
``w^T dy``, ``(f B) dS'^T`` in the backward, and every product of float32
inputs) is at `_PRECISION`. Then ``y``
from the tokens and from the state, and the next state. The forward writes
the state each chunk is entered with (``ssm_states``, as the XLA path). The
backward walks the chunks from the last (index map ``n - 1 - i``), ``dS`` in
a VMEM scratch: it rebuilds a chunk's intra-chunk quantities from its inputs
and its kept state (no forward is run again) and writes dx in x's dtype, the
direct part of ``ddt`` and ``da`` as float32 rows, and each head block's
share of dB and dC in float32 (the head axis of the grid is parallel), which
are summed outside with ``ddt += da A`` and ``dA = sum da dt``. Both kernels
run where `chunk_ssd` is called, so their custom calls carry the caller's
scopes (``ssm/ssm_scan`` in `nn/mamba2.py`), forward and backward.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jimm_tpu.ops.delta_rule import _dot, _flip, _interpret, _iota

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
    if kernel_takes(x.shape, B.shape, chunk, _default_backend()):
        registry.counter("kernel_total").inc()
        return _kernel_ssd(x, dt, A, B, C, chunk)
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


# -- the Pallas kernels -------------------------------------------------------

#: rows of a tile: a chunk is ``chunk / _ROWS`` tiles, and a pair of tokens in
#: two tiles meets through the parts of its exponent (`_parts`)
_ROWS = 128
#: the most heads a grid step holds, each product a batch of them. One
#: layer's forward + backward at (1, 16384, 64, 64), N = 128, on the v5e:
#: 24.2 ms at two heads, 21.5 at four, 19.9 at eight, 19.6 at sixteen, whose
#: kernels take Mosaic twice as long to compile (PERF.md, section 6)
_HEADS = 8
#: what a call may take of VMEM (the backward uses 19.4 MiB of it at eight
#: heads, the forward 11.0)
_VMEM_LIMIT = 64 << 20


def _default_backend() -> str:
    return jax.default_backend()


def _heads_a_step(heads_per_group: int, head_dim: int) -> int | None:
    """The most heads up to `_HEADS` that divide a group's heads and fill
    whole 128-lane tiles of ``x``; None where no count does."""
    fits = [n for n in range(1, min(heads_per_group, _HEADS) + 1)
            if heads_per_group % n == 0 and n * head_dim % 128 == 0]
    return max(fits) if fits else None


def kernel_takes(x_shape, b_shape, chunk: int, backend: str) -> bool:
    """Whether `chunk_ssd` runs on the Pallas kernels: on a TPU, with a head
    count a step whose ``x`` lanes are whole 128-lane tiles, a state ``N`` of
    whole tiles and a chunk of whole 128-row tiles. Everything else (the CPU,
    the tiny preset's heads of 16 with a state of 16 in chunks of 16, any
    other width) takes the XLA path."""
    _, _, h, p = x_shape
    g, n = b_shape[2:]
    return (backend == "tpu" and h % g == 0
            and _heads_a_step(h // g, p) is not None
            and n % 128 == 0 and chunk % _ROWS == 0)


def _exact(t) -> bool:
    return not isinstance(t, tuple) and t.dtype == jnp.bfloat16


def _pieces(a: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """A float32 ``a`` as three bfloat16 pieces whose sum is ``a`` exactly:
    its top 8 significant bits, the top 8 of what is left, and the rest (8
    at most), each cut off by a mask of the bits (a cast to bfloat16 and
    back may be dropped by XLA as excess precision; a mask may not)."""
    def top(t):
        bits = jax.lax.bitcast_convert_type(t, jnp.uint32)
        return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                            jnp.float32)

    hi = top(a)
    mid = top(a - hi)
    return tuple(t.astype(jnp.bfloat16) for t in (hi, mid, a - hi - mid))


def _split_for(a: jax.Array, *partners: jax.Array):
    """``a``'s `_pieces`, cut once for every product it enters, where each
    operand it meets there is bfloat16; else ``a``."""
    return _pieces(a) if all(map(_exact, partners)) else a


def _stacked(t, axis: int) -> jax.Array:
    """An operand of a split product along its contraction axis: a float32
    one's `_pieces` side by side, an exact one three times."""
    parts = t if isinstance(t, tuple) else [t] * 3 if _exact(t) \
        else _pieces(t)
    return jnp.concatenate(parts, axis)


def _mx(a, b, form: str = "nn") -> jax.Array:
    """`_dot`'s product in the fewest MXU passes that keep its terms. Two
    bfloat16 operands: one pass, every product exact in float32. One
    bfloat16 operand and one float32 (or that one's `_pieces`): one pass of
    the float32 operand's three bfloat16 pieces side by side along the
    contraction against the exact operand three times, what `_PRECISION`'s
    six passes sum less the three that multiply the exact operand's zero low
    pieces; counted in ``jimm_ssm_split_products_total`` as the kernel body
    is traced. Two float32 operands: `_dot`, six passes."""
    if not (_exact(a) or _exact(b)):
        return _dot(a, b, form)
    n = (b if _exact(b) else a).ndim
    at_a, at_b = {"nn": (n - 1, n - 2), "nt": (n - 1, n - 1),
                  "tn": (n - 2, n - 2)}[form]
    if not (_exact(a) and _exact(b)):
        from jimm_tpu.obs.registry import get_registry
        get_registry("jimm_ssm").counter("split_products_total").inc()
        a, b = _stacked(a, at_a), _stacked(b, at_b)
    batch = tuple(range(n - 2))
    return jax.lax.dot_general(a, b, (((at_a,), (at_b,)), (batch, batch)),
                               preferred_element_type=jnp.float32)


def _split_heads(block: jax.Array, heads: int) -> jax.Array:
    """A ``(L, heads * P)`` block as ``(heads, L, P)``, in its dtype."""
    p = block.shape[-1] // heads
    return jnp.stack([block[:, i * p:(i + 1) * p] for i in range(heads)])


def _join_heads(x: jax.Array) -> jax.Array:
    return jnp.concatenate([x[i] for i in range(x.shape[0])], axis=-1)


def _parts(a_ref, dt_ref, exact) -> dict:
    """What a chunk's exponents are built from, tile by tile, for the rows
    ``a, dt (1, heads, 1, L)`` (read a tile at a time): ``head`` (``(heads,
    R, 1)``: the sum of ``a`` over the tile's tokens up to the row's),
    ``tail`` (after the row's),
    ``whole`` (``(heads, 1, 1)``), ``inside`` (``(heads, R, R)``: ``E`` on
    the tile's diagonal block, a product of the triangle of ones with ``a``;
    ``-inf`` above the diagonal), ``into``, ``out_of`` and ``total`` of the
    chunk, each a sum of same-signed parts, and ``dt`` as rows and columns.
    The triangle of ones is in the dtype ``exact``, the inputs'."""
    _, heads, _, length = a_ref.shape
    r = _ROWS
    row, col = _iota((r, r), 0), _iota((r, r), 1)
    upto, after = col <= row, col > row
    # V_sj = [s > j]: rows s, lanes j
    later = jnp.broadcast_to(jnp.where(row > col, 1.0, 0.0).astype(exact),
                             (heads, r, r))
    p = {k: [] for k in ("head", "tail", "whole", "inside", "dt_row",
                         "dt_col")}
    for t in range(length // r):
        a = jnp.broadcast_to(a_ref[0, :, :, t * r:(t + 1) * r], (heads, r, r))
        p["head"].append(jnp.sum(jnp.where(upto, a, 0.0), -1, keepdims=True))
        p["tail"].append(jnp.sum(jnp.where(after, a, 0.0), -1, keepdims=True))
        p["whole"].append(jnp.sum(a[:, :1], -1, keepdims=True))
        p["inside"].append(jnp.where(
            upto, _mx(jnp.where(upto, a, 0.0), later), -jnp.inf))
        p["dt_row"].append(dt_ref[0, :, :, t * r:(t + 1) * r])
        p["dt_col"].append(_flip(p["dt_row"][-1]))
    n = len(p["whole"])
    p["tail_row"] = [_flip(x) for x in p["tail"]]
    p["into"] = [p["head"][t] + sum(p["whole"][:t], 0.0) for t in range(n)]
    p["out_of"] = [p["tail"][t] + sum(p["whole"][t + 1:], 0.0)
                   for t in range(n)]
    p["total"] = sum(p["whole"][1:], p["whole"][0])
    p["upto"], p["after"], p["later"] = upto, after, later
    return p


def _exponent(p: dict, rt: int, ct: int) -> jax.Array:
    """``E`` on the block of row tile ``rt`` and key tile ``ct <= rt``: the
    tile's own where they are one, else the row's head, the whole tiles
    between and the key's tail."""
    if rt == ct:
        return p["inside"][rt]
    return p["head"][rt] + (sum(p["whole"][ct + 1:rt], 0.0)
                            + p["tail_row"][ct])


def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, kept_ref, state, *,
                heads: int):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    r = _ROWS
    x = _split_heads(x_ref[0], heads)
    p = _parts(a_ref, dt_ref, x.dtype)
    B, C = b_ref[0], c_ref[0]
    s0 = state[...]
    kept_ref[0, :, 0] = s0
    s0_cut = _split_for(s0, C)
    new, ys = jnp.exp(p["total"]) * s0, []
    for rt in range(len(p["whole"])):
        rows = slice(rt * r, (rt + 1) * r)
        c = jnp.broadcast_to(C[rows], (heads, r, C.shape[-1]))
        y = jnp.exp(p["into"][rt]) * _mx(c, s0_cut, "nt")
        for ct in range(rt + 1):
            keys = slice(ct * r, (ct + 1) * r)
            w = jnp.exp(_exponent(p, rt, ct)) * _mx(C[rows], B[keys], "nt") \
                * p["dt_row"][ct]
            y = y + _mx(w, x[:, keys])
        ys.append(y)
        written = jnp.exp(p["out_of"][rt]) * p["dt_col"][rt] \
            * B[rows].astype(jnp.float32)
        new = new + _mx(x[:, rows], written, "tn")
    state[...] = new
    y_ref[0] = _join_heads(jnp.concatenate(ys, axis=-2)).astype(y_ref.dtype)


def _bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, kept_ref, dy_ref,
                dx_ref, ddt_ref, da_ref, db_ref, dc_ref, d_state, *,
                heads: int):
    @pl.when(pl.program_id(2) == 0)
    def _():
        d_state[...] = jnp.zeros_like(d_state)

    r = _ROWS
    x, dy = _split_heads(x_ref[0], heads), _split_heads(dy_ref[0], heads)
    p = _parts(a_ref, dt_ref, x.dtype)
    B, C = b_ref[0], c_ref[0]
    s0, ds1 = kept_ref[0, :, 0], d_state[...]
    s0_cut, ds1_cut = _split_for(s0, C), _split_for(ds1, x)
    n = len(p["whole"])
    zero_col = jnp.zeros((heads, r, 1), jnp.float32)
    zero_row = jnp.zeros((heads, 1, r), jnp.float32)
    dx = [jnp.zeros((heads, r, x.shape[-1]), jnp.float32)] * n
    ddt, d_inside = [zero_row] * n, [zero_row] * n
    d_head, d_tail = [zero_col] * n, [zero_col] * n
    d_whole = [jnp.zeros((heads, 1, 1), jnp.float32)] * n
    db = [jnp.zeros((r, B.shape[-1]), jnp.float32)] * n
    dc = list(db)
    decay = jnp.exp(p["total"])
    ds0 = decay * ds1
    d_total = jnp.sum(jnp.sum(ds1 * s0, -1, keepdims=True), -2,
                      keepdims=True) * decay
    for rt in range(n):
        rows = slice(rt * r, (rt + 1) * r)
        c = jnp.broadcast_to(C[rows], (heads, r, C.shape[-1]))
        dy_r = dy[:, rows]
        # y += exp(into) (C S^T)
        dyi = jnp.exp(p["into"][rt]) * dy_r
        d_into = jnp.sum(dyi * _mx(c, s0_cut, "nt"), -1, keepdims=True)
        d_head[rt] = d_head[rt] + d_into
        for t in range(rt):
            d_whole[t] = d_whole[t] + jnp.sum(d_into, -2, keepdims=True)
        dc[rt] = dc[rt] + jnp.sum(_dot(dyi, s0), 0)
        ds0 = ds0 + _mx(dyi, c, "tn")
        # y += (exp(E) * C B^T * dt) x
        for ct in range(rt + 1):
            keys = slice(ct * r, (ct + 1) * r)
            e = jnp.exp(_exponent(p, rt, ct))
            wn = e * _mx(C[rows], B[keys], "nt")
            w = wn * p["dt_row"][ct]
            dw = _mx(dy_r, x[:, keys], "nt")
            dx[ct] = dx[ct] + _dot(w, dy_r, "tn")
            ddt[ct] = ddt[ct] + jnp.sum(dw * wn, -2, keepdims=True)
            dE = dw * w
            dcb = _split_for(jnp.sum(dw * e * p["dt_row"][ct], 0), B, C)
            dc[rt] = dc[rt] + _mx(dcb, B[keys])
            db[ct] = db[ct] + _mx(dcb, C[rows], "tn")
            if rt == ct:
                # E = (U * a) V: a_s gets sum_r U_rs (dE V^T)_rs
                d_inside[ct] = d_inside[ct] + jnp.sum(jnp.where(
                    p["upto"], _mx(dE, p["later"], "nt"), 0.0), -2,
                    keepdims=True)
            else:
                d_head[rt] = d_head[rt] + jnp.sum(dE, -1, keepdims=True)
                d_tail[ct] = d_tail[ct] + _flip(jnp.sum(dE, -2,
                                                        keepdims=True))
                between = jnp.sum(jnp.sum(dE, -1, keepdims=True), -2,
                                  keepdims=True)
                for t in range(ct + 1, rt):
                    d_whole[t] = d_whole[t] + between
    # S' = exp(total) S + x^T (exp(out_of) dt B)
    for ct in range(n):
        keys = slice(ct * r, (ct + 1) * r)
        out = jnp.exp(p["out_of"][ct])
        f = out * p["dt_col"][ct]
        b = jnp.broadcast_to(B[keys].astype(jnp.float32),
                             (heads, r, B.shape[-1]))
        dbd = _mx(x[:, keys], ds1_cut)
        dx[ct] = dx[ct] + _dot(f * b, ds1, "nt")
        db[ct] = db[ct] + jnp.sum(f * dbd, 0)
        df = jnp.sum(dbd * b, -1, keepdims=True)
        d_tail[ct] = d_tail[ct] + df * f
        for t in range(ct + 1, n):
            d_whole[t] = d_whole[t] + jnp.sum(df * f, -2, keepdims=True)
        ddt[ct] = ddt[ct] + _flip(df * out)
    da = []
    for t in range(n):
        da.append(d_inside[t] + d_whole[t] + d_total
                  + jnp.sum(jnp.where(p["upto"], d_head[t], 0.0), -2,
                            keepdims=True)
                  + jnp.sum(jnp.where(p["after"], d_tail[t], 0.0), -2,
                            keepdims=True))
    d_state[...] = ds0
    dx_ref[0] = _join_heads(jnp.concatenate(dx, axis=-2)).astype(dx_ref.dtype)
    ddt_ref[0] = jnp.concatenate(ddt, axis=-1)
    da_ref[0] = jnp.concatenate(da, axis=-1)
    db_ref[0] = jnp.concatenate(db, axis=0)
    dc_ref[0] = jnp.concatenate(dc, axis=0)


def _pallas(kernel, name: str, args, in_kinds, out, out_kinds, chunk: int,
            groups: int):
    """One kernel over the grid ``(B, H / heads, chunks)``; a ``kind`` is
    ``"x"`` (a ``(B, S, H * P)`` array: a chunk's rows, the lanes of a head
    block), ``"row"`` (``(B, H, 1, S)`` rows of ``dt`` or ``a``), ``"bc"``
    (``B`` or ``C``, ``(B, S, G * N)``: the group of the step's heads),
    ``"part"`` (``(B, S, H / heads * N)``: a head block's share of ``dB`` or
    ``dC``) or ``"kept"`` (the states, ``(B, H, chunks, P, N)``). The
    backward (``ssd_bwd``) walks the chunks from the last."""
    b, h, _, s = args[1].shape
    p = args[0].shape[2] // h
    n = args[3].shape[2] // groups
    heads = _heads_a_step(h // groups, p)
    chunks = s // chunk
    seg = (lambda i: chunks - 1 - i) if name == "ssd_bwd" else (lambda i: i)
    per_group = h // groups // heads
    specs = {
        "x": pl.BlockSpec((1, chunk, heads * p),
                          lambda i, j, t: (i, seg(t), j)),
        "row": pl.BlockSpec((1, heads, 1, chunk),
                            lambda i, j, t: (i, j, 0, seg(t))),
        "bc": pl.BlockSpec((1, chunk, n),
                           lambda i, j, t: (i, seg(t), j // per_group)),
        "part": pl.BlockSpec((1, chunk, n), lambda i, j, t: (i, seg(t), j)),
        "kept": pl.BlockSpec((1, heads, 1, p, n),
                             lambda i, j, t: (i, j, seg(t), 0, 0))}
    return pl.pallas_call(
        partial(kernel, heads=heads),
        grid=(b, h // heads, chunks),
        in_specs=[specs[k] for k in in_kinds],
        out_specs=[specs[k] for k in out_kinds],
        out_shape=out,
        scratch_shapes=[pltpu.VMEM((heads, p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(), name=name)(*args)


def _fwd_call(x, dt, a, B, C, chunk: int, groups: int):
    """``y (B, S, H * P)`` float32 and the state each chunk is entered with,
    for ``x (B, S, H * P)``, ``dt, a (B, H, 1, S)``, ``B, C (B, S, G * N)``,
    ``S`` a multiple of ``chunk``."""
    b, h, _, s = dt.shape
    p, n = x.shape[2] // h, B.shape[2] // groups
    return _pallas(
        _fwd_kernel, "ssd_fwd", (x, dt, a, B, C),
        ["x", "row", "row", "bc", "bc"],
        [jax.ShapeDtypeStruct(x.shape, jnp.float32),
         jax.ShapeDtypeStruct((b, h, s // chunk, p, n), jnp.float32)],
        ["x", "kept"], chunk, groups)


@partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kernel_scan(x, dt, a, B, C, chunk: int, groups: int) -> jax.Array:
    return _fwd_call(x, dt, a, B, C, chunk, groups)[0]


def _kernel_scan_fwd(x, dt, a, B, C, chunk, groups):
    y, kept = _fwd_call(x, dt, a, B, C, chunk, groups)
    return y, (x, dt, a, B, C, checkpoint_name(kept, "ssm_states"))


def _kernel_scan_bwd(chunk, groups, residuals, dy):
    """``dx`` in x's dtype, ``ddt`` (its direct terms) and ``da`` float32;
    ``dB``, ``dC`` as the head blocks' float32 shares, summed here and cast
    to their inputs' dtypes."""
    x, dt, a, B, C, _ = residuals
    b, h, _, s = dt.shape
    blocks = h // _heads_a_step(h // groups, x.shape[2] // h)
    n = B.shape[2] // groups
    part = jax.ShapeDtypeStruct((b, s, blocks * n), jnp.float32)
    dx, ddt, da, db, dc = _pallas(
        _bwd_kernel, "ssd_bwd", (*residuals, dy),
        ["x", "row", "row", "bc", "bc", "kept", "x"],
        [jax.ShapeDtypeStruct(x.shape, x.dtype),
         jax.ShapeDtypeStruct(dt.shape, jnp.float32),
         jax.ShapeDtypeStruct(a.shape, jnp.float32), part, part],
        ["x", "row", "row", "part", "part"], chunk, groups)

    def summed(d, like):
        return jnp.sum(d.reshape(b, s, groups, blocks // groups, n), 3) \
            .reshape(like.shape).astype(like.dtype)

    return dx, ddt, da, summed(db, B), summed(dc, C)


_kernel_scan.defvjp(_kernel_scan_fwd, _kernel_scan_bwd)


def _kernel_ssd(x, dt, A, B, C, chunk: int) -> jax.Array:
    """`chunk_ssd` on the kernels: ``x``, ``B``, ``C`` in the model's layout
    (merged trailing axes), ``dt`` and ``a = dt A`` as rows, padded only
    where the length is no multiple of the chunk."""
    b, s, h, p = x.shape
    pad = -s % chunk

    def flat(t):
        if pad:
            t = jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        return t.reshape(b, s + pad, -1)

    def rows(t):
        return jnp.moveaxis(flat(t), 2, 1)[:, :, None, :]

    dt = dt.astype(jnp.float32)
    y = _kernel_scan(flat(x), rows(dt), rows(dt * A.astype(jnp.float32)),
                     flat(B), flat(C), chunk, B.shape[2])
    return y.reshape(b, s + pad, h, p)[:, :s]
