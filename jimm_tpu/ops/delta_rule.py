"""The gated delta rule of Kimi Delta Attention, in chunks.

Per head, with a state ``S (d_k, d_v)`` that starts at zero, a per-channel
decay ``alpha_t = exp(g_t)`` (``g_t <= 0``) and a write strength ``b_t``:

    S_t = (I - b_t k_t k_t^T) Diag(alpha_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

i.e. decay the state per key channel, read what the key already holds
(``k^T S``), write ``b (v - k^T S)`` along ``k``, read with ``q``.

`chunk_kda` computes it in chunks of ``C`` tokens. With ``G_r`` the sum of
``g`` over a chunk's tokens up to ``r`` (its own included), ``S`` the state a
chunk is entered with and ``u_j = b_j (v_j - (Diag(alpha_j) S_{j-1})^T k_j)``
what token ``j`` writes:

    A_rj = b_r sum_c k_rc k_jc exp(G_rc - G_jc)      j < r, else 0
    T    = (I + A)^-1 Diag(b)                         by halves, no solve
    W    = T (K * exp(G)),   U = T V                  u = U - W S
    P_rj = sum_c q_rc k_jc exp(G_rc - G_jc)          j <= r, else 0
    O    = (Q * exp(G)) S + P u
    S'   = Diag(exp(G_last)) S + (K * exp(G_last - G))^T u

Everything but ``u = U - W S``, ``O`` and ``S'`` is computed for several
chunks at once; the state is carried over the chunks for those.

**Every exponent taken is a difference ``G_i - G_j`` with ``i >= j``**, so it
is never positive. At the published gate a channel loses up to 11 nats a
token: ``exp(-G)`` leaves float32's range within 8 tokens, and the textbook
factoring ``(K e^G)(K e^-G)^T`` of ``A`` and ``P`` overflows inside one chunk.
Here a chunk is cut into sub-chunks of `_SUB` (8) tokens: on the diagonal
sub-chunks the exponent is taken pair by pair (``_SUB x _SUB x d_k`` a
sub-chunk, summed over the channel inside the same fusion), off the diagonal
through the sub-chunk's first row ``ref``: ``exp(G_i - G_ref)`` on the row's
side and ``exp(G_ref - G_j)`` on the key's, each at most 1. A factor that
underflows to zero stands for a product that is smaller still.

float32 inside, every product at `_PRECISION` (the MXU's six-pass float32;
on the v5e the setting moved neither the time nor a bit of the XLA path's
output). Two paths compute it, and `chunk_kda` chooses between them from what
it can observe (`kernel_takes`): on a TPU, with ``d_k = d_v`` a whole number
of 128-lane tiles and a chunk of 64 or 128, the Pallas kernels; everywhere
else (the CPU, the tiny preset's heads of 16 in chunks of 16, any other
width) the XLA path, which is also the tests' oracle. No flag picks one.

**The kernels** (``kda_fwd``, ``kda_bwd``) read q, k, v, g in the model's
``(B, S, H * D)`` layout and ``beta`` as ``(B, H, 1, S)`` rows, and write o
(and the gradients) there: no pad, transpose or reshape of a q-sized array
around them. The grid is ``(B, H / heads, segments)``: a step holds
`_heads_a_step` heads (their lanes of a 128-row segment, each head a
``(heads, R, D)`` batch of every product) and walks the segments in order
(the last axis ``"arbitrary"``), the state ``(heads, d_v, d_k)`` float32 in a
VMEM scratch, zeroed at the first. A segment is `_ROWS` = 128 tokens, two
chunks of 64: everything that no state enters (``G`` by the triangle of
ones, the decayed grams by the rule above, the inverse by halves on whole
128 x 128 masks, ``W``, ``U``) is one product for both chunks, block-diagonal
by chunk; ``u = U - W S``, ``o`` and the next state go chunk by chunk. Below
the diagonal sub-chunks a pair meets through the middle row of the one block
of 16, 32 or 64 tokens whose lower half holds its row and upper half its key
(`_halves`: three products a segment, where the XLA path takes one a
sub-chunk). The forward writes out the state each segment is entered with
and the segment's ``(I + A)^-1``, the chunks' diagonal blocks side by side
(`_fold`), both named ``kda_states``: 268 + 134 MB a layer at (1, 16384, 32,
128). The backward walks the segments from the last (index map ``n - 1 -
i``), ``dS`` in a VMEM scratch: it rebuilds a segment's intra-chunk
quantities from its inputs and the kept inverse, its second chunk's state
from the kept one, and writes dq, dk, dv (in their inputs' dtypes), dg and
dbeta. Both kernels run where `chunk_kda` is called, so their custom calls
carry the caller's scopes (``kda/kda_scan`` in `nn/kda.py`), forward and
backward.

**The XLA path** is held to little memory by its form: an outer `lax.scan`
walks SLABS of chunks, and inside a slab everything but the state's two
products is computed for the slab's chunks at once while an inner scan
carries the state over them. The backward (`_scan`'s own rule) keeps the
inputs and the state each slab is entered with (2 MB a head group; 268 MB a
layer at 16,384 tokens in slabs of 2 chunks), walks the slabs in reverse and
rebuilds one slab at a time, differentiating it on the spot. Kept by plain
autodiff, one layer's intermediates are some twenty ``tokens x heads x d_k``
float32 arrays and the pairwise exponents (``tokens x _SUB x heads x d_k``:
gigabytes, which XLA writes out where the backward reads them three times):
more than a 16 GB chip has beside the model (PERF.md, PR 38). A slab holds as
many chunks as keep its pairwise exponents under `_PAIRWISE_BYTES`. The kept
states carry the name ``kda_states``, so a remat policy that keeps them (and
the caller's copy of the output) need not run the forward again in a layer's
backward (`nn/transformer.py::Transformer._remat_policy`).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: tokens of a sub-chunk: the pairwise exponents cost ``_SUB`` per token and
#: channel, the off-diagonal ones ``C / _SUB``. One layer's forward + backward
#: at (1, 16384, 32, 128) on the v5e: 134.8 ms at 8 against 142.4 at 16
#: (PERF.md, PR 38)
_SUB = 8
_PRECISION = jax.lax.Precision.HIGHEST
#: the most the pairwise exponents of one slab of chunks may take: two chunks
#: a slab at that shape. Smaller slabs are faster, not only leaner: 134.8 ms
#: at 32 MiB, 194.2 at 256 MiB (8 chunks), 248.3 at 1 GiB with the solve
#: (the intermediates of a large slab stream through HBM)
_PAIRWISE_BYTES = 32 << 20


def _mm(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.einsum(spec, a, b, precision=_PRECISION,
                      preferred_element_type=jnp.float32)


def _decayed_grams(q: jax.Array, k: jax.Array, G: jax.Array
                   ) -> tuple[jax.Array, jax.Array]:
    """``sum_c x_rc k_jc exp(G_rc - G_jc)`` for ``j <= r`` and 0 above the
    diagonal, for ``x = k`` and ``x = q``: two ``(..., C, C)`` from
    ``(..., C, d)`` inputs. No exponent is positive."""
    *lead, C, d = k.shape
    n = C // _SUB
    x = jnp.stack([k, q], axis=-3).reshape(*lead, 2, n, _SUB, d)
    ks = k.reshape(*lead, n, _SUB, d)
    Gs = G.reshape(*lead, n, _SUB, d)
    # the diagonal sub-chunks, pair by pair
    lower = jnp.tril(jnp.ones((_SUB, _SUB), bool))[..., None]
    decay = jnp.exp(jnp.where(
        lower, Gs[..., :, None, :] - Gs[..., None, :, :], -jnp.inf))
    diag = jnp.sum(x[..., :, None, :]
                   * (ks[..., None, :, :] * decay)[..., None, :, :, :, :],
                   axis=-1)                                # (..., 2, n, r, j)
    blocks = jnp.eye(n, dtype=diag.dtype)[:, None, :, None]
    full = (diag[..., :, :, None, :] * blocks).reshape(*lead, 2, C, C)
    if n > 1:
        # below them, through each row sub-chunk's first row
        ref = Gs[..., :, :1, :]                            # (..., n, 1, d)
        rows = x * jnp.exp(Gs - ref)[..., None, :, :, :]
        before = (jnp.arange(C)[None, :]
                  < _SUB * jnp.arange(n)[:, None])[..., None]  # (n, C, 1)
        keys = k[..., None, :, :] * jnp.exp(jnp.where(
            before, ref - G[..., None, :, :], -jnp.inf))   # (..., n, C, d)
        full = full + _mm("...xirc,...ijc->...xirj", rows, keys) \
            .reshape(*lead, 2, C, C)
    return full[..., 0, :, :], full[..., 1, :, :]


def _unit_lower_inverse(a: jax.Array) -> jax.Array:
    """``(I + a)^-1`` for strictly lower triangular ``a (..., C, C)``, ``C`` a
    power of two, by halves: with the inverses ``P`` and ``Q`` of two
    neighbouring diagonal blocks, the block under them is ``-Q c P``. Six
    levels of small batched products at 64; XLA's own triangular solve is a
    library call that took 22 ms a layer and pass at 16,384 tokens, the
    longest single operation of the scan (PERF.md, PR 38)."""
    *lead, C, _ = a.shape
    inv = jnp.ones((*lead, C, 1, 1), a.dtype)        # blocks of one: the 1s
    size = 1
    while size < C:
        n = C // (2 * size)
        blocks = jnp.moveaxis(jnp.diagonal(
            a.reshape(*lead, n, 2 * size, n, 2 * size), axis1=-4, axis2=-2),
            -1, -3)                                   # (..., n, 2s, 2s)
        pairs = inv.reshape(*lead, n, 2, size, size)
        p, q = pairs[..., 0, :, :], pairs[..., 1, :, :]
        under = -_mm("...ij,...jk->...ik", q,
                     _mm("...ij,...jk->...ik", blocks[..., size:, :size], p))
        inv = jnp.concatenate([
            jnp.concatenate([p, jnp.zeros_like(p)], axis=-1),
            jnp.concatenate([under, q], axis=-1)], axis=-2)
        size *= 2
    return inv.reshape(*lead, C, C)


def _slab(state: jax.Array, xs: tuple[jax.Array, ...]
          ) -> tuple[jax.Array, jax.Array]:
    """The chunks of one slab, ``(slab, B, H, C, ...)`` each of ``q, k, v, g,
    beta``, from the state they are entered with: the state they leave and
    their ``o (slab, B, H, C, d_v)``."""
    q, k, v, g, beta = (x.astype(jnp.float32) for x in xs)
    chunk = k.shape[-2]
    # the running sum of the gate inside a chunk, as a product with a
    # triangle of ones (a `cumsum` lowers to a windowed reduction)
    G = _mm("rc,...cd->...rd", jnp.tril(jnp.ones((chunk, chunk), g.dtype)), g)
    a_kk, p = _decayed_grams(q, k, G)
    t = _unit_lower_inverse(jnp.tril(a_kk, -1) * beta[..., None])
    wu = _mm("...rc,...cd->...rd", t, jnp.concatenate(
        [k * jnp.exp(G), v], axis=-1) * beta[..., None])
    w, u = wu[..., :k.shape[-1]], wu[..., k.shape[-1]:]
    last = G[..., -1:, :]

    def step(state, xs):
        w, u, k_end, decay = xs
        written = u - _mm("bhcd,bhde->bhce", w, state)
        new = decay[..., None] * state \
            + _mm("bhcd,bhce->bhde", k_end, written)
        return new, (state, written)

    state, (states, written) = jax.lax.scan(
        step, state, (w, u, k * jnp.exp(last - G), jnp.exp(last[..., 0, :])))
    return state, _mm("nbhcd,nbhde->nbhce", q * jnp.exp(G), states) \
        + _mm("nbhcj,nbhje->nbhce", p, written)


def _walk(xs: tuple[jax.Array, ...]) -> tuple[jax.Array, jax.Array]:
    """`_slab` over the slabs in order, from a state of zeros: every slab's
    ``o`` and the state each slab is entered with."""
    _, b, h, _, d = xs[1].shape[1:]

    def body(state, x):
        new, o = _slab(state, x)
        return new, (o, state)

    return jax.lax.scan(
        body, jnp.zeros((b, h, d, xs[2].shape[-1]), jnp.float32), xs)[1]


@jax.custom_vjp
def _scan(xs: tuple[jax.Array, ...]) -> jax.Array:
    return _walk(xs)[0]


def _scan_fwd(xs):
    o, entered = _walk(xs)
    return o, (xs, checkpoint_name(entered, "kda_states"))


def _scan_bwd(residuals, d_o):
    xs, entered = residuals

    def body(d_state, args):
        state, x, d_o = args
        d_state, d_x = jax.vjp(_slab, state, x)[1]((d_state, d_o))
        return d_state, d_x

    return (jax.lax.scan(body, jnp.zeros_like(entered[0]),
                         (entered, xs, d_o), reverse=True)[1],)


_scan.defvjp(_scan_fwd, _scan_bwd)


def _default_backend() -> str:
    return jax.default_backend()


def kernel_takes(k_shape, v_shape, chunk: int, backend: str) -> bool:
    """Whether `chunk_kda` runs on the Pallas kernels: on a TPU, ``d_k =
    d_v`` a whole number of 128-lane tiles, a chunk of 64 or 128 tokens (two
    or one to a segment of `_ROWS`). Everything else (the CPU, the tiny
    preset's heads of 16 in chunks of 16, any other width) takes the XLA
    path."""
    return (backend == "tpu" and k_shape[-1] == v_shape[-1]
            and k_shape[-1] % 128 == 0 and 64 <= chunk <= _ROWS)


def chunk_kda(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
              beta: jax.Array, *, chunk: int = 64) -> jax.Array:
    """``o (B, S, H, d_v)`` float32 of the recurrence above for ``q, k, g
    (B, S, H, d_k)``, ``v (B, S, H, d_v)`` and ``beta (B, S, H)``; ``g <= 0``
    (hand ``g`` over in float32: its running sum is taken as it comes).
    ``chunk`` is a power of two, `_SUB` or more; a length that is no multiple
    of it is padded with tokens that leave the state alone."""
    if chunk % _SUB or chunk & (chunk - 1):
        raise ValueError(f"chunk {chunk} is no power of two from {_SUB} up")
    b, s, h, d = k.shape
    n = -(-s // chunk)
    from jimm_tpu.obs.registry import get_registry
    registry = get_registry("jimm_kda")
    registry.counter("calls_total").inc()
    registry.counter("chunks_total").inc(n)
    if kernel_takes(k.shape, v.shape, chunk, _default_backend()):
        registry.counter("kernel_total").inc()
        return _kernel_kda(q, k, v, g, beta, chunk)
    slab = min(n, max(1, _PAIRWISE_BYTES
                         // (b * h * 2 * chunk * _SUB * d * 4)))
    while n % slab:
        slab -= 1

    def slabs(x: jax.Array) -> jax.Array:
        # (B, S, H, ...) -> (slabs, slab, B, H, C, ...), zeros after the end
        # (in the caller's dtype: a slab is cast when its turn comes)
        x = jnp.pad(x, [(0, 0), (0, n * chunk - s)] + [(0, 0)] * (x.ndim - 2))
        x = jnp.moveaxis(x.reshape(b, n, chunk, *x.shape[2:]), 3, 2)
        return jnp.moveaxis(x, 1, 0).reshape(n // slab, slab, b, h, chunk,
                                             *x.shape[4:])

    o = _scan(tuple(slabs(x) for x in (q, k, v, g, beta)))
    o = jnp.moveaxis(o.reshape(n, b, h, chunk, -1), (0, 2), (1, 3))
    return o.reshape(b, n * chunk, h, -1)[:, :s]


# -- the Pallas kernels -------------------------------------------------------

#: rows of a grid step: a segment of ``_ROWS / chunk`` chunks, one MXU tile
#: of rows, so that every product that no state enters is one for the
#: segment's chunks at once (block-diagonal by chunk). The forward keeps the
#: state each segment is entered with: 268 MB a layer at (1, 16384, 32, 128),
#: what the XLA path keeps at two chunks a slab
_ROWS = 128
#: heads a grid step holds, each product a batch of them: independent work
#: for the MXU between a chain's dependent products. One layer's forward +
#: backward at (1, 16384, 32, 128) on the v5e: 70.8 ms at one head, 52.6 at
#: two, 51.1 at four; with the inverse kept, 45.3 at two and 44.2 at four
#: (PERF.md, PR 39)
_HEADS = 4
#: what a call may take of VMEM (the backward uses 29 MB of it at four heads)
_VMEM_LIMIT = 64 << 20


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _dot(a: jax.Array, b: jax.Array, form: str = "nn") -> jax.Array:
    """``a @ b``, ``a @ b^T`` (``"nt"``) or ``a^T @ b`` (``"tn"``) on the
    last two axes, batched over the leading (the heads of a grid step)."""
    n = a.ndim
    contract = {"nn": ((n - 1,), (n - 2,)), "nt": ((n - 1,), (n - 1,)),
                "tn": ((n - 2,), (n - 2,))}[form]
    batch = tuple(range(n - 2))
    return jax.lax.dot_general(a, b, (contract, (batch, batch)),
                               precision=_PRECISION,
                               preferred_element_type=jnp.float32)


def _iota(shape: tuple[int, int], dim: int) -> jax.Array:
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _at(x: jax.Array, p: int, block: int = _SUB) -> jax.Array:
    """Row ``p`` of each ``block`` of rows of ``x (..., R, D)``, over the
    block."""
    *lead, r, d = x.shape
    blocks = x.reshape(*lead, r // block, block, d)
    return jnp.broadcast_to(blocks[..., p:p + 1, :], blocks.shape) \
        .reshape(x.shape)


def _sub_sum(x: jax.Array) -> jax.Array:
    """The sum of each sub-chunk's rows of ``x (..., R, D)``, over it."""
    *lead, r, d = x.shape
    blocks = x.reshape(*lead, r // _SUB, _SUB, d)
    return jnp.broadcast_to(jnp.sum(blocks, axis=-2, keepdims=True),
                            blocks.shape).reshape(x.shape)


def _flip(x: jax.Array) -> jax.Array:
    """A ``(..., 1, R)`` row as a ``(..., R, 1)`` column or back, exactly: a
    diagonal, summed."""
    r = max(x.shape[-2:])
    eye = _iota((r, r), 0) == _iota((r, r), 1)
    diagonal = jnp.where(eye, jnp.broadcast_to(x, (*x.shape[:-2], r, r)), 0.0)
    return jnp.sum(diagonal, axis=-1 if x.shape[-2] == 1 else -2,
                   keepdims=True)


def _masks(chunk: int) -> dict:
    row, col = _iota((_ROWS, _ROWS), 0), _iota((_ROWS, _ROWS), 1)
    same = (row ^ col) < chunk                      # the same chunk
    return {"row": row, "col": col, "lower": same & (col <= row),
            "strict": same & (col < row)}


def _inverse_by_halves(a: jax.Array, chunk: int) -> jax.Array:
    """`_unit_lower_inverse` of each chunk's diagonal block of ``a (..., R,
    R)`` at once: the blocks of a level are masks, every product one ``R x R
    x R``."""
    row, col = _iota(a.shape[-2:], 0), _iota(a.shape[-2:], 1)
    inv = jnp.broadcast_to(jnp.where(row == col, 1.0, 0.0), a.shape)
    size = 1
    while size < chunk:
        under = ((row ^ col) < 2 * size) & ((row & size) != 0) \
            & ((col & size) == 0)
        inv = inv - _dot(inv, _dot(jnp.where(under, a, 0.0), inv))
        size *= 2
    return inv


def _halves(G: jax.Array, chunk: int) -> list:
    """The levels of halves above the sub-chunks, blocks of ``2 * _SUB`` up
    to the chunk: a pair of tokens in two sub-chunks meets in the one block
    whose lower half holds the row and upper half the key, and its exponent
    goes through the block's middle row ``mid``: ``(block, exp(G - G_mid)``
    on the lower half's rows, ``exp(G_mid - G)`` on the upper half's keys)``,
    each 0 elsewhere and never above 1."""
    rows = _iota(G.shape[-2:], 0)
    levels, block = [], 2 * _SUB
    while block <= chunk:
        mid = _at(G, block // 2, block)
        lower = (rows & block // 2) != 0
        levels.append((block, jnp.exp(jnp.where(lower, G - mid, -jnp.inf)),
                       jnp.exp(jnp.where(lower, -jnp.inf, mid - G))))
        block *= 2
    return levels


def _segment_parts(q, k, v, g, b_row, chunk: int, t=None) -> dict:
    """What a segment computes before a state enters, for ``q, k, v, g (H,
    R, D)`` float32 and ``b_row (H, 1, R)``. The decayed grams take the XLA
    path's rule: pair by pair on the diagonal sub-chunks (``e[p]``: the
    exponent against the sub-chunk's row ``p``, on the rows at or after it),
    through a middle row between them (`_halves`). ``t``: the inverse, where
    the forward kept it."""
    r = k.shape[-2]
    m = _masks(chunk)
    start = m["row"] & ~(_SUB - 1)
    sub_row = _iota(k.shape[-2:], 0) & (_SUB - 1)
    G = _dot(jnp.broadcast_to(jnp.where(m["lower"], 1.0, 0.0),
                              (*g.shape[:-2], r, r)), g)
    e = [jnp.exp(jnp.where(sub_row >= p, G - _at(G, p), -jnp.inf))
         for p in range(_SUB)]
    kref = [_at(k, p) for p in range(_SUB)]
    m_k = m_q = jnp.zeros((*k.shape[:-2], r, r), jnp.float32)
    for p in range(_SUB):
        ek = kref[p] * e[p]
        at_p = m["col"] == start + p
        m_k = jnp.where(at_p, jnp.sum(k * ek, axis=-1, keepdims=True), m_k)
        m_q = jnp.where(at_p, jnp.sum(q * ek, axis=-1, keepdims=True), m_q)
    levels = _halves(G, chunk)
    for block, down, up in levels:
        grams = _dot(jnp.concatenate([k * down, q * down], axis=-2), k * up,
                     "nt")
        rows = _iota(grams.shape[-2:], 0) & (r - 1)
        grams = jnp.where((rows ^ _iota(grams.shape[-2:], 1)) < block, grams,
                          0.0)
        m_k, m_q = m_k + grams[..., :r, :], m_q + grams[..., r:, :]
    b = _flip(b_row)
    m_k = jnp.where(m["strict"], m_k, 0.0)
    if t is None:
        t = _inverse_by_halves(b * m_k, chunk)
    gam = jnp.exp(G)
    wu = _dot(t, jnp.concatenate([b * k * gam, b * v], axis=-1))
    to_end = jnp.exp(_at(G, chunk - 1, chunk) - G)
    d = k.shape[-1]
    return {"e": e, "kref": kref, "levels": levels, "m_k": m_k, "m_q": m_q,
            "b": b, "t": t, "wu": wu, "gam": gam, "w": wu[..., :d],
            "u": wu[..., d:], "to_end": to_end, "k_end": k * to_end,
            "masks": m, "g_last": [G[..., c * chunk - 1:c * chunk, :]
                                   for c in range(1, r // chunk + 1)]}


def _segment_walk(p: dict, q, state, chunk: int, with_o: bool):
    """The segment's chunks in order from the state it is entered with
    (held transposed, ``(H, d_v, d_k)``: a key channel's decay is then a row
    that scales columns). ``u`` of every chunk, the state each was entered
    with, the state the segment leaves and, ``with_o``, the output."""
    written, states, inter = [], [], []
    for c in range(q.shape[-2] // chunk):
        rows = slice(c * chunk, (c + 1) * chunk)
        states.append(state)
        u = p["u"][..., rows, :] - _dot(p["w"][..., rows, :], state, "nt")
        if with_o:
            inter.append(_dot(q[..., rows, :] * p["gam"][..., rows, :],
                              state, "nt"))
        state = state * jnp.exp(p["g_last"][c]) \
            + _dot(u, p["k_end"][..., rows, :], "tn")
        written.append(u)
    written = jnp.concatenate(written, axis=-2)
    o = jnp.concatenate(inter, axis=-2) + _dot(p["m_q"], written) \
        if with_o else None
    return written, states, state, o


def _segment_grads(p: dict, q, k, v, written, states, d_state, do,
                   chunk: int):
    """The segment's backward: ``dq, dk, dv, dg (H, R, D)``, ``db (H, 1,
    R)`` and the gradient of the state it was entered with, from the
    gradient of the state it left, the chunks in reverse."""
    r, d = k.shape[-2:]
    m = p["masks"]
    start = m["row"] & ~(_SUB - 1)
    sub_row = _iota((r, d), 0) & (_SUB - 1)
    n = r // chunk
    from_o = _dot(p["m_q"], do, "tn")
    d_written, d_kend, d_qg, d_w, d_last = ([None] * n for _ in range(5))
    for c in reversed(range(n)):
        sl = slice(c * chunk, (c + 1) * chunk)
        state, last = states[c], jnp.exp(p["g_last"][c])
        du = from_o[..., sl, :] + _dot(p["k_end"][..., sl, :], d_state, "nt")
        d_kend[c] = _dot(written[..., sl, :], d_state)
        d_last[c] = jnp.broadcast_to(
            jnp.sum(d_kend[c] * p["k_end"][..., sl, :], axis=-2,
                    keepdims=True)
            + jnp.sum(d_state * state, axis=-2, keepdims=True) * last,
            d_kend[c].shape)
        d_qg[c] = _dot(do[..., sl, :], state)
        d_w[c] = -_dot(du, state)
        d_state = d_state * last \
            + _dot(do[..., sl, :], q[..., sl, :] * p["gam"][..., sl, :],
                   "tn") \
            - _dot(du, p["w"][..., sl, :], "tn")
        d_written[c] = du
    d_written, d_kend, d_qg, d_w, d_last = (
        jnp.concatenate(x, axis=-2)
        for x in (d_written, d_kend, d_qg, d_w, d_last))
    gam, b = p["gam"], p["b"]
    d_mq = jnp.where(m["lower"], _dot(do, written, "nt"), 0.0)
    d_r = _dot(p["t"], jnp.concatenate([d_w, d_written], axis=-1), "tn")
    d_rw, d_ru = d_r[..., :d], d_r[..., d:]
    # -T^T dT T^T, with dT = d_wu (what T multiplied)^T and T times it = wu
    d_a = jnp.where(m["strict"], -_dot(d_r, p["wu"], "nt"), 0.0)
    db = jnp.sum(d_ru * v + d_rw * k * gam, axis=-1, keepdims=True) \
        + jnp.sum(d_a * p["m_k"], axis=-1, keepdims=True)
    d_mk = b * d_a
    # the grams: the row side (k's and q's) and the key side (k's, of both)
    dxk = dxq = d_key = jnp.zeros(k.shape, jnp.float32)
    for pos in range(_SUB):
        at_p = m["col"] == start + pos
        ck = jnp.sum(jnp.where(at_p, d_mk, 0.0), axis=-1, keepdims=True)
        cq = jnp.sum(jnp.where(at_p, d_mq, 0.0), axis=-1, keepdims=True)
        ek = p["kref"][pos] * p["e"][pos]
        dxk = dxk + ck * ek
        dxq = dxq + cq * ek
        d_key = d_key + jnp.where(
            sub_row == pos, _sub_sum((ck * k + cq * q) * p["e"][pos]), 0.0)
    for block, down, up in p["levels"]:
        same = (m["row"] ^ m["col"]) < block
        dm = jnp.concatenate([jnp.where(same, d_mk, 0.0),
                              jnp.where(same, d_mq, 0.0)], axis=-2)
        dx = _dot(dm, k * up)
        dxk = dxk + dx[..., :r, :] * down
        dxq = dxq + dx[..., r:, :] * down
        d_key = d_key + up * _dot(
            dm, jnp.concatenate([k * down, q * down], axis=-2), "tn")
    d_g = k * dxk + q * dxq - k * d_key - d_kend * p["k_end"] \
        + (d_qg * q + b * k * d_rw) * gam
    lower = jnp.broadcast_to(jnp.where(m["lower"], 1.0, 0.0),
                             (*k.shape[:-2], r, r))
    dg = _dot(lower, d_g, "tn") + d_last
    dk = d_kend * p["to_end"] + b * gam * d_rw + dxk + d_key
    return (d_qg * gam + dxq, dk, b * d_ru, dg, _flip(db), d_state)


def _fold(t: jax.Array, chunk: int) -> jax.Array:
    """The chunks' diagonal blocks of a block-diagonal ``(..., R, R)`` side
    by side in ``(..., R, chunk)``, and back (`_unfold`)."""
    return sum(t[..., c:c + chunk] for c in range(0, t.shape[-1], chunk))


def _unfold(t: jax.Array, chunk: int) -> jax.Array:
    same = (_iota((_ROWS, _ROWS), 0) ^ _iota((_ROWS, _ROWS), 1)) < chunk
    return jnp.where(same, jnp.concatenate([t] * (_ROWS // chunk), axis=-1),
                     0.0)


def _heads_of(ref, heads: int) -> jax.Array:
    """The grid step's ``(R, heads * D)`` block as ``(heads, R, D)``
    float32."""
    d = ref.shape[-1] // heads
    return jnp.stack([ref[0, :, h * d:(h + 1) * d] for h in range(heads)]) \
        .astype(jnp.float32)


def _put_heads(ref, x: jax.Array) -> None:
    heads, _, d = x.shape
    for h in range(heads):
        ref[0, :, h * d:(h + 1) * d] = x[h].astype(ref.dtype)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, kept_ref, t_ref,
                state, *, chunk: int, heads: int):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    q, k, v, g = (_heads_of(r, heads) for r in (q_ref, k_ref, v_ref, g_ref))
    kept_ref[0, :, 0] = state[...]
    parts = _segment_parts(q, k, v, g, b_ref[0], chunk)
    t_ref[0, :, 0] = _fold(parts["t"], chunk)
    _, _, state[...], o = _segment_walk(parts, q, state[...], chunk,
                                        with_o=True)
    _put_heads(o_ref, o)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, kept_ref, t_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, d_state, *,
                chunk: int, heads: int):
    @pl.when(pl.program_id(2) == 0)
    def _():
        d_state[...] = jnp.zeros_like(d_state)

    q, k, v, g = (_heads_of(r, heads) for r in (q_ref, k_ref, v_ref, g_ref))
    parts = _segment_parts(q, k, v, g, b_ref[0], chunk,
                           _unfold(t_ref[0, :, 0], chunk))
    # the states of the segment's chunks, from the one the forward kept
    written, states, _, _ = _segment_walk(parts, q, kept_ref[0, :, 0], chunk,
                                          with_o=False)
    *grads, db_ref[0], d_state[...] = _segment_grads(
        parts, q, k, v, written, states, d_state[...],
        _heads_of(do_ref, heads), chunk)
    for ref, x in zip((dq_ref, dk_ref, dv_ref, dg_ref), grads):
        _put_heads(ref, x)


def _heads_a_step(h: int) -> int:
    """The most heads up to `_HEADS` that divide ``h``."""
    return max(n for n in range(1, min(h, _HEADS) + 1) if h % n == 0)


def _pallas(kernel, name: str, args, in_kinds, out, out_kinds, chunk: int):
    """One kernel over the grid ``(B, H / heads, segments)``; a ``kind`` is
    ``"x"`` (a ``(B, S, H * D)`` array: a segment's rows, the lanes of a head
    group), ``"b"`` (``beta``'s ``(B, H, 1, S)`` rows), ``"kept"`` (the
    states ``(B, H, segments, D, D)``) or ``"t"`` (the inverses, `_fold`ed:
    ``(B, H, segments, R, chunk)``). The backward (``kda_bwd``) walks the
    segments from the last."""
    b, h, s = args[4].shape[0], args[4].shape[1], args[4].shape[3]
    d = args[0].shape[2] // h
    heads, n = _heads_a_step(h), s // _ROWS
    seg = (lambda i: n - 1 - i) if name == "kda_bwd" else (lambda i: i)
    specs = {
        "x": pl.BlockSpec((1, _ROWS, heads * d),
                          lambda i, j, t: (i, seg(t), j)),
        "b": pl.BlockSpec((1, heads, 1, _ROWS),
                          lambda i, j, t: (i, j, 0, seg(t))),
        "kept": pl.BlockSpec((1, heads, 1, d, d),
                             lambda i, j, t: (i, j, seg(t), 0, 0)),
        "t": pl.BlockSpec((1, heads, 1, _ROWS, chunk),
                          lambda i, j, t: (i, j, seg(t), 0, 0))}
    return pl.pallas_call(
        partial(kernel, chunk=chunk, heads=heads),
        grid=(b, h // heads, n),
        in_specs=[specs[x] for x in in_kinds],
        out_specs=[specs[x] for x in out_kinds],
        out_shape=out,
        scratch_shapes=[pltpu.VMEM((heads, d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(), name=name)(*args)


def _fwd_call(q, k, v, g, beta, chunk: int):
    """``o (B, S, H * D)`` float32, the state each segment is entered with
    and the segment's ``(I + A)^-1``, for ``q, k, v, g (B, S, H * D)`` and
    ``beta (B, H, 1, S)``, ``S`` a multiple of `_ROWS`."""
    b, h, s = beta.shape[0], beta.shape[1], beta.shape[3]
    d = q.shape[2] // h
    return _pallas(
        _fwd_kernel, "kda_fwd", (q, k, v, g, beta), ["x", "x", "x", "x", "b"],
        [jax.ShapeDtypeStruct(q.shape, jnp.float32),
         jax.ShapeDtypeStruct((b, h, s // _ROWS, d, d), jnp.float32),
         jax.ShapeDtypeStruct((b, h, s // _ROWS, _ROWS, chunk), jnp.float32)],
        ["x", "kept", "t"], chunk)


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def _kernel_scan(q, k, v, g, beta, chunk: int) -> jax.Array:
    return _fwd_call(q, k, v, g, beta, chunk)[0]


def _kernel_scan_fwd(q, k, v, g, beta, chunk):
    o, kept, t = _fwd_call(q, k, v, g, beta, chunk)
    return o, (q, k, v, g, beta, checkpoint_name(kept, "kda_states"),
               checkpoint_name(t, "kda_states"))


def _kernel_scan_bwd(chunk, residuals, do):
    """``dq, dk, dv`` in their inputs' dtypes, ``dg``, ``dbeta`` float32."""
    return tuple(_pallas(
        _bwd_kernel, "kda_bwd", (*residuals, do),
        ["x", "x", "x", "x", "b", "kept", "t", "x"],
        [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in residuals[:5]],
        ["x", "x", "x", "x", "b"], chunk))


_kernel_scan.defvjp(_kernel_scan_fwd, _kernel_scan_bwd)


def _kernel_kda(q, k, v, g, beta, chunk: int) -> jax.Array:
    """`chunk_kda` on the kernels: the model's layout in and out, padded only
    where the length is no multiple of `_ROWS`."""
    b, s, h, d = k.shape
    pad = -s % _ROWS

    def flat(x):
        if pad:
            x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        return x.reshape(b, s + pad, -1)

    rows = jnp.moveaxis(flat(beta), 2, 1)[:, :, None, :]
    o = _kernel_scan(flat(q), flat(k), flat(v), flat(g), rows, chunk)
    return o.reshape(b, s + pad, h, d)[:, :s]
