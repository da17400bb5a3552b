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

Everything but ``u = U - W S`` and ``S'`` is computed for several chunks at
once; a `lax.scan` carries ``S`` over the chunks and does those two products.

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

float32 inside, every product at `_PRECISION` (the MXU's six-pass float32; on
the v5e the setting moved neither the time nor a bit of the output). The loop
is held to little memory by its form: an outer `lax.scan` walks SLABS of
chunks, and inside a slab everything but the state's two products is computed
for the slab's chunks at once while an inner scan carries the state over them.
The backward (`_scan`'s own rule) keeps the inputs and the state each slab is
entered with (2 MB a head group; 268 MB a layer at 16,384 tokens in slabs of 2
chunks), walks the slabs in reverse and rebuilds one slab at a time,
differentiating it on the spot. Kept by plain autodiff, one layer's
intermediates are some twenty ``tokens x heads x d_k`` float32 arrays and the
pairwise exponents (``tokens x _SUB x heads x d_k``: gigabytes, which XLA
writes out where the backward reads them three times): more than a 16 GB chip
has beside the model (PERF.md, PR 38). A slab holds as many chunks as keep its
pairwise exponents under `_PAIRWISE_BYTES`. The kept states carry the name
``kda_states``, so a remat policy that keeps them (and the caller's copy of
the output) need not run the forward again in a layer's backward
(`nn/transformer.py::Transformer._remat_policy`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

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
    slab = min(n, max(1, _PAIRWISE_BYTES
                         // (b * h * 2 * chunk * _SUB * d * 4)))
    while n % slab:
        slab -= 1
    from jimm_tpu.obs.registry import get_registry
    registry = get_registry("jimm_kda")
    registry.counter("calls_total").inc()
    registry.counter("chunks_total").inc(n)

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
