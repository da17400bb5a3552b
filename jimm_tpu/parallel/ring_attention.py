"""Ring attention: exact attention over sequences sharded across devices.

Long-context sequence/context parallelism (absent from the reference — max
sequence there is 577 vision tokens, SURVEY §2.3). The sequence axis is
sharded over a mesh axis; each device keeps its local query block while
key/value blocks travel around the ring via ``jax.lax.ppermute``. Online
(flash-style) softmax accumulation in fp32 makes the result exact — identical
to full attention — while no device ever materializes the full sequence or
the full attention matrix. Differentiable end-to-end through the
``lax.scan``-of-``ppermute`` (JAX AD transposes the permutes).

Complements the Pallas flash kernel (`jimm_tpu/ops/flash_attention.py`):
flash blocks *within* a chip, the ring blocks *across* chips; compose them by
passing ``impl="flash"`` so each local block product uses the kernel.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Zigzag sequence layout (causal load balancing)
# ---------------------------------------------------------------------------
#
# With contiguous sharding, causal ring attention is imbalanced: the device
# holding the LAST chunk attends every other chunk (works in all rounds)
# while the first-chunk device works only in its own round. The zigzag
# layout splits the sequence into 2*n_dev chunks and gives device i the pair
# (i, 2n-1-i): early-half work and late-half work cancel, so every device
# does ~2 half-chunk products per round — per-rank times balance.

def zigzag_order(seq_len: int, n_dev: int):
    """Permutation taking the natural sequence order to the zigzag layout:
    position block i of the output is chunk i followed by chunk 2n-1-i, so
    plain contiguous sharding over ``n_dev`` devices lands each device its
    zigzag pair. ``seq_len`` must divide into 2*n_dev chunks."""
    import numpy as onp
    if seq_len % (2 * n_dev):
        raise ValueError(f"seq_len {seq_len} not divisible by 2*{n_dev}")
    c = seq_len // (2 * n_dev)
    parts = []
    for i in range(n_dev):
        parts.append(onp.arange(i * c, (i + 1) * c))
        j = 2 * n_dev - 1 - i
        parts.append(onp.arange(j * c, (j + 1) * c))
    return onp.concatenate(parts)


def zigzag_shard(x: jax.Array, n_dev: int, axis: int = 1) -> jax.Array:
    """Reorder ``axis`` from natural to zigzag layout (see `zigzag_order`)."""
    return jnp.take(x, zigzag_order(x.shape[axis], n_dev), axis=axis)


def zigzag_unshard(x: jax.Array, n_dev: int, axis: int = 1) -> jax.Array:
    """Inverse of `zigzag_shard`."""
    import numpy as onp
    order = zigzag_order(x.shape[axis], n_dev)
    inverse = onp.argsort(order)
    return jnp.take(x, inverse, axis=axis)


def _positions(dev, local_len: int, n_dev: int, zigzag: bool) -> jax.Array:
    """Global sequence positions of a device's local chunk. ``dev`` may be a
    traced ``axis_index``."""
    if not zigzag:
        return dev * local_len + jnp.arange(local_len)
    if local_len % 2:
        raise ValueError("zigzag needs an even local sequence length")
    h = local_len // 2
    early = dev * h + jnp.arange(h)
    late = (2 * n_dev - 1 - dev) * h + jnp.arange(h)
    return jnp.concatenate([early, late])


def _block(q, k, v, mask):
    """One (q-block x kv-block) partial attention: returns unnormalized
    accumulator pieces (m, p_sum, pv) in fp32. Shapes (B, Sq, N, D)."""
    d = q.shape[-1]
    qf = q.astype(jnp.float32) / jnp.sqrt(d)
    s = jnp.einsum("bqnd,bknd->bnqk", qf, k.astype(jnp.float32))
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)  # (B, N, Sq)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    pv = jnp.einsum("bnqk,bknd->bqnd", p, v.astype(jnp.float32))
    return m, l, pv


def _ring_local_flash(q, k, v, *, axis_name: str, causal: bool = False,
                      zigzag: bool = False):
    """Ring step where each local (q x kv-chunk) product is the Pallas flash
    kernel (`flash_attention_lse`); chunk results are merged by logsumexp
    reweighting.

    Causal decomposes per chunk pair (block-causal ring attention): the OWN
    chunk is a causal flash call (q/k positions align), chunks from EARLIER
    ring owners attend in full, and later owners' chunks are skipped
    entirely (``lax.cond`` keeps the carry) — no masked flops, and the skip
    halves the average work like the dense causal case.

    ``zigzag`` balances that skip across ranks (`zigzag_order` layout):
    each device holds the (i, 2n-1-i) chunk pair and every round runs
    exactly two half-chunk flash products regardless of rank, so the
    ppermute barrier no longer waits on the last-chunk straggler."""
    from jimm_tpu.ops.flash_attention import flash_attention_lse

    n_dev = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, sq, n, d = q.shape
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    def merge(qh, k_cur, v_cur, lse, acc, *, is_causal=False):
        o_blk, lse_blk = flash_attention_lse(qh, k_cur, v_cur,
                                             is_causal=is_causal)
        lse_new = jnp.logaddexp(lse, lse_blk)
        w_old = jnp.exp(lse - lse_new).transpose(0, 2, 1)[..., None]
        w_blk = jnp.exp(lse_blk - lse_new).transpose(0, 2, 1)[..., None]
        return lse_new, acc * w_old + o_blk.astype(jnp.float32) * w_blk

    if causal and zigzag:
        return _ring_zigzag_causal_flash(q, k, v, merge, idx=idx,
                                         n_dev=n_dev, axis_name=axis_name,
                                         perm=perm)

    combine = partial(merge, q)

    # own chunk first (the only causal-masked pair), then n_dev-1
    # permute+combine steps — no wasted final permute
    lse0 = jnp.full((b, n, sq), NEG_INF, jnp.float32)
    acc0 = jnp.zeros((b, sq, n, d), jnp.float32)
    lse, acc = combine(k, v, lse0, acc0, is_causal=causal)

    def step(carry, j):
        k_cur, v_cur, lse, acc = carry
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        if causal:
            src = (idx - j) % n_dev  # ring owner of this kv chunk
            lse, acc = jax.lax.cond(
                src < idx,  # strictly earlier positions: full attention
                lambda args: combine(k_cur, v_cur, *args),
                lambda args: args,
                (lse, acc))
        else:
            lse, acc = combine(k_cur, v_cur, lse, acc)
        return (k_cur, v_cur, lse, acc), None

    (_, _, _, acc), _ = jax.lax.scan(step, (k, v, lse, acc),
                                     jnp.arange(1, n_dev))
    return acc.astype(q.dtype)


def _ring_zigzag_causal_flash(q, k, v, merge, *, idx, n_dev, axis_name, perm):
    """Causal flash ring in the zigzag layout. Local chunks are the halves
    (early e at global chunk ``idx``, late l at ``2n-1-idx``). Chunk-level
    causality per (q half, kv half) pair:

    - own round: e<-e causal, l<-l causal, l<-e full (e<-l impossible);
    - kv from earlier rank s<i: e<-e full, l<-e full (both kv_l skipped:
      pos 2n-1-s > 2n-1-i = pos(q_l) and > i = pos(q_e));
    - kv from later rank s>i: l<-e full, l<-l full (q_e sees nothing).

    Every branch is two half-products -> balanced per-rank work."""
    b, sq, n, d = q.shape
    if sq % 2:
        raise ValueError("zigzag needs an even local sequence length")
    h = sq // 2

    def halves(x):
        return x[:, :h], x[:, h:]

    q_e, q_l = halves(q)
    lse0 = jnp.full((b, n, h), NEG_INF, jnp.float32)
    acc0 = jnp.zeros((b, h, n, d), jnp.float32)

    k_e, v_e = k[:, :h], v[:, :h]
    k_l, v_l = k[:, h:], v[:, h:]
    lse_e, acc_e = merge(q_e, k_e, v_e, lse0, acc0, is_causal=True)
    lse_l, acc_l = merge(q_l, k_l, v_l, lse0, acc0, is_causal=True)
    lse_l, acc_l = merge(q_l, k_e, v_e, lse_l, acc_l)

    def step(carry, j):
        k_cur, v_cur, lse_e, acc_e, lse_l, acc_l = carry
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        k_e, v_e = k_cur[:, :h], v_cur[:, :h]
        k_l, v_l = k_cur[:, h:], v_cur[:, h:]
        src = (idx - j) % n_dev

        def from_earlier(args):
            lse_e, acc_e, lse_l, acc_l = args
            lse_e, acc_e = merge(q_e, k_e, v_e, lse_e, acc_e)
            lse_l, acc_l = merge(q_l, k_e, v_e, lse_l, acc_l)
            return lse_e, acc_e, lse_l, acc_l

        def from_later(args):
            lse_e, acc_e, lse_l, acc_l = args
            lse_l, acc_l = merge(q_l, k_e, v_e, lse_l, acc_l)
            lse_l, acc_l = merge(q_l, k_l, v_l, lse_l, acc_l)
            return lse_e, acc_e, lse_l, acc_l

        lse_e, acc_e, lse_l, acc_l = jax.lax.cond(
            src < idx, from_earlier, from_later,
            (lse_e, acc_e, lse_l, acc_l))
        return (k_cur, v_cur, lse_e, acc_e, lse_l, acc_l), None

    (_, _, _, acc_e, _, acc_l), _ = jax.lax.scan(
        step, (k, v, lse_e, acc_e, lse_l, acc_l), jnp.arange(1, n_dev))
    return jnp.concatenate([acc_e, acc_l], axis=1).astype(q.dtype)


def _ring_local(q, k, v, *, axis_name: str, causal: bool,
                zigzag: bool = False):
    n_dev = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, sq, n, d = q.shape
    sk = k.shape[1]

    q_pos = _positions(idx, sq, n_dev, zigzag)

    def combine(j, k_cur, v_cur, m, l, acc):
        src = (idx - j) % n_dev  # ring owner of the current kv chunk
        k_pos = _positions(src, sk, n_dev, zigzag)
        mask = jnp.ones((sq, sk), bool)
        if causal:
            mask = k_pos[None, :] <= q_pos[:, None]
        m_blk, l_blk, pv_blk = _block(q, k_cur, v_cur,
                                      mask[None, None])  # (B,N,Sq[,D])
        m_new = jnp.maximum(m, m_blk)
        c_old = jnp.exp(m - m_new)
        c_blk = jnp.exp(m_blk - m_new)
        l_new = l * c_old + l_blk * c_blk
        acc_new = (acc * c_old.transpose(0, 2, 1)[..., None]
                   + pv_blk * c_blk.transpose(0, 2, 1)[..., None])
        return m_new, l_new, acc_new

    def step(carry, j):
        k_cur, v_cur, m, l, acc = carry
        m, l, acc = combine(j, k_cur, v_cur, m, l, acc)
        perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, m, l, acc), None

    m0 = jnp.full((b, n, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, n, sq), jnp.float32)
    acc0 = jnp.zeros((b, sq, n, d), jnp.float32)
    # n_dev-1 permuting steps, then the final chunk without the last permute
    (k, v, m, l, acc), _ = jax.lax.scan(step, (k, v, m0, l0, acc0),
                                        jnp.arange(n_dev - 1))
    m, l, acc = combine(n_dev - 1, k, v, m, l, acc)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc / l_safe.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   mesh: Mesh | None = None, axis_name: str = "seq",
                   is_causal: bool = False, impl: str = "einsum",
                   zigzag: bool = False) -> jax.Array:
    """Exact attention over ``(B, S, N, D)`` q/k/v whose sequence dim is
    sharded over ``axis_name``. Equals full (unsharded) attention to fp32
    accuracy.

    ``mesh=None`` uses the ambient mesh installed by
    ``jimm_tpu.parallel.use_sharding`` / ``jax.set_mesh``.

    ``impl="flash"`` runs each local (q x kv-chunk) product through the
    Pallas flash kernel and merges chunks by logsumexp reweighting — flash
    blocks within the chip, the ring blocks across chips; causal runs
    block-causally (own chunk causal, earlier chunks full, later skipped).
    ``impl="auto"`` picks flash on TPU, einsum otherwise.

    ``zigzag=True`` expects inputs (and produces outputs) in the
    `zigzag_order` sequence layout, which balances the causal skip across
    ranks (the contiguous layout leaves the last rank working every round).
    Use `zigzag_shard` / `zigzag_unshard` at the pipeline boundary — inside
    the model nothing changes because attention is permutation-covariant in
    sequence once positions are accounted for.
    """
    from jimm_tpu.parallel.mesh import resolve_mesh_axis
    # Works both outside and inside jit: the abstract mesh mirrors the
    # ambient concrete mesh installed by use_sharding/jax.set_mesh, and
    # shard_map binds the concrete one itself when no mesh is passed.
    shape = resolve_mesh_axis(mesh, axis_name)
    if impl == "auto":
        # Same shape gate as dot_product_attention's auto path: the Pallas
        # kernel is validated for head_dim 64/128/256 and per-chip chunks
        # worth blocking; everything else takes the einsum path.
        local_seq = q.shape[1] // shape[axis_name]
        flash_ok = (jax.default_backend() == "tpu"
                    and q.shape[-1] in (64, 128, 256) and local_seq >= 128)
        impl = "flash" if flash_ok else "einsum"
    if impl == "flash":
        local = partial(_ring_local_flash, axis_name=axis_name,
                        causal=is_causal, zigzag=zigzag)
    elif impl == "einsum":
        local = partial(_ring_local, axis_name=axis_name, causal=is_causal,
                        zigzag=zigzag)
    else:
        raise ValueError(f"unknown ring attention impl {impl!r}")
    kwargs = {} if mesh is None else {"mesh": mesh}  # None -> ambient mesh
    fn = jax.shard_map(
        local,
        in_specs=(P(None, axis_name), P(None, axis_name), P(None, axis_name)),
        out_specs=P(None, axis_name),
        check_vma=False, **kwargs)
    return fn(q, k, v)
