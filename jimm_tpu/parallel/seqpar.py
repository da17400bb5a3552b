"""Sequence-parallel attention plans: ring KV rotation and Ulysses head
scatter, behind one planner.

`ring_attention.py` proved the mechanism for plain softmax: shard the
sequence over a ``"seq"`` mesh axis, rotate KV chunks with
``jax.lax.ppermute``, and fold each hop into the online-normalizer carry
(the flash carry is associative, so the result is exact). This module
generalizes it into the *production* sequence-parallel path:

- **Variants share the carry.** Softmax, key-padding-masked softmax, and
  sigmoid attention all run through one hop loop. The mask chunk travels
  WITH its KV chunk around the ring (a ``(B, Sk/p)`` additive row vector per
  device), so NaFlex batches shard their padding too. Sigmoid has no row
  normalizer — its hops are plainly additive and reuse the same loop with a
  trivial carry.

- **Custom VJP re-rotates for dK/dV.** JAX AD through a scan-of-ppermute
  would save every hop's KV chunk — O(p) copies of the full KV, exactly the
  memory the ring exists to avoid. The hand-written backward recomputes each
  hop's probabilities from the saved GLOBAL ``(o, lse)`` (one chunk each),
  rotating ``(k, v, mask, dk_acc, dv_acc)`` together so gradient
  accumulators ride the same ring; after the last hop one final ppermute
  homes dk/dv to their owner devices. Per-hop grads against global
  statistics are exact: ``p_ij = exp(s_ij - lse_i)`` and
  ``delta_i = sum_j do_ij * o_ij`` already include every other chunk's
  contribution.

- **Per-hop flash on TPU.** With ``impl="flash"`` each hop's local product
  is the PR 9 Pallas core — `ring_hop_fwd`/`ring_hop_bwd` expose the shared
  kernel with external residuals, so the ring backward drives the SAME
  ``ds = p * (dp - delta)`` kernels as the single-chip path. ``impl="auto"``
  picks flash on TPU for supported head dims, einsum elsewhere (CPU tests
  run the einsum hops).

- **Ulysses is the alternate plan, not a fork.** When ``heads % p == 0``
  an all-to-all trades seq sharding for head sharding around the UNMODIFIED
  local kernel (`parallel/ulysses.py`), moving ~``4/p`` of the activation
  bytes per device versus ring's ``2·(p-1)/p`` — cheaper for ``p > 2``.
  `plan_seq_parallel` encodes that rule; `seq_parallel_attention` applies
  it (FastUSP: ring and head-scatter are alternate plans chosen by
  topology, PAPERS.md).

Observability: every hop runs under a ``ring_hop`` span +
``jax.named_scope`` (host span measures trace-time and annotates the
profiler timeline; the named scope labels the device timeline), and the
``jimm_ring_bytes_permuted_total`` counter accounts the plan's per-step
ppermute volume (incremented per wrapper call — once per trace under jit,
i.e. the counter tracks *planned* bytes/step, correlate with step counts
for rates).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


NEG_INF = -1e30

__all__ = ["seq_parallel_attention", "ring_attention_sp", "plan_seq_parallel",
           "seqpar_comm_bytes"]


# ---------------------------------------------------------------------------
# Planner
# ---------------------------------------------------------------------------

def seqpar_comm_bytes(b: int, s: int, n: int, d: int, p: int, *,
                      itemsize: int = 2, plan: str = "ring",
                      masked: bool = False) -> int:
    """Per-device bytes moved by one FORWARD step of a sequence-parallel
    plan over a ``p``-way axis (the number `jimm_ring_bytes_permuted_total`
    accounts, and the docs/performance.md table's formula).

    ring: ``(p-1)`` hops each rotating the local K and V chunks (plus the
    f32 mask rows when masked); ulysses: tiled all_to_all of q/k/v in and o
    out, each moving ``(p-1)/p`` of the local tensor.
    """
    local = (s // p) * n * d * itemsize * b
    if plan == "ring":
        bytes_ = 2 * (p - 1) * local
        if masked:
            bytes_ += (p - 1) * b * (s // p) * 4  # f32 additive mask rows
        return bytes_
    if plan == "ulysses":
        return 4 * local * (p - 1) // p
    raise ValueError(f"unknown seq-parallel plan {plan!r}")


def plan_seq_parallel(num_heads: int, axis_n: int, *,
                      plan: str = "auto") -> str:
    """Choose ring vs Ulysses for a ``p``-way seq axis.

    Ulysses needs ``heads % p == 0`` (the all_to_all splits the head axis).
    When it qualifies, its per-device comm volume is ``4·(p-1)/p²`` of the
    sequence activations versus ring's ``2·(p-1)/p`` — strictly cheaper for
    ``p > 2`` and a tie at ``p == 2``, where ring wins by overlapping each
    hop's compute with the next ppermute. Hence: ulysses iff divisible and
    ``p > 2``."""
    if plan != "auto":
        if plan not in ("ring", "ulysses"):
            raise ValueError(f"unknown seq-parallel plan {plan!r}")
        if plan == "ulysses" and num_heads % axis_n:
            raise ValueError(
                f"ulysses needs num_heads ({num_heads}) divisible by the "
                f"seq axis ({axis_n}); use plan='ring'")
        return plan
    if num_heads % axis_n == 0 and axis_n > 2:
        return "ulysses"
    return "ring"


# ---------------------------------------------------------------------------
# Ring core: one hop loop, three variants, custom VJP
# ---------------------------------------------------------------------------

def _rotate(axis_name, perm, *xs):
    """ppermute every non-None operand one step around the ring."""
    return tuple(None if x is None else jax.lax.ppermute(x, axis_name, perm)
                 for x in xs)


def _hop_scores(q, k_cur, mask_cur, sm_scale, causal, q_pos, k_pos):
    """f32 scores for one (local q × visiting kv chunk) product:
    ``(B, N, Sq, Sk)`` with the traveling additive mask rows and (when
    causal) the global-position causal term folded in."""
    s = jnp.einsum("bqnd,bknd->bnqk", q.astype(jnp.float32) * sm_scale,
                   k_cur.astype(jnp.float32))
    if mask_cur is not None:
        s = s + mask_cur[:, None, None, :]
    if causal:
        s = s + jnp.where(k_pos[None, :] <= q_pos[:, None], 0.0,
                          NEG_INF)[None, None]
    return s


def _hop_span(j: int):
    """Host span + device named_scope for ring hop ``j`` (see module doc)."""
    from contextlib import ExitStack

    from jimm_tpu.obs.spans import span
    stack = ExitStack()
    stack.enter_context(span("ring_hop"))
    stack.enter_context(jax.named_scope(f"ring_hop{j}"))
    return stack


def _ring_fwd_local(q, k, v, maskrows, axis_name, kind, causal, sm_scale,
                    logit_bias, impl, blocks):
    """Per-device forward: returns ``(o, lse)`` (lse None for sigmoid).
    ``maskrows`` is the local additive f32 ``(B, Sk/p)`` chunk or None."""
    n_dev = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, sq, n, d = q.shape
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
    q_pos = idx * sq + jnp.arange(sq) if causal else None

    if impl == "flash":
        return _ring_fwd_local_flash(q, k, v, maskrows, axis_name=axis_name,
                                     kind=kind, sm_scale=sm_scale,
                                     logit_bias=logit_bias, blocks=blocks,
                                     perm=perm, n_dev=n_dev)

    k_cur, v_cur, mask_cur = k, v, maskrows
    if kind == "sigmoid":
        acc = jnp.zeros((b, sq, n, d), jnp.float32)
        for j in range(n_dev):
            with _hop_span(j):
                src = (idx - j) % n_dev
                k_pos = src * sq + jnp.arange(sq) if causal else None
                s = _hop_scores(q, k_cur, mask_cur, sm_scale, causal,
                                q_pos, k_pos)
                p = jax.nn.sigmoid(s + logit_bias)
                acc = acc + jnp.einsum("bnqk,bknd->bqnd", p,
                                       v_cur.astype(jnp.float32))
                if j != n_dev - 1:
                    k_cur, v_cur, mask_cur = _rotate(
                        axis_name, perm, k_cur, v_cur, mask_cur)
        return acc.astype(q.dtype), None

    m = jnp.full((b, n, sq), NEG_INF, jnp.float32)
    l = jnp.zeros((b, n, sq), jnp.float32)
    acc = jnp.zeros((b, sq, n, d), jnp.float32)
    for j in range(n_dev):
        with _hop_span(j):
            src = (idx - j) % n_dev
            k_pos = src * sq + jnp.arange(sq) if causal else None
            s = _hop_scores(q, k_cur, mask_cur, sm_scale, causal,
                            q_pos, k_pos)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            scale = jnp.exp(m - m_new)
            l = l * scale + jnp.sum(p, axis=-1)
            acc = (acc * scale.transpose(0, 2, 1)[..., None]
                   + jnp.einsum("bnqk,bknd->bqnd", p,
                                v_cur.astype(jnp.float32)))
            m = m_new
            if j != n_dev - 1:
                k_cur, v_cur, mask_cur = _rotate(
                    axis_name, perm, k_cur, v_cur, mask_cur)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o = (acc / l_safe.transpose(0, 2, 1)[..., None]).astype(q.dtype)
    lse = m + jnp.log(l_safe)
    return o, lse


def _ring_fwd_local_flash(q, k, v, maskrows, *, axis_name, kind, sm_scale,
                          logit_bias, blocks, perm, n_dev):
    """Per-hop Pallas flash hops, merged by logsumexp reweighting (softmax)
    or plain summation (sigmoid). Runs in the flattened-heads ``(B*N, S, D)``
    space of the kernel family."""
    from jimm_tpu.ops.flash_attention import (VariantSpec, _expand_mask,
                                              _flatten_heads, ring_hop_fwd)
    b, sq, n, d = q.shape
    block_q, block_k = blocks
    spec = VariantSpec(kind="softmax" if kind == "softmax" else kind,
                       has_mask=maskrows is not None)
    q3, k3, v3 = map(_flatten_heads, (q, k, v))
    mask3 = (_expand_mask(maskrows > NEG_INF / 2, n)
             if maskrows is not None else None)

    if kind == "sigmoid":
        acc = jnp.zeros_like(q3, dtype=jnp.float32)
        for j in range(n_dev):
            with _hop_span(j):
                o_blk, _ = ring_hop_fwd(q3, k3, v3, mask3, spec, sm_scale,
                                        logit_bias, block_q, block_k)
                acc = acc + o_blk.astype(jnp.float32)
                if j != n_dev - 1:
                    k3, v3, mask3 = _rotate(axis_name, perm, k3, v3, mask3)
        return acc.astype(q.dtype).reshape(b, n, sq, d).transpose(
            0, 2, 1, 3), None

    lse = jnp.full((b * n, sq), NEG_INF, jnp.float32)
    acc = jnp.zeros_like(q3, dtype=jnp.float32)
    for j in range(n_dev):
        with _hop_span(j):
            o_blk, lse_blk = ring_hop_fwd(q3, k3, v3, mask3, spec, sm_scale,
                                          0.0, block_q, block_k)
            lse_new = jnp.logaddexp(lse, lse_blk)
            acc = (acc * jnp.exp(lse - lse_new)[..., None]
                   + o_blk.astype(jnp.float32)
                   * jnp.exp(lse_blk - lse_new)[..., None])
            lse = lse_new
            if j != n_dev - 1:
                k3, v3, mask3 = _rotate(axis_name, perm, k3, v3, mask3)
    o = acc.astype(q.dtype).reshape(b, n, sq, d).transpose(0, 2, 1, 3)
    return o, lse.reshape(b, n, sq)


def _hop_bwd_tile(q, k_cur, v_cur, mask_cur, do32, lse, delta, kind,
                  sm_scale, logit_bias, causal, q_pos, k_pos):
    """One (local q × visiting kv chunk) backward tile: recompute this
    hop's probabilities against the GLOBAL ``lse`` and return the
    ``(dq, dk, dv)`` increments. ``delta`` is None for sigmoid."""
    s = _hop_scores(q, k_cur, mask_cur, sm_scale, causal, q_pos, k_pos)
    dp = jnp.einsum("bqnd,bknd->bnqk", do32, v_cur.astype(jnp.float32))
    if kind == "sigmoid":
        p = jax.nn.sigmoid(s + logit_bias)
        ds = p * (1.0 - p) * dp
    else:
        p = jnp.exp(s - lse[..., None])
        ds = p * (dp - delta[..., None])
    dq_inc = sm_scale * jnp.einsum("bnqk,bknd->bqnd", ds,
                                   k_cur.astype(jnp.float32))
    dk_inc = sm_scale * jnp.einsum("bnqk,bqnd->bknd", ds,
                                   q.astype(jnp.float32))
    dv_inc = jnp.einsum("bnqk,bqnd->bknd", p, do32)
    return dq_inc, dk_inc, dv_inc


def _ring_bwd_local(q, k, v, maskrows, o, lse, do, axis_name, kind, causal,
                    sm_scale, logit_bias, impl, blocks):
    """Per-device backward. Recomputes each hop's probabilities against the
    GLOBAL (o, lse); (k, v, mask, dk_acc, dv_acc) rotate together and a
    final ppermute returns the accumulators to their owners."""
    n_dev = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, sq, n, d = q.shape
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    if impl == "flash":
        return _ring_bwd_local_flash(q, k, v, maskrows, o, lse, do,
                                     axis_name=axis_name, kind=kind,
                                     sm_scale=sm_scale, logit_bias=logit_bias,
                                     blocks=blocks, perm=perm, n_dev=n_dev)

    q_pos = idx * sq + jnp.arange(sq) if causal else None
    do32 = do.astype(jnp.float32)
    delta = None
    if kind == "softmax":
        # delta already includes every chunk's contribution (o is global)
        delta = jnp.sum(do32 * o.astype(jnp.float32),
                        axis=-1).transpose(0, 2, 1)  # (B, N, Sq)

    k_cur, v_cur, mask_cur = k, v, maskrows
    dq = jnp.zeros((b, sq, n, d), jnp.float32)
    dk_acc = jnp.zeros((b, sq, n, d), jnp.float32)
    dv_acc = jnp.zeros((b, sq, n, d), jnp.float32)
    for j in range(n_dev):
        with _hop_span(j):
            src = (idx - j) % n_dev
            k_pos = src * sq + jnp.arange(sq) if causal else None
            dq_inc, dk_inc, dv_inc = _hop_bwd_tile(
                q, k_cur, v_cur, mask_cur, do32, lse, delta, kind,
                sm_scale, logit_bias, causal, q_pos, k_pos)
            dq = dq + dq_inc
            dk_acc = dk_acc + dk_inc
            dv_acc = dv_acc + dv_inc
            if j != n_dev - 1:
                k_cur, v_cur, mask_cur, dk_acc, dv_acc = _rotate(
                    axis_name, perm, k_cur, v_cur, mask_cur, dk_acc, dv_acc)
    # accumulators now hold grads for chunk (idx+1) % n_dev; one more hop
    # homes them (full circle)
    dk_acc, dv_acc = _rotate(axis_name, perm, dk_acc, dv_acc)
    return (dq.astype(q.dtype), dk_acc.astype(k.dtype),
            dv_acc.astype(v.dtype),
            None if maskrows is None else jnp.zeros_like(maskrows))


def _ring_bwd_local_flash(q, k, v, maskrows, o, lse, do, *, axis_name, kind,
                          sm_scale, logit_bias, blocks, perm, n_dev):
    """Flash-kernel hops for the backward: the shared `_flash_bwd` kernels
    run per hop with external GLOBAL (o, lse) residuals — the same
    ``ds = p * (dp - delta)`` tiles as the single-chip path."""
    from jimm_tpu.ops.flash_attention import (VariantSpec, _expand_mask,
                                              _flatten_heads, ring_hop_bwd)
    b, sq, n, d = q.shape
    block_q, block_k = blocks
    spec = VariantSpec(kind="softmax" if kind == "softmax" else kind,
                       has_mask=maskrows is not None)
    q3, k3, v3, do3 = map(_flatten_heads, (q, k, v, do))
    o3 = _flatten_heads(o)
    lse3 = lse.reshape(b * n, sq) if lse is not None else None
    mask3 = (_expand_mask(maskrows > NEG_INF / 2, n)
             if maskrows is not None else None)

    dq3 = jnp.zeros_like(q3, dtype=jnp.float32)
    dk3 = jnp.zeros_like(k3, dtype=jnp.float32)
    dv3 = jnp.zeros_like(v3, dtype=jnp.float32)
    for j in range(n_dev):
        with _hop_span(j):
            dq_h, dk_h, dv_h = ring_hop_bwd(q3, k3, v3, mask3, o3, lse3, do3,
                                            spec, sm_scale, logit_bias,
                                            block_q, block_k)
            dq3 = dq3 + dq_h.astype(jnp.float32)
            dk3 = dk3 + dk_h.astype(jnp.float32)
            dv3 = dv3 + dv_h.astype(jnp.float32)
            if j != n_dev - 1:
                k3, v3, mask3, dk3, dv3 = _rotate(axis_name, perm, k3, v3,
                                                  mask3, dk3, dv3)
    dk3, dv3 = _rotate(axis_name, perm, dk3, dv3)

    def un3(x, like):
        return x.astype(like.dtype).reshape(b, n, sq, d).transpose(0, 2, 1, 3)

    return (un3(dq3, q), un3(dk3, k), un3(dv3, v),
            None if maskrows is None else jnp.zeros_like(maskrows))


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _ring_core(q, k, v, maskrows, axis_name, kind, causal, sm_scale,
               logit_bias, impl, blocks):
    o, _ = _ring_fwd_local(q, k, v, maskrows, axis_name, kind, causal,
                           sm_scale, logit_bias, impl, blocks)
    return o


def _ring_core_fwd(q, k, v, maskrows, axis_name, kind, causal, sm_scale,
                   logit_bias, impl, blocks):
    o, lse = _ring_fwd_local(q, k, v, maskrows, axis_name, kind, causal,
                             sm_scale, logit_bias, impl, blocks)
    # residuals: ONE local chunk each — no per-hop KV copies (the whole
    # point of writing this VJP by hand)
    return o, (q, k, v, maskrows, o, lse)


def _ring_core_bwd(axis_name, kind, causal, sm_scale, logit_bias, impl,
                   blocks, res, do):
    q, k, v, maskrows, o, lse = res
    dq, dk, dv, dmask = _ring_bwd_local(q, k, v, maskrows, o, lse, do,
                                        axis_name, kind, causal, sm_scale,
                                        logit_bias, impl, blocks)
    return dq, dk, dv, dmask


_ring_core.defvjp(_ring_core_fwd, _ring_core_bwd)


# ---------------------------------------------------------------------------
# Host-side wrappers
# ---------------------------------------------------------------------------

def _canon_mask_rows(mask, b: int, sk: int):
    """Bool key-padding mask ((B, Sk) or (B, 1, 1, Sk)) -> additive f32
    ``(B, Sk)`` rows (0 keep / NEG_INF drop) — the form that rotates."""
    if mask.ndim == 4:
        if mask.shape[1] != 1 or mask.shape[2] != 1:
            raise ValueError(
                "sequence-parallel attention supports KEY-PADDING masks "
                f"only ((B, Sk) or (B, 1, 1, Sk)); got {tuple(mask.shape)}")
        mask = mask[:, 0, 0, :]
    if mask.shape != (b, sk):
        raise ValueError(f"key-padding mask shape {tuple(mask.shape)} does "
                         f"not match (B, Sk)=({b}, {sk})")
    return jnp.where(mask != 0, 0.0, NEG_INF).astype(jnp.float32)


def _resolve_ring_blocks(q, k, v, n_dev: int, kind: str, masked: bool):
    """Per-hop flash block sizes through the tune cache: keyed on the LOCAL
    chunk shapes (what each hop's kernel actually sees), kernel name
    ``"ring_attention"``. Lookup only — never a measurement. A chunk short
    enough for the family's single-tile rule is one block, as on one chip."""
    from jimm_tpu.ops.flash_attention import (DEFAULT_BLOCK_K,
                                              DEFAULT_BLOCK_Q, VariantSpec,
                                              _fit_blocks)
    from jimm_tpu.tune import best_config
    local = lambda x: (x.shape[0], x.shape[1] // n_dev) + x.shape[2:]  # noqa: E731
    cfg = best_config("ring_attention", (local(q), local(k), local(v)),
                      (q.dtype, k.dtype, v.dtype),
                      default={"block_q": DEFAULT_BLOCK_Q,
                               "block_k": DEFAULT_BLOCK_K})
    return _fit_blocks(q.shape[1] // n_dev, k.shape[1] // n_dev, q.shape[-1],
                       q.dtype.itemsize,
                       VariantSpec(kind=kind, has_mask=masked),
                       int(cfg["block_q"]), int(cfg["block_k"]))


def _count_permuted_bytes(q, n_dev: int, *, plan: str, masked: bool) -> None:
    from jimm_tpu.obs.registry import get_registry
    b, s, n, d = q.shape
    by = seqpar_comm_bytes(b, s, n, d, n_dev, itemsize=q.dtype.itemsize,
                          plan=plan, masked=masked)
    get_registry("jimm_ring").counter(
        "jimm_ring_bytes_permuted_total").inc(by * n_dev)


def ring_attention_sp(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      mask: jax.Array | None = None, kind: str = "softmax",
                      is_causal: bool = False, mesh: Mesh | None = None,
                      axis_name: str = "seq", impl: str = "auto",
                      logit_bias: float | None = None) -> jax.Array:
    """Exact sequence-parallel attention over ``(B, S, N, D)`` q/k/v whose
    sequence dim is sharded over ``axis_name``; the key-padding ``mask``
    (bool ``(B, S)`` or ``(B, 1, 1, S)``) shards and rotates with KV.

    ``kind``: ``"softmax"`` (optionally masked/causal) or ``"sigmoid"``
    (SigLIP pairing; ``logit_bias`` defaults to ``-log(S_global)`` exactly
    like the single-chip op). ``impl``: ``"einsum"``, ``"flash"`` (per-hop
    Pallas core; non-causal only), or ``"auto"``.
    """
    from jimm_tpu.parallel.mesh import resolve_mesh_axis
    if kind not in ("softmax", "sigmoid"):
        raise ValueError(f"unknown ring variant kind {kind!r}")
    shape = resolve_mesh_axis(mesh, axis_name)
    n_dev = shape[axis_name]
    b, s, n, d = q.shape
    if s % n_dev or k.shape[1] % n_dev:
        raise ValueError(
            f"sequence length {s} (q) / {k.shape[1]} (k) not divisible by "
            f"seq axis {axis_name}={n_dev}")
    if q.shape[1] != k.shape[1]:
        raise ValueError("ring attention shards one sequence axis; "
                         f"Sq={q.shape[1]} != Sk={k.shape[1]}")
    sm_scale = 1.0 / math.sqrt(d)
    if kind == "sigmoid" and logit_bias is None:
        logit_bias = -math.log(max(k.shape[1], 1))
    maskrows = None if mask is None else _canon_mask_rows(mask, b, k.shape[1])

    if impl == "auto":
        flash_ok = (jax.default_backend() == "tpu" and d in (64, 128, 256)
                    and s // n_dev >= 128 and not is_causal)
        impl = "flash" if flash_ok else "einsum"
    if impl not in ("einsum", "flash"):
        raise ValueError(f"unknown ring attention impl {impl!r}")
    if impl == "flash" and is_causal:
        raise ValueError("the per-hop flash ring is non-causal (the hop "
                         "mask is key-padding rows); causal softmax rings "
                         "go through parallel/ring_attention.py")
    blocks = (_resolve_ring_blocks(q, k, v, n_dev, kind, mask is not None)
              if impl == "flash" else (0, 0))

    _count_permuted_bytes(q, n_dev, plan="ring", masked=mask is not None)
    lb = 0.0 if logit_bias is None else float(logit_bias)

    def local(q, k, v, mr):
        # custom_vjp nondiff args are positional by contract
        return _ring_core(q, k, v, mr, axis_name, kind, is_causal, sm_scale,
                          lb, impl, blocks)

    kwargs = {} if mesh is None else {"mesh": mesh}
    fn = jax.shard_map(local,
                       in_specs=(P(None, axis_name),) * 4,
                       out_specs=P(None, axis_name),
                       check_vma=False, **kwargs)
    return fn(q, k, v, maskrows)


def seq_parallel_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           mask: jax.Array | None = None,
                           kind: str = "softmax", is_causal: bool = False,
                           mesh: Mesh | None = None, axis_name: str = "seq",
                           plan: str = "auto", impl: str = "auto",
                           logit_bias: float | None = None) -> jax.Array:
    """One entry for both sequence-parallel plans: picks ring vs Ulysses via
    `plan_seq_parallel` (heads divisibility + comm cost), then dispatches.
    Exact in both cases."""
    from jimm_tpu.parallel.mesh import resolve_mesh_axis
    shape = resolve_mesh_axis(mesh, axis_name)
    n_dev = shape[axis_name]
    plan = plan_seq_parallel(q.shape[2], n_dev, plan=plan)
    if plan == "ulysses":
        from jimm_tpu.parallel.ulysses import ulysses_attention
        _count_permuted_bytes(q, n_dev, plan="ulysses",
                              masked=mask is not None)
        return ulysses_attention(q, k, v, mask=mask, kind=kind,
                                 is_causal=is_causal, mesh=mesh,
                                 axis_name=axis_name, impl=impl,
                                 logit_bias=logit_bias)
    return ring_attention_sp(q, k, v, mask=mask, kind=kind,
                             is_causal=is_causal, mesh=mesh,
                             axis_name=axis_name, impl=impl,
                             logit_bias=logit_bias)
