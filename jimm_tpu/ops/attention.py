"""Attention dispatch: one functional entry point, swappable kernels.

The reference locks attention to ``flax.nnx.MultiHeadAttention``'s einsum path
(ref `common/transformer.py:67-87`). Here attention is a *function* over
``(B, S, N, D)`` q/k/v so the kernel is a config choice:

- ``"xla"``  — ``jax.nn.dot_product_attention`` (XLA fuses; fine for short
  vision/text sequences and for CPU tests).
- ``"flash"`` — Pallas TPU flash attention (fwd + custom-vjp bwd), used for
  training and long sequences. See `jimm_tpu/ops/flash_attention.py`.
  Key-padding masks route to the masked variant automatically.
- ``"flash_masked"`` — the key-padding-mask member of the flash family:
  per-sample ``(B, Sk)`` masks (NaFlex variable-resolution batches, MAP
  pooling) with flash tiling — no dense ``(B, N, Sq, Sk)`` scores.
- ``"flash_bias"`` — flash with an additive logits bias broadcastable to
  ``(N, Sq, Sk)`` (relative-position style), differentiable in the bias.
- ``"sigmoid"`` — sigmoid attention (no row normalizer, per "Theory,
  Analysis, and Best Practices for Sigmoid Self-Attention"): the natural
  pairing for SigLIP's sigmoid loss. Supports key-padding masks.
- ``"ring"`` — sequence-parallel ring attention over the ambient mesh's
  ``seq`` axis (long context across chips; flash within each hop on TPU).
  Key-padding masks ride the rotation. Causal softmax keeps the
  zigzag-balanced ring in `jimm_tpu/parallel/ring_attention.py`; the
  masked/sigmoid variants run the shared-carry ring in
  `jimm_tpu/parallel/seqpar.py`.
- ``"ulysses"`` — all-to-all sequence parallelism over the same ``seq``
  axis: one head-redistributing all_to_all in, full-sequence local
  attention (flash on TPU), one all_to_all out. Exact causal for free;
  needs ``num_heads`` divisible by the axis. See
  `jimm_tpu/parallel/ulysses.py`.
- ``"saveable"`` — explicit einsum attention whose bf16 probabilities carry a
  ``checkpoint_name`` so the ``"dots+attn"`` remat policy can keep them: the
  remat'd backward then skips the qk^T + softmax recompute at the cost of one
  (B, N, Sq, Sk) bf16 tensor per layer. Only sensible at short sequence.
- ``"auto"`` — when the ambient mesh carries a live ``seq`` axis and the
  shapes divide, route to the sequence-parallel planner (ring vs ulysses
  by comm cost — `jimm_tpu/parallel/seqpar.py`); otherwise flash on TPU
  when shapes qualify, else XLA. Key-padding masks route to
  ``flash_masked`` (instead of silently densifying) and batch-free biases
  to ``flash_bias``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name


def _default_backend() -> str:
    # Deliberately NOT cached: a script may dispatch once (initializing the
    # default platform) and then reconfigure jax.config / JAX_PLATFORMS; a
    # cached answer would lock "auto" onto the stale backend forever (same
    # reasoning as flash_attention._interpret).
    return jax.default_backend()


def _flash_eligible(q: jax.Array, k: jax.Array) -> bool:
    # the threshold predates the single-tile regime of the flash kernels
    # (PERF.md §7, candidate (2), has the readings that question it): when
    # it was set, XLA's fused attention won below seq 512 (grid-step
    # overhead dominated the tiled Pallas kernel at small tiles); flash wins
    # from 512 up and scales to long context where XLA's materialized S^2
    # probabilities drown in HBM traffic. Head dims are NOT gated here anymore: off-tile D (e.g. 80,
    # 96) is lane-padded to the next supported tile inside the flash
    # wrapper. Measured on v5e: padding D=80 -> 128 costs ~1.25x the
    # D=128 kernel's matmul FLOPs but still beats XLA's dense path past
    # the same seq-512 crossover, so eligibility stays a pure seq test.
    return q.shape[1] >= 512 and k.shape[1] >= 512


def _ambient_seq_axis() -> tuple[str, int] | None:
    """The ambient mesh's sequence-parallel axis, if one is installed and
    still available: size > 1 and not already consumed by an enclosing
    ``shard_map`` (a nested manual axis cannot be re-mapped). This is the
    gate that lets ``impl="auto"`` route to the sequence-parallel schemes
    exactly when the program runs under a seq-sharded mesh — single-chip
    programs never pay for the check beyond a mesh lookup."""
    from jimm_tpu.parallel.sharding import current_rules, manual_axis_names
    rules = current_rules()
    axis = (rules.seq if rules is not None and rules.seq else "seq")
    if not isinstance(axis, str):
        return None
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return None
    size = int(dict(mesh.shape).get(axis, 1))
    if size <= 1 or axis in manual_axis_names(mesh):
        return None
    return axis, size


def _is_key_padding_mask(mask: jax.Array) -> bool:
    """True for masks the flash family handles natively: per-sample key
    masks shaped ``(B, Sk)`` or the broadcast convention ``(B, 1, 1, Sk)``
    (what ``nn/vision.py`` builds for NaFlex / MAP pooling)."""
    if mask.ndim == 2:
        return True
    return mask.ndim == 4 and mask.shape[1] == 1 and mask.shape[2] == 1


#: the paths that take grouped key/value heads and a causal window
_GROUPED_WINDOW_IMPLS = ("auto", "flash", "xla", "einsum")


def dot_product_attention(
    q: jax.Array,  # (B, Sq, N, D)
    k: jax.Array,  # (B, Sk, N or N_kv, D)
    v: jax.Array,  # (B, Sk, N or N_kv, D)
    *,
    is_causal: bool = False,
    window: int | None = None,
    mask: jax.Array | None = None,  # broadcastable to (B, N, Sq, Sk), bool
    bias: jax.Array | None = None,  # additive logits bias
    impl: str = "auto",
) -> jax.Array:
    """Scaled dot-product attention over (batch, seq, heads, head_dim).

    **Grouped key/value heads**: k and v may have ``N_kv`` heads, a divisor
    of q's ``N``; query head ``h`` then reads key/value head
    ``h // (N / N_kv)``. The flash path fetches k and v at their own heads
    (no repeated copy, forward or backward) and returns dk and dv at them.
    **window** (with ``is_causal``): key ``j`` is visible to query ``i`` iff
    ``0 <= i - j < window``, the query's own position counted. A window that
    reaches over all ``Sk`` keys hides nothing and is dropped before dispatch,
    so such a call IS the plain causal call (same kernels, same cache key).
    Both run on the ``flash`` (a plain call: no mask, no bias), ``xla`` and
    ``einsum`` paths and through ``auto``; every other ``impl`` refuses
    them."""
    from jimm_tpu.ops.flash_attention import kv_group, live_window
    window = live_window(window, is_causal, k.shape[1])
    grouped = kv_group(q, k, v) > 1
    if (grouped or window is not None) and (
            impl not in _GROUPED_WINDOW_IMPLS
            or impl == "flash" and (mask is not None or bias is not None)):
        raise ValueError(
            f"impl={impl!r} takes neither grouped key/value heads nor a "
            "window (with a mask or a bias: impl='xla'); use one of "
            f"{_GROUPED_WINDOW_IMPLS}")
    if impl == "auto":
        # Sequence parallelism first: when the ambient mesh carries a live
        # seq axis the activations are (or are about to be) sharded along
        # sequence, so a single-chip kernel would silently all-gather the
        # full S — route to the seq-parallel schemes instead. Sq != Sk
        # (e.g. the MAP-pooling 1-row probe) or non-divisible lengths fall
        # through to the single-chip paths below.
        sp = (None if bias is not None or grouped or window is not None
              or (mask is not None and not _is_key_padding_mask(mask))
              else _ambient_seq_axis())
        if (sp is not None and q.shape[1] == k.shape[1]
                and q.shape[1] % sp[1] == 0):
            from jimm_tpu.parallel.seqpar import seq_parallel_attention
            return seq_parallel_attention(q, k, v, mask=mask,
                                          is_causal=is_causal,
                                          axis_name=sp[0], plan="auto")
        if _default_backend() == "tpu" and _flash_eligible(q, k):
            if bias is not None and mask is None and bias.ndim <= 3:
                impl = "flash_bias"
            elif bias is not None:
                impl = "xla"
            elif mask is None:
                impl = "flash"
            elif _is_key_padding_mask(mask) and not grouped and window is None:
                impl = "flash_masked"
            else:
                impl = "xla"
        else:
            impl = "xla"
    if impl == "flash":
        if mask is not None:
            if not _is_key_padding_mask(mask):
                raise ValueError(
                    "flash attention supports key-padding masks only "
                    "((B, Sk) or (B, 1, 1, Sk)); arbitrary "
                    f"{tuple(mask.shape)} masks need impl='xla'")
            impl = "flash_masked"
        elif bias is not None:
            impl = "flash_bias"
        else:
            from jimm_tpu.ops.flash_attention import flash_attention
            return flash_attention(q, k, v, is_causal=is_causal,
                                   window=window)
    if impl == "flash_masked":
        if bias is not None:
            raise ValueError("flash_masked does not take a bias; use "
                             "impl='flash_bias' (bias only) or impl='xla'")
        if mask is None:
            raise ValueError("impl='flash_masked' requires a key-padding "
                             "mask ((B, Sk) or (B, 1, 1, Sk))")
        from jimm_tpu.ops.flash_attention import flash_attention_masked
        return flash_attention_masked(q, k, v, mask, is_causal=is_causal)
    if impl == "flash_bias":
        if bias is None:
            raise ValueError("impl='flash_bias' requires a bias "
                             "broadcastable to (N, Sq, Sk)")
        if mask is not None:
            raise ValueError("flash_bias does not take a mask; use "
                             "impl='flash_masked' (mask only) or "
                             "impl='xla'")
        from jimm_tpu.ops.flash_attention import flash_attention_bias
        return flash_attention_bias(q, k, v, bias, is_causal=is_causal)
    if impl == "flash_int8":
        if mask is not None or bias is not None:
            raise ValueError(
                "flash_int8 does not support masks or biases — the int8 "
                "score kernel has no mask/bias plumbing; use is_causal, "
                "or impl='flash_masked' / 'xla' for masked batches")
        from jimm_tpu.ops.flash_attention_int8 import flash_attention_int8
        return flash_attention_int8(q, k, v, is_causal=is_causal)
    if impl == "sigmoid":
        if bias is not None:
            raise ValueError("sigmoid attention takes no additive bias "
                             "(its scalar logit_bias is set by the op)")
        if mask is not None and not _is_key_padding_mask(mask):
            raise ValueError(
                "sigmoid attention supports key-padding masks only "
                f"((B, Sk) or (B, 1, 1, Sk)); got {tuple(mask.shape)}")
        from jimm_tpu.ops.flash_attention import sigmoid_attention
        return sigmoid_attention(q, k, v, is_causal=is_causal, mask=mask)
    if impl in ("ring", "ulysses"):
        if bias is not None:
            raise ValueError(
                f"{impl} attention does not take an additive bias — the "
                "cross-chip exchange only rotates per-sample key-padding "
                "rows; use impl='flash_bias' single-chip or impl='xla'")
        if mask is not None and not _is_key_padding_mask(mask):
            raise ValueError(
                f"{impl} attention supports key-padding masks only "
                f"((B, Sk) or (B, 1, 1, Sk)); got {tuple(mask.shape)} — "
                "arbitrary masks need impl='xla'")
        from jimm_tpu.parallel.sharding import current_rules
        rules = current_rules()
        axis = (rules.seq if rules is not None and rules.seq else "seq")
        if impl == "ring" and is_causal and mask is None:
            # causal softmax keeps the zigzag-balanced ring (exact causal
            # skipping); the seqpar ring is the masked/sigmoid generalist
            from jimm_tpu.parallel.ring_attention import ring_attention
            return ring_attention(q, k, v, axis_name=axis,
                                  is_causal=True, impl="auto")
        from jimm_tpu.parallel.seqpar import seq_parallel_attention
        return seq_parallel_attention(q, k, v, mask=mask, axis_name=axis,
                                      is_causal=is_causal, plan=impl)
    if impl == "xla":
        d_v = v.shape[-1]
        # XLA's op takes grouped heads as they are; its window is (keys left
        # of the query, keys right of it), the query's own not counted
        local = {} if window is None else {
            "local_window_size": (window - 1, 0)}
        if d_v != q.shape[-1]:
            # XLA's op wants one head width: zero columns of v give zero
            # columns of the output, cut off again
            v = jnp.pad(v, ((0, 0),) * 3 + ((0, q.shape[-1] - d_v),))
            return jax.nn.dot_product_attention(
                q, k, v, bias=bias, mask=mask, is_causal=is_causal,
                **local)[..., :d_v]
        return jax.nn.dot_product_attention(q, k, v, bias=bias, mask=mask,
                                            is_causal=is_causal, **local)
    if impl == "saveable":
        return saveable_attention(q, k, v, is_causal=is_causal, mask=mask,
                                  bias=bias)
    if impl == "einsum":  # reference semantics, fp32 softmax; used in tests
        return reference_attention(q, k, v, is_causal=is_causal, mask=mask,
                                   bias=bias, window=window)
    raise ValueError(f"unknown attention impl {impl!r}")


def saveable_attention(q, k, v, *, is_causal=False, mask=None, bias=None):
    """Attention with fp32-softmax numerics (matching the XLA path) whose
    probabilities are bf16-cast and checkpoint-named: under a ``"dots+attn"``
    remat policy the backward reuses them instead of recomputing
    qk^T + softmax — ~half the attention recompute FLOPs for
    ``O(B*N*Sq*Sk)`` bytes of HBM. The ``p @ v`` product is a batched dot,
    deliberately NOT saved (recomputing it from saved p is one matmul)."""
    dtype = q.dtype
    depth = q.shape[-1]
    logits = jnp.einsum("bqnd,bknd->bnqk", q, k,
                        preferred_element_type=jnp.float32)
    logits = logits * (1.0 / depth ** 0.5)
    sq, sk = logits.shape[-2], logits.shape[-1]
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if is_causal:
        causal = jnp.tril(jnp.ones((sq, sk), dtype=bool))
        logits = jnp.where(causal, logits, -jnp.inf)
    if mask is not None:
        logits = jnp.where(mask, logits, -jnp.inf)
    probs = checkpoint_name(
        jax.nn.softmax(logits, axis=-1).astype(dtype), "attn_probs")
    return jnp.einsum("bnqk,bknd->bqnd", probs, v)


def reference_attention(q, k, v, *, is_causal=False, mask=None, bias=None,
                        window=None):
    """Plain einsum attention with fp32 softmax — numerical oracle for tests.
    Grouped key/value heads are repeated to q's; ``window`` as in
    `dot_product_attention`."""
    dtype = q.dtype
    depth = q.shape[-1]
    if k.shape[2] != q.shape[2]:
        group = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    q = q.astype(jnp.float32) / jnp.sqrt(depth)
    logits = jnp.einsum("bqnd,bknd->bnqk", q, k.astype(jnp.float32))
    sq, sk = logits.shape[-2], logits.shape[-1]
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if is_causal:
        causal = jnp.tril(jnp.ones((sq, sk), dtype=bool))
        logits = jnp.where(causal, logits, -jnp.inf)
    if window is not None:
        near = jnp.arange(sq)[:, None] - jnp.arange(sk)[None, :] < window
        logits = jnp.where(near, logits, -jnp.inf)
    if mask is not None:
        logits = jnp.where(mask, logits, -jnp.inf)
    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bnqk,bknd->bqnd", weights, v.astype(jnp.float32))
    return out.astype(dtype)


def reference_sigmoid_attention(q, k, v, *, is_causal=False, mask=None,
                                logit_bias=None):
    """Einsum sigmoid attention with fp32 activations — the numerical
    oracle for `jimm_tpu.ops.flash_attention.sigmoid_attention` (same
    ``-log(Sk)`` default logit bias, same mask convention)."""
    import math
    dtype = q.dtype
    depth = q.shape[-1]
    sk = k.shape[1]
    if logit_bias is None:
        logit_bias = -math.log(max(sk, 1))
    q = q.astype(jnp.float32) / jnp.sqrt(depth)
    logits = jnp.einsum("bqnd,bknd->bnqk", q, k.astype(jnp.float32))
    logits = logits + logit_bias
    sq = logits.shape[-2]
    if is_causal:
        causal = jnp.tril(jnp.ones((sq, sk), dtype=bool))
        logits = jnp.where(causal, logits, -jnp.inf)
    if mask is not None:
        if mask.ndim == 2:
            mask = mask[:, None, None, :]
        logits = jnp.where(mask, logits, -jnp.inf)
    weights = jax.nn.sigmoid(logits)
    out = jnp.einsum("bnqk,bknd->bqnd", weights, v.astype(jnp.float32))
    return out.astype(dtype)
