"""Attention dispatch: one functional entry point, swappable kernels.

The reference locks attention to ``flax.nnx.MultiHeadAttention``'s einsum path
(ref `common/transformer.py:67-87`). Here attention is a *function* over
``(B, S, N, D)`` q/k/v so the kernel is a config choice:

- ``"xla"``  — ``jax.nn.dot_product_attention`` (XLA fuses; what wins for
  short or small calls, whose scores stay on the chip, and for CPU tests).
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
  where `_flash_eligible` says the kernels win (every call from 512 tokens
  up; under 512 from 128 tokens and 20 Mi scores up at a head width on the
  tiles, set from chip readings), else XLA. Key-padding masks route to
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


#: from this many tokens (the shorter of S_q, S_k) every plain, masked or
#: bias call takes the flash family, as it has since the tiled kernels
_FLASH_MIN_SEQ = 512
#: under `_FLASH_MIN_SEQ`: one whole 128-lane tile of tokens, and this many
#: scores (B * N * S_q * S_k; 80 MiB of them in float32) in the call
_SHORT_MIN_SEQ = 128
_SHORT_MIN_SCORES = 20 << 20


def _mesh_partitions() -> bool:
    """Whether the ambient mesh would have XLA partition this call: an axis
    of more than one device that no enclosing ``shard_map`` has mapped.
    Mosaic kernels cannot be partitioned automatically (the step of a
    ``data`` / ``model`` mesh with a kernel call in it does not compile)."""
    from jimm_tpu.parallel.sharding import manual_axis_names
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return False
    manual = manual_axis_names(mesh)
    return any(size > 1 and name not in manual
               for name, size in dict(mesh.shape).items())


def _flash_eligible(q: jax.Array, k: jax.Array, *,
                    has_bias: bool = False) -> bool:
    """THE dispatch rule of ``impl="auto"`` on a TPU, a test of what the call
    shows: lengths, batch, heads, head width, bias, and the ambient mesh.

    From 512 tokens up every call takes the flash family (unchanged: the
    tiled kernels stream where XLA's S^2 probabilities drown in HBM traffic;
    an off-tile head width is lane-padded inside the wrapper). Under 512 the
    single-tile pair takes a call when all of these hold, each set from the
    readings below:

    - ``min(S_q, S_k) >= 128``: a shorter sequence is padded to one 128-lane
      tile and does that tile's work; nothing under 128 won a column, and a
      1-row MAP probe against 256 keys loses threefold.
    - ``B * N * S_q * S_k >= 20 Mi`` scores: XLA keeps a score tensor of
      tens of MiB on the chip between its fusions and is then 2-3 times
      faster than any kernel (a serving batch of 8); past about 96 MiB of
      float32 scores it spills and loses.
    - the head width is 64, 128 or 256 (72, 80 and 96 pay the pad to 128
      lanes: forward slower, training a tie), q and k have the same heads
      (a grouped call runs the tiled kernels: forward 1.5 times XLA's at 256
      and 384), there is no bias (the bias variant stays tiled; not
      measured), and no mesh axis would partition the call.

    Causality, a window, a key-padding mask and the dtype do not enter: at
    `(128, 256, 12, 64)` causal, windowed, masked and float32 calls read
    like the plain bf16 one or better (XLA's masked forward at 196 doubles).

    Readings (v5e, PR 35, `docs/performance.md` has them all): one call as
    `Attention` makes it, ``(B, S, N * D)`` in and out, bf16, host clock over
    30 back-to-back calls, ms, XLA | kernels; forward / forward + backward /
    the same under ``--remat dots``' policy (XLA's forward runs twice, the
    kernels' ``o`` and ``lse`` are kept by name). ``*``: eight chained calls
    in one program, where the host's 0.2 ms a dispatch would hide the call.

        (128, 256, 12, 64)  SigLIP-B/16-256  1.45|1.16  5.71|2.34  6.87|2.36
          with a key-padding mask            1.45|1.18  5.70|2.36  6.87|2.38
        (128, 197, 12, 64)  ViT-B/16-224     1.26|1.58  4.86|3.22  5.37|3.77
        (128, 196, 12, 64)  SigLIP-B/16-224  1.25|1.57  4.85|3.22  5.36|3.79
        (64, 257, 16, 64)   CLIP-L/14        1.45|1.44  5.91|3.00  6.54|3.31
        (128, 128, 12, 64)                   0.47|0.36  1.32|1.10  1.61|1.22
        (128, 129, 12, 64)                   0.88|1.44  3.23|2.99  3.65|3.10
        (128, 112, 12, 64)                   0.31|0.37  1.13|1.08  1.27|1.17
        (128, 64, 12, 64)   SigLIP text      0.37|0.36  1.03|1.07  1.29|1.12
        (128, 50, 12, 64)   ViT-B/32         0.44|0.46  1.00|1.24  1.32|1.37
        (128, 77, 8, 64) causal, CLIP text   0.19|0.36  0.55|0.92  0.54|1.04
        (32, 256, 16, 72)   So400m/14-224    0.54|0.69  1.64|1.41  1.92|1.85
        (32, 257, 16, 80)   ViT-H/14         0.79|1.35  2.64|2.70  3.14|3.32
        (128, 256, 12 over 4, 64) causal     1.37|2.13  5.46|4.90  6.18|5.92
        (128, 1 | 256, 12, 64)  MAP probe    0.22|0.46  0.52|1.74  0.81|1.74
        (8, 256, 12, 64) *                   0.026|0.075
        (32, 197, 12, 64) *                  0.14|0.30  0.84|0.63  1.07|0.64
        (32, 256, 12, 64) *                  0.32|0.29  0.98|0.61  1.41|0.63

    A shape sits on the side whose worst column loses least (so a forward
    that loses twofold outweighs a training step that wins by half, as at
    `(32, 197, 12, 64)`: the serve engine's forward shares this dispatch);
    a shape within 15 % in every column stays on XLA, where it was (S = 64).
    **What the rule costs:** lengths of 129-223 at a training batch run the
    kernels of their padded 256: training gains 30-50 % a layer from 144 up,
    and a forward alone at batch 64-128 loses 21-25 % at 196 / 197; at
    129-143 that measure would keep XLA (forward 63 % slower, training 8-15 %
    faster at 129: the rule's worst point, where no preset is). The kernels
    clean an edge tile's rows there (1.57 ms where the aligned 256 takes
    1.16)."""
    from jimm_tpu.ops.flash_attention import _HEAD_TILES
    b, s_q, n, d = q.shape
    s_k = k.shape[1]
    shorter = min(s_q, s_k)
    if shorter >= _FLASH_MIN_SEQ:
        return True
    return (shorter >= _SHORT_MIN_SEQ
            and b * n * s_q * s_k >= _SHORT_MIN_SCORES
            and d in _HEAD_TILES and k.shape[2] == n and not has_bias
            and not _mesh_partitions())


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
        if _default_backend() == "tpu" and _flash_eligible(
                q, k, has_bias=bias is not None):
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
