"""All-to-all (Ulysses-style) sequence parallelism: the second SP scheme.

Complements `ring_attention` (absent from the reference, whose max sequence
is 577 vision tokens — SURVEY §2.3). Where the ring keeps queries local and
rotates key/value chunks via ``ppermute`` (P2P bandwidth, O(p) steps), the
all-to-all scheme redistributes ONCE per attention call: an
``all_to_all`` swaps the sharded axis from sequence to heads, every device
runs ordinary full-sequence attention over its head subset — causal masking
is exact with zero extra machinery, and the single-chip Pallas flash kernel
applies unchanged — then a second ``all_to_all`` swaps back. Four
all-to-alls total (q, k, v in; o out) instead of a p-step scan; the trade
is head-count divisibility (``num_heads % axis_size == 0``) and all-to-all bandwidth,
which rides the TPU ICI fabric well.

Same call contract as `ring_attention`: full ``(B, S, N, D)`` arrays whose
sequence dim is sharded over ``axis_name``; exact (fp32-softmax) equality
with unsharded attention is tested in `tests/test_ulysses.py`.
"""

from __future__ import annotations

from functools import partial

import jax
from jax.sharding import Mesh, PartitionSpec as P


def _seq_to_heads(x: jax.Array, axis_name: str) -> jax.Array:
    """(B, S/p, N, D) per device -> (B, S, N/p, D): shard heads, gather
    sequence. One tiled all-to-all over the SP axis."""
    return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)


def _heads_to_seq(x: jax.Array, axis_name: str) -> jax.Array:
    """Inverse of `_seq_to_heads`."""
    return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)


def _ulysses_local(q, k, v, mask, *, axis_name: str, kind: str, causal: bool,
                   impl: str, logit_bias):
    # head divisibility was validated by ulysses_attention before shard_map.
    # The key-padding mask enters REPLICATED (every device holds the full
    # (B, S) rows — bytes are trivial next to KV) so the local full-sequence
    # kernel applies it directly: no gather, nothing rides the exchange.
    qg = _seq_to_heads(q, axis_name)
    kg = _seq_to_heads(k, axis_name)
    vg = _seq_to_heads(v, axis_name)
    if kind == "sigmoid":
        if impl == "flash":
            from jimm_tpu.ops.flash_attention import sigmoid_attention
            o = sigmoid_attention(qg, kg, vg, is_causal=causal, mask=mask,
                                  logit_bias=logit_bias)
        else:
            from jimm_tpu.ops.attention import reference_sigmoid_attention
            o = reference_sigmoid_attention(qg, kg, vg, is_causal=causal,
                                            mask=mask, logit_bias=logit_bias)
    elif impl == "flash":
        if mask is not None:
            from jimm_tpu.ops.flash_attention import flash_attention_masked
            o = flash_attention_masked(qg, kg, vg, mask, is_causal=causal)
        else:
            from jimm_tpu.ops.flash_attention import flash_attention
            o = flash_attention(qg, kg, vg, is_causal=causal)
    else:
        from jimm_tpu.ops.attention import reference_attention
        mask4 = mask if mask is None else (mask != 0)[:, None, None, :]
        o = reference_attention(qg, kg, vg, is_causal=causal, mask=mask4)
    return _heads_to_seq(o, axis_name)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      mask: jax.Array | None = None, kind: str = "softmax",
                      mesh: Mesh | None = None, axis_name: str = "seq",
                      is_causal: bool = False, impl: str = "auto",
                      logit_bias: float | None = None) -> jax.Array:
    """Exact attention over ``(B, S, N, D)`` q/k/v whose sequence dim is
    sharded over ``axis_name``, via head redistribution (see module
    docstring). ``impl="flash"`` runs each device's full-sequence head
    subset through the Pallas kernel (``"auto"``: flash on TPU when shapes
    qualify, einsum otherwise).

    ``mask`` is a per-sample key-padding mask (bool ``(B, S)`` or
    ``(B, 1, 1, S)``), passed replicated to the local kernels.
    ``kind="sigmoid"`` runs SigLIP-style sigmoid attention (``logit_bias``
    defaults to ``-log(S_global)`` inside the op — after redistribution the
    local kernel sees the full sequence, so the single-chip default is
    already the global one)."""
    from jimm_tpu.parallel.mesh import resolve_mesh_axis
    shape = resolve_mesh_axis(mesh, axis_name)
    if q.shape[2] % shape[axis_name]:
        raise ValueError(f"ulysses attention needs num_heads {q.shape[2]} "
                         f"divisible by the {axis_name!r} axis size "
                         f"{shape[axis_name]} (use attn_impl='ring' "
                         "otherwise)")
    if kind not in ("softmax", "sigmoid"):
        raise ValueError(f"unknown ulysses variant kind {kind!r}")
    if mask is not None and mask.ndim == 4:
        if mask.shape[1] != 1 or mask.shape[2] != 1:
            raise ValueError(
                "ulysses attention supports KEY-PADDING masks only "
                f"((B, Sk) or (B, 1, 1, Sk)); got {tuple(mask.shape)}")
        mask = mask[:, 0, 0, :]
    if impl == "auto":
        # after redistribution each device sees the FULL sequence, so the
        # measured single-chip crossover gate applies to the global length
        from jimm_tpu.ops.attention import _flash_eligible
        flash_ok = (jax.default_backend() == "tpu" and _flash_eligible(q, k))
        impl = "flash" if flash_ok else "einsum"
    if impl not in ("flash", "einsum"):
        raise ValueError(f"unknown ulysses attention impl {impl!r}")
    local = partial(_ulysses_local, axis_name=axis_name, kind=kind,
                    causal=is_causal, impl=impl, logit_bias=logit_bias)
    kwargs = {} if mesh is None else {"mesh": mesh}  # None -> ambient mesh
    fn = jax.shard_map(
        local,
        in_specs=(P(None, axis_name), P(None, axis_name), P(None, axis_name),
                  P()),  # mask replicated — see _ulysses_local
        out_specs=P(None, axis_name),
        check_vma=False, **kwargs)
    return fn(q, k, v, mask)
