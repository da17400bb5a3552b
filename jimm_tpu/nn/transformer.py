"""Transformer encoder stack, TPU-first.

Differences from the reference (`src/jimm/common/transformer.py`):

- Layers are *stacked* (one set of parameters with a leading ``layers`` dim,
  built via ``nnx.vmap``) and the forward is a ``jax.lax.scan`` via
  ``nnx.scan`` — constant compile time in depth and a clean FSDP unit,
  instead of the reference's python-unrolled ``nnx.Sequential``
  (ref `common/transformer.py:171-188`).
- Attention is a swappable functional kernel (`jimm_tpu/ops/attention.py`)
  over explicit ``(B, S, N, D)`` tensors with plain ``(H, H)`` projection
  kernels, not ``nnx.MultiHeadAttention``'s ``(H, N, D)`` layout — simpler
  checkpoint mapping and a direct hand-off to Pallas flash attention.
- Sharding comes from logical axis names resolved by a rules table
  (`jimm_tpu/parallel/sharding.py`), not per-callsite PartitionSpecs.

Parity-preserved semantics (SURVEY Appendix A):
- pre-LN residual order ``x + attn(ln1(x))``; ``x + mlp(ln2(x))``
  (ref `common/transformer.py:130-131`).
- causal masking equivalent to the reference's sliced float ``tril`` mask
  (ref `common/transformer.py:125-129`, `models/clip.py:62`).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from flax import nnx

from jax.ad_checkpoint import checkpoint_name

from jimm_tpu.configs import TransformerConfig
from jimm_tpu.ops.activations import get_activation
from jimm_tpu.ops.attention import dot_product_attention
from jimm_tpu.parallel.sharding import logical, logical_constraint

Dtype = jnp.dtype | None


def _linear(din: int, dout: int, names: tuple, rngs: nnx.Rngs, *,
            use_bias: bool = True, dtype: Dtype, param_dtype) -> nnx.Linear:
    return nnx.Linear(
        din, dout, use_bias=use_bias, dtype=dtype, param_dtype=param_dtype,
        kernel_init=logical(nnx.initializers.xavier_uniform(), *names),
        bias_init=logical(nnx.initializers.zeros_init(), names[-1]),
        rngs=rngs)


def _layernorm(dim: int, eps: float, rngs: nnx.Rngs, *, dtype: Dtype,
               param_dtype, impl: str = "xla") -> nnx.Module:
    if impl == "fused":
        from jimm_tpu.nn.norm import FusedLayerNorm
        return FusedLayerNorm(dim, epsilon=eps, dtype=dtype,
                              param_dtype=param_dtype, rngs=rngs)
    return nnx.LayerNorm(
        dim, epsilon=eps, dtype=dtype, param_dtype=param_dtype,
        scale_init=logical(nnx.initializers.ones_init(), "embed"),
        bias_init=logical(nnx.initializers.zeros_init(), "embed"),
        rngs=rngs)


def _norm(cfg: TransformerConfig, rngs: nnx.Rngs, *, dtype: Dtype,
          param_dtype, gain: float = 1.0) -> nnx.Module:
    """The block's normalisation: LayerNorm, or RMSNorm for the decoder
    family (``x / sqrt(mean(x^2) + eps) * w``, statistics in float32; ``w``
    starts at ``gain``)."""
    if cfg.norm == "rms":
        start = (nnx.initializers.ones_init() if gain == 1.0
                 else nnx.initializers.constant(gain))
        return nnx.RMSNorm(
            cfg.width, epsilon=cfg.ln_eps, dtype=dtype,
            param_dtype=param_dtype, scale_init=logical(start, "embed"),
            rngs=rngs)
    return _layernorm(cfg.width, cfg.ln_eps, rngs, dtype=dtype,
                      param_dtype=param_dtype, impl=cfg.ln_impl)


def rope_tables(seq_len: int, head_dim: int, theta: float
                ) -> tuple[jax.Array, jax.Array]:
    """``(cos, sin)`` of the rotary angles, each ``(seq_len, head_dim / 2)``
    float32: position ``t`` turns pair ``i`` by ``t * theta**(-2i / D)``."""
    inv_freq = theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                         / head_dim)
    angles = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, rope: tuple[jax.Array, jax.Array]) -> jax.Array:
    """Rotate ``(B, S, N, D)`` q or k: the rotate-half pairing, element ``i``
    with ``i + D/2``, in float32, back in ``x``'s dtype."""
    cos, sin = (t[None, :, None, :] for t in rope)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


class Attention(nnx.Module):
    """Multi-head attention with (H, H) q/k/v/out kernels; supports
    self-attention and cross-attention (MAP pooling probe).

    ``fused_qkv`` computes the three projections as one ``(H, 3H)`` matmul
    by concatenating the kernels at call time — parameters (and therefore
    checkpoints) stay separate, the concat is tiny next to the matmul, and
    gradients flow back through the slice.

    With ``gqa`` (`GQAConfig`) the heads have a width of their own and k and
    v fewer heads than q:

        q = x W_q -> (B, S, N, D);  k, v = x W_k, x W_v -> (B, S, N_kv, D)
        q, k = RMS_D(q), RMS_D(k)          one learned D-vector each (qk_norm)
        a windowed layer: rotary on q and k, key j visible to query i iff
            0 <= i - j < window;  a full layer: no rotary, every j <= i
        out = (softmax(q k^T / sqrt(D)) v * sigmoid(x W_gate)) W_o   (gate)

    ``scale`` (with ``gqa``) stands in for ``1 / sqrt(D)``: q is multiplied
    by ``scale * sqrt(D)`` before the call.
    """

    def __init__(self, width: int, num_heads: int, rngs: nnx.Rngs, *,
                 is_causal: bool = False, impl: str = "auto",
                 fused_qkv: bool = False, use_bias: bool = True,
                 gqa=None, ln_eps: float = 1e-6, scale: float | None = None,
                 dtype: Dtype = None, param_dtype=jnp.float32):
        if gqa is None and width % num_heads:
            raise ValueError(f"width {width} not divisible by heads {num_heads}")
        if gqa is not None and (fused_qkv or not is_causal):
            raise ValueError("grouped-query attention is causal and keeps "
                             "its three projections apart")
        self.num_heads = num_heads
        self.head_dim = gqa.head_dim if gqa else width // num_heads
        self.kv_heads = gqa.kv_heads if gqa else num_heads
        self.is_causal = is_causal
        self.impl = impl
        self.fused_qkv = fused_qkv
        self.dtype = dtype
        self.gqa = gqa
        self.q_scale = (None if scale is None
                        else scale * math.sqrt(self.head_dim))
        lin = partial(_linear, use_bias=use_bias, dtype=dtype,
                      param_dtype=param_dtype)
        inner, kv_inner = (n * self.head_dim
                           for n in (num_heads, self.kv_heads))
        self.q = lin(width, inner, ("embed", "heads"), rngs)
        self.k = lin(width, kv_inner, ("embed", "heads"), rngs)
        self.v = lin(width, kv_inner, ("embed", "heads"), rngs)
        self.out = lin(inner, width, ("heads", "embed"), rngs)
        if gqa is not None and gqa.gate:
            self.gate = lin(width, inner, ("embed", "heads"), rngs)
        if gqa is not None and gqa.qk_norm:
            def head_norm():
                return nnx.RMSNorm(
                    self.head_dim, epsilon=ln_eps, dtype=dtype,
                    param_dtype=param_dtype, scale_init=logical(
                        nnx.initializers.ones_init(), None), rngs=rngs)
            self.q_norm, self.k_norm = head_norm(), head_norm()

    def _project_qkv(self, x: jax.Array) -> tuple[jax.Array, ...]:
        w = jnp.concatenate([self.q.kernel[...], self.k.kernel[...],
                             self.v.kernel[...]], axis=1)
        dtype = self.dtype or x.dtype
        qkv = x.astype(dtype) @ w.astype(dtype)
        if self.q.bias is not None:
            b = jnp.concatenate([self.q.bias[...], self.k.bias[...],
                                 self.v.bias[...]])
            qkv = qkv + b.astype(dtype)
        return tuple(jnp.split(qkv, 3, axis=-1))

    def _grouped(self, x: jax.Array, mask, rope, full) -> jax.Array:
        """The ``gqa`` forward; ``full``: this layer is a full-attention
        layer (a Python bool, or a traced one where a stack mixes the kinds:
        then both calls are built once and a `lax.cond` picks)."""
        g = self.gqa
        b, s, _ = x.shape
        with jax.named_scope("attn"):
            q = self.q(x).reshape(b, s, self.num_heads, self.head_dim)
            k = self.k(x).reshape(b, s, self.kv_heads, self.head_dim)
            v = self.v(x).reshape(b, s, self.kv_heads, self.head_dim)
            if g.qk_norm:
                q, k = self.q_norm(q), self.k_norm(k)
            if self.q_scale is not None:
                q = q * jnp.asarray(self.q_scale, q.dtype)

            def attend(full: bool) -> jax.Array:
                qr, kr = (q, k) if full or rope is None else (
                    apply_rope(q, rope), apply_rope(k, rope))
                with jax.named_scope("attn_full" if full else "attn_window"):
                    return dot_product_attention(
                        qr, kr, v, is_causal=True, mask=mask, impl=self.impl,
                        window=None if full else g.window)

            if isinstance(full, bool):
                o = attend(full)
            else:
                o = jax.lax.cond(full, lambda: attend(True),
                                 lambda: attend(False))
            o = o.reshape(b, s, self.num_heads * self.head_dim)
            if g.gate:
                o = o * jax.nn.sigmoid(self.gate(x))
            return self.out(o)

    def __call__(self, x: jax.Array, kv: jax.Array | None = None,
                 mask: jax.Array | None = None,
                 rope: tuple[jax.Array, jax.Array] | None = None,
                 full: bool | jax.Array = False) -> jax.Array:
        if self.gqa is not None:
            return self._grouped(x, mask, rope, full)
        B, Sq, _ = x.shape
        if kv is None and self.fused_qkv:
            q, k, v = self._project_qkv(x)
            Sk = Sq
        else:
            kv = x if kv is None else kv
            Sk = kv.shape[1]
            q, k, v = self.q(x), self.k(kv), self.v(kv)
        q = q.reshape(B, Sq, self.num_heads, self.head_dim)
        k = k.reshape(B, Sk, self.num_heads, self.head_dim)
        v = v.reshape(B, Sk, self.num_heads, self.head_dim)
        if rope is not None:
            q, k = apply_rope(q, rope), apply_rope(k, rope)
        o = dot_product_attention(q, k, v, is_causal=self.is_causal,
                                  mask=mask, impl=self.impl)
        return self.out(o.reshape(B, Sq, self.num_heads * self.head_dim))


class Mlp(nnx.Module):
    """``fc2(act(fc1(x)))``; ``gated``: ``fc2(act(gate(x)) * fc1(x))``
    (SwiGLU with ``act="silu"``: ``fc1`` is the up projection)."""

    def __init__(self, width: int, mlp_dim: int, act: str, rngs: nnx.Rngs, *,
                 gated: bool = False, use_bias: bool = True,
                 dtype: Dtype = None, param_dtype=jnp.float32):
        lin = partial(_linear, use_bias=use_bias, dtype=dtype,
                      param_dtype=param_dtype)
        self.fc1 = lin(width, mlp_dim, ("embed", "mlp"), rngs)
        self.fc2 = lin(mlp_dim, width, ("mlp", "embed"), rngs)
        if gated:
            self.gate = lin(width, mlp_dim, ("embed", "mlp"), rngs)
        self.gated = gated
        self.act: Callable = get_activation(act)

    def __call__(self, x: jax.Array) -> jax.Array:
        if self.gated:
            h = self.act(self.gate(x)) * self.fc1(x)
        else:
            h = self.act(self.fc1(x))
        # name is free (identity) unless a "+act" remat policy saves it
        return self.fc2(checkpoint_name(h, "act_out"))


#: dropout-stream draws per Block.__call__ (attn residual + mlp residual);
#: the pipelined path strides its pinned RngCounts by this
_BLOCK_DROPOUT_DRAWS = 2


class Block(nnx.Module):
    """Pre-LN residual block (ref `common/transformer.py:116-132`). With
    ``cfg.post_norm`` each sub-layer's output is normed once more before
    the residual add (the decoder family's "sandwich")."""

    def __init__(self, cfg: TransformerConfig, rngs: nnx.Rngs, *,
                 dtype: Dtype = None, param_dtype=jnp.float32):
        norm = partial(_norm, cfg, rngs, dtype=dtype, param_dtype=param_dtype)
        self.ln1 = norm()
        if cfg.mla is not None:
            from jimm_tpu.nn.mla import LatentAttention
            self.attn = LatentAttention(cfg, rngs, dtype=dtype,
                                        param_dtype=param_dtype)
        elif cfg.kda is not None:
            from jimm_tpu.nn.kda import KimiDeltaAttention
            self.attn = KimiDeltaAttention(cfg, rngs, dtype=dtype,
                                           param_dtype=param_dtype)
        elif cfg.mamba is not None:
            from jimm_tpu.nn.mamba2 import Mamba2
            self.attn = Mamba2(cfg, rngs, dtype=dtype,
                               param_dtype=param_dtype)
        else:
            self.attn = Attention(cfg.width, cfg.num_heads, rngs,
                                  is_causal=cfg.causal, impl=cfg.attn_impl,
                                  fused_qkv=cfg.fused_qkv,
                                  use_bias=cfg.use_bias, gqa=cfg.gqa,
                                  ln_eps=cfg.ln_eps, scale=cfg.attn_scale,
                                  dtype=dtype, param_dtype=param_dtype)
        self.layer_kinds = cfg.gqa is not None  # windowed or full, per call
        self.ln2 = norm()
        self.sparse = cfg.moe is not None
        if self.sparse:
            from jimm_tpu.nn.moe import SparseMoe
            self.mlp = SparseMoe(cfg, rngs, dtype=dtype,
                                 param_dtype=param_dtype)
        else:
            self.mlp = Mlp(cfg.width, cfg.mlp_dim, cfg.act, rngs,
                           gated=cfg.gated_mlp, use_bias=cfg.use_bias,
                           dtype=dtype, param_dtype=param_dtype)
        self.dropout = nnx.Dropout(cfg.dropout, rngs=rngs)
        self.post_norm = cfg.post_norm
        self.residual_scale = cfg.residual_scale
        if cfg.post_norm:
            self.ln1_post = norm(gain=cfg.post_norm_gain)
            self.ln2_post = norm(gain=cfg.post_norm_gain)

    def __call__(self, x: jax.Array, mask: jax.Array | None = None,
                 rope: tuple[jax.Array, jax.Array] | None = None,
                 full: bool | jax.Array = False
                 ) -> jax.Array | tuple[jax.Array, jax.Array]:
        """The block's output; a sparse block returns ``(output, the experts
        each token chose)``. ``full``: under `GQAConfig`, whether this layer
        is a full-attention layer."""
        # ln outputs carry a checkpoint name so "+ln" remat policies can keep
        # them (skipping the LN recompute in the backward); plain identity
        # under every other policy
        a = self.attn(checkpoint_name(self.ln1(x), "ln_out"), mask=mask,
                      rope=rope, **({"full": full} if self.layer_kinds else {}))
        x = x + self._residual(self.ln1_post(a) if self.post_norm else a)
        m = self.mlp(checkpoint_name(self.ln2(x), "ln_out"))
        if self.sparse:
            m, chosen = m
        x = x + self._residual(self.ln2_post(m) if self.post_norm else m)
        x = logical_constraint(x, "batch", "seq", None)
        return (x, chosen) if self.sparse else x

    def _residual(self, y: jax.Array) -> jax.Array:
        """What a sub-layer adds to the residual stream."""
        if self.residual_scale != 1.0:
            y = y * jnp.asarray(self.residual_scale, y.dtype)
        return self.dropout(y)


class Transformer(nnx.Module):
    """Depth-stacked encoder, scanned over the ``layers`` axis. With
    ``cfg.loops`` the stack is entered that many times (`_apply_loops`); with
    ``cfg.moe`` the call returns ``(output, the experts each token chose
    (depth, tokens, top_k))``."""

    def __init__(self, cfg: TransformerConfig, rngs: nnx.Rngs, *,
                 dtype: Dtype = None, param_dtype=jnp.float32):
        self.cfg = cfg

        @nnx.split_rngs(splits=cfg.depth)
        @nnx.vmap(in_axes=0, out_axes=0,
                  transform_metadata={nnx.PARTITION_NAME: "layers"})
        def create_block(rngs: nnx.Rngs) -> Block:
            return Block(cfg, rngs, dtype=dtype, param_dtype=param_dtype)

        # the clone keeps the blocks' captured RngState from aliasing the
        # caller's rngs
        self.blocks = create_block(nnx.clone(rngs))
        if cfg.loops:
            if cfg.pipeline:
                raise ValueError("the looped stack has no pipelined path")
            # closes every pass; its output is the pass's result AND the
            # next pass's input
            self.norm = _norm(cfg, rngs, dtype=dtype, param_dtype=param_dtype)
        if cfg.pipeline and cfg.pp_virtual > 1 and cfg.pp_stages:
            # circular placement is baked into STORAGE order once at
            # construction (stored row j = canonical layer order[j]), so the
            # pipelined forward needs no per-step cross-stage all-to-all;
            # loaders/exporters reorder at their stacking edge to match
            from jimm_tpu.parallel.pipeline import circular_layer_order
            order = circular_layer_order(cfg.depth, cfg.pp_stages,
                                         cfg.pp_virtual)
            state = nnx.state(self.blocks)
            nnx.update(self.blocks,
                       jax.tree.map(lambda p: p[order], state))
        if cfg.pipeline and cfg.dropout > 0.0:
            # persistent schedule-tick counter: offsets the per-tick dropout
            # rng folding so masks differ across training steps (pipelined
            # path only — rng mutations inside shard_map don't propagate)
            self.pp_tick = nnx.Variable(jnp.zeros((), jnp.uint32))

    def _remat_policy(self):
        # "dots" keeps weight-matmul outputs (NOT the batched qk/pv dots —
        # saving S^2 attention probabilities is pure HBM waste) plus the
        # flash kernel's o/lse residuals, so the backward recomputes only
        # elementwise ops; "none" is classic full rematerialization.
        # "+ln" / "+act" additionally keep the LayerNorm / MLP-activation
        # outputs — a bit more HBM for one less elementwise recompute pass
        # each (half of SigLIP-B's `fwd_bwd` is not matmul time, PERF.md §5).
        from jimm_tpu.configs import remat_policy_parts
        policy = self.cfg.remat_policy
        if policy == "none":
            # everything is recomputed, but a sparse layer's routing choices
            # (`nn/moe.py::SparseMoe.route`): a top-k is not continuous
            if self.cfg.moe is not None:
                return jax.checkpoint_policies.save_only_these_names(
                    "moe_chosen")
            return None
        parts = remat_policy_parts(policy)
        # ... and the delta-rule scan's and the state-space scan's outputs and
        # kept states (`ops/delta_rule.py`, `ops/ssd.py`), so neither kernel
        # nor scan runs twice
        names = ["flash_o", "flash_lse", "kda_o", "kda_states", "ssm_y",
                 "ssm_states"]
        if "ln" in parts:
            names.append("ln_out")
        if "act" in parts:
            names.append("act_out")
        if "attn" in parts:
            # only the "saveable" attention impl emits this name — with any
            # other impl the save-list entry matches nothing and the run
            # silently measures plain "dots"
            if self.cfg.attn_impl != "saveable":
                raise ValueError(
                    f"remat_policy {policy!r} saves attention probabilities, "
                    f"but attn_impl={self.cfg.attn_impl!r} never emits them; "
                    "use attn_impl='saveable'")
            names.append("attn_probs")
        return jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            jax.checkpoint_policies.save_only_these_names(*names))

    def _apply_stack(self, blocks: Block, x: jax.Array,
                     mask: jax.Array | None = None,
                     rope: tuple[jax.Array, jax.Array] | None = None
                     ) -> jax.Array:
        """Scan ``x`` through a stacked block module (all layers or one
        pipeline stage's local slice). ``mask`` (bool, broadcastable to
        (B, N, Sq, Sk)) and the ``rope`` tables ride into every layer as
        closure captures — they are layer-invariant, so not scan carries."""
        # a sparse block's routing choices come out stacked by layer
        out_axes = (nnx.Carry, 0) if self.cfg.moe is not None else nnx.Carry
        scanned = partial(nnx.scan, out_axes=out_axes,
                          unroll=self.cfg.scan_unroll,
                          transform_metadata={nnx.PARTITION_NAME: "layers"})
        full = (self.cfg.gqa.full_layers(self.cfg.depth)
                if self.cfg.gqa is not None else ())
        if len(set(full)) > 1:
            # windowed layers beside full ones in one stack: the layer's kind
            # rides the scan beside its weights
            def body(block: Block, x: jax.Array, full: jax.Array):
                return block(x, mask=mask, rope=rope, full=full)

            if self.cfg.remat:
                body = nnx.remat(body, policy=self._remat_policy())
            return scanned(body, in_axes=(0, nnx.Carry, 0))(
                blocks, x, jnp.asarray(full))

        def body(block: Block, x: jax.Array) -> jax.Array:
            return block(x, mask=mask, rope=rope,
                         **({"full": full[0]} if full else {}))

        if self.cfg.remat:
            body = nnx.remat(body, policy=self._remat_policy())
        return scanned(body, in_axes=(0, nnx.Carry))(blocks, x)

    def _apply_loops(self, x: jax.Array, mask: jax.Array | None,
                     rope: tuple[jax.Array, jax.Array] | None) -> jax.Array:
        """``cfg.loops`` passes through the SAME stacked blocks, as one rolled
        outer scan whose body is `_apply_stack` (its own scan, unroll and
        remat policy) and the closing norm. The weights are closed over, not
        scanned: a shared leaf's gradient is the sum over its passes, and the
        backward keeps what the remat policy allows for loops x depth block
        applications. Returns the pass outputs, ``(loops, B, S, width)``."""
        def one_pass(modules, x):
            blocks, norm = modules
            x = norm(self._apply_stack(blocks, x, mask, rope))
            return x, x

        scan = nnx.scan(one_pass, in_axes=(None, nnx.Carry),
                        out_axes=(nnx.Carry, 0), length=self.cfg.loops)
        return scan((self.blocks, self.norm), x)[1]

    def __call__(self, x: jax.Array,
                 mask: jax.Array | None = None) -> jax.Array:
        rope = None
        if self.cfg.rope_theta is not None:
            rope = rope_tables(x.shape[1], self.cfg.rope_dim,
                               self.cfg.rope_theta)
        if self.cfg.loops:
            return self._apply_loops(x, mask, rope)
        if not self.cfg.pipeline:
            return self._apply_stack(self.blocks, x, mask, rope)
        if mask is not None:
            raise ValueError(
                "attention masks are not supported on the pipelined path "
                "yet (the stage loop has no mask plumbing); use "
                "pipeline=False — the non-pipelined path runs key-padding "
                "masks on the flash kernel (impl='flash_masked' / 'auto')")

        from jimm_tpu.parallel.pipeline import (circular_layer_order,
                                                pipeline_forward)
        from jimm_tpu.parallel.sharding import current_rules

        from jimm_tpu.configs import validate_pipeline

        mesh = jax.sharding.get_abstract_mesh()
        n_stage = dict(mesh.shape).get("stage", 0)
        # shared checks (stage axis present, depth divisibility, pp_stages
        # match) — identical function and messages as the parse-time path
        validate_pipeline(self.cfg, n_stages=n_stage)
        n_virtual = self.cfg.pp_virtual
        rules = current_rules()
        batch_axis = rules.batch if rules is not None else None
        if isinstance(batch_axis, str) and batch_axis not in mesh.shape:
            batch_axis = None
        graphdef, state = nnx.split(self.blocks)
        if n_virtual > 1 and self.cfg.pp_stages != n_stage:
            # a truthy-but-mismatched pp_stages was already rejected by
            # validate_pipeline above; pp_stages unknown at construction:
            # fall back to permuting per call — correct, but a cross-stage
            # all-to-all each step; set cfg.pp_stages to bake the placement
            # into storage instead
            order = circular_layer_order(self.cfg.depth, n_stage, n_virtual)
            state = jax.tree.map(lambda p: p[order], state)

        dropout_active = (self.cfg.dropout > 0.0
                          and not self.blocks.dropout.deterministic)
        tick_offset = 0
        if dropout_active:
            # rng mutations inside shard_map/scan are discarded, so dropout
            # draws fold the schedule tick into each layer's OWN key via the
            # RngCount slot; the persistent step counter advances the offset
            # so masks differ across training steps too.
            from jimm_tpu.parallel.pipeline import num_ticks
            t_total = num_ticks(self.cfg.pp_microbatches, n_stage, n_virtual)
            tick_offset = self.pp_tick.get_value()
            self.pp_tick.set_value(tick_offset + jnp.uint32(t_total))

        def stage_apply(state_chunk, xm, tick):
            # plain lax.scan + per-layer merge (nnx.scan can't consume
            # modules whose arrays were introduced at the enclosing
            # shard_map trace level)
            def body(h, layer_state):
                if dropout_active:
                    # a Block consumes _BLOCK_DROPOUT_DRAWS counts per call,
                    # so stride the pinned count — otherwise tick t's last
                    # draw equals tick t+1's first and masks repeat shifted
                    layer_state = _set_rng_counts(
                        layer_state, tick * _BLOCK_DROPOUT_DRAWS)
                return nnx.merge(graphdef, layer_state)(h), None

            if self.cfg.remat:
                body = jax.checkpoint(body, policy=self._remat_policy())
            out, _ = jax.lax.scan(body, xm, state_chunk,
                                  unroll=self.cfg.scan_unroll)
            return out

        return pipeline_forward(stage_apply, state, x,
                                n_microbatches=self.cfg.pp_microbatches,
                                n_virtual=n_virtual,
                                batch_axis=batch_axis,
                                tick_offset=tick_offset)


def _is_rng_count(leaf) -> bool:
    return isinstance(leaf, nnx.RngCount)


def _set_rng_counts(state, value) -> nnx.State:
    """Functionally pin every RngCount in ``state`` to ``value`` — each
    (layer key, tick) pair then draws a unique, deterministic dropout mask."""
    flat = nnx.to_flat_state(state)
    new = [(p, l.replace(jnp.asarray(value, jnp.uint32))
            if _is_rng_count(l) else l) for p, l in flat]
    return nnx.from_flat_state(new)
