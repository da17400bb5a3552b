"""Pipeline parallelism: depth-sharded layer stacks with a microbatched
collective-permute loop, GPipe or interleaved (circular-placement) schedule.

Absent from the reference (its stack is a python ``nnx.Sequential``,
ref `common/transformer.py:171-188` — SURVEY §2.3 marks PP absent). The
encoder's parameters are already *stacked* with a leading ``layers`` axis, so
pipelining is just another sharding of that axis: each device on the
``stage`` mesh axis holds layer blocks, and microbatches circulate
stage→stage over ICI via ``jax.lax.ppermute`` (the SPMD "pipelining via
collective permute" pattern — no per-stage programs, one SPMD program).

Schedules (``n_virtual = V``):

- ``V=1`` (GPipe fill-and-drain): device ``d`` holds layers
  ``[d*L/S, (d+1)*L/S)``; ``T = M + S - 1`` ticks; bubble ``(S-1)/T``.
- ``V>1`` (interleaved / circular placement, Megatron-style): device ``d``
  holds the V NON-contiguous blocks ``{v*S + d}``, and each microbatch makes
  V laps around the ring. Fill/drain cost stays one ring traversal while
  compute per microbatch is spread over ``V*S`` ticks, so the bubble shrinks
  to ``(S-1) / (V*M + (V+1)*S/V ...)`` ≈ ``(S-1)/(V*M)`` — V=2 roughly
  halves it. Requires ``M % S == 0``.

Scheduling identity (V>1): microbatch ``m = g*S + r`` is processed by device
``d`` on lap ``v`` at tick ``t = g*V*S + v*S + r + d``. Given ``(t, d)`` the
base-S/base-V decomposition of ``t - d`` recovers a unique ``(g, v, r)``, so
every device computes at most one (microbatch, lap) per tick — the property
that makes the whole schedule one ``lax.scan``.

Each tick is passed to ``stage_apply`` so dropout can fold the tick into its
rng stream (fresh masks per microbatch — see `nn/transformer.py`).
Differentiable end-to-end (`lax.scan` of `ppermute`), composes with remat
inside each stage.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def circular_layer_order(n_layers: int, n_stages: int, n_virtual: int
                         ) -> np.ndarray:
    """Permutation of the stacked ``layers`` axis that realizes circular
    placement under contiguous ``P("stage")`` sharding: device ``d``'s
    contiguous shard contains global blocks ``{v*n_stages + d}``."""
    if n_layers % (n_stages * n_virtual):
        raise ValueError(f"{n_layers} layers not divisible by "
                         f"{n_stages} stages x {n_virtual} virtual chunks")
    chunk = n_layers // (n_stages * n_virtual)
    idx = []
    for d in range(n_stages):
        for v in range(n_virtual):
            block = v * n_stages + d
            idx.extend(range(block * chunk, (block + 1) * chunk))
    return np.asarray(idx)


def num_ticks(n_microbatches: int, n_stages: int, n_virtual: int = 1) -> int:
    """Schedule length in ticks — the single source of truth shared by the
    scan below and the dropout tick-offset bookkeeping in
    `Transformer.__call__` (jimm_tpu/nn/transformer.py)."""
    m, s, v = n_microbatches, n_stages, n_virtual
    if v == 1:
        return m + s - 1
    return (m // s - 1) * v * s + (v + 1) * s - 1


def pipeline_forward(stage_apply: Callable, stage_params, x: jax.Array, *,
                     n_microbatches: int, n_virtual: int = 1,
                     axis_name: str = "stage", mesh: Mesh | None = None,
                     batch_axis: str | None = None,
                     tick_offset: jax.Array | int = 0) -> jax.Array:
    """Run ``x`` through a depth-stacked stack pipelined over ``axis_name``.

    - ``stage_params``: pytree whose every leaf has a leading global
      ``layers`` dim, sharded over ``axis_name``. For ``n_virtual > 1`` the
      layers must already be permuted by :func:`circular_layer_order`.
    - ``stage_apply(chunk_params, xm, tick)``: applies one virtual chunk's
      layers to a microbatch (typically an ``nnx.merge`` + scan over the
      chunk); ``tick`` is the traced schedule tick (plus ``tick_offset``,
      which callers advance per training step) for dropout rng folding.
    - ``x``: ``(B, ...)`` activations; ``B`` must divide by
      ``n_microbatches`` (times the ``batch_axis`` size if given).
    - ``batch_axis``: optional mesh axis the batch dim is sharded over
      (pipeline x data parallelism).
    """
    from jimm_tpu.configs import check_pp_schedule

    M, V = n_microbatches, n_virtual
    check_pp_schedule(M, V)
    x_spec = P(batch_axis) if batch_axis else P()

    def local(params_local, x_local):
        stage = jax.lax.axis_index(axis_name)
        S = jax.lax.axis_size(axis_name)
        b = x_local.shape[0]
        check_pp_schedule(M, V, n_stages=S, local_batch=b)
        micro = x_local.reshape(M, b // M, *x_local.shape[1:])
        # chunked params: leading dim (V * layers_per_chunk) -> (V, chunk)
        params_v = jax.tree.map(
            lambda p: p.reshape(V, p.shape[0] // V, *p.shape[1:]),
            params_local)

        t_total = num_ticks(M, S, V)

        def step(carry, t):
            ring, acc = carry
            td = t - stage
            q = jnp.floor_divide(td, S)
            r = td - q * S  # in [0, S)
            qc = jnp.maximum(q, 0)
            v = jnp.remainder(qc, V)
            g = jnp.floor_divide(qc, V)
            # stage 0 injects microbatch g*S + r at the start of lap 0
            m_cur = g * S + r  # the microbatch this tick works on
            m_inj = jnp.clip(m_cur, 0, M - 1)
            inject = (stage == 0) & (v == 0)
            inp = jnp.where(inject, micro[m_inj], ring)
            chunk = jax.tree.map(lambda p: p[v], params_v)
            out = stage_apply(chunk, inp, t + tick_offset)
            # collect finished microbatches into an M-slot accumulator as
            # they drain (NOT a (t_total, ...) stack — at V>1 that would
            # hold ~V*M outputs live through the backward for M results):
            # microbatch m finishes when the LAST stage completes lap V-1
            done = ((stage == S - 1) & (v == V - 1) & (td >= 0)
                    & (m_cur < M))
            upd = jnp.where(done, out, jax.lax.dynamic_index_in_dim(
                acc, m_inj, keepdims=False))
            acc = jax.lax.dynamic_update_index_in_dim(acc, upd, m_inj, 0)
            perm = [(i, (i + 1) % S) for i in range(S)]
            return (jax.lax.ppermute(out, axis_name, perm), acc), None

        acc0 = jnp.zeros_like(micro)
        (_, acc), _ = jax.lax.scan(step, (jnp.zeros_like(micro[0]), acc0),
                                   jnp.arange(t_total))
        # only the last stage wrote real outputs; broadcast them to all
        result = jax.lax.psum(
            jnp.where(stage == S - 1, acc, jnp.zeros_like(acc)), axis_name)
        return result.reshape(b, *x_local.shape[1:])

    kwargs = {} if mesh is None else {"mesh": mesh}
    fn = jax.shard_map(local,
                       in_specs=(P(axis_name), x_spec),
                       out_specs=x_spec,
                       check_vma=False, **kwargs)
    return fn(stage_params, x)
