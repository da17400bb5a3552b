"""Pallas TPU flash attention: a templated kernel family, not one kernel.

The tiling / online-normalizer / custom-VJP scaffolding is shared; a
:class:`VariantSpec` (score transform + normalizer kind, mask source, bias
source) instantiates the members:

- ``flash_attention``        — softmax, optional causal (the original).
- ``flash_attention_lse``    — softmax returning per-row logsumexp (the
  ring-attention building block).
- ``flash_attention_masked`` — softmax with a per-sample ``(B, Sk)``
  key-padding mask, streamed as additive f32 rows. Unblocks NaFlex and MAP
  pooling on the flash path (`nn/vision.py::forward_naflex`).
- ``flash_attention_bias``   — softmax with an additive bias broadcastable
  to ``(N, Sq, Sk)`` (relative-position style), fwd + bwd including dbias
  via a dedicated batch-innermost accumulation kernel.
- ``sigmoid_attention``      — elementwise ``sigmoid(s + logit_bias)``
  scores, NO row normalizer ("Theory, Analysis, and Best Practices for
  Sigmoid Self-Attention"): the online loop drops the m/l statistics
  entirely, and the backward needs no lse/delta.

Two tilings of one algorithm, chosen by a test of shapes alone
(`_single_tile_hb`; no flag, no `impl` string):

- **single tile** — every image preset (S = 196..729): a head's whole
  lane-padded sequence is one resident tile, so each row sees all its keys
  at once. The kernels read q, k, v (and ``do``, ``o``) and write o (and
  dq, dk, dv) in the model's own ``(B, S, N*D)`` layout: grid
  ``(B, N/hb)``, a cell's block is batch row b, the whole sequence as an
  edge block (S_p rows over an array of S: nothing is padded in HBM, the
  kernels zero the edge rows in VMEM) and the ``hb*D`` lanes of head group
  g, a head a static lane slice of it. So no XLA transpose, pad or slice
  runs around them. The forward is one exact row softmax per head (no m/l
  scratch, no rescale, no init/finalize), and the backward is ONE kernel
  that takes ``delta`` and all three gradients from a single pass over the
  scores (5 matmuls, 1 exponential per score element). The ring's hops,
  which hold ``(B*N, S, D)`` rows, are the same kernels with one head in a
  row. The bias variant stays tiled.
- **tiled** — longer sequences (and explicit block requests): the kernels
  described next.

Tiled kernel structure (all variants): the kv loop is a GRID dimension,
not an in-kernel loop over a resident copy — each (head-block, q-block,
kv-block) grid cell sees one (block_q, d) q tile and one (block_k, d) k/v
tile, so VMEM holds a single working set while Mosaic's grid pipeline
streams the next kv block from HBM in parallel with compute. Softmax
variants keep the flash-attention recurrence in VMEM scratch ((block_q, 128)
lane-broadcast m/l, fp32 accumulator); the sigmoid variant keeps only the
accumulator. HBM traffic is O(S*D) and VMEM is O(block^2). A grid cell
holds ``hb`` heads (`_pick_hb`: as many as `_per_head_vmem_bytes` fits into
`_VMEM_BUDGET`, which the call states to Mosaic as ``vmem_limit_bytes``),
walked by one straight-line loop so that one head's softmax runs beside
another head's matmuls. A causal call's grid is not the ``(q, kv)``
rectangle but the list of its live block pairs (`_live_pairs`, two
scalar-prefetched int32 tables: row-major for the forward and dq,
column-major for dk/dv and the fused backward), so no grid step is taken for
a block above the diagonal.

The tiled backward recomputes attention blockwise (from the saved logsumexp
for softmax kinds; from scratch for sigmoid), in one of two arrangements of
the same sums, picked by a test of shapes alone (`_DQ_RESIDENT_BUDGET`; no
flag, no preset's name):

- **fused** — while the fp32 dq of a cell's heads over their whole padded
  S_q fits in VMEM (up to 65,536 tokens at D = 128, 32,768 at 256): ONE
  kernel on dk/dv's column-major grid. A block pair's s, dp, p and ds are
  computed once; dk and dv accumulate over the column's q blocks in scratch
  as they always have, and ``ds k`` adds into the resident dq at the step's
  q block, whose pairs arrive at ascending kv block, dq's own order. Every
  visit writes the q block's sum so far to its output block; the last one
  holds the whole sum. 8 MXU passes a pair at 256 | 128 lanes where the pair
  of kernels makes 11, 5 matmuls where it makes 7.
- **dq + dk/dv** — above that bound: the flash-attention-2 arrangement, two
  kernels that each recompute s and dp.

For the bias variant a further kernel's grid runs batch innermost to
accumulate dbias across samples.

``flash_attention`` also takes **grouped key/value heads** (k and v with
fewer heads than q, a divisor of its count) and a **causal window**. Grouped
calls run the tiled kernels at any length (a single-tile cell slices one head
count's lanes): a cell's ``hb`` query heads all read ONE k/v head (``hb``
divides the group), whose blocks the index maps fetch at its own index, so k
and v are never repeated in HBM; dk/dv's grid runs over the k/v heads with the
group's cells as a last, innermost axis that accumulates into the one head's
scratch, so dk and dv leave at the k/v heads' own count (fused, the dq of all
the group's cells stays resident). Under a window the
live-pair tables also drop the pairs wholly left of it and the position mask
takes its second edge; a window that reaches over every key is dropped before
dispatch (`live_window`), so that call is the plain causal one.

Numerical contract: softmax variants match
`jimm_tpu.ops.attention.reference_attention` (fp32 softmax einsum) to
~1e-5 in f32; the sigmoid variant matches
`reference_sigmoid_attention`. Tested in interpret mode on CPU
(`tests/test_flash_variants.py`), compiled for a described v5e topology
(`tests/test_tpu_compile.py`) and run compiled on the chip
(`chip_smoke.py`).

Masking uses a large negative constant (not -inf) so padded/fully-masked
rows degrade to garbage-but-finite values — no NaNs reach the gradient.
Contract for the masked softmax variants: a query row whose keys are ALL
masked produces finite garbage output, and contributes exactly zero
gradient as long as its output cotangent is zero — consumers must mask
such rows downstream (NaFlex's MAP pooling does). The sigmoid variant has
no such row: zero valid keys simply yields a zero output row.

Head dims that are not one of the tested MXU tiles (64/128/256) are
zero-padded to the next tile inside the wrappers (the padded lanes
contribute 0 to every dot product and are sliced off the outputs; the
single-tile regime pads the 4-D view once, the tiled its flattened copy), so the
dispatch layer no longer falls back to XLA on e.g. d=80 towers — see the
crossover note in docs/performance.md.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
#: default per-grid-cell tile extents. 512 amortizes grid-step overhead
#: (measured ~2x faster than 128 at seq 256-1k on v5e) while the fp32
#:  (block_q, block_k) logits tile stays ~1MB — far under VMEM; _prologue
#: clamps to the padded sequence so short sequences use one tile. Blocks of
#: 1024 and 2048 (tried from a scratch copy; `_pick_block` stops at 512)
#: lost to 512 with four heads a cell on the v5e: forward + backward a
#: layer at (2, 8192, 32, 192 | 128) causal 53.9 ms against 57.4-66.1, at
#: (1, 4096, 16, 128) 2.74 against 3.14-3.69 (PERF.md, PR 33).
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
_LANES = 128  # scratch m/l are lane-broadcast for Mosaic-friendly layout

#: head-dim tiles the kernels are tuned for; other dims zero-pad up
_HEAD_TILES = (64, 128, 256)


class VariantSpec(NamedTuple):
    """Static template parameters for one family member (hashable — rides
    through ``custom_vjp`` nondiff args and ``partial`` into the kernels).

    - ``kind``: ``"softmax"`` (online max/sum recurrence, lse residual) or
      ``"sigmoid"`` (elementwise transform, accumulate-only loop).
    - ``has_mask``: stream per-sample additive key-padding rows
      ``(BN, 1, Sk)`` (0 keep / NEG_INF drop) into every score tile.
    - ``has_bias``: stream additive ``(N, Sq, Sk)`` f32 bias tiles into
      every score tile; the backward gains a dbias kernel.
    """

    kind: str = "softmax"
    has_mask: bool = False
    has_bias: bool = False


_SOFTMAX = VariantSpec()


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _bcast_lanes(x: jax.Array) -> jax.Array:
    """(n,) -> (n, 128) with every lane equal."""
    return jnp.broadcast_to(x[:, None], (x.shape[0], _LANES))


def _from_lanes(x: jax.Array) -> jax.Array:
    """(n, 128) all-lanes-equal -> (n,). max is exact on equal lanes."""
    return jnp.max(x, axis=1)


def _scores(q, k, sm_scale, mask_row, bias_tile, pos_mask):
    """One head's fp32 score tile: dot, scale, additive mask/bias, then the
    positional (padding/causal) mask, if the tile has one. q/k stay in their
    storage dtype (bf16) so the MXU runs at full bf16 rate with fp32
    accumulation; the softmax scale is applied to the fp32 logits AFTER the
    dot (pre-scaling q in bf16 would round)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale
    if bias_tile is not None:
        s = s + bias_tile
    if mask_row is not None:
        s = s + mask_row
    return s if pos_mask is None else jnp.where(pos_mask, s, NEG_INF)


# ---------------------------------------------------------------------------
# What a tiled grid step is: its block pair and the scores that exist in it
# ---------------------------------------------------------------------------

def _last_kv(qi, block_q: int, block_k: int, n_k: int, causal: bool):
    """The last kv block a q block's row visits: under ``causal`` the one
    that holds the row's last query's own key."""
    if not causal:
        return n_k - 1
    return jnp.minimum(n_k - 1, ((qi + 1) * block_q - 1) // block_k)


def _first_kv(qi, block_q: int, block_k: int, window: int | None):
    """The first kv block a q block's row visits: 0, or under a ``window``
    the one that holds the oldest key the block's first query still sees."""
    if window is None:
        return 0
    return jnp.maximum(qi * block_q - (window - 1), 0) // block_k


def _last_q(kj, block_q: int, block_k: int, n_q: int, window: int | None):
    """The last q block a kv block's column visits (dk/dv): the last one, or
    under a ``window`` the one whose queries still see the block's last
    key."""
    if window is None:
        return n_q - 1
    return jnp.minimum(n_q - 1, ((kj + 1) * block_k + window - 2) // block_q)


def _block_ids(refs, causal: bool, kv_major: bool = False):
    """``(q block, kv block, the operands' refs)`` of a grid step. A causal
    grid is ``(heads, live pairs)`` and reads the step's block pair from the
    two prefetched tables of `_live_pairs`; any other is the rectangle
    ``(heads, q, kv)``, or ``(heads, kv, q)`` for dk/dv."""
    if causal:
        qi_tab, kj_tab, *refs = refs
        t = pl.program_id(1)
        return qi_tab[t], kj_tab[t], refs
    a, b = pl.program_id(1), pl.program_id(2)
    return (b, a, refs) if kv_major else (a, b, refs)


def _pos_mask(qi, kj, block_q: int, block_k: int, causal: bool, *,
              sq_real: int | None = None, sk_real: int | None = None,
              window: int | None = None):
    """``(block_q, block_k)`` predicate of the scores that exist: keys (or,
    for dk/dv, queries) the array has, under ``causal`` keys at or left
    of the query and, under a ``window``, fewer than that many positions
    left of it (the query's own counted). Head-independent: built once per
    grid step."""
    k_pos = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    q_pos = None
    if causal or sq_real is not None:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
    pos = k_pos < sk_real if sk_real is not None else q_pos < sq_real
    if window is not None:
        pos = pos & (q_pos - k_pos < window)
    return pos & (k_pos <= q_pos) if causal else pos


# ---------------------------------------------------------------------------
# Forward kernel (template)
# ---------------------------------------------------------------------------

def _fwd_kernel(*refs, sk_real: int, block_k: int, causal: bool,
                sm_scale: float, logit_bias: float, n_k: int,
                spec: VariantSpec, window: int | None = None):
    qi, kj, refs = _block_ids(refs, causal)
    softmax = spec.kind == "softmax"
    it = iter(refs)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    mask_ref = next(it) if spec.has_mask else None
    bias_ref = next(it) if spec.has_bias else None
    o_ref = next(it)
    lse_ref = next(it) if softmax else None
    m_scr = next(it) if softmax else None
    l_scr = next(it) if softmax else None
    acc_scr = next(it)
    hb, bq, d = q_ref.shape
    # grouped key/value heads: the cell's query heads read ONE k/v head
    hkv = k_ref.shape[0]

    @pl.when(kj == _first_kv(qi, bq, block_k, window))
    def _init():
        if softmax:
            m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
            l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    # position mask is head-independent: build once, reuse per head
    pos = _pos_mask(qi, kj, bq, block_k, causal, sk_real=sk_real,
                    window=window)
    # static loop over the hb heads resident in this grid cell: one straight
    # body, in which a head's softmax runs beside another head's matmuls
    for h in range(hb):
        v = v_ref[h * hkv // hb]
        s = _scores(q_ref[h], k_ref[h * hkv // hb], sm_scale,
                    mask_ref[h] if spec.has_mask else None,
                    bias_ref[h] if spec.has_bias else None, pos)
        if softmax:
            m_prev = _from_lanes(m_scr[h])
            l_prev = _from_lanes(l_scr[h])
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
            p = jnp.exp(s - m_new[:, None])
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=1)
            acc_scr[h] = acc_scr[h] * corr[:, None] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h] = _bcast_lanes(m_new)
            l_scr[h] = _bcast_lanes(l_new)
        else:
            # no normalizer, no running statistics: each kv block's
            # sigmoid scores contribute independently to the sum
            p = jax.nn.sigmoid(s + logit_bias)
            acc_scr[h] += jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(kj == _last_kv(qi, bq, block_k, n_k, causal))
    def _finalize():
        for h in range(hb):
            if softmax:
                m = _from_lanes(m_scr[h])
                l = _from_lanes(l_scr[h])
                l_safe = jnp.where(l == 0.0, 1.0, l)
                o_ref[h] = (acc_scr[h] / l_safe[:, None]).astype(o_ref.dtype)
                lse_ref[h, 0, :] = m + jnp.log(l_safe)
            else:
                o_ref[h] = acc_scr[h].astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# Backward kernels (templates)
# ---------------------------------------------------------------------------

def _ds_tile(spec, s, do, v, lse, delta, logit_bias):
    """Shared backward score-gradient: recompute p from the fp32 score
    tile, then ``ds`` (unscaled — the chain-rule sm_scale lands at the
    dq/dk finalize, and dbias takes ds as-is). ``lse``/``delta`` are the
    rows' statistics, ``(rows,)`` as the tiled kernels read them or already
    columns ``(rows, 1)``. Returns (p, ds)."""
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if spec.kind == "softmax":
        col = (lambda x: x[:, None]) if lse.ndim == 1 else (lambda x: x)
        p = jnp.exp(s - col(lse))
        ds = p * (dp - col(delta))
    else:
        p = jax.nn.sigmoid(s + logit_bias)
        ds = p * (1.0 - p) * dp
    return p, ds


def _bwd_dq_kernel(*refs, sk_real: int, block_k: int, causal: bool,
                   sm_scale: float, logit_bias: float, n_k: int,
                   spec: VariantSpec, window: int | None = None):
    qi, kj, refs = _block_ids(refs, causal)
    softmax = spec.kind == "softmax"
    it = iter(refs)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    mask_ref = next(it) if spec.has_mask else None
    bias_ref = next(it) if spec.has_bias else None
    do_ref = next(it)
    lse_ref = next(it) if softmax else None
    delta_ref = next(it) if softmax else None
    dq_ref = next(it)
    dq_scr = next(it)
    hb, bq, d = q_ref.shape
    hkv = k_ref.shape[0]

    @pl.when(kj == _first_kv(qi, bq, block_k, window))
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    pos = _pos_mask(qi, kj, bq, block_k, causal, sk_real=sk_real,
                    window=window)
    for h in range(hb):
        k = k_ref[h * hkv // hb]
        s = _scores(q_ref[h], k, sm_scale,
                    mask_ref[h] if spec.has_mask else None,
                    bias_ref[h] if spec.has_bias else None, pos)
        _, ds = _ds_tile(spec, s, do_ref[h], v_ref[h * hkv // hb],
                         lse_ref[h, 0, :] if softmax else None,
                         delta_ref[h, 0, :] if softmax else None,
                         logit_bias)
        dq_scr[h] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == _last_kv(qi, bq, block_k, n_k, causal))
    def _finalize():
        dq_ref[...] = (dq_scr[...] * sm_scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, sq_real: int, block_q: int, causal: bool,
                    sm_scale: float, logit_bias: float, n_q: int,
                    spec: VariantSpec, window: int | None = None,
                    cells: int = 1, fused: bool = False):
    """``cells`` > 1 (grouped key/value heads, a group wider than a cell): the
    grid's last axis walks the group's cells innermost, all adding into the
    one k/v head's scratch, so dk and dv leave summed over the group.

    ``fused``: the call is the whole tiled backward. Each block pair's
    ``ds`` also adds ``ds k`` into a fp32 dq accumulator that holds ALL the q
    blocks of the cell's heads (and of the group's other cells) in VMEM over
    the sweep, at the step's q block. On this grid a q block's pairs arrive
    at ascending kv block, `_bwd_dq_kernel`'s order, so the sums are its
    sums. Every visit writes the q block's sum so far, scaled and cast, to
    its dq block: the last visit holds the whole sum and is the last to be
    written back."""
    qi, kj, refs = _block_ids(refs, causal, kv_major=True)
    softmax = spec.kind == "softmax"
    it = iter(refs)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    mask_ref = next(it) if spec.has_mask else None
    bias_ref = next(it) if spec.has_bias else None
    do_ref = next(it)
    lse_ref = next(it) if softmax else None
    delta_ref = next(it) if softmax else None
    dk_ref = next(it)
    dv_ref = next(it)
    dq_ref = next(it) if fused else None
    dk_scr = next(it)
    dv_scr = next(it)
    dq_scr = next(it) if fused else None
    hkv, bk, d = k_ref.shape
    hb = q_ref.shape[0]
    cell = pl.program_id(2 if causal else 3) if cells > 1 else None
    # a causal column starts at the first q block that reaches it
    first = qi == (jnp.minimum(kj * bk // block_q, n_q - 1) if causal else 0)

    @pl.when(first if cell is None else first & (cell == 0))
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    if fused:
        # the q block's place in the resident accumulator
        slot = qi if cell is None else cell * n_q + qi

        @pl.when(kj == _first_kv(qi, block_q, bk, window))
        def _init_dq():
            dq_scr[slot] = jnp.zeros(dq_scr.shape[1:], jnp.float32)

    pos = _pos_mask(qi, kj, block_q, bk, causal, sq_real=sq_real,
                    window=window)
    for h in range(hb):
        q = q_ref[h]
        do = do_ref[h]
        k = k_ref[h * hkv // hb]
        s = _scores(q, k, sm_scale,
                    mask_ref[h] if spec.has_mask else None,
                    bias_ref[h] if spec.has_bias else None, pos)
        p, ds = _ds_tile(spec, s, do, v_ref[h * hkv // hb],
                         lse_ref[h, 0, :] if softmax else None,
                         delta_ref[h, 0, :] if softmax else None,
                         logit_bias)
        # dv's MXU input is a rounded copy; ds keeps the fp32 p
        # (matching the dq kernel) so dk isn't computed from a
        # double-rounded p
        dv_scr[h * hkv // hb] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = ds.astype(q.dtype)
        dk_scr[h * hkv // hb] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if fused:
            dq_scr[slot, h] += jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    if fused:
        dq_ref[...] = (dq_scr[slot] * sm_scale).astype(dq_ref.dtype)

    last = qi == _last_q(kj, block_q, bk, n_q, window)

    @pl.when(last if cell is None else last & (cell == cells - 1))
    def _finalize():
        # ds was accumulated unscaled; the chain-rule sm_scale lands here
        dk_ref[...] = (dk_scr[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_dbias_kernel(*refs, sq_real: int, sk_real: int, block_q: int,
                      block_k: int, causal: bool, sm_scale: float,
                      logit_bias: float, n_b: int, spec: VariantSpec):
    """dbias for the bias variant: grid (N/hb, n_q, n_k, B) with batch
    INNERMOST ("arbitrary"), so one (head-block, q-block, k-block) bias
    tile stays resident while per-sample ds tiles accumulate in scratch;
    the result is written once at the last batch step. dbias is exactly
    ``ds`` (no sm_scale — bias adds to the scaled logits)."""
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    bi = pl.program_id(3)
    softmax = spec.kind == "softmax"
    it = iter(refs)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    mask_ref = next(it) if spec.has_mask else None
    bias_ref = next(it)
    do_ref = next(it)
    lse_ref = next(it) if softmax else None
    delta_ref = next(it) if softmax else None
    db_ref = next(it)
    db_scr = next(it)
    hb, bq, d = q_ref.shape

    @pl.when(bi == 0)
    def _init():
        db_scr[...] = jnp.zeros(db_scr.shape, jnp.float32)

    pos = _pos_mask(qi, kj, bq, block_k, causal, sk_real=sk_real)
    for h in range(hb):
        s = _scores(q_ref[h], k_ref[h], sm_scale,
                    mask_ref[h] if spec.has_mask else None,
                    bias_ref[h], pos)
        _, ds = _ds_tile(spec, s, do_ref[h], v_ref[h],
                         lse_ref[h, 0, :] if softmax else None,
                         delta_ref[h, 0, :] if softmax else None,
                         logit_bias)
        db_scr[h] += ds

    @pl.when(bi == n_b - 1)
    def _finalize():
        db_ref[...] = db_scr[...]


# ---------------------------------------------------------------------------
# Single-tile kernels: the caller's own (B', S, N'*D) layout, one resident
# (S_p, hb*D) slab per operand; a head is a static lane slice of the slab
# ---------------------------------------------------------------------------

_TN = (((0,), (0,)), ((), ()))  # a^T b: contract the row dimension of both


def _single_tile_masks(mask_ref, sq_p: int, sk_p: int, sk_real: int,
                       causal: bool, window: int | None = None):
    """The masks every head of a single-tile cell shares: the additive
    ``(1, sk_p)`` key row (the sample's key-padding row, NEG_INF past the
    array's last key; one add per score, no 2-D iota), and the causal
    triangle as a 2-D predicate only where it is asked for. The row's edge
    lanes hold whatever the VMEM tile held, so they are selected away, not
    added to."""
    key_row = None if mask_ref is None else mask_ref[0]
    if sk_real < sk_p:
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (1, sk_p), 1)
        key_row = jnp.where(k_pos < sk_real,
                            0.0 if key_row is None else key_row, NEG_INF)
    pos = None
    if causal:
        pos = (jax.lax.broadcasted_iota(jnp.int32, (sq_p, sk_p), 1)
               <= jax.lax.broadcasted_iota(jnp.int32, (sq_p, sk_p), 0))
    if window is not None:  # a causal call's second edge
        pos = pos & (jax.lax.broadcasted_iota(jnp.int32, (sq_p, sk_p), 0)
                     - jax.lax.broadcasted_iota(jnp.int32, (sq_p, sk_p), 1)
                     < window)
    return key_row, pos


def _real_rows(s_p: int, s_real: int, lanes: int):
    """``(s_p, lanes)`` predicate of the rows the array has, None if it has
    them all. The block's row extent is the lane-padded S_p over an array of
    S rows: the rows past S are not padded in HBM, their VMEM holds
    unspecified values (NaN in interpret mode) and their writes are
    dropped."""
    if s_real == s_p:
        return None
    return jax.lax.broadcasted_iota(jnp.int32, (s_p, lanes), 0) < s_real


def _lane_group(ref, lanes, rows=None):
    """One lane group of a resident slab with its edge rows zeroed: 0 * NaN
    is NaN, so every operand of a contraction over those rows is cleaned."""
    x = ref[0, :, lanes]
    return x if rows is None else jnp.where(rows, x, jnp.zeros_like(x))


def _group_lanes(width: int, d: int) -> int:
    """Lanes a cell's head loop takes per iteration: 256 (four heads of 64,
    two of 128, one of 256), whole lane tiles so that the rolled loop's
    dynamic lane offset is tile-aligned; a narrower or odd slab (3 heads of
    64: the whole row) is one group. Forward + backward a layer at ViT-L's
    shape on the v5e, and Mosaic's time to compile the pair there: 128 lanes
    2.80 ms / 2.5 s, 256 lanes 2.71 ms / 4.2 s, all 512 unrolled 2.63 ms /
    16.4 s (PERF.md, PR 28)."""
    gw = max(d, 2 * _LANES)
    return gw if width % gw == 0 else width


def _for_lane_groups(width: int, d: int, body) -> None:
    """``body(first head, lanes)`` per lane group of a ``width``-lane slab."""
    gw = _group_lanes(width, d)
    if gw == width:
        body(0, slice(None))
        return

    def step(g, carry):
        body(g * (gw // d), pl.ds(pl.multiple_of(g * gw, gw), gw))
        return carry
    jax.lax.fori_loop(0, width // gw, step, 0)


def _put_heads(ref, lanes, heads) -> None:
    """The group's per-head ``(S_p, d)`` results side by side, one store."""
    ref[0, :, lanes] = (heads[0] if len(heads) == 1
                        else jnp.concatenate(heads, axis=1)).astype(ref.dtype)


def _fwd_single_kernel(*refs, d: int, sq: int, sk: int, causal: bool,
                       sm_scale: float, logit_bias: float, spec: VariantSpec,
                       window: int | None = None):
    """Grid ``(B', N'/hb)``: every row sees all its keys at once, so the
    softmax is one exact pass per head — no running max/sum, no accumulator
    rescale, no init/finalize steps."""
    softmax = spec.kind == "softmax"
    it = iter(refs)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    mask_ref = next(it) if spec.has_mask else None
    o_ref = next(it)
    lse_ref = next(it) if softmax else None
    (_, sq_p, width), sk_p = q_ref.shape, k_ref.shape[1]
    gw = _group_lanes(width, d)
    key_row, pos = _single_tile_masks(mask_ref, sq_p, sk_p, sk, causal,
                                      window)
    # q's edge rows only so that the lse written for them is finite
    q_rows = _real_rows(sq_p, sq, gw) if softmax else None
    k_rows = _real_rows(sk_p, sk, gw)

    def group(h0, lanes):
        qg = _lane_group(q_ref, lanes, q_rows)
        kg, vg = (_lane_group(r, lanes, k_rows) for r in (k_ref, v_ref))
        outs = []
        for j in range(gw // d):
            head = slice(j * d, (j + 1) * d)
            v = vg[:, head]
            s = _scores(qg[:, head], kg[:, head], sm_scale, key_row, None,
                        pos)
            if softmax:
                m = jnp.max(s, axis=1, keepdims=True)
                p = jnp.exp(s - m)
                # the row's max contributes exp(0): l >= 1, never 0
                l = jnp.sum(p, axis=1, keepdims=True)
            else:
                p = jax.nn.sigmoid(s + logit_bias)
            o = jax.lax.dot_general(p.astype(v.dtype), v,
                                    (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if softmax:
                o = o / l
                lse_ref[0, h0 + j, 0, :] = (m + jnp.log(l))[:, 0]
            outs.append(o)
        _put_heads(o_ref, lanes, outs)

    _for_lane_groups(width, d, group)


def _bwd_single_kernel(*refs, d: int, sq: int, sk: int, causal: bool,
                       sm_scale: float, logit_bias: float, spec: VariantSpec,
                       has_dlse: bool, window: int | None = None):
    """Grid ``(B', N'/hb)``, all three gradients from one pass over the
    scores: 5 matmuls and 1 exponential per score element, every operand
    read once (the tiled dq + dk/dv pair recomputes s, p and dp in each
    kernel). ``delta = rowsum(do * o)`` is taken here, less the lse
    cotangent."""
    softmax = spec.kind == "softmax"
    it = iter(refs)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    mask_ref = next(it) if spec.has_mask else None
    do_ref = next(it)
    o_ref = next(it) if softmax else None
    lse_ref = next(it) if softmax else None
    dlse_ref = next(it) if has_dlse else None
    dq_ref, dk_ref, dv_ref = next(it), next(it), next(it)
    (_, sq_p, width), sk_p = q_ref.shape, k_ref.shape[1]
    gw = _group_lanes(width, d)
    key_row, pos = _single_tile_masks(mask_ref, sq_p, sk_p, sk, causal,
                                      window)
    q_rows, k_rows = _real_rows(sq_p, sq, gw), _real_rows(sk_p, sk, gw)

    def group(h0, lanes):
        qg, dog = (_lane_group(r, lanes, q_rows) for r in (q_ref, do_ref))
        kg, vg = (_lane_group(r, lanes, k_rows) for r in (k_ref, v_ref))
        if softmax:
            dog32 = dog.astype(jnp.float32)
            do_o = dog32 * _lane_group(o_ref, lanes, q_rows).astype(
                jnp.float32)
        dqs, dks, dvs = [], [], []
        for j in range(gw // d):
            head = slice(j * d, (j + 1) * d)
            q, k, do = qg[:, head], kg[:, head], dog[:, head]
            s = _scores(q, k, sm_scale, key_row, None, pos)
            lse = delta = None
            if softmax:
                lse = lse_ref[0, h0 + j, 0, :][:, None]
                delta = jnp.sum(do_o[:, head], axis=1, keepdims=True)
                if has_dlse:
                    # the lse output adds dlse_i * p_ij to ds_ij, and
                    # ds = p * (dp - delta): delta -= dlse covers it
                    delta = delta - dlse_ref[0, h0 + j, 0, :][:, None]
            p, ds = _ds_tile(spec, s, do, vg[:, head], lse, delta,
                             logit_bias)
            # dv = p^T do and dk = ds^T q, taken as (do^T p)^T and
            # (q^T ds)^T: the operand Mosaic has to transpose is then the
            # (S, D) one and the (D, S) result is turned back, not the
            # (S, S) tile (1.17 -> 1.03 ms a call at ViT-L's shapes on the
            # v5e). p is rounded only as dv's MXU operand; ds comes from
            # the fp32 p.
            dvs.append(jax.lax.dot_general(
                do, p.astype(do.dtype), _TN,
                preferred_element_type=jnp.float32).T)
            ds = ds.astype(q.dtype)
            dks.append(jax.lax.dot_general(
                q, ds, _TN, preferred_element_type=jnp.float32).T * sm_scale)
            dqs.append(jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale)
        for ref, heads in ((dq_ref, dqs), (dk_ref, dks), (dv_ref, dvs)):
            _put_heads(ref, lanes, heads)

    _for_lane_groups(width, d, group)


# ---------------------------------------------------------------------------
# Host-side wrappers
# ---------------------------------------------------------------------------

def _flatten_heads(x: jax.Array) -> jax.Array:
    """``(B, S, N, D)`` -> ``(B*N, S, D)``, a transposed copy: the tiled
    regime's (and the int8 kernels', and the ring's) layout."""
    b, s, n, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * n, s, d)


def _unflatten_heads(x: jax.Array, b: int, n: int) -> jax.Array:
    bn, s, d = x.shape
    return x.reshape(b, n, s, d).transpose(0, 2, 1, 3)


def _pad_seq(x: jax.Array, target: int) -> jax.Array:
    pad = target - x.shape[1]
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0)))


def _pad_mask(maskadd: jax.Array, sk_p: int) -> jax.Array:
    """Tiled regime: additive ``(BN, 1, Sk)`` mask rows out to the padded key
    length (the kernels mask the padded keys themselves)."""
    return jnp.pad(maskadd, ((0, 0), (0, 0), (0, sk_p - maskadd.shape[2])))


def _pad_last(x: jax.Array, target: int) -> jax.Array:
    pad = target - x.shape[-1]
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),))


def _head_pad_target(d: int) -> int:
    """Next supported head tile >= d (64/128/256), or the 128-padded width
    past 256. Zero-padded lanes contribute 0 to q·k and produce output
    columns the wrappers slice off, so ANY head dim runs on the flash path
    (the dispatch allowlist used to punt d=80-style towers to XLA)."""
    for t in _HEAD_TILES:
        if d <= t:
            return t
    return _ceil_to(d, _LANES)


def _interpret() -> bool:
    # looked up per call (NOT cached): scripts may configure the platform
    # after an earlier flash-attention touch, and a cached answer would
    # silently run the kernel interpreted on TPU (or compiled on CPU)
    return jax.default_backend() != "tpu"


def _live_pairs(n_q: int, n_k: int, block_q: int, block_k: int,
                kv_major: bool = False, window: int | None = None):
    """The causal grid: int32 tables ``(q block, kv block)`` of the block
    pairs that hold a score, i.e. whose first key is at or left of their last
    query, row-major for the forward and dq (a q row's pairs follow each
    other, kv block 0 first, the diagonal's last) or, ``kv_major``, column-
    major for dk/dv. There a kv column right of every query (S_k > S_q) keeps
    the pair of the last q block, which the mask empties, so that its dk/dv
    blocks are still written. Returns the two tables and the number of
    pairs that hold a score (all of them but those). Under a ``window`` the
    pairs wholly left of it go too (their last key is ``window`` or more
    positions left of their first query): 108 of the 136 pairs at 8192 tokens
    under a window of 4096. The tables are scalar-
    prefetched into SMEM, 8 bytes a pair: 136 pairs at 8192 tokens in blocks
    of 512, 32,896 (263 KB) at 131,072, the longest the described v5e
    compiles in `tests/test_tpu_compile.py`."""
    import numpy as np
    pairs = [(i, j) for i in range(n_q) for j in range(n_k)
             if j * block_k <= (i + 1) * block_q - 1
             and (window is None
                  or i * block_q - ((j + 1) * block_k - 1) < window)]
    scored = len(pairs)
    if kv_major:
        reached = {j for _, j in pairs}
        pairs += [(n_q - 1, j) for j in range(n_k) if j not in reached]
        pairs.sort(key=lambda p: (p[1], p[0]))
    qi, kj = zip(*pairs)
    return np.asarray(qi, np.int32), np.asarray(kj, np.int32), scored


class _TiledGrid(NamedTuple):
    """One tiled call's grid: its extents, the scalar-prefetch tables (none
    for the rectangle), ``index(f)`` that turns ``f(cell of query heads, cell
    of key/value heads, q block, kv block)`` into the grid's index map, and
    the steps that compute."""

    grid: tuple
    tables: tuple
    index: object
    live_steps: int


def _tiled_grid(n_h: int, n_q: int, n_k: int, block_q: int, block_k: int,
                causal: bool, kv_major: bool = False,
                window: int | None = None, cells: int = 1) -> _TiledGrid:
    """Forward and dq walk a q block's kv blocks innermost; dk/dv
    (``kv_major``) a kv block's q blocks. Causal: the live pairs only.
    ``n_h`` cells of query heads, ``cells`` of them to one cell of key/value
    heads (grouped heads; 1: a cell holds as many of each). dk/dv then runs
    over the key/value cells with the group's ``cells`` as a last, innermost
    axis, so that one k/v head's gradient is finished in one place."""
    inner = kv_major and cells > 1
    if inner:
        def heads(h, c):
            return h * cells + c, h
    elif cells > 1:
        def heads(h):
            return h, h // cells
    else:
        def heads(h):
            return h, h
    lead, tail = (n_h // cells, (cells,)) if inner else (n_h, ())
    if causal:
        qi, kj, scored = _live_pairs(n_q, n_k, block_q, block_k, kv_major,
                                     window)
        if inner:
            def index(f):
                return lambda h, t, c, qi, kj: f(*heads(h, c), qi[t], kj[t])
        else:
            def index(f):
                return lambda h, t, qi, kj: f(*heads(h), qi[t], kj[t])
        return _TiledGrid((lead, len(qi), *tail),
                          (jnp.asarray(qi), jnp.asarray(kj)), index,
                          n_h * scored)
    if inner:
        return _TiledGrid((lead, n_k, n_q, *tail), (),
                          lambda f: lambda h, j, i, c: f(*heads(h, c), i, j),
                          n_h * n_q * n_k)
    if kv_major:
        return _TiledGrid((n_h, n_k, n_q), (),
                          lambda f: lambda h, j, i: f(*heads(h), i, j),
                          n_h * n_q * n_k)
    return _TiledGrid((n_h, n_q, n_k), (),
                      lambda f: lambda h, i, j: f(*heads(h), i, j),
                      n_h * n_q * n_k)


def _tiled_specs(g: _TiledGrid, hb: int, block_q: int, block_k: int, d: int,
                 d_v: int, n_hb: int, hkv: int | None = None) -> dict:
    """The BlockSpecs of a tiled call's operands by kind: ``q`` (and dq),
    ``k`` (dk), ``v`` (dv), ``o`` (do), the rows' ``stat`` (lse, delta), the
    ``mask`` rows, the ``bias`` tiles. Bias tiles are per HEAD (no batch
    dim): flattened head-block h of the (B*N)-row grid maps to bias
    head-block ``h % (N/hb)``. k and v blocks hold ``hkv`` heads (grouped
    key/value heads: one, fetched at its own index; else ``hb``)."""
    hkv = hb if hkv is None else hkv

    def at(shape, f):
        return pl.BlockSpec(shape, g.index(f))
    return {
        "q": at((hb, block_q, d), lambda h, g, i, j: (h, i, 0)),
        "k": at((hkv, block_k, d), lambda h, g, i, j: (g, j, 0)),
        "v": at((hkv, block_k, d_v), lambda h, g, i, j: (g, j, 0)),
        "o": at((hb, block_q, d_v), lambda h, g, i, j: (h, i, 0)),
        "stat": at((hb, 1, block_q), lambda h, g, i, j: (h, 0, i)),
        "mask": at((hb, 1, block_k), lambda h, g, i, j: (h, 0, j)),
        "bias": at((hb, block_q, block_k),
                   lambda h, g, i, j: (h % n_hb, i, j)),
    }


def _tiled_call(kernel, g: _TiledGrid, in_specs, out_specs, out_shape,
                scratch, vmem_limit: int, inputs, *, carried: int = 1,
                window: int | None = None, grouped: bool = False,
                fused: bool = False):
    """The one pallas_call of the tiled regime's forward, dq and dk/dv (or
    the ``fused`` backward). ``carried``: the grid's last axes that
    accumulate into scratch: the pairs (or a block's row or column of the
    rectangle), before them the rectangle's other axis where dq stays
    resident over it, after them a group's cells (dk/dv)."""
    _count_call("tiled", steps=math.prod(g.grid), live_steps=g.live_steps,
                window=window is not None, grouped=grouped, fused=fused)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (len(g.grid) - carried)
        + ("arbitrary",) * carried, vmem_limit_bytes=vmem_limit)
    if not g.tables:
        return pl.pallas_call(
            kernel, grid=g.grid, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, scratch_shapes=scratch,
            compiler_params=params, interpret=_interpret())(*inputs)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(g.tables), grid=g.grid,
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape, compiler_params=params,
        interpret=_interpret())(*g.tables, *inputs)


#: what `_per_head_vmem_bytes` x heads may add up to for one tiled grid
#: cell. A call asks Mosaic for twice its model as scoped VMEM
#: (`_tiled_vmem_limit`: the pipeline's second buffers, Mosaic's matmul and
#: relayout temporaries), so at most 64 MiB of the v5e's 128, like the
#: single-tile calls; the 8 MiB this was before was sized for the 16 MiB
#: default scope and held a cell of 512 x 512 blocks at one or two heads.
_VMEM_BUDGET = 32 * 1024 * 1024

#: what the fused tiled backward's dq accumulators may add up to for one grid
#: cell, on top of `_VMEM_BUDGET`'s tiles: they are scratch, held once (no
#: second buffer), so a fused call states at most 64 + 32 of the v5e's 128
#: MiB. THE rule of the backward's arrangement, a test of shapes alone: a
#: call whose padded S_q x D x 4 bytes a head fit under it at some head count
#: is ONE kernel, any other (65,536 tokens up at D = 128, 32,768 at 256)
#: keeps the dq + dk/dv pair.
_DQ_RESIDENT_BUDGET = 32 * 1024 * 1024


def _per_head_vmem_bytes(block_q: int, block_k: int, d: int, *,
                         kind: str = "softmax", has_mask: bool = False,
                         has_bias: bool = False, dq_rows: int = 0) -> int:
    """Estimated resident VMEM per head in one grid cell — the model behind
    `_pick_hb`, exposed for `scripts/vmem_probe.py` to validate against
    Mosaic's compile-time accounting (one shared formula, no drift). Sized
    on the backward, which holds the most: the fp32 s, p, dp and ds tiles
    and the two MXU-operand copies of p and ds (20 bytes a score: 20 MB at
    1024 x 1024), and in the fused backward the head's ``dq_rows`` resident
    rows of fp32 dq (its whole padded S_q, times the cells of its group). The
    per-variant terms are mirrored jax-free in `tune/space.py` (sync-tested in
    tests/test_tune.py)."""
    n = (3 * block_k * d * 2            # k/v in + one of q/do
         + 2 * block_q * d * 2          # q tile + bf16 out tile
         + 2 * block_q * d * 4          # fp32 accumulators
         + block_q * block_k * 20       # s, p, dp, ds fp32 + 2 bf16 copies
         + dq_rows * d * 4)             # fused backward: resident fp32 dq
    if kind == "softmax":
        n += 2 * block_q * _LANES * 4   # m/l stats scratch (sigmoid: none)
    if has_mask:
        n += block_k * 4                # additive key-padding row
    if has_bias:
        n += 2 * block_q * block_k * 4  # bias in-tile + dbias scratch/out
    return n


def _spec_vmem_bytes(block_q: int, block_k: int, d: int,
                     spec: VariantSpec, dq_rows: int = 0) -> int:
    return _per_head_vmem_bytes(block_q, block_k, d, kind=spec.kind,
                                has_mask=spec.has_mask,
                                has_bias=spec.has_bias, dq_rows=dq_rows)


def _pick_hb(bn: int, block_q: int, block_k: int, d: int,
             spec: VariantSpec = _SOFTMAX, n_heads: int | None = None,
             group: int = 1, dq_seq: int = 0) -> int:
    """Heads per grid cell: the per-head (S, 64) matmuls are too small to
    hide the ~us grid-step sequencing cost, so each cell processes `hb`
    heads back to back (measured ~2x on ViT-shape attention on v5e), and a
    head's vector work (the softmax over a 1 MB score tile) overlaps
    another head's matmuls only inside one straight-line body: at
    (2, 8192, 32, 192 | 128) causal forward + backward a layer read 59.4 /
    57.0 / 54.1 / 57.6 ms at 1 / 2 / 4 / 8 heads, at (1, 4096, 16, 128)
    2.88 / 2.92 / 2.75 / 2.66 (PERF.md, PR 33; eight heads of 512 x 512,
    over the budget, take Mosaic 17-22 s to compile against 2-4; the eight
    of 256 x 256 the budget admits 2 s, and read 4-8 % under four at 1280
    tokens). The bias variant additionally needs hb | N so
    a head block never straddles two samples' rows (its bias index map
    divides by N/hb). Under grouped key/value heads (``group`` query heads to
    one) a cell's heads all read the same k/v head, so ``hb`` divides the
    group: 6, 3, 2 or 1 of 48 heads over 8.

    ``dq_seq`` (the fused backward: the padded S_q): the cell also keeps the
    fp32 dq of its heads' whole sequence, and of its group's other cells',
    under `_DQ_RESIDENT_BUDGET`; 0 heads if no count fits it, and the
    backward is then the dq + dk/dv pair at the forward's heads."""
    per_head = _spec_vmem_bytes(block_q, block_k, d, spec)
    for hb in ((8, 4, 2, 1) if group == 1 else range(min(group, 8), 0, -1)):
        if bn % hb or (group > 1 and group % hb):
            continue
        if spec.has_bias and (n_heads or bn) % hb:
            continue
        if hb > 1 and hb * per_head > _VMEM_BUDGET:
            continue
        if max(hb, group) * dq_seq * d * 4 <= _DQ_RESIDENT_BUDGET:
            return hb
    return 0


def _tiled_vmem_limit(hb: int, block_q: int, block_k: int, d: int,
                      spec: VariantSpec, dq_rows: int = 0) -> int:
    """The scoped VMEM a tiled call states (forward, dq, dk/dv and dbias
    alike): twice its model of the tiles and never under the budget, as
    `_single_tile_call` does, and once the fused backward's ``dq_rows``
    resident rows a head. `_pick_hb` holds the tiles and the rows under their
    budgets, so at most 64 MiB, 96 fused."""
    return max(hb * (_spec_vmem_bytes(block_q, block_k, d, spec)
                     + _spec_vmem_bytes(block_q, block_k, d, spec, dq_rows)),
               _VMEM_BUDGET)


#: what the single-tile model below may add up to for one grid cell. A call
#: asks Mosaic for twice its model as scoped VMEM, so at most 64 MiB of the
#: v5e's 128 (the 16 MiB default scope is what `_VMEM_BUDGET` was sized for).
#: It admits S_p <= 1152 for bf16 at D_p 64 and 128; on the v5e the single
#: tile beat the tiled kernels at every admitted length tried (640, 768, 1024,
#: 1152; PERF.md, PR 25).
_SINGLE_TILE_BUDGET = 32 * 1024 * 1024


def _single_tile_vmem_bytes(sq_p: int, sk_p: int, d: int, itemsize: int,
                            spec: VariantSpec) -> tuple[int, int]:
    """``(per resident head, live temporaries)`` bytes of a single-tile
    cell, sized on the fused backward (the larger of the two kernels): its
    q/do/o/dq and k/v/dk/dv tiles with the pipeline's double buffering and
    the 8-sublane stat/mask rows per head, and one head's fp32 s/p/dp/ds
    with the two MXU-operand copies, which the head loop reuses."""
    per_head = 2 * 4 * (sq_p + sk_p) * d * itemsize
    if spec.kind == "softmax":
        per_head += 2 * 2 * 8 * sq_p * 4
    if spec.has_mask:
        per_head += 2 * 8 * sk_p * 4
    return per_head, sq_p * sk_p * (4 * 4 + 2 * itemsize)


def _single_tile_hb(n: int, sq_p: int, sk_p: int, d: int, itemsize: int,
                    spec: VariantSpec) -> int:
    """THE regime rule, a test of shapes alone: heads per cell if the whole
    padded sequence of a group of the row's ``n`` heads fits the budget as
    one resident tile, else 0 (the tiled kernels run). A group is a slab of
    ``hb * d`` lanes of the ``(B', S, n * d)`` array, so it is whole 128-lane
    tiles or the whole row (D = 64: an even hb). The bias variant stays
    tiled: its dbias kernel accumulates over the batch, which is another
    grid."""
    if spec.has_bias:
        return 0
    per_head, live = _single_tile_vmem_bytes(sq_p, sk_p, d, itemsize, spec)
    for hb in (8, 4, 2, 1, n):
        if (n % hb == 0 and (hb == n or hb * d % _LANES == 0)
                and hb * per_head + live <= _SINGLE_TILE_BUDGET):
            return hb
    return 0


def _single_tile_plan(n: int, sq: int, sk: int, d: int, itemsize: int,
                      spec: VariantSpec, block_q: int, block_k: int):
    """``(heads per cell or 0, S_q padded, S_k padded)`` at these blocks:
    the single-tile regime is one block each way that the rule admits."""
    sq_p, sk_p = _ceil_to(sq, block_q), _ceil_to(sk, block_k)
    if sq_p != block_q or sk_p != block_k:
        return 0, sq_p, sk_p
    return _single_tile_hb(n, sq_p, sk_p, d, itemsize, spec), sq_p, sk_p


def _count_call(regime: str, steps: int = 0, live_steps: int = 0, *,
                window: bool = False, grouped: bool = False,
                fused: bool = False) -> None:
    """One count per pallas_call built (trace time, like the tuner's
    ``jimm_tune_*``): ``jimm_flash_single_tile_total`` /
    ``jimm_flash_tiled_total``, and ``jimm_flash_direct_total`` for a call
    that reads and writes its caller's layout with no XLA transpose, pad or
    slice of a q-sized array around it. A tiled call adds the grid steps one
    execution of it takes and those of them that compute
    (``jimm_flash_tiled_grid_steps_total`` /
    ``jimm_flash_tiled_live_steps_total``: equal but for the empty pairs
    `_live_pairs` keeps where keys lie right of every query).
    ``jimm_flash_window_total`` counts the calls built with a causal window,
    ``jimm_flash_grouped_kv_total`` those with fewer key/value heads than
    query heads, ``jimm_flash_fused_bwd_total`` the tiled backwards built as
    one kernel (dq, dk and dv from one pass over the block pairs)."""
    from jimm_tpu.obs.registry import get_registry
    registry = get_registry("jimm_flash")
    registry.counter(f"{regime}_total").inc()
    if steps:
        registry.counter(f"{regime}_grid_steps_total").inc(steps)
        registry.counter(f"{regime}_live_steps_total").inc(live_steps)
    if window:
        registry.counter("window_total").inc()
    if grouped:
        registry.counter("grouped_kv_total").inc()
    if fused:
        registry.counter("fused_bwd_total").inc()


def _single_tile_call(kernel, inputs, outputs, n: int, hb: int, sq_p: int,
                      sk_p: int, spec: VariantSpec, **static):
    """The one pallas_call of the single-tile regime. ``inputs`` /
    ``outputs`` are ``(array or its ShapeDtypeStruct, kind)`` with kind
    ``"q"`` / ``"k"`` (a ``(B', S, n * d)`` array: batch row b, the whole
    lane-padded sequence as an edge block over the unpadded array, the lanes
    of head group g), ``"stat"`` (``(B', n, 1, sq_p)`` f32 rows) or
    ``"mask"`` (the sample's ``(B', 1, S_k)`` additive row)."""
    q = inputs[0][0]
    d = q.shape[2] // n
    specs = {
        "q": pl.BlockSpec((1, sq_p, hb * d), lambda b, g: (b, 0, g)),
        "k": pl.BlockSpec((1, sk_p, hb * d), lambda b, g: (b, 0, g)),
        "stat": pl.BlockSpec((1, hb, 1, sq_p), lambda b, g: (b, g, 0, 0)),
        "mask": pl.BlockSpec((1, 1, sk_p), lambda b, g: (b, 0, 0)),
    }
    per_head, live = _single_tile_vmem_bytes(sq_p, sk_p, d, q.dtype.itemsize,
                                             spec)
    _count_call("single_tile", window=static.get("window") is not None)
    _count_call("direct")
    return pl.pallas_call(
        partial(kernel, d=d, spec=spec, **static),
        grid=(q.shape[0], n // hb),
        in_specs=[specs[kind] for _, kind in inputs],
        out_specs=[specs[kind] for _, kind in outputs],
        out_shape=[shape for shape, _ in outputs],
        # twice the model: Mosaic's own matmul and relayout temporaries
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=max(2 * (hb * per_head + live),
                                 _SINGLE_TILE_BUDGET)),
        interpret=_interpret(),
    )(*(x for x, _ in inputs))


def _fwd_single(q, k, v, maskadd, causal, spec, sm_scale, logit_bias,
                n: int, hb: int, sq_p: int, sk_p: int,
                window: int | None = None):
    """The forward as one resident tile per head group: ``o`` in q's layout
    and, for the softmax kinds, the lane-padded ``(B', n, 1, sq_p)`` lse."""
    softmax = spec.kind == "softmax"
    inputs = [(q, "q"), (k, "k"), (v, "k")]
    if spec.has_mask:
        inputs.append((maskadd, "mask"))
    outputs = [(jax.ShapeDtypeStruct(q.shape, q.dtype), "q")]
    if softmax:
        outputs.append((jax.ShapeDtypeStruct((q.shape[0], n, 1, sq_p),
                                             jnp.float32), "stat"))
    outs = _single_tile_call(_fwd_single_kernel, inputs, outputs, n, hb, sq_p,
                             sk_p, spec, sq=q.shape[1], sk=k.shape[1],
                             causal=causal, sm_scale=sm_scale,
                             logit_bias=logit_bias, window=window)
    return outs[0], (outs[1] if softmax else None)


def _bwd_single(q, k, v, maskadd, do, o, lse, dlse, causal, spec, sm_scale,
                logit_bias, n: int, hb: int, sq_p: int, sk_p: int,
                window: int | None = None):
    """dq, dk, dv in q / k / v's layout from the one fused backward kernel.
    ``lse`` is the forward's padded residual, or the ring's merged
    ``(B', S_q)`` rows, which are padded here (f32 rows, not a q-sized
    array)."""
    softmax = spec.kind == "softmax"
    inputs = [(q, "q"), (k, "k"), (v, "k")]
    if spec.has_mask:
        inputs.append((maskadd, "mask"))
    inputs.append((do, "q"))
    if softmax:
        if lse.ndim == 2:
            lse = jnp.pad(lse.astype(jnp.float32),
                          ((0, 0), (0, sq_p - lse.shape[1])))[:, None, None]
        inputs += [(o, "q"), (lse, "stat")]
        if dlse is not None:
            inputs.append((dlse.astype(jnp.float32), "stat"))
    return _single_tile_call(
        _bwd_single_kernel, inputs,
        [(jax.ShapeDtypeStruct(x.shape, q.dtype), kind)
         for x, kind in ((q, "q"), (k, "k"), (v, "k"))],
        n, hb, sq_p, sk_p, spec, sq=q.shape[1], sk=k.shape[1], causal=causal,
        sm_scale=sm_scale, logit_bias=logit_bias,
        has_dlse=softmax and dlse is not None, window=window)


def _fwd_pallas(q3, k3, v3, maskadd, bias, causal, spec, sm_scale,
                logit_bias, block_q, block_k, n, group=1, window=None):
    """Assemble and run the forward pallas_call for any variant. Returns
    ``(o, lse or None)`` as the regime keeps them: single-tile in the layout
    it was given (``n`` heads in a row) with the padded 4-D lse, tiled
    (``n == 1``: `_prologue` flattened the heads) cut back to S_q rows."""
    softmax = spec.kind == "softmax"
    bn, sq, d = q3.shape
    sk, dv = k3.shape[1], v3.shape[2]
    hb, sq_p, sk_p = _single_tile_plan(n, sq, sk, d // n, q3.dtype.itemsize,
                                       spec, block_q, block_k)
    # the single-tile kernels know one head width and one head count
    if hb and dv == d and group == 1:
        return _fwd_single(q3, k3, v3, maskadd, causal, spec, sm_scale,
                           logit_bias, n, hb, sq_p, sk_p, window)
    qp, kp, vp = (_pad_seq(q3, sq_p), _pad_seq(k3, sk_p), _pad_seq(v3, sk_p))
    n_q, n_k = sq_p // block_q, sk_p // block_k
    n_heads = bias.shape[0] if spec.has_bias else bn
    hb = _pick_hb(bn, block_q, block_k, d, spec, n_heads, group)
    g = _tiled_grid(bn // hb, n_q, n_k, block_q, block_k, causal,
                    window=window, cells=group // hb if group > 1 else 1)
    sp = _tiled_specs(g, hb, block_q, block_k, d, dv, n_heads // hb,
                      1 if group > 1 else hb)
    kernel = partial(_fwd_kernel, sk_real=sk, block_k=block_k, causal=causal,
                     sm_scale=sm_scale, logit_bias=logit_bias, n_k=n_k,
                     spec=spec, window=window)
    inputs = [qp, kp, vp]
    in_specs = [sp["q"], sp["k"], sp["v"]]
    if spec.has_mask:
        inputs.append(_pad_mask(maskadd, sk_p))
        in_specs.append(sp["mask"])
    if spec.has_bias:
        inputs.append(jnp.pad(bias, ((0, 0), (0, sq_p - sq),
                                     (0, sk_p - sk))))
        in_specs.append(sp["bias"])
    out_specs = [sp["o"]]
    out_shape = [jax.ShapeDtypeStruct((bn, sq_p, dv), q3.dtype)]
    scratch = [pltpu.VMEM((hb, block_q, dv), jnp.float32)]
    if softmax:
        out_specs.append(sp["stat"])
        out_shape.append(jax.ShapeDtypeStruct((bn, 1, sq_p), jnp.float32))
        scratch = [pltpu.VMEM((hb, block_q, _LANES), jnp.float32),
                   pltpu.VMEM((hb, block_q, _LANES), jnp.float32)] + scratch
    outs = _tiled_call(kernel, g, in_specs, out_specs, out_shape, scratch,
                       _tiled_vmem_limit(hb, block_q, block_k, d, spec),
                       inputs, window=window, grouped=group > 1)
    return outs[0][:, :sq], (outs[1][:, 0, :sq] if softmax else None)


def _flash_fwd_impl(q3, k3, v3, maskadd, bias, causal, spec, sm_scale,
                    logit_bias, block_q, block_k, n, group=1, window=None):
    o, lse = _fwd_pallas(q3, k3, v3, maskadd, bias, causal, spec, sm_scale,
                         logit_bias, block_q, block_k, n, group, window)
    # the names make o/lse saveable by remat policies (`"dots"` in
    # `Transformer._remat_policy` saves them): jax.checkpoint traces through
    # custom_vjp fwd rules, and without a saveable mark the whole forward
    # kernel would re-run inside the backward pass of a remat'd layer
    o = checkpoint_name(o, "flash_o")
    if lse is not None:
        lse = checkpoint_name(lse, "flash_lse")
    return o, (q3, k3, v3, maskadd, bias, o, lse)


@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11, 12, 13))
def _flash(q3, k3, v3, maskadd, bias, causal, spec, sm_scale, logit_bias,
           block_q, block_k, n, group=1, window=None):
    """``group`` query heads read one key/value head (k3 and v3 then hold
    ``1 / group`` of q3's rows); ``window``: a causal call's second edge."""
    o, _ = _flash_fwd_impl(q3, k3, v3, maskadd, bias, causal, spec,
                           sm_scale, logit_bias, block_q, block_k, n, group,
                           window)
    return o


def _flash_fwd(q3, k3, v3, maskadd, bias, causal, spec, sm_scale,
               logit_bias, block_q, block_k, n, group=1, window=None):
    return _flash_fwd_impl(q3, k3, v3, maskadd, bias, causal, spec,
                           sm_scale, logit_bias, block_q, block_k, n, group,
                           window)


def _flash_bwd(causal, spec, sm_scale, logit_bias, block_q, block_k, n, res,
               do, dlse=None, group=1, window=None):
    softmax = spec.kind == "softmax"
    q3, k3, v3, maskadd, bias, o, lse = res
    bn, sq, d = q3.shape
    sk, d_v = k3.shape[1], v3.shape[2]
    hb, sq_p, sk_p = _single_tile_plan(n, sq, sk, d // n, q3.dtype.itemsize,
                                       spec, block_q, block_k)
    if hb and d_v == d and group == 1:
        dq, dk, dv = _bwd_single(q3, k3, v3, maskadd, do, o, lse, dlse,
                                 causal, spec, sm_scale, logit_bias, n, hb,
                                 sq_p, sk_p, window)
        return (dq, dk, dv,
                jnp.zeros_like(maskadd) if spec.has_mask else None, None)
    n_q, n_k = sq_p // block_q, sk_p // block_k
    qp, dop = _pad_seq(q3, sq_p), _pad_seq(do, sq_p)
    kp, vp = _pad_seq(k3, sk_p), _pad_seq(v3, sk_p)
    n_heads = bias.shape[0] if spec.has_bias else bn
    # one kernel where the heads' fp32 dq stays in VMEM over the sweep (the
    # rule is `_DQ_RESIDENT_BUDGET`'s), else the dq + dk/dv pair
    hb = _pick_hb(bn, block_q, block_k, d, spec, n_heads, group, dq_seq=sq_p)
    fused = hb > 0
    if not fused:
        hb = _pick_hb(bn, block_q, block_k, d, spec, n_heads, group)
    n_hb = n_heads // hb
    # grouped key/value heads: a cell's heads read one k/v head, and
    # ``cells`` cells make up a group
    hkv, cells = (1, group // hb) if group > 1 else (hb, 1)

    mp = _pad_mask(maskadd, sk_p) if spec.has_mask else None
    bp = (jnp.pad(bias, ((0, 0), (0, sq_p - sq), (0, sk_p - sk)))
          if spec.has_bias else None)
    stats = []
    if softmax:
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1)
        if dlse is not None:
            # An lse cotangent folds exactly into delta: the lse output adds
            # dlse_i * p_ij to ds_ij, and the kernels compute
            # ds = p * (dp - delta), so delta -= dlse covers it for free.
            delta = delta - dlse.astype(jnp.float32)
        lse_p = jnp.pad(lse, ((0, 0), (0, sq_p - lse.shape[1])))[:, None]
        delta_p = jnp.pad(delta, ((0, 0), (0, sq_p - delta.shape[1])))[:, None]
        stats = [lse_p, delta_p]

    vmem_limit = _tiled_vmem_limit(hb, block_q, block_k, d, spec)
    static = dict(causal=causal, sm_scale=sm_scale, logit_bias=logit_bias,
                  spec=spec, window=window)
    counted = dict(window=window, grouped=group > 1)

    def operands(kv_major):
        """A backward call's grid, its operands' specs and the inputs dq and
        dk/dv share: q, k, v, the mask rows, the bias tiles, do and the
        rows' statistics."""
        g = _tiled_grid(bn // hb, n_q, n_k, block_q, block_k, causal,
                        kv_major, window, cells)
        sp = _tiled_specs(g, hb, block_q, block_k, d, d_v, n_hb, hkv)
        inputs, specs = [qp, kp, vp], [sp["q"], sp["k"], sp["v"]]
        if spec.has_mask:
            inputs.append(mp)
            specs.append(sp["mask"])
        if spec.has_bias:
            inputs.append(bp)
            specs.append(sp["bias"])
        inputs.append(dop)
        specs.append(sp["o"])
        if softmax:
            inputs += stats
            specs += [sp["stat"], sp["stat"]]
        return g, sp, inputs, specs

    # ---- dq (above the residency bound) -----------------------------------
    if not fused:
        g, sp, inputs, specs = operands(kv_major=False)
        dq = _tiled_call(
            partial(_bwd_dq_kernel, sk_real=sk, block_k=block_k, n_k=n_k,
                    **static),
            g, specs, sp["q"], jax.ShapeDtypeStruct((bn, sq_p, d), q3.dtype),
            [pltpu.VMEM((hb, block_q, d), jnp.float32)], vmem_limit,
            inputs, **counted)

    # ---- dk / dv, and under the bound dq with them ------------------------
    g, sp, inputs, specs = operands(kv_major=True)
    out_specs = [sp["k"], sp["v"]]
    out_shape = [jax.ShapeDtypeStruct((bn // group, sk_p, d), q3.dtype),
                 jax.ShapeDtypeStruct((bn // group, sk_p, d_v), q3.dtype)]
    scratch = [pltpu.VMEM((hkv, block_k, d), jnp.float32),
               pltpu.VMEM((hkv, block_k, d_v), jnp.float32)]
    carried, limit = (2 if cells > 1 else 1), vmem_limit
    if fused:
        out_specs.append(sp["q"])
        out_shape.append(jax.ShapeDtypeStruct((bn, sq_p, d), q3.dtype))
        scratch.append(pltpu.VMEM((cells * n_q, hb, block_q, d), jnp.float32))
        # dq lives across every axis but the heads'
        carried = len(g.grid) - 1
        limit = _tiled_vmem_limit(hb, block_q, block_k, d, spec,
                                  dq_rows=cells * sq_p)
    dk, dv, *rest = _tiled_call(
        partial(_bwd_dkv_kernel, sq_real=sq, block_q=block_q, n_q=n_q,
                cells=cells, fused=fused, **static),
        g, specs, out_specs, out_shape, scratch, limit, inputs,
        carried=carried, fused=fused, **counted)
    dq = (rest[0] if fused else dq)[:, :sq]

    # ---- dbias ------------------------------------------------------------
    dbias = None
    if spec.has_bias:
        n_b = bn // n_heads
        q_idx4 = lambda h, i, j, b: (b * n_hb + h, i, 0)      # noqa: E731
        kv_idx4 = lambda h, i, j, b: (b * n_hb + h, j, 0)     # noqa: E731
        stat_idx4 = lambda h, i, j, b: (b * n_hb + h, 0, i)   # noqa: E731
        db_inputs = [qp, kp, vp]
        db_specs = [pl.BlockSpec((hb, block_q, d), q_idx4),
                    pl.BlockSpec((hb, block_k, d), kv_idx4),
                    pl.BlockSpec((hb, block_k, d_v), kv_idx4)]
        if spec.has_mask:
            db_inputs.append(mp)
            db_specs.append(pl.BlockSpec(
                (hb, 1, block_k), lambda h, i, j, b: (b * n_hb + h, 0, j)))
        db_inputs.append(bp)
        db_specs.append(pl.BlockSpec((hb, block_q, block_k),
                                     lambda h, i, j, b: (h, i, j)))
        db_inputs.append(dop)
        db_specs.append(pl.BlockSpec((hb, block_q, d_v), q_idx4))
        if softmax:
            db_inputs += stats
            db_specs += [pl.BlockSpec((hb, 1, block_q), stat_idx4),
                         pl.BlockSpec((hb, 1, block_q), stat_idx4)]
        steps = n_hb * n_q * n_k * n_b  # the rectangle: every step computes
        _count_call("tiled", steps=steps, live_steps=steps)
        dbias = pl.pallas_call(
            partial(_bwd_dbias_kernel, sq_real=sq, sk_real=sk,
                    block_q=block_q, block_k=block_k, causal=causal,
                    sm_scale=sm_scale, logit_bias=logit_bias, n_b=n_b,
                    spec=spec),
            grid=(n_hb, n_q, n_k, n_b),
            in_specs=db_specs,
            out_specs=pl.BlockSpec((hb, block_q, block_k),
                                   lambda h, i, j, b: (h, i, j)),
            out_shape=jax.ShapeDtypeStruct((n_heads, sq_p, sk_p),
                                           jnp.float32),
            scratch_shapes=[pltpu.VMEM((hb, block_q, block_k), jnp.float32)],
            # batch innermost so the bias tile accumulates in scratch
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",) * 3 + ("arbitrary",),
                vmem_limit_bytes=vmem_limit),
            interpret=_interpret(),
        )(*db_inputs)[:, :sq, :sk]

    # the mask is non-learnable by contract (it is expanded from a boolean
    # key-padding mask host-side); its zero cotangent dead-ends in the
    # wrapper's jnp.where over constants
    dmask = jnp.zeros_like(maskadd) if spec.has_mask else None
    return dq, dk[:, :sk], dv[:, :sk], dmask, dbias


def _flash_vjp_bwd(causal, spec, sm_scale, logit_bias, block_q, block_k, n,
                   group, window, res, do):
    return _flash_bwd(causal, spec, sm_scale, logit_bias, block_q, block_k,
                      n, res, do, None, group, window)


_flash.defvjp(_flash_fwd, _flash_vjp_bwd)


def _pick_block(seq: int, requested: int) -> int:
    """Largest block (<= requested) that minimizes padded-sequence length:
    dead-tile work grows with ceil_to(seq, block)^2, so e.g. seq 577 takes
    block 128 (pad to 640) over 512 (pad to 1024), while exact multiples
    keep the biggest tile. Always a multiple of 128: the (hb, 1, block)
    lse/delta blocks put the block extent in the LANE dimension, where
    Mosaic requires a 128 multiple — a sub-128 request would lower on some
    toolchains only by luck of the block==array escape hatch."""
    best = None
    for b in (512, 256, 128):
        if b > requested:
            continue
        padded = _ceil_to(seq, b)
        if best is None or padded < best[0]:
            best = (padded, b)
    return best[1] if best else _LANES


def _resolve_blocks(q, k, v, block_q, block_k,
                    kernel: str = "flash_attention"):
    """Trace-time (host-side) block resolution through the tune cache:
    ``None`` means "tuned value if the persistent cache has one for these
    shapes/dtypes, else the shipped default" — lookup only, never a
    measurement (docs/tuning.md). Explicit ints win, so the tuner's own
    bench closures cannot recurse. Each family member looks up under its
    own kernel name (its VMEM footprint, and therefore its feasible and
    optimal blocks, differ)."""
    if block_q is not None and block_k is not None:
        return int(block_q), int(block_k)
    from jimm_tpu.tune import best_config
    cfg = best_config(kernel, (q.shape, k.shape, v.shape),
                      (q.dtype, k.dtype, v.dtype),
                      default={"block_q": DEFAULT_BLOCK_Q,
                               "block_k": DEFAULT_BLOCK_K})
    return (int(block_q if block_q is not None else cfg["block_q"]),
            int(block_k if block_k is not None else cfg["block_k"]))


def _fit_blocks(sq: int, sk: int, d: int, itemsize: int, spec: VariantSpec,
                block_q: int, block_k: int, requested: bool = False):
    """The blocks the kernels run at. Unless the caller ``requested`` its
    own, a sequence the single-tile rule admits is one block, its whole
    lane-padded length (an edge block of the kernels' index maps: nothing is
    padded in HBM); every other takes the resolved blocks fitted to its
    length (`_pick_block`), which the tiled regime pads its flattened
    ``(B*N, S, D)`` copies to, as it always has."""
    sq_p, sk_p = _ceil_to(sq, _LANES), _ceil_to(sk, _LANES)
    if not requested and _single_tile_hb(1, sq_p, sk_p, d, itemsize, spec):
        return sq_p, sk_p
    return (min(_pick_block(sq, block_q), sq_p),
            min(_pick_block(sk, block_k), sk_p))


def _prologue(q, k, v, block_q, block_k, kernel: str = "flash_attention",
              spec: VariantSpec = _SOFTMAX, grouped: bool = False):
    """Scale, block and layout selection for every entry point: returns
    ``(q, k, v, sm_scale, block_q, block_k, n)``. Where the single-tile rule
    admits the shape, q/k/v stay in the model's layout, ``(B, S, N, D)``
    seen as ``(B, S, N * D_p)`` with ``n = N`` heads in a row (a free
    reshape; an off-tile head dim is zero-padded once on the 4-D view).
    Only the tiled regime still flattens the heads to ``(B * N, S, D_p)``
    rows (a transposed copy of each), ``n = 1``; ``grouped`` calls (fewer
    key/value heads than query heads) always do, each array at its own head
    count, since a single-tile cell slices one head count's lanes. The scale
    uses the REAL d."""
    b, sq, n, d = q.shape
    sm_scale = 1.0 / (d ** 0.5)
    dp = _head_pad_target(d)
    requested = block_q is not None or block_k is not None
    block_q, block_k = _resolve_blocks(q, k, v, block_q, block_k,
                                       kernel=kernel)
    block_q, block_k = _fit_blocks(sq, k.shape[1], dp, q.dtype.itemsize, spec,
                                   block_q, block_k, requested or grouped)
    if not grouped and _single_tile_plan(
            n, sq, k.shape[1], dp, q.dtype.itemsize, spec, block_q,
            block_k)[0]:
        # one head width here: a narrower v is padded out to q's
        q3, k3, v3 = (_pad_last(x, dp).reshape(*x.shape[:2], n * dp)
                      for x in (q, k, v))
        return q3, k3, v3, sm_scale, block_q, block_k, n
    # the tiled kernels take v (and o, do, dv) at its own tile: latent
    # attention's values are 128 wide beside q and k of 192
    q3, k3, v3 = (_pad_last(_flatten_heads(x), _head_pad_target(x.shape[-1]))
                  for x in (q, k, v))
    return q3, k3, v3, sm_scale, block_q, block_k, 1


def _epilogue(o: jax.Array, b: int, n: int, d: int, n_row: int) -> jax.Array:
    """`_prologue`'s layout (``n_row`` heads in a row) back to
    ``(B, S, N, D)``, ``d`` the width of v."""
    if n_row == n:
        return o.reshape(b, o.shape[1], n, -1)[..., :d]
    return _unflatten_heads(o, b, n)[..., :d]


def _lse_rows(lse: jax.Array, sq: int) -> jax.Array:
    """The lse residual as rows of S_q, whichever regime kept it (the
    single-tile regime keeps the kernels' lane-padded ``(B', n, 1, S_p)``)."""
    return lse[:, :, 0, :sq] if lse.ndim == 4 else lse


def _canon_mask(mask: jax.Array, b: int, sk: int) -> jax.Array:
    """Accept ``(B, Sk)`` or the dispatch convention ``(B, 1, 1, Sk)``
    (bool/int, True = attend); return ``(B, Sk)`` bool."""
    if mask.ndim == 4:
        if mask.shape[1] != 1 or mask.shape[2] != 1:
            raise ValueError(
                "masked flash attention supports KEY-PADDING masks only "
                f"((B, Sk) or (B, 1, 1, Sk)); got {mask.shape} — arbitrary "
                "(B, N, Sq, Sk) masks need impl='xla'")
        mask = mask[:, 0, 0, :]
    if mask.shape != (b, sk):
        raise ValueError(f"key-padding mask shape {mask.shape} does not "
                         f"match (B, Sk)=({b}, {sk})")
    return mask != 0


def _expand_mask(mask: jax.Array, n: int) -> jax.Array:
    """(B, Sk) bool -> (B*n, 1, Sk) additive f32 rows (0 keep / NEG_INF
    drop), one per row of the kernels' batch: ``n = 1`` in the model's
    layout (the single-tile cells index the sample's row), ``n = N`` copies
    in `_flatten_heads` row order for the flattened one."""
    b, sk = mask.shape
    add = jnp.where(mask, 0.0, NEG_INF).astype(jnp.float32)
    return jnp.broadcast_to(add[:, None, None, :],
                            (b, n, 1, sk)).reshape(b * n, 1, sk)


def _canon_bias(bias: jax.Array, n: int, sq: int, sk: int) -> jax.Array:
    """Broadcast an additive bias to per-head ``(N, Sq, Sk)`` f32 (grads
    flow back through the broadcast to the caller's shape)."""
    return jnp.broadcast_to(bias.astype(jnp.float32), (n, sq, sk))


def kv_group(q: jax.Array, k: jax.Array, v: jax.Array) -> int:
    """Query heads to one key/value head of ``(B, S, N, D)`` q, k, v."""
    n, n_kv = q.shape[2], k.shape[2]
    if v.shape[2] != n_kv or n % n_kv:
        raise ValueError(f"{n} query heads do not divide over {n_kv} key and "
                         f"{v.shape[2]} value heads")
    return n // n_kv


def live_window(window: int | None, is_causal: bool, sk: int) -> int | None:
    """A causal call's ``window`` (key j is visible to query i iff
    ``0 <= i - j < window``), or None where it hides nothing: a window that
    reaches back over all ``sk`` keys IS the plain causal call."""
    if window is None:
        return None
    if not is_causal or window < 1:
        raise ValueError(f"window={window} needs is_causal=True and at least "
                         "the query's own position")
    return None if window >= sk else int(window)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    is_causal: bool = False, window: int | None = None,
                    block_q: int | None = None,
                    block_k: int | None = None) -> jax.Array:
    """Flash attention over ``(B, S, N, D)`` q/k/v; v's heads may have a
    width of their own (the output then has it). k and v may have fewer heads
    than q, a divisor of its count (grouped-query attention: query head h
    reads key/value head ``h // (N / N_kv)``; k and v are fetched at their own
    heads, never repeated, and dk, dv come back at them). ``window``: a causal
    call sees only the ``window`` newest keys of each query, its own counted
    (`live_window`). Scale is 1/sqrt(D) of q like
    `jax.nn.dot_product_attention`. Runs the Pallas interpreter off-TPU so
    CPU tests exercise the same code path. Block sizes default to the tune
    cache's answer for these shapes (falling back to ``DEFAULT_BLOCK_*``)."""
    b, _, n, d = q.shape
    group = kv_group(q, k, v)
    window = live_window(window, is_causal, k.shape[1])
    q3, k3, v3, sm_scale, block_q, block_k, n_row = _prologue(
        q, k, v, block_q, block_k, grouped=group > 1)
    o = _flash(q3, k3, v3, None, None, is_causal, _SOFTMAX, sm_scale, 0.0,
               block_q, block_k, n_row, group, window)
    return _epilogue(o, b, n, v.shape[-1], n_row)


def flash_attention_masked(q: jax.Array, k: jax.Array, v: jax.Array,
                           mask: jax.Array, *,
                           is_causal: bool = False,
                           block_q: int | None = None,
                           block_k: int | None = None) -> jax.Array:
    """Flash attention with a per-sample key-padding mask (the NaFlex /
    MAP-pooling case): ``mask`` is ``(B, Sk)`` or ``(B, 1, 1, Sk)``
    bool/int, True = attend. Masked keys receive exactly zero attention
    and zero gradient. Rows with NO valid key produce finite garbage (see
    module docstring) — mask them downstream, as NaFlex pooling does."""
    b, _, n, d = q.shape
    spec = VariantSpec(kind="softmax", has_mask=True)
    q3, k3, v3, sm_scale, block_q, block_k, n_row = _prologue(
        q, k, v, block_q, block_k, kernel="flash_attention_masked", spec=spec)
    maskadd = _expand_mask(_canon_mask(mask, b, k.shape[1]), n // n_row)
    o = _flash(q3, k3, v3, maskadd, None, is_causal, spec, sm_scale, 0.0,
               block_q, block_k, n_row)
    return _epilogue(o, b, n, v.shape[-1], n_row)


def flash_attention_bias(q: jax.Array, k: jax.Array, v: jax.Array,
                         bias: jax.Array, *,
                         is_causal: bool = False,
                         block_q: int | None = None,
                         block_k: int | None = None) -> jax.Array:
    """Flash attention with an additive logits bias broadcastable to
    ``(N, Sq, Sk)`` (relative-position style; shared across the batch).
    Differentiable in ``bias`` — the backward runs a dedicated
    batch-innermost accumulation kernel, never materializing
    ``(B, N, Sq, Sk)``."""
    b, sq, n, d = q.shape
    sk = k.shape[1]
    bias3 = _canon_bias(bias, n, sq, sk)
    spec = VariantSpec(kind="softmax", has_bias=True)
    q3, k3, v3, sm_scale, block_q, block_k, n_row = _prologue(
        q, k, v, block_q, block_k, kernel="flash_attention_bias", spec=spec)
    o = _flash(q3, k3, v3, None, bias3, is_causal, spec, sm_scale, 0.0,
               block_q, block_k, n_row)
    return _epilogue(o, b, n, v.shape[-1], n_row)


def sigmoid_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      is_causal: bool = False,
                      mask: jax.Array | None = None,
                      logit_bias: float | None = None,
                      block_q: int | None = None,
                      block_k: int | None = None) -> jax.Array:
    """Sigmoid attention: ``o = sigmoid(q k^T / sqrt(D) + logit_bias) v``
    — no row normalizer, so the online loop keeps no statistics and the
    backward needs no lse/delta. ``logit_bias`` defaults to ``-log(Sk)``
    (the sigmoid-attention paper's initialization, which matches softmax's
    1/Sk row mass at init). Optional key-padding ``mask`` as in
    `flash_attention_masked`; masked (and fully-masked) rows are exactly
    zero here — sigmoid(NEG_INF) underflows to 0, no garbage rows."""
    b, _, n, d = q.shape
    sk = k.shape[1]
    if logit_bias is None:
        logit_bias = -math.log(max(sk, 1))
    spec = VariantSpec(kind="sigmoid", has_mask=mask is not None)
    q3, k3, v3, sm_scale, block_q, block_k, n_row = _prologue(
        q, k, v, block_q, block_k, kernel="sigmoid_attention", spec=spec)
    maskadd = (_expand_mask(_canon_mask(mask, b, sk), n // n_row)
               if mask is not None else None)
    o = _flash(q3, k3, v3, maskadd, None, is_causal, spec, sm_scale,
               float(logit_bias), block_q, block_k, n_row)
    return _epilogue(o, b, n, v.shape[-1], n_row)


# ---------------------------------------------------------------------------
# (o, lse) variant — building block for cross-chip ring attention
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_lse(q3, k3, v3, causal, sm_scale, block_q, block_k, n):
    o, res = _flash_fwd_impl(q3, k3, v3, None, None, causal, _SOFTMAX,
                             sm_scale, 0.0, block_q, block_k, n)
    return o, res[6]


def _flash_lse_fwd(q3, k3, v3, causal, sm_scale, block_q, block_k, n):
    o, res = _flash_fwd_impl(q3, k3, v3, None, None, causal, _SOFTMAX,
                             sm_scale, 0.0, block_q, block_k, n)
    return (o, res[6]), res


def _flash_lse_bwd(causal, sm_scale, block_q, block_k, n, res, cts):
    do, dlse = cts
    # The lse cotangent is exact and free: it folds into the delta term of
    # the standard flash backward (see _flash_bwd) — no extra passes, no
    # materialized attention matrix. It comes in the residual's own form
    # (`_lse_rows` is differentiated outside), so padded where that is.
    dq, dk, dv, _, _ = _flash_bwd(causal, _SOFTMAX, sm_scale, 0.0, block_q,
                                  block_k, n, res, do, dlse)
    return dq, dk, dv


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention_lse(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        is_causal: bool = False,
                        block_q: int | None = None,
                        block_k: int | None = None
                        ) -> tuple[jax.Array, jax.Array]:
    """Like `flash_attention` but also returns the per-row logsumexp
    ``(B, N, S)`` so partial results over kv chunks can be merged exactly
    (the ring-attention combine)."""
    b, sq, n, d = q.shape
    q3, k3, v3, sm_scale, block_q, block_k, n_row = _prologue(
        q, k, v, block_q, block_k)
    o3, lse3 = _flash_lse(q3, k3, v3, is_causal, sm_scale, block_q, block_k,
                          n_row)
    return (_epilogue(o3, b, n, v.shape[-1], n_row),
            _lse_rows(lse3, sq).reshape(b, n, sq))


# ---------------------------------------------------------------------------
# External-residual hop entry points — the sequence-parallel ring
# (`jimm_tpu/parallel/seqpar.py`) drives the SAME kernels per KV hop
# ---------------------------------------------------------------------------

def ring_hop_fwd(q3, k3, v3, maskadd, spec, sm_scale, logit_bias,
                 block_q, block_k):
    """One ring-hop forward in flattened-heads ``(B*N, S, D)`` space (to the
    kernels: rows of one head, ``n = 1``): returns ``(o, lse)`` for the
    hop's local (q × visiting-KV) product (``lse`` is None for the sigmoid
    kind, which keeps no normalizer). The caller owns the cross-hop merge
    and differentiation — this is a plain function, not a custom_vjp."""
    o, res = _flash_fwd_impl(q3, k3, v3, maskadd, None, False, spec,
                             sm_scale, logit_bias, block_q, block_k, 1)
    lse = res[6]
    sq = q3.shape[1]
    return o, None if lse is None else _lse_rows(lse, sq).reshape(-1, sq)


def ring_hop_bwd(q3, k3, v3, maskadd, o3, lse3, do3, spec, sm_scale,
                 logit_bias, block_q, block_k):
    """One ring-hop backward against GLOBAL residuals: ``o3``/``lse3`` are
    the fully-merged output and logsumexp (all chunks folded), so the
    kernels' ``p = exp(s - lse)`` and ``delta = rowsum(do·o)`` are the
    global row statistics and the per-hop dq/dk/dv are exact partial
    gradients — summing them over hops reproduces the unsharded backward.
    (Sigmoid ignores o3/lse3: no normalizer, no delta.)"""
    dq, dk, dv, _, _ = _flash_bwd(False, spec, sm_scale, logit_bias,
                                  block_q, block_k, 1,
                                  (q3, k3, v3, maskadd, None, o3, lse3), do3)
    return dq, dk, dv
