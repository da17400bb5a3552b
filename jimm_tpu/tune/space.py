"""Per-kernel block-size search spaces with static feasibility pruning.

Candidates that cannot lower (tile-alignment) or cannot fit (VMEM) are
pruned *before* anything is measured, so a sweep never wastes reps on a
config Mosaic would reject. The VMEM model for flash attention mirrors
``ops.flash_attention._per_head_vmem_bytes`` — duplicated here (like the
linter's mesh-axis table) so this module never imports jax; a sync test in
`tests/test_tune.py` keeps the two formulas from drifting.
"""

from __future__ import annotations

from typing import Any, Sequence

__all__ = ["FLASH_BLOCKS", "FLASH_VMEM_BUDGET", "FP8_MATMUL_BLOCK_M",
           "FP8_MATMUL_BLOCK_N",
           "INT8_FLASH_BLOCKS", "INT8_MATMUL_BLOCK_M",
           "INT8_MATMUL_BLOCK_N", "LN_BLOCK_ROWS", "RETRIEVAL_BLOCK_N",
           "VMEM_BUDGET", "bias_flash_space", "bias_flash_vmem_bytes",
           "flash_space", "flash_vmem_bytes", "fp8_matmul_space",
           "fp8_matmul_vmem_bytes", "int8_flash_bwd_vmem_bytes",
           "int8_flash_space", "int8_flash_vmem_bytes", "int8_matmul_space",
           "int8_matmul_vmem_bytes", "ivf_space", "ivf_vmem_bytes",
           "kernel_space", "ln_space",
           "ln_vmem_bytes", "masked_flash_space", "masked_flash_vmem_bytes",
           "retrieval_space", "retrieval_vmem_bytes", "ring_space",
           "ring_vmem_bytes", "sigmoid_space", "sigmoid_vmem_bytes",
           "tier_space"]

_LANES = 128
_SUBLANES = 8
_INT8_SUBLANES = 32

#: the per-cell budget of the matmul, LayerNorm and retrieval kernels, which
#: run under Mosaic's default 16 MiB scope (mirrors ``_VMEM_BUDGET`` of
#: ops.int8_matmul / fp8_matmul / flash_attention_int8; sync-tested)
VMEM_BUDGET = 8 * 1024 * 1024

#: mirrors ops.flash_attention._VMEM_BUDGET (sync-tested): the tiled flash
#: calls state their own scoped VMEM (twice their model, at most 64 MiB of
#: the v5e's 128), so a cell's working set may reach 32 MiB
FLASH_VMEM_BUDGET = 32 * 1024 * 1024

#: the flash grid tiles Mosaic handles well: lane-aligned powers of two.
#: `_pick_block` in the kernel clamps to the padded sequence, so candidates
#: larger than the (128-padded) sequence are redundant and pruned here.
#: Blocks of 1024 and 2048 lost to 512 at every shape measured (PERF.md,
#: PR 33) and are not offered.
FLASH_BLOCKS = (128, 256, 512)

#: LN row-block candidates — sublane-aligned, from minimum tile to the
#: point where the (block_rows, features) fp32 working set dominates VMEM
LN_BLOCK_ROWS = (8, 16, 32, 64, 128, 256, 512)

#: corpus-block candidates for the streaming top-k scan — lane-aligned so
#: the (block_n, D) corpus tile and (B, block_n) score tile both land on
#: 128-lane boundaries; larger blocks amortize the per-step top_k merge,
#: smaller ones cap the resident score tile
RETRIEVAL_BLOCK_N = (128, 256, 512, 1024, 2048, 4096)


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def flash_vmem_bytes(block_q: int, block_k: int, d: int,
                     dq_rows: int = 0) -> int:
    """jax-free mirror of ``_per_head_vmem_bytes`` (see module docstring).
    ``dq_rows``: the fused tiled backward's resident fp32 dq rows a head (its
    padded S_q); the block search prunes on the tiles alone, as `_pick_hb`
    holds the two under budgets of their own."""
    return (
        3 * block_k * d * 2
        + 2 * block_q * d * 2
        + 2 * block_q * _LANES * 4
        + 2 * block_q * d * 4
        + block_q * block_k * 20   # s, p, dp, ds fp32 + two bf16 MXU copies
        + dq_rows * d * 4)


def _attn_space(shapes: Sequence[Sequence[int]], vmem_fn) -> list[dict]:
    """Shared ``{"block_q", "block_k"}`` pruning for the attention family:
    same lane-aligned candidates, variant-specific VMEM formula."""
    q, k = shapes[0], shapes[1]
    sq, sk, d = int(q[-3]), int(k[-3]), int(q[-1])
    out = []
    for bq in FLASH_BLOCKS:
        if bq > _ceil_to(sq, _LANES):
            continue
        for bk in FLASH_BLOCKS:
            if bk > _ceil_to(sk, _LANES):
                continue
            if vmem_fn(bq, bk, d) > FLASH_VMEM_BUDGET:
                continue
            out.append({"block_q": bq, "block_k": bk})
    return out or [{"block_q": FLASH_BLOCKS[0], "block_k": FLASH_BLOCKS[0]}]


def flash_space(shapes: Sequence[Sequence[int]],
                dtypes: Sequence[Any] = ()) -> list[dict]:
    """Feasible ``{"block_q", "block_k"}`` candidates for q/k/v shapes
    ``(B, S, N, D)`` (or head-flattened ``(BN, S, D)``)."""
    return _attn_space(shapes, flash_vmem_bytes)


def masked_flash_vmem_bytes(block_q: int, block_k: int, d: int) -> int:
    """Softmax flash + the additive key-padding row: one f32 ``(1, bk)``
    mask tile per grid cell (mirrors ``has_mask`` in
    ``_per_head_vmem_bytes``)."""
    return flash_vmem_bytes(block_q, block_k, d) + block_k * 4


def masked_flash_space(shapes: Sequence[Sequence[int]],
                       dtypes: Sequence[Any] = ()) -> list[dict]:
    """Candidates for key-padding-mask flash (NaFlex / MAP pooling)."""
    return _attn_space(shapes, masked_flash_vmem_bytes)


def bias_flash_vmem_bytes(block_q: int, block_k: int, d: int) -> int:
    """Softmax flash + two f32 ``(bq, bk)`` tiles: the resident bias
    in-tile and the dbias scratch/out tile of the backward's accumulation
    kernel (mirrors ``has_bias`` in ``_per_head_vmem_bytes``)."""
    return flash_vmem_bytes(block_q, block_k, d) + 2 * block_q * block_k * 4


def bias_flash_space(shapes: Sequence[Sequence[int]],
                     dtypes: Sequence[Any] = ()) -> list[dict]:
    """Candidates for additive-bias flash (relative-position style)."""
    return _attn_space(shapes, bias_flash_vmem_bytes)


def sigmoid_vmem_bytes(block_q: int, block_k: int, d: int) -> int:
    """Sigmoid attention keeps no online m/l statistics (no row
    normalizer), dropping the two ``(bq, 128)`` f32 stat tiles; the
    optional key-padding row stays in the budget because serving routes
    padded batches through it (mirrors ``kind='sigmoid', has_mask=True``
    in ``_per_head_vmem_bytes``)."""
    return (flash_vmem_bytes(block_q, block_k, d)
            - 2 * block_q * _LANES * 4
            + block_k * 4)


def sigmoid_space(shapes: Sequence[Sequence[int]],
                  dtypes: Sequence[Any] = ()) -> list[dict]:
    """Candidates for sigmoid attention (no-normalizer online loop)."""
    return _attn_space(shapes, sigmoid_vmem_bytes)


def ring_vmem_bytes(block_q: int, block_k: int, d: int) -> int:
    """Per-hop cell of the sequence-parallel ring
    (`parallel/seqpar.py`): each hop IS a masked softmax flash call over
    the local chunk (the traveling key-padding row resident like the
    single-chip masked variant), so the hop's VMEM model is the masked
    formula — the ring adds HBM-resident chunk buffers, not VMEM
    (mirrors ``kind='softmax', has_mask=True`` in
    ``_per_head_vmem_bytes``; sync-tested)."""
    return masked_flash_vmem_bytes(block_q, block_k, d)


def ring_space(shapes: Sequence[Sequence[int]],
               dtypes: Sequence[Any] = ()) -> list[dict]:
    """Feasible ``{"block_q", "block_k"}`` candidates for ONE ring hop.
    ``shapes`` are the per-device LOCAL chunk shapes ``(B, S/p, N, D)`` —
    the key the wrapper resolves under (`seqpar._resolve_ring_blocks`):
    the hop kernel never sees more than a chunk, so candidates larger
    than the 128-padded chunk are redundant exactly like the single-chip
    clamp."""
    return _attn_space(shapes, ring_vmem_bytes)


def ln_vmem_bytes(block_rows: int, features: int) -> int:
    """Coarse upper bound on one LN grid cell's resident fp32 working set:
    x/do/dx tiles plus temporaries at the 128-padded feature width, and the
    two (8, features) partial blocks."""
    fp = _ceil_to(features, _LANES)
    return 6 * block_rows * fp * 4 + 2 * _SUBLANES * fp * 4


def ln_space(shapes: Sequence[Sequence[int]],
             dtypes: Sequence[Any] = ()) -> list[dict]:
    """Feasible ``{"block_rows"}`` candidates for an ``(rows, features)``
    LayerNorm input."""
    rows, features = int(shapes[0][-2]), int(shapes[0][-1])
    out = []
    for br in LN_BLOCK_ROWS:
        if br > _ceil_to(rows, _SUBLANES):
            continue
        if ln_vmem_bytes(br, features) > VMEM_BUDGET:
            continue
        out.append({"block_rows": br})
    return out or [{"block_rows": LN_BLOCK_ROWS[0]}]


def retrieval_vmem_bytes(block_n: int, dim: int, batch: int = 64) -> int:
    """Coarse resident working set of one streaming top-k scan step: the
    f32-upcast corpus block, the query tile, and the (batch, block_n)
    score tile — doubled for the pipeline's in-flight block."""
    fp_d = _ceil_to(dim, _LANES)
    return 2 * (block_n * fp_d * 4 + batch * fp_d * 4
                + batch * block_n * 4)


def retrieval_space(shapes: Sequence[Sequence[int]],
                    dtypes: Sequence[Any] = ()) -> list[dict]:
    """Feasible ``{"block_n"}`` candidates for a top-k workload shaped
    ``[(batch, dim), (n_rows, dim)]``. Blocks past the 128-padded corpus
    are redundant (one padded block already covers every row)."""
    batch, dim = int(shapes[0][-2]), int(shapes[0][-1])
    n_rows = int(shapes[-1][-2])
    out = []
    for bn in RETRIEVAL_BLOCK_N:
        if bn > _ceil_to(max(n_rows, 1), _LANES) and out:
            continue
        if retrieval_vmem_bytes(bn, dim, batch) > VMEM_BUDGET:
            continue
        out.append({"block_n": bn})
    return out or [{"block_n": RETRIEVAL_BLOCK_N[0]}]


def ivf_vmem_bytes(block_n: int, dim: int, batch: int = 64) -> int:
    """Coarse resident working set of one IVF rescore step. Unlike the
    exact scan — one shared block per step — the IVF scan gathers *each
    query its own* candidate block, so the f32-upcast block tile and the
    id row are batch-multiplied: feasible blocks shrink as the query
    bucket grows. Doubled for the pipeline's in-flight gather."""
    fp_d = _ceil_to(dim, _LANES)
    return 2 * batch * (block_n * fp_d * 4   # gathered (B, bn, D) blocks
                        + block_n * 4        # (B, bn) scores
                        + block_n * 4        # (B, bn) row-id gather
                        + fp_d * 4)          # query tile


def ivf_space(shapes: Sequence[Sequence[int]],
              dtypes: Sequence[Any] = ()) -> list[dict]:
    """Feasible ``{"block_n"}`` candidates for an IVF workload shaped
    ``[(batch, dim), (n_rows, dim)]``. Same candidate grid as the exact
    scan; the batch-multiplied VMEM model does the pruning. Smaller blocks
    also waste less rescore work (a cluster pads to whole blocks), so the
    feasibility floor returning the smallest block is the safe default."""
    batch, dim = int(shapes[0][-2]), int(shapes[0][-1])
    n_rows = int(shapes[-1][-2])
    out = []
    for bn in RETRIEVAL_BLOCK_N:
        if bn > _ceil_to(max(n_rows, 1), _LANES) and out:
            continue
        if ivf_vmem_bytes(bn, dim, batch) > VMEM_BUDGET:
            continue
        out.append({"block_n": bn})
    return out or [{"block_n": RETRIEVAL_BLOCK_N[0]}]


def tier_space(shapes: Sequence[Sequence[int]],
               dtypes: Sequence[Any] = ()) -> list[dict]:
    """Feasible ``{"block_n"}`` candidates for the tiered searcher's hot
    scan. The device program is the IVF scan plus a probe-selection
    output (a few KiB — below model resolution), so feasibility is the
    IVF model's; what differs is the *preference*: block_n is also the
    hot arena's allocation quantum, so smaller blocks pack more clusters
    per device budget (see ``tune.api._tier_default``)."""
    return ivf_space(shapes, dtypes)


#: int8 matmul grid tiles: rows align to the int8 32-sublane tile, columns
#: to 128 lanes. The wrapper clamps to the padded M/N, so oversize
#: candidates are pruned here as redundant.
INT8_MATMUL_BLOCK_M = (32, 64, 128, 256, 512)
INT8_MATMUL_BLOCK_N = (128, 256, 512)

#: int8 flash q/k blocks share the f32 kernel's lane-aligned candidates
#: (`_pick_block` clamps to the padded sequence the same way)
INT8_FLASH_BLOCKS = (128, 256, 512)


def int8_matmul_vmem_bytes(block_m: int, block_n: int, k: int) -> int:
    """jax-free mirror of ``ops.int8_matmul._per_cell_vmem_bytes``
    (sync-tested): int8 x/w tiles at 128-padded K, lane-broadcast row
    scales, 1-D column scale + bias, int32 acc + f32 epilogue + out."""
    kp = _ceil_to(k, _LANES)
    return (block_m * kp
            + kp * block_n
            + block_m * _LANES * 4
            + 2 * block_n * 4
            + 3 * block_m * block_n * 4)


def int8_matmul_space(shapes: Sequence[Sequence[int]],
                      dtypes: Sequence[Any] = ()) -> list[dict]:
    """Feasible ``{"block_m", "block_n"}`` candidates for an int8 matmul
    shaped ``[(M, K), (K, N)]``. Blocks past the tile-padded M/N are
    redundant (the wrapper clamps); VMEM-infeasible cells are pruned."""
    m, k = int(shapes[0][-2]), int(shapes[0][-1])
    n = int(shapes[1][-1])
    out = []
    for bm in INT8_MATMUL_BLOCK_M:
        if bm > _ceil_to(m, _INT8_SUBLANES):
            continue
        for bn in INT8_MATMUL_BLOCK_N:
            if bn > _ceil_to(n, _LANES):
                continue
            if int8_matmul_vmem_bytes(bm, bn, k) > VMEM_BUDGET:
                continue
            out.append({"block_m": bm, "block_n": bn})
    return out or [{"block_m": INT8_MATMUL_BLOCK_M[0],
                    "block_n": INT8_MATMUL_BLOCK_N[0]}]


#: fp8 matmul grid tiles: same alignment story as int8 (fp8 Mosaic tiles
#: are (32, 128) too), so the candidate grids coincide
FP8_MATMUL_BLOCK_M = (32, 64, 128, 256, 512)
FP8_MATMUL_BLOCK_N = (128, 256, 512)


def fp8_matmul_vmem_bytes(block_m: int, block_n: int, k: int) -> int:
    """jax-free mirror of ``ops.fp8_matmul._per_cell_vmem_bytes``
    (sync-tested): fp8 a/b tiles at 128-padded K, the lane-broadcast
    per-tensor scale, bias, f32 acc + out."""
    kp = _ceil_to(k, _LANES)
    return (block_m * kp
            + kp * block_n
            + _LANES * 4
            + block_n * 4
            + 2 * block_m * block_n * 4)


def fp8_matmul_space(shapes: Sequence[Sequence[int]],
                     dtypes: Sequence[Any] = ()) -> list[dict]:
    """Feasible ``{"block_m", "block_n"}`` candidates for an fp8 matmul
    shaped ``[(M, K), (K, N)]``. Same pruning story as the int8 space:
    blocks past the tile-padded M/N are redundant (the wrapper clamps),
    VMEM-infeasible cells are dropped."""
    m, k = int(shapes[0][-2]), int(shapes[0][-1])
    n = int(shapes[1][-1])
    out = []
    for bm in FP8_MATMUL_BLOCK_M:
        if bm > _ceil_to(m, _INT8_SUBLANES):
            continue
        for bn in FP8_MATMUL_BLOCK_N:
            if bn > _ceil_to(n, _LANES):
                continue
            if fp8_matmul_vmem_bytes(bm, bn, k) > VMEM_BUDGET:
                continue
            out.append({"block_m": bm, "block_n": bn})
    return out or [{"block_m": FP8_MATMUL_BLOCK_M[0],
                    "block_n": FP8_MATMUL_BLOCK_N[0]}]


def int8_flash_vmem_bytes(block_q: int, block_k: int, d: int) -> int:
    """jax-free mirror of ``ops.flash_attention_int8._per_head_vmem_bytes``
    (sync-tested): int8 q/k at the 128-padded head dim, storage-dtype v and
    out, f32 stats/accumulator, lse-layout scale tiles, and the f32 lse
    out row the backward consumes."""
    dp = _ceil_to(d, _LANES)
    return (block_q * dp + block_k * dp
            + 2 * block_k * d * 2
            + block_q * d * 2
            + 2 * block_q * _LANES * 4
            + block_q * d * 4
            + (block_q + block_k) * 4
            + block_q * 4
            + block_q * block_k * 6)


def int8_flash_bwd_vmem_bytes(block_q: int, block_k: int, d: int) -> int:
    """jax-free mirror of
    ``ops.flash_attention_int8._per_head_bwd_vmem_bytes`` (sync-tested):
    the dq / dkv backward cells' shared upper bound — int8 q/k tiles,
    storage-dtype v/do, scale + lse + delta rows, f32 dq and dk/dv
    scratch, and the recomputed s/p/ds f32 temporaries."""
    dp = _ceil_to(d, _LANES)
    return (block_q * dp + block_k * dp
            + block_k * d * 2 + block_q * d * 2
            + (block_q + block_k) * 4
            + 2 * block_q * 4
            + (block_k * dp + block_k * d) * 4
            + block_q * dp * 4
            + 3 * block_q * block_k * 4)


def int8_flash_space(shapes: Sequence[Sequence[int]],
                     dtypes: Sequence[Any] = ()) -> list[dict]:
    """Feasible ``{"block_q", "block_k"}`` candidates for int8 flash
    attention over q/k/v shapes ``(B, S, N, D)`` (or head-flattened).
    Blocks are shared between forward and backward, so a candidate must
    fit both cells' working sets."""
    q, k = shapes[0], shapes[1]
    sq, sk, d = int(q[-3]), int(k[-3]), int(q[-1])
    out = []
    for bq in INT8_FLASH_BLOCKS:
        if bq > _ceil_to(sq, _LANES):
            continue
        for bk in INT8_FLASH_BLOCKS:
            if bk > _ceil_to(sk, _LANES):
                continue
            if max(int8_flash_vmem_bytes(bq, bk, d),
                   int8_flash_bwd_vmem_bytes(bq, bk, d)) > VMEM_BUDGET:
                continue
            out.append({"block_q": bq, "block_k": bk})
    return out or [{"block_q": INT8_FLASH_BLOCKS[0],
                    "block_k": INT8_FLASH_BLOCKS[0]}]


_SPACES = {"flash_attention": flash_space,
           "flash_attention_masked": masked_flash_space,
           "flash_attention_bias": bias_flash_space,
           "sigmoid_attention": sigmoid_space,
           "layer_norm": ln_space,
           "retrieval_topk": retrieval_space,
           "retrieval_ivf": ivf_space,
           "retrieval_tier": tier_space,
           "int8_matmul": int8_matmul_space,
           "fp8_matmul": fp8_matmul_space,
           "flash_attention_int8": int8_flash_space,
           "ring_attention": ring_space}


def kernel_space(kernel: str, shapes: Sequence[Sequence[int]],
                 dtypes: Sequence[Any] = ()) -> list[dict]:
    """Pruned candidate list for ``kernel`` at the given shapes."""
    try:
        fn = _SPACES[kernel]
    except KeyError:
        raise KeyError(f"no search space for kernel {kernel!r}; "
                       f"known: {sorted(_SPACES)}") from None
    return fn(shapes, dtypes)
