"""Batch shape buckets — the server-side half of the JLT103 discipline.

A jitted forward compiles one executable per input shape. A server that
dispatches whatever batch size the traffic happens to produce compiles an
unbounded family of programs (cache-key churn, multi-second stalls mid-
traffic). The fix is the same one the linter's JLT103 trace check certifies
from the model side: declare a small, fixed set of batch buckets up front,
pad every micro-batch up to the nearest bucket, and warm-compile each bucket
once at startup. After warmup the engine never sees a new shape.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

#: CPU-smoke bucket set: small enough that warmup is a few tiny compiles.
DEFAULT_BATCH_BUCKETS: tuple[int, ...] = (1, 2, 4, 8)

#: TPU bucket set: powers of two up to the single-chip throughput batch the
#: inference bench tracks (256 is BASELINE's inference batch).
TPU_BATCH_BUCKETS: tuple[int, ...] = (1, 2, 4, 8, 16, 32, 64, 128, 256)

#: NaFlex token-sequence buckets: variable-resolution batches pad their
#: patch sequences to the nearest bucket, so the NaFlex forward compiles
#: one program per (batch bucket, seq bucket) pair instead of one per
#: traffic-dependent grid. 256 = a 16x16 patch grid, 576 = 24x24 (the
#: SigLIP2 NaFlex training default), 1024 = 32x32. Padding is carried by
#: the key mask, which the attention dispatch runs on the masked flash
#: variant — mask CONTENTS are runtime data, so every real-token count
#: shares the bucket's one executable.
DEFAULT_NAFLEX_SEQ_BUCKETS: tuple[int, ...] = (256, 576, 1024)

#: precisions a serving stack can declare. The dtype names the precision
#: the warm-compiled forwards COMPUTE in — batch assembly stays fp32
#: images; "int8" means quantized weights + dynamic int8 activations
#: inside the Pallas kernels (docs/quantization.md).
SERVE_DTYPES: tuple[str, ...] = ("float32", "bfloat16", "int8")


@dataclasses.dataclass(frozen=True)
class BucketTable:
    """An ascending, de-duplicated set of allowed batch sizes, tagged with
    the serving precision. The dtype rides the table (not the engine)
    because it is part of the same compile-shape contract: one warm
    executable per (bucket, dtype), and the ready line reports both
    axes."""

    sizes: tuple[int, ...]
    dtype: str = "float32"

    def __post_init__(self) -> None:
        sizes = tuple(sorted(set(int(s) for s in self.sizes)))
        if not sizes or sizes[0] < 1:
            raise ValueError(f"bucket sizes must be >= 1, got {self.sizes}")
        object.__setattr__(self, "sizes", sizes)
        if self.dtype not in SERVE_DTYPES:
            raise ValueError(f"unknown serve dtype {self.dtype!r}; "
                             f"known: {SERVE_DTYPES}")

    @property
    def max_size(self) -> int:
        return self.sizes[-1]

    def select(self, n: int) -> int | None:
        """Smallest bucket holding ``n`` items (None when ``n`` exceeds the
        largest bucket — the caller splits or rejects)."""
        if n < 1:
            raise ValueError(f"need n >= 1, got {n}")
        for size in self.sizes:
            if size >= n:
                return size
        return None

    def shed(self, n: int) -> int:
        """Largest bucket not exceeding ``n`` — the graceful-degradation
        choice: dispatch a full smaller bucket now instead of waiting to
        fill a bigger one."""
        if n < 1:
            raise ValueError(f"need n >= 1, got {n}")
        best = self.sizes[0]
        for size in self.sizes:
            if size <= n:
                best = size
        return best


def pad_batch(rows: Sequence[np.ndarray], bucket: int) -> np.ndarray:
    """Stack ``rows`` (identical shapes/dtypes) and zero-pad the batch axis
    up to ``bucket``. Rows beyond ``len(rows)`` are padding; the engine
    slices them off the output before completing futures."""
    if not rows:
        raise ValueError("empty batch")
    if len(rows) > bucket:
        raise ValueError(f"{len(rows)} rows do not fit bucket {bucket}")
    stacked = np.stack(rows)
    if len(rows) == bucket:
        return stacked
    pad = np.zeros((bucket - len(rows),) + stacked.shape[1:], stacked.dtype)
    return np.concatenate([stacked, pad])


def default_buckets(platform: str | None = None,
                    dtype: str = "float32") -> BucketTable:
    """The platform's declared bucket table at the given serving precision.
    ``platform`` defaults to the active JAX backend; resolving it lazily
    keeps this module importable without initializing a backend."""
    if platform is None:
        import jax
        platform = jax.default_backend()
    return BucketTable(TPU_BATCH_BUCKETS if platform == "tpu"
                       else DEFAULT_BATCH_BUCKETS, dtype=dtype)
