"""Measured ceilings of one chip on the plainest programs: a chain of 4096^3
bfloat16 matmuls and an elementwise pass over 2 GiB. Prints one JSON line;
the numbers go into ``benchmarks/peaks.json`` beside the published peaks
(utilizations stay against the published ones).

    python3 benchmarks/tools/ceilings.py        # needs a TPU; exits 2 without
"""

from __future__ import annotations

import json
import sys
import time


def main() -> int:
    import jax
    import jax.numpy as jnp
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"ceilings.py needs a TPU; JAX found {dev.platform}. Nothing "
              f"ran.", file=sys.stderr)
        return 2

    n, chain = 4096, 64

    @jax.jit
    def matmuls(a, b):
        def body(_, x):
            return (x @ b).astype(jnp.bfloat16)
        return jax.lax.fori_loop(0, chain, body, a)

    ka, kb = jax.random.split(jax.random.key(0))
    a = jax.random.normal(ka, (n, n), jnp.bfloat16)
    b = jax.random.normal(kb, (n, n), jnp.bfloat16) / 64.0
    matmuls(a, b).block_until_ready()
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        matmuls(a, b).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    tflops = 2.0 * n ** 3 * chain / best / 1e12

    elems = (2 << 30) // 2  # 2 GiB of bfloat16
    passes = 8

    @jax.jit
    def elementwise(x):
        def body(_, y):
            return y * jnp.bfloat16(1.0009765625) + jnp.bfloat16(0.5)
        return jax.lax.fori_loop(0, passes, body, x)

    x = jnp.ones((elems // 1024, 1024), jnp.bfloat16)
    elementwise(x).block_until_ready()
    best_e = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        elementwise(x).block_until_ready()
        best_e = min(best_e, time.perf_counter() - t0)
    # each pass reads 2 GiB and writes 2 GiB
    gbps = 2.0 * (2 << 30) * passes / best_e / 1e9

    print(json.dumps({
        "device_kind": dev.device_kind,
        "matmul_4096_bf16_tflops": tflops, "matmul_chain": chain,
        "matmul_best_s": best,
        "elementwise_2gib_gbps": gbps, "elementwise_passes": passes,
        "elementwise_best_s": best_e,
        "memory_stats": dev.memory_stats(),
        "timing": "host clock around block_until_ready, best of 5"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
