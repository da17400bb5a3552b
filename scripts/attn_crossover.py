"""Find the flash-vs-XLA attention crossover sequence length on this chip.

Times fwd+bwd at fixed B*N*S (constant work per config would need B to
shrink as S grows; we instead keep total tokens constant) and prints TF/s,
informing the `impl="auto"` dispatch rule in `jimm_tpu.ops.attention`.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def timeit(fn, *args, iters=10):
    def chained(args, n):
        def body(args, _):
            out = fn(*args)
            q = args[0] + 1e-6 * out[0].astype(args[0].dtype)
            return (q,) + tuple(args[1:]), None
        args, _ = jax.lax.scan(body, args, None, length=n)
        return args

    chained = jax.jit(chained, static_argnums=1)
    float(jnp.sum(chained(args, iters)[0].astype(jnp.float32)))
    t0 = time.perf_counter()
    float(jnp.sum(chained(args, iters)[0].astype(jnp.float32)))
    return (time.perf_counter() - t0) / iters


def main():
    import argparse
    from functools import partial

    from jimm_tpu.ops.flash_attention import flash_attention

    p = argparse.ArgumentParser()
    p.add_argument("--causal", action="store_true",
                   help="also time causal flash: with skipped kv blocks "
                        "eliding both compute AND their DMA, causal should "
                        "approach half the non-causal time at long seq")
    args = p.parse_args()

    print("backend:", jax.default_backend(), jax.devices()[0].device_kind)
    rng = np.random.RandomState(0)
    N, D = 12, 64
    total_tokens = 128 * 256  # constant B*S
    for S in (64, 128, 256, 512, 1024, 2048, 4096, 8192):
        B = max(1, total_tokens // S)
        q = jnp.asarray(rng.randn(B, S, N, D), jnp.bfloat16)
        k = jnp.asarray(rng.randn(B, S, N, D), jnp.bfloat16)
        v = jnp.asarray(rng.randn(B, S, N, D), jnp.bfloat16)
        flops = 3.5 * 4 * B * N * S * S * D

        def loss_of(attn):
            def f(q, k, v):
                return jnp.sum(attn(q, k, v).astype(jnp.float32))
            return jax.jit(jax.grad(f, argnums=(0, 1, 2)))

        tf = timeit(loss_of(flash_attention), q, k, v)
        tx = timeit(loss_of(
            lambda q, k, v: jax.nn.dot_product_attention(q, k, v)), q, k, v)
        win = "flash" if tf < tx else "xla"
        causal_col = ""
        if args.causal:
            tc = timeit(loss_of(partial(flash_attention, is_causal=True)),
                        q, k, v)
            causal_col = (f"  causal {tc*1e3:8.2f} ms "
                          f"({tc/tf:4.2f}x of full)")
        print(f"  S={S:5d} B={B:4d}: flash {tf*1e3:8.2f} ms "
              f"({flops/tf/1e12:6.2f} TF/s)  xla {tx*1e3:8.2f} ms "
              f"({flops/tx/1e12:6.2f} TF/s)  -> {win}{causal_col}")


if __name__ == "__main__":
    main()
