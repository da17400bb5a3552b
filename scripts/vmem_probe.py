"""Validate `_pick_hb`'s VMEM model against compiled reality (the budget,
32 MiB a cell under a limit the call states, and the per-head byte estimate
are checked on the chip only at the shapes of PERF.md's PR 33 entry — an
overestimate silently halves head batching, an underestimate would
OOM at exotic shapes).

Method: for each shipped (bn, seq, d) combination, force the heads-per-cell
value and ask Mosaic to COMPILE the forward and backward flash kernels.
Mosaic statically rejects kernels whose resident tiles exceed VMEM, so
"largest hb that compiles" is the hardware truth. We probe `_pick_hb`'s
choice (must compile), then one step larger (if that also compiles, the
model is conservative there). Prints one JSON line per probe:

    {"metric": "vmem_probe", "bn":..., "seq":..., "d":..., "hb":...,
     "which": "fwd"|"bwd", "chosen": bool, "ok": bool, "est_bytes": ...,
     "err": "..."}

Run on the TPU; off-TPU it exits non-zero — interpret mode has no VMEM to
validate.
"""

from __future__ import annotations

import json
import os
import sys
import time


def probe(bn: int, seq: int, d: int, budget_deadline: float) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from jimm_tpu.ops import flash_attention as fa

    rng = np.random.RandomState(0)
    # the public API takes (B, S, N, D); use N=bn heads with B=1 so the
    # flattened head-batch dim equals bn exactly
    q = jnp.asarray(rng.randn(1, seq, bn, d), jnp.bfloat16)
    k = jnp.asarray(rng.randn(1, seq, bn, d), jnp.bfloat16)
    v = jnp.asarray(rng.randn(1, seq, bn, d), jnp.bfloat16)

    # the REAL call path's block selection (incl. the ceil-to-128 cap) and
    # the REAL per-head formula — the probe must validate what ships
    *_, block_q, block_k, _ = fa._prologue(q, k, v, fa.DEFAULT_BLOCK_Q,
                                           fa.DEFAULT_BLOCK_K)
    chosen = fa._pick_hb(bn, block_q, block_k, d)
    est = fa._per_head_vmem_bytes(block_q, block_k, d)

    def compiles(which: str) -> tuple[bool, str]:
        try:
            if which == "fwd":
                fn = jax.jit(lambda a, b, c: fa.flash_attention(a, b, c))
            else:
                fn = jax.jit(jax.grad(
                    lambda a, b, c: fa.flash_attention(a, b, c)
                    .astype(jnp.float32).sum(), argnums=(0, 1, 2)))
            fn.lower(q, k, v).compile()
            return True, ""
        except Exception as e:  # noqa: BLE001 — Mosaic VMEM reject lands here
            return False, repr(e)[-400:]

    # probe the chosen hb and, if divisibility allows, one step larger
    candidates = [chosen]
    if bn % (chosen * 2) == 0:
        candidates.append(chosen * 2)
    orig = fa._pick_hb
    try:
        for hb in candidates:
            for which in ("fwd", "bwd"):
                if time.monotonic() > budget_deadline:
                    print(json.dumps({"metric": "vmem_probe",
                                      "note": "budget exhausted"}),
                          flush=True)
                    return
                # (a forced backward is the fused one, its dq resident on
                # top of the tiles, whatever the residency bound says)
                fa._pick_hb = lambda *a, _hb=hb, **kw: _hb
                ok, err = compiles(which)
                print(json.dumps({
                    "metric": "vmem_probe", "bn": bn, "seq": seq, "d": d,
                    "block_q": block_q, "block_k": block_k, "hb": hb,
                    "which": which, "chosen": hb == chosen, "ok": ok,
                    "est_bytes_per_head": est,
                    "est_cell_bytes": est * hb, "err": err,
                }), flush=True)
    finally:
        fa._pick_hb = orig


def main() -> int:
    import jimm_tpu.utils.env
    jimm_tpu.utils.env.configure_platform()
    import jax
    if jax.default_backend() != "tpu":
        print("vmem_probe needs a TPU: interpret mode has no VMEM to "
              "validate", file=sys.stderr)
        return 1
    budget = float(os.environ.get("VMEM_PROBE_BUDGET_S", "540"))
    deadline = time.monotonic() + budget
    # shipped shapes: ViT-B/16-256 towers (batch 128 x 12 heads, S=256 and
    # S=64 text), long-context ring chunks, and a d=128 exotic
    for bn, seq, d in [(1536, 256, 64), (1536, 64, 64),
                       (8, 8192, 64), (16, 2048, 64), (8, 2048, 128)]:
        probe(bn, seq, d, deadline)
    return 0


if __name__ == "__main__":
    sys.exit(main())
