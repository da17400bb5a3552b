"""Quickest proof that the program still starts on the chip.

    python chip_smoke.py               # one TPU chip: train phase + kernel phase
    python chip_smoke.py --multichip   # four chips: the sharded train path and
                                       # the one-device run it is compared with
    python chip_smoke.py --rehearse [--multichip]   # tiny size, any backend

One process, no network, nothing outside the checkout. Without ``--rehearse``
it needs a TPU and exits non-zero, printing no result, when JAX finds none.
The size of a rehearsal is chosen by that flag and never by the backend the
script happens to find, and the device it reports is the device it ran on.

Train phase: ``jimm_tpu.cli`` ``train`` — what ``python -m jimm_tpu train``
and ``jimm-tpu train`` call — on ``siglip-base-patch16-256`` at its published
widths, bf16, global batch 128, synthetic pairs, dense sigmoid loss, ten
steps. Every runtime flag is passed explicitly, so what ran is what is
printed. Checks: finite loss on every step, lower at the end than at the
start, and no compilation after the first step.

Kernel phase: every Pallas kernel family at the widths the larger presets
route through it (ViT-L/16-384 S=577, So400m/14-384 S=729 D=72, fused
LayerNorm at 768 and 1152, fp8 and int8 matmuls at ViT-B MLP widths),
forward and backward, against the plain reference; on the TPU each must have
lowered to a Mosaic custom call and ``impl="auto"`` must pick flash at S=577.
Each flash case prints the regime its calls took (``flash_calls_built``) and
must have built one single-tile forward and one fused backward.

Multichip phase: the same preset over four chips (mesh ``data=2,model=2``,
rules ``fsdp_tp``, ring sigmoid loss) against the same seed and batches on
ONE of those devices with the dense loss. Checks: per-step losses agree to
bf16 tolerance on identical batches, every device holds about a quarter of
the parameter and optimizer bytes, and the compiled step contains
collective-permute and all-gather / reduce-scatter.

Step times printed here are smoke readings around ``block_until_ready``, not
a benchmark. The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import gc
import json
import statistics
import sys
import time
import traceback
from importlib import metadata
from pathlib import Path

REPO = Path(__file__).resolve().parent
OUT = REPO / "chiprun_out" / "chip_smoke"
PRESET = "siglip-base-patch16-256"

#: every execution choice spelled out, the unroll among them, which
#: `cli.resolve_runtime` would otherwise set to the depth on the TPU
RUNTIME_FLAGS = ["--remat", "dots", "--attn-impl", "auto", "--ln-impl", "xla",
                 "--scan-unroll", "1", "--precision", "bf16"]

#: bf16 keeps 8 significant bits; the tests' bf16 bounds (2e-2 flash, 3e-2
#: LayerNorm) are for O(1) values and scale with the reference's magnitude
BF16_TOL = 2e-2


def say(**fields) -> None:
    print(json.dumps(fields), flush=True)


def train_argv(args, *extra: str) -> list[str]:
    argv = ["train", "--preset", PRESET, "--bf16",
            "--batch-size", "8" if args.rehearse else "128",
            "--seed", str(args.seed), "--log-every", "1",
            *RUNTIME_FLAGS, *extra]
    if args.rehearse:
        argv.append("--tiny")
    return argv


def late_compiles(rows: list[dict]) -> list[str]:
    """Functions the backend was asked for after the first step's row: a
    run's rows carry what each step compiled or loaded."""
    return [fun for row in rows[1:]
            for kind, fun, _, _ in row.get("compiles", ())
            if kind == "compile"]


def read_metrics(path: Path) -> list[dict]:
    return [json.loads(line) for line in path.read_text().splitlines()]


# ---------------------------------------------------------------------------
# Phase: the main path, one chip
# ---------------------------------------------------------------------------

def train_phase(args, watch) -> None:
    import jax
    import numpy as np

    from jimm_tpu import cli

    steps = 10
    metrics_path = OUT / "train_metrics.jsonl"
    metrics_path.unlink(missing_ok=True)
    argv = train_argv(args, "--loss", "siglip", "--steps", str(steps),
                      "--metrics-file", str(metrics_path))
    say(phase="train", argv=argv)
    rc = cli.main(argv)
    if rc != 0:
        raise AssertionError(f"jimm_tpu.cli train exited {rc}")
    rows = read_metrics(metrics_path)
    losses = [r["loss"] for r in rows]
    step_ms = [r["step_time_s"] * 1e3 for r in rows]
    late = late_compiles(rows)
    stats = jax.local_devices()[0].memory_stats() or {}
    say(phase="train", steps=len(rows), loss=losses,
        first_step_s=step_ms[0] / 1e3,
        steady_step_ms_median=statistics.median(step_ms[1:]),
        steady_step_ms=step_ms[1:],
        reading="smoke reading around block_until_ready, not a benchmark",
        compile_requests=watch.requests,
        compile_requests_after_first_step=late,
        peak_bytes_in_use=stats.get("peak_bytes_in_use", "not reported"),
        compile_cache=dict(watch.cache))
    if len(rows) != steps:
        raise AssertionError(f"{len(rows)} steps logged, expected {steps}")
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    if late:
        raise AssertionError(f"compiled after the first step: {late}")


# ---------------------------------------------------------------------------
# Phase: the kernels, forward and backward, against the plain reference
# ---------------------------------------------------------------------------

def _via_vjp(fn):
    """(inputs..., cotangent) -> (out, grads wrt every input)."""
    import jax

    def run(*inputs_and_cot):
        *inputs, cot = inputs_and_cot
        out, vjp = jax.vjp(fn, *inputs)
        return out, vjp(cot.astype(out.dtype))
    return run


def _f32(tree):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(
        lambda a: a.astype(jnp.float32)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)


def kernel_cases(args):
    """Yields (name, kernel, reference, inputs, tolerance). ``kernel`` and
    ``reference`` map ``inputs`` to ``(out, grads)``; the last input is the
    output cotangent."""
    import jax
    import jax.numpy as jnp

    from jimm_tpu.ops import fp8_matmul as f8
    from jimm_tpu.ops.attention import reference_attention
    from jimm_tpu.ops.flash_attention import (flash_attention,
                                              flash_attention_masked)
    from jimm_tpu.ops.int8_matmul import quantize_rows, quantized_linear
    from jimm_tpu.ops.layer_norm import layer_norm

    keys = iter(jax.random.split(jax.random.key(args.seed), 64))

    def normal(shape, dtype=jnp.bfloat16):
        return jax.random.normal(next(keys), shape, dtype)

    small = args.rehearse
    for b, s, n, d in ([(1, 72, 2, 64), (1, 40, 2, 72)] if small
                       else [(32, 577, 16, 64), (16, 729, 16, 72)]):
        shape = (b, s, n, d)
        yield (f"flash_attention {shape}", _via_vjp(flash_attention),
               _via_vjp(reference_attention),
               [normal(shape) for _ in range(4)], BF16_TOL)

    shape = (2, 72, 2, 64) if small else (32, 577, 16, 64)
    b, s = shape[:2]
    lengths = jax.random.randint(next(keys), (b,), s // 4, s + 1)
    mask = jnp.arange(s)[None, :] < lengths[:, None]
    yield (f"flash_attention_masked {shape} ragged",
           _via_vjp(lambda q, k, v: flash_attention_masked(q, k, v, mask)),
           _via_vjp(lambda q, k, v: reference_attention(
               q, k, v, mask=mask[:, None, None, :])),
           [normal(shape) for _ in range(4)], BF16_TOL)

    def ln_reference(x, scale, bias):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-6) * scale + bias

    for rows, feat in ([(40, 96)] if small
                       else [(32768, 768), (11664, 1152)]):
        yield (f"layer_norm ({rows}, {feat})",
               _via_vjp(lambda x, g, b: layer_norm(x, g, b, 1e-6)),
               _via_vjp(ln_reference),
               [normal((rows, feat)), 1 + 0.1 * normal((feat,)),
                normal((feat,)), normal((rows, feat))], 3e-2)

    m, k, n = (40, 96, 136) if small else (4096, 768, 3072)

    def dequant(x, scale, dtype):
        return f8.quantize_tensor(x, scale, dtype).astype(jnp.float32) * scale

    def fp8_reference(x, w, bias, dy):
        # replays the kernel's quantization decisions in plain XLA:
        # e4m3 forward, e5m2 dynamic-scaled cotangent, straight-through
        e4, e5 = jnp.float8_e4m3fn, jnp.float8_e5m2
        x_deq = dequant(x, f8.dynamic_scale(x, e4), e4)
        w_deq = dequant(w, f8.dynamic_scale(w, e4), e4)
        dy_deq = dequant(dy, f8.dynamic_scale(dy, e5), e5)
        return (x_deq @ w_deq + bias,
                (dy_deq @ w_deq.T, x_deq.T @ dy_deq, jnp.sum(dy, axis=0)))

    # bf16 operands as the fp8_hybrid train path feeds them; the kernel's
    # output is f32, so the cotangent is too
    yield (f"fp8_matmul ({m}, {k})@({k}, {n})", _via_vjp(f8.fp8_matmul),
           fp8_reference,
           [normal((m, k)), normal((k, n)), normal((n,)),
            normal((m, n), jnp.float32)], BF16_TOL)

    w = normal((k, n), jnp.float32)
    w_scale = jnp.max(jnp.abs(w), axis=0) / 127.0
    w_q = jnp.clip(jnp.round(w / w_scale), -127, 127).astype(jnp.int8)

    def int8_reference(x, bias, dy):
        x_q, x_scale = quantize_rows(x)
        w_deq = w_q.astype(jnp.float32) * w_scale
        return ((x_q.astype(jnp.float32) * x_scale[:, None]) @ w_deq + bias,
                (dy @ w_deq.T, jnp.sum(dy, axis=0)))

    yield (f"quantized_linear ({m}, {k})@({k}, {n})",
           _via_vjp(lambda x, bias: quantized_linear(x, w_q, w_scale, bias)),
           int8_reference,
           [normal((m, k)), normal((n,)), normal((m, n), jnp.float32)],
           BF16_TOL)


def kernel_phase(args, watch) -> None:
    import jax
    import jax.numpy as jnp

    from jimm_tpu import tune
    from jimm_tpu.obs.registry import snapshot
    from jimm_tpu.ops.attention import dot_product_attention

    on_tpu = jax.default_backend() == "tpu"
    # block sizes come from the tune cache at trace time: keep the lookup
    # inside the checkout
    tune.configure(REPO / ".tune_cache")
    failed = []

    def max_err(got, want):
        pairs = zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True)
        err = scale = 0.0
        for g, w in pairs:
            g, w = g.astype(jnp.float32), w.astype(jnp.float32)
            if not bool(jnp.all(jnp.isfinite(g))):
                return float("inf"), float(jnp.max(jnp.abs(w)))
            err = max(err, float(jnp.max(jnp.abs(g - w))
                                 / jnp.maximum(1.0, jnp.max(jnp.abs(w)))))
            scale = max(scale, float(jnp.max(jnp.abs(w))))
        return err, scale

    def flash_calls_built(before):
        """The flash family counts each pallas_call it builds by regime:
        e.g. {"single_tile": 2} is one forward and one fused backward."""
        return {k[len("jimm_flash_"):-len("_total")]: int(v - before.get(k, 0))
                for k, v in snapshot().items()
                if k.startswith("jimm_flash_") and v != before.get(k, 0)}

    for name, kernel, reference, inputs, tol in kernel_cases(args):
        try:
            before = snapshot()
            compiled = jax.jit(kernel).lower(*inputs).compile()  # jaxlint: disable=JL008 a different function each pass, jitted once
            regime = flash_calls_built(before)
            mosaic = "tpu_custom_call" in compiled.as_text()
            out, grads = compiled(*inputs)
            with jax.default_matmul_precision("highest"):
                ref_out, ref_grads = jax.jit(reference)(*_f32(inputs))  # jaxlint: disable=JL008 as above
            fwd_err, _ = max_err(out, ref_out)
            bwd_err, ref_scale = max_err(grads, ref_grads)
            ok = fwd_err <= tol and bwd_err <= tol and (mosaic or not on_tpu)
            if name.startswith("flash_attention"):
                # both preset lengths are under the single-tile rule: one
                # forward and ONE backward kernel, no tiled dq + dk/dv pair
                ok = ok and regime == {"single_tile": 2, "direct": 2}
            say(phase="kernels", case=name, ok=ok,
                lowering="mosaic" if mosaic else "interpreter",
                **({"flash_calls_built": regime} if regime else {}),
                fwd_max_err=fwd_err, bwd_max_err=bwd_err, tolerance=tol,
                err_unit="max abs error over max(1, max|reference|)",
                max_abs_reference_grad=ref_scale)
        except Exception:  # noqa: BLE001 — recorded, fails the phase below
            traceback.print_exc()
            say(phase="kernels", case=name, ok=False, error="raised")
            ok = False
        if not ok:
            failed.append(name)
        del inputs
        gc.collect()

    if on_tpu:
        # the dispatch rule itself: at ViT-L/16-384's S=577 "auto" must
        # route to the flash kernel, not to XLA attention
        spec = jax.ShapeDtypeStruct((32, 577, 16, 64), jnp.bfloat16)
        text = jax.jit(lambda q, k, v: dot_product_attention(
            q, k, v, impl="auto")).lower(spec, spec, spec).compile().as_text()
        picked = "tpu_custom_call" in text
        say(phase="kernels", case='impl="auto" at S=577 picks flash',
            ok=picked)
        if not picked:
            failed.append("auto dispatch")
    if failed:
        raise AssertionError(f"kernel cases failed: {failed}")


# ---------------------------------------------------------------------------
# Phase: four chips against one
# ---------------------------------------------------------------------------

def state_bytes_by_device(*modules) -> tuple[dict[str, int], int]:
    """Bytes of ``modules``' state resident on each device, and the bytes
    of one whole copy."""
    import jax
    from flax import nnx
    held: dict[str, int] = {}
    whole = 0
    for leaf in jax.tree.leaves([nnx.state(m) for m in modules]):
        whole += leaf.nbytes
        for shard in leaf.addressable_shards:
            key = str(shard.device)
            held[key] = held.get(key, 0) + shard.data.nbytes
    return held, whole


def multichip_phase(args, watch) -> None:
    import jax
    import numpy as np

    from jimm_tpu import cli
    from jimm_tpu.parallel import use_sharding

    parser = cli.build_parser()

    def run(tag: str, *extra: str):
        path = OUT / f"multichip_{tag}.jsonl"
        path.unlink(missing_ok=True)
        argv = train_argv(args, "--steps", "3", "--batch-fingerprint",
                          "--metrics-file", str(path), *extra)
        say(phase="multichip", run=tag, argv=argv)
        return cli.train(parser.parse_args(argv)), read_metrics(path)

    sharded, rows4 = run("four_chips", "--mesh", "data=2,model=2",
                         "--max-devices", "4", "--rules", "fsdp_tp",
                         "--loss", "siglip_ring")
    late = late_compiles(rows4)
    held, whole = state_bytes_by_device(sharded.model, sharded.optimizer)
    shares = {dev: n / whole for dev, n in sorted(held.items())}
    with use_sharding(sharded.mesh, sharded.rules):
        text = sharded.step_fn.lower(sharded.model, sharded.optimizer,
                                     *sharded.batch).compile().as_text()
    collectives = {op: text.count(f" {op}(") + text.count(f" {op}-start(")
                   for op in ("collective-permute", "all-gather",
                              "reduce-scatter", "all-reduce")}
    say(phase="multichip", state_bytes_one_copy=whole,
        state_share_by_device=shares, collectives_in_step=collectives,
        first_step_s=rows4[0]["step_time_s"],
        step_ms=[r["step_time_s"] * 1e3 for r in rows4[1:]],
        compile_requests_after_first_step=late,
        reading="smoke reading, not a benchmark")
    del sharded
    gc.collect()

    _, rows1 = run("one_chip", "--mesh", "data=1", "--max-devices", "1",
                   "--loss", "siglip")
    loss4 = [r["loss"] for r in rows4]
    loss1 = [r["loss"] for r in rows1]
    same_batches = ([r["batch_fingerprint"] for r in rows4]
                    == [r["batch_fingerprint"] for r in rows1])
    say(phase="multichip", loss_four_chips=loss4, loss_one_chip=loss1,
        same_batches=same_batches, tolerance=BF16_TOL)

    if not same_batches:
        raise AssertionError("the two runs did not see the same batches")
    if not np.all(np.isfinite(loss4 + loss1)):
        raise AssertionError(f"non-finite loss: {loss4} {loss1}")
    if not np.allclose(loss4, loss1, rtol=BF16_TOL, atol=BF16_TOL):
        raise AssertionError(f"losses disagree: {loss4} vs {loss1}")
    if len(shares) != 4 or max(shares.values()) > 0.3:
        raise AssertionError(
            f"state is not spread a quarter per device: {shares}")
    if not (collectives["collective-permute"]
            and (collectives["all-gather"] or collectives["reduce-scatter"])):
        raise AssertionError(f"collectives missing from the step: "
                             f"{collectives}")


# ---------------------------------------------------------------------------

def enable_cache() -> str:
    from jimm_tpu.aot.export import enable_persistent_cache
    return enable_persistent_cache()


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--multichip", action="store_true",
                   help="run the four-chip path and its one-device "
                        "comparison, and no other phase")
    p.add_argument("--rehearse", action="store_true",
                   help="tiny sizes on whatever backend is there (CPU "
                        "rehearsal of the script itself)")
    p.add_argument("--seed", type=int, default=0,
                   help="seeds the weights, the batches and the kernel "
                        "inputs")
    args = p.parse_args(argv)

    import jax
    if args.rehearse and args.multichip:
        # four virtual devices, should the rehearsal land on the CPU
        jax.config.update("jax_num_cpu_devices", 4)
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    need = 4 if args.multichip else 1
    if not args.rehearse and (device["platform"] != "tpu"
                              or len(devices) < need):
        print(f"chip_smoke.py needs {need} TPU chip(s); JAX found {device}. "
              f"Nothing ran. (--rehearse runs the script at a tiny size on "
              f"any backend.)", file=sys.stderr)
        return 2

    OUT.mkdir(parents=True, exist_ok=True)
    cache_dir = enable_cache()
    from jimm_tpu.data.preprocess import native_available
    say(phase="setup", jax=jax.__version__,
        jaxlib=metadata.version("jaxlib"), libtpu=metadata.version("libtpu"),
        flax=metadata.version("flax"), device=device,
        size="rehearsal (tiny)" if args.rehearse else "full width",
        seed=args.seed, compile_cache_dir=cache_dir,
        native_preprocess_lib=(
            f"{'built' if native_available() else 'not built'}; the "
            f"synthetic-pairs path preprocesses nothing"))

    # counts over the whole script, the kernels' compiles too. Each
    # `cli.train` opens its own for its rows and the `jimm_train` registry:
    # this one counts into a registry of its own, or both would count there
    from jimm_tpu.obs import CompileWatch, MetricRegistry
    watch = CompileWatch(MetricRegistry("chip_smoke")).listen()
    phases = ([("multichip", multichip_phase)] if args.multichip
              else [("train", train_phase), ("kernels", kernel_phase)])
    failed = []
    try:
        for name, phase in phases:
            t0 = time.perf_counter()
            try:
                phase(args, watch)
            except Exception:  # noqa: BLE001 — reported; the script fails
                traceback.print_exc()
                failed.append(name)
            say(phase=name, passed=name not in failed,
                seconds=time.perf_counter() - t0,
                compile_cache_so_far=dict(watch.cache))
            gc.collect()
    finally:
        watch.close()
    if failed:
        print(json.dumps({"ok": False, "failed": failed, "device": device}),
              flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
