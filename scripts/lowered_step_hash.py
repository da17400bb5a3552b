"""sha256 of the lowered train step (StableHLO for a described v5e chip, no
chip needed) of every cell in a checkout's ``BENCHMARK.json``: what a PR that
must leave the accepted cells' programs alone compares between the parent
commit and its own tree (PERF.md section 7, the rule drawn from PR 31).

    JAX_PLATFORMS=cpu python scripts/lowered_step_hash.py <checkout> [cell ...]

Normalised before hashing: the checkout's path, the counters the process
appends to function symbols (``@closed_call_717``: they move with whatever was
traced before), and the Mosaic kernels' serialized bodies (they carry source
line numbers; ``tpu_custom_call`` and the count of bodies cut are printed
beside the hash). Tracing only: nothing is compiled or run, so a full-size
step costs seconds and little memory. The text goes to
``<checkout>/.bench_runs/lowered_<cell>.txt`` for a diff when two hashes
differ. A cell whose preset the checkout lacks is skipped."""
import hashlib, json, os, re, sys
root = os.path.abspath(sys.argv[1])
sys.path.insert(0, root)
os.environ.setdefault("TPU_LOG_DIR", "disabled")
import jax, jax.numpy as jnp
from flax import nnx
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
jax.config.update("jax_enable_compilation_cache", False)
import jimm_tpu
assert os.path.abspath(jimm_tpu.__file__).startswith(root), jimm_tpu.__file__
from jimm_tpu import cli, preset
from jimm_tpu.configs import with_runtime
from jimm_tpu.ops import attention, delta_rule, flash_attention as fa, ssd
from jimm_tpu.train import (OptimizerConfig, make_classifier_train_step, make_contrastive_train_step,
                            make_optimizer)
from jimm_tpu.train.trainer import make_lm_train_step
fa._interpret = delta_rule._interpret = ssd._interpret = lambda: False
attention._default_backend = delta_rule._default_backend = ssd._default_backend = lambda: "tpu"
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one = SingleDeviceSharding(topo.devices[0])
manifest = json.load(open(os.path.join(root, "BENCHMARK.json")))
wanted = sys.argv[2:]
for w in manifest["workloads"]:
    if wanted and w["name"] not in wanted:
        continue
    cell = json.load(open(os.path.join(root, "benchmarks/workloads", w["name"] + ".json")))
    config = json.load(open(os.path.join(root, "benchmarks/configs", w["config"] + ".json")))
    t = cell["traffic_params"]
    argv = ["train", "--preset", config["preset"], "--batch-size", str(t["batch_size"]), "--steps", "30", *t["cli_args"]]
    if "num_layers" in config:
        argv += ["--num-layers", str(config["num_layers"]), "--seq-len", str(t["seq_len"])]
    args = cli.build_parser().parse_args(argv)
    try:
        fam = cli._family(args.preset)
        cfg = preset(args.preset)
    except (KeyError, SystemExit):
        print(w["name"], "skipped: no preset", args.preset, flush=True)
        continue
    if args.num_layers:
        cfg = cli._replace_towers(cfg, depth=args.num_layers, seq_len=args.seq_len)
    if getattr(args, "num_classes", None) and hasattr(cfg, "num_classes"):
        import dataclasses
        cfg = dataclasses.replace(cfg, num_classes=args.num_classes)
    cfg = cli._replace_towers(cfg, **cli.resolve_runtime(args, cfg, None, "tpu"))
    cls = cli._model_cls(fam)

    def build():
        model = cls(cfg, rngs=nnx.Rngs(0), dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
        return model, make_optimizer(model, OptimizerConfig(total_steps=30))

    model, optimizer = nnx.eval_shape(build)
    b = t["batch_size"]
    if fam in cli.LM_FAMILIES:
        step = make_lm_train_step(fam)
        batch = (jax.ShapeDtypeStruct((b, cfg.decoder.seq_len + 1), jnp.int32, sharding=one),)
    elif fam == "vit":
        step = make_classifier_train_step()
        v = cfg.vision
        batch = (jax.ShapeDtypeStruct((b, v.image_size, v.image_size, 3), jnp.float32, sharding=one),
                 jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one))
    else:
        step = make_contrastive_train_step("siglip")
        v = cfg.vision
        batch = (jax.ShapeDtypeStruct((b, v.image_size, v.image_size, 3), jnp.float32, sharding=one),
                 jax.ShapeDtypeStruct((b, cfg.text.context_length), jnp.int32, sharding=one))
    place = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one) if hasattr(a, "shape") else a, tree)
    gm, sm = nnx.split(model); go, so = nnx.split(optimizer)
    model, optimizer = nnx.merge(gm, place(sm)), nnx.merge(go, place(so))
    text = step.lower(model, optimizer, *batch).as_text()
    text = text.replace(root, "<checkout>")
    text = re.sub(r"@([A-Za-z_.]+?)_\d+\b", r"@\1_N", text)  # symbol counters of the process
    bodies = len(re.findall(r'backend_config = "\{[^\n]*', text))
    text = re.sub(r'backend_config = "\{[^\n]*', 'backend_config = <kernel>', text)
    print(w["name"], hashlib.sha256(text.encode()).hexdigest()[:16], "lines", text.count("\n"),
          "tpu_custom_call", text.count("tpu_custom_call"), "kernel bodies cut", bodies, flush=True)
    out = os.path.join(root, ".bench_runs")
    os.makedirs(out, exist_ok=True)
    open(os.path.join(out, f"lowered_{w['name']}.txt"), "w").write(text)
