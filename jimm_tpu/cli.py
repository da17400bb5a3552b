"""Command-line interface (SURVEY §5 config row: the reference has no CLI or
flag system at all — hyperparameters live in module constants,
ref `examples/vit_training.py:18-29`).

Subcommands::

    python -m jimm_tpu presets                      # list named model presets
    python -m jimm_tpu train --preset ... --steps N # training (synthetic or --data)
    python -m jimm_tpu classify IMG --ckpt ...      # zero-shot classification
    python -m jimm_tpu evaluate --data ...          # accuracy / retrieval metrics
    python -m jimm_tpu prepare-data SRC OUT         # raw images -> tfrecord shards
    python -m jimm_tpu export SRC OUT               # HF checkpoint -> safetensors dir
    python -m jimm_tpu export-run OUT --ckpt-dir D  # training run -> HF safetensors
    python -m jimm_tpu inspect FILE.safetensors     # tensor names/shapes/dtypes
    python -m jimm_tpu bench-forward --preset ...   # jitted forward throughput
    python -m jimm_tpu profile-analyze DIR          # per-op trace summary
    python -m jimm_tpu build-native                 # compile the C++ preprocessing lib
    python -m jimm_tpu obs snapshot URL|FILE        # print/save a unified metric dump
    python -m jimm_tpu obs tail URL|JSONL           # follow metrics live
    python -m jimm_tpu obs diff BEFORE AFTER        # structural metric diff

`train` runs entirely offline on procedural data (`jimm_tpu.data.synthetic`)
so it works with zero network on CPU or TPU, and exercises the real stack:
mesh + sharding rules, jitted step, checkpoint/resume, metrics JSONL.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import sys
import types
from typing import Any


def _configure_backend(args: argparse.Namespace) -> None:
    import os

    import jimm_tpu.utils.env as env
    env.configure_platform(platform=getattr(args, "platform", None),
                           host_devices=getattr(args, "host_devices", None))
    # join the cluster before any backend use when (a) running under
    # `python -m jimm_tpu.launch` (or a hand-exported process group), or
    # (b) the environment looks like a multi-host TPU pod — skipping init
    # there would silently train an independent copy per host. The pod
    # path uses jax's argless auto-detect (metadata server), whose failure
    # mode on a NON-pod TPU host is a hang — so markers that single-host
    # environments also set must not trigger it:
    # TPU_WORKER_HOSTNAMES counts only with >1 hosts (single-host VMs set it
    # to one name), TPU_WORKER_ID alone never counts, and an explicit
    # non-TPU --platform skips cluster join entirely.
    if os.environ.get("JIMM_NUM_PROCESSES"):
        # explicit opt-in (launcher or hand-exported group): always honored,
        # on any platform — this path never touches the TPU metadata server
        from jimm_tpu.parallel import initialize_distributed
        initialize_distributed()
        return
    if getattr(args, "platform", None) not in (None, "tpu"):
        return  # explicit non-TPU platform: never probe the TPU runtime
    hostnames = [h for h in
                 os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",") if h]
    pod_markers = ("CLOUD_TPU_TASK_ID", "MEGASCALE_COORDINATOR_ADDRESS")
    if any(m in os.environ for m in pod_markers) or len(hostnames) > 1:
        from jimm_tpu.parallel import initialize_distributed
        initialize_distributed()


def _configure_journal(args: argparse.Namespace) -> None:
    """Point the process-wide flight-recorder journal at ``--journal PATH``
    (no flag: in-memory ring only, or the JIMM_JOURNAL env default)."""
    if getattr(args, "journal", None):
        from jimm_tpu.obs.journal import configure_journal
        configure_journal(args.journal)


def _parse_mesh(spec: str | None, max_devices: int | None = None):
    """``"data=4,model=2"`` -> Mesh (None -> no mesh: replicated 1-device).

    ``max_devices`` restricts the mesh to the first N visible devices —
    the elastic-restart path: a shrunk attempt plans its mesh over the
    surviving subset while the process still sees the full virtual device
    list (``make_mesh`` requires the axis product to equal the device
    count, so the subset must be explicit)."""
    if not spec:
        return None
    from jimm_tpu.parallel import make_mesh
    axes = {}
    for part in spec.split(","):
        name, _, size = part.partition("=")
        axes[name.strip()] = int(size)
    devices = None
    if max_devices is not None:
        import jax
        visible = jax.devices()
        if not 1 <= max_devices <= len(visible):
            raise SystemExit(f"--max-devices {max_devices} out of range "
                             f"(1..{len(visible)} visible)")
        devices = visible[:max_devices]
    return make_mesh(axes, devices=devices)


#: model family (the prefix of its presets' names) -> its class in `jimm_tpu`
_FAMILIES = {"vit": "VisionTransformer", "clip": "CLIP", "siglip": "SigLIP",
             "ouro": "Ouro", "kanana": "Kanana", "trinity": "Trinity",
             "kimi": "KimiLinear", "granite": "Granite"}


def _lm_counters(cfg: Any, batch_size: int) -> list[tuple[str, str, Any]]:
    """A language model's per-step registry counters, ``(registry, counter,
    amount)``: ``amount`` is a number, or the name of the step's metric to
    add."""
    d = cfg.decoder
    counters = [("jimm_lm", "tokens_total", batch_size * d.seq_len)]
    if hasattr(d, "loops"):
        # block applications (passes x layers), the unit a looped model's
        # cost is counted in
        counters.append(("jimm_loop", "block_applications_total",
                         d.loops * d.depth))
    if getattr(d, "moe", None) is not None:
        counters += [
            ("jimm_moe", "assignments_total", batch_size * d.seq_len
             * d.moe.top_k * (d.depth - d.dense_layers)),
            ("jimm_moe", "held_assignments_total", "moe_held_rows")]
    return counters


#: the language-model families: trained from --seed on the program's own
#: token generator by `make_lm_train_step(<family>)`, each with its optimizer
#: defaults of `train` (--lr, --warmup-steps) where the loop's own do not do.
#: The looped decoder's exit gates saturate within 20 Adam steps at 1e-3 with
#: no warm-up, and three of its four passes then train on no gradient
#: (docs/models/Ouro.md); the sparse decoder starts as gently (no reading at
#: 1e-3: 20 Adam steps of that size move a 2048-wide decoder's every weight
#: by about its own spread, routers included)
LM_FAMILIES = {"ouro": {"lr": 1e-4, "warmup_steps": 20},
               "kanana": {"lr": 1e-4, "warmup_steps": 20},
               "trinity": {"lr": 1e-4, "warmup_steps": 20},
               "kimi": {"lr": 1e-4, "warmup_steps": 20},
               "granite": {"lr": 1e-4, "warmup_steps": 20}}


def _family(preset_name: str) -> str:
    for fam in _FAMILIES:
        if preset_name.startswith(fam):
            return fam
    raise SystemExit(f"cannot infer model family from preset {preset_name!r}")


def _model_cls(fam: str):
    import jimm_tpu
    return getattr(jimm_tpu, _FAMILIES[fam])


def _replace_towers(cfg: Any, **fields: Any) -> Any:
    """dataclasses.replace the same fields in every tower config the model
    has: vision (and text), or the language model's decoder."""
    for tower in ("vision", "text", "decoder"):
        if not hasattr(cfg, tower):
            continue
        try:
            cfg = dataclasses.replace(cfg, **{tower: dataclasses.replace(
                getattr(cfg, tower), **fields)})
        except TypeError as e:
            raise SystemExit(f"the {tower} stack does not take this: {e}")
    return cfg


def _main_tower(cfg: Any) -> Any:
    """The tower the loop's set-up reads depth and precision from."""
    return cfg.decoder if hasattr(cfg, "decoder") else cfg.vision


# optimizer defaults of `train` (--lr, --warmup-steps); `LM_FAMILIES` carry
# their own
_OPTIMIZER_DEFAULTS = {"lr": 1e-3, "warmup_steps": 0}


def _norm_for(fam: str) -> dict:
    """Family-correct file-pipeline normalization (HF processor
    conventions): CLIP's mean/std; ViT/SigLIP use the 0.5 defaults. Shared
    by train and evaluate so both see the same pixels."""
    if fam == "clip":
        from jimm_tpu.data.preprocess import CLIP_MEAN, CLIP_STD
        return {"mean": CLIP_MEAN, "std": CLIP_STD}
    return {}


def _is_tar_data(data: str) -> bool:
    """Route --data to the webdataset loader when it names tar shards
    (compressed .tar.gz/.tar.zst included)."""
    from pathlib import Path
    p = Path(data)
    if p.is_dir():
        return (not any(p.glob("*.tfrecord*"))) and any(p.glob("*.tar*"))
    return ".tar" in p.name


def _dataset_classes(data: str) -> list[str] | None:
    """Ordered class names from the classes.json prepare-data writes next
    to the shards (index == label id) — resolved by the container's own
    path rules (tfrecord or tar), so every --data form (dir, glob, file)
    works for both formats."""
    import json
    from pathlib import Path

    if _is_tar_data(data):
        from jimm_tpu.data.webdataset import resolve_tar_paths as resolve
    else:
        from jimm_tpu.data.records import resolve_paths as resolve
    try:
        cj = Path(resolve(data)[0]).parent / "classes.json"
    except FileNotFoundError:
        return None  # the loader itself will raise with the right message
    if cj.is_file():
        return list(json.loads(cj.read_text()))
    return None


def _num_classes_from_data(data: str) -> int | None:
    classes = _dataset_classes(data)
    if classes is not None:
        print(f"num_classes={len(classes)} from classes.json")
        return len(classes)
    return None


def _swap_classifier(model, n_target: int, *, dtype, seed: int,
                     mesh=None, rules=None) -> None:
    """Replace a ViT's classification head with a fresh ``n_target``-wide
    zero-init Linear (the standard fine-tune head swap). Shared by train
    and evaluate so both rebuild the same architecture around an orbax
    checkpoint."""
    import dataclasses as _dc

    from flax import nnx

    from jimm_tpu.parallel.sharding import logical, shard_model
    cfg = model.config
    model.classifier = nnx.Linear(
        cfg.vision.width, n_target, dtype=dtype, param_dtype=dtype,
        kernel_init=logical(nnx.initializers.zeros_init(),
                            "embed", "classes"),
        bias_init=logical(nnx.initializers.zeros_init(), "classes"),
        rngs=nnx.Rngs(seed))
    model.config = _dc.replace(cfg, num_classes=n_target,
                               do_classification=True)
    if mesh is not None:
        shard_model(model, mesh, rules)


def _fit_head(model, n: int | None, *, dtype, seed: int = 0,
              mesh=None, rules=None) -> None:
    """Make a loaded ViT's classifier match the task: swap in a fresh
    ``n``-wide head when the count differs (or the checkpoint is headless),
    error when headless with no count known. One decision shared by train,
    evaluate, and export-run — they must rebuild identical architectures."""
    cfg = model.config
    if n and (not cfg.do_classification or n != cfg.num_classes):
        _swap_classifier(model, n, dtype=dtype, seed=seed, mesh=mesh,
                         rules=rules)
        print(f"fresh classifier head: {n} classes")
    elif not cfg.do_classification:
        raise SystemExit("checkpoint has no classifier head; pass "
                         "--num-classes (or put classes.json next to "
                         "--data)")


def _restore_run(args: argparse.Namespace):
    """Rebuild the architecture a training run used (--preset [+--tiny] or
    --from-pretrained [+--image-size], with the vit head swap) and restore
    its orbax checkpoint over it. Shared by `evaluate` and `export-run` —
    they must reconstruct the exact same model to load the weights."""
    import jax.numpy as jnp
    from flax import nnx

    from jimm_tpu import preset

    fam = _family(args.preset)
    dtype = jnp.bfloat16 if args.bf16 else jnp.float32
    data = getattr(args, "data", None)
    n = (args.num_classes or (_num_classes_from_data(data) if data else None)
         if fam == "vit" else None)
    if args.from_pretrained:
        if args.tiny:
            raise SystemExit("--tiny conflicts with --from-pretrained "
                             "(the checkpoint defines the architecture)")
        # the training run was `train --from-pretrained X`: rebuild the
        # same architecture (incl. head swap) before restoring over it
        model = _model_cls(fam).from_pretrained(
            args.from_pretrained, dtype=dtype, image_size=args.image_size)
        if fam == "vit":
            _fit_head(model, n, dtype=dtype)
    else:
        cfg = preset(args.preset)
        if args.tiny:
            cfg = _tiny_override(cfg)
        if n:
            # must match the classifier head the training run used
            cfg = dataclasses.replace(cfg, num_classes=n)
        model = _model_cls(fam)(cfg, rngs=nnx.Rngs(0), dtype=dtype,
                                param_dtype=dtype)
    from jimm_tpu.train import CheckpointManager
    step = CheckpointManager(args.ckpt_dir).restore(model)
    print(f"restored step {step} from {args.ckpt_dir}")
    return fam, model


def _tiny_override(cfg: Any) -> Any:
    """Shrink any preset to CPU-demo size, keeping its architecture class."""
    from jimm_tpu.configs import (CLIPConfig, GraniteConfig, KananaConfig,
                                  KDAConfig, KimiLinearConfig, Mamba2Config,
                                  MLAConfig, OuroConfig, SigLIPConfig,
                                  TrinityConfig, ViTConfig)

    # depth 4 (not 2) so tiny runs can still exercise pipeline stages x
    # virtual-chunk splits (depth % (stages * virtual) == 0 for 2x2)
    def shrink_vision(v):
        return dataclasses.replace(v, image_size=32, patch_size=16, width=64,
                                   depth=4, num_heads=2, mlp_dim=128)

    def shrink_text(t):
        return dataclasses.replace(t, vocab_size=64, context_length=8,
                                   width=64, depth=4, num_heads=2, mlp_dim=128)

    if isinstance(cfg, ViTConfig):
        return dataclasses.replace(cfg, vision=shrink_vision(cfg.vision))
    if isinstance(cfg, (CLIPConfig, SigLIPConfig)):
        return dataclasses.replace(cfg, vision=shrink_vision(cfg.vision),
                                   text=shrink_text(cfg.text),
                                   projection_dim=64)
    if isinstance(cfg, OuroConfig):
        # the passes stay: a tiny looped model is still looped
        return dataclasses.replace(cfg, decoder=dataclasses.replace(
            cfg.decoder, vocab_size=512, seq_len=32, width=64, depth=2,
            num_heads=4, mlp_dim=176))
    if isinstance(cfg, KananaConfig):
        # one dense layer and two sparse ones; 4 of 16 experts held, top-2
        return dataclasses.replace(cfg, decoder=dataclasses.replace(
            cfg.decoder, vocab_size=512, seq_len=32, width=64, depth=3,
            num_heads=4, mlp_dim=176,
            mla=MLAConfig(kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
                          v_head_dim=16),
            moe=dataclasses.replace(cfg.decoder.moe, num_experts=16, top_k=2,
                                    expert_dim=48, held_experts=4)))
    if isinstance(cfg, TrinityConfig):
        # layers 5-8 of the pattern: one dense layer (windowed) and three
        # sparse ones (windowed, full, windowed); 4 query heads over 2
        # key/value heads, a window of 8 under 32 tokens, 4 of 8 experts held
        return dataclasses.replace(cfg, decoder=dataclasses.replace(
            cfg.decoder, vocab_size=512, seq_len=32, width=64, depth=4,
            num_heads=4, mlp_dim=176,
            gqa=dataclasses.replace(cfg.decoder.gqa, head_dim=32, kv_heads=2,
                                    window=8),
            moe=dataclasses.replace(cfg.decoder.moe, num_experts=8, top_k=2,
                                    expert_dim=48, held_experts=4)))
    if isinstance(cfg, KimiLinearConfig):
        # published layers 1-5 as the preset holds them: (KDA, dense), two
        # (KDA, sparse), (MLA without rotary, sparse), (KDA, sparse); chunks
        # of 16 under 32 tokens, 4 of 16 experts held
        return dataclasses.replace(cfg, decoder=dataclasses.replace(
            cfg.decoder, vocab_size=512, seq_len=32, width=64, depth=5,
            num_heads=4, mlp_dim=176,
            mla=MLAConfig(kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
                          v_head_dim=16),
            kda=KDAConfig(num_heads=4, head_dim=16, gate_rank=16, chunk=16),
            moe=dataclasses.replace(cfg.decoder.moe, num_experts=16, top_k=2,
                                    expert_dim=48, held_experts=4)))
    if isinstance(cfg, GraniteConfig):
        # published layers 0-9 as the preset holds them: Mamba-2 x 5, the
        # attention layer, Mamba-2 x 4; 4 state-space heads of 16 with a
        # state of 16, chunks of 16 under 32 tokens, 4 query heads over 2
        return dataclasses.replace(cfg, decoder=dataclasses.replace(
            cfg.decoder, vocab_size=512, seq_len=32, width=64, depth=10,
            num_heads=4, mlp_dim=176,
            gqa=dataclasses.replace(cfg.decoder.gqa, head_dim=16, kv_heads=2),
            mamba=Mamba2Config(num_heads=4, head_dim=16, state=16,
                               chunk=16)))
    raise TypeError(type(cfg))


def _serve_dtype(args: argparse.Namespace) -> str:
    """Resolve the serving precision from --dtype / the legacy --bf16."""
    if getattr(args, "bf16", False) and args.dtype not in (None, "bf16"):
        raise SystemExit(f"--bf16 conflicts with --dtype {args.dtype}")
    return args.dtype or ("bf16" if getattr(args, "bf16", False) else "f32")


# ---------------------------------------------------------------------------
# Subcommands
# ---------------------------------------------------------------------------

def cmd_presets(args: argparse.Namespace) -> int:
    _configure_backend(args)
    import math

    from flax import nnx

    from jimm_tpu.configs import PRESETS

    def params_m(name: str, cfg: Any) -> str:
        # abstract construction: shapes only, nothing allocated
        model = nnx.eval_shape(
            lambda: _model_cls(_family(name))(cfg, rngs=nnx.Rngs(0)))
        n = sum(math.prod(v.shape)
                for _, v in nnx.to_flat_state(nnx.state(model, nnx.Param)))
        return f"{n / 1e6:8.1f}M"

    for name, cfg in PRESETS.items():
        if hasattr(cfg, "decoder"):
            d = cfg.decoder
            kind = (f"x {d.loops} passes" if hasattr(d, "loops") else
                    "dense" if d.moe is None else
                    f"experts={d.moe.held_experts}/{d.moe.num_experts} held")
            print(f"{name:32s} {params_m(name, cfg)} "
                  f"decoder(width={d.width} depth={d.depth} {kind} "
                  f"vocab={d.vocab_size} seq={d.seq_len})")
            continue
        v = cfg.vision
        extra = ""
        if hasattr(cfg, "text"):
            extra = (f" text(width={cfg.text.width} depth={cfg.text.depth} "
                     f"vocab={cfg.text.vocab_size})")
        print(f"{name:32s} {params_m(name, cfg)} "
              f"vision(width={v.width} depth={v.depth} "
              f"img={v.image_size} patch={v.patch_size}){extra}")
    return 0


def _batch_fingerprint(batch) -> int:
    """48-bit content hash of a batch's host bytes.

    Small enough to round-trip float64 metrics paths (JSONL, registry)
    exactly — equal fingerprints at equal steps between a resumed run and
    an uninterrupted control is the zero-replay/zero-skip resume proof.
    Pulls the batch to host, so it is opt-in (--batch-fingerprint)."""
    import hashlib

    import jax
    import numpy as np
    h = hashlib.sha1()
    for leaf in jax.tree.leaves(batch):
        h.update(np.asarray(leaf).tobytes())
    return int(h.hexdigest()[:12], 16)


def resolve_runtime(args: argparse.Namespace, cfg: Any, mesh: Any,
                    backend: str) -> dict[str, Any]:
    """How this train run executes: the ``with_runtime`` fields that the
    flags, the mesh and the platform decide. Nothing else decides: an unset
    flag leaves its field at the config's default on every backend, but for
    the layer loop's unroll, which on a TPU is the main tower's depth."""
    rt: dict[str, Any] = {}
    if args.attn_impl:
        rt["attn_impl"] = args.attn_impl
    if args.remat:
        from jimm_tpu.configs import parse_remat
        try:
            rt.update(parse_remat(args.remat))
        except ValueError as e:
            raise SystemExit(f"--remat: {e}")
    if args.ln_impl:
        rt["ln_impl"] = args.ln_impl
    if args.fused_qkv:
        rt["fused_qkv"] = True
    if args.precision:
        rt["precision"] = args.precision
    pp_extra = {}
    if args.pipeline_virtual > 1:
        if args.rules != "pp":
            raise SystemExit("--pipeline-virtual needs --rules pp")
        # bake circular placement into storage when the stage count is
        # known from --mesh (avoids a per-step cross-stage all-to-all)
        stages = dict(mesh.shape).get("stage", 0) if mesh is not None else 0
        pp_extra = dict(pp_virtual=args.pipeline_virtual, pp_stages=stages)
    if args.pipeline_microbatches:
        if args.pipeline_microbatches < 1:
            raise SystemExit("--pipeline-microbatches must be >= 1")
        if args.rules != "pp":
            raise SystemExit("--pipeline-microbatches needs --rules pp "
                             "(layers sharded over the 'stage' mesh axis)")
        rt.update(pipeline=True, **pp_extra,
                  pp_microbatches=args.pipeline_microbatches)
    elif args.rules == "pp":
        # --rules pp without the flag: default to the config's microbatch
        # count rather than silently running the unpipelined scan with
        # stage-sharded params (correct but all-gathers every layer)
        rt.update(pipeline=True, **pp_extra)
    if args.scan_unroll >= 1:  # any explicit value wins, including 1
        rt["scan_unroll"] = args.scan_unroll
    elif (args.scan_unroll == 0 and not args.from_pretrained
            and backend == "tpu"):
        # auto: full unroll on TPU, resolved against the preset's depth
        # (a checkpoint's depth is unknown here — explicit unrolls only)
        rt["scan_unroll"] = _main_tower(cfg).depth
    return rt


def cmd_train(args: argparse.Namespace) -> int:
    train(args)
    return 0


def train(args: argparse.Namespace) -> Any:
    """The ``train`` subcommand. Returns the finished run's live objects
    (``model``, ``optimizer``, ``step_fn``, ``mesh``, ``rules``, last
    ``batch``) for a caller that inspects where the state landed
    (``chip_smoke.py``)."""
    # goodput ledger, born with the call: set-up runs under the phases of
    # its ``setup`` bucket, every loop region under a measure() bucket, so
    # the end-of-run report decomposes the call's wall time into
    # setup/compile/data_wait/step/checkpoint/host_sync/other.
    # This tracing adds no frame under train() and no local to it, on
    # purpose: on a TPU host every word of stack under the imports below
    # cost seconds of set-up (docs/observability.md, "What the set-up
    # timeline may not cost")
    from jimm_tpu import obs
    acct = obs.GoodputAccounter()
    with acct.measure("backend_init"):
        _configure_backend(args)
        _configure_journal(args)
        if args.compilation_cache_dir:
            # persistent XLA compile cache: restarted runs (preemption,
            # resume, sweep retries) skip straight past the train-step compile
            from jimm_tpu.aot.export import enable_persistent_cache
            enable_persistent_cache(args.compilation_cache_dir)
    with acct.measure("imports"):
        import jax.numpy as jnp
        import numpy as np
        from flax import nnx

        from jimm_tpu import preset
        from jimm_tpu.data import (PrefetchIterator, blob_classification,
                                   contrastive_pairs, token_sequences)
        from jimm_tpu.parallel import PRESET_RULES, use_sharding
        from jimm_tpu.train import (CheckpointManager, MetricsLogger,
                                    OptimizerConfig, StepTimer,
                                    make_classifier_train_step,
                                    make_contrastive_train_step,
                                    make_lm_train_step, make_optimizer)
    # the run's one listener to jax.monitoring: every compile request,
    # named. It listens inside the with-blocks that name it (the stretches
    # of set-up that build programs, and the loop), so it is gone on every
    # way out of them
    acct.compiles = obs.CompileWatch()

    fam = _family(args.preset)
    for name, value in {**_OPTIMIZER_DEFAULTS,
                        **LM_FAMILIES.get(fam, {})}.items():
        if getattr(args, name) is None:
            setattr(args, name, value)
            if name == "warmup_steps":  # a default fits the run, quietly
                args.warmup_steps = min(value, max(args.steps - 1, 0))
    if args.naflex and fam != "siglip":
        raise SystemExit("--naflex trains SigLIP2-style models; "
                         "use a siglip preset")
    cfg = preset(args.preset)
    if args.tiny:
        if args.from_pretrained:
            # --tiny shrinks the PRESET; with --from-pretrained the
            # architecture comes from the checkpoint, so the flag would be
            # silently ignored — refuse the contradiction
            raise SystemExit("--tiny conflicts with --from-pretrained "
                             "(the checkpoint defines the architecture)")
        cfg = _tiny_override(cfg)
    if fam in LM_FAMILIES:
        if args.from_pretrained or args.data:
            raise SystemExit("the language-model family trains from --seed "
                             "on the program's own token generator: no "
                             "checkpoint loader and no --data reader yet")
        lm = {k: v for k, v in (("depth", args.num_layers),
                                ("seq_len", args.seq_len)) if v}
        cfg = _replace_towers(cfg, **lm)
    elif args.num_layers or args.seq_len:
        raise SystemExit("--num-layers and --seq-len shape a language model "
                         "(an ouro preset, a kanana preset, a trinity "
                         "preset, a kimi-linear preset, a granite preset)")

    with acct.measure("backend_init"):
        # the first touch of the backend: the TPU runtime starts here
        mesh = _parse_mesh(args.mesh, max_devices=args.max_devices)
        import jax
        # built ONCE: the preset path applies the fields to cfg, the
        # fine-tune path passes them to from_pretrained
        rt = resolve_runtime(args, cfg, mesh, jax.default_backend())
    if rt and not args.from_pretrained:
        cfg = _replace_towers(cfg, **rt)
    def _validate_pp(cfg_obj) -> None:
        # fail bad pipeline configs before any compile, with the exact
        # message the shard_map trace would produce minutes in — preset
        # path pre-model, fine-tune path right after the checkpoint load
        if args.rules != "pp":
            return
        from jimm_tpu.configs import validate_pipeline
        mesh_shape = dict(mesh.shape) if mesh is not None else {}
        data_axis = mesh_shape.get("data", 1)
        if args.batch_size % data_axis:
            # floor division below would validate a WRONG local batch and
            # let a config pass (or fail confusingly) that the real
            # shard-map trace rejects minutes later
            raise SystemExit(f"--batch-size {args.batch_size} is not "
                             f"divisible by the data mesh axis ({data_axis})")
        local_batch = args.batch_size // data_axis
        try:
            for tname in ("vision", "text"):
                tower = getattr(cfg_obj, tname, None)
                if tower is not None:
                    validate_pipeline(tower,
                                      n_stages=mesh_shape.get("stage", 0),
                                      local_batch=local_batch,
                                      tower_name=tname)
        except ValueError as e:
            raise SystemExit(f"pipeline config: {e}")

    if not args.from_pretrained:
        _validate_pp(cfg)
    n_classes = None
    if fam == "vit":
        n_classes = args.num_classes or (
            _num_classes_from_data(args.data) if args.data else None)
        if n_classes is None and not args.data:
            n_classes = 4  # synthetic classes
        if n_classes:
            cfg = dataclasses.replace(cfg, num_classes=n_classes)

    rules = PRESET_RULES[args.rules] if args.rules else (
        PRESET_RULES["dp"] if mesh is not None else None)
    dtype = jnp.bfloat16 if args.bf16 else jnp.float32

    with acct.measure("model_build"), acct.compiles:
        if args.from_pretrained:
            # fine-tune: architecture from the checkpoint, execution strategy
            # from the SAME rt dict the preset path applies (built above)
            try:
                model = _model_cls(fam).from_pretrained(
                    args.from_pretrained, mesh=mesh,
                    rules=rules if rules is not None else "replicated",
                    dtype=dtype, runtime=rt or None,
                    image_size=args.image_size)
            except ValueError as e:
                # a checkpoint depth incompatible with the stage/virtual layout
                # raises during construction (interleaved placement is baked
                # into storage) — give it the same fast, clean exit as the
                # parse-time checks; any OTHER load error keeps its traceback
                if (args.rules == "pp" and "divisible" in str(e)
                        and "stage" in str(e)):
                    raise SystemExit(f"pipeline config: {e}")
                raise
            if fam == "vit":
                _fit_head(model, n_classes, dtype=dtype, seed=args.seed,
                          mesh=mesh, rules=rules)
            cfg = model.config
            _validate_pp(cfg)
        else:
            model = _model_cls(fam)(cfg, rngs=nnx.Rngs(args.seed), mesh=mesh,
                                    rules=rules, dtype=dtype,
                                    param_dtype=dtype)
        # low-precision training surgery, BEFORE the optimizer is built: the
        # optimizer tracks nnx.Param state, and the fp8 wrapper shares the
        # Linear's kernel/bias Params (amax histories are plain Variables, so
        # they never enter optimizer state)
        precision = getattr(_main_tower(cfg), "precision", "bf16")
        if precision != "bf16":
            from jimm_tpu.quant.policy import apply_precision_policy
            n_lowp = apply_precision_policy(model, precision)
            print(f"precision policy {precision}: {n_lowp} modules rewritten")
    # --moment-dtype wins over the legacy --bf16-momentum sugar
    moment_dtype = ({"f32": "float32", "bf16": "bfloat16"}[args.moment_dtype]
                    if args.moment_dtype
                    else ("bfloat16" if args.bf16_momentum else None))
    # under the mesh: the optimizer's scalar counters are then born with the
    # mesh in their type, as the step returns them — created outside it they
    # change type after step 0 and the whole step compiles a second time
    with acct.measure("optimizer_build"), acct.compiles, \
            use_sharding(mesh, rules):
        optimizer = make_optimizer(model, OptimizerConfig(
            learning_rate=args.lr, weight_decay=args.weight_decay,
            warmup_steps=args.warmup_steps, total_steps=args.steps,
            moment_dtype=moment_dtype))

    # deterministic fault drill: --fake-failure-at-step N is historical
    # sugar for the crash@N entry of the general --inject-faults plan
    fault_spec = args.inject_faults or ""
    if args.fake_failure_at_step is not None:
        crash = f"crash@{args.fake_failure_at_step}"
        fault_spec = f"{fault_spec},{crash}" if fault_spec else crash
    fault_plan = None
    if fault_spec:
        from jimm_tpu.resilience import FaultPlan
        try:
            fault_plan = FaultPlan.parse(fault_spec)
        except ValueError as e:
            raise SystemExit(f"--inject-faults: {e}")
        if fault_plan.needs("corrupt") and not args.ckpt_dir:
            raise SystemExit("--inject-faults: corrupt@STEP needs --ckpt-dir")
    if args.preemption_save and not args.ckpt_dir:
        raise SystemExit("--preemption-save needs --ckpt-dir")

    # mesh= records the topology each save was sharded over and counts a
    # topology change when a restore crosses mesh shapes (elastic restarts)
    ckpt = None
    start_step = 0
    if args.ckpt_dir:
        with acct.measure("checkpoint"), acct.compiles:
            ckpt = CheckpointManager(args.ckpt_dir,
                                     save_interval_steps=args.save_every,
                                     mesh=mesh)
            if args.resume:
                try:
                    start_step = ckpt.restore(model, optimizer) + 1
                    print(f"resumed from step {start_step - 1}")
                except FileNotFoundError:
                    pass

    # deterministic resume: resumed step N sees the same batch it would have
    # in the uninterrupted run. File pipelines fast-forward the raw example
    # stream (no image decode); synthetic generators just skip batches.
    data_kw = dict(shard_index=jax.process_index(),
                   shard_count=jax.process_count(),
                   shuffle_buffer=args.shuffle_buffer, seed=args.seed,
                   skip_examples=start_step * args.batch_size,
                   **_norm_for(fam))

    grain_stream = None  # consumed-state tracker for exact checkpoint/resume

    def _grain_data(task: str):
        nonlocal grain_stream
        if _is_tar_data(args.data):
            raise SystemExit("--loader grain reads tfrecord shards; tar "
                             "(webdataset) data uses --loader records")
        import base64

        from jimm_tpu.data.grain_pipeline import (CheckpointableGrainStream,
                                                  make_grain_loader)
        extra = ({"seq_len": cfg.text.context_length}
                 if task == "contrastive" else {})
        loader = make_grain_loader(
            args.data, args.batch_size, task=task,
            image_size=cfg.vision.image_size, seed=args.seed,
            worker_count=args.data_workers,
            shard_index=jax.process_index(),
            shard_count=jax.process_count(), **_norm_for(fam), **extra)
        grain_iter = iter(loader)
        saved = (ckpt.last_restored_extra.get("grain_state")
                 if ckpt is not None else None)
        if start_step and saved:
            # exact position from the checkpoint — no decode replay, no
            # skipped batches: the saved state is the one captured with the
            # last batch the train loop actually consumed (see
            # CheckpointableGrainStream), so resume lands on the very next
            # batch even though PrefetchIterator had read ahead.
            grain_iter.set_state(base64.b64decode(saved))
        else:
            for _ in range(start_step):  # pre-grain_state checkpoint:
                next(grain_iter)         # replay (decodes) to position
        grain_stream = CheckpointableGrainStream(grain_iter)
        return grain_stream.batches()

    with acct.measure("data_build"), acct.compiles:
        lm_counters = ()
        if fam in LM_FAMILIES:
            step_fn = make_lm_train_step(fam, donate=True)
            d = cfg.decoder
            data = token_sequences(args.batch_size, seq_len=d.seq_len,
                                   vocab_size=d.vocab_size, seed=args.seed)
            lm_counters = [
                (obs.get_registry(registry).counter(name), amount)
                for registry, name, amount
                in _lm_counters(cfg, args.batch_size)]
        elif fam == "vit":
            step_fn = make_classifier_train_step(donate=True)
            if args.data and args.loader == "grain":
                data = _grain_data("classification")
            elif args.data:
                if _is_tar_data(args.data):
                    from jimm_tpu.data.webdataset import (
                        wds_classification_batches as classification_batches)
                else:
                    from jimm_tpu.data.records import classification_batches
                data = classification_batches(
                    args.data, args.batch_size,
                    image_size=cfg.vision.image_size, **data_kw)
            else:
                # temporal towers train on synthetic (B, T, H, W, C) clips;
                # the file loaders stay image-only for now
                data = blob_classification(args.batch_size,
                                           image_size=cfg.vision.image_size,
                                           num_classes=cfg.num_classes,
                                           seed=args.seed,
                                           num_frames=cfg.vision.num_frames)
        else:
            # ring losses shard the batch over the "data" axis — on a mesh
            # without one (e.g. model-only TP) the dense loss is the default
            ring_ok = mesh is not None and ("data" in mesh.shape
                                            or mesh.shape.get("seq", 1) > 1)
            if fam == "clip":
                loss_kind = args.loss or ("clip_ring" if ring_ok else "clip")
            else:
                loss_kind = args.loss or ("siglip_ring" if ring_ok
                                          else "siglip")
            # a seq axis joins the pair-dimension ring: the contrastive batch
            # shards over ("data", "seq") combined, so sequence-parallel
            # meshes spend every chip on the pairwise loss too
            loss_axis = "data"
            if (loss_kind.endswith("_ring") and mesh is not None
                    and mesh.shape.get("seq", 1) > 1):
                loss_axis = tuple(a for a in ("data", "seq")
                                  if a in mesh.shape)
            step_fn = make_contrastive_train_step(loss_kind, mesh=mesh,
                                                  axis_name=loss_axis,
                                                  donate=True)
            if rules is not None and isinstance(loss_axis, tuple):
                # batches land sharded over both pair axes (the loss's
                # shard_map in_specs expect it)
                rules = dataclasses.replace(rules, batch=loss_axis)
            if args.naflex:
                # variable-resolution SigLIP2 training (beyond the reference)
                if fam != "siglip":
                    raise SystemExit("--naflex trains SigLIP2-style models; "
                                     "use a siglip preset")
                if args.rules == "pp":
                    raise SystemExit("--naflex needs attention masks, which "
                                     "the pipelined path does not support "
                                     "yet")
                if args.data and (args.loader == "grain"
                                  or _is_tar_data(args.data)):
                    raise SystemExit("--naflex reads tfrecord shards (records "
                                     "loader) or synthetic data")
                naflex_kw = dict(patch_size=cfg.vision.patch_size,
                                 max_num_patches=cfg.vision.num_patches,
                                 seq_len=cfg.text.context_length)
                if args.data:
                    from jimm_tpu.data.records import naflex_image_text_batches
                    data = naflex_image_text_batches(
                        args.data, args.batch_size, **naflex_kw, **data_kw)
                else:
                    from jimm_tpu.data.synthetic import (
                        naflex_contrastive_pairs)
                    data = naflex_contrastive_pairs(
                        args.batch_size, **naflex_kw,
                        vocab_size=cfg.text.vocab_size, seed=args.seed)
            elif args.data and args.loader == "grain":
                data = _grain_data("contrastive")
            elif args.data:
                if _is_tar_data(args.data):
                    from jimm_tpu.data.webdataset import (
                        wds_image_text_batches as image_text_batches)
                else:
                    from jimm_tpu.data.records import image_text_batches
                data = image_text_batches(
                    args.data, args.batch_size,
                    image_size=cfg.vision.image_size,
                    seq_len=cfg.text.context_length, **data_kw)
            else:
                data = contrastive_pairs(args.batch_size,
                                         image_size=cfg.vision.image_size,
                                         vocab_size=cfg.text.vocab_size,
                                         seq_len=cfg.text.context_length,
                                         seed=args.seed)
        if not args.data:
            for _ in range(start_step):
                next(data)

    logger = MetricsLogger(path=args.metrics_file, print_every=args.log_every,
                           tensorboard_dir=args.tensorboard_dir,
                           registry=obs.get_registry("jimm_train"))
    timer = StepTimer()
    profiler_ctx = None
    # continuous profiling ring: a bounded on-disk rotation of short
    # step-window captures, plus anomaly-triggered deep captures (installed
    # process-globally so resilience paths can maybe_trigger() into it)
    prof_ring = None
    if args.prof_ring:
        from jimm_tpu.obs.prof.capture import configure_capture
        prof_ring = configure_capture(
            args.prof_ring, max_ring_bytes=args.prof_ring_bytes,
            every_steps=args.prof_every, window_steps=args.prof_window)

    # preemption guard: SIGTERM sets a flag the loop polls; the handler
    # turns it into a grace-window async save + resumable PreemptedError
    guard = None
    preempt = None
    if args.preemption_save:
        from jimm_tpu.resilience import PreemptionGuard, PreemptionHandler
        guard = PreemptionGuard().install()
        preempt = PreemptionHandler(guard, ckpt,
                                    grace_steps=args.grace_steps,
                                    accounter=acct)

    def place(batch):
        # tree-map: a NaFlex batch nests the image triple inside
        return jax.tree.map(jnp.asarray, batch)

    with acct.measure("data_build"), acct.compiles:
        if mesh is not None:
            # places in its own thread; the loop's "place" phase does not exist
            data = PrefetchIterator(data, mesh=mesh, rules=rules)
        if grain_stream is not None:
            # advance consumed_state batch-by-batch on THIS (consumer) side of
            # the prefetch queue, so checkpoints record the trained-on position
            data = grain_stream.track(data)

    # profile steps start+2..start+4 (past compile), falling back to the
    # whole run when it is shorter than that
    profile_start = min(start_step + 2, max(args.steps - 1, start_step))
    profile_stop = min(start_step + 4, args.steps - 1)
    ahead_total = obs.get_registry("jimm_train").counter(
        "steps_dispatched_ahead_total")
    ahead_before = ahead_total.value  # the registry outlives a run
    # step_time_s of the newest steps after the compiling one, for the MFU
    steady_times: collections.deque[float] = collections.deque(maxlen=128)
    batch = None
    # the step in flight: dispatched, its loss not yet waited for. The loop
    # launches the next step before it reads this one's loss, so the device
    # always has a program queued behind the running one and every host
    # phase (next_batch, place, dispatch, the wake-up, the log) runs behind
    # a program. Its ``phases`` are the spans of its row so far.
    flight = None

    def finish_step_in_flight():
        """Wait for the step in flight, then write its row."""
        nonlocal flight, profiler_ctx
        f, flight = flight, None
        timer.start()
        with acct.measure("device_wait", f.bucket):
            # the host's time on this step: its call and its wait, which no
            # longer touch (the next step's call lies between them). From
            # call to arrival would span two programs.
            dt = f.dispatch_s + timer.stop(f.metrics["loss"])
        if f.bucket == "step":
            steady_times.append(dt)
        if profiler_ctx is not None and f.step == profile_stop:
            profiler_ctx.__exit__(None, None, None)
            profiler_ctx = None
            print(f"profile trace written to {args.profile_dir}")
        with acct.measure("host_sync"):
            # one transfer for the whole dict (a looped model's step
            # returns nine scalars; one by one they cost 4-8 ms)
            host_metrics = {k: float(v) for k, v
                            in jax.device_get(f.metrics).items()}
            for counter, amount in lm_counters:
                counter.inc(host_metrics[amount] if isinstance(amount, str)
                            else amount)
            if f.fp is not None:
                host_metrics["batch_fingerprint"] = f.fp
            # grouped by step, not by the clock: what the next step measured
            # before this row (its next_batch .. dispatch) waits for its own,
            # and so does what it compiled or loaded: all of set-up in the
            # first row, nothing in a steady step's
            logger.log(f.step, step_time_s=dt, **host_metrics,
                       file_only={"phases": f.phases + acct.drain(),
                                  **obs.compiles.row_keys(
                                      f.compiles + acct.compiles.drain())})

    try:
        acct.compiles.listen()
        with use_sharding(mesh, rules):
            for step in range(start_step, args.steps):
                if prof_ring is not None:
                    prof_ring.on_step(step)
                if args.profile_dir and step == profile_start:
                    if prof_ring is not None:
                        # one profiler session at a time: a live ring window
                        # would deadlock the blocking one-shot trace below
                        prof_ring.flush()
                    from jimm_tpu.train.profile import trace
                    profiler_ctx = trace(args.profile_dir)
                    profiler_ctx.__enter__()
                with acct.measure("next_batch"):
                    batch = next(data)
                if mesh is None:
                    with acct.measure("place"):
                        batch = place(batch)
                # hash before step_fn runs (it donates the state, nothing
                # of the batch)
                fp = (_batch_fingerprint(batch)
                      if args.batch_fingerprint else None)
                # the first step traces + compiles under the same call; its
                # dispatch and its wait land in the "compile" bucket,
                # steady-state in "step"
                bucket = "compile" if step == start_step else "step"
                timer.start()
                with acct.measure("dispatch", bucket):
                    metrics = step_fn(model, optimizer, *batch)
                dispatch_s = timer.stop()
                # this step's spans and compile events so far, before the
                # step in flight takes what follows (a pair in the one local
                # the spans had: the note at the top of train())
                own = acct.drain(), acct.compiles.drain()
                if flight is not None:
                    if not flight.metrics["loss"].is_ready():
                        # launched behind a running program: the device
                        # goes from one to the next without the host
                        ahead_total.inc()
                    finish_step_in_flight()
                # model / optimizer hold the state after `step` from here to
                # the next dispatch. Its row begins with the host_sync of the
                # row before, then its own phases.
                flight = types.SimpleNamespace(
                    step=step, metrics=metrics, fp=fp, bucket=bucket,
                    dispatch_s=dispatch_s, phases=acct.drain() + own[0],
                    compiles=own[1] + acct.compiles.drain())
                extra = None
                if ckpt is not None and grain_stream is not None:
                    import base64
                    extra = {"grain_state": base64.b64encode(
                        grain_stream.consumed_state).decode("ascii")}
                saved_now = False
                if ckpt is not None and (preempt is None
                                         or not preempt.draining):
                    # while the grace save drains, later per-step saves are
                    # pointless — nothing after it survives the restart. A
                    # save blocks on this step's arrays: the steps that save
                    # give up the overlap.
                    with acct.measure("checkpoint"):
                        saved_now = ckpt.save(step, model, optimizer,
                                              extra=extra)
                    flight.phases += acct.drain()
                    flight.compiles += acct.compiles.drain()
                if fault_plan is not None:
                    # drill events for this step (stall/corrupt/preempt/
                    # crash); a preempt's SIGTERM lands before the guard
                    # check below, same as a real maintenance signal
                    fault_plan.fire(step, ckpt=ckpt)
                if preempt is not None:
                    preempt.after_step(step, model, optimizer, extra=extra,
                                       already_saved=saved_now)
    finally:
        try:
            # every way out (the end, PreemptedError, a drill's crash, an
            # exception) leaves one row per dispatched step
            if flight is not None:
                finish_step_in_flight()
        finally:
            acct.compiles.close()
            if guard is not None:
                guard.uninstall()
            if profiler_ctx is not None:
                # crash mid-profile: still flush what was captured
                profiler_ctx.__exit__(None, None, None)
                print(f"profile trace written to {args.profile_dir}")
            if prof_ring is not None:
                # commit a half-open window so the newest capture survives
                # a crash — the whole point of a flight-recorder ring
                prof_ring.close()
            # a mid-run crash must not strand buffered TensorBoard events
            # (the EventFileWriter queue flushes on close, not per event)
            logger.close()
    if ckpt is not None:
        ckpt.wait()
        ckpt.close()
    import json as _json

    from jimm_tpu.train.metrics import mfu as _mfu, train_step_flops
    # an MFU exists only against a chip's published peak: no TPU, no MFU
    achieved_mfu = None
    if steady_times and jax.default_backend() == "tpu":
        # the median, because the last step waits through the whole of its
        # program: no call follows it
        import statistics
        achieved_mfu = _mfu(
            train_step_flops(cfg, args.batch_size),
            statistics.median(steady_times),
            n_devices=mesh.devices.size if mesh is not None else 1)
    # precision + moment_dtype ride the goodput line so that its readers
    # (scripts/lowp_train_smoke.py) can put a difference down to the policy
    # that produced it
    print("goodput: " + _json.dumps({
        **acct.report(mfu=achieved_mfu),
        # share of the run's steps launched while the step before was still
        # running: (steps - 1) / steps where the host keeps up
        "dispatched_ahead_frac": round(
            (ahead_total.value - ahead_before)
            / max(args.steps - start_step, 1), 4),
        "precision": precision,
        "moment_dtype": moment_dtype or "param",
    }))
    return types.SimpleNamespace(model=model, optimizer=optimizer,
                                 step_fn=step_fn, mesh=mesh, rules=rules,
                                 batch=batch)


def _argv_flag_value(argv: list[str], flag: str, default):
    """Last occurrence wins, mirroring argparse."""
    value = default
    for i, tok in enumerate(argv):
        if tok == flag and i + 1 < len(argv):
            value = argv[i + 1]
        elif tok.startswith(flag + "="):
            value = tok.split("=", 1)[1]
    return value


def cmd_supervise(args: argparse.Namespace) -> int:
    """Run ``train`` as restartable attempts.

    A preemption (PreemptedError out of the grace-window save) or worker
    death restarts the command with ``--resume`` after a bounded jittered
    backoff, up to ``--max-restarts`` times, then gives up loudly.
    In-process — one interpreter, one metric registry — so
    ``jimm_train_restarts_total`` and the lost-work goodput bucket
    accumulate across attempts; ``launch.py --restarts`` applies the same
    policy at process-group granularity.

    ``--elastic`` replans the mesh before every attempt from the devices
    still available (``--shrink-plan`` shrinks the budget between attempts
    for drills), so a restart that lost hosts restores its checkpoint onto
    the smaller mesh (resharding-on-restore) instead of crashing on the old
    shape. ``--adapt`` runs a :class:`~jimm_tpu.resilience.GoodputAdvisor`
    over the per-attempt goodput breakdown and carries its bounded knob
    decisions (checkpoint cadence, grace steps, scan unroll) into the next
    attempt's flags. Without these flags, behavior is byte-identical to the
    static supervise loop."""
    from jimm_tpu.resilience import BackoffPolicy, GiveUpError, Supervisor
    _configure_journal(args)
    cmd = list(args.train_args or [])
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd or cmd[0] != "train":
        raise SystemExit("supervise wraps the train subcommand: "
                         "jimm-tpu supervise [options] -- train ...")
    if "--ckpt-dir" not in cmd:
        raise SystemExit("supervise needs --ckpt-dir in the train command "
                         "(restarts resume from checkpoints)")
    if "--preemption-save" not in cmd:
        cmd.append("--preemption-save")
    shrink_plan = None
    if args.shrink_plan:
        if not args.elastic:
            raise SystemExit("--shrink-plan is an --elastic drill knob")
        try:
            shrink_plan = [int(x) for x in args.shrink_plan.split(",")]
        except ValueError:
            raise SystemExit(f"--shrink-plan {args.shrink_plan!r}: expected "
                             "comma-separated device counts, e.g. 8,4")
        if any(n < 1 for n in shrink_plan):
            raise SystemExit("--shrink-plan device counts must be >= 1")
    advisor = None
    if args.adapt:
        from jimm_tpu.resilience import GoodputAdvisor

        # seed the knobs from the train command itself
        advisor = GoodputAdvisor(knobs={
            "save_every": int(_argv_flag_value(cmd, "--save-every", 50)),
            "grace_steps": int(_argv_flag_value(cmd, "--grace-steps", 1)),
            "scan_unroll": int(_argv_flag_value(cmd, "--scan-unroll", 0)),
        })
    sup = Supervisor(max_restarts=args.max_restarts,
                     backoff=BackoffPolicy(base_s=args.backoff_base_s,
                                           max_s=args.backoff_max_s,
                                           jitter=0.5, seed=args.seed))
    # elastic state threaded through attempts: the previous attempt's mesh
    # width (to count replans) and the goodput counter values already
    # booked (to hand the advisor per-attempt deltas)
    elastic_state: dict[str, Any] = {"last_k": None, "booked": {}}

    def _observe_goodput(attempt_i: int, t0: float) -> None:
        from jimm_tpu import obs
        snap = obs.snapshot()
        prefix = "jimm_train_goodput_"
        deltas = {}
        for key, value in snap.items():
            if key.startswith(prefix) and key.endswith("_seconds_total"):
                bucket = key[len(prefix):-len("_seconds_total")]
                deltas[bucket] = value - elastic_state["booked"].get(key, 0.0)
                elastic_state["booked"][key] = value
        import time as _time
        advisor.observe(attempt_i, _time.monotonic() - t0, deltas)

    def attempt(i: int, resume: bool) -> int:
        argv = list(cmd)
        if resume and "--resume" not in argv:
            argv.append("--resume")
        if args.elastic:
            import jax
            avail = len(jax.devices())
            if shrink_plan is not None:
                avail = min(avail,
                            shrink_plan[min(i, len(shrink_plan) - 1)])
            from jimm_tpu.resilience import plan_data_axis
            batch = int(_argv_flag_value(argv, "--batch-size", 32))
            k = plan_data_axis(avail, batch)
            # appended AFTER the user's flags: argparse last-wins makes the
            # replanned mesh effective without rewriting their command
            argv += ["--mesh", f"data={k}", "--rules", "dp",
                     "--max-devices", str(k)]
            if (elastic_state["last_k"] is not None
                    and k != elastic_state["last_k"]):
                from jimm_tpu.obs import get_registry
                from jimm_tpu.obs.journal import get_journal
                get_registry("jimm_train").counter(
                    "topology_changes_total").inc()
                # runs inside the supervisor's correlate(incident) scope,
                # so the replan joins the preemption/crash chain ambiently
                get_journal().emit("mesh_replanned", attempt=i + 1,
                                   data_from=elastic_state["last_k"],
                                   data_to=k, devices=avail)
                print(f"[supervise] attempt {i + 1}: replanned mesh "
                      f"data={elastic_state['last_k']} -> data={k} "
                      f"({avail} devices available)")
            elastic_state["last_k"] = k
        if advisor is not None:
            argv += advisor.argv_overrides()
        import time as _time
        t0 = _time.monotonic()
        try:
            ns = build_parser().parse_args(argv)
            return ns.fn(ns)
        finally:
            if advisor is not None:
                _observe_goodput(i, t0)

    try:
        rc = sup.run(attempt)
    except GiveUpError as e:
        print(f"supervise: {e}", file=sys.stderr)
        return 1
    # one parseable line with the resilience counters, so external drills
    # (scripts/resilience_smoke.py, CI) can assert on them cross-process
    import json as _json

    from jimm_tpu import obs
    snap = obs.snapshot()
    keys = ("jimm_train_restarts_total", "jimm_train_preemptions_total",
            "jimm_train_checkpoint_quarantined_total",
            "jimm_train_goodput_lost_work_seconds_total",
            "jimm_train_goodput_preemption_save_seconds_total")
    if args.elastic:
        keys += ("jimm_train_topology_changes_total",
                 "jimm_train_checkpoint_topology_changes_total")
    if advisor is not None:
        keys += ("jimm_train_goodput_advisor_decisions_total",)
    print("resilience: "
          + _json.dumps({k: snap.get(k, 0.0) for k in keys}))
    return rc


def cmd_evaluate(args: argparse.Namespace) -> int:
    """Evaluate a model over a file dataset (single non-repeating pass).

    - vit: top-1 accuracy over labeled records
    - clip/siglip: in-batch retrieval R@1, image->text and text->image
      (diagonal is the positive pair, as in contrastive training)

    Weights: ``--ckpt`` (HF checkpoint: local safetensors file/dir or hub
    id) or ``--preset`` + ``--ckpt-dir`` (orbax training checkpoint).
    Prints one JSON line.
    """
    _configure_backend(args)
    import json

    import jax.numpy as jnp
    import numpy as np
    from flax import nnx

    from jimm_tpu import preset
    from jimm_tpu.utils import jit_forward

    if args.ckpt:
        if not (args.model or args.preset):
            raise SystemExit("--ckpt needs --model (or --preset to infer "
                             "the family)")
        fam = args.model or _family(args.preset)
        model = _model_cls(fam).from_pretrained(
            args.ckpt, dtype=jnp.bfloat16 if args.bf16 else None)
        cfg = model.config
    else:
        if not (args.preset and args.ckpt_dir):
            raise SystemExit("need --ckpt, or --preset with --ckpt-dir")
        fam, model = _restore_run(args)
        cfg = model.config

    # family-correct normalization, SAME helper as cmd_train's loaders —
    # eval must see the pixels training saw; square resize is the shared
    # file-pipeline convention (classify's center-crop is for wild images)
    norm = _norm_for(fam)

    if args.naflex and (fam == "vit" or args.zero_shot):
        raise SystemExit("--naflex applies to clip/siglip retrieval "
                         "evaluation (not vit accuracy or --zero-shot)")
    fwd = jit_forward(model)
    n = 0
    if args.zero_shot:
        if fam == "vit":
            raise SystemExit("--zero-shot needs a contrastive model "
                             "(clip/siglip); vit evaluates accuracy "
                             "directly")
        metrics, n = _zero_shot_eval(args, model, cfg, norm)
    elif fam == "vit":
        if _is_tar_data(args.data):
            from jimm_tpu.data.webdataset import (
                wds_classification_batches as classification_batches)
        else:
            from jimm_tpu.data.records import classification_batches
        correct = 0
        for images, labels in classification_batches(
                args.data, args.batch_size, image_size=cfg.vision.image_size,
                repeat=False, shuffle_buffer=0, drop_remainder=False):
            pred = np.asarray(jnp.argmax(fwd(jnp.asarray(images)), axis=-1))
            correct += int((pred == labels).sum())
            n += len(labels)
        if not n:
            raise SystemExit(f"no examples in {args.data}")
        metrics = {"top1_accuracy": round(correct / n, 4)}
    else:
        if args.naflex:
            # variable-resolution retrieval: aspect-preserving NaFlex
            # batches + masked logits instead of the square resize
            if fam != "siglip":
                raise SystemExit("--naflex evaluates SigLIP2-style models; "
                                 "use --model siglip")
            if _is_tar_data(args.data):
                raise SystemExit("--naflex reads tfrecord shards")
            from jimm_tpu.data.records import naflex_image_text_batches

            def batches():
                return naflex_image_text_batches(
                    args.data, args.batch_size,
                    patch_size=cfg.vision.patch_size,
                    max_num_patches=cfg.vision.num_patches,
                    seq_len=cfg.text.context_length, repeat=False,
                    shuffle_buffer=0, drop_remainder=False, **norm)

            logits_fn = nnx.jit(
                lambda m, im, tok: m.logits_naflex(*im, tok))
        else:
            if _is_tar_data(args.data):
                from jimm_tpu.data.webdataset import (
                    wds_image_text_batches as image_text_batches)
            else:
                from jimm_tpu.data.records import image_text_batches

            def batches():
                return image_text_batches(
                    args.data, args.batch_size,
                    image_size=cfg.vision.image_size,
                    seq_len=cfg.text.context_length, repeat=False,
                    shuffle_buffer=0, drop_remainder=False, **norm)

            logits_fn = nnx.jit(lambda m, im, tok: m(im, tok))
        i2t = t2i = 0
        for images, tokens in batches():
            if args.naflex:
                images = tuple(jnp.asarray(a) for a in images)
            else:
                images = jnp.asarray(images)
            logits = np.asarray(
                logits_fn(model, images, jnp.asarray(tokens)), np.float32)
            diag = np.arange(len(logits))
            i2t += int((logits.argmax(axis=1) == diag).sum())
            t2i += int((logits.argmax(axis=0) == diag).sum())
            n += len(logits)
        if not n:
            raise SystemExit(f"no examples in {args.data}")
        metrics = {"retrieval_r1_image_to_text": round(i2t / n, 4),
                   "retrieval_r1_text_to_image": round(t2i / n, 4)}
    print(json.dumps({"examples": n, "batch_size": args.batch_size,
                      **metrics}))
    return 0


def _zero_shot_eval(args: argparse.Namespace, model, cfg, norm
                    ) -> tuple[dict, int]:
    """Zero-shot classification accuracy (the CLIP-paper benchmark flow)
    over *classification* records: ensemble classifier weights from a
    tokens file, then one image-encoder pass + a (B, D) @ (D, C) matmul
    per batch — no text tower in the loop.

    ``--zero-shot tokens.json``: ``{label: [ids]}`` or
    ``{label: [[ids], [ids], ...]}`` (multiple prompt templates per class,
    ensemble-averaged). Class order follows the dataset's own
    ``classes.json`` when present (index == label id), else the file's
    insertion order.
    """
    import json

    import jax.numpy as jnp
    import numpy as np
    from flax import nnx

    from jimm_tpu.data.records import pad_tokens
    from jimm_tpu.utils.zero_shot import zero_shot_logits_from_features

    table = json.loads(open(args.zero_shot).read())
    labels = _dataset_classes(args.data) or list(table)
    missing = [label for label in labels if label not in table]
    if missing:
        raise SystemExit(f"--zero-shot file lacks tokens for classes "
                         f"{missing[:5]} (dataset classes.json order)")
    rows, owner = [], []
    for ci, label in enumerate(labels):
        entry = table[label]
        per_class = entry if entry and isinstance(entry[0], list) else [entry]
        for r in per_class:
            if len(r) > cfg.text.context_length:
                raise SystemExit(
                    f"tokens for {label!r} are {len(r)} ids but the "
                    f"checkpoint's context_length is "
                    f"{cfg.text.context_length}; re-tokenize to fit")
            rows.append(pad_tokens(r, cfg.text.context_length))
            owner.append(ci)
    emb = np.array(model.encode_text(jnp.asarray(np.stack(rows))),
                   np.float32)  # copy: jax buffers are read-only
    emb /= np.linalg.norm(emb, axis=-1, keepdims=True)
    owner_arr = np.asarray(owner)
    weights = np.stack([emb[owner_arr == ci].mean(axis=0)
                        for ci in range(len(labels))])
    weights /= np.linalg.norm(weights, axis=-1, keepdims=True)
    weights = jnp.asarray(weights)

    if _is_tar_data(args.data):
        from jimm_tpu.data.webdataset import (
            wds_classification_batches as classification_batches)
    else:
        from jimm_tpu.data.records import classification_batches
    encode = nnx.jit(lambda m, im: m.encode_image(im))
    correct = n = 0
    for images, y in classification_batches(
            args.data, args.batch_size, image_size=cfg.vision.image_size,
            repeat=False, shuffle_buffer=0, drop_remainder=False, **norm):
        feats = encode(model, jnp.asarray(images))
        logits = np.asarray(
            zero_shot_logits_from_features(model, feats, weights),
            np.float32)
        correct += int((logits.argmax(axis=1) == y).sum())
        n += len(y)
    if not n:
        raise SystemExit(f"no examples in {args.data}")
    return {"zero_shot_top1": round(correct / n, 4),
            "classes": len(labels),
            "prompts": len(rows)}, n


def cmd_export_run(args: argparse.Namespace) -> int:
    """Export a TRAINING RUN (orbax checkpoint) as an HF-interoperable
    safetensors directory — the fine-tune → share loop: the output loads in
    `transformers` and back through `from_pretrained`. (`export` converts
    HF checkpoints; this converts this framework's own runs.)"""
    _configure_backend(args)
    _, model = _restore_run(args)
    _model_save(model, args)
    print(f"exported {args.ckpt_dir} -> {args.out}")
    return 0


def cmd_prepare_data(args: argparse.Namespace) -> int:
    """Build tfrecord shards (the format `--data` consumes) from raw files.

    - ``--task classification``: SRC/<class_name>/*.{jpg,jpeg,png} — labels
      are sorted class-directory indices; writes ``classes.json`` alongside
      the shards.
    - ``--task contrastive``: SRC holds the images; ``--captions`` is a TSV
      of ``relative/path<TAB>caption``. Captions that are whitespace-
      separated integers are taken as pre-tokenized ids; otherwise
      ``--tokenizer`` names a HuggingFace tokenizer (needs the optional
      ``transformers`` install — tokenization is offline-optional tooling,
      never a runtime dependency).
    """
    import json
    import re
    from pathlib import Path

    from jimm_tpu.data.tfrecord import TFRecordWriter, encode_example

    src, out = Path(args.src), Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    stale = sorted(out.glob("part-*.tfrecord"))
    if stale:
        # the readers glob the whole dir: leftover higher-numbered shards
        # from a previous run would silently mix into the dataset
        raise SystemExit(f"{out} already holds {len(stale)} shard(s) "
                         f"({stale[0].name}..); remove them or use a fresh "
                         "output directory")
    exts = {".jpg", ".jpeg", ".png"}
    _INT = re.compile(r"^-?\d+$")

    class ShardWriter:
        """Rotates part-NNNNN.tfrecord files every --shard-size examples."""

        def __init__(self):
            self.n_in_shard = 0
            self.shards = 0
            self.total = 0
            self._w = None

        def write(self, payload: bytes) -> None:
            if self._w is None or self.n_in_shard >= args.shard_size:
                self.close()
                self._w = TFRecordWriter(
                    out / f"part-{self.shards:05d}.tfrecord")
                self.shards += 1
                self.n_in_shard = 0
            self._w.write(payload)
            self.n_in_shard += 1
            self.total += 1

        def close(self) -> None:
            if self._w is not None:
                self._w.close()
                self._w = None

    writer = ShardWriter()
    classes: dict[str, int] = {}
    try:
        if args.task == "classification":
            names = sorted(d.name for d in src.iterdir() if d.is_dir())
            if not names:
                raise SystemExit(f"no class directories under {src}")
            classes = {name: i for i, name in enumerate(names)}
            for name, label in classes.items():
                for img in sorted((src / name).iterdir()):
                    if img.suffix.lower() not in exts or not img.is_file():
                        continue
                    writer.write(encode_example({"image": img.read_bytes(),
                                                 "label": label}))
        else:  # contrastive
            if not args.captions:
                raise SystemExit("--task contrastive needs --captions TSV")
            tok = None
            for ln, line in enumerate(
                    Path(args.captions).read_text().splitlines(), 1):
                if not line.strip():
                    continue
                rel, _, caption = line.partition("\t")
                parts = caption.split()
                if not parts:
                    raise SystemExit(f"{args.captions}:{ln}: no caption "
                                     f"after TAB (line {line[:60]!r})")
                if all(_INT.match(p) for p in parts):
                    ids = [int(p) for p in parts]  # pre-tokenized
                else:
                    if tok is None:
                        if not args.tokenizer:
                            raise SystemExit(
                                f"{args.captions}:{ln}: text caption needs "
                                "--tokenizer (HF name/path)")
                        from transformers import AutoTokenizer  # opt tooling
                        tok = AutoTokenizer.from_pretrained(args.tokenizer)
                    ids = tok(caption)["input_ids"]
                if len(ids) > args.seq_len:
                    # keep the FINAL token when truncating: CLIP pools the
                    # text tower at the EOT position (argmax of ids), and a
                    # plain tail-chop would drop it — `classify` refuses
                    # exactly this silent truncation (see its context-length
                    # guard); the training-data writer must not do it either
                    ids = list(ids[:args.seq_len - 1]) + [ids[-1]]
                writer.write(encode_example(
                    {"image": (src / rel).read_bytes(),
                     "tokens": ids}))
    finally:
        writer.close()  # flush the open shard even on a mid-run error
    if not writer.total:
        raise SystemExit(f"no examples found under {src}")
    if classes:
        # written last: a failed run must not leave a plausible-looking
        # classes.json next to no (or partial) shards
        (out / "classes.json").write_text(json.dumps(classes, indent=2))
    print(f"wrote {writer.total} examples in {writer.shards} shard(s) "
          f"to {out}")
    return 0


def cmd_classify(args: argparse.Namespace) -> int:
    """Zero-shot image classification with CLIP/SigLIP (the reference's
    `examples/clip_inference.py` flow as a command).

    Label prompts come from ``--labels`` (tokenized via ``--tokenizer``, an
    optional HF tokenizer — tooling only, never a runtime dependency) or
    from ``--tokens-file`` (JSON ``{label: [token ids]}``, fully offline).
    """
    _configure_backend(args)
    import json

    import jax.numpy as jnp
    import numpy as np

    from jimm_tpu.data.preprocess import (CLIP_MEAN, CLIP_STD, SIGLIP_MEAN,
                                          SIGLIP_STD, preprocess_batch)
    from jimm_tpu.data.records import decode_image, pad_tokens
    from jimm_tpu.serve.cache import class_embedding_cache, prompt_set_key
    from jimm_tpu.utils.zero_shot import (weights_from_rows,
                                          zero_shot_logits_from_features)

    model_cls = _model_cls(args.model)
    dtype = jnp.bfloat16 if args.bf16 else jnp.float32
    model = model_cls.from_pretrained(args.ckpt, dtype=dtype)
    cfg = model.config

    if args.tokens_file:
        if args.ensemble:
            raise SystemExit("--ensemble builds prompts from templates; it "
                             "needs --labels (+ a tokenizer), not "
                             "--tokens-file")
        table = json.loads(open(args.tokens_file).read())
        labels = list(table)
        rows = [table[k] for k in labels]
        for k, r in table.items():
            if len(r) > cfg.text.context_length:
                # silent truncation could drop the EOT token CLIP pools at
                raise SystemExit(
                    f"tokens for {k!r} are {len(r)} ids but the checkpoint's "
                    f"context_length is {cfg.text.context_length}; "
                    "re-tokenize to fit")
    else:
        if not args.labels:
            raise SystemExit("need --labels (with --tokenizer or a CLIP "
                             "checkpoint dir holding vocab.json/merges.txt), "
                             "or --tokens-file")
        labels = [s.strip() for s in args.labels.split(",") if s.strip()]
        template = args.template or "a photo of a {}"
        if args.ensemble:
            # CLIP-paper recipe: average each class over prompt templates
            # (normalize, mean, renormalize); an explicit --template
            # supplies the set ("|"-separated; a single entry works), else
            # the builtin 7-template subset
            from jimm_tpu.utils.zero_shot import TEMPLATES, expand_templates
            templates = (tuple(t for t in args.template.split("|") if t)
                         if args.template else TEMPLATES)
            prompts = expand_templates(labels, templates)
        else:
            prompts = [template.format(label) for label in labels]
        rows = None
        if not args.tokenizer and args.model == "clip":
            # zero-dependency path: every HF CLIP checkpoint ships its BPE
            # vocab; use the built-in tokenizer when the files are local
            from pathlib import Path

            from jimm_tpu.data.clip_tokenizer import CLIPTokenizer
            p = Path(args.ckpt)
            d = p if p.is_dir() else p.parent
            if (d / "vocab.json").is_file() and (d / "merges.txt").is_file():
                rows = CLIPTokenizer.from_dir(d)(
                    prompts, context_length=cfg.text.context_length)
        if rows is None:
            if not args.tokenizer:
                raise SystemExit(
                    "no vocab.json/merges.txt next to the checkpoint; pass "
                    "--tokenizer (HF name/path) or --tokens-file")
            from transformers import AutoTokenizer  # optional tooling
            tok = AutoTokenizer.from_pretrained(args.tokenizer)
            rows = tok(prompts, padding="max_length", truncation=True,
                       max_length=cfg.text.context_length)["input_ids"]
    text = jnp.asarray(np.stack(
        [pad_tokens(r, cfg.text.context_length) for r in rows]))

    # class weights go through the serving embedding cache, keyed on
    # (checkpoint, family, dtype, token rows): repeat classify calls in one
    # process — and the `jimm-tpu serve` endpoint — skip the text tower.
    # Non-ensemble is the one-row-per-class special case of the same
    # normalize/mean/renormalize math, so every path shares one matmul form.
    if args.ensemble:
        n_templates = text.shape[0] // len(labels)
        owner = [i // n_templates for i in range(text.shape[0])]
    else:
        owner = list(range(len(labels)))
    model_key = (f"{args.model}:{args.ckpt}:"
                 f"{'bf16' if args.bf16 else 'f32'}")
    if args.index:
        # persistent tier: the retrieval store's prompt cache survives
        # process restarts, so repeat CLI invocations skip the text tower
        # entirely (same get_or_build surface as the in-process cache)
        from jimm_tpu.retrieval import VectorStore
        cache = VectorStore(args.index).prompt_cache()
    else:
        cache = class_embedding_cache()
    weights = cache.get_or_build(
        prompt_set_key(model_key, np.asarray(text)),
        lambda: np.asarray(
            weights_from_rows(model, text, owner, len(labels)), np.float32))

    with open(args.image, "rb") as f:
        img = decode_image(f.read())
    mean, std = ((CLIP_MEAN, CLIP_STD) if args.model == "clip"
                 else (SIGLIP_MEAN, SIGLIP_STD))
    if args.naflex:
        # variable-resolution path: aspect-preserving patch grid + mask
        # instead of the square resize (SigLIP2 NaFlex; beyond the
        # reference's non-NaFlex-only support)
        if args.model != "siglip":
            raise SystemExit("--naflex is a SigLIP2 feature; use "
                             "--model siglip")
        from jimm_tpu.data.naflex import patchify_naflex
        from jimm_tpu.data.preprocess import to_float_normalized
        im = to_float_normalized(img[None], mean, std)[0]
        patches, shapes, mask = patchify_naflex(
            [im], patch_size=cfg.vision.patch_size,
            max_num_patches=cfg.vision.num_patches)
        feats = model.encode_image_naflex(
            jnp.asarray(patches, dtype), jnp.asarray(shapes),
            jnp.asarray(mask))
    else:
        # CLIP checkpoints are trained with shortest-side resize + center
        # crop; SigLIP's processor resizes straight to the square
        batch = preprocess_batch(img[None],
                                 image_size=cfg.vision.image_size,
                                 mean=mean, std=std,
                                 crop=args.model == "clip")
        feats = model.encode_image(jnp.asarray(batch, dtype))
    logits = np.asarray(zero_shot_logits_from_features(
        model, feats, jnp.asarray(weights)), np.float32)[0]
    if args.model == "siglip":
        scores = 1.0 / (1.0 + np.exp(-logits))  # per-pair sigmoid
    else:
        e = np.exp(logits - logits.max())
        scores = e / e.sum()
    for i in np.argsort(-scores):
        print(f"{scores[i]:8.4f}  {labels[i]}")
    return 0


def _model_save(model, args: argparse.Namespace) -> None:
    """Model-method export (flavor-aware for SigLIP): --flavor picks the
    HF format for SigLIP2-origin checkpoints; default matches the source."""
    flavor = getattr(args, "flavor", "auto")
    if flavor != "auto" and not hasattr(model, "_save_pretrained_siglip2"):
        raise SystemExit("--flavor applies to SigLIP models only")
    if flavor == "auto":
        model.save_pretrained(args.out)
    else:
        model.save_pretrained(args.out, flavor=flavor)


def cmd_export(args: argparse.Namespace) -> int:
    _configure_backend(args)
    import jax.numpy as jnp

    dtype = jnp.bfloat16 if args.bf16 else jnp.float32
    model = _model_cls(args.model).from_pretrained(args.src, dtype=dtype)
    _model_save(model, args)
    print(f"exported {args.src} -> {args.out}")
    return 0


def cmd_inspect(args: argparse.Namespace) -> int:
    import math

    from jimm_tpu.weights.safetensors_io import read_header
    header, _ = read_header(args.file)
    total = 0
    for name, meta in sorted(header.items()):
        if name == "__metadata__":
            continue
        shape, dtype = meta["shape"], meta["dtype"]
        total += math.prod(int(s) for s in shape)
        print(f"{name:60s} {dtype:10s} {tuple(shape)}")
    print(f"-- {total / 1e6:.1f}M parameters")
    return 0


def _positive_int(v: str) -> int:
    n = int(v)
    if n < 1:
        raise argparse.ArgumentTypeError(f"must be >= 1, got {n}")
    return n


def cmd_profile_analyze(args: argparse.Namespace) -> int:
    """Offline per-op summary of a jax.profiler capture (no TensorBoard)."""
    from jimm_tpu.train.profile import op_stats, summarize
    device = None if args.device < 0 else args.device
    print(summarize(op_stats(args.dir, device=device), top=args.top,
                    steps=args.steps))
    return 0


def cmd_build_native(args: argparse.Namespace) -> int:
    """Compile the native host-preprocessing library (g++, no deps)."""
    import pathlib
    import subprocess
    native_dir = pathlib.Path(__file__).resolve().parents[1] / "native"
    rc = subprocess.call(["make", "-C", str(native_dir)])
    if rc == 0:
        from jimm_tpu.data.preprocess import _load_library
        ok = _load_library() is not None
        print("native preprocessing library built and loadable"
              if ok else "built, but failed to load")
        return 0 if ok else 1
    return rc


def cmd_bench_forward(args: argparse.Namespace) -> int:
    _configure_backend(args)
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax import nnx

    from jimm_tpu import preset
    from jimm_tpu.utils import jit_forward

    fam = _family(args.preset)
    cfg = preset(args.preset)
    if args.tiny:
        cfg = _tiny_override(cfg)
    dtype = jnp.bfloat16 if args.bf16 else jnp.float32
    model = _model_cls(fam)(cfg, rngs=nnx.Rngs(0), dtype=dtype, param_dtype=dtype)
    fwd = jit_forward(model)

    rng = np.random.RandomState(0)
    images = jnp.asarray(rng.randn(args.batch_size, cfg.vision.image_size,
                                   cfg.vision.image_size, 3), dtype)
    inputs = (images,)
    if fam in ("clip", "siglip"):
        text = jnp.asarray(rng.randint(1, cfg.text.vocab_size,
                                       size=(args.batch_size,
                                             cfg.text.context_length)),
                           jnp.int32)
        inputs = (images, text)

    out = fwd(*inputs)
    jax.device_get(out)
    t0 = time.perf_counter()
    for _ in range(args.steps):
        out = fwd(*inputs)
    jax.device_get(out)
    dt = (time.perf_counter() - t0) / args.steps
    print(f"{args.preset}: {args.batch_size / dt:.1f} images/sec "
          f"({dt * 1e3:.2f} ms/batch of {args.batch_size})")
    return 0


def _parse_pool_model(spec: str) -> tuple[str, str, str]:
    """Parse one ``--pool-model NAME=PRESET[@DTYPE]`` spec into
    ``(name, preset, dtype)``. DTYPE defaults to f32."""
    name, sep, rest = spec.partition("=")
    if not sep or not name or not rest:
        raise SystemExit(f"--pool-model {spec!r}: expected "
                         "NAME=PRESET[@DTYPE]")
    if name == "default":
        raise SystemExit("--pool-model: 'default' names the primary model; "
                         "pick another name")
    preset_name, _, dtype = rest.partition("@")
    dtype = dtype or "f32"
    if dtype not in ("f32", "bf16", "int8"):
        raise SystemExit(f"--pool-model {spec!r}: dtype must be "
                         "f32|bf16|int8")
    return name, preset_name, dtype


def cmd_serve(args: argparse.Namespace) -> int:
    """HTTP micro-batching inference server (see docs/serving.md).

    Loads a checkpoint (or random-initializes a preset — wiring and latency
    smoke tests without weights), warm-compiles every batch bucket, then
    serves ``/v1/embed`` and ``/v1/classify`` with bounded-queue admission
    control. ``/healthz`` and ``/metrics`` report engine state.
    """
    _configure_backend(args)
    _configure_journal(args)
    import json
    import time

    import jax.numpy as jnp
    from flax import nnx

    from jimm_tpu import preset
    from jimm_tpu.serve import (AdmissionPolicy, BucketTable, InferenceEngine,
                                ServingServer, ZeroShotService,
                                counting_forward, default_buckets)

    if args.tune_cache:
        # point kernel block resolution at an offline-tuned cache BEFORE any
        # trace: ops consult tune.best_config at trace time (lookup only —
        # serving never measures; populate with `jimm-tpu tune`)
        from jimm_tpu.tune import configure as tune_configure
        tune_configure(args.tune_cache)

    serve_dtype = _serve_dtype(args)
    # int8 builds/loads the model in f32, then quantizes in place below
    dtype = jnp.bfloat16 if serve_dtype == "bf16" else jnp.float32
    if args.ckpt:
        fam = args.model or (_family(args.preset) if args.preset else None)
        if fam is None:
            raise SystemExit("--ckpt needs --model (or --preset) to pick "
                             "the model family")
        model = _model_cls(fam).from_pretrained(args.ckpt, dtype=dtype)
        model_key = f"{fam}:{args.ckpt}"
    elif args.preset:
        fam = _family(args.preset)
        cfg = preset(args.preset)
        if args.tiny:
            cfg = _tiny_override(cfg)
        model = _model_cls(fam)(cfg, rngs=nnx.Rngs(0), dtype=dtype,
                                param_dtype=dtype)
        model_key = f"{fam}:{args.preset}" + (":tiny" if args.tiny else "")
    else:
        raise SystemExit("need --ckpt (with --model) or --preset")
    model_key += ":" + serve_dtype
    if serve_dtype == "int8":
        if args.model_parallel > 1:
            raise SystemExit("--dtype int8 does not support "
                             "--model-parallel > 1 yet (QuantLinear params "
                             "carry no logical sharding axes); use data "
                             "replicas")
        # in-place Linear -> QuantLinear surgery BEFORE any forward is
        # built, so the warm compiles (and AOT fingerprints, via the
        # aggregate param_dtype) see the quantized model
        from jimm_tpu.quant import quantize_model
        quantize_model(model)

    method = "encode_image" if fam in ("clip", "siglip") else "__call__"
    size = model.config.vision.image_size
    store = None
    if args.aot_store:
        # store-first warm start: buckets precompiled by `jimm-tpu aot
        # warmup` deserialize instead of compiling; anything else compiles
        # fresh and is written through for the next restart
        from jimm_tpu.aot import ArtifactStore
        store = ArtifactStore(args.aot_store)
    from jimm_tpu.serve.topology import build_replica_forwards, plan_topology
    plan = plan_topology(args.replicas, args.model_parallel,
                         getattr(args, "seq_parallel", 1))

    def _build_forward(mdl, mdl_method, mdl_size, key):
        if not plan.is_trivial:
            # multi-chip serving: N replica groups of (data=1, model=k)
            # submeshes, each with its own sharded param copy + warm
            # forward, load-balanced behind the one admission queue
            return build_replica_forwards(
                mdl, plan, method=mdl_method,
                item_shape=(mdl_size, mdl_size, 3), store=store, label=key)
        if store is not None:
            from jimm_tpu.aot.warmup import AotForward
            fwd = AotForward(mdl, method=mdl_method,
                             item_shape=(mdl_size, mdl_size, 3),
                             store=store, label=key)
            return fwd, fwd.trace_count
        return counting_forward(mdl, mdl_method)

    forward, trace_count = _build_forward(model, method, size, model_key)
    _bucket_dtypes = {"f32": "float32", "bf16": "bfloat16", "int8": "int8"}
    bucket_dtype = _bucket_dtypes[serve_dtype]
    buckets = (BucketTable(tuple(int(s) for s in args.buckets.split(",")),
                           dtype=bucket_dtype)
               if args.buckets else default_buckets(dtype=bucket_dtype))
    policy = AdmissionPolicy(max_queue=args.queue_size,
                             default_timeout_s=args.timeout_s,
                             shed_fraction=args.shed_fraction)
    qos = None
    if args.qos_policy:
        # tenant-aware admission + weighted-fair scheduling; without the
        # flag `qos` stays None and every serve path below is byte-
        # identical to the policy-free server
        from jimm_tpu.serve.qos import QosScheduler, load_policy
        qos = QosScheduler(load_policy(args.qos_policy))
    engine = InferenceEngine(forward, item_shape=(size, size, 3),
                             buckets=buckets,
                             max_delay_ms=args.max_delay_ms, policy=policy,
                             trace_count=trace_count, qos=qos)
    if qos is not None and qos.registry.slo:
        # the policy's slo section -> per-tenant burn-rate tracking; a
        # fast burn escalates into the self-heal path and flips /healthz
        from jimm_tpu.obs.slo import SloEngine
        engine.attach_slo(SloEngine.from_objective_dicts(qos.registry.slo))
    if args.self_heal:
        if plan.is_trivial:
            raise SystemExit("--self-heal needs a replica topology "
                             "(--replicas/--model-parallel > 1): a single "
                             "lane has nothing to replan around")
        # watchdog escalation: fence -> probe/revive -> rebuild the full
        # replica set from the AOT store and replan around the dead lane.
        # The factory reuses _build_forward, so a warm store means the
        # rebuild deserializes executables — zero fresh traces.
        engine.set_heal(
            lambda: _build_forward(model, method, size, model_key))
    pool = None
    pool_traces = []
    pool_models = [model]
    if args.pool_model:
        # multi-model residency: each extra model gets its own warm engine
        # (own buckets + own AOT fingerprint via its model_key, so the
        # f32/int8 twins never adopt each other's executables) behind the
        # same metrics surface and QoS scheduler; requests route with the
        # `model=` field / X-Jimm-Model header
        from jimm_tpu.serve.qos import ModelPool
        engines = {"default": engine}
        for spec in args.pool_model:
            pname, ppreset, pdtype = _parse_pool_model(spec)
            if pname in engines:
                raise SystemExit(f"--pool-model: duplicate name {pname!r}")
            pfam = _family(ppreset)
            pcfg = preset(ppreset)
            if args.tiny:
                pcfg = _tiny_override(pcfg)
            pjdtype = jnp.bfloat16 if pdtype == "bf16" else jnp.float32
            pmodel = _model_cls(pfam)(pcfg, rngs=nnx.Rngs(0), dtype=pjdtype,
                                      param_dtype=pjdtype)
            pkey = (f"{pfam}:{ppreset}" + (":tiny" if args.tiny else "")
                    + ":" + pdtype)
            if pdtype == "int8":
                if args.model_parallel > 1:
                    raise SystemExit(
                        f"--pool-model {pname}: int8 does not support "
                        "--model-parallel > 1 (same constraint as --dtype "
                        "int8); use data replicas")
                from jimm_tpu.quant import quantize_model
                quantize_model(pmodel)
            pmethod = ("encode_image" if pfam in ("clip", "siglip")
                       else "__call__")
            psize = pmodel.config.vision.image_size
            pforward, ptrace = _build_forward(pmodel, pmethod, psize, pkey)
            pengine = InferenceEngine(
                pforward, item_shape=(psize, psize, 3),
                buckets=BucketTable(buckets.sizes,
                                    dtype=_bucket_dtypes[pdtype]),
                max_delay_ms=args.max_delay_ms, policy=policy,
                metrics=engine.metrics, qos=qos)
            # per-model compile gauge (the bare `compile_count` gauge stays
            # the default model's, bound above via trace_count=)
            engine.metrics.bind_gauge(f"model_{pname}_compile_count", ptrace)
            pool_traces.append(ptrace)
            pool_models.append(pmodel)
            engines[pname] = pengine
        pool = ModelPool(engines, default="default")
        # every extra engine's __init__ re-bound queue_depth_now to its own
        # queue (latest wins); restore it to the default model's
        engine.metrics.bind_gauge(
            "queue_depth_now",
            lambda e=engine: (float(e._queue.qsize())
                              if e._queue is not None else 0.0))
    zero_shot = (ZeroShotService(model, model_key=model_key)
                 if fam in ("clip", "siglip") else None)
    retrieval = None
    index_daemon = None
    if args.index:
        if not args.index_store:
            raise SystemExit("--index needs --index-store (the vector "
                             "store root)")
        # /v1/search: load the named index snapshot and build its warm
        # searcher over the same topology (and AOT store) as the engine
        from jimm_tpu.retrieval import RetrievalService, VectorStore
        vstore = VectorStore(args.index_store)
        retrieval = RetrievalService.from_store(
            vstore, args.index, k=args.search_k, plan=plan,
            aot_store=store, mode=args.index_mode, nprobe=args.nprobe,
            nprobe_max=args.nprobe_max,
            device_budget_bytes=(args.tier_device_budget_mb << 20
                                 if args.tier_device_budget_mb is not None
                                 else None),
            host_budget_bytes=(args.tier_host_budget_mb << 20
                               if args.tier_host_budget_mb is not None
                               else None))
        if args.tier_daemon_interval is not None:
            if args.index_mode != "tiered":
                raise SystemExit("--tier-daemon-interval needs "
                                 "--index-mode tiered")
            from jimm_tpu.retrieval.tier import IndexDaemon
            index_daemon = IndexDaemon(vstore, args.index,
                                       retrieval.searcher)
            index_daemon.start(args.tier_daemon_interval)
    elif args.index_store:
        raise SystemExit("--index-store needs --index (the index name)")
    logger = None
    if args.metrics_file:
        from jimm_tpu.train.metrics import MetricsLogger
        logger = MetricsLogger(path=args.metrics_file,
                               print_every=10 ** 9)  # JSONL only, no console
    monitor = None
    if args.prof_dir:
        # continuous profiling + HBM watchdog: the capture manager is
        # process-global so heal/replan/SLO-burn paths (and POST
        # /admin/prof/trigger) deep-capture onto their incident cids
        from jimm_tpu.obs.prof.capture import configure_capture
        from jimm_tpu.obs.prof.memory import MemoryMonitor
        configure_capture(args.prof_dir)
        monitor = MemoryMonitor()

        def _model_pool_bytes() -> float:
            import jax
            total = 0.0
            for m in pool_models:
                for leaf in jax.tree_util.tree_leaves(nnx.state(m)):
                    total += float(getattr(leaf, "nbytes", 0) or 0)
            return total

        monitor.register_subsystem("model_pool", _model_pool_bytes)
        monitor.register_subsystem(
            "serve_buffers", lambda: float(engine._traces_bytes))
        if retrieval is not None:
            info = retrieval.describe()
            if info["mode"] == "tiered":
                # tiered residency: report the (flat) hot-arena bytes,
                # not the corpus size the budget exists to decouple from
                monitor.register_subsystem(
                    "retrieval_index",
                    lambda s=retrieval.searcher: float(s.resident_bytes()))
            else:
                monitor.register_subsystem(
                    "retrieval_index",
                    lambda r=info["rows"], d=info["dim"]: float(r * d * 4))
        monitor.start()
    server = ServingServer(engine, zero_shot=zero_shot,
                           retrieval=retrieval, host=args.host,
                           port=args.port, metrics_logger=logger,
                           metrics_log_every_s=args.metrics_every_s,
                           pool=pool)
    t0 = time.monotonic()
    server.start()
    ready = {"status": "serving", "host": args.host,
             "port": server.port, "model": model_key,
             "buckets": list(buckets.sizes), "dtype": buckets.dtype,
             "warmup_s": round(time.monotonic() - t0, 3),
             "compile_count": trace_count() + sum(t() for t in pool_traces)}
    if qos is not None:
        ready["qos"] = {"policy": args.qos_policy,
                        "classes": list(qos.registry.class_order),
                        "tenants": sorted(qos.registry.tenants)}
        if qos.registry.slo:
            ready["qos"]["slo"] = sorted(qos.registry.slo)
    if pool is not None:
        ready["models"] = pool.describe()
    if not plan.is_trivial:
        ready["topology"] = plan.describe()
    if args.aot_store:
        ready["aot"] = {str(k): v["source"]
                        for k, v in sorted(engine.warmup_report.items())}
    if retrieval is not None:
        info = retrieval.describe()
        ready["retrieval"] = {"index": info["index"], "rows": info["rows"],
                              "dim": info["dim"], "k": info["k"],
                              "block_n": info["block_n"],
                              "partitions": info["partitions"],
                              "mode": info["mode"]}
        if info["mode"] in ("ivf", "tiered"):
            ready["retrieval"]["nprobe"] = info["nprobe"]
            ready["retrieval"]["nprobe_max"] = info["nprobe_max"]
            ready["retrieval"]["clusters"] = info["clusters"]
        if info["mode"] == "tiered":
            ready["retrieval"]["resident_bytes"] = info["resident_bytes"]
            ready["retrieval"]["tiers"] = info["tiers"]
            if index_daemon is not None:
                ready["retrieval"]["daemon"] = index_daemon.describe()
        if args.aot_store:
            ready["retrieval"]["aot"] = {
                str(b): s for b, s in sorted(
                    retrieval.searcher.warmup_report.items())}
    print(json.dumps(ready), flush=True)
    try:
        if args.max_seconds:
            time.sleep(args.max_seconds)
            server.stop()
        else:
            server.serve_forever()
    finally:
        if index_daemon is not None:
            index_daemon.stop()
        if monitor is not None:
            monitor.stop()
        if args.prof_dir:
            from jimm_tpu.obs.prof.capture import get_capture_manager
            mgr = get_capture_manager()
            if mgr is not None:
                mgr.flush()
    return 0


# ---------------------------------------------------------------------------
# Parser
# ---------------------------------------------------------------------------

def _add_backend_flags(p: argparse.ArgumentParser) -> None:
    p.add_argument("--platform", choices=["cpu", "tpu"], default=None,
                   help="force a JAX backend (default: environment)")
    p.add_argument("--host-devices", type=int, default=None,
                   help="virtual CPU device count (for mesh testing)")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="jimm_tpu")
    sub = p.add_subparsers(dest="cmd", required=True)

    sp = sub.add_parser("presets", help="list named model presets")
    _add_backend_flags(sp)
    sp.set_defaults(fn=cmd_presets)

    sp = sub.add_parser("train", help="train on synthetic data (offline)")
    sp.add_argument("--preset", required=True)
    sp.add_argument("--tiny", action="store_true",
                    help="shrink the preset to CPU-demo size")
    sp.add_argument("--from-pretrained", default=None,
                    help="fine-tune from an HF checkpoint (local file/dir "
                         "or hub id); --preset then only names the family")
    sp.add_argument("--image-size", type=int, default=None,
                    help="with --from-pretrained: load at a different "
                         "resolution (pos-embed grid interpolation)")
    sp.add_argument("--steps", type=int, default=100)
    sp.add_argument("--batch-size", type=int, default=32)
    sp.add_argument("--data", default=None,
                    help="tfrecord file/dir/glob with image+label (vit) or "
                         "image+tokens (clip/siglip) examples; default: "
                         "procedural synthetic data")
    sp.add_argument("--shuffle-buffer", type=int, default=256,
                    help="example shuffle-buffer size for --data "
                         "(records loader)")
    sp.add_argument("--loader", default="records",
                    choices=["records", "grain"],
                    help="--data pipeline: 'records' (generator, buffer "
                         "shuffle) or 'grain' (parallel workers, global "
                         "shuffle, checkpointable iteration)")
    sp.add_argument("--data-workers", type=int, default=0,
                    help="grain loader subprocess count (0 = in-process)")
    sp.add_argument("--num-classes", type=int, default=None,
                    help="override classifier width (vit + --data)")
    sp.add_argument("--num-layers", type=int, default=None,
                    help="language model: train the preset with its first "
                         "N layers (one pipeline stage's share)")
    sp.add_argument("--seq-len", type=int, default=None,
                    help="language model: tokens of a training sequence")
    sp.add_argument("--lr", type=float, default=None,
                    help="peak learning rate (default 1e-3; a language-model "
                         "preset 1e-4)")
    sp.add_argument("--weight-decay", type=float, default=1e-4)
    sp.add_argument("--warmup-steps", type=int, default=None,
                    help="linear warm-up steps (default 0; a language-model "
                         "preset 20)")
    sp.add_argument("--seed", type=int, default=0)
    sp.add_argument("--bf16", action="store_true")
    sp.add_argument("--compilation-cache-dir", default=None,
                    help="persist XLA compiles to this dir (jax "
                         "compilation cache) so restarted runs skip the "
                         "train-step compile; JAX_COMPILATION_CACHE_DIR, "
                         "when set, wins over this flag")
    sp.add_argument("--mesh", default=None,
                    help='e.g. "data=4,model=2" or "data=2,model=1,seq=4" '
                         '(a seq axis turns on sequence-parallel attention '
                         'and joins the ring losses; default: no mesh)')
    sp.add_argument("--max-devices", type=int, default=None,
                    help="build the mesh over only the first N visible "
                         "devices (elastic restarts: a shrunk attempt plans "
                         "over the surviving subset and restore reshards "
                         "the checkpoint onto it)")
    sp.add_argument("--rules", default=None,
                    choices=["replicated", "dp", "tp", "fsdp",
                             "fsdp_tp", "sp", "fsdp_sp", "pp"],
                    help="sharding rules preset (requires --mesh)")
    sp.add_argument("--loss", default=None,
                    choices=["clip", "clip_ring", "siglip", "siglip_ring"])
    sp.add_argument("--naflex", action="store_true",
                    help="variable-resolution SigLIP2 training: NaFlex "
                         "(patches, shapes, mask) batches from tfrecords "
                         "(or synthetic mixed-aspect data) instead of "
                         "square images")
    sp.add_argument("--attn-impl", default=None,
                    choices=["auto", "xla", "flash", "flash_int8", "ring",
                             "ulysses", "saveable"],
                    help="attention kernel for both towers "
                         "(ring/ulysses = sequence-parallel over a seq mesh "
                         "axis: ppermute kv ring vs all-to-all head "
                         "redistribution; "
                         "flash_int8 = int8-QK flash, fwd+bwd; "
                         "saveable = checkpoint-named probs for --remat "
                         "dots+attn)")
    sp.add_argument("--precision", default=None,
                    choices=["bf16", "fp8_hybrid", "int8_qk"],
                    help="training precision policy: bf16 (as built), "
                         "fp8_hybrid (eligible Linears matmul in e4m3 fwd / "
                         "e5m2 grad with delayed per-tensor scaling), "
                         "int8_qk (attention via the int8-QK flash kernel)")
    sp.add_argument("--remat", default=None,
                    help="activation remat in the layer scan: none (off), "
                         "full (recompute all), or dots with +ln/+act/+attn "
                         "suffixes (save matmul [+layernorm][+activation]"
                         "[+attention-prob] outputs)")
    sp.add_argument("--ln-impl", default=None, choices=["xla", "fused"],
                    help="LayerNorm kernel (fused = one-pass Pallas)")
    sp.add_argument("--fused-qkv", action="store_true",
                    help="q/k/v as one (H, 3H) matmul")
    sp.add_argument("--bf16-momentum", action="store_true",
                    help="keep Adam's first moment in bfloat16 (halves that "
                         "buffer's HBM footprint and traffic)")
    sp.add_argument("--moment-dtype", default=None, choices=["f32", "bf16"],
                    help="Adam first-moment dtype (OptimizerConfig."
                         "moment_dtype); wins over --bf16-momentum and is "
                         "stamped on the goodput line")
    sp.add_argument("--pipeline-microbatches", type=int, default=0,
                    help="enable pipeline parallelism with N microbatches "
                         "(needs a 'stage' mesh axis and --rules pp)")
    sp.add_argument("--pipeline-virtual", type=int, default=1,
                    help="interleaved PP: virtual chunks per stage "
                         "(circular placement; shrinks the bubble ~Vx)")
    sp.add_argument("--scan-unroll", type=int, default=0,
                    help="layer-scan unroll factor (0 = auto: full unroll "
                         "on TPU for better XLA scheduling, 1 on CPU)")
    sp.add_argument("--ckpt-dir", default=None)
    sp.add_argument("--resume", action="store_true")
    sp.add_argument("--fake-failure-at-step", type=int, default=None,
                    help="failure drill: crash after checkpointing this step "
                         "(recover with --resume); sugar for "
                         "--inject-faults crash@STEP")
    sp.add_argument("--inject-faults", default=None,
                    help="deterministic fault drill plan: comma-separated "
                         "kind@STEP entries — preempt@N (SIGTERM to self), "
                         "crash@N (hard failure after N's checkpoint), "
                         "stall@N:SECONDS (slow-host sleep), corrupt@N "
                         "(garbage the newest committed checkpoint)")
    sp.add_argument("--preemption-save", action="store_true",
                    help="catch SIGTERM and spend the grace window on an "
                         "async checkpoint save overlapping the next "
                         "--grace-steps steps, then exit resumable "
                         "(needs --ckpt-dir)")
    sp.add_argument("--grace-steps", type=int, default=1,
                    help="training steps to overlap with the preemption "
                         "save before exiting (0 = save and exit at once)")
    sp.add_argument("--batch-fingerprint", action="store_true",
                    help="log a content hash of every consumed batch to the "
                         "metrics stream (proves zero-replay/zero-skip "
                         "resume; pulls each batch to host)")
    sp.add_argument("--save-every", type=int, default=50)
    sp.add_argument("--log-every", type=int, default=10)
    sp.add_argument("--metrics-file", default=None,
                    help="JSONL metrics output path")
    sp.add_argument("--tensorboard-dir", default=None,
                    help="write TensorBoard scalar events here")
    sp.add_argument("--profile-dir", default=None,
                    help="capture a jax.profiler trace of steps 2-4 here")
    sp.add_argument("--prof-ring", default=None, metavar="DIR",
                    help="continuous profiling: keep a bounded on-disk "
                         "ring of short step-window captures here, and "
                         "accept anomaly-triggered deep captures "
                         "(jimm-tpu obs prof ls/show/diff)")
    sp.add_argument("--prof-every", type=int, default=200,
                    help="capture a ring window every N steps")
    sp.add_argument("--prof-window", type=int, default=2,
                    help="steps per ring window capture")
    sp.add_argument("--prof-ring-bytes", type=int, default=64 << 20,
                    help="ring byte budget; oldest captures evicted")
    sp.add_argument("--journal", default=None, metavar="FILE",
                    help="persist flight-recorder events (preemption, "
                         "checkpoint, reshard) to this rotating JSONL "
                         "journal")
    _add_backend_flags(sp)
    sp.set_defaults(fn=cmd_train)

    sp = sub.add_parser("supervise",
                        help="run train as restartable attempts "
                             "(preemption/crash -> backoff -> --resume)")
    sp.add_argument("--max-restarts", type=int, default=3,
                    help="restarts before giving up")
    sp.add_argument("--backoff-base-s", type=float, default=1.0)
    sp.add_argument("--backoff-max-s", type=float, default=30.0)
    sp.add_argument("--seed", type=int, default=None,
                    help="seed the restart-backoff jitter "
                         "(reproducible drills)")
    sp.add_argument("--elastic", action="store_true",
                    help="replan the mesh from surviving devices before "
                         "every attempt (--mesh data=K --max-devices K "
                         "appended to the train command); restore reshards "
                         "the checkpoint onto the new shape")
    sp.add_argument("--shrink-plan", default=None,
                    help="elastic drill: comma-separated device budgets per "
                         "attempt, e.g. 8,4 = first attempt sees 8 devices, "
                         "every later attempt 4 (simulates losing hosts)")
    sp.add_argument("--adapt", action="store_true",
                    help="run the GoodputAdvisor over per-attempt goodput "
                         "breakdowns and carry its bounded knob decisions "
                         "(--save-every/--grace-steps/--scan-unroll) into "
                         "the next attempt")
    sp.add_argument("--journal", default=None, metavar="FILE",
                    help="persist flight-recorder events (attempts, "
                         "restarts, replans, advisor decisions) to this "
                         "rotating JSONL journal — one correlated incident "
                         "chain per failure")
    sp.add_argument("train_args", nargs=argparse.REMAINDER,
                    help="-- train --preset ... --ckpt-dir ...")
    sp.set_defaults(fn=cmd_supervise)

    sp = sub.add_parser("evaluate",
                        help="accuracy / retrieval metrics over a dataset")
    sp.add_argument("--data", required=True,
                    help="tfrecord file/dir/glob (single pass, no repeat)")
    sp.add_argument("--batch-size", type=int, default=32)
    sp.add_argument("--ckpt", default=None,
                    help="HF checkpoint (local file/dir or hub id)")
    sp.add_argument("--model", default=None,
                    choices=["vit", "clip", "siglip"],
                    help="model family for --ckpt (else from --preset name)")
    sp.add_argument("--preset", default=None)
    sp.add_argument("--tiny", action="store_true")
    sp.add_argument("--ckpt-dir", default=None,
                    help="orbax training checkpoint (with --preset)")
    sp.add_argument("--from-pretrained", default=None,
                    help="with --ckpt-dir: the HF checkpoint the training "
                         "run fine-tuned from (rebuilds that architecture)")
    sp.add_argument("--zero-shot", default=None, metavar="TOKENS_JSON",
                    help="zero-shot classification accuracy over labeled "
                         "records (clip/siglip): {label: [ids]} or "
                         "{label: [[ids], ...]} for prompt ensembles; "
                         "class order from the dataset's classes.json")
    sp.add_argument("--naflex", action="store_true",
                    help="SigLIP2 retrieval over NaFlex variable-resolution "
                         "batches (aspect-preserving) instead of the square "
                         "resize")
    sp.add_argument("--image-size", type=int, default=None,
                    help="with --from-pretrained: the --image-size the "
                         "training run used")
    sp.add_argument("--num-classes", type=int, default=None,
                    help="classifier width of the trained head (vit + "
                         "--ckpt-dir; default: classes.json next to --data)")
    sp.add_argument("--bf16", action="store_true")
    _add_backend_flags(sp)
    sp.set_defaults(fn=cmd_evaluate)

    sp = sub.add_parser("classify",
                        help="zero-shot image classification (CLIP/SigLIP)")
    sp.add_argument("image", help="image file (PNG/JPEG)")
    sp.add_argument("--ckpt", required=True,
                    help="checkpoint: local safetensors file/dir or HF repo")
    sp.add_argument("--model", default="clip", choices=["clip", "siglip"])
    sp.add_argument("--labels", default=None,
                    help='comma-separated label names, e.g. "cat,dog"')
    sp.add_argument("--template", default=None,
                    help="prompt template applied to each label (default "
                         "'a photo of a {}'); with --ensemble, a "
                         "\"|\"-separated template set")
    sp.add_argument("--tokenizer", default=None,
                    help="HF tokenizer for --labels (optional tooling)")
    sp.add_argument("--tokens-file", default=None,
                    help="JSON {label: [token ids]} — offline alternative "
                         "to --tokenizer")
    sp.add_argument("--ensemble", action="store_true",
                    help="prompt-template ensemble per class (the CLIP-"
                         "paper recipe): normalize/mean/renormalize text "
                         "embeddings over templates; --template with "
                         "\"|\"-separated entries overrides the builtin set")
    sp.add_argument("--naflex", action="store_true",
                    help="SigLIP2 NaFlex path: keep the image's aspect "
                         "ratio (variable-resolution patches + mask) "
                         "instead of squashing to the square")
    sp.add_argument("--index", default=None, metavar="STORE",
                    help="retrieval vector-store root to persist class "
                         "embeddings in: repeat invocations (across "
                         "processes) skip the text tower")
    sp.add_argument("--bf16", action="store_true")
    _add_backend_flags(sp)
    sp.set_defaults(fn=cmd_classify)

    sp = sub.add_parser("prepare-data",
                        help="build tfrecord shards from raw image files")
    sp.add_argument("src", help="source directory (class dirs, or images)")
    sp.add_argument("out", help="output directory for part-*.tfrecord")
    sp.add_argument("--task", default="classification",
                    choices=["classification", "contrastive"])
    sp.add_argument("--captions", default=None,
                    help="TSV: relative/path<TAB>caption (contrastive)")
    sp.add_argument("--tokenizer", default=None,
                    help="HF tokenizer for text captions (optional tooling; "
                         "integer captions are used as pre-tokenized ids)")
    sp.add_argument("--seq-len", type=int, default=64,
                    help="truncate token ids to this length")
    sp.add_argument("--shard-size", type=int, default=1000,
                    help="examples per tfrecord shard")
    sp.set_defaults(fn=cmd_prepare_data)

    sp = sub.add_parser("export",
                        help="load a checkpoint and save as HF safetensors")
    sp.add_argument("src", help="HF repo id, local file, or local dir")
    sp.add_argument("out", help="output directory")
    sp.add_argument("--model", required=True, choices=["vit", "clip", "siglip"])
    sp.add_argument("--flavor", default="auto",
                    choices=["auto", "siglip", "siglip2"],
                    help="SigLIP export format: auto = match the source "
                         "checkpoint (Siglip2-origin stays Siglip2Model-"
                         "loadable); siglip forces the v1 layout")
    sp.add_argument("--bf16", action="store_true")
    _add_backend_flags(sp)
    sp.set_defaults(fn=cmd_export)

    sp = sub.add_parser("export-run",
                        help="export a training run (orbax) as HF safetensors")
    sp.add_argument("out", help="output directory")
    sp.add_argument("--ckpt-dir", required=True,
                    help="orbax checkpoint directory of the run")
    sp.add_argument("--preset", required=True,
                    help="preset the run trained (or its family, with "
                         "--from-pretrained)")
    sp.add_argument("--flavor", default="auto",
                    choices=["auto", "siglip", "siglip2"],
                    help="SigLIP export format (see `export --flavor`)")
    sp.add_argument("--tiny", action="store_true")
    sp.add_argument("--from-pretrained", default=None,
                    help="HF checkpoint the run fine-tuned from")
    sp.add_argument("--image-size", type=int, default=None)
    sp.add_argument("--num-classes", type=int, default=None)
    sp.add_argument("--bf16", action="store_true")
    _add_backend_flags(sp)
    sp.set_defaults(fn=cmd_export_run)

    sp = sub.add_parser("inspect", help="list tensors in a safetensors file")
    sp.add_argument("file")
    sp.set_defaults(fn=cmd_inspect)

    sp = sub.add_parser("profile-analyze",
                        help="per-op summary of a jax.profiler trace dir")
    sp.add_argument("dir", help="--profile-dir of a train run")
    sp.add_argument("--top", type=int, default=25)
    sp.add_argument("--steps", type=_positive_int, default=1,
                    help="steps captured, to report per-step numbers")
    sp.add_argument("--device", type=int, default=0,
                    help="device index to report (-1 = sum across devices)")
    sp.set_defaults(fn=cmd_profile_analyze)

    sp = sub.add_parser("build-native",
                        help="compile native/libjimm_preprocess.so")
    sp.set_defaults(fn=cmd_build_native)

    sp = sub.add_parser("serve",
                        help="HTTP micro-batching inference server")
    sp.add_argument("--ckpt", default=None,
                    help="checkpoint: local safetensors file/dir or HF repo")
    sp.add_argument("--model", default=None,
                    choices=["vit", "clip", "siglip"],
                    help="model family of --ckpt")
    sp.add_argument("--preset", default=None,
                    help="random-init a preset instead of --ckpt (wiring/"
                         "latency smoke tests)")
    sp.add_argument("--tiny", action="store_true")
    sp.add_argument("--host", default="127.0.0.1")
    sp.add_argument("--port", type=int, default=8000,
                    help="listening port (0 = pick a free one)")
    sp.add_argument("--buckets", default=None,
                    help="comma-separated batch buckets to warm-compile, "
                         'e.g. "1,4,16,64" (default: platform table)')
    sp.add_argument("--max-delay-ms", type=float, default=5.0,
                    help="micro-batch coalescing window")
    sp.add_argument("--replicas", type=int, default=1,
                    help="independent serving replicas to partition the "
                         "visible devices into; micro-batches are load-"
                         "balanced across them (1 = classic single-device "
                         "serve)")
    sp.add_argument("--model-parallel", type=int, default=1,
                    help="devices per replica: each forward's params are "
                         "tensor-parallel over a (data=1, model=k) submesh "
                         "(big towers that don't fit one chip)")
    sp.add_argument("--seq-parallel", type=int, default=1,
                    help="sequence-parallel ways per replica: the submesh "
                         "grows a seq axis and attention runs ring/ulysses "
                         "across it (sequences too long for one chip; "
                         "composes with --model-parallel)")
    sp.add_argument("--self-heal", action="store_true",
                    help="escalate a watchdog fence: probe the fenced "
                         "replica (transient fault -> revive in place), "
                         "else rebuild the replica set from the AOT store "
                         "and replan around it live (zero fresh traces "
                         "when the store is warm)")
    sp.add_argument("--queue-size", type=int, default=256,
                    help="admission bound; requests past it get a 503 "
                         "queue_full")
    sp.add_argument("--timeout-s", type=float, default=5.0,
                    help="default per-request deadline")
    sp.add_argument("--shed-fraction", type=float, default=0.5,
                    help="queue fill fraction past which the batcher stops "
                         "waiting for stragglers")
    sp.add_argument("--max-seconds", type=float, default=None,
                    help="serve this long then exit (scripted smoke runs; "
                         "default: until Ctrl-C)")
    sp.add_argument("--metrics-file", default=None,
                    help="append metric snapshots as JSONL "
                         "(train/metrics.py format)")
    sp.add_argument("--metrics-every-s", type=float, default=10.0)
    sp.add_argument("--prof-dir", default=None, metavar="DIR",
                    help="continuous profiling + HBM watchdog: keep the "
                         "anomaly-triggered capture ring here (heal/replan/"
                         "SLO-burn incidents and POST /admin/prof/trigger "
                         "deep-capture onto their cids) and sample "
                         "jimm_hbm_* device-memory gauges")
    sp.add_argument("--bf16", action="store_true",
                    help="legacy spelling of --dtype bf16")
    sp.add_argument("--dtype", choices=["f32", "bf16", "int8"], default=None,
                    help="serving precision (default f32). int8 quantizes "
                         "the weights in place at startup (symmetric "
                         "per-channel) and dispatches the fused Pallas "
                         "int8 matmul path — docs/quantization.md")
    sp.add_argument("--aot-store", default=None,
                    help="consult this AOT artifact store before any "
                         "fresh compile (populate with `jimm-tpu aot "
                         "warmup`); misses are written through")
    sp.add_argument("--tune-cache", default=None,
                    help="resolve Pallas kernel block sizes from this "
                         "tuned-config cache (populate with `jimm-tpu "
                         "tune`); lookup only — misses fall back to safe "
                         "defaults, serving never measures")
    sp.add_argument("--index-store", default=None,
                    help="vector store root holding retrieval indexes "
                         "(populate with `jimm-tpu index build/add`); "
                         "enables /v1/search")
    sp.add_argument("--index", default=None,
                    help="index name inside --index-store to serve")
    sp.add_argument("--search-k", type=int, default=10,
                    help="compiled top-k carry width; /v1/search requests "
                         "may ask for any k up to this")
    sp.add_argument("--index-mode", default="exact",
                    choices=["exact", "ivf", "tiered"],
                    help="retrieval mode: exact streaming top-k, "
                         "two-stage IVF over the index's trained codebook "
                         "(train with `jimm-tpu index train-centroids`), "
                         "or tiered — IVF under an explicit device byte "
                         "budget with warm/cold spill to host RAM and the "
                         "store's artifact dir (docs/retrieval.md)")
    sp.add_argument("--nprobe", type=int, default=None,
                    help="ivf/tiered mode: default clusters probed per "
                         "query (requests may override up to --nprobe-max; "
                         "default: min(8, --nprobe-max))")
    sp.add_argument("--nprobe-max", type=int, default=32,
                    help="ivf/tiered mode: compiled probe-width ceiling — "
                         "any nprobe up to this reuses one program (a "
                         "runtime scalar, never a recompile)")
    sp.add_argument("--tier-device-budget-mb", type=int, default=None,
                    help="tiered mode: hot-arena HBM budget in MiB "
                         "(default 64); device-resident bytes stay flat "
                         "at this cap however large the corpus grows")
    sp.add_argument("--tier-host-budget-mb", type=int, default=None,
                    help="tiered mode: host-RAM budget for warm "
                         "full-precision rows; clusters past it spill to "
                         "disk segments (default: unbounded host)")
    sp.add_argument("--tier-daemon-interval", type=float, default=None,
                    metavar="SECONDS",
                    help="tiered mode: start the autonomous IndexDaemon "
                         "(retrain/build-ivf/compact/re-tier on staleness "
                         "and access drift) at this tick interval")
    sp.add_argument("--qos-policy", default=None, metavar="FILE",
                    help="tenant QoS policy (JSON/TOML): priority classes, "
                         "per-tenant token-bucket rate limits, and queue "
                         "quotas; enables weighted-fair scheduling and "
                         "class-ordered shedding (docs/qos.md). Without it "
                         "serving is byte-identical to the policy-free "
                         "server")
    sp.add_argument("--pool-model", action="append", default=None,
                    metavar="NAME=PRESET[@DTYPE]",
                    help="additional resident model (repeatable): random-"
                         "init PRESET at DTYPE (f32|bf16|int8, default "
                         "f32), warm its own engine + AOT fingerprint, and "
                         "route requests naming model=NAME to it; inherits "
                         "--tiny/--buckets/--aot-store")
    sp.add_argument("--journal", default=None, metavar="FILE",
                    help="persist flight-recorder events (replica faults, "
                         "fences, heals, replans, SLO burns) to this "
                         "rotating JSONL journal")
    _add_backend_flags(sp)
    sp.set_defaults(fn=cmd_serve)

    sp = sub.add_parser("bench-forward", help="jitted forward throughput")
    sp.add_argument("--preset", required=True)
    sp.add_argument("--tiny", action="store_true")
    sp.add_argument("--batch-size", type=int, default=32)
    sp.add_argument("--steps", type=int, default=20)
    sp.add_argument("--bf16", action="store_true")
    _add_backend_flags(sp)
    sp.set_defaults(fn=cmd_bench_forward)

    # jimm-tpu obs {snapshot,tail,diff} — pure-host metric tooling (no jax)
    from jimm_tpu.obs.cli import add_obs_parser
    add_obs_parser(sub)

    # jimm-tpu aot {warmup,ls,gc,verify} — AOT compile-artifact store
    from jimm_tpu.aot.cli import add_aot_parser
    add_aot_parser(sub)

    # jimm-tpu tune {run,ls} — persistent Pallas kernel autotuner
    from jimm_tpu.tune.cli import add_tune_parser
    add_tune_parser(sub)

    # jimm-tpu index {build,add,ls,verify,compact} — retrieval stores (no jax)
    from jimm_tpu.retrieval.cli import add_index_parser
    add_index_parser(sub)

    # jimm-tpu qos {ls,validate} — tenant QoS policy tooling (no jax)
    from jimm_tpu.serve.qos.cli import add_qos_parser
    add_qos_parser(sub)

    # jimm-tpu cascade {calibrate,ls} — cascade calibration tooling (no jax)
    from jimm_tpu.serve.cascade.cli import add_cascade_parser
    add_cascade_parser(sub)

    return p


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    from jimm_tpu.resilience import PreemptedError
    try:
        return args.fn(args)
    except PreemptedError as e:
        # bare `train` hit by SIGTERM: state is saved; exit clean and
        # resumable instead of with a traceback (75 = EX_TEMPFAIL)
        print(str(e), file=sys.stderr)
        return 75


if __name__ == "__main__":
    sys.exit(main())
