"""Layer 1.5 (``--jaxpr``): jaxpr-level invariants between the AST rules
and the lowered-HLO checks.

``jax.make_jaxpr`` abstractly traces each registered entry point — no
compile, no execution, seconds not minutes — and asserts properties the
AST can't see and program text makes awkward:

- **JLT104** f32 promotion drift in low-precision paths: inside the
  ``fp8_hybrid`` / ``int8_qk`` policy-rewritten forwards, count
  ``convert_element_type`` equations promoting a low-precision operand
  (int8 / fp8 / bf16) to f32, plus weak-typed f32 results (a Python
  scalar leaking into the traced graph). Each entry commits a budget in
  the goldens file; drift above it means the quantized path silently
  re-materializes wide tiles — the dynamic complement of JL012/JL016.
- **JLT105** trace-time-baked host constants: a serve forward whose
  closed jaxpr carries a large ndarray const re-embeds that array in
  every process's compile — the recompile-per-process hazard the AOT
  store cannot fingerprint away, because the bytes live in the program.
  State must enter as arguments.
- **JLT106** collective count drift: the number of ``psum`` /
  ``all_gather`` / ``reduce_scatter`` / ... equations per entry point is
  compared to the committed golden (``jaxpr_goldens.json``). A collective
  appearing (or vanishing) without the golden being updated is a sharding
  regression, not a refactor.

Entry points and goldens are injectable for tests; exceptions surface as
JLT000 findings like the trace layer's.
"""

from __future__ import annotations

import json
import pathlib

from jimm_tpu.lint.core import ERROR, WARNING, Finding

__all__ = ["ENTRY_POINTS", "GOLDENS_PATH", "run_jaxpr_checks",
           "collective_counts", "f32_promotions", "update_goldens"]

GOLDENS_PATH = pathlib.Path(__file__).resolve().parent \
    / "jaxpr_goldens.json"

#: dtypes whose promotion to f32 JLT104 counts against the budget
LOWP_DTYPES = frozenset({"int8", "float8_e4m3fn", "float8_e5m2", "bfloat16"})

#: cross-device collective primitives JLT106 tracks
COLLECTIVE_PRIMITIVES = frozenset({
    "psum", "psum_invariant", "all_gather", "reduce_scatter", "ppermute",
    "all_to_all", "pmax", "pmin", "axis_index"})

#: a const bigger than this (bytes) is "baked", not a tolerable epsilon
CONST_BUDGET_BYTES = 1024

_TINY = dict(image_size=16, patch_size=8, width=32, depth=2, num_heads=2,
             mlp_dim=64)


# ---------------------------------------------------------------------------
# registered entry points: name -> () -> (fn, args) for jax.make_jaxpr
# ---------------------------------------------------------------------------

def _vit_state_forward(policy: str):
    """Tiny ViT forward with state passed as an ARGUMENT (the shape every
    serve forward must have), optionally policy-rewritten."""
    import jax.numpy as jnp
    from flax import nnx

    from jimm_tpu import VisionTransformer, ViTConfig, VisionConfig
    from jimm_tpu.quant.policy import apply_precision_policy

    cfg = ViTConfig(vision=VisionConfig(**_TINY), num_classes=4)
    model = VisionTransformer(cfg, rngs=nnx.Rngs(0))
    if policy != "bf16":
        apply_precision_policy(model, policy)
    graphdef, state = nnx.split(model)

    def forward(state, images):
        return nnx.merge(graphdef, state)(images)

    return forward, (state, jnp.zeros((2, 16, 16, 3), jnp.float32))


def _entry_serve_forward():
    return _vit_state_forward("bf16")


def _entry_fp8_hybrid():
    return _vit_state_forward("fp8_hybrid")


def _entry_int8_qk():
    return _vit_state_forward("int8_qk")


def _entry_data_parallel_psum():
    """shard_map data-parallel loss: the one entry that SHOULD carry a
    collective — exactly one psum — so JLT106 pins the count from both
    sides."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    devices = jax.devices()
    mesh = Mesh(np.array(devices).reshape(len(devices)), ("data",))

    def mean_loss(x):
        def shard_loss(xs):
            local = jnp.sum(xs * xs)
            return jax.lax.psum(local, "data")

        return jax.shard_map(shard_loss, mesh=mesh, in_specs=P("data"),
                             out_specs=P())(x)

    return mean_loss, (jnp.zeros((len(devices) * 2, 4), jnp.float32),)


ENTRY_POINTS = {
    "serve_forward_vit": _entry_serve_forward,
    "precision_fp8_hybrid": _entry_fp8_hybrid,
    "precision_int8_qk": _entry_int8_qk,
    "data_parallel_psum": _entry_data_parallel_psum,
}


def _jaxpr_path(entry: str) -> str:
    return f"<jaxpr:{entry}>"


# ---------------------------------------------------------------------------
# jaxpr walkers
# ---------------------------------------------------------------------------

def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for item in (v if isinstance(v, (list, tuple)) else [v]):
            if hasattr(item, "eqns"):  # raw Jaxpr (e.g. shard_map body)
                yield item
            elif hasattr(item, "jaxpr"):  # ClosedJaxpr (e.g. pjit body)
                yield item.jaxpr

def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _walk_eqns(sub)


def collective_counts(closed_jaxpr) -> dict[str, int]:
    """Histogram of collective primitives, recursing into sub-jaxprs
    (pjit/shard_map/scan bodies)."""
    counts: dict[str, int] = {}
    for eqn in _walk_eqns(closed_jaxpr.jaxpr):
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMITIVES:
            counts[name] = counts.get(name, 0) + 1
    return counts


def f32_promotions(closed_jaxpr) -> tuple[int, int]:
    """(low-precision -> f32 convert count, weak-typed f32 result count)
    across the whole jaxpr."""
    promos = 0
    weak = 0
    for eqn in _walk_eqns(closed_jaxpr.jaxpr):
        if eqn.primitive.name == "convert_element_type":
            src = getattr(getattr(eqn.invars[0], "aval", None), "dtype", None)
            dst = eqn.params.get("new_dtype")
            if str(dst) == "float32" and str(src) in LOWP_DTYPES:
                promos += 1
        for out in eqn.outvars:
            aval = getattr(out, "aval", None)
            if getattr(aval, "weak_type", False) \
                    and str(getattr(aval, "dtype", "")) == "float32":
                weak += 1
    return promos, weak


def _big_consts(closed_jaxpr) -> list[tuple]:
    out = []
    for const in closed_jaxpr.consts:
        nbytes = getattr(const, "nbytes", 0)
        if nbytes and nbytes > CONST_BUDGET_BYTES:
            out.append((tuple(getattr(const, "shape", ())),
                        str(getattr(const, "dtype", "?")), int(nbytes)))
    return out


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------

def _load_goldens(path=None) -> dict:
    p = pathlib.Path(path) if path is not None else GOLDENS_PATH
    try:
        return json.loads(p.read_text())
    except (OSError, ValueError):
        return {}


def _check_entry(entry: str, make, golden: dict | None) -> list[Finding]:
    import jax

    fn, args = make()
    closed = jax.make_jaxpr(fn)(*args)
    findings: list[Finding] = []
    path = _jaxpr_path(entry)

    # JLT104 — promotion drift vs committed budget
    promos, weak = f32_promotions(closed)
    if golden is not None and "f32_promotions" in golden:
        budget = int(golden["f32_promotions"])
        if promos + weak > budget:
            findings.append(Finding(
                "JLT104", ERROR, path, 0,
                f"{promos} low-precision->f32 promotions + {weak} "
                f"weak-typed f32 results exceed the committed budget of "
                f"{budget} — the quantized path is re-materializing wide "
                f"values (or a Python scalar leaked into the trace); fix "
                f"the promotion or update jaxpr_goldens.json with the "
                f"reviewed new budget"))

    # JLT105 — trace-time-baked host constants
    for shape, dtype, nbytes in _big_consts(closed):
        findings.append(Finding(
            "JLT105", ERROR, path, 0,
            f"trace-time constant {dtype}{list(shape)} ({nbytes} bytes) "
            f"is baked into the jaxpr — closed-over host arrays recompile "
            f"per process and defeat AOT-store fingerprinting; pass the "
            f"array as an argument (donated state), not a closure"))

    # JLT106 — collective count drift vs golden
    counts = collective_counts(closed)
    if golden is None or "collectives" not in golden:
        findings.append(Finding(
            "JLT106", WARNING, path, 0,
            f"no committed collective golden for entry `{entry}` "
            f"(observed {counts or '{}'}) — run `python -m jimm_tpu.lint "
            f"--jaxpr --update-goldens` and commit jaxpr_goldens.json"))
    elif counts != dict(golden["collectives"]):
        findings.append(Finding(
            "JLT106", ERROR, path, 0,
            f"collective counts drifted: observed {counts or '{}'} vs "
            f"committed {golden['collectives']} — a collective appeared or "
            f"vanished without review; fix the sharding or update "
            f"jaxpr_goldens.json deliberately"))
    return findings


def run_jaxpr_checks(entry_points: dict | None = None,
                     goldens: dict | None = None) -> list[Finding]:
    """Run JLT104–JLT106 over every entry point (default: the registered
    set, with goldens from :data:`GOLDENS_PATH`). Exceptions become JLT000
    findings — a broken trace is a finding, not a linter crash."""
    import jax

    try:  # must land before the XLA backend initializes; refused after
        jax.config.update("jax_num_cpu_devices", 8)
    except RuntimeError:
        pass

    entries = ENTRY_POINTS if entry_points is None else entry_points
    all_goldens = _load_goldens() if goldens is None else goldens
    findings: list[Finding] = []
    for entry, make in entries.items():
        try:
            findings.extend(_check_entry(entry, make,
                                         all_goldens.get(entry)))
        except Exception as e:  # noqa: BLE001 — surface, don't crash
            findings.append(Finding(
                "JLT000", ERROR, _jaxpr_path(entry), 0,
                f"jaxpr check raised {type(e).__name__}: {e}"))
    return findings


def update_goldens(path=None) -> dict:
    """Re-trace every registered entry point and write the observed
    collective counts and promotion budgets to the goldens file. Returns
    the written mapping."""
    import jax

    try:
        jax.config.update("jax_num_cpu_devices", 8)
    except RuntimeError:
        pass
    out: dict[str, dict] = {}
    for entry, make in ENTRY_POINTS.items():
        fn, args = make()
        closed = jax.make_jaxpr(fn)(*args)
        promos, weak = f32_promotions(closed)
        out[entry] = {"collectives": collective_counts(closed),
                      "f32_promotions": promos + weak}
    p = pathlib.Path(path) if path is not None else GOLDENS_PATH
    p.write_text(json.dumps(out, indent=2, sort_keys=True) + "\n")
    return out
