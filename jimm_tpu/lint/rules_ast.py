"""Layer-1 lint rules — pure ``ast``, no JAX import.

Rule catalog (see ``docs/static_analysis.md`` for the narrative version):

- **JL002** host-device sync inside jitted code: ``.item()``,
  ``float()``/``int()``/``bool()``/``np.asarray()`` on traced values, and
  Python ``if`` on a traced value (shape/dtype/``is None`` tests are static
  and exempt).
- **JL003** train-step-shaped jit (carries optimizer state) without
  ``donate_argnums``, and train-step builder calls without ``donate=`` in
  library code (tests are exempt — they exercise the default).
- **JL004** ``PartitionSpec`` axis names outside the canonical mesh-axis
  vocabulary (a typo'd axis silently shards nothing).
- **JL005** Pallas block/VMEM shapes that violate the TPU (8, 128)
  sublane/lane tiling or exceed the VMEM budget estimate.
- **JL006** blocking host sync (``.block_until_ready()``, ``np.asarray``,
  ``jax.device_get``, ``.item()``) inside an ``async def`` in serving code —
  it stalls the event loop that is supposed to keep coalescing batches;
  device waits belong in sync ``*_blocking`` helpers run via an executor.
- **JL007** bare ``print(`` in ``jimm_tpu/`` library code — telemetry
  belongs in the ``jimm_tpu.obs`` registry / ``MetricsLogger`` where it is
  structured, rate-limited, and exportable; CLI entry points
  (``cli.py``/``__main__.py``/``launch.py``) and scripts are exempt.
- **JL008** ``jax.jit`` / ``nnx.jit`` invoked (or a jit-decorated function
  defined) inside a loop body or per-request handler — every pass builds a
  fresh jit wrapper with an empty compile cache, so the work recompiles
  per iteration/request and defeats both bucket warmup and the AOT
  artifact store. Hoist the jit to module/init scope; tests are exempt.
- **JL009** hardcoded Pallas block-size literal (``block_q=128`` /
  ``block_k=...`` / ``block_rows=...``) at a call site outside
  ``jimm_tpu/ops/`` and ``jimm_tpu/tune/`` — a pinned int overrides the
  persistent autotuner (``jimm_tpu.tune.best_config``) for every shape and
  backend; leave the kwarg off (or pass ``None``) so tuned configs apply,
  or tune offline with ``jimm-tpu tune``. Tests are exempt; deliberate
  pins carry a ``# jaxlint: disable=JL009`` justification.
- **JL010** ``jax.device_put`` without an explicit placement (no second
  positional argument and no ``device=``/``sharding=`` kwarg) in
  ``serve/`` or ``parallel/`` code — an unplaced put lands the array
  replicated on the default device, silently undoing the submesh layout
  every replica forward depends on (mismatched-layout retrace or a wrong-
  device transfer per call). Pass the target ``NamedSharding`` (or
  device); deliberate default placements carry a
  ``# jaxlint: disable=JL010`` justification.
- **JL011** host-side full sort over array data (``np.argsort`` /
  ``np.sort`` / ``jnp.sort`` variants, or ``sorted()`` over a value that
  came off a device) in ``serve/`` or ``retrieval/`` hot paths — an O(N
  log N) host sort over a corpus-sized array is the exact anti-pattern
  the streaming top-k exists to avoid: score selection belongs on device
  via ``jax.lax.top_k``; host-side *final merges* over bounded candidate
  sets use ``np.lexsort`` (which is why lexsort is not banned).
  Deliberate host sorts carry a ``# jaxlint: disable=JL011``
  justification.
- **JL012** silent float32/float64 upcast (``.astype(jnp.float32)`` /
  ``jax.lax.convert_element_type(x, jnp.float32)``) in quantized ops
  code outside a ``*dequant*``/``*quantize*``-named function — the int8
  fast path wins by keeping operands int8 until the one fused dequant
  at the accumulator; a stray upcast anywhere else re-materializes f32
  tiles in VMEM and silently hands the MXU a f32 matmul. Rescales live
  in ``_dequant``-style helpers (docs/quantization.md); deliberate
  upcasts carry a ``# jaxlint: disable=JL012`` justification.
- **JL013** broad exception swallowed silently (``except Exception:
  pass``, bare ``except:``, or ``except BaseException:`` with a
  pass-only body) in ``serve/``, ``train/``, or ``resilience/`` library
  code — these are the paths whose failures the supervisor, the replica
  watchdog, and the preemption handler exist to SEE; a silent swallow
  turns worker death into a hang and a corrupt checkpoint into a cold
  start. Handle it, log it, or narrow the except; deliberate best-effort
  swallows carry a ``# jaxlint: disable=JL013`` justification. Tests are
  exempt.
- **JL014** unbounded request-keyed table growth in ``serve/`` library
  code: a ``self.<table>[<param>] = ...`` (or ``.setdefault(<param>,
  ...)``) where the key comes from a caller-supplied parameter and the
  class never evicts from that table (``.pop``/``.popitem``/``.clear``/
  ``del``). A per-tenant/per-model dict keyed by whatever clients send is
  a memory leak an adversary controls — one request per invented name
  grows the table forever. Key runtime state by *configuration* (the
  policy file's tenant names, the pool's operator-built model table) and
  map unknown ids onto one shared default slot, or give the table an
  eviction path; deliberate bounded tables carry a
  ``# jaxlint: disable=JL014`` justification. Tests are exempt.
- **JL015** structured event emitted as a bare ``print(json.dumps(...))``
  (or a print concatenating/formatting a ``json.dumps`` result) in
  ``serve/``, ``train/``, or ``resilience/`` code — ad-hoc JSON on stdout
  has no sequence number, no timestamp, no correlation id, and no
  crash-safe file behind it, so the incident chain the flight recorder
  reconstructs (fault → fence → heal → replan) silently loses the event.
  Emit through ``jimm_tpu.obs.journal`` instead; CLI entry points
  (``cli.py``/``__main__.py``/``launch.py``) keep their sanctioned
  parseable ready-lines, and deliberate console sinks carry a
  ``# jaxlint: disable=JL015`` justification. Tests are exempt.
- **JL016** bare low-precision cast (``.astype(jnp.float8_e4m3fn)`` /
  ``.astype(jnp.float8_e5m2)`` / ``.astype(jnp.int8)`` or the
  ``convert_element_type`` spelling) in ``ops/`` or ``train/`` code
  outside a ``*quantize*``/``*scale*``-named function — a narrow-format
  cast without an explicit scale silently saturates (e4m3 tops out at
  448, int8 at 127): nothing crashes, the tensor just loses its top
  octaves and training quality decays untraceably. Quantization lives in
  the scaling helpers (``quantize_tensor`` / ``quantize_rows`` /
  ``dynamic_scale``, docs/quantization.md) where amax -> scale -> clip
  -> cast travel together; expression-derived dtypes
  (``x.astype(k.dtype)``) stay legal, and deliberate unscaled casts
  carry a ``# jaxlint: disable=JL016`` justification. Tests are exempt.
- **JL021** numeric confidence-threshold literal in ``serve/cascade/``
  code outside the calibration module — a threshold hardcoded into a
  router or autoscaler (``threshold = 0.92``, ``confidence=0.9``,
  ``conf >= 0.95``) silently overrides whatever was *fit* on a holdout
  set for the contracted disagreement rate, and drifts the moment the
  model, dtype twin, or traffic changes. Thresholds are data: fit them
  with ``jimm-tpu cascade calibrate`` and load the content-addressed
  artifact (``load_calibration``); only ``calibrate.py`` (where fitting
  lives) and tests may spell threshold numbers. Deliberate literals
  carry a ``# jaxlint: disable=JL021`` justification.
- **JL022** direct ``jax.profiler.start_trace`` / ``stop_trace`` call
  outside ``jimm_tpu/obs/prof/`` — the runtime supports ONE active
  profiler session per process, and the continuous capture ring
  (``--prof-ring`` / ``--prof-dir``) may be holding it at any moment: a
  second ``start_trace`` raises mid-incident, exactly when the capture
  mattered. All session control lives behind the ring's session lock —
  one-shot traces go through
  ``jimm_tpu.obs.prof.capture.profiler_session`` (or
  ``train.profile.trace``), anomaly captures through
  ``CaptureManager.trigger``. Tests are exempt; deliberate direct calls
  carry a ``# jaxlint: disable=JL022`` justification.
- **JL024** dense score materialization or full-KV ``all_gather`` inside
  ``parallel/seqpar`` — the sequence-parallel ring's contract is that no
  device ever holds the full sequence: KV chunks move peer-to-peer via
  ``ppermute`` (O(local) memory per hop) and scores exist only one
  chunk-pair tile at a time inside per-hop helpers. An ``all_gather``
  reassembles the full KV on every device (memory scales with S again,
  exactly what the seq axis was bought to avoid), and a score-shaped
  ``einsum`` (output keeping a free sequence letter from each operand)
  outside a ``*hop*``-named function is the full ``(S, S)`` matrix.
  Deliberate gathers carry a ``# jaxlint: disable=JL024`` justification.
"""

from __future__ import annotations

import ast

from jimm_tpu.lint.core import ERROR, WARNING, Finding

#: canonical physical mesh-axis vocabulary. Mirrors
#: ``jimm_tpu.parallel.mesh.MESH_AXES`` — duplicated here so layer 1 never
#: imports JAX; ``tests/test_lint.py`` asserts the two stay in sync.
CANONICAL_MESH_AXES = frozenset({"data", "model", "replica", "seq", "stage"})

#: parameter names that mark a jitted function as a train step carrying
#: optimizer state (JL003)
OPTIMIZER_PARAM_NAMES = frozenset({"optimizer", "opt", "opt_state",
                                   "optimizer_state"})

TRAIN_STEP_BUILDERS = frozenset({"make_classifier_train_step",
                                 "make_contrastive_train_step"})

#: attribute reads on a traced value that are static at trace time (inspect
#: metadata, not data) — branching on them is fine
STATIC_ATTRS = frozenset({"shape", "ndim", "dtype", "size", "sharding"})

DEFAULT_VMEM_BUDGET = 16 * 1024 * 1024  # bytes; ~v5e per-core VMEM

_DTYPE_BYTES = {"float64": 8, "int64": 8, "float32": 4, "int32": 4,
                "uint32": 4, "bfloat16": 2, "float16": 2, "int16": 2,
                "int8": 1, "uint8": 1, "bool_": 1}


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _annotate_parents(tree: ast.AST) -> None:
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            child._jaxlint_parent = node  # type: ignore[attr-defined]


def _parent(node: ast.AST) -> ast.AST | None:
    return getattr(node, "_jaxlint_parent", None)


def _dotted(node: ast.AST) -> str | None:
    """``jax.config.update``-style dotted name for Name/Attribute chains."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_jit_expr(node: ast.AST) -> bool:
    name = _dotted(node)
    if name is None:
        return False
    return name == "jit" or name.endswith(".jit")


def _jit_decorator(dec: ast.expr) -> ast.expr | None:
    """The decorator expression if it jit-wraps the function: ``@jit``,
    ``@jax.jit`` / ``@nnx.jit``, ``@jit(...)``, ``@partial(jit, ...)``."""
    if _is_jit_expr(dec):
        return dec
    if isinstance(dec, ast.Call):
        if _is_jit_expr(dec.func):
            return dec
        fname = _dotted(dec.func)
        if fname in ("partial", "functools.partial") and dec.args \
                and _is_jit_expr(dec.args[0]):
            return dec
    return None


def _decorator_keywords(dec: ast.expr) -> set[str]:
    if isinstance(dec, ast.Call):
        return {kw.arg for kw in dec.keywords if kw.arg}
    return set()


def _jitted_functions(tree: ast.AST):
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                jd = _jit_decorator(dec)
                if jd is not None:
                    yield node, jd
                    break


# ---------------------------------------------------------------------------
# JL002 — host-device sync inside jitted code
# ---------------------------------------------------------------------------

def _only_static_uses(value: ast.expr, tainted: set[str]) -> bool:
    """True when every tainted name in ``value`` is reached only through a
    static-metadata attribute (``x.dtype``, ``x.shape``, ...) or
    ``len``/``isinstance`` — such an expression is trace-time static, so
    a local assigned from it (``dtype = x.dtype``) must NOT be tainted:
    branching on it later is as legal as branching on ``x.dtype``
    directly."""
    found_any = False
    for node in ast.walk(value):
        if not (isinstance(node, ast.Name) and node.id in tainted):
            continue
        found_any = True
        parent = _parent(node)
        if isinstance(parent, ast.Attribute) and parent.attr in STATIC_ATTRS:
            continue
        if isinstance(parent, ast.Call) and _dotted(parent.func) in (
                "len", "isinstance"):
            continue
        return False
    return found_any


def _tainted_names(fn: ast.FunctionDef) -> set[str]:
    """Function parameters plus locals assigned from expressions that use
    them — a one-pass, forward-only approximation of 'traced value'.
    Locals assigned purely from static metadata of traced values
    (``dtype = x.dtype``; ``n = len(x)``) stay untainted."""
    args = fn.args
    tainted = {a.arg for a in (args.posonlyargs + args.args + args.kwonlyargs)
               if a.arg not in ("self", "cls")}
    for a in (args.vararg, args.kwarg):
        if a is not None:
            tainted.add(a.arg)
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and any(
                isinstance(n, ast.Name) and n.id in tainted
                for n in ast.walk(node.value)):
            if _only_static_uses(node.value, tainted):
                continue
            for target in node.targets:
                for t in ast.walk(target):
                    if isinstance(t, ast.Name):
                        tainted.add(t.id)
    return tainted


def _mentions_tainted(node: ast.AST, tainted: set[str]) -> bool:
    return any(isinstance(n, ast.Name) and n.id in tainted
               for n in ast.walk(node))


def _branch_is_static(test: ast.expr, tainted: set[str]) -> bool:
    """True for trace-time-static branch tests: ``is (not) None``,
    ``isinstance``, and tests that touch traced values only through static
    metadata attributes (``.shape``/``.ndim``/``.dtype``/``len()``)."""
    if isinstance(test, ast.Compare) and all(
            isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops):
        return True
    for node in ast.walk(test):
        if not (isinstance(node, ast.Name) and node.id in tainted):
            continue
        parent = _parent(node)
        if isinstance(parent, ast.Attribute) and parent.attr in STATIC_ATTRS:
            continue
        if isinstance(parent, ast.Call) and _dotted(parent.func) in (
                "len", "isinstance"):
            continue
        # raw traced value in the test
        return False
    return True


def check_host_sync_in_jit(tree: ast.AST, path: str) -> list[Finding]:
    findings = []
    for fn, _dec in _jitted_functions(tree):
        tainted = _tainted_names(fn)
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                fname = _dotted(node.func)
                if isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "item" and not node.args:
                    findings.append(Finding(
                        "JL002", ERROR, path, node.lineno,
                        f".item() inside jitted `{fn.name}` forces a "
                        f"host-device sync"))
                elif fname in ("float", "int", "bool") and node.args \
                        and _mentions_tainted(node.args[0], tainted):
                    findings.append(Finding(
                        "JL002", ERROR, path, node.lineno,
                        f"{fname}() on a traced value inside jitted "
                        f"`{fn.name}` forces a host-device sync"))
                elif fname in ("np.asarray", "np.array", "numpy.asarray",
                               "numpy.array", "onp.asarray") and node.args \
                        and _mentions_tainted(node.args[0], tainted):
                    findings.append(Finding(
                        "JL002", ERROR, path, node.lineno,
                        f"{fname}() on a traced value inside jitted "
                        f"`{fn.name}` copies device data to host"))
            elif isinstance(node, ast.If) \
                    and _mentions_tainted(node.test, tainted) \
                    and not _branch_is_static(node.test, tainted):
                findings.append(Finding(
                    "JL002", ERROR, path, node.lineno,
                    f"Python `if` on a traced value inside jitted "
                    f"`{fn.name}` — use jnp.where/lax.cond"))
    return findings


# ---------------------------------------------------------------------------
# JL003 — train-step jit without donation
# ---------------------------------------------------------------------------

def _path_is_test(path: str) -> bool:
    base = path.replace("\\", "/").rsplit("/", 1)[-1]
    return base.startswith("test_") or base == "conftest.py"


def check_train_step_donation(tree: ast.AST, path: str) -> list[Finding]:
    findings = []
    for fn, dec in _jitted_functions(tree):
        params = {a.arg for a in fn.args.posonlyargs + fn.args.args
                  + fn.args.kwonlyargs}
        if not params & OPTIMIZER_PARAM_NAMES:
            continue
        if not _decorator_keywords(dec) & {"donate_argnums", "donate",
                                           "donate_argnames"}:
            findings.append(Finding(
                "JL003", ERROR, path, fn.lineno,
                f"jitted train step `{fn.name}` carries optimizer state "
                f"({sorted(params & OPTIMIZER_PARAM_NAMES)}) without "
                f"donate_argnums — params/m/v double-buffer in HBM"))
    if not _path_is_test(path):
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fname = _dotted(node.func)
            if fname is None:
                continue
            if fname.rsplit(".", 1)[-1] not in TRAIN_STEP_BUILDERS:
                continue
            if any(kw.arg == "donate" for kw in node.keywords):
                continue
            findings.append(Finding(
                "JL003", ERROR, path, node.lineno,
                f"{fname}(...) without donate= leaves donation off on a "
                f"training hot path; pass donate=True (or donate=False "
                f"with a reason)"))
    return findings


# ---------------------------------------------------------------------------
# JL004 — PartitionSpec axis vocabulary
# ---------------------------------------------------------------------------

def _spec_strings(args: list[ast.expr]):
    for arg in args:
        for node in ast.walk(arg):
            if isinstance(node, ast.Constant) and isinstance(node.value, str):
                yield node


def check_partition_spec_axes(tree: ast.AST, path: str) -> list[Finding]:
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fname = _dotted(node.func)
        if fname is None:
            continue
        if fname != "P" and fname.rsplit(".", 1)[-1] != "PartitionSpec":
            continue
        for s in _spec_strings(list(node.args)):
            if s.value not in CANONICAL_MESH_AXES:
                findings.append(Finding(
                    "JL004", ERROR, path, s.lineno,
                    f"PartitionSpec axis {s.value!r} is not a canonical "
                    f"mesh axis {sorted(CANONICAL_MESH_AXES)} — typo'd "
                    f"axes silently shard nothing"))
    return findings


# ---------------------------------------------------------------------------
# JL005 — Pallas tiling / VMEM budget
# ---------------------------------------------------------------------------

def _module_int_constants(tree: ast.AST) -> dict[str, int]:
    consts: dict[str, int] = {}
    body = getattr(tree, "body", [])
    for node in body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            val = _resolve_int(node.value, consts)
            if val is not None:
                consts[node.targets[0].id] = val
    return consts


def _resolve_int(node: ast.expr, consts: dict[str, int]) -> int | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        return node.value
    if isinstance(node, ast.Name):
        return consts.get(node.id)
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        v = _resolve_int(node.operand, consts)
        return None if v is None else -v
    if isinstance(node, ast.BinOp):
        left = _resolve_int(node.left, consts)
        right = _resolve_int(node.right, consts)
        if left is None or right is None:
            return None
        try:
            if isinstance(node.op, ast.Add):
                return left + right
            if isinstance(node.op, ast.Sub):
                return left - right
            if isinstance(node.op, ast.Mult):
                return left * right
            if isinstance(node.op, ast.FloorDiv):
                return left // right
            if isinstance(node.op, ast.Mod):
                return left % right
            if isinstance(node.op, ast.Pow):
                return left ** right
        except (ZeroDivisionError, OverflowError, ValueError):
            return None
    return None


def _dtype_bytes(node: ast.expr | None) -> int:
    name = (_dotted(node) or "") if node is not None else ""
    leaf = name.rsplit(".", 1)[-1]
    return _DTYPE_BYTES.get(leaf, 4)


def _check_shape(dims: list[int | None], bytes_per_elem: int, budget: int,
                 path: str, lineno: int, what: str) -> list[Finding]:
    findings = []
    if dims and dims[-1] is not None and dims[-1] != 1 \
            and dims[-1] % 128 != 0:
        findings.append(Finding(
            "JL005", ERROR, path, lineno,
            f"{what} last dim {dims[-1]} is not a multiple of the 128-lane "
            f"TPU tile — the Mosaic pad wastes VMEM and VPU lanes"))
    if len(dims) >= 2 and dims[-2] is not None and dims[-2] != 1 \
            and dims[-2] % 8 != 0:
        findings.append(Finding(
            "JL005", ERROR, path, lineno,
            f"{what} second-minor dim {dims[-2]} is not a multiple of the "
            f"8-sublane TPU tile"))
    if all(d is not None for d in dims) and dims:
        total = bytes_per_elem
        for d in dims:
            total *= d  # type: ignore[operator]
        if total > budget:
            findings.append(Finding(
                "JL005", ERROR, path, lineno,
                f"{what} is {total / 2**20:.1f} MiB, over the "
                f"{budget / 2**20:.1f} MiB VMEM budget (tune with "
                f"--vmem-budget)"))
    return findings


def check_pallas_tiling(tree: ast.AST, path: str,
                        vmem_budget: int | None = None) -> list[Finding]:
    budget = vmem_budget if vmem_budget is not None else DEFAULT_VMEM_BUDGET
    consts = _module_int_constants(tree)
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fname = _dotted(node.func)
        leaf = fname.rsplit(".", 1)[-1] if fname else None
        if leaf == "BlockSpec":
            for arg in node.args:
                if isinstance(arg, ast.Tuple):
                    dims = [_resolve_int(e, consts) for e in arg.elts]
                    findings.extend(_check_shape(
                        dims, 4, budget, path, node.lineno,
                        "BlockSpec block shape"))
                    break  # one shape tuple per BlockSpec
        elif leaf in ("VMEM", "SMEM") and fname and "." in fname:
            if node.args and isinstance(node.args[0], ast.Tuple):
                dims = [_resolve_int(e, consts)
                        for e in node.args[0].elts]
                dtype = node.args[1] if len(node.args) > 1 else None
                if leaf == "VMEM":
                    findings.extend(_check_shape(
                        dims, _dtype_bytes(dtype), budget, path,
                        node.lineno, "VMEM scratch shape"))
    return findings


# ---------------------------------------------------------------------------
# JL006 — blocking host sync on the serve event loop
# ---------------------------------------------------------------------------

#: dotted call names that materialize device data on host (block the caller)
HOST_SYNC_CALLS = frozenset({"np.asarray", "np.array", "numpy.asarray",
                             "numpy.array", "onp.asarray", "jax.device_get",
                             "device_get"})


def _path_is_serve(path: str) -> bool:
    parts = path.replace("\\", "/").split("/")
    return "serve" in parts or parts[-1] == "serve.py"


def check_async_host_sync(tree: ast.AST, path: str) -> list[Finding]:
    """JL006: in serving code, ``async def`` bodies run on the engine's
    event loop — the thing that must stay free to coalesce batches. A
    blocking host sync there stalls every in-flight request. Sync helper
    functions (run via ``run_in_executor``) are the sanctioned home for
    device waits, so nested sync ``def``/``lambda`` bodies are exempt."""
    if not _path_is_serve(path):
        return []
    findings = []
    for fn in ast.walk(tree):
        if isinstance(fn, ast.AsyncFunctionDef):
            findings += _scan_async_body(fn, path)
    return findings


def _scan_async_body(fn: ast.AsyncFunctionDef, path: str) -> list[Finding]:
    findings = []
    stack: list[ast.AST] = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue  # different execution context (executor helpers / the
            #           outer walk already visits nested async defs)
        if isinstance(node, ast.Call):
            fname = _dotted(node.func)
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "block_until_ready":
                findings.append(Finding(
                    "JL006", ERROR, path, node.lineno,
                    f".block_until_ready() inside async `{fn.name}` blocks "
                    f"the serve event loop — move the device wait into a "
                    f"sync *_blocking helper run via run_in_executor"))
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "item" and not node.args:
                findings.append(Finding(
                    "JL006", ERROR, path, node.lineno,
                    f".item() inside async `{fn.name}` forces a host sync "
                    f"on the serve event loop — read results in a sync "
                    f"*_blocking helper run via run_in_executor"))
            elif fname in HOST_SYNC_CALLS:
                findings.append(Finding(
                    "JL006", ERROR, path, node.lineno,
                    f"{fname}() inside async `{fn.name}` can block the "
                    f"serve event loop on a device transfer — do host "
                    f"materialization in a sync *_blocking helper run via "
                    f"run_in_executor"))
        stack.extend(ast.iter_child_nodes(node))
    return findings


# ---------------------------------------------------------------------------
# JL007 — bare print() in library code
# ---------------------------------------------------------------------------

#: basenames where print IS the product (user-facing command entry points)
PRINT_EXEMPT_BASENAMES = frozenset({"cli.py", "__main__.py", "launch.py"})


def _path_is_library(path: str) -> bool:
    """True for files inside the ``jimm_tpu`` package that are not command
    entry points (scripts/ and tests/ are outside the package entirely)."""
    parts = path.replace("\\", "/").split("/")
    return "jimm_tpu" in parts[:-1] \
        and parts[-1] not in PRINT_EXEMPT_BASENAMES


def check_bare_print(tree: ast.AST, path: str) -> list[Finding]:
    """JL007: library code must not ``print`` — a stray print per step is
    unstructured, unrateable console spam that bypasses every exporter.
    Route output through ``jimm_tpu.obs`` (registry/span) or
    ``train.metrics.MetricsLogger``; a deliberate console sink carries a
    ``# jaxlint: disable=JL007`` justification."""
    if not _path_is_library(path):
        return []
    findings = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
                and node.func.id == "print":
            findings.append(Finding(
                "JL007", ERROR, path, node.lineno,
                "bare print() in library code — log through the "
                "jimm_tpu.obs registry or MetricsLogger (CLI modules and "
                "scripts are exempt; suppress deliberate console sinks "
                "with # jaxlint: disable=JL007)"))
    return findings


# ---------------------------------------------------------------------------
# JL008 — jit inside a loop body or per-request handler
# ---------------------------------------------------------------------------

#: method names that handle one network request per call
#: (http.server's do_VERB convention; add as serving grows transports)
REQUEST_HANDLER_NAMES = frozenset({"do_GET", "do_POST", "do_PUT",
                                   "do_DELETE", "do_HEAD"})


def _enclosing_loop(node: ast.AST) -> ast.AST | None:
    """The innermost For/While/AsyncFor whose *body* (not iter/test)
    contains ``node``, without crossing a function boundary — a jit inside
    a ``def`` that merely sits in a loop runs once per call, not per
    iteration of the outer loop."""
    cur: ast.AST | None = node
    while cur is not None:
        parent = _parent(cur)
        if isinstance(parent, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.Lambda)):
            return None
        if isinstance(parent, (ast.For, ast.AsyncFor, ast.While)) \
                and cur not in (getattr(parent, "iter", None),
                                getattr(parent, "test", None)):
            return parent
        cur = parent
    return None


def _enclosing_handler(node: ast.AST, path: str) -> str | None:
    """Name of the per-request handler ``node`` sits in, if any: a
    ``do_VERB`` method anywhere, or any ``async def`` in serving code
    (the engine's event-loop coroutines each run per request/batch)."""
    cur: ast.AST | None = _parent(node)
    while cur is not None:
        if isinstance(cur, ast.FunctionDef) \
                and cur.name in REQUEST_HANDLER_NAMES:
            return cur.name
        if isinstance(cur, ast.AsyncFunctionDef) and _path_is_serve(path):
            return cur.name
        cur = _parent(cur)
    return None


def check_jit_in_loop(tree: ast.AST, path: str) -> list[Finding]:
    """JL008: a ``jit`` call in a loop body or request handler makes a new
    wrapper (and a cold compile cache) every pass — the exact recompile
    hazard bucket warmup and the AOT store exist to eliminate. Tests are
    exempt: they intentionally construct jits per-case."""
    if _path_is_test(path):
        return []
    findings = []
    seen_lines: set[int] = set()
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and _is_jit_expr(node.func)):
            continue
        where = None
        if _enclosing_loop(node) is not None:
            where = "a loop body"
        else:
            handler = _enclosing_handler(node, path)
            if handler is not None:
                where = f"per-request handler `{handler}`"
        if where is None or node.lineno in seen_lines:
            continue
        seen_lines.add(node.lineno)
        fname = _dotted(node.func) or "jit"
        findings.append(Finding(
            "JL008", ERROR, path, node.lineno,
            f"{fname}(...) inside {where} builds a fresh wrapper (and "
            f"recompiles) every pass, defeating bucket warmup and the AOT "
            f"artifact store — hoist the jit to module or __init__ scope"))
    for fn, dec in _jitted_functions(tree):
        if _enclosing_loop(fn) is not None and fn.lineno not in seen_lines:
            seen_lines.add(fn.lineno)
            findings.append(Finding(
                "JL008", ERROR, path, fn.lineno,
                f"jit-decorated `{fn.name}` is defined inside a loop body "
                f"— each iteration makes a new function object with a cold "
                f"compile cache; define it once outside the loop"))
    return findings


# ---------------------------------------------------------------------------
# JL009 — hardcoded block-size literal bypasses the autotuner
# ---------------------------------------------------------------------------

#: kernel block kwargs the tune cache owns (``jimm_tpu.tune.api.KERNELS``)
TUNABLE_BLOCK_KWARGS = frozenset({"block_q", "block_k", "block_rows"})

#: package directories where explicit int blocks are the mechanism itself:
#: ops modules define the safe defaults, and the tuner's bench closures MUST
#: pass explicit ints (that is the no-recursion contract with best_config)
_BLOCK_LITERAL_EXEMPT_DIRS = frozenset({"ops", "tune"})


def _path_is_block_exempt(path: str) -> bool:
    parts = path.replace("\\", "/").split("/")
    if "jimm_tpu" in parts[:-1]:
        rel = parts[parts.index("jimm_tpu") + 1:-1]
        if _BLOCK_LITERAL_EXEMPT_DIRS & set(rel):
            return True
    return _path_is_test(path)


def check_block_size_literal(tree: ast.AST, path: str) -> list[Finding]:
    """JL009: a literal ``block_q=128``-style kwarg at a call site pins one
    block size for every shape, dtype, and TPU generation, silently masking
    whatever ``jimm_tpu.tune`` has measured as best. Call sites should omit
    the kwarg (ops resolve it through ``tune.best_config`` with a safe
    default); genuinely deliberate pins take a
    ``# jaxlint: disable=JL009`` with a reason."""
    if _path_is_block_exempt(path):
        return []
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        for kw in node.keywords:
            if kw.arg not in TUNABLE_BLOCK_KWARGS:
                continue
            if isinstance(kw.value, ast.Constant) \
                    and isinstance(kw.value.value, int) \
                    and not isinstance(kw.value.value, bool):
                findings.append(Finding(
                    "JL009", ERROR, path, kw.value.lineno,
                    f"hardcoded {kw.arg}={kw.value.value} bypasses the "
                    f"persistent autotuner for every shape/backend — omit "
                    f"the kwarg so jimm_tpu.tune.best_config resolves it "
                    f"(tune offline with `jimm-tpu tune`), or justify the "
                    f"pin with # jaxlint: disable=JL009"))
    return findings


# ---------------------------------------------------------------------------
# JL010 — unplaced device_put in sharding-sensitive code
# ---------------------------------------------------------------------------

def _path_is_parallel(path: str) -> bool:
    parts = path.replace("\\", "/").split("/")
    return "parallel" in parts or parts[-1] == "parallel.py"


def check_device_put_placement(tree: ast.AST, path: str) -> list[Finding]:
    """JL010: in ``serve/`` and ``parallel/`` code every ``jax.device_put``
    must say *where* — a second positional argument or a ``device=``/
    ``sharding=`` kwarg. A bare put places the array on the default device,
    which in a multi-replica topology is some other replica's submesh: the
    sharded executable then either retraces for the mismatched layout or
    pays a cross-device transfer on every batch. Deliberate default
    placements carry a ``# jaxlint: disable=JL010`` justification."""
    if not (_path_is_serve(path) or _path_is_parallel(path)):
        return []
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fname = _dotted(node.func)
        if fname is None or fname.rsplit(".", 1)[-1] != "device_put":
            continue
        if len(node.args) >= 2:
            continue  # positional device/sharding
        if any(kw.arg in ("device", "sharding") for kw in node.keywords):
            continue
        findings.append(Finding(
            "JL010", ERROR, path, node.lineno,
            f"{fname}(...) without a device/sharding places the array on "
            f"the default device — in sharded serving that is the wrong "
            f"submesh (layout retrace or per-batch cross-device copy); "
            f"pass the replica's NamedSharding, or justify the default "
            f"placement with # jaxlint: disable=JL010"))
    return findings


# ---------------------------------------------------------------------------
# JL011 — host-side full sort in serving/retrieval hot paths
# ---------------------------------------------------------------------------

#: dotted sort calls that rank an entire array on host — O(N log N) on the
#: request path where the device's O(N) ``lax.top_k`` (plus a bounded-set
#: ``np.lexsort`` merge, deliberately absent from this list) is the contract
HOST_SORT_CALLS = frozenset({
    "np.argsort", "np.sort", "numpy.argsort", "numpy.sort",
    "jnp.argsort", "jnp.sort", "jax.numpy.argsort", "jax.numpy.sort",
})

#: calls whose results are host copies of (potentially corpus-sized) device
#: or numpy array data — seeds the taint that makes ``sorted()`` suspicious
ARRAY_SOURCE_CALLS = frozenset({"np.asarray", "np.array", "numpy.asarray",
                                "numpy.array", "jnp.asarray", "jnp.array",
                                "jax.device_get", "device_get"})


def _path_is_retrieval(path: str) -> bool:
    parts = path.replace("\\", "/").split("/")
    return "retrieval" in parts or parts[-1] == "retrieval.py"


def _array_tainted_names(scope: ast.AST) -> set[str]:
    """Names assigned (directly or transitively) from array-materializing
    calls inside ``scope`` — one forward pass, JL002-style."""
    tainted: set[str] = set()
    for node in ast.walk(scope):
        if not isinstance(node, ast.Assign):
            continue
        from_array = any(
            isinstance(n, ast.Call) and _dotted(n.func) in ARRAY_SOURCE_CALLS
            for n in ast.walk(node.value))
        if from_array or _mentions_tainted(node.value, tainted):
            for target in node.targets:
                # plain names (incl. tuple unpacking) only: a subscript or
                # attribute store does not make its container an array
                if isinstance(target, (ast.Tuple, ast.List)):
                    tainted.update(t.id for t in target.elts
                                   if isinstance(t, ast.Name))
                elif isinstance(target, ast.Name):
                    tainted.add(target.id)
    return tainted


def check_host_sort(tree: ast.AST, path: str) -> list[Finding]:
    """JL011: serving/retrieval hot paths must not full-sort on host. The
    banned calls rank every element of their input; over a device array
    that also forces the whole corpus through a transfer first. Selection
    runs on device (``jax.lax.top_k`` per block + streaming merge); only
    the bounded per-partition candidate merge belongs on host, and that is
    ``np.lexsort``'s job. Tests are exempt (oracles *should* argsort)."""
    if not (_path_is_serve(path) or _path_is_retrieval(path)) \
            or _path_is_test(path):
        return []
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fname = _dotted(node.func)
        if fname in HOST_SORT_CALLS:
            findings.append(Finding(
                "JL011", ERROR, path, node.lineno,
                f"{fname}() full-sorts on host in a serving/retrieval hot "
                f"path — rank on device with jax.lax.top_k (streaming "
                f"merge for big corpora); np.lexsort over a bounded "
                f"candidate set is the sanctioned host-side final merge, "
                f"or justify with # jaxlint: disable=JL011"))
    seen: set[int] = set()
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        tainted = _array_tainted_names(fn)
        if not tainted:
            continue
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Name) \
                    and node.func.id == "sorted" and node.args \
                    and _mentions_tainted(node.args[0], tainted) \
                    and node.lineno not in seen:
                seen.add(node.lineno)
                findings.append(Finding(
                    "JL011", ERROR, path, node.lineno,
                    f"sorted() over array-derived data in `{fn.name}` "
                    f"full-sorts on host in a serving/retrieval hot path "
                    f"— use jax.lax.top_k on device (np.lexsort for "
                    f"bounded final merges), or justify with "
                    f"# jaxlint: disable=JL011"))
    return findings


# ---------------------------------------------------------------------------
# JL012 — silent f32 upcast in quantized ops code
# ---------------------------------------------------------------------------

#: dtype leaf names whose appearance as an astype/convert target undoes the
#: int8 storage win (bf16 stays allowed: mixed-precision epilogues are fine)
_WIDE_FLOAT_DTYPES = frozenset({"float32", "float64"})

#: substrings that sanction an enclosing function as THE dequant site — the
#: one place per kernel where the int32 accumulator meets its scales
_DEQUANT_NAME_MARKS = ("dequant", "quantize")


def _path_is_quant_ops(path: str) -> bool:
    """Quantization code: anything under a ``quant/`` package, plus ops
    modules whose basename marks them as int8/quantized kernels."""
    parts = path.replace("\\", "/").split("/")
    if "quant" in parts[:-1]:
        return True
    base = parts[-1]
    return "ops" in parts[:-1] and ("int8" in base or "quant" in base)


def _wide_float_target(node: ast.expr) -> str | None:
    """The float32/float64 name if ``node`` denotes one (dotted name like
    ``jnp.float32`` or a ``"float32"`` string constant), else None."""
    name = _dotted(node)
    if name is not None:
        leaf = name.rsplit(".", 1)[-1]
        return leaf if leaf in _WIDE_FLOAT_DTYPES else None
    if isinstance(node, ast.Constant) and isinstance(node.value, str) \
            and node.value in _WIDE_FLOAT_DTYPES:
        return node.value
    return None


def _in_dequant_function(node: ast.AST) -> bool:
    cur: ast.AST | None = _parent(node)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)) and any(
                mark in cur.name for mark in _DEQUANT_NAME_MARKS):
            return True
        cur = _parent(cur)
    return False


def check_quant_upcast(tree: ast.AST, path: str) -> list[Finding]:
    """JL012: quantized ops keep everything int8 until the single fused
    dequant — that is the whole bandwidth/MXU win. An ``.astype(f32)`` or
    ``convert_element_type(x, f32)`` sprinkled anywhere else silently
    rebuilds full-width tiles, and nothing crashes: the kernel just stops
    being an int8 kernel. The sanctioned home for the rescale is a
    function whose name says so (``_dequant*`` / ``*quantize*``);
    deliberate upcasts elsewhere carry ``# jaxlint: disable=JL012``."""
    if not _path_is_quant_ops(path) or _path_is_test(path):
        return []
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        target = None
        how = None
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr == "astype" and node.args:
            target = _wide_float_target(node.args[0])
            how = f".astype({target})"
        else:
            fname = _dotted(node.func)
            if fname is not None \
                    and fname.rsplit(".", 1)[-1] == "convert_element_type" \
                    and len(node.args) >= 2:
                target = _wide_float_target(node.args[1])
                how = f"convert_element_type(..., {target})"
        if target is None or _in_dequant_function(node):
            continue
        findings.append(Finding(
            "JL012", ERROR, path, node.lineno,
            f"{how} in quantized ops code outside a dequant/quantize "
            f"helper silently re-materializes wide tiles and forfeits the "
            f"int8 MXU path — keep the rescale in the fused _dequant "
            f"epilogue (docs/quantization.md), or justify with "
            f"# jaxlint: disable=JL012"))
    return findings


# ---------------------------------------------------------------------------
# JL013 — silently swallowed broad exception in resilience-critical paths
# ---------------------------------------------------------------------------

def _path_is_resilient(path: str) -> bool:
    parts = path.replace("\\", "/").split("/")
    return bool({"serve", "train", "resilience"} & set(parts))


def check_swallowed_exception(tree: ast.AST, path: str) -> list[Finding]:
    """JL013: a broad except with a pass-only body in serve/train/
    resilience library code. The whole resilience design rests on failures
    being *observable* — the supervisor restarts on worker death, the
    replica watchdog fences a failing lane, the checkpoint fallback
    quarantines corrupt steps — and every one of those signals dies at an
    ``except Exception: pass``. Narrow excepts (``except OSError: pass``
    around a close()) stay legal: the rule targets the handlers broad
    enough to eat the failures the machinery above must see."""
    if not _path_is_resilient(path) or _path_is_test(path):
        return []
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        broad = node.type is None or (
            isinstance(node.type, ast.Name)
            and node.type.id in ("Exception", "BaseException"))
        if broad and all(isinstance(s, ast.Pass) for s in node.body):
            findings.append(Finding(
                "JL013", ERROR, path, node.lineno,
                "broad exception swallowed silently — in serve/train/"
                "resilience paths this hides worker death from the "
                "supervisor and the watchdog; handle, log, or narrow it "
                "(deliberate best-effort swallows carry a "
                "# jaxlint: disable=JL013 justification)"))
    return findings


# ---------------------------------------------------------------------------
# JL014 — unbounded request-keyed table growth in serving state
# ---------------------------------------------------------------------------

_EVICTION_METHODS = frozenset({"pop", "popitem", "popleft", "clear"})


def _self_attr_name(node: ast.AST) -> str | None:
    """``self.<attr>`` -> attr name (None for anything else)."""
    if isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name) \
            and node.value.id == "self":
        return node.attr
    return None


def _evicted_attrs(cls: ast.ClassDef) -> set[str]:
    """Attributes of ``cls`` that have SOME eviction path anywhere in the
    class body: ``self.x.pop/popitem/popleft/clear(...)`` or
    ``del self.x[...]``. Presence of any eviction op is the evidence the
    table is managed, so every write to it stays legal."""
    evicted: set[str] = set()
    for node in ast.walk(cls):
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr in _EVICTION_METHODS:
            attr = _self_attr_name(node.func.value)
            if attr is not None:
                evicted.add(attr)
        elif isinstance(node, ast.Delete):
            for target in node.targets:
                if isinstance(target, ast.Subscript):
                    attr = _self_attr_name(target.value)
                    if attr is not None:
                        evicted.add(attr)
    return evicted


def _names_in(node: ast.AST) -> set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def check_unbounded_tenant_table(tree: ast.AST, path: str) -> list[Finding]:
    """JL014: serving state keyed by caller-supplied identifiers with no
    eviction. The QoS discipline is that runtime tables are bounded by
    *configuration* (policy-file tenants, the operator's model pool), not
    by traffic: anonymous/unknown ids share one default slot. This rule
    catches the regression where a handler quietly grows
    ``self.per_tenant[tenant_id]`` per request — unbounded memory an
    adversary can drive by inventing names."""
    if not _path_is_serve(path) or _path_is_test(path):
        return []
    findings = []
    for cls in ast.walk(tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        evicted = _evicted_attrs(cls)
        for fn in ast.walk(cls):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            params = {a.arg for a in (fn.args.posonlyargs + fn.args.args
                                      + fn.args.kwonlyargs)} - {"self"}
            if not params:
                continue
            for node in ast.walk(fn):
                attr = key = None
                if isinstance(node, (ast.Assign, ast.AnnAssign)):
                    targets = (node.targets
                               if isinstance(node, ast.Assign)
                               else [node.target])
                    for target in targets:
                        if isinstance(target, ast.Subscript):
                            a = _self_attr_name(target.value)
                            if a is not None:
                                attr, key = a, target.slice
                elif isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "setdefault" and node.args:
                    a = _self_attr_name(node.func.value)
                    if a is not None:
                        attr, key = a, node.args[0]
                if attr is None or attr in evicted \
                        or not (_names_in(key) & params):
                    continue
                findings.append(Finding(
                    "JL014", ERROR, path, node.lineno,
                    f"self.{attr} grows per caller-supplied key with no "
                    f"eviction anywhere in {cls.name} — a request-keyed "
                    f"table is memory an adversary controls (one invented "
                    f"tenant/model name per request, forever). Key state "
                    f"by configuration and map unknown ids to a shared "
                    f"default slot, add an eviction path, or justify with "
                    f"# jaxlint: disable=JL014"))
    return findings


# ---------------------------------------------------------------------------
# JL015 — journal bypass: print(json.dumps(...)) structured-event emission
# ---------------------------------------------------------------------------

def _is_json_dumps_call(node: ast.AST) -> bool:
    """``json.dumps(...)`` / ``_json.dumps(...)`` / bare ``dumps(...)``."""
    if not isinstance(node, ast.Call):
        return False
    fn = node.func
    if isinstance(fn, ast.Attribute):
        return fn.attr == "dumps"
    return isinstance(fn, ast.Name) and fn.id == "dumps"


def check_journal_bypass(tree: ast.AST, path: str) -> list[Finding]:
    """JL015: in serve/train/resilience code, a structured event printed
    as ad-hoc JSON bypasses the flight recorder. The journal exists so an
    incident reads back as ONE correlated chain — seq, timestamps, cid —
    from a crash-safe rotating file; a ``print(json.dumps({...}))`` emits
    the same fact as an orphan line only a console scraper can find.
    Walking the print's argument subtrees catches the concatenation and
    f-string spellings too (``print("x: " + json.dumps(d))``)."""
    if not _path_is_resilient(path) or _path_is_test(path):
        return []
    base = path.replace("\\", "/").rsplit("/", 1)[-1]
    if base in PRINT_EXEMPT_BASENAMES:
        return []
    findings = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id == "print"):
            continue
        if any(_is_json_dumps_call(sub) for arg in node.args
               for sub in ast.walk(arg)):
            findings.append(Finding(
                "JL015", ERROR, path, node.lineno,
                "structured event printed as ad-hoc JSON — this bypasses "
                "the flight-recorder journal (no seq/ts/cid, not crash-"
                "safe), orphaning the event from its incident chain; emit "
                "via jimm_tpu.obs.journal (get_journal().emit(...)) or "
                "justify with # jaxlint: disable=JL015"))
    return findings


# ---------------------------------------------------------------------------
# JL016 — bare low-precision cast outside a scaling/quantization helper
# ---------------------------------------------------------------------------

#: dtype leaf names whose appearance as a cast target narrows precision on
#: the training fast path — each has a sanctioned scaled home
_LOWP_DTYPES = frozenset({"float8_e4m3fn", "float8_e5m2", "int8"})

#: substrings that sanction an enclosing function as a scaling-aware
#: quantization site (quantize_tensor / quantize_rows / _quantize_heads /
#: dynamic_scale / delayed_scale ...)
_SCALING_NAME_MARKS = ("quantize", "scale")


def _path_is_precision_critical(path: str) -> bool:
    """Kernel and trainer code: the two trees where a low-precision cast
    is a numerics decision, not a storage format."""
    parts = path.replace("\\", "/").split("/")
    return bool({"ops", "train"} & set(parts[:-1]))


def _lowp_target(node: ast.expr) -> str | None:
    """The low-precision dtype name if ``node`` denotes one (dotted name
    like ``jnp.float8_e4m3fn`` or an ``"int8"`` string constant), else
    None. Expression-derived dtypes (``k.dtype``) resolve to the leaf
    ``dtype`` and stay legal by construction."""
    name = _dotted(node)
    if name is not None:
        leaf = name.rsplit(".", 1)[-1]
        return leaf if leaf in _LOWP_DTYPES else None
    if isinstance(node, ast.Constant) and isinstance(node.value, str) \
            and node.value in _LOWP_DTYPES:
        return node.value
    return None


def _in_scaling_function(node: ast.AST) -> bool:
    cur: ast.AST | None = _parent(node)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)) and any(
                mark in cur.name for mark in _SCALING_NAME_MARKS):
            return True
        cur = _parent(cur)
    return False


def check_bare_lowp_cast(tree: ast.AST, path: str) -> list[Finding]:
    """JL016: fp8/int8 casts in ops/train code must travel with a scale.
    A bare ``.astype(jnp.float8_e4m3fn)`` saturates everything past 448
    (int8 past 127) — no crash, no NaN guard trips, the tensor just loses
    its top octaves and the loss curve quietly degrades. The sanctioned
    homes are functions whose names say they scale (``quantize_tensor``,
    ``quantize_rows``, ``dynamic_scale``, ...) where the amax reduction,
    the scale division, the clip, and the cast are one auditable unit."""
    if not _path_is_precision_critical(path) or _path_is_test(path):
        return []
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        target = None
        how = None
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr == "astype" and node.args:
            target = _lowp_target(node.args[0])
            how = f".astype({target})"
        else:
            fname = _dotted(node.func)
            if fname is not None \
                    and fname.rsplit(".", 1)[-1] == "convert_element_type" \
                    and len(node.args) >= 2:
                target = _lowp_target(node.args[1])
                how = f"convert_element_type(..., {target})"
        if target is None or _in_scaling_function(node):
            continue
        findings.append(Finding(
            "JL016", ERROR, path, node.lineno,
            f"bare {how} outside a quantize/scale helper saturates at the "
            f"format max with no scale to absorb the range — route the "
            f"cast through a scaling helper (quantize_tensor / "
            f"quantize_rows, docs/quantization.md) so amax -> scale -> "
            f"clip -> cast stay together, or justify with "
            f"# jaxlint: disable=JL016"))
    return findings


# ---------------------------------------------------------------------------
# JL021 — hardcoded confidence-threshold literal in cascade routing code
# ---------------------------------------------------------------------------

#: name substrings that mark a binding/comparison as a confidence threshold
_THRESHOLD_NAME_MARKS = ("threshold", "confidence")

#: the one cascade module where threshold numbers legitimately live:
#: the fitter/loader itself
_CALIBRATION_BASENAMES = frozenset({"calibrate.py"})


def _path_is_cascade(path: str) -> bool:
    parts = path.replace("\\", "/").split("/")
    return "cascade" in parts[:-1] and "serve" in parts


def _is_threshold_name(node: ast.AST) -> bool:
    """True when ``node`` names something threshold-like (``threshold``,
    ``self.confidence``, ``escalation_threshold`` ...)."""
    if isinstance(node, ast.Name):
        name = node.id
    elif isinstance(node, ast.Attribute):
        name = node.attr
    else:
        return False
    name = name.lower()
    return any(mark in name for mark in _THRESHOLD_NAME_MARKS)


def _numeric_literal(node: ast.AST) -> bool:
    """A bare int/float constant, possibly under a unary +/- (``0.92``,
    ``-1.5``). Deliberately NOT any-literal-in-subtree: ``round(conf, 6)``
    carries a 6 but decides nothing."""
    if isinstance(node, ast.UnaryOp) \
            and isinstance(node.op, (ast.UAdd, ast.USub)):
        node = node.operand
    return (isinstance(node, ast.Constant)
            and isinstance(node.value, (int, float))
            and not isinstance(node.value, bool))


def check_cascade_thresholds(tree: ast.AST, path: str) -> list[Finding]:
    """JL021: in ``serve/cascade/`` (outside ``calibrate.py`` and tests),
    no numeric literal may bind to or compare against a threshold-named
    value — routers load calibration artifacts, they never ship
    thresholds."""
    if not _path_is_cascade(path) or _path_is_test(path):
        return []
    if path.replace("\\", "/").rsplit("/", 1)[-1] in _CALIBRATION_BASENAMES:
        return []

    def finding(node: ast.AST, how: str) -> Finding:
        return Finding(
            "JL021", ERROR, path, node.lineno,
            f"hardcoded confidence-threshold literal ({how}) in cascade "
            "routing code — thresholds are fit on a holdout set "
            "(jimm-tpu cascade calibrate) and loaded from the "
            "content-addressed store (load_calibration), never spelled "
            "in code; justify deliberate literals with "
            "# jaxlint: disable=JL021")

    findings = []
    for node in ast.walk(tree):
        # threshold = 0.92 / self.confidence_floor: float = 0.9
        if isinstance(node, ast.Assign) and _numeric_literal(node.value):
            if any(_is_threshold_name(t) for t in node.targets):
                findings.append(finding(node, "assignment"))
        elif isinstance(node, ast.AnnAssign) and node.value is not None \
                and _numeric_literal(node.value) \
                and _is_threshold_name(node.target):
            findings.append(finding(node, "assignment"))
        # fn(threshold=0.92)
        elif isinstance(node, ast.Call):
            for kw in node.keywords:
                if kw.arg is not None and any(
                        mark in kw.arg.lower()
                        for mark in _THRESHOLD_NAME_MARKS) \
                        and _numeric_literal(kw.value):
                    findings.append(finding(kw.value, f"{kw.arg}= keyword"))
        # def route(..., threshold=0.92)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            args = node.args
            all_args = args.posonlyargs + args.args + args.kwonlyargs
            all_defaults = args.defaults + args.kw_defaults
            for arg, default in zip(all_args[-len(all_defaults):]
                                    if all_defaults else [], all_defaults):
                if default is not None and _numeric_literal(default) \
                        and any(mark in arg.arg.lower()
                                for mark in _THRESHOLD_NAME_MARKS):
                    findings.append(finding(default,
                                            f"{arg.arg}= default"))
        # conf >= 0.95  /  0.95 < confidence
        elif isinstance(node, ast.Compare):
            operands = [node.left] + list(node.comparators)
            if any(_is_threshold_name(op) for op in operands) and any(
                    _numeric_literal(op) for op in operands):
                findings.append(finding(node, "comparison"))
    return findings


# ---------------------------------------------------------------------------
# JL022 — direct profiler session control outside obs/prof/
# ---------------------------------------------------------------------------

#: the two jax.profiler calls that claim/release THE process profiler
#: session (TraceAnnotation etc. are session-agnostic and stay legal)
_PROFILER_SESSION_FNS = frozenset({"start_trace", "stop_trace"})


def _path_is_prof_home(path: str) -> bool:
    """Inside ``jimm_tpu/obs/prof/`` — the sanctioned session owner."""
    parts = path.replace("\\", "/").split("/")
    return "prof" in parts[:-1] and "obs" in parts


def check_profiler_bypass(tree: ast.AST, path: str) -> list[Finding]:
    """JL022: ``jax.profiler.start_trace``/``stop_trace`` called outside
    ``obs/prof/`` — the process has ONE profiler session and the capture
    ring may be holding it; direct session control races the ring instead
    of serializing on its lock. Catches both the attribute spelling
    (``jax.profiler.start_trace(...)``) and names imported from
    ``jax.profiler`` directly."""
    if _path_is_prof_home(path) or _path_is_test(path):
        return []
    imported: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) \
                and node.module == "jax.profiler":
            for alias in node.names:
                if alias.name in _PROFILER_SESSION_FNS:
                    imported.add(alias.asname or alias.name)
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fname = _dotted(node.func)
        if fname is None:
            continue
        leaf = fname.rsplit(".", 1)[-1]
        if fname in imported or (leaf in _PROFILER_SESSION_FNS
                                 and fname.endswith(f"profiler.{leaf}")):
            findings.append(Finding(
                "JL022", ERROR, path, node.lineno,
                f"direct jax.profiler.{leaf} outside obs/prof — the "
                "process has ONE profiler session and the continuous "
                "capture ring may be holding it (a second start_trace "
                "raises mid-incident, exactly when the capture mattered). "
                "Use jimm_tpu.obs.prof.capture.profiler_session (or "
                "train.profile.trace) so sessions serialize on the ring's "
                "lock, or justify with # jaxlint: disable=JL022"))
    return findings


# ---------------------------------------------------------------------------
# JL024 — sequence-parallel discipline inside parallel/seqpar
# ---------------------------------------------------------------------------

def _path_is_seqpar(path: str) -> bool:
    """Non-test files named ``seqpar*`` under ``parallel/`` — the modules
    whose whole point is never holding the full sequence on one device."""
    parts = path.replace("\\", "/").split("/")
    return (not _path_is_test(path) and "parallel" in parts
            and parts[-1].startswith("seqpar"))


def _einsum_is_dense_scores(equation: str) -> bool:
    """True for ``"bqnd,bknd->bnqk"``-shaped equations: each operand
    contributes exactly one free letter to the output and those two
    letters are the output's trailing pair — the ``(..., Sq, Sk)`` outer
    product over two sequence axes, i.e. materialized attention scores.
    The trailing-pair requirement keeps ``p @ V`` contractions
    (``"bnqk,bknd->bqnd"``) and plain projections clean."""
    try:
        ins, out = equation.replace(" ", "").split("->")
        a, b = ins.split(",")
    except ValueError:
        return False
    free_a = (set(a) - set(b)) & set(out)
    free_b = (set(b) - set(a)) & set(out)
    if len(free_a) != 1 or len(free_b) != 1 or len(out) < 2:
        return False
    return set(out[-2:]) == free_a | free_b


def _enclosing_function_name(node: ast.AST) -> str:
    cur = _parent(node)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return cur.name
        cur = _parent(cur)
    return ""


def check_seqpar_discipline(tree: ast.AST, path: str) -> list[Finding]:
    """JL024: dense ``(S, S)`` score materialization or unpermuted full-KV
    gathers inside ``parallel/seqpar``.

    The ring's contract is that no device ever holds more than one
    sequence chunk of K/V or one chunk-pair tile of scores: KV moves by
    ``ppermute`` (peer-to-peer, O(local) memory) and scores exist only
    per hop. Two AST shapes break that contract mechanically:

    - ``jax.lax.all_gather`` — reassembles the full sequence on every
      device, turning the ring into replicated attention with extra
      steps (memory scales with S again, exactly what the seq axis was
      bought to avoid);
    - a score-shaped ``einsum`` (output carrying a free sequence letter
      from each operand) outside a per-hop helper (function name
      containing ``hop``) — at module scope that outer product is the
      full (S, S) score matrix, not a chunk tile.

    ``tests/lint_fixtures/jimm_tpu/parallel/`` keeps the living fixture."""
    if not _path_is_seqpar(path):
        return []
    imported_gather: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module in (
                "jax.lax", "jax._src.lax.parallel"):
            for alias in node.names:
                if alias.name == "all_gather":
                    imported_gather.add(alias.asname or alias.name)
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fname = _dotted(node.func)
        if fname is None:
            continue
        leaf = fname.rsplit(".", 1)[-1]
        if fname in imported_gather or (leaf == "all_gather"
                                        and fname.endswith("lax.all_gather")):
            findings.append(Finding(
                "JL024", ERROR, path, node.lineno,
                "all_gather inside parallel/seqpar reassembles the full "
                "KV sequence on every device — per-device memory scales "
                "with S again, defeating the seq axis. Rotate chunks with "
                "jax.lax.ppermute (see _rotate), or justify with "
                "# jaxlint: disable=JL024"))
            continue
        if leaf == "einsum" and node.args \
                and isinstance(node.args[0], ast.Constant) \
                and isinstance(node.args[0].value, str) \
                and _einsum_is_dense_scores(node.args[0].value) \
                and "hop" not in _enclosing_function_name(node):
            findings.append(Finding(
                "JL024", ERROR, path, node.lineno,
                "score-shaped einsum outside a per-hop helper "
                "materializes the dense (S, S) score matrix — seqpar "
                "scores may only exist one chunk-pair tile at a time "
                "inside *hop* functions, or justify with "
                "# jaxlint: disable=JL024"))
    return findings


# ---------------------------------------------------------------------------

def run_all(tree: ast.AST, path: str,
            vmem_budget: int | None = None) -> list[Finding]:
    _annotate_parents(tree)
    findings: list[Finding] = []
    findings += check_host_sync_in_jit(tree, path)
    findings += check_train_step_donation(tree, path)
    findings += check_partition_spec_axes(tree, path)
    findings += check_pallas_tiling(tree, path, vmem_budget)
    findings += check_async_host_sync(tree, path)
    findings += check_bare_print(tree, path)
    findings += check_jit_in_loop(tree, path)
    findings += check_block_size_literal(tree, path)
    findings += check_device_put_placement(tree, path)
    findings += check_host_sort(tree, path)
    findings += check_quant_upcast(tree, path)
    findings += check_swallowed_exception(tree, path)
    findings += check_unbounded_tenant_table(tree, path)
    findings += check_journal_bypass(tree, path)
    findings += check_bare_lowp_cast(tree, path)
    findings += check_cascade_thresholds(tree, path)
    findings += check_profiler_bypass(tree, path)
    findings += check_seqpar_discipline(tree, path)
    return findings
