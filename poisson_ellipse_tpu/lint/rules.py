"""The tpulint rule registry: TPU001–TPU022.

Each rule is a generator over a :class:`~poisson_ellipse_tpu.lint.visitor.
Module`, yielding :class:`~poisson_ellipse_tpu.lint.report.Finding`s.
Suppression (``# tpulint: disable=CODE``) and select/ignore filtering are
applied by the runner, not here. Rules are deliberately conservative:
when a shape, dtype or callee cannot be resolved statically they stay
silent — a lint gate that cries wolf gets deleted from CI.

| code   | name               | hazard                                        |
|--------|--------------------|-----------------------------------------------|
| TPU001 | f64-literal        | float64 dtype silently downcast w/o x64       |
| TPU002 | traced-branch      | Python if/while on a traced value             |
| TPU003 | host-sync          | host sync reachable from a jitted hot loop    |
| TPU004 | missing-donation   | jit with large-array params, no donate_argnums|
| TPU005 | pallas-tile        | BlockSpec off the (8, 128) grid / VMEM budget |
| TPU006 | jit-per-call       | jax.jit rebuilt per loop step / per call      |
| TPU007 | unfused-reductions | adjacent independent global reductions in one |
|        |                    | loop body that could share a stacked collective|
| TPU008 | host-sync-in-loop  | host sync / host callback inside a traced loop|
|        |                    | body, or a fence-wrapper sync in a per-dispatch|
|        |                    | Python measurement loop                        |
| TPU009 | swallowed-exception| bare/broad `except` whose handler neither     |
|        |                    | re-raises nor hands off to a configured       |
|        |                    | classify-and-re-raise helper — device-runtime |
|        |                    | errors silently eaten                         |
| TPU010 | recompile-hazard   | `.lower().compile()` AOT chains inside Python |
|        |                    | loop bodies, and calls of static-argnum jitted|
|        |                    | callables whose static argument varies with a |
|        |                    | loop — a fresh trace+compile per iteration    |
| TPU011 | unfenced-timing    | a `time.time()`/`perf_counter()` span closing |
|        |                    | over a jitted dispatch with no fence between  |
|        |                    | the dispatch and the clock read — async       |
|        |                    | dispatch means the bracket timed the queue,   |
|        |                    | not the work                                  |
| TPU012 | unbounded-queue    | a module/class-level list or deque grown by   |
|        |                    | append with no maxlen and no draining bound — |
|        |                    | a long-lived serving process's memory leak    |
|        |                    | (the backpressure rule: bound it or shed)     |
| TPU013 | retraced-levels    | host-side recursion/loops that rebuild traced |
|        |                    | callables per call — a recursive fn holding a |
|        |                    | jit/AOT construction, or a jit-factory call   |
|        |                    | whose argument varies with a Python loop —    |
|        |                    | the MG-level recompile hazard: level count    |
|        |                    | must be static per grid bucket (TPU010's      |
|        |                    | factory-call sibling)                         |
| TPU014 | retry-without-     | an unbounded `while True` retry loop whose    |
|        | backoff            | exception handler swallows-and-loops with     |
|        |                    | neither a backoff/sleep call nor an attempt   |
|        |                    | cap in sight — the hot-spin retry storm that  |
|        |                    | turns one failing dispatch into a pegged host |
|        |                    | and a hammered runtime                        |
| TPU015 | host-roundtrip     | `float()`/`int()`/`bool()`/`.item()` on a     |
|        |                    | value derived from the array parameters of a  |
|        |                    | traced function or an `xp=`-dual geometry     |
|        |                    | function — a host round-trip that breaks the  |
|        |                    | traced path (ConcretizationTypeError on jit)  |
|        |                    | and silently downcasts the host-f64 one;      |
|        |                    | validation runs on host arrays, the traced    |
|        |                    | path stays pure                               |
| TPU016 | wall-clock-deadline| `time.time()` feeding a comparison used as a  |
|        |                    | lease/deadline/timeout — wall clocks step     |
|        |                    | under NTP, so a wall-clock lease fires early  |
|        |                    | or never; deadline arithmetic must read       |
|        |                    | `time.monotonic()` (timestamps that are only  |
|        |                    | recorded, never compared, stay silent)        |
| TPU017 | backprop-through-  | `jax.grad`/`jax.vjp` applied to a function    |
|        | loop               | that binds a `lax.while_loop`-based solver    |
|        |                    | entry without going through the implicit      |
|        |                    | (`custom_vjp`) wrapper — while_loop has no    |
|        |                    | reverse rule (trace error), and an unrolled   |
|        |                    | workaround stores thousands of iterates; the  |
|        |                    | IFT adjoint (`diff.adjoint.solve_implicit`)   |
|        |                    | is one extra solve with the same operator     |
| TPU018 | silent-downcast    | a bf16/f16 value (`.astype(bfloat16)` result  |
|        |                    | or arithmetic over such values) flows into a  |
|        |                    | reduction with no f32/f64 accumulator route — |
|        |                    | 8-mantissa-bit accumulation loses digits      |
|        |                    | linearly in n; upcast first, pass a wide      |
|        |                    | `dtype=`, or route via `mixed-accum-fns` (the |
|        |                    | storage-vs-compute fence of `ops.precision`)  |
| TPU019 | hardcoded-tunable  | a bare numeric literal bound to a tunable     |
|        |                    | knob keyword (Chebyshev degree, MG depth/ν,   |
|        |                    | s-step s, chunk size) at a solver-builder     |
|        |                    | call site (`tunable-fns`) — the autotuner     |
|        |                    | (`runtime.autotune`) can neither see nor      |
|        |                    | overrule it; route the value through the      |
|        |                    | engine-capability table, a named constant, or |
|        |                    | the tuned-config registry                     |
| TPU020 | raw-collective     | a raw jax.lax collective (psum / ppermute /   |
|        |                    | all_gather / ...) issued outside the blessed  |
|        |                    | communication modules (`collective-modules`,  |
|        |                    | default parallel/) — the contract matrix's    |
|        |                    | cadence budgets (analysis/, ENGINE_CAPS) only |
|        |                    | sweep that layer, so a stray collective       |
|        |                    | drifts the count invisibly; deliberate        |
|        |                    | exceptions carry a justified disable          |
| TPU021 | wall-clock-lease   | wall-clock reads (`time.time()`,              |
|        |                    | `datetime.now()`) used in lease/deadline      |
|        |                    | ARITHMETIC (`t0 + lease_s`, `now - started`) —|
|        |                    | TPU016's comparison prong extended: a duration|
|        |                    | or deadline COMPUTED from the wall clock is   |
|        |                    | stepped by NTP before any comparison happens; |
|        |                    | bare record-only timestamps stay silent       |
| TPU022 | unbounded-cache    | a module/class-level cache-named dict (name   |
|        |                    | contains cache/memo/pool) grown by key        |
|        |                    | assignment or setdefault with no eviction     |
|        |                    | route (pop/popitem/clear/del/rebind) — the    |
|        |                    | cache grows with the key space, not the       |
|        |                    | working set; TPU012's mapping sibling (the    |
|        |                    | solvecache LRU-cap discipline, fenced)        |
"""

from __future__ import annotations

import ast
import dataclasses
import fnmatch
import functools
import os
from typing import Callable, Iterator, Optional

from poisson_ellipse_tpu.lint.report import Finding
from poisson_ellipse_tpu.lint.visitor import Module, TracedFn


@dataclasses.dataclass
class LintConfig:
    """Knobs shared by the CLI and the pytest gate (``[tool.tpulint]``)."""

    paths: tuple[str, ...] = ("poisson_ellipse_tpu",)
    exclude: tuple[str, ...] = ()
    select: Optional[frozenset[str]] = None
    ignore: frozenset[str] = frozenset()
    per_path_ignores: dict[str, tuple[str, ...]] = dataclasses.field(
        default_factory=dict
    )
    # TPU004: only jit sites whose callee has at least this many
    # non-static positional params are assumed to carry "large" operands.
    min_donate_params: int = 3
    # TPU006: functions matching these names are jit *factories* (build
    # once, call many — the repo-wide contract); construction inside them
    # is not a per-call hazard.
    jit_factory_patterns: tuple[str, ...] = ("build_*", "make_*")
    # TPU005: itemsize assumed for tiles whose dtype cannot be resolved.
    assumed_itemsize: int = 4
    # TPU007: additional reduction-rooted callables (fnmatch patterns
    # over resolved qualnames) beyond the built-in jax.lax.psum /
    # jax.numpy.sum — a project names its own grid_dot-style wrappers
    # here so the rule sees through them.
    reduction_roots: tuple[str, ...] = ()
    # TPU008: fence-style sync wrappers (resolved-qualname fnmatch
    # patterns) — functions that block the host on device work. Calls to
    # them inside Python for/while loops are per-iteration host syncs:
    # justified exactly at timing-protocol fences, which carry an
    # annotation saying so.
    host_sync_fns: tuple[str, ...] = ("*.timing.fence", "fence")
    # TPU009: classify-and-re-raise helpers (resolved-qualname fnmatch
    # patterns). A broad handler that hands the exception to one of
    # these is compliant — the helper raises the classified SolveError
    # on the caller's behalf, so the handler body carries no literal
    # `raise` of its own.
    reraise_fns: tuple[str, ...] = ()
    # TPU010: functions matching these names are deliberate AOT warm-up
    # sites (cache fills, capacity probes) — a lower().compile() chain
    # in a loop there is the *fix* for recompile hazards, not one.
    # jit_factory_patterns are exempt as well (build-once contract).
    aot_warmup_fns: tuple[str, ...] = ("warmup*", "precompile*")
    # TPU014: backoff-style callables (leaf-name/qualname fnmatch
    # patterns). A retry loop that calls one of these between attempts
    # is pacing itself; one that calls none AND carries no attempt cap
    # is the hot-spin retry storm the rule exists to fence.
    retry_backoff_fns: tuple[str, ...] = (
        "*sleep*", "*backoff*", "idle", "*.idle", "wait", "*.wait",
    )
    # TPU017: `lax.while_loop`-based solver entries (leaf-name/qualname
    # fnmatch patterns). Applying reverse-mode autodiff to a function
    # that binds one of these — without going through the implicit
    # (custom_vjp) wrapper — either trace-errors (while_loop has no
    # reverse rule) or, via a naive unroll, backpropagates through
    # thousands of iterations.
    loop_solver_fns: tuple[str, ...] = (
        "pcg", "pcg_pipelined", "pcg_batched", "pcg_batched_pipelined",
        "guarded_solve", "solve_batched", "solve_sharded", "elastic_solve",
    )
    # TPU017: the implicit-differentiation wrappers whose presence in
    # the same target means the gradient is routed correctly (the IFT
    # adjoint of ``diff.adjoint``, one extra solve — not a backprop
    # through the loop).
    implicit_solver_fns: tuple[str, ...] = (
        "solve_implicit", "solve_operands", "*ImplicitSolver*",
        "custom_linear_solve",
    )
    # TPU018: sanctioned mixed-precision reducers (fnmatch patterns) —
    # callables that take narrow (bf16/f16) operands but accumulate at
    # f32/f64 internally (the mixed Pallas kernels, ops.precision's
    # helpers). A narrow value flowing into one of these is the
    # designed route, not a silent downcast.
    mixed_accum_fns: tuple[str, ...] = (
        "*_mixed_pallas", "*.precision.load", "*.precision.store",
    )
    # TPU019: solver-builder callables (leaf-name/qualname fnmatch
    # patterns) whose tunable-knob keyword arguments must come from the
    # autotune registry / engine-capability table / named constants —
    # a bare numeric literal at one of these call sites is a hardcoded
    # tunable the autotuner can never see or overrule.
    tunable_fns: tuple[str, ...] = (
        "build_solver", "build_*_solver", "build_*_stepper",
        "make_precond", "make_vcycle", "make_fcycle", "guarded_solve",
        "solve_batched", "pcg_sstep", "resolve_fmg_config",
    )
    # TPU020: the modules licensed to issue raw jax.lax collectives
    # ("/"-normalized path fnmatch patterns). Every cadence the contract
    # matrix (analysis/) pins — psums per body, halo ppermute budgets —
    # is counted over the communication layer; a collective issued
    # outside it is invisible to those budgets until it breaks one.
    collective_modules: tuple[str, ...] = (
        "*/parallel/*", "parallel/*",
    )
    # TPU021: the wall-clock sources whose results must not feed
    # lease/deadline/duration arithmetic (resolved-qualname fnmatch
    # patterns — a project wrapping another stepping clock, e.g.
    # `arrow.utcnow`, extends the set here). time.monotonic() and
    # perf_counter() are immune by construction and never listed.
    wall_clock_fns: tuple[str, ...] = (
        "time.time", "datetime.datetime.now", "datetime.datetime.utcnow",
        "datetime.now", "datetime.utcnow",
    )


@dataclasses.dataclass(frozen=True)
class Rule:
    code: str
    name: str
    summary: str
    check: Callable[[Module, LintConfig], Iterator[Finding]]


RULES: dict[str, Rule] = {}


def rule(code: str, name: str, summary: str):
    def deco(fn):
        RULES[code] = Rule(code, name, summary, fn)
        return fn

    return deco


def _finding(module: Module, node: ast.AST, code: str, message: str) -> Finding:
    return Finding(
        path=module.path,
        line=getattr(node, "lineno", 1),
        col=getattr(node, "col_offset", 0) + 1,
        code=code,
        message=message,
    )


# --------------------------------------------------------------------------
# TPU001 — float64 literals that silently downcast under disabled x64
# --------------------------------------------------------------------------

_F64_NAMES = frozenset(
    {"jax.numpy.float64", "jax.numpy.double", "numpy.float64", "numpy.double"}
)
_F64_STRINGS = frozenset({"float64", "double", "f8", "<f8"})
# positional index of the dtype parameter for common jnp constructors
_DTYPE_POS = {
    "array": 1, "asarray": 1, "zeros": 1, "ones": 1, "empty": 1, "full": 2,
}


def _is_f64_dtype_expr(module: Module, node: ast.AST) -> bool:
    q = module.qualname(node)
    if q == "float" or q in _F64_NAMES:
        return True
    return isinstance(node, ast.Constant) and node.value in _F64_STRINGS


@rule(
    "TPU001",
    "f64-literal",
    "float64/`float` dtypes under jnp silently downcast to float32 when "
    "jax_enable_x64 is off",
)
def check_f64_literal(module: Module, config: LintConfig) -> Iterator[Finding]:
    flagged: set[tuple[int, int]] = set()

    def flag(node, msg):
        key = (node.lineno, node.col_offset)
        if key not in flagged:
            flagged.add(key)
            yield _finding(module, node, "TPU001", msg)

    for node in ast.walk(module.tree):
        if isinstance(node, ast.Call):
            q = module.qualname(node.func) or ""
            if not q.startswith("jax.numpy."):
                continue
            dtype_expr = None
            for kw in node.keywords:
                if kw.arg == "dtype":
                    dtype_expr = kw.value
            pos = _DTYPE_POS.get(q.rsplit(".", 1)[1])
            if dtype_expr is None and pos is not None and pos < len(node.args):
                dtype_expr = node.args[pos]
            if dtype_expr is not None and _is_f64_dtype_expr(module, dtype_expr):
                yield from flag(
                    dtype_expr,
                    f"`{q.removeprefix('jax.')}` built with a float64/"
                    "`float` dtype: silently becomes float32 under disabled "
                    "x64 — spell the narrow dtype you mean, or gate on "
                    "`jax.config.jax_enable_x64`",
                )
        elif isinstance(node, (ast.Attribute, ast.Name)):
            if module.qualname(node) in ("jax.numpy.float64", "jax.numpy.double"):
                parent = Module.parent(node)
                if isinstance(parent, ast.Attribute):
                    continue  # the inner part of a longer dotted name
                yield from flag(
                    node,
                    "`jnp.float64` is float32 under disabled x64 — this "
                    "reference silently changes meaning with the flag",
                )


# --------------------------------------------------------------------------
# TPU002 — Python control flow on traced values
# --------------------------------------------------------------------------


@rule(
    "TPU002",
    "traced-branch",
    "Python `if`/`while` on a traced value inside a jit/loop-body function",
)
def check_traced_branch(module: Module, config: LintConfig) -> Iterator[Finding]:
    for fn in module.traced_fns:
        tainted = module.tainted_names(fn)
        if not tainted:
            continue
        for node in ast.walk(fn.node):
            if isinstance(node, (ast.If, ast.While)) and module.expr_mentions(
                node.test, tainted
            ):
                kw = "while" if isinstance(node, ast.While) else "if"
                yield _finding(
                    module,
                    node,
                    "TPU002",
                    f"Python `{kw}` on a traced value in a {fn.kind} "
                    "function: fails at trace time or silently bakes one "
                    "branch into the compile — use `jax.lax.cond`/"
                    "`jnp.where` (or mark the argument static)",
                )


# --------------------------------------------------------------------------
# TPU003 — host syncs reachable from jitted hot loops
# --------------------------------------------------------------------------

_HOST_SYNC_METHODS = frozenset({"block_until_ready", "item", "tolist"})
_HOST_SYNC_CALLS = frozenset(
    {"jax.block_until_ready", "jax.device_get", "numpy.asarray", "numpy.array"}
)
_HOST_CAST_BUILTINS = frozenset({"float", "int", "bool"})


def _host_sync_site(module: Module, node: ast.Call, tainted: set[str]):
    """Classify one Call as a host-sync construct, or None.

    The single source of the matcher + taint semantics shared by TPU003
    and TPU008 (two copies drifted once — the numpy taint guard — so the
    classification lives here exactly once). Returns (kind, label):
    kind "method" (``x.item()``-style), "call" (``jax.device_get`` /
    host-numpy materialisation of a traced value), or "cast"
    (``float(x)`` on a traced value).
    """
    q = module.qualname(node.func) or ""
    if (
        isinstance(node.func, ast.Attribute)
        and node.func.attr in _HOST_SYNC_METHODS
        and q not in _HOST_SYNC_CALLS
    ):
        return "method", node.func.attr
    if q in _HOST_SYNC_CALLS:
        # numpy.asarray/array only sync when fed a traced value; on host
        # constants they are trace-time constant folding, not a sync
        needs_taint = q.startswith("numpy.")
        if not needs_taint or (
            node.args and module.expr_mentions(node.args[0], tainted)
        ):
            return "call", q
        return None
    if (
        isinstance(node.func, ast.Name)
        and node.func.id in _HOST_CAST_BUILTINS
        and q == node.func.id  # not shadowed by an import
        and node.args
        and module.expr_mentions(node.args[0], tainted)
    ):
        return "cast", node.func.id
    return None


def _host_sync_findings(
    module: Module,
    fn_node: ast.AST,
    tainted: set[str],
    origin: str,
    seen: set[tuple[int, frozenset[str]]],
    depth: int = 0,
) -> Iterator[Finding]:
    key = (id(fn_node), frozenset(tainted))
    if key in seen or depth > 8:
        return
    seen.add(key)
    for node in ast.walk(fn_node):
        if not isinstance(node, ast.Call):
            continue
        site = _host_sync_site(module, node, tainted)
        if site is not None:
            kind, label = site
            message = {
                "method": (
                    f"`.{label}()` is a host sync reachable from "
                    f"{origin}: the loop stalls on a device round-trip "
                    "every dispatch — hoist it out of the hot path"
                ),
                "call": (
                    f"`{label}` forces a device→host transfer reachable "
                    f"from {origin} — keep the hot loop device-resident"
                ),
                "cast": (
                    f"`{label}()` on a traced value reachable from "
                    f"{origin}: blocks on the device to produce a Python "
                    "scalar — keep the value on device or move the cast "
                    "out of the traced path"
                ),
            }[kind]
            yield _finding(module, node, "TPU003", message)
            continue
        if isinstance(node.func, ast.Attribute) or (
            module.qualname(node.func) or ""
        ) in _HOST_SYNC_CALLS:
            # a classified-negative sync-shaped call (e.g. untainted
            # numpy.asarray): don't descend into it as a local callee
            continue
        # shallow same-module reachability: follow calls to local defs,
        # mapping argument taint onto their parameters
        if isinstance(node.func, ast.Name):
            callee = module.functions.get(node.func.id)
            if callee is not None and callee is not fn_node:
                params = [p.arg for p in callee.args.args]
                callee_tainted = {
                    params[i]
                    for i, arg in enumerate(node.args)
                    if i < len(params) and module.expr_mentions(arg, tainted)
                }
                yield from _host_sync_findings(
                    module, callee, callee_tainted, origin, seen, depth + 1
                )


@rule(
    "TPU003",
    "host-sync",
    "host-sync call (`.block_until_ready()`, `float(x)`, `np.asarray`) "
    "reachable from a jitted hot loop",
)
def check_host_sync(module: Module, config: LintConfig) -> Iterator[Finding]:
    """Division of labour with TPU008: syncs lexically inside a
    ``while_loop``/``scan``/``fori_loop`` body are that rule's territory
    (one defect, one code, one suppression) — this rule covers the
    jit-def/jit-call surface and its same-module reachability."""
    seen: set[tuple[int, frozenset[str]]] = set()
    emitted: set[tuple[int, int]] = set()
    loop_spans = [
        (fn.node.lineno, getattr(fn.node, "end_lineno", fn.node.lineno))
        for fn in module.traced_fns
        if fn.kind == "loop-body"
    ]
    for fn in module.traced_fns:
        if fn.kind == "loop-body":
            continue  # TPU008 reports these, with the loop-specific fix
        name = getattr(fn.node, "name", "<lambda>")
        origin = f"{fn.kind} `{name}`"
        for f in _host_sync_findings(
            module, fn.node, module.tainted_names(fn), origin, seen
        ):
            if any(a <= f.line <= b for a, b in loop_spans):
                continue  # lexically inside a loop body nested in a jit fn
            if (f.line, f.col) not in emitted:
                emitted.add((f.line, f.col))
                yield f


# --------------------------------------------------------------------------
# TPU004 — jit call sites with large-array params missing donate_argnums
# --------------------------------------------------------------------------


@rule(
    "TPU004",
    "missing-donation",
    "jax.jit over a many-array-param callable without donate_argnums/"
    "donate_argnames",
)
def check_missing_donation(module: Module, config: LintConfig) -> Iterator[Finding]:
    for node in ast.walk(module.tree):
        target = None
        jit_call = None
        if isinstance(node, ast.Call):
            wrapped = module.jit_construction(node)
            if wrapped is None:
                continue
            jit_call, target = node, module.resolve_callable(wrapped)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                head = dec.func if isinstance(dec, ast.Call) else dec
                if module.is_jit_name(head) or (
                    isinstance(dec, ast.Call)
                    and dec.args
                    and module.is_jit_name(dec.args[0])
                ):
                    jit_call, target = (
                        dec if isinstance(dec, ast.Call) else None
                    ), node
        if target is None or not hasattr(target, "args"):
            continue
        if target.args.vararg is not None:
            continue  # arity unknowable
        static = (
            module._jit_static_params(jit_call, target)
            if jit_call is not None
            else frozenset()
        )
        n_params = len(
            [
                p.arg
                for p in (
                    list(getattr(target.args, "posonlyargs", []))
                    + list(target.args.args)
                )
                if p.arg not in static and p.arg not in ("self", "cls")
            ]
        )
        if n_params < config.min_donate_params:
            continue
        kwargs = {kw.arg for kw in jit_call.keywords} if jit_call is not None else set()
        if kwargs & {"donate_argnums", "donate_argnames"}:
            continue
        site = node if isinstance(node, ast.Call) else (jit_call or node)
        name = getattr(target, "name", "<lambda>")
        yield _finding(
            module,
            site,
            "TPU004",
            f"jax.jit over `{name}` ({n_params} array-like params) without "
            "donate_argnums/donate_argnames: every dispatch keeps all "
            "inputs alive alongside the outputs — donate consumed operands, "
            "or suppress with a note when callers reuse them",
        )


# --------------------------------------------------------------------------
# TPU005 — Pallas BlockSpec tiles off the (8, 128) grid / over VMEM budget
# --------------------------------------------------------------------------

_SUBLANE, _LANE = 8, 128
_ITEMSIZE_BY_DTYPE = {
    "float64": 8, "int64": 8, "float32": 4, "int32": 4, "uint32": 4,
    "bfloat16": 2, "float16": 2, "int16": 2, "int8": 1, "uint8": 1,
    "bool_": 1, "bool": 1, "float8_e4m3fn": 1, "float8_e5m2": 1,
}
_MIN_VMEM_FALLBACK = 128 * 1024 * 1024


@functools.lru_cache(maxsize=1)
def _min_vmem_capacity() -> int:
    """Smallest per-core VMEM across the supported parts, read statically
    from ``utils/device.py``'s ``_VMEM_CAPACITY`` table (no jax import:
    the linter must run identically with no accelerator runtime)."""
    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "utils", "device.py"
    )
    try:
        with open(path) as f:
            tree = ast.parse(f.read())
        namespace: dict[str, object] = {}
        for node in tree.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                target = node.targets[0]
                if isinstance(target, ast.Name) and target.id in (
                    "_MIB",
                    "_VMEM_CAPACITY",
                ):
                    code = compile(ast.Expression(node.value), path, "eval")
                    namespace[target.id] = eval(code, {}, dict(namespace))
        table = namespace.get("_VMEM_CAPACITY")
        if isinstance(table, dict) and table:
            return min(int(v) for v in table.values())
    except (OSError, SyntaxError, ValueError, NameError, TypeError):
        pass
    return _MIN_VMEM_FALLBACK


def _itemsize_of(module: Module, node: Optional[ast.AST], fallback: int) -> int:
    if node is None:
        return fallback
    q = module.qualname(node) or ""
    return _ITEMSIZE_BY_DTYPE.get(q.rsplit(".", 1)[-1], fallback)


def _blockspec_shape(module: Module, call: ast.Call):
    """(shape tuple of int-or-None, memory_space qualname) of a BlockSpec."""
    shape_expr = call.args[0] if call.args else None
    memspace = None
    for kw in call.keywords:
        if kw.arg == "block_shape":
            shape_expr = kw.value
        elif kw.arg == "memory_space":
            memspace = module.qualname(kw.value) or ""
    if not isinstance(shape_expr, (ast.Tuple, ast.List)):
        return None, memspace
    dims = tuple(
        e.value if isinstance(e, ast.Constant) and isinstance(e.value, int) else None
        for e in shape_expr.elts
    )
    return dims, memspace


def _is_vmem_space(memspace: Optional[str]) -> bool:
    return memspace is None or memspace.endswith(".VMEM")


@rule(
    "TPU005",
    "pallas-tile",
    "Pallas BlockSpec tile off the (8, 128) sublane/lane grid, or a "
    "kernel VMEM working set over the smallest supported part's budget",
)
def check_pallas_tile(module: Module, config: LintConfig) -> Iterator[Finding]:
    min_vmem = _min_vmem_capacity()
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue
        q = module.qualname(node.func) or ""
        if q.endswith(".BlockSpec") and q.startswith("jax.experimental.pallas"):
            dims, memspace = _blockspec_shape(module, node)
            if dims is None or not _is_vmem_space(memspace):
                continue
            checks = []
            if len(dims) >= 1 and dims[-1] is not None:
                checks.append((dims[-1], _LANE, "lane (minor)"))
            if len(dims) >= 2 and dims[-2] is not None:
                checks.append((dims[-2], _SUBLANE, "sublane (second-minor)"))
            for value, mult, which in checks:
                if value % mult != 0:
                    yield _finding(
                        module,
                        node,
                        "TPU005",
                        f"BlockSpec {which} dim {value} is not a multiple "
                        f"of {mult}: Mosaic pads every tile to the "
                        f"({_SUBLANE}, {_LANE}) grid, silently wasting "
                        "VMEM and lanes — pick an aligned tile",
                    )
        elif q.endswith(".pallas_call"):
            total = 0
            for kw in node.keywords:
                if kw.arg != "scratch_shapes":
                    continue
                entries = (
                    kw.value.elts
                    if isinstance(kw.value, (ast.Tuple, ast.List))
                    else []
                )
                for entry in entries:
                    if not isinstance(entry, ast.Call):
                        continue
                    eq = module.qualname(entry.func) or ""
                    if not eq.endswith(".VMEM"):
                        continue
                    shape = entry.args[0] if entry.args else None
                    if not isinstance(shape, (ast.Tuple, ast.List)):
                        continue
                    dims = [
                        e.value
                        if isinstance(e, ast.Constant) and isinstance(e.value, int)
                        else None
                        for e in shape.elts
                    ]
                    if any(d is None for d in dims):
                        total = None  # unknowable statically: stay silent
                        break
                    n = 1
                    for d in dims:
                        n *= d
                    itemsize = _itemsize_of(
                        module,
                        entry.args[1] if len(entry.args) > 1 else None,
                        config.assumed_itemsize,
                    )
                    total += n * itemsize
                if total is None:
                    break
            if total and total > min_vmem:
                yield _finding(
                    module,
                    node,
                    "TPU005",
                    f"pallas_call VMEM scratch working set ≈{total // 1024 // 1024} "
                    f"MiB exceeds the smallest supported part's "
                    f"{min_vmem // 1024 // 1024} MiB budget "
                    "(utils/device.py capability table) — tile smaller or "
                    "gate the kernel on `utils.device.vmem_capacity_bytes`",
                )


# --------------------------------------------------------------------------
# TPU007 — adjacent un-fused global reductions in one jitted loop body
# --------------------------------------------------------------------------

# reductions every jax project has; projects add their own wrappers via
# LintConfig.reduction_roots ([tool.tpulint] reduction-roots)
_REDUCTION_ROOTS = ("jax.lax.psum", "jax.numpy.sum")


def _statement_targets(stmt: ast.stmt) -> set[str]:
    names: set[str] = set()
    targets = []
    if isinstance(stmt, ast.Assign):
        targets = stmt.targets
    elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
        targets = [stmt.target]
    elif isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
        # a def whose body holds the reduction: its influence flows
        # through the bound name (callers of the closure)
        names.add(stmt.name)
    else:
        # compound statement (if/for/with/try...) holding the reduction:
        # every name it stores is a potential carrier — over-approximate
        # so a dependent follow-up reduction stays silent
        names |= {
            n.id
            for n in ast.walk(stmt)
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Store)
        }
    for target in targets:
        names |= {n.id for n in ast.walk(target) if isinstance(n, ast.Name)}
    return names


def _reads_any(module: Module, stmt: ast.stmt, names: set[str]) -> bool:
    """Does the statement read any of ``names``? Assignments are tested
    on their value expression; compound statements (a nested ``def``
    whose body consumes a reduction-derived scalar, a loop, a ``with``)
    on the whole node — over-approximating reads keeps the rule quiet
    exactly when the dependence question gets murky."""
    node = getattr(stmt, "value", None)
    if node is None:
        node = stmt
    return module.expr_mentions(node, names)


def _reduction_sites(module: Module, stmt: ast.stmt, roots) -> list[ast.Call]:
    """Calls in ``stmt`` whose callee resolves to a global-reduction root.

    ``jnp.sum`` with an explicit ``axis=`` is a partial reduction (stays
    an array), not a scalar collective candidate — skipped.
    """
    out = []
    for node in ast.walk(stmt):
        if not isinstance(node, ast.Call):
            continue
        q = module.qualname(node.func) or ""
        if not any(fnmatch.fnmatch(q, pat) for pat in roots):
            continue
        if q.rsplit(".", 1)[-1] == "sum" and (
            len(node.args) > 1  # positional axis: jnp.sum(a, 0)
            or any(kw.arg in ("axis", "axes") for kw in node.keywords)
        ):
            continue
        out.append(node)
    return out


@rule(
    "TPU007",
    "unfused-reductions",
    "two adjacent independent global reductions (psum / jnp.sum-rooted "
    "dots) in one jitted loop body that could share a single stacked "
    "collective",
)
def check_unfused_reductions(module: Module, config: LintConfig) -> Iterator[Finding]:
    """Inside a ``lax.while_loop``/``scan``/``fori_loop`` body, two
    reduction-rooted statements with no data dependence between them
    serialize the loop on two reduce→broadcast latencies where a single
    stacked reduction (``jnp.stack`` of the partials → one ``psum`` /
    one fused sum pass) would pay one. Reductions that are genuinely
    sequential — the second reads a value derived from the first — are
    the algorithm's critical path, not a fusion miss, and stay silent;
    so do multiple reductions already stacked into one statement.
    """
    roots = _REDUCTION_ROOTS + tuple(config.reduction_roots)
    for fn in module.traced_fns:
        if fn.kind != "loop-body":
            continue
        body = fn.node.body
        if not isinstance(body, list):
            continue  # lambda body: a single expression, one statement
        prev_line: Optional[int] = None
        taint: set[str] = set()
        for stmt in body:
            sites = _reduction_sites(module, stmt, roots)
            if not sites:
                # propagate the previous reduction's influence forward
                if prev_line is not None and _reads_any(module, stmt, taint):
                    taint |= _statement_targets(stmt)
                continue
            if prev_line is not None and not _reads_any(module, stmt, taint):
                yield _finding(
                    module,
                    sites[0],
                    "TPU007",
                    "global reduction independent of the one at line "
                    f"{prev_line} in the same loop body: the two "
                    "serialize on separate reduce->broadcast latencies "
                    "(2 collectives on a mesh) — stack the partials and "
                    "issue one fused reduction (the grid_dots / stacked-"
                    "psum idiom), or suppress with a note when the "
                    "ordering is load-bearing",
                )
            prev_line = stmt.lineno
            taint = _statement_targets(stmt)


# --------------------------------------------------------------------------
# TPU006 — jax.jit constructed per loop step / per call
# --------------------------------------------------------------------------


@rule(
    "TPU006",
    "jit-per-call",
    "jax.jit constructed inside a Python loop or per-call closure "
    "(recompilation hazard)",
)
def check_jit_per_call(module: Module, config: LintConfig) -> Iterator[Finding]:
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue
        if module.jit_construction(node) is None:
            continue
        in_loop = any(
            isinstance(anc, (ast.For, ast.While, ast.AsyncFor))
            for anc in module.ancestors(node)
        )
        if in_loop:
            yield _finding(
                module,
                node,
                "TPU006",
                "jax.jit constructed inside a Python loop: every iteration "
                "builds a fresh callable with an empty dispatch cache — "
                "hoist the jit out of the loop",
            )
            continue
        parent = Module.parent(node)
        if isinstance(parent, ast.Call) and parent.func is node:
            yield _finding(
                module,
                node,
                "TPU006",
                "jax.jit(...)(...) constructs and calls in one expression: "
                "the traced cache dies with the expression, so every "
                "evaluation recompiles — bind the jitted callable once",
            )
            continue
        enclosing = module.enclosing_function(node)
        if enclosing is None:
            continue  # module scope: constructed once at import
        name = getattr(enclosing, "name", "<lambda>")
        if any(
            fnmatch.fnmatch(name, pat) for pat in config.jit_factory_patterns
        ):
            continue
        stmt = module.nearest_statement(node)
        if isinstance(stmt, ast.Return):
            continue  # a factory by shape: the jit object is the product
        yield _finding(
            module,
            node,
            "TPU006",
            f"jax.jit constructed per call of `{name}` (neither returned "
            "nor in a recognised factory): callers re-entering this "
            "function retrace from scratch — hoist the jit, return it, or "
            "suppress with a note when single-shot construction is the "
            "point",
        )


# --------------------------------------------------------------------------
# TPU008 — host syncs / host callbacks inside loop bodies
# --------------------------------------------------------------------------

# per-iteration host callback registrars: each invocation inside a loop
# body is a device->host round-trip every iteration (jax.debug.print is
# asynchronous and deliberately not listed)
_CALLBACK_REGISTRARS = frozenset(
    {
        "jax.debug.callback",
        "jax.pure_callback",
        "jax.experimental.io_callback",
    }
)


def _is_fence_wrapper(q: str, config: LintConfig) -> bool:
    return bool(q) and any(
        fnmatch.fnmatch(q, pat) for pat in config.host_sync_fns
    )


@rule(
    "TPU008",
    "host-sync-in-loop",
    "host sync or per-iteration host callback inside a traced loop body, "
    "or a fence-wrapper sync inside a per-dispatch Python loop",
)
def check_host_sync_in_loop(module: Module, config: LintConfig) -> Iterator[Finding]:
    """The stage4 anti-pattern, fenced off structurally: the reference
    synchronises host and device every PCG iteration (3 device→host
    round-trips + 6 syncs, ``poisson_mpi_cuda2.cu:846-939``), and the
    single design inversion this framework is built on is that nothing
    inside the iteration ever touches the host. Two prongs:

    - *traced loop bodies* (``lax.while_loop``/``scan``/``fori_loop``
      bodies): any host-sync construct (``.item()``, ``.tolist()``,
      ``.block_until_ready()``, ``jax.device_get``, ``float()``/``int()``/
      ``bool()`` on a traced value, a configured fence wrapper) or any
      host-callback registration (``jax.pure_callback``,
      ``jax.experimental.io_callback``, ``jax.debug.callback``) — the
      convergence-telemetry layer exists precisely so nobody needs these
      (``obs.convergence``: on-device ring buffers instead of per-
      iteration callbacks).
    - *host measurement loops*: a call to a fence-style wrapper
      (``host-sync-fns`` config; ``utils.timing.fence`` by default)
      inside a Python ``for``/``while`` loop blocks the host once per
      pass. At a timing-protocol fence that IS the measurement —
      annotate the site; anywhere else it is a dispatch-pipeline stall.
    """
    emitted: set[tuple[int, int]] = set()

    def once(finding):
        key = (finding.line, finding.col)
        if key not in emitted:
            emitted.add(key)
            yield finding

    # prong 1: traced loop bodies (nested defs included — a helper defined
    # in the body runs under the same trace)
    for fn in module.traced_fns:
        if fn.kind != "loop-body":
            continue
        tainted = module.tainted_names(fn)
        name = getattr(fn.node, "name", "<lambda>")
        for node in ast.walk(fn.node):
            if not isinstance(node, ast.Call):
                continue
            q = module.qualname(node.func) or ""
            site = _host_sync_site(module, node, tainted)
            if site is not None:
                kind, label = site
                message = {
                    "method": (
                        f"`.{label}()` inside loop body `{name}`: a host "
                        "sync EVERY iteration — the stage4 anti-pattern; "
                        "record per-iteration scalars on device instead "
                        "(obs.convergence ring buffers)"
                    ),
                    "call": (
                        f"`{label}` inside loop body `{name}`: a "
                        "device→host round-trip every iteration — keep "
                        "the loop device-resident (obs.convergence "
                        "captures per-iteration series without leaving "
                        "the chip)"
                    ),
                    "cast": (
                        f"`{label}()` on a traced value inside loop body "
                        f"`{name}`: blocks for a Python scalar every "
                        "iteration — keep the value on device"
                    ),
                }[kind]
                yield from once(_finding(module, node, "TPU008", message))
            elif _is_fence_wrapper(q, config):
                yield from once(_finding(
                    module, node, "TPU008",
                    f"`{q}` inside loop body `{name}`: a device→host "
                    "round-trip every iteration — keep the loop device-"
                    "resident (obs.convergence captures per-iteration "
                    "series without leaving the chip)",
                ))
            elif q in _CALLBACK_REGISTRARS:
                yield from once(_finding(
                    module, node, "TPU008",
                    f"`{q}` inside loop body `{name}`: registers a host "
                    "callback that fires every iteration — per-iteration "
                    "telemetry belongs in on-device buffers "
                    "(obs.convergence), not callbacks",
                ))

    # prong 2: fence wrappers inside host-level Python loops
    loop_body_fns = {
        id(fn.node) for fn in module.traced_fns if fn.kind == "loop-body"
    }
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue
        q = module.qualname(node.func) or ""
        if not _is_fence_wrapper(q, config):
            continue
        in_host_loop = False
        for anc in module.ancestors(node):
            if id(anc) in loop_body_fns:
                in_host_loop = False  # prong 1 territory
                break
            if isinstance(anc, (ast.For, ast.While, ast.AsyncFor)):
                in_host_loop = True
        if in_host_loop:
            yield from once(_finding(
                module, node, "TPU008",
                f"`{q}` inside a Python loop: one host↔device sync per "
                "pass. A timing-protocol fence is the one justified case "
                "— annotate it with a note; otherwise hoist the sync out "
                "and let dispatches pipeline",
            ))


# --------------------------------------------------------------------------
# TPU009 — bare/broad except blocks that swallow device-runtime errors
# --------------------------------------------------------------------------

_BROAD_EXCEPTION_NAMES = frozenset(
    {"Exception", "BaseException", "builtins.Exception",
     "builtins.BaseException"}
)


def _is_broad_handler(module: Module, handler: ast.ExceptHandler) -> bool:
    """Bare ``except:``, ``except Exception/BaseException``, or a tuple
    containing either. A *narrow* class the code chose deliberately
    (ValueError, XlaRuntimeError, ...) is a stated intent and stays
    silent — the hazard is the catch-all that eats whatever the device
    runtime throws."""
    if handler.type is None:
        return True
    types = (
        handler.type.elts
        if isinstance(handler.type, (ast.Tuple, ast.List))
        else [handler.type]
    )
    for t in types:
        if (module.qualname(t) or "") in _BROAD_EXCEPTION_NAMES:
            return True
    return False


def _handler_reraises(module: Module, handler: ast.ExceptHandler,
                      config: LintConfig) -> bool:
    """Does the handler body itself re-raise (or call a reraise-fn)?

    Scope-aware: a ``raise`` inside a nested ``def``/``lambda``/class is
    merely *defined* in the handler, never executed by it — descending
    into those scopes would let ``except Exception: def f(): raise``
    pass, which is exactly the swallow the rule fences (same stance as
    the other rules' traced-scope walks)."""
    stack: list[ast.AST] = list(handler.body)
    while stack:
        node = stack.pop()
        if isinstance(
            node,
            (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda, ast.ClassDef),
        ):
            continue
        if isinstance(node, ast.Raise):
            return True
        if isinstance(node, ast.Call):
            q = module.qualname(node.func) or ""
            if q and any(
                fnmatch.fnmatch(q, pat) for pat in config.reraise_fns
            ):
                return True
        stack.extend(ast.iter_child_nodes(node))
    return False


# --------------------------------------------------------------------------
# TPU010 — recompilation hazards: AOT chains in loops, loop-varying statics
# --------------------------------------------------------------------------


def _is_lower_compile_chain(node: ast.Call) -> bool:
    """``<expr>.lower(...).compile(...)`` — the AOT compile chain."""
    f = node.func
    return (
        isinstance(f, ast.Attribute)
        and f.attr == "compile"
        and isinstance(f.value, ast.Call)
        and isinstance(f.value.func, ast.Attribute)
        and f.value.func.attr == "lower"
    )


def _in_python_loop(module: Module, node: ast.AST) -> bool:
    return any(
        isinstance(anc, (ast.For, ast.While, ast.AsyncFor))
        for anc in module.ancestors(node)
    )


def _enclosing_is_exempt(module: Module, node: ast.AST,
                         config: LintConfig) -> bool:
    """Deliberate-AOT carve-out: warm-up fns and jit factories may
    compile in loops — that IS the warm pool being filled once."""
    enclosing = module.enclosing_function(node)
    if enclosing is None:
        return False
    name = getattr(enclosing, "name", "<lambda>")
    patterns = config.aot_warmup_fns + config.jit_factory_patterns
    return any(fnmatch.fnmatch(name, pat) for pat in patterns)


def _static_jit_bindings(module: Module):
    """name → (static positional indices, static keyword names) for every
    ``f = jax.jit(g, static_argnums=…/static_argnames=…)`` binding whose
    static spec is a literal. Non-literal specs stay silent (the rule's
    conservative stance)."""
    out: dict[str, tuple[frozenset[int], frozenset[str]]] = {}
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        target = node.targets[0]
        if not isinstance(target, ast.Name):
            continue
        call = node.value
        if not isinstance(call, ast.Call) or module.jit_construction(call) is None:
            continue
        nums: set[int] = set()
        names: set[str] = set()
        literal = True
        for kw in call.keywords:
            if kw.arg == "static_argnums":
                lit = Module._literal_int_tuple(kw.value)
                if lit is None and isinstance(kw.value, ast.Constant) and \
                        isinstance(kw.value.value, int):
                    lit = (kw.value.value,)
                if lit is None:
                    literal = False
                    break
                nums.update(lit)
            elif kw.arg == "static_argnames":
                vals = (
                    kw.value.elts
                    if isinstance(kw.value, (ast.Tuple, ast.List))
                    else [kw.value]
                )
                if not all(
                    isinstance(v, ast.Constant) and isinstance(v.value, str)
                    for v in vals
                ):
                    literal = False
                    break
                names.update(v.value for v in vals)
        if not literal or not (nums or names):
            continue
        out[target.id] = (frozenset(nums), frozenset(names))
    return out


def _loop_targets(loop: ast.AST) -> set[str]:
    """Names a loop rebinds per iteration: ``for`` targets, plus names
    assigned anywhere in a ``while`` body (over-approximate)."""
    if isinstance(loop, (ast.For, ast.AsyncFor)):
        return {
            n.id for n in ast.walk(loop.target) if isinstance(n, ast.Name)
        }
    names: set[str] = set()
    for stmt in loop.body:
        for n in ast.walk(stmt):
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Store):
                names.add(n.id)
    return names


@rule(
    "TPU010",
    "recompile-hazard",
    "`.lower().compile()` inside a Python loop body, or a static-argnum "
    "jitted call whose static argument varies with the loop — a fresh "
    "trace+compile per iteration/request",
)
def check_recompile_hazard(module: Module, config: LintConfig) -> Iterator[Finding]:
    """The serving-path cold-start hazard, fenced structurally.

    Two prongs (TPU006 owns the third recompile shape — ``jax.jit``
    *construction* in loops/per-call closures — so it is not repeated
    here):

    - *AOT chains in loops*: ``f.lower(args).compile()`` inside a Python
      ``for``/``while`` compiles a fresh executable every iteration —
      per-request latency in the hundreds of ms to minutes. Deliberate
      warm-up sites (a pool being filled once, a capacity probe walking
      an engine ladder) live in functions named per ``aot-warmup-fns`` /
      ``jit-factory-patterns`` and stay silent; everything else should
      bucket its shapes (``runtime.compile_cache``) or hoist.
    - *Loop-varying statics*: calling a ``jax.jit(g, static_argnums=…)``
      binding with a static-position argument that mentions a name the
      loop rebinds keys the trace cache on a fresh Python value per
      iteration — every call retraces and recompiles. Pass the value as
      a traced operand (the solvers' traced ``limit`` bound is the house
      pattern), or hoist the call.
    """
    statics = _static_jit_bindings(module)
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue
        if _is_lower_compile_chain(node):
            if _in_python_loop(module, node) and not _enclosing_is_exempt(
                module, node, config
            ):
                yield _finding(
                    module,
                    node,
                    "TPU010",
                    ".lower().compile() inside a Python loop: a fresh "
                    "XLA compile every iteration — bucket the shapes and "
                    "AOT once (runtime.compile_cache), hoist the compile, "
                    "or move it into a warm-up function (aot-warmup-fns) "
                    "if this loop IS the one-time pool fill",
                )
            continue
        if not (isinstance(node.func, ast.Name) and node.func.id in statics):
            continue
        nums, names = statics[node.func.id]
        for loop in module.ancestors(node):
            if not isinstance(loop, (ast.For, ast.While, ast.AsyncFor)):
                continue
            varying = _loop_targets(loop)
            hot_args = [
                arg
                for i, arg in enumerate(node.args)
                if i in nums and module.expr_mentions(arg, varying)
            ] + [
                kw.value
                for kw in node.keywords
                if kw.arg in names
                and module.expr_mentions(kw.value, varying)
            ]
            if hot_args:
                yield _finding(
                    module,
                    hot_args[0],
                    "TPU010",
                    f"static argument of jitted `{node.func.id}` varies "
                    "with the enclosing loop: the dispatch cache keys on "
                    "its Python value, so every iteration retraces and "
                    "recompiles — pass it as a traced operand (the "
                    "solvers' traced `limit` pattern) or hoist the call",
                )
                break


# --------------------------------------------------------------------------
# TPU011 — unfenced timing spans around jitted dispatches
# --------------------------------------------------------------------------

# wall-clock sources whose bracket defines a timing span
_TIMER_CALLS = frozenset({"time.time", "time.perf_counter", "time.monotonic"})


def _is_timer_call(module: Module, node: ast.AST) -> bool:
    return (
        isinstance(node, ast.Call)
        and (module.qualname(node.func) or "") in _TIMER_CALLS
    )


def _jitted_names(module: Module, config: LintConfig) -> frozenset[str]:
    """Names statically known to hold dispatchable compiled callables:
    bound from a ``jax.jit(...)`` construction, from a
    ``.lower().compile()`` AOT chain, or (tuple-unpacked) from a call to
    a jit factory (``jit-factory-patterns`` — the repo's ``build_*``
    return their jitted solver). Over-approximate on tuple targets: the
    non-callable elements are never *called*, so they cannot fire."""
    out: set[str] = set()
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Assign):
            continue
        value = node.value
        if not isinstance(value, ast.Call):
            continue
        leaf = (module.qualname(value.func) or "").rsplit(".", 1)[-1]
        if not (
            module.jit_construction(value) is not None
            or _is_lower_compile_chain(value)
            or any(
                fnmatch.fnmatch(leaf, pat)
                for pat in config.jit_factory_patterns
            )
        ):
            continue
        for target in node.targets:
            out.update(
                n.id for n in ast.walk(target) if isinstance(n, ast.Name)
            )
    return frozenset(out)


def _is_fence_call(module: Module, node: ast.Call, config: LintConfig) -> bool:
    """A call that blocks the host on device work: a configured fence
    wrapper (``host-sync-fns`` — the same allowlist TPU008 treats as a
    per-iteration sync), ``jax.block_until_ready``, or any
    ``.block_until_ready()`` method."""
    q = module.qualname(node.func) or ""
    if _is_fence_wrapper(q, config) or q == "jax.block_until_ready":
        return True
    return (
        isinstance(node.func, ast.Attribute)
        and node.func.attr == "block_until_ready"
    )


@rule(
    "TPU011",
    "unfenced-timing",
    "time.time()/perf_counter() span closing over a jitted dispatch with "
    "no block_until_ready/fence between the dispatch and the clock read",
)
def check_unfenced_timing(module: Module, config: LintConfig) -> Iterator[Finding]:
    """JAX dispatch is asynchronous: ``t0 = perf_counter(); out =
    solver(x); t = perf_counter() - t0`` times the enqueue, not the
    solve — a number that *looks* plausible and is off by the whole
    device execution (the bug class every fenced timing site in
    ``harness.run`` exists to avoid). The rule finds a span —
    ``NAME = <timer>()`` later read as ``<timer>() - NAME`` in the same
    scope — containing a call to a statically-known jitted callable
    (:func:`_jitted_names`) with no fence (``host-sync-fns`` config,
    ``jax.block_until_ready``, or a ``.block_until_ready()`` method —
    the TPU008 fence allowlist, reused) between the LAST such dispatch
    and the closing clock read. Deadline checks (``timer() - t0`` in a
    different function, the guard's pattern) and compile/host-only
    brackets stay silent by construction."""
    jitted = _jitted_names(module, config)
    if not jitted:
        return

    def scope_nodes(scope):
        """Nodes belonging to ``scope`` itself — nested function/lambda
        bodies are their own span scopes (a start in one function and a
        clock read in another is not a span) and are not descended into."""
        skip = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
        stack = [n for n in scope.body if not isinstance(n, skip)]
        while stack:
            node = stack.pop()
            yield node
            stack.extend(
                c
                for c in ast.iter_child_nodes(node)
                if not isinstance(c, skip)
            )

    scopes: list[ast.AST] = [module.tree]
    scopes += [
        n
        for n in ast.walk(module.tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    ]
    emitted: set[tuple[int, int]] = set()
    for scope in scopes:
        starts: dict[str, list[int]] = {}
        closes: list[tuple[int, str, ast.AST]] = []
        jit_lines: list[int] = []
        fence_lines: list[int] = []
        for node in scope_nodes(scope):
            if isinstance(node, ast.Assign) and _is_timer_call(
                module, node.value
            ):
                for target in node.targets:
                    if isinstance(target, ast.Name):
                        starts.setdefault(target.id, []).append(node.lineno)
            elif (
                isinstance(node, ast.BinOp)
                and isinstance(node.op, ast.Sub)
                and _is_timer_call(module, node.left)
                and isinstance(node.right, ast.Name)
            ):
                closes.append((node.lineno, node.right.id, node))
            elif isinstance(node, ast.Call):
                if _is_fence_call(module, node, config):
                    fence_lines.append(node.lineno)
                elif (
                    isinstance(node.func, ast.Name)
                    and node.func.id in jitted
                ):
                    jit_lines.append(node.lineno)
        for close_line, name, close_node in closes:
            opened = [ln for ln in starts.get(name, []) if ln < close_line]
            if not opened:
                continue
            start_line = max(opened)
            dispatches = [
                ln for ln in jit_lines if start_line < ln < close_line
            ]
            if not dispatches:
                continue
            last = max(dispatches)
            if any(last <= ln <= close_line for ln in fence_lines):
                continue
            key = (close_node.lineno, close_node.col_offset)
            if key in emitted:
                continue
            emitted.add(key)
            yield _finding(
                module,
                close_node,
                "TPU011",
                f"timing span `{name}` closes over the jitted dispatch at "
                f"line {last} with no fence: dispatch is asynchronous, so "
                "this bracket measured the enqueue, not the device work — "
                "fence the result (utils.timing.fence / "
                "jax.block_until_ready) before reading the clock, or "
                "suppress with a note if the enqueue itself is the "
                "measurement",
            )


# --------------------------------------------------------------------------
# TPU012 — unbounded module/class-level queues in serving/driver code
# --------------------------------------------------------------------------

# container mutations that grow / that bound a queue-shaped binding
_QUEUE_GROW = frozenset(
    {"append", "appendleft", "extend", "extendleft", "insert"}
)
_QUEUE_BOUND = frozenset({"pop", "popleft", "clear", "remove"})


def _queue_ctor(module: Module, node: ast.AST) -> Optional[str]:
    """"list"/"deque" when ``node`` constructs an unbounded growable
    container — ``[]``, ``list()``, ``deque(...)`` with no ``maxlen``,
    or ``dataclasses.field(default_factory=list|deque)`` — else None.
    A ``maxlen`` keyword (or deque's second positional) is the bound
    and silences the rule at the source."""
    if isinstance(node, ast.List) and not node.elts:
        return "list"
    if not isinstance(node, ast.Call):
        return None
    leaf = (module.qualname(node.func) or "").rsplit(".", 1)[-1]
    if leaf == "list" and not node.args and not node.keywords:
        return "list"
    if leaf == "deque":
        if len(node.args) >= 2 or any(
            kw.arg == "maxlen" for kw in node.keywords
        ):
            return None
        return "deque"
    if leaf == "field":
        for kw in node.keywords:
            if (
                kw.arg == "default_factory"
                and isinstance(kw.value, ast.Name)
                and kw.value.id in ("list", "deque")
            ):
                return kw.value.id
    return None


def _attr_is_self(node: ast.AST, attr: str) -> bool:
    return (
        isinstance(node, ast.Attribute)
        and node.attr == attr
        and isinstance(node.value, ast.Name)
        and node.value.id == "self"
    )


def _shadowing_functions(root: ast.AST, name: str) -> set:
    """Function subtrees within ``root`` where ``name`` is a *local* —
    a parameter or a bare-name assignment target without a ``global``
    declaration. Usage of the bare name inside them refers to the
    local, not the module-level candidate, and must not be smeared
    onto it (a local ``q.append`` is not a leak of the global ``q``,
    and a local ``q.pop`` is not its bound)."""
    shadowing: set = set()
    for fn in ast.walk(root):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        a = fn.args
        params = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
        if a.vararg:
            params.append(a.vararg.arg)
        if a.kwarg:
            params.append(a.kwarg.arg)
        rebinds = name in params
        declared_global = False
        # nested defs are classified on their own: prune their whole
        # subtrees, not just the def node — ast.walk would keep yielding
        # their bodies, smearing an inner local rebinding onto this
        # function and silencing real growth in it
        nested = {
            n for n in ast.walk(fn)
            if n is not fn
            and isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        }
        for node in _walk_excluding(fn, nested):
            if isinstance(node, (ast.Global, ast.Nonlocal)):
                declared_global |= name in node.names
            elif isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                targets = (
                    node.targets if isinstance(node, ast.Assign)
                    else [node.target]
                )
                rebinds |= any(
                    isinstance(t, ast.Name) and t.id == name
                    for t in targets
                )
        if rebinds and not declared_global:
            shadowing.add(fn)
    return shadowing


def _walk_excluding(root: ast.AST, exclude: set):
    """``ast.walk`` that does not descend into the ``exclude`` nodes."""
    stack = [root]
    while stack:
        node = stack.pop()
        if node in exclude and node is not root:
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def _empty_container_expr(node: ast.AST) -> bool:
    """An expression that builds a fresh empty container — the value
    side of the swap-and-reset drain idiom (``out, q = q, []``)."""
    if isinstance(node, (ast.List, ast.Tuple, ast.Set)) and not node.elts:
        return True
    if isinstance(node, ast.Dict) and not node.keys:
        return True
    if isinstance(node, ast.Call) and not node.args:
        leaf = (
            node.func.attr if isinstance(node.func, ast.Attribute)
            else node.func.id if isinstance(node.func, ast.Name)
            else None
        )
        return leaf in ("list", "deque", "set", "dict")
    return False


def _queue_usage(scope: ast.AST, matches,
                 exclude: set = frozenset(),
                 defining: ast.AST | None = None) -> tuple[bool, bool]:
    """(grows, bounded) for a candidate binding within ``scope``.
    ``matches(expr)`` tests whether an expression references the
    binding (a module-level name or a ``self.attr``); ``exclude``
    subtrees (shadowing scopes) are not descended into. Bounds: any
    shrinking method call, ``del q[...]``, a slice/index assignment
    (the windowed-drain idiom), or a rebinding to a fresh empty
    container (the swap-and-reset drain idiom) — ``defining`` is the
    candidate's own initialiser, which must not count as that bound."""
    grows = bounded = False
    for node in _walk_excluding(scope, exclude):
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
            if matches(node.func.value):
                if node.func.attr in _QUEUE_GROW:
                    grows = True
                elif node.func.attr in _QUEUE_BOUND:
                    bounded = True
        elif isinstance(node, ast.Delete):
            for target in node.targets:
                if isinstance(target, ast.Subscript) and matches(
                    target.value
                ):
                    bounded = True
        elif isinstance(node, (ast.Assign, ast.AugAssign)):
            targets = (
                node.targets if isinstance(node, ast.Assign) else [node.target]
            )
            # pair each target with its value, unpacking same-length
            # tuple assignments so `out, q = q, []` sees (q, [])
            pairs: list[tuple[ast.AST, ast.AST]] = []
            for target in targets:
                if (
                    isinstance(target, (ast.Tuple, ast.List))
                    and isinstance(node.value, (ast.Tuple, ast.List))
                    and len(target.elts) == len(node.value.elts)
                ):
                    pairs.extend(zip(target.elts, node.value.elts))
                else:
                    pairs.append((target, node.value))
            for target, value in pairs:
                if isinstance(target, ast.Subscript) and matches(
                    target.value
                ):
                    bounded = True
                elif (
                    isinstance(node, ast.Assign)
                    and node is not defining
                    and matches(target)
                    and _empty_container_expr(value)
                ):
                    bounded = True
    return grows, bounded


@rule(
    "TPU012",
    "unbounded-queue",
    "module/class-level list or deque grown by append with no maxlen and "
    "no draining bound — a long-lived serving process leaks memory",
)
def check_unbounded_queue(module: Module, config: LintConfig) -> Iterator[Finding]:
    """The backpressure rule, fenced structurally.

    A request queue, event buffer or result list that lives at module
    or instance scope and only ever grows is fine in a batch job and a
    memory leak in a server: admission without a bound converts
    overload into latency and then into an OOM kill (the failure mode
    ``serve.queue`` exists to prevent — reject loudly with
    ``retry_after`` instead of buffering forever). Candidates are
    *long-lived* bindings only — module-level names and ``self``
    attributes (including ``dataclasses.field(default_factory=list)``)
    initialised to ``[]``/``list()``/``deque()`` without ``maxlen`` —
    that some function then grows (``append``/``extend``/…). Function
    locals are scoped to one call and stay silent. Any visible bound —
    ``deque(maxlen=…)``, a shrinking call (``pop``/``popleft``/
    ``clear``/``remove``), a ``del q[…]`` window trim, or a slice
    assignment — silences the finding: the rule wants *a* bound, not a
    particular one (``obs.metrics.Histogram``'s windowed ``del`` is the
    house pattern)."""
    # module-level names
    for stmt in module.tree.body:
        target = value = None
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 and (
            isinstance(stmt.targets[0], ast.Name)
        ):
            target, value = stmt.targets[0], stmt.value
        elif isinstance(stmt, ast.AnnAssign) and isinstance(
            stmt.target, ast.Name
        ) and stmt.value is not None:
            target, value = stmt.target, stmt.value
        if target is None:
            continue
        kind = _queue_ctor(module, value)
        if kind is None:
            continue
        name = target.id

        def matches(expr, name=name):
            return isinstance(expr, ast.Name) and expr.id == name

        grows, bounded = _queue_usage(
            module.tree, matches,
            exclude=_shadowing_functions(module.tree, name),
            defining=stmt,
        )
        if grows and not bounded:
            yield _finding(
                module,
                stmt,
                "TPU012",
                f"module-level {kind} `{name}` grows via append with no "
                "bound: a long-lived serving process leaks memory here — "
                "bound it (deque(maxlen=...), a windowed del, a drain) "
                "or shed at admission (serve.queue's backpressure "
                "contract)",
            )
    # class-level / instance attributes
    for cls in ast.walk(module.tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        candidates: dict[str, tuple[ast.AST, str]] = {}
        for stmt in cls.body:
            target = value = None
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 and (
                isinstance(stmt.targets[0], ast.Name)
            ):
                target, value = stmt.targets[0], stmt.value
            elif isinstance(stmt, ast.AnnAssign) and isinstance(
                stmt.target, ast.Name
            ) and stmt.value is not None:
                target, value = stmt.target, stmt.value
            if target is None:
                continue
            kind = _queue_ctor(module, value)
            if kind is not None:
                candidates[target.id] = (stmt, kind)
        for node in ast.walk(cls):
            if isinstance(node, ast.Assign):
                pairs = [(t, node.value) for t in node.targets]
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                # `self.q: deque = deque()` — an annotation must not
                # exempt the exact initialiser the rule exists to catch
                pairs = [(node.target, node.value)]
            else:
                continue
            for target, value in pairs:
                if (
                    isinstance(target, ast.Attribute)
                    and isinstance(target.value, ast.Name)
                    and target.value.id == "self"
                ):
                    kind = _queue_ctor(module, value)
                    if kind is not None and target.attr not in candidates:
                        candidates[target.attr] = (node, kind)
        for attr, (site, kind) in candidates.items():

            def matches(expr, attr=attr, cls_name=cls.name):
                # self.attr or ClassName.attr — a bare method-local
                # name sharing the attribute's spelling is a different
                # binding and must not be smeared onto it
                return _attr_is_self(expr, attr) or (
                    isinstance(expr, ast.Attribute)
                    and expr.attr == attr
                    and isinstance(expr.value, ast.Name)
                    and expr.value.id == cls_name
                )

            grows, bounded = _queue_usage(cls, matches, defining=site)
            if grows and not bounded:
                yield _finding(
                    module,
                    site,
                    "TPU012",
                    f"instance-level {kind} `{attr}` of class "
                    f"`{cls.name}` grows via append with no bound: every "
                    "request leaves a residue a long-lived server never "
                    "frees — bound it (deque(maxlen=...), a windowed del "
                    "like obs.metrics.Histogram, a drain) or shed at "
                    "admission",
                )


# --------------------------------------------------------------------------
# TPU013 — traced callables rebuilt by host recursion / loop-varying factories
# --------------------------------------------------------------------------


@rule(
    "TPU013",
    "retraced-levels",
    "host-side Python recursion holding a jit/AOT construction, or a "
    "jit-factory call whose argument varies with an enclosing Python "
    "loop — a fresh trace+compile per recursion level / iteration",
)
def check_retraced_levels(module: Module, config: LintConfig) -> Iterator[Finding]:
    """The multigrid-levels recompile hazard, fenced structurally.

    A V-cycle written as host recursion that jits per level — or a
    driver looping over level/engine configurations through a
    ``build_*``/``make_*`` factory — keys a fresh trace on every call,
    so what reads as an O(levels) loop compiles O(levels) executables
    per *solve*. The house contract is the opposite: level count is a
    STATIC config per grid bucket, the recursion unrolls inside ONE
    traced computation (``mg.vcycle``), and factories are called once
    at build time. Two prongs (TPU010 owns the raw ``.lower().compile()``
    -in-loop and static-argnum shapes; TPU006 the jit-construction-in-
    loop shape — neither is repeated here):

    - *recursive trace construction*: a function that calls itself AND
      constructs ``jax.jit`` / a ``.lower().compile()`` chain in its
      body — recursion depth is a runtime value, so each level builds
      its own traced callable with its own empty cache.
    - *loop-varying factory calls*: a call to a jit factory
      (``jit-factory-patterns`` — the names whose return value is a
      compiled callable) inside a Python loop, with an argument that
      mentions a name the loop rebinds: one fresh solver build (trace +
      compile) per iteration. Deliberate build-per-rung sites (warm-up
      pools, capacity/degradation ladders) live in exempt functions
      (``aot-warmup-fns`` / factories) or carry an annotation saying
      why the rebuild IS the point.
    """
    exempt_pats = config.aot_warmup_fns + config.jit_factory_patterns
    for node in ast.walk(module.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if any(fnmatch.fnmatch(node.name, pat) for pat in exempt_pats):
            # a factory's JOB is construction: bounded build-time
            # recursion (the auto-engine chain) is not the hot path
            continue
        calls_self = any(
            isinstance(c, ast.Call)
            and isinstance(c.func, ast.Name)
            and c.func.id == node.name
            for c in ast.walk(node)
        )
        if not calls_self:
            continue
        for c in ast.walk(node):
            if isinstance(c, ast.Call) and (
                module.jit_construction(c) is not None
                or _is_lower_compile_chain(c)
            ):
                yield _finding(
                    module,
                    c,
                    "TPU013",
                    f"recursive `{node.name}` builds a traced callable "
                    "per recursion level: the level count becomes a "
                    "runtime value and every call re-traces — make the "
                    "level list static and unroll the recursion inside "
                    "one traced function (the mg.vcycle pattern)",
                )
                break

    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue
        if isinstance(node.func, ast.Name):
            leaf = node.func.id
        elif isinstance(node.func, ast.Attribute):
            leaf = node.func.attr
        else:
            continue
        if not any(
            fnmatch.fnmatch(leaf, pat)
            for pat in config.jit_factory_patterns
        ):
            continue
        # the patterns name PROJECT factories; jax's own make_*/build_*
        # helpers (pltpu.make_async_copy & co.) are in-trace primitives,
        # not trace factories
        if (module.qualname(node.func) or "").startswith("jax."):
            continue
        if _enclosing_is_exempt(module, node, config):
            continue
        for loop in module.ancestors(node):
            if not isinstance(loop, (ast.For, ast.While, ast.AsyncFor)):
                continue
            varying = _loop_targets(loop)
            hot = [
                arg
                for arg in list(node.args)
                + [kw.value for kw in node.keywords]
                if module.expr_mentions(arg, varying)
            ]
            if hot:
                yield _finding(
                    module,
                    hot[0],
                    "TPU013",
                    f"jit factory `{leaf}` called with a loop-varying "
                    "argument: every iteration traces and compiles a "
                    "fresh solver — hoist the build, make the varying "
                    "config static per bucket (runtime.compile_cache), "
                    "or suppress with a note when the per-rung rebuild "
                    "is deliberate (degradation ladders, warm-up fills)",
                )
                break


@rule(
    "TPU009",
    "swallowed-exception",
    "bare/broad `except` whose handler neither re-raises nor calls a "
    "configured classify-and-re-raise helper",
)
def check_swallowed_exception(module: Module, config: LintConfig) -> Iterator[Finding]:
    """A compiled dispatch fails through exactly one channel: the
    exception. XLA's RESOURCE_EXHAUSTED, a Mosaic compile error, a
    poisoned-carry assertion — all arrive as a ``RuntimeError`` a bare
    ``except`` will happily eat, turning a classifiable failure into a
    silently wrong or missing result (the reference's CUDA stages check
    no return codes at all — SURVEY §5; this rule is the regression
    fence for the opposite stance). A broad handler is compliant when
    its body re-raises (anything — the classified ``SolveError``
    classification in ``resilience.errors`` is the house idiom) or hands the
    exception to a ``reraise-fns``-configured helper; genuinely
    deliberate swallows (best-effort accounting, report-the-failure
    rows) carry a ``# tpulint: disable=TPU009`` with a note, exactly
    like every other waived finding."""
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Try):
            continue
        for handler in node.handlers:
            if not _is_broad_handler(module, handler):
                continue
            if _handler_reraises(module, handler, config):
                continue
            label = (
                "bare `except:`"
                if handler.type is None
                else f"`except {ast.unparse(handler.type)}`"
            )
            yield _finding(
                module,
                handler,
                "TPU009",
                f"{label} swallows device-runtime errors: OOM, compile "
                "failures and poisoned-solve exceptions all arrive here "
                "and vanish — re-raise a classified error "
                "(resilience.errors.SolveError), call a reraise-fns "
                "helper, or suppress with a note when the swallow is "
                "deliberate",
            )


# --------------------------------------------------------------------------
# TPU014 — unbounded retry loops with neither backoff nor an attempt cap
# --------------------------------------------------------------------------


def _walk_same_scope(root: ast.AST):
    """Walk a subtree WITHOUT descending into nested function/class
    definitions — their loops and handlers belong to their own scope."""
    stack = list(ast.iter_child_nodes(root))
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda, ast.ClassDef)):
            continue
        stack.extend(ast.iter_child_nodes(node))


def _handler_retries(handler: ast.ExceptHandler) -> bool:
    """True when the handler swallows and lets the loop spin again: no
    raise, no return, no break anywhere in its body (a `continue` or a
    plain fall-through both re-enter the loop)."""
    for node in ast.walk(handler):
        if isinstance(node, (ast.Raise, ast.Return, ast.Break)):
            return False
    return True


def _is_backoff_call(module: Module, node: ast.Call,
                     config: LintConfig) -> bool:
    if isinstance(node.func, ast.Name):
        leaf = node.func.id
    elif isinstance(node.func, ast.Attribute):
        leaf = node.func.attr
    else:
        return False
    q = module.qualname(node.func) or leaf
    return any(
        fnmatch.fnmatch(leaf, pat) or fnmatch.fnmatch(q, pat)
        for pat in config.retry_backoff_fns
    )


def _has_capped_exit(loop: ast.While) -> bool:
    """True when the loop carries a recognizable attempt cap: an `if`
    whose test is a comparison and whose body OR else-arm exits the
    loop (raise / return / break) — both the `if attempt > budget:
    raise` shape and its inverted `if attempt <= budget: continue /
    else: raise` spelling."""
    for node in _walk_same_scope(loop):
        if not isinstance(node, ast.If):
            continue
        if not isinstance(node.test, ast.Compare):
            continue
        for stmt in node.body + node.orelse:
            for sub in ast.walk(stmt):
                if isinstance(sub, (ast.Raise, ast.Return, ast.Break)):
                    return True
    return False


@rule(
    "TPU014",
    "retry-without-backoff",
    "unbounded `while True` retry loop whose handler swallows-and-loops "
    "with neither a backoff call nor an attempt cap",
)
def check_retry_without_backoff(module: Module, config: LintConfig) -> Iterator[Finding]:
    """The retry-storm fence. A serving stack retries by design — the
    scheduler's ladder, the guard's recovery budget — but every one of
    those sites is *paced* (exponential backoff through a sleep/idle
    callable) or *capped* (`attempt > budget` raising a classified
    error). A `while True:` whose `except` swallows the failure and
    loops again with neither is the pattern that turns one failing
    dispatch into a pegged host core and a hammered device runtime —
    and, at pod scale, one sick worker into a thundering herd.

    Conservative by construction: only constant-true `while` loops are
    considered (a tested loop condition is itself a bound); a handler
    "retries" only when its body has no raise/return/break at all; any
    call matching ``retry-backoff-fns`` (``[tool.tpulint]``) counts as
    pacing, and any compare-guarded raise/return/break as a cap.
    Worklist-draining loops whose retry consumes state (the checkpoint
    quarantine walk) carry an annotation saying so, like every other
    waived finding.
    """
    for loop in ast.walk(module.tree):
        if not isinstance(loop, ast.While):
            continue
        test = loop.test
        if not (isinstance(test, ast.Constant) and bool(test.value)):
            continue  # a real condition is a bound; out of scope
        retrying = [
            handler
            for node in _walk_same_scope(loop)
            if isinstance(node, ast.Try)
            for handler in node.handlers
            if _handler_retries(handler)
        ]
        if not retrying:
            continue
        paced = any(
            isinstance(node, ast.Call)
            and _is_backoff_call(module, node, config)
            for node in _walk_same_scope(loop)
        )
        if paced or _has_capped_exit(loop):
            continue
        yield _finding(
            module,
            retrying[0],
            "TPU014",
            "`while True` retry: this handler swallows the failure and "
            "loops again with no backoff call and no attempt cap — a "
            "failing dispatch becomes a hot spin. Pace it (retry-"
            "backoff-fns), cap it (`if attempt > budget: raise`), or "
            "suppress with a note when the retry consumes a finite "
            "worklist",
        )


# --------------------------------------------------------------------------
# TPU015 — host round-trips on traced / xp-dual geometry values
# --------------------------------------------------------------------------

_ROUNDTRIP_CALLS = frozenset({"float", "int", "bool"})
_ROUNDTRIP_METHODS = frozenset({"item", "tolist"})


def _xp_dual_fns(module: Module) -> Iterator[TracedFn]:
    """Functions following the repo's ``xp=`` array-module convention
    (``models.ellipse`` / ``geom.sdf``): one body serving BOTH the
    host-f64 numpy path and the traced jnp path. Their array parameters
    get the same taint treatment as a jitted function's — a host
    round-trip in one breaks the traced half of the contract."""
    for fn in module.functions.values():
        a = fn.args
        names = [p.arg for p in getattr(a, "posonlyargs", [])]
        names += [p.arg for p in a.args] + [p.arg for p in a.kwonlyargs]
        if "xp" not in names:
            continue
        # xp itself (and self) are module/instance handles, not data;
        # default-valued parameters are config scalars (samples=16), not
        # the coordinate arrays the dual-path contract is about
        static = {"xp", "self"}
        pos = [p.arg for p in getattr(a, "posonlyargs", [])] + [
            p.arg for p in a.args
        ]
        if a.defaults:
            static.update(pos[len(pos) - len(a.defaults):])
        for p, d in zip(a.kwonlyargs, a.kw_defaults):
            if d is not None:
                static.add(p.arg)
        yield TracedFn(fn, "xp-dual", frozenset(static))


@rule(
    "TPU015",
    "host-roundtrip",
    "float()/int()/bool()/.item() on a value derived from a traced or "
    "xp-dual function's array parameters — a host round-trip where the "
    "computation must stay pure",
)
def check_host_roundtrip(module: Module, config: LintConfig) -> Iterator[Finding]:
    """The geometry-purity fence. Admissibility validation runs on HOST
    float64 arrays by contract (``geom.validate``), and the traced
    assembly/solve path must stay pure — so any ``float(x)`` /
    ``int(x)`` / ``bool(x)`` / ``x.item()`` / ``x.tolist()`` applied to
    a value derived from the array parameters of a *traced* function
    (jit-decorated, jit-wrapped, or a lax loop body) or of an
    ``xp=``-dual geometry function is a bug by construction: under jit
    it raises ``ConcretizationTypeError`` at best (and forces a silent
    device sync at worst), and on the host path it silently collapses
    an f64 array fact into one Python scalar.

    Conservative by the registry's standing rules: only direct calls on
    expressions whose taint is established by the shallow forward taint
    of ``Module.tainted_names`` — static facts (``x.shape``,
    ``len(x)``) never taint, and untraced host drivers (the guard's
    chunk loop, the harness) are out of scope. Lax loop BODIES are
    TPU008's domain (one defect, one code): this rule keeps the
    jit-def/jit-call surface and the xp-dual geometry functions.
    """
    fns = [f for f in module.traced_fns if f.kind != "loop-body"]
    fns += list(_xp_dual_fns(module))
    seen_nodes: set[int] = set()
    for fn in fns:
        if id(fn.node) in seen_nodes:
            continue
        seen_nodes.add(id(fn.node))
        tainted = module.tainted_names(fn)
        if not tainted:
            continue
        body = fn.node.body if isinstance(fn.node.body, list) else [fn.node.body]
        for stmt in body:
            for node in ast.walk(stmt):
                if not isinstance(node, ast.Call):
                    continue
                if (
                    isinstance(node.func, ast.Name)
                    and node.func.id in _ROUNDTRIP_CALLS
                    and len(node.args) == 1
                    and module.expr_mentions(node.args[0], tainted)
                ):
                    name = getattr(fn.node, "name", "<lambda>")
                    yield _finding(
                        module,
                        node,
                        "TPU015",
                        f"`{node.func.id}(...)` on a value derived from "
                        f"the array parameters of `{name}` — a host "
                        "round-trip inside a traced/xp-dual computation. "
                        "Keep the computation in array ops; do host "
                        "conversions in the (untraced) caller on host "
                        "arrays",
                    )
                elif (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr in _ROUNDTRIP_METHODS
                    and not node.args
                    and module.expr_mentions(node.func.value, tainted)
                ):
                    name = getattr(fn.node, "name", "<lambda>")
                    yield _finding(
                        module,
                        node,
                        "TPU015",
                        f"`.{node.func.attr}()` on a value derived from "
                        f"the array parameters of `{name}` — a host "
                        "round-trip inside a traced/xp-dual computation. "
                        "Keep the computation in array ops; do host "
                        "conversions in the (untraced) caller on host "
                        "arrays",
                    )


# --------------------------------------------------------------------------
# TPU016 — wall-clock time feeding lease/deadline/timeout comparisons
# --------------------------------------------------------------------------


def _wall_clock_calls(module: Module, root: ast.AST) -> list[ast.Call]:
    """Every ``time.time()`` call in ``root``'s subtree."""
    return [
        node
        for node in ast.walk(root)
        if isinstance(node, ast.Call)
        and (module.qualname(node.func) or "") == "time.time"
    ]


def _is_ordering_compare(node: ast.Compare) -> bool:
    """A deadline check is an ORDERING comparison (<, <=, >, >=): an
    identity/equality/membership test (``is None`` lazy-init guards,
    ``rid in finished``) reads a value, not a clock order, and must not
    turn a record-only timestamp into a finding."""
    return any(
        isinstance(op, (ast.Lt, ast.LtE, ast.Gt, ast.GtE))
        for op in node.ops
    )


def _name_compared_in(scope_root: ast.AST, name: str,
                      exclude: set = frozenset()) -> bool:
    """Is ``name`` read inside any ORDERING comparison within
    ``scope_root``, excluding the ``exclude`` subtrees (scopes where
    the spelling is a different local binding — the TPU012 shadowing
    discipline, reused)?"""
    for node in _walk_excluding(scope_root, exclude):
        if not isinstance(node, ast.Compare) or not _is_ordering_compare(node):
            continue
        for sub in ast.walk(node):
            if isinstance(sub, ast.Name) and sub.id == name:
                return True
    return False


def _self_attr_compared(scope_root: ast.AST, attr: str) -> bool:
    """Is ``self.<attr>`` read inside any comparison within
    ``scope_root`` — the assignment's enclosing CLASS (methods share
    the instance, so attribute deadlines are class-wide), or the module
    for a classless ``self`` oddity? Another class's same-named
    attribute is a different instance's slot and must not be smeared
    onto a record-only timestamp here."""
    for node in ast.walk(scope_root):
        if not isinstance(node, ast.Compare) or not _is_ordering_compare(
            node
        ):
            continue
        for sub in ast.walk(node):
            if _attr_is_self(sub, attr):
                return True
    return False


def _enclosing_class(module: Module, node: ast.AST):
    """The innermost ClassDef enclosing ``node`` (None outside one)."""
    innermost = None
    for anc in module.ancestors(node):
        if isinstance(anc, ast.ClassDef):
            if innermost is None or anc.lineno >= innermost.lineno:
                innermost = anc
    return innermost


@rule(
    "TPU016",
    "wall-clock-deadline",
    "time.time() feeding a comparison used as a lease/deadline/timeout — "
    "NTP steps make wall-clock deadlines fire early or never; use "
    "time.monotonic()",
)
def check_wall_clock_deadline(module: Module, config: LintConfig) -> Iterator[Finding]:
    """The lease-correctness fence the fleet layer is built on
    (``fleet.replica``): a lease, deadline or timeout computed from
    ``time.time()`` is one NTP step away from firing years early (a
    backward step fences a healthy replica and hands its work off
    twice) or never (a forward step keeps a dead one's lease alive
    forever). ``time.monotonic()`` is immune by construction, which is
    why every clock in ``serve``/``fleet`` is injectable monotonic.

    Two conservative prongs — a wall-clock read that is merely
    *recorded* (a ``t_admit_unix`` journal field, a trace record's
    ``unix_time``) is a timestamp, not a deadline, and stays silent:

    - **compared directly** — a ``time.time()`` call anywhere inside a
      comparison (``if time.time() > deadline``, ``time.time() - t0 >
      timeout``): the comparison IS the deadline check.
    - **bound then compared** — a name (or ``self`` attribute) assigned
      an expression containing ``time.time()`` (``deadline =
      time.time() + lease_s``) that is later read inside some
      comparison in the same module: the binding feeds a deadline even
      though the compare sits elsewhere.
    """
    emitted: set[tuple[int, int]] = set()

    def once(finding):
        key = (finding.line, finding.col)
        if key not in emitted:
            emitted.add(key)
            yield finding

    # prong 1: time.time() inside an ORDERING comparison (equality/
    # membership/identity tests read values, not clock order)
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Compare) or not _is_ordering_compare(
            node
        ):
            continue
        for call in _wall_clock_calls(module, node):
            yield from once(_finding(
                module,
                call,
                "TPU016",
                "`time.time()` inside a comparison: this is a "
                "wall-clock deadline/timeout check, and an NTP step "
                "makes it fire early or never — use `time.monotonic()` "
                "for every lease/deadline/timeout comparison "
                "(timestamps that are only recorded may stay on the "
                "wall clock)",
            ))

    # prong 2: NAME/self.ATTR = <expr containing time.time()>, with the
    # binding later read inside a comparison the binding is VISIBLE to —
    # a function-local `t0` compared in some other function's scope is a
    # different binding and must not be smeared onto this one (the
    # TPU012 shadowing discipline, reused)
    for node in ast.walk(module.tree):
        if not isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            continue
        value = node.value
        if value is None:
            continue
        calls = _wall_clock_calls(module, value)
        if not calls:
            continue
        targets = (
            node.targets if isinstance(node, ast.Assign) else [node.target]
        )
        # direct Name / self.ATTR targets only (tuple-unpacked included):
        # a subscript target (`records[rid] = {..., time.time()}`) binds
        # a container ITEM no comparison can read by name — walking its
        # index expression would smear unrelated compared names (a
        # `rid in finished` membership test) onto a record-only
        # timestamp, which is exactly the false positive that gets a
        # lint gate deleted from CI
        flat: list[ast.AST] = []
        for target in targets:
            if isinstance(target, (ast.Tuple, ast.List)):
                flat.extend(target.elts)
            else:
                flat.append(target)
        enclosing = module.enclosing_function(node)
        hot = False
        for t in flat:
            if isinstance(t, ast.Name):
                scope = enclosing if enclosing is not None else module.tree
                if _name_compared_in(
                    scope, t.id, _shadowing_functions(scope, t.id)
                ):
                    hot = True
            elif _attr_is_self(t, getattr(t, "attr", "")):
                cls = _enclosing_class(module, node)
                if _self_attr_compared(
                    cls if cls is not None else module.tree, t.attr
                ):
                    hot = True
        if not hot:
            continue
        for call in calls:
            yield from once(_finding(
                module,
                call,
                "TPU016",
                "`time.time()` feeds a binding later used in a "
                "comparison: a wall-clock lease/deadline — an NTP step "
                "makes it fire early or never. Compute deadlines from "
                "`time.monotonic()`; keep wall-clock reads for "
                "record-only timestamps",
            ))


# --------------------------------------------------------------------------
# TPU017 — reverse-mode autodiff over a while_loop-based solver entry
# --------------------------------------------------------------------------

# the reverse-mode entries: these stage a backward pass over their
# target. jax.jvp/jacfwd are forward-mode (while_loop supports them)
# and stay out of scope.
_REVERSE_AD_ENTRIES = frozenset({
    "jax.grad", "jax.value_and_grad", "jax.vjp", "jax.jacrev",
    "jax.hessian",
})


def _matches_fn(module: Module, node: ast.AST,
                patterns: tuple[str, ...]) -> bool:
    """Does a callee expression match any pattern — by resolved
    qualname or by leaf name (``solver.pcg`` matches ``pcg``)?"""
    q = module.qualname(node) or ""
    leaf = ""
    if isinstance(node, ast.Name):
        leaf = node.id
    elif isinstance(node, ast.Attribute):
        leaf = node.attr
    return any(
        fnmatch.fnmatch(q, pat) or fnmatch.fnmatch(leaf, pat)
        for pat in patterns
    )


def _resolve_grad_target(module: Module, target: ast.AST):
    """What reverse-mode will differentiate through, when statically
    visible: ``("direct", node)`` for a bare callee reference (an
    imported/attribute solver name — checked against the patterns by
    name), ``("body", ast)`` for a lambda or locally-defined function
    (checked by walking the body), recursing through a
    ``functools.partial``'s first argument either way. None when the
    target is opaque (a computed expression) — the registry's
    conservative stance."""
    if isinstance(target, ast.Lambda):
        return ("body", target.body)
    if isinstance(target, ast.Name):
        fn = module.functions.get(target.id)
        if fn is not None:
            return ("body", fn)
        return ("direct", target)
    if isinstance(target, ast.Attribute):
        return ("direct", target)
    if isinstance(target, ast.Call) and target.args:
        q = module.qualname(target.func) or ""
        if q in ("functools.partial", "partial"):
            return _resolve_grad_target(module, target.args[0])
    return None


@rule(
    "TPU017",
    "backprop-through-loop",
    "reverse-mode autodiff (jax.grad/jax.vjp/...) applied to a "
    "while_loop-based solver entry without the implicit custom_vjp "
    "wrapper — no reverse rule for while_loop, and an unroll "
    "backpropagates through thousands of iterations",
)
def check_backprop_through_loop(module: Module,
                                config: LintConfig) -> Iterator[Finding]:
    """The differentiable-solving fence. Every solver entry in this
    repo binds its iteration as a fused ``lax.while_loop`` — which has
    NO reverse-mode rule: ``jax.grad`` over one either raises at trace
    time (dynamic trip count) or, rewritten to a scanned/unrolled loop,
    stores every iterate of a thousand-iteration solve. The correct
    route is the implicit-function-theorem wrapper
    (``diff.adjoint.solve_implicit`` / ``ImplicitSolver``): one extra
    PCG solve with the same operator.

    Conservative per the registry's standing rules: a finding needs a
    reverse-mode entry (``jax.grad``/``value_and_grad``/``vjp``/
    ``jacrev``/``hessian``) whose target is statically visible (a
    lambda, a local def, a direct solver-entry reference, or a
    ``functools.partial`` of one) and binds a configured
    ``loop-solver-fns`` callee; a target that also touches one of the
    ``implicit-solver-fns`` is routing through the wrapper and stays
    silent. Opaque targets are skipped, not guessed at.
    """
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        q = module.qualname(node.func) or ""
        if q not in _REVERSE_AD_ENTRIES:
            continue
        resolved = _resolve_grad_target(module, node.args[0])
        if resolved is None:
            continue
        kind, payload = resolved
        if kind == "direct":
            # bare callee reference, possibly through a partial:
            # jax.grad(pcg) / jax.vjp(functools.partial(pcg, problem))
            if not _matches_fn(module, payload, config.loop_solver_fns):
                continue
            solver_name = (
                payload.id if isinstance(payload, ast.Name)
                else payload.attr
            )
        else:
            hits = []
            routed = False
            for sub in ast.walk(payload):
                if not isinstance(sub, ast.Call):
                    continue
                if _matches_fn(module, sub.func, config.implicit_solver_fns):
                    routed = True
                    break
                if _matches_fn(module, sub.func, config.loop_solver_fns):
                    hits.append(sub)
            if routed or not hits:
                continue
            first = hits[0].func
            solver_name = (
                first.id if isinstance(first, ast.Name)
                else getattr(first, "attr", "<solver>")
            )
        entry = q.rsplit(".", 1)[1]
        yield _finding(
            module,
            node,
            "TPU017",
            f"`jax.{entry}` over `{solver_name}` backpropagates through "
            "a `lax.while_loop` solver iteration — no reverse rule "
            "(trace error) or an unbounded-memory unroll. Differentiate "
            "through the IFT wrapper instead "
            "(`diff.adjoint.solve_implicit` / `ImplicitSolver.solve`: "
            "the adjoint is one extra solve with the same operator)",
        )


# --------------------------------------------------------------------------
# TPU018 — half-width values flowing into a reduction without a wide
# accumulator route
# --------------------------------------------------------------------------

# dtype spellings that mean "16-bit float" — the storage widths whose
# accumulation error grows like n·2⁻⁸ instead of n·2⁻²⁴
_NARROW_DTYPE_LEAVES = frozenset({"bfloat16", "float16"})
_NARROW_DTYPE_STRINGS = frozenset({"bfloat16", "float16", "bf16", "f16"})
_WIDE_DTYPE_LEAVES = frozenset({"float32", "float64"})
_WIDE_DTYPE_STRINGS = frozenset({"float32", "float64", "f32", "f64"})

# built-in reduction sinks (the TPU007 reduction_roots knob extends the
# set with a project's own grid_dot-style wrappers)
_REDUCTION_SINKS = frozenset({
    "jax.numpy.sum", "jax.numpy.mean", "jax.numpy.dot", "jax.numpy.vdot",
    "jax.numpy.einsum", "jax.numpy.matmul", "jax.numpy.tensordot",
    "jax.numpy.inner", "jax.lax.psum", "numpy.sum", "numpy.dot",
    "numpy.einsum",
})


def _dtype_class(module: Module, node: ast.AST) -> Optional[str]:
    """"narrow" / "wide" / None for a dtype expression, when statically
    visible (an attribute like jnp.bfloat16, or a string literal)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        if node.value in _NARROW_DTYPE_STRINGS:
            return "narrow"
        if node.value in _WIDE_DTYPE_STRINGS:
            return "wide"
        return None
    leaf = None
    if isinstance(node, ast.Attribute):
        leaf = node.attr
    elif isinstance(node, ast.Name):
        leaf = node.id
    if leaf in _NARROW_DTYPE_LEAVES:
        return "narrow"
    if leaf in _WIDE_DTYPE_LEAVES:
        return "wide"
    return None


def _astype_class(module: Module, node: ast.AST) -> Optional[str]:
    """The dtype class of an ``x.astype(...)`` call, else None."""
    if (
        isinstance(node, ast.Call)
        and isinstance(node.func, ast.Attribute)
        and node.func.attr == "astype"
        and node.args
    ):
        return _dtype_class(module, node.args[0])
    return None


def _expr_is_narrow(module: Module, node: ast.AST,
                    narrow_names: set) -> bool:
    """Does this expression statically carry a 16-bit float value all
    the way to its root? Conservative: anything unresolvable reads as
    not-narrow (the registry's stay-silent stance). An inner
    ``.astype(f32/f64)`` re-widens the value and stops the flow."""
    cls = _astype_class(module, node)
    if cls == "narrow":
        return True
    if cls == "wide":
        return False
    if isinstance(node, ast.Name):
        return node.id in narrow_names
    if isinstance(node, ast.BinOp):
        left = _expr_is_narrow(module, node.left, narrow_names)
        right = _expr_is_narrow(module, node.right, narrow_names)
        if left and right:
            return True
        # narrow ∘ python-scalar stays narrow under weak-type promotion;
        # narrow ∘ wide promotes wide (not a finding)
        if left and isinstance(node.right, ast.Constant):
            return True
        if right and isinstance(node.left, ast.Constant):
            return True
        return False
    if isinstance(node, ast.UnaryOp):
        return _expr_is_narrow(module, node.operand, narrow_names)
    if isinstance(node, ast.Subscript):
        return _expr_is_narrow(module, node.value, narrow_names)
    if isinstance(node, ast.Call):
        # abs/negative-style elementwise wrappers keep the dtype; treat
        # only jnp.abs / abs conservatively, everything else opaque
        q = module.qualname(node.func) or ""
        if q in ("jax.numpy.abs", "abs") and node.args:
            return _expr_is_narrow(module, node.args[0], narrow_names)
        return False
    return False


def _scan_scope_tpu018(module: Module, config: LintConfig, body,
                       mixed_fns: tuple[str, ...]):
    """Walk one scope's statements in order, tracking names bound to
    narrow values, yielding reductions fed by them."""
    reduction_roots = tuple(_REDUCTION_SINKS) + tuple(config.reduction_roots)
    narrow_names: set = set()
    for stmt in body:
        for node in ast.walk(stmt):
            if not isinstance(node, ast.Call):
                continue
            q = module.qualname(node.func) or ""
            leaf = (
                node.func.attr if isinstance(node.func, ast.Attribute)
                else getattr(node.func, "id", "")
            )
            if _matches_fn(module, node.func, mixed_fns):
                continue  # a blessed wide-accumulator route
            is_sink = any(
                fnmatch.fnmatch(q, pat) or fnmatch.fnmatch(leaf, pat)
                for pat in reduction_roots
            ) or q in _REDUCTION_SINKS
            if not is_sink:
                continue
            # an explicit wide accumulator silences the sink
            if any(
                kw.arg == "dtype"
                and _dtype_class(module, kw.value) == "wide"
                for kw in node.keywords
            ):
                continue
            for arg in node.args:
                if _expr_is_narrow(module, arg, narrow_names):
                    yield node, leaf or q
                    break
        # statement-order narrowness tracking (after scanning: a
        # reduction inside the RHS sees the PRE-assignment bindings)
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 and \
                isinstance(stmt.targets[0], ast.Name):
            name = stmt.targets[0].id
            if _expr_is_narrow(module, stmt.value, narrow_names):
                narrow_names.add(name)
            else:
                narrow_names.discard(name)


@rule(
    "TPU018",
    "silent-downcast",
    "a bf16/f16 value (an .astype(bfloat16/float16) result, or "
    "arithmetic over such values) flows into a reduction with no "
    "f32/f64 accumulator route — the sum accumulates at 8 mantissa "
    "bits and loses digits linearly in n",
)
def check_silent_downcast(module: Module,
                          config: LintConfig) -> Iterator[Finding]:
    """The storage-vs-compute precision fence (``ops.precision``). The
    bf16-storage contract is narrow in HBM, WIDE in every accumulator:
    a reduction whose operand tree is statically 16-bit (an
    ``.astype(jnp.bfloat16)``/"bf16" result, a name bound to one, or
    arithmetic over such values) accumulates at 8 mantissa bits —
    round-off grows like n·2⁻⁸ and a grid-sized sum is wrong in the
    third digit. The route out is an upcast before the reduction
    (``.astype(jnp.float32)``, fused by XLA into the consumer — free on
    the HBM side), an explicit ``dtype=jnp.float32`` accumulator on the
    reduction itself, or one of the configured ``mixed-accum-fns`` —
    the project's sanctioned mixed-precision reducers (the Pallas mixed
    kernels, ``ops.precision``'s load/store helpers).

    Conservative per the registry's standing rules: dtypes must be
    statically visible (attribute or string literal), unresolvable
    expressions read as not-narrow, and only same-scope, statement-
    ordered name bindings propagate narrowness.
    """
    mixed_fns = config.mixed_accum_fns
    scopes = [module.tree.body]
    for node in ast.walk(module.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            scopes.append(node.body)
    seen: set = set()
    for body in scopes:
        for call, sink in _scan_scope_tpu018(module, config, body,
                                             mixed_fns):
            key = (call.lineno, call.col_offset)
            if key in seen:
                continue
            seen.add(key)
            yield _finding(
                module,
                call,
                "TPU018",
                f"`{sink}` reduces a bf16/f16-typed operand with no "
                "f32/f64 accumulator route — 8 mantissa bits lose "
                "digits linearly in element count. Upcast first "
                "(`.astype(jnp.float32)` fuses into the consumer: the "
                "HBM read stays narrow), pass `dtype=jnp.float32` to "
                "the reduction, or route through a `mixed-accum-fns` "
                "helper (ops.precision / the mixed Pallas kernels)",
            )


# --------------------------------------------------------------------------
# TPU019 — numeric literals hardcoding tunable solver knobs at call sites
# --------------------------------------------------------------------------

# the knob vocabulary: keyword names that select engine configurations
# the autotuner owns (solver.engine.ENGINE_CAPS tunables + the serve
# chunk axis). A literal bound to one of these at a builder call site
# freezes a choice the closed loop exists to make.
_TUNABLE_KWARGS = frozenset({
    "cheb_degree", "coarse_degree", "nu", "levels", "n_vcycles",
    "sstep_s", "chunk", "degree",
})

# enclosing-function shapes where a knob literal IS the registry: the
# static defaults the tuner scores against (default_*/resolve_*_config
# constructors) and the tuner's own candidate sweeps (tune*/candidates)
_TUNABLE_EXEMPT_FNS = ("default_*", "resolve_*_config", "tune*",
                       "candidates", "*_config")


def _enclosing_fn_name(module: Module, node: ast.AST) -> str:
    """Name of the innermost enclosing function definition, or ''
    (the visitor's parent links; lambdas are anonymous, keep walking)."""
    for anc in module.ancestors(node):
        if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return anc.name
    return ""


@rule(
    "TPU019",
    "hardcoded-tunable",
    "a bare numeric literal bound to a tunable knob keyword at a "
    "solver-builder call site — the autotune registry can neither see "
    "nor overrule it",
)
def check_hardcoded_tunable(module: Module,
                            config: LintConfig) -> Iterator[Finding]:
    """The autotuning fence (``runtime.autotune``). The engine zoo's
    knobs — Chebyshev degree, MG depth/ν/coarse degree, F-cycle
    correction count, s-step block size, serve chunk — are selected per
    shape by the closed-loop tuner and recorded once in the
    engine-capability table (``solver.engine.ENGINE_CAPS``). A numeric
    literal bound to one of those keywords at a builder call site
    (``tunable-fns``) silently pins the choice where neither the table
    nor the registry can reach it: the tuned config loads, the literal
    wins, and the regression gate blames the wrong layer.

    Compliant routes: a named constant (module UPPERCASE or a config
    dataclass field), the capability table's ``tunables`` row, or a
    value threaded from the tuned-config registry. Exemptions keep the
    registry definable at all: the autotune module itself, and
    default-config constructors / tuner candidate sweeps
    (``default_*`` / ``resolve_*_config`` / ``tune*`` / ``candidates``)
    — the one place a static default's literal must live.
    """
    norm_path = module.path.replace(os.sep, "/")
    if norm_path.endswith("runtime/autotune.py"):
        return  # the registry itself: candidate sweeps ARE literals
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue
        if not _matches_fn(module, node.func, config.tunable_fns):
            continue
        hits = [
            kw for kw in node.keywords
            if kw.arg in _TUNABLE_KWARGS
            and isinstance(kw.value, ast.Constant)
            and isinstance(kw.value.value, (int, float))
            and not isinstance(kw.value.value, bool)
        ]
        if not hits:
            continue
        enclosing = _enclosing_fn_name(module, node)
        if any(fnmatch.fnmatch(enclosing, pat)
               for pat in _TUNABLE_EXEMPT_FNS):
            continue
        leaf = (
            node.func.attr if isinstance(node.func, ast.Attribute)
            else getattr(node.func, "id", "<call>")
        )
        for kw in hits:
            yield _finding(
                module,
                kw.value,
                "TPU019",
                f"`{leaf}(... {kw.arg}={kw.value.value!r})` hardcodes a "
                "tunable knob at a builder call site — the autotuner "
                "(runtime.autotune) and the engine-capability table "
                "(solver.engine.ENGINE_CAPS) can neither see nor "
                "overrule it. Route the value through a named "
                "constant, the table's tunables row, or the tuned-"
                "config registry",
            )


# --------------------------------------------------------------------------
# TPU020 — raw collectives outside the blessed communication modules
# --------------------------------------------------------------------------

_COLLECTIVE_FNS = frozenset({
    "jax.lax.psum", "jax.lax.pmean", "jax.lax.pmax", "jax.lax.pmin",
    "jax.lax.ppermute", "jax.lax.pshuffle", "jax.lax.psum_scatter",
    "jax.lax.all_gather", "jax.lax.all_to_all",
})


@rule(
    "TPU020",
    "raw-collective",
    "a raw jax.lax collective issued outside the blessed communication "
    "modules (`collective-modules`) — the contract matrix's cadence "
    "budgets cannot account for it",
)
def check_raw_collective(module: Module,
                         config: LintConfig) -> Iterator[Finding]:
    """The communication-layer fence. The engine zoo's collective
    cadences — 2 psums per classical body, ONE per pipelined body, the
    ``halos_per_precond`` ppermute budgets — are declared in
    ``ENGINE_CAPS`` and pinned by the contract matrix (``analysis/``)
    over the builders in ``parallel/``. A ``lax.psum``/``lax.ppermute``
    issued from any other module joins a traced computation those
    budgets never swept: the count drifts, the matrix stays green, and
    the regression surfaces as a multichip perf mystery instead of a
    lint line.

    ``collective-modules`` (path fnmatch patterns) names the licensed
    layer — ``parallel/`` by default. Deliberate exceptions (a
    bandwidth probe measuring the collective itself) carry a
    ``# tpulint: disable=TPU020`` with the justification. Anonymous
    sources (``<snippet>``) are skipped: a path-classified rule cannot
    place them in a layer.
    """
    if module.path == "<snippet>":
        return
    norm_path = module.path.replace(os.sep, "/")
    if any(
        fnmatch.fnmatch(norm_path, pat)
        for pat in config.collective_modules
    ):
        return
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue
        q = module.qualname(node.func)
        if q in _COLLECTIVE_FNS:
            yield _finding(
                module,
                node,
                "TPU020",
                f"raw `{q.removeprefix('jax.')}` outside the "
                "communication layer — the contract matrix's cadence "
                "budgets (analysis/, ENGINE_CAPS) only sweep "
                "`collective-modules`; route the exchange through "
                "parallel/ or annotate the deliberate exception",
            )


# --------------------------------------------------------------------------
# TPU021 — wall-clock reads feeding lease/deadline/duration ARITHMETIC
# --------------------------------------------------------------------------

# the arithmetic operators that turn a clock read into a deadline or a
# duration (unary ops and bit ops read as something else entirely)
_ARITH_OPS = (ast.Add, ast.Sub, ast.Mult, ast.Div, ast.FloorDiv, ast.Mod)


def _config_wall_clock_calls(module: Module, root: ast.AST,
                             config: LintConfig) -> list[ast.Call]:
    """Every call of a configured wall-clock source (`wall-clock-fns`)
    in ``root``'s subtree."""
    out = []
    for node in ast.walk(root):
        if not isinstance(node, ast.Call):
            continue
        q = module.qualname(node.func) or ""
        if any(fnmatch.fnmatch(q, pat) for pat in config.wall_clock_fns):
            out.append(node)
    return out


def _arith_ancestor(module: Module, node: ast.AST) -> Optional[ast.BinOp]:
    for anc in module.ancestors(node):
        if isinstance(anc, ast.BinOp) and isinstance(anc.op, _ARITH_OPS):
            return anc
    return None


def _inside_ordering_compare(module: Module, node: ast.AST) -> bool:
    return any(
        isinstance(anc, ast.Compare) and _is_ordering_compare(anc)
        for anc in module.ancestors(node)
    )


def _name_in_arith(scope_root: ast.AST, name: str,
                   exclude: set = frozenset()) -> bool:
    """Is ``name`` read as an operand of arithmetic within
    ``scope_root`` (same shadowing discipline as TPU016's
    :func:`_name_compared_in`)?"""
    for node in _walk_excluding(scope_root, exclude):
        if not isinstance(node, ast.BinOp) or not isinstance(
            node.op, _ARITH_OPS
        ):
            continue
        for sub in ast.walk(node):
            if isinstance(sub, ast.Name) and sub.id == name:
                return True
    return False


def _self_attr_in_arith(scope_root: ast.AST, attr: str) -> bool:
    for node in ast.walk(scope_root):
        if not isinstance(node, ast.BinOp) or not isinstance(
            node.op, _ARITH_OPS
        ):
            continue
        for sub in ast.walk(node):
            if _attr_is_self(sub, attr):
                return True
    return False


@rule(
    "TPU021",
    "wall-clock-lease",
    "a wall-clock read (time.time()/datetime.now()) feeding lease/"
    "deadline/duration ARITHMETIC — NTP steps the clock mid-computation; "
    "compute spans and deadlines from time.monotonic()",
)
def check_wall_clock_lease(module: Module,
                           config: LintConfig) -> Iterator[Finding]:
    """TPU016's arithmetic sibling. TPU016 fires when a wall-clock read
    reaches a COMPARISON (the deadline check itself); this rule fires
    one step earlier, when the read feeds lease/deadline/duration
    ARITHMETIC — ``deadline = time.time() + lease_s``,
    ``elapsed = datetime.now() - started`` — whether or not the result
    is ever compared in this module. The computed value is already
    wrong the instant NTP steps the clock: handed to a peer process, a
    trace record used for pacing, or a retry budget, it fires early or
    never with no comparison in sight for TPU016 to catch. The scopes
    are disjoint by construction: a read inside an ordering comparison
    is TPU016's finding and skipped here.

    Two prongs, mirroring TPU016's, same conservative stance — a bare
    recorded timestamp (``"t_admit_unix": time.time()``, a trace
    record's ``unix_time``) touches no arithmetic and stays silent:

    - **arithmetic directly** — a configured wall-clock call
      (`wall-clock-fns`: ``time.time``, ``datetime.now``/``utcnow`` by
      default) that is an operand of ``+ - * / // %``.
    - **bound then arithmetic** — a name (or ``self`` attribute)
      assigned from a wall-clock read, later used as an arithmetic
      operand visible to that binding (the TPU012 shadowing
      discipline, reused via TPU016's machinery).
    """
    emitted: set[tuple[int, int]] = set()

    def once(finding):
        key = (finding.line, finding.col)
        if key not in emitted:
            emitted.add(key)
            yield finding

    # prong 1: the wall-clock call itself is an arithmetic operand —
    # unless the whole expression sits inside an ordering comparison,
    # which is TPU016's finding (the scopes stay disjoint)
    for call in _config_wall_clock_calls(module, module.tree, config):
        if _arith_ancestor(module, call) is None:
            continue
        if _inside_ordering_compare(module, call):
            continue
        q = module.qualname(call.func)
        yield from once(_finding(
            module,
            call,
            "TPU021",
            f"`{q}()` feeds lease/deadline/duration arithmetic: an NTP "
            "step lands inside the computed value — compute spans and "
            "deadlines from `time.monotonic()` and keep wall-clock "
            "reads for record-only timestamps",
        ))

    # prong 2: NAME/self.ATTR = <wall-clock read>, with the binding
    # later an arithmetic operand in a scope the binding is visible to
    for node in ast.walk(module.tree):
        if not isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            continue
        value = node.value
        if value is None:
            continue
        calls = _config_wall_clock_calls(module, value, config)
        if not calls:
            continue
        targets = (
            node.targets if isinstance(node, ast.Assign) else [node.target]
        )
        flat: list[ast.AST] = []
        for target in targets:
            if isinstance(target, (ast.Tuple, ast.List)):
                flat.extend(target.elts)
            else:
                flat.append(target)
        enclosing = module.enclosing_function(node)
        hot = False
        for t in flat:
            if isinstance(t, ast.Name):
                scope = enclosing if enclosing is not None else module.tree
                if _name_in_arith(
                    scope, t.id, _shadowing_functions(scope, t.id)
                ):
                    hot = True
            elif _attr_is_self(t, getattr(t, "attr", "")):
                cls = _enclosing_class(module, node)
                if _self_attr_in_arith(
                    cls if cls is not None else module.tree, t.attr
                ):
                    hot = True
        if not hot:
            continue
        for call in calls:
            yield from once(_finding(
                module,
                call,
                "TPU021",
                "wall-clock read bound to a name later used in "
                "arithmetic: the computed lease/deadline/duration is "
                "stepped by NTP before anything compares it — bind "
                "`time.monotonic()` for anything that feeds arithmetic",
            ))


# --------------------------------------------------------------------------
# TPU022 — unbounded dict caches in long-lived serving/runtime code
# --------------------------------------------------------------------------

# bindings whose NAME declares cache intent — the conservative gate: a
# dict that is not named like a cache is somebody's data structure, not
# this rule's business (a lint gate that cries wolf gets deleted)
_CACHE_NAME_MARKERS = ("cache", "memo", "pool")

# dict mutations that grow / that evict
_CACHE_GROW = frozenset({"setdefault", "update"})
_CACHE_EVICT = frozenset({"pop", "popitem", "clear"})


def _cache_named(name: str) -> bool:
    low = name.lower()
    return any(m in low for m in _CACHE_NAME_MARKERS)


def _dict_ctor(module: Module, node: ast.AST) -> Optional[str]:
    """"dict"/"OrderedDict" when ``node`` constructs an empty mapping —
    ``{}``, ``dict()``, ``OrderedDict()``, or ``dataclasses.field(
    default_factory=dict|OrderedDict)`` — else None."""
    if isinstance(node, ast.Dict) and not node.keys:
        return "dict"
    if not isinstance(node, ast.Call):
        return None
    leaf = (module.qualname(node.func) or "").rsplit(".", 1)[-1]
    if leaf in ("dict", "OrderedDict") and not node.args and not node.keywords:
        return leaf
    if leaf == "field":
        for kw in node.keywords:
            if (
                kw.arg == "default_factory"
                and isinstance(kw.value, ast.Name)
                and kw.value.id in ("dict", "OrderedDict")
            ):
                return kw.value.id
    return None


def _cache_usage(scope: ast.AST, matches,
                 exclude: set = frozenset(),
                 defining: ast.AST | None = None) -> tuple[bool, bool]:
    """(grows, evicts) for a candidate cache binding within ``scope``.

    Grows: ``c[k] = v`` subscript assignment, ``c.setdefault(...)``,
    ``c.update(...)``. Evicts: ``c.pop/popitem/clear``, ``del c[k]``,
    or a rebinding to a fresh empty container (the drop-the-pool
    idiom). The same visibility discipline as TPU012's
    :func:`_queue_usage` — ``exclude`` subtrees (shadowing scopes) are
    not descended into — but with the subscript-assignment polarity
    FLIPPED: for a list, ``q[i] = x`` is the windowed-drain bound; for
    a dict, ``c[k] = v`` is exactly the unbounded admission this rule
    exists to fence."""
    grows = evicts = False
    for node in _walk_excluding(scope, exclude):
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
            if matches(node.func.value):
                if node.func.attr in _CACHE_GROW:
                    grows = True
                elif node.func.attr in _CACHE_EVICT:
                    evicts = True
        elif isinstance(node, ast.Delete):
            for target in node.targets:
                if isinstance(target, ast.Subscript) and matches(
                    target.value
                ):
                    evicts = True
        elif isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            targets = (
                node.targets if isinstance(node, ast.Assign)
                else [node.target]
            )
            value = node.value
            for target in targets:
                if isinstance(target, ast.Subscript) and matches(
                    target.value
                ):
                    grows = True
                elif (
                    node is not defining
                    and matches(target)
                    and value is not None
                    and _empty_container_expr(value)
                ):
                    evicts = True
    return grows, evicts


@rule(
    "TPU022",
    "unbounded-cache",
    "a module/class-level cache-named dict grown by key assignment with "
    "no eviction route — every distinct key a long-lived server sees "
    "stays resident forever",
)
def check_unbounded_cache(module: Module,
                          config: LintConfig) -> Iterator[Finding]:
    """TPU012's mapping sibling: the cache-discipline rule.

    A compile cache, solve cache or warm pool that lives at module or
    instance scope and admits entries (``c[key] = value``,
    ``setdefault``) without any eviction route grows with the *key
    space*, not the working set — in a serving process where keys carry
    request-derived content (grid buckets are finite; RHS sketches are
    not), that is an OOM with a delay fuse. The repo's own discipline
    is the fix this rule points at: ``runtime.solvecache.SolveCache``
    (LRU key cap + per-key ring), ``runtime.compile_cache`` (bounded
    bucketing), or a drop-and-rebuild (``_ctxs.clear()`` on mesh
    degrade).

    Deliberately conservative, mirroring TPU012's machinery:

    - candidates are long-lived bindings only — module-level names and
      ``self`` attributes (incl. ``field(default_factory=dict)``)
      initialised to ``{}``/``dict()``/``OrderedDict()`` — whose NAME
      declares cache intent (contains ``cache``/``memo``/``pool``); a
      dict not named like a cache is a data structure, not a finding;
    - any visible eviction silences it: ``pop``/``popitem``/``clear``,
      ``del c[key]``, or a rebinding to a fresh empty container;
      function-local caches are scoped to one call and stay silent
      (TPU012's shadowing discipline, reused verbatim).
    """
    # module-level names
    for stmt in module.tree.body:
        target = value = None
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 and (
            isinstance(stmt.targets[0], ast.Name)
        ):
            target, value = stmt.targets[0], stmt.value
        elif isinstance(stmt, ast.AnnAssign) and isinstance(
            stmt.target, ast.Name
        ) and stmt.value is not None:
            target, value = stmt.target, stmt.value
        if target is None or not _cache_named(target.id):
            continue
        kind = _dict_ctor(module, value)
        if kind is None:
            continue
        name = target.id

        def matches(expr, name=name):
            return isinstance(expr, ast.Name) and expr.id == name

        grows, evicts = _cache_usage(
            module.tree, matches,
            exclude=_shadowing_functions(module.tree, name),
            defining=stmt,
        )
        if grows and not evicts:
            yield _finding(
                module,
                stmt,
                "TPU022",
                f"module-level {kind} cache `{name}` admits entries with "
                "no eviction route: every distinct key stays resident "
                "for the life of the process — bound it (LRU cap like "
                "runtime.solvecache.SolveCache, a popitem ring, a "
                "clear() on rebuild) or key it by a finite bucket space",
            )
    # class-level / instance attributes
    for cls in ast.walk(module.tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        candidates: dict[str, tuple[ast.AST, str]] = {}
        for stmt in cls.body:
            target = value = None
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 and (
                isinstance(stmt.targets[0], ast.Name)
            ):
                target, value = stmt.targets[0], stmt.value
            elif isinstance(stmt, ast.AnnAssign) and isinstance(
                stmt.target, ast.Name
            ) and stmt.value is not None:
                target, value = stmt.target, stmt.value
            if target is None or not _cache_named(target.id):
                continue
            kind = _dict_ctor(module, value)
            if kind is not None:
                candidates[target.id] = (stmt, kind)
        for node in ast.walk(cls):
            if isinstance(node, ast.Assign):
                pairs = [(t, node.value) for t in node.targets]
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                pairs = [(node.target, node.value)]
            else:
                continue
            for target, value in pairs:
                if (
                    isinstance(target, ast.Attribute)
                    and isinstance(target.value, ast.Name)
                    and target.value.id == "self"
                    and _cache_named(target.attr)
                ):
                    kind = _dict_ctor(module, value)
                    if kind is not None and target.attr not in candidates:
                        candidates[target.attr] = (node, kind)
        for attr, (site, kind) in candidates.items():

            def matches(expr, attr=attr, cls_name=cls.name):
                return _attr_is_self(expr, attr) or (
                    isinstance(expr, ast.Attribute)
                    and expr.attr == attr
                    and isinstance(expr.value, ast.Name)
                    and expr.value.id == cls_name
                )

            grows, evicts = _cache_usage(cls, matches, defining=site)
            if grows and not evicts:
                yield _finding(
                    module,
                    site,
                    "TPU022",
                    f"instance-level {kind} cache `{attr}` of class "
                    f"`{cls.name}` admits entries with no eviction "
                    "route: the cache grows with the key space, not the "
                    "working set — bound it (LRU cap + per-key ring "
                    "like runtime.solvecache.SolveCache) or drop and "
                    "rebuild it at a lifecycle boundary",
                )
