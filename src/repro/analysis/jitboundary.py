"""Jit-boundary analyzer — the discipline that keeps the streamed engine's
host-driven loop fast: nothing inside a jitted stage forces a host sync,
and nothing at a call site feeds a fresh Python scalar to a static jit
parameter (each distinct value = one full recompile).

AST rules (over src/repro)
--------------------------
host-sync-in-jit    float()/int()/bool()/np.asarray()/np.array() applied to
                    a non-static parameter inside a jax.jit-decorated
                    function, or `.item()`/`.tolist()` on one. On a traced
                    value these either crash at trace time or silently
                    constant-fold a device sync into every call.
scalar-static-arg   a call site passing `float(...)`/`int(...)`/`.item()`
                    results into a static parameter of a module-level
                    jitted function — every new value misses the jit cache
                    and recompiles (the streamed engine's per-round stages
                    would pay this once per round).

Runtime rule
------------
streamed-retrace    run a tiny streamed fit TWICE with identical shapes and
                    count jit tracing-cache misses on the second run. The
                    per-round host stages (`engine._lid_batch`,
                    `_gather_fold`, ...) are keyed by static config,
                    shapes and fold sizes only (a fold's size comes from
                    the host's slot count, `civs.fold_size`, which the same
                    data repeats), so the second fit must trace NOTHING; a
                    miss means a stage's signature hashes something
                    per-call (exactly the regression this gate exists to
                    catch). Needs jax's internal test_util counter; if the
                    installed jax doesn't expose it the check is skipped
                    (noted in the report), never silently passed.
"""

from __future__ import annotations

import ast
from typing import Optional

from repro.analysis import astutil
from repro.analysis.report import Report, Violation

PASS = "jitboundary"

HOST_CASTS = frozenset(("float", "int", "bool", "complex"))
HOST_ARRAY_CALLS = frozenset((
    "numpy.asarray", "numpy.array", "numpy.ascontiguousarray",
    "numpy.copy", "jax.device_get",
))
SYNC_METHODS = frozenset(("item", "tolist", "block_until_ready"))

# jitted functions scanned only under src/repro — benchmarks/examples are
# one-shot drivers where a recompile is a non-event
SCAN_ROOTS = ("src/repro",)


class _JitDef:
    def __init__(self, node: ast.FunctionDef, statics: frozenset[str]):
        self.node = node
        self.statics = statics
        self.params = frozenset(
            a.arg for a in (node.args.posonlyargs + node.args.args
                            + node.args.kwonlyargs))
        self.dynamic = self.params - statics
        # positional order for mapping call-site args to static names
        self.arg_order = [a.arg for a in
                          (node.args.posonlyargs + node.args.args)]


def _jit_defs(tree: ast.AST, imports: astutil.ImportTable) -> list[_JitDef]:
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for dec in node.decorator_list:
            statics = astutil.jit_static_argnames(dec, imports)
            if statics is not None:
                out.append(_JitDef(node, statics))
                break
    return out


def _is_scalarizing_call(node: ast.expr) -> Optional[str]:
    """'float(...)' / 'x.item()' shape of an argument expression, if any."""
    if isinstance(node, ast.Call):
        if isinstance(node.func, ast.Name) and node.func.id in ("float",
                                                                "int"):
            return f"{node.func.id}(...)"
        if (isinstance(node.func, ast.Attribute)
                and node.func.attr == "item"):
            return ".item()"
    return None


def check_source(rel: str, src: str, tree: ast.AST, pragmas,
                 ) -> list[Violation]:
    imports = astutil.ImportTable(tree)
    out: list[Violation] = []

    def emit(rule: str, line: int, msg: str) -> None:
        out.append(pragmas.apply(Violation(PASS, rule, rel, line, msg)))

    defs = _jit_defs(tree, imports)
    by_name = {d.node.name: d for d in defs}

    # -- host-sync-in-jit -------------------------------------------------
    for d in defs:
        nested = {n for f in ast.walk(d.node)
                  if isinstance(f, (ast.FunctionDef, ast.AsyncFunctionDef))
                  and f is not d.node
                  for n in ast.walk(f)}
        for node in ast.walk(d.node):
            if not isinstance(node, ast.Call) or node in nested:
                continue
            func_name = astutil.dotted_name(node.func)
            full = imports.resolve(func_name) if func_name else None
            bad = None
            if (isinstance(node.func, ast.Name)
                    and node.func.id in HOST_CASTS):
                bad = f"{node.func.id}()"
            elif full in HOST_ARRAY_CALLS:
                bad = full
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr in SYNC_METHODS):
                root = astutil.base_name(node.func.value)
                if root in d.dynamic:
                    emit("host-sync-in-jit", node.lineno,
                         f".{node.func.attr}() on traced parameter "
                         f"{root!r} inside jitted {d.node.name!r} — "
                         "implicit device sync / trace-time crash")
                continue
            if bad is None:
                continue
            roots = {astutil.base_name(a) for a in node.args}
            traced = sorted(r for r in roots if r in d.dynamic)
            if traced:
                emit("host-sync-in-jit", node.lineno,
                     f"{bad} applied to traced parameter(s) "
                     f"{', '.join(traced)} inside jitted "
                     f"{d.node.name!r} — hoist out of the jit boundary "
                     "or mark the argument static")

    # -- scalar-static-arg ------------------------------------------------
    jitted_nodes = {d.node for d in defs}
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = astutil.dotted_name(node.func)
        d = by_name.get(name) if name else None
        if d is None or not d.statics:
            continue
        # skip the definition's own decorators
        if any(node in ast.walk(dec) for j in jitted_nodes
               for dec in j.decorator_list):
            continue
        hits = []
        for i, a in enumerate(node.args):
            shape = _is_scalarizing_call(a)
            if shape and i < len(d.arg_order) and (
                    d.arg_order[i] in d.statics):
                hits.append((d.arg_order[i], shape))
        for kw in node.keywords:
            shape = _is_scalarizing_call(kw.value)
            if shape and kw.arg in d.statics:
                hits.append((kw.arg, shape))
        for pname, shape in hits:
            emit("scalar-static-arg", node.lineno,
                 f"{shape} fed to static parameter {pname!r} of jitted "
                 f"{name!r} — every distinct value recompiles; pass it "
                 "dynamically or hoist the cast to a config constant")
    return out


def run(root: str, report: Report, pragma_cache) -> None:
    n_files = n_jit = 0
    for rel in astutil.iter_source_files(root, roots=SCAN_ROOTS):
        try:
            src, tree = astutil.parse_file(root, rel)
        except SyntaxError:
            continue        # dispatch already reported it
        n_files += 1
        pragmas = pragma_cache.get(rel, src)
        imports = astutil.ImportTable(tree)
        n_jit += len(_jit_defs(tree, imports))
        report.extend(check_source(rel, src, tree, pragmas))
    report.note(PASS, files_scanned=n_files, jitted_functions=n_jit)


# ---------------------------------------------------------- runtime check --
def run_streamed_retrace(report: Report, rounds: int = 6) -> None:
    """Fit a tiny streamed instance twice; the second run must not trace."""
    try:
        from jax._src import test_util as jtu
        counter = jtu.count_jit_tracing_cache_miss
    except (ImportError, AttributeError):
        report.note(PASS, streamed_retrace="skipped: jax test_util "
                    "tracing-cache counter unavailable")
        return
    import jax
    import numpy as np
    from repro.core.alid import ALIDConfig
    from repro.core.engine import EngineSpec, fit
    from repro.data.synthetic import make_blobs_with_noise

    spec = make_blobs_with_noise(3, 40, 80, d=8, seed=0)
    cfg = ALIDConfig(a_cap=48, delta=16, seeds_per_round=8,
                     max_rounds=rounds,
                     spec=EngineSpec(engine="streamed", n_shards=4))
    rng = jax.random.PRNGKey(0)
    fit(np.asarray(spec.points), cfg, rng)          # warm every stage cache
    with counter() as count:
        fit(np.asarray(spec.points), cfg, rng)      # identical shapes
    misses = count[0] if isinstance(count, (list, tuple)) else count()
    report.note(PASS, streamed_retrace_misses=int(misses))
    if misses:
        report.add(Violation(
            PASS, "streamed-retrace", "src/repro/core/engine.py", 0,
            f"{misses} jit tracing-cache miss(es) on a repeat streamed fit "
            "with identical shapes — a per-round host stage is hashing "
            "per-call state into its jit signature"))
