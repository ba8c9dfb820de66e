"""Repo-wide custom lint gate (tier-1).

Three AST lints over every ``paddle_tpu/`` source file, no imports needed:

1. **Broad except swallows** — an ``except``/``except Exception``/
   ``except BaseException`` handler whose body does nothing (only
   ``pass``/``continue``/a bare constant) hides real failures; ADVICE
   rounds repeatedly flagged these (e.g. the `_in_manual_mesh_context`
   swallow that masked the jax-0.4.37 drift until PR 1 narrowed it).
   Existing sites are enumerated in a FROZEN per-file allowlist: the
   count can only shrink.  Adding a new swallow fails this test — narrow
   the exception type or handle/log it; removing one fails until the
   allowlist is ratcheted down to match.
2. **Duplicate register_op names** — the runtime registry raises on a
   duplicate at import time, but only for modules the package actually
   imports; the AST scan also covers flag-gated or lazily imported files,
   and duplicate ``register_shape_fn`` names identically.
3. **Metric-name gate** — every metric name passed to the observability
   registry helpers (``inc_counter``/``set_gauge``/``observe_hist``) must
   be a string LITERAL registered in the frozen
   ``observability.metrics.METRIC_NAMES`` table (duplicates rejected): a
   typo'd or free-form name would otherwise create a silently empty time
   series.  Mirrors the duplicate-op-registration gate.
"""
import ast
import collections
import os

import pytest

ROOT = os.path.join(os.path.dirname(__file__), os.pardir, "paddle_tpu")

# ---------------------------------------------------------------------------
# Frozen allowlist: relpath (from repo root) -> number of PERMITTED broad
# except-swallow sites.  Never add entries or raise counts — narrow the
# exception instead.  When you remove a swallow, ratchet its count down.
# ---------------------------------------------------------------------------
EXCEPT_SWALLOW_ALLOWLIST = {
    # last-resort CLI/config probing fallbacks, each commented in-source
    "paddle_tpu/cli.py": 1,
    "paddle_tpu/data_feeder.py": 1,
    # distributed best-effort cleanup paths (peer already gone)
    # (checkpoint.py's restore-fallback swallow was converted to a
    # logged + counted fallback in the fault-tolerance PR — ratcheted out)
    "paddle_tpu/distributed/master.py": 1,
}


def _iter_sources():
    for dirpath, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in sorted(files):
            if f.endswith(".py"):
                path = os.path.join(dirpath, f)
                rel = os.path.relpath(
                    path, os.path.join(ROOT, os.pardir)).replace(os.sep, "/")
                with open(path) as fh:
                    yield rel, ast.parse(fh.read(), filename=rel)


def _is_broad(handler: ast.ExceptHandler) -> bool:
    t = handler.type
    if t is None:                                   # bare except:
        return True
    elts = t.elts if isinstance(t, ast.Tuple) else [t]
    return any(isinstance(e, ast.Name) and
               e.id in ("Exception", "BaseException") for e in elts)


def _swallows(handler: ast.ExceptHandler) -> bool:
    """Body does nothing: only pass/continue/bare constants (docstrings)."""
    for stmt in handler.body:
        if isinstance(stmt, (ast.Pass, ast.Continue)):
            continue
        if isinstance(stmt, ast.Expr) and isinstance(stmt.value,
                                                     ast.Constant):
            continue
        return False
    return True


def test_no_new_broad_except_swallows():
    found = collections.defaultdict(list)
    for rel, tree in _iter_sources():
        for node in ast.walk(tree):
            if isinstance(node, ast.ExceptHandler) and _is_broad(node) \
                    and _swallows(node):
                found[rel].append(node.lineno)

    problems = []
    for rel, lines in sorted(found.items()):
        allowed = EXCEPT_SWALLOW_ALLOWLIST.get(rel, 0)
        if len(lines) > allowed:
            problems.append(
                f"{rel}: {len(lines)} broad except-swallow(s) at lines "
                f"{lines}, allowlist permits {allowed} — narrow the "
                f"exception type or handle the error instead of adding "
                f"a swallow")
    for rel, allowed in sorted(EXCEPT_SWALLOW_ALLOWLIST.items()):
        actual = len(found.get(rel, []))
        if actual < allowed:
            problems.append(
                f"{rel}: allowlist permits {allowed} swallow(s) but only "
                f"{actual} remain — ratchet EXCEPT_SWALLOW_ALLOWLIST down "
                f"so the count can only shrink")
    assert not problems, "\n".join(problems)


def test_lowering_context_is_constructed_in_the_executor_only():
    """How executor options become a LoweringContext is written ONCE
    (``Executor._lowering_context``): an eager replay that built its own
    (as observability.nanprov once did) would lower under another
    configuration than the compiled step the day an option is added."""
    sites = [rel for rel, tree in _iter_sources()
             for node in ast.walk(tree)
             if isinstance(node, ast.Call)
             and getattr(node.func, "id", getattr(node.func, "attr", None))
             == "LoweringContext"]
    assert sites == ["paddle_tpu/core/executor.py"], sites


def _registered_names(call_name: str):
    """(name, file, lineno) for every string literal passed to
    register_op(...) / register_shape_fn(...) decorator calls."""
    out = []
    for rel, tree in _iter_sources():
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            target = fn.id if isinstance(fn, ast.Name) else (
                fn.attr if isinstance(fn, ast.Attribute) else None)
            if target != call_name:
                continue
            for arg in node.args:
                if isinstance(arg, ast.Constant) and isinstance(arg.value,
                                                                str):
                    out.append((arg.value, rel, node.lineno))
    return out


def test_no_duplicate_register_op_names():
    for call in ("register_op", "register_shape_fn", "register_shard_fn",
                 "register_rowwise", "register_tunable"):
        by_name = collections.defaultdict(list)
        for name, rel, lineno in _registered_names(call):
            by_name[name].append(f"{rel}:{lineno}")
        dupes = {n: sites for n, sites in by_name.items()
                 if len(sites) > 1}
        assert not dupes, (
            f"duplicate {call} names (the second registration would "
            f"raise at import time, or silently never load if the module "
            f"is flag-gated): {dupes}")
        assert by_name, f"AST scan found no {call} calls — lint is broken"


# ---------------------------------------------------------------------------
# Metric-name gate (paddle_tpu.observability.metrics.METRIC_NAMES)
# ---------------------------------------------------------------------------
_METRIC_HELPERS = ("inc_counter", "set_gauge", "observe_hist")
# the registry module itself delegates name -> self._registry.<helper>(name)
# with a variable, by construction — it is the ONE place free-form names
# are allowed (its own METRIC_NAMES table is what the gate checks against)
_METRIC_DEFINING_FILE = "paddle_tpu/observability/metrics.py"


def _metric_names_table():
    """(name, kind) rows parsed from the METRIC_NAMES literal — no import,
    so the gate also covers a syntactically valid but unimportable state."""
    path = os.path.join(ROOT, "observability", "metrics.py")
    with open(path) as fh:
        tree = ast.parse(fh.read(), filename=path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "METRIC_NAMES"
                for t in node.targets):
            rows = ast.literal_eval(node.value)
            return [(name, kind) for name, kind, _help in rows]
    raise AssertionError("METRIC_NAMES literal not found in metrics.py")


def _iter_lint_sources():
    """Everything the metric gate covers: the package plus the driver."""
    yield from _iter_sources()
    bench = os.path.join(ROOT, os.pardir, "bench.py")
    with open(bench) as fh:
        yield "bench.py", ast.parse(fh.read(), filename="bench.py")


def test_metric_names_table_well_formed():
    rows = _metric_names_table()
    names = [n for n, _ in rows]
    dupes = {n for n in names if names.count(n) > 1}
    assert not dupes, f"duplicate METRIC_NAMES entries: {sorted(dupes)}"
    assert names, "METRIC_NAMES is empty — the gate has nothing to check"
    for name, kind in rows:
        assert "/" in name, f"metric {name!r} is not namespaced (sub/name)"
        assert kind in ("counter", "gauge", "histogram"), \
            f"metric {name!r}: unknown kind {kind!r}"


def test_metric_helper_names_are_registered_literals():
    registered = {n for n, _ in _metric_names_table()}
    problems, used = [], set()
    for rel, tree in _iter_lint_sources():
        if rel == _METRIC_DEFINING_FILE:
            continue
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            target = fn.id if isinstance(fn, ast.Name) else (
                fn.attr if isinstance(fn, ast.Attribute) else None)
            if target not in _METRIC_HELPERS:
                continue
            if not node.args:
                problems.append(f"{rel}:{node.lineno}: {target} without a "
                                f"positional metric name")
                continue
            arg = node.args[0]
            if not (isinstance(arg, ast.Constant)
                    and isinstance(arg.value, str)):
                problems.append(
                    f"{rel}:{node.lineno}: {target} metric name must be a "
                    f"string literal (free-form names defeat the typo "
                    f"gate)")
                continue
            used.add(arg.value)
            if arg.value not in registered:
                problems.append(
                    f"{rel}:{node.lineno}: metric {arg.value!r} is not in "
                    f"observability.metrics.METRIC_NAMES — register it "
                    f"there (typo?)")
    assert not problems, "\n".join(problems)
    assert used, "AST scan found no metric-helper calls — lint is broken"


def test_metric_gate_matches_live_registry():
    """The parsed table and the imported module agree (guards against the
    literal-eval scan drifting from what the registry actually builds)."""
    from paddle_tpu.observability.metrics import METRIC_NAMES
    assert [(n, k) for n, k, _ in METRIC_NAMES] == _metric_names_table()


def test_lint_gate_covers_testing_package():
    """The fault-injection harness (paddle_tpu/testing/) is inside every
    lint's scan set — its metric writes and exception handling are held
    to the same gates as the rest of the package."""
    rels = {rel for rel, _ in _iter_sources()}
    assert "paddle_tpu/testing/faultinject.py" in rels
    assert "paddle_tpu/testing/__init__.py" in rels
    # and the fault/* names it writes are registered in the frozen table
    registered = {n for n, _ in _metric_names_table()}
    assert "fault/injected" in registered
    assert {n for n in registered if n.startswith("fault/")} >= {
        "fault/injected", "fault/retries", "fault/preemptions",
        "fault/restarts", "fault/checkpoint_saves",
        "fault/checkpoint_restores", "fault/checkpoint_fallbacks",
        "fault/tasks_returned"}


def _top_level_package_imports(pkg: str):
    """(rel, lineno) of every TOP-LEVEL import of ``pkg`` from outside
    its own directory — the static half of a package's zero-cost-when-
    unused contract (lazy imports inside function bodies are fine)."""

    def _is_pkg_import(node):
        if isinstance(node, ast.Import):
            return any(a.name.startswith(f"paddle_tpu.{pkg}")
                       for a in node.names)
        if isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if (mod.startswith(f"paddle_tpu.{pkg}")
                    or mod == pkg or mod.startswith(f"{pkg}.")):
                return True
            # `from paddle_tpu import <pkg>` / `from . import <pkg>`
            # / `from .. import <pkg>` — the package arrives as a NAME,
            # module says nothing about it
            if mod in ("paddle_tpu", "") or node.level > 0:
                return any(a.name == pkg or a.name.startswith(f"{pkg}.")
                           for a in node.names)
        return False

    found = []
    for rel, tree in _iter_sources():
        if rel.startswith(f"paddle_tpu/{pkg}/"):
            continue
        # walk with function-nesting context
        def visit(node, in_func):
            for child in ast.iter_child_nodes(node):
                nested = in_func or isinstance(
                    child, (ast.FunctionDef, ast.AsyncFunctionDef))
                if _is_pkg_import(child) and not in_func:
                    found.append((rel, child.lineno))
                visit(child, nested)
        visit(tree, False)
    return found


def test_serving_package_only_imported_lazily():
    """Zero-cost-when-unused, statically enforced: no module outside
    paddle_tpu/serving/ may import the serving package at TOP LEVEL —
    only inside a function body (lazy, like the CLI's serve branch).
    This is what guarantees ``import paddle_tpu`` never pulls the
    server; tests/test_serving_chaos.py proves the same fact at runtime
    in a fresh interpreter (under -m slow — a full subprocess import
    costs ~12 s of tier-1 budget)."""
    problems = [
        f"{rel}:{lineno}: top-level import of the serving package — "
        f"must be lazy (inside a function) so `import paddle_tpu` "
        f"stays serving-free"
        for rel, lineno in _top_level_package_imports("serving")]
    assert not problems, "\n".join(problems)
    # and the one sanctioned lazy site exists (the CLI serve branch)
    with open(os.path.join(ROOT, "cli.py")) as fh:
        assert "from paddle_tpu.serving.cli import serve_main" in fh.read()


def test_tuning_package_only_imported_lazily():
    """Same contract for the autotuner: declaring a tunable
    (core.registry.register_tunable) costs nothing, and only an explicit
    autotune opt-in may load paddle_tpu/tuning/ — every call site
    (executor dispatch chunking, reader prefetch defaults, serving
    batcher, flash-attention layer blocks, the CLI tune branch) imports
    it inside a function body.  `import paddle_tpu` stays tuning-free
    (tests/test_tuning.py proves the runtime half)."""
    problems = [
        f"{rel}:{lineno}: top-level import of the tuning package — "
        f"must be lazy (inside a function) so training paths that "
        f"never opt in never load the autotuner"
        for rel, lineno in _top_level_package_imports("tuning")]
    assert not problems, "\n".join(problems)
    # and the ONE sanctioned lazy replay site exists: the shared
    # core.registry.resolve_tuned helper every call site (executor,
    # reader, serving, sparse session) now
    # routes through (round-15 dedup of the per-module copies)
    with open(os.path.join(ROOT, "core", "registry.py")) as fh:
        assert "from ..tuning.store import tuned" in fh.read()


def test_lint_gate_covers_serving_package():
    """The serving runtime (paddle_tpu/serving/) is inside every lint's
    scan set — its metric writes and exception handling are held to the
    same gates — and the serving/* names it writes are frozen in the
    METRIC_NAMES table."""
    rels = {rel for rel, _ in _iter_sources()}
    assert "paddle_tpu/serving/__init__.py" in rels
    assert "paddle_tpu/serving/server.py" in rels
    assert "paddle_tpu/serving/model.py" in rels
    assert "paddle_tpu/serving/cli.py" in rels
    registered = {n for n, _ in _metric_names_table()}
    assert {n for n in registered if n.startswith("serving/")} >= {
        "serving/requests", "serving/batches", "serving/shed",
        "serving/deadline_expired", "serving/breaker_open",
        "serving/queue_depth", "serving/batch_size",
        "serving/request_ms"}


def test_registry_matches_ast_scan():
    """The AST scan and the live registry agree — guards against the scan
    silently missing a registration idiom (e.g. names built dynamically)."""
    from paddle_tpu.core.registry import registered_ops

    ast_names = {n for n, _, _ in _registered_names("register_op")}
    live = set(registered_ops())
    # live ⊆ ast: every imported op was visible to the scan.  (ast - live
    # is legitimate: flag-gated modules may not be imported here.)
    missing = live - ast_names
    assert not missing, (
        f"ops registered at runtime but invisible to the AST lint "
        f"(dynamic name construction defeats the duplicate gate): "
        f"{sorted(missing)}")


def test_lint_gate_covers_tuning_package():
    """The autotuner (paddle_tpu/tuning/) is inside every lint's scan
    set — its metric writes and exception handling are held to the same
    gates — and the tuning/* names it writes are frozen in the
    METRIC_NAMES table."""
    rels = {rel for rel, _ in _iter_sources()}
    assert "paddle_tpu/tuning/__init__.py" in rels
    assert "paddle_tpu/tuning/tunables.py" in rels
    assert "paddle_tpu/tuning/search.py" in rels
    assert "paddle_tpu/tuning/store.py" in rels
    assert "paddle_tpu/tuning/targets.py" in rels
    registered = {n for n, _ in _metric_names_table()}
    assert {n for n in registered if n.startswith("tuning/")} >= {
        "tuning/trials", "tuning/trial_ms", "tuning/failures",
        "tuning/winners", "tuning/refusals", "tuning/replays"}


def test_tunable_registry_matches_ast_scan():
    """Agreement gate for the autotuner knob declarations: every live
    register_tunable name is a string literal the duplicate lint can
    see.  (ast - live is legitimate: serving and the sparse session
    register lazily.)  Every declared entry must also pass
    the registry's own validation — importing the declaring modules here
    IS that check, since register_tunable validates at call time."""
    import importlib

    from paddle_tpu.core.registry import registered_tunables

    # surface the lazily-imported declarations so live is maximal
    importlib.import_module("paddle_tpu.serving.server")
    importlib.import_module("paddle_tpu.serving.decode")
    importlib.import_module("paddle_tpu.sparse.session")

    ast_names = {n for n, _, _ in _registered_names("register_tunable")}
    live = set(registered_tunables())
    missing = live - ast_names
    assert not missing, (
        f"tunables registered at runtime but invisible to the AST lint "
        f"(dynamic name construction defeats the duplicate gate): "
        f"{sorted(missing)}")
    assert live >= {"executor/run_pipelined", "reader/prefetch",
                    "serving/batcher", "serving/decode_slots",
                    "sparse/hot_rows", "sparse/prefetch",
                    "sparse/push_flush"}, \
        f"expected initial tunable coverage missing: {sorted(live)}"


# ---------------------------------------------------------------------------
# Span-name gate (paddle_tpu.observability.tracing.SPAN_NAMES) — the
# tracing mirror of the metric gate: every span name passed to span()/
# start_span() must be a string literal frozen in SPAN_NAMES.  The SAME
# gate holds the phase log's names: every record_phase() call passes a
# literal frozen in core.compile_cache.PHASE_NAMES.
# ---------------------------------------------------------------------------
# (table, file that holds its literal, helpers whose first argument is a
# name of it, file left out of the scan).  The tracing module itself
# passes names through variables by construction (its SPAN_NAMES table is
# what the gate checks against); compile_cache.py's own record_phase calls
# pass literals like everyone's, so nothing is left out there.
_NAME_TABLES = {
    "SPAN_NAMES": ("observability/tracing.py", ("span", "start_span"),
                   "paddle_tpu/observability/tracing.py"),
    "PHASE_NAMES": ("core/compile_cache.py", ("record_phase",), None),
}
_name_tables = pytest.mark.parametrize("table", sorted(_NAME_TABLES))


def _names_table(table):
    """Names parsed from the table's literal — no import, so the gate
    also covers a syntactically valid but unimportable state."""
    path = os.path.join(ROOT, *_NAME_TABLES[table][0].split("/"))
    with open(path) as fh:
        tree = ast.parse(fh.read(), filename=path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == table
                for t in node.targets):
            rows = ast.literal_eval(node.value)
            return [name for name, _help in rows]
    raise AssertionError(f"{table} literal not found in {path}")


def _span_names_table():
    return _names_table("SPAN_NAMES")


@_name_tables
def test_span_names_table_well_formed(table):
    names = _names_table(table)
    dupes = {n for n in names if names.count(n) > 1}
    assert not dupes, f"duplicate {table} entries: {sorted(dupes)}"
    assert names, f"{table} is empty — the gate has nothing to check"
    for name in names:
        assert "/" in name, f"{table}: {name!r} is not namespaced (sub/name)"


@_name_tables
def test_span_helper_names_are_registered_literals(table):
    _file, helpers, defining_file = _NAME_TABLES[table]
    registered = set(_names_table(table))
    problems, used = [], set()
    for rel, tree in _iter_lint_sources():
        if rel == defining_file:
            continue
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            target = fn.id if isinstance(fn, ast.Name) else (
                fn.attr if isinstance(fn, ast.Attribute) else None)
            if target not in helpers:
                continue
            if not node.args:
                problems.append(f"{rel}:{node.lineno}: {target} without a "
                                f"positional name")
                continue
            arg = node.args[0]
            if not (isinstance(arg, ast.Constant)
                    and isinstance(arg.value, str)):
                problems.append(
                    f"{rel}:{node.lineno}: {target} name must be a "
                    f"string literal (free-form names defeat the typo "
                    f"gate)")
                continue
            used.add(arg.value)
            if arg.value not in registered:
                problems.append(
                    f"{rel}:{node.lineno}: {arg.value!r} is not in "
                    f"{table} — register it there (typo?)")
    assert not problems, "\n".join(problems)
    assert used, f"AST scan found no {helpers} calls — lint is broken"
    # the full causal chain is instrumented: every frozen name is LIVE
    # at some call site (a dead table row is a removed instrumentation
    # point, which deserves a conscious table edit)
    assert used == registered, (
        f"{table} and call sites disagree: "
        f"unused={sorted(registered - used)} "
        f"unregistered={sorted(used - registered)}")


@_name_tables
def test_span_gate_matches_live_registry(table):
    import importlib
    module = importlib.import_module(
        "paddle_tpu." + _NAME_TABLES[table][0][:-3].replace("/", "."))
    assert [n for n, _ in getattr(module, table)] == _names_table(table)


def test_attribution_module_only_imported_lazily():
    """The doctor engine (observability/attribution.py) pulls
    analysis.cost_model; like serving and tuning, only the opted-in
    surfaces (doctor CLI, bench drivers) may import it — no top-level
    import outside paddle_tpu/observability/, and the observability
    package __init__ itself must not import it either (the `observe`
    hot path stays attribution-free)."""
    problems = []
    for rel, tree in _iter_sources():
        for node in ast.walk(tree):
            if not isinstance(node, (ast.Import, ast.ImportFrom)):
                continue
            mod = getattr(node, "module", "") or ""
            names = [a.name for a in node.names]
            hit = (
                ("observability.attribution" in mod)
                or (mod.endswith("observability") and
                    "attribution" in names)
                or (isinstance(node, ast.ImportFrom) and node.level > 0
                    and mod == "" and "attribution" in names)
                or (isinstance(node, ast.ImportFrom) and node.level > 0
                    and mod == "attribution")
                or (isinstance(node, ast.Import) and any(
                    "observability.attribution" in n for n in names)))
            if not hit:
                continue
            if rel == "paddle_tpu/observability/attribution.py":
                continue
            # lazy (inside a function body) is the sanctioned form —
            # detect top-level by column 0 of module/class scope walk
            problems.append((rel, node.lineno))
    # re-scan with function context to keep only TOP-LEVEL hits
    toplevel = []
    for rel, lineno in problems:
        path = os.path.join(ROOT, os.pardir, rel)
        with open(path) as fh:
            tree = ast.parse(fh.read(), filename=rel)

        def visit(node, in_func):
            for child in ast.iter_child_nodes(node):
                nested = in_func or isinstance(
                    child, (ast.FunctionDef, ast.AsyncFunctionDef))
                if getattr(child, "lineno", None) == lineno \
                        and not in_func \
                        and isinstance(child,
                                       (ast.Import, ast.ImportFrom)):
                    toplevel.append(f"{rel}:{lineno}")
                visit(child, nested)
        visit(tree, False)
    assert not toplevel, (
        "top-level import of observability.attribution — must be lazy "
        "(inside a function) so the observe hot path never pays for "
        "the cost model: " + ", ".join(toplevel))
    # and the sanctioned lazy site exists (the doctor CLI branch)
    with open(os.path.join(ROOT, "cli.py")) as fh:
        assert "from paddle_tpu.observability import attribution" \
            in fh.read()


def _top_level_obs_submodule_imports(submod: str):
    """(rel, lineno) of every TOP-LEVEL import of
    ``paddle_tpu/observability/<submod>.py`` from any OTHER module —
    the static half of a lazy-only observability submodule's zero-cost
    contract (attribution and collector pull what the hot path must
    not pay for)."""
    target = f"observability.{submod}"
    own = f"paddle_tpu/observability/{submod}.py"

    def _is_hit(node):
        mod = getattr(node, "module", "") or ""
        names = [a.name for a in node.names]
        return (
            (target in mod)
            or (mod.endswith("observability") and submod in names)
            or (isinstance(node, ast.ImportFrom) and node.level > 0
                and mod == "" and submod in names)
            or (isinstance(node, ast.ImportFrom) and node.level > 0
                and mod == submod)
            or (isinstance(node, ast.Import) and any(
                target in n for n in names)))

    found = []
    for rel, tree in _iter_sources():
        if rel == own:
            continue

        def visit(node, in_func):
            for child in ast.iter_child_nodes(node):
                nested = in_func or isinstance(
                    child, (ast.FunctionDef, ast.AsyncFunctionDef))
                if isinstance(child, (ast.Import, ast.ImportFrom)) \
                        and not in_func and _is_hit(child):
                    found.append(f"{rel}:{child.lineno}")
                visit(child, nested)
        visit(tree, False)
    return found


def test_collector_module_only_imported_lazily():
    """The fleet metrics collector (observability/collector.py) can dial
    sockets and pull the sparse wire stack — only the opted-in surfaces
    (the fleet-stats CLI branch, library callers inside a function) may
    import it.  No top-level import anywhere else, and the observability
    package __init__ must not import it (importing
    paddle_tpu.observability stays cheap and socket-free)."""
    toplevel = _top_level_obs_submodule_imports("collector")
    assert not toplevel, (
        "top-level import of observability.collector — must be lazy "
        "(inside a function) so importing the observability package "
        "never pays for the collector's socket/wire stack: "
        + ", ".join(toplevel))
    # and the sanctioned lazy site exists (the fleet-stats CLI branch)
    with open(os.path.join(ROOT, "cli.py")) as fh:
        assert "from paddle_tpu.observability import collector" \
            in fh.read()


def test_lint_gate_covers_collector_module():
    """observability/collector.py is inside every lint's scan set and
    its collector/* metric names are frozen in METRIC_NAMES, so its
    helper calls ride the literal-name typo gate."""
    rels = {rel for rel, _ in _iter_sources()}
    assert "paddle_tpu/observability/collector.py" in rels
    registered = {n for n, _ in _metric_names_table()}
    assert {n for n in registered if n.startswith("collector/")} >= {
        "collector/merges", "collector/sources"}
    assert {n for n in registered if n.startswith("trace/")} >= {
        "trace/context_rejected"}


# ---------------------------------------------------------------------------
# Tier-1 time-budget guard: subprocess rounds must be @slow.  Each
# jax-importing subprocess costs ~10-30s of the 870s tier-1 cap (the
# suite runs at ~95% of it on this container); the PR 6/8/9/11
# convention pushes them to `-m slow`.  Frozen allowlist below: the few
# CHEAP subprocess tests deliberately kept tier-1 — never add entries,
# only remove them (the ratchet direction mirrors the except-swallow
# gate).
# ---------------------------------------------------------------------------
SUBPROCESS_FAST_ALLOWLIST = {
    # ~4s: the only cross-process coverage of the master's lease-lapse
    # re-serve (a dead trainer's task re-queues for a healthy one)
    "tests/test_master_service.py": {
        "test_elastic_trainer_death_cross_process"},
    # pre-existing CPU-backend collectives round (known-failing where
    # multiprocess CPU collectives are unimplemented; kept tier-1 so a
    # chip/GPU session surfaces it immediately)
    "tests/test_multiprocess_launch.py": {
        "test_two_process_distributed_train_and_checkpoint"},
    # PR 21 (bring-up): what these pin only exists at process start — the
    # backend a fresh interpreter picks, the cache directory JAX read from
    # its environment, a backend-free import — so they cannot run
    # in-process, and a chip session is too late to find them broken.
    # ~30 s (the pre-flight) + 3 x ~3 s
    "tests/test_chip_smoke.py": {
        "test_chip_smoke_cpu_preflight_passes",
        "test_chip_smoke_refuses_cpu_without_explicit_request",
        "test_bench_refuses_cpu",
        "test_imports_initialise_no_backend"},
    # 3 x ~5 s; replace the in-process persistent-layer round trip and the
    # cold/warm subprocess smoke that went with the serialize layer
    "tests/test_compile_cache.py": {
        "test_cache_dir_from_environment_is_used_untouched",
        "test_cache_dir_defaults_to_fixed_path_in_checkout"},
}


def _iter_test_sources():
    tests_dir = os.path.dirname(os.path.abspath(__file__))
    for f in sorted(os.listdir(tests_dir)):
        if f.startswith("test_") and f.endswith(".py"):
            path = os.path.join(tests_dir, f)
            with open(path) as fh:
                yield f"tests/{f}", ast.parse(fh.read(), filename=path)


def _mentions_slow(node) -> bool:
    return "slow" in ast.dump(node)


def test_subprocess_test_rounds_are_slow_marked():
    problems = []
    for rel, tree in _iter_test_sources():
        module_slow = any(
            isinstance(node, ast.Assign)
            and any(getattr(t, "id", None) == "pytestmark"
                    for t in node.targets)
            and _mentions_slow(node.value)
            for node in tree.body)
        if module_slow:
            continue
        # module-level helpers whose body touches subprocess: a test
        # calling one is a subprocess test (the _run(...) idiom)
        def touches_subprocess(fn):
            return any(isinstance(n, ast.Name) and n.id == "subprocess"
                       for n in ast.walk(fn))
        helpers = {node.name for node in tree.body
                   if isinstance(node, ast.FunctionDef)
                   and not node.name.startswith("test_")
                   and touches_subprocess(node)}

        def is_subprocess_test(fn):
            if touches_subprocess(fn):
                return True
            for n in ast.walk(fn):
                if isinstance(n, ast.Call) \
                        and isinstance(n.func, ast.Name) \
                        and n.func.id in helpers:
                    return True
            return False

        allowed = SUBPROCESS_FAST_ALLOWLIST.get(rel, set())
        for node in tree.body:
            if not (isinstance(node, ast.FunctionDef)
                    and node.name.startswith("test_")):
                continue
            if not is_subprocess_test(node):
                continue
            if any(_mentions_slow(d) for d in node.decorator_list):
                continue
            if node.name in allowed:
                continue
            problems.append(
                f"{rel}:{node.lineno}: {node.name} spawns a subprocess "
                f"but is not @pytest.mark.slow — each jax-importing "
                f"round costs ~10-30s of the 870s tier-1 cap; mark it "
                f"slow (PR 6/8/9/11 convention) or argue it into the "
                f"frozen SUBPROCESS_FAST_ALLOWLIST")
    assert not problems, "\n".join(problems)
    # the allowlist itself stays honest: every entry still exists
    by_file = {rel: {node.name for node in tree.body
                     if isinstance(node, ast.FunctionDef)}
               for rel, tree in _iter_test_sources()}
    for rel, names in SUBPROCESS_FAST_ALLOWLIST.items():
        missing = names - by_file.get(rel, set())
        assert not missing, (
            f"{rel}: allowlisted subprocess test(s) no longer exist — "
            f"ratchet SUBPROCESS_FAST_ALLOWLIST down: {sorted(missing)}")


def _top_level_serving_submodule_imports(submods=("http", "fleet")):
    """(rel, lineno) of every TOP-LEVEL import of
    paddle_tpu/serving/{http,fleet}.py from any OTHER module — including
    serving/__init__.py and serving/cli.py: importing paddle_tpu.serving
    (the Server surface) must not load the network front or the fleet
    router.  Lazy imports inside function bodies are the sanctioned
    form.  Careful with stdlib collisions: absolute ``import
    http.client`` is NOT a hit."""
    own = {f"paddle_tpu/serving/{m}.py" for m in submods}

    def _is_hit(node, rel):
        in_serving = rel.startswith("paddle_tpu/serving/")
        full = tuple(f"paddle_tpu.serving.{m}" for m in submods)
        if isinstance(node, ast.Import):
            return any(a.name.startswith(full) for a in node.names)
        if isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if mod.startswith(full):
                return True
            if mod in ("paddle_tpu.serving", "serving"):
                return any(a.name in submods for a in node.names)
            if node.level > 0 and in_serving:
                # from .http import X / from . import http
                if mod in submods:
                    return True
                if mod == "" and any(a.name in submods
                                     for a in node.names):
                    return True
        return False

    found = []
    for rel, tree in _iter_sources():
        if rel in own:
            continue

        def visit(node, in_func):
            for child in ast.iter_child_nodes(node):
                nested = in_func or isinstance(
                    child, (ast.FunctionDef, ast.AsyncFunctionDef))
                if _is_hit(child, rel) and not in_func:
                    found.append((rel, child.lineno))
                visit(child, nested)
        visit(tree, False)
    return found


def test_http_and_fleet_modules_only_imported_lazily():
    """Zero-cost-when-unused for the NEW serving-fleet modules (ISSUE
    11): importing paddle_tpu — or paddle_tpu.serving itself, i.e.
    running a plain Server — loads neither serving/http.py nor
    serving/fleet.py.  Only the opted-in surfaces (`serve --http`, the
    `fleet` CLI branch) may import them, lazily.
    tests/test_fleet_chaos.py proves the runtime half in a fresh
    interpreter (@slow)."""
    problems = [
        f"{rel}:{lineno}: top-level import of serving.http/serving.fleet "
        f"— must be lazy (inside a function) so `import paddle_tpu"
        f".serving` stays front/fleet-free"
        for rel, lineno in _top_level_serving_submodule_imports()]
    assert not problems, "\n".join(problems)
    # and the sanctioned lazy sites exist
    with open(os.path.join(ROOT, "serving", "cli.py")) as fh:
        assert "from .http import HttpFront" in fh.read()   # serve --http
    with open(os.path.join(ROOT, "cli.py")) as fh:
        assert "from paddle_tpu.serving.fleet import fleet_main" \
            in fh.read()                                    # fleet branch
    with open(os.path.join(ROOT, "serving", "fleet.py")) as fh:
        assert "from .http import HttpFront" in fh.read()   # fleet_main


def test_lint_gate_covers_http_and_fleet_modules():
    """serving/http.py + serving/fleet.py are inside every lint's scan
    set, their http/* + fleet/* metric names are frozen in METRIC_NAMES,
    and their span names are frozen in SPAN_NAMES (the used==registered
    span check then keeps both instrumented)."""
    rels = {rel for rel, _ in _iter_sources()}
    assert "paddle_tpu/serving/http.py" in rels
    assert "paddle_tpu/serving/fleet.py" in rels
    registered = {n for n, _ in _metric_names_table()}
    assert {n for n in registered if n.startswith("http/")} >= {
        "http/requests", "http/rejected", "http/auth_failures",
        "http/request_ms"}
    assert {n for n in registered if n.startswith("fleet/")} >= {
        "fleet/requests", "fleet/failovers", "fleet/evictions",
        "fleet/relaunches", "fleet/router_shed", "fleet/scale_outs",
        "fleet/scale_ins", "fleet/replicas"}
    spans = set(_span_names_table())
    assert {"http/request", "fleet/autoscale"} <= spans


def _top_level_distributed_submodule_imports(submod: str):
    """(rel, lineno) of every TOP-LEVEL import of
    ``paddle_tpu/distributed/<submod>.py`` from any OTHER module —
    including distributed/__init__.py: importing paddle_tpu (or the
    distributed package for its Master/Supervisor surface) must not
    load the elastic service."""
    target = f"distributed.{submod}"
    own = f"paddle_tpu/distributed/{submod}.py"

    def _is_hit(node, rel):
        in_pkg = rel.startswith("paddle_tpu/distributed/")
        mod = getattr(node, "module", "") or ""
        names = [a.name for a in node.names]
        if isinstance(node, ast.Import):
            return any(f"paddle_tpu.{target}" in n for n in names)
        if target in mod:
            return True
        if mod.endswith("distributed") and submod in names:
            return True
        if node.level > 0 and in_pkg:
            # from .elastic import X / from . import elastic
            if mod == submod:
                return True
            if mod == "" and submod in names:
                return True
        return False

    found = []
    for rel, tree in _iter_sources():
        if rel == own:
            continue

        def visit(node, in_func):
            for child in ast.iter_child_nodes(node):
                nested = in_func or isinstance(
                    child, (ast.FunctionDef, ast.AsyncFunctionDef))
                if isinstance(child, (ast.Import, ast.ImportFrom)) \
                        and not in_func and _is_hit(child, rel):
                    found.append(f"{rel}:{child.lineno}")
                visit(child, nested)
        visit(tree, False)
    return found


def test_elastic_module_only_imported_lazily():
    """Zero-cost-when-unused for the elastic training service (ISSUE
    13): importing paddle_tpu — or paddle_tpu.distributed itself, i.e.
    using Master/Supervisor/CheckpointManager — loads neither the
    elastic coordinator nor its analysis/planner import chain.  Only
    the opted-in surfaces (the `elastic` CLI branch, an explicit
    `from paddle_tpu.distributed.elastic import ...`) may load it,
    lazily."""
    toplevel = _top_level_distributed_submodule_imports("elastic")
    assert not toplevel, (
        "top-level import of distributed.elastic — must be lazy "
        "(inside a function) so `import paddle_tpu` stays "
        "elastic-free: " + ", ".join(toplevel))
    # and the sanctioned lazy site exists (the CLI elastic branch)
    with open(os.path.join(ROOT, "cli.py")) as fh:
        assert "from paddle_tpu.distributed.elastic import elastic_main" \
            in fh.read()
    # the distributed package __init__ must not re-export it either
    with open(os.path.join(ROOT, "distributed", "__init__.py")) as fh:
        assert "elastic" not in fh.read()


def test_lint_gate_covers_elastic_module():
    """distributed/elastic.py is inside every lint's scan set, its
    elastic/* metric names are frozen in METRIC_NAMES, its span name is
    frozen in SPAN_NAMES (the used==registered check then keeps the
    resize boundary instrumented), and the new injection sites are
    registered in the faultinject harness."""
    rels = {rel for rel, _ in _iter_sources()}
    assert "paddle_tpu/distributed/elastic.py" in rels
    registered = {n for n, _ in _metric_names_table()}
    assert {n for n in registered if n.startswith("elastic/")} >= {
        "elastic/workers", "elastic/heartbeats", "elastic/drains",
        "elastic/resizes", "elastic/resize_ms"}
    assert "elastic/resize" in set(_span_names_table())
    from paddle_tpu.testing.faultinject import KNOWN_SITES
    assert {"elastic.worker", "master.heartbeat"} <= set(KNOWN_SITES)


def test_sparse_package_only_imported_lazily():
    """Zero-cost-when-unused for the sparse parameter server (ISSUE 14):
    importing paddle_tpu — or running an Executor/Trainer without
    sparse_tables — never loads paddle_tpu/sparse/.  The trainer wiring
    is DUCK-TYPED (train(sparse_tables=session) calls methods on the
    session object), so no module outside the package needs even a lazy
    import; the one sanctioned lazy site is the reverse direction —
    sparse/session.py pulling serving.Model for the serve attachment —
    which lives inside the package and stays lazy for serving's own
    gate."""
    problems = [
        f"{rel}:{lineno}: top-level import of the sparse package — "
        f"must be lazy (inside a function) so `import paddle_tpu` and "
        f"every non-sparse training path stay sparse-free"
        for rel, lineno in _top_level_package_imports("sparse")]
    assert not problems, "\n".join(problems)
    # the serving attachment inside the package is itself lazy (the
    # serving gate would reject a top-level form; assert the sanctioned
    # lazy site exists so the attachment cannot silently disappear)
    with open(os.path.join(ROOT, "sparse", "session.py")) as fh:
        assert "from ..serving.model import Model" in fh.read()


def test_lint_gate_covers_sparse_package():
    """paddle_tpu/sparse/ is inside every lint's scan set, its sparse/*
    metric names are frozen in METRIC_NAMES, its pull/push span pair is
    frozen in SPAN_NAMES (the used==registered check then keeps the rim
    instrumented), and the sparse.push injection site is registered in
    the faultinject harness."""
    rels = {rel for rel, _ in _iter_sources()}
    assert "paddle_tpu/sparse/__init__.py" in rels
    assert "paddle_tpu/sparse/table.py" in rels
    assert "paddle_tpu/sparse/session.py" in rels
    registered = {n for n, _ in _metric_names_table()}
    assert {n for n in registered if n.startswith("sparse/")} >= {
        "sparse/pulls", "sparse/pulled_rows", "sparse/pushes",
        "sparse/pushed_rows", "sparse/pull_ms", "sparse/push_ms",
        "sparse/cache_hits", "sparse/cache_misses", "sparse/live_rows"}
    spans = set(_span_names_table())
    assert {"sparse/pull", "sparse/push"} <= spans
    from paddle_tpu.testing.faultinject import KNOWN_SITES
    assert "sparse.push" in KNOWN_SITES


def _top_level_sparse_submodule_imports(
        submods=("wire", "pserver", "client")):
    """(rel, lineno) of every TOP-LEVEL import of the sparse WIRE TIER
    (paddle_tpu/sparse/{wire,pserver,client}.py) from any module outside
    the tier itself — including sparse/__init__.py, table.py and
    session.py: importing paddle_tpu.sparse (the in-process
    SparseTable/SparseSession surface) must not load a socket stack.
    Lazy imports inside function bodies are the sanctioned form."""
    own = {f"paddle_tpu/sparse/{m}.py" for m in submods}

    def _is_hit(node, rel):
        in_sparse = rel.startswith("paddle_tpu/sparse/")
        full = tuple(f"paddle_tpu.sparse.{m}" for m in submods)
        if isinstance(node, ast.Import):
            return any(a.name.startswith(full) for a in node.names)
        if isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if mod.startswith(full):
                return True
            if mod in ("paddle_tpu.sparse", "sparse"):
                return any(a.name in submods for a in node.names)
            if node.level > 0 and in_sparse:
                # from .wire import X / from . import wire
                if mod in submods:
                    return True
                if mod == "" and any(a.name in submods
                                     for a in node.names):
                    return True
        return False

    found = []
    for rel, tree in _iter_sources():
        if rel in own:
            continue

        def visit(node, in_func):
            for child in ast.iter_child_nodes(node):
                nested = in_func or isinstance(
                    child, (ast.FunctionDef, ast.AsyncFunctionDef))
                if _is_hit(child, rel) and not in_func:
                    found.append((rel, child.lineno))
                visit(child, nested)
        visit(tree, False)
    return found


def test_pserver_wire_tier_only_imported_lazily():
    """Zero-cost-when-unused for the sparse parameter-server WIRE tier
    (ISSUE 17): importing paddle_tpu — or paddle_tpu.sparse itself,
    i.e. running the in-process table — loads none of sparse/wire.py,
    sparse/pserver.py, sparse/client.py.  Only the opted-in surfaces
    (the `pserver` CLI branch, an explicit `from
    paddle_tpu.sparse.client import RemoteSparseTable`) may load them,
    lazily."""
    problems = [
        f"{rel}:{lineno}: top-level import of the sparse wire tier — "
        f"must be lazy (inside a function) so `import "
        f"paddle_tpu.sparse` stays socket-free"
        for rel, lineno in _top_level_sparse_submodule_imports()]
    assert not problems, "\n".join(problems)
    # and the sanctioned lazy site exists (the CLI pserver branch)
    with open(os.path.join(ROOT, "cli.py")) as fh:
        assert "from paddle_tpu.sparse.pserver import pserver_main" \
            in fh.read()
    # the sparse package __init__ must not re-export the tier either
    with open(os.path.join(ROOT, "sparse", "__init__.py")) as fh:
        body = fh.read().split('"""', 2)[2]      # docstring MAY name it
        for mod in ("wire", "pserver", "client"):
            assert f"import {mod}" not in body


def test_lint_gate_covers_pserver_tier():
    """sparse/{wire,pserver,client}.py are inside every lint's scan
    set, the pserver/* metric names are frozen in METRIC_NAMES, the
    pserver/rpc span is frozen in SPAN_NAMES (the used==registered
    check then keeps the client round instrumented), and the chaos
    sites are registered in the faultinject harness."""
    rels = {rel for rel, _ in _iter_sources()}
    assert "paddle_tpu/sparse/wire.py" in rels
    assert "paddle_tpu/sparse/pserver.py" in rels
    assert "paddle_tpu/sparse/client.py" in rels
    registered = {n for n, _ in _metric_names_table()}
    assert {n for n in registered if n.startswith("pserver/")} >= {
        "pserver/requests", "pserver/pull_rows", "pserver/push_rows",
        "pserver/wire_bytes_in", "pserver/wire_bytes_out",
        "pserver/frame_ms", "pserver/reconnects",
        "pserver/replication_lag_ms", "pserver/backup_pushes",
        "pserver/checkpoints"}
    assert "pserver/rpc" in set(_span_names_table())
    from paddle_tpu.testing.faultinject import KNOWN_SITES
    assert {"pserver.rpc", "pserver.shard"} <= set(KNOWN_SITES)


def test_shard_fn_registry_matches_ast_scan():
    """Same agreement gate for the sharding-propagation rules: every
    live register_shard_fn name is a string literal the duplicate lint
    can see, and every rule targets a registered op (a rule for a
    nonexistent op would never fire — a silent planner blind spot)."""
    from paddle_tpu.core.registry import (registered_ops,
                                          registered_shard_fns)

    ast_names = {n for n, _, _ in _registered_names("register_shard_fn")}
    live = set(registered_shard_fns())
    missing = live - ast_names
    assert not missing, (
        f"shard fns registered at runtime but invisible to the AST lint: "
        f"{sorted(missing)}")
    stale = live - set(registered_ops())
    assert not stale, (
        f"shard fns for unregistered ops (dead rules): {sorted(stale)}")
    assert live, "no shard fns registered — the planner has no rules"

# ---------------------------------------------------------------------------
# Thread-name-prefix gate (observability.metrics.THREAD_NAME_PREFIXES)
# ---------------------------------------------------------------------------
def _thread_prefix_table():
    """(prefix, help) rows parsed from the THREAD_NAME_PREFIXES literal —
    no import, same contract as the metric-name gate."""
    path = os.path.join(ROOT, "observability", "metrics.py")
    with open(path) as fh:
        tree = ast.parse(fh.read(), filename=path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "THREAD_NAME_PREFIXES"
                for t in node.targets):
            return list(ast.literal_eval(node.value))
    raise AssertionError(
        "THREAD_NAME_PREFIXES literal not found in metrics.py")


def test_thread_prefix_table_well_formed():
    rows = _thread_prefix_table()
    assert rows, "THREAD_NAME_PREFIXES is empty — PT055 has no registry"
    prefixes = [p for p, _help in rows]
    dupes = {p for p in prefixes if prefixes.count(p) > 1}
    assert not dupes, f"duplicate thread prefixes: {sorted(dupes)}"
    for p, help_ in rows:
        assert p.startswith("pt-"), (
            f"thread prefix {p!r} must claim the framework's pt- "
            f"namespace")
        assert len(p) > len("pt-"), f"thread prefix {p!r} is bare"
        assert help_.strip(), f"thread prefix {p!r} has no help text"
    # no prefix may shadow another (pt-a and pt-a-b would make the
    # runtime attribution of a pt-a-b-* thread ambiguous)
    for a in prefixes:
        for b in prefixes:
            assert a == b or not b.startswith(a + "-"), (
                f"thread prefix {b!r} is shadowed by {a!r}")


def test_thread_prefix_gate_matches_live_registry():
    from paddle_tpu.observability.metrics import THREAD_NAME_PREFIXES
    assert list(THREAD_NAME_PREFIXES) == _thread_prefix_table()


# ---------------------------------------------------------------------------
# Concurrency verifier gate (analysis.concurrency, PT05x):
# the current tree must be clean modulo the FROZEN baseline, and the
# baseline can only shrink (the except-swallow ratchet convention).
# ---------------------------------------------------------------------------
def test_concurrency_baseline_well_formed():
    from paddle_tpu.analysis.concurrency import BASELINE
    for (rel, code), (count, why) in BASELINE.items():
        assert rel.startswith("paddle_tpu/"), (rel, code)
        assert code.startswith("PT05"), (
            f"baseline key {code!r} is not a PT05x concurrency code")
        assert count >= 1, (
            f"baseline entry {(rel, code)} permits {count} findings — "
            f"zero-count entries are dead weight; delete them")
        assert why.strip(), (
            f"baseline entry {(rel, code)} has no justification — every "
            f"accepted finding carries a one-line why")


def test_concurrency_tree_clean_vs_baseline():
    """Tier-1 ratchet: the PT05x pass over today's tree yields NO findings
    beyond the frozen baseline, and no baseline entry budgets MORE
    findings than remain (fix-or-justify, count-can-only-shrink)."""
    from paddle_tpu.analysis import concurrency as cc

    findings = cc.analyze_package()
    new, _suppressed, stale = cc.apply_baseline(findings)
    assert not new, (
        "new concurrency findings (fix them or — only for accepted-by-"
        "design sites — add a justified BASELINE entry):\n"
        + "\n".join(f.render() for f in new))
    assert not stale, (
        f"stale BASELINE entries budget more findings than remain — "
        f"ratchet them down so the count can only shrink: {stale}")


def test_concurrency_pass_covers_threaded_modules():
    """The analyzer's scan set is the same walk as every other lint —
    pin that the threaded modules it exists for are actually inside it,
    and that the pass sees their locks (a lock-model regression that
    finds NO locks would pass the ratchet vacuously)."""
    from paddle_tpu.analysis import concurrency as cc

    rels = {rel for rel, _ in _iter_sources()}
    for mod in ("paddle_tpu/serving/server.py",
                "paddle_tpu/serving/decode.py",
                "paddle_tpu/serving/fleet.py",
                "paddle_tpu/sparse/session.py",
                "paddle_tpu/distributed/master.py",
                "paddle_tpu/distributed/checkpoint.py",
                "paddle_tpu/reader/pipeline.py",
                "paddle_tpu/observability/export.py"):
        assert mod in rels, f"{mod} missing from the lint scan set"
    # the model sees the watched-factory idiom as locks: server.py's
    # runtime condition + state locks must resolve, else PT050-053
    # silently cover nothing
    path = os.path.join(ROOT, "serving", "server.py")
    with open(path) as fh:
        src = fh.read()
    import paddle_tpu.analysis.concurrency as ccmod
    tree = ast.parse(src)
    mm = ccmod._ModuleModel(tree, "paddle_tpu/serving/server.py")
    kinds = set(mm.attr_kind_index.values())
    assert {"lock", "cond"} <= kinds, (
        f"concurrency model no longer resolves server.py's locks/"
        f"conditions (saw kinds {sorted(kinds)}) — the PT05x rules "
        f"would run vacuously")


def test_lockwatch_factories_adopted_in_threaded_modules():
    """The serving/sparse/distributed lock creation sites route through
    testing.lockwatch factories (make_lock/make_rlock/make_condition),
    so enabling PADDLE_TPU_LOCKWATCH actually watches them; raw
    threading.Lock() in these modules would silently escape the
    watchdog.  Infrastructure locks are exempt BY DESIGN: the metrics
    registry's own lock (lockwatch writes metrics — recursion), the
    compile cache and profiler (leaf locks on paths the watchdog
    traverses), and lockwatch itself."""
    exempt = {
        "paddle_tpu/observability/metrics.py",
        "paddle_tpu/core/compile_cache.py",
        "paddle_tpu/profiler.py",
        "paddle_tpu/testing/lockwatch.py",
        "paddle_tpu/testing/faultinject.py",
    }
    offenders = []
    for rel, tree in _iter_sources():
        if rel in exempt:
            continue
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if isinstance(fn, ast.Attribute) \
                    and isinstance(fn.value, ast.Name) \
                    and fn.value.id == "threading" \
                    and fn.attr in ("Lock", "RLock", "Condition"):
                offenders.append(f"{rel}:{node.lineno}: threading."
                                 f"{fn.attr}()")
    assert not offenders, (
        "raw threading primitives outside the exempt infrastructure "
        "set — route them through testing.lockwatch factories so the "
        "order watchdog can see them:\n" + "\n".join(offenders))
