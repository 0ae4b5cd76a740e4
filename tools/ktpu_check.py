#!/usr/bin/env python3
"""ktpu-check: the unified static-analysis driver for this repo.

One registry of analysis passes, one CLI, one exit code — the
``hack/verify-*`` + ``go vet`` + race-discipline role Kubernetes gets from
its toolchain, rebuilt for this Python/JAX port. Every pass is a pure
function over the source tree returning findings; the driver runs them all
(``--all``) or selectively (``--pass NAME``), prints a listing, and exits
nonzero on any finding.

Passes
======

``metrics``    dead-metric gate: every metric registered in
               SchedulerMetrics must be fed outside its definition.
``spans``      span-name lint: every emitted span name must be in bench.py's
               critical-path attribution table (or the ignore list).
``markers``    perf-scale tests must carry ``@pytest.mark.slow``.
``pb2-drift``  the vendored ktpu_device_pb2 module must match the .proto.
``locks``      lock-discipline: per class, attributes accessed under
               ``with self._lock`` must not be touched unguarded elsewhere.
``jit``        jit-boundary: functions reachable from the jitted entry
               points must not host-sync traced values (int()/float()/
               bool()/.item()/np.asarray), branch on them in Python, or
               declare unhashable static args.
``errors``     error taxonomy: ``backend/`` raises use the typed taxonomy
               (backend/errors.py); broad ``except Exception`` handlers
               reclassify or carry a reviewed justification.
``suppress``   suppression hygiene: every ``# ktpu: *-ok(...)`` marker
               carries a non-empty reason (an exception without a reason is
               itself a finding — every suppression is a reviewed decision).

Suppression grammar (all per-line, reason mandatory)
====================================================

    # ktpu: unguarded-ok(reason)      silence one locks finding
    # ktpu: host-sync-ok(reason)      silence one jit finding
    # ktpu: taxonomy-ok(reason)       silence one errors raise finding
    # ktpu: broad-except-ok(reason)   justify one broad except handler
    # ktpu: locked                    on a ``def`` line: the function runs
                                      with its class lock held by contract
                                      (callers acquire it) — its accesses
                                      count as guarded

Usage
=====

    python -m tools.ktpu_check --all            # every pass, exit 1 on any
    python -m tools.ktpu_check --pass locks     # one pass
    python -m tools.ktpu_check --all --json     # machine-readable (trends)
    python -m tools.ktpu_check --list           # registry

The old CLIs (``tools/check_metrics.py``, ``tools/check_markers.py``,
``tools/gen_pb2.py --check``) remain as thin shims over this registry.
"""

from __future__ import annotations

import ast
import json
import os
import re
import sys
from typing import Dict, List, NamedTuple, Optional, Sequence, Set, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "kubernetes_tpu")
TESTS = os.path.join(REPO, "tests")
METRICS_FILE = os.path.join(PKG, "metrics", "scheduler_metrics.py")
BENCH_FILE = os.path.join(REPO, "bench.py")


class Finding(NamedTuple):
    path: str
    line: int
    message: str

    def render(self) -> str:
        rel = os.path.relpath(self.path, REPO) if os.path.isabs(self.path) else self.path
        return f"{rel}:{self.line} {self.message}"


# --------------------------------------------------------------- registry

PASSES: "Dict[str, tuple]" = {}


def register(name: str, description: str):
    def deco(fn):
        PASSES[name] = (fn, description)
        return fn

    return deco


def _walk_py(root: str):
    for dirpath, _dirs, files in os.walk(root):
        for f in sorted(files):
            if f.endswith(".py"):
                yield os.path.join(dirpath, f)


def _read(path: str) -> str:
    with open(path, encoding="utf-8") as fh:
        return fh.read()


# ----------------------------------------------------------- suppressions

_SUPPRESS_RE = re.compile(
    r"#\s*ktpu:\s*(unguarded-ok|host-sync-ok|taxonomy-ok|broad-except-ok"
    r"|dispatch-ok)"
    r"\s*\(([^)]*)\)")
_LOCKED_RE = re.compile(r"#\s*ktpu:\s*locked\b")
_ANY_MARKER_RE = re.compile(r"#\s*ktpu:\s*([\w-]+)")


class _Suppressions:
    """Per-file ``# ktpu:`` marker index. A finding at line L is suppressed
    when L (or the statement's first line) carries the matching marker WITH
    a non-empty reason; empty reasons are surfaced by the ``suppress``
    pass, not honored here."""

    def __init__(self, src: str):
        self.by_line: Dict[int, List[Tuple[str, str]]] = {}
        self.locked_lines: Set[int] = set()
        for i, line in enumerate(src.splitlines(), start=1):
            for m in _SUPPRESS_RE.finditer(line):
                self.by_line.setdefault(i, []).append(
                    (m.group(1), m.group(2).strip()))
            if _LOCKED_RE.search(line):
                self.locked_lines.add(i)

    def silences(self, marker: str, *lines: int) -> bool:
        for ln in lines:
            for kind, reason in self.by_line.get(ln, ()):
                if kind == marker and reason:
                    return True
        return False


def _suppression_files():
    yield from _walk_py(PKG)
    yield BENCH_FILE
    for f in sorted(os.listdir(os.path.join(REPO, "tools"))):
        if f.endswith(".py"):
            yield os.path.join(REPO, "tools", f)


@register("suppress", "every # ktpu marker is well-formed and carries a reason")
def pass_suppress(files=None) -> List[Finding]:
    known = {"unguarded-ok", "host-sync-ok", "taxonomy-ok", "broad-except-ok",
             "dispatch-ok", "locked"}
    out: List[Finding] = []
    for path in (files if files is not None else _suppression_files()):
        try:
            src = _read(path)
        except OSError:
            continue
        if "ktpu:" not in src:
            continue
        for i, line in enumerate(src.splitlines(), start=1):
            m = _ANY_MARKER_RE.search(line)
            if not m:
                continue
            kind = m.group(1)
            if kind not in known:
                out.append(Finding(path, i, f"unknown ktpu marker {kind!r} "
                                   f"(known: {sorted(known)})"))
                continue
            if kind == "locked":
                continue
            sm = _SUPPRESS_RE.search(line)
            if sm is None:
                out.append(Finding(
                    path, i, f"malformed suppression '# ktpu: {kind}': "
                    "expected '(reason)'"))
            elif not sm.group(2).strip():
                out.append(Finding(
                    path, i, f"suppression '# ktpu: {kind}()' has no reason "
                    "— every exception is a reviewed decision"))
    return out


# ===================================================================== metrics
# (absorbed from tools/check_metrics.py — the PR-2 dead-metric gate)

_MUTATORS = ("observe", "inc", "set")


def registered_metrics(tree: ast.Module):
    """Metric attribute names from ``self.<attr> = r.register(...)``
    assignments in SchedulerMetrics.__init__."""
    attrs = []
    for cls in ast.walk(tree):
        if not (isinstance(cls, ast.ClassDef) and cls.name == "SchedulerMetrics"):
            continue
        for fn in cls.body:
            if not (isinstance(fn, ast.FunctionDef) and fn.name == "__init__"):
                continue
            for node in ast.walk(fn):
                if not isinstance(node, ast.Assign) or len(node.targets) != 1:
                    continue
                tgt = node.targets[0]
                if (isinstance(tgt, ast.Attribute)
                        and isinstance(tgt.value, ast.Name)
                        and tgt.value.id == "self"
                        and isinstance(node.value, ast.Call)
                        and isinstance(node.value.func, ast.Attribute)
                        and node.value.func.attr == "register"):
                    attrs.append(tgt.attr)
    return attrs


def helper_map(tree: ast.Module):
    """SchedulerMetrics method name → set of metric attrs it mutates."""
    out = {}
    for cls in ast.walk(tree):
        if not (isinstance(cls, ast.ClassDef) and cls.name == "SchedulerMetrics"):
            continue
        for fn in cls.body:
            if not isinstance(fn, ast.FunctionDef) or fn.name == "__init__":
                continue
            touched = set()
            for node in ast.walk(fn):
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr in _MUTATORS
                        and isinstance(node.func.value, ast.Attribute)
                        and isinstance(node.func.value.value, ast.Name)
                        and node.func.value.value.id == "self"):
                    touched.add(node.func.value.attr)
            if touched:
                out[fn.name] = touched
    return out


def find_dead_metrics(pkg: str = None, metrics_file: str = None):
    pkg = pkg or PKG
    metrics_file = metrics_file or METRICS_FILE
    tree = ast.parse(_read(metrics_file))
    attrs = registered_metrics(tree)
    helpers = helper_map(tree)

    outside = []
    for path in _walk_py(pkg):
        if os.path.abspath(path) == os.path.abspath(metrics_file):
            continue
        outside.append(_read(path))
    blob = "\n".join(outside)

    live_helpers = {name for name in helpers
                    if re.search(rf"\.{name}\s*\(", blob)}
    dead = []
    for attr in attrs:
        direct = re.search(rf"\.{attr}\.(?:{'|'.join(_MUTATORS)})\s*\(", blob)
        via_helper = any(attr in helpers[h] for h in live_helpers)
        if not direct and not via_helper:
            dead.append(attr)
    return attrs, dead


@register("metrics", "registered SchedulerMetrics are observed somewhere")
def pass_metrics() -> List[Finding]:
    attrs, dead = find_dead_metrics()
    return [Finding(METRICS_FILE, 0,
                    f"dead metric: {a} is registered but never "
                    "observed/inc'd/set outside its definition")
            for a in dead]


# ======================================================================= spans
# (absorbed from tools/check_metrics.py — the PR-7 span-name lint)

SPAN_IGNORE_PREFIXES = ("framework.", "plugin.")


def _literal_prefix(node):
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value, True
    if isinstance(node, ast.JoinedStr):
        parts = []
        for v in node.values:
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                parts.append(v.value)
            else:
                break
        return ("".join(parts), False) if parts else (None, False)
    if (isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add)
            and isinstance(node.left, ast.Constant)
            and isinstance(node.left.value, str)):
        return node.left.value, False
    return None, False


def emitted_span_names(pkg: str = None):
    names, prefixes = set(), set()
    for path in _walk_py(pkg or PKG):
        try:
            tree = ast.parse(_read(path))
        except SyntaxError:
            continue
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)):
                continue
            arg = None
            if node.func.attr in ("span", "span_remote") and node.args:
                arg = node.args[0]
            elif node.func.attr == "span_from_remote" and len(node.args) >= 2:
                arg = node.args[1]
            elif node.func.attr in ("emit", "emit_deferred") and node.args:
                # tracing.emit(name, start_ns, end_ns): explicit-timestamp
                # finished-span export (dispatch-profiler child spans, and
                # the deferred GC spans)
                arg = node.args[0]
            if arg is None:
                continue
            val, exact = _literal_prefix(arg)
            if val is None:
                continue
            (names if exact else prefixes).add(val)
    return names, prefixes


def bench_span_table(path: str = None):
    tree = ast.parse(_read(path or BENCH_FILE))
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        tgt = node.targets[0]
        if not (isinstance(tgt, ast.Name) and tgt.id == "CRITICAL_PATH_SPANS"):
            continue
        return {n.value for n in ast.walk(node.value)
                if isinstance(n, ast.Constant) and isinstance(n.value, str)}
    return set()


def find_unattributed_spans(pkg: str = None, bench_path: str = None):
    names, prefixes = emitted_span_names(pkg)
    table = bench_span_table(bench_path)
    bad = [n for n in sorted(names)
           if n not in table and not n.startswith(SPAN_IGNORE_PREFIXES)]
    for p in sorted(prefixes):
        if p.startswith(SPAN_IGNORE_PREFIXES):
            continue
        if any(t.startswith(p) for t in table):
            continue
        bad.append(p + "*")
    return sorted(names | prefixes), bad


@register("spans", "emitted span names appear in bench.py's attribution table")
def pass_spans() -> List[Finding]:
    _emitted, bad = find_unattributed_spans()
    return [Finding(BENCH_FILE, 0,
                    f"unattributed span: {n} is emitted but absent from "
                    "CRITICAL_PATH_SPANS and the ignore list")
            for n in bad]


# ====================================================================== events
# Flight-recorder event-kind lint (the span-name lint's twin, PR-7 follow-
# up): every LITERAL kind passed to ``telemetry.event(...)`` or
# ``<...>.flight.record(...)`` inside the package must appear in the
# declared registry frozenset ``backend/telemetry.py EVENT_KINDS`` — a new
# lifecycle event cannot ship without joining the documented vocabulary.

TELEMETRY_FILE = os.path.join(PKG, "backend", "telemetry.py")


def declared_event_kinds(path: str = None) -> Set[str]:
    """The EVENT_KINDS frozenset literal from backend/telemetry.py."""
    tree = ast.parse(_read(path or TELEMETRY_FILE))
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        tgt = node.targets[0]
        if not (isinstance(tgt, ast.Name) and tgt.id == "EVENT_KINDS"):
            continue
        return {c.value for c in ast.walk(node.value)
                if isinstance(c, ast.Constant) and isinstance(c.value, str)}
    return set()


def emitted_event_kinds(pkg: str = None) -> List[Tuple[str, int, str]]:
    """(path, line, kind) for every literal event-kind emission site:
    calls whose attribute is ``event`` (``telemetry.event`` and
    ``DeviceTelemetry.event`` call-throughs) or ``record`` on a
    flight-recorder receiver (``self.flight.record`` / ``flight.record``).
    Non-literal first args (pass-through helpers) are skipped — they
    forward kinds already checked at their own literal call sites."""
    out: List[Tuple[str, int, str]] = []
    for path in _walk_py(pkg or PKG):
        try:
            tree = ast.parse(_read(path))
        except SyntaxError:
            continue
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.args):
                continue
            attr = node.func.attr
            if attr == "record":
                recv = node.func.value
                recv_name = (recv.attr if isinstance(recv, ast.Attribute)
                             else recv.id if isinstance(recv, ast.Name)
                             else "")
                if recv_name != "flight":
                    continue
            elif attr != "event":
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                out.append((path, node.lineno, arg.value))
    return out


def find_undeclared_events(pkg: str = None,
                           telemetry_path: str = None) -> List[Finding]:
    declared = declared_event_kinds(telemetry_path)
    if not declared:
        return [Finding(telemetry_path or TELEMETRY_FILE, 0,
                        "EVENT_KINDS registry frozenset not found — the "
                        "events lint has nothing to check against")]
    return [Finding(path, line,
                    f"undeclared flight-recorder event kind {kind!r}: add "
                    "it to backend/telemetry.py EVENT_KINDS (the declared "
                    "postmortem vocabulary) or rename to a declared kind")
            for path, line, kind in emitted_event_kinds(pkg)
            if kind not in declared]


@register("events", "emitted flight-recorder event kinds are declared in "
                    "telemetry.EVENT_KINDS")
def pass_events() -> List[Finding]:
    return find_undeclared_events()


# ==================================================================== dispatch
# Device-dispatch attribution lint (the events lint's sibling, PR-17): a
# jitted entry point invoked OUTSIDE a ``telemetry.dispatch(...)`` context
# manager produces device time the DispatchLedger can never attribute — it
# shows up as unexplained commit-wait dwell in the waterfall. Two rules:
# every literal program name handed to the dispatch/cost-probe family must
# appear in the declared ``backend/telemetry.py PROGRAM_NAMES`` registry,
# and every call of a discovered jit entry (tools-wide ``_collect_jit_
# functions`` — the same discovery the jit pass trusts) must sit lexically
# under a ``with <...>.dispatch(...)`` block. Exemptions: calls in the
# entry's own defining module (composition inside the profiled boundary —
# batch.py assembling schedule_batch from its cores), calls from inside
# another jit entry (traced composition never blocks on device), and
# reviewed ``# ktpu: dispatch-ok(reason)`` sites.

_DISPATCH_PROGRAM_ATTRS = ("dispatch", "cost_probe", "dispatch_window",
                           "dispatch_phases", "record_window",
                           "record_phases")


def declared_program_names(path: str = None) -> Set[str]:
    """The PROGRAM_NAMES frozenset literal from backend/telemetry.py."""
    tree = ast.parse(_read(path or TELEMETRY_FILE))
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        tgt = node.targets[0]
        if not (isinstance(tgt, ast.Name) and tgt.id == "PROGRAM_NAMES"):
            continue
        return {c.value for c in ast.walk(node.value)
                if isinstance(c, ast.Constant) and isinstance(c.value, str)}
    return set()


def dispatch_program_sites(pkg: str = None) -> List[Tuple[str, int, str]]:
    """(path, line, program) for every literal program name handed to the
    dispatch-attribution family (``telemetry.dispatch`` / ``cost_probe`` /
    ``dispatch_window`` / ``dispatch_phases`` and the DispatchLedger
    ``record_window`` / ``record_phases`` methods). Non-literal first args
    are pass-through helpers, checked at their own literal sites."""
    out: List[Tuple[str, int, str]] = []
    for path in _walk_py(pkg or PKG):
        try:
            tree = ast.parse(_read(path))
        except SyntaxError:
            continue
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _DISPATCH_PROGRAM_ATTRS
                    and node.args):
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                out.append((path, node.lineno, arg.value))
    return out


def _jit_entry_aliases(pkg: str) -> Dict[str, str]:
    """Every name a jit entry is callable under -> its defining file:
    decorated function names plus the assignment targets of
    ``x = jit(f, ...)`` bindings (callers invoke the TARGET name)."""
    aliases: Dict[str, str] = {}
    fns, entries, _sites = _collect_jit_functions(pkg)
    for name in entries:
        info = fns.get(name)
        if info is not None:
            aliases[name] = info.path
    for path in _walk_py(pkg):
        try:
            tree = ast.parse(_read(path))
        except SyntaxError:
            continue
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and isinstance(node.value, ast.Call)):
                continue
            call = node.value
            if (_callable_name(call.func) == "jit" and call.args
                    and isinstance(call.args[0], ast.Name)):
                aliases[node.targets[0].id] = path
    return aliases


def _is_dispatch_with(withnode: ast.With) -> bool:
    for item in withnode.items:
        ctx = item.context_expr
        if (isinstance(ctx, ast.Call) and isinstance(ctx.func, ast.Attribute)
                and ctx.func.attr == "dispatch"):
            return True
    return False


def find_unattributed_dispatches(pkg: str = None,
                                 telemetry_path: str = None) -> List[Finding]:
    pkg = pkg or PKG
    declared = declared_program_names(telemetry_path)
    if not declared:
        return [Finding(telemetry_path or TELEMETRY_FILE, 0,
                        "PROGRAM_NAMES registry frozenset not found — the "
                        "dispatch lint has nothing to check against")]
    findings = [
        Finding(path, line,
                f"undeclared dispatch program {prog!r}: add it to "
                "backend/telemetry.py PROGRAM_NAMES (the declared device-"
                "time attribution vocabulary) or rename to a declared one")
        for path, line, prog in dispatch_program_sites(pkg)
        if prog not in declared]
    aliases = _jit_entry_aliases(pkg)
    entry_names = set(aliases)
    for path in _walk_py(pkg):
        src = _read(path)
        try:
            tree = ast.parse(src)
        except SyntaxError:
            continue
        sup = _Suppressions(src)

        def walk(node, in_dispatch, in_entry):
            if isinstance(node, ast.With) and _is_dispatch_with(node):
                in_dispatch = True
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # a fresh function body is a fresh lexical scope: an
                # enclosing `with dispatch` does NOT cover calls made when
                # the nested function runs later
                in_dispatch = False
                in_entry = node.name in entry_names
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id in entry_names
                    and not in_dispatch and not in_entry
                    and aliases[node.func.id] != path
                    and not sup.silences("dispatch-ok", node.lineno)):
                findings.append(Finding(
                    path, node.lineno,
                    f"unattributed dispatch: jitted entry {node.func.id}() "
                    "called outside 'with telemetry.dispatch(...)' — its "
                    "device time lands in no program's ledger; wrap the "
                    "call or suppress with '# ktpu: dispatch-ok(reason)'"))
            for child in ast.iter_child_nodes(node):
                walk(child, in_dispatch, in_entry)

        walk(tree, False, False)
    return findings


@register("dispatch", "jit-entry calls run under telemetry.dispatch with a "
                      "declared PROGRAM_NAMES program")
def pass_dispatch() -> List[Finding]:
    return find_unattributed_dispatches()


# ===================================================================== markers
# (absorbed from tools/check_markers.py — the PR-4 slow-marker lint)

PERF_SCALE_NODES = 1000
SOAK_SCALE = 16
SOAK_ROUNDS = 16


def _is_slow_mark(node: ast.AST) -> bool:
    if isinstance(node, ast.Call):
        node = node.func
    return (isinstance(node, ast.Attribute) and node.attr == "slow"
            and isinstance(node.value, ast.Attribute)
            and node.value.attr == "mark")


def _has_slow(decorators) -> bool:
    return any(_is_slow_mark(d) for d in decorators)


def _module_marked_slow(tree: ast.Module) -> bool:
    for node in tree.body:
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name) and tgt.id == "pytestmark":
                    for cand in ast.walk(node.value):
                        if _is_slow_mark(cand):
                            return True
    return False


def _test_cases_key(call: ast.Call):
    if not (isinstance(call.func, ast.Subscript)
            and isinstance(call.func.value, ast.Name)
            and call.func.value.id == "TEST_CASES"):
        return None
    sl = call.func.slice
    if isinstance(sl, ast.Constant) and isinstance(sl.value, str):
        return sl.value
    return ""


def _int_kw(call: ast.Call, name: str):
    for k in call.keywords:
        if (k.arg == name and isinstance(k.value, ast.Constant)
                and isinstance(k.value.value, int)):
            return k.value.value
    return None


def _is_perf_scale(fn: ast.FunctionDef) -> bool:
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        kw_names = {k.arg for k in node.keywords}
        for k in node.keywords:
            if (k.arg == "nodes" and isinstance(k.value, ast.Constant)
                    and isinstance(k.value.value, int)
                    and k.value.value >= PERF_SCALE_NODES):
                return True
        key = _test_cases_key(node)
        if key is not None and "nodes" not in kw_names:
            return True
        if key == "SchedulingSoak":
            scale, rounds = _int_kw(node, "scale"), _int_kw(node, "rounds")
            if (scale is None or scale >= SOAK_SCALE
                    or rounds is None or rounds >= SOAK_ROUNDS):
                return True
    return False


def find_unmarked(paths=None) -> List[Tuple[str, int, str]]:
    violations = []
    paths = paths or sorted(
        os.path.join(TESTS, f) for f in os.listdir(TESTS)
        if f.startswith("test_") and f.endswith(".py"))
    for path in paths:
        tree = ast.parse(_read(path))
        if _module_marked_slow(tree):
            continue
        scopes = [(tree.body, False)]
        for cls in tree.body:
            if isinstance(cls, ast.ClassDef):
                scopes.append((cls.body, _has_slow(cls.decorator_list)))
        for body, class_slow in scopes:
            for fn in body:
                if not isinstance(fn, ast.FunctionDef):
                    continue
                if not fn.name.startswith("test_"):
                    continue
                if class_slow or _has_slow(fn.decorator_list):
                    continue
                if _is_perf_scale(fn):
                    violations.append((path, fn.lineno, fn.name))
    return violations


@register("markers", "perf-scale tests carry @pytest.mark.slow")
def pass_markers() -> List[Finding]:
    return [Finding(path, line,
                    f"perf-scale test {name} (>= {PERF_SCALE_NODES} nodes or "
                    "TEST_CASES defaults) lacks @pytest.mark.slow")
            for path, line, name in find_unmarked()]


# =================================================================== pb2 drift
# (absorbed from ``tools/gen_pb2.py --check``)


@register("pb2-drift", "vendored ktpu_device_pb2 matches native/ktpu_device.proto")
def pass_pb2_drift() -> List[Finding]:
    import importlib.util

    tool = os.path.join(REPO, "tools", "gen_pb2.py")
    out_path = os.path.join(PKG, "native", "ktpu_device_pb2.py")
    try:
        spec = importlib.util.spec_from_file_location("_ktpu_gen_pb2", tool)
        gen = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(gen)
        content = gen.generate()
    except ImportError:
        return []  # google.protobuf absent: the vendored module is unusable
        # anyway and the grpc suites skip — nothing to gate
    try:
        current = _read(out_path)
    except OSError:
        return [Finding(out_path, 0, "vendored pb2 module missing; run "
                        "python tools/gen_pb2.py")]
    if current != content:
        return [Finding(out_path, 0, "vendored pb2 module is stale vs "
                        "native/ktpu_device.proto; run python tools/gen_pb2.py")]
    return []


# ======================================================================= locks
# Lock-discipline AST pass: per class, learn which ``self.<x>`` attributes
# are accessed under ``with self.<lock>`` and flag unguarded accesses to the
# same attributes elsewhere in the class.
#
# Scope rules (kept deliberately intraprocedural — no cross-function lock
# state):
#   * a class participates when some method assigns ``self.<name> = Lock()/
#     RLock()/Condition()/locktrace.make_lock()/make_rlock()``;
#   * an attribute is a CANDIDATE when it is (a) accessed at least once
#     inside a with-lock block anywhere in the class AND (b) mutated outside
#     ``__init__`` (rebinding, augmented assignment, ``self.x[k] = / del``,
#     or a mutating method call like ``self.x.pop(...)``) — config fields
#     assigned once at construction are exempt;
#   * guarded contexts: ``with self.<lock>:`` bodies, ``__init__`` (no
#     concurrent aliases exist yet), methods decorated ``@_locked``, and
#     methods whose ``def`` line carries ``# ktpu: locked`` (the reviewed
#     "caller holds the lock" contract);
#   * nested functions/lambdas are UNGUARDED even when defined under the
#     lock (they escape the critical section);
#   * ``# ktpu: unguarded-ok(reason)`` silences one line.

_LOCK_CTOR_NAMES = {"Lock", "RLock", "Condition",
                    "make_lock", "make_rlock", "make_condition"}
_MUTATING_CALLS = {"add", "append", "appendleft", "clear", "discard",
                   "extend", "insert", "pop", "popitem", "popleft", "remove",
                   "setdefault", "update", "sort", "reverse", "notify",
                   "notify_all"}


def _callable_name(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    return None


def _self_attr(node: ast.AST) -> Optional[str]:
    """X for a ``self.X`` attribute node."""
    if (isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name)
            and node.value.id == "self"):
        return node.attr
    return None


class _Access(NamedTuple):
    attr: str
    line: int
    write: bool
    guarded: bool
    method: str
    # True only for rebinding writes (assign/augassign/subscript-store/del):
    # candidacy keys off these — a ``.pop()``/``.append()`` call mutates the
    # CONTENTS (often of a sub-object with its own lock) and stays an access
    # but does not by itself make the attribute lock-owned
    rebind: bool = False


def _scan_class(cls: ast.ClassDef, sup: _Suppressions) -> List[_Access]:
    lock_attrs: Set[str] = set()
    for node in ast.walk(cls):
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            attr = _self_attr(node.targets[0])
            if (attr and isinstance(node.value, ast.Call)
                    and _callable_name(node.value.func) in _LOCK_CTOR_NAMES):
                lock_attrs.add(attr)
    if not lock_attrs:
        return []

    accesses: List[_Access] = []

    def is_lock_with(withnode: ast.With) -> bool:
        for item in withnode.items:
            a = _self_attr(item.context_expr)
            if a in lock_attrs:
                return True
        return False

    def record(attr: Optional[str], node: ast.AST, write: bool,
               guarded: bool, method: str, rebind: bool = False):
        if attr and attr not in lock_attrs:
            accesses.append(_Access(attr, node.lineno, write, guarded,
                                    method, rebind))

    def walk(node: ast.AST, guarded: bool, method: str):
        if isinstance(node, ast.With) and is_lock_with(node):
            for item in node.items:
                walk(item.context_expr, guarded, method)
            for child in node.body:
                walk(child, True, method)
            return
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            # nested defs/lambdas escape the critical section — unless the
            # nested def itself carries the reviewed '# ktpu: locked'
            # contract (e.g. commit closures run by a locked helper)
            nested_locked = (not isinstance(node, ast.Lambda)
                             and (node.lineno in sup.locked_lines
                                  or node.name.endswith("_locked")))
            for child in ast.iter_child_nodes(node):
                walk(child, nested_locked, method)
            return
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                _record_target(tgt, guarded, method)
            walk(node.value, guarded, method)
            return
        if isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            _record_target(node.target, guarded, method)
            if getattr(node, "value", None) is not None:
                walk(node.value, guarded, method)
            return
        if isinstance(node, ast.Delete):
            for tgt in node.targets:
                _record_target(tgt, guarded, method)
            return
        if isinstance(node, ast.Call):
            fn = node.func
            if (isinstance(fn, ast.Attribute)
                    and fn.attr in _MUTATING_CALLS):
                recv = _self_attr(fn.value)
                if recv:
                    record(recv, fn.value, True, guarded, method)
                    for arg in list(node.args) + [k.value for k in node.keywords]:
                        walk(arg, guarded, method)
                    return
            for child in ast.iter_child_nodes(node):
                walk(child, guarded, method)
            return
        attr = _self_attr(node)
        if attr is not None:
            record(attr, node, isinstance(node.ctx, (ast.Store, ast.Del)),
                   guarded, method)
            return
        for child in ast.iter_child_nodes(node):
            walk(child, guarded, method)

    def _record_target(tgt: ast.AST, guarded: bool, method: str):
        attr = _self_attr(tgt)
        if attr is not None:
            record(attr, tgt, True, guarded, method, rebind=True)
            return
        if isinstance(tgt, ast.Subscript):
            attr = _self_attr(tgt.value)
            if attr is not None:
                record(attr, tgt.value, True, guarded, method, rebind=True)
                walk(tgt.slice, guarded, method)
                return
        walk(tgt, guarded, method)

    for fn in cls.body:
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        locked = (
            fn.name == "__init__"
            # the *_locked naming convention IS the caller-holds-the-lock
            # contract this codebase already uses (_clear_unschedulable_
            # locked, _flush_waiting_locked, _drop_service_locked, ...)
            or fn.name.endswith("_locked")
            or fn.lineno in sup.locked_lines
            or any(ln in sup.locked_lines
                   for ln in range(fn.lineno,
                                   (fn.body[0].lineno if fn.body else fn.lineno)))
            or any(_callable_name(d) in ("_locked", "locked")
                   for d in fn.decorator_list))
        for stmt in fn.body:
            walk(stmt, locked, fn.name)
    return accesses


def find_lock_violations(pkg: str = None) -> List[Finding]:
    out: List[Finding] = []
    for path in _walk_py(pkg or PKG):
        src = _read(path)
        if "Lock(" not in src and "make_lock" not in src \
                and "make_rlock" not in src and "Condition(" not in src:
            continue
        try:
            tree = ast.parse(src)
        except SyntaxError:
            continue
        sup = _Suppressions(src)
        for cls in [n for n in ast.walk(tree) if isinstance(n, ast.ClassDef)]:
            accesses = _scan_class(cls, sup)
            if not accesses:
                continue
            # __init__ is exempt from flagging (no concurrent alias exists
            # yet) but is NOT evidence of lock discipline
            guarded_attrs = {a.attr for a in accesses
                             if a.guarded and a.method != "__init__"}
            mutated = {a.attr for a in accesses
                       if a.rebind and a.method != "__init__"}
            candidates = guarded_attrs & mutated
            for a in accesses:
                if a.guarded or a.attr not in candidates:
                    continue
                if sup.silences("unguarded-ok", a.line):
                    continue
                verb = "write to" if a.write else "read of"
                out.append(Finding(
                    path, a.line,
                    f"unguarded {verb} {cls.name}.{a.attr} in {a.method}(): "
                    f"this attribute is accessed under the class lock "
                    f"elsewhere — guard it, mark the method '# ktpu: locked' "
                    f"if callers hold the lock, or suppress with "
                    f"'# ktpu: unguarded-ok(reason)'"))
    return out


@register("locks", "lock-guarded attributes are never accessed unguarded")
def pass_locks() -> List[Finding]:
    return find_lock_violations()


# ========================================================================= jit
# Jit-boundary / device-sync pass.
#
# Discovers the jitted entry points (``@jax.jit``, ``@functools.partial(
# jax.jit, static_argnames=...)``, ``x = jax.jit(f)``), walks the call graph
# over the package's module-level functions, propagates which parameters are
# STATIC (non-traced) through call sites, and flags host syncs and retrace
# hazards inside the traced region:
#
#   J1  int()/float()/bool() of a traced value      (implicit device sync)
#   J2  .item() on a traced value                   (implicit device sync)
#   J3  np.asarray()/np.array()/... of a traced value (host materialization)
#   J4  Python if/while/ternary on a traced value   (ConcretizationError —
#       or worse, a silent retrace per distinct value via static fallback)
#   J5  unhashable (list/dict/set) defaults for declared static args
#
# Shape/metadata access is static (``x.shape[0]``, ``x.ndim``, ``len(x)``),
# ``is (not) None`` tests are fine (tracers are never None), and values
# derived only from static parameters stay static. Suppress one line with
# ``# ktpu: host-sync-ok(reason)``.

_SHAPE_ATTRS = {"shape", "ndim", "size", "dtype", "nbytes"}
_NP_HOST_FNS = {"asarray", "array", "ascontiguousarray", "copy", "frombuffer",
                "save", "tolist"}


class _FnInfo(NamedTuple):
    path: str
    module: str          # module basename, e.g. "batch"
    node: ast.FunctionDef
    params: Tuple[str, ...]
    imports: Dict[str, str]   # local alias -> module basename (or func name)


def _param_names(fn: ast.FunctionDef) -> Tuple[str, ...]:
    a = fn.args
    names = [p.arg for p in getattr(a, "posonlyargs", [])] + \
            [p.arg for p in a.args] + [p.arg for p in a.kwonlyargs]
    return tuple(names)


def _module_imports(tree: ast.Module) -> Dict[str, str]:
    """alias -> imported module basename or imported function name."""
    out: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for al in node.names:
                out[al.asname or al.name.split(".")[0]] = \
                    al.name.rsplit(".", 1)[-1]
        elif isinstance(node, ast.ImportFrom):
            for al in node.names:
                out[al.asname or al.name] = al.name
    return out


def _jit_static_names(dec: ast.AST) -> Optional[Set[str]]:
    """The static_argnames set when ``dec`` is a jit decorator, else None."""
    if isinstance(dec, ast.Attribute) and dec.attr == "jit":
        return set()
    if isinstance(dec, ast.Name) and dec.id == "jit":
        return set()
    if isinstance(dec, ast.Call):
        fname = _callable_name(dec.func)
        if fname == "jit":
            inner = None
        elif fname == "partial":
            if not (dec.args and _callable_name(dec.args[0]) == "jit"):
                return None
            inner = dec
        else:
            return None
        statics: Set[str] = set()
        for kw in (inner or dec).keywords:
            if kw.arg in ("static_argnames", "static_argnums"):
                for c in ast.walk(kw.value):
                    if isinstance(c, ast.Constant) and isinstance(c.value, str):
                        statics.add(c.value)
        return statics
    return None


def _collect_jit_functions(pkg: str):
    """(functions by name, entry -> static names, jit decl sites)."""
    fns: Dict[str, _FnInfo] = {}
    entries: Dict[str, Set[str]] = {}
    jit_sites: List[Tuple[str, ast.AST, Set[str], str]] = []
    for path in _walk_py(pkg):
        try:
            tree = ast.parse(_read(path))
        except SyntaxError:
            continue
        module = os.path.splitext(os.path.basename(path))[0]
        imports = _module_imports(tree)
        for node in tree.body:
            if isinstance(node, ast.FunctionDef):
                # first definition wins on name collision; module-level only
                fns.setdefault(node.name, _FnInfo(
                    path, module, node, _param_names(node), imports))
                for dec in node.decorator_list:
                    statics = _jit_static_names(dec)
                    if statics is not None:
                        entries[node.name] = statics
                        jit_sites.append((path, node, statics, node.name))
            elif isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                call = node.value
                if (_callable_name(call.func) == "jit" and call.args
                        and isinstance(call.args[0], ast.Name)):
                    statics = {c.value for kw in call.keywords
                               if kw.arg in ("static_argnames",)
                               for c in ast.walk(kw.value)
                               if isinstance(c, ast.Constant)
                               and isinstance(c.value, str)}
                    entries[call.args[0].id] = statics
                    jit_sites.append((path, call, statics, call.args[0].id))
    return fns, entries, jit_sites


def _expr_names(node: ast.AST) -> Set[str]:
    """Name leaves that could carry tracedness: prunes static subtrees
    (shape/dtype metadata, len(), ``x is None`` operands are NOT pruned
    here — branch rule handles those)."""
    out: Set[str] = set()

    def rec(n: ast.AST):
        if isinstance(n, ast.Attribute):
            if n.attr in _SHAPE_ATTRS:
                return  # metadata: static regardless of the base
            rec(n.value)
            return
        if isinstance(n, ast.Compare) and all(
                isinstance(op, (ast.Is, ast.IsNot)) for op in n.ops):
            return  # identity tests yield host bools (tracers aren't None)
        if isinstance(n, ast.Call):
            fname = _callable_name(n.func)
            if fname in ("len", "isinstance", "getattr", "hasattr", "type"):
                return  # static metadata/introspection
            for child in ast.iter_child_nodes(n):
                rec(child)
            return
        if isinstance(n, ast.Name):
            out.add(n.id)
            return
        for child in ast.iter_child_nodes(n):
            rec(child)

    rec(node)
    return out


def _strip_none_tests(node: ast.AST) -> List[ast.AST]:
    """Operands of a branch test that remain relevant after dropping
    ``x is None`` / ``x is not None`` comparisons."""
    if isinstance(node, ast.BoolOp):
        out = []
        for v in node.values:
            out.extend(_strip_none_tests(v))
        return out
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.Not):
        return _strip_none_tests(node.operand)
    if isinstance(node, ast.Compare):
        if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
            return []
        return [node]
    return [node]


class _TracedScan:
    """Per-function traced-name flow + rule application."""

    def __init__(self, info: _FnInfo, traced_params: Set[str],
                 sup: _Suppressions, findings: List[Finding],
                 np_aliases: Set[str]):
        self.info = info
        self.traced: Set[str] = set(traced_params)
        self.sup = sup
        self.findings = findings
        self.np_aliases = np_aliases
        self.calls: List[ast.Call] = []

    def is_traced(self, node: ast.AST) -> bool:
        return bool(_expr_names(node) & self.traced)

    def flag(self, node: ast.AST, msg: str):
        if self.sup.silences("host-sync-ok", node.lineno,
                             getattr(node, "end_lineno", node.lineno)):
            return
        self.findings.append(Finding(
            self.info.path, node.lineno,
            f"{msg} in traced function {self.info.node.name}() — "
            "suppress with '# ktpu: host-sync-ok(reason)' if reviewed"))

    def run(self):
        # two passes so later-defined helpers feeding earlier names settle
        for _ in range(2):
            for node in ast.walk(self.info.node):
                self._propagate(node)
        for node in ast.walk(self.info.node):
            self._apply_rules(node)

    def _propagate(self, node: ast.AST):
        if isinstance(node, ast.Assign):
            if self.is_traced(node.value):
                for tgt in node.targets:
                    for n in ast.walk(tgt):
                        if isinstance(n, ast.Name):
                            self.traced.add(n.id)
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            if getattr(node, "value", None) is not None \
                    and self.is_traced(node.value) \
                    and isinstance(node.target, ast.Name):
                self.traced.add(node.target.id)
        elif isinstance(node, (ast.For,)):
            if self.is_traced(node.iter):
                for n in ast.walk(node.target):
                    if isinstance(n, ast.Name):
                        self.traced.add(n.id)
        elif isinstance(node, ast.NamedExpr):
            if self.is_traced(node.value) and isinstance(node.target, ast.Name):
                self.traced.add(node.target.id)
        elif isinstance(node, (ast.FunctionDef, ast.Lambda)):
            # nested function (scan body / vmapped inner): its params are
            # traced operands
            if node is not self.info.node:
                a = node.args
                for p in list(getattr(a, "posonlyargs", [])) + list(a.args) \
                        + list(a.kwonlyargs):
                    self.traced.add(p.arg)

    def _apply_rules(self, node: ast.AST):
        if isinstance(node, ast.Call):
            self.calls.append(node)
            fname = _callable_name(node.func)
            if (isinstance(node.func, ast.Name)
                    and fname in ("int", "float", "bool", "complex")
                    and node.args and self.is_traced(node.args[0])):
                self.flag(node, f"{fname}() on a traced value forces a "
                          "blocking device sync")
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr == "item"
                  and self.is_traced(node.func.value)):
                self.flag(node, ".item() on a traced value forces a "
                          "blocking device sync")
            elif (isinstance(node.func, ast.Attribute)
                  and isinstance(node.func.value, ast.Name)
                  and node.func.value.id in self.np_aliases
                  and node.func.attr in _NP_HOST_FNS
                  and node.args and self.is_traced(node.args[0])):
                self.flag(node, f"np.{node.func.attr}() on a traced value "
                          "materializes it on host")
        elif isinstance(node, (ast.If, ast.While)):
            for operand in _strip_none_tests(node.test):
                if self.is_traced(operand):
                    self.flag(node, "Python branch on a traced value (use "
                              "jnp.where/lax.cond, or make the input a "
                              "static arg)")
                    break
        elif isinstance(node, ast.IfExp):
            for operand in _strip_none_tests(node.test):
                if self.is_traced(operand):
                    self.flag(node, "Python conditional expression on a "
                              "traced value (use jnp.where)")
                    break
        elif isinstance(node, ast.Assert):
            for operand in _strip_none_tests(node.test):
                if self.is_traced(operand):
                    self.flag(node, "assert on a traced value forces a "
                              "blocking device sync")
                    break


def find_jit_violations(pkg: str = None) -> List[Finding]:
    pkg = pkg or PKG
    fns, entries, jit_sites = _collect_jit_functions(pkg)
    findings: List[Finding] = []
    src_cache: Dict[str, _Suppressions] = {}
    np_alias_cache: Dict[str, Set[str]] = {}

    def sup_for(path: str) -> _Suppressions:
        if path not in src_cache:
            src_cache[path] = _Suppressions(_read(path))
        return src_cache[path]

    def np_aliases_for(path: str) -> Set[str]:
        if path not in np_alias_cache:
            aliases = {"np", "numpy"}
            try:
                tree = ast.parse(_read(path))
                for node in ast.walk(tree):
                    if isinstance(node, ast.Import):
                        for al in node.names:
                            if al.name == "numpy":
                                aliases.add(al.asname or "numpy")
            except SyntaxError:
                pass
            np_alias_cache[path] = aliases
        return np_alias_cache[path]

    # J5: unhashable defaults for declared static args, at the jit site
    for path, node, statics, name in jit_sites:
        info = fns.get(name)
        if info is None or not statics:
            continue
        fn = info.node
        a = fn.args
        pos = list(getattr(a, "posonlyargs", [])) + list(a.args)
        defaults = list(a.defaults)
        pairs = list(zip(pos[len(pos) - len(defaults):], defaults)) + [
            (p, d) for p, d in zip(a.kwonlyargs, a.kw_defaults) if d is not None]
        for p, d in pairs:
            if p.arg in statics and isinstance(d, (ast.List, ast.Dict,
                                                   ast.Set)):
                sup = sup_for(info.path)
                if not sup.silences("host-sync-ok", d.lineno):
                    findings.append(Finding(
                        info.path, d.lineno,
                        f"static arg {p.arg!r} of jitted {name}() defaults "
                        "to an unhashable literal — jit static args must "
                        "hash (use a tuple)"))

    # traced-param fixed point over the call graph
    traced_params: Dict[str, Set[str]] = {}
    for name, statics in entries.items():
        info = fns.get(name)
        if info is None:
            continue
        traced_params[name] = {p for p in info.params if p not in statics}

    for _ in range(12):  # bounded fixed point
        changed = False
        scans: Dict[str, _TracedScan] = {}
        for name, tp in list(traced_params.items()):
            info = fns.get(name)
            if info is None:
                continue
            scan = _TracedScan(info, tp, sup_for(info.path), [],
                               np_aliases_for(info.path))
            scan.run()
            scans[name] = scan
            for call in scan.calls:
                callee = _callable_name(call.func)
                # resolve `from x import f` aliasing and module-attr calls
                target = None
                if isinstance(call.func, ast.Name) and callee in fns:
                    target = callee
                elif isinstance(call.func, ast.Attribute) and \
                        isinstance(call.func.value, ast.Name):
                    mod_alias = call.func.value.id
                    mod = info.imports.get(mod_alias)
                    if mod is not None and callee in fns \
                            and fns[callee].module == mod:
                        target = callee
                if target is None:
                    continue
                if target in entries and target != name:
                    # a nested call into another jit entry: that entry's
                    # declared static_argnames are authoritative — caller
                    # tracedness must not overwrite its static surface
                    continue
                tinfo = fns[target]
                tparams = traced_params.setdefault(target, set())
                before = len(tparams)
                for i, arg in enumerate(call.args):
                    if i < len(tinfo.params) and scan.is_traced(arg):
                        tparams.add(tinfo.params[i])
                for kw in call.keywords:
                    if kw.arg in tinfo.params and scan.is_traced(kw.value):
                        tparams.add(kw.arg)
                if len(tparams) != before:
                    changed = True
        if not changed:
            break

    # final scan with settled traced sets
    for name, tp in traced_params.items():
        info = fns.get(name)
        if info is None:
            continue
        scan = _TracedScan(info, tp, sup_for(info.path), findings,
                           np_aliases_for(info.path))
        scan.run()
    return findings


@register("jit", "no host syncs / retrace hazards reachable from jitted entries")
def pass_jit() -> List[Finding]:
    return find_jit_violations()


# ====================================================================== errors
# Error-taxonomy pass over backend/: raises use the typed taxonomy; broad
# ``except Exception`` handlers reclassify into it or carry a reviewed
# justification comment.

_UNTYPED_RAISES = {"RuntimeError", "Exception", "BaseException", "OSError",
                   "IOError", "SystemError", "StandardError"}
_TAXONOMY = {"DeviceServiceError", "TransientDeviceError",
             "PermanentDeviceError", "StaleEpochError", "ConflictError",
             "CapacityError"}


def _handler_reclassifies(handler: ast.ExceptHandler) -> bool:
    for node in ast.walk(handler):
        if isinstance(node, ast.Raise):
            if node.exc is None:
                return True  # bare re-raise: the original type propagates
            name = _callable_name(node.exc if not isinstance(node.exc, ast.Call)
                                  else node.exc.func)
            if name in _TAXONOMY:
                return True
    return False


def _line_has_justification(src_lines: List[str], lineno: int) -> bool:
    """True when the except line carries an explanatory comment — either a
    ``# ktpu: broad-except-ok(reason)`` marker or a prose comment with
    content beyond a bare lint pragma (the established
    ``# noqa: BLE001 — reason`` idiom)."""
    line = src_lines[lineno - 1] if lineno - 1 < len(src_lines) else ""
    if "#" not in line:
        return False
    comment = line.split("#", 1)[1]
    m = _SUPPRESS_RE.search(line)
    if m:
        return m.group(1) == "broad-except-ok" and bool(m.group(2).strip())
    stripped = re.sub(r"noqa(:\s*[\w,]+)?", "", comment)
    stripped = stripped.strip(" #—-:\t")
    return bool(stripped)


def find_error_violations(backend: str = None) -> List[Finding]:
    backend = backend or os.path.join(PKG, "backend")
    out: List[Finding] = []
    for path in _walk_py(backend):
        src = _read(path)
        try:
            tree = ast.parse(src)
        except SyntaxError:
            continue
        sup = _Suppressions(src)
        src_lines = src.splitlines()
        for node in ast.walk(tree):
            if isinstance(node, ast.Raise) and node.exc is not None:
                exc = node.exc
                name = _callable_name(exc.func) if isinstance(exc, ast.Call) \
                    else None
                if name in _UNTYPED_RAISES:
                    if not sup.silences("taxonomy-ok", node.lineno,
                                        getattr(node, "end_lineno",
                                                node.lineno)):
                        out.append(Finding(
                            path, node.lineno,
                            f"untyped raise {name}(...) on the device path — "
                            "use the backend/errors.py taxonomy (Transient/"
                            "Permanent/StaleEpoch/Conflict) or suppress with "
                            "'# ktpu: taxonomy-ok(reason)'"))
            elif isinstance(node, ast.ExceptHandler):
                broad = (node.type is None
                         or (isinstance(node.type, ast.Name)
                             and node.type.id in ("Exception", "BaseException")))
                if not broad:
                    continue
                if _handler_reclassifies(node):
                    continue
                if _line_has_justification(src_lines, node.lineno):
                    continue
                out.append(Finding(
                    path, node.lineno,
                    "broad 'except Exception' without reclassification into "
                    "the typed taxonomy or a justification comment "
                    "('# reason' / '# ktpu: broad-except-ok(reason)')"))
    return out


@register("errors", "backend/ raises are typed; broad excepts justify themselves")
def pass_errors() -> List[Finding]:
    return find_error_violations()


# ========================================================================= CLI


def run_passes(names: Sequence[str]) -> Dict[str, List[Finding]]:
    results: Dict[str, List[Finding]] = {}
    for name in names:
        fn, _desc = PASSES[name]
        results[name] = fn()
    return results


def main(argv: Optional[Sequence[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    as_json = "--json" in argv
    argv = [a for a in argv if a != "--json"]
    if "--list" in argv:
        for name, (_fn, desc) in PASSES.items():
            print(f"{name:12s} {desc}")
        return 0
    names: List[str] = []
    if "--all" in argv:
        names = list(PASSES)
    i = 0
    while i < len(argv):
        if argv[i] == "--pass":
            if i + 1 >= len(argv) or argv[i + 1] not in PASSES:
                print(f"usage: --pass <{'|'.join(PASSES)}>", file=sys.stderr)
                return 2
            names.append(argv[i + 1])
            i += 2
        elif argv[i] == "--all":
            i += 1
        else:
            print(f"unknown argument {argv[i]!r} "
                  "(try --all, --pass NAME, --list, --json)", file=sys.stderr)
            return 2
    if not names:
        names = list(PASSES)
    seen = set()
    names = [n for n in names if not (n in seen or seen.add(n))]

    results = run_passes(names)
    total = sum(len(v) for v in results.values())
    if as_json:
        print(json.dumps({
            "passes": {
                name: {
                    "findings": [
                        {"path": os.path.relpath(f.path, REPO)
                         if os.path.isabs(f.path) else f.path,
                         "line": f.line, "message": f.message}
                        for f in findings],
                    "count": len(findings),
                } for name, findings in results.items()},
            "total": total,
        }, indent=2))
        return 1 if total else 0
    for name, findings in results.items():
        if findings:
            print(f"FAIL {name} ({len(findings)}):")
            for f in findings:
                print(f"  - {f.render()}")
        else:
            print(f"ok   {name}: clean")
    if total:
        print(f"\n{total} finding(s) across "
              f"{sum(1 for v in results.values() if v)} pass(es)")
    return 1 if total else 0


if __name__ == "__main__":
    raise SystemExit(main())
