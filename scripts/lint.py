#!/usr/bin/env python
"""Dependency-free lint gate: the fallback for containers without ruff.

Enforces the core of the ruff.toml rule set with only the stdlib:

- E9:   files must parse (`compile()`; a broken file must never merge);
- F401: unused imports (respects `# noqa` / `# noqa: F401` on the
        import line; `__init__.py` re-export facades are exempt, and
        `__graft_entry__.py`-style underscore names are kept);
- F811: an import name rebound by a later import in the same scope;
- F821: undefined names AT MODULE LEVEL (function bodies are scoped
        territory ruff handles; the module-level subset is where a
        broken refactor leaves a dangling reference that only fires
        at import time on someone else's machine);
- F841: locals assigned but never read inside a function, with the
        conservative exemptions ruff defaults to (underscore names,
        tuple unpacking, augmented assigns, `locals()`/`exec` users);
- M001-M003: metric naming (repo-local, AST-scoped to the
        observability registry call sites `.counter(` / `.gauge(` /
        `.histogram(` / `count_metric(` / `observe_metric(` with a
        constant name): counters must end `_total`, histograms must
        carry a unit suffix (`_ms`/`_us`/`_s`/`_seconds`/`_bytes`/
        `_tokens`/`_pages`), gauges must NOT end `_total`.
        Non-constant names (f-string fan-outs like
        `f"serving_kvtier_{k}"`) are out of a static linter's reach
        and skipped.
- W001: direct wall-clock reads (`time.time()` / `time.monotonic()` /
        `datetime.now()` / `datetime.utcnow()`) inside the serving
        and observability trees.  Those layers are driven by
        injectable clocks (`now` parameters, `clock=` seams) so that
        replay, chaos tests and the protocol model checker can run
        them deterministically; a raw clock read bypasses every one
        of those seams.  Deliberate reads (export timestamps, log
        wall-stamps) carry `# noqa: W001` with a justification.

Usage:  python scripts/lint.py [paths...]     (default: repo tree)
Exit 0 = clean, 1 = findings.  Tier-1 runs it as a test
(`tests/test_tooling.py::test_static_gate_passes[lint]`), so the
gate holds in every container, with or without ruff.
"""

from __future__ import annotations

import ast
import pathlib
import re
import sys

EXCLUDE_PARTS = {"__pycache__", ".git", "csrc"}
NOQA_RE = re.compile(r"#\s*noqa(?::\s*(?P<codes>[A-Z0-9, ]+))?", re.I)


def _noqa(lines, lineno: int, code: str) -> bool:
    try:
        m = NOQA_RE.search(lines[lineno - 1])
    except IndexError:
        return False
    if not m:
        return False
    codes = m.group("codes")
    if codes is None:
        return True        # bare noqa silences everything
    return code in {c.strip() for c in codes.split(",")}


class _Imports:
    """Module-TOP-LEVEL import bindings plus all name usage anywhere.

    Function-local imports are deliberately out of scope: the
    codebase's lazy-import idiom re-imports the same name in many
    functions, which a scope-blind checker would misread as F811.
    Imports under top-level `if`/`try` are conditional by design and
    exempt too.  Ruff (when installed) checks the full scoped rules.
    """

    def __init__(self, tree: ast.Module):
        self.imports = {}     # name -> lineno of the binding
        self.rebound = []     # (name, first_lineno, again_lineno)
        self.used = set()
        for node in tree.body:
            if isinstance(node, ast.Import):
                for alias in node.names:
                    self._bind(alias.asname or alias.name.split(".")[0],
                               node.lineno)
            elif isinstance(node, ast.ImportFrom):
                if node.module == "__future__":
                    continue
                for alias in node.names:
                    if alias.name != "*":
                        self._bind(alias.asname or alias.name,
                                   node.lineno)
        for node in ast.walk(tree):
            if isinstance(node, ast.Name) and isinstance(node.ctx,
                                                         ast.Load):
                self.used.add(node.id)

    def _bind(self, name: str, lineno: int):
        if name in self.imports:
            self.rebound.append((name, self.imports[name], lineno))
        self.imports[name] = lineno


def lint_file(path: pathlib.Path) -> list[str]:
    src = path.read_text()
    problems = []
    try:
        tree = ast.parse(src, filename=str(path))
    except SyntaxError as e:
        return [f"{path}:{e.lineno}: E999 {e.msg}"]

    lines = src.splitlines()
    v = _Imports(tree)

    # Names listed in __all__ count as used (and ONLY those strings —
    # treating every string constant as a usage would silently miss
    # unused imports that ruff flags, diverging the two gates).
    for node in tree.body:
        targets = (node.targets if isinstance(node, ast.Assign)
                   else [node.target] if isinstance(node, ast.AugAssign)
                   else [])
        if any(isinstance(t, ast.Name) and t.id == "__all__"
               for t in targets):
            for c in ast.walk(node):
                if isinstance(c, ast.Constant) and isinstance(c.value,
                                                              str):
                    v.used.add(c.value)

    # F401 exemption for re-export facades mirrors ruff.toml's
    # per-file-ignores exactly: __init__.py skips F401 only — F811
    # still applies there.
    if path.name != "__init__.py":
        for name, lineno in sorted(v.imports.items(),
                                   key=lambda p: p[1]):
            if name.startswith("_"):
                continue
            if name in v.used:
                continue
            if _noqa(lines, lineno, "F401"):
                continue
            problems.append(
                f"{path}:{lineno}: F401 `{name}` imported but unused")

    for name, first, again in v.rebound:
        if _noqa(lines, again, "F811"):
            continue
        problems.append(
            f"{path}:{again}: F811 import `{name}` shadows the import "
            f"on line {first}")

    problems.extend(_f821_module_level(tree, path, lines))
    problems.extend(_f841_unused_locals(tree, path, lines))
    problems.extend(_metric_names(tree, path, lines))
    problems.extend(_wallclock_reads(tree, path, lines))
    return problems


# ---------------------------------------------------------------------------
# W001: wall-clock reads in clock-injected layers
# ---------------------------------------------------------------------------

#: Path fragments naming the layers whose code must take time as a
#: parameter (every public entry point threads `now`): a raw clock
#: read there silently forks simulated time from wall time and breaks
#: replay determinism — the exact bug class the incident recorder and
#: the protocol model checker exist to rule out.
_WALLCLOCK_SCOPES = (
    ("triton_distributed_tpu", "serving"),
    ("triton_distributed_tpu", "observability"),
)

#: (module-ish receiver, attribute) pairs that read the wall clock.
#: `time.perf_counter` is excluded: the codebase uses it only for
#: self-timing spans whose durations are reported, never fed back
#: into protocol state.
_WALLCLOCK_ATTRS = {
    ("time", "time"),
    ("time", "monotonic"),
    ("datetime", "now"),
    ("datetime", "utcnow"),
}


def _in_wallclock_scope(path: pathlib.Path) -> bool:
    parts = tuple(path.parts)
    for scope in _WALLCLOCK_SCOPES:
        for i in range(len(parts) - len(scope) + 1):
            if parts[i:i + len(scope)] == scope:
                return True
    return False


def _wallclock_reads(tree: ast.Module, path, lines) -> list[str]:
    """Direct clock reads where the architecture says time is an
    argument.  Receiver matching is name-based (`time.time()`,
    `datetime.now()`, `datetime.datetime.now()`) — aliased imports
    (`from time import time`) don't occur in-tree and a scope-blind
    fallback shouldn't guess at them."""
    if not _in_wallclock_scope(pathlib.Path(str(path))):
        return []
    problems = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if not isinstance(fn, ast.Attribute):
            continue
        recv = fn.value
        # `time.time()` / `datetime.now()` and the spelled-out
        # `datetime.datetime.now()`.
        recv_name = (recv.id if isinstance(recv, ast.Name)
                     else recv.attr if isinstance(recv, ast.Attribute)
                     else None)
        if (recv_name, fn.attr) not in _WALLCLOCK_ATTRS:
            continue
        if _noqa(lines, node.lineno, "W001"):
            continue
        problems.append(
            f"{path}:{node.lineno}: W001 wall-clock read "
            f"`{recv_name}.{fn.attr}()` in a clock-injected layer "
            f"(thread `now` through, or `# noqa: W001` with why)")
    return problems


# ---------------------------------------------------------------------------
# M001-M003: metric naming at registry call sites
# ---------------------------------------------------------------------------

#: Unit suffixes a histogram name must end in — a histogram without a
#: unit is unreadable on a dashboard (what is `accept_len` 3 OF?).
METRIC_UNIT_SUFFIXES = ("_ms", "_us", "_s", "_seconds", "_bytes",
                       "_tokens", "_pages")

#: Method/function name -> metric kind, for call sites whose first
#: argument is a string constant.
_METRIC_CALLS = {
    "counter": "counter",
    "count_metric": "counter",
    "gauge": "gauge",
    "histogram": "histogram",
    "observe_metric": "histogram",
}


def _metric_names(tree: ast.Module, path, lines) -> list[str]:
    """Prometheus-style naming, enforced where metrics are BORN (the
    registry call site) so a misnamed series never reaches a
    dashboard: counters end `_total` (M001), histograms end in a
    unit suffix (M002), gauges never end `_total` (M003 — a gauge
    named like a counter lies about its semantics)."""
    problems = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        fn = node.func
        callee = (fn.attr if isinstance(fn, ast.Attribute)
                  else fn.id if isinstance(fn, ast.Name) else None)
        kind = _METRIC_CALLS.get(callee)
        if kind is None:
            continue
        arg = node.args[0]
        if not (isinstance(arg, ast.Constant)
                and isinstance(arg.value, str)):
            continue      # f-string fan-outs: not statically checkable
        name = arg.value
        if not re.fullmatch(r"[a-z][a-z0-9_]*", name):
            continue      # label keys etc. piped through helpers
        lineno = node.lineno
        if kind == "counter" and not name.endswith("_total"):
            if not _noqa(lines, lineno, "M001"):
                problems.append(
                    f"{path}:{lineno}: M001 counter `{name}` must "
                    f"end in `_total`")
        elif kind == "histogram" and not name.endswith(
                METRIC_UNIT_SUFFIXES):
            if not _noqa(lines, lineno, "M002"):
                problems.append(
                    f"{path}:{lineno}: M002 histogram `{name}` must "
                    f"end in a unit suffix "
                    f"({'/'.join(METRIC_UNIT_SUFFIXES)})")
        elif kind == "gauge" and name.endswith("_total"):
            if not _noqa(lines, lineno, "M003"):
                problems.append(
                    f"{path}:{lineno}: M003 gauge `{name}` must not "
                    f"end in `_total` (counter naming on a gauge)")
    return problems


# ---------------------------------------------------------------------------
# F821: undefined names at module level
# ---------------------------------------------------------------------------

#: Names the import machinery defines in every module namespace.
_MODULE_DUNDERS = {
    "__name__", "__file__", "__doc__", "__package__", "__spec__",
    "__loader__", "__builtins__", "__annotations__", "__path__",
    "__all__", "__version__",
}


def _bound_names(node) -> set:
    """Every name a statement (and its nested scopes' HEADERS) binds
    into the enclosing namespace."""
    out = set()

    def target_names(t):
        for n in ast.walk(t):
            if isinstance(n, ast.Name):
                out.add(n.id)

    if isinstance(node, (ast.Import, ast.ImportFrom)):
        for alias in node.names:
            if alias.name == "*":
                continue
            out.add(alias.asname or alias.name.split(".")[0])
    elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                           ast.ClassDef)):
        out.add(node.name)
    elif isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
        for t in getattr(node, "targets", None) or [node.target]:
            target_names(t)
    elif isinstance(node, (ast.For, ast.AsyncFor)):
        target_names(node.target)
    elif isinstance(node, (ast.With, ast.AsyncWith)):
        for item in node.items:
            if item.optional_vars is not None:
                target_names(item.optional_vars)
    elif isinstance(node, ast.ExceptHandler) and node.name:
        out.add(node.name)
    elif isinstance(node, ast.Global):
        out.update(node.names)
    return out


def _f821_module_level(tree: ast.Module, path, lines) -> list[str]:
    """Undefined names in code executed at module scope.  Order-blind
    on purpose (all module bindings count, wherever they appear):
    misses use-before-def but never false-positives on forward
    references, which is the right trade for a fallback gate."""
    import builtins

    defined = set(dir(builtins)) | set(_MODULE_DUNDERS)

    def collect(body):
        for node in body:
            defined.update(_bound_names(node))
            # Recurse into module-level control flow, but never into
            # function/class bodies (their scopes are ruff's job; a
            # class body's bindings aren't module names anyway).
            if isinstance(node, (ast.If, ast.For, ast.AsyncFor,
                                 ast.While, ast.With, ast.AsyncWith,
                                 ast.Try)):
                for field in ("body", "orelse", "finalbody",
                              "handlers"):
                    for child in getattr(node, field, []) or []:
                        if isinstance(child, ast.ExceptHandler):
                            defined.update(_bound_names(child))
                            collect(child.body)
                        else:
                            collect([child])

    collect(tree.body)

    problems = []
    seen = set()

    def scan_expr(node):
        """Loads in a module-level expression; comprehension/lambda
        locals are tracked as an extra defined set."""
        extra = set()
        for n in ast.walk(node):
            if isinstance(n, (ast.ListComp, ast.SetComp, ast.DictComp,
                              ast.GeneratorExp)):
                for gen in n.generators:
                    for t in ast.walk(gen.target):
                        if isinstance(t, ast.Name):
                            extra.add(t.id)
            elif isinstance(n, ast.Lambda):
                a = n.args
                for arg in (a.posonlyargs + a.args + a.kwonlyargs
                            + ([a.vararg] if a.vararg else [])
                            + ([a.kwarg] if a.kwarg else [])):
                    extra.add(arg.arg)
            elif isinstance(n, ast.NamedExpr):
                if isinstance(n.target, ast.Name):
                    extra.add(n.target.id)
        for n in ast.walk(node):
            if (isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)
                    and n.id not in defined and n.id not in extra
                    and n.id not in seen):
                if _noqa(lines, n.lineno, "F821"):
                    continue
                seen.add(n.id)
                problems.append(
                    f"{path}:{n.lineno}: F821 undefined name `{n.id}` "
                    f"at module level")

    def scan(body):
        for node in body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                for dec in node.decorator_list:
                    scan_expr(dec)
                continue  # inner scopes are out of the fallback's net
            if isinstance(node, (ast.If, ast.While)):
                scan_expr(node.test)
                scan(node.body)
                scan(node.orelse)
            elif isinstance(node, (ast.For, ast.AsyncFor)):
                scan_expr(node.iter)
                scan(node.body)
                scan(node.orelse)
            elif isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    scan_expr(item.context_expr)
                scan(node.body)
            elif isinstance(node, ast.Try):
                scan(node.body)
                for h in node.handlers:
                    if h.type is not None:
                        scan_expr(h.type)
                    scan(h.body)
                scan(node.orelse)
                scan(node.finalbody)
            elif isinstance(node, (ast.Import, ast.ImportFrom,
                                   ast.Global, ast.Nonlocal)):
                continue
            else:
                scan_expr(node)

    scan(tree.body)
    return problems


# ---------------------------------------------------------------------------
# F841: locals assigned but never used (function scope)
# ---------------------------------------------------------------------------

def _f841_unused_locals(tree: ast.Module, path, lines) -> list[str]:
    problems = []
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        # `locals()` / `exec` / `eval` make any name observable.
        dynamic = any(
            isinstance(n, ast.Name) and n.id in ("locals", "exec",
                                                 "eval", "vars")
            for n in ast.walk(fn))
        if dynamic:
            continue
        declared = set()
        for n in ast.walk(fn):
            if isinstance(n, (ast.Global, ast.Nonlocal)):
                declared.update(n.names)
        # Loads (and deletes) anywhere in the function subtree count
        # as uses — including closures reading from nested defs.
        used = {n.id for n in ast.walk(fn)
                if isinstance(n, ast.Name)
                and isinstance(n.ctx, (ast.Load, ast.Del))}
        # Collect assignments from THIS function's scope only: nested
        # defs are their own walk targets and class bodies bind class
        # attributes, not locals.
        scope_nodes = []
        stack = list(fn.body)
        while stack:
            n = stack.pop()
            scope_nodes.append(n)
            if not isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef, ast.Lambda)):
                stack.extend(ast.iter_child_nodes(n))
        assigns = {}           # name -> first assignment lineno
        for n in scope_nodes:
            # Only simple single-Name targets: tuple unpacking and
            # attribute/subscript targets are exempt (ruff default).
            if isinstance(n, ast.Assign) and len(n.targets) == 1 \
                    and isinstance(n.targets[0], ast.Name):
                name = n.targets[0].id
            elif isinstance(n, ast.AnnAssign) and n.value is not None \
                    and isinstance(n.target, ast.Name):
                name = n.target.id
            else:
                continue
            if name.startswith("_") or name in declared:
                continue
            if name not in assigns or n.lineno < assigns[name]:
                assigns[name] = n.lineno
        for name, lineno in sorted(assigns.items(), key=lambda p: p[1]):
            if name in used:
                continue
            if _noqa(lines, lineno, "F841"):
                continue
            problems.append(
                f"{path}:{lineno}: F841 local `{name}` is assigned "
                f"but never used")
    return problems


def main(argv) -> int:
    roots = [pathlib.Path(p) for p in argv] or [
        pathlib.Path("triton_distributed_tpu"),
        pathlib.Path("tests"),
        pathlib.Path("scripts"),
        pathlib.Path("examples"),
        pathlib.Path("tests_tpu"),
    ]
    files = []
    for root in roots:
        if root.is_file() and root.suffix == ".py":
            files.append(root)
        elif root.is_dir():
            files.extend(
                p for p in sorted(root.rglob("*.py"))
                if not EXCLUDE_PARTS & set(p.parts))
    problems = []
    for f in files:
        problems.extend(lint_file(f))
    for p in problems:
        print(p)
    print(f"lint: {len(files)} files, {len(problems)} problem(s)")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
