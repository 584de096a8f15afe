#!/usr/bin/env python
"""Auto-generated metrics reference: scrape every registry call site
into a name -> type -> labels table in docs/observability.md.

Hand-written metric tables rot the day after the next counter lands.
This script walks the package AST for the same call sites the lint
gate checks (`.counter(` / `.gauge(` / `.histogram(` with a constant
first argument, plus the `count_metric` / `observe_metric` helpers),
collects the label keys passed as keywords, and rewrites the block
between the markers::

    <!-- BEGIN metrics-reference ... -->
    <!-- END metrics-reference -->

F-string names (`f"serving_kvtier_{k}"`) are emitted as wildcard
rows (`serving_kvtier_*`) — the fan-out values are runtime data, the
prefix is not.

Usage::

    python scripts/gen_metrics_reference.py            # rewrite
    python scripts/gen_metrics_reference.py --check    # drift gate

``--check`` exits 1 when the committed table differs from the
scraped one — tier-1 runs it as a test
(`tests/test_tooling.py::test_static_gate_passes[metrics_reference]`)
so the reference cannot drift from the code.
"""

from __future__ import annotations

import argparse
import ast
import pathlib
import re
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
DOC = REPO / "docs" / "observability.md"
BEGIN = ("<!-- BEGIN metrics-reference "
         "(generated by scripts/gen_metrics_reference.py) -->")
END = "<!-- END metrics-reference -->"

EXCLUDE_PARTS = {"__pycache__", ".git"}

_METRIC_CALLS = {
    "counter": "counter",
    "count_metric": "counter",
    # `serving/pages.py`'s lazy-import wrapper of `count_metric`
    "_count_metric": "counter",
    "gauge": "gauge",
    "histogram": "histogram",
    "observe_metric": "histogram",
}

#: Keyword arguments of the helper call sites that are NOT labels.
_NON_LABEL_KWARGS = {"n", "value"}


def _const_or_pattern(arg) -> str | None:
    """A constant metric name, a wildcard pattern for an f-string
    with a constant prefix, or None when unscrapable."""
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return arg.value
    if isinstance(arg, ast.JoinedStr):
        parts = []
        for v in arg.values:
            if isinstance(v, ast.Constant):
                parts.append(str(v.value))
            else:
                parts.append("*")
        pattern = "".join(parts)
        return pattern if re.match(r"[a-z]", pattern) else None
    return None


def scrape(root: pathlib.Path) -> dict:
    """name -> {kind, labels (set), modules (set)}."""
    out: dict = {}
    for path in sorted(root.rglob("*.py")):
        if EXCLUDE_PARTS & set(path.parts):
            continue
        try:
            tree = ast.parse(path.read_text(), filename=str(path))
        except SyntaxError:
            continue
        mod = str(path.relative_to(REPO))
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            fn = node.func
            callee = (fn.attr if isinstance(fn, ast.Attribute)
                      else fn.id if isinstance(fn, ast.Name)
                      else None)
            kind = _METRIC_CALLS.get(callee)
            if kind is None:
                continue
            name = _const_or_pattern(node.args[0])
            if name is None or not re.match(r"[a-z][a-z0-9_*]*$",
                                            name):
                continue
            labels = {kw.arg for kw in node.keywords
                      if kw.arg is not None
                      and kw.arg not in _NON_LABEL_KWARGS}
            row = out.setdefault(name, {"kind": kind,
                                        "labels": set(),
                                        "modules": set()})
            # A name registered as two kinds is a bug the registry
            # itself raises on; the scrape keeps the first and the
            # table shows both modules so the clash is visible.
            row["labels"] |= labels
            row["modules"].add(mod)
    return out


def render(metrics: dict) -> str:
    lines = [
        BEGIN,
        "",
        "| metric | type | labels | call sites |",
        "|---|---|---|---|",
    ]
    for name in sorted(metrics):
        row = metrics[name]
        disp = name.replace("*", "\\*")
        labels = (", ".join(f"`{k}`" for k in sorted(row["labels"]))
                  or "—")
        mods = ", ".join(f"`{m}`" for m in sorted(row["modules"]))
        lines.append(f"| `{disp}` | {row['kind']} | {labels} "
                     f"| {mods} |")
    lines += ["", END]
    return "\n".join(lines)


def splice(doc_text: str, table: str) -> str:
    begin = doc_text.find(BEGIN)
    end = doc_text.find(END)
    if begin < 0 or end < 0:
        raise SystemExit(
            f"gen_metrics_reference: markers not found in {DOC} "
            f"(need both {BEGIN!r} and {END!r})")
    return doc_text[:begin] + table + doc_text[end + len(END):]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="exit 1 when the committed table drifted "
                         "from the scraped call sites")
    ap.add_argument("--root", default=str(REPO
                                          / "triton_distributed_tpu"))
    args = ap.parse_args(argv)
    metrics = scrape(pathlib.Path(args.root))
    table = render(metrics)
    current = DOC.read_text()
    updated = splice(current, table)
    if args.check:
        if updated != current:
            print("gen_metrics_reference: docs/observability.md "
                  "metrics reference has DRIFTED from the code — "
                  "run `python scripts/gen_metrics_reference.py` "
                  "and commit the result")
            return 1
        print(f"gen_metrics_reference: in sync "
              f"({len(metrics)} metrics)")
        return 0
    if updated != current:
        DOC.write_text(updated)
        print(f"gen_metrics_reference: rewrote {DOC} "
              f"({len(metrics)} metrics)")
    else:
        print(f"gen_metrics_reference: already in sync "
              f"({len(metrics)} metrics)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
