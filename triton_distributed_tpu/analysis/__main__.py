"""CLI sweep: sanitize every registered kernel across its meshes.

    python -m triton_distributed_tpu.analysis              # comm sweep
    python -m triton_distributed_tpu.analysis --check resources
    python -m triton_distributed_tpu.analysis --check serving
    python -m triton_distributed_tpu.analysis --check protocol
    python -m triton_distributed_tpu.analysis --check all
    python -m triton_distributed_tpu.analysis --list
    python -m triton_distributed_tpu.analysis -k allgather.ring
    python -m triton_distributed_tpu.analysis --mesh tp=4
    python -m triton_distributed_tpu.analysis --json out.json
    python -m triton_distributed_tpu.analysis -k allreduce.chain \\
        --dump-graph graph.dot

``--check`` picks the analysis family: ``comm`` (default — the
cross-rank comm-graph sanitizer), ``resources`` (the VMEM / tiling /
block-index-bounds abstract interpreter over every registered kernel,
comm AND compute), ``serving`` (the paged-serving refcount/donation
model checker), ``protocol`` (the cluster wire/routing/failover
protocol model checker — every interleaving of deliver / drop /
duplicate / corrupt / crash / staleness over a small scope), or
``all``.

Exit status: 0 = no findings, 1 = findings, 2 = usage error.
Tier-1 runs the comm + resources sweeps and the serving model check
as tests (`tests/test_analysis.py`, `test_resources`, `test_serving_model`).
"""

from __future__ import annotations

import argparse
import fnmatch
import json
import sys


def _parse_mesh(text):
    axes = {}
    for part in text.split(","):
        axis, _, size = part.partition("=")
        if not size:
            raise argparse.ArgumentTypeError(
                f"mesh spec {text!r} must look like tp=4 or x=2,y=2")
        axes[axis] = int(size)
    return axes


def main(argv=None) -> int:
    from triton_distributed_tpu import analysis

    parser = argparse.ArgumentParser(
        prog="python -m triton_distributed_tpu.analysis",
        description="Static comm-graph sanitizer sweep over registered "
                    "kernels.")
    parser.add_argument("--check", default="comm",
                        choices=("comm", "resources", "serving",
                                 "protocol", "all"),
                        help="analysis family to run (default: comm)")
    parser.add_argument("-k", "--kernel", action="append", default=None,
                        help="kernel name or glob (repeatable); default: "
                             "all registered")
    parser.add_argument("--mesh", type=_parse_mesh, default=None,
                        help="override mesh shape, e.g. tp=4 or x=2,y=2")
    parser.add_argument("--list", action="store_true",
                        help="list registered kernels and exit")
    parser.add_argument("--json", metavar="PATH", default=None,
                        help="write findings as JSON (- for stdout)")
    parser.add_argument("--dump-graph", metavar="PATH", default=None,
                        help="write the comm graph (graphviz dot) of the "
                             "first analyzed (kernel, mesh) and exit")
    parser.add_argument("-q", "--quiet", action="store_true",
                        help="print only findings and the final summary")
    args = parser.parse_args(argv)

    comm_names = analysis.all_kernels()
    resource_names = (analysis.all_resource_kernels()
                      if args.check in ("resources", "all") else [])
    names = sorted(set(comm_names) | set(resource_names))
    if args.kernel:
        selected = [n for n in names
                    if any(fnmatch.fnmatch(n, pat) or n == pat
                           for pat in args.kernel)]
        if not selected:
            print(f"no registered kernel matches {args.kernel}; "
                  f"known: {', '.join(names)}", file=sys.stderr)
            return 2
        names = selected

    if args.list:
        from triton_distributed_tpu.analysis.registry import get_kernel
        for n in names:
            if n in comm_names:
                meshes = ", ".join(
                    ",".join(f"{a}={s}" for a, s in m.items())
                    for m in get_kernel(n).meshes)
            else:
                meshes = "[capture]"
            print(f"{n:40s} {meshes}")
        return 0

    if args.dump_graph:
        from triton_distributed_tpu.analysis.context import record_traces
        from triton_distributed_tpu.analysis.graph import build_graph
        from triton_distributed_tpu.analysis.registry import iter_specs
        for _, _, spec in iter_specs(names, args.mesh):
            machine = record_traces(spec.body, axis_sizes=spec.axis_sizes,
                                    refs=spec.refs, sems=spec.sems,
                                    grid=spec.grid)
            with open(args.dump_graph, "w") as fh:
                fh.write(build_graph(machine).to_dot())
            print(f"wrote {args.dump_graph} for {spec.name}")
            return 0
        print("nothing analyzed", file=sys.stderr)
        return 2

    total = 0
    swept = 0
    rows = []

    def consume(label, results):
        nonlocal total, swept
        for name, axis_sizes, findings in results:
            swept += 1
            mesh_str = (",".join(f"{a}={s}"
                                 for a, s in axis_sizes.items())
                        or "single")
            if findings:
                total += len(findings)
                print(f"FAIL {name} [{mesh_str}] ({label}): "
                      f"{len(findings)} finding(s)")
                for f in findings:
                    print(f"  {f}")
            elif not args.quiet:
                print(f"ok   {name} [{mesh_str}] ({label})")
            rows.extend({
                "check": label,
                "kernel": name,
                "mesh": axis_sizes,
                "kind": f.kind.value,
                "rank": list(f.rank) if f.rank is not None else None,
                "sem": f.sem,
                "ref": f.ref,
                "message": f.message,
            } for f in findings)

    if args.check in ("comm", "all"):
        consume("comm", analysis.sweep(
            [n for n in names if n in comm_names], args.mesh))
    if args.check in ("resources", "all"):
        consume("resources", analysis.sweep_resources(names, args.mesh))
    if args.check in ("serving", "all"):
        findings = analysis.check_serving_model()
        consume("serving", [("serving.paged", {}, findings)])
        # Cross-tier scope: demote/promote/adopt interleavings over
        # the spill tier (content round-trip, dangling promotes,
        # refcounts across the ship seam).
        tier_findings = analysis.check_serving_model(
            analysis.tier_scope())
        consume("serving", [("serving.kvtier", {}, tier_findings)])
    if args.check in ("protocol", "all"):
        consume("protocol",
                [(f"cluster.protocol.{label}", {}, findings)
                 for label, findings in analysis.sweep_protocol()])

    if args.json:
        payload = json.dumps({"findings": rows, "swept": swept}, indent=2)
        if args.json == "-":
            print(payload)
        else:
            with open(args.json, "w") as fh:
                fh.write(payload + "\n")

    print(f"analysis sweep [{args.check}]: {swept} (kernel, mesh) "
          f"pairs, {total} finding(s)")
    return 1 if total else 0


if __name__ == "__main__":
    sys.exit(main())
