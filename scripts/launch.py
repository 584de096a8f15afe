#!/usr/bin/env python
"""Multi-process SPMD launcher — the reference's `scripts/launch.sh`
(torchrun + NVSHMEM env bootstrap) re-done for JAX.

Spawns N copies of a script with the environment that
`triton_distributed_tpu.parallel.mesh.initialize_distributed` reads
(`TDT_NUM_PROCESSES` / `TDT_PROCESS_ID` / `TDT_COORDINATOR`), waits for
all of them, and tears the group down on first failure — the role
torchrun plays for the reference (RANK/WORLD_SIZE env + rendezvous).

On a TPU pod each host launches one process (`--nproc` defaults to 1
there; the TPU runtime supplies inter-host topology).  On CPU the same
flow runs an N-process gloo-backed group on one machine — the
multi-process test harness.

Usage:
    python scripts/launch.py --nproc 4 your_script.py [args...]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read_heartbeats(directory):
    """Stdlib-only heartbeat reader (the launcher deliberately never
    imports jax/the package: worker startup cost stays in the workers,
    and this runs inside the SIGALRM handler).  Same file format as
    ``observability.exporter`` writes."""
    beats = {}
    for path in glob.glob(os.path.join(directory,
                                       "heartbeat-rank-*.json")):
        try:
            with open(path) as f:
                hb = json.load(f)
            beats[int(hb["rank"])] = hb
        except (OSError, ValueError, KeyError):
            continue
    return beats


def _rank_health_lines(hb_dir):
    """Render per-rank heartbeat freshness: which rank stopped beating
    and what its last span was — the difference between "exit 124" and
    "rank 2 wedged in span 'dcn_collective' for 9s"."""
    beats = _read_heartbeats(hb_dir)
    if not beats:
        return [f"watchdog: no heartbeats under {hb_dir} (workers "
                "never armed TDT_HEARTBEAT_DIR?)"]
    try:
        interval = float(os.environ.get("TDT_HEARTBEAT_INTERVAL",
                                        "1.0"))
    except ValueError:
        interval = 1.0
    now = time.time()
    lines = ["watchdog: rank health from heartbeats:"]
    ages = {}
    for rank, hb in sorted(beats.items()):
        age = now - float(hb.get("unix_time", 0.0))
        ages[rank] = age
        stale = age > 3.0 * interval
        step = (f" step={hb['step']}"
                if hb.get("step") is not None else "")
        lines.append(
            f"  rank {rank}: [{'STALLED' if stale else 'ok':>7}] "
            f"last beat {age:.1f}s ago, "
            f"last span={hb.get('last_span')!r}{step}")
    stale_ranks = [r for r, a in ages.items()
                   if a > 3.0 * interval]
    if stale_ranks:
        worst = max(stale_ranks, key=ages.get)
        lines.append(
            f"watchdog: stalled rank {worst} "
            f"(no heartbeat for {ages[worst]:.1f}s), last span="
            f"{beats[worst].get('last_span')!r}, open spans="
            f"{beats[worst].get('open_spans')}")
    else:
        # Every beat is fresh: do NOT pin the hang on a healthy rank.
        # Either --timeout is shorter than the workload, or the wedge
        # releases the GIL (e.g. a blocking device wait), which keeps
        # the daemon beat thread alive — report the facts instead.
        stalest = max(ages, key=ages.get)
        lines.append(
            "watchdog: all heartbeats fresh — no stalled rank "
            "detected (timeout shorter than the workload, or the "
            f"wedge keeps beats alive); stalest is rank {stalest} "
            f"({ages[stalest]:.1f}s ago, last span="
            f"{beats[stalest].get('last_span')!r})")
    return lines


def _run_doctor(dirs):
    """Invoke the incident doctor over the run's artifact directories
    after a failed exit.  Subprocess for the same reason as the trace
    merge (the package imports jax); the report lands next to the
    artifacts and its verdict is echoed to stderr."""
    dirs = [d for d in dict.fromkeys(dirs) if d and os.path.isdir(d)]
    if not dirs:
        return
    env = dict(os.environ)
    env["PYTHONPATH"] = (REPO_ROOT + os.pathsep
                         + env.get("PYTHONPATH", ""))
    try:
        res = subprocess.run(
            [sys.executable, "-m",
             "triton_distributed_tpu.observability.doctor",
             *dirs, "-q"],
            env=env, capture_output=True, text=True, timeout=180)
        report = os.path.join(dirs[0], "incident_report.md")
        if res.returncode == 0:
            print(f"launch: incident report -> {report}",
                  file=sys.stderr, flush=True)
            # Surface the one-line verdict without re-dumping the
            # whole report into a log that already has backtraces.
            try:
                with open(os.path.join(dirs[0],
                                       "incident_report.json")) as f:
                    print("launch: doctor verdict: "
                          + json.load(f).get("verdict", ""),
                          file=sys.stderr, flush=True)
            except (OSError, ValueError):
                pass
        else:
            out = (res.stdout + res.stderr).strip()
            if out:
                print(f"launch: doctor failed: {out[-500:]}",
                      file=sys.stderr, flush=True)
    except (OSError, subprocess.SubprocessError) as e:
        print(f"launch: doctor failed: {e}", file=sys.stderr,
              flush=True)


class _RendezvousServer:
    """The rank-directory server for ``--roles`` launches (protocol:
    ``serving/cluster/net/rendezvous.py`` — one JSON line up per rank,
    one directory line back once EVERY rank registered).  Lives in
    the PARENT, stdlib-only, because the parent owns the process
    group: when a rank dies mid-handshake the launcher aborts the
    rendezvous (pending connections closed WITHOUT a reply, which the
    clients surface as `RendezvousError`) and fails the launch with
    exit 2 instead of letting the survivors block until --timeout."""

    def __init__(self, world):
        self.world = int(world)
        self._srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._srv.setsockopt(socket.SOL_SOCKET,
                             socket.SO_REUSEADDR, 1)
        self._srv.bind(("127.0.0.1", 0))
        self._srv.listen(self.world + 8)
        self._srv.settimeout(0.25)
        self.addr = f"127.0.0.1:{self._srv.getsockname()[1]}"
        self._ranks = {}
        self._conns = {}
        self._lock = threading.Lock()
        self.complete = threading.Event()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._serve,
                                        daemon=True)
        self._thread.start()

    def _serve(self):
        while not (self._stop.is_set() or self.complete.is_set()):
            try:
                conn, _ = self._srv.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            try:
                conn.settimeout(10.0)
                buf = b""
                while not buf.endswith(b"\n"):
                    chunk = conn.recv(65536)
                    if not chunk:
                        raise OSError("eof before registration")
                    buf += chunk
                reg = json.loads(buf.decode())
                rank = int(reg["rank"])
            except (OSError, ValueError, KeyError, TypeError):
                conn.close()
                continue
            with self._lock:
                old = self._conns.pop(rank, None)
                self._ranks[rank] = {
                    "role": str(reg.get("role", "")),
                    "index": int(reg.get("index", 0)),
                    "addr": str(reg.get("addr", ""))}
                self._conns[rank] = conn
                done = len(self._ranks) == self.world
            if old is not None:
                old.close()
            if done:
                self._release()

    def _release(self):
        reply = (json.dumps({
            "ok": True, "world": self.world, "t0": time.time(),
            "ranks": {str(r): v for r, v in self._ranks.items()}})
            .encode() + b"\n")
        with self._lock:
            conns, self._conns = dict(self._conns), {}
        for conn in conns.values():
            try:
                conn.sendall(reply)
            except OSError:
                pass
            conn.close()
        self.complete.set()
        try:
            self._srv.close()
        except OSError:
            pass

    def abort(self):
        """Close every held connection WITHOUT a reply — each blocked
        rank fails with `RendezvousError` immediately."""
        self._stop.set()
        with self._lock:
            conns, self._conns = dict(self._conns), {}
        for conn in conns.values():
            conn.close()
        try:
            self._srv.close()
        except OSError:
            pass


def _merge_traces(trace_dir):
    """Merge per-rank traces after the group exits.  Subprocess (the
    package imports jax — keep the launcher light), same CLI a human
    would run by hand."""
    env = dict(os.environ)
    env["PYTHONPATH"] = (REPO_ROOT + os.pathsep
                         + env.get("PYTHONPATH", ""))
    try:
        # -c instead of -m: the package __init__ imports the timeline
        # module, and runpy warns when re-executing an already-imported
        # module — same entry point, without the noise.
        res = subprocess.run(
            [sys.executable, "-c",
             "import sys; "
             "from triton_distributed_tpu.observability import "
             "timeline; sys.exit(timeline.main(sys.argv[1:]))",
             trace_dir, "--report"],
            env=env, capture_output=True, text=True, timeout=120)
        out = (res.stdout + res.stderr).strip()
        if out:
            print(out, file=sys.stderr, flush=True)
    except (OSError, subprocess.SubprocessError) as e:
        print(f"launch: trace merge failed: {e}", file=sys.stderr,
              flush=True)


def _offset_port(base: str, rank: int) -> str:
    """Per-rank metrics port: ``0`` (ephemeral) stays ``0`` for every
    rank, a numeric base offsets by rank, anything malformed passes
    through (the exporter already survives a bad value)."""
    try:
        port = int(base)
    except ValueError:
        return base
    return base if port == 0 else str(port + rank)


def _merge_ports(ports_dir):
    """Fold the per-rank ``ports-rank-<N>.json`` endpoint files the
    exporters advertised into one ``ports.json`` — the single file
    the watch CLI / fleet collector read to find the fleet."""
    import glob as _glob
    import json as _json
    ranks = []
    for path in sorted(_glob.glob(os.path.join(
            ports_dir, "ports-rank-*.json"))):
        try:
            with open(path) as f:
                ranks.append(_json.load(f))
        except (OSError, ValueError):
            continue
    if not ranks:
        return
    ranks.sort(key=lambda r: r.get("rank", 0))
    path = os.path.join(ports_dir, "ports.json")
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            _json.dump({"schema": 1, "ranks": ranks}, f, indent=1)
        os.replace(tmp, path)
    except OSError as e:
        print(f"launch: ports merge failed: {e}", file=sys.stderr,
              flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nproc", type=int, default=1,
                    help="processes to spawn on this host")
    ap.add_argument("--coordinator", default="127.0.0.1:12357",
                    help="coordinator address (host:port)")
    ap.add_argument("--node-rank", type=int, default=0,
                    help="index of this host in a multi-host launch")
    ap.add_argument("--nnodes", type=int, default=1)
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend (test harness)")
    ap.add_argument("--roles", default=None, metavar="SPEC",
                    help="cluster role assignment, e.g. "
                         "'router:1,prefill:1,replica:2' — ranks get "
                         "roles by contiguous ranges in the order "
                         "given (rank 0 = first role) and each worker "
                         "sees TDT_ROLE / TDT_ROLE_INDEX / "
                         "TDT_CLUSTER_SPEC, so one launch line brings "
                         "up a whole serving topology "
                         "(serving/cluster.role_from_env reads them). "
                         "The counts must sum to the world size; with "
                         "--nproc left at its default on one node, "
                         "nproc grows to the spec total")
    ap.add_argument("--flight-dir", default=None,
                    help="arm the per-rank flight recorder: workers "
                         "dump their recent kernel events to this "
                         "directory on SIGTERM/SIGUSR1 (default: "
                         "inherit TDT_FLIGHT_RECORDER, else off)")
    ap.add_argument("--trace-dir", default=None,
                    help="arm runtime span tracing: workers export "
                         "per-rank Chrome traces here "
                         "(trace-rank-N.json) and write heartbeats to "
                         "<dir>/heartbeats; on exit the launcher "
                         "merges the traces into merged_trace.json + "
                         "straggler_report.json")
    ap.add_argument("--timeout", type=float, default=0,
                    help="watchdog: SIGTERM the group after this many "
                         "seconds (0 = no limit).  With --flight-dir "
                         "set, a hung DCN launch leaves per-rank "
                         "flight-recorder dumps instead of silence; "
                         "with --trace-dir set, the timeout report "
                         "names the stalled rank and its last span "
                         "from heartbeats")
    ap.add_argument("script")
    ap.add_argument("script_args", nargs=argparse.REMAINDER)
    args = ap.parse_args()

    # --roles: parse 'router:1,prefill:1,replica:2' into a rank ->
    # (role, index-within-role) map.  Stdlib-only, like the rest of
    # the launcher.
    role_of = None
    roles_spec = None
    if args.roles:
        known = ("router", "replica", "prefill")
        pairs = []
        for part in args.roles.split(","):
            name, _, count = part.partition(":")
            name = name.strip()
            if name not in known or not count.strip().isdigit():
                print(f"launch: bad --roles entry {part!r} (want "
                      f"role:count with role in {known})",
                      file=sys.stderr)
                return 2
            if any(n == name for n, _ in pairs):
                # A repeated role would restart TDT_ROLE_INDEX at 0
                # mid-range (two workers believing they are the same
                # replica) and collapse in role_from_env()'s
                # {role: count} spec — reject it.
                print(f"launch: duplicate --roles entry {name!r} "
                      f"(give each role once, with its total count)",
                      file=sys.stderr)
                return 2
            pairs.append((name, int(count)))
        total = sum(c for _, c in pairs)
        if args.nproc == 1 and args.nnodes == 1 and total > 1:
            args.nproc = total     # one launch line, whole topology
        if total != args.nproc * args.nnodes:
            print(f"launch: --roles totals {total} but world size is "
                  f"{args.nproc * args.nnodes}", file=sys.stderr)
            return 2
        roles_spec = ",".join(f"{n}:{c}" for n, c in pairs)
        role_of = {}
        rank = 0
        for name, count in pairs:
            for idx in range(count):
                role_of[rank] = (name, idx)
                rank += 1

    world = args.nproc * args.nnodes
    # One process per chip.  Every child inherits this environment, so
    # without a CPU pin each one initialises the TPU runtime — and a
    # chip belongs to one process at a time: the second fails or hangs.
    # The router owns no model and always starts on the CPU; more than
    # one model-owning process on this host is refused here, with the
    # reason, rather than left to hang.
    on_cpu = args.cpu or os.environ.get("JAX_PLATFORMS") == "cpu"
    local_ranks = range(args.node_rank * args.nproc,
                        (args.node_rank + 1) * args.nproc)
    chip_procs = sum(1 for r in local_ranks
                     if role_of is None or role_of[r][0] != "router")
    if not on_cpu and chip_procs > 1:
        print(f"launch: {chip_procs} model-owning processes on this "
              f"host would each initialise the TPU runtime, and a chip "
              f"belongs to one process at a time.  Pass --cpu (test "
              f"harness), or start one such process per host and let "
              f"it drive every local chip.", file=sys.stderr)
        return 2
    # --roles launches get the rank-directory server: role processes
    # rendezvous here (net/rendezvous.py) before opening their data
    # plane, and a rank dying mid-handshake aborts the whole launch
    # with exit 2 instead of hanging the survivors until --timeout.
    rdv = _RendezvousServer(world) if role_of is not None else None
    procs = []
    rank_of_pid = {}
    # Heartbeats ride under the trace dir (or wherever the user
    # already pointed TDT_HEARTBEAT_DIR) — the watchdog reads them to
    # name the stalled rank.
    hb_dir = (os.path.join(args.trace_dir, "heartbeats")
              if args.trace_dir
              else os.environ.get("TDT_HEARTBEAT_DIR"))
    # Metrics-endpoint discovery: every rank binds its OWN port (the
    # parent's TDT_METRICS_PORT is offset by rank below — inheriting
    # it verbatim made every role process race for the same bind and
    # all but one silently lose their /metrics).  Each rank
    # advertises its actual endpoint into ports_dir
    # (ports-rank-<N>.json, exporter-side), merged to ports.json
    # after the run so the fleet collector / watch CLI can find the
    # fleet without guessing.
    ports_dir = (args.trace_dir if args.trace_dir
                 else os.environ.get("TDT_PORTS_DIR"))

    def _kill_group(sig=signal.SIGTERM):
        for p in procs:
            if p.poll() is None:
                p.send_signal(sig)

    # Installed BEFORE the spawn loop: a SIGTERM mid-spawn (harness
    # timeout while workers pay interpreter+jax startup) must not
    # orphan the already-spawned half of the group — stranded workers
    # keep ports and CPU, deadlocking every later launch.
    signal.signal(signal.SIGTERM,
                  lambda *a: (_kill_group(), sys.exit(143)))

    # Watchdog: a wedged group (the classic silent DCN hang) gets
    # SIGTERMed after --timeout seconds; workers with the flight
    # recorder armed dump their event rings from their own SIGTERM
    # handlers before dying, so the hang becomes diagnosable.
    timed_out = []
    health_lines = []
    if args.timeout > 0:
        def _on_alarm(*a):
            if not any(p.poll() is None for p in procs):
                return  # everyone already exited: not a hang
            if timed_out:
                # Second firing: the grace period elapsed and someone
                # ignored SIGTERM (wedged in a compiled collective,
                # holding the GIL away from its dump handler) —
                # SIGKILL so os.wait() below can ever return.
                _kill_group(signal.SIGKILL)
                return
            timed_out.append(True)
            # BEFORE killing: heartbeat files are freshest now, and a
            # wedged rank is still distinguishable from its healthy
            # peers (its beat is the stale one).
            if hb_dir:
                health_lines.extend(_rank_health_lines(hb_dir))
                print("\n".join(health_lines), file=sys.stderr,
                      flush=True)
            _kill_group()
            signal.setitimer(signal.ITIMER_REAL, 10)  # dump grace
        signal.signal(signal.SIGALRM, _on_alarm)
        signal.setitimer(signal.ITIMER_REAL, args.timeout)

    for local in range(args.nproc):
        rank = args.node_rank * args.nproc + local
        env = dict(os.environ)
        env["TDT_NUM_PROCESSES"] = str(world)
        env["TDT_PROCESS_ID"] = str(rank)
        env["TDT_COORDINATOR"] = args.coordinator
        if args.flight_dir:
            env["TDT_FLIGHT_RECORDER"] = args.flight_dir
        if args.trace_dir:
            env["TDT_TRACE_DIR"] = args.trace_dir
            env["TDT_HEARTBEAT_DIR"] = hb_dir
        if args.cpu or (role_of is not None
                        and role_of[rank][0] == "router"):
            env["JAX_PLATFORMS"] = "cpu"
        base_port = os.environ.get("TDT_METRICS_PORT")
        if base_port and world > 1:
            env["TDT_METRICS_PORT"] = _offset_port(base_port, rank)
        if ports_dir:
            env["TDT_PORTS_DIR"] = ports_dir
        if role_of is not None:
            role, idx = role_of[rank]
            env["TDT_ROLE"] = role
            env["TDT_ROLE_INDEX"] = str(idx)
            env["TDT_CLUSTER_SPEC"] = roles_spec
            env["TDT_RENDEZVOUS"] = rdv.addr
        procs.append(subprocess.Popen(
            [sys.executable, args.script, *args.script_args], env=env))
        rank_of_pid[procs[-1].pid] = rank

    rc = 0
    try:
        # First failure kills the group (a hung peer would otherwise
        # deadlock the collectives).
        pending = {p.pid: p for p in procs}
        while pending and rc == 0:
            pid, status = os.wait()
            p = pending.pop(pid, None)
            if p is None:
                continue
            code = os.waitstatus_to_exitcode(status)
            if (rdv is not None and not rdv.complete.is_set()
                    and code != 0):
                # A role process DIED before the directory assembled:
                # its peers are blocked in rendezvous and would sit
                # there until --timeout.  Abort the handshake (their
                # connections close without a reply -> RendezvousError
                # in each) and fail the launch NOW.  (A clean exit 0
                # is NOT a death: role workers that never dial the
                # rendezvous — env-plumbing smoke runs — finish
                # normally.)
                role, idx = role_of[rank_of_pid.get(pid, -1)] \
                    if rank_of_pid.get(pid, -1) in role_of \
                    else ("?", "?")
                print(f"launch: rank {rank_of_pid.get(pid)} "
                      f"({role}:{idx}) exited {code} during "
                      "rendezvous handshake; aborting launch",
                      file=sys.stderr, flush=True)
                rdv.abort()
                rc = 2
            elif code != 0:
                rc = code
        for p in pending.values():
            p.send_signal(signal.SIGTERM)
        for p in pending.values():
            p.wait()
        # Group fully reaped: disarm the watchdog so a run finishing
        # just under --timeout cannot be relabelled 124 by an alarm
        # firing during cleanup (the finally block has its own
        # SIGTERM→SIGKILL escalation and needs no timer).
        if args.timeout > 0:
            signal.setitimer(signal.ITIMER_REAL, 0)
    except KeyboardInterrupt:
        # Disarm the watchdog first: a Ctrl-C near the deadline must
        # report 130, not be relabelled 124 by an alarm firing during
        # the grace loop below.
        if args.timeout > 0:
            signal.setitimer(signal.ITIMER_REAL, 0)
        # Give the workers a grace period to run their own SIGINT
        # cleanup (finalize_distributed, port release) before the
        # finally-block's SIGTERM backstop fires.
        _kill_group(signal.SIGINT)
        deadline = 20
        while deadline and any(p.poll() is None for p in procs):
            time.sleep(0.25)
            deadline -= 1
        rc = 130
    finally:
        if rdv is not None:
            rdv.abort()      # idempotent; releases port + held conns
        # SIGTERM, then escalate: a worker wedged in a collective can
        # ignore SIGTERM and outlive the launcher holding ports (ADVICE
        # r4) — poll briefly and SIGKILL survivors.
        _kill_group()
        deadline = 20  # 5 s
        while deadline and any(p.poll() is None for p in procs):
            time.sleep(0.25)
            deadline -= 1
        _kill_group(signal.SIGKILL)
        for p in procs:
            if p.poll() is None:
                p.wait()
    if args.trace_dir:
        # Group fully reaped: merge whatever per-rank traces the
        # workers exported into one timeline + straggler report.
        _merge_traces(args.trace_dir)
    if ports_dir:
        _merge_ports(ports_dir)
    if timed_out:
        rc = 124  # timeout(1) convention
        # Re-state the verdict next to the exit code (the at-alarm
        # report may have scrolled past a long worker backtrace).
        for line in health_lines[-1:]:
            print(line, file=sys.stderr, flush=True)
    if rc != 0:
        # Watchdog fired (124) or a rank died nonzero: turn whatever
        # artifacts the run left (flight dumps, traces, heartbeats)
        # into one incident report, automatically.
        _run_doctor([args.flight_dir, args.trace_dir, hb_dir])
    return rc


if __name__ == "__main__":
    sys.exit(main())
