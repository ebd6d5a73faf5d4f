#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``:

    python3 benchmarks/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

This process never touches JAX: a chip belongs to one process, and for
a training cell that is the cluster's compute process, for a serving
cell this command's one child.  The cell's configuration, traffic mix
and per-layer readers are found by the names ``BENCHMARK.json`` gives
them; nothing here knows any of those names.  The last line of
standard output is the result object; without a TPU holding the chips
the cell asks for the command fails and prints no result.
"""

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
RESULT_TAG = "BENCH_RESULT "
#: the driver allows a warm run 360 s
CHILD_TIMEOUT_S = 1150.0
RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device")


def load_cell(workload, root=ROOT):
    """The spec of ``workload``: its entry, configuration, traffic mix
    and the metrics that list it (or list no cell at all)."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit("unknown workload %r; BENCHMARK.json has %s" % (
            workload, sorted(cells)))
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(root, configs[cell["config"]]["file"])) as f:
        config = json.load(f)
    mix_dir = os.path.join(root, bench["paths"][0], "traffic")
    with open(os.path.join(mix_dir, cell["traffic"] + ".json")) as f:
        mix = json.load(f)

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "workload": workload, "chips": cell["chips"], "config": config,
        "traffic": mix,
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


def _stat_fields(path):
    with open(path) as f:
        # after the parenthesised command name: state ppid pgrp session
        return f.read().rsplit(")", 1)[1].split()


def session_members(sid):
    """Pids of session ``sid`` that still run.  A process whose leading
    thread has exited reads as a zombie while its other threads are
    still tearing down — and still hold its chip — so a process counts
    until every one of its threads has ended."""
    pids = []
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            if int(_stat_fields("/proc/%s/stat" % entry)[3]) != sid:
                continue
            tasks = os.listdir("/proc/%s/task" % entry)
            if any(_stat_fields("/proc/%s/task/%s/stat" % (entry, t))[0]
                   != "Z" for t in tasks):
                pids.append(int(entry))
        except (OSError, IndexError, ValueError):
            continue  # raced a process exit
    return pids


def kill_session(sid, wait_s=90.0):
    """SIGKILL every process of session ``sid`` — the child and whatever
    it started (executors leave its process group, not its session) —
    and wait until each has ended: a chip owner's teardown takes seconds
    after the signal, and until it is over the chip is still held."""
    deadline = time.monotonic() + wait_s
    while True:
        pids = session_members(sid)
        if not pids or time.monotonic() > deadline:
            return not pids
        for pid in pids:
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
        time.sleep(0.2)


def chips_free(wait_s=120.0):
    """Wait until no TPU of this host is held.  A chip owner that was
    killed while it shut down (a cluster's compute process is) leaves
    its chips busy for a while after its last thread has gone; the next
    run's owner would then fail to open them.  The group files under
    ``/dev/vfio`` open only when free; a host without them has nothing
    to wait for."""
    deadline = time.monotonic() + wait_s
    try:
        groups = [g for g in os.listdir("/dev/vfio") if g.isdigit()]
    except OSError:
        return True
    while True:
        busy = []
        for g in groups:
            try:
                os.close(os.open("/dev/vfio/" + g, os.O_RDWR))
            except OSError as e:
                if e.errno == 16:  # EBUSY
                    busy.append(g)
        if not busy or time.monotonic() > deadline:
            return not busy
        time.sleep(0.5)


def run_child(spec, env):
    """Run the chip-owning child in a session of its own; its result,
    or None.  The session is killed whatever happens."""
    work = tempfile.mkdtemp(prefix="bench_run_")
    spec = dict(spec, trace_dir=os.path.join(work, "trace"))
    spec_path = os.path.join(work, "spec.json")
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    proc = subprocess.Popen(
        [sys.executable, "-m", "benchmarks.runners", spec_path],
        env=env, cwd=ROOT, stdout=subprocess.PIPE, text=True,
        start_new_session=True,
    )
    timer = threading.Timer(
        CHILD_TIMEOUT_S, kill_session, args=(proc.pid, 0.0))
    timer.start()
    result = None
    try:
        for line in proc.stdout:
            if line.startswith(RESULT_TAG):
                result = json.loads(line[len(RESULT_TAG):])
            else:
                sys.stderr.write(line)
        rc = proc.wait()
    finally:
        timer.cancel()
        proc.kill()
        proc.wait()
        gone = kill_session(proc.pid) and chips_free()
        shutil.rmtree(work, ignore_errors=True)
    if not gone:
        print("benchmark: a process of the run would not end",
              file=sys.stderr)
        return None
    return result if rc == 0 else None


def main(argv=None, rehearse=None):
    t_start = time.time()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="also read the lower-precision control's "
                         "numbers (never part of a measured run)")
    args = ap.parse_args(argv)

    spec = load_cell(args.workload)
    if rehearse:
        # a test's tiny rehearsal on the CPU: sizes it overrides, and a
        # result that says it is no measurement
        spec["config"].update(rehearse.get("config", {}))
        spec["traffic"].update(rehearse.get("traffic", {}))
        spec["rehearse"] = dict(rehearse)
    spec.update(seed=args.seed, seconds=args.seconds, trace=args.trace,
                control=args.control, t_start=t_start)
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    if not rehearse:
        env["JAX_PLATFORMS"] = "tpu"  # no chip is an error, not a CPU run
    env["PYTHONPATH"] = os.pathsep.join(
        [ROOT] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                  if p])
    result = run_child(spec, env)
    if result is None or any(k not in result for k in RESULT_KEYS):
        print("benchmark: the run produced no result", file=sys.stderr)
        return 1
    for name, c in result.get("checks", {}).items():
        print("check %s %.6g limit %.6g" % (name, c["value"], c["limit"]),
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
