"""A run's set-up, from the start of ``run.py`` to the window's open,
as the program's own spans record it.

The program (``telemetry.tracing.watch_jit``, installed with the compile
cache by every chip owner) records every outermost trace, lowering and
compile as ``jit.trace`` / ``jit.lower`` / ``jit.compile``, and JAX's
backend creation as ``setup.device``; the serving engine records each
pass of its scheduler as ``engine.lifecycle`` (with the job's chunk
index) and the trainer each step's ``feed_wait`` / ``h2d`` /
``dispatch`` / ``train.callback``.
The two ends come from the run itself: ``t_start`` from the spec
``run.py`` wrote beside the trace directory, the open from
``counters["setup_s"]`` (both on the Unix clock the ring is on).

The ``jit.*`` spans between them give the seconds of the programs'
Python, of their compiles and the count of traces.  The phases are cut
HERE, from the program's spans, not recorded by the program (the
runners that would record them are the benchmark's own): they tile the
set-up, and need ``setup.device``:

- ``launch``: ``run.py`` started → the backend's creation begins (the
  child's start and imports; for training the cluster's start, the
  compute process's start and ``import jax``);
- ``device``: the backend's creation (TPU init);
- ``weights``: → the first job's scheduler makes its first pass
  (serving) or the first step begins (training): the runner's imports,
  the seeded weights, building the program (and, serving, the first
  engine: its decoder and banks);
- serving: ``warmup`` (the warm-up job, every prompt bucket and the
  chunk traced, lowered, loaded and run) → the window's job begins —
  a job is an engine of its own, so it begins where the chunk index
  falls back — and ``warm_in`` → the open;
- training: ``checked_steps`` (the union of the checked steps' spans)
  and ``norms`` (the rest: the leaf and change norms between and after
  them).

A parent commit (no such spans), a run with telemetry off (an empty
ring) and a ring that may have lost its set-up (it is first in, first
out) give None, and the metrics are left out.
"""

import json
import os
import sys

from benchmarks import program_spans, trace_reduce

#: names of a training step's spans (``dp.train_on_feed``)
STEP_SPANS = ("feed_wait", "h2d", "dispatch", "train.callback")
_memo = {}


def run_spec():
    """The spec ``run.py`` handed this run's child, beside the capture
    of its profile (the readers run only in traced runs)."""
    capture = program_spans.newest_capture()
    if capture is None:
        return None
    # <work>/trace/plugins/profile/<session>/<host>.xplane.pb
    path = os.path.join(capture.rsplit(os.sep + "trace" + os.sep, 1)[0],
                        "spec.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def covered(union, start, end):
    """Seconds of ``union`` (``trace_reduce.union_seconds``'s merged
    intervals) between ``start`` and ``end``."""
    return sum(max(0.0, min(b, end) - max(a, start)) for a, b in union)


def job_starts(passes):
    """Where each serving job began: its scheduler's first pass.  A job
    is an engine of its own, whose chunk index starts again at 0, so a
    pass whose ``chunk`` is lower than the one before it begins a job."""
    starts, last = [], None
    for s in sorted(passes, key=lambda s: s["t0"]):
        chunk = (s.get("attrs") or {}).get("chunk", 0)
        if last is None or chunk < last:
            starts.append(s["t0"])
        last = chunk
    return starts


def value(counters, part, key):
    """One number of :func:`reading` (``part`` ``"phases"`` or
    ``"jit"``), or None where it was not read."""
    got = reading(counters)
    return None if got is None or got[part] is None else got[part].get(key)


def reading(counters):
    """``{"setup_s", "phases": {name: seconds} or None, "jit": {...} or
    None}`` or None; worked out once a run (its five readers all ask)
    and printed on standard error with :func:`detail`."""
    spec = run_spec()
    if spec is None or counters.get("setup_s") is None:
        return None
    key = (spec.get("t_start"), counters["setup_s"])
    if key not in _memo:
        t_start, t_open = key[0], key[0] + key[1]
        ring = set_up_spans(t_start, t_open)
        got = _memo[key] = None if ring is None else _reading(
            ring, t_start, t_open)
        if got is not None:
            print("setup phases: %s" % json.dumps(
                dict(got, detail=detail(ring, t_start, t_open))),
                file=sys.stderr, flush=True)
    return _memo[key]


def set_up_spans(t_start, t_open):
    """The ring's spans that began at ``t_start`` or later, each with
    its ``end``; None where the ring may have lost some of the set-up's
    (it dropped spans, and its oldest ended after ``t_start``)."""
    from tensorflowonspark_tpu import telemetry

    tracer = telemetry.get_tracer()
    spans = tracer.spans()
    if getattr(tracer, "dropped_spans", 0) and (
            not spans or spans[0]["t0"] + spans[0]["dur"] >= t_start):
        return None
    return [dict(s, end=s["t0"] + s["dur"]) for s in spans
            if s["dur"] > 0.0 and s["t0"] >= t_start]


def _jit_spans(ring, t_open):
    return [s for s in ring if s["trace"] == "jit" and s["end"] <= t_open]


def _unions(jit):
    """The merged intervals of the programs' Python (traces and
    lowerings) and of their compiles."""
    _, python = trace_reduce.union_seconds(
        (s["t0"], s["end"]) for s in jit
        if s["name"] in ("jit.trace", "jit.lower"))
    _, compiled = trace_reduce.union_seconds(
        (s["t0"], s["end"]) for s in jit if s["name"] == "jit.compile")
    return python, compiled


def _reading(ring, t_start, t_open):
    parts = phase_parts(ring, t_start, t_open)
    jit = _jit_spans(ring, t_open)
    if parts is None and not jit:
        return None
    got = {"setup_s": t_open - t_start, "phases": None, "jit": None}
    if parts is not None:
        got["phases"] = {name: sum(b - a for a, b in ivs)
                         for name, ivs in parts.items()}
    if jit:
        python, compiled = _unions(jit)
        caches = {}
        for s in jit:
            if s["name"] == "jit.compile":
                cache = (s.get("attrs") or {}).get("cache", "off")
                caches[cache] = caches.get(cache, 0) + 1
        got["jit"] = {
            "trace_s": covered(python, t_start, t_open),
            "compile_s": covered(compiled, t_start, t_open),
            # every trace JAX reported: an outermost one is a span, an
            # inner one is counted in the span that holds it
            "traces": sum((s["name"] == "jit.trace")
                          + (s.get("attrs") or {}).get("nested", 0)
                          for s in jit),
            "compiles": caches,
        }
    return got


def phase_parts(ring, t_start, t_open):
    """Each phase as the intervals it is made of; None without a
    ``setup.device`` span before the open."""
    device = [s for s in ring
              if s["name"] == "setup.device" and s["end"] <= t_open]
    if not device:
        return None
    dev0 = min(s["t0"] for s in device)
    dev1 = max(s["end"] for s in device)
    jobs = job_starts(s for s in ring if s["name"] == "engine.lifecycle"
                      and dev1 <= s["t0"] < t_open)
    steps = [s for s in ring if s["name"] in STEP_SPANS
             and str(s.get("trace")).startswith("step")
             and dev1 <= s["t0"] and s["end"] <= t_open]
    parts = {"launch": [(t_start, dev0)], "device": [(dev0, dev1)]}
    if len(jobs) >= 2:
        parts.update(weights=[(dev1, jobs[0])], warmup=[(jobs[0], jobs[-1])],
                     warm_in=[(jobs[-1], t_open)])
    elif steps:
        first = min(s["t0"] for s in steps)
        _, union = trace_reduce.union_seconds(
            (s["t0"], s["end"]) for s in steps)
        checked = [(max(a, first), min(b, t_open)) for a, b in union]
        gaps, cursor = [], first
        for a, b in checked + [(t_open, t_open)]:
            if a > cursor:
                gaps.append((cursor, a))
            cursor = max(cursor, b)
        parts.update(weights=[(dev1, first)], checked_steps=checked,
                     norms=gaps)
    else:
        parts["rest"] = [(dev1, t_open)]
    return parts


def detail(ring, t_start, t_open):
    """What the stderr line adds for a reader of the log, and no metric
    reads: the programs' Python and compiles in each phase, the three
    longest traces ``(fun, s, nested)``, the count of ``jit.*`` spans
    and the ring's dropped spans."""
    from tensorflowonspark_tpu import telemetry

    jit = _jit_spans(ring, t_open)
    python, compiled = _unions(jit)
    parts = phase_parts(ring, t_start, t_open) or {}

    def by_phase(union):
        return {name: sum(covered(union, a, b) for a, b in ivs)
                for name, ivs in parts.items()}

    return {
        "trace_s_by_phase": by_phase(python),
        "compile_s_by_phase": by_phase(compiled),
        "longest_traces": [
            (s["attrs"].get("fun"), s["dur"], s["attrs"].get("nested"))
            for s in sorted((s for s in jit if s["name"] == "jit.trace"),
                            key=lambda s: -s["dur"])[:3]],
        "spans": len(jit),
        "ring_dropped": getattr(telemetry.get_tracer(), "dropped_spans", 0),
    }
