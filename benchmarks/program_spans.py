"""The program's own spans, placed on a profiler trace.

``tensorflowonspark_tpu.telemetry`` keeps a ring of spans in the
process that ran the program, each with its start on the profiler's
clock (Unix seconds).  A trace as ``trace_reduce.load_xplane`` reads it
is relative to the moment its profiler session began, and that moment
— ``profile_start_time`` on the capture's ``Task Environment`` plane —
is not in the dict the readers are handed.  So ``load`` finds the
capture this process wrote, reads the moment from it, and hands back
the ring moved onto the trace's clock.  A reader then trusts the pair
only after ``clock_holds``: what the trace and the ring both saw lies
where it must.

A parent commit whose tracer is not on the profiler's clock (it has
no ``tracing.profile_start_ns``), a rehearsal on the CPU (no device plane, so no window) and a run with
telemetry off (an empty ring) all give None, and the metric is left
out of the line.
"""

import functools
import glob
import os
import re
import statistics
import tempfile
import traceback

from benchmarks import trace_reduce
from benchmarks.runners import common

#: slack of the host-against-host check: the ring's clock follows the
#: profiler's to a few microseconds (tests/test_tracing_clock.py)
HOST_SLACK_NS = 100e3
#: a step's program may start this long after its ``dispatch`` span
LAUNCH_WITHIN_NS = 50e6
#: ... and this long BEFORE it: the device plane's clock leads the
#: host's by 1.0-1.1 ms on a TPU v5e (PERF.md section 6, PR 26), so a
#: program launched a few hundred microseconds into the span reads as
#: starting before it
DEVICE_LEAD_NS = 2e6


def newest_capture():
    """The ``.xplane.pb`` this run's profiler session wrote.  ``run.py``
    makes a run's work directory with ``mkdtemp(prefix="bench_run_")``
    and the runners trace into its ``trace``; the readers are handed
    the loaded trace, not its path."""
    paths = glob.glob(os.path.join(
        tempfile.gettempdir(), "bench_run_*", "trace", "plugins",
        "profile", "*", "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


@functools.lru_cache(maxsize=2)
def _start_of(path):
    # every reader of a run asks; the capture is read once
    from tensorflowonspark_tpu.telemetry import tracing

    return tracing.profile_start_ns(path)


def session_start_ns():
    """Unix ns at which the trace's profiler session began; None where
    no capture is found, or the program's tracer is not on the
    profiler's clock (a parent commit's)."""
    from tensorflowonspark_tpu.telemetry import tracing

    path = newest_capture()
    if path is None or not hasattr(tracing, "profile_start_ns"):
        return None
    return _start_of(path)


def load(trace):
    """``(spans, window)``: every span of the process-wide ring with
    ``start``/``end`` in the trace's nanoseconds (``name``, ``attrs``
    and ``trace`` as recorded; zero-duration marks left out), and
    ``trace_reduce.window_of(trace)``.  None where there is no window,
    no session start or no span."""
    window = trace_reduce.window_of(trace)
    if window is None:
        return None
    from tensorflowonspark_tpu import telemetry

    origin = session_start_ns()
    if origin is None:
        return None
    tracer = telemetry.get_tracer()
    spans = [
        {"name": s["name"], "trace": s.get("trace"),
         "attrs": s.get("attrs") or {},
         "start": s["t0"] * 1e9 - origin,
         "end": (s["t0"] + s["dur"]) * 1e9 - origin}
        for s in tracer.spans() if s["dur"] > 0.0
    ]
    return (spans, window) if spans else None


def named(spans, name, window=None):
    """The spans called ``name``, in time order; with ``window``, those
    that start inside it."""
    return sorted(
        (s for s in spans if s["name"] == name and (
            window is None or window[0] <= s["start"] <= window[1])),
        key=lambda s: s["start"])


def step_program_starts(trace):
    """Start (ns) of every run of the trainer's step program on the
    lowest-numbered chip (``step_ms.train``'s events)."""
    planes = trace_reduce.device_planes(trace)
    if not planes:
        return []
    rx = re.compile(common.load_module("step_ms.train").STEP_PROGRAM)
    return [
        s for name, s, _ in trace_reduce.line_events(
            planes[min(planes)], trace_reduce.MODULES_LINE)
        if rx.search(name)
    ]


def clock_holds(trace, spans):
    """True when what the trace and the ring both saw agrees: every
    ``bench.source`` annotation of the trace (the source's ``next``)
    lies inside an ``engine.pull`` span (the engine's call of it), and
    every run of the trainer's step program starts after the start of
    a ``dispatch`` span and within ``LAUNCH_WITHIN_NS`` of it.  A trace
    with neither kind of evidence is not trusted."""
    sources = [s for s in trace_reduce.host_spans(trace)
               if s[0] == "bench.source"]
    steps = step_program_starts(trace)
    if not sources and not steps:
        return False
    pulls = named(spans, "engine.pull")
    for _, start, end in sources:
        if not any(p["start"] - HOST_SLACK_NS <= start
                   and end <= p["end"] + HOST_SLACK_NS for p in pulls):
            return False
    dispatches = named(spans, "dispatch")
    for start in steps:
        if not any(-DEVICE_LEAD_NS <= start - d["start"] <= LAUNCH_WITHIN_NS
                   for d in dispatches):
            return False
    return True


def checked(trace):
    """``load(trace)`` if the clock check holds, else None.  A reader
    must never fail its run: whatever goes wrong in finding or reading
    the capture is printed and read as nothing to read."""
    try:
        loaded = load(trace)
    except Exception:  # noqa: BLE001 - the run's result must still print
        traceback.print_exc()
        return None
    if loaded is None or not clock_holds(trace, loaded[0]):
        return None
    return loaded


def median_ms(values_ns):
    return statistics.median(values_ns) / 1e6 if values_ns else None


def median_duration_ms(trace, name):
    """Median duration (ms) of the spans called ``name`` that start
    inside the traced window; None where ``checked`` gives nothing."""
    loaded = checked(trace)
    if loaded is None:
        return None
    spans, window = loaded
    return median_ms([s["end"] - s["start"]
                      for s in named(spans, name, window)])
