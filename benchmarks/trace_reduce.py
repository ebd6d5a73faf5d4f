"""From a profiler trace to numbers.  The profiler's ``.xplane.pb`` is
read once into a plain dict (``load_xplane``) — planes, their lines,
events as ``[name, start_ns, duration_ns]`` — and everything else is
arithmetic on that dict, so the tests run it on a small recorded trace
kept as JSON beside them.

Device planes are named ``/device:TPU:<n>``.  On such a plane the line
``XLA Modules`` holds one event per executed program (named
``jit_<function>(<fingerprint>)``) and ``XLA Ops`` one per operation
inside it; the host plane ``/host:CPU`` holds a line per thread with
the ``TraceAnnotation`` spans the benchmark puts round its own calls.
"""

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


def load_xplane(log_dir, keep_host=("bench.",)):
    """Read the newest trace under ``log_dir`` (as
    ``jax.profiler.start_trace`` wrote it).  Host events are kept only
    where their name starts with one of ``keep_host``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % (log_dir,))
    data = ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        is_dev = DEVICE_PLANE.match(plane.name) is not None
        if not is_dev and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            if is_dev and line.name not in (MODULES_LINE, OPS_LINE):
                continue
            events = [
                [_short(ev.name) if is_dev else ev.name,
                 float(ev.start_ns), float(ev.duration_ns)]
                for ev in line.events
                if is_dev or ev.name.startswith(tuple(keep_host))
            ]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _short(name):
    """An ``XLA Ops`` event is named by its whole HLO instruction
    (``%fusion.3 = bf16[...] fusion(...), ...``): keep the
    instruction's name, and the target of a custom call (a Pallas
    kernel's name rides there)."""
    head = name.split(" = ", 1)[0].lstrip("%")
    m = re.search(r'custom_call_target="([^"]+)"', name)
    k = re.search(r'kernel_name[=:]\s*"?([A-Za-z0-9_.\-]+)', name)
    if k:
        return "%s[%s]" % (head, k.group(1))
    return "%s[%s]" % (head, m.group(1)) if m else head


def device_planes(trace):
    """``{chip index: plane}`` of the trace's device planes."""
    out = {}
    for plane in trace["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        if m:
            out[int(m.group(1))] = plane
    return out


def line_events(plane, line_name):
    for line in plane["lines"]:
        if line["name"] == line_name:
            return line["events"]
    return []


def union_seconds(intervals):
    """Length of the union of ``(start_ns, end_ns)`` intervals, in
    seconds, and the merged intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return sum(e - s for s, e in merged) / 1e9, merged


def window_of(trace):
    """``(start_ns, end_ns)`` spanned by every device event."""
    starts, ends = [], []
    for plane in device_planes(trace).values():
        for line in plane["lines"]:
            for _, s, d in line["events"]:
                starts.append(s)
                ends.append(s + d)
    if not starts:
        return None
    return min(starts), max(ends)


def busy(trace):
    """Per chip: seconds in which an operation ran (union of the ``XLA
    Ops`` events; of the modules where a plane has no op line), and the
    merged busy intervals."""
    out = {}
    for chip, plane in device_planes(trace).items():
        events = line_events(plane, OPS_LINE) or line_events(
            plane, MODULES_LINE)
        secs, merged = union_seconds(
            [(s, s + d) for _, s, d in events if d > 0])
        out[chip] = {"busy_s": secs, "intervals": merged}
    return out


def program_events(trace, pattern, chip=None):
    """Durations (seconds) of the executed programs whose module name
    matches the regular expression ``pattern``, on ``chip`` (default:
    the lowest-numbered chip in the trace)."""
    planes = device_planes(trace)
    if not planes:
        return []
    plane = planes[min(planes) if chip is None else chip]
    rx = re.compile(pattern)
    return [
        d / 1e9 for name, _, d in line_events(plane, MODULES_LINE)
        if rx.search(name)
    ]


def op_seconds(trace, pattern, chip=None):
    """Summed device time (seconds) and count of the ``XLA Ops`` events
    whose name matches ``pattern``."""
    planes = device_planes(trace)
    if not planes:
        return 0.0, 0
    plane = planes[min(planes) if chip is None else chip]
    rx = re.compile(pattern)
    hits = [d for name, _, d in line_events(plane, OPS_LINE)
            if rx.search(name)]
    return sum(hits) / 1e9, len(hits)


def top_device_ops(trace, n=10, chip=None):
    """``[[name, seconds], ...]``: the operations that took most device
    time, names without their numeric suffix summed together."""
    planes = device_planes(trace)
    if not planes:
        return []
    plane = planes[min(planes) if chip is None else chip]
    total = {}
    for name, _, d in line_events(plane, OPS_LINE):
        key = re.sub(r"\.\d+(?=$|\[)", "", name) or name
        total[key] = total.get(key, 0.0) + d / 1e9
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in ranked]


def host_spans(trace):
    """``[(name, start_ns, end_ns)]`` of the benchmark's annotations."""
    spans = []
    for plane in trace["planes"]:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            spans += [(n, s, s + d) for n, s, d in line["events"]]
    return spans


def idle_gaps(trace, n=10, chip=None):
    """``[[name, seconds], ...]``: idle time of the chip inside the
    traced window, summed by what the host was doing meanwhile — the
    innermost benchmark annotation open at that moment, or
    ``unattributed``."""
    b = busy(trace)
    win = window_of(trace)
    if not b or win is None:
        return []
    chip = min(b) if chip is None else chip
    spans = host_spans(trace)
    edges = [win[0]]
    for s, e in b[chip]["intervals"]:
        edges += [s, e]
    edges.append(win[1])
    total = {}
    for i in range(0, len(edges), 2):
        start, end = edges[i], edges[i + 1]
        if end <= start:
            continue
        # cut the gap where a span begins or ends; each piece goes to
        # the innermost span that covers it
        cuts = sorted({start, end} | {
            t for sp in spans for t in sp[1:] if start < t < end})
        for a, b in zip(cuts, cuts[1:]):
            mid = 0.5 * (a + b)
            inside = [sp for sp in spans if sp[1] <= mid <= sp[2]]
            name = (
                min(inside, key=lambda sp: sp[2] - sp[1])[0]
                if inside else "unattributed"
            )
            total[name] = total.get(name, 0.0) + (b - a) / 1e9
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in ranked]


def summary(trace):
    """What every traced result line carries under ``device``:
    ``busy_s`` averaged over the chips, ``window_s``, and the busy
    seconds chip by chip."""
    win = window_of(trace)
    b = busy(trace)
    if win is None or not b:
        return None
    per_chip = {c: v["busy_s"] for c, v in sorted(b.items())}
    return {
        "window_s": (win[1] - win[0]) / 1e9,
        "busy_s": sum(per_chip.values()) / len(per_chip),
        "busy_s_by_chip": per_chip,
    }
