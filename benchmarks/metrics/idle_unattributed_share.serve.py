"""Share of the busiest chip's idle time, inside the traced window,
that no span of the program covers: the idle intervals
(``trace_reduce.busy``) go through the program's own
``tracing.attribute`` over the ring's spans, and what comes back as
``unattributed`` is the part no host phase owns yet."""

from benchmarks import program_spans, trace_reduce


def idle_by_span(trace, spans, window):
    """``{span name or "unattributed": seconds}`` over the idle
    intervals of the busiest chip inside ``window``."""
    from tensorflowonspark_tpu.telemetry import tracing

    busy = trace_reduce.busy(trace)
    chip = max(busy, key=lambda c: busy[c]["busy_s"])
    edges = [window[0]]
    for start, end in busy[chip]["intervals"]:
        edges += [start, end]
    edges.append(window[1])
    idle = [(edges[i] / 1e9, edges[i + 1] / 1e9)
            for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    return tracing.attribute(idle, [
        {"name": s["name"], "t0": s["start"] / 1e9,
         "dur": (s["end"] - s["start"]) / 1e9} for s in spans
    ])


def reduce(trace, counters, cell):
    loaded = program_spans.checked(trace)
    if loaded is None:
        return None
    by_span = idle_by_span(trace, *loaded)
    total = sum(by_span.values())
    if total <= 0:
        return None
    return 100.0 * by_span.get("unattributed", 0.0) / total
