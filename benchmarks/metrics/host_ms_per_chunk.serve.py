"""The host's serial part of a decode chunk: from the moment the
scheduler holds chunk k's tokens (end of its ``engine.chunk.wait``
span) to the moment chunk k+1 has been dispatched and the scheduler
waits again (start of the next ``engine.chunk.wait``) — consume,
finalize, hand rows out, lifecycle, pull, admit, dispatch.  The median
over the consecutive chunks of the traced window."""

from benchmarks import program_spans


def reduce(trace, counters, cell):
    loaded = program_spans.checked(trace)
    if loaded is None:
        return None
    spans, window = loaded
    waits = program_spans.named(spans, "engine.chunk.wait", window)
    return program_spans.median_ms([
        b["start"] - a["end"] for a, b in zip(waits, waits[1:])
        if b["attrs"].get("chunk") == a["attrs"].get("chunk", -2) + 1
    ])
