"""Share of the decoder's slots in flight when a chunk was dispatched:
the mean of ``live`` over ``slots`` on the traced window's
``engine.chunk`` spans."""

from benchmarks import program_spans


def reduce(trace, counters, cell):
    loaded = program_spans.checked(trace)
    if loaded is None:
        return None
    spans, window = loaded
    shares = [
        s["attrs"]["live"] / s["attrs"]["slots"]
        for s in program_spans.named(spans, "engine.chunk", window)
        if s["attrs"].get("slots")
    ]
    return 100.0 * sum(shares) / len(shares) if shares else None
