"""Seconds of the set-up spent in the programs' Python: the length of
the union of the ``jit.trace`` and ``jit.lower`` spans that ended
before the open.  Paid on every run, cache or not: a cache hit needs
the lowered module for its key."""

from benchmarks import setup_spans


def reduce(trace, counters, cell):
    return setup_spans.value(counters, "jit", "trace_s")
