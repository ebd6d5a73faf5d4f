"""Seconds of the set-up inside ``compile_or_get_cached``: the length
of the union of the ``jit.compile`` spans that ended before the open —
reading executables back when the cache is warm, compiling when it is
cold."""

from benchmarks import setup_spans


def reduce(trace, counters, cell):
    return setup_spans.value(counters, "jit", "compile_s")
