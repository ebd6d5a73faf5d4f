"""How many times the set-up traced a function's Python: over the
``jit.*`` spans that ended before the open, one for each ``jit.trace``
(a program's outermost trace) and each span's ``nested`` (the traces
that ran inside it: inner ``jit`` calls, Python traced while a rule
lowers).  A count, not a time: the same on every run of one tree, warm
or cold."""

from benchmarks import setup_spans


def reduce(trace, counters, cell):
    return setup_spans.value(counters, "jit", "traces")
