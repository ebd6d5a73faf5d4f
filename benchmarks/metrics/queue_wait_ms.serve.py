"""How long a request waited between arriving at the engine and the
admit pass that took it: the median duration of the traced window's
``queue_wait`` spans."""

from benchmarks import program_spans


def reduce(trace, counters, cell):
    return program_spans.median_duration_ms(trace, "queue_wait")
