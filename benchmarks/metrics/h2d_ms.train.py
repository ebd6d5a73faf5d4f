"""Host time of laying one step's batch out on the chips: the median
duration of the traced window's ``h2d`` spans."""

from benchmarks import program_spans


def reduce(trace, counters, cell):
    return program_spans.median_duration_ms(trace, "h2d")
