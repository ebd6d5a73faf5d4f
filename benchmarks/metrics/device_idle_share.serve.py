"""Share of the traced window in which no operation ran on the chip:
1 − union of the device-busy intervals over the window."""

from benchmarks import trace_reduce


def reduce(trace, counters, cell):
    s = trace_reduce.summary(trace)
    if s is None or s["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - max(s["busy_s_by_chip"].values()) / s["window_s"])
