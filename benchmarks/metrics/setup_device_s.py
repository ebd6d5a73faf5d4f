"""Seconds of the set-up spent creating the backend — TPU init: the
program's ``setup.device`` span (``tracing.watch_jit``, from JAX's own
records round it).  Nothing where the program records no such span, as
on a parent commit or with telemetry off."""

from benchmarks import setup_spans


def reduce(trace, counters, cell):
    return setup_spans.value(counters, "phases", "device")
