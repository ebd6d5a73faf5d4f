"""Seconds of the set-up from the backend's creation to the first job
(serving: the warm-up's first ``engine.lifecycle`` pass) or step
(training: its first ``feed_wait``) — the runner's imports, the seeded
weights and building the program (``setup_spans``)."""

from benchmarks import setup_spans


def reduce(trace, counters, cell):
    return setup_spans.value(counters, "phases", "weights")
