"""Device time of one decode step: the median duration of the decode
chunk program (``SlotDecoder._chunk_impl``) in the trace, over the
steps one chunk holds."""

import statistics

from benchmarks import trace_reduce

CHUNK_PROGRAM = r"^jit__chunk(_spec)?_impl"


def reduce(trace, counters, cell):
    chunks = trace_reduce.program_events(trace, CHUNK_PROGRAM)
    if not chunks or not counters.get("chunk_size"):
        return None
    return 1e3 * statistics.median(chunks) / counters["chunk_size"]
