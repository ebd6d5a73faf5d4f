"""Share of the window the CHIP spent on admissions (the prefill
programs and the lane installs after them), where
``prefill_wall_share.serve`` reads only the host's wall in dispatching
them: the window less its decode chunks, each at the chunk program's
median device time in the trace, over the window.  The engine keeps
the chip busy from chunk to chunk (``device_idle_share.serve``), so
what the chunks leave is the admissions'.  The traced seconds alone
would not do: a prefill of 1-3 s is whole inside them or absent."""

import statistics

from benchmarks import trace_reduce
from benchmarks.runners.common import load_module


def reduce(trace, counters, cell):
    chunk = load_module("decode_step_ms.serve").CHUNK_PROGRAM
    seconds = trace_reduce.program_events(trace, chunk)
    if (not seconds or not counters.get("window_s")
            or counters.get("chunks") is None):
        return None
    decode_s = counters["chunks"] * statistics.median(seconds)
    return 100.0 * max(0.0, 1.0 - decode_s / counters["window_s"])
