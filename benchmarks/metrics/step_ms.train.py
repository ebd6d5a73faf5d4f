"""Device time of one optimizer step: the median duration of the
trainer's step program in the trace (``train_step``, or ``multi`` over
the steps it fuses), on the lowest-numbered chip."""

import statistics

from benchmarks import trace_reduce

STEP_PROGRAM = r"^jit_(train_step|multi)\b"


def step_events(trace):
    """Durations of the step program's runs in the trace."""
    return trace_reduce.program_events(trace, STEP_PROGRAM)


def reduce(trace, counters, cell):
    steps = step_events(trace)
    if not steps:
        return None
    fused = cell["config"]["program"].get("steps_per_execution", 1)
    return 1e3 * statistics.median(steps) / fused
