"""The grouped-matmul kernel (``ops/gmm.py``, ``grouped_matmul`` in the
trace) against its roofline inside the decode step: the least time the
chip could take for one step's routed experts — each HIT expert's three
matrices read once, one expert's operations per local assignment (the
chunk program's counts, a step) — over the kernel's device time a step:
its events that start inside a decode chunk program, over the steps
those programs hold.  Prefill's calls of the same kernel lie outside
the chunk programs and are not read."""

import re

from benchmarks import flops_glm_dsa_moe as fl
from benchmarks import moe_counts
from benchmarks import trace_reduce
from benchmarks.runners.common import load_module

KERNEL = r"^grouped_matmul"


def kernel_seconds_per_step(trace, counters, pattern=KERNEL):
    """Device seconds per decode step of the kernel whose events'
    names match ``pattern``, or None."""
    planes = trace_reduce.device_planes(trace)
    if not planes or not counters.get("chunk_size"):
        return None
    plane = planes[min(planes)]
    chunk = re.compile(load_module("decode_step_ms.serve").CHUNK_PROGRAM)
    spans = [(s, s + d) for name, s, d in trace_reduce.line_events(
        plane, trace_reduce.MODULES_LINE) if chunk.search(name)]
    kernel = re.compile(pattern)
    inside = sum(
        d for name, s, d in trace_reduce.line_events(
            plane, trace_reduce.OPS_LINE)
        if kernel.search(name) and any(a <= s < b for a, b in spans))
    if not spans or not inside:
        return None
    return inside / 1e9 / (len(spans) * counters["chunk_size"])


def reduce(trace, counters, cell):
    seconds = kernel_seconds_per_step(trace, counters)
    counts = moe_counts.per_step(trace, counters)
    if cell.get("peaks") is None or not seconds or counts is None:
        return None
    model = cell["config"]
    ops, nbytes = fl.grouped_matmul_work(
        model, counts["moe_local_assignments"], counts["moe_experts_hit"],
        model["dtype"])
    least_s, _ = fl.roofline_seconds(
        ops, nbytes, cell["peaks"], model["dtype"])
    return 100.0 * least_s / seconds
