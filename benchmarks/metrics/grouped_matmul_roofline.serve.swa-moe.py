"""The grouped-matmul kernel (``ops/gmm.py``, ``grouped_matmul`` in the
trace) against its roofline inside the decode step of the
softmax-routed block, where every expert is held: the least time the
chip could take for one step's experts — each HIT expert's three
matrices read once, one expert's operations per assignment (the chunk
program's counts, a step) — over the kernel's device time a step (its
events inside the decode chunk programs; a prefill's calls lie outside
them and are not read)."""

from benchmarks import flops_swa_moe as fl
from benchmarks import moe_counts
from benchmarks.runners.common import load_module


def reduce(trace, counters, cell):
    seconds = load_module(
        "grouped_matmul_roofline.serve").kernel_seconds_per_step(
            trace, counters)
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
