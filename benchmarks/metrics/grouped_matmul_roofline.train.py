"""The grouped-matmul kernels (``ops/gmm.py``: ``grouped_matmul``,
``grouped_matmul_dx``, ``grouped_matmul_dw`` in the trace) against
their roofline inside the training step: the least time the chip could
take for a step's routed products — one expert's operations per LOCAL
assignment, forward, input gradient and weight gradient; each held
expert a routing pass reached read once a pass (the step's own
counters, a step of the window) — over the kernels' device time a
step."""

from benchmarks import flops_mla_moe_train as fl
from benchmarks import trace_reduce
from benchmarks.runners.common import load_module

KERNEL = r"^grouped_matmul"
COUNTS = ("moe_local_assignments", "moe_experts_hit")


def reduce(trace, counters, cell):
    seconds, events = trace_reduce.op_seconds(trace, KERNEL)
    steps = len(load_module("step_ms.train").step_events(trace))
    if (cell.get("peaks") is None or not events or not steps
            or not counters.get("steps")
            or any(counters.get(k) is None for k in COUNTS)):
        return None
    model = cell["config"]
    local, hit = (counters[k] / counters["steps"] for k in COUNTS)
    ops, nbytes = fl.grouped_matmul_work(model, local, hit, model["dtype"])
    least_s, _ = fl.roofline_seconds(
        ops, nbytes, cell["peaks"], model["dtype"])
    return 100.0 * least_s / (seconds / steps)
