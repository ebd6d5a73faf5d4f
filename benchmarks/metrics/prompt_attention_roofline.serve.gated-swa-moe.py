"""The flash forward kernel of the prompts (``ops/flash_attention.py``
under ``Attention._prompt_attention``, ``attn._prompt_attention`` in
the trace) against its roofline, for the gated block: for each of the
kernel's events, one layer of one prompt, the least time the chip
could take for a score and a weighted sum a head and visible pair of
its span (causal, and inside the window on a sliding layer) and for
q, k, v read and the output written once
(``flops_gated_swa_moe.prompt_attention_work``), summed over the
events, over their device time.  An event's span and query heads are
read from its own instruction (the ``[B, H, S, head_dim]`` operand in
the capture the runner wrote); the heads say the layer's kind (72 on
the sliding layers, 48 on the full ones).  Nothing where the capture
holds no such event, as on the CPU."""

import re
import traceback

from benchmarks import flops_gated_swa_moe as fl
from benchmarks import program_spans, trace_reduce

KERNEL = "_prompt_attention"
SHAPE = re.compile(r"bf16\[(\d+),(\d+),(\d+),(\d+)\]")


def events(path, head_dim):
    """``[(heads, span, seconds)]`` of the kernel's events on the
    lowest-numbered chip of the capture at ``path``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = {}
    for plane in data.planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if m:
            planes[int(m.group(1))] = plane
    if not planes:
        return []
    out = []
    for line in planes[min(planes)].lines:
        if line.name != trace_reduce.OPS_LINE:
            continue
        for ev in line.events:
            if KERNEL not in ev.name:
                continue
            # the instruction's text: in the event's name, or in its
            # stats where a profiler keeps the name short
            text = " ".join([ev.name] + [
                v for _, v in ev.stats if isinstance(v, str)])
            if "custom" not in text:
                continue
            shape = next((
                tuple(int(g) for g in m.groups())
                for m in SHAPE.finditer(text)
                if int(m.group(4)) == head_dim), None)
            if shape is not None:
                out.append((shape[1], shape[2], ev.duration_ns / 1e9))
    return out


def reduce(trace, counters, cell):
    path = program_spans.newest_capture()
    if cell.get("peaks") is None or path is None:
        return None
    model = cell["config"]
    # the window of each head count's layers (one, or the kind is moot)
    windows = {}
    for h, w in zip(fl.layer_heads(model), fl.windows(model)):
        windows.setdefault(h, set()).add(w)
    try:
        found = events(path, model["head_dim"])
    except Exception:  # noqa: BLE001 - the run's result must still print
        traceback.print_exc()
        return None
    least = seconds = 0.0
    for heads, span, secs in found:
        if len(windows.get(heads, ())) != 1:
            return None
        (window,) = windows[heads]
        ops, nbytes = fl.prompt_attention_work(
            model, span, heads, window, model["dtype"])
        least += fl.roofline_seconds(
            ops, nbytes, cell["peaks"], model["dtype"])[0]
        seconds += secs
    return 100.0 * least / seconds if seconds else None
