"""The host's serial part of an optimizer step: from the end of the
previous step's ``train.callback`` span (the caller has read that
step's metrics) to the end of this step's ``dispatch`` span (the step
program has been handed to the device) — feed wait, host-to-device
copy, dispatch.  The median over the steps of the traced window."""

from benchmarks import program_spans


def reduce(trace, counters, cell):
    loaded = program_spans.checked(trace)
    if loaded is None:
        return None
    spans, window = loaded
    callbacks = program_spans.named(spans, "train.callback")
    serial = []
    for d in program_spans.named(spans, "dispatch", window):
        before = [c["end"] for c in callbacks if c["end"] <= d["start"]]
        if before:
            serial.append(d["end"] - before[-1])
    return program_spans.median_ms(serial)
