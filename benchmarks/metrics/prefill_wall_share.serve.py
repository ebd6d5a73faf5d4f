"""Share of the window the engine's host spent dispatching prefills:
growth of ``stats["prefill_wall_sec"]`` inside the window over the
window (a host-clock sum kept by the engine)."""


def reduce(trace, counters, cell):
    if "prefill_wall_s" not in counters or not counters.get("window_s"):
        return None
    return 100.0 * counters["prefill_wall_s"] / counters["window_s"]
