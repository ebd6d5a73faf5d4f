"""What the per-layer readers of the sigmoid-routed cells share: the
expert counts on the traced window's ``engine.chunk`` spans (the three
integers the chunk program returns with its tokens)."""

from benchmarks import program_spans

KEYS = ("moe_assignments", "moe_local_assignments", "moe_experts_hit")


def per_step(trace, counters):
    """``{key: mean per decode step}`` over the traced window's chunks,
    or None where no span carries the counts (a parent commit, the
    CPU, telemetry off)."""
    loaded = program_spans.checked(trace)
    if loaded is None or not counters.get("chunk_size"):
        return None
    spans, window = loaded
    chunks = [
        s["attrs"] for s in program_spans.named(spans, "engine.chunk", window)
        if all(s["attrs"].get(k) is not None for k in KEYS)
    ]
    if not chunks:
        return None
    steps = len(chunks) * counters["chunk_size"]
    return {k: sum(c[k] for c in chunks) / steps for k in KEYS}
