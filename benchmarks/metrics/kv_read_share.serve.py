"""Share of the KV banks' positions that a decode step reads: the mean
of ``kv_read_tokens`` over ``kv_bank_tokens`` on the traced window's
``engine.chunk`` spans (the engine's own reckoning of each slot's live
span rounded out to the blocks its decode attention fetches; 100 where
every step reads every bank whole).  Nothing where the spans do not
carry the two counters, as on a parent commit."""

from benchmarks import program_spans


def reduce(trace, counters, cell):
    loaded = program_spans.checked(trace)
    if loaded is None:
        return None
    spans, window = loaded
    shares = [
        s["attrs"]["kv_read_tokens"] / s["attrs"]["kv_bank_tokens"]
        for s in program_spans.named(spans, "engine.chunk", window)
        if s["attrs"].get("kv_bank_tokens")
        and s["attrs"].get("kv_read_tokens") is not None
    ]
    return 100.0 * sum(shares) / len(shares) if shares else None
