"""Positions a decode step's attention READS against the positions
that are live: the mean of ``attn_read_tokens`` over
``attn_context_tokens`` on the traced window's ``engine.chunk`` spans,
both summed over slots and layers by the engine (latent or key/value
rows on every layer, index keys on the layers that own an index; live
= every request's prompt and answer so far).  100 is dense attention
over exactly the live keys; a step that reads whole banks under a mask
reads more, one that gathers its selected rows less.  Nothing where
the spans do not carry the two counters, as on a parent commit."""

from benchmarks import program_spans


def reduce(trace, counters, cell):
    loaded = program_spans.checked(trace)
    if loaded is None:
        return None
    spans, window = loaded
    shares = [
        s["attrs"]["attn_read_tokens"] / s["attrs"]["attn_context_tokens"]
        for s in program_spans.named(spans, "engine.chunk", window)
        if s["attrs"].get("attn_context_tokens")
        and s["attrs"].get("attn_read_tokens") is not None
    ]
    return 100.0 * sum(shares) / len(shares) if shares else None
