"""Latent attention with a learned sparse index and sigmoid-routed
experts through the SERVING path — ``SlotDecoder``, ``ServingEngine``,
``predict_rows(schedule="continuous")`` — held against the plain
reference (``benchmarks/reference/glm_dsa_moe.py``) at small widths,
seeded weights, float32: by the gap of each served token's reference
logit below the reference's best, not by tokens.  The model itself
against the reference: tests/test_mla_moe.py.

Tolerances.  Program and reference both run float32 at matmul
precision ``highest``; what separates them is the order of float32
sums (blocked queries, absorbed products, sorted expert rows), which at
these widths moves a logit of size ~3 by a few 1e-6.  The limit is
2e-5 everywhere a logit is compared; a selection or a routing that
differed by ONE key or expert moves logits by 1e-2 and more.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.runners import serve_mla_moe as runner
from tensorflowonspark_tpu.models import transformer as tr

from test_mla_moe import build, highest, tokens_of  # noqa: F401 - a fixture


def test_the_slot_decoder_keeps_latent_and_index_banks():
    _, model, params = build(max_position_embeddings=512)
    dec = tr.SlotDecoder(model, params, 3, 16, cache_len=300, chunk_size=4,
                         pad_multiple=8)
    assert dec.attn_impl == "latent"
    banks = {"/".join(str(getattr(k, "key", k)) for k in path): leaf.shape
             for path, leaf in jax.tree_util.tree_flatten_with_path(
                 dec.cache)[0]}
    # a latent row is kv_lora_rank + rope = 24, out to whole lanes; a
    # bank is cache_len out to whole blocks of 128 positions; the index
    # keys live on the "full" layers only
    assert banks["block_0/attn/latent"] == (3, 384, 128)
    assert banks["block_1/attn/latent"] == (3, 384, 128)
    assert banks["block_0/attn/index_key"] == (3, 384, 16)
    assert "block_1/attn/index_key" not in banks
    assert "block_2/attn/index_key" in banks
    # the decode kernel reads the blocks of 128 between a slot's pad
    # region and its position, one block for a lane nobody holds: a
    # prompt of 130 in its bucket of 136 and 3 tokens on spans 6..138
    assert dec.kv_read_tokens([(130, 3)]) == (2 * 128 + 2 * 128, 3 * 384)
    # over the layers: rows on all 4, the index's keys whole on 2
    assert dec.attn_read_tokens([(130, 3), (20, 1)]) == (
        (2 * 128 + 128 + 128) * 4 + 3 * 384 * 2, (133 + 21) * 4)


def test_what_latent_banks_do_not_serve_yet_is_refused_by_name():
    from tensorflowonspark_tpu.prefix_cache import PrefixCache

    _, model, params = build()
    with pytest.raises(ValueError, match="prefix reuse"):
        tr.SlotDecoder(model, params, 2, 8, cache_len=32,
                       prefix_cache=PrefixCache(block_tokens=8))
    with pytest.raises(ValueError, match="paged latent pages"):
        tr.SlotDecoder(model, params, 2, 8, cache_len=32,
                       kv_layout="paged")
    with pytest.raises(ValueError, match="latent banks"):
        tr.generate_speculative(model, params, jnp.ones((1, 4), jnp.int32), 4)


def test_slots_at_mixed_positions_serve_the_reference_s_best_tokens():
    # three requests of different lengths through admit + chunks, one
    # lane idle: at every served position the served token's reference
    # logit is the reference's best (float32: gap nought but for a
    # near-tie)
    cfg, model, params = build(seed=5)
    dec = tr.SlotDecoder(model, params, 4, 12, cache_len=64, chunk_size=4,
                         pad_multiple=8)
    prompts = [tokens_of(n, seed=n) for n in (5, 19, 33)]
    rows = [[int(dec.admit(i, p))] for i, p in enumerate(prompts)]
    for _ in range(2):
        toks, _ = dec.step_chunk()
        for i, row in enumerate(rows):
            row.extend(int(t) for t in toks[i])
    samples = [(p, np.asarray(r, np.int32)) for p, r in zip(prompts, rows)]
    gaps = runner.served_gaps(cfg, 5, samples, "float32", row_multiple=64)
    assert gaps["tokens_compared"] == 27
    assert gaps["served_gap_max"] < 1e-4
    counts = dec.last_chunk_counts
    # 3 live rows x 3 choices x 3 sparse layers x 4 steps
    assert counts["moe_assignments"] == 108
    assert 0 < counts["moe_local_assignments"] < 108
    assert 0 < counts["moe_experts_hit"] <= 4 * 3 * 4


@pytest.mark.parametrize("pad_multiple,lengths,forms", [
    # buckets of 128 and 256: 1 x 1 and 2 x 2 blocks of the span kernel
    (128, (100, 130), ["latent_span_kernel", "latent_span_kernel"]),
    # a bucket of 136 (no block divides it) beside one of 128
    (8, (131, 122), ["einsum", "latent_span_kernel"]),
])
def test_prompts_admitted_through_the_span_kernel_serve_the_reference_s_best(
        pad_multiple, lengths, forms):
    # the serving path with the prefill's attention as the span kernel
    # where the bucket allows: the engine's span says which it was, and
    # the tokens sit on the reference's best either way
    from tensorflowonspark_tpu import serving, serving_engine, telemetry

    cfg, model, params = build(seed=5, max_position_embeddings=512)

    class Plan:
        answer_len = np.array([6])
        prompt_len = np.array([256])

    predict = tr.serving_builder(params, dict(
        runner.program_config(cfg, Plan), pad_multiple=pad_multiple,
        chunk_size=4))
    prompts = [tokens_of(n, seed=n) for n in lengths]
    tracer = telemetry.get_tracer()
    tracer.clear()
    outs = list(serving.predict_rows(
        predict, [{"prompt": p} for p in prompts], {"prompt": "tokens"},
        batch_size=2, schedule="continuous", on_error="raise"))
    prefills = {s["attrs"]["prompt_tokens"]: s["attrs"]["attn"]
                for s in tracer.spans() if s["name"] == "prefill"}
    assert [prefills[n] for n in lengths] == forms
    samples = [(p, np.asarray(o["generated"])) for p, o in zip(prompts, outs)]
    gaps = runner.served_gaps(cfg, 5, samples, "float32", row_multiple=64)
    assert gaps["tokens_compared"] == 12
    assert gaps["served_gap_max"] < 1e-4


def test_one_continuous_predict_rows_job_end_to_end_with_its_counters():
    from tensorflowonspark_tpu import serving, serving_engine, telemetry

    cfg, model, params = build(seed=6)
    class Plan:
        answer_len = np.array([10])
        prompt_len = np.array([40])

    predict = tr.serving_builder(params, dict(
        runner.program_config(cfg, Plan), pad_multiple=8, chunk_size=4))
    prompts = [tokens_of(n, seed=100 + n) for n in (7, 30, 16, 22)]
    rows = [{"prompt": p, "max_new": 6 + i} for i, p in enumerate(prompts)]
    tracer = telemetry.get_tracer()
    tracer.clear()  # a full ring does not grow: read this job's spans only
    stats = {}
    outs = list(serving.predict_rows(
        predict, rows,
        {"prompt": "tokens", "max_new": serving_engine.BUDGET_INPUT},
        batch_size=3, schedule="continuous", on_error="raise", stats=stats))
    assert [int(o["generated_len"]) for o in outs] == [6, 7, 8, 9]
    samples = [(p, np.asarray(o["generated"][:int(o["generated_len"])]))
               for p, o in zip(prompts, outs)]
    gaps = runner.served_gaps(cfg, 6, samples, "float32", row_multiple=64)
    assert gaps["served_gap_max"] < 1e-4
    assert stats["attn"] == "latent"
    spans = tracer.spans()
    chunks = [s["attrs"] for s in spans if s["name"] == "engine.chunk"]
    assert chunks and all(
        c["attn_read_tokens"]
        == 4 * c["kv_read_tokens"] + 2 * c["kv_bank_tokens"]
        for c in chunks)
    assert all(c["attn_context_tokens"] > 0 for c in chunks)
    for c in chunks:
        assert c["moe_assignments"] == c["live"] * 3 * 3 * 4
        assert 0 <= c["moe_local_assignments"] <= c["moe_assignments"]
        assert c["moe_experts_hit"] <= 4 * 3 * 4
    prefills = [s["attrs"] for s in spans if s["name"] == "prefill"]
    assert sorted(p["prompt_tokens"] for p in prefills) == [7, 16, 22, 30]
    assert sorted(p["bucket"] for p in prefills) == [8, 16, 24, 32]
    # no block of the span kernel divides these buckets
    assert {p["attn"] for p in prefills} == {"einsum"}
    counters = telemetry.get_registry().snapshot()["counters"]
    for name in ("moe_assignments", "moe_local_assignments",
                 "moe_experts_hit", "attn_read_tokens",
                 "attn_context_tokens"):
        assert counters["serving." + name] > 0
