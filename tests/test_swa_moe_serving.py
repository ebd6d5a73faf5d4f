"""Window and full attention layers through the SERVING path —
``SlotDecoder`` with rings beside whole banks, prompts admitted through
the flash kernel, ``ServingEngine``, ``predict_rows(schedule=
"continuous")`` — held against the plain reference
(``benchmarks/reference/swa_moe.py``) at small widths, seeded weights,
float32: by the gap of each served token's reference logit below the
reference's best over the reference's FULL forward, not by tokens.  The
model itself against the reference: tests/test_swa_moe.py.

Tolerances.  As there: float32 at ``highest`` on both sides leaves the
order of float32 sums, a few 1e-6 on logits of size ~3, so a served
token's gap is nought but for a near-tie: under 1e-4.  Each planted
fault — a window of W + 1, a ring one row short, the full layers
rotated without YaRN, gates not renormalised — reads over 1e-3.
"""

import jax
import numpy as np
import pytest

from benchmarks.runners import serve_swa_moe as runner
from benchmarks.tests import faults_swa_moe
from tensorflowonspark_tpu.models import transformer as tr
from tensorflowonspark_tpu.ops import moe as moe_ops

from test_swa_moe import build, highest, tokens_of  # noqa: F401 - a fixture


def serve(dec, prompts, chunks):
    """Greedy answers of ``prompts`` through admits and ``chunks``
    decode chunks: ``[(prompt, served ids)]``."""
    rows = [[int(dec.admit(i, p))] for i, p in enumerate(prompts)]
    for _ in range(chunks):
        toks, _ = dec.step_chunk()
        for i, row in enumerate(rows):
            row.extend(int(t) for t in toks[i])
    return [(p, np.asarray(r, np.int32)) for p, r in zip(prompts, rows)]


def banks_of(dec):
    return {"/".join(str(getattr(k, "key", k)) for k in path): leaf.shape
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                dec.cache)[0]}


def test_slots_at_mixed_positions_past_the_window_and_the_wrap():
    # rings of 8 rows (the window: no tile-legal block at this head
    # size) on the three sliding layers, a whole bank on the full one;
    # three requests of different lengths, one lane idle, 25 tokens
    # each: every one decodes past the window AND past a ring's wrap,
    # the two longer ones are prefilled past it too
    cfg, model, params = build(seed=5)
    dec = tr.SlotDecoder(model, params, 4, 32, cache_len=96, chunk_size=4,
                         pad_multiple=8)
    assert dec.model.cfg.fresh_prompts and dec.attn_impl == "dot"
    banks = banks_of(dec)
    for layer, rows in enumerate([8, 8, 8, 96]):
        assert banks["block_%d/attn/cached_key" % layer] == (4, rows, 2, 16)
        assert banks["block_%d/attn/cached_value" % layer] == (
            4, rows, 2, 16)
    # bytes by kind: 4 slots x rows x (2 heads x 16 x 4 bytes x K and V)
    assert dec.kv_bank_bytes() == {
        "ring": 4 * 24 * 256, "whole": 4 * 96 * 256,
        "unringed": 4 * 4 * 96 * 256}
    # masked einsums read every bank whole, ring or not
    assert dec.kv_read_tokens([(5, 3)]) == (4 * 30, 4 * 30)
    assert dec.attn_read_tokens([(5, 3), (19, 2)]) == (
        4 * 120, (8 + 21) * 4)
    samples = serve(dec, [tokens_of(n, seed=n) for n in (5, 19, 33)], 6)
    gaps = runner.served_gaps(cfg, 5, samples, "float32", row_multiple=64)
    assert gaps["tokens_compared"] == 75
    assert gaps["served_gap_max"] < 1e-4
    counts = dec.last_chunk_counts
    # 3 live rows x 2 choices x 4 layers x 4 steps, every expert held
    assert counts["moe_assignments"] == 96
    assert counts["moe_local_assignments"] == 96
    assert 4 * 4 * 2 <= counts["moe_experts_hit"] <= 4 * 4 * 6


def test_rings_through_the_decode_kernel_and_prompts_through_flash():
    # a head size of whole lanes: rings of 512 rows (a window of 8 in
    # blocks of 256, and one more) beside a bank of 768, both through
    # the block-walking kernel; buckets of 128 through the flash
    # kernel (its blocks set to 128), the left pad rotated behind the
    # prompt.  The longer request is prefilled to row 500 and decodes
    # across the wrap at 512
    cfg, model, params = build(
        seed=7, num_attention_heads=2, num_key_value_heads=1, head_dim=128,
        num_hidden_layers=2,
        layer_types=["sliding_attention", "full_attention"],
        mlp_layer_types=["sparse"] * 2,
        program=dict(block_q=128, block_k=128))
    dec = tr.SlotDecoder(model, params, 2, 24, cache_len=768, chunk_size=4,
                         pad_multiple=128)
    assert dec.attn_impl == "kernel"
    assert dec._layer_rows == [512, 768] and dec._layer_blocks == [256, 256]
    assert dec.prefill_attn(512) == "flash" and dec.prefill_attn(64) == "dot"
    # a prompt of 500 in its bucket of 512 and 3 tokens on: the query
    # sits at row 514; the ring layer reads the two blocks its window
    # 507..514 straddles, the full layer the three blocks from the pad
    # on; the idle lane one block each
    assert dec._layer_reads([(500, 3)]) == [512 + 256, 768 + 256]
    assert dec.kv_read_tokens([(500, 3)]) == (896, 2 * 640)
    samples = serve(
        dec, [tokens_of(500, seed=2), tokens_of(130, seed=3)], 5)
    gaps = runner.served_gaps(cfg, 7, samples, "float32", row_multiple=256)
    assert gaps["tokens_compared"] == 42
    assert gaps["served_gap_max"] < 1e-4


def test_what_rings_do_not_serve_keeps_whole_banks():
    # prefix continuation and a draft's verify block attend over the
    # bank behind a span: those decoders keep whole banks on every
    # layer, as before
    from tensorflowonspark_tpu.prefix_cache import PrefixCache

    _, model, params = build()
    dec = tr.SlotDecoder(model, params, 2, 8, cache_len=64,
                         prefix_cache=PrefixCache(block_tokens=8))
    assert not dec.model.cfg.fresh_prompts
    assert dec._layer_rows == [64] * 4
    assert dec.kv_bank_bytes()["ring"] == 0


@pytest.mark.parametrize("fault", sorted(faults_swa_moe.FAULTS))
def test_a_planted_fault_reads_not_correct(monkeypatch, fault):
    # each fault is planted in the program alone; the gap it opens is
    # far over what the order of float32 sums leaves
    monkeypatch.setattr(
        tr.TransformerConfig, "window_of", tr.TransformerConfig.window_of)
    monkeypatch.setattr(
        tr.TransformerConfig, "rope_of", tr.TransformerConfig.rope_of)
    monkeypatch.setattr(tr, "ring_rows", tr.ring_rows)
    monkeypatch.setattr(moe_ops, "sigmoid_topk", moe_ops.sigmoid_topk)
    faults_swa_moe.plant(fault)
    cfg, model, params = build(seed=5)
    dec = tr.SlotDecoder(model, params, 2, 32, cache_len=96, chunk_size=4,
                         pad_multiple=8)
    samples = serve(dec, [tokens_of(n, seed=n) for n in (19, 33)], 4)
    gaps = runner.served_gaps(cfg, 5, samples, "float32", row_multiple=64)
    assert gaps["served_gap_max"] > 1e-3, gaps


def test_one_continuous_predict_rows_job_end_to_end_with_its_counters():
    from tensorflowonspark_tpu import serving, serving_engine, telemetry

    cfg, model, params = build(seed=6)

    class Plan:
        answer_len = np.array([16])
        prompt_len = np.array([40])

    predict = tr.serving_builder(params, dict(
        runner.program_config(cfg, Plan), pad_multiple=8, chunk_size=4))
    prompts = [tokens_of(n, seed=100 + n) for n in (7, 30, 16, 22)]
    rows = [{"prompt": p, "max_new": 12 + i} for i, p in enumerate(prompts)]
    tracer = telemetry.get_tracer()
    tracer.clear()  # a full ring does not grow: read this job's spans only
    stats = {}
    outs = list(serving.predict_rows(
        predict, rows,
        {"prompt": "tokens", "max_new": serving_engine.BUDGET_INPUT},
        batch_size=3, schedule="continuous", on_error="raise", stats=stats))
    assert [int(o["generated_len"]) for o in outs] == [12, 13, 14, 15]
    samples = [(p, np.asarray(o["generated"][:int(o["generated_len"])]))
               for p, o in zip(prompts, outs)]
    gaps = runner.served_gaps(cfg, 6, samples, "float32", row_multiple=64)
    assert gaps["served_gap_max"] < 1e-4
    assert stats["attn"] == "dot"
    # banks of 40 + 16 rows: three rings of 8 beside one whole bank
    row = 2 * 2 * 16 * 4
    assert stats["kv_bank_bytes_ring"] == 3 * 3 * 8 * row
    assert stats["kv_bank_bytes_whole"] == 3 * 56 * row
    assert stats["kv_bank_bytes_unringed"] == 3 * 4 * 56 * row
    gauges = telemetry.get_registry().snapshot()["gauges"]
    assert gauges["serving.kv_bank_bytes_ring"] == 3 * 3 * 8 * row
    spans = tracer.spans()
    chunks = [s["attrs"] for s in spans if s["name"] == "engine.chunk"]
    assert chunks
    for c in chunks:
        # the mean over the layers, and their sum
        assert c["kv_bank_tokens"] == 3 * (3 * 8 + 56) // 4
        assert c["attn_read_tokens"] == 3 * (3 * 8 + 56)
        assert c["attn_context_tokens"] > 0
        assert c["moe_assignments"] == c["live"] * 2 * 4 * 4
        assert c["moe_local_assignments"] == c["moe_assignments"]
        assert 0 < c["moe_experts_hit"] <= 8 * 4 * 4
    prefills = [s["attrs"] for s in spans if s["name"] == "prefill"]
    assert sorted(p["bucket"] for p in prefills) == [8, 16, 24, 32]
    assert {p["attn"] for p in prefills} == {"dot"}
    counters = telemetry.get_registry().snapshot()["counters"]
    for name in ("moe_assignments", "moe_experts_hit", "attn_read_tokens"):
        assert counters["serving." + name] > 0
