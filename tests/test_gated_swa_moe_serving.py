"""Query heads by layer type, the per-head gate, a partial rotation and a
held share of experts with a shared one through the SERVING path —
``SlotDecoder`` with rings beside whole banks, prompts admitted through
the flash kernel, ``ServingEngine``, ``predict_rows(schedule=
"continuous")`` — held against the plain reference
(``benchmarks/reference/gated_swa_moe.py``) at small widths, seeded
weights, float32: by the gap of each served token's reference logit
below the reference's best over the reference's FULL forward, not by
tokens.  The model itself against the reference:
tests/test_gated_swa_moe.py.

Tolerances.  As there: float32 at ``highest`` on both sides leaves the
order of float32 sums, a few 1e-6 on logits of size ~4, so a served
token's gap is nought but for a near-tie: under 1e-4.  Each planted
fault — the gate skipped, the full layers rotated whole, the routed
scaling left at 1, the shared expert left out, a window of W + 1 —
reads over 1e-3.
"""

import numpy as np
import pytest

from benchmarks.runners import serve_gated_swa_moe as runner
from benchmarks.tests import faults_gated_swa_moe
from tensorflowonspark_tpu.models import moe
from tensorflowonspark_tpu.models import transformer as tr

from test_chip_lowering import _v5e, mosaic  # noqa: F401 - a fixture
from test_gated_swa_moe import build, highest, tokens_of  # noqa: F401
from test_swa_moe_serving import serve


def test_slots_at_mixed_positions_past_the_window_and_the_wrap():
    # rings of 8 rows (the window: no tile-legal block at this head
    # size) on the three sliding layers of 9 heads, whole banks on the
    # two full ones of 6; three requests of different lengths, one lane
    # idle, 25 tokens each: every one decodes past the window AND past
    # a ring's wrap, the two longer ones are prefilled past it too
    cfg, model, params = build(seed=5)
    dec = tr.SlotDecoder(model, params, 4, 32, cache_len=96, chunk_size=4,
                         pad_multiple=8)
    assert dec.model.cfg.fresh_prompts and dec.attn_impl == "dot"
    assert dec._layer_rows == [96, 8, 8, 8, 96]
    # masked einsums read every bank whole: 4 slots, by kind
    assert dec.kv_read_by_kind([(5, 3)]) == {
        "ring": 4 * 3 * 8, "whole": 4 * 2 * 96}
    # a prompt's pairs times heads: the full layers causal over 16, the
    # sliding ones inside their window of 8
    assert dec.prefill_pairs(16) == {
        "full": 2 * 6 * (16 * 17 // 2),
        "window": 3 * 9 * (8 * 9 // 2 + 8 * 8)}
    samples = serve(dec, [tokens_of(n, seed=n) for n in (5, 19, 33)], 6)
    gaps = runner.served_gaps(cfg, 5, samples, "float32", row_multiple=64)
    assert gaps["tokens_compared"] == 75
    assert gaps["served_gap_max"] < 1e-4
    counts = dec.last_chunk_counts
    # 3 live rows x 3 choices x 4 sparse layers x 4 steps, of which
    # those landing on the 4 held experts (2-5 of 8) are local
    assert counts["moe_assignments"] == 144
    assert 0 < counts["moe_local_assignments"] < 144
    assert 0 < counts["moe_experts_hit"] <= 4 * 4 * 4


def test_rings_through_the_decode_kernel_and_prompts_through_flash():
    # a head size of whole lanes: a full layer of 2 query heads (group
    # 2) beside a sliding one of 3 (group 3) over one key/value head,
    # rings of 512 rows (a window of 8 in blocks of 256, and one more)
    # beside a bank of 768, both through the block-walking kernel;
    # buckets of 128 through the flash kernel (its blocks set to 128).
    # The longer request is prefilled to row 500 and decodes across the
    # wrap at 512
    cfg, model, params = build(
        seed=7, num_attention_heads=2, num_attention_heads_per_layer=[2, 3],
        num_key_value_heads=1, head_dim=128, num_hidden_layers=2,
        layer_types=["full_attention", "sliding_attention"],
        mlp_layer_types=["dense", "sparse"],
        program=dict(block_q=128, block_k=128))
    dec = tr.SlotDecoder(model, params, 2, 24, cache_len=768, chunk_size=4,
                         pad_multiple=128)
    assert dec.attn_impl == "kernel"
    assert dec._layer_rows == [768, 512] and dec._layer_blocks == [256, 256]
    assert dec.prefill_attn(512) == "flash" and dec.prefill_attn(64) == "dot"
    samples = serve(
        dec, [tokens_of(500, seed=2), tokens_of(130, seed=3)], 5)
    gaps = runner.served_gaps(cfg, 7, samples, "float32", row_multiple=256)
    assert gaps["tokens_compared"] == 42
    assert gaps["served_gap_max"] < 1e-4


@pytest.mark.parametrize("fault", sorted(faults_gated_swa_moe.FAULTS))
def test_a_planted_fault_reads_not_correct(monkeypatch, fault):
    # each fault is planted in the program alone; the gap it opens is
    # far over what the order of float32 sums leaves
    monkeypatch.setattr(tr.Attention, "__call__", tr.Attention.__call__)
    monkeypatch.setattr(moe.SigmoidMoE, "__call__", moe.SigmoidMoE.__call__)
    monkeypatch.setattr(
        tr.TransformerConfig, "window_of", tr.TransformerConfig.window_of)
    monkeypatch.setattr(
        tr.TransformerConfig, "rotary_of", tr.TransformerConfig.rotary_of)
    faults_gated_swa_moe.plant(fault)
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
    spans = tracer.spans()
    chunks = [s["attrs"] for s in spans if s["name"] == "engine.chunk"]
    assert chunks
    for c in chunks:
        # banks of 40 + 16 rows: three rings of 8 beside two whole
        # banks, each read whole by the masked einsums
        assert c["kv_read_ring"] == 3 * 3 * 8
        assert c["kv_read_whole"] == 3 * 2 * 56
        assert c["attn_read_tokens"] == c["kv_read_ring"] + c["kv_read_whole"]
        assert c["moe_assignments"] == c["live"] * 3 * 4 * 4
        assert 0 < c["moe_local_assignments"] < c["moe_assignments"]
    prefills = [s["attrs"] for s in spans if s["name"] == "prefill"]
    assert sorted(p["bucket"] for p in prefills) == [8, 16, 24, 32]
    for p in prefills:
        n = p["bucket"]
        assert p["attn_pairs_full"] == 2 * 6 * n * (n + 1) // 2
        assert p["attn_pairs_window"] == 3 * 9 * (36 + (n - 8) * 8)
    assert stats["kv_read_ring"] == 3 * 3 * 8


@pytest.mark.parametrize("heads,rows,window,ring", [
    (72, 768, 512, True), (48, 20480, 0, False)])
def test_the_decode_kernel_compiles_for_v5e_at_the_cell_s_groups(
        mosaic, heads, rows, window, ring):
    """The cell's decode step over 49 slots: query groups of 9 over
    rings of 768 rows on the sliding layers, groups of 6 over whole
    banks of 20480 on the full ones, 8 key/value heads of 128 — Mosaic
    takes the kernel at both, and no bank is copied."""
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.ops import paged_attention as pa

    dev = _v5e()
    b, hkv, d = 49, 8, 128

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    bank = arg((b, rows, hkv, d), jnp.bfloat16)
    # at the program's own precision (this file's fixture asks float32
    # products of every matmul, which Mosaic refuses on bf16 operands)
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(lambda q, k, v, pos, pad: pa.bank_attention(
            q, k, v, pos, pad, window=window, ring=ring)).lower(
                arg((b, heads, d), jnp.bfloat16), bank, bank,
                arg((b,), jnp.int32), arg((b,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < (
        b * rows * hkv * d * 2) // 8


def test_a_gated_full_layer_prefills_16384_for_v5e_with_scores_in_vmem(
        mosaic):
    """One full layer of the cell (48 query heads over 8, half-rotary
    YaRN, the per-head gate) prefilling the longest bucket into a whole
    bank of 20480: through the flash kernel, so no float32 score tensor
    ``[48, 16384, 20480]`` is ever in HBM."""
    import jax
    import jax.numpy as jnp

    from test_gated_swa_moe import config_of, published

    dev = _v5e()
    cfg = config_of(dict(published(), dtype="bfloat16"), fresh_prompts=True)
    assert cfg.heads_of(0) == 48 and cfg.rotary_of(0) == 64
    assert tr.bank_rows(cfg, 0, 20480) == 20480
    assert tr.prefill_flash(cfg, 16384)
    attn = tr.Attention(cfg, layer=0)
    x = jnp.zeros((1, 16384, cfg.embed_dim), jnp.bfloat16)
    pos = jnp.zeros((1, 16384), jnp.int32)
    shapes = jax.eval_shape(
        lambda: attn.init(jax.random.PRNGKey(0), x[:, :1], pos[:, :1]))
    assert shapes["params"]["gate"]["kernel"].shape == (3072, 48)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, jnp.bfloat16 if a.dtype == jnp.float32 else a.dtype,
            sharding=dev), tree)

    bank = jax.ShapeDtypeStruct((1, 20480, 8, 128), jnp.bfloat16,
                                sharding=dev)
    cache = {"cached_key": bank, "cached_value": bank}

    def prefill(params, cache, x, pos, pad):
        return attn.apply({"params": params, "cache": cache}, x, pos,
                          decode=True, pad_start=pad, mutable=["cache"])

    with jax.default_matmul_precision("default"):
        compiled = jax.jit(prefill, donate_argnums=(1,)).lower(
            on_chip(shapes["params"]), cache, on_chip(x), on_chip(pos),
            jax.ShapeDtypeStruct((1,), jnp.int32, sharding=dev)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9
