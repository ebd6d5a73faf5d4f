"""Window and full attention layers side by side, a RoPE a layer type
(YaRN on the full ones), the q/k norm and softmax-routed experts
through the never-a-drop share layer: the program against the plain
reference (``benchmarks/reference/swa_moe.py``) at small widths, seeded
weights, float32 — logits, not tokens.  The serving path (rings,
flash prefill, the engine): tests/test_swa_moe_serving.py.

Tolerances.  Program and reference both run float32 at matmul
precision ``highest``; what separates them is the order of float32
sums (blocked queries, sorted expert rows), which at these widths
moves a logit of size ~3 by a few 1e-6.  The limit is 2e-5 everywhere
a logit is compared; a window off by one key, a ring one row short, a
RoPE of the wrong type or an unnormalised gate moves logits by 1e-3
and more (the planted faults of tests/test_swa_moe_serving.py).
"""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights_swa_moe as weights
from benchmarks.reference import swa_moe as ref
from benchmarks.runners import serve_swa_moe as runner
from tensorflowonspark_tpu.models import moe
from tensorflowonspark_tpu.models import transformer as tr
from tensorflowonspark_tpu.ops import paged_attention as pa

TOL = 2e-5
HERE = os.path.dirname(os.path.abspath(__file__))
#: YaRN with a small original length, so that test positions pass it
TINY_ROPE = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 10000.0, "factor": 4,
        "original_max_position_embeddings": 16, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 0.1 * math.log(4) + 1},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000.0},
}


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def model_dict(**over):
    """The published configuration's keys at test widths: 4 layers
    (sliding, sliding, sliding, full), a window of 8, 8 experts of
    which a token takes 2."""
    with open(os.path.join(
            HERE, "..",
            "benchmarks/configs/mellum2-12b-a2.5b.serve.json")) as f:
        cfg = json.load(f)
    cfg.update(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, intermediate_size=128, moe_intermediate_size=32,
        num_experts=8, num_experts_per_tok=2, vocab_size=256,
        num_hidden_layers=4,
        layer_types=["sliding_attention"] * 3 + ["full_attention"],
        mlp_layer_types=["sparse"] * 4, sliding_window=8,
        rope_parameters=TINY_ROPE, max_position_embeddings=1024,
        dtype="float32", cache_dtype="float32", program={},
    )
    cfg.update(over)
    return cfg


def config_of(cfg, **over):
    class Plan:
        answer_len = np.array([16])
        prompt_len = np.array([80])

    pc = runner.program_config(cfg, Plan)
    for k in ("mode", "max_new_tokens", "max_prompt_len", "pad_multiple"):
        pc.pop(k, None)
    pc.update(over)
    return tr.TransformerConfig(**pc)


def build(seed=3, program=None, **over):
    cfg = model_dict(**over)
    params = weights.make_params(cfg, seed, "float32")
    return cfg, tr.Transformer(config_of(cfg, **(program or {}))), params


def tokens_of(n, seed=1):
    return np.random.default_rng(seed).integers(1, 256, n).astype(np.int32)


def test_the_program_s_full_forward_is_the_reference_s():
    cfg, model, params = build()
    tokens = tokens_of(48)
    got = model.apply({"params": params}, tokens[None])[0]
    want = ref.forward(jnp.asarray(tokens), params, cfg)
    assert float(jnp.max(jnp.abs(want))) > 1.0
    assert float(jnp.max(jnp.abs(got - want))) < TOL
    # the runner's reference, a layer's weights at a time
    rows = runner.reference_logits(
        cfg, 3, runner.reference_hidden(cfg, 3, tokens, "float32"),
        "float32")
    assert float(jnp.max(jnp.abs(rows - want))) < TOL


def test_each_mechanism_moves_the_reference_s_logits():
    # the comparison above would not notice a mechanism the reference
    # lacked too: every one of them changes the reference's own answer
    cfg, _, params = build()
    tokens = jnp.asarray(tokens_of(48))
    want = ref.forward(tokens, params, cfg)

    def moved(**over):
        return float(jnp.max(jnp.abs(
            ref.forward(tokens, params, dict(cfg, **over)) - want)))

    assert moved(sliding_window=9) > 1e-3
    assert moved(layer_types=["sliding_attention"] * 4) > 1e-3
    assert moved(rope_parameters=dict(
        TINY_ROPE, full_attention=TINY_ROPE["sliding_attention"])) > 1e-3
    assert moved(norm_topk_prob=False) > 1e-3
    assert moved(num_experts_per_tok=3) > 1e-3


def test_yarn_frequencies_are_the_published_blend():
    got = tr.yarn_inv_freq(128, 5e5, 16.0, 8192, 32.0, 1.0)
    # by hand: c(n) = 128 ln(8192 / (2 pi n)) / (2 ln 5e5)
    c = lambda n: 128 * math.log(8192 / (2 * math.pi * n)) / (  # noqa: E731
        2 * math.log(5e5))
    low, high = math.floor(c(32)), math.ceil(c(1))
    assert (low, high) == (18, 35)
    i = np.arange(64)
    extrap = 5e5 ** (-2 * i / 128)
    np.testing.assert_allclose(got[:low + 1], extrap[:low + 1], rtol=1e-6)
    np.testing.assert_allclose(got[high:], extrap[high:] / 16, rtol=1e-6)
    mid = (low + high) // 2
    ramp = (mid - low) / (high - low)
    np.testing.assert_allclose(
        got[mid], extrap[mid] / 16 * ramp + extrap[mid] * (1 - ramp),
        rtol=1e-6)
    # the reference computes its own, from the configuration's keys
    with open(os.path.join(
            HERE, "..",
            "benchmarks/configs/mellum2-12b-a2.5b.serve.json")) as f:
        published = json.load(f)
    want, factor = ref.inv_freq(published, "full_attention")
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert factor == pytest.approx(0.1 * math.log(16) + 1, rel=1e-12)
    cfg = config_of(dict(published, dtype="bfloat16"))
    theta, freq, mult = cfg.rope_of(3)
    np.testing.assert_allclose(freq, want, rtol=1e-6)
    assert (theta, mult) == (5e5, factor)
    assert cfg.rope_of(0) == (5e5, None, 1.0)
    assert [cfg.window_of(i) for i in range(8)] == [
        1024, 1024, 1024, 0] * 2


def test_the_defaults_are_the_programs_of_before():
    cfg = tr.TransformerConfig(attention_window=24, rope_theta=1e4)
    assert [cfg.window_of(i) for i in range(4)] == [24] * 4
    assert cfg.rope_of(2) == (1e4, None, 1.0)
    assert not (cfg.qk_norm or cfg.fresh_prompts or cfg.layer_types)
    assert cfg.ffn_kind(0) == "dense"
    soft = tr.TransformerConfig(num_experts=4, expert_dispatch="dropless")
    assert soft.ffn_kind(0) == "moe"  # MoEMLP as it was
    share = tr.TransformerConfig(num_experts=4, expert_dispatch="share")
    assert share.ffn_kind(0) == "sigmoid_moe"
    # no q/k norm, no per-type leaves in a default model's tree
    tree = jax.eval_shape(lambda: tr.Transformer(
        tr.TransformerConfig(num_layers=1)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)))["params"]
    assert sorted(tree["block_0"]["attn"]) == ["k", "out", "q", "v"]
    x = jnp.ones((1, 3, 2, 8))
    pos = jnp.arange(3)[None]
    np.testing.assert_array_equal(
        tr.rope(x, pos, 1e4), tr.rope(x, pos, 1e4, False, None, 1.0))
    with pytest.raises(ValueError, match="layer_types names 3 layers"):
        tr.TransformerConfig(num_layers=4, layer_types=["full_attention"] * 3)


@pytest.mark.parametrize("rows", [7, 600])
def test_softmax_scores_through_the_share_layer_are_the_reference_s_experts(
        rows):
    # a decode step's handful of rows (tiles of 16) and a prompt (one
    # routed span): the same layer under softmax scores, no bias, no
    # shared expert, every expert held
    cfg = model_dict()
    p = weights.block_params(cfg, weights.seed_key(5), 0, jnp.float32)["moe"]
    layer = moe.SigmoidMoE(
        router_experts=8, num_experts=8, mlp_dim=32, embed_dim=64, k=2,
        shared_experts=0, dtype="float32", scoring="softmax")
    x = jax.random.normal(jax.random.PRNGKey(rows), (1, rows, 64))
    got, sown = layer.apply({"params": p}, x, mutable=["moe_stats"])
    want = ref.experts(x[0], p, cfg, "f32")
    assert float(jnp.max(jnp.abs(got[0] - want))) < TOL
    assert "router_bias" not in jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0), x))["params"]
    chose = sown["moe_stats"]["held_choices"][0]
    assert chose.shape == (rows, 8) and int(chose.sum()) == rows * 2
    weight = np.asarray(ref.route(x[0], p, cfg, "f32"))
    np.testing.assert_array_equal(np.asarray(chose) > 0, weight > 0)
    np.testing.assert_allclose(weight.sum(-1), 1.0, rtol=1e-6)


def test_a_ring_is_the_window_in_whole_blocks_and_one_more():
    served = tr.TransformerConfig(
        head_dim=128, layer_types=["sliding_attention", "full_attention"],
        num_layers=2, sliding_window=1024, fresh_prompts=True)
    assert tr.ring_rows(served, 1024) == 1280
    assert tr.ring_rows(served, 1000) == 1280
    assert tr.ring_rows(served, 1025) == 1536
    assert [tr.bank_rows(served, i, 10240) for i in (0, 1)] == [1280, 10240]
    assert tr.bank_rows(served, 0, 1024) == 1024  # shorter than its ring
    # no tile-legal block (a test's head size): the window itself
    assert tr.ring_rows(tr.TransformerConfig(head_dim=16), 8) == 8
    # a rule of the program: one window over banks of 1536 changes
    # nothing, and rings at longer banks
    one = tr.TransformerConfig(head_dim=128, attention_window=4096,
                               fresh_prompts=True)
    assert tr.bank_rows(one, 0, 1536) == 1536
    assert tr.bank_rows(one, 0, 10240) == 4352
    # only a decoder whose every span is a fresh prompt asks for rings
    import dataclasses

    assert tr.bank_rows(dataclasses.replace(
        served, fresh_prompts=False), 0, 10240) == 10240
    model = tr.Transformer(dataclasses.replace(
        served, num_heads=2, num_kv_heads=1, embed_dim=32, vocab_size=64))
    cache = tr.init_cache(model, 3, cache_len=2048)
    assert cache["block_0"]["attn"]["cached_key"].shape == (3, 1280, 1, 128)
    assert cache["block_1"]["attn"]["cached_value"].shape == (
        3, 2048, 1, 128)


def test_a_prompt_goes_through_flash_when_the_shapes_say_so():
    base = dict(head_dim=128, fresh_prompts=True)
    assert tr.prefill_flash(tr.TransformerConfig(**base), 8192)
    assert tr.prefill_flash(tr.TransformerConfig(**base), 1024)
    # shorter than a block, or not a whole number of them: the einsums
    assert not tr.prefill_flash(tr.TransformerConfig(**base), 512)
    assert not tr.prefill_flash(tr.TransformerConfig(**base), 1536)
    assert tr.prefill_flash(tr.TransformerConfig(
        block_q=128, block_k=128, **base), 384)
    for off in (dict(fresh_prompts=False), dict(head_dim=64),
                dict(cache_dtype="int8"), dict(mesh=object())):
        assert not tr.prefill_flash(
            tr.TransformerConfig(**dict(base, **off)), 8192)


@pytest.mark.parametrize("cache", ["bfloat16", "int8"])
def test_the_decode_kernel_walks_a_ring_s_live_blocks_across_the_wrap(cache):
    # 3 slots over rings of 384 rows (3 blocks of 128), window 200:
    # queries at positions before, at and far past the wrap, one with a
    # pad region; against plain attention over the positions the ring
    # holds
    b, h, hkv, d, rows, window = 3, 4, 2, 128, 384, 200
    rng = np.random.default_rng(0)
    positions = np.array([150, 383 + 128, 1000])
    pad = np.array([20, 0, 0])
    last = int(positions.max()) + 1
    keys = rng.standard_normal((b, last, hkv, d)).astype(np.float32)
    vals = rng.standard_normal((b, last, hkv, d)).astype(np.float32)
    q = rng.standard_normal((b, h, d)).astype(np.float32)
    ring_k = np.zeros((b, rows, hkv, d), np.float32)
    ring_v = np.zeros((b, rows, hkv, d), np.float32)
    for s in range(b):
        for p in range(positions[s] + 1):  # later positions overwrite
            ring_k[s, p % rows], ring_v[s, p % rows] = keys[s, p], vals[s, p]
    scales = {}
    if cache == "int8":
        from tensorflowonspark_tpu import quantize as qz

        ring_k, ks = qz.quantize_leaf(jnp.asarray(ring_k), reduce_axes=(3,))
        ring_v, vs = qz.quantize_leaf(jnp.asarray(ring_v), reduce_axes=(3,))
        scales = dict(k_scale=ks, v_scale=vs)
    got = pa.bank_attention(
        jnp.asarray(q), jnp.asarray(ring_k), jnp.asarray(ring_v),
        jnp.asarray(positions, jnp.int32), jnp.asarray(pad, jnp.int32),
        window=window, ring=True, **scales)
    for s in range(b):
        lo = max(pad[s], positions[s] + 1 - window)
        k = keys[s, lo:positions[s] + 1]
        v = vals[s, lo:positions[s] + 1]
        for head in range(h):
            logits = k[:, head // 2] @ q[s, head] * d ** -0.5
            w = np.exp(logits - logits.max())
            want = (w / w.sum()) @ v[:, head // 2]
            np.testing.assert_allclose(
                got[s, head], want, atol=5e-2 if cache == "int8" else 1e-5)
    with pytest.raises(ValueError, match="holds a window of at most 256"):
        pa.bank_attention(
            jnp.asarray(q), jnp.asarray(ring_k), jnp.asarray(ring_v),
            jnp.asarray(positions, jnp.int32), jnp.asarray(pad, jnp.int32),
            window=300, ring=True, **scales)
