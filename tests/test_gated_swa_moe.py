"""Query heads that differ by layer type, a sigmoid gate on each head's
output, a partial rotation under YaRN on the full layers, and a held
share of softmax-routed experts beside a shared expert after a leading
dense layer: the program against the plain reference
(``benchmarks/reference/gated_swa_moe.py``) at small widths, seeded
weights, float32 — logits, not tokens.  The serving path (rings, flash
prefill, the engine): tests/test_gated_swa_moe_serving.py.  And the
configurations that use none of it: their programs are the parent
commit's, character for character, traced as often.

Tolerances.  Program and reference both run float32 at matmul
precision ``highest``; what separates them is the order of float32
sums (blocked queries, sorted expert rows), which at these widths
moves a logit of size ~4 by a few 1e-6.  The limit is 2e-5 everywhere
a logit is compared; each mechanism this file checks moves logits by
1e-3 and more.
"""

import hashlib
import json
import math
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights_gated_swa_moe as weights
from benchmarks.reference import gated_swa_moe as ref
from benchmarks.runners import serve_gated_swa_moe as runner
from tensorflowonspark_tpu.models import moe
from tensorflowonspark_tpu.models import transformer as tr

TOL = 2e-5
HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(
    HERE, "..", "benchmarks/configs/laguna-s-2.1.serve-ep8.json")
#: YaRN over half of each head on the full layers, with a small
#: original length so that test positions pass it; the default RoPE
#: over the whole head on the sliding ones
TINY_ROPE = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 10000.0, "factor": 4,
        "original_max_position_embeddings": 16, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 0.1 * math.log(4) + 1,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {
        "rope_type": "default", "rope_theta": 10000.0,
        "partial_rotary_factor": 1},
}


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def published():
    with open(CONFIG) as f:
        return json.load(f)


def model_dict(**over):
    """The published configuration's keys at test widths: 5 layers
    (full of 6 heads and dense, sliding of 9 heads x 3, full of 6), 3
    key/value heads, a window of 8, experts 2-5 held of 8 of which a
    token takes 3, a shared expert."""
    cfg = published()
    cfg.update(
        hidden_size=64, num_key_value_heads=3, head_dim=16,
        num_attention_heads=6,
        num_attention_heads_per_layer=[6, 9, 9, 9, 6],
        intermediate_size=128, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, num_experts=4,
        expert_share={"first": 2, "held": 4, "of": 8},
        num_experts_per_tok=3, vocab_size=256, sliding_window=8,
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
    # the tree holds each layer's own heads and its gate
    attn = [params["block_%d" % i]["attn"] for i in range(5)]
    assert [a["q"]["kernel"].shape[1] for a in attn] == [6, 9, 9, 9, 6]
    assert [a["gate"]["kernel"].shape for a in attn] == [
        (64, 6), (64, 9), (64, 9), (64, 9), (64, 6)]
    assert "mlp" in params["block_0"] and "moe" in params["block_1"]


def test_each_mechanism_moves_the_reference_s_logits():
    # the comparison above would not notice a mechanism the reference
    # lacked too: every one of them changes the reference's own answer
    cfg, _, params = build()
    tokens = jnp.asarray(tokens_of(48))
    want = ref.forward(tokens, params, cfg)

    def moved(p=params, **over):
        return float(jnp.max(jnp.abs(
            ref.forward(tokens, p, dict(cfg, **over)) - want)))

    assert moved(gating="") > 1e-3
    full_turn = {k: dict(v, partial_rotary_factor=1)
                 for k, v in TINY_ROPE.items()}
    assert moved(rope_parameters=full_turn) > 1e-3
    assert moved(moe_routed_scaling_factor=1.0) > 1e-3
    unshared = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf * 0 if "shared_wo" in jax.tree_util.keystr(
            path) else leaf, params)
    assert moved(unshared) > 1e-3
    # the sliding layers' three extra heads a key/value head matter:
    # the same weights cut to 6 heads on every layer answer otherwise
    six = jax.tree.map(lambda x: x, params)
    for i in (1, 2, 3):
        attn = dict(six["block_%d" % i]["attn"])
        # heads are grouped by their key/value head: keep 2 of each 3
        keep = np.asarray([h for h in range(9) if h % 3 != 2])
        attn["q"] = {"kernel": attn["q"]["kernel"][:, keep]}
        attn["gate"] = {"kernel": attn["gate"]["kernel"][:, keep]}
        attn["out"] = {"kernel": attn["out"]["kernel"][keep]}
        six["block_%d" % i] = dict(six["block_%d" % i], attn=attn)
    assert moved(six, num_attention_heads_per_layer=[6] * 5) > 1e-3


def test_yarn_over_the_rotated_width_and_heads_by_layer():
    cfg = config_of(dict(published(), dtype="bfloat16"))
    assert [cfg.heads_of(i) for i in range(5)] == [48, 72, 72, 72, 48]
    assert [cfg.rotary_of(i) for i in range(5)] == [64, 128, 128, 128, 64]
    assert [cfg.window_of(i) for i in range(5)] == [0, 512, 512, 512, 0]
    theta, freq, factor = cfg.rope_of(0)
    want, ref_factor = ref.inv_freq(published(), "full_attention")
    assert freq.shape == (32,) and want.shape == (32,)
    np.testing.assert_allclose(freq, want, rtol=1e-6)
    np.testing.assert_allclose(
        freq, tr.yarn_inv_freq(64, 5e5, 128.0, 8192, 32.0, 1.0), rtol=0)
    assert (theta, factor) == (5e5, ref_factor) == (5e5, 1.4852030263919618)
    assert cfg.rope_of(1) == (1e4, None, 1.0)
    assert cfg.gating == "per-head" and cfg.routed_scaling == 2.5
    assert (cfg.num_experts, cfg.router_experts, cfg.shared_experts) == (
        32, 256, 1)
    # a partial rotation leaves the unrotated dimensions as they were
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 16))
    pos = jnp.arange(5)[None] + 3
    out = tr.rope(x, pos, 1e4, rotary=8)
    np.testing.assert_array_equal(out[..., 8:], x[..., 8:])
    np.testing.assert_allclose(out[..., :8], tr.rope(x[..., :8], pos, 1e4),
                               rtol=0, atol=0)
    np.testing.assert_array_equal(tr.rope(x, pos, 1e4, rotary=16),
                                  tr.rope(x, pos, 1e4))


def test_the_new_keys_default_to_the_programs_of_before():
    cfg = tr.TransformerConfig(num_layers=3, num_heads=4, head_dim=16)
    assert [cfg.heads_of(i) for i in range(3)] == [4] * 3
    assert [cfg.rotary_of(i) for i in range(3)] == [16] * 3
    assert cfg.gating == "" and cfg.num_attention_heads_per_layer == ()
    tree = jax.eval_shape(lambda: tr.Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)))["params"]
    assert sorted(tree["block_0"]["attn"]) == ["k", "out", "q", "v"]
    with pytest.raises(ValueError, match="needs num_kv_heads"):
        tr.TransformerConfig(num_layers=2,
                             num_attention_heads_per_layer=[4, 6])
    with pytest.raises(ValueError, match="names 1 layers"):
        tr.TransformerConfig(num_layers=2, num_kv_heads=2,
                             num_attention_heads_per_layer=[4])
    with pytest.raises(ValueError, match="gating 'per-token'"):
        tr.TransformerConfig(gating="per-token")


#: sha256 of the lowered StableHLO of three tiny configurations that set
#: none of the new keys — one window on every layer, window and full
#: layers with YaRN, q/k norms and softmax-routed experts through the
#: share layer over rings, and paged banks — each as a training
#: forward, a prefill into the cache and one decode step of two slots,
#: and the traces JAX reported while lowering the nine, all recorded
#: from the parent commit (d07da7b) in a fresh process
PARENT_PROGRAMS = {
    "dense_window": {
        "forward": "98bf99bfd28b6b079a04009ef6577cbb76736f0ff5e2a67c202ee57aebf3d1fd",
        "prefill": "49756dc6dbbf27d3dc53cf366681d6faf752a12ee988b84be37c8954a9007c5f",
        "step": "bc5e2a9b7118854d9fad6a0441cc9813647b31b75bc0f7e2ea9e3b0122984474",
        "traces": 1032,
    },
    "swa_moe": {
        "forward": "5eb123a24e1fa6668fc7114ecf1e96bd3f9bb704fb1ee414c46d249e5ce57eb4",
        "prefill": "8ad7103145830aa68c16e9ab188e05627e66bcef196652cb034f730c9a8f4ae6",
        "step": "e22cc1fb9c969a0d7bb0dad9bc1721889251ef4c4f72f37aedc3b7e49bfe3839",
        "traces": 2089,
    },
    "paged": {
        "forward": "b3c4d7d0a4a9a67f1f03842d30d46d9f188e199b068efc1e7636df778332b8f2",
        "prefill": "e7a14a7542b30e9014424d4034e6479fb4db4c696c8315d917ec3c7bf5227e3a",
        "step": "3b8e5645dca5a993bacb811639e3d5107bc561a8ccb0eb87af2abcc15742ed58",
        "traces": 1016,
    },
}
_TINY = dict(vocab_size=64, num_layers=2, num_heads=4, num_kv_heads=2,
             head_dim=16, embed_dim=32, mlp_dim=64, max_seq_len=64,
             dtype="float32")
PINNED_CONFIGS = {
    "dense_window": dict(_TINY, attention_window=8),
    "swa_moe": dict(
        _TINY, layer_types=["sliding_attention", "full_attention"],
        sliding_window=8, qk_norm=True,
        layer_rope={
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 1e4, "factor": 4,
                "original_max_position_embeddings": 16, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.1386},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 1e4}},
        mlp_layer_types=["sparse", "sparse"], num_experts=4, expert_k=2,
        expert_dispatch="share", shared_experts=0, moe_mlp_dim=16,
        fresh_prompts=True),
    "paged": dict(_TINY, kv_layout="paged", kv_pages=9, kv_page_tokens=8,
                  kv_slot_blocks=8, paged_decode_impl="gather"),
}


def pinned_programs():
    """``{config: {program: sha256, "traces": n}}`` of
    :data:`PINNED_CONFIGS` (run in a fresh process: JAX's caches of
    inner traces make the count depend on what ran before)."""
    import time

    from tensorflowonspark_tpu import telemetry
    from tensorflowonspark_tpu.telemetry import tracing

    def text_of(lowered):
        # private functions renumbered in order of appearance, as
        # tests/test_mla_moe_train.py does
        seen = {}
        return re.sub(r"@(\w+?)_\d+\b", lambda m: "@%s_%d" % (
            m.group(1), seen.setdefault(m.group(0), len(seen))),
            lowered.as_text())

    tracing.watch_jit()
    out = {}
    for name, fields in PINNED_CONFIGS.items():
        since = time.time()
        model = tr.Transformer(tr.TransformerConfig(**fields))
        tok = jax.ShapeDtypeStruct((2, 8), jnp.int32)
        one = jax.ShapeDtypeStruct((2, 1), jnp.int32)
        pos = jax.ShapeDtypeStruct((2,), jnp.int32)
        params = jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32)))["params"]
        cache = jax.eval_shape(lambda: tr.init_cache(model, 2, cache_len=64))
        lowered = {
            "forward": jax.jit(lambda p, t: model.apply({"params": p}, t)
                               ).lower(params, tok),
            "prefill": jax.jit(lambda p, c, t: model.apply(
                {"params": p, "cache": c}, t, decode=True,
                mutable=["cache"])).lower(params, cache, tok),
        }
        if name == "paged":
            tables = jax.ShapeDtypeStruct((2, 8), jnp.int32)
            lowered["step"] = jax.jit(lambda p, c, t, s, bt: model.apply(
                {"params": p, "cache": c}, t, decode=True, slot_positions=s,
                block_tables=bt, mutable=["cache"])).lower(
                    params, cache, one, pos, tables)
        else:
            lowered["step"] = jax.jit(lambda p, c, t, s: model.apply(
                {"params": p, "cache": c}, t, decode=True, slot_positions=s,
                mutable=["cache"])).lower(params, cache, one, pos)
        out[name] = {k: hashlib.sha256(text_of(v).encode()).hexdigest()
                     for k, v in lowered.items()}
        out[name]["traces"] = sum(
            1 + s["attrs"]["nested"]
            for s in telemetry.get_tracer().spans(trace="jit")
            if s["t0"] >= since and s["name"] == "jit.trace")
    return out


def test_configurations_without_the_new_keys_lower_to_the_parent_s_programs():
    code = ("import json, sys; sys.path[:0] = [%r, %r]; "
            "import test_gated_swa_moe as t; "
            "print(json.dumps(t.pinned_programs()))") % (
                HERE, os.path.dirname(HERE))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=HERE,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert json.loads(proc.stdout.strip().splitlines()[-1]) == (
        PARENT_PROGRAMS)


@pytest.mark.parametrize("rows", [7, 600])
def test_the_shares_add_up_to_the_uncut_layer(rows):
    """Every share of 2 experts of 8, each with the shared expert: their
    outputs summed with the shared expert counted once are the uncut
    reference layer (all 8 held) — what the absent chips would add is
    exactly the other shares' routed parts.  A decode step's handful of
    rows and a prompt routed once (a span)."""
    uncut = model_dict(num_experts=8,
                       expert_share={"first": 0, "held": 8, "of": 8})
    key = weights.seed_key(9)
    whole = weights.block_params(uncut, key, 1, jnp.float32)["moe"]
    x = jax.random.normal(jax.random.PRNGKey(rows), (1, rows, 64))
    want = ref.sparse_ffn(x[0], whole, uncut, "f32")
    shared = ref.gated(x[0], whole["shared_wi"]["kernel"],
                       whole["shared_wg"]["kernel"],
                       whole["shared_wo"]["kernel"], "f32")
    total = -3 * shared
    for first in (0, 2, 4, 6):
        part = model_dict(num_experts=2, expert_share={
            "first": first, "held": 2, "of": 8})
        p = weights.block_params(part, key, 1, jnp.float32)["moe"]
        # the held experts are the uncut layer's, whichever share holds
        np.testing.assert_array_equal(p["wi"], whole["wi"][first:first + 2])
        layer = moe.SigmoidMoE(
            router_experts=8, num_experts=2, expert_first=first,
            mlp_dim=32, embed_dim=64, k=3, scaling=2.5, shared_experts=1,
            dtype="float32", scoring="softmax")
        got = layer.apply({"params": p}, x)[0]
        np.testing.assert_allclose(
            got, ref.sparse_ffn(x[0], p, part, "f32"), rtol=0, atol=TOL)
        total = total + got
    assert float(jnp.max(jnp.abs(want))) > 0.1
    np.testing.assert_allclose(total, want, rtol=0, atol=4 * TOL)


@pytest.mark.parametrize("layer", [0, 1])
def test_one_attention_layer_is_the_reference_s(layer):
    # the program's attention layer alone, full (6 heads, half rotated
    # under YaRN) and sliding (9 heads, all rotated), gated and not
    cfg = model_dict()
    p = weights.block_params(cfg, weights.seed_key(4), layer,
                             jnp.float32)["attn"]
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 24, 64))
    pos = jnp.arange(24)
    for gating in ("per-head", ""):
        got = tr.Attention(config_of(cfg, gating=gating), layer=layer).apply(
            {"params": p}, x, pos[None])[0]
        want = ref.attention(x[0], p, dict(cfg, gating=gating), layer, pos,
                             "f32")
        np.testing.assert_allclose(got, want, rtol=0, atol=TOL)
    assert float(jnp.max(jnp.abs(got - tr.Attention(
        config_of(cfg), layer=layer).apply({"params": p}, x, pos[None])[0]
    ))) > 1e-3
