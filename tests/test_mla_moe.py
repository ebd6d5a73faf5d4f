"""Latent attention with a learned sparse index, and sigmoid-routed
experts held as one chip's share: the program against the plain
reference (``benchmarks/reference/glm_dsa_moe.py``) at small widths,
seeded weights, float32 — logits, not tokens.

Tolerances.  Program and reference both run float32 at matmul
precision ``highest``; what separates them is the order of float32
sums (blocked queries, absorbed products, sorted expert rows), which at
these widths moves a logit of size ~3 by a few 1e-6.  The limit is
2e-5 everywhere a logit is compared; a selection or a routing that
differed by ONE key or expert moves logits by 1e-2 and more.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights_glm_dsa_moe as weights
from benchmarks.reference import glm_dsa_moe as ref
from benchmarks.runners import serve_mla_moe as runner
from tensorflowonspark_tpu.models import mla, moe
from tensorflowonspark_tpu.models import transformer as tr
from tensorflowonspark_tpu.ops import latent_attention as la
from tensorflowonspark_tpu.ops import moe as moe_ops

TOL = 2e-5
HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def model_dict(**over):
    """The published configuration's keys at test widths."""
    with open(os.path.join(
            HERE, "..", "benchmarks/configs/glm-5.2.serve-ep16.json")) as f:
        cfg = json.load(f)
    cfg.update(
        hidden_size=64, num_attention_heads=4, q_lora_rank=32,
        kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=8,
        v_head_dim=16, index_n_heads=2, index_head_dim=16, index_topk=12,
        intermediate_size=96, moe_intermediate_size=32,
        n_shared_experts=1, num_experts_per_tok=3, n_routed_experts=4,
        expert_share={"first": 4, "held": 4, "of": 16}, vocab_size=256,
        num_hidden_layers=4,
        mlp_layer_types=["dense", "sparse", "sparse", "sparse"],
        indexer_types=["full", "shared", "full", "shared"],
        max_position_embeddings=128, dtype="float32",
        cache_dtype="float32", program={},
    )
    cfg.update(over)
    return cfg


def config_of(cfg):
    class Plan:
        answer_len = np.array([16])
        prompt_len = np.array([80])

    pc = runner.program_config(cfg, Plan)
    for k in ("mode", "max_new_tokens", "max_prompt_len"):
        pc.pop(k)
    return tr.TransformerConfig(**pc)


def build(seed=3, **over):
    cfg = model_dict(**over)
    params = weights.make_params(cfg, seed, "float32")
    return cfg, tr.Transformer(config_of(cfg)), params


def tokens_of(n, seed=1):
    return np.random.default_rng(seed).integers(1, 256, n).astype(np.int32)


def step_logits(model, params, tokens, prompt_len, pad=0):
    """Logits at positions ``prompt_len - 1 ..`` by the serving path's
    two programs: a left-padded prefill into a fresh cache, then one
    absorbed decode step a token (teacher-forced on ``tokens``)."""
    @jax.jit
    def prefill(cache, padded, pads):
        return model.apply(
            {"params": params, "cache": cache}, padded, decode=True,
            mutable=["cache"], pad_start=pads, last_only=True)

    @jax.jit
    def step(cache, tok, pads, at):
        return model.apply(
            {"params": params, "cache": cache}, tok, decode=True,
            mutable=["cache", "moe_stats"], pad_start=pads,
            slot_positions=at)

    cache = tr.init_cache(model, 1, cache_len=96)
    padded = np.zeros((1, pad + prompt_len), np.int32)
    padded[0, pad:] = tokens[:prompt_len]
    pads = jnp.asarray([pad])
    logits, mut = prefill(cache, jnp.asarray(padded), pads)
    out, cache = [logits[0, 0]], mut["cache"]
    for i in range(prompt_len, len(tokens)):
        logits, mut = step(cache, jnp.asarray(tokens[None, i:i + 1]), pads,
                           jnp.asarray([pad + i]))
        out.append(logits[0, 0])
        cache = mut["cache"]
    return jnp.stack(out)


def forward(model, params, tokens):
    return jax.jit(lambda p, t: model.apply({"params": p}, t))(
        params, jnp.asarray(tokens)[None])[0]


def reference(cfg, params, tokens):
    return jax.jit(lambda p, t: ref.forward(t, p, cfg))(
        params, jnp.asarray(tokens))


# ----------------------------------------------------------------------
# the model against the reference
# ----------------------------------------------------------------------


def test_full_forward_is_the_reference_s():
    cfg, model, params = build()
    tokens = tokens_of(40)  # 40 > index_topk 12: the selection bites
    got = forward(model, params, tokens)
    want = reference(cfg, params, tokens)
    assert float(jnp.max(jnp.abs(got - want))) < TOL


@pytest.fixture
def span_kernel_calls(monkeypatch):
    """The span kernel's blocks cut to test size (a prefill of 16
    tokens is then 2 x 2 blocks of 8, or one of 16), and the block
    sizes of every call of the kernel."""
    monkeypatch.setattr(la, "SPAN_BLOCKS", ((16, 16), (8, 8)))
    calls, kernel = [], la.latent_span_attention

    def counted(*args, **kw):
        calls.append(kw["blocks"])
        return kernel(*args, **kw)

    monkeypatch.setattr(la, "latent_span_attention", counted)
    return calls


@pytest.mark.parametrize("prompt_len,total,pad", [
    (6, 11, 0),    # every context under index_topk: all keys selected
    (20, 30, 4),   # over it, behind a pad region
    (9, 18, 7),    # crossing it while decoding
])
def test_prefill_then_decode_is_the_reference_s_full_forward(
        prompt_len, total, pad):
    cfg, model, params = build()
    tokens = tokens_of(total, seed=prompt_len)
    got = step_logits(model, params, tokens, prompt_len, pad)
    want = reference(cfg, params, tokens)[prompt_len - 1:]
    assert float(jnp.max(jnp.abs(got - want))) < TOL


@pytest.mark.parametrize("prompt_len,total,pad,blocks", [
    (6, 11, 2, (8, 8)),      # one block, all keys selected
    (20, 30, 4, (8, 8)),     # 3 x 3 blocks, the pad region inside one
    (9, 18, 7, (16, 16)),    # one block of 16, crossing index_topk
    (23, 31, 9, (16, 16)),   # 2 x 2 blocks, a whole key block of pad
])
def test_a_prefill_through_the_span_kernel_then_decode_is_the_reference_s(
        prompt_len, total, pad, blocks, span_kernel_calls):
    # the same comparison with the prefill's attention through the span
    # kernel on all 4 layers (2 "full", 2 "shared"), the decode steps
    # reading the rows it banked
    cfg, model, params = build()
    tokens = tokens_of(total, seed=prompt_len)
    got = step_logits(model, params, tokens, prompt_len, pad)
    assert span_kernel_calls == [blocks] * 4
    want = reference(cfg, params, tokens)[prompt_len - 1:]
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def test_a_span_longer_than_a_super_block_is_the_reference_s(monkeypatch):
    # the cell's prompts are 5 to 8 super-blocks of 2048 queries, each
    # expanding the keys up to its own last query and mapping over
    # sub-blocks of 128; here 4 super-blocks of 16 in sub-blocks of 8,
    # full forward and a padded prefill into the banks
    monkeypatch.setattr(mla, "Q_SUPER", 16)
    monkeypatch.setattr(mla, "Q_SUB", 8)
    cfg, model, params = build()
    tokens = tokens_of(64, seed=4)
    want = reference(cfg, params, tokens)
    got = forward(model, params, tokens)
    assert float(jnp.max(jnp.abs(got - want))) < TOL
    tokens = tokens_of(35, seed=6)
    got = step_logits(model, params, tokens, 27, pad=5)  # a bucket of 32
    want = reference(cfg, params, tokens)[26:]
    assert float(jnp.max(jnp.abs(got - want))) < TOL


@pytest.mark.parametrize("indexer,span,pad,supers,blocks", [
    # a "full" layer over 48 keys with index_topk 12: the selection bites
    ("full", 48, 0, None, (16, 16)),
    # a "shared" layer attends over the set it is fed
    ("shared", 48, 0, None, (16, 16)),
    # no index: every visible key
    ("", 48, 0, None, (16, 16)),
    # a left pad region that holds a whole key block and cuts the next
    ("full", 48, 21, None, (16, 16)),
    ("shared", 40, 19, None, (8, 8)),
    # 4 super-blocks of 16 queries in sub-blocks of 8: the selection is
    # made a super-block at a time, the keys expanded once
    ("full", 64, 5, (16, 8), (16, 16)),
    # a span the blocks do not divide keeps the einsums: same answer
    ("full", 44, 3, None, None),
], ids=["full", "shared", "no-index", "pad-full", "pad-shared",
        "super-blocks", "undivided"])
def test_the_span_kernel_is_the_einsum_form(
        indexer, span, pad, supers, blocks, span_kernel_calls, monkeypatch):
    # one layer filling its banks (decode=True: the span kernel where
    # the blocks divide the span) against the same layer in a full
    # forward (the einsums): the selection bit for bit, the output to
    # float32 rounding
    if supers:
        monkeypatch.setattr(mla, "Q_SUPER", supers[0])
        monkeypatch.setattr(mla, "Q_SUB", supers[1])
    _, model, params = build()
    assert mla.span_blocks(model.cfg, True, span) == blocks
    assert mla.span_blocks(model.cfg, False, span) is None
    x = jax.random.normal(jax.random.PRNGKey(span), (1, span, 64))
    pos, pads = jnp.arange(span)[None], jnp.asarray([pad])
    fed = None
    if indexer == "shared":
        _, fed = mla.MLAttention(model.cfg, indexer="full").apply(
            {"params": params["block_0"]["attn"]}, x, pos, pad_start=pads)
    layer = mla.MLAttention(model.cfg, indexer=indexer)
    weights_ = {"params": params[
        "block_0" if indexer == "full" else "block_1"]["attn"]}
    want, want_sel = layer.apply(weights_, x, pos, pad_start=pads, sel=fed)
    assert span_kernel_calls == []
    (got, got_sel), banked = layer.apply(
        weights_, x, pos, decode=True, pad_start=pads, sel=fed,
        mutable=["cache"])
    assert span_kernel_calls == ([blocks] if blocks else [])
    if indexer:
        assert got_sel.dtype == jnp.bool_
        assert bool(jnp.all(got_sel == want_sel))
        if indexer == "full":  # and it bites: 12 of up to 48 - pad keys
            assert int(got_sel[0, -1].sum()) == 12
    else:
        assert got_sel is None and want_sel is None
    assert float(jnp.max(jnp.abs(got - want))) < TOL
    assert banked["cache"]["latent"].shape == (1, 128, 128)


def test_the_span_kernel_in_bfloat16_is_within_rounding_of_float32():
    # the kernel as the chip runs it — bfloat16 operands, float32
    # scores and sums, probabilities rounded for the second product —
    # at its real block size (a span of 384 is 3 x 3 blocks of 128),
    # against float32 einsums over the same bfloat16 values
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    b, h, s, d, dv = 2, 2, 384, 32, 16
    q, key = (jax.random.normal(k[i], (b, h, s, d)).astype(jnp.bfloat16)
              for i in (0, 1))
    v = jax.random.normal(k[2], (b, h, s, dv)).astype(jnp.bfloat16)
    pad = jnp.asarray([0, 150])
    at = jnp.arange(s)
    itself = at[:, None] == at[None]
    mask = ((at[None] <= at[:, None]) & (at[None] >= pad[:, None, None]) & (
        jax.random.uniform(k[3], (b, s, s)) < 0.4)) | itself
    assert la.span_blocks(s) == (128, 128)
    got = la.latent_span_attention(
        q, key, v, mask.astype(jnp.int8), pad, scale=0.2)
    assert got.dtype == jnp.bfloat16 and got.shape == (b, h, s, dv)
    f32 = jnp.float32
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(f32), key.astype(f32))
    probs = jax.nn.softmax(
        jnp.where(mask[:, None], logits * 0.2, -jnp.inf), axis=-1)
    want = jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(f32))
    # a context of size ~1 rounded to 8 bits, its probabilities too
    assert float(jnp.max(jnp.abs(got.astype(f32) - want))) < 2e-2
    assert la.span_blocks(16384) == la.span_blocks(10240) == (2048, 512)
    assert la.span_blocks(1024 * 13) == (1024, 512)
    assert la.span_blocks(2048 + 128) == (128, 128)
    assert la.span_blocks(100) is None
    with pytest.raises(ValueError, match="do not divide a span"):
        la.latent_span_attention(
            q[:, :, :100], key[:, :, :100], v[:, :, :100],
            mask[:, :100, :100].astype(jnp.int8), pad, scale=0.2)


def test_a_dropped_selection_shows_through_the_span_kernel(
        span_kernel_calls, monkeypatch):
    # the benchmark's planted fault (every query attends to the most
    # recent index_topk keys) replaces mla.topk_mask: the kernel path
    # calls the selection by that module-level name, so the fault
    # reaches a prefill through the kernel as it reaches the einsums
    from benchmarks.tests import faults_glm_dsa_moe

    cfg, model, params = build()
    tokens = tokens_of(30, seed=20)
    want = reference(cfg, params, tokens)[19:]
    monkeypatch.setattr(mla, "topk_mask", mla.topk_mask)  # restored after
    faults_glm_dsa_moe.plant("recent_keys_only")
    got = step_logits(model, params, tokens, 20, pad=4)
    assert span_kernel_calls == [(8, 8)] * 4
    assert float(jnp.max(jnp.abs(got - want))) > 1e-2


def test_absorbed_decode_is_the_non_absorbed_span_form():
    # the same positions computed once as a span (non-absorbed, keys
    # expanded per head) and once token by token against the latent
    # bank (absorbed): one product, two groupings of its sums
    _, model, params = build()
    tokens = tokens_of(26, seed=8)
    span = forward(model, params, tokens)[13:]
    steps = step_logits(model, params, tokens, 14)
    assert float(jnp.max(jnp.abs(span - steps))) < TOL


def test_under_index_topk_positions_the_layer_is_dense_latent_attention():
    cfg, sparse, params = build(index_topk=64)
    dense = tr.Transformer(dataclasses.replace(
        sparse.cfg, indexer_types=("",) * 4))
    tokens = tokens_of(40, seed=2)
    a = forward(sparse, params, tokens)
    b = forward(dense, params, tokens)
    assert float(jnp.max(jnp.abs(a - b))) < TOL
    # and past it the selection changes the result
    cfg, narrow, params = build(index_topk=12)
    c = forward(narrow, params, tokens)
    assert float(jnp.max(jnp.abs(a - c))) > 1e-2


def test_a_shared_layer_attends_over_the_full_layer_s_set():
    cfg, model, params = build()
    mcfg = model.cfg
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 64))
    pos = jnp.arange(32)[None]
    full = mla.MLAttention(mcfg, indexer="full")
    shared = mla.MLAttention(mcfg, indexer="shared")
    pf, ps = params["block_0"]["attn"], params["block_1"]["attn"]
    assert "index_q" in pf and "index_q" not in ps
    _, sel = full.apply({"params": pf}, x, pos)
    assert sel.shape == (1, 32, 32)
    kept = np.asarray(sel[0]).sum(axis=1)
    assert list(kept) == [min(t + 1, 12) for t in range(32)]
    got, passed_on = shared.apply({"params": ps}, x, pos, sel=sel)
    assert passed_on is sel or bool(jnp.all(passed_on == sel))
    want, _ = ref.attention(
        x[0], ps, cfg, jnp.arange(32), "f32", "shared", sel[0])
    assert float(jnp.max(jnp.abs(got[0] - want))) < TOL
    # another set gives another result: the set is what it attends over
    other = jnp.tril(jnp.ones((1, 32, 32), bool))
    moved, _ = shared.apply({"params": ps}, x, pos, sel=other)
    assert float(jnp.max(jnp.abs(moved - got))) > 1e-3
    with pytest.raises(ValueError, match="'full' layer before it"):
        shared.apply({"params": ps}, x, pos)


def test_the_selection_is_the_exact_top_k_with_ties_to_the_lower_index():
    rng = np.random.default_rng(0)
    scores = rng.normal(size=(5, 300)).astype(np.float32)
    scores[0, 10:200] = 0.25          # a plateau across the boundary
    scores[1, ::3] = -0.0             # signed zeros are one value
    scores[1, 1::3] = 0.0
    scores[2] = np.round(scores[2], 1)  # many ties
    visible = rng.random((5, 300)) < 0.8
    visible[4, 40:] = False           # fewer visible than k
    got = np.asarray(mla.topk_mask(
        jnp.asarray(scores), jnp.asarray(visible), 64))
    want = np.asarray(ref.select(
        jnp.asarray(scores), jnp.asarray(visible), 64))
    assert (got == want).all()
    assert list(got.sum(axis=1)[:4]) == [64] * 4
    assert got[4].sum() == visible[4].sum()
    # the plateau is taken from the left
    taken = np.flatnonzero(got[0] & (scores[0] == 0.25))
    plateau = np.flatnonzero(visible[0] & (scores[0] == 0.25))
    assert list(taken) == list(plateau[:len(taken)])


def test_the_latent_decode_kernel_is_the_two_einsums():
    # three slots over banks of two blocks of 128: one early in its
    # first block, one behind a pad region that spans a block edge, one
    # idle lane that sees itself alone at the bank's last position
    from tensorflowonspark_tpu.ops import latent_attention as la

    b, h, length, w = 3, 8, 256, 128
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k[0], (b, h, w))
    bank = jax.random.normal(k[1], (b, length, w))
    pos, pad = jnp.asarray([5, 130, 255]), jnp.asarray([0, 100, 255])
    kpos = jnp.arange(length)[None]
    itself = kpos == pos[:, None]
    sel = (kpos <= pos[:, None]) & (kpos >= pad[:, None]) & (
        jax.random.uniform(k[2], (b, length)) < 0.5) | itself
    got = la.latent_decode_attention(
        q, bank, jnp.where(sel, 0.0, la.MASKED)[:, None],
        jnp.minimum(pad, pos), pos, scale=0.1)
    logits = jnp.einsum("bhc,blc->bhl", q, bank) * 0.1
    probs = jax.nn.softmax(
        jnp.where(sel[:, None], logits, -jnp.inf), axis=-1)
    want = jnp.einsum("bhl,blc->bhc", probs, bank)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    assert la.block_rows(20480, 640) == 1024
    assert la.block_rows(384, 128) == 128
    assert la.block_rows(96, 128) is None and la.block_rows(256, 72) is None


def test_interleaved_rope_rotates_adjacent_pairs():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 8))
    pos = jnp.arange(5)[None] + 3
    got = tr.rope(x, pos, 8e6, interleave=True)
    want = ref.rope_pairs(x[0], pos[0], 8e6)
    assert float(jnp.max(jnp.abs(got[0] - want))) < 1e-6
    # and it is not the split-halves rotation
    assert float(jnp.max(jnp.abs(got - tr.rope(x, pos, 8e6)))) > 1e-2


# ----------------------------------------------------------------------
# the router and the expert share
# ----------------------------------------------------------------------


def test_the_router_s_bias_moves_the_choice_and_never_the_weight():
    scores = jnp.asarray([[0.9, 0.8, 0.7, 0.6, 0.5, 0.4]])
    zero = jnp.zeros((6,))
    experts, gates = moe_ops.sigmoid_topk(scores, zero, 3, scaling=2.5)
    assert list(np.asarray(experts[0])) == [0, 1, 2]
    np.testing.assert_allclose(
        np.asarray(gates[0]), 2.5 * np.array([0.9, 0.8, 0.7]) / 2.4,
        rtol=1e-6)
    bias = zero.at[5].set(1.0)        # lifts expert 5 over the others
    experts, gates = moe_ops.sigmoid_topk(scores, bias, 3, scaling=2.5)
    assert sorted(np.asarray(experts[0])) == [0, 1, 5]
    by_expert = dict(zip(np.asarray(experts[0]).tolist(),
                         np.asarray(gates[0]).tolist()))
    # expert 5 weighs by its own score 0.4, not by 1.4
    np.testing.assert_allclose(by_expert[5], 2.5 * 0.4 / 2.1, rtol=1e-6)
    np.testing.assert_allclose(sum(by_expert.values()), 2.5, rtol=1e-6)


def _moe_layer(first, held, experts=16):
    return moe.SigmoidMoE(
        router_experts=experts, num_experts=held, expert_first=first,
        mlp_dim=32, embed_dim=64, k=3, scaling=2.5, shared_experts=1,
        dtype="float32")


@pytest.mark.parametrize("tokens", [1, 640])
def test_no_assignment_is_dropped_at_batch_one_and_at_a_full_bucket(
        tokens, monkeypatch):
    # every token's routed sum equals the plain per-expert sum, also
    # when EVERY token picks the same held experts (a router no
    # capacity could take) and when the sorted rows go through in
    # chunks (640 tokens are a span: 1920 rows, all local, 5 chunks)
    cfg = model_dict()
    p = weights.block_params(
        cfg, weights.seed_key(4), 1, jnp.float32)["moe"]
    p = dict(p, router_bias=p["router_bias"].at[4:7].set(5.0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, tokens, 64))
    monkeypatch.setattr(moe, "SPAN_CHUNK_BYTES", 512 * 64 * 4)
    got, stats = _moe_layer(4, 4).apply(
        {"params": p}, x, mutable=["moe_stats"])
    want = ref.sparse_ffn(x[0], p, cfg, "f32")
    assert float(jnp.max(jnp.abs(got[0] - want))) < TOL
    chose = np.asarray(stats["moe_stats"]["held_choices"][0])
    assert chose.shape == (tokens, 4)
    assert (chose[:, :3] == 1).all() and (chose[:, 3] == 0).all()


def test_the_shares_parts_and_the_shared_expert_once_are_the_uncut_layer():
    cfg = model_dict(
        n_routed_experts=16, expert_share={"first": 0, "held": 16, "of": 16})
    whole = weights.block_params(
        cfg, weights.seed_key(9), 2, jnp.float32)["moe"]
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 64))
    uncut = _moe_layer(0, 16).apply({"params": whole}, x)
    zero_shared = {k: jax.tree.map(jnp.zeros_like, v) if k.startswith(
        "shared") else v for k, v in whole.items()}
    shared_only = _moe_layer(0, 16).apply(
        {"params": dict(whole, router_bias=whole["router_bias"])}, x
    ) - _moe_layer(0, 16).apply({"params": zero_shared}, x)
    parts = 0
    for first in range(0, 16, 4):
        share_cfg = model_dict(
            expert_share={"first": first, "held": 4, "of": 16})
        held = weights.block_params(
            share_cfg, weights.seed_key(9), 2, jnp.float32)["moe"]
        # the same experts, whichever share draws them
        np.testing.assert_array_equal(
            np.asarray(held["wi"]), np.asarray(whole["wi"][first:first + 4]))
        part = _moe_layer(first, 4).apply({"params": held}, x)
        parts = parts + (part - shared_only)
    assert float(jnp.max(jnp.abs(parts + shared_only - uncut))) < TOL
    assert float(jnp.max(jnp.abs(shared_only))) > 1e-2


def test_a_share_outside_the_router_s_experts_is_refused():
    x = jnp.zeros((1, 2, 64))
    with pytest.raises(ValueError, match="not among the router"):
        _moe_layer(14, 4).init(jax.random.PRNGKey(0), x)
