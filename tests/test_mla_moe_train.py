"""Latent attention and sigmoid-routed experts WITH A GRADIENT: the
program's training span (``MLAttention`` with no query latent through
the flash kernels, ``SigmoidMoE`` through the grouped matmul that skips
dead tiles forward and backward) against the plain reference
(``benchmarks/reference/mla_moe_train.py``) at small widths on seeded
weights.

Tolerances.  In float32 both sides sum in float32 and differ by the
order of their sums (blocked scores, sorted expert rows): a leaf's
gradient moves by a few 1e-6 of its largest entry, held to 5e-5; a
token routed to ONE other expert moves expert leaves by 1e-2 and more.
In bfloat16 the program rounds every activation to 8 bits of mantissa
(2^-9 = 0.2% a value) and at these widths (64 columns, 128 tokens: a
leaf's norm sums a few thousand rounded terms) a leaf's gradient NORM
reads 0.5-3% off the float32 reference's; the band is 8% and the loss
1%.  Pallas kernels run interpreted here, at their smallest blocks.
"""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks import compare
from benchmarks import weights_mla_moe_train as weights
from benchmarks.reference import mla_moe_train as ref
from benchmarks.runners import train_mla_moe as runner
from benchmarks.tests import faults_mla_moe_train as faults
from tensorflowonspark_tpu.models import mla, moe
from tensorflowonspark_tpu.models import transformer as tr
from tensorflowonspark_tpu.ops import flash_attention as fa
from tensorflowonspark_tpu.ops import gmm
from tensorflowonspark_tpu.ops import moe as moe_ops

TOL = 5e-5
BF16_NORM_BAND = 0.08
BF16_LOSS_BAND = 0.01

#: a key-for-key miniature of the published configuration: 1 dense +
#: 2 sparse layers, this chip experts 4-7 of 16
TINY = dict(
    hidden_size=64, num_attention_heads=4, q_lora_rank=None,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
    n_shared_experts=2, num_experts_per_tok=3, n_routed_experts=4,
    expert_share={"first": 4, "held": 4, "of": 16}, vocab_size=128,
    num_hidden_layers=3, first_k_dense_replace=1, rope_theta=50000,
    rms_norm_eps=1e-5, routed_scaling_factor=2.446,
    scoring_func="sigmoid", dtype="float32",
    program={"attention_impl": "flash", "block_q": 32, "block_k": 32,
             "remat": False, "rope_interleave": True},
)
#: the cell rematerialises every block: so does the float32 case
REMAT = dict(TINY, program=dict(TINY["program"], remat=True))
ROWS, SEQ = 2, 64


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def program_grads(cfg, params, tokens):
    model = runner.program_model(cfg, SEQ)
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        moe.sigmoid_moe_loss_fn(model), has_aux=True))(
            params, {"tokens": tokens}, None)
    return float(loss), {k: int(v) for k, v in aux.items()}, grads


@pytest.fixture(scope="module")
def case():
    """Seeded weights and rows, the reference's loss, gradient and
    count, and the program's in float32."""
    with jax.default_matmul_precision("highest"):
        params = weights.make_params(TINY, 2 ** 31 + 3, jnp.float32)
        tokens = np.stack([
            np.random.default_rng([5, r]).integers(1, 128, SEQ)
            for r in range(ROWS)]).astype(np.int32)
        want = ref.loss_and_grads(params, tokens, TINY)
        got = program_grads(REMAT, params, jnp.asarray(tokens))
    return params, tokens, want, got


def leaf_gaps(got, want):
    """``{leaf: largest difference over the reference's largest
    entry}``."""
    return {
        k: float(jnp.max(jnp.abs(g - w)) / (jnp.max(jnp.abs(w)) + 1e-12))
        for (k, g), (_, w) in zip(compare.leaf_paths(got),
                                  compare.leaf_paths(want))
    }


# -- (a), (b): the model against the reference --------------------------


def test_loss_and_every_leaf_s_gradient_are_the_reference_s(case):
    params, _, (loss, grads, local), (got_loss, aux, got) = case
    assert jax.tree.structure(got) == jax.tree.structure(grads)
    assert abs(got_loss - loss) < TOL * loss
    gaps = leaf_gaps(got, grads)
    bias = [k for k in gaps if k.endswith("router_bias")]
    assert len(bias) == 2 and len(gaps) == len(jax.tree.leaves(grads))
    worst = max((v, k) for k, v in gaps.items() if k not in bias)
    assert worst[0] < TOL, worst
    # no assignment dropped: the program's count IS the reference's
    assert aux["moe_local_assignments"] == local > 0
    assert aux["moe_rows_multiplied"] >= aux["moe_local_assignments"]
    assert 0 < aux["moe_experts_hit"] <= 2 * 4


def test_in_bfloat16_every_leaf_s_norm_is_within_rounding(case):
    params, tokens, (loss, grads, _), _ = case
    got_loss, _, got = program_grads(
        dict(TINY, dtype="bfloat16"), params, jnp.asarray(tokens))
    assert abs(got_loss - loss) < BF16_LOSS_BAND * loss
    want = {k: v for k, v in compare.leaf_norms(grads).items()
            if not k.endswith("router_bias")}
    worst, leaf = compare.worst_leaf_gap(compare.leaf_norms(got), want)
    assert 1e-4 < worst < BF16_NORM_BAND, (worst, leaf)


# -- (c): the correction bias ------------------------------------------


def test_the_router_s_bias_takes_no_gradient_and_no_step(case):
    params, tokens, (_, ref_grads, _), (_, _, grads) = case
    for i in (1, 2):
        name = "block_%d" % i
        assert not np.any(np.asarray(grads[name]["moe"]["router_bias"]))
        assert not np.any(np.asarray(ref_grads[name]["moe"]["router_bias"]))
        # the gate's gradient does reach the router
        assert float(jnp.max(jnp.abs(grads[name]["moe"]["router"]))) > 1e-6
    opt = moe.leave_router_bias(optax.adamw(1e-2, weight_decay=0.1))
    state, p = opt.init(params), params
    for _ in range(3):
        # any gradient will do: even one that pushes the bias
        g = jax.tree.map(jnp.ones_like, p)
        updates, state = opt.update(g, state, p)
        p = optax.apply_updates(p, updates)
    for i in (1, 2):
        was, now = (t["block_%d" % i]["moe"] for t in (params, p))
        np.testing.assert_array_equal(
            np.asarray(now["router_bias"]), np.asarray(was["router_bias"]))
        assert float(jnp.max(jnp.abs(now["router"] - was["router"]))) > 1e-3


# -- (d): dead tiles ----------------------------------------------------


def test_the_backward_skips_dead_tiles_and_is_the_skip_nothing_backward():
    rng, bm = np.random.default_rng(0), 8
    g, k, held, first, d, f = 64, 3, 4, 4, 32, 48
    scores = jnp.asarray(rng.uniform(size=(g, 16)), jnp.float32)
    scores = scores.at[:, 5].set(-1.0)   # held expert 1: never chosen
    experts, gates = moe_ops.sigmoid_topk(scores, jnp.zeros((16,)), k, 2.0)
    lay = moe_ops.share_layout(experts, first, held, bm=bm)
    tiles, live = lay.tile_expert.shape[0], int(lay.live_tiles[0])
    assert 0 < live < tiles // 2
    x = jnp.asarray(rng.normal(size=(g, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(held, d, f)), jnp.float32)
    weigh = jnp.cos(jnp.arange(g * f, dtype=jnp.float32)).reshape(g, f)

    def loss(mm, x, w):
        ys = mm(moe_ops.dispatch_sorted(x, lay), w)
        return jnp.sum(moe_ops.combine_share(ys, lay, gates) * weigh)

    def skipping(xs, w):
        return gmm.grouped_matmul_live(
            xs, w, lay.tile_expert, lay.live_tiles, bm)

    def nothing_skipped(xs, w):
        return gmm.grouped_matmul(xs, w, lay.tile_expert, bm)

    got = jax.grad(lambda x, w: loss(skipping, x, w), argnums=(0, 1))(x, w)
    want = jax.grad(
        lambda x, w: loss(nothing_skipped, x, w), argnums=(0, 1))(x, w)
    for a, b in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)
    assert not np.any(np.asarray(got[1][1]))       # no row: dw == 0
    assert all(np.any(np.asarray(got[1][e])) for e in (0, 2, 3))
    # what a dead tile holds is never looked at: poison it
    dead = jnp.arange(tiles * bm) >= live * bm
    xs = jnp.where(dead[:, None], jnp.nan, moe_ops.dispatch_sorted(x, lay))
    dy = jnp.where(dead[:, None], jnp.nan, jnp.ones((tiles * bm, f)))
    dw = gmm.tgmm_call(
        xs, dy, lay.tile_expert, held, bm=bm, live_tiles=lay.live_tiles)
    dx = gmm.gmm_dxt_call(
        dy, w, lay.tile_expert, bm=bm, live_tiles=lay.live_tiles)
    assert np.all(np.isfinite(np.asarray(dw)))
    assert np.all(np.isfinite(np.asarray(dx)[: live * bm]))


# -- (d'): a span routed once, a chunk of the sorted rows at a time ------


def _span_layer():
    return moe.SigmoidMoE(
        router_experts=16, num_experts=4, expert_first=4, mlp_dim=32,
        embed_dim=64, k=3, scaling=2.446, shared_experts=2,
        dtype="float32")


def _plain_share(p, x):
    """The reference's plain per-expert sum — every held expert over
    EVERY token, weighed by the token's gate for it (zero where it did
    not choose it), the shared experts once — and ``[tokens, 16]``
    bool: the token chose that expert."""
    return (ref.sparse_ffn(x, p, TINY, "f32")[0],
            np.asarray(ref.route(x, p, TINY, "f32")[1]))


#: name -> (tokens, experts the router's bias lifts over the others)
SPANS = {
    # the worst case: all 1920 choices land here, 640 rows an expert
    # (three tiles: a run straddles a chunk's edge), every chunk runs
    "every_choice_local": (640, (4, 5, 6)),
    # no chunk runs: the shared expert's part alone
    "nothing_local": (640, (0, 1, 2)),
    # experts 4 and 5 take every token (runs of 640 rows over chunks
    # of 512), 6 and 7 what the third choice sends them
    "a_run_straddles_a_chunk_edge": (640, (4, 5)),
    # a span that is a multiple of no tile and no chunk, as routed
    "an_odd_span_length": (777, ()),
}


@pytest.fixture(params=sorted(SPANS))
def span_case(request, monkeypatch):
    monkeypatch.setattr(moe, "SPAN_CHUNK_BYTES", 512 * 64 * 4)
    tokens, lifted = SPANS[request.param]
    x = jax.random.normal(jax.random.PRNGKey(1), (1, tokens, 64))
    p = weights.block_params(
        TINY, weights.seed_key(4), 1, jnp.float32)["moe"]
    for e in lifted:
        p["router_bias"] = p["router_bias"].at[e].set(5.0)
    return request.param, _span_layer(), p, x


def test_a_span_in_chunks_is_the_plain_per_expert_sum_and_its_gradient(
        span_case):
    name, layer, p, x = span_case
    weigh = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(
        x.shape[1:])

    def program(p, x):
        return layer.apply({"params": p}, x, differentiable=True)[0]

    def plain(p, x):
        return _plain_share(p, x[0])[0]

    got, want = program(p, x), plain(p, x)
    assert float(jnp.max(jnp.abs(got - want))) < TOL
    g_got = jax.grad(lambda p, x: jnp.sum(program(p, x) * weigh), (0, 1))(
        p, x)
    g_want = jax.grad(lambda p, x: jnp.sum(plain(p, x) * weigh), (0, 1))(
        p, x)
    gaps = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / (
            jnp.max(jnp.abs(b)) + 1e-30)), g_got, g_want)
    assert max(jax.tree.leaves(gaps)) < TOL, gaps
    dp = g_got[0]
    assert not np.any(np.asarray(dp["router_bias"]))
    routed = [np.any(np.asarray(dp[k])) for k in ("wi", "wg", "wo")]
    if name == "nothing_local":
        # y is the shared expert's alone: no routed leaf and no gate
        # takes a gradient
        assert not any(routed) and not np.any(np.asarray(dp["router"]))
    else:
        assert all(routed) and np.any(np.asarray(dp["router"]))


def test_a_span_s_four_counts_are_numpy_s(span_case):
    name, layer, p, x = span_case
    _, stats = layer.apply(
        {"params": p}, x, differentiable=True, mutable=["moe_stats"])
    got = {k: int(v[0]) for k, v in stats["moe_stats"].items()
           if k != "held_choices"}
    chosen = _plain_share(p, x[0])[1]
    per_expert = chosen[:, 4:8].sum(0).tolist()
    tiles = [-(-c // 256) for c in per_expert]
    owners = np.repeat(np.arange(4), tiles)        # live tile -> expert
    chunks = [owners[i:i + 2] for i in range(0, len(owners), 2)]
    assert got == {
        "local_assignments": sum(per_expert),
        "experts_hit": sum(len(set(c)) for c in chunks),
        "rows_multiplied": 256 * sum(tiles),
        "rows_moved": 512 * len(chunks),
    }
    held = np.asarray(stats["moe_stats"]["held_choices"][0])
    assert held.sum(0).tolist() == per_expert
    if name == "nothing_local":
        assert got["rows_moved"] == 0
    if name == "every_choice_local":
        assert got["local_assignments"] == chosen.sum() == 3 * 640
        # three runs of 640 rows in tiles of 256: a chunk's edge cuts
        # each, and the expert on both sides of it counts twice
        assert got["experts_hit"] == 6 and got["rows_moved"] == 2560


def test_a_run_cut_by_every_chunk_edge_sums_dw_over_the_chunks():
    # the function alone at the smallest tile: expert 0's 40 rows lie
    # over three chunks of 16 rows, expert 2 holds none, and rows of
    # x / gates that no pair of the layout names are never read
    rng, bm, rows = np.random.default_rng(0), 8, 16
    g, k, held, d, f = 44, 2, 3, 16, 24
    experts = np.stack([np.where(np.arange(g) < 40, 0, 5),
                        np.where(np.arange(g) % 4 == 0, 1, 6)], axis=1)
    gates = jnp.asarray(rng.uniform(0.2, 1.0, size=(g, k)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(g, d)), jnp.float32)
    w = tuple(jnp.asarray(rng.normal(size=s) / 4, jnp.float32)
              for s in ((held, d, f), (held, d, f), (held, f, d)))
    lay = moe_ops.span_layout(jnp.asarray(experts), 0, held, bm, rows)
    chunks, hit = moe_ops.span_chunks(lay, bm, rows)
    # 40 rows -> 5 tiles, 11 rows -> 2 tiles: 4 chunks; expert 0 is
    # read by three of them, expert 1 by two
    assert (int(lay.live_tiles[0]), int(chunks), int(hit)) == (7, 4, 5)
    weigh = jnp.sin(jnp.arange(g * d, dtype=jnp.float32)).reshape(g, d)

    def program(x, gates, w):
        return jnp.sum(weigh * moe_ops.share_span(
            x, gates, w, lay, bm, rows))

    def plain(x, gates, w):
        wi, wg, wo = w
        y = 0.0
        for e in range(held):
            gate = jnp.sum(jnp.where(experts == e, gates, 0.0), axis=-1)
            y = y + gate[:, None] * (
                (jax.nn.silu(x @ wg[e]) * (x @ wi[e])) @ wo[e])
        return jnp.sum(weigh * y)

    got = jax.grad(program, (0, 1, 2))(x, gates, w)
    want = jax.grad(plain, (0, 1, 2))(x, gates, w)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)
    assert not np.any(np.asarray(got[2][0][2]))     # no row: dw == 0
    # a gate of a pair routed elsewhere weighs nothing here
    assert not np.any(np.asarray(got[1])[experts >= held])


@pytest.mark.parametrize("load", [
    "as_routed", "all_on_the_first_expert", "nothing_local",
    "the_last_experts_empty"])
def test_a_span_s_layout_is_the_one_pass_layout_of_the_same_choices(load):
    # the span's layout (one comparison for a tile's expert, every
    # sorted row's pair) against ``share_layout`` (a search, a slot ->
    # token map): the same tiles, the same live prefix, the same token
    # in every sorted row — also where trailing experts hold no row and
    # the dead tiles must repeat the last LIVE tile's expert
    rng = np.random.default_rng(5)
    g, k, first, held, bm = 300, 3, 4, 4, 8
    experts = rng.integers(0, 16, size=(g, k))
    if load == "all_on_the_first_expert":
        experts[:] = first
    elif load == "nothing_local":
        experts[:] = 1
    elif load == "the_last_experts_empty":
        experts = np.where(experts >= first + 2, 0, experts)
    experts = jnp.asarray(experts, jnp.int32)
    one = moe_ops.share_layout(experts, first, held, bm=bm)
    span = moe_ops.span_layout(experts, first, held, bm, 4 * bm)
    t = one.tile_expert.shape[0]
    live = int(one.live_tiles[0])
    assert int(span.live_tiles[0]) == live
    assert np.array_equal(np.asarray(span.tile_expert[:t]),
                          np.asarray(one.tile_expert))
    assert np.all(np.asarray(span.tile_expert[t:])
                  == np.asarray(one.tile_expert)[-1])
    pairs = np.asarray(span.pairs)
    token = np.where(pairs < g * k, pairs // k, g)
    assert np.array_equal(token[:t * bm], np.asarray(one.slot_token))
    assert np.all(token[live * bm:] == g)
    assert np.array_equal(np.asarray(span.local), np.asarray(one.local))


@pytest.mark.parametrize("what", ["gates", "their_gradient"])
def test_the_masked_pick_reads_the_gathered_scores_to_the_bit(what):
    # a span picks its chosen scores by a masked sum over the experts,
    # a decode step gathers them: the same gates, the same gradient
    scores = jax.nn.sigmoid(jax.random.normal(
        jax.random.PRNGKey(3), (96, 16)))
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(4), (16,))
    weigh = jnp.cos(jnp.arange(96 * 3, dtype=jnp.float32)).reshape(96, 3)

    def gates(scores, masked):
        return moe_ops.sigmoid_topk(
            scores, bias, 3, 2.446, masked_pick=masked)

    if what == "gates":
        got, want = gates(scores, True), gates(scores, False)
        assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
        assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))
    else:
        got, want = (jax.grad(lambda s: jnp.sum(
            gates(s, masked)[1] * weigh))(scores)
            for masked in (True, False))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=0, atol=1e-7)
        assert np.any(np.asarray(got))


@pytest.mark.parametrize("policy", ["block_keeps_the_routing", "none"])
def test_a_rematerialised_block_keeps_a_span_s_routing_and_no_row(policy):
    # what the backward of a block under ``remat_policy="block"``
    # starts from: the chosen experts and the sorted rows' pairs, 4 bytes a
    # pair each, and no array with a token's row in it
    from jax._src.ad_checkpoint import saved_residuals

    layer = _span_layer()
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 640, 64))
    p = weights.block_params(
        TINY, weights.seed_key(4), 1, jnp.float32)["moe"]
    names = moe.SPAN_SAVED if policy != "none" else ()
    block = jax.checkpoint(
        lambda p, x: layer.apply({"params": p}, x, differentiable=True),
        policy=jax.checkpoint_policies.save_only_these_names(*names))
    kept = [(aval.shape, str(aval.dtype)) for aval, why in
            saved_residuals(block, p, x) if "argument" not in why]
    # 1920 pairs + a tile an expert = 2944 sorted rows, in two chunks
    # of 2560 (a balanced router's 480 rows in tiles + two tiles each)
    want = [((640, 3), "int32"), ((5120,), "int32")] if names else []
    assert sorted(kept) == sorted(want), kept
    # ... and the gradient is the one the unwrapped layer gives
    loss = lambda f: lambda p, x: jnp.sum(jnp.sin(f(p, x)))  # noqa: E731
    got = jax.grad(loss(block), (0, 1))(p, x)
    plain = jax.grad(loss(lambda p, x: layer.apply(
        {"params": p}, x, differentiable=True)), (0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(plain)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


#: a dense model of two layers through the same flash kernels
DENSE = tr.TransformerConfig(
    vocab_size=128, num_layers=2, num_heads=4, head_dim=16, embed_dim=64,
    mlp_dim=96, max_seq_len=SEQ, dtype="float32", attention_impl="flash",
    block_q=32, block_k=32, remat=True)


def _kernels(jaxpr, found=None):
    """``{kernel function's name: pallas calls}`` over a jaxpr and every
    jaxpr inside it."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["jaxpr"].debug_info.func_src_info.split()[0]
            found[name] = found.get(name, 0) + 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _kernels(sub, found)
    return found


@pytest.fixture(scope="module", params=["mla", "dense"])
def remat_case(request):
    """A 2-layer model through flash under ``remat_policy="block"``:
    what its gradient runs, what its remat saves, and its gradient, each
    as the block's policy is now and as it was when it kept a span's
    routing alone."""
    from jax._src.ad_checkpoint import saved_residuals

    tokens = jnp.asarray(np.random.default_rng(0).integers(
        1, 128, (ROWS, SEQ)), jnp.int32)
    if request.param == "mla":
        cfg = dict(REMAT, num_hidden_layers=2)
        params = weights.make_params(cfg, 2 ** 31 + 5, jnp.float32)
        loss_of = moe.sigmoid_moe_loss_fn(runner.program_model(cfg, SEQ))

        def loss(p):
            return loss_of(p, {"tokens": tokens}, None)[0]
    else:
        model = tr.Transformer(DENSE)
        params = model.init(jax.random.PRNGKey(0), tokens)["params"]
        loss_of = tr.loss_fn(model)

        def loss(p):
            return loss_of(p, {"tokens": tokens}, None)

    def reading():
        return (_kernels(jax.make_jaxpr(jax.grad(loss))(params).jaxpr),
                jax.jit(jax.grad(loss))(params))

    kernels, grads = reading()
    kept = [(aval.shape, why) for aval, why in saved_residuals(loss, params)
            if "flash_attention" in why]
    keep = jax.checkpoint_policies.save_only_these_names
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax.checkpoint_policies, "save_only_these_names",
                   lambda *names: keep(*(n for n in names
                                         if n not in fa.FLASH_SAVED)))
        kernels_before, grads_before = reading()
    return kernels, kernels_before, kept, grads, grads_before


def test_a_rematerialised_block_runs_flash_s_forward_once_a_layer(
        remat_case):
    kernels, before, _, _, _ = remat_case
    # two layers: a forward, a dq and a dk/dv kernel each, where the
    # block's backward ran the forward kernel again to get its residuals
    assert {k: kernels[k] for k in ("_fwd_kernel", "_dq_kernel",
                                    "_dkv_kernel")} == {
        "_fwd_kernel": 2, "_dq_kernel": 2, "_dkv_kernel": 2}, kernels
    assert before["_fwd_kernel"] == 4, before


def test_a_rematerialised_block_keeps_flash_s_output_and_lse(remat_case):
    _, _, kept, _, _ = remat_case
    # each layer's [B, S, H, dv] context and [B, H, S, 1] lse, and
    # nothing else of the kernel (q, k and v are made again)
    assert sorted(shape for shape, _ in kept) == sorted(
        [(ROWS, SEQ, 4, 16)] * 2 + [(ROWS, 4, SEQ, 1)] * 2), kept
    assert all("named 'flash_lse'" in why
               for shape, why in kept if shape[-1] == 1), kept


def test_a_rematerialised_block_s_gradient_is_the_one_it_made_twice(
        remat_case):
    _, _, _, grads, before = remat_case
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(before)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert any(np.any(np.asarray(g)) for g in jax.tree.leaves(grads))


@pytest.mark.parametrize("rows,differentiable", [
    (17, False), (17, True), (511, False)])
def test_fewer_rows_than_a_span_take_the_one_pass_over_tiles_of_16(
        rows, differentiable):
    # a decode step's rows (16 callers and one free lane) and anything
    # under 512: no loop, no chunk, nothing named for a remat to keep,
    # and the answer is the plain per-expert sum
    layer = _span_layer()
    x = jax.random.normal(jax.random.PRNGKey(2), (1, rows, 64))
    p = weights.block_params(
        TINY, weights.seed_key(4), 1, jnp.float32)["moe"]

    def program(p, x):
        return layer.apply({"params": p}, x, differentiable=differentiable,
                           mutable=["moe_stats"])

    text = str(jax.make_jaxpr(program)(p, x))
    assert "while" not in text and "name[" not in text
    assert "custom_vjp" in text if differentiable else (
        "custom_vjp" not in text)
    got, stats = program(p, x)
    assert float(jnp.max(jnp.abs(
        got[0] - _plain_share(p, x[0])[0]))) < TOL
    assert ("rows_moved" in stats["moe_stats"]) == differentiable
    # a span of 512 rows does loop
    x512 = jnp.zeros((1, 512, 64))
    assert "while" in str(jax.make_jaxpr(program)(p, x512))


# -- (e): flash attention with a value head of its own ------------------


def _einsum_attention(q, k, v, scale):
    s = q.shape[1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(seen, logits, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


@pytest.mark.parametrize("dqk,dv,seq,block", [
    (24, 16, 64, 32),      # the tiny model's head sizes
    (192, 128, 256, 128),  # the published pair: whole lanes
])
def test_flash_with_its_own_value_head_is_the_einsum_form(
        dqk, dv, seq, block):
    ks = jax.random.split(jax.random.PRNGKey(dqk), 4)
    q = jax.random.normal(ks[0], (1, seq, 2, dqk))
    k = jax.random.normal(ks[1], (1, seq, 2, dqk))
    v = jax.random.normal(ks[2], (1, seq, 2, dv))
    cot = jax.random.normal(ks[3], (1, seq, 2, dv))
    scale = dqk ** -0.5

    def flash(q, k, v):
        return fa.flash_attention(
            q, k, v, causal=True, scale=scale, block_q=block, block_k=block)

    out, vjp = jax.vjp(flash, q, k, v)
    want, want_vjp = jax.vjp(
        lambda q, k, v: _einsum_attention(q, k, v, scale), q, k, v)
    assert out.shape == (1, seq, 2, dv)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want), rtol=0, atol=2e-5)
    for got, ref_, like in zip(vjp(cot), want_vjp(cot), (q, k, v)):
        assert got.shape == like.shape
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref_), rtol=0, atol=1e-4)


#: sha256 of the lowered StableHLO (forward and the three gradient
#: kernels, their grids and block maps) of ``flash_attention``'s
#: gradient at EQUAL head sizes, recorded from the parent commit
#: (ac6ae75): the same characters are the same arithmetic, so the cells
#: that train through these kernels at one head size get bit-equal
#: numbers.  The lowered text and not the jaxpr: a checkpoint name is an
#: equation of the jaxpr and lowers to nothing
PARENT_FLASH_PROGRAMS = {
    (0, 2): "9975f1d2be12dd97e8b2700fde7eeca02ee7b1c8910c106fb964e5b730cf9d7c",
    (160, 1): "91dfe9d2bb86152d91c700dbf96f94e15951524b152921e3611cf7c4160d6c0e",
}


@pytest.mark.parametrize("window,hkv", sorted(PARENT_FLASH_PROGRAMS))
def test_flash_at_equal_head_sizes_is_the_parent_s_program(window, hkv):
    q = jax.ShapeDtypeStruct((1, 256, 2, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 256, hkv, 128), jnp.bfloat16)

    def loss(q, k, v):
        return fa.flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128,
            window=window).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, k, k).as_text()   # no source positions in it
    # private functions renumbered in order of appearance: the numbers
    # the lowering gives them count more than what the module keeps
    seen = {}
    text = re.sub(r"@(\w+?)_\d+\b", lambda m: "@%s_%d" % (
        m.group(1), seen.setdefault(m.group(0), len(seen))), text)
    assert hashlib.sha256(text.encode()).hexdigest() == (
        PARENT_FLASH_PROGRAMS[window, hkv])


def test_flash_still_refuses_keys_and_values_of_different_spans():
    q = jnp.zeros((1, 64, 2, 16))
    with pytest.raises(ValueError, match="k/v must match"):
        fa.flash_attention(q, q, jnp.zeros((1, 32, 2, 16)))


# -- (f): the shares add up ---------------------------------------------


def test_eight_shares_and_the_shared_experts_once_are_the_uncut_layer():
    """Values AND input gradients: what the absent chips would add is
    exactly the other shares' routed parts.  ONE traced program serves
    the eight shares: share ``j`` is the layer with experts ``2j, 2j +
    1`` rotated to the front of the router (``expert_first=0``); the
    shares by ``expert_first`` itself are ``tests/test_mla_moe.py``'s
    (forward) and the model's above (experts 4-7)."""
    uncut = dict(TINY, n_routed_experts=16,
                 expert_share={"first": 0, "held": 16, "of": 16})
    whole = weights.block_params(
        uncut, weights.seed_key(9), 2, jnp.float32)["moe"]
    x = jax.random.normal(jax.random.PRNGKey(2), (96, 64))
    cot = jax.random.normal(jax.random.PRNGKey(3), (96, 64))
    layer = moe.SigmoidMoE(
        router_experts=16, num_experts=2, mlp_dim=32, embed_dim=64,
        expert_first=0, k=3, scaling=2.446, shared_experts=2,
        dtype="float32")

    @jax.jit
    def share(p, first):
        held = dict(p, router=jnp.roll(p["router"], -first, axis=1),
                    router_bias=jnp.roll(p["router_bias"], -first), **{
            k: jax.lax.dynamic_slice_in_dim(p[k], first, 2)
            for k in ("wi", "wg", "wo")})
        y, vjp = jax.vjp(lambda x: layer.apply(
            {"params": held}, x[None], differentiable=True)[0], x)
        return y, vjp(cot)[0]

    want, want_vjp = jax.vjp(
        lambda x: ref.sparse_ffn(x, whole, uncut, "f32")[0], x)
    once, once_vjp = jax.vjp(lambda x: ref.gated(
        x, whole["shared_wi"]["kernel"], whole["shared_wg"]["kernel"],
        whole["shared_wo"]["kernel"], "f32"), x)
    total, dx = once, once_vjp(cot)[0]
    for first in range(0, 16, 2):
        part, dpart = share(whole, first)
        total = total + (part - once)
        dx = dx + (dpart - once_vjp(cot)[0])
    assert float(jnp.max(jnp.abs(total - want))) < TOL
    assert float(jnp.max(jnp.abs(dx - want_vjp(cot)[0]))) < TOL
    assert float(jnp.max(jnp.abs(want - once))) > 1e-2


# -- (g): no query latent ----------------------------------------------


def test_without_a_query_latent_the_layer_builds_one_projection():
    model = runner.program_model(TINY, SEQ)
    shapes = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, SEQ), jnp.int32)))
    attn = shapes["params"]["block_1"]["attn"]
    assert set(attn) == {"q", "kv_a", "kv_norm", "kv_b", "out"}
    assert attn["q"].shape == (64, 4, 16 + 8)
    assert mla.flash_span(model.cfg, "", False, None, SEQ)
    assert not mla.flash_span(model.cfg, "", True, None, SEQ)
    assert not mla.flash_span(model.cfg, "shared", False, None, SEQ)


def test_an_index_layer_without_a_query_latent_is_refused():
    import dataclasses

    cfg = dataclasses.replace(
        runner.program_model(TINY, SEQ).cfg,
        indexer_types=("full", "shared", "shared"), index_n_heads=2,
        index_head_dim=16, index_topk=8)
    with pytest.raises(ValueError, match="q_lora_rank must be set"):
        tr.Transformer(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, SEQ), jnp.int32))


# -- (h): planted faults ------------------------------------------------


def _checks(got_loss, got_grads, want):
    loss, grads, local = want
    norms = compare.leaf_norms(grads)
    return runner.checks_of(
        [got_loss], compare.leaf_norms(got_grads), norms, [local], 0.0,
        {"losses": [loss], "grad_norms": norms, "change_norms": norms,
         "local_assignments": [local]},
        [runner.LOSS_LIMIT, runner.GRAD_LIMIT, 1.0, 0.0])[0]


def test_the_comparison_holds_for_the_program_as_it_is(case):
    _, _, want, (got_loss, _, got) = case
    checks = _checks(got_loss, got, want)
    assert all(c["value"] <= c["limit"] for c in checks.values()), checks


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_a_planted_fault_fails_the_comparison(case, fault, monkeypatch):
    params, tokens, want, _ = case
    # the faults patch these in place: put them back afterwards
    monkeypatch.setattr(
        moe, "sigmoid_moe_loss_fn", moe.sigmoid_moe_loss_fn)
    monkeypatch.setattr(gmm, "gmm_dxt_call", gmm.gmm_dxt_call)
    monkeypatch.setattr(gmm, "tgmm_call", gmm.tgmm_call)
    faults.plant(fault)
    got_loss, _, got = program_grads(TINY, params, jnp.asarray(tokens))
    checks = _checks(got_loss, got, want)
    assert checks["grad_norm_gap_worst_leaf"]["value"] > (
        checks["grad_norm_gap_worst_leaf"]["limit"]), checks
