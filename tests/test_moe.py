"""MoE routing invariants + expert-parallel numerics."""

import pytest

pytestmark = pytest.mark.slow

import jax
import jax.numpy as jnp
import numpy as np
import optax

from tensorflowonspark_tpu.models import moe as moe_models
from tensorflowonspark_tpu.models import transformer as tr
from tensorflowonspark_tpu.ops import gmm as gmm_ops
from tensorflowonspark_tpu.ops import moe as moe_ops
from tensorflowonspark_tpu.parallel import dp, sharding as sh
from tensorflowonspark_tpu.parallel.mesh import build_mesh


class TestGating:
    def _logits(self, g=64, e=4, seed=0):
        return jnp.asarray(
            np.random.RandomState(seed).randn(g, e).astype(np.float32)
        )

    def test_slots_hold_at_most_one_token(self):
        logits = self._logits()
        dispatch, _, _ = moe_ops.top_k_gating(logits, 4, capacity=8, k=2)
        per_slot = jnp.sum(dispatch, axis=0)  # [E, C]
        assert float(jnp.max(per_slot)) <= 1.0 + 1e-6

    def test_token_dispatched_to_at_most_k(self):
        logits = self._logits()
        dispatch, _, _ = moe_ops.top_k_gating(logits, 4, capacity=64, k=2)
        per_token = jnp.sum(dispatch, axis=(1, 2))
        assert float(jnp.max(per_token)) <= 2.0 + 1e-6

    def test_combine_weights_normalized(self):
        logits = self._logits()
        _, combine, _ = moe_ops.top_k_gating(logits, 4, capacity=64, k=2)
        totals = jnp.sum(combine, axis=(1, 2))
        # ample capacity: every token lands, weights renormalize to 1
        np.testing.assert_allclose(totals, np.ones(64), atol=1e-5)

    def test_capacity_drops_overflow(self):
        # all tokens prefer expert 0 -> only `capacity` land
        logits = jnp.tile(
            jnp.asarray([[10.0, 0.0, 0.0, 0.0]]), (32, 1)
        )
        dispatch, _, _ = moe_ops.top_k_gating(logits, 4, capacity=8, k=1)
        assert float(jnp.sum(dispatch[:, 0])) == 8.0

    def test_aux_loss_uniform_is_one(self):
        # perfectly uniform router -> aux loss == 1 (its minimum)
        g, e = 64, 4
        logits = jnp.zeros((g, e))
        _, _, aux = moe_ops.top_k_gating(logits, e, capacity=64, k=2)
        assert 0.99 <= float(aux) <= 1.3

    def test_capacity_formula_aligned(self):
        cap = moe_ops.expert_capacity(1024, 8, capacity_factor=1.0, k=2)
        assert cap % 8 == 0 and cap >= 256

    def test_routing_indices_match_dense_gating(self):
        # the index-based router must reproduce the dense one-hot
        # path's slot assignment, gates, drops, and aux loss exactly
        logits = self._logits(g=96, e=4, seed=3)
        e, cap, k = 4, 16, 2  # tight capacity: forces drops
        dispatch, combine, aux_d = moe_ops.top_k_gating(
            logits, e, cap, k=k
        )
        experts, slots, gates, aux_i = moe_ops.top_k_routing(
            logits, e, cap, k=k
        )
        np.testing.assert_allclose(float(aux_d), float(aux_i), atol=1e-6)
        g = logits.shape[0]
        dense_from_idx = np.zeros((g, e, cap), np.float32)
        combine_from_idx = np.zeros((g, e, cap), np.float32)
        ex, sl, gt = map(np.asarray, (experts, slots, gates))
        for t in range(g):
            for j in range(k):
                if gt[t, j] > 0:
                    dense_from_idx[t, ex[t, j], sl[t, j]] = 1.0
                    combine_from_idx[t, ex[t, j], sl[t, j]] = gt[t, j]
        np.testing.assert_allclose(dense_from_idx, dispatch, atol=1e-6)
        np.testing.assert_allclose(combine_from_idx, combine, atol=1e-5)

    def test_gather_dispatch_combine_match_einsum(self):
        # dispatch_gather/combine_gather == the dense einsums on the
        # same routing decisions (including dropped tokens)
        logits = self._logits(g=96, e=4, seed=4)
        e, cap, k = 4, 16, 2
        rng = np.random.RandomState(5)
        x = jnp.asarray(rng.randn(96, 8).astype(np.float32))
        dispatch, combine, _ = moe_ops.top_k_gating(logits, e, cap, k=k)
        experts, slots, gates, _ = moe_ops.top_k_routing(
            logits, e, cap, k=k
        )
        xe_dense = jnp.einsum("gec,gd->ecd", dispatch, x)
        xe_idx = moe_ops.dispatch_gather(x, experts, slots, gates, e, cap)
        np.testing.assert_allclose(xe_idx, xe_dense, atol=1e-5)
        ye = jnp.asarray(rng.randn(e, cap, 8).astype(np.float32))
        y_dense = jnp.einsum("gec,ecd->gd", combine, ye)
        y_idx = moe_ops.combine_gather(ye, experts, slots, gates)
        np.testing.assert_allclose(y_idx, y_dense, atol=1e-5)

    def test_gather_dispatch_gradients_flow(self):
        # d(loss)/dx must agree between the gather and einsum paths
        logits = self._logits(g=32, e=4, seed=6)
        e, cap, k = 4, 8, 2
        x0 = jnp.asarray(
            np.random.RandomState(7).randn(32, 8).astype(np.float32)
        )

        def loss_idx(x):
            experts, slots, gates, _ = moe_ops.top_k_routing(
                logits, e, cap, k=k
            )
            xe = moe_ops.dispatch_gather(x, experts, slots, gates, e, cap)
            return jnp.sum(jnp.sin(xe))

        def loss_dense(x):
            dispatch, _, _ = moe_ops.top_k_gating(logits, e, cap, k=k)
            return jnp.sum(jnp.sin(jnp.einsum("gec,gd->ecd", dispatch, x)))

        np.testing.assert_allclose(
            jax.grad(loss_idx)(x0), jax.grad(loss_dense)(x0),
            atol=1e-5, rtol=1e-5,
        )


class TestGroupedMatmul:
    """Pallas gmm kernels (interpret mode on CPU) vs the jnp reference."""

    def _case(self, t=6, bm=8, e=3, d=16, f=32, seed=0):
        rng = np.random.RandomState(seed)
        x = jnp.asarray(rng.randn(t * bm, d).astype(np.float32))
        w = jnp.asarray(rng.randn(e, d, f).astype(np.float32) * 0.1)
        te = jnp.asarray(np.sort(rng.randint(0, e, t)).astype(np.int32))
        return x, w, te

    def test_forward_matches_reference(self):
        x, w, te = self._case()
        y = gmm_ops.gmm_call(x, w, te, bm=8, bf=16)
        yr = gmm_ops.gmm_reference(x, w, te, bm=8)
        np.testing.assert_allclose(y, yr, atol=1e-4, rtol=1e-4)

    def test_gradients_match_reference(self):
        x, w, te = self._case(seed=1)

        def loss_k(x, w):
            return jnp.sum(gmm_ops.grouped_matmul(x, w, te, 8, 16) ** 2)

        def loss_r(x, w):
            return jnp.sum(gmm_ops.gmm_reference(x, w, te, bm=8) ** 2)

        gk = jax.grad(loss_k, argnums=(0, 1))(x, w)
        gr = jax.grad(loss_r, argnums=(0, 1))(x, w)
        np.testing.assert_allclose(gk[0], gr[0], atol=1e-3, rtol=1e-3)
        np.testing.assert_allclose(gk[1], gr[1], atol=1e-3, rtol=1e-3)

    def test_dxt_kernel_matches_transposed_copy(self):
        # the stored-layout dx kernel (ADVICE r4 #4: no swapaxes HBM
        # copy) vs the old transposed-copy path, multi-block d
        x, w, te = self._case(d=256, f=32, seed=3)
        dy = jnp.asarray(
            np.random.RandomState(9).randn(x.shape[0], 32).astype(np.float32)
        )
        dx = gmm_ops.gmm_dxt_call(dy, w, te, bm=8, bd=128)
        wt = jnp.swapaxes(w, 1, 2)
        dx_ref = gmm_ops.gmm_call(dy, wt, te, bm=8, bf=16)
        assert dx is not None
        np.testing.assert_allclose(dx, dx_ref, atol=1e-4, rtol=1e-4)

    def test_dxt_falls_back_when_f_exceeds_vmem(self):
        # no resident full-F block possible -> None (bwd then takes the
        # transposed-copy path); exercised with a fake huge f via the
        # picker directly so the test stays small
        assert gmm_ops._pick_bd(256, 1024, 4096, None) > 0
        assert gmm_ops._pick_bd(256, 1024, 1 << 22, None) == 0

    def test_pick_bd_scales_with_itemsize(self):
        # ADVICE: the VMEM fit estimate must use the operand byte
        # width — float32 working sets are 2x bf16, so a block that
        # just fits at itemsize=2 must shrink (or vanish) at 4, and
        # every accepted block's double-buffered working set must
        # stay under the 14MB scoped-VMEM budget
        budget = 14 * 1024 * 1024
        for bm, d, f in ((256, 1024, 4096), (256, 2048, 8192),
                         (512, 1024, 2048)):
            b2 = gmm_ops._pick_bd(bm, d, f, None, itemsize=2)
            b4 = gmm_ops._pick_bd(bm, d, f, None, itemsize=4)
            assert b4 <= b2
            for itemsize, b in ((2, b2), (4, b4)):
                if b:
                    ws = 2 * itemsize * (bm * f + b * f + bm * b)
                    assert ws <= budget, (itemsize, b, ws)
        # a shape where the f32 working set cannot fit but bf16 can
        assert gmm_ops._pick_bd(256, 1024, 8192, None, itemsize=2) > 0
        assert gmm_ops._pick_bd(256, 1024, 8192, None, itemsize=4) == 0

    def test_absent_expert_gets_zero_grad(self):
        # expert never referenced by any tile -> dw exactly 0 there
        x, w, _ = self._case(seed=2)
        te = jnp.asarray(np.array([0, 0, 1, 1, 1, 1], np.int32))
        dw = jax.grad(
            lambda w: jnp.sum(gmm_ops.grouped_matmul(x, w, te, 8, 16))
        )(w)
        np.testing.assert_allclose(dw[2], np.zeros_like(dw[2]))
        assert float(jnp.max(jnp.abs(dw[0]))) > 0


class TestDropless:
    def _logits(self, g=64, e=4, seed=0):
        return jnp.asarray(
            np.random.RandomState(seed).randn(g, e).astype(np.float32)
        )

    def test_layout_invariants(self):
        logits = self._logits(g=96, e=4, seed=3)
        experts, gates, _ = moe_ops.dropless_topk(logits, k=2)
        bm, e = 8, 4
        lay = moe_ops.dropless_layout(experts, e, bm=bm)
        dest = np.asarray(lay.dest)
        te = np.asarray(lay.tile_expert)
        st = np.asarray(lay.slot_token)
        # every (token, choice) got a unique slot, owned by its expert
        assert len(np.unique(dest.reshape(-1))) == dest.size
        exp = np.asarray(experts)
        for t in range(dest.shape[0]):
            for j in range(dest.shape[1]):
                assert te[dest[t, j] // bm] == exp[t, j]
                assert st[dest[t, j]] == t  # slot maps back to token
        # pad slots point at the sentinel row
        used = np.zeros(st.shape[0], bool)
        used[dest.reshape(-1)] = True
        assert (st[~used] == logits.shape[0]).all()

    def test_dispatch_combine_roundtrip(self):
        # gates sum to 1 per token => combine(dispatch(x)) == x
        logits = self._logits(g=32, e=4, seed=4)
        experts, gates, _ = moe_ops.dropless_topk(logits, k=2)
        lay = moe_ops.dropless_layout(experts, 4, bm=8)
        x = jnp.asarray(
            np.random.RandomState(5).randn(32, 8).astype(np.float32)
        )
        xs = moe_ops.dispatch_sorted(x, lay)
        y = moe_ops.combine_sorted(xs, lay, gates)
        np.testing.assert_allclose(y, x, atol=1e-5, rtol=1e-5)

    def test_mlp_matches_gather_when_nothing_drops(self):
        # ample capacity: gather (capacity path) and dropless must agree
        d, m, e = 16, 32, 4
        x = jnp.asarray(
            np.random.RandomState(6).randn(2, 16, d).astype(np.float32)
        )
        outs = {}
        for dispatch in ("gather", "dropless"):
            layer = moe_models.MoEMLP(
                num_experts=e, mlp_dim=m, embed_dim=d, k=2,
                capacity_factor=4.0, dtype="float32",
                dispatch=dispatch, gmm_block_rows=8,
            )
            params = layer.init(jax.random.PRNGKey(0), x)["params"]
            outs[dispatch] = layer.apply({"params": params}, x)
        np.testing.assert_allclose(
            outs["dropless"], outs["gather"], atol=1e-4, rtol=1e-4
        )

    def test_nothing_drops_under_total_imbalance(self):
        # every token routed to expert 0: the capacity path would drop
        # most of them; dropless must process all (== dense FFN of e0)
        d, m, e, g = 8, 16, 4, 24
        # strictly positive activations so the rigged router below is
        # deterministic (logits are linear in x — a sign flip would
        # let another expert win a tie)
        x = jnp.asarray(
            np.abs(
                np.random.RandomState(7).randn(1, g, d)
            ).astype(np.float32) + 0.1
        )
        layer = moe_models.MoEMLP(
            num_experts=e, mlp_dim=m, embed_dim=d, k=1,
            dtype="float32", dispatch="dropless", gmm_block_rows=8,
        )
        params = dict(
            layer.init(jax.random.PRNGKey(0), x)["params"]
        )
        # rig the router: column 0 all-ones => logit_0 = sum(x) > 0
        # while every other expert's logit is exactly 0
        router = np.zeros((d, e), np.float32)
        router[:, 0] = 1.0
        params["router"] = jnp.asarray(router)
        params = jax.tree.map(jnp.asarray, params)
        out = layer.apply({"params": params}, x)
        wi, wg, wo = (params[n][0] for n in ("wi", "wg", "wo"))
        ref = (jax.nn.silu(x @ wg) * (x @ wi)) @ wo
        np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)

    def test_dropless_rejects_expert_sharded_mesh(self):
        import pytest

        mesh = build_mesh({"data": 2, "expert": 4})
        cfg = tr.TransformerConfig(
            vocab_size=32, num_layers=1, num_heads=2, head_dim=8,
            embed_dim=16, mlp_dim=32, dtype="float32", num_experts=4,
            expert_dispatch="dropless", mesh=mesh,
        )
        model = tr.Transformer(cfg)
        with pytest.raises(ValueError, match="dropless"):
            model.init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
            )

    def test_dropless_transformer_trains(self):
        cfg = tr.TransformerConfig(
            vocab_size=64, num_layers=1, num_heads=2, head_dim=8,
            embed_dim=32, mlp_dim=64, dtype="float32",
            num_experts=4, expert_k=2, expert_dispatch="dropless",
        )
        model = tr.Transformer(cfg)
        tokens = jnp.asarray(
            np.random.RandomState(8).randint(0, 64, (4, 16)), jnp.int32
        )
        params = model.init(jax.random.PRNGKey(0), tokens[:1])["params"]
        loss = moe_models.moe_loss_fn(model)
        opt = optax.adam(1e-2)
        opt_state = opt.init(params)

        @jax.jit
        def step(params, opt_state):
            (l, aux), g = jax.value_and_grad(loss, has_aux=True)(
                params, {"tokens": tokens}, None
            )
            updates, opt_state = opt.update(g, opt_state)
            return optax.apply_updates(params, updates), opt_state, l

        losses = []
        for _ in range(8):
            params, opt_state, l = step(params, opt_state)
            losses.append(float(l))
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]


class TestCapacityHonesty:
    """The drop-rate honesty guard: throughput
    numbers taken at a capacity factor that drops >2% of token updates
    must say so, and the quality cost must be quantified somewhere a
    reader can check — the CF=1.0 vs CF=1.25 convergence smoke below."""

    def test_check_drop_rate_quiet_below_threshold(self):
        assert moe_models.check_drop_rate(0.0) is None
        assert moe_models.check_drop_rate(0.019, capacity_factor=1.25) is None
        assert moe_models.check_drop_rate(moe_models.DROP_RATE_WARN) is None

    def test_check_drop_rate_warns_above_threshold(self, caplog):
        import logging

        with caplog.at_level(
            logging.WARNING, logger="tensorflowonspark_tpu.models.moe"
        ):
            msg = moe_models.check_drop_rate(
                0.121, capacity_factor=1.0, where="bench MoE"
            )
        assert msg is not None
        # the annotation must name the rate, the knob, and the fixes
        assert "12.1%" in msg and "capacity_factor" in msg
        assert "dropless" in msg and "bench MoE" in msg
        assert any("drop_rate" in r.message for r in caplog.records)

    def _train(self, cf, steps=30):
        """Train a small MoE transformer at the given capacity factor
        on a rigged-imbalance token stream; returns (final_loss,
        measured drop_rate on the trained router)."""
        cfg = tr.TransformerConfig(
            vocab_size=64, num_layers=1, num_heads=2, head_dim=8,
            embed_dim=32, mlp_dim=64, dtype="float32",
            num_experts=4, expert_k=2, capacity_factor=cf,
        )
        model = tr.Transformer(cfg)
        # skewed token distribution: repeated low ids make the router
        # concentrate, so CF=1.0 actually drops (uniform streams can
        # sit below the threshold and the comparison tests nothing)
        rng = np.random.RandomState(11)
        tokens = jnp.asarray(
            np.minimum(
                rng.zipf(1.6, size=(8, 16)) - 1, 63
            ).astype(np.int64),
            jnp.int32,
        )
        params = model.init(jax.random.PRNGKey(0), tokens[:1])["params"]
        loss = moe_models.moe_loss_fn(model)
        opt = optax.adam(1e-2)
        opt_state = opt.init(params)

        @jax.jit
        def step(params, opt_state):
            (l, _), g = jax.value_and_grad(loss, has_aux=True)(
                params, {"tokens": tokens}, None
            )
            updates, opt_state = opt.update(g, opt_state)
            return optax.apply_updates(params, updates), opt_state, l

        first = last = None
        for _ in range(steps):
            params, opt_state, l = step(params, opt_state)
            last = float(l)
            first = last if first is None else first
        # drop-rate telemetry on the trained router
        _, stats = model.apply(
            {"params": params}, tokens, mutable=["moe_stats"]
        )
        rates = jax.tree.leaves(stats.get("moe_stats", {}))
        drop = float(sum(jnp.mean(r) for r in rates) / len(rates))
        assert np.isfinite(last) and last < first
        return last, drop

    def test_cf_convergence_smoke(self):
        # the quality/throughput tradeoff, measured: tighter capacity
        # (CF=1.0) drops more (token, choice) updates than CF=1.25,
        # and the converged loss stays comparable at this scale — the
        # cost is bounded, not free
        loss_tight, drop_tight = self._train(cf=1.0)
        loss_ample, drop_ample = self._train(cf=1.25)
        assert drop_tight >= drop_ample
        # small-model bound: a capacity factor must not wreck
        # convergence outright; a blow-up here means drops are eating
        # the gradient signal, not just padding
        assert loss_tight < loss_ample + 0.25, (loss_tight, loss_ample)


class TestMoEMLP:
    def test_single_expert_equals_dense_ffn(self):
        d, m = 16, 32
        layer = moe_models.MoEMLP(
            num_experts=1, mlp_dim=m, embed_dim=d, k=1,
            capacity_factor=2.0, dtype="float32",
        )
        x = jnp.asarray(
            np.random.RandomState(0).randn(2, 8, d).astype(np.float32)
        )
        params = layer.init(jax.random.PRNGKey(0), x)["params"]
        out = layer.apply({"params": params}, x)

        wi, wg, wo = (params[n][0] for n in ("wi", "wg", "wo"))
        ref = (jax.nn.silu(x @ wg) * (x @ wi)) @ wo
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-4)

    def test_moe_transformer_trains_on_expert_mesh(self):
        cfg = tr.TransformerConfig(
            vocab_size=64, num_layers=2, num_heads=2, head_dim=8,
            embed_dim=32, mlp_dim=64, dtype="float32",
            num_experts=4, expert_k=2,
        )
        model = tr.Transformer(cfg)
        tokens = jnp.asarray(
            np.random.RandomState(1).randint(0, 64, (8, 16)), jnp.int32
        )
        params = model.init(jax.random.PRNGKey(0), tokens[:1])["params"]

        mesh = build_mesh({"data": 2, "expert": 4})
        trainer = dp.SyncTrainer(
            moe_models.moe_loss_fn(model),
            optax.adam(1e-2),
            mesh=mesh,
            rules=sh.RULES_EP,
            annotations=tr.logical_axes(params),
            has_aux=True,
        )
        state = trainer.create_state(params)
        # expert weights actually sharded over the expert axis
        wi = state.params["block_0"]["moe"]["wi"]
        spec = wi.sharding.spec
        assert "expert" in str(spec), spec

        losses = []
        for i in range(10):
            state, metrics = trainer.step(
                state, {"tokens": tokens}, jax.random.PRNGKey(i)
            )
            losses.append(float(metrics["loss"]))
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]
        assert float(metrics["moe_aux"]) > 0

    def test_sharded_matches_unsharded(self):
        cfg = tr.TransformerConfig(
            vocab_size=32, num_layers=1, num_heads=2, head_dim=8,
            embed_dim=16, mlp_dim=32, dtype="float32", num_experts=4,
        )
        model = tr.Transformer(cfg)
        tokens = jnp.asarray(
            np.random.RandomState(2).randint(0, 32, (8, 8)), jnp.int32
        )
        params = model.init(jax.random.PRNGKey(0), tokens[:1])["params"]
        loss = moe_models.moe_loss_fn(model)

        ref_l, _ = loss(params, {"tokens": tokens}, None)

        mesh = build_mesh({"data": 2, "expert": 4})
        sharded = sh.shard_params(
            params, sh.RULES_EP, mesh, tr.logical_axes(params)
        )
        got_l, _ = jax.jit(loss)(sharded, {"tokens": tokens}, None)
        np.testing.assert_allclose(
            float(got_l), float(ref_l), atol=1e-5, rtol=1e-5
        )
