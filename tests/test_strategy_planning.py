"""Strategy-level planning helpers (ep/cp/tp modules).

The capacity-planning/validation surfaces of the strategy modules: EP
expert sizing, CP strategy choice and comms volumes, TP placement
pre-flight.
"""

import jax
import jax.numpy as jnp
import numpy as np

from tensorflowonspark_tpu.parallel import cp, ep, tp
from tensorflowonspark_tpu.parallel.mesh import build_mesh


class TestEPPlan:
    def test_capacity_and_mesh_fit(self):
        plan = ep.plan(
            num_experts=8,
            tokens_per_batch=4096,
            k=2,
            capacity_factor=1.25,
            n_devices=8,
            embed_dim=512,
            mlp_dim=2048,
        )
        # balanced share = k*T/E = 1024; cf 1.25 -> 1280, +1 and rounded
        # up to the 8-sublane multiple -> 1288 (ops.moe.expert_capacity)
        assert plan["capacity_per_expert"] == 1288
        assert plan["expert_axis"] == 8
        assert plan["experts_per_device"] == 1
        assert plan["slack"] >= 1.25 - 1e-6
        assert 0.0 <= plan["drop_at_2x_hotspot"] < 1.0
        assert plan["expert_bytes_per_device"] == 3 * 512 * 2048 * 2
        assert plan["alltoall_bytes_per_layer"] == 2 * 2 * 4096 * 512 * 2

    def test_non_dividing_device_count_falls_back(self):
        plan = ep.plan(num_experts=6, tokens_per_batch=64, n_devices=4)
        assert plan["expert_axis"] == 3  # largest divisor of 6 <= 4
        assert plan["experts_per_device"] == 2

    def test_utilization(self):
        probs = jnp.full((32, 4), 0.25)
        load, imbalance = ep.utilization(probs, 4)
        np.testing.assert_allclose(np.asarray(load), [0.25] * 4, atol=1e-6)
        assert abs(imbalance - 1.0) < 1e-5

    def test_trainer_trains(self):
        mesh = build_mesh({"data": 2, "expert": 4})

        def loss_fn(params, batch, rng):
            pred = batch["x"] @ params["w"]
            return jnp.mean((pred - batch["y"]) ** 2), {}

        import optax

        trainer = ep.trainer(loss_fn, optax.sgd(0.1), mesh)
        state = trainer.create_state({"w": jnp.zeros((4,))})
        batch = {
            "x": np.random.RandomState(0).randn(16, 4).astype(np.float32),
            "y": np.zeros((16,), np.float32),
        }
        state, metrics = trainer.step(state, batch)
        assert np.isfinite(float(metrics["loss"]))


class TestCPPlan:
    def test_choose_strategy(self):
        # short local seq + dividing heads -> ulysses
        assert cp.choose_strategy(8192, num_heads=8, head_dim=64, seq_devices=4) == "ulysses"
        # heads don't divide -> ring
        assert cp.choose_strategy(8192, num_heads=6, head_dim=64, seq_devices=4) == "ring"
        # very long local seq -> ring (hops hide under compute)
        assert cp.choose_strategy(65536, num_heads=8, head_dim=64, seq_devices=4) == "ring"
        assert cp.choose_strategy(4096, num_heads=8, head_dim=64, seq_devices=1) == "ring"

    def test_plan_volumes(self):
        plan = cp.plan(
            seq_len=32768, batch=1, num_heads=8, head_dim=64,
            seq_devices=8, dtype_bytes=2,
        )
        assert plan["local_seq"] == 4096
        # ring: 2*B*localS*H*D*bytes per hop x (N-1) hops
        hop = 2 * 1 * 4096 * 8 * 64 * 2
        assert plan["ring_bytes_per_call"] == hop * 7
        assert plan["ring_hops"] == 7
        assert plan["ulysses_valid"]
        assert plan["naive_scores_bytes"] == 1 * 8 * 32768 * 32768 * 4
        assert plan["recommended"] in ("ring", "ulysses")


class TestTPValidate:
    def test_reports_unsharded_targeted_dim(self):
        from tensorflowonspark_tpu.models import transformer as tr
        from tensorflowonspark_tpu.parallel import sharding as sh

        mesh = build_mesh({"data": 2, "model": 4})
        # heads=2 cannot shard over model=4 -> must be reported
        cfg = tr.TransformerConfig(
            vocab_size=64, num_layers=1, num_heads=2, head_dim=8,
            embed_dim=16, mlp_dim=32, dtype="float32",
        )
        model = tr.Transformer(cfg)
        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        report = tp.validate(
            params, tr.logical_axes(params), mesh, rules=sh.RULES_TP
        )
        assert report["total_param_bytes"] > 0
        assert report["sharding_ratio"] > 1.0  # something did shard
        flagged = {
            logical for _, _, logical, _ in report["unsharded_targeted_dims"]
        }
        assert "heads" in flagged

    def test_tuple_container_params_counted_fully(self):
        # a tuple *container* inside params must not swallow its
        # annotation leaves (flatten_up_to, not plain tree_leaves)
        from tensorflowonspark_tpu.parallel import sharding as sh  # noqa: F401

        mesh = build_mesh({"data": 4, "model": 2})
        params = {"blocks": (jnp.zeros((4, 8)), jnp.zeros((8, 4)))}
        ann = {"blocks": (("embed", "mlp"), ("mlp", "embed"))}
        report = tp.validate(params, ann, mesh, rules=(("mlp", "model"),))
        assert report["total_param_bytes"] == 256
        assert report["sharding_ratio"] == 2.0
        assert report["unsharded_targeted_dims"] == []

    def test_clean_placement_reports_nothing(self):
        from tensorflowonspark_tpu.models import transformer as tr
        from tensorflowonspark_tpu.parallel import sharding as sh

        mesh = build_mesh({"data": 2, "model": 4})
        cfg = tr.TransformerConfig(
            vocab_size=64, num_layers=1, num_heads=4, head_dim=8,
            embed_dim=16, mlp_dim=32, dtype="float32",
        )
        model = tr.Transformer(cfg)
        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        report = tp.validate(
            params, tr.logical_axes(params), mesh, rules=sh.RULES_TP
        )
        assert report["unsharded_targeted_dims"] == []
