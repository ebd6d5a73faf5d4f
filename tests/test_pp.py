"""Pipeline parallelism vs a sequential single-device reference.

The numerics contract: a P-stage microbatched pipeline computes exactly
the same function as applying all L layers sequentially — forward AND
gradients (the backward pipeline is autodiff through scan+ppermute).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

pytestmark = pytest.mark.slow
from jax.sharding import PartitionSpec as P

from tensorflowonspark_tpu.parallel import pp
from tensorflowonspark_tpu.parallel.mesh import build_mesh


def _layer_fn(params, h):
    return jnp.tanh(h @ params["w"] + params["b"])


def _make_layers(num_layers, dim, seed=0):
    rng = np.random.RandomState(seed)
    return [
        {
            "w": jnp.asarray(rng.randn(dim, dim).astype(np.float32) * 0.3),
            "b": jnp.asarray(rng.randn(dim).astype(np.float32) * 0.1),
        }
        for _ in range(num_layers)
    ]


def _sequential(layers, x):
    for lp in layers:
        x = _layer_fn(lp, x)
    return x


class TestPipelinePrimitive:
    @pytest.mark.parametrize("num_micro", [4, 8])
    def test_matches_sequential(self, num_micro):
        dim, num_layers, stages = 16, 8, 4
        layers = _make_layers(num_layers, dim)
        stacked = pp.stack_stage_params(layers, stages)
        mesh = build_mesh({"data": 2, "pipe": 4})

        x = np.random.RandomState(1).randn(num_micro, 4, dim).astype(np.float32)
        ref = _sequential(layers, x.reshape(-1, dim)).reshape(x.shape)

        stage = functools.partial(pp._layers_scan, _layer_fn)

        @functools.partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P("pipe"), stacked), P()),
            out_specs=P(),
            check_vma=False,
        )
        def run(stage_params, micro):
            return pp.pipeline(
                stage, pp.local_stage(stage_params), micro, axis_name="pipe"
            )

        out = run(stacked, jnp.asarray(x))
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    def test_stack_requires_divisibility(self):
        layers = _make_layers(6, 4)
        with pytest.raises(ValueError, match="divide"):
            pp.stack_stage_params(layers, 4)


class TestPipelineTrainer:
    def _setup(self, mesh_axes, num_layers=4, dim=8, stages=None,
               interleave=1):
        mesh = build_mesh(mesh_axes)
        stages = stages or mesh.shape["pipe"]
        rng = np.random.RandomState(2)
        layers = _make_layers(num_layers, dim, seed=3)
        params = {
            "stages": pp.stack_stage_params(
                layers, stages, interleave=interleave
            ),
            "first": {
                "w_in": jnp.asarray(rng.randn(dim, dim).astype(np.float32) * 0.3)
            },
            "last": {
                "w_out": jnp.asarray(rng.randn(dim, 1).astype(np.float32) * 0.3)
            },
        }

        def first_fn(p, batch):
            return batch["x"] @ p["w_in"]

        def last_fn(p, h, batch):
            pred = (h @ p["w_out"])[:, 0]
            loss = jnp.mean((pred - batch["y"]) ** 2)
            return loss, {"mse": loss}

        def reference_loss(params, batch):
            h = batch["x"] @ params["first"]["w_in"]
            for lp in layers_from_stacked(params["stages"]):
                h = _layer_fn(lp, h)
            pred = (h @ params["last"]["w_out"])[:, 0]
            return jnp.mean((pred - batch["y"]) ** 2)

        def layers_from_stacked(stacked):
            if interleave > 1:
                # [P, v, lc, ...]: absolute chunk a = c*P + d at [d, c]
                p_, v_, l_ = jax.tree.leaves(stacked)[0].shape[:3]
                return [
                    jax.tree.map(lambda x: x[a % p_, a // p_, j], stacked)
                    for a in range(p_ * v_)
                    for j in range(l_)
                ]
            p_, l_ = jax.tree.leaves(stacked)[0].shape[:2]
            out = []
            for i in range(p_):
                for j in range(l_):
                    out.append(jax.tree.map(lambda x: x[i, j], stacked))
            return out

        return mesh, params, first_fn, last_fn, reference_loss

    def test_loss_and_grads_match_reference(self):
        mesh, params, first_fn, last_fn, ref_loss = self._setup(
            {"data": 2, "pipe": 4}
        )
        batch = {
            "x": np.random.RandomState(4).randn(16, 8).astype(np.float32),
            "y": np.random.RandomState(5).randn(16).astype(np.float32),
        }
        # SGD lr=1 turns the param delta into the (negated) gradient
        trainer = pp.PipelineTrainer(
            _layer_fn, first_fn, last_fn, optax.sgd(1.0), mesh,
            num_microbatches=4,
        )
        state = trainer.create_state(jax.tree.map(jnp.asarray, params))
        old_params = jax.tree.map(np.asarray, state.params)  # donated below
        new_state, metrics = trainer.step(state, batch)

        ref_l, ref_g = jax.value_and_grad(ref_loss)(
            params, jax.tree.map(jnp.asarray, batch)
        )
        np.testing.assert_allclose(
            float(metrics["loss"]), float(ref_l), atol=1e-5, rtol=1e-5
        )
        got_g = jax.tree.map(
            lambda old, new: old - np.asarray(new), old_params, new_state.params
        )
        for path, g in jax.tree_util.tree_flatten_with_path(got_g)[0]:
            r = functools.reduce(
                lambda t, k: t[k.key if hasattr(k, "key") else k.idx],
                path,
                ref_g,
            )
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(r), atol=1e-4, rtol=1e-4,
                err_msg=str(path),
            )

    def _run_pp_tp_case(self, schedule, interleave=1, num_layers=4,
                        seed=11):
        """Shared 3-axis harness: pipeline stages whose inner matmuls
        are tensor-parallel on ``model`` (Megatron column/row pair with
        tp_copy/tp_reduce), under data parallelism — mesh
        {data:2, pipe:2, model:2}.  Loss and every gradient must equal
        the sequential single-device reference (SGD lr=1 turns the
        param delta into the negated gradient)."""
        from tensorflowonspark_tpu.parallel.tp import tp_copy, tp_reduce

        dim, hid, stages = 8, 16, 2
        rng = np.random.RandomState(seed)
        layers = [
            {
                "w1": jnp.asarray(rng.randn(dim, hid).astype(np.float32) * 0.3),
                "w2": jnp.asarray(rng.randn(hid, dim).astype(np.float32) * 0.3),
                "b": jnp.asarray(rng.randn(dim).astype(np.float32) * 0.1),
            }
            for _ in range(num_layers)
        ]

        def tp_layer_fn(lp, h):
            z = jnp.tanh(tp_copy(h, "model") @ lp["w1"])
            return tp_reduce(z @ lp["w2"], "model") + lp["b"]

        def ref_layer_fn(lp, h):
            return jnp.tanh(h @ lp["w1"]) @ lp["w2"] + lp["b"]

        mesh = build_mesh({"data": 2, "pipe": 2, "model": 2})
        params = {
            "stages": pp.stack_stage_params(
                layers, stages, interleave=interleave
            ),
            "first": {
                "w_in": jnp.asarray(rng.randn(dim, dim).astype(np.float32) * 0.3)
            },
            "last": {
                "w_out": jnp.asarray(rng.randn(dim, 1).astype(np.float32) * 0.3)
            },
        }
        # interleaved stage stacks are [P, v, L/(P*v), ...]: the TP
        # specs grow a chunk dim but still lead with pipe
        chunk = (None,) if interleave > 1 else ()
        stage_specs = {
            "w1": P("pipe", *chunk, None, None, "model"),  # column-par.
            "w2": P("pipe", *chunk, None, "model", None),  # row-par.
            "b": P("pipe"),
        }

        def first_fn(p, batch):
            return batch["x"] @ p["w_in"]

        def last_fn(p, h, batch):
            pred = (h @ p["w_out"])[:, 0]
            loss = jnp.mean((pred - batch["y"]) ** 2)
            return loss, {}

        def iter_layers(st):
            if interleave > 1:
                # absolute chunk a lives at [a % P, a // P]
                p_, v_, l_ = jax.tree.leaves(st)[0].shape[:3]
                return (
                    jax.tree.map(lambda x: x[a % p_, a // p_, j], st)
                    for a in range(p_ * v_)
                    for j in range(l_)
                )
            p_, l_ = jax.tree.leaves(st)[0].shape[:2]
            return (
                jax.tree.map(lambda x: x[i, j], st)
                for i in range(p_)
                for j in range(l_)
            )

        def ref_loss(params, batch):
            h = batch["x"] @ params["first"]["w_in"]
            for lp in iter_layers(params["stages"]):
                h = ref_layer_fn(lp, h)
            pred = (h @ params["last"]["w_out"])[:, 0]
            return jnp.mean((pred - batch["y"]) ** 2)

        batch = {
            "x": np.random.RandomState(seed + 1).randn(16, dim).astype(
                np.float32
            ),
            "y": np.random.RandomState(seed + 2).randn(16).astype(
                np.float32
            ),
        }
        trainer = pp.PipelineTrainer(
            tp_layer_fn, first_fn, last_fn, optax.sgd(1.0), mesh,
            num_microbatches=4, schedule=schedule,
            interleave=interleave, stage_specs=stage_specs,
        )
        state = trainer.create_state(jax.tree.map(jnp.asarray, params))
        old_params = jax.tree.map(np.asarray, state.params)
        new_state, metrics = trainer.step(state, batch)

        ref_l, ref_g = jax.value_and_grad(ref_loss)(
            params, jax.tree.map(jnp.asarray, batch)
        )
        np.testing.assert_allclose(
            float(metrics["loss"]), float(ref_l), atol=1e-5, rtol=1e-5
        )
        got_g = jax.tree.map(
            lambda old, new: old - np.asarray(new), old_params,
            new_state.params,
        )
        for path, g in jax.tree_util.tree_flatten_with_path(got_g)[0]:
            r = functools.reduce(
                lambda t, k: t[k.key if hasattr(k, "key") else k.idx],
                path,
                ref_g,
            )
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(r), atol=1e-4, rtol=1e-4,
                err_msg=str(path),
            )

    @pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
    def test_pp_x_tp_loss_and_grads_match_reference(self, schedule):
        self._run_pp_tp_case(schedule)

    def test_pp_x_tp_interleaved_matches_reference(self):
        self._run_pp_tp_case(
            "interleaved", interleave=2, num_layers=8, seed=21
        )

    def test_requires_pipe_axis(self):
        mesh = build_mesh({"data": 8})
        with pytest.raises(ValueError, match="pipe"):
            pp.PipelineTrainer(
                _layer_fn, lambda p, b: b["x"], lambda p, h, b: (0.0, {}),
                optax.sgd(1.0), mesh, num_microbatches=2,
            )

    def test_stage_specs_must_lead_with_pipe(self):
        # forgetting the leading pipe dim would run stage 0's weights on
        # every stage with no shape error — must be rejected up front
        mesh = build_mesh({"pipe": 2, "model": 2, "data": 2})
        with pytest.raises(ValueError, match="leading"):
            pp.PipelineTrainer(
                _layer_fn, lambda p, b: b["x"], lambda p, h, b: (0.0, {}),
                optax.sgd(1.0), mesh, num_microbatches=2,
                stage_specs={"w": P(None, None, None, "model")},
            )

    def test_training_reduces_loss(self):
        mesh, params, first_fn, last_fn, _ = self._setup(
            {"pipe": 8}, num_layers=8
        )
        batch = {
            "x": np.random.RandomState(6).randn(32, 8).astype(np.float32),
            "y": np.random.RandomState(7).randn(32).astype(np.float32),
        }
        trainer = pp.PipelineTrainer(
            _layer_fn, first_fn, last_fn, optax.adam(3e-3), mesh,
            num_microbatches=8,
        )
        state = trainer.create_state(jax.tree.map(jnp.asarray, params))
        losses = []
        for _ in range(30):
            state, metrics = trainer.step(state, batch)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0] * 0.7, losses[:3] + losses[-3:]

    def test_1f1b_loss_and_grads_match_gpipe_reference(self):
        # the hand-scheduled 1F1B step computes the SAME gradients as
        # the AD-derived GPipe step and the sequential reference
        mesh, params, first_fn, last_fn, ref_loss = self._setup(
            {"data": 2, "pipe": 4}
        )
        batch = {
            "x": np.random.RandomState(4).randn(16, 8).astype(np.float32),
            "y": np.random.RandomState(5).randn(16).astype(np.float32),
        }
        trainer = pp.PipelineTrainer(
            _layer_fn, first_fn, last_fn, optax.sgd(1.0), mesh,
            num_microbatches=4, schedule="1f1b",
        )
        state = trainer.create_state(jax.tree.map(jnp.asarray, params))
        old_params = jax.tree.map(np.asarray, state.params)
        new_state, metrics = trainer.step(state, batch)

        ref_l, ref_g = jax.value_and_grad(ref_loss)(
            params, jax.tree.map(jnp.asarray, batch)
        )
        np.testing.assert_allclose(
            float(metrics["loss"]), float(ref_l), atol=1e-5, rtol=1e-5
        )
        got_g = jax.tree.map(
            lambda old, new: old - np.asarray(new), old_params, new_state.params
        )
        for path, g in jax.tree_util.tree_flatten_with_path(got_g)[0]:
            r = functools.reduce(
                lambda t, k: t[k.key if hasattr(k, "key") else k.idx],
                path,
                ref_g,
            )
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(r), atol=1e-4, rtol=1e-4,
                err_msg=str(path),
            )

    @pytest.mark.parametrize(
        "axes,layers,m",
        [
            ({"data": 2, "pipe": 4}, 8, 4),
            # m=8 > stash depth: exercises the modular stash/handoff
            # slot reuse the static analysis sized
            ({"data": 4, "pipe": 2}, 8, 8),
        ],
    )
    def test_interleaved_loss_and_grads_match_reference(self, axes, layers, m):
        # the interleaved tick program computes the SAME gradients as
        # the sequential reference (hence also GPipe/1F1B, which match
        # it by the tests above)
        mesh, params, first_fn, last_fn, ref_loss = self._setup(
            axes, num_layers=layers, stages=axes["pipe"], interleave=2
        )
        rows = 16 * m // 4  # local batch must divide by m on every shard
        batch = {
            "x": np.random.RandomState(4).randn(rows, 8).astype(np.float32),
            "y": np.random.RandomState(5).randn(rows).astype(np.float32),
        }
        trainer = pp.PipelineTrainer(
            _layer_fn, first_fn, last_fn, optax.sgd(1.0), mesh,
            num_microbatches=m, schedule="interleaved", interleave=2,
        )
        state = trainer.create_state(jax.tree.map(jnp.asarray, params))
        old_params = jax.tree.map(np.asarray, state.params)
        new_state, metrics = trainer.step(state, batch)

        ref_l, ref_g = jax.value_and_grad(ref_loss)(
            params, jax.tree.map(jnp.asarray, batch)
        )
        np.testing.assert_allclose(
            float(metrics["loss"]), float(ref_l), atol=1e-5, rtol=1e-5
        )
        got_g = jax.tree.map(
            lambda old, new: old - np.asarray(new), old_params, new_state.params
        )
        for path, g in jax.tree_util.tree_flatten_with_path(got_g)[0]:
            r = functools.reduce(
                lambda t, k: t[k.key if hasattr(k, "key") else k.idx],
                path,
                ref_g,
            )
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(r), atol=1e-4, rtol=1e-4,
                err_msg=str(path),
            )

    def test_interleaved_training_reduces_loss(self):
        mesh, params, first_fn, last_fn, _ = self._setup(
            {"data": 4, "pipe": 2}, num_layers=8, stages=2, interleave=2
        )
        batch = {
            "x": np.random.RandomState(6).randn(32, 8).astype(np.float32),
            "y": np.random.RandomState(7).randn(32).astype(np.float32),
        }
        trainer = pp.PipelineTrainer(
            _layer_fn, first_fn, last_fn, optax.adam(3e-3), mesh,
            num_microbatches=8, schedule="interleaved", interleave=2,
        )
        state = trainer.create_state(jax.tree.map(jnp.asarray, params))
        losses = []
        for _ in range(20):
            state, metrics = trainer.step(state, batch)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0] * 0.8, losses[:3] + losses[-3:]

    def test_interleaved_requires_v_ge_2(self):
        mesh = build_mesh({"pipe": 2, "data": 4})
        with pytest.raises(ValueError, match="interleave"):
            pp.PipelineTrainer(
                _layer_fn, lambda p, b: b["x"], lambda p, h, b: (0.0, {}),
                optax.sgd(1.0), mesh, num_microbatches=2,
                schedule="interleaved", interleave=1,
            )

    def test_1f1b_training_reduces_loss(self):
        mesh, params, first_fn, last_fn, _ = self._setup(
            {"data": 2, "pipe": 4}, num_layers=4, stages=4
        )
        batch = {
            "x": np.random.RandomState(6).randn(32, 8).astype(np.float32),
            "y": np.random.RandomState(7).randn(32).astype(np.float32),
        }
        trainer = pp.PipelineTrainer(
            _layer_fn, first_fn, last_fn, optax.adam(3e-3), mesh,
            num_microbatches=8, schedule="1f1b",
        )
        state = trainer.create_state(jax.tree.map(jnp.asarray, params))
        losses = []
        for _ in range(20):
            state, metrics = trainer.step(state, batch)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0] * 0.8, losses[:3] + losses[-3:]


class TestSchedules:
    """Scheduled-ops trace tests: 1F1B's activation
    stash is O(P) where GPipe's is O(M), and the interleaved schedule
    has measurably fewer idle ticks; single-slot handoff buffers never
    overrun."""

    def test_1f1b_stash_bound_vs_gpipe(self):
        from tensorflowonspark_tpu.parallel import pp_schedule as ps

        p, m = 4, 16  # M = 4P
        g = ps.stats(ps.simulate(p, m, "gpipe"))
        f = ps.stats(ps.simulate(p, m, "1f1b"))
        assert g["peak_in_flight"] == [m] * p
        assert f["peak_in_flight"] == [p - d for d in range(p)]
        # same bubble at v=1 (the memory, not the bubble, is the win)
        assert f["makespan"] == g["makespan"] == 2 * (m + p - 1)

    def test_interleaved_1f1b_fewer_idle_ticks(self):
        from tensorflowonspark_tpu.parallel import pp_schedule as ps

        p, m, v = 4, 16, 2  # M = 4P, two virtual chunks per device
        g = ps.stats(ps.simulate(p, m, "gpipe"))
        i = ps.stats(ps.simulate(p, m, "1f1b", interleave=v), unit_time=1.0 / v)
        assert sum(i["idle_ticks"]) < sum(g["idle_ticks"])
        assert i["bubble_fraction"] < g["bubble_fraction"]
        assert i["makespan"] < g["makespan"]

    @pytest.mark.parametrize("p,m,v", [(2, 4, 1), (4, 8, 1), (8, 32, 1)])
    def test_analyze_program_v1_single_slot(self, p, m, v):
        # static buffer analysis confirms the v=1 executor's geometry:
        # single-slot handoffs, O(P) stash
        from tensorflowonspark_tpu.parallel import pp_schedule as ps

        tab = ps.simulate(p, m, "1f1b")
        geom = ps.analyze_program(tab, p)
        assert geom == {
            "stash_slots": min(p, m), "fwd_slots": 1, "bwd_slots": 1,
        }

    @pytest.mark.parametrize(
        "p,m,v", [(2, 4, 2), (4, 8, 2), (2, 6, 3), (4, 16, 2)]
    )
    def test_analyze_program_interleaved_depths(self, p, m, v):
        # the chunk-cycling order needs deeper handoff banks; the
        # analysis must find finite depths (i.e. the schedule is
        # executable) and a stash no deeper than the microbatch count
        from tensorflowonspark_tpu.parallel import pp_schedule as ps

        tab = ps.simulate(p, m, "1f1b", interleave=v)
        geom = ps.analyze_program(tab, p, interleave=v)
        assert 1 <= geom["fwd_slots"] <= m
        assert 1 <= geom["bwd_slots"] <= m
        assert geom["stash_slots"] <= m

    @pytest.mark.parametrize("p,m", [(2, 4), (4, 8), (3, 9), (4, 5), (8, 32)])
    def test_single_slot_handoff_never_overruns(self, p, m):
        # the execution in pp.py keeps ONE fwd and ONE bwd buffer; the
        # schedule must never produce unit j+1 before j was consumed
        from tensorflowonspark_tpu.parallel import pp_schedule as ps

        tab = ps.simulate(p, m, "1f1b")
        tick_f, tick_b = {}, {}
        for d in range(p):
            for t, u in enumerate(tab[d]):
                if u is None:
                    continue
                (tick_f if u.kind == "F" else tick_b)[(d, u.mb)] = t
        for d in range(1, p):
            for j in range(m - 1):
                assert tick_f[(d - 1, j + 1)] >= tick_f[(d, j)]
        for d in range(p - 1):
            for j in range(m - 1):
                assert tick_b[(d + 1, j + 1)] >= tick_b[(d, j)]
