"""Mesh / sharding / DP-trainer tests on the virtual 8-device CPU mesh
(conftest.py forces xla_force_host_platform_device_count=8 — the
reference's analogue was a 2-worker local Spark Standalone cluster,
reference: test/run_tests.sh:16-27)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec

from tensorflowonspark_tpu.parallel import dp, mesh as mesh_mod, sharding as sh


class TestMesh:
    def test_default_all_data(self):
        m = mesh_mod.build_mesh()
        assert m.shape["data"] == 8

    def test_spec_resolve_wildcard(self):
        spec = mesh_mod.MeshSpec(data=-1, model=2)
        assert spec.resolve(8) == [("data", 4), ("model", 2)]

    def test_spec_resolve_exact(self):
        spec = mesh_mod.MeshSpec.from_axes([("pipe", 2), ("data", 4)])
        assert spec.resolve(8) == [("pipe", 2), ("data", 4)]

    def test_spec_mismatch_raises(self):
        with pytest.raises(ValueError):
            mesh_mod.MeshSpec(data=3).resolve(8)
        with pytest.raises(ValueError):
            mesh_mod.MeshSpec.from_axes([("a", -1), ("b", -1)]).resolve(8)

    def test_canonical_order(self):
        spec = mesh_mod.MeshSpec(model=2, data=-1, pipe=1)
        names = [n for n, _ in spec.resolve(8)]
        assert names == ["pipe", "data", "model"]

    def test_mesh_axis_size(self):
        m = mesh_mod.build_mesh({"data": 4, "model": 2})
        assert mesh_mod.mesh_axis_size(m, "data") == 4
        assert mesh_mod.mesh_axis_size(m, "data", "model") == 8
        assert mesh_mod.mesh_axis_size(m, "absent") == 1


class TestShardingRules:
    def test_apply_rules_basic(self):
        m = mesh_mod.build_mesh({"data": 4, "model": 2})
        spec = sh.apply_rules(("batch", None, "heads"), sh.RULES_TP, m)
        assert spec == PartitionSpec("data", None, "model")

    def test_apply_rules_absent_axis_drops(self):
        m = mesh_mod.build_mesh({"data": 8})
        spec = sh.apply_rules(("batch", "mlp"), sh.RULES_TP, m)
        # no 'model' axis on this mesh -> mlp resolves to replicated
        assert spec == PartitionSpec("data")

    def test_mesh_axis_used_once_per_spec(self):
        m = mesh_mod.build_mesh({"data": 4, "model": 2})
        spec = sh.apply_rules(("mlp", "heads"), sh.RULES_TP, m)
        # both map to 'model'; second dimension must not reuse it
        assert spec == PartitionSpec("model")

    def test_param_specs_heuristic_fsdp(self):
        m = mesh_mod.build_mesh({"fsdp": 8})
        params = {"w": jnp.zeros((16, 6)), "b": jnp.zeros((6,))}
        specs = sh.param_specs(params, sh.RULES_FSDP, m)
        assert specs["w"] == PartitionSpec("fsdp")  # dim0=16 divisible
        assert specs["b"] == PartitionSpec()  # 6 not divisible by 8

    def test_shard_batch_single_process(self):
        m = mesh_mod.build_mesh({"data": 8})
        batch = {"x": np.ones((16, 4), np.float32)}
        out = sh.shard_batch(batch, m)
        assert out["x"].sharding.spec == PartitionSpec("data")


class TestSyncTrainer:
    def _make(self, m=None):
        from tensorflowonspark_tpu.models import mlp

        model = mlp.MNISTNet(hidden=32)
        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 28 * 28))
        )["params"]
        trainer = dp.SyncTrainer(
            mlp.loss_fn(model),
            optax.sgd(0.1),
            mesh=m,
            annotations=mlp.logical_axes(params),
            has_aux=True,
        )
        return trainer, params

    def test_loss_decreases(self):
        trainer, params = self._make()
        state = trainer.create_state(params)
        rng = jax.random.PRNGKey(1)
        x = jax.random.normal(rng, (64, 28 * 28))
        y = (jnp.arange(64) % 10).astype(jnp.int32)
        losses = []
        for i in range(10):
            state, metrics = trainer.step(state, (x, y), jax.random.PRNGKey(i))
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0]
        assert int(state.step) == 10

    def test_multi_step_matches_sequential(self):
        # K fused steps (one dispatch, lax.scan) must equal K calls of
        # step() with the same batches/rngs
        m = mesh_mod.build_mesh({"data": 8})
        trainer, params = self._make(m)
        K = 4
        rng = jax.random.PRNGKey(7)
        xs = np.asarray(jax.random.normal(rng, (K, 32, 784)), np.float32)
        ys = np.tile((np.arange(32) % 10).astype(np.int32), (K, 1))
        rngs = jax.random.split(jax.random.PRNGKey(3), K)

        s_seq = trainer.create_state(params)
        for i in range(K):
            s_seq, m_seq = trainer.step(s_seq, (xs[i], ys[i]), rngs[i])

        s_multi = trainer.create_state(params)
        s_multi, m_multi = trainer.multi_step(s_multi, (xs, ys), rngs)

        assert int(s_multi.step) == K
        assert m_multi["loss"].shape == (K,)
        np.testing.assert_allclose(
            float(m_multi["loss"][-1]), float(m_seq["loss"]), rtol=1e-5
        )
        for a, b in zip(
            jax.tree_util.tree_leaves(s_seq.params),
            jax.tree_util.tree_leaves(s_multi.params),
        ):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)

    def test_step_on_device_with_prefetch(self):
        # the prefetch + step_on_device pairing matches plain step()
        from tensorflowonspark_tpu.data.feed import prefetch_to_device

        m = mesh_mod.build_mesh({"data": 8})
        trainer, params = self._make(m)
        xs = np.asarray(
            jax.random.normal(jax.random.PRNGKey(5), (3, 32, 784)), np.float32
        )
        ys = np.tile((np.arange(32) % 10).astype(np.int32), (3, 1))
        rngs = jax.random.split(jax.random.PRNGKey(2), 3)

        s_ref = trainer.create_state(params)
        for i in range(3):
            s_ref, m_ref = trainer.step(s_ref, (xs[i], ys[i]), rngs[i])

        s_dev = trainer.create_state(params)
        it = prefetch_to_device(
            ((xs[i], ys[i]) for i in range(3)),
            size=2,
            sharding=trainer.batch_sharding(),
        )
        for i, db in enumerate(it):
            s_dev, m_dev = trainer.step_on_device(s_dev, db, rngs[i])

        np.testing.assert_allclose(
            float(m_dev["loss"]), float(m_ref["loss"]), rtol=1e-5
        )

    def test_batch_is_sharded_over_data_axis(self):
        m = mesh_mod.build_mesh({"data": 8})
        trainer, params = self._make(m)
        state = trainer.create_state(params)
        x = np.zeros((32, 784), np.float32)
        y = np.zeros((32,), np.int32)
        state, metrics = trainer.step(state, (x, y))
        # params stay replicated under DP rules
        leaf = jax.tree_util.tree_leaves(state.params)[0]
        assert leaf.sharding.is_fully_replicated

    def test_fsdp_params_sharded(self):
        from tensorflowonspark_tpu.models import mlp

        m = mesh_mod.build_mesh({"fsdp": 8})
        model = mlp.MNISTNet(hidden=32)
        params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 784)))[
            "params"
        ]
        trainer = dp.SyncTrainer(
            mlp.loss_fn(model),
            optax.sgd(0.1),
            mesh=m,
            rules=sh.RULES_FSDP,
            annotations=mlp.logical_axes(params),
            has_aux=True,
        )
        state = trainer.create_state(params)
        k = state.params["dense1"]["kernel"]
        assert not k.sharding.is_fully_replicated
        x = np.zeros((16, 784), np.float32)
        y = np.zeros((16,), np.int32)
        state, metrics = trainer.step(state, (x, y))
        assert np.isfinite(metrics["loss"])

    def test_model_state_batchnorm(self):
        from tensorflowonspark_tpu.models import resnet

        model = resnet.ResNetCIFAR(depth=8, dtype="float32")
        variables = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3))
        )
        trainer = dp.SyncTrainer(
            resnet.loss_fn(model),
            optax.sgd(0.01),
            has_model_state=True,
        )
        state = trainer.create_state(
            variables["params"], {"batch_stats": variables["batch_stats"]}
        )
        x = np.random.RandomState(0).rand(8, 32, 32, 3).astype(np.float32)
        y = np.zeros((8,), np.int32)
        # snapshot before stepping: the step donates the old state's buffers
        old_stats = np.asarray(
            jax.tree_util.tree_leaves(state.model_state)[0]
        ).copy()
        state, metrics = trainer.step(state, (x, y))
        new_stats = np.asarray(jax.tree_util.tree_leaves(state.model_state)[0])
        assert np.isfinite(metrics["loss"])
        assert not np.allclose(old_stats, new_stats)


class TestGlobalStop:
    def test_single_process_passthrough(self):
        assert dp.all_hosts_ready(True)
        assert not dp.all_hosts_ready(False)

    def test_default_batch_dicts(self):
        rows = [{"a": 1, "b": 2.0}, {"a": 3, "b": 4.0}]
        batch = dp._default_batch(rows)
        assert batch["a"].tolist() == [1, 3]

    def test_default_batch_tuples(self):
        rows = [(1, 2.0), (3, 4.0)]
        batch = dp._default_batch(rows)
        assert batch[0].tolist() == [1, 3]


def test_multi_step_on_device_matches_multi_step():
    # the device-resident benchmarking path must be numerically
    # identical to multi_step (which places host batches itself)
    import jax
    import numpy as np
    import optax

    from tensorflowonspark_tpu.parallel import dp, sharding as sh

    def loss(params, batch, rng):
        import jax.numpy as jnp

        x, y = batch
        return jnp.mean((jnp.dot(x, params["w"]) - y) ** 2)

    rng_np = np.random.RandomState(0)
    K = 3
    stacked = (
        rng_np.rand(K, 8, 4).astype(np.float32),
        rng_np.rand(K, 8).astype(np.float32),
    )
    rngs = jax.random.split(jax.random.PRNGKey(0), K)

    def run(on_device):
        trainer = dp.SyncTrainer(loss, optax.adam(0.05))
        state = trainer.create_state({"w": np.zeros(4, np.float32)})
        if on_device:
            dev = sh.shard_batch(
                stacked, trainer.mesh, trainer.data_axes, leading_dims=1
            )
            state, m = trainer.multi_step_on_device(state, dev, rngs)
        else:
            state, m = trainer.multi_step(state, stacked, rngs)
        return np.asarray(state.params["w"]), np.asarray(m["loss"])

    w_host, l_host = run(False)
    w_dev, l_dev = run(True)
    np.testing.assert_allclose(w_host, w_dev, rtol=1e-6)
    np.testing.assert_allclose(l_host, l_dev, rtol=1e-6)


# ----------------------------------------------------------------------
# the residual stream split over tokens between blocks under `model`
# ----------------------------------------------------------------------

TINY_LM = dict(
    vocab_size=64, num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8,
    embed_dim=32, mlp_dim=64, max_seq_len=16, dtype="float32",
    attention_impl="dot",
)
#: rows and tokens of the tiny step: 4 rows of 16, so 2 rows a data
#: shard and 8 tokens a model shard
TOKENS_SHAPE = (4, 16)


def _tiny_step(axes):
    """Loss and gradients of one step of the tiny LM under a mesh of
    ``axes`` (None: no mesh), the same parameters and rows each time;
    the step's compiled text and the ``train.residual_shards`` gauge
    its trace set."""
    from tensorflowonspark_tpu import telemetry
    from tensorflowonspark_tpu.models import transformer as tr

    params = tr.Transformer(tr.TransformerConfig(**TINY_LM)).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))["params"]
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), TOKENS_SHAPE, 0, 64), np.int32)
    mesh = None
    if axes is not None:
        mesh = mesh_mod.build_mesh(
            axes, devices=jax.devices()[:int(np.prod(list(axes.values())))])
        params = sh.shard_params(
            params, sh.RULES_TP, mesh, tr.logical_axes(params))
        tokens = sh.shard_batch(tokens, mesh)
    loss = tr.loss_fn(tr.Transformer(tr.TransformerConfig(
        **TINY_LM, mesh=mesh)))
    step = jax.jit(jax.value_and_grad(
        lambda p, t: loss(p, {"tokens": t}, None)))
    telemetry.get_registry().gauge("train.residual_shards").set(0)
    compiled = step.lower(params, tokens).compile()
    shards = telemetry.get_registry().snapshot()["gauges"][
        "train.residual_shards"]
    value, grads = step(params, tokens)
    return float(value), jax.tree.map(np.asarray, grads), compiled, shards


@pytest.fixture(scope="module")
def unsharded_step():
    return _tiny_step(None)


@pytest.mark.parametrize("axes,shards", [
    ({"data": 2, "model": 2}, 2),
    ({"data": 2}, 1),
    (None, 1),
], ids=["data2-model2", "data2", "no-mesh"])
def test_a_token_split_residual_stream_takes_the_same_step(
        unsharded_step, axes, shards):
    value, grads, compiled, gauge = _tiny_step(axes)
    want_value, want_grads = unsharded_step[:2]
    assert gauge == shards
    np.testing.assert_allclose(value, want_value, rtol=1e-6)
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    if shards == 1:
        return
    # the stream [rows of a data shard, S, D] is gathered whole over
    # `model` (pairs of devices) inside the projections: on the CPU a
    # reduce-scatter is an all-reduce sliced by its consumers, so the
    # TPU form is held in tests/test_chip_lowering.py
    text = compiled.as_text()
    gathers = [line for line in text.splitlines()
               if re.search(r"= f32\[2,16,32\]\S* all-gather\(", line)]
    assert len(gathers) >= 2 * TINY_LM["num_layers"] + 1
    assert all("replica_groups=[2,2]<=[4]" in g and "dimensions={1}" in g
               for g in gathers)


def text_of(lowered):
    """A lowered program's StableHLO text with private functions
    renumbered in order of appearance (their numbers depend on what the
    process traced before)."""
    seen = {}
    return re.sub(r"@(\w+?)_\d+\b", lambda m: "@%s_%d" % (
        m.group(1), seen.setdefault(m.group(0), len(seen))),
        lowered.as_text())


def bypassed_programs():
    """``{program: sha256 of its StableHLO}`` of the programs the token
    split must leave as they were — a training step on Moonlight's
    shape of mesh (``{"data": 1}``), a SlotDecoder's prefill and decode
    chunk with no mesh and under ``serving_mesh(tp=2)`` — and
    ``"traces"``, the ``jit.trace`` count (with nested ones) of the tiny
    step on the 2x2 mesh.  Run in a fresh process: JAX's caches of inner
    traces make the count depend on what ran before."""
    import hashlib
    import time

    from tensorflowonspark_tpu import telemetry
    from tensorflowonspark_tpu.models import transformer as tr
    from tensorflowonspark_tpu.telemetry import tracing

    tracing.watch_jit()
    out = {}
    params = tr.Transformer(tr.TransformerConfig(**TINY_LM)).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))["params"]
    tokens = jnp.zeros(TOKENS_SHAPE, jnp.int32)

    def train_step(axes):
        mesh = mesh_mod.build_mesh(
            axes, devices=jax.devices()[:int(np.prod(list(axes.values())))])
        loss = tr.loss_fn(tr.Transformer(tr.TransformerConfig(
            **TINY_LM, mesh=mesh)))
        p = sh.shard_params(params, sh.RULES_TP, mesh,
                            tr.logical_axes(params))
        return jax.jit(jax.value_and_grad(
            lambda p, t: loss(p, {"tokens": t}, None))).lower(
                p, sh.shard_batch(tokens, mesh))

    lowered = {"train_data1": train_step({"data": 1})}
    for name, tp in (("", None), ("_tp2", 2)):
        from tensorflowonspark_tpu.parallel.mesh import serving_mesh

        dec = tr.SlotDecoder(
            tr.Transformer(tr.TransformerConfig(**TINY_LM)), params, 2, 4,
            cache_len=32, chunk_size=2, pad_multiple=8,
            mesh=serving_mesh(tp=tp))
        lowered["prefill" + name] = dec._prefill_jit.lower(
            dec._params, dec._dparams, dec.cache, dec.draft_cache,
            dec.state, jnp.int32(0), jnp.zeros((1, 8), jnp.int32),
            jnp.zeros((1,), jnp.int32), dec._next_key())
        lowered["chunk" + name] = dec._chunk_jit.lower(
            dec._params, dec.cache, dec.state,
            jnp.zeros((2,), bool), None, dec._next_key(dec.chunk_size))
    out = {k: hashlib.sha256(text_of(v).encode()).hexdigest()
           for k, v in lowered.items()}
    since = time.time()
    train_step({"data": 2, "model": 2})
    out["traces"] = sum(
        1 + s["attrs"]["nested"]
        for s in telemetry.get_tracer().spans(trace="jit")
        if s["t0"] >= since and s["name"] == "jit.trace")
    return out


#: :func:`bypassed_programs` on the commit before the token split
#: (jax 0.9.0)
PARENT_PROGRAMS = {
    "train_data1":
        "931417f464ceaed0c6f56d40db309a34f83fa27b4c88774c20211f5738fbf3d7",
    "prefill":
        "a64c9ecbacc2325cf164e0d15bbefa095cd916224aa16e94f8f7f8389b415e38",
    "chunk":
        "76f1d67c687ca23701bad683cd90b93923c73790528df75cf7670da21e1986f5",
    "prefill_tp2":
        "c9bde7ed2e906bd798eeb86ed5be9a7c619990eb64920e50d23872b533b4f850",
    "chunk_tp2":
        "f893f9c757886301bd225f1751b8e14d4cf059fc97457477bb5927ee0faf509c",
    "traces": 211,
}


def test_programs_off_the_token_split_lower_as_before():
    import json
    import os
    import subprocess
    import sys

    here = os.path.dirname(os.path.abspath(__file__))
    code = ("import json, sys; sys.path[:0] = [%r, %r]; "
            "import test_parallel as t; "
            "print(json.dumps(t.bypassed_programs()))") % (
                here, os.path.dirname(here))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=here,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    traces = got.pop("traces")
    want = dict(PARENT_PROGRAMS)
    assert got == {k: v for k, v in want.items() if k != "traces"}
    # the set-up budget: the split's step traces at most 1% more
    assert traces <= want["traces"] * 1.01
