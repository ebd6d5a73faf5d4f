"""On-device preprocessing (data/preprocess.py) — the widening half of
the narrow-dtype data plane (docs/data_plane.md).

The load-bearing contract: a uint8 batch widened ON DEVICE by
``make_preprocess(dtype, scale, mean, std)`` matches the host-side
``x.astype(np.float32) * scale`` path to float32 tolerance, through
every wiring point — the raw fn, ``prefetch_to_device(preprocess=)``,
``SyncTrainer(device_preprocess=)``, and the serving predictor wrap.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from tensorflowonspark_tpu.data import preprocess as pp
from tensorflowonspark_tpu.data.feed import prefetch_to_device
from tensorflowonspark_tpu.parallel import dp


def _pixels(shape=(4, 8, 8, 3), seed=0):
    return np.random.RandomState(seed).randint(
        0, 256, size=shape
    ).astype(np.uint8)


# ----------------------------------------------------------------------
# make_preprocess
# ----------------------------------------------------------------------


def test_cast_scale_matches_host_float_path():
    pre = pp.make_preprocess(scale=1.0 / 255.0)
    x = _pixels()
    out = np.asarray(jax.jit(pre)(x))
    assert out.dtype == np.float32
    np.testing.assert_allclose(
        out, x.astype(np.float32) / 255.0, rtol=1e-6
    )


def test_mean_std_normalization():
    mean = np.array([125.3, 123.0, 113.9], np.float32)
    std = np.array([63.0, 62.1, 66.7], np.float32)
    pre = pp.make_preprocess(mean=mean, std=std)
    x = _pixels(seed=1)
    out = np.asarray(jax.jit(pre)(x))
    ref = (x.astype(np.float32) - mean) / std
    np.testing.assert_allclose(out, ref, rtol=1e-5)


def test_default_selection_transforms_only_narrow_columns():
    # narrow (uint8) widens; int64 labels and float32 extras pass
    # through untransformed
    pre = pp.make_preprocess(scale=1.0 / 255.0)
    x = _pixels()
    y = np.arange(4, dtype=np.int64)
    w = np.ones((4,), np.float32) * 7.0
    ox, oy, ow = pre((x, y, w))
    assert np.asarray(ox).dtype == np.float32
    np.testing.assert_array_equal(np.asarray(oy), y)
    np.testing.assert_array_equal(np.asarray(ow), w)


def test_explicit_columns_dict_and_tuple():
    pre_d = pp.make_preprocess(columns=("img",), scale=2.0)
    batch = {"img": np.ones((2, 3), np.float32), "k": np.ones(2, np.float32)}
    out = pre_d(batch)
    np.testing.assert_allclose(np.asarray(out["img"]), 2.0 * batch["img"])
    np.testing.assert_allclose(np.asarray(out["k"]), batch["k"])
    pre_t = pp.make_preprocess(columns=(1,), offset=1.0)
    a, b = pre_t((np.zeros(3, np.float32), np.zeros(3, np.float32)))
    np.testing.assert_allclose(np.asarray(a), 0.0)
    np.testing.assert_allclose(np.asarray(b), 1.0)


def test_center_crop():
    pre = pp.make_preprocess(crop=(4, 6))
    x = _pixels((2, 8, 10, 3))
    out = np.asarray(pre(x))
    assert out.shape == (2, 4, 6, 3)
    np.testing.assert_allclose(
        out, x[:, 2:6, 2:8].astype(np.float32)
    )


def test_flip_requires_and_uses_rng():
    pre = pp.make_preprocess(flip=True)
    assert pp.takes_rng(pre)
    x = _pixels((6, 4, 4, 1), seed=3)
    # no rng: deterministic pass-through (eval/serving path)
    np.testing.assert_allclose(
        np.asarray(pre(x, None)), x.astype(np.float32)
    )
    out = np.asarray(pre(x, jax.random.PRNGKey(0)))
    flipped = x.astype(np.float32)[:, :, ::-1]
    plain = x.astype(np.float32)
    for i in range(x.shape[0]):
        assert (
            np.allclose(out[i], flipped[i])
            or np.allclose(out[i], plain[i])
        )
    # with this key at least one row must flip and one must not
    # (bernoulli(0.5) over 6 rows — deterministic given the key)
    flips = [np.allclose(out[i], flipped[i]) and not
             np.allclose(out[i], plain[i]) for i in range(6)]
    assert any(flips) and not all(flips)


def test_deterministic_preprocess_does_not_advertise_rng():
    assert not pp.takes_rng(pp.make_preprocess(scale=0.5))


def test_resolve_preprocess_spec_dict_and_callable():
    fn = pp.resolve_preprocess({"scale": 0.5})
    x = np.ones((2, 2), np.uint8)
    np.testing.assert_allclose(np.asarray(fn(x)), 0.5)
    same = pp.resolve_preprocess(fn)
    assert same is fn
    assert pp.resolve_preprocess(None) is None
    with pytest.raises(TypeError):
        pp.resolve_preprocess(42)


# ----------------------------------------------------------------------
# prefetch_to_device(preprocess=...)
# ----------------------------------------------------------------------


def test_prefetch_applies_device_preprocess():
    batches = [_pixels((2, 4), seed=i) for i in range(3)]
    out = list(prefetch_to_device(
        iter(batches), size=2, preprocess={"scale": 1.0 / 255.0}
    ))
    assert len(out) == 3
    for i, b in enumerate(out):
        arr = np.asarray(b)
        assert arr.dtype == np.float32
        np.testing.assert_allclose(
            arr, batches[i].astype(np.float32) / 255.0, rtol=1e-6
        )


def test_prefetch_preprocess_skips_host_count():
    items = [(_pixels((2, 4), seed=i), 2 - i) for i in range(2)]
    out = list(prefetch_to_device(
        iter(items), size=2, preprocess={"scale": 1.0}
    ))
    for i, (batch, n) in enumerate(out):
        assert type(n) is int and n == 2 - i
        assert np.asarray(batch).dtype == np.float32


@pytest.mark.parametrize("host_prefetch", [False, True])
def test_prefetch_host_prefetch_preserves_order_and_values(host_prefetch):
    # the synchronous path and the background-thread path hand the
    # consumer the same batches in the same order
    batches = [np.full((2, 2), i, np.uint8) for i in range(8)]
    out = list(prefetch_to_device(
        iter(batches), size=2, host_prefetch=host_prefetch
    ))
    assert len(out) == 8
    for i, b in enumerate(out):
        np.testing.assert_array_equal(
            np.asarray(b), np.full((2, 2), i)
        )


def test_prefetch_host_prefetch_forwards_iterator_errors():
    def it():
        yield np.zeros((2, 2), np.uint8)
        raise RuntimeError("decode exploded")

    gen = prefetch_to_device(it(), size=2, host_prefetch=True)
    next(gen)
    with pytest.raises(RuntimeError, match="decode exploded"):
        list(gen)


def test_prefetch_host_prefetch_abandonment_does_not_hang():
    # dropping the generator mid-stream must release the worker (stop
    # flag honored) — a deadlock here would hang the whole suite
    batches = [np.zeros((2, 2), np.uint8) for _ in range(64)]
    gen = prefetch_to_device(iter(batches), size=2, host_prefetch=True)
    next(gen)
    gen.close()  # GeneratorExit → finally → stop.set()


# ----------------------------------------------------------------------
# SyncTrainer(device_preprocess=...)
# ----------------------------------------------------------------------


def _mse_loss(params, batch, rng):
    x, y = batch
    pred = jnp.dot(x.reshape(x.shape[0], -1), params["w"])
    return jnp.mean((pred - y.astype(jnp.float32)) ** 2)


def test_sync_trainer_device_preprocess_parity_with_host_path():
    rng_np = np.random.RandomState(0)
    xs = [rng_np.randint(0, 256, (8, 16)).astype(np.uint8)
          for _ in range(5)]
    ys = [rng_np.rand(8).astype(np.float32) for _ in range(5)]

    def run(device):
        trainer = dp.SyncTrainer(
            _mse_loss, optax.adam(0.05),
            device_preprocess=(
                {"columns": (0,), "scale": 1.0 / 255.0} if device
                else None
            ),
        )
        state = trainer.create_state({"w": np.zeros(16, np.float32)})
        losses = []
        for x, y in zip(xs, ys):
            batch = (x, y) if device else (
                x.astype(np.float32) / 255.0, y
            )
            state, m = trainer.step(state, batch, jax.random.PRNGKey(7))
            losses.append(float(m["loss"]))
        return losses, np.asarray(state.params["w"])

    dev_losses, dev_w = run(True)
    host_losses, host_w = run(False)
    np.testing.assert_allclose(dev_losses, host_losses, rtol=1e-5)
    np.testing.assert_allclose(dev_w, host_w, rtol=1e-5, atol=1e-7)


def test_sync_trainer_multi_step_applies_preprocess_per_scan_step():
    # the fused multi-step scan must widen each step's batch the same
    # way the single-step program does
    rng_np = np.random.RandomState(1)
    xs = np.stack([rng_np.randint(0, 256, (8, 8)).astype(np.uint8)
                   for _ in range(3)])
    ys = np.stack([rng_np.rand(8).astype(np.float32) for _ in range(3)])
    rngs = jax.random.split(jax.random.PRNGKey(0), 3)

    def run(fused):
        trainer = dp.SyncTrainer(
            _mse_loss, optax.sgd(0.1),
            device_preprocess={"columns": (0,), "scale": 1.0 / 255.0},
        )
        state = trainer.create_state({"w": np.zeros(8, np.float32)})
        if fused:
            state, _ = trainer.multi_step(state, (xs, ys), rngs)
        else:
            for i in range(3):
                state, _ = trainer.step(state, (xs[i], ys[i]), rngs[i])
        return np.asarray(state.params["w"])

    np.testing.assert_allclose(run(True), run(False), rtol=1e-5)


def test_sync_trainer_rng_preprocess_consumes_split_key():
    # an rng-taking preprocess (random flip) must (a) run under jit and
    # (b) be deterministic given the step rng
    def loss(params, batch, rng):
        x = batch
        return jnp.mean(x * params["w"])

    trainer = dp.SyncTrainer(
        loss, optax.sgd(0.1),
        device_preprocess=pp.make_preprocess(flip=True),
    )
    assert trainer._pre_takes_rng
    state = trainer.create_state({"w": np.ones((), np.float32)})
    x = _pixels((8, 4, 4, 1), seed=5)
    _, m1 = trainer.step(state, x, jax.random.PRNGKey(3))
    state2 = trainer.create_state({"w": np.ones((), np.float32)})
    _, m2 = trainer.step(state2, x, jax.random.PRNGKey(3))
    assert float(m1["loss"]) == float(m2["loss"])


# ----------------------------------------------------------------------
# serving wrap
# ----------------------------------------------------------------------


def test_serving_with_preprocess_matches_host_widened_rows():
    from tensorflowonspark_tpu import serving
    from tensorflowonspark_tpu.models.mlp import MNISTNet

    net = MNISTNet(hidden=16)
    params = net.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 28, 28))
    )["params"]

    def builder(p, config):
        from tensorflowonspark_tpu.models import base

        return base.make_serving_predict(
            base.as_variables(p),
            lambda v, x: net.apply(v, jnp.asarray(x)),
            "image",
            lambda logits: {"logits": np.asarray(logits)},
        )

    predict = builder(params, {})
    wrapped = serving.with_preprocess(predict, {"scale": 1.0 / 255.0})
    rows_u8 = [
        {"img": _pixels((28, 28), seed=i).reshape(28, 28)}
        for i in range(4)
    ]
    rows_f32 = [
        {"img": r["img"].astype(np.float32) / 255.0} for r in rows_u8
    ]
    out_u8 = list(serving.predict_rows(
        wrapped, rows_u8, {"img": "image"}, batch_size=4
    ))
    out_f32 = list(serving.predict_rows(
        predict, rows_f32, {"img": "image"}, batch_size=4
    ))
    for a, b in zip(out_u8, out_f32):
        np.testing.assert_allclose(
            a["logits"], b["logits"], rtol=1e-4, atol=1e-5
        )


def test_load_predictor_reads_preprocess_from_metadata(tmp_path):
    from tensorflowonspark_tpu import serving
    from tensorflowonspark_tpu.checkpoint import save_for_serving
    from tensorflowonspark_tpu.models.mlp import MNISTNet

    net = MNISTNet(hidden=16)
    params = jax.tree.map(
        np.asarray,
        net.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28)))["params"],
    )
    export = str(tmp_path / "export")
    save_for_serving(
        export, params,
        extra_metadata={
            "model_ref": "tensorflowonspark_tpu.models.mlp:serving_builder",
            "model_config": {"hidden": 16, "input_name": "image"},
            # the export declares its wire contract: ship uint8,
            # widen on device
            "preprocess": {"scale": 1.0 / 255.0},
        },
    )
    predict = serving.load_predictor(export, use_cache=False)
    # preprocess=False disables even the metadata-declared stage
    plain = serving.load_predictor(
        export, use_cache=False, preprocess=False
    )
    row = _pixels((28, 28), seed=9)
    out = list(serving.predict_rows(
        predict, [{"img": row}], {"img": "image"}, batch_size=1
    ))[0]
    ref = list(serving.predict_rows(
        plain, [{"img": row.astype(np.float32) / 255.0}],
        {"img": "image"}, batch_size=1,
    ))[0]
    np.testing.assert_allclose(
        out["logits"], ref["logits"], rtol=1e-4, atol=1e-5
    )
