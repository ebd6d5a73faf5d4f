"""compat shim tests (reference: tensorflowonspark/compat.py roles)."""

import numpy as np

from tensorflowonspark_tpu import compat


def test_export_saved_model_chief_only(tmp_path):
    params = {"w": np.arange(3, dtype=np.float32)}
    assert compat.export_saved_model(params, str(tmp_path / "e"), is_chief=False) is None
    out = compat.export_saved_model(
        params, str(tmp_path / "e"), is_chief=True,
        metadata={"model_ref": "tensorflowonspark_tpu.models.linear:serving_builder"},
    )
    assert out is not None
    from tensorflowonspark_tpu.checkpoint import load_for_serving

    loaded, meta = load_for_serving(str(tmp_path / "e"))
    np.testing.assert_array_equal(loaded["w"], params["w"])
    assert "model_ref" in meta


def test_disable_auto_shard_noop():
    sentinel = object()
    assert compat.disable_auto_shard(sentinel) is sentinel


def test_accelerator_probe_runs():
    assert compat.is_accelerator_available() in (True, False)
    assert compat.is_gpu_available is compat.is_accelerator_available


def test_shard_map_runs_on_this_build():
    import jax
    import jax.numpy as jnp

    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    out = compat.shard_map(
        lambda a: a * 2,
        mesh=mesh, in_specs=(P("x"),), out_specs=P("x"),
        check_vma=False,
    )(jnp.ones((2,), jnp.float32))
    np.testing.assert_array_equal(np.asarray(out), [2.0, 2.0])


def test_axis_size_inside_shard_map():
    import jax
    import jax.numpy as jnp

    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    sizes = {}

    def f(a):
        sizes["x"] = compat.axis_size("x")
        return a

    compat.shard_map(
        f, mesh=mesh, in_specs=(P("x"),), out_specs=P("x"),
        check_vma=False,
    )(jnp.ones((2,), jnp.float32))
    assert sizes["x"] == 1

