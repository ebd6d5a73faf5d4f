"""Real multi-process distributed training over the full stack.

The reference validated its multi-worker contract against a live
2-worker Spark Standalone cluster (reference: test/run_tests.sh:16-27);
this is the same posture applied to the JAX bootstrap: two LocalEngine
executor processes each spawn a compute process that calls
``ctx.initialize_distributed()`` (``jax.distributed.initialize`` with
CPU Gloo collectives) and runs ``SyncTrainer.train_on_feed`` as ONE
synchronized 4-device mesh spanning both processes.

Asserted here:

- ``jax.process_count() == 2`` inside every compute process — the
  TF_CONFIG-replacement path is actually executed, not short-circuited;
- the global stop fires with uneven feeds and neither process deadlocks
  in a collective;
- both processes execute the SAME number of steps with IDENTICAL
  per-step losses (the loss is a global mean over the sharded batch —
  divergence would mean the mesh was never actually synchronized).
"""

import time

import pytest

pytestmark = pytest.mark.slow

from tensorflowonspark_tpu.cluster import cluster as tpu_cluster
from tensorflowonspark_tpu.cluster import manager as mgr_mod
from tensorflowonspark_tpu.cluster.cluster import InputMode
from tensorflowonspark_tpu.engine import LocalEngine


def _dist_train_fn(args, ctx):
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    ctx.initialize_distributed()

    import jax.numpy as jnp
    import optax

    from tensorflowonspark_tpu.parallel import dp
    from tensorflowonspark_tpu.parallel.mesh import MeshSpec, build_mesh

    ctx.mgr.set("process_count", jax.process_count())
    mesh = build_mesh(MeshSpec(data=-1))  # all global devices

    def loss_fn(params, batch, rng):
        x, y = batch
        pred = jnp.dot(x.astype(jnp.float32), params["w"])
        return jnp.mean((pred - y.astype(jnp.float32)) ** 2)

    trainer = dp.SyncTrainer(loss_fn, optax.sgd(0.05), mesh=mesh)
    state = trainer.create_state({"w": jnp.zeros((3,), jnp.float32)})
    feed = ctx.get_data_feed(train_mode=True)
    losses = []
    state = trainer.train_on_feed(
        state,
        feed,
        batch_size=8,
        metrics_callback=lambda step, m: losses.append(
            round(float(m["loss"]), 6)
        ),
        log_every=0,
    )
    ctx.mgr.set("losses", losses)
    # drain whatever the feeder still holds so its queue.join() returns
    feed.terminate()


def _row(i):
    # deterministic regression rows (features in [0,1)): y = x . [1, 2, 3]
    x = ((i % 7) / 7.0, ((i * 3) % 5) / 5.0, ((i * 5) % 11) / 11.0)
    y = x[0] * 1.0 + x[1] * 2.0 + x[2] * 3.0
    return (x, y)


def test_two_process_synchronized_mesh():
    # each worker: 2 virtual CPU devices -> one 4-device global mesh
    engine = LocalEngine(
        2, env={"XLA_FLAGS": "--xla_force_host_platform_device_count=2"}
    )
    try:
        cluster = tpu_cluster.run(
            engine,
            _dist_train_fn,
            args={},
            num_executors=2,
            input_mode=InputMode.SPARK,
        )
        # uneven feed: 4 partitions of different sizes; whichever worker
        # runs dry first must stop BOTH (no deadlock in the collective)
        sizes = [48, 48, 48, 12]
        start = 0
        partitions = []
        for s in sizes:
            partitions.append([_row(i) for i in range(start, start + s)])
            start += s
        cluster.train(partitions, num_epochs=1, feed_timeout=120)
        cluster.shutdown(grace_secs=5, timeout=300)

        # collect per-process results from the node managers
        per_node = {}
        for n in cluster.cluster_info:
            m = mgr_mod.connect(tuple(n["addr"]), bytes.fromhex(n["authkey"]))
            deadline = time.time() + 60
            losses = None
            while time.time() < deadline:
                losses = m.get("losses")._getvalue()
                if losses is not None:
                    break
                time.sleep(0.5)
            assert m.get("process_count")._getvalue() == 2, (
                "initialize_distributed did not form a 2-process cluster"
            )
            per_node[n["executor_id"]] = losses
    finally:
        engine.stop()

    assert len(per_node) == 2
    (a, b) = per_node.values()
    assert a is not None and b is not None, per_node
    assert len(a) > 0, "no synchronized steps executed"
    assert len(a) == len(b), (
        "processes executed different step counts: {0} vs {1}".format(
            len(a), len(b)
        )
    )
    assert a == b, "per-step losses diverged across processes"
    # training made progress on the known-weights regression
    assert a[-1] < a[0]
