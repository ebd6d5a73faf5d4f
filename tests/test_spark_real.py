"""End-to-end SparkEngine tests against REAL pyspark executors.

The reference's whole suite ran on a live 2-worker Spark Standalone
cluster (reference: test/run_tests.sh:16-27) because local mode hides
the process boundaries TFoS depends on.  Same posture here:
``local-cluster[2,1,1024]`` gives two genuine executor JVMs, each with
its own python worker — the flagship claim ("turn a Spark job's
executors into a TPU cluster") exercised on Spark itself.

Gated: pyspark is not in the TPU image; CI installs it (see
.github/workflows/ci.yml job ``spark``) and runs ``pytest -m spark``.
"""

import os
import sys
import time

import pytest

pyspark = pytest.importorskip("pyspark")

pytestmark = pytest.mark.spark


def _ship_this_module_by_value():
    """Functions in this module must reach the python workers.  Under
    pytest the tests directory is on ``sys.path`` only in-process, so
    by-reference pickling would fail on the executors; register the
    module for by-value pickling with pyspark's serializer."""
    try:
        from pyspark import cloudpickle as _cp

        _cp.register_pickle_by_value(sys.modules[__name__])
    except Exception:  # noqa: BLE001 - older cloudpickle: fall through
        pass


@pytest.fixture(scope="module")
def sc():
    from pyspark import SparkConf, SparkContext

    # local-cluster worker JVMs inherit this process's environment:
    # propagate the import roots so executors resolve the package and
    # this test module the same way the driver does
    os.environ["PYTHONPATH"] = os.pathsep.join(
        [p for p in sys.path if p] +
        [os.environ.get("PYTHONPATH", "")]
    ).strip(os.pathsep)
    _ship_this_module_by_value()
    conf = (
        SparkConf()
        .setMaster("local-cluster[2,1,1024]")
        .setAppName("tfos-tpu-spark-e2e")
        .set("spark.executor.instances", "2")
        .set("spark.cores.max", "2")
        .set("spark.executor.memory", "1g")
        .set("spark.python.worker.reuse", "true")
    )
    sc = SparkContext(conf=conf)
    yield sc
    sc.stop()


def _square_fn(args, ctx):
    feed = ctx.get_data_feed(train_mode=False)
    while not feed.should_stop():
        batch = feed.next_batch(10)
        if batch:
            feed.batch_results([x * x for x in batch])


def _consume_fn(args, ctx):
    feed = ctx.get_data_feed(train_mode=True)
    total = 0
    while not feed.should_stop():
        total += len(feed.next_batch(16))
    ctx.mgr.set("consumed", total)


def test_spark_engine_metadata(sc):
    from tensorflowonspark_tpu.engine import SparkEngine

    eng = SparkEngine(sc)
    assert eng.num_executors == 2
    assert eng.run_job(lambda it: [sum(it)], [[1, 2], [3]], collect=True) == [3, 3]


def test_cluster_inference_roundtrip_on_spark(sc):
    from tensorflowonspark_tpu.cluster import cluster as tpu_cluster
    from tensorflowonspark_tpu.cluster.cluster import InputMode

    cluster = tpu_cluster.run(
        sc,  # raw SparkContext: run() wraps it in SparkEngine
        _square_fn,
        args={},
        num_executors=2,
        input_mode=InputMode.SPARK,
    )
    data = list(range(100))
    rdd = sc.parallelize(data, 4)
    # native path: the RDD is fed in place (mapPartitions), and the
    # lazy result RDD is the reference's inference() contract
    result_rdd = cluster.inference(rdd, feed_timeout=120, lazy=True)
    results = result_rdd.collect()
    assert sorted(results) == sorted(x * x for x in data)
    cluster.shutdown(grace_secs=2, timeout=120)


def test_cluster_train_rdd_native_on_spark(sc):
    from tensorflowonspark_tpu.cluster import cluster as tpu_cluster
    from tensorflowonspark_tpu.cluster.cluster import InputMode

    cluster = tpu_cluster.run(
        sc,
        _consume_fn,
        args={},
        num_executors=2,
        input_mode=InputMode.SPARK,
    )
    rdd = sc.parallelize(
        [(float(i), float(2 * i)) for i in range(200)], 4
    )
    cluster.train(rdd, num_epochs=2, feed_timeout=120)
    cluster.shutdown(grace_secs=2, timeout=120)


def _fail_during_feed_fn(args, ctx):
    raise RuntimeError("injected failure before consuming")


def test_failure_during_feed_surfaces_on_spark(sc):
    # the reference ran its feed failure-injection tests on the real
    # cluster (reference: test/test_TFCluster.py:50-68): a compute
    # process that dies must fail the Spark feed job, not hang it
    from tensorflowonspark_tpu.cluster import cluster as tpu_cluster
    from tensorflowonspark_tpu.cluster.cluster import InputMode

    cluster = tpu_cluster.run(
        sc,
        _fail_during_feed_fn,
        args={},
        num_executors=2,
        input_mode=InputMode.SPARK,
    )
    rdd = sc.parallelize(list(range(40)), 4)
    with pytest.raises(Exception, match="injected failure"):
        cluster.train(rdd, feed_timeout=30)
    with pytest.raises(Exception):
        cluster.shutdown(timeout=120)


class _RDDStream(object):
    """foreachRDD contract over real Spark RDDs, driven synchronously —
    the DStream hook exercised on genuine executors without requiring
    the (pyspark>=4-removed) pyspark.streaming API."""

    def __init__(self, rdds):
        self.rdds = rdds

    def foreachRDD(self, fn):
        for rdd in self.rdds:
            fn(rdd)


def test_train_dstream_foreachrdd_on_spark(sc):
    from tensorflowonspark_tpu.cluster import cluster as tpu_cluster
    from tensorflowonspark_tpu.cluster.cluster import InputMode

    cluster = tpu_cluster.run(
        sc,
        _consume_fn,
        args={},
        num_executors=2,
        input_mode=InputMode.SPARK,
    )
    stream = _RDDStream(
        [sc.parallelize([(float(i), 0.0) for i in range(40)], 2)
         for _ in range(3)]
    )
    cluster.train_dstream(stream, feed_timeout=120)
    cluster.shutdown(grace_secs=2, timeout=120)


def test_train_dstream_queue_stream_on_spark(sc):
    # the real pyspark.streaming path (reference:
    # examples/mnist/estimator/mnist_spark_streaming.py).  pyspark 4.x
    # removed DStreams — skip loudly there; the foreachRDD contract
    # itself is covered by test_train_dstream_foreachrdd_on_spark.
    streaming = pytest.importorskip(
        "pyspark.streaming",
        reason="pyspark>=4 removed the DStream API",
    )
    from tensorflowonspark_tpu.cluster import cluster as tpu_cluster
    from tensorflowonspark_tpu.cluster.cluster import InputMode

    cluster = tpu_cluster.run(
        sc,
        _consume_fn,
        args={},
        num_executors=2,
        input_mode=InputMode.SPARK,
    )
    ssc = streaming.StreamingContext(sc, batchDuration=1)
    rdds = [
        sc.parallelize([(float(i), 0.0) for i in range(40)], 2)
        for _ in range(3)
    ]
    cluster.train_dstream(ssc.queueStream(rdds), feed_timeout=120)
    ssc.start()
    time.sleep(8)  # let the micro-batches drain through the feed
    ssc.stop(stopSparkContext=False, stopGraceFully=True)
    cluster.shutdown(grace_secs=2, timeout=120)


# --- estimator/model on a real cluster --------------------------------
# (reference: test/test_pipeline.py:91-170 ran fit+transform on the live
# Standalone cluster; known-weights acceptance value 3.14+1.618=4.758)

W_TRUE = [3.14, 1.618]


def _linreg_train_fn(args, ctx):
    import jax
    import numpy as np
    import optax

    from tensorflowonspark_tpu.checkpoint import save_for_serving
    from tensorflowonspark_tpu.models import linear

    feed = ctx.get_data_feed(
        train_mode=True, input_mapping=args.input_mapping
    )
    params = linear.init_params(2)
    tx = optax.adam(0.1)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(linear.loss_fn)(params, batch)
        updates, opt_state = tx.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    for batch in feed.batches(args.batch_size):
        data = {
            "features": np.asarray(
                [np.asarray(v, np.float32) for v in batch["x"]]
            ),
            "label": np.asarray(
                [np.asarray(v, np.float32) for v in batch["y"]]
            ),
        }
        params, opt_state, _ = step(params, opt_state, data)

    if ctx.job_name == "worker" and ctx.task_index == 0:
        save_for_serving(
            args.export_dir,
            jax.tree.map(np.asarray, params),
            extra_metadata={
                "model_ref":
                    "tensorflowonspark_tpu.models.linear:serving_builder",
                "model_config": {"input_name": "features"},
            },
        )


def test_estimator_fit_then_transform_on_spark(sc, tmp_path):
    import numpy as np

    from tensorflowonspark_tpu.engine import SparkEngine
    from tensorflowonspark_tpu.pipeline import TFEstimator, TFModel

    spark = pyspark.sql.SparkSession(sc)
    rng = np.random.RandomState(0)
    feats = rng.uniform(-1, 1, size=(512, 2)).astype(np.float64)
    labels = feats @ np.asarray(W_TRUE)
    df = spark.createDataFrame(
        [(feats[i].tolist(), [float(labels[i])]) for i in range(len(feats))],
        ["x", "y"],
    )

    export_dir = str(tmp_path / "export")
    est = (
        TFEstimator(_linreg_train_fn, {}, engine=SparkEngine(sc))
        .setInputMapping({"x": "features", "y": "label"})
        .setClusterSize(2)
        .setEpochs(25)
        .setBatchSize(32)
        .setExportDir(export_dir)
        .setGraceSecs(1)
        .setFeedTimeout(120)
    )
    model = est.fit(df)  # DataFrame fed in place on the executors
    assert isinstance(model, TFModel)

    test_df = spark.createDataFrame(
        [([1.0, 1.0],), ([2.0, 0.0],), ([0.0, 1.0],)], ["x"]
    )
    model.setInputMapping({"x": "features"})
    model.setOutputMapping({"prediction": "pred"})
    model.engine = SparkEngine(sc)
    out = model.transform(test_df)
    # native-DataFrame contract: a TYPED
    # DataFrame evaluated lazily on the executors, schema derived from
    # the predictor (reference: TFModel.scala:294-335)
    assert hasattr(out, "schema"), "transform must return a DataFrame"
    assert [f.name for f in out.schema.fields] == ["pred"]
    rows = out.collect()
    assert len(rows) == 3
    preds = [float(np.ravel(r["pred"])[0]) for r in rows]
    assert preds[0] == pytest.approx(4.758, abs=0.2)
    assert preds[1] == pytest.approx(6.28, abs=0.25)
    assert preds[2] == pytest.approx(1.618, abs=0.2)


def test_model_transform_lazy_executor_side(sc, tmp_path):
    """transform() with an explicit output schema runs NO Spark job at
    call time (fully lazy — reference: pipeline.py:460-489), preserves
    the input partitioning, and never routes rows through the driver."""
    import numpy as np

    import jax

    from tensorflowonspark_tpu.checkpoint import save_for_serving
    from tensorflowonspark_tpu.engine import SparkEngine
    from tensorflowonspark_tpu.pipeline import TFModel

    spark = pyspark.sql.SparkSession(sc)
    export_dir = str(tmp_path / "export_known")
    save_for_serving(
        export_dir,
        jax.tree.map(
            np.asarray,
            {
                "w": np.asarray(W_TRUE, np.float32),
                "b": np.zeros((), np.float32),
            },
        ),
        extra_metadata={
            "model_ref":
                "tensorflowonspark_tpu.models.linear:serving_builder",
            "model_config": {"input_name": "features"},
        },
    )

    n_parts = 4
    df = spark.createDataFrame(
        [([float(i), float(i % 3)],) for i in range(64)], ["x"]
    ).repartition(n_parts)
    model = (
        TFModel({"output_schema": [("pred", "float")]})
        .setExportDir(export_dir)
        .setInputMapping({"x": "features"})
        .setOutputMapping({"prediction": "pred"})
    )
    model.engine = SparkEngine(sc)

    jobs_before = len(sc.statusTracker().getJobIdsForGroup())
    out = model.transform(df)
    jobs_after = len(sc.statusTracker().getJobIdsForGroup())
    assert jobs_after == jobs_before, (
        "transform with an explicit output_schema must be fully lazy"
    )
    # input partitioning preserved: the mapPartitions path keeps the
    # executor-side layout (a driver collect would re-parallelize)
    assert out.rdd.getNumPartitions() == n_parts
    got = sorted(float(r["pred"]) for r in out.collect())
    want = sorted(
        float(np.dot([float(i), float(i % 3)], W_TRUE)) for i in range(64)
    )
    np.testing.assert_allclose(got, want, rtol=1e-5)
