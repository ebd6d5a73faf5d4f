"""The child of a training cell.  It is the cluster's DRIVER and never
touches JAX: ``cluster.run(LocalEngine(1), main_fun, InputMode.SPARK)``
starts one executor whose compute process owns every chip of the host,
``cluster.train`` feeds it a lazy partition of seeded token rows
through ``DataFeed`` (shm ring on), and ``main_fun`` — below, run in the
compute process — builds ONE trainer and state, drives them through the
first steps by ``SyncTrainer.train_on_feed`` itself, and hands the same
objects to the window.

``main_fun``'s ``metrics_callback`` reads each step's loss (which
waits for the step) and then the clock; when the window has lasted
``--seconds`` it touches a stop file — the partition's generator sees
it and ends the feed — and leaves ``train_on_feed`` by an exception of
its own.  The reference then follows the first steps on the same rows.
"""

import gc
import os
import time

import numpy as np

from benchmarks import traffic
from benchmarks.flops import shapes
from benchmarks.runners import common

REPORT_KEY = "bench_report"

#: limits of the numbers compared, set from readings on the chip
#: (PERF.md §2): loss of each step against the reference's; worst leaf
#: of the first gradient's norm and of the parameters' change
LOSS_LIMIT = 0.003
GRAD_LIMIT = 0.005
CHANGE_LIMIT = 0.006


class WindowClosed(Exception):
    """Raised by the metrics callback to leave ``train_on_feed``."""


def row_partition(mix, seed, vocab, stop_path):
    """The lazy partition: row ``i`` of the seed's stream until the
    stop file appears (generated on the executor)."""

    def gen():
        i = 0
        while not os.path.exists(stop_path):
            yield {"tokens": traffic.packed_row(mix, seed, i, vocab)}
            i += 1

    return gen


def program_model(cfg, mesh, seq_len):
    from tensorflowonspark_tpu.models import transformer as tr

    s = shapes(cfg)
    prog = cfg["program"]
    return tr.Transformer(tr.TransformerConfig(
        vocab_size=s["v"], num_layers=s["layers"], num_heads=s["h"],
        num_kv_heads=s["hkv"], head_dim=s["dh"], embed_dim=s["d"],
        mlp_dim=s["f"], max_seq_len=seq_len, dtype=cfg["dtype"],
        attention_window=s["window"],
        attention_impl=prog["attention_impl"], mesh=mesh,
        block_q=min(prog["block_q"], seq_len),
        block_k=min(prog["block_k"], seq_len),
        remat=bool(prog.get("remat", False)),
    ))


def main_fun(spec, ctx):
    """Runs in the cluster's compute process, the chips' only owner."""
    t_entered = time.time()
    ctx.initialize_distributed()
    rehearse = spec.get("rehearse")
    device = common.claim_device(spec["chips"], rehearse)
    compiles = common.CompileMeter()
    if rehearse and rehearse.get("fault"):
        from benchmarks.tests import faults

        faults.plant(rehearse["fault"])
    import jax
    import jax.extend.backend  # noqa: F401 - clear_backends, at the end
    import optax

    from tensorflowonspark_tpu import telemetry
    from tensorflowonspark_tpu.data import columnar, shm_ring, tfrecord
    from tensorflowonspark_tpu.models import transformer as tr
    from tensorflowonspark_tpu.parallel import dp, sharding as sh
    from tensorflowonspark_tpu.parallel.mesh import build_mesh

    from benchmarks import compare, weights

    native = {"shm_ring": shm_ring.available(),
              "tfrecord_codec": tfrecord.native_available(),
              "example_codec": columnar.native_available()}
    if not all(native.values()):
        raise RuntimeError("native libraries missing: %s" % native)
    cfg, mix, seed = spec["config"], spec["traffic"], spec["seed"]
    if not rehearse:
        from benchmarks import peaks

        spec = dict(spec, hbm_bytes=peaks.peaks_for(
            device["kind"])["hbm_bytes"])
    opt = cfg["optimizer"]
    rows, seq = int(mix["rows_per_step"]), int(mix["seq_len"])
    mesh = build_mesh(cfg["program"]["mesh"])
    model = program_model(cfg, mesh, seq)
    from jax.sharding import NamedSharding, PartitionSpec

    def laid_out(specs):
        return jax.tree.map(lambda spec: NamedSharding(mesh, spec), specs)

    def over_all_chips(shapes):
        # the seeded parameters only stand by while create_state copies
        # them: a quarter on each chip, so the hole they leave is small
        def spec(a):
            axes = [None] * a.ndim
            for i, n in enumerate(a.shape):
                if n % mesh.size == 0:
                    axes[i] = tuple(mesh.axis_names)
                    break
            return PartitionSpec(*axes)

        return laid_out(jax.tree.map(spec, shapes))

    # create_state copies the parameters it is given, so for a moment
    # they stand twice.  Made first, they would leave a hole at the
    # bottom of every chip's memory when dropped, and the step's
    # temporaries need one unbroken run: hold the bottom with a
    # placeholder while they are made, so that they sit above the state
    # and the room they leave joins the free run
    held = None
    if not rehearse:
        held = jax.jit(
            lambda: jax.numpy.zeros(
                (mesh.size, int(0.55 * spec["hbm_bytes"]) // 4),
                jax.numpy.float32),
            out_shardings=NamedSharding(
                mesh, PartitionSpec(tuple(mesh.axis_names))))()
    params = weights.make_params(
        cfg, seed, cfg["param_dtype"], shardings=over_all_chips)
    del held
    annotations = tr.logical_axes(params)
    moments_like = laid_out(
        sh.param_specs(params, sh.RULES_TP, mesh, annotations))
    adamw = optax.adamw(
        opt["learning_rate"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
        weight_decay=opt["weight_decay"])

    def init_sharded(p):
        # SyncTrainer.create_state jits this with no output sharding,
        # and zeros that depend on no input come out REPLICATED: 8 bytes
        # a parameter on every chip (PERF.md section 7).  Lay the moments
        # out like the parameters they mirror.
        def like_params(x):
            if jax.tree.structure(x) != jax.tree.structure(p):
                return x
            return jax.tree.map(
                jax.lax.with_sharding_constraint, x, moments_like)

        return jax.tree.map(
            like_params, adamw.init(p),
            is_leaf=lambda x: jax.tree.structure(x) == jax.tree.structure(p))

    trainer = dp.SyncTrainer(
        tr.loss_fn(model),
        optax.GradientTransformation(init_sharded, adamw.update),
        mesh=mesh, rules=sh.RULES_TP, annotations=annotations,
    )
    state = trainer.create_state(params)
    del params
    replicated = [
        name for name, leaf in compare.leaf_paths(state.opt_state)
        if leaf.ndim >= 2 and leaf.sharding.is_fully_replicated
    ]
    if replicated:
        raise RuntimeError(
            "optimizer moments left replicated: %s" % replicated[:3])
    feed = ctx.get_data_feed(train_mode=True)
    feed_hist = telemetry.get_registry().histogram("train.feed_wait_sec")

    def feed_wait():
        return float(feed_hist.sum)

    done = []            # (step, clock after the step's loss was read, loss)
    win = {"open": None, "close": None}
    profile = (common.ProfileWindow(spec["trace_dir"], seconds=3.5)
               if spec["trace"] else None)

    def on_step(step, metrics):
        loss = float(metrics["loss"])     # waits for the step
        now = time.monotonic()
        done.append((len(done) + 1, now, loss))
        if win["open"] is None:
            return
        since = now - win["open"]
        if (profile is not None and profile.started_at is None
                and since >= spec["seconds"] - profile.seconds):
            # the window's last steps; the profiler is stopped (and
            # writes its trace) only after the window has closed
            profile.start()
        if since >= spec["seconds"]:
            win["close"] = now
            win["feed_wait_close"] = feed_wait()
            win["compiles_close"] = compiles.count
            open(spec["stop_path"], "w").close()
            raise WindowClosed()

    def drive(state, steps):
        return trainer.train_on_feed(
            state, feed, batch_size=rows, max_steps=steps, log_every=0,
            steps_per_execution=cfg["program"]["steps_per_execution"],
            columnar=True, terminate_on_max_steps=False,
            metrics_callback=on_step,
        )

    # the first steps, through the window's own call and feed
    check_steps = int(mix["check_steps"])
    state = drive(state, 1)
    mu = next(s.mu for s in jax.tree.leaves(
        state.opt_state, is_leaf=lambda x: hasattr(x, "mu"))
        if hasattr(s, "mu"))
    got_grad = {k: v / (1.0 - opt["b1"])
                for k, v in compare.leaf_norms(mu).items()}
    del mu
    state = drive(state, check_steps - 1)
    got_change = compare.change_norms(
        state.params, cfg, seed, cfg["param_dtype"])
    got_losses = [loss for _, _, loss in done]

    # the window: the same trainer, state and feed
    win["open"] = time.monotonic()
    setup_s = time.time() - spec["t_start"]
    feed_wait_open, compiles_open = feed_wait(), compiles.count
    steps_open = len(done)
    with jax.profiler.TraceAnnotation("bench.train_on_feed"):
        try:
            drive(state, None)
        except WindowClosed:
            pass
    if profile is not None and profile.running:
        profile.stop()
    if win["close"] is None:
        raise RuntimeError("the feed ended before the window closed")
    feed.terminate()
    window_s = win["close"] - win["open"]
    steps_in = len(done) - steps_open
    if win["compiles_close"] != compiles_open:
        raise RuntimeError("a program compiled inside the window")
    peak = common.memory_peak_bytes()
    wire = feed.wire_stats()
    if wire["ring_records"] < 1:
        raise RuntimeError("the shm ring carried nothing")

    # free the program's state, then follow the first steps
    del state, trainer, model
    gc.collect()
    batches = [
        np.stack([traffic.packed_row(mix, seed, s * rows + r,
                                     cfg["vocab_size"])
                  for r in range(rows)])
        for s in range(check_steps)
    ]
    t_check = time.monotonic()
    want = compare.train_reference(
        cfg, seed, batches, opt, devices=jax.devices())
    check_s = time.monotonic() - t_check
    # the limits are the chip's, for full-width leaves; a rehearsal's
    # leaves are a few thousand numbers and their norms ten times noisier
    limits = [lim * (10.0 if rehearse else 1.0)
              for lim in (LOSS_LIMIT, GRAD_LIMIT, CHANGE_LIMIT)]
    checks, detail = compare.train_checks(
        got_losses, got_grad, got_change, want, *limits)
    extra = {}
    if spec.get("control"):
        for name, kw in (("control_int8", {"mode": "int8"}),
                         ("fault_half_batch", {"rows": range(rows // 2)})):
            alt = compare.train_reference(
                cfg, seed, batches, opt, devices=jax.devices(), **kw)
            c, _ = compare.train_checks(
                alt["losses"], alt["grad_norms"], alt["change_norms"],
                want, *limits)
            extra[name] = {k: v["value"] for k, v in c.items()}
    correct = common.checks_hold(checks)

    tokens_step = rows * seq
    result = {
        "correct": bool(correct), "attempted": steps_in, "failed": 0,
        "metrics": {},
        "device": dict(device, memory_peak_bytes=peak),
        "window_s": window_s, "steps": steps_in, "detail": detail,
        "check_s": check_s,
    }
    result.update(extra)
    if rehearse:
        result["rehearsal"] = "tiny sizes on the CPU: not a measurement"
    if not spec["trace"]:
        result["metrics"] = {
            "train_tok_s": {"value": steps_in * tokens_step / window_s,
                            "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    else:
        from benchmarks import peaks, trace_reduce

        trace = trace_reduce.load_xplane(spec["trace_dir"])
        counters = {
            "window_s": window_s, "setup_s": setup_s, "steps": steps_in,
            "tokens_per_step": tokens_step, "rows_per_step": rows,
            "seq_len": seq,
            "feed_wait_s": win["feed_wait_close"] - feed_wait_open,
            "cluster_start_s": t_entered - spec["t_cluster_run"],
        }
        cell = {"config": cfg, "traffic": mix, "chips": spec["chips"],
                "peaks": (None if rehearse
                          else peaks.peaks_for(device["kind"]))}
        result["metrics"] = common.per_layer_metrics(
            spec["per_layer"], trace, counters, cell)
        summary = trace_reduce.summary(trace)
        if summary is not None:
            result["device"].update(
                busy_s=summary["busy_s"], window_s=summary["window_s"])
            result["busy_s_by_chip"] = summary["busy_s_by_chip"]
            result["breakdown"] = {
                "device_ops": trace_reduce.top_device_ops(trace),
                "idle_gaps": trace_reduce.idle_gaps(trace),
            }
    result["checks"] = checks
    ctx.mgr.set(REPORT_KEY, result)
    # give the chips back before this process is told to go: killed in
    # the middle of the runtime's own shutdown it leaves them busy
    del want, feed
    gc.collect()
    jax.clear_caches()
    jax.extend.backend.clear_backends()


def run(spec):
    from tensorflowonspark_tpu.cluster import cluster as tpu_cluster
    from tensorflowonspark_tpu.cluster import manager as mgr_mod
    from tensorflowonspark_tpu.cluster.cluster import InputMode
    from tensorflowonspark_tpu.engine import LocalEngine

    cfg, mix = spec["config"], spec["traffic"]
    env = {"TFOS_SHM_FEED": "1"}
    if spec.get("rehearse"):
        # toy rows are below the production ring/queue cut-over, and
        # the mesh needs as many (virtual) devices as the cell chips
        env["TFOS_SHM_RING_MIN_ROW_BYTES"] = "1"
        env["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=%d" % spec["chips"])
    stop_path = os.path.join(os.path.dirname(spec["trace_dir"]), "stop")
    spec = dict(spec, stop_path=stop_path, t_cluster_run=time.time())
    engine = LocalEngine(1, env=env, deterministic=True)
    report = None
    try:
        cluster = tpu_cluster.run(
            engine, main_fun, args=spec, num_executors=1,
            input_mode=InputMode.SPARK, reservation_timeout=120,
        )
        cluster.train(
            [row_partition(mix, spec["seed"], cfg["vocab_size"], stop_path)],
            num_epochs=1, feed_timeout=900,
        )
        node = cluster.cluster_info[0]
        m = mgr_mod.connect(
            tuple(node["addr"]), bytes.fromhex(node["authkey"]))
        deadline = time.time() + 900
        while time.time() < deadline:
            report = m.get(REPORT_KEY)._getvalue()
            if report is not None or str(
                    m.get("compute_state")._getvalue()) == "failed":
                break
            time.sleep(0.25)
        cluster.shutdown(grace_secs=2, timeout=120)
    finally:
        engine.stop()
    if report is None:
        raise RuntimeError("the compute process never reported")
    return report
