"""The child of a training cell whose model is the latent-attention /
sigmoid-routed-experts block (``reference/mla_moe_train.py``) as ONE
chip's share of an expert-parallel layer.  The same child as
``runners/train.py`` — the cluster's DRIVER, never touching JAX:
``cluster.run(LocalEngine(1), main_fun, InputMode.SPARK)``, the lazy
seeded partition through ``DataFeed`` (shm ring on), ONE trainer and
state through the first steps by ``SyncTrainer.train_on_feed`` itself
and then the window, the same three numbers compared and the same
counters keys, so that the readers the training cells share read both.
What differs:

- the configuration's keys map onto the program's latent-attention and
  expert-share fields (``program_model``); the loss is
  ``moe.sigmoid_moe_loss_fn`` (the step's expert counts come out as its
  aux) and the optimizer is masked off the routers' correction bias
  (``moe.leave_router_bias``);
- the reference follows the first steps a layer and a row at a time on
  the ONE chip, its AdamW moments waiting on the host between a
  block's updates (float32 parameters, gradient and moments are 10.7
  GB; the program's are freed first);
- beside the three numbers: the routers' bias must not have moved, and
  the program's count of local assignments is held against the
  reference's.

The body of ``main_fun`` repeats ``train.main_fun``'s (which builds its
model, loss and reference inline); PERF.md section 7 asks the next
``benchmark`` issue for one training runner whose model module the
configuration names.
"""

import gc
import os
import time

import numpy as np

from benchmarks import traffic
from benchmarks import weights_mla_moe_train as weights
from benchmarks.runners import common
from benchmarks.runners.train import REPORT_KEY, WindowClosed, row_partition

#: limits of the numbers compared, set from readings on the chip
#: (PERF.md section 2): loss of each step against the reference's; worst
#: leaf of the first gradient's norm and of the parameters' change.
#: The program's largest over 10 seeds 8.2e-5, 0.00263, 0.00036; the
#: int8 control's smallest over 3 seeds 9.5e-5 (the loss does not tell
#: it apart: it keeps the accepted cells' limit), 0.406, 0.0145; the
#: half-batch fault's 6.9e-4, 0.429, 0.168
LOSS_LIMIT = 0.003
GRAD_LIMIT = 0.02
CHANGE_LIMIT = 0.003
#: the program's count of routed rows that landed on the held experts,
#: over the checked steps, against the reference's: equal in float32
#: (the rehearsal, tier-1); in bfloat16 the sixth choice of about one
#: token in a thousand differs from the float32 reference's (the
#: program's largest 0.00123, the int8 control's 0.0015-0.0031: not told
#: apart), and a dropped tile of 256 rows in a step's 61 thousand is
#: 0.004 a tile (the half-batch fault reads 0.50)
ASSIGNMENTS_LIMIT = 0.01
MOE_KEYS = ("moe_local_assignments", "moe_experts_hit",
            "moe_rows_multiplied")
FROZEN = "router_bias"


def program_model(cfg, seq_len):
    """The program's model from the published keys.  ``cfg.mesh`` stays
    None: on one chip every Pallas call runs as written."""
    from tensorflowonspark_tpu.models import transformer as tr

    z = weights.sizes(cfg)
    prog = cfg["program"]
    return tr.Transformer(tr.TransformerConfig(
        vocab_size=z["v"], num_layers=z["layers"], num_heads=z["h"],
        embed_dim=z["d"], mlp_dim=z["f"], max_seq_len=seq_len,
        dtype=cfg["dtype"], rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_theta"],
        rope_interleave=bool(prog["rope_interleave"]),
        attention_kind="mla", q_lora_rank=z["rq"], kv_lora_rank=z["rkv"],
        qk_nope_head_dim=z["dn"], qk_rope_head_dim=z["dr"],
        v_head_dim=z["dv"],
        mlp_layer_types=[weights.ffn_kind(cfg, i)
                         for i in range(z["layers"])],
        router_scoring=cfg["scoring_func"], router_experts=z["experts"],
        num_experts=z["held"], expert_first=z["first"], expert_k=z["k"],
        shared_experts=z["shared"],
        routed_scaling=cfg["routed_scaling_factor"], moe_mlp_dim=z["fe"],
        attention_impl=prog["attention_impl"],
        block_q=min(prog["block_q"], seq_len),
        block_k=min(prog["block_k"], seq_len),
        remat=bool(prog.get("remat", False)),
    ))


# ----------------------------------------------------------------------
# the comparison that decides ``correct``
# ----------------------------------------------------------------------


def change_norms(params, model, seed, dtype="float32"):
    """``compare.change_norms`` with this configuration's weights: per
    leaf, the norm of ``params`` minus the seed's initial values, drawn
    again a block at a time."""
    import jax
    import jax.numpy as jnp

    from benchmarks import compare

    dtype = jnp.dtype(dtype)
    key = weights.seed_key(seed)

    def gap(a, b):
        return jnp.sqrt(jnp.sum(jnp.square(
            a.astype(jnp.float32) - b.astype(jnp.float32))))

    out = jax.jit(lambda part, key: jax.tree.map(
        gap, part, weights.outer_params(model, key, dtype)))(
            {k: v for k, v in params.items()
             if not k.startswith("block_")}, key)
    for i in range(model["num_hidden_layers"]):
        name = "block_%d" % i
        out[name] = jax.jit(lambda part, key, i=i: jax.tree.map(
            gap, part, weights.block_params(model, key, i, dtype)))(
                params[name], key)
    return {k: float(v) for k, v in compare.leaf_paths(out)}


def train_reference(model, seed, batches, opt, mode="f32", rows=None):
    """Follow the program's first ``len(batches)`` optimizer steps with
    the plain reference on ONE device: float32 parameters from the
    seed, the mean next-token loss over the step's rows, AdamW on every
    leaf but the routers' correction bias (which stays as drawn).  The
    parameters stay on the device; a block's gradient is used — its
    norms taken on the first step, its AdamW update applied — and
    dropped as it is finished, and the moments wait on the host between
    a block's updates.  Returns the losses, the first gradient's leaf
    norms, the leaf norms of the parameters' change, and the choices
    that landed on the held experts a step."""
    import jax
    import jax.numpy as jnp

    from benchmarks import compare
    from benchmarks.reference import mla_moe_train as ref

    params = weights.make_params(model, seed, jnp.float32)
    moments = {}     # part name -> (mu, nu) as numpy trees, on the host
    update = jax.jit(
        lambda p, g, mu, nu, step: compare._adam_tree(
            p, g, mu, nu, step, opt),
        donate_argnums=(2, 3))
    losses, locals_, grad_norms = [], [], {}

    def parts_of(name):
        return ("ln_f", "lm_head") if name == "head" else (name,)

    for step, batch in enumerate(batches, 1):
        def on_grad(name, grad, step=step):
            keys = parts_of(name)
            if name != "head":
                grad = {name: grad}
            if step == 1:
                grad_norms.update(compare.leaf_norms(grad))
            part = {k: params[k] for k in keys}
            # the correction bias takes no step: its gradient is nought
            # and the optimizer is told to leave it (no weight decay)
            frozen = jax.tree_util.tree_map_with_path(
                lambda path, leaf: str(getattr(
                    path[-1], "key", path[-1])) == FROZEN, part)
            if name not in moments:
                moments[name] = tuple(
                    jax.tree.map(lambda x: np.zeros(x.shape, np.float32),
                                 part) for _ in range(2))
            mu, nu = moments[name]
            new, mu, nu = update(
                part, grad, jax.tree.map(jnp.asarray, mu),
                jax.tree.map(jnp.asarray, nu), float(step))
            new = jax.tree.map(
                lambda keep, old, moved: old if keep else moved,
                frozen, {k: params[k] for k in keys}, new)
            moments[name] = (jax.tree.map(np.asarray, mu),
                             jax.tree.map(np.asarray, nu))
            params.update(new)

        t0 = time.monotonic()
        loss, _, local = ref.loss_and_grads(
            params, np.asarray(batch), model, mode, rows, on_grad)
        losses.append(loss)
        locals_.append(local)
        print("reference %s step %d: %.1f s" % (
            mode, step, time.monotonic() - t0), flush=True)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change_norms(params, model, seed),
            "local_assignments": locals_}


def frozen_leaves(params):
    """``{leaf name: values on the host}`` of the routers' correction
    bias (a few hundred numbers)."""
    from benchmarks import compare

    return {k: np.asarray(v) for k, v in compare.leaf_paths(params)
            if k.endswith("/" + FROZEN)}


def checks_of(got_losses, got_grad, got_change, got_local, moved, want,
              limits):
    """``compare.train_checks`` over every leaf but the routers' bias
    (the optimizer leaves it: the reference's gradient of it is nought
    and the program keeps no moment of it), that bias's own check —
    ``moved``, the largest difference between the bias after the
    checked steps and the bias the state was made with, which has to
    be nought to the bit — and the count of local assignments."""
    from benchmarks import compare

    def trained(norms):
        return {k: v for k, v in norms.items()
                if not k.endswith("/" + FROZEN)}

    loss_limit, grad_limit, change_limit, count_limit = limits
    checks, detail = compare.train_checks(
        got_losses, trained(got_grad), trained(got_change),
        dict(want, grad_norms=trained(want["grad_norms"]),
             change_norms=trained(want["change_norms"])),
        loss_limit, grad_limit, change_limit)
    checks["router_bias_moved"] = {"value": float(moved), "limit": 0.0}
    off = max(abs(a - b) / max(b, 1)
              for a, b in zip(got_local, want["local_assignments"]))
    checks["local_assignments_gap"] = {
        "value": float(off), "limit": count_limit}
    detail.update(local_assignments=[int(x) for x in got_local],
                  reference_local_assignments=[
                      int(x) for x in want["local_assignments"]])
    return checks, detail


# ----------------------------------------------------------------------
# the run
# ----------------------------------------------------------------------


def main_fun(spec, ctx):
    """Runs in the cluster's compute process, the chip's only owner."""
    t_entered = time.time()
    ctx.initialize_distributed()
    rehearse = spec.get("rehearse")
    device = common.claim_device(spec["chips"], rehearse)
    compiles = common.CompileMeter()
    if rehearse and rehearse.get("fault"):
        from benchmarks.tests import faults_mla_moe_train

        faults_mla_moe_train.plant(rehearse["fault"])
    import jax
    import jax.extend.backend  # noqa: F401 - clear_backends, at the end
    import optax

    from tensorflowonspark_tpu import telemetry
    from tensorflowonspark_tpu.data import columnar, shm_ring, tfrecord
    from tensorflowonspark_tpu.models import moe
    from tensorflowonspark_tpu.parallel import dp
    from tensorflowonspark_tpu.parallel.mesh import build_mesh

    from benchmarks import compare

    native = {"shm_ring": shm_ring.available(),
              "tfrecord_codec": tfrecord.native_available(),
              "example_codec": columnar.native_available()}
    if not all(native.values()):
        raise RuntimeError("native libraries missing: %s" % native)
    cfg, mix, seed = spec["config"], spec["traffic"], spec["seed"]
    opt = cfg["optimizer"]
    rows, seq = int(mix["rows_per_step"]), int(mix["seq_len"])
    mesh = build_mesh(cfg["program"]["mesh"])
    model = program_model(cfg, seq)
    params = weights.make_params(cfg, seed, cfg["param_dtype"])
    adamw = optax.adamw(
        opt["learning_rate"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
        weight_decay=opt["weight_decay"])
    trainer = dp.SyncTrainer(
        moe.sigmoid_moe_loss_fn(model), moe.leave_router_bias(adamw),
        mesh=mesh, has_aux=True,
    )
    bias_made = frozen_leaves(params)
    state = trainer.create_state(params)
    del params
    feed = ctx.get_data_feed(train_mode=True)
    feed_hist = telemetry.get_registry().histogram("train.feed_wait_sec")

    def feed_wait():
        return float(feed_hist.sum)

    done = []       # (step, clock after the step's loss was read, loss)
    counts = []     # the step's expert counts, as the step returned them
    win = {"open": None, "close": None}
    profile = (common.ProfileWindow(spec["trace_dir"], seconds=3.5)
               if spec["trace"] else None)

    def on_step(step, metrics):
        loss = float(metrics["loss"])     # waits for the step
        now = time.monotonic()
        done.append((len(done) + 1, now, loss))
        counts.append({k: int(v) for k, v in jax.device_get(
            {k: metrics[k] for k in MOE_KEYS}).items()})
        if win["open"] is None:
            return
        since = now - win["open"]
        if (profile is not None and profile.started_at is None
                and since >= spec["seconds"] - profile.seconds):
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
    got_change = change_norms(state.params, cfg, seed, cfg["param_dtype"])
    moved = max(float(np.max(np.abs(v - bias_made[k])))
                for k, v in frozen_leaves(state.params).items())
    got_losses = [loss for _, _, loss in done]
    got_local = [c["moe_local_assignments"] for c in counts]

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
    jax.clear_caches()
    batches = [
        np.stack([traffic.packed_row(mix, seed, s * rows + r,
                                     cfg["vocab_size"])
                  for r in range(rows)])
        for s in range(check_steps)
    ]
    t_check = time.monotonic()
    want = train_reference(cfg, seed, batches, opt)
    check_s = time.monotonic() - t_check
    # the limits are the chip's, for full-width leaves; a rehearsal's
    # leaves are a few thousand numbers and their norms ten times
    # noisier (it runs float32: its counts are held to be equal)
    limits = [lim * (10.0 if rehearse else 1.0)
              for lim in (LOSS_LIMIT, GRAD_LIMIT, CHANGE_LIMIT)]
    limits.append(0.0 if rehearse else ASSIGNMENTS_LIMIT)
    checks, detail = checks_of(
        got_losses, got_grad, got_change, got_local, moved, want, limits)
    extra = {}
    if spec.get("control"):
        for name, kw in (("control_int8", {"mode": "int8"}),
                         ("fault_half_batch", {"rows": range(rows // 2)})):
            alt = train_reference(cfg, seed, batches, opt, **kw)
            c, _ = checks_of(
                alt["losses"], alt["grad_norms"], alt["change_norms"],
                alt["local_assignments"], 0.0, want, limits)
            extra[name] = {k: v["value"] for k, v in c.items()}
    correct = common.checks_hold(checks)

    tokens_step = rows * seq
    in_window = counts[steps_open:]
    result = {
        "correct": bool(correct), "attempted": steps_in, "failed": 0,
        "metrics": {},
        "device": dict(device, memory_peak_bytes=peak),
        "window_s": window_s, "steps": steps_in, "detail": detail,
        "check_s": check_s,
    }
    for key in MOE_KEYS:
        # what this seed's router sends to the held experts, a step
        result[key + "_per_step"] = sum(
            c[key] for c in in_window) / max(1, len(in_window))
    # ... and how that moves as the router trains: the window's first
    # and last step
    result["moe_local_assignments_window"] = [
        in_window[0][MOE_KEYS[0]], in_window[-1][MOE_KEYS[0]]]
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
        # the window's expert counts, summed over its steps
        counters.update(
            (key, sum(c[key] for c in in_window)) for key in MOE_KEYS)
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
                "device_ops": trace_reduce.top_device_ops(trace, n=16),
                "idle_gaps": trace_reduce.idle_gaps(trace),
            }
    result["checks"] = checks
    ctx.mgr.set(REPORT_KEY, result)
    # give the chip back before this process is told to go: killed in
    # the middle of the runtime's own shutdown it leaves it busy
    del want, feed
    gc.collect()
    jax.clear_caches()
    jax.extend.backend.clear_backends()


def run(spec):
    """``train.run`` with this file's ``main_fun``."""
    from tensorflowonspark_tpu.cluster import cluster as tpu_cluster
    from tensorflowonspark_tpu.cluster import manager as mgr_mod
    from tensorflowonspark_tpu.cluster.cluster import InputMode
    from tensorflowonspark_tpu.engine import LocalEngine

    cfg, mix = spec["config"], spec["traffic"]
    env = {"TFOS_SHM_FEED": "1"}
    if spec.get("rehearse"):
        # toy rows are below the production ring/queue cut-over
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
