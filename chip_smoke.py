#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls, at
the full width of the 334M flagship LM (L16 H8 Dh128 Dm1024 Dff4096
V32000 S2048, bf16; weights random from a seed):

- *kernels*: every Pallas kernel compiled by Mosaic (not interpreted) and
  checked against its in-repo jnp reference at flagship geometry;
- *train*: ``cluster.run(LocalEngine(1))`` -> ``cluster.train`` ->
  ``ctx.get_data_feed()`` -> ``SyncTrainer.train_on_feed`` with flash
  attention, over every chip the compute process can see;
- *serve*: ``serving_builder(mode="generate")`` + ``predict_rows(
  schedule="continuous")`` in the contiguous and the paged-kernel layout;
- *multichip* (hosts showing >= 4 devices): training on ``data=2 x
  model=2``, four one-chip replicas, four executors x one chip joined by
  ``ctx.initialize_distributed()``, and the refusal of co-hosted
  executors that would each claim every chip.

Process model: a chip belongs to one process.  This parent never touches
a JAX backend; every phase is a child that owns the chip(s), runs, and is
fully gone (its whole session is killed) before the next starts.  For
*train* the chip owner is the cluster's compute process — the phase's
driver and executor processes stay off JAX as well.

Exit code 0 and the verdict as the last stdout line — exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
the device as JAX reports it — only when every phase passed on a TPU; the
line before it (``summary: {...}``) carries the per-phase detail, each
phase's ``compile_sec`` / ``cache_hits`` / ``cache_misses`` read from the
program's ``jit.compile`` spans (a miss: a compile that asked the
persistent cache and did not find its executable; all three None with
``TFOS_TELEMETRY=0``).
``--tiny`` together with ``JAX_PLATFORMS=cpu`` runs the same code at toy
sizes on the CPU (kernels interpreted) to debug the command; its output
says it is not a chip run and its verdict names platform ``cpu``.
"""

import argparse
import json
import math
import os
import signal
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
RESULT_TAG = "PHASE_RESULT "

#: wall budget of the whole script (the driver allows 1200 s)
DEADLINE_SEC = 1140.0

FLAGSHIP = dict(
    vocab_size=32000, num_layers=16, num_heads=8, head_dim=128,
    embed_dim=1024, mlp_dim=4096, max_seq_len=2048, dtype="bfloat16",
)
TINY = dict(
    vocab_size=256, num_layers=2, num_heads=4, head_dim=16,
    embed_dim=64, mlp_dim=128, max_seq_len=128, dtype="bfloat16",
)

#: bf16 tolerances, as max|got - ref| / max|ref| (operands bf16, f32
#: accumulation on both sides; the backward sums more rounded terms)
TOL_FWD = 2e-2
TOL_BWD = 5e-2


def sizes(tiny):
    """Workload sizes: flagship on the chip, toy in ``--tiny``."""
    if tiny:
        return dict(
            cfg=TINY, batch=8, seq=64, spe=4, steps=8, block=64,
            requests=6, prompt=(10, 24), new=4, slots=4, chunk=2,
            pad=8, attn_seq=128, attn_batch=1, page_bf16=8, page_int8=8,
            gmm=dict(e=4, d=128, f=256, bm=32, tiles=8),
        )
    return dict(
        cfg=FLAGSHIP, batch=8, seq=2048, spe=4, steps=8, block=1024,
        requests=16, prompt=(100, 256), new=32, slots=8, chunk=16,
        pad=128, attn_seq=2048, attn_batch=8, page_bf16=16, page_int8=32,
        gmm=dict(e=8, d=1024, f=4096, bm=256, tiles=32),
    )


# ----------------------------------------------------------------------
# helpers for processes that own the chip
# ----------------------------------------------------------------------


def compile_report():
    """Seconds this process spent in XLA/Mosaic compiles (cache reads
    included) and its persistent-cache hits and misses: the program's
    own ``jit.compile`` spans (``tracing.watch_jit``, installed with the
    compile cache by :func:`claim_device`).  ``cache_misses`` counts the
    compiles that asked the cache and did not find their executable
    (before PR 38 it counted the cache's writes).  None for each where
    telemetry is off (``TFOS_TELEMETRY=0``): no span was recorded."""
    from tensorflowonspark_tpu import telemetry

    tracer = telemetry.get_tracer()
    if not tracer.enabled:
        return dict.fromkeys(("compile_sec", "cache_hits", "cache_misses"))
    spans = tracer.spans(name="jit.compile")
    caches = [s["attrs"]["cache"] for s in spans]
    return {
        "compile_sec": round(sum(s["dur"] for s in spans), 2),
        "cache_hits": caches.count("hit"),
        "cache_misses": caches.count("miss"),
    }


def _total(counts):
    """The sum of the processes' counts; None where one read none."""
    counts = list(counts)
    return None if None in counts else sum(counts)


def claim_device(tiny):
    """First JAX touch of a chip-owning process: place the compile
    cache, then refuse to run on anything but the expected platform (the
    guard against JAX's silent CPU fallback, which would interpret every
    kernel and still print numbers)."""
    from tensorflowonspark_tpu.utils.compile_cache import (
        ensure_compile_cache,
    )

    cache = ensure_compile_cache()
    import jax

    devs = jax.devices()
    info = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    want = "cpu" if tiny else "tpu"
    if info["platform"] != want:
        raise RuntimeError(
            "expected platform {0!r}, JAX gave {1}".format(want, info)
        )
    print(
        "device: platform=%s device_kind=%s count=%d local=%d process=%d/%d "
        "cache_dir=%s" % (
            info["platform"], info["kind"], info["count"],
            jax.local_device_count(), jax.process_index(),
            jax.process_count(), cache,
        ),
        flush=True,
    )
    return info


def rel_err(got, ref):
    """``max|got - ref| / max|ref|`` in f32, traced (inf when ``got``
    holds a non-finite value, so it can never pass a tolerance)."""
    import jax.numpy as jnp

    if got.shape != ref.shape:
        raise RuntimeError("shape %s != %s" % (got.shape, ref.shape))
    got = got.astype(jnp.float32)
    ref = ref.astype(jnp.float32)
    err = jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref))
    return jnp.where(jnp.isfinite(got).all(), err, jnp.inf)


def check(name, err, tol):
    err = float(err)
    print("  %-34s rel_err=%.4f (tol %.2g)" % (name, err, tol), flush=True)
    if not err <= tol:
        raise RuntimeError(
            "%s: rel_err %.4f exceeds tolerance %.2g" % (name, err, tol)
        )
    return round(err, 5)


def emit(result):
    print(RESULT_TAG + json.dumps(result), flush=True)


# ----------------------------------------------------------------------
# phase: kernels
# ----------------------------------------------------------------------


def phase_kernels(tiny):
    sz = sizes(tiny)
    device = claim_device(tiny)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorflowonspark_tpu import compat
    from tensorflowonspark_tpu.ops import gmm, paged_attention as pa
    from tensorflowonspark_tpu.ops.attention import dot_attention
    from tensorflowonspark_tpu.ops.flash_attention import flash_attention

    interpreted = compat.pallas_interpret()
    if interpreted != tiny:
        raise RuntimeError(
            "pallas interpret=%s in %s mode" % (
                interpreted, "tiny" if tiny else "chip")
        )
    print("kernels: pallas %s" % (
        "INTERPRETED (tiny mode, not a chip run)" if interpreted
        else "compiled by Mosaic"), flush=True)
    cfg = sz["cfg"]
    h, d = cfg["num_heads"], cfg["head_dim"]
    errs = {}

    def compare(kernel_fn, ref_fn, with_grads=True):
        """ONE program per case: kernel and reference (forward, and the
        vjp of a shared cotangent) plus the error reductions."""
        def run(cot, *args):
            sides = []
            for fn in (kernel_fn, ref_fn):
                if with_grads:
                    out, vjp = jax.vjp(fn, *args)
                    sides.append((out,) + vjp(cot))
                else:
                    sides.append((fn(*args),))
            return tuple(rel_err(a, r) for a, r in zip(*sides))

        return jax.jit(run)

    # -- flash attention fwd + bwd vs dot_attention ---------------------
    b, s, blk = sz["attn_batch"], sz["attn_seq"], sz["block"]
    for name, hkv, window in (("flash_mha", h, 0),
                              ("flash_gqa_window", 2, s // 4)):
        ks = jax.random.split(jax.random.PRNGKey(len(name)), 4)
        q = jax.random.normal(ks[0], (b, s, h, d), jnp.bfloat16)
        k = jax.random.normal(ks[1], (b, s, hkv, d), jnp.bfloat16)
        v = jax.random.normal(ks[2], (b, s, hkv, d), jnp.bfloat16)
        do = jax.random.normal(ks[3], (b, s, h, d), jnp.bfloat16)
        got = compare(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, block_q=blk, block_k=blk,
                window=window),
            lambda q, k, v: dot_attention(
                q, k, v, causal=True, window=window),
        )(do, q, k, v)
        for part, err in zip(("out", "dq", "dk", "dv"), got):
            errs["%s.%s" % (name, part)] = check(
                "%s.%s" % (name, part), err,
                TOL_FWD if part == "out" else TOL_BWD,
            )

    # -- paged attention (single-token decode) vs the gather path -------
    slots = sz["slots"]
    rng = np.random.RandomState(0)
    span = sz["prompt"][1] + sz["new"]
    for name, hkv, page, int8 in (
        ("paged_bf16", h, sz["page_bf16"], False),
        ("paged_gqa_int8", 2, sz["page_int8"], True),
    ):
        nb = -(-span // page)
        pages = slots * nb + 1
        ks = jax.random.split(jax.random.PRNGKey(len(name)), 5)
        q = jax.random.normal(ks[0], (slots, h, d), jnp.bfloat16)
        shape = (pages, page, hkv, d)
        if int8:
            kp = jax.random.randint(ks[1], shape, -127, 128, jnp.int8)
            vp = jax.random.randint(ks[2], shape, -127, 128, jnp.int8)
            scales = dict(
                k_scale_pool=jax.random.uniform(
                    ks[3], shape[:3] + (1,), jnp.float32, 0.005, 0.02),
                v_scale_pool=jax.random.uniform(
                    ks[4], shape[:3] + (1,), jnp.float32, 0.005, 0.02),
            )
        else:
            kp = jax.random.normal(ks[1], shape, jnp.bfloat16)
            vp = jax.random.normal(ks[2], shape, jnp.bfloat16)
            scales = {}
        # every slot owns a shuffled, disjoint set of physical pages
        tables = jnp.asarray(
            1 + rng.permutation(slots * nb).reshape(slots, nb), jnp.int32
        )
        lengths = jnp.asarray(
            rng.randint(sz["prompt"][0], span + 1, (slots,)), jnp.int32
        )
        (err,) = compare(
            lambda q, kp, vp, tables, lengths, scales: pa.paged_attention(
                q, kp, vp, tables, lengths, **scales),
            lambda q, kp, vp, tables, lengths, scales:
            pa.paged_gather_attention(
                q[:, None], kp, vp, tables, (lengths - 1)[:, None],
                **scales)[:, 0],
            with_grads=False,
        )(None, q, kp, vp, tables, lengths, scales)
        errs[name] = check(name, err, TOL_FWD)

    # -- the same kernel over contiguous banks vs the masked einsums ----
    # the serving cell's own geometry on the chip (8 kv heads of 128,
    # 4 query heads each, banks of 1536), mixed positions and pads
    bk = dict(hkv=2, group=2, bank=256, slots=4) if tiny else dict(
        hkv=8, group=4, bank=1536, slots=16)
    ks = jax.random.split(jax.random.PRNGKey(27), 3)
    shape = (bk["slots"], bk["bank"], bk["hkv"], 128)
    q = jax.random.normal(
        ks[0], (bk["slots"], bk["hkv"] * bk["group"], 128), jnp.bfloat16)
    kb = jax.random.normal(ks[1], shape, jnp.bfloat16)
    vb = jax.random.normal(ks[2], shape, jnp.bfloat16)
    positions = jnp.asarray(
        rng.randint(0, bk["bank"], (bk["slots"],)), jnp.int32)
    pads = jnp.minimum(
        jnp.asarray(rng.randint(0, 64, (bk["slots"],)), jnp.int32),
        positions)

    def masked_dot(q, kb, vb, positions, pads):
        kpos = jnp.arange(kb.shape[1])[None, :]
        vis = jnp.logical_and(
            kpos <= positions[:, None], kpos >= pads[:, None])
        mask = jnp.where(vis, 0.0, -jnp.inf)[:, None, None, :]
        return dot_attention(
            q[:, None], kb, vb, causal=False, mask=mask)[:, 0]

    (err,) = compare(pa.bank_attention, masked_dot, with_grads=False)(
        None, q, kb, vb, positions, pads)
    errs["bank_gqa"] = check("bank_gqa", err, TOL_FWD)

    # -- grouped matmul fwd / dx / dw vs gmm_reference -------------------
    g = sz["gmm"]
    n = g["tiles"] * g["bm"]
    te = jnp.asarray(
        np.sort(rng.randint(0, g["e"], (g["tiles"],))), jnp.int32
    )
    # float32 too: its backward needs the byte-width-aware block sizing
    for name, dtype in (("gmm_bf16", jnp.bfloat16),
                        ("gmm_f32", jnp.float32)):
        ks = jax.random.split(jax.random.PRNGKey(len(name)), 3)
        x = jax.random.normal(ks[0], (n, g["d"]), dtype)
        w = jax.random.normal(ks[1], (g["e"], g["d"], g["f"]), dtype)
        w = w * (g["d"] ** -0.5)
        dy = jax.random.normal(ks[2], (n, g["f"]), dtype)
        got = compare(
            lambda x, w: gmm.grouped_matmul(x, w, te, g["bm"]),
            lambda x, w: gmm.gmm_reference(x, w, te, g["bm"]),
        )(dy, x, w)
        for part, err in zip(("y", "dx", "dw"), got):
            errs["%s.%s" % (name, part)] = check(
                "%s.%s" % (name, part), err,
                TOL_FWD if part == "y" else TOL_BWD,
            )

    emit(dict(phase="kernels", device=device, interpreted=interpreted,
              rel_err=errs, **compile_report()))


# ----------------------------------------------------------------------
# phase: train (cluster -> feed -> trainer)
# ----------------------------------------------------------------------


def _train_main(args, ctx):
    """The user ``main_fun``: runs in the cluster's compute process,
    the only process of the phase that may touch the chip."""
    tiny, sz = args["tiny"], sizes(args["tiny"])
    t_start = time.perf_counter()
    # join the co-hosted processes into one slice BEFORE the first
    # device query (a no-op for a single-process cluster)
    ctx.initialize_distributed()
    device = claim_device(tiny)
    import jax
    import jax.numpy as jnp
    import optax

    from tensorflowonspark_tpu import compat
    from tensorflowonspark_tpu.data import columnar, shm_ring, tfrecord
    from tensorflowonspark_tpu.models import transformer as tr
    from tensorflowonspark_tpu.parallel import dp, sharding as sh
    from tensorflowonspark_tpu.parallel.mesh import build_mesh

    native = {
        "shm_ring": shm_ring.available(),
        "tfrecord_codec": tfrecord.native_available(),
        "example_codec": columnar.native_available(),
    }
    if not all(native.values()):
        raise RuntimeError(
            "native libraries missing (pure-python fallback): %s" % native
        )
    if compat.pallas_interpret() != tiny:
        raise RuntimeError("pallas interpret mode does not match the run")

    mesh = build_mesh(args["mesh"])
    tp = mesh.shape.get("model", 1) > 1
    cfg = tr.TransformerConfig(
        attention_impl="flash", mesh=mesh, block_q=sz["block"],
        block_k=sz["block"], **dict(sz["cfg"], max_seq_len=sz["seq"])
    )
    model = tr.Transformer(cfg)
    local_batch = args["local_batch"]
    params = jax.jit(lambda r: model.init(
        r, jnp.zeros((1, sz["seq"]), jnp.int32))["params"]
    )(jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    trainer = dp.SyncTrainer(
        tr.loss_fn(model), optax.adamw(1e-3), mesh=mesh,
        rules=sh.RULES_TP if tp else sh.RULES_DP,
        annotations=tr.logical_axes(params) if tp else None,
    )
    state = trainer.create_state(params)
    del params
    t_built = time.perf_counter()

    losses = []
    feed = ctx.get_data_feed(train_mode=True)
    state = trainer.train_on_feed(
        state, feed, batch_size=local_batch,
        steps_per_execution=sz["spe"], max_steps=args["steps"],
        log_every=0, columnar=True,
        # the feed holds exactly max_steps batches: nothing to drain
        terminate_on_max_steps=False,
        metrics_callback=lambda step, m: losses.append(
            (int(step), float(m["loss"]))),
    )
    jax.block_until_ready(state.params)
    t_trained = time.perf_counter()

    # every device of the mesh must hold its shard of the state
    leaves = jax.tree.leaves(state.params) + jax.tree.leaves(
        state.opt_state)
    holders = set()
    for leaf in leaves:
        holders |= set(leaf.devices())
    sharded = sum(
        1 for leaf in leaves
        if leaf.addressable_shards[0].data.shape != leaf.shape
    )
    mem = {}
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        mem[str(dev.id)] = round(
            stats.get("peak_bytes_in_use", 0) / 2 ** 30, 2)
    ctx.mgr.set("smoke_report", dict(
        device=device, mesh=dict(mesh.shape), n_params=int(n_params),
        steps=int(state.step), losses=losses, wire=feed.wire_stats(),
        native=native,
        state_devices=len(holders), mesh_devices=int(mesh.size),
        sharded_leaves=sharded, peak_gib_by_device=mem,
        build_sec=round(t_built - t_start, 1),
        train_sec=round(t_trained - t_built, 1), **compile_report()
    ))


def _token_partition(seed, rows_per_step, steps, seq, vocab):
    """A lazy partition (generated on the executor): the SAME seeded
    batch of token rows, ``steps`` times over."""

    def gen():
        import numpy as np

        batch = np.random.RandomState(seed).randint(
            0, vocab, (rows_per_step, seq)).astype(np.int32)
        for _ in range(steps):
            for row in batch:
                yield {"tokens": row}

    return gen


def run_cluster(tiny, label, mesh, executors=1, chips_per_node=None,
                steps=None, local_batch=None):
    """Drive cluster.run -> cluster.train -> shutdown and return every
    node's report.  This process is the cluster DRIVER: it must not
    touch a JAX backend (the compute processes own the chips)."""
    from tensorflowonspark_tpu.cluster import cluster as tpu_cluster
    from tensorflowonspark_tpu.cluster import manager as mgr_mod
    from tensorflowonspark_tpu.cluster.cluster import InputMode
    from tensorflowonspark_tpu.engine import LocalEngine

    sz = sizes(tiny)
    steps = steps or sz["steps"]
    local_batch = local_batch or sz["batch"]
    env = {"TFOS_SHM_FEED": "1"}
    if tiny:
        # toy rows are below the production ring/queue cut-over
        env["TFOS_SHM_RING_MIN_ROW_BYTES"] = "1"
    engine = LocalEngine(executors, env=env, deterministic=True)
    try:
        cluster = tpu_cluster.run(
            engine, _train_main,
            args=dict(tiny=tiny, mesh=mesh, steps=steps,
                      local_batch=local_batch),
            num_executors=executors, input_mode=InputMode.SPARK,
            num_chips_per_node=chips_per_node, reservation_timeout=120,
        )
        cluster.train(
            [_token_partition(i, local_batch, steps, sz["seq"],
                              sz["cfg"]["vocab_size"])
             for i in range(executors)],
            num_epochs=1, feed_timeout=600,
        )
        reports = []
        for node in cluster.cluster_info:
            m = mgr_mod.connect(
                tuple(node["addr"]), bytes.fromhex(node["authkey"]))
            deadline = time.time() + 600
            while time.time() < deadline:
                rep = m.get("smoke_report")._getvalue()
                # a compute process that died reports nothing: stop
                # waiting, shutdown() below raises its traceback
                if rep is not None or str(
                        m.get("compute_state")._getvalue()) == "failed":
                    break
                time.sleep(0.5)
            reports.append(rep)
        cluster.shutdown(grace_secs=2, timeout=120)
    finally:
        engine.stop()
    if None in reports:
        raise RuntimeError("%s: a compute process never reported" % label)

    for rep in reports:
        losses = [l for _, l in rep["losses"]]
        print("%s: mesh=%s params=%.0fM steps=%d losses=%s ring_records=%d "
              "peak_GiB=%s build=%.1fs train=%.1fs compile=%ss" % (
                  label, rep["mesh"], rep["n_params"] / 1e6, rep["steps"],
                  ["%.4f" % l for l in losses],
                  rep["wire"]["ring_records"], rep["peak_gib_by_device"],
                  rep["build_sec"], rep["train_sec"], rep["compile_sec"]),
              flush=True)
        if rep["steps"] != steps:
            raise RuntimeError("%s: ran %d of %d steps" % (
                label, rep["steps"], steps))
        if not all(math.isfinite(l) for l in losses):
            raise RuntimeError("%s: non-finite loss %s" % (label, losses))
        if len(losses) > 1 and not losses[-1] < losses[0]:
            raise RuntimeError("%s: loss did not fall: %s" % (label, losses))
        if rep["wire"]["ring_records"] < 1:
            raise RuntimeError("%s: the shm ring carried nothing" % label)
        if rep["state_devices"] != rep["mesh_devices"]:
            raise RuntimeError(
                "%s: state lives on %d of %d mesh devices" % (
                    label, rep["state_devices"], rep["mesh_devices"]))
        if rep["mesh"].get("model", 1) > 1 and not rep["sharded_leaves"]:
            raise RuntimeError("%s: no state leaf is sharded" % label)
    first = reports[0]
    return dict(
        device=first["device"], mesh=first["mesh"],
        processes=len(reports), steps=first["steps"],
        losses=[l for _, l in first["losses"]],
        ring_records=[r["wire"]["ring_records"] for r in reports],
        peak_gib_by_device=[r["peak_gib_by_device"] for r in reports],
        compile_sec=first["compile_sec"],
        cache_hits=_total(r["cache_hits"] for r in reports),
        cache_misses=_total(r["cache_misses"] for r in reports),
    )


def phase_train(tiny):
    emit(dict(phase="train", **run_cluster(tiny, "train", mesh=None)))


def phase_mc_train_tp(tiny):
    emit(dict(phase="mc_train_tp", **run_cluster(
        tiny, "mc_train_tp", mesh={"data": 2, "model": 2})))


def phase_mc_executors(tiny):
    """Four executors x one chip, one global mesh: one optimizer step
    (each process feeds its own quarter of the global batch)."""
    emit(dict(phase="mc_executors", **run_cluster(
        tiny, "mc_executors", mesh=None, executors=4, chips_per_node=1,
        steps=1, local_batch=4)))


def phase_mc_refuse(tiny):
    """The README quick-start shape on one TPU host — several compute
    executors, ``num_chips_per_node`` unset — must be refused by name,
    fast, before any compute process claims a chip."""
    from tensorflowonspark_tpu.cluster import cluster as tpu_cluster
    from tensorflowonspark_tpu.cluster.cluster import InputMode
    from tensorflowonspark_tpu.cluster.tpu_info import ChipLayoutError

    if tiny:
        raise RuntimeError("a CPU host has no chips to oversubscribe")
    t0 = time.perf_counter()
    try:
        tpu_cluster.run(4, _train_main, args={}, input_mode=InputMode.SPARK,
                        reservation_timeout=120)
    except ChipLayoutError as e:
        wall = time.perf_counter() - t0
        print("mc_refuse: refused in %.1fs: %s" % (wall, e), flush=True)
    else:
        raise RuntimeError("co-hosted executors were not refused")
    if wall > 60:
        raise RuntimeError("refusal took %.0fs" % wall)
    emit(dict(phase="mc_refuse", refused_sec=round(wall, 1)))


# ----------------------------------------------------------------------
# phase: serve
# ----------------------------------------------------------------------


def _serve_setup(tiny):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorflowonspark_tpu.models import transformer as tr

    sz = sizes(tiny)
    model = tr.Transformer(tr.TransformerConfig(**sz["cfg"]))
    params = jax.jit(lambda r: model.init(
        r, jnp.zeros((1, 8), jnp.int32))["params"])(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    lo, hi = sz["prompt"]
    rows = [
        {"prompt": rng.randint(
            1, sz["cfg"]["vocab_size"], (n,)).astype(np.int32)}
        for n in rng.randint(lo, hi + 1, size=sz["requests"])
    ]
    config = dict(
        sz["cfg"], mode="generate", max_new_tokens=sz["new"],
        pad_multiple=sz["pad"], chunk_size=sz["chunk"],
        max_prompt_len=hi,
    )
    return sz, params, rows, config


def _serve_once(predict, rows, sz, replicas=1):
    """One predict_rows job; every request must come back as ``new``
    in-vocabulary tokens, with zero error records."""
    import numpy as np

    from tensorflowonspark_tpu import serving

    stats = {}
    t0 = time.perf_counter()
    out = list(serving.predict_rows(
        predict, rows, {"prompt": "tokens"}, batch_size=sz["slots"],
        schedule="continuous", on_error="record", stats=stats,
        replicas=replicas,
    ))
    wall = time.perf_counter() - t0
    errors = [r["error"] for r in out if "error" in r]
    if len(out) != len(rows) or errors:
        raise RuntimeError("answered %d of %d, error records: %s" % (
            len(out) - len(errors), len(rows), errors[:3]))
    toks = np.stack([np.asarray(r["generated"]) for r in out])
    if toks.shape != (len(rows), sz["new"]) or not (
            (toks >= 0) & (toks < sz["cfg"]["vocab_size"])).all():
        raise RuntimeError("bad generated block %s" % (toks.shape,))
    return toks, stats, wall


def phase_serve(tiny):
    device = claim_device(tiny)
    from tensorflowonspark_tpu.models import transformer as tr

    sz, params, rows, config = _serve_setup(tiny)
    toks, report = {}, {}
    for layout, extra in (
        ("contiguous", {}),
        ("paged", dict(kv_layout="paged", paged_impl="kernel",
                       kv_page_tokens=sz["page_bf16"])),
    ):
        predict = tr.serving_builder(params, dict(config, **extra))
        toks[layout], stats, wall = _serve_once(predict, rows, sz)
        report[layout] = dict(
            wall_sec=round(wall, 1), admitted=stats["admitted"],
            chunks=stats["chunks"], errors=stats.get("errors", 0),
        )
        print("serve[%s]: %d requests answered, 0 error records, "
              "%d chunks, %.1fs (compile included)" % (
                  layout, len(rows), stats["chunks"], wall), flush=True)
    same = toks["contiguous"] == toks["paged"]
    share = float(same.mean())
    # token 0 comes from prefill (one code path); token 1 is the first
    # the paged KERNEL decodes.  With random weights a bf16 near-tie may
    # flip an argmax and the tails then diverge, so the share is
    # reported, and only a broken kernel (agreement ~1/vocab) fails.
    first_decoded = float(same[:, 1].mean())
    print("serve: layouts agree on %.1f%% of tokens (%.0f%% of requests "
          "on the first kernel-decoded token)" % (
              100 * share, 100 * first_decoded), flush=True)
    if first_decoded < 0.5:
        raise RuntimeError(
            "paged kernel disagrees with the contiguous layout on the "
            "first decoded token of %.0f%% of requests" % (
                100 * (1 - first_decoded)))
    emit(dict(phase="serve", device=device, layouts=report,
              layout_token_agreement=round(share, 4),
              first_decoded_agreement=round(first_decoded, 4),
              **compile_report()))


def phase_mc_replicas(tiny):
    """predict_rows(replicas=4): one replica per chip — four distinct
    devices across the replicas' weights and KV pools."""
    device = claim_device(tiny)
    import jax

    from tensorflowonspark_tpu.models import transformer as tr

    sz, params, rows, config = _serve_setup(tiny)
    predict = tr.serving_builder(params, dict(
        config, kv_layout="paged", paged_impl="kernel",
        kv_page_tokens=sz["page_bf16"]))
    # replica 0 serves ``predict``; the others come from its public
    # make_replica() hook — record them to inspect their decoders after
    predictors = [predict]
    make_replica = predict.make_replica

    def recording_make_replica():
        predictors.append(make_replica())
        return predictors[-1]

    predict.make_replica = recording_make_replica
    _serve_once(predict, rows, sz, replicas=4)
    placements = []
    for i, p in enumerate(predictors):
        dec = p.make_slot_decoder(sz["slots"])  # memoized: the live one
        placements.append(dict(
            replica=i,
            weights=sorted({
                d.id for leaf in jax.tree.leaves(dec.snapshot_weights()[:2])
                for d in leaf.devices()}),
            pools=sorted({d.id for leaf in jax.tree.leaves(dec.cache)
                          for d in leaf.devices()}),
        ))
    print("mc_replicas: %s" % placements, flush=True)
    homes = [tuple(p["weights"]) for p in placements]
    if (len(set(homes)) != 4 or any(len(h) != 1 for h in homes)
            or any(p["weights"] != p["pools"] for p in placements)):
        raise RuntimeError(
            "replicas do not own one distinct device each: %s" % placements)
    emit(dict(phase="mc_replicas", device=device, placements=placements,
              **compile_report()))


PHASES = {
    "kernels": phase_kernels,
    "train": phase_train,
    "serve": phase_serve,
    "mc_train_tp": phase_mc_train_tp,
    "mc_replicas": phase_mc_replicas,
    "mc_executors": phase_mc_executors,
    "mc_refuse": phase_mc_refuse,
}
SINGLE = ("kernels", "train", "serve")
MULTI = ("mc_train_tp", "mc_replicas", "mc_executors", "mc_refuse")


# ----------------------------------------------------------------------
# the parent: never touches a JAX backend
# ----------------------------------------------------------------------


def kill_session(sid):
    """SIGKILL every process of session ``sid`` — the phase child and
    whatever it left behind (executors and compute processes put
    themselves in their own process GROUPS, but stay in the session).
    A chip is free for the next phase only when its owner is gone."""
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open("/proc/%s/stat" % entry) as f:
                # fields after the parenthesised command name:
                # state ppid pgrp session ...
                fields = f.read().rsplit(")", 1)[1].split()
            if int(fields[3]) == sid:
                os.kill(int(entry), signal.SIGKILL)
        except (OSError, IndexError, ValueError):
            continue  # raced a process exit


def cache_entries(path):
    if not path or not os.path.isdir(path):
        return 0
    return sum(1 for f in os.listdir(path) if f.endswith("-cache"))


def run_phase(name, tiny, env, timeout):
    """Run one phase in a child that owns the chip; returns its result
    dict.  Raises when the child fails, hangs or reports nothing."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", name]
    if tiny:
        cmd.append("--tiny")
    proc = subprocess.Popen(
        cmd, env=env, cwd=REPO, stdout=subprocess.PIPE, text=True,
        start_new_session=True,
    )
    timer = threading.Timer(timeout, kill_session, args=(proc.pid,))
    timer.start()
    result = None
    try:
        for line in proc.stdout:
            if line.startswith(RESULT_TAG):
                result = json.loads(line[len(RESULT_TAG):])
            else:
                sys.stdout.write(line)
                sys.stdout.flush()
        rc = proc.wait()
    finally:
        timer.cancel()
        kill_session(proc.pid)
        proc.wait()
    if rc != 0 or result is None:
        raise RuntimeError(
            "phase %s failed (exit code %s%s)" % (
                name, rc, "" if result else ", no result"))
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="toy sizes on the CPU (needs JAX_PLATFORMS=cpu); "
                         "NOT a chip run")
    ap.add_argument("--phase", choices=sorted(PHASES),
                    help="run ONE phase in this process (what the parent "
                         "spawns; also for debugging a single phase)")
    args = ap.parse_args(argv)

    cpu_pinned = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    if args.tiny != cpu_pinned:
        ap.error(
            "--tiny and JAX_PLATFORMS=cpu go together: the tiny mode runs "
            "on the CPU only when both are given, and without --tiny this "
            "is a chip run that refuses a CPU-pinned JAX"
        )
    if args.phase:
        PHASES[args.phase](args.tiny)
        return 0

    from tensorflowonspark_tpu.utils import compile_cache

    env = dict(os.environ)
    if not args.tiny:
        env["JAX_PLATFORMS"] = "tpu"  # a missing chip is an error
    # cache every program, however quick its compile: with the stock
    # 1 s threshold a borderline program is cached by one run and not
    # the next, and "a second run adds no entries" stops being a test
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                  if p])
    cache = compile_cache.cache_dir()
    t_all = time.monotonic()
    print("chip_smoke: %s, compile cache %s (%d entries)" % (
        "TINY CPU MODE - NOT A CHIP RUN" if args.tiny else "chip run",
        cache or "<disabled>", cache_entries(cache)), flush=True)

    results = {}
    device = None
    plan = list(SINGLE)
    while plan:
        name = plan.pop(0)
        left = DEADLINE_SEC - (time.monotonic() - t_all)
        before = cache_entries(cache)
        t0 = time.monotonic()
        res = run_phase(name, args.tiny, env, timeout=max(30.0, left))
        wall = time.monotonic() - t0
        added = cache_entries(cache) - before
        res.update(wall_sec=round(wall, 1), cache_entries_added=added)
        results[name] = res
        print("phase %-13s ok: wall %.1fs, compiling %ss, cache %s "
              "+%d entries (%s hits / %s misses)" % (
                  name, wall, res.get("compile_sec", 0.0), cache, added,
                  res.get("cache_hits", 0), res.get("cache_misses", 0)),
              flush=True)
        if device is None:
            device = res["device"]
            if device["count"] >= 4 and args.tiny:
                # a CPU host has no chips to oversubscribe
                print("mc_refuse: skipped in tiny mode", flush=True)
                plan += [p for p in MULTI if p != "mc_refuse"]
            elif device["count"] >= 4:
                plan += MULTI
            else:
                print("multichip: skipped, %d device(s)" % device["count"],
                      flush=True)

    summary = {
        "tiny": args.tiny,
        "wall_sec": round(time.monotonic() - t_all, 1),
        "cache_dir": cache,
        "cache_entries_added": sum(
            r["cache_entries_added"] for r in results.values()),
        "phases": results,
    }
    if args.tiny:
        summary["note"] = "tiny CPU mode: not a chip run"
    summary["claim"] = None  # a bring-up proof, not a measurement
    print("summary: " + json.dumps(summary), flush=True)
    # the verdict: the LAST stdout line, these keys and no others
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
