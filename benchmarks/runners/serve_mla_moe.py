"""The chip-owning child of a serving cell whose model is the
latent-attention / sparse-index / sigmoid-routed-experts block
(``reference/glm_dsa_moe.py``): seeded weights in the served dtype →
``transformer.serving_builder`` → ONE
``serving.predict_rows(schedule="continuous")`` job fed by a closed
loop of callers — the same path, window and accounting as
``runners/serve.py``, whose source, sampling and result helpers this
file imports.  What differs:

- the configuration's keys map onto the program's latent-attention and
  expert-share fields (``program_config``);
- the job runs ``clients + 1`` slots and the source answers the
  engine's pull with its ``None`` heartbeat when no caller is free, so
  the engine pulls — and this benchmark reads its clock, opens and
  closes the window and starts the profiler — at EVERY chunk boundary,
  not only when a request completes (one every few seconds here);
- ``correct`` compares with ``reference/glm_dsa_moe.py``, a row at a
  time (``served_gaps``).

The body of ``run`` repeats ``serve.run``'s: PERF.md section 7 asks the
next ``benchmark`` issue for one serving runner whose model module the
configuration names.
"""

import functools
import gc
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import compare, traffic
from benchmarks import weights_glm_dsa_moe as weights
from benchmarks.reference import glm_dsa_moe as ref
from benchmarks.runners import common, serve

#: the gap of a served token's reference logit below the reference's
#: best, over every served token compared: its mean and its 95th
#: percentile; set from readings on the chip (PERF.md section 2):
#: the program 0.0021-0.0031 and 0-0.0039, the int8 control
#: 0.0100-0.0129 and 0.054-0.092
SERVED_GAP_MEAN_LIMIT = 0.006
SERVED_GAP_P95_LIMIT = 0.03
#: seconds of the window's end a traced run profiles: longer than two
#: prefills back to back (2 x 2.95 s), which pass without a pull — at
#: ``ProfileWindow``'s 1.5 s the profiler of one run started at the
#: pull that closed the window and its trace held no chunk at all
PROFILE_SECONDS = 6.0
#: the chunk program's expert counts (``serving.*`` counters)
MOE_COUNTERS = ("moe_assignments", "moe_local_assignments",
                "moe_experts_hit")
#: rows of the comparison are padded to a multiple of this, so that a
#: run's few row lengths find the reference's programs compiled
ROW_MULTIPLE = 4096


def program_config(cfg, plan):
    """``serving_builder``'s config from the published keys; every
    serving knob the file's ``program`` does not name stays at the
    program's default."""
    z = weights.sizes(cfg)
    return dict(
        vocab_size=z["v"], num_layers=z["layers"], num_heads=z["h"],
        embed_dim=z["d"], mlp_dim=z["f"],
        max_seq_len=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_parameters"]["rope_theta"],
        rope_interleave=bool(cfg["rope_interleave"]),
        attention_kind="mla", q_lora_rank=z["rq"], kv_lora_rank=z["rkv"],
        qk_nope_head_dim=z["dn"], qk_rope_head_dim=z["dr"],
        v_head_dim=z["dv"], index_n_heads=z["j"], index_head_dim=z["di"],
        index_topk=z["topk"], indexer_types=list(cfg["indexer_types"]),
        mlp_layer_types=list(cfg["mlp_layer_types"]),
        router_scoring=cfg["scoring_func"], router_experts=z["experts"],
        num_experts=z["held"], expert_first=z["first"], expert_k=z["k"],
        shared_experts=z["shared"],
        routed_scaling=cfg["routed_scaling_factor"], moe_mlp_dim=z["fe"],
        dtype=cfg["dtype"], cache_dtype=cfg["cache_dtype"],
        mode="generate",
        max_new_tokens=int(plan.answer_len.max()),
        max_prompt_len=int(plan.prompt_len.max()),
        **cfg.get("program", {})
    )


class HeartbeatSource(serve.ClosedLoopSource):
    """``serve.ClosedLoopSource`` for a job that keeps one lane free.
    With no caller free it hands the engine its heartbeat (``None``:
    "no request right now", ``ServingEngine._pull_one``), so the engine
    pulls at EVERY chunk boundary, and every pull is logged: its time,
    the engine's chunk counter, the requests sent before it.  The
    window is the PLANNED one, ``warm_in_s`` after the first pull and
    ``seconds`` long whatever the chip was doing at its edges
    (``tokens_between``); ``t_open`` and ``t_close`` stay the first
    pulls at or after the planned edges, where the snapshots for the
    exact checks and the per-layer counters are taken."""

    clock = staticmethod(time.monotonic)

    def __init__(self, *args):
        super().__init__(*args)
        self.pulls = []  # (time, chunks, requests sent before the pull)
        self.planned_open = self.planned_close = None

    def _snapshot(self):
        from tensorflowonspark_tpu import telemetry

        snap = super()._snapshot()
        counters = telemetry.get_registry().snapshot()["counters"]
        snap["moe"] = {
            k: counters.get("serving." + k, 0) for k in MOE_COUNTERS}
        return snap

    def _next(self):
        now = self.clock()
        for idx in self.stats.get("done_at", ()):
            if idx not in self._seen_done:
                self._seen_done.add(idx)
                self.free.append(self.sent[idx]["client"])
        if self.t_first is None:
            self.t_first = now
            self.planned_open = now + self.warm_in_s
            self.planned_close = self.planned_open + self.seconds
        self.pulls.append((now, self.stats.get("chunks", 0), len(self.sent)))
        if self.t_open is None and now >= self.planned_open:
            if self.stats["admitted"] < self.plan.clients:
                raise RuntimeError(
                    "warm-in of %.1fs ended with %d of %d callers "
                    "admitted" % (self.warm_in_s, self.stats["admitted"],
                                  self.plan.clients))
            self.t_open, self.open_snap = now, self._snapshot()
        if (self.profile is not None and self.t_open is not None
                and self.profile.started_at is None
                and now >= self.planned_close - self.profile.seconds):
            self.trace_positions = [
                len(r["prompt"]) + self.generated(r, self.stats["chunks"])
                for r in self.sent if r["index"] not in self._seen_done
            ]
            self.profile.start()
        if now >= self.planned_close:
            self.t_close, self.close_snap = now, self._snapshot()
            raise StopIteration
        if not self.free:
            return None
        client = self.free.popleft()
        prompt, budget = self.plan.next_request(client)
        self.sent.append({
            "index": len(self.sent), "client": client, "prompt": prompt,
            "budget": budget, "chunks_at_pull": self.stats.get("chunks", 0),
            "in_window": self.t_open is not None,
        })
        return {"prompt": prompt, "max_new": budget,
                "deadline_sec": max(1e-3, self.planned_close - now)}

    def generated_curve(self):
        """``(times, tokens)``: the tokens every request had generated,
        as a piecewise-linear function of time through the logged
        pulls.  Between two pulls the chip runs the prefills admitted
        at the first one and then the chunks, so what the chunks
        generated is spread evenly over the END of the interval, as
        long as that many chunks take when nothing else runs (the
        median over all intervals: most hold no prefill), and nothing
        before it."""
        t, c, n = (np.asarray(col) for col in zip(*self.pulls))
        total = np.asarray([
            sum(self.generated(r, ci) for r in self.sent[:ni])
            for ci, ni in zip(c, n)], float)
        dt, dc = np.diff(t), np.diff(c)
        ran = dc > 0
        chunk_s = float(np.median(dt[ran] / dc[ran])) if ran.any() else 0.0
        times, tokens = [t[0]], [total[0]]
        for i in range(len(dt)):
            start = t[i + 1] - min(dt[i], dc[i] * chunk_s)
            times += [start, t[i + 1]]
            tokens += [total[i], total[i + 1]]
        return np.asarray(times), np.asarray(tokens), chunk_s

    def tokens_between(self, t0, t1):
        """Tokens generated between two times inside the logged pulls:
        a chunk that an edge cuts counts in proportion to its overlap."""
        times, tokens, _ = self.generated_curve()
        lo, hi = np.interp([t0, t1], times, tokens)
        return float(hi - lo)


# ----------------------------------------------------------------------
# the comparison that decides ``correct``
# ----------------------------------------------------------------------


def _items(model):
    """The configuration's published keys as a hashable static
    argument (JSON text)."""
    return json.dumps(
        {k: v for k, v in model.items()
         if k not in ("assumed", "deployment", "source", "program")},
        sort_keys=True)


@functools.partial(jax.jit, static_argnames=("items", "dtype", "kind"))
def _block_weights(key, index, items, dtype, kind):
    # ``kind`` is a layer of the same kinds as ``index`` (static: it
    # picks the leaf set and the equations); ``index`` keys the weights
    return weights.block_params(
        json.loads(items), key, index, jnp.dtype(dtype), kinds_of=kind)


@functools.partial(jax.jit, static_argnames=("items", "kind", "mode"))
def _block_step(x, sel, p, items, kind, mode):
    # the weights come drawn (``_block_weights``): drawn inside this
    # program, the compiler kept every leaf's random bits alive at once
    # under ``mode="bf16"`` (20 GB by its own plan for the v5e)
    return ref.block(x, p, json.loads(items), kind,
                     jnp.arange(x.shape[0]), mode, sel)


@functools.partial(jax.jit, static_argnames=("items", "dtype"))
def _embed(tokens, key, items, dtype):
    return ref.embed(tokens, weights.outer_params(
        json.loads(items), key, jnp.dtype(dtype)))


@functools.partial(jax.jit, static_argnames=("items", "dtype", "mode"))
def _head(x, key, items, dtype, mode):
    model = json.loads(items)
    return ref.head(x, weights.outer_params(model, key, jnp.dtype(dtype)),
                    model, mode)


def reference_logits(model, seed, tokens, dtype, mode="f32"):
    """Logits ``[L, vocab]`` of the reference over ONE row, the weights
    drawn layer by layer from ``seed`` in ``dtype``."""
    items, key = _items(model), weights.seed_key(seed)
    kinds = [weights.layer_kinds(model, i)
             for i in range(model["num_hidden_layers"])]
    x = _embed(jnp.asarray(tokens), key, items, dtype)
    sel = jnp.zeros((1, 1), bool)  # a "full" layer comes first
    for i, kind in enumerate(kinds):
        like = kinds.index(kind)
        x, sel = _block_step(
            x, sel, _block_weights(key, jnp.int32(i), items, dtype, like),
            items, like, mode)
    return _head(x, key, items, dtype, mode)


def _summary(gaps):
    """Maximum, mean and 95th percentile of the per-token gaps."""
    return {"max": float(np.max(gaps)), "mean": float(np.mean(gaps)),
            "p95": float(np.quantile(gaps, 0.95))}


#: what ``--control`` reads beside the program: the reference's own
#: first choices with every matmul in int8 (the control, which has to
#: lie outside the limits) and at the program's precision (bfloat16
#: inputs and activations: how far rounding alone moves the answer)
LOW_MODES = {"int8": "control_gap_", "bf16": "bf16_gap_"}


def served_gaps(model, seed, samples, dtype, control=False,
                row_multiple=ROW_MULTIPLE):
    """``compare.served_gaps`` against this file's reference, a row at
    a time (a row is up to 20 thousand tokens): over every served token
    of ``samples``, the gap by which the served token's reference
    logit lies below the reference's best — its maximum, its mean and
    its 95th percentile — and with ``control`` the same three for the
    token each of ``LOW_MODES`` puts first."""
    gaps = {"served": []}
    for sample in samples:
        tokens, served = compare._pad_rows([sample], row_multiple)
        srv = jnp.asarray(served[0])
        valid = np.asarray(srv >= 0)
        logits = reference_logits(model, seed, tokens[0], dtype)
        gaps["served"].append(np.asarray(
            compare._gaps(logits, srv, srv >= 0))[valid])
        for mode in LOW_MODES if control else ():
            low = reference_logits(model, seed, tokens[0], dtype, mode)
            first = jnp.argmax(low, axis=-1).astype(jnp.int32)
            gaps.setdefault(mode, []).append(np.asarray(
                compare._gaps(logits, first, srv >= 0))[valid])
    gaps = {k: np.concatenate(v) for k, v in gaps.items()}
    out = {"tokens_compared": int(gaps["served"].size)}
    for mode, values in gaps.items():
        prefix = LOW_MODES.get(mode, "served_gap_")
        out.update((prefix + k, v) for k, v in _summary(values).items())
    return out


def trace_counters(source, stats, slots, setup_s, window_s,
                   tokens_in_window):
    """What the per-layer readers get beside the trace: differences of
    the two snapshots (whole chunks), the requests with what each had
    generated at either, the slots' positions when the profiler
    started; the keys ``runners/serve.py`` gives, so that the readers
    the two serving cells share read both."""
    o, c = source.open_snap, source.close_snap
    return {
        "window_s": window_s, "setup_s": setup_s,
        "tokens_in_window": tokens_in_window,
        "chunk_size": stats["chunk_size"],
        "chunks": c["chunks"] - o["chunks"],
        "prefill_wall_s": c["prefill_wall_s"] - o["prefill_wall_s"],
        "decode_wall_s": c["decode_wall_s"] - o["decode_wall_s"],
        "requests": [
            {"prompt": len(r["prompt"]), "in_window": r["in_window"],
             "gen_open": source.generated(r, o["chunks"]),
             "gen_close": source.generated(r, c["chunks"])}
            for r in source.sent
        ],
        "decode_positions": source.trace_positions or [],
        "slots": slots,
        "bank_len": int(stats.get("kv_bank_tokens", 0)) // slots,
    }


# ----------------------------------------------------------------------
# the run
# ----------------------------------------------------------------------


def run(spec):
    t_start = spec["t_start"]
    cfg, mix = spec["config"], spec["traffic"]
    rehearse = spec.get("rehearse")
    device = common.claim_device(spec["chips"], rehearse)
    compiles = common.CompileMeter()
    from tensorflowonspark_tpu import serving, serving_engine
    from tensorflowonspark_tpu.models import transformer as tr

    if rehearse and rehearse.get("fault"):
        from benchmarks.tests import faults_glm_dsa_moe

        faults_glm_dsa_moe.plant(rehearse["fault"])
    plan = traffic.ClosedLoop(mix, spec["seed"], cfg["vocab_size"])
    params = weights.make_params(cfg, spec["seed"], cfg["dtype"])
    predict = tr.serving_builder(params, program_config(cfg, plan))
    del params
    profile = (
        common.ProfileWindow(spec["trace_dir"], PROFILE_SECONDS)
        if spec["trace"] else None
    )
    stats = {}
    source = HeartbeatSource(
        plan, stats, spec["seconds"], float(mix["warm_in_s"]), profile,
        compiles, jax.profiler.TraceAnnotation,
    )
    mapping = {
        "prompt": "tokens", "max_new": serving_engine.BUDGET_INPUT,
        "deadline_sec": serving_engine.DEADLINE_INPUT,
    }
    slots = plan.clients + 1  # a lane always free: a pull every chunk
    # warm up every prompt shape the plan holds and the decode chunk,
    # in a job of the same geometry: nothing compiles inside the window
    longest = int(plan.prompt_len.max())
    warm = [
        {"prompt": traffic.token_ids(
            spec["seed"], 2 ** 31 - 1, n, min(n, longest),
            cfg["vocab_size"]),
         "max_new": 2, "deadline_sec": 3600.0}
        for n in plan.prompt_buckets(predict.pad_multiple)
    ]
    warmed = list(serving.predict_rows(
        predict, warm, mapping, batch_size=slots,
        schedule="continuous", on_error="raise",
    ))
    if len(warmed) != len(warm):
        raise RuntimeError("the warm-up job lost rows")
    job = serving.predict_rows(
        predict, source, mapping, batch_size=slots,
        schedule="continuous", on_error="record", stats=stats,
    )
    outputs = []
    while True:
        with jax.profiler.TraceAnnotation("bench.predict_rows"):
            out = next(job, None)
        if out is None:
            break
        outputs.append(out)
    if source.t_close is None or source.t_open is None:
        raise RuntimeError("the job ended before the window closed")
    # set-up ends where the planned window opens; ``window_s`` is the
    # span between the two pulls that took the snapshots, which the
    # per-layer counters below are differences of
    setup_s = (time.time() - t_start) - (
        time.monotonic() - source.planned_open)
    window_s = source.t_close - source.t_open
    o, c = source.open_snap, source.close_snap
    if c["compiles"] != o["compiles"]:
        raise RuntimeError(
            "%d program(s) compiled inside the window" % (
                c["compiles"] - o["compiles"]))
    if profile is not None and profile.running:
        profile.stop()
    done = sorted(c["done"] - o["done"])
    peak = common.memory_peak_bytes()

    served, failed, short = serve._served(source, outputs)
    # every token generated inside the planned window, a chunk that an
    # edge cuts in proportion (``HeartbeatSource.tokens_between``);
    # between the snapshots in whole chunks, for the per-layer counters
    # and held exactly against what every request returned: as
    # serve.run
    tokens_planned = source.tokens_between(
        source.planned_open, source.planned_close)
    tokens_in_window = sum(
        source.generated(r, c["chunks"]) - source.generated(r, o["chunks"])
        for r in source.sent
    )
    ends = range(c["chunks"], stats["chunks"] + 1)
    miscounted = sum(
        len(ids) not in {source.generated(req, n) for n in ends}
        for req, ids, _ in served
    )
    samples = serve._sample(served, spec["seed"], int(mix["check_sample"]))
    # free the program's weights and banks before the reference runs
    del job, predict
    gc.collect()
    t_check = time.monotonic()
    gaps = served_gaps(
        cfg, spec["seed"], samples, cfg["dtype"],
        control=bool(spec.get("control")),
        row_multiple=int((rehearse or {}).get("row_multiple", ROW_MULTIPLE)),
    ) if samples else {"served_gap_max": float("nan"),
                       "served_gap_mean": float("nan"),
                       "served_gap_p95": float("nan"), "tokens_compared": 0}
    check_s = time.monotonic() - t_check
    checks = {
        "served_gap_p95": {
            "value": gaps["served_gap_p95"], "limit": SERVED_GAP_P95_LIMIT},
        "served_gap_mean": {
            "value": gaps["served_gap_mean"], "limit": SERVED_GAP_MEAN_LIMIT},
        "answers_not_of_budget": {"value": float(short), "limit": 0.0},
        "tokens_miscounted": {"value": float(miscounted), "limit": 0.0},
        "requests_failed": {"value": float(failed), "limit": 0.0},
    }
    correct = common.checks_hold(checks)

    result = {
        "correct": bool(correct), "attempted": len(done) + failed,
        "failed": failed, "metrics": {},
        "device": dict(device, memory_peak_bytes=peak),
        "window_s": window_s, "check_s": check_s,
        "tokens_between_snapshots": tokens_in_window,
        "chunk_s": source.generated_curve()[2],
        "requests_completed": len(done),
        "tokens_compared": gaps["tokens_compared"],
        # read, not held to a limit: PERF.md section 2 says why
        "served_gap_max": gaps["served_gap_max"],
    }
    result.update((k, v) for k, v in gaps.items()
                  if k.startswith(("control_gap_", "bf16_gap_")))
    steps = max(1, c["chunks"] - o["chunks"]) * stats["chunk_size"]
    for key in MOE_COUNTERS[1:]:
        # what this seed's router sends to the held experts, a step
        result[key + "_per_step"] = (c["moe"][key] - o["moe"][key]) / steps
    if rehearse:
        result["rehearsal"] = "tiny sizes on the CPU: not a measurement"
    if not spec["trace"]:
        result["metrics"] = {
            "serve_tok_s": {
                "value": tokens_planned / spec["seconds"],
                "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    else:
        from benchmarks import peaks, trace_reduce

        trace = trace_reduce.load_xplane(spec["trace_dir"])
        counters = trace_counters(
            source, stats, slots, setup_s, window_s, tokens_in_window)
        cell = {
            "config": cfg, "traffic": mix, "chips": spec["chips"],
            "peaks": (None if rehearse
                      else peaks.peaks_for(device["kind"])),
        }
        result["metrics"] = common.per_layer_metrics(
            spec["per_layer"], trace, counters, cell)
        summary = trace_reduce.summary(trace)
        if summary is not None:
            result["device"].update(
                busy_s=summary["busy_s"], window_s=summary["window_s"])
            result["breakdown"] = {
                "device_ops": trace_reduce.top_device_ops(trace),
                "idle_gaps": trace_reduce.idle_gaps(trace),
                # the admissions the traced seconds happened to hold
                "prefill_programs_s": trace_reduce.program_events(
                    trace, r"^jit__prefill"),
            }
    result["checks"] = checks
    return result
