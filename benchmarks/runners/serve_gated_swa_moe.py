"""The chip-owning child of a serving cell whose model is the gated
window-and-full-attention / softmax-routed-experts block with a shared
expert (``reference/gated_swa_moe.py``): seeded weights in the served
dtype → ``transformer.serving_builder`` → ONE
``serving.predict_rows(schedule="continuous")`` job fed by a closed
loop of callers — the same path, window and accounting as
``runners/serve_swa_moe.py``, whose sampling and result helpers, and
``runners/serve_mla_moe.py``'s heartbeat source and counters, this file
imports.  What differs:

- the configuration's keys map onto the program's query heads by
  layer, the per-head output gate, the rotated share of a head by
  layer type, the held share of the experts with a shared one and the
  routed scaling (``program_config``), checked BEFORE the device is
  claimed: a program that lacks those fields fails here, within
  seconds;
- ``correct`` compares with ``reference/gated_swa_moe.py``, a row at a
  time and the head over the served positions alone
  (``served_gaps``); the requests compared always hold the longest
  and one whose prompt is longer than the full layers' YaRN original
  context (``serve_swa_moe.sample``);
- the result says when the last caller was admitted
  (``all_admitted_s``, seconds after the first pull), which is what
  the mix's ``warm_in_s`` is set from.
"""

import functools
import gc
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import compare, traffic
from benchmarks import weights_gated_swa_moe as weights
from benchmarks.reference import gated_swa_moe as ref
from benchmarks.runners import common, serve
from benchmarks.runners.serve_mla_moe import (
    LOW_MODES, MOE_COUNTERS, HeartbeatSource, _items, _summary,
    trace_counters,
)
from benchmarks.runners.serve_swa_moe import BANK_KINDS, HEAD_ROWS, sample

#: the gap of a served token's reference logit below the reference's
#: best, over every served token compared: its mean and its 95th
#: percentile; set from readings on the chip (PERF.md section 2): the
#: program 0.00075-0.00151 and 0 on every run, the int8 control
#: 0.00647-0.00670 and 0.0428-0.0449 — each limit over twice the
#: program's largest and under the control's smallest, which both
#: limits read not correct
SERVED_GAP_MEAN_LIMIT = 0.004
SERVED_GAP_P95_LIMIT = 0.02
#: seconds of the window's end a traced run profiles: long enough to
#: hold a few prefills (a prompt of 16384 is about a second)
PROFILE_SECONDS = 6.0
#: rows of the comparison are padded to a multiple of this, so that a
#: run's few row lengths find the reference's programs compiled
ROW_MULTIPLE = 2048
#: what the program must have to run this cell
PROGRAM_FIELDS = ("layer_types", "layer_rope", "qk_norm", "sliding_window",
                  "num_attention_heads_per_layer", "gating",
                  "expert_first", "router_experts", "shared_experts",
                  "routed_scaling")


def program_config(cfg, plan, mix=None):
    """``serving_builder``'s config from the published keys; every
    serving knob the file's ``program`` does not name stays at the
    program's default.  The banks are sized for the longest prompt and
    answer the MIX may draw (its ``hi``s: 16384 + 4096 = 20480 rows, a
    whole number of the decode kernel's blocks)."""
    z = weights.sizes(cfg)
    longest = (
        (int(plan.prompt_len.max()), int(plan.answer_len.max()))
        if mix is None else
        (int(mix["prompt_tokens"]["hi"]), int(mix["answer_tokens"]["hi"])))
    if z["fs"] % z["fe"]:
        raise ValueError("the shared expert is not a whole number of "
                         "routed experts' widths")
    return dict(
        vocab_size=z["v"], num_layers=z["layers"],
        num_heads=cfg["num_attention_heads"],
        num_attention_heads_per_layer=list(
            cfg["num_attention_heads_per_layer"]),
        num_kv_heads=z["hkv"], head_dim=z["dh"], embed_dim=z["d"],
        mlp_dim=z["f"], max_seq_len=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], qk_norm=True,
        gating=cfg["gating"], layer_types=list(cfg["layer_types"]),
        sliding_window=z["window"], layer_rope=cfg["rope_parameters"],
        mlp_layer_types=list(cfg["mlp_layer_types"]),
        router_scoring="softmax", expert_dispatch="share",
        num_experts=z["held"], router_experts=z["experts"],
        expert_first=z["first"], expert_k=z["k"],
        routed_scaling=cfg["moe_routed_scaling_factor"],
        shared_experts=z["fs"] // z["fe"], moe_mlp_dim=z["fe"],
        dtype=cfg["dtype"], cache_dtype=cfg["cache_dtype"],
        mode="generate",
        max_new_tokens=longest[1], max_prompt_len=longest[0],
        **cfg.get("program", {})
    )


def lacks(tr):
    """The fields and methods this cell needs that the program ``tr``
    (``models/transformer.py``) does not have."""
    fields = {f.name for f in tr.dataclasses.fields(tr.TransformerConfig)}
    return sorted(
        [k for k in PROGRAM_FIELDS if k not in fields]
        + [k for k in ("heads_of", "rotary_of")
           if not hasattr(tr.TransformerConfig, k)])


class AdmittedSource(HeartbeatSource):
    """``HeartbeatSource`` that notes when every caller had been
    admitted: ``all_admitted_s``, seconds from the first pull to the
    first pull that found them all in."""

    all_admitted_s = None

    def _next(self):
        if (self.all_admitted_s is None and self.t_first is not None
                and self.stats.get("admitted", 0) >= self.plan.clients):
            self.all_admitted_s = self.clock() - self.t_first
        return super()._next()


# ----------------------------------------------------------------------
# the comparison that decides ``correct``
# ----------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("items", "dtype", "kind"))
def _block_weights(key, index, items, dtype, kind):
    # ``kind`` is a layer of the same kinds as ``index`` (static: it
    # picks the leaf set); ``index`` keys the weights
    return weights.block_params(
        json.loads(items), key, index, jnp.dtype(dtype), kinds_of=kind)


@functools.partial(jax.jit, static_argnames=("items", "kind", "mode"))
def _block_step(x, p, items, kind, mode):
    return ref.block(x, p, json.loads(items), kind,
                     jnp.arange(x.shape[0]), mode)


@functools.partial(jax.jit, static_argnames=("items", "dtype"))
def _embed(tokens, key, items, dtype):
    return ref.embed(tokens, weights.outer_params(
        json.loads(items), key, jnp.dtype(dtype)))


@functools.partial(jax.jit, static_argnames=("items", "dtype", "mode"))
def _head(x, key, items, dtype, mode):
    model = json.loads(items)
    return ref.head(x, weights.outer_params(model, key, jnp.dtype(dtype)),
                    model, mode)


def reference_hidden(model, seed, tokens, dtype, mode="f32"):
    """The reference's last hidden rows ``[L, hidden]`` over ONE row,
    the weights drawn layer by layer from ``seed`` in ``dtype`` (one
    program for the layers of the same kinds)."""
    items, key = _items(model), weights.seed_key(seed)
    kinds = [weights.layer_kinds(model, i)
             for i in range(model["num_hidden_layers"])]
    x = _embed(jnp.asarray(tokens), key, items, dtype)
    for i, kind in enumerate(kinds):
        like = kinds.index(kind)
        x = _block_step(
            x, _block_weights(key, jnp.int32(i), items, dtype, like),
            items, like, mode)
    return x


def reference_logits(model, seed, hidden, dtype, mode="f32"):
    """Logits ``[n, vocab]`` of ``hidden[n]``, :data:`HEAD_ROWS` at a
    time."""
    items, key = _items(model), weights.seed_key(seed)
    return jnp.concatenate([
        _head(hidden[i:i + HEAD_ROWS], key, items, dtype, mode)
        for i in range(0, hidden.shape[0], HEAD_ROWS)])


def served_gaps(model, seed, samples, dtype, control=False,
                row_multiple=ROW_MULTIPLE):
    """``serve_swa_moe.served_gaps`` against this file's reference:
    over every served token of ``samples`` the gap by which the served
    token's reference logit lies below the reference's best — its
    maximum, mean and 95th percentile — and with ``control`` the same
    three for the token each of ``LOW_MODES`` puts first.  Only the
    served positions (from the prompt's last token on) go through the
    head."""
    gaps = {"served": []}
    for prompt, ids in samples:
        tokens, _ = compare._pad_rows([(prompt, ids)], row_multiple)
        n = -(-len(ids) // HEAD_ROWS) * HEAD_ROWS
        # logits at position t predict token t + 1
        at = np.minimum(len(prompt) - 1 + np.arange(n), tokens.shape[1] - 1)
        srv = np.full((n,), -1, np.int32)
        srv[:len(ids)] = ids
        valid = srv >= 0
        srv = jnp.asarray(srv)
        logits = reference_logits(
            model, seed,
            reference_hidden(model, seed, tokens[0], dtype)[at], dtype)
        gaps["served"].append(np.asarray(
            compare._gaps(logits, srv, srv >= 0))[valid])
        for mode in LOW_MODES if control else ():
            low = reference_logits(
                model, seed,
                reference_hidden(model, seed, tokens[0], dtype, mode)[at],
                dtype, mode)
            first = jnp.argmax(low, axis=-1).astype(jnp.int32)
            gaps.setdefault(mode, []).append(np.asarray(
                compare._gaps(logits, first, srv >= 0))[valid])
    gaps = {k: np.concatenate(v) for k, v in gaps.items()}
    out = {"tokens_compared": int(gaps["served"].size)}
    for mode, values in gaps.items():
        prefix = LOW_MODES.get(mode, "served_gap_")
        out.update((prefix + k, v) for k, v in _summary(values).items())
    return out


# ----------------------------------------------------------------------
# the run
# ----------------------------------------------------------------------


def run(spec):
    t_start = spec["t_start"]
    cfg, mix = spec["config"], spec["traffic"]
    rehearse = spec.get("rehearse")
    from tensorflowonspark_tpu.models import transformer as tr

    # a program without these cannot run the cell: say so now, before
    # the device is claimed
    missing = lacks(tr)
    if missing:
        raise RuntimeError(
            "this program's TransformerConfig lacks %s" % ", ".join(missing))
    plan = traffic.ClosedLoop(mix, spec["seed"], cfg["vocab_size"])
    program = program_config(cfg, plan, mix)
    device = common.claim_device(spec["chips"], rehearse)
    compiles = common.CompileMeter()
    from tensorflowonspark_tpu import serving, serving_engine

    if rehearse and rehearse.get("fault"):
        from benchmarks.tests import faults_gated_swa_moe

        faults_gated_swa_moe.plant(rehearse["fault"])
    params = weights.make_params(cfg, spec["seed"], cfg["dtype"])
    predict = tr.serving_builder(params, program)
    del params
    profile = (
        common.ProfileWindow(spec["trace_dir"], PROFILE_SECONDS)
        if spec["trace"] else None
    )
    stats = {}
    source = AdmittedSource(
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
    # edge cuts in proportion; between the snapshots in whole chunks,
    # for the per-layer counters and held exactly against what every
    # request returned: as serve_swa_moe.run
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
    bank_bytes = {k: stats.get("kv_bank_bytes_" + k) for k in BANK_KINDS}
    # a ring's rows, from what the banks hold (the bank's length is
    # the longest bucket plus the longest answer): every request
    # compared has decoded across the wrap
    layers = cfg["layer_types"]
    pad = predict.pad_multiple
    bank_len = -(-program["max_prompt_len"] // pad) * pad + (
        program["max_new_tokens"])
    ring_rows = 0
    if bank_bytes["ring"]:
        ring_rows = round(
            bank_len * len(layers) * bank_bytes["ring"]
            / (layers.count("sliding_attention") * bank_bytes["unringed"]))
    # one compared prompt passes the full layers' YaRN original context
    original = int(cfg["rope_parameters"]["full_attention"].get(
        "original_max_position_embeddings", longest // 2))
    samples = sample(
        served, spec["seed"], int(mix["check_sample"]), ring_rows,
        min(original, longest // 2))
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
    # the limits' reasons: SERVED_GAP_*_LIMIT above; the exact checks
    # hold every request to its budget, every token counted in the
    # window to what the requests returned, and no request lost
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
        "all_admitted_s": source.all_admitted_s,
        "tokens_between_snapshots": tokens_in_window,
        "chunk_s": source.generated_curve()[2],
        "requests_completed": len(done),
        "requests_compared": len(samples),
        "prompts_compared": [len(p) for p, _ in samples],
        "tokens_compared": gaps["tokens_compared"],
        "ring_rows": ring_rows,
        # what the last chunk's program attended with and read, by the
        # engine's own reckoning
        "decode": {k: stats.get(k) for k in (
            "attn", "kv_read_tokens", "kv_bank_tokens", "attn_read_tokens",
            "attn_context_tokens", "kv_read_ring", "kv_read_whole")},
        # read, not held to a limit: PERF.md section 2 says why
        "served_gap_max": gaps["served_gap_max"],
    }
    result.update((k, v) for k, v in gaps.items()
                  if k.startswith(("control_gap_", "bf16_gap_")))
    steps = max(1, c["chunks"] - o["chunks"]) * stats["chunk_size"]
    for key in MOE_COUNTERS:
        # what this seed's router asks of the experts, a step
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
        counters["kv_bank_bytes"] = bank_bytes
        if source.trace_positions:
            result["decode"]["positions_mean"] = float(
                np.mean(source.trace_positions))
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
