"""The chip-owning child of a serving cell: seeded weights in the
served dtype → ``transformer.serving_builder`` → ONE
``serving.predict_rows(schedule="continuous")`` job fed by a closed
loop of callers, measured from when the first wave has drained into
steady decode until ``--seconds`` later.

Every in-flight request carries the engine's own per-request deadline
set to the window's planned close, so the job ends at the first chunk
boundary after it instead of draining for tens of seconds; what such a
request had generated comes back as its ``partial`` tokens.
"""

import collections
import gc
import time

import numpy as np

from benchmarks import compare, traffic
from benchmarks.flops import shapes
from benchmarks.runners import common

#: widest gap of a served token's reference logit below the
#: reference's best; set from readings on the chip (PERF.md §2)
SERVED_GAP_LIMIT = 0.2
#: the same gap averaged over every served token compared
SERVED_GAP_MEAN_LIMIT = 0.004


def program_config(cfg, plan):
    """``serving_builder``'s config from the published keys; every
    serving knob the file's ``program`` does not name stays at the
    program's default."""
    s = shapes(cfg)
    return dict(
        vocab_size=s["v"], num_layers=s["layers"], num_heads=s["h"],
        num_kv_heads=s["hkv"], head_dim=s["dh"], embed_dim=s["d"],
        mlp_dim=s["f"], max_seq_len=cfg["max_position_embeddings"],
        attention_window=s["window"], dtype=cfg["dtype"],
        cache_dtype=cfg["cache_dtype"], mode="generate",
        max_new_tokens=int(plan.answer_len.max()),
        max_prompt_len=int(plan.prompt_len.max()),
        **cfg.get("program", {})
    )


class ClosedLoopSource(object):
    """The ``rows`` iterator of the job.  The engine pulls a row each
    time a slot is free, always between two decode chunks; every pull
    is also where this benchmark reads its clock and the engine's
    counters, opens and closes the window, and starts and stops the
    profiler."""

    def __init__(self, plan, stats, seconds, warm_in_s, profile,
                 compiles, annotate):
        self.plan, self.stats = plan, stats
        self.seconds, self.warm_in_s = seconds, warm_in_s
        self.profile, self.compiles = profile, compiles
        self._annotate = annotate
        self.free = collections.deque(range(plan.clients))
        self.sent = []          # per request: client, prompt, budget, ...
        self._seen_done = set()
        self.t_first = self.t_open = self.t_close = None
        self.open_snap = self.close_snap = None
        self.trace_positions = None

    def __iter__(self):
        return self

    def _snapshot(self):
        st = self.stats
        return {
            "done": set(st["done_at"]), "chunks": st["chunks"],
            "prefill_wall_s": st["prefill_wall_sec"],
            "decode_wall_s": st["decode_wall_sec"],
            "compiles": self.compiles.count,
        }

    def generated(self, req, chunks):
        """Tokens request ``req`` had generated when the engine's chunk
        counter read ``chunks``: one from its prefill, ``chunk_size`` a
        chunk after, up to its budget."""
        if chunks < req["chunks_at_pull"]:
            return 0
        steps = (chunks - req["chunks_at_pull"]) * self.stats["chunk_size"]
        return min(req["budget"], 1 + steps)

    def __next__(self):
        with self._annotate("bench.source"):
            return self._next()

    def _next(self):
        now = time.monotonic()
        for idx in self.stats.get("done_at", ()):
            if idx not in self._seen_done:
                self._seen_done.add(idx)
                self.free.append(self.sent[idx]["client"])
        if self.t_first is None:
            self.t_first = now
        planned_open = self.t_first + self.warm_in_s
        planned_close = planned_open + self.seconds
        if self.t_open is None and now >= planned_open:
            if self.stats["admitted"] < self.plan.clients:
                raise RuntimeError(
                    "warm-in of %.1fs ended with %d of %d callers "
                    "admitted" % (self.warm_in_s, self.stats["admitted"],
                                  self.plan.clients))
            self.t_open, self.open_snap = now, self._snapshot()
        if (self.profile is not None and self.t_open is not None
                and self.profile.started_at is None
                and now >= planned_close - self.profile.seconds):
            # the last seconds of the window; the profiler is stopped
            # (and writes its trace) only after the window has closed
            self.trace_positions = [
                len(r["prompt"]) + self.generated(r, self.stats["chunks"])
                for r in self.sent if r["index"] not in self._seen_done
            ]
            self.profile.start()
        if now >= planned_close:
            self.t_close, self.close_snap = now, self._snapshot()
            raise StopIteration
        client = self.free.popleft()
        prompt, budget = self.plan.next_request(client)
        self.sent.append({
            "index": len(self.sent), "client": client, "prompt": prompt,
            "budget": budget, "chunks_at_pull": self.stats.get("chunks", 0),
            "in_window": self.t_open is not None,
        })
        return {"prompt": prompt, "max_new": budget,
                "deadline_sec": max(1e-3, planned_close - now)}


def _served(source, outputs):
    """``(request, served ids, whole)`` for every request that returned
    tokens: whole answers, and the partial ones the close cut short."""
    served, failed, short = [], 0, 0
    for req, out in zip(source.sent, outputs):
        err = out.get("error")
        if err is None:
            n = int(out["generated_len"])
            short += n != req["budget"]
            served.append((req, np.asarray(out["generated"][:n]), True))
        elif err["kind"] == "deadline":
            served.append((req, np.asarray(err.get("partial", []),
                                           np.int32), False))
        else:
            failed += 1
    return served, failed, short


def _sample(served, seed, k):
    """``k`` finished requests drawn from the seed, the longest in."""
    whole = [s for s in served if s[2]]
    if not whole:
        return []
    longest = max(range(len(whole)),
                  key=lambda i: len(whole[i][0]["prompt"]) + len(whole[i][1]))
    rest = [i for i in range(len(whole)) if i != longest]
    rng = np.random.default_rng([int(seed), 0xC4EC])
    picks = [longest] + list(
        rng.permutation(rest)[:max(0, k - 1)].astype(int))
    return [(whole[i][0]["prompt"], whole[i][1]) for i in picks]


def run(spec):
    t_start = spec["t_start"]
    cfg, mix = spec["config"], spec["traffic"]
    rehearse = spec.get("rehearse")
    device = common.claim_device(spec["chips"], rehearse)
    compiles = common.CompileMeter()
    import jax

    from tensorflowonspark_tpu import serving, serving_engine
    from tensorflowonspark_tpu.models import transformer as tr

    from benchmarks import weights

    plan = traffic.ClosedLoop(mix, spec["seed"], cfg["vocab_size"])
    params = weights.make_params(cfg, spec["seed"], cfg["dtype"])
    predict = tr.serving_builder(params, program_config(cfg, plan))
    del params
    profile = (
        common.ProfileWindow(spec["trace_dir"]) if spec["trace"] else None
    )
    stats = {}
    source = ClosedLoopSource(
        plan, stats, spec["seconds"], float(mix["warm_in_s"]), profile,
        compiles, jax.profiler.TraceAnnotation,
    )
    mapping = {
        "prompt": "tokens", "max_new": serving_engine.BUDGET_INPUT,
        "deadline_sec": serving_engine.DEADLINE_INPUT,
    }
    # warm up every prompt shape the plan holds (the program pads
    # prompts to predict.pad_multiple) and the decode chunk, in a job
    # of the same geometry, so nothing compiles inside the window
    longest = int(plan.prompt_len.max())
    warm = [
        {"prompt": traffic.token_ids(spec["seed"], 2 ** 31 - 1, n, min(n, longest),
                                     cfg["vocab_size"]),
         "max_new": 2, "deadline_sec": 3600.0}
        for n in plan.prompt_buckets(predict.pad_multiple)
    ]
    warmed = list(serving.predict_rows(
        predict, warm, mapping, batch_size=plan.clients,
        schedule="continuous", on_error="raise",
    ))
    if len(warmed) != len(warm):
        raise RuntimeError("the warm-up job lost rows")
    job = serving.predict_rows(
        predict, source, mapping, batch_size=plan.clients,
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
    setup_s = (time.time() - t_start) - (time.monotonic() - source.t_open)
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

    served, failed, short = _served(source, outputs)
    # every token generated inside the window, reckoned for every
    # request from the engine's chunk counter at the open and at the
    # close.  The reckoning itself is held, exactly, against what every
    # request returned: its count at one of the chunk boundaries from the
    # close to the job's end (deadlines are stamped a moment apart, so a
    # request cut short may run one more chunk than its neighbour)
    tokens_in_window = sum(
        source.generated(r, c["chunks"]) - source.generated(r, o["chunks"])
        for r in source.sent
    )
    ends = range(c["chunks"], stats["chunks"] + 1)
    miscounted = sum(
        len(ids) not in {source.generated(req, n) for n in ends}
        for req, ids, _ in served
    )
    samples = _sample(served, spec["seed"], int(mix["check_sample"]))
    # free the program's weights and banks before the reference runs
    del job, predict
    gc.collect()
    t_check = time.monotonic()
    gaps = compare.served_gaps(
        cfg, spec["seed"], samples, cfg["dtype"],
        control=bool(spec.get("control")),
    ) if samples else {"served_gap_max": float("nan"),
                       "served_gap_mean": float("nan"), "tokens_compared": 0}
    check_s = time.monotonic() - t_check
    checks = {
        "served_gap_max": {
            "value": gaps["served_gap_max"], "limit": SERVED_GAP_LIMIT},
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
        "requests_completed": len(done),
        "tokens_compared": gaps["tokens_compared"],
    }
    for key in ("control_gap_max", "control_gap_mean"):
        if key in gaps:
            result[key] = gaps[key]
    if rehearse:
        result["rehearsal"] = "tiny sizes on the CPU: not a measurement"
    if not spec["trace"]:
        result["metrics"] = {
            "serve_tok_s": {
                "value": tokens_in_window / window_s, "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    else:
        from benchmarks import peaks, trace_reduce

        trace = trace_reduce.load_xplane(spec["trace_dir"])
        requests = [
            {"prompt": len(r["prompt"]), "in_window": r["in_window"],
             "gen_open": source.generated(r, o["chunks"]),
             "gen_close": source.generated(r, c["chunks"])}
            for r in source.sent
        ]
        counters = {
            "window_s": window_s, "setup_s": setup_s,
            "tokens_in_window": tokens_in_window,
            "chunk_size": stats["chunk_size"],
            "chunks": c["chunks"] - o["chunks"],
            "prefill_wall_s": c["prefill_wall_s"] - o["prefill_wall_s"],
            "decode_wall_s": c["decode_wall_s"] - o["decode_wall_s"],
            "requests": requests,
            "decode_positions": source.trace_positions or [],
        }
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
            }
    result["checks"] = checks
    return result
