"""The latent-attention / sparse-index / routed-experts configuration:
its reference against the program at tiny widths, the low-precision
control outside the limits, a planted fault under the timed path, the
operation and byte counts on hand-counted shapes, and the cell
rehearsed end to end through ``run.main`` with its own tiny sizes."""

import contextlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import flops_glm_dsa_moe as fl
from benchmarks import weights_glm_dsa_moe as weights
from benchmarks.reference import glm_dsa_moe as ref
from benchmarks.runners import serve_mla_moe as runner
from benchmarks.tests.conftest import ROOT
from benchmarks.tests.test_run_e2e import bench, rehearse
from benchmarks.tests.test_span_readers import ring, trace  # noqa: F401

CELL = "glm52-longdoc-decode"

#: a key-for-key miniature of the published configuration
TINY_GLM = dict(
    hidden_size=64, num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16,
    index_n_heads=2, index_head_dim=16, index_topk=12,
    intermediate_size=96, moe_intermediate_size=32, n_shared_experts=1,
    num_experts_per_tok=3, n_routed_experts=4,
    expert_share={"first": 4, "held": 4, "of": 16},
    vocab_size=256, num_hidden_layers=4, first_k_dense_replace=1,
    mlp_layer_types=["dense", "sparse", "sparse", "sparse"],
    indexer_types=["full", "shared", "full", "shared"],
    max_position_embeddings=256,
)
#: the rehearsal serves in float32, so that the CPU run's ``correct``
#: says something (bf16 at widths this small reads gaps near 1)
TINY_CELL = dict(
    config=dict(TINY_GLM, dtype="float32", cache_dtype="float32"),
    traffic=dict(
        clients=3, warm_in_s=2.0, check_sample=2, requests_per_client=400,
        prompt_tokens={"dist": "uniform", "lo": 16, "hi": 40},
        answer_tokens={"dist": "loguniform", "lo": 8, "hi": 24},
    ),
    row_multiple=16,
)


def tiny_model(dtype="float32"):
    with open(os.path.join(
            ROOT, "benchmarks/configs/glm-5.2.serve-ep16.json")) as f:
        cfg = json.load(f)
    cfg.update(TINY_GLM, dtype=dtype, cache_dtype=dtype)
    cfg["program"] = {"pad_multiple": 8}
    return cfg


def program(cfg, params, slots=2, cache_len=96, max_new=16):
    from tensorflowonspark_tpu.models import transformer as tr

    class Plan:
        answer_len = np.array([max_new])
        prompt_len = np.array([cache_len - max_new])

    pc = runner.program_config(cfg, Plan)
    for k in ("mode", "max_new_tokens", "max_prompt_len", "pad_multiple"):
        pc.pop(k)
    model = tr.Transformer(tr.TransformerConfig(**pc))
    return model, tr.SlotDecoder(
        model, params, slots, max_new, cache_len=cache_len, chunk_size=4,
        pad_multiple=8)


def served_rows(cfg, seed, prompts, new=12):
    """Greedy answers of ``prompts`` through the slot decoder."""
    params = weights.make_params(cfg, seed, cfg["dtype"])
    _, dec = program(cfg, params, slots=len(prompts))
    firsts = [int(dec.admit(i, p)) for i, p in enumerate(prompts)]
    rows = [[f] for f in firsts]
    while len(rows[0]) < new:
        toks, _ = dec.step_chunk()
        for i, row in enumerate(rows):
            row.extend(int(t) for t in toks[i])
    return [(np.asarray(p), np.asarray(r[:new], np.int32))
            for p, r in zip(prompts, rows)]


def prompts_for(seed, lengths, vocab=256):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, n).astype(np.int32) for n in lengths]


def test_the_configuration_keeps_every_published_width():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5.2")
    with open(os.path.join(
            ROOT, "benchmarks/configs/glm-5.2.serve-ep16.json")) as f:
        cfg = json.load(f)
    differ = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differ == set(cfg["reduced"])
    assert cfg["source"] == row["source_url"]
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in cfg["reduced"])
    for key in ("published", "assumed", "deployment", "expert_share"):
        assert key in cfg
    s = weights.sizes(cfg)
    assert (s["held"], s["experts"], s["k"]) == (16, 256, 8)


def test_the_traffic_file_is_the_issue_s():
    with open(os.path.join(
            ROOT, "benchmarks/traffic/longdoc-reason-closed.json")) as f:
        mix = json.load(f)
    assert mix == {
        "loop": "closed", "clients": 16,
        "prompt_tokens": {"dist": "uniform", "lo": 8192, "hi": 16384},
        "answer_tokens": {"dist": "loguniform", "lo": 1024, "hi": 4096},
        "sharing": {"kind": "none"}, "sampling": "greedy",
        "first_wave": "residual", "schedule_seed": 28,
        # ISSUE 28's 20 s raised by its rule, in steps of 5 until the
        # 16 first admissions (29.2 s of prefill) fit
        "requests_per_client": 8, "warm_in_s": 30.0, "check_sample": 4,
    }


def test_the_drawn_correction_bias_changes_choices_and_keeps_the_load():
    # over 256 experts of unit-normal router logits, as a unit-variance
    # stream gives them: the drawn bias changes the chosen eight for a
    # fair share of tokens, never a weight, and no expert's load moves
    # far from its share
    key = jax.random.PRNGKey(3)
    logits = jax.random.normal(key, (4096, 256), jnp.float32)
    bias = weights._leaf(jax.random.fold_in(key, 1), (256,), "correction",
                         jnp.float32)
    model = dict(TINY_GLM, num_experts_per_tok=8, n_routed_experts=256,
                 expert_share=None, routed_scaling_factor=2.5)
    p = {"router": jnp.eye(256), "router_bias": bias}
    with_bias = np.asarray(ref.route(logits, p, model, "f32"))
    without = np.asarray(ref.route(
        logits, dict(p, router_bias=jnp.zeros(256)), model, "f32"))
    changed = np.any((with_bias > 0) != (without > 0), axis=-1)
    assert 0.1 < changed.mean() < 0.6
    same = ~changed
    np.testing.assert_allclose(with_bias[same], without[same], rtol=1e-6)
    load = (with_bias > 0).mean(axis=0) * 256 / 8
    assert np.abs(load - (without > 0).mean(axis=0) * 256 / 8).max() < 0.2


def test_the_program_s_full_forward_is_the_reference_s():
    # float32 on both sides at matmul precision highest: what is left
    # is the order of float32 sums (1e-5 of logits of size ~3)
    cfg = tiny_model()
    params = weights.make_params(cfg, 11, "float32")
    model, _ = program(cfg, params)
    tokens = prompts_for(3, [48])[0]
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, tokens[None])[0]
        want = ref.forward(jnp.asarray(tokens), params, cfg)
        rows = runner.reference_logits(cfg, 11, tokens, "float32")
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5
    assert float(jnp.max(jnp.abs(rows - want))) < 2e-5


def test_served_tokens_sit_on_the_reference_s_best_and_the_control_does_not():
    # float32 serving: prefill, the latent and index banks and the
    # absorbed decode steps give the reference's own argmax but for
    # float32 near-ties (gap under 1e-4); the int8 control's first
    # choices lie further off than the limits allow
    cfg = tiny_model()
    samples = served_rows(cfg, 5, prompts_for(7, [31, 22]))
    with jax.default_matmul_precision("highest"):
        gaps = runner.served_gaps(cfg, 5, samples, "float32", control=True,
                                  row_multiple=16)
    assert gaps["tokens_compared"] == 24
    assert gaps["served_gap_max"] < 1e-4
    assert gaps["control_gap_max"] > gaps["served_gap_max"] * 100
    assert gaps["control_gap_mean"] > 0
    assert gaps["served_gap_p95"] <= gaps["served_gap_max"]
    # the reference at the program's own precision reads between them
    assert gaps["served_gap_max"] < gaps["bf16_gap_mean"]
    assert gaps["bf16_gap_mean"] < gaps["control_gap_mean"]
    assert set(gaps) == {"tokens_compared"} | {
        prefix + k for prefix in ("served_gap_", "control_gap_", "bf16_gap_")
        for k in ("max", "mean", "p95")}


def test_the_planted_fault_reads_not_correct(monkeypatch):
    from benchmarks.tests import faults_glm_dsa_moe
    from tensorflowonspark_tpu.models import mla

    cfg = tiny_model()
    monkeypatch.setattr(mla, "topk_mask", mla.topk_mask)
    faults_glm_dsa_moe.plant("recent_keys_only")
    samples = served_rows(cfg, 5, prompts_for(7, [31, 22]))
    with jax.default_matmul_precision("highest"):
        gaps = runner.served_gaps(cfg, 5, samples, "float32",
                                  row_multiple=16)
    assert gaps["served_gap_max"] > 1e-2


def test_counts_on_hand_counted_shapes():
    m = dict(TINY_GLM, routed_scaling_factor=2.5)
    # attention: 64*32 + 32*4*20 + 64*24 + 16*4*28 + 4*16*64
    assert fl.attention_params(m, "shared") == 2048 + 2560 + 1536 + 1792 + 4096
    # the index adds 32*2*16 + 64*16 + 64*2
    assert fl.attention_params(m, "full") == 12032 + 1024 + 1024 + 128
    assert fl.expert_params(m) == 3 * 64 * 32
    assert fl.ffn_params(m, "dense") == 3 * 64 * 96
    assert fl.ffn_params(m, "sparse") == 64 * 16 + 6144
    assert fl.token_params(m) == (
        2 * 14208 + 2 * 12032 + 18432 + 3 * 7168)
    assert (fl.sparse_layers(m), fl.index_layers(m)) == (3, 2)
    # queries 0..9 under top-4: 1+2+3+4 then 6 x 4
    assert fl.selected_pairs(10, 4) == 34
    assert fl.selected_pairs(10, 4, start=8) == 8
    assert fl.visible_pairs(10, start=8) == 19
    # one token at position 9 (10 visible keys), top-12: all 10
    flops, nbytes = fl.decode_step_work(m, [9], 2, 2, "bfloat16", "bfloat16")
    assert flops == (
        2 * (fl.token_params(m) + 64 * 256) + 2 * 6144 * 2
        + 2 * 4 * 36 * 10 * 4 + 2 * 2 * 16 * 10 * 2)
    assert nbytes == 2 * (
        fl.token_params(m) + 64 * 256 + 9 * 64 + 2 * 6144
        + 24 * 10 * 4 + 16 * 10 * 2)
    # the decode kernel's own work: 10 keys x 4 layers, rows of 24
    f, b = fl.latent_attention_work(m, [9])
    assert (f, b) == (2 * 4 * (24 + 16) * 10 * 4,
                      4 * (2 * 24 * 10 + 2 * 2 * 1 * 4 * 24))
    f, b = fl.grouped_matmul_work(m, 5, 2)
    assert (f, b) == (2 * 6144 * 5, 2 * (2 * 6144 + 5 * (128 + 96)))
    # a prompt of 10 tokens, the expectation of 4/16 local
    assert fl.forward_flops(m, 10) == (
        2 * fl.token_params(m) * 10 + 2 * 6144 * 10 * 3 * 0.25 * 3
        + 2 * 4 * 36 * 55 * 4 + 2 * 2 * 16 * 55 * 2 + 2 * 64 * 256)


def test_the_cell_rehearses_on_the_cpu_with_its_own_tiny_sizes():
    proc, result = rehearse(ROOT, CELL, TINY_CELL, trace=0, seed=2 ** 31 + 9)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert result["correct"] is True and result["failed"] == 0
    assert "not a measurement" in result["rehearsal"]
    assert set(result["metrics"]) == {"serve_tok_s", "setup_s"}
    assert result["tokens_compared"] > 0
    assert "check served_gap_mean" in proc.stderr


def test_the_rehearsed_cell_under_the_planted_fault_is_not_correct():
    # bf16 serving at tiny widths reads gaps of a few hundredths; the
    # fault is far outside the cell's limits all the same
    proc, result = rehearse(
        ROOT, CELL, dict(TINY_CELL, fault="recent_keys_only"), seed=5)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert result["correct"] is False


def test_a_traced_rehearsal_leaves_out_what_it_cannot_read():
    proc, result = rehearse(ROOT, CELL, TINY_CELL, trace=1, seed=17)
    assert proc.returncode == 0, proc.stderr[-3000:]
    mine = {m["name"] for m in bench()["per_layer"]
            if CELL in m.get("workloads", [CELL])}
    # no device plane on the CPU: every device and ring reader is silent
    assert set(result["metrics"]) <= mine


class FakePlan:
    clients = 2

    def __init__(self):
        # the first request ends with its sixth chunk of 4 steps
        self.budgets = iter([25] + [64] * 8)

    def next_request(self, client):
        return np.zeros(40, np.int32), next(self.budgets)


def drive(schedule, warm_in_s=4.0, seconds=10.0, profile=None):
    """A ``HeartbeatSource`` pulled at the ``(time, chunks done,
    requests finished)`` moments of ``schedule``, with the engine's
    ``stats`` as they would read then; ``(source, stats, what each
    pull gave)``."""
    from benchmarks.runners.common import CompileMeter

    stats = {"chunks": 0, "chunk_size": 4, "admitted": 0, "done_at": {},
             "prefill_wall_sec": 0.0, "decode_wall_sec": 0.0,
             "kv_bank_tokens": 3 * 96}
    source = runner.HeartbeatSource(
        FakePlan(), stats, seconds, warm_in_s, profile, CompileMeter(),
        lambda name: contextlib.nullcontext())
    gave = []
    for now, chunks, finished in schedule:
        source.clock = lambda now=now: now
        stats["chunks"] = chunks
        for idx in finished:
            stats["done_at"][idx] = now
        try:
            row = next(source)
        except StopIteration:
            gave.append("stop")
            break
        gave.append(row)
        stats["admitted"] += row is not None
    return source, stats, gave


def test_the_window_is_planned_and_an_edge_cuts_a_chunk_in_proportion():
    # two callers admitted at 0 s (the two pulls of one pass, then the
    # heartbeat); a chunk of 4 steps a second; between the pulls at 6
    # and 10 s the chip first runs a prefill (request 0 ended at 6, its
    # caller sends the next), then ONE chunk in the last second
    schedule = [(0.0, 0, []), (0.001, 0, []), (0.002, 0, [])]
    schedule += [(float(t), t, []) for t in range(1, 6)]
    schedule += [(6.0, 6, [0]), (6.001, 6, []), (10.0, 7, [])]
    schedule += [(10.0 + t, 7 + t, []) for t in range(1, 6)]
    source, stats, gave = drive(schedule, warm_in_s=4.5, seconds=9.0)
    assert [g is None for g in gave[:4]] == [False, False, True, True]
    assert gave[-1] == "stop" and gave[8] is not None  # sent at 6.0
    assert (source.planned_open, source.planned_close) == (4.5, 13.5)
    # the snapshots wait for a pull: 5.0 and 14.0
    assert (source.t_open, source.t_close) == (5.0, 14.0)
    times, tokens, chunk_s = source.generated_curve()
    assert chunk_s == 1.0
    # 2 requests x 4 tokens a chunk; half of chunk 5 lies inside
    assert source.tokens_between(4.5, 5.0) == pytest.approx(4.0)
    assert source.tokens_between(5.0, 6.0) == pytest.approx(8.0)
    # the prefill's stall gives nothing, whichever edge falls in it;
    # the request's first token counts where it was sent
    assert source.tokens_between(6.5, 9.0) == pytest.approx(0.0)
    # the chunk after it, the last second: 2 x 4 tokens
    assert source.tokens_between(9.0, 9.5) == pytest.approx(4.0)
    assert source.tokens_between(6.002, 10.0) == pytest.approx(8.0)
    whole = source.tokens_between(source.planned_open, source.planned_close)
    # 0.5 chunk, chunk 6, the first token, chunk 7, 3.5 chunks after
    assert whole == pytest.approx(4 + 8 + 1 + 8 + 28)
    # the deadline every request carries is the planned close
    assert gave[8]["deadline_sec"] == pytest.approx(13.5 - 6.0)


def test_a_warm_in_too_short_for_the_admissions_raises():
    with pytest.raises(RuntimeError, match="1 of 2 callers admitted"):
        source, stats, _ = drive([(0.0, 0, [])], warm_in_s=1.0)
        source.clock = lambda: 1.5
        next(source)


def cell_entries():
    return [m for m in bench()["per_layer"]
            if CELL in m.get("workloads", [CELL])]


def test_every_reader_of_the_cell_reads_a_recorded_trace(trace, ring):
    # the CPU gives a rehearsal no device plane, so the readers meet
    # the small recorded trace here, under the runner's own counters
    # and the spans the engine records for this cell: each of the
    # cell's metrics, joined or new, has to come back with a number
    import copy

    from benchmarks import peaks
    from benchmarks.runners import common

    schedule = [(0.0, 0, []), (0.001, 0, []), (0.002, 0, [])]
    schedule += [(float(t), t, []) for t in range(1, 9)]
    source, stats, _ = drive(schedule, warm_in_s=2.0, seconds=4.0)
    source.trace_positions = [40 + 9, 40 + 9]
    counters = runner.trace_counters(
        source, stats, 3, 1.5, source.t_close - source.t_open, 32)
    assert counters["chunks"] == 4 and counters["bank_len"] == 96
    trace = copy.deepcopy(trace)
    ops = next(line["events"] for plane in trace["planes"]
               for line in plane["lines"]
               if line["name"] == "XLA Ops")
    # two operations of every chunk program stand for the kernels
    for n, ev in enumerate(e for e in ops if e[0].startswith("fusion")):
        ev[0] = ("grouped_matmul.%d[tpu_custom_call]" if n % 2
                 else "latent_decode_attention.%d[tpu_custom_call]") % n
    for k, (pull, chunk) in enumerate([
            ((48.50, 51.30), 50.43), ((57.84, 59.97), 58.99),
            ((66.53, 68.71), 67.69)]):
        ring("engine.pull", *pull, trace="engine", chunk=7 + k)
        ring("engine.chunk", chunk, chunk + 0.42, trace="engine",
             chunk=7 + k, live=2, slots=3, attn_read_tokens=6 * 128 + 2 * 96,
             attn_context_tokens=6 * 50, moe_assignments=4 * 2 * 8 * 5,
             moe_local_assignments=20, moe_experts_hit=12)
        ring("engine.chunk.wait", chunk + 0.02, chunk + 0.40,
             trace="engine", chunk=7 + k)
    ring("queue_wait", 58.0, 58.4, trace="req")
    with open(os.path.join(
            ROOT, "benchmarks/configs/glm-5.2.serve-ep16.json")) as f:
        cfg = json.load(f)
    cell = {"config": cfg, "traffic": {}, "chips": 1,
            "peaks": peaks.peaks_for("TPU v5 lite")}
    got = common.per_layer_metrics(cell_entries(), trace, counters, cell)
    assert set(got) == {m["name"] for m in cell_entries()}
    assert len(got) == 13  # seven joined, six of this cell alone
    assert all(np.isfinite(v["value"]) for v in got.values())
    # 4 chunks of 0.361 ms in a window of 4 s
    assert got["prefill_device_share.serve"]["value"] == pytest.approx(
        100.0 * (1 - 4 * 0.000361 / 4.0), rel=1e-4)
    assert got["attn_read_share.serve"]["value"] == pytest.approx(320.0)
    assert got["slot_occupancy.serve"]["value"] == pytest.approx(200 / 3)


def test_the_new_entries_only_add():
    b = bench()
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert CELL in next(m for m in b["end_to_end"]
                        if m["name"] == "serve_tok_s")["workloads"]
    for name in ("mfu.serve", "decode_roofline.serve", "kv_read_share.serve"):
        assert CELL not in next(
            m for m in b["per_layer"] if m["name"] == name)["workloads"]
    for name in ("mfu.serve.mla-moe", "decode_roofline.serve.mla-moe",
                 "attn_read_share.serve", "grouped_matmul_roofline.serve",
                 "latent_decode_attention_roofline.serve",
                 "prefill_device_share.serve"):
        entry = next(m for m in b["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks/metrics", name + ".py"))
