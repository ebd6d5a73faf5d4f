"""The window-and-full-attention / softmax-routed-experts
configuration: the file against the catalog, the traffic file against
the issue, the operation and byte counts on hand-counted shapes, each
reader of the cell on a recorded trace (a number) and on a parent's
(nothing), the low-precision control outside the limits, and the cell
rehearsed end to end through ``run.main`` with its own tiny sizes —
clean, traced, and under each planted fault."""

import copy
import json
import math
import os

import jax
import numpy as np
import pytest

from benchmarks import flops_swa_moe as fl
from benchmarks import weights_swa_moe as weights
from benchmarks.runners import serve_swa_moe as runner
from benchmarks.tests import faults_swa_moe
from benchmarks.tests.conftest import ROOT
from benchmarks.tests.test_glm_dsa_moe import drive
from benchmarks.tests.test_run_e2e import bench, rehearse
from benchmarks.tests.test_span_readers import ring, trace  # noqa: F401

CELL = "mellum2-ide-mixed-decode"
CONFIG = "benchmarks/configs/mellum2-12b-a2.5b.serve.json"

#: a key-for-key miniature of the published configuration: 4 layers
#: (sliding x 3, full), a window of 8, 8 experts of which a token takes
#: 2, YaRN with an original length the test positions pass
TINY = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, intermediate_size=128, moe_intermediate_size=32,
    num_experts=8, num_experts_per_tok=2, vocab_size=256,
    num_hidden_layers=4,
    layer_types=["sliding_attention"] * 3 + ["full_attention"],
    mlp_layer_types=["sparse"] * 4, sliding_window=8,
    max_position_embeddings=256,
    rope_parameters={
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 10000.0, "factor": 4,
            "original_max_position_embeddings": 16, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 0.1 * math.log(4) + 1},
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000.0}},
    program={"pad_multiple": 8},
)
#: the rehearsal serves in float32, so that the CPU run's ``correct``
#: says something (bf16 at widths this small reads gaps near 1)
TINY_CELL = dict(
    config=dict(TINY, dtype="float32", cache_dtype="float32"),
    traffic=dict(
        clients=3, warm_in_s=2.0, check_sample=2, requests_per_client=400,
        prompt_tokens={"dist": "loguniform", "lo": 8, "hi": 40},
        answer_tokens={"dist": "loguniform", "lo": 8, "hi": 24},
    ),
    row_multiple=16,
)


def published():
    with open(os.path.join(ROOT, CONFIG)) as f:
        return json.load(f)


def test_the_configuration_keeps_every_published_width():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    cfg = published()
    differ = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differ == set(cfg["reduced"]) == {
        "num_hidden_layers", "layer_types", "mlp_layer_types"}
    assert cfg["source"] == row["source_url"]
    # the cut keeps two whole periods of the published pattern
    assert cfg["layer_types"] == row["config"]["layer_types"][:8]
    assert cfg["mlp_layer_types"] == row["config"]["mlp_layer_types"][:8]
    for key in ("published", "assumed", "deployment"):
        assert key in cfg
    s = weights.sizes(cfg)
    assert (s["experts"], s["k"], s["v"], s["layers"]) == (64, 8, 98304, 8)
    # the count: 417.7 M a layer (ISSUE 36 rounds up), 453 M outside them
    assert fl.attention_params(cfg) == 21_233_664
    assert fl.expert_params(cfg) == 6_193_152
    layer = fl.attention_params(cfg) + 2304 * 64 + 64 * fl.expert_params(cfg)
    assert layer == 417_742_848  # 0.835 GB in bfloat16
    assert 2 * fl.head_params(cfg) == 452_984_832


def test_the_traffic_file_is_the_issue_s():
    with open(os.path.join(
            ROOT, "benchmarks/traffic/ide-mixed-closed.json")) as f:
        mix = json.load(f)
    warm_in = mix.pop("warm_in_s")
    assert mix == {
        "loop": "closed", "clients": 96,
        "prompt_tokens": {"dist": "loguniform", "lo": 512, "hi": 8192},
        "answer_tokens": {"dist": "loguniform", "lo": 256, "hi": 2048},
        "sharing": {"kind": "none"}, "sampling": "greedy",
        "first_wave": "residual", "schedule_seed": 36,
        "requests_per_client": 8, "check_sample": 6,
    }
    # ISSUE 36's 15 s, raised by its rule in steps of 5 until the first
    # wave is admitted before the open (PERF.md section 4 says what it
    # came to)
    assert warm_in >= 15.0 and warm_in % 5 == 0


def test_the_banks_are_sized_for_the_mix_not_for_the_schedule():
    # the schedule's longest answer is 2047 tokens: a bank of 8192 +
    # 2047 rows has no block of the decode kernel that divides it, and
    # the full layers' step would read it whole under a mask (the
    # cell's first chip run did: PERF.md section 6)
    from benchmarks import traffic
    from tensorflowonspark_tpu.ops.paged_attention import bank_block

    with open(os.path.join(
            ROOT, "benchmarks/traffic/ide-mixed-closed.json")) as f:
        mix = json.load(f)
    plan = traffic.ClosedLoop(mix, 1, 98304)
    assert int(plan.answer_len.max()) < 2048
    pc = runner.program_config(published(), plan, mix)
    assert (pc["max_prompt_len"], pc["max_new_tokens"]) == (8192, 2048)
    assert pc["pad_multiple"] == 1024
    assert bank_block(8192 + 2048, 128, "bfloat16") == 256
    assert bank_block(8192 + int(plan.answer_len.max()), 128,
                      "bfloat16") is None


def test_counts_on_hand_counted_shapes():
    m = TINY
    assert fl.windows(m) == [8, 8, 8, 0]
    # q and out 64*4*16 each, k and v 64*2*16 each
    assert fl.attention_params(m) == 2 * 4096 + 2 * 2048
    assert fl.expert_params(m) == 3 * 64 * 32
    assert fl.token_params(m) == 4 * (12288 + 64 * 8)
    assert (fl.seen_keys(3, 8), fl.seen_keys(20, 8), fl.seen_keys(20, 0)) == (
        4, 8, 21)
    # queries 0..9 under a window of 4: 1+2+3+4 then 6 x 4
    assert fl.seen_pairs(10, 4) == 34
    assert fl.seen_pairs(10, 4, start=8) == 8
    assert fl.seen_pairs(10, 0, start=8) == 19
    # one token at position 19: 8 keys on three layers, 20 on the full
    f, b = fl.bank_attention_work(m, [19], "bfloat16", "bfloat16")
    assert f == 4 * 4 * 16 * (3 * 8 + 20)
    assert b == 2 * 2 * 16 * 2 * 44 + 2 * 2 * 1 * 4 * 16 * 4
    flops, nbytes = fl.decode_step_work(m, [19], 8, 7, "bfloat16", "bfloat16")
    assert flops == (2 * (fl.token_params(m) + 64 * 256) + 2 * 6144 * 8
                     + 4 * 4 * 16 * 44)
    assert nbytes == 2 * (
        fl.token_params(m) + 64 * 256 + 9 * 64 + 8 * 16 + 7 * 6144
    ) + 128 * 44
    f, b = fl.grouped_matmul_work(m, 8, 7)
    assert (f, b) == (2 * 6144 * 8, 2 * (7 * 6144 + 8 * (128 + 96)))
    # a prompt of 10 tokens: window pairs 52 (1..8, 8, 8) x 3, all 55 x 1
    assert fl.forward_flops(m, 10) == (
        2 * fl.token_params(m) * 10 + 2 * 6144 * 10 * 2 * 4
        + 4 * 4 * 16 * (3 * 52 + 55) + 2 * 64 * 256)
    f, b = fl.prefill_work(m, 10)
    assert f == fl.forward_flops(m, 10)
    assert b == fl.weight_bytes(m, 32) + 10 * 4 * (2 * 64 * 2 + 128)


def test_the_control_lies_outside_what_the_program_reads():
    # float32 serving at tiny widths: the served tokens sit on the
    # reference's best, the int8 control's first choices do not, and
    # the reference at the program's own precision reads between
    from benchmarks.tests.test_glm_dsa_moe import prompts_for
    from tensorflowonspark_tpu.models import transformer as tr

    cfg = dict(published(), **TINY_CELL["config"])
    params = weights.make_params(cfg, 5, "float32")

    class Plan:
        answer_len = np.array([16])
        prompt_len = np.array([48])

    pc = runner.program_config(cfg, Plan)
    for k in ("mode", "max_new_tokens", "max_prompt_len", "pad_multiple"):
        pc.pop(k)
    with jax.default_matmul_precision("highest"):
        dec = tr.SlotDecoder(
            tr.Transformer(tr.TransformerConfig(**pc)), params, 2, 16,
            cache_len=64, chunk_size=4, pad_multiple=8)
        prompts = prompts_for(7, [31, 22])
        rows = [[int(dec.admit(i, p))] for i, p in enumerate(prompts)]
        for _ in range(3):
            toks, _ = dec.step_chunk()
            for i, row in enumerate(rows):
                row.extend(int(t) for t in toks[i])
        samples = [(p, np.asarray(r, np.int32)) for p, r in zip(prompts, rows)]
        gaps = runner.served_gaps(cfg, 5, samples, "float32", control=True,
                                  row_multiple=16)
    assert gaps["tokens_compared"] == 26
    assert gaps["served_gap_max"] < 1e-4
    assert gaps["control_gap_max"] > gaps["served_gap_max"] * 100
    assert gaps["control_gap_mean"] > gaps["bf16_gap_mean"] >= 0
    assert set(gaps) == {"tokens_compared"} | {
        prefix + k for prefix in ("served_gap_", "control_gap_", "bf16_gap_")
        for k in ("max", "mean", "p95")}


def test_the_sample_is_past_the_wrap_with_a_long_prompt_in_it():
    served = [({"prompt": np.zeros(p, np.int32)}, np.zeros(a, np.int32), ok)
              for p, a, ok in [
                  (600, 300, True), (7000, 1000, True), (5000, 400, True),
                  (900, 900, True), (512, 256, True), (8000, 100, False),
                  (1500, 700, True), (700, 700, True), (3000, 2000, True)]]
    for seed in range(12):
        picks = runner.sample(served, seed, 4, 1280, 4096)
        sizes = [(len(p), len(a)) for p, a in picks]
        assert len(sizes) == 4 and len(set(sizes)) == 4
        assert sizes[0] == (7000, 1000)            # the longest, always
        assert all(p + a > 1280 for p, a in sizes)  # past a ring's wrap
        assert (8000, 100) not in sizes             # cut short: not whole
    # a draw without a long prompt gets one in place of its last
    few = [s for s in served if len(s[0]["prompt"]) != 7000]
    for seed in range(12):
        picks = runner.sample(few, seed, 2, 1280, 4096)
        assert any(len(p) > 4096 for p, _ in picks)


def cell_entries():
    return [m for m in bench()["per_layer"]
            if CELL in m.get("workloads", [CELL])]


def recorded(trace, ring, with_counts=True):  # noqa: F811
    """The small recorded trace as a run of this cell's: two
    operations of every chunk program stand for the kernels, the
    engine's spans carry (or, as on a parent, lack) the new counts."""
    schedule = [(0.0, 0, []), (0.001, 0, []), (0.002, 0, [])]
    schedule += [(float(t), t, []) for t in range(1, 9)]
    source, stats, _ = drive(schedule, warm_in_s=2.0, seconds=4.0)
    source.trace_positions = [40 + 9, 40 + 9]
    counters = runner.trace_counters(
        source, stats, 3, 1.5, source.t_close - source.t_open, 32)
    trace = copy.deepcopy(trace)
    ops = next(line["events"] for plane in trace["planes"]
               for line in plane["lines"] if line["name"] == "XLA Ops")
    for n, ev in enumerate(e for e in ops if e[0].startswith("fusion")):
        ev[0] = ("grouped_matmul.%d[tpu_custom_call]" if n % 2
                 else "block_decode_attention.%d[tpu_custom_call]") % n
    extra = dict(attn_read_tokens=6 * 128 + 2 * 96,
                 attn_context_tokens=8 * 50, moe_assignments=2 * 8 * 8 * 4,
                 moe_local_assignments=2 * 8 * 8 * 4,
                 moe_experts_hit=100) if with_counts else {}
    for k, (pull, chunk) in enumerate([
            ((48.50, 51.30), 50.43), ((57.84, 59.97), 58.99),
            ((66.53, 68.71), 67.69)]):
        ring("engine.pull", *pull, trace="engine", chunk=7 + k)
        ring("engine.chunk", chunk, chunk + 0.42, trace="engine",
             chunk=7 + k, live=2, slots=3, **extra)
        ring("engine.chunk.wait", chunk + 0.02, chunk + 0.40,
             trace="engine", chunk=7 + k)
    ring("queue_wait", 58.0, 58.4, trace="req")
    if with_counts:
        counters["kv_bank_bytes"] = {
            "ring": 6 * 1280, "whole": 2 * 10240, "unringed": 8 * 10240}
    return trace, counters


def test_every_reader_of_the_cell_reads_a_recorded_trace(trace, ring):  # noqa: F811
    from benchmarks import peaks
    from benchmarks.runners import common

    trace, counters = recorded(trace, ring)
    cell = {"config": published(), "traffic": {}, "chips": 1,
            "peaks": peaks.peaks_for("TPU v5 lite")}
    got = common.per_layer_metrics(cell_entries(), trace, counters, cell)
    assert set(got) == {m["name"] for m in cell_entries()}
    assert len(got) == 14  # nine joined, five of this cell alone
    assert all(np.isfinite(v["value"]) for v in got.values())
    assert got["kv_resident_share.serve"]["value"] == pytest.approx(
        100 * (6 * 1280 + 2 * 10240) / (8 * 10240))
    assert got["attn_read_share.serve"]["value"] == pytest.approx(240.0)
    for name in got:
        if "roofline" in name or "mfu" in name:
            assert 0 < got[name]["value"]


def test_on_a_parent_s_spans_the_new_readers_return_nothing(trace, ring):  # noqa: F811
    # a program without the counts and the gauges (the benchmark's
    # files laid over the parent commit): every new reader that needs
    # them is silent, none raises
    from benchmarks import peaks
    from benchmarks.runners import common

    trace, counters = recorded(trace, ring, with_counts=False)
    cell = {"config": published(), "traffic": {}, "chips": 1,
            "peaks": peaks.peaks_for("TPU v5 lite")}
    new = [m for m in cell_entries() if m["workloads"] == [CELL]]
    assert len(new) == 5
    got = common.per_layer_metrics(new, trace, counters, cell)
    assert set(got) == {"mfu.serve.swa-moe",
                        "bank_attention_roofline.serve.swa-moe"}


def test_the_new_entries_only_add():
    b = bench()
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert cell["traffic"] == "ide-mixed-closed"
    assert CELL in next(m for m in b["end_to_end"]
                        if m["name"] == "serve_tok_s")["workloads"]
    for name in ("mfu.serve", "decode_roofline.serve", "kv_read_share.serve",
                 "mfu.serve.mla-moe", "grouped_matmul_roofline.serve"):
        assert CELL not in next(
            m for m in b["per_layer"] if m["name"] == name)["workloads"]
    for name in ("mfu.serve.swa-moe", "decode_roofline.serve.swa-moe",
                 "bank_attention_roofline.serve.swa-moe",
                 "grouped_matmul_roofline.serve.swa-moe",
                 "kv_resident_share.serve"):
        entry = next(m for m in b["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "serve_tok_s"
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks/metrics", name + ".py"))


def test_the_cell_rehearses_on_the_cpu_with_its_own_tiny_sizes():
    proc, result = rehearse(ROOT, CELL, TINY_CELL, trace=0, seed=2 ** 31 + 9)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert result["correct"] is True and result["failed"] == 0
    assert "not a measurement" in result["rehearsal"]
    assert set(result["metrics"]) == {"serve_tok_s", "setup_s"}
    assert result["tokens_compared"] > 0 and result["ring_rows"] == 8
    assert result["moe_local_assignments_per_step"] == (
        result["moe_assignments_per_step"])
    assert "check served_gap_mean" in proc.stderr


def test_a_traced_rehearsal_leaves_out_what_it_cannot_read():
    proc, result = rehearse(ROOT, CELL, TINY_CELL, trace=1, seed=17)
    assert proc.returncode == 0, proc.stderr[-3000:]
    mine = {m["name"] for m in cell_entries()}
    # no device plane on the CPU: the device readers are silent; the
    # gauges' reader is not
    assert set(result["metrics"]) <= mine
    assert result["metrics"]["kv_resident_share.serve"]["value"] == (
        pytest.approx(100 * (3 * 8 + 64) / (4 * 64)))


@pytest.mark.parametrize("fault", sorted(faults_swa_moe.FAULTS))
def test_the_rehearsed_cell_under_a_planted_fault_is_not_correct(fault):
    proc, result = rehearse(
        ROOT, CELL, dict(TINY_CELL, fault=fault), seed=5)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["checks"].values())
