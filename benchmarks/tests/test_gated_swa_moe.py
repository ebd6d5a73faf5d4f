"""The gated window-and-full-attention / softmax-routed-experts
configuration with a shared expert: the file against the catalog, the
traffic file against the cell's definition, the operation and byte
counts on hand-counted shapes, each reader of the cell on a recorded
trace (a number) and on a parent's (nothing), the low-precision
control outside the limits, and the cell rehearsed end to end through
``run.main`` with its own tiny sizes — clean, traced, and under each
planted fault."""

import json
import math
import os

import numpy as np
import pytest

from benchmarks import flops_gated_swa_moe as fl
from benchmarks import weights_gated_swa_moe as weights
from benchmarks.runners import serve_gated_swa_moe as runner
from benchmarks.tests import faults_gated_swa_moe
from benchmarks.tests.conftest import ROOT
from benchmarks.tests.test_run_e2e import bench, rehearse
from benchmarks.tests.test_span_readers import ring, trace  # noqa: F401
from benchmarks.tests.test_swa_moe import recorded

CELL = "laguna-agent-repo-decode"
CONFIG = "benchmarks/configs/laguna-s-2.1.serve-ep8.json"
MIX = "benchmarks/traffic/agent-repo-closed.json"
NEW = ("mfu.serve.gated-swa-moe", "decode_roofline.serve.gated-swa-moe",
       "bank_attention_roofline.serve.gated-swa-moe",
       "prompt_attention_roofline.serve.gated-swa-moe")

#: a key-for-key miniature of the published configuration: 5 layers
#: (full of 6 heads and dense, sliding of 9 x 3, full of 6) over 3
#: key/value heads, a window of 8, experts 2-5 held of 8 of which a
#: token takes 3 and a shared one, half-rotary YaRN with an original
#: length the test positions pass
TINY = dict(
    hidden_size=64, num_attention_heads=6,
    num_attention_heads_per_layer=[6, 9, 9, 9, 6], num_key_value_heads=3,
    head_dim=16, intermediate_size=128, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, num_experts=4,
    expert_share={"first": 2, "held": 4, "of": 8}, num_experts_per_tok=3,
    vocab_size=256, sliding_window=8, max_position_embeddings=256,
    rope_parameters={
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 10000.0, "factor": 4,
            "original_max_position_embeddings": 16, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 0.1 * math.log(4) + 1,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000.0,
            "partial_rotary_factor": 1}},
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
    cfg = published()
    assert set(cfg["reduced"]) == set(cfg["published"]) == {
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "gating_types", "num_attention_heads_per_layer", "num_experts",
        "vocab_size"}
    assert cfg["source"] == (
        "https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json")
    # the published widths, as the source has them
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["sliding_window"]) == (
                3072, 128, 8, 12288, 1024, 1024, 10, 512)
    # the cut keeps the first 5 of the published pattern: (full,
    # sliding x 3) x 12 at (48, 72, 72, 72) heads, layer 0 dense
    assert cfg["layer_types"] == ["full_attention"] + [
        "sliding_attention"] * 3 + ["full_attention"]
    assert cfg["num_attention_heads_per_layer"] == [48, 72, 72, 72, 48]
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert cfg["gating_types"] == ["per_head"] * 5
    for key in ("published", "assumed", "deployment", "parameters"):
        assert key in cfg
    assert cfg["published"]["num_experts"] == 256
    assert cfg["published"]["vocab_size"] == 100352
    assert cfg["expert_share"] == {"first": 0, "held": 32, "of": 256}
    assert cfg["vocab_share"]["rows"] * 8 == 100352
    # the count: 1.717 B parameters, 3.43 GB in bf16
    p = cfg["parameters"]
    assert fl.attention_params(cfg, 0) == 44_187_648
    assert fl.attention_params(cfg, 1) == 63_135_744
    assert p["total"] == 1_716_988_160
    total = sum(
        fl.attention_params(cfg, i) + fl.ffn_params(cfg, i)
        + 2 * 3072 + 2 * 128
        + (32 * fl.expert_params(cfg) if i else 0) for i in range(5))
    assert total + 2 * fl.head_params(cfg) + 3072 == p["total"]


def test_the_traffic_file_is_the_cell_s_definition():
    with open(os.path.join(ROOT, MIX)) as f:
        mix = json.load(f)
    warm_in = mix.pop("warm_in_s")
    assert mix == {
        "loop": "closed", "clients": 48,
        "prompt_tokens": {"dist": "loguniform", "lo": 2048, "hi": 16384},
        "answer_tokens": {"dist": "loguniform", "lo": 512, "hi": 4096},
        "sharing": {"kind": "none"}, "sampling": "greedy",
        "first_wave": "residual", "schedule_seed": 41,
        "requests_per_client": 16, "check_sample": 6,
    }
    # 15 s, raised in steps of 5 until every caller is admitted before
    # the open (PERF.md section 4)
    assert warm_in >= 15.0 and warm_in % 5 == 0


def test_the_banks_are_sized_for_the_mix():
    from benchmarks import traffic
    from tensorflowonspark_tpu.ops.paged_attention import bank_block

    with open(os.path.join(ROOT, MIX)) as f:
        mix = json.load(f)
    plan = traffic.ClosedLoop(mix, 1, 12544)
    pc = runner.program_config(published(), plan, mix)
    assert (pc["max_prompt_len"], pc["max_new_tokens"]) == (16384, 4096)
    assert pc["pad_multiple"] == 2048
    assert bank_block(16384 + 4096, 128, "bfloat16") == 256
    assert (pc["num_experts"], pc["router_experts"], pc["expert_first"],
            pc["shared_experts"], pc["routed_scaling"]) == (
                32, 256, 0, 1, 2.5)


def test_counts_on_hand_counted_shapes():
    m = dict(published(), **TINY)
    assert fl.windows(m) == [0, 8, 8, 8, 0]
    assert fl.layer_heads(m) == [6, 9, 9, 9, 6]
    # q and out 64*h*16 each, k and v 64*3*16 each, the gate 64*h
    assert fl.attention_params(m, 0) == 2 * 64 * 6 * 16 + 2 * 3072 + 64 * 6
    assert fl.attention_params(m, 1) == 2 * 64 * 9 * 16 + 2 * 3072 + 64 * 9
    assert fl.ffn_params(m, 0) == 3 * 64 * 128
    assert fl.ffn_params(m, 1) == 64 * 8 + 3 * 64 * 32
    assert fl.local_share(m) == 3 * 4 / 8
    # one token at position 19: 8 keys on three layers of 9 heads, 20
    # on two of 6
    f, b = fl.bank_attention_work(m, [19])
    assert f == 4 * 16 * (3 * 9 * 8 + 2 * 6 * 20)
    assert b == 2 * 3 * 16 * 2 * (3 * 8 + 2 * 20) + 2 * 2 * 16 * 39
    flops, nbytes = fl.decode_step_work(m, [19], 8, 7)
    assert flops == (2 * (fl.token_params(m) + 64 * 256) + 2 * 6144 * 8
                     + 4 * 16 * (3 * 9 * 8 + 2 * 6 * 20))
    assert nbytes == 2 * (
        fl.token_params(m) + 64 * 256 + 11 * 64 + 10 * 16 + 7 * 6144
    ) + 192 * 64
    # a prompt of 10 tokens: window pairs 52 (1..8, 8, 8), all 55
    assert fl.forward_flops(m, 10) == (
        2 * fl.token_params(m) * 10 + 2 * 6144 * 10 * 1.5 * 4
        + 4 * 16 * (3 * 9 * 52 + 2 * 6 * 55) + 2 * 64 * 256)
    f, b = fl.prompt_attention_work(m, 10, 9, 8)
    assert (f, b) == (4 * 16 * 9 * 52, 2 * 10 * 16 * (18 + 6))


def test_the_control_lies_outside_what_the_program_reads():
    # float32 serving at tiny widths: the served tokens sit on the
    # reference's best, the int8 control's first choices do not, and
    # the reference at the program's own precision reads between
    import jax

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


def cell_entries():
    return [m for m in bench()["per_layer"]
            if CELL in m.get("workloads", [CELL])]


def test_every_reader_of_the_cell_reads_a_recorded_trace(trace, ring):  # noqa: F811
    from benchmarks import peaks
    from benchmarks.runners import common

    trace, counters = recorded(trace, ring)
    cell = {"config": published(), "traffic": {}, "chips": 1,
            "peaks": peaks.peaks_for("TPU v5 lite")}
    got = common.per_layer_metrics(cell_entries(), trace, counters, cell)
    # the prompts' flash events are read from the run's own capture,
    # and set-up from its set-up spans, which a recorded trace does not
    # come with
    assert set(got) == {m["name"] for m in cell_entries()
                        if not m["name"].startswith("setup_")} - {NEW[3]}
    assert len(cell_entries()) == 9 + 5 + 4
    assert all(np.isfinite(v["value"]) for v in got.values())
    for name in got:
        if "roofline" in name or "mfu" in name:
            assert 0 < got[name]["value"]


def test_on_a_parent_s_spans_the_new_readers_return_nothing(trace, ring):  # noqa: F811
    from benchmarks import peaks
    from benchmarks.runners import common

    trace, counters = recorded(trace, ring, with_counts=False)
    cell = {"config": published(), "traffic": {}, "chips": 1,
            "peaks": peaks.peaks_for("TPU v5 lite")}
    new = [m for m in cell_entries() if m["workloads"] == [CELL]]
    assert sorted(m["name"] for m in new) == sorted(NEW)
    got = common.per_layer_metrics(new, trace, counters, cell)
    assert set(got) == {"mfu.serve.gated-swa-moe",
                        "bank_attention_roofline.serve.gated-swa-moe"}


def test_the_new_entries_only_add():
    b = bench()
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert cell["traffic"] == "agent-repo-closed"
    assert CELL in next(m for m in b["end_to_end"]
                        if m["name"] == "serve_tok_s")["workloads"]
    for name in ("mfu.serve.swa-moe", "decode_roofline.serve.swa-moe",
                 "bank_attention_roofline.serve.swa-moe",
                 "grouped_matmul_roofline.serve.swa-moe",
                 "kv_resident_share.serve", "mfu.serve.mla-moe",
                 "decode_roofline.serve.mla-moe",
                 "grouped_matmul_roofline.serve"):
        assert CELL not in next(
            m for m in b["per_layer"] if m["name"] == name)["workloads"]
    for name in NEW:
        entry = next(m for m in b["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "serve_tok_s"
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks/metrics", name + ".py"))


def test_the_program_of_a_parent_is_refused_before_the_device():
    # a TransformerConfig without the new fields: the runner names them
    import dataclasses

    class Old(object):
        @dataclasses.dataclass(frozen=True)
        class TransformerConfig(object):
            layer_types: tuple = ()
            layer_rope: tuple = ()

    Old.dataclasses = dataclasses
    missing = runner.lacks(Old)
    assert "num_attention_heads_per_layer" in missing
    assert "gating" in missing and "rotary_of" in missing
    from tensorflowonspark_tpu.models import transformer as tr

    assert runner.lacks(tr) == []


def test_the_cell_rehearses_on_the_cpu_with_its_own_tiny_sizes():
    proc, result = rehearse(ROOT, CELL, TINY_CELL, trace=0, seed=2 ** 31 + 9)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert result["correct"] is True and result["failed"] == 0
    assert "not a measurement" in result["rehearsal"]
    assert set(result["metrics"]) == {"serve_tok_s", "setup_s"}
    assert result["tokens_compared"] > 0 and result["ring_rows"] == 8
    assert 0 < result["moe_local_assignments_per_step"] < (
        result["moe_assignments_per_step"])
    assert result["all_admitted_s"] is not None
    assert "check served_gap_mean" in proc.stderr


def test_a_traced_rehearsal_leaves_out_what_it_cannot_read():
    proc, result = rehearse(ROOT, CELL, TINY_CELL, trace=1, seed=17)
    assert proc.returncode == 0, proc.stderr[-3000:]
    mine = {m["name"] for m in cell_entries()}
    assert set(result["metrics"]) <= mine
    assert not set(result["metrics"]) & set(NEW[1:])


@pytest.mark.parametrize("fault", sorted(faults_gated_swa_moe.FAULTS))
def test_the_rehearsed_cell_under_a_planted_fault_is_not_correct(fault):
    proc, result = rehearse(
        ROOT, CELL, dict(TINY_CELL, fault=fault), seed=5)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["checks"].values())
