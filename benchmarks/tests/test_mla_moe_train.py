"""The latent-attention / sigmoid-routed configuration that is
TRAINED: its file against the catalog, its traffic file, the operation
and byte counts on hand-counted shapes, the new readers on a small
recorded-style trace, and the cell rehearsed end to end through
``run.main`` with its own tiny sizes — as it is, and under each planted
fault (``faults_mla_moe_train.py``).  The program against the reference
leaf by leaf is ``tests/test_mla_moe_train.py``."""

import json
import os

import pytest

from benchmarks import flops_mla_moe_train as fl
from benchmarks import weights_mla_moe_train as weights
from benchmarks.runners import common
from benchmarks.tests import faults_mla_moe_train as faults
from benchmarks.tests.conftest import ROOT
from benchmarks.tests.test_run_e2e import bench, rehearse

CELL = "moonlight-train-ep8"
CONFIG = "benchmarks/configs/moonlight-16b-a3b.train-ep8.json"

#: a key-for-key miniature of the published configuration, in float32
#: so that the CPU run's ``correct`` says something
TINY_CELL = dict(
    config=dict(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
        n_routed_experts=4, num_experts_per_tok=3,
        expert_share={"first": 4, "held": 4, "of": 16}, vocab_size=256,
        num_hidden_layers=3, max_position_embeddings=128,
        dtype="float32"),
    traffic=dict(
        seq_len=128,
        documents={"dist": "lognormal", "median": 30, "sigma": 1.0,
                   "lo": 4, "hi": 128}),
)


def config():
    with open(os.path.join(ROOT, CONFIG)) as f:
        return json.load(f)


def test_the_configuration_keeps_every_published_width():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Moonlight-16B-A3B")
    cfg = config()
    differ = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differ == set(cfg["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert cfg["source"] == row["source_url"]
    assert cfg["published"] == {k: row["config"][k] for k in cfg["reduced"]}
    for key in ("assumed", "deployment", "expert_share", "vocab_share"):
        assert key in cfg
    assert set(cfg["assumed"]) >= {
        "correction_bias", "balance_loss", "rope", "weights", "optimizer"}
    s = weights.sizes(cfg)
    assert (s["held"], s["experts"], s["k"], s["rq"]) == (8, 64, 6, 0)
    # the issue's reckoning: 668.9 M parameters, 10.70 GB at 16 bytes
    assert weights.total_params(cfg) == 668890432
    assert cfg["program"]["mesh"] == {"data": 1}


def test_the_traffic_file_is_the_issue_s():
    with open(os.path.join(
            ROOT, "benchmarks/traffic/packed-8k-feed-2row.json")) as f:
        mix = json.load(f)
    packing = mix.pop("packing")
    assert "last document cut" in packing
    assert mix == {
        "loop": "train_feed", "rows_per_step": 2, "seq_len": 8192,
        "documents": {"dist": "lognormal", "median": 600, "sigma": 1.2,
                      "lo": 16, "hi": 8192},
        "bos_id": 1, "check_steps": 3,
    }


def test_counts_on_hand_counted_shapes():
    cfg = config()
    # the issue's reckoning of one layer, in parameters
    assert fl.attention_params(cfg) == (
        2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048)
    assert fl.expert_params(cfg) == 3 * 2048 * 1408
    assert fl.ffn_params(cfg, "dense") == 3 * 2048 * 11264
    assert fl.ffn_params(cfg, "sparse") == 2048 * 64 + 2 * 3 * 2048 * 1408
    assert fl.kinds(cfg) == ["dense"] + ["sparse"] * 5
    assert fl.expected_local(cfg, 16384) == 16384 * 6 / 8 * 5
    m = dict(TINY_CELL["config"], q_lora_rank=None, n_shared_experts=2,
             first_k_dense_replace=1)
    # attention: 64*4*24 + 64*24 + 16*4*32 + 4*16*64
    assert fl.attention_params(m) == 6144 + 1536 + 2048 + 4096
    assert fl.token_params(m) == (
        3 * 13824 + 3 * 64 * 96 + 2 * (64 * 16 + 2 * 6144) + 64 * 256)
    # 2 rows of 10 tokens, 7 local assignments
    fwd = fl.forward_flops(m, 2, 10, 7)
    assert fwd == (2 * fl.token_params(m) * 20 + 2 * 6144 * 7
                   + 2 * 4 * (24 + 16) * 55 * 2 * 3)
    assert fl.step_flops(m, 2, 10, 7) == 3 * fwd
    f, b = fl.flash_work(m, 2, 10)
    assert f == 3 * 6 * (24 + 16) * 55 * 2 * 4
    assert b == 3 * 2 * (6 * 20 * 4 * 24 + 6 * 20 * 4 * 16)
    f, b = fl.grouped_matmul_work(m, 7, 3)
    assert (f, b) == (3 * 2 * 6144 * 7,
                      2 * 3 * (3 * 6144 + 7 * (128 + 96)))


def _trace(step_s, flash_s, gmm_s):
    ops = [["attn._flash_span.%d[tpu_custom_call]" % i, 10.0 * i,
            flash_s * 1e9 / 3] for i in range(3)]
    ops += [[name + ".1[tpu_custom_call]", 100.0, gmm_s * 1e9 / 3]
            for name in ("grouped_matmul", "grouped_matmul_dx",
                         "grouped_matmul_dw")]
    ops.append(["fusion.7", 200.0, 1e6])
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules",
         "events": [["jit_train_step(123)", 0.0, step_s * 1e9]]},
    ]}]}


def test_the_new_readers_on_a_small_trace():
    cfg = config()
    peaks = {"flops_per_s": {"bfloat16": 197e12}, "hbm_bytes_per_s": 819e9}
    cell = {"config": cfg, "chips": 1, "peaks": peaks}
    counters = {"window_s": 2.0, "steps": 2, "rows_per_step": 2,
                "seq_len": 8192, "moe_local_assignments": 2 * 61440,
                "moe_experts_hit": 2 * 320, "moe_rows_multiplied": 2 * 81920}
    trace = _trace(step_s=1.0, flash_s=0.2, gmm_s=0.1)

    def read(name, counters=counters, cell=cell):
        return common.load_reader(name)(trace, counters, cell)

    ops = fl.step_flops(cfg, 2, 8192, 61440)
    assert read("mfu.train.mla-moe") == pytest.approx(
        100 * 2 * ops / (2.0 * 197e12))
    assert 40 < ops / 1e12 < 50    # the issue's ~43 TFLOP a step
    f, b = fl.flash_work(cfg, 2, 8192)
    assert read("flash_roofline.train.mla") == pytest.approx(
        100 * max(f / 197e12, b / 819e9) / 0.2)
    f, b = fl.grouped_matmul_work(cfg, 61440, 320)
    assert read("grouped_matmul_roofline.train") == pytest.approx(
        100 * max(f / 197e12, b / 819e9) / 0.1)
    assert read("moe_tile_fill.train") == pytest.approx(75.0)
    # a program that counts nothing (the parent's), or the CPU: nothing
    bare = {k: v for k, v in counters.items() if not k.startswith("moe_")}
    for name in ("mfu.train.mla-moe", "grouped_matmul_roofline.train",
                 "moe_tile_fill.train"):
        assert read(name, counters=bare) is None
    assert read("flash_roofline.train.mla",
                cell=dict(cell, peaks=None)) is None


def test_the_cell_rehearses_on_the_cpu_with_its_own_tiny_sizes():
    proc, result = rehearse(ROOT, CELL, TINY_CELL, trace=0, seed=2 ** 31 + 9)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result["correct"] is True and result["failed"] == 0
    assert "not a measurement" in result["rehearsal"]
    assert set(result["metrics"]) == {"train_tok_s", "setup_s"}
    # float32: the program's count of local assignments IS the
    # reference's, and the bias has not moved by a bit
    checks = result["checks"]
    assert checks["local_assignments_gap"] == {"value": 0.0, "limit": 0.0}
    assert checks["router_bias_moved"] == {"value": 0.0, "limit": 0.0}
    assert result["detail"]["local_assignments"] == (
        result["detail"]["reference_local_assignments"])
    assert result["moe_rows_multiplied_per_step"] >= (
        result["moe_local_assignments_per_step"]) > 0


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_the_rehearsed_cell_under_a_planted_fault_is_not_correct(fault):
    proc, result = rehearse(
        ROOT, CELL, dict(TINY_CELL, fault=fault), trace=0, seed=11)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["checks"].values())


def test_a_traced_rehearsal_leaves_out_what_it_cannot_read():
    proc, result = rehearse(ROOT, CELL, TINY_CELL, trace=1, seed=17)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result["correct"] is True
    # no device plane on the CPU: the device readers find nothing; the
    # counters' own reader does
    assert set(result["metrics"]) <= {
        m["name"] for m in bench()["per_layer"]
        if CELL in m.get("workloads", [])}
    assert "moe_tile_fill.train" in result["metrics"]
    assert "flash_roofline.train.mla" not in result["metrics"]


def test_the_new_entries_only_add():
    b = bench()
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    assert cell == b["workloads"][-1]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert cell["traffic"] == "packed-8k-feed-2row"
    assert CELL in next(m for m in b["end_to_end"]
                        if m["name"] == "train_tok_s")["workloads"]
    for name in ("mfu.train", "flash_roofline.train"):
        assert CELL not in next(
            m for m in b["per_layer"] if m["name"] == name)["workloads"]
    listed = [m["name"] for m in b["per_layer"]
              if CELL in m.get("workloads", [])]
    assert len(listed) == 11
    new = ["mfu.train.mla-moe", "flash_roofline.train.mla",
           "grouped_matmul_roofline.train", "moe_tile_fill.train"]
    assert [m["name"] for m in b["per_layer"][-4:]] == new
    for name in new:
        entry = next(m for m in b["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "train_tok_s"
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks/metrics", name + ".py"))
