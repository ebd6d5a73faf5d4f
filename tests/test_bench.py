"""bench.py record-emission tests (VERDICT r5 Weak #1 / Next #1).

The driver parses the LAST stdout line through a ~2000-char tail
window; the old single giant record line overflowed it and nulled the
parsed record.  bench.main now writes the FULL record to a file and
prints only a compact summary line — these tests pin the contract:
the line is standalone-parseable JSON, carries exactly the headline
keys, and stays under 1500 chars even for a fully-populated record.
"""

import json

import bench


def _full_record():
    """A representative fully-populated record (values shaped like
    a real run's, including the continuous row)."""
    return {
        "metric": "resnet50_224_train_images_per_sec",
        "value": 2675.11,
        "unit": "images/sec",
        "platform": "tpu",
        "device_kind": "TPU v5 lite",
        "baseline_source": "A100 2500 img/s ResNet50 " + "x" * 120,
        "flops_per_image_gflop": 12.3,
        "tflops_per_sec": 32.9,
        "mfu": 0.167,
        "baseline_img_per_sec": 2536.6,
        "vs_baseline": 1.0546,
        "spark_feed": {
            "queue": {"rows_per_sec": 5664.8, "steps_per_sec": 88.51,
                      "steps": 1280, "feed_wall_sec": 29.96},
            "ring": {"rows_per_sec": 6100.0, "steps_per_sec": 95.31,
                     "steps": 1280, "feed_wall_sec": 27.1,
                     "wire_mb_per_step": 0.0512},
            "ring_f32": {"rows_per_sec": 5100.0, "steps_per_sec": 79.7,
                         "wire_mb_per_step": 0.2016},
            "wire_narrowing": {
                "uint8_wire_mb_per_step": 0.0512,
                "float32_wire_mb_per_step": 0.2016,
                "wire_ratio": 3.94,
                "uint8_vs_float32_rows": 1.2,
            },
            "image_queue": {"rows_per_sec": 612.3, "mb_per_sec": 92.2},
            "image_ring": {"rows_per_sec": 2368.8, "mb_per_sec": 356.6},
            "ring_vs_queue": 1.08,
        },
        "transformer": {
            "metric": "transformer_lm_train_tokens_per_sec",
            "value": 57501.2, "unit": "tokens/sec", "mfu": 0.702,
            "config": {"L": 16, "H": 8, "Dh": 128, "Dm": 1024,
                       "Dff": 4096, "V": 32000, "S": 2048, "B": 8},
            "baseline_source": "A100 at ~50% MFU " + "y" * 80,
            "vs_baseline": 1.51,
        },
        "decode": {"decode_ms_per_step": 1.01,
                   "decode_tokens_per_sec": 7920.8},
        "decode_long": {"bf16_ms_per_step": 3.16,
                        "int8_weights_kv_ms_per_step": 1.85},
        "long_context": {"s8k": {"flash_ms": 6.1}, "s32k": {"flash_ms": 91.7}},
        "serving_generate": {
            "rows_per_sec": 59.77,
            "generated_tokens_per_sec": 3825.0,
            "latency_p50_ms": 540.0,
            "latency_p99_ms": 1062.3,
            "continuous": {
                "rows_per_sec": 78.41,
                "delivered_tokens_per_sec": 3100.2,
                "latency_p50_ms": 310.9,
                "latency_p99_ms": 890.4,
                "slots": 8, "chunk_size": 16, "admitted": 64,
                "chunks": 25, "speedup_vs_static": 1.31,
            },
        },
        "serving_overload": {
            "rows": 48, "slots": 4, "queue_depth": 4,
            "block": {"goodput_rows_s": 9.1, "completed": 48, "shed": 0,
                      "latency_p50_ms": 2600.0, "latency_p99_ms": 5100.0},
            "reject": {"goodput_rows_s": 11.8, "completed": 9, "shed": 39,
                       "latency_p50_ms": 420.0, "latency_p99_ms": 760.0},
            "degrade": {"goodput_rows_s": 21.4, "completed": 48,
                        "degraded": 31, "latency_p50_ms": 900.0,
                        "latency_p99_ms": 2200.0},
        },
        "serving_hotswap": {
            "rows": 24, "slots": 4, "swaps": 1,
            "swap_latency_ms": 41.3, "swap_dropped": 0,
            "swap_requeued": 3, "weight_generation": 1,
            "goodput_rows_s": 18.2, "baseline_rows_s": 19.9,
            "goodput_dip_pct": 8.5,
        },
        "serving_fleet": {
            "slots": 2, "offered": 16, "host_cpus": 1,
            "replicas": {
                "1": {"served": 8, "shed": 8, "served_frac": 0.5,
                      "rows_per_sec": 420.1, "wall_sec": 0.019},
                "2": {"served": 16, "shed": 0, "served_frac": 1.0,
                      "rows_per_sec": 7.5, "wall_sec": 2.13},
                "3": {"served": 16, "shed": 0, "served_frac": 1.0,
                      "rows_per_sec": 5.7, "wall_sec": 2.83},
            },
            "fleet_goodput_2x": 2.0, "fleet_goodput_3x": 2.0,
            "wall_ratio_2x": 0.02, "token_exact": True,
            "affinity": {"affinity_hit_rate": 0.703,
                         "random_hit_rate": 0.594,
                         "shared_frac": 0.8},
            "fleet_affinity_hit_rate": 0.703,
            "deploy": {"state": "done", "replicas_swapped": 3,
                       "served": 206, "deploy_dropped": 0},
        },
        "serving_prefix": {
            "rows": 32, "slots": 8, "prefix_len": 320,
            "cold_rows_per_sec": 33.5,
            "shared80": {"rows_per_sec": 55.3, "hit_rate": 0.781,
                         "prefix_tokens_saved": 8000,
                         "latency_p50_ms": 93.3,
                         "latency_p99_ms": 160.1},
            "shared0": {"rows_per_sec": 29.3, "hit_rate": 0.0},
            "prefix_gain": 1.653, "outputs_match": True,
        },
        "serving_speculative": {
            "batch": 4, "max_new_tokens": 64, "draft_len": 4,
            "plain_tokens_per_sec": 457.5,
            "spec_tokens_per_sec": 382.7,
            "speedup_vs_greedy": 0.837, "accept_rate": 0.918,
            "rounds": 13, "tokens_per_verify": 4.92,
            "token_exact": True,
        },
        "serving_paged": {
            "slots": 4, "max_new_tokens": 16, "prefix_len": 256,
            "decode": {
                "contiguous_tokens_per_sec": 1211.4,
                "paged_kernel_tokens_per_sec": 15.8,
                "paged_gather_tokens_per_sec": 941.5,
                "paged_vs_contiguous": 0.777, "token_exact": True,
            },
            "admit": {"contiguous_ms": 18.45, "paged_ms": 3.98,
                      "n_admits": 12, "shared_prefix_tokens": 256},
            "paged_admit_gain": 4.637,
            "int4": {"tokens_per_sec": 958.6,
                     "int8_tokens_per_sec": 1003.4,
                     "int4_vs_int8": 0.955, "impl": "gather"},
            "pool": {"pool_pages": 253, "pool_pages_used": 17},
        },
        "serving_disagg": {
            "slots": 4, "max_new_tokens": 16, "rows": 24,
            "mix": "1/3 long prompts (96-160 tok) among short (6-18)",
            "unified": {"ttft_p50_ms": 20.4, "ttft_p99_ms": 408.2,
                        "latency_p99_ms": 453.3, "rows_per_sec": 30.3,
                        "prefill_wall_sec": 0.45},
            "disagg": {"ttft_p50_ms": 26.2, "ttft_p99_ms": 409.7,
                       "latency_p99_ms": 454.1, "rows_per_sec": 28.1,
                       "prefill_wall_sec": 0.47},
            "ttft_p50_ms": 26.2, "ttft_p99_ms": 409.7,
            "serving_disagg_p99_gain": 0.996, "token_exact": True,
        },
        "serving_faults": {
            "slots": 2, "max_new_tokens": 12, "rows": 24,
            "kill_prefill": {"clean_rows_per_sec": 96.7,
                             "fault_rows_per_sec": 89.7,
                             "fault_recovery_sec": 0.019,
                             "fault_goodput_dip_pct": 7.24,
                             "token_exact": True,
                             "pool_balanced": True},
            "kill_replica": {"clean_rows_per_sec": 98.8,
                             "fault_rows_per_sec": 95.5,
                             "fault_recovery_sec": 0.009,
                             "fault_goodput_dip_pct": 3.42,
                             "token_exact": True,
                             "redispatch_sec": 0.03,
                             "redispatched": 5},
            "fault_recovery_sec": 0.019,
            "fault_goodput_dip_pct": 7.24, "dropped": 0,
        },
        "serving_tpu": {"mnist": {"rows_per_sec": 643.2},
                        "resnet50": {"rows_per_sec": 51.5,
                                     "wire_mb_per_batch": 38.535},
                        "resnet50_uint8": {"rows_per_sec": 172.0,
                                           "wire_mb_per_batch": 9.634},
                        "uint8_wire_ratio": 4.0,
                        "uint8_vs_float32_rows": 3.34},
        "dataplane": {"batches": 48, "sync_wall_sec": 1.62,
                      "overlap_wall_sec": 1.21, "overlap_gain": 1.34},
        "telemetry_overhead": {
            "train_steps": 160,
            "train_steps_s_instrumented": 114.2,
            "train_steps_s_disabled": 115.6,
            "overhead_pct": 1.21,
            "serving_rows_s_instrumented": 610.4,
            "serving_rows_s_disabled": 618.0,
            "serving_overhead_pct": 1.24,
            "health_overhead_pct": 1.6,
            "alerts_fired": 1,
            "health_scrapes": 34,
            "forensics_overhead_pct": 1.8,
            "serving_forensics_overhead_pct": 1.5,
            "forensics_dumps": 1,
            "journal_events": 42,
            "ledger_overhead_pct": 1.4,
            "usage_top_tenant_share": 0.52,
            "usage_tenants": 4,
            "usage_requests": 24,
            "latency_exemplars": 3,
        },
        "planner": {
            "planner_gap_pct": 4.2, "replan_events": 1,
            "replans": [{"trigger": "dcn_rtt", "knob": "push_every",
                         "old": 8, "new": 25, "applied": True}],
            "workloads": {
                "serving_continuous": {"gap_pct": 4.2,
                                       "identical": False},
                "serving_disagg_mixed": {"gap_pct": 0.0,
                                         "identical": False},
                "train_hier_ps": {"gap_pct": 0.0, "identical": False},
            },
            "profile_source": "roofline", "platform": "cpu",
        },
        "async_ps_tpu": {"async_pipelined_steps_per_sec": 9.4,
                         "async_compressed_steps_per_sec": 61.7,
                         "async_compressed_wire_kb_per_step": 812.4,
                         "async_compressed_topk_pe4_steps_per_sec": 84.2,
                         "compression_gain": 6.56,
                         "async_vs_sync": 0.599,
                         "async_vs_sync_uncompressed": 0.091,
                         "hierarchical_steps_per_sec": 94.8,
                         "hierarchical_wire_kb_per_step": 101.6,
                         "hier_ps_vs_sync": 0.92,
                         "sync_steps_per_sec": 103.0},
        "serving_cpu": {"rows_per_sec": 34395.2},
        "async_ps": {"async_steps_per_sec": 1135.2},
        "skipped": {"decode_long": "budget: 10s left < ~160s needed"},
        "bench_wall_sec": 741.2,
    }


def test_summary_is_compact_standalone_json(tmp_path):
    line = bench.emit_record(
        _full_record(), full_path=str(tmp_path / "full.json")
    )
    assert len(line) <= 1500
    parsed = json.loads(line)  # standalone-parseable
    assert parsed["resnet50_img_s"] == 2675.11
    assert parsed["vs_baseline"] == 1.0546
    assert parsed["lm_tok_s"] == 57501.2
    assert parsed["lm_mfu"] == 0.702
    assert parsed["spark_feed_steps_s"] == 95.31  # ring preferred
    assert parsed["moe_tok_s"] is None  # not in the default record
    assert parsed["serving_generate_rows_s"] == 59.77
    assert parsed["serving_continuous_rows_s"] == 78.41
    assert parsed["serving_overload_goodput"] == 11.8  # reject-policy row
    assert parsed["swap_latency_ms"] == 41.3  # hot-swap transaction
    assert parsed["swap_dropped"] == 0  # the zero-downtime contract
    # fleet plane (ISSUE 13): served-goodput at the 2x burst + the
    # affinity hit rate on the 80%-shared workload
    assert parsed["fleet_goodput_2x"] == 2.0
    assert parsed["fleet_affinity_hit_rate"] == 0.703
    assert parsed["serving_prefix_gain"] == 1.653  # 80%-shared vs cold
    assert parsed["spec_accept_rate"] == 0.918
    # paged KV plane (ISSUE 12): zero-copy cached admits + int4 decode
    assert parsed["paged_admit_gain"] == 4.637
    assert parsed["int4_tok_s"] == 958.6
    # disaggregated prefill/decode plane (ISSUE 17): split-vs-unified
    # TTFT p99 ratio + the split engine's TTFT p50
    assert parsed["serving_disagg_p99_gain"] == 0.996
    assert parsed["serving_ttft_ms"] == 26.2
    # fault-containment plane (ISSUE 19): worst-of-two contained
    # faults' added wall + goodput dip
    assert parsed["fault_recovery_sec"] == 0.019
    assert parsed["fault_goodput_dip_pct"] == 7.24
    # auto-parallelism planner plane (ISSUE 18): worst-case gap of
    # config="auto" vs hand-tuned, and the exactly-one-re-plan count
    # from the injected-drift mini-run
    assert parsed["planner_gap_pct"] == 4.2
    assert parsed["replan_events"] == 1
    assert parsed["async_ps_compressed_steps_s"] == 61.7
    assert parsed["async_vs_sync"] == 0.599
    assert parsed["hier_ps_vs_sync"] == 0.92  # two-tier plane (ISSUE 9)
    assert parsed["feed_wire_mb_per_step"] == 0.0512  # narrowed wire
    assert parsed["serving_u8_vs_f32"] == 3.34
    assert parsed["decode_overlap_gain"] == 1.34
    assert parsed["telemetry_overhead_pct"] == 1.21
    # health plane (ISSUE 10): scrape+SLO+straggler+exposition riding
    assert parsed["health_overhead_pct"] == 1.6
    assert parsed["alerts_fired"] == 1
    # forensics plane (ISSUE 11): journal + flight recorder live
    assert parsed["forensics_overhead_pct"] == 1.8
    # cost-attribution plane (ISSUE 14): ledger + exemplars riding
    # the full stack, and the skewed workload's heavy hitter
    assert parsed["ledger_overhead_pct"] == 1.4
    assert parsed["usage_top_tenant_share"] == 0.52
    assert parsed["wall_sec"] == 741.2


def test_summary_keys_are_exactly_the_headline_set(tmp_path):
    line = bench.emit_record(
        _full_record(), full_path=str(tmp_path / "full.json")
    )
    assert sorted(json.loads(line)) == sorted([
        "resnet50_img_s", "vs_baseline", "lm_tok_s", "lm_mfu",
        "spark_feed_steps_s", "moe_tok_s", "serving_generate_rows_s",
        "serving_continuous_rows_s", "serving_overload_goodput",
        "swap_latency_ms", "swap_dropped",
        "fleet_goodput_2x", "fleet_affinity_hit_rate",
        "serving_prefix_gain", "spec_accept_rate",
        "paged_admit_gain", "int4_tok_s",
        "serving_disagg_p99_gain", "serving_ttft_ms",
        "fault_recovery_sec", "fault_goodput_dip_pct",
        "planner_gap_pct", "replan_events",
        "async_ps_compressed_steps_s",
        "async_vs_sync", "hier_ps_vs_sync", "feed_wire_mb_per_step",
        "serving_u8_vs_f32",
        "decode_overlap_gain", "telemetry_overhead_pct",
        "health_overhead_pct", "alerts_fired",
        "forensics_overhead_pct", "ledger_overhead_pct",
        "usage_top_tenant_share", "wall_sec",
        "full_record",
    ])


def test_summary_survives_an_absurd_full_record_path(tmp_path):
    # every summary value is a plucked number; the one unbounded field
    # is the full-record PATH — a deeply nested run directory must not
    # push the line past the driver's tail window (the r5 failure mode
    # regression-tested at its root)
    deep = tmp_path
    for i in range(40):
        deep = deep / ("deeply-nested-run-directory-%02d" % i)
    deep.mkdir(parents=True)
    line = bench.emit_record(
        _full_record(), full_path=str(deep / "full.json")
    )
    assert len(line) <= 1500
    parsed = json.loads(line)
    assert parsed["resnet50_img_s"] == 2675.11
    assert parsed["full_record"] == "full.json"  # shortened, not lost


def test_full_record_lands_in_file(tmp_path):
    path = str(tmp_path / "full.json")
    record = _full_record()
    line = bench.emit_record(record, full_path=path)
    assert json.loads(line)["full_record"] == path
    with open(path) as f:
        landed = json.load(f)
    # emit_record attaches the final metrics-registry snapshot to the
    # FULL record (ISSUE 7 satellite) — never to the summary line
    snap = landed.pop("telemetry")
    assert set(snap) == {"counters", "gauges", "histograms"}
    assert landed == record
    assert "telemetry" not in json.loads(line)


def test_partial_record_summarizes_to_nones(tmp_path):
    # a timeout-killed run emits after each section: the line must be
    # valid from the very first (near-empty) record on
    for record in ({}, {"spark_feed": {"queue": {"steps_per_sec": 88.5}}}):
        line = bench.emit_record(
            dict(record), full_path=str(tmp_path / "p.json")
        )
        parsed = json.loads(line)
        assert len(line) <= 1500
        assert parsed["resnet50_img_s"] is None
        assert parsed["serving_continuous_rows_s"] is None
    assert parsed["spark_feed_steps_s"] == 88.5  # queue fallback


def test_unwritable_full_path_still_emits_summary(tmp_path):
    line = bench.emit_record(
        _full_record(),
        full_path=str(tmp_path / "no_such_dir" / "full.json"),
    )
    parsed = json.loads(line)
    assert parsed["full_record"] is None
    assert parsed["resnet50_img_s"] == 2675.11


# --- bench --compare (per-key deltas + regression gate, ISSUE 9) -------


def test_compare_flags_regressions_in_the_right_direction(tmp_path):
    prev = bench.bench_summary(_full_record())
    cur = dict(prev)
    cur["lm_tok_s"] = prev["lm_tok_s"] * 0.8          # throughput DOWN: bad
    cur["swap_latency_ms"] = prev["swap_latency_ms"] * 2  # latency UP: bad
    cur["resnet50_img_s"] = prev["resnet50_img_s"] * 1.5  # UP: good
    cur["wall_sec"] = prev["wall_sec"] * 0.5          # lower-better DOWN: good
    out = bench.compare_records(prev, cur)
    assert "lm_tok_s" in out["regressions"]
    assert "swap_latency_ms" in out["regressions"]
    assert "resnet50_img_s" not in out["regressions"]
    assert "wall_sec" not in out["regressions"]
    # per-key deltas carry prev/cur/pct
    d = out["deltas"]["lm_tok_s"]
    assert d["prev"] == prev["lm_tok_s"] and d["cur"] == cur["lm_tok_s"]
    assert abs(d["pct"] + 20.0) < 0.01


def test_compare_within_threshold_is_clean():
    prev = bench.bench_summary(_full_record())
    cur = {k: (v * 1.05 if isinstance(v, float) and v else v)
           for k, v in prev.items()}
    out = bench.compare_records(prev, cur)
    assert out["regressions"] == []
    assert out["compared"] > 5


def test_compare_reports_uncomparable_keys():
    prev = bench.bench_summary(_full_record())
    cur = dict(prev, lm_tok_s=None)  # row vanished
    out = bench.compare_records(prev, cur)
    assert "lm_tok_s" in out["uncomparable"]
    assert "lm_tok_s" not in out["deltas"]


def test_load_compare_record_roundtrips_a_full_record(tmp_path):
    path = tmp_path / "full.json"
    path.write_text(json.dumps(_full_record()))
    got = bench.load_compare_record(str(path))
    assert got["lm_tok_s"] == 57501.2
    assert got["hier_ps_vs_sync"] == 0.92


def test_load_compare_record_handles_driver_wrapper(tmp_path):
    # BENCH_r0N.json shape: {n, cmd, rc, tail, parsed} — when the run
    # predates the summary-line contract, sections are recovered from
    # the (possibly head-truncated) stdout tail
    record = _full_record()
    tail = json.dumps(record)
    wrapper = {"n": 5, "cmd": "python bench.py", "rc": 0,
               "tail": tail[-2000:], "parsed": None}
    path = tmp_path / "BENCH_r0X.json"
    path.write_text(json.dumps(wrapper))
    got = bench.load_compare_record(str(path))
    # the tail ends with async_ps_tpu and the final sections: those
    # must be recovered; the truncated head ones are simply absent
    assert got["async_vs_sync"] == 0.599
    assert got["hier_ps_vs_sync"] == 0.92


def test_run_compare_cli_shape(tmp_path):
    prev = tmp_path / "prev.json"
    cur = tmp_path / "cur.json"
    prev.write_text(json.dumps(_full_record()))
    rec = _full_record()
    rec["transformer"]["value"] = 1.0  # massive regression
    cur.write_text(json.dumps(rec))
    out = bench.run_compare(str(prev), str(cur))
    assert out["anchor"] == str(prev)
    assert "lm_tok_s" in out["regressions"]
