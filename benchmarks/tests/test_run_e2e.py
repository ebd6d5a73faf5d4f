"""``run.py`` end to end: refused without a chip; a tiny rehearsal on
the CPU (a flag of this test, not of the command) that says it is no
measurement; and a cell, a configuration, a mix and a per-layer reader
added by files and entries alone."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.tests.conftest import ROOT, TINY_SERVE, TINY_TRAIN

REHEARSALS = {"serve": TINY_SERVE, "train": TINY_TRAIN}


def bench(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def rehearse(root, workload, tiny, trace=0, seed=7):
    """Run ``run.main`` of the tree at ``root`` in a process of its own
    (it spawns the child that touches JAX)."""
    code = (
        "import json, sys; sys.path.insert(0, %r); "
        "from benchmarks import run; "
        "sys.exit(run.main(sys.argv[1:], rehearse=json.loads(%r)))"
    ) % (root, json.dumps(tiny))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([root, ROOT]))
    proc = subprocess.run(
        [sys.executable, "-c", code, "--workload", workload, "--seed",
         str(seed), "--seconds", "2", "--trace", str(trace)],
        cwd=root, env=env, capture_output=True, text=True, timeout=900,
    )
    lines = proc.stdout.strip().splitlines()
    return proc, (json.loads(lines[-1]) if lines else None)


def path_of(workload):
    b = bench()
    cell = next(w for w in b["workloads"] if w["name"] == workload)
    cfg = next(c for c in b["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, cfg["file"])) as f:
        return json.load(f)["path"]


def test_without_a_chip_the_command_fails_and_prints_no_result():
    cell = bench()["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


@pytest.mark.parametrize("workload", [w["name"] for w in bench()["workloads"]])
def test_every_cell_rehearses_on_the_cpu(workload):
    tiny = REHEARSALS[path_of(workload)]
    proc, result = rehearse(ROOT, workload, tiny, trace=0, seed=2 ** 31 + 5)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result["correct"] is True and result["failed"] == 0
    assert "not a measurement" in result["rehearsal"]
    assert result["device"]["platform"] == "cpu"
    b = bench()
    mine = {m["name"] for m in b["end_to_end"]
            if workload in m.get("workloads", [workload])}
    assert set(result["metrics"]) == mine
    assert list(result)[-1] == "checks"
    for name, c in result["checks"].items():
        assert "check %s" % name in proc.stderr


def test_a_cell_a_config_a_mix_and_a_reader_are_added_by_files_alone(tmp_path):
    root = str(tmp_path / "tree")
    os.makedirs(root)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {
        os.path.join(d, f): os.path.getmtime(os.path.join(d, f))
        for d, _, fs in os.walk(os.path.join(root, "benchmarks"))
        for f in fs
    }
    b = bench()
    old_cell = b["workloads"][0]
    old_cfg = next(c for c in b["configs"] if c["name"] == old_cell["config"])
    # a configuration of its own: a file and an entry
    with open(os.path.join(ROOT, old_cfg["file"])) as f:
        cfg = json.load(f)
    new_cfg_file = "benchmarks/configs/added-model.json"
    with open(os.path.join(root, new_cfg_file), "w") as f:
        json.dump(cfg, f)
    b["configs"].append(dict(old_cfg, name="added-model", file=new_cfg_file))
    # a traffic mix of its own: a data file
    with open(os.path.join(
            ROOT, "benchmarks/traffic", old_cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    with open(os.path.join(root, "benchmarks/traffic/added-mix.json"),
              "w") as f:
        json.dump(dict(mix, schedule_seed=99), f)
    # a per-layer metric of its own: a reader and an entry
    with open(os.path.join(root, "benchmarks/metrics/added_metric.py"),
              "w") as f:
        f.write("def reduce(trace, counters, cell):\n"
                "    return 1e3 * counters['window_s']\n")
    b["per_layer"].append({
        "name": "added_metric", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "serving engine",
        "moves": b["end_to_end"][0]["name"], "workloads": ["added-cell"]})
    # the cell: an entry
    b["workloads"].append(dict(
        old_cell, name="added-cell", config="added-model",
        traffic="added-mix"))
    for m in b["end_to_end"]:
        if "workloads" in m and old_cell["name"] in m["workloads"]:
            m["workloads"].append("added-cell")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)

    tiny = REHEARSALS[cfg["path"]]
    proc, result = rehearse(root, "added-cell", tiny, trace=1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result["correct"] is True
    assert result["metrics"]["added_metric"]["unit"] == "ms"
    # nothing that was there was edited
    for path, mtime in before.items():
        assert os.path.getmtime(path) == mtime, path


@pytest.mark.parametrize(
    "fault", ["state_unchanged", "half_batch", "no_gradient_exchange"])
def test_a_training_fault_under_the_timed_path_is_not_correct(fault):
    cell = next(w["name"] for w in bench()["workloads"]
                if path_of(w["name"]) == "train")
    proc, result = rehearse(ROOT, cell, dict(TINY_TRAIN, fault=fault))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["checks"].values())
