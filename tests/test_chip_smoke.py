"""``chip_smoke.py`` from tier-1: the tiny CPU mode end to end, and the
refusal to report anything without a chip.

The real run happens on a TPU (``python chip_smoke.py``); what tier-1
can pin is that the command itself works — every phase child starts,
the cluster->feed->trainer and serving paths finish at toy size, the
parent parses their results — and that no combination of flags turns a
CPU run into an ``"ok": true`` chip record.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, env, timeout):
    return subprocess.run(
        [sys.executable, SMOKE] + args, env=env, cwd=REPO,
        capture_output=True, text=True, timeout=timeout,
    )


def _env(tmp_path, **extra):
    env = dict(os.environ)
    # ONE cpu device (the conftest's 8-device forcing would arm the
    # multichip phases), and a private cache so the run is cold
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count")
    )
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    env.update(extra)
    return env


def test_tiny_mode_end_to_end(tmp_path):
    proc = _run(["--tiny"], _env(tmp_path, JAX_PLATFORMS="cpu"), 240)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    out = proc.stdout
    assert "NOT A CHIP RUN" in out
    assert "multichip: skipped, 1 device(s)" in out
    lines = out.strip().splitlines()
    # the verdict is the last line: exactly these keys, the device as JAX
    # reports it (a CPU here, so it can never pass for a chip record)
    verdict = json.loads(lines[-1])
    assert verdict == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    assert list(verdict) == ["ok", "device"]
    assert list(verdict["device"]) == ["platform", "kind", "count"]
    assert lines[-2].startswith("summary: ")
    summary = json.loads(lines[-2][len("summary: "):])
    assert summary["tiny"] is True
    assert summary["claim"] is None
    assert list(summary)[-1] == "claim"
    assert sorted(summary["phases"]) == ["kernels", "serve", "train"]
    for name, phase in summary["phases"].items():
        assert phase["wall_sec"] > 0 and "compile_sec" in phase, name
        assert "cache_entries_added" in phase, name
    # the cache went where the environment said, and nowhere else
    assert summary["cache_dir"] == str(tmp_path / "cache")
    assert summary["cache_entries_added"] > 0
    train = summary["phases"]["train"]
    assert train["steps"] == 8 and train["losses"][-1] < train["losses"][0]
    assert train["ring_records"] == [1]  # the native shm ring carried it
    serve = summary["phases"]["serve"]
    assert serve["layouts"]["paged"]["errors"] == 0
    assert 0.0 < serve["layout_token_agreement"] <= 1.0
    assert summary["phases"]["kernels"]["interpreted"] is True


def test_no_chip_no_result(tmp_path):
    # a CPU-pinned JAX without --tiny: refused before anything runs
    proc = _run([], _env(tmp_path, JAX_PLATFORMS="cpu"), 60)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    # --tiny without the CPU pin: refused the same way
    env = _env(tmp_path)
    env.pop("JAX_PLATFORMS", None)
    proc = _run(["--tiny"], env, 60)
    assert proc.returncode != 0 and '"ok"' not in proc.stdout
    # nothing pinned, no chip on this host: the first phase child asks
    # for the TPU, fails, and the script stops without a result line
    if os.path.exists("/dev/vfio") or os.path.exists("/dev/accel0"):
        return  # a TPU host: unpinned, this would BE a chip run
    proc = _run([], env, 120)
    assert proc.returncode != 0, proc.stdout[-2000:]
    assert '"ok"' not in proc.stdout
    assert "Unable to initialize backend 'tpu'" in proc.stderr


def test_kill_session_reaps_grandchildren(tmp_path):
    """A phase child's leftovers (executors put themselves in their own
    process GROUPS) die with its session — the next phase finds the chip
    free."""
    sys.path.insert(0, REPO)
    import time

    import chip_smoke

    pidfile = tmp_path / "pid"
    child = subprocess.Popen(
        [sys.executable, "-c",
         "import os, subprocess, sys, time\n"
         "p = subprocess.Popen([sys.executable, '-c', "
         "'import os, time; os.setpgid(0, 0); time.sleep(600)'])\n"
         "open(%r, 'w').write(str(p.pid))\n"
         "time.sleep(600)\n" % str(pidfile)],
        start_new_session=True,
    )
    deadline = time.time() + 30
    while not pidfile.exists() or not pidfile.read_text():
        assert time.time() < deadline
        time.sleep(0.05)
    grandchild = int(pidfile.read_text())
    chip_smoke.kill_session(child.pid)
    assert child.wait(timeout=30) == -9
    deadline = time.time() + 30
    while True:  # reparented to init: poll /proc until it is reaped
        try:
            with open("/proc/%d/stat" % grandchild) as f:
                if f.read().rsplit(")", 1)[1].split()[0] == "Z":
                    break
        except OSError:
            break
        assert time.time() < deadline, "grandchild survived kill_session"
        time.sleep(0.05)
