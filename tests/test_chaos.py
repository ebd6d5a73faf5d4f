"""Chaos-injection tests: heartbeat liveness, supervised restart,
checkpoint auto-resume, and the deterministic fault harness itself.

Fast tests (no `slow` marker) exercise the liveness plane, the retry
policy, the partition ledger, and the TCP gremlin in-process — they run
in the tier-1 lane and the CI chaos lane.  The end-to-end kill-and-
recover tests over a real LocalEngine multiprocess cluster carry `slow`.
"""

import os
import signal
import time

import pytest

pytestmark = pytest.mark.chaos

from tensorflowonspark_tpu.cluster import manager as mgr_mod
from tensorflowonspark_tpu.cluster import reservation
from tensorflowonspark_tpu.testing import chaos
from tensorflowonspark_tpu.utils.retry import Backoff, RetryError, retry_call


# ----------------------------------------------------------------------
# heartbeat plane (fast)
# ----------------------------------------------------------------------


@pytest.fixture()
def server():
    srv = reservation.Server(1, heartbeat_interval=0.1)
    srv.start()
    yield srv
    srv.stop()


def test_heartbeats_keep_executor_alive(server):
    hb = reservation.Heartbeater(server.addr, 3, interval=0.1).start()
    try:
        time.sleep(0.6)
        assert server.liveness.dead() == {}
        assert server.liveness.last_seen(3) < 0.5
    finally:
        hb.stop()


def test_dropped_heartbeats_declare_dead_within_miss_threshold(server):
    drop = {"on": False}
    hb = reservation.Heartbeater(
        server.addr, 3, interval=0.1, chaos_fn=lambda: drop["on"]
    ).start()
    try:
        time.sleep(0.4)
        assert server.liveness.dead() == {}
        drop["on"] = True  # simulated partition: frames stop arriving
        t0 = time.monotonic()
        while not server.liveness.dead():
            time.sleep(0.02)
            assert time.monotonic() - t0 < 5.0, "death never detected"
        detection = time.monotonic() - t0
        # the contract: ~3 missed intervals, nowhere near feed_timeout
        assert detection < 1.5, detection
        diag = server.liveness.dead()[3]
        assert "no heartbeat" in diag["reason"]
        # partition heals: beats resume, executor recovers
        drop["on"] = False
        t0 = time.monotonic()
        while server.liveness.dead():
            time.sleep(0.02)
            assert time.monotonic() - t0 < 5.0, "never recovered"
    finally:
        hb.stop()


def test_monitor_does_not_judge_silence_through_its_own_stall(server):
    """A frozen HOST (TPU initialisation stalls every process of the VM
    for seconds) silences the nodes and the monitor alike: waking up,
    the monitor must not read the gap as death — but real silence is
    still caught one deadline later, and an explicit compute-dead
    report is never discounted."""
    from tensorflowonspark_tpu.cluster.cluster import ClusterMonitor

    monitor = ClusterMonitor(server, [{"executor_id": 3}])
    server.liveness.beat(3)
    deadline = server.liveness.deadline  # 3 x 0.1 s
    time.sleep(deadline + 0.1)  # everyone was frozen: no beats, no polls
    monitor._tick(overslept=deadline + 0.1)
    monitor._poll()
    assert monitor.error is None
    server.liveness.beat(3)  # the node thawed too
    monitor._tick(overslept=0.0)
    monitor._poll()
    assert monitor.error is None
    # genuine silence after the stall: judged once the blind window ends
    time.sleep(2 * deadline + 0.1)
    monitor._poll()
    assert "no heartbeat" in monitor.error
    # a REPORTED death is evidence whatever the monitor slept through
    reported = ClusterMonitor(server, [{"executor_id": 5}])
    server.liveness.forget(3)
    server.liveness.beat(5, compute_alive=False)
    reported._tick(overslept=60.0)
    reported._poll()
    assert "compute process dead" in reported.error


def test_compute_dead_flag_is_immediate(server):
    hb = reservation.Heartbeater(
        server.addr, 5, interval=0.1, alive_fn=lambda: False
    )
    hb.beat_once()
    # no waiting out the miss threshold: the explicit flag is enough
    assert 5 in server.liveness.dead()
    assert "compute process dead" in server.liveness.dead()[5]["reason"]
    hb.stop()


def test_farewell_stops_tracking(server):
    hb = reservation.Heartbeater(server.addr, 4, interval=0.1)
    hb.beat_once()
    assert server.liveness.last_seen(4) is not None
    hb.stop()  # sends FAREWELL
    assert server.liveness.last_seen(4) is None
    time.sleep(0.5)
    assert server.liveness.dead() == {}


def test_rebirth_generation_rules(server):
    c = reservation.Client(server.addr)
    try:
        assert c.rebirth(0, 0) == 1
        # simultaneous death: executor 1 (still at generation 0) JOINS
        # generation 1 instead of bumping past it
        assert c.rebirth(1, 0) == 1
        # a later death from generation 1 bumps to 2
        assert c.rebirth(0, 1) == 2
        _, dead = c.get_liveness()
        assert server.generation == 2
    finally:
        c.close()


def test_heartbeat_reply_carries_cluster_generation(server):
    c = reservation.Client(server.addr)
    hb = reservation.Heartbeater(server.addr, 7, interval=0.05).start()
    try:
        c.rebirth(9, 0)
        deadline = time.monotonic() + 5
        while hb.cluster_generation < 1:
            time.sleep(0.02)
            assert time.monotonic() < deadline
        assert hb.cluster_generation == 1
    finally:
        hb.stop()
        c.close()


# ----------------------------------------------------------------------
# retry policy (fast; satellite: reservation client backoff + deadline)
# ----------------------------------------------------------------------


def test_backoff_respects_deadline():
    sleeps = []
    bo = Backoff(deadline=0.3, base=0.05, sleep=sleeps.append)
    t0 = time.monotonic()
    attempts = 0
    for attempt in bo:
        attempts += 1
        attempt.note(OSError("nope"))
        # simulate wall clock passing (sleep is stubbed out)
        if attempts > 50:
            break
        time.sleep(0.05)
    assert attempts >= 2
    err = bo.exhausted("reach the thing")
    assert isinstance(err, RetryError)
    assert "reach the thing" in str(err)
    assert "nope" in str(err)


def test_backoff_immune_to_wall_clock_jumps():
    # satellite: deadlines run on a monotonic clock, injectable for
    # tests.  A patched clock drives the budget deterministically: a
    # simulated wall-clock step (NTP, suspend) must neither spuriously
    # expire a live budget nor extend an exhausted one.
    class Clock(object):
        def __init__(self):
            self.t = 100.0

        def __call__(self):
            return self.t

    clk = Clock()
    bo = Backoff(deadline=10.0, base=0.01, sleep=lambda s: None,
                 clock=clk)
    it = iter(bo)
    next(it)            # arms the deadline at t=100
    clk.t = 109.0       # 9s elapsed: still inside the budget
    next(it)
    clk.t = 110.5       # past the 10s budget: exhausted
    with pytest.raises(StopIteration):
        next(it)

    # a backwards wall-clock step CANNOT revive the budget (monotonic
    # clocks never go backwards; the injected clock proves the policy
    # depends only on the clock handed to it, never time.time())
    clk2 = Clock()
    calls = {"n": 0}

    def always():
        calls["n"] += 1
        clk2.t += 0.05  # each attempt costs 50ms of monotonic time
        raise OSError("still down")

    with pytest.raises(RetryError, match="patched-clock target"):
        retry_call(always, "patched-clock target", deadline=0.2,
                   base=0.01, clock=clk2)
    # elapsed-time exhaustion: ~0.2s / 0.05s-per-attempt, not the
    # hours a wall-clock-jumped loop would spin for
    assert 2 <= calls["n"] <= 10


def test_retry_call_succeeds_after_transient_failures():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    assert retry_call(flaky, "flaky thing", deadline=10.0, base=0.01) == "ok"
    assert calls["n"] == 3


def test_retry_call_exhaustion_names_target():
    def always():
        raise OSError("still down")

    with pytest.raises(RetryError, match="connect to 10.9.8.7:1234"):
        retry_call(
            always, "connect to 10.9.8.7:1234", deadline=0.2, base=0.01
        )


def test_reservation_client_connect_error_names_server():
    # satellite contract: exhaustion error names the server address
    with pytest.raises(ConnectionError, match=r"127\.0\.0\.1.*1\b"):
        reservation.Client(("127.0.0.1", 1), retry_deadline=0.3)


# ----------------------------------------------------------------------
# chaos plan + harness (fast)
# ----------------------------------------------------------------------


def test_chaos_plan_roundtrip(tmp_path):
    plan = (
        chaos.ChaosPlan()
        .kill_worker(executor_id=1, at_step=5)
        .drop_heartbeats(executor_id=0, beats=4)
    )
    path = plan.save(tmp_path / "plan.json")
    loaded = chaos.ChaosPlan.load(path)
    assert loaded.faults == plan.faults
    assert chaos.TFOS_CHAOS_PLAN in plan.env(path)


def test_step_fault_fn_kills_at_step(tmp_path, monkeypatch):
    path = chaos.ChaosPlan().kill_worker(1, at_step=5).save(
        tmp_path / "p.json"
    )
    monkeypatch.setenv(chaos.TFOS_CHAOS_PLAN, str(path))
    kills = []
    monkeypatch.setattr(os, "kill", lambda pid, sig: kills.append((pid, sig)))

    class Ctx(object):
        executor_id = 1
        generation = 0

    fault = chaos.step_fault_fn(Ctx())
    fault(4)
    assert kills == []
    fault(5)
    assert kills == [(os.getpid(), signal.SIGKILL)]


def test_step_fault_fn_spent_after_rebirth(tmp_path, monkeypatch):
    # the replacement (generation 1) must NOT re-trigger the generation-0
    # kill when it replays the same step from the checkpoint
    path = chaos.ChaosPlan().kill_worker(1, at_step=5).save(
        tmp_path / "p.json"
    )
    monkeypatch.setenv(chaos.TFOS_CHAOS_PLAN, str(path))
    kills = []
    monkeypatch.setattr(os, "kill", lambda pid, sig: kills.append(pid))

    class Ctx(object):
        executor_id = 1
        generation = 1

    fault = chaos.step_fault_fn(Ctx())
    fault(5)
    fault(50)
    assert kills == []


def test_heartbeat_chaos_fn_budget(tmp_path, monkeypatch):
    path = chaos.ChaosPlan().drop_heartbeats(2, beats=3).save(
        tmp_path / "p.json"
    )
    monkeypatch.setenv(chaos.TFOS_CHAOS_PLAN, str(path))
    assert chaos.heartbeat_chaos_fn(0) is None  # not targeted
    drop = chaos.heartbeat_chaos_fn(2)
    assert [drop() for _ in range(5)] == [True, True, True, False, False]


def test_no_plan_means_no_chaos(monkeypatch):
    monkeypatch.delenv(chaos.TFOS_CHAOS_PLAN, raising=False)
    assert chaos.load_plan() is None
    assert chaos.heartbeat_chaos_fn(0) is None


# ----------------------------------------------------------------------
# TCP gremlin: sever reservation connections (fast)
# ----------------------------------------------------------------------


def test_gremlin_cut_mid_session_client_reconnects(server):
    gremlin = chaos.TcpGremlin(server.addr)
    addr = gremlin.start()
    try:
        client = reservation.Client(addr, retry_deadline=10.0)
        client.register({"executor_id": 0, "host": "h"})
        assert gremlin.cut_all() >= 1  # sever the live connection
        # the next request rides the backoff+reconnect path and succeeds
        resp = client.heartbeat(0)
        assert resp["type"] == "OK"
        client.close()
    finally:
        gremlin.stop()


def test_gremlin_refused_connections_are_retried(server):
    gremlin = chaos.TcpGremlin(server.addr)
    addr = gremlin.start()
    gremlin.refuse_next(2)
    try:
        client = reservation.Client(addr, retry_deadline=15.0)
        assert client.heartbeat(1)["type"] == "OK"
        assert gremlin.connections >= 3  # two cut on accept + one live
        client.close()
    finally:
        gremlin.stop()


# ----------------------------------------------------------------------
# partition ledger + queue reset (fast)
# ----------------------------------------------------------------------


def test_partition_ledger_state_machine():
    ledger = mgr_mod.PartitionLedger()
    ledger.op("begin", "p0")
    ledger.op("begin", "p1")
    assert ledger.op("pending") == ["p0", "p1"]
    ledger.op("deliver", "p0")
    assert ledger.op("committed") == []
    assert ledger.op("commit") == 1  # only delivered ones promote
    assert ledger.op("committed") == ["p0"]
    assert ledger.op("pending") == ["p1"]
    # a requeued partition begins again and can commit on the retry
    ledger.op("begin", "p1")
    ledger.op("deliver", "p1")
    assert ledger.op("commit") == 1
    assert ledger.op("pending") == []
    with pytest.raises(ValueError):
        ledger.op("bogus")


def test_reset_queue_releases_blocked_join():
    import threading
    import uuid

    mgr, _ = mgr_mod.start(uuid.uuid4().bytes, ["input", "error"])
    try:
        q = mgr.get_queue("input")
        for i in range(6):
            q.put(i)
        # a consumer pops two items and "dies" without task_done
        q.get(), q.get()
        released = []
        t = threading.Thread(target=lambda: (q.join(), released.append(1)),
                             daemon=True)
        t.start()
        time.sleep(0.3)
        assert not released
        discarded = mgr.reset_queue("input")._getvalue()
        assert discarded == 4
        t.join(timeout=5)
        assert released, "reset did not release the blocked join()"
        # the queue stays usable for the replacement incarnation
        q.put("fresh")
        assert q.get() == "fresh"
        q.task_done()
    finally:
        mgr.shutdown()


# ----------------------------------------------------------------------
# end-to-end kill-and-recover over the LocalEngine (slow)
# ----------------------------------------------------------------------


def _slow_consume_fn(args, ctx):
    import time as _t

    feed = ctx.get_data_feed(train_mode=True)
    while not feed.should_stop():
        feed.next_batch(4)
        _t.sleep(0.05)


def _make_rows(n, seed):
    import numpy as np

    rng = np.random.RandomState(seed)
    X = rng.randn(n, 2)
    y = 2.0 * X[:, 0] - 3.0 * X[:, 1] + 1.0
    return [(float(a), float(b), float(c)) for (a, b), c in zip(X, y)]


def _sgd_train_fn(args, ctx):
    """Linear-regression SGD with Checkpointer auto-resume — the resume
    contract the supervisor relies on, minus JAX-jit noise (numpy keeps
    the slow-lane wall clock down; the Checkpointer/orbax path is the
    same one dp.train_on_feed(checkpointer=...) drives)."""
    import numpy as np

    from tensorflowonspark_tpu.checkpoint import Checkpointer
    from tensorflowonspark_tpu.testing import chaos as _chaos

    fault = _chaos.step_fault_fn(ctx)
    ckpt = Checkpointer(
        os.path.join(args["ckpt_dir"], "w%d" % ctx.task_index),
        max_to_keep=None,
    )
    state = {"w": np.zeros(2), "b": np.zeros(()),
             "step": np.zeros((), np.int64)}
    if ckpt.latest_step() is not None:
        state = {k: np.asarray(v) for k, v in ckpt.restore(state).items()}
    steps = int(state["step"])
    feed = ctx.get_data_feed(train_mode=True)
    while not feed.should_stop():
        rows = feed.next_batch(16)
        if not rows:
            continue
        fault(steps)
        arr = np.asarray(rows, dtype=np.float64)
        X, y = arr[:, :2], arr[:, 2]
        err = X @ state["w"] + state["b"] - y
        state["w"] = state["w"] - 0.05 * (X.T @ err) / len(y)
        state["b"] = state["b"] - 0.05 * err.mean()
        steps += 1
        state["step"] = np.asarray(steps, np.int64)
        if steps % args["ckpt_every"] == 0:
            ckpt.save(steps, state, wait=True)
            feed.commit_partitions()
    ckpt.save(steps, state, wait=True)
    feed.commit_partitions()
    ckpt.close()
    eval_rows = _make_rows(256, seed=999)
    arr = np.asarray(eval_rows, dtype=np.float64)
    loss = float(
        np.mean((arr[:, :2] @ state["w"] + state["b"] - arr[:, 2]) ** 2)
    )
    ctx.mgr.set("final_loss", loss)
    ctx.mgr.set("generation_seen", ctx.generation)


@pytest.mark.slow
def test_kill_mid_training_detected_fast_without_elastic():
    """Acceptance: a worker killed mid-feed is detected in < 10s (not
    the 600s feed timeout) and the error names the dead executor."""
    import threading

    from tensorflowonspark_tpu.cluster import cluster as tpu_cluster
    from tensorflowonspark_tpu.cluster.cluster import (
        DeadExecutorError,
        InputMode,
    )
    from tensorflowonspark_tpu.engine import LocalEngine

    engine = LocalEngine(2)
    try:
        cluster = tpu_cluster.run(
            engine, _slow_consume_fn, args={}, num_executors=2,
            input_mode=InputMode.SPARK, heartbeat_interval=0.5,
        )
        threading.Timer(
            1.0, lambda: chaos.kill_compute(cluster, 1)
        ).start()
        parts = [[float(i) for i in range(200)] for _ in range(8)]
        t0 = time.monotonic()
        with pytest.raises(DeadExecutorError, match="executor 1"):
            cluster.train(parts, feed_timeout=600)
        assert time.monotonic() - t0 < 10.0
        # teardown stays bounded; a SIGKILL'd worker left no traceback
        # in its error queue, so the failure was train()'s to report
        try:
            cluster.shutdown(grace_secs=0, timeout=15)
        except RuntimeError:
            pass
    finally:
        engine.stop()


def _run_sgd_cluster(tmp_path, tag, kill):
    from tensorflowonspark_tpu.cluster import cluster as tpu_cluster
    from tensorflowonspark_tpu.cluster.cluster import InputMode
    from tensorflowonspark_tpu.engine import LocalEngine

    env = {}
    if kill:
        plan = chaos.ChaosPlan().kill_worker(executor_id=1, at_step=6)
        env = plan.env(plan.save(tmp_path / ("plan_%s.json" % tag)))
    # deterministic task routing: each worker sees the same 4 partitions
    # every epoch, so both runs converge identically instead of one
    # worker under-training on a work-stealing skew (the engine mode
    # built for sharp integration assertions)
    engine = LocalEngine(2, env=env, deterministic=True)
    try:
        cluster = tpu_cluster.run(
            engine, _sgd_train_fn,
            args={"ckpt_dir": str(tmp_path / ("ckpt_" + tag)),
                  "ckpt_every": 4},
            num_executors=2, input_mode=InputMode.SPARK,
            elastic=True, heartbeat_interval=0.5, max_restarts=2,
        )
        rows = _make_rows(512, seed=0)
        parts = [rows[i::8] for i in range(8)]
        cluster.train(parts, num_epochs=6, feed_timeout=60)
        cluster.shutdown(grace_secs=1, timeout=60)
        losses, gens = [], []
        for n in cluster.cluster_info:
            m = mgr_mod.connect(
                tuple(n["addr"]), bytes.fromhex(n["authkey"])
            )
            losses.append(m.get("final_loss")._getvalue())
            gens.append(m.get("generation_seen")._getvalue())
        return losses, gens
    finally:
        engine.stop()


@pytest.mark.slow
def test_elastic_kill_resumes_from_checkpoint_with_loss_parity(tmp_path):
    """Acceptance: with elastic=True, killing worker 1 mid-training
    triggers a supervised restart that resumes from the last complete
    checkpoint, requeues uncommitted partitions, and converges to the
    same final loss as an uninterrupted run."""
    clean_losses, clean_gens = _run_sgd_cluster(tmp_path, "clean", kill=False)
    assert clean_gens == [0, 0]
    chaos_losses, chaos_gens = _run_sgd_cluster(tmp_path, "chaos", kill=True)
    # the kill actually happened and the cluster was reborn
    assert any(g and g > 0 for g in chaos_gens), chaos_gens
    # final-loss parity: converged SGD lands at the optimum either way
    for lc, lk in zip(sorted(clean_losses), sorted(chaos_losses)):
        assert lc < 0.05 and lk < 0.05, (clean_losses, chaos_losses)
        assert abs(lc - lk) < 0.05, (clean_losses, chaos_losses)


# ----------------------------------------------------------------------
# kill-the-leader: the hierarchical gradient plane's chaos family (fast)
# ----------------------------------------------------------------------


def test_hier_leader_fault_fn_arms_from_plan(tmp_path, monkeypatch):
    from tensorflowonspark_tpu.parallel import hier_ps

    path = chaos.ChaosPlan().kill_leader(at_window=3).save(
        tmp_path / "p.json"
    )
    monkeypatch.setenv(chaos.TFOS_CHAOS_PLAN, str(path))
    fault = chaos.hier_leader_fault_fn()
    assert fault is not None
    fault(2)  # below the window: nothing
    with pytest.raises(hier_ps.LeaderKilled):
        fault(3)
    fault(10)  # spent: fires once


def test_hier_leader_fault_fn_absent_without_plan(monkeypatch):
    monkeypatch.delenv(chaos.TFOS_CHAOS_PLAN, raising=False)
    assert chaos.hier_leader_fault_fn() is None


def test_kill_the_leader_reelects_with_loss_parity(tmp_path, monkeypatch):
    """The hierarchical-plane kill-and-recover e2e (fast lane: the pod
    is in-process, the global PS shards and the wire are real).

    The plan kills the pod leader mid-push at DCN window 2; the
    trainer must re-elect, resume the ledger from the server's applied
    floor, re-push the dead epoch's pending windows, and converge to
    the same answer as an unkilled run — with every (pod, window)
    applied EXACTLY once on every shard and the successor's
    error-feedback epoch starting clean."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.parallel import hier_ps
    from tensorflowonspark_tpu.parallel import ps as ps_mod

    target = np.asarray([1.0, -2.0, 0.5, 3.0], np.float32)

    def loss_fn(params, batch):
        del batch
        return jnp.sum((params["w"] - target) ** 2)

    def run(with_chaos):
        servers = [ps_mod.ParamServerShard() for _ in range(2)]
        addrs = []
        for s in servers:
            _, port = s.start("127.0.0.1", 0)
            addrs.append("127.0.0.1:{0}".format(port))
        if with_chaos:
            path = chaos.ChaosPlan().kill_leader(at_window=2).save(
                tmp_path / "leader_plan.json"
            )
            monkeypatch.setenv(chaos.TFOS_CHAOS_PLAN, str(path))
        else:
            monkeypatch.delenv(chaos.TFOS_CHAOS_PLAN, raising=False)
        tr = hier_ps.HierTrainer(
            loss_fn, addrs,
            optimizer=("sgd", {"learning_rate": 0.05}),
            push_every=2, codec="int8", reply_codec="same",
            members=(0, 1), member_id=0,
            fault_fn=chaos.hier_leader_fault_fn(),
        )
        tr.init({"w": np.zeros(4, np.float32)})
        for _ in range(80):
            tr.step(None)
        out = np.asarray(jax.device_get(tr.drain())["w"])
        epochs = tr.dcn_epochs()
        logs = [list(s.applied_log) for s in servers]
        probe = ps_mod.PSClient(addrs)
        probe.init({"w": np.zeros(4, np.float32)}, ("delta", {}))
        srv = np.asarray(probe.pull()["w"])
        probe.close()
        tr.stop()
        for s in servers:
            s.stop()
        return out, epochs, logs, srv

    clean, _, _, _ = run(with_chaos=False)
    killed, epochs, logs, srv = run(with_chaos=True)
    # loss parity with the unkilled run
    np.testing.assert_allclose(killed, target, atol=1e-2)
    np.testing.assert_allclose(killed, clean, atol=1e-2)
    # the global tier kept tracking the pod THROUGH the failover (the
    # successor pushes new windows, not just the re-pushed backlog)
    np.testing.assert_allclose(srv, killed, atol=1e-3)
    # re-election happened: two leader epochs, successor is member 1
    assert [e["member"] for e in epochs] == [0, 1]
    dead, live = epochs
    # the successor's ledger resumed from the server's applied floor
    # and drained clean (no window stranded)
    assert live["resumed_from"] >= 1
    assert live["pending"] == [] and dead["pending"]
    # ledger: every (pod, window) applied exactly once per shard, no
    # gaps — no gradient double-applied, none silently dropped
    for log in logs:
        assert len(set(log)) == len(log)
        seqs = sorted(w for _, w in log)
        assert seqs == list(range(len(seqs)))


# ----------------------------------------------------------------------
# straggler injection (ISSUE 10): plan hooks (fast) + health-plane e2e
# ----------------------------------------------------------------------


def test_slow_executor_plan_targets_only_its_executor(tmp_path, monkeypatch):
    plan = chaos.ChaosPlan().slow_executor(1, 0.02)
    monkeypatch.setenv(
        chaos.TFOS_CHAOS_PLAN, plan.save(tmp_path / "plan.json")
    )

    class Ctx:
        executor_id = 1

    class Other:
        executor_id = 0

    assert chaos.slow_feed_fn(Other()) is None  # non-target: no hook
    delay = chaos.slow_feed_fn(Ctx())
    assert delay is not None
    t0 = time.perf_counter()
    delay()
    assert time.perf_counter() - t0 >= 0.02


def test_slow_executor_batch_budget(tmp_path, monkeypatch):
    plan = chaos.ChaosPlan().slow_executor(0, 0.02, batches=2)
    monkeypatch.setenv(
        chaos.TFOS_CHAOS_PLAN, plan.save(tmp_path / "plan.json")
    )

    class Ctx:
        executor_id = 0

    delay = chaos.slow_feed_fn(Ctx())
    t0 = time.perf_counter()
    delay()
    delay()
    assert time.perf_counter() - t0 >= 0.04
    t1 = time.perf_counter()
    delay()  # budget spent: full speed again
    assert time.perf_counter() - t1 < 0.015


def test_slow_feed_wraps_and_proxies():
    class FakeFeed:
        marker = "yes"

        def next_batch(self, n):
            return list(range(n))

        def should_stop(self):
            return False

    calls = []
    feed = chaos.SlowFeed(FakeFeed(), lambda: calls.append(1))
    assert feed.next_batch(3) == [0, 1, 2]
    assert calls == [1]
    assert feed.should_stop() is False   # proxied
    assert feed.marker == "yes"          # attribute passthrough


def test_tcp_gremlin_delay_slows_the_wire():
    # the WIRE-phase straggler flavor: a gremlin delay measurably
    # stretches a round trip through the proxy, and delay(0) restores
    import socket
    import threading as _threading

    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.bind(("127.0.0.1", 0))
    srv.listen(4)

    def echo_once():
        conn, _ = srv.accept()
        while True:
            data = conn.recv(1024)
            if not data:
                return
            conn.sendall(data)

    _threading.Thread(target=echo_once, daemon=True).start()
    gremlin = chaos.TcpGremlin(srv.getsockname())
    addr = gremlin.start()
    try:
        c = socket.create_connection(addr, timeout=5)

        def rtt():
            t0 = time.perf_counter()
            c.sendall(b"ping")
            assert c.recv(1024) == b"ping"
            return time.perf_counter() - t0

        fast = min(rtt() for _ in range(3))
        gremlin.delay(0.05)
        slow = rtt()
        assert slow >= 0.05  # one direction stalled at least once
        gremlin.delay(0)
        assert min(rtt() for _ in range(3)) < 0.04
        c.close()
    finally:
        gremlin.stop()
        srv.close()


def _straggler_train_fn(args, ctx):
    """Feed-consuming loop publishing the REAL per-executor telemetry
    the health plane scrapes (train.step_sec / feed_wait_sec / steps),
    with the chaos straggler hook wrapping the feed — the stall lands
    inside feed_wait exactly like a slow data pipeline."""
    import time as _t

    import numpy as np

    from tensorflowonspark_tpu import telemetry, tensorboard
    from tensorflowonspark_tpu.testing import chaos as _chaos

    reg = telemetry.get_registry()
    h_step = reg.histogram("train.step_sec")
    h_feed = reg.histogram("train.feed_wait_sec")
    steps = reg.counter("train.steps")
    feed = ctx.get_data_feed(train_mode=True)
    delay = _chaos.slow_feed_fn(ctx)
    if delay is not None:
        feed = _chaos.SlowFeed(feed, delay)
    while not feed.should_stop():
        t0 = _t.perf_counter()
        rows = feed.next_batch(4)
        h_feed.observe(_t.perf_counter() - t0)
        if not rows:
            continue
        t1 = _t.perf_counter()
        float(np.sum(np.asarray(rows, dtype=np.float64)))
        _t.sleep(0.004)
        h_step.observe(_t.perf_counter() - t1)
        steps.inc()
        # feeds the auto-triggered capture so its step budget finishes
        # while batches still flow (dp.train_on_feed does the same)
        tensorboard.profile_step()


@pytest.mark.slow
def test_straggler_e2e_flagged_attributed_and_profiled(tmp_path):
    """Acceptance (ISSUE 10): an injected slow executor is flagged
    within one evaluation window, attributed to the FEED phase, and a
    profiler capture is triggered on that node only."""
    import threading as _threading

    from tensorflowonspark_tpu.cluster import cluster as tpu_cluster
    from tensorflowonspark_tpu.cluster.cluster import InputMode
    from tensorflowonspark_tpu.engine import LocalEngine

    plan = chaos.ChaosPlan().slow_executor(1, 0.08)
    env = plan.env(plan.save(tmp_path / "plan.json"))
    env["TFOS_TELEMETRY_PUBLISH_INTERVAL"] = "0.2"
    env["TFOS_TELEMETRY"] = "1"
    prof_dir = str(tmp_path / "prof")
    engine = LocalEngine(2, env=env, deterministic=True)
    try:
        cluster = tpu_cluster.run(
            engine, _straggler_train_fn, args={}, num_executors=2,
            input_mode=InputMode.SPARK, heartbeat_interval=0.5,
        )
        window = 20.0
        plane = cluster.start_health_plane(
            interval=0.5, profile_steps=3, profile_dir=prof_dir,
            straggler_opts={
                "window": window, "min_samples": 5, "ratio": 2.0,
            },
        )
        flag_at = {}

        def watch():
            while not flag_at and not plane._stop.is_set():
                if plane.hints:
                    flag_at["t"] = time.monotonic()
                    return
                time.sleep(0.1)

        watcher = _threading.Thread(target=watch, daemon=True)
        t_start = time.monotonic()
        watcher.start()
        # enough work that the slow node is still feeding well past
        # detection: exec 1 runs ~30 batches/partition x 4 at 80ms+
        parts = [[float(i) for i in range(120)] for _ in range(8)]
        cluster.train(parts, feed_timeout=120)
        # detection + the profile ack need a few more beats
        deadline = time.monotonic() + 20
        state1 = None
        while time.monotonic() < deadline:
            if plane.hints and state1 is not None:
                break
            node1 = next(
                n for n in cluster.cluster_info
                if n["executor_id"] == 1
            )
            try:
                v = cluster._connect(node1).get(
                    "profile_state"
                )._getvalue()
                if isinstance(v, dict):
                    state1 = v
            except Exception:
                pass
            time.sleep(0.3)

        # 1) flagged, the RIGHT node, the RIGHT phase, within a window
        assert plane.hints, "straggler never flagged"
        assert set(plane.hints) == {1}
        hint = plane.hints[1]
        assert hint["phase"] == "feed", hint
        assert flag_at["t"] - t_start <= window + 10.0
        # the monitor surfaced the same hint
        assert cluster.monitor.health_hints[1]["phase"] == "feed"

        # 2) the profiler fired on the flagged node ONLY
        assert state1 is not None, "profile request never acked"
        assert state1["seq"] >= 1
        node0 = next(
            n for n in cluster.cluster_info if n["executor_id"] == 0
        )
        v0 = cluster._connect(node0).get("profile_state")._getvalue()
        assert v0 is None, "profiler fired on the healthy node too"
        if state1.get("started"):
            # the capture landed on disk (graceful-degradation builds
            # report started=False instead)
            assert os.path.isdir(state1["log_dir"])

        cluster.shutdown(grace_secs=1, timeout=60)
    finally:
        engine.stop()
