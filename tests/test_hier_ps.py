"""Hierarchical (two-tier) parameter-server tests.

Unit: on-device optimizer parity vs the PS server's numpy rules, the
explicit ICI collectives (psum-mean / reduce-scatter) on a virtual
``ps``-axis mesh, server-side window ledger dedup, leader election.
Integration: pure-ICI convergence with the ZERO-host-readback telemetry
assert, DCN-tier convergence with exactly-once window applies, leader
failover with ledger/EF-epoch audit, the AsyncTrainer
``topology="hierarchical"`` facade, and the supervisor's leader
publication.  Multi-process ICI runs a real 2-process Gloo group; the
single-process mesh tests cover the collective math as well.
"""


import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tensorflowonspark_tpu import telemetry
from tensorflowonspark_tpu.parallel import hier_ps, ps
from tensorflowonspark_tpu.parallel.mesh import AXIS_PS, build_mesh

TARGET = np.asarray([1.0, -2.0, 0.5, 3.0], np.float32)


def quad_loss(params, batch):
    del batch
    return jnp.sum((params["w"] - TARGET) ** 2)


@pytest.fixture()
def shards():
    servers = [ps.ParamServerShard() for _ in range(2)]
    addrs = []
    for s in servers:
        host, port = s.start("127.0.0.1", 0)
        addrs.append("127.0.0.1:{0}".format(port))
    yield servers, addrs
    for s in servers:
        s.stop()


# --- on-device optimizers ---------------------------------------------


@pytest.mark.parametrize("spec", [
    ("sgd", {"learning_rate": 0.1}),
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}),
    ("adagrad", {"learning_rate": 0.05}),
    ("adam", {"learning_rate": 0.01}),
])
def test_device_optimizer_matches_numpy_server_rule(spec):
    # the local tier's jitted apply must be the SAME arithmetic the
    # global shards run — otherwise the two tiers drift by design
    rng = np.random.RandomState(0)
    p = {"w": rng.randn(7).astype(np.float32),
         "b": rng.randn(3).astype(np.float32)}
    dopt = hier_ps.build_device_optimizer(spec)
    state = dopt.init(p)
    nopt = ps._build_optimizer(spec)
    dev, host = dict(p), {k: v.copy() for k, v in p.items()}
    update = jax.jit(dopt.update)
    for i in range(4):
        g = {k: rng.randn(*v.shape).astype(np.float32)
             for k, v in p.items()}
        dev, state = update(dev, g, state)
        host = {k: nopt.update(k, host[k], g[k]) for k in host}
    for k in p:
        np.testing.assert_allclose(
            np.asarray(dev[k]), host[k], rtol=1e-5, atol=1e-6
        )


def test_unknown_device_optimizer_rejected():
    with pytest.raises(ValueError):
        hier_ps.build_device_optimizer(("magic", {})).init({"w": np.ones(2)})


# --- ICI collective math (single-process virtual mesh) -----------------


def test_ici_mean_matches_numpy():
    mesh = build_mesh({AXIS_PS: 4}, devices=jax.devices()[:4])
    rng = np.random.RandomState(1)
    stacked = {
        "a": rng.randn(4, 8, 3).astype(np.float32),
        "b": rng.randn(4, 16).astype(np.float32),
    }
    got = hier_ps.ici_mean(stacked, mesh)
    for k in stacked:
        np.testing.assert_allclose(
            np.asarray(got[k]), stacked[k].mean(0), rtol=1e-5, atol=1e-6
        )


def test_ici_reduce_scatter_mean_matches_psum():
    # the bandwidth-optimal form must be numerically the psum-mean
    mesh = build_mesh({AXIS_PS: 4}, devices=jax.devices()[:4])
    rng = np.random.RandomState(2)
    stacked = {"a": rng.randn(4, 8, 5).astype(np.float32)}
    rs = hier_ps.ici_reduce_scatter_mean(stacked, mesh)
    pm = hier_ps.ici_mean(stacked, mesh)
    np.testing.assert_allclose(
        np.asarray(rs["a"]), np.asarray(pm["a"]), rtol=1e-5, atol=1e-6
    )


def test_ici_helpers_width_one_is_identity():
    mesh = build_mesh({AXIS_PS: 1}, devices=jax.devices()[:1])
    stacked = {"a": np.arange(6, dtype=np.float32).reshape(1, 6)}
    got = hier_ps.ici_mean(stacked, mesh)
    np.testing.assert_array_equal(np.asarray(got["a"]), stacked["a"][0])


@pytest.mark.slow
def test_two_process_ici_mean(tmp_path):
    # REAL cross-process ICI aggregation (Gloo collectives)
    from conftest import launch_two_workers

    worker_src = """
import os, sys
rank, port = int(sys.argv[1]), int(sys.argv[2])
os.environ["JAX_PLATFORMS"] = "cpu"
# ONE device per process (the suite's 8-device forcing is inherited)
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
sys.path.insert(0, os.environ["TFOS_REPO"])
import numpy as np
import jax
jax.distributed.initialize(
    coordinator_address="127.0.0.1:%d" % port,
    num_processes=2, process_id=rank,
)
from tensorflowonspark_tpu.parallel import hier_ps
from tensorflowonspark_tpu.parallel.mesh import AXIS_PS, build_mesh
mesh = build_mesh({AXIS_PS: 2})
# ici_mean takes the GLOBAL [width, ...] stack (every process passes the
# same value; each holds only its own member row on its device), so the
# psum of rows [1, 2] crosses the process boundary: 1.5 everywhere
members = np.stack([np.full((4,), 1.0), np.full((4,), 2.0)]).astype(
    np.float32)
got = hier_ps.ici_mean({"g": members}, mesh)
from jax.experimental import multihost_utils
out = np.asarray(multihost_utils.process_allgather(np.asarray(got["g"])))
assert out.shape == (2, 4) and np.allclose(out, 1.5), out
print("ICI_OK", out.reshape(-1)[:2])
"""
    outputs = launch_two_workers(worker_src, tmp_path)
    assert all("ICI_OK" in o for o in outputs), outputs


# --- leader election ---------------------------------------------------


def test_elect_leader_lowest_live():
    assert hier_ps.elect_leader([3, 1, 2]) == 1
    assert hier_ps.elect_leader([3, 1, 2], dead=[1]) == 2
    assert hier_ps.elect_leader([3, 1, 2], dead=[1, 2]) == 3
    with pytest.raises(RuntimeError):
        hier_ps.elect_leader([1], dead=[1])


def test_current_leader_reads_kv():
    class _Mgr(object):
        def __init__(self, v):
            self.v = v

        def get(self, key):
            assert key == "hier_leader"
            return self.v

    assert hier_ps.current_leader(_Mgr(2)) == 2
    assert hier_ps.current_leader(_Mgr(None), default=7) == 7

    class _Broken(object):
        def get(self, key):
            raise IOError("kv gone")

    assert hier_ps.current_leader(_Broken(), default=0) == 0


def test_supervisor_publishes_leader():
    # the supervisor's election hook: lowest peer at the generation
    from tensorflowonspark_tpu.cluster.supervisor import Supervisor

    sup = Supervisor.__new__(Supervisor)

    class _Ctx(object):
        executor_id = 1

    class _Mgr(object):
        def __init__(self):
            self.kv = {}

        def set(self, k, v):
            self.kv[k] = v

    sup.ctx = _Ctx()
    sup.mgr = _Mgr()
    sup.generation = 3
    sup.compute_eids = [0, 1, 2]
    assert sup._publish_leader([1, 2]) == 1
    assert sup.mgr.kv["hier_leader"] == 1

    class _Client(object):
        def get_liveness(self):
            return {
                "0": {"generation": 1},   # dead: never re-registered
                "1": {"generation": 3},
                "2": {"generation": 3},
            }, {}

    assert sup._peers_at_generation(_Client(), 3) == [1, 2]


# --- server-side window ledger ----------------------------------------


def test_window_dedup_applies_once(shards):
    servers, addrs = shards
    client = ps.PSClient(addrs)
    client.init({"w": np.zeros(4, np.float32)}, ("delta", {}))
    d = {"w": np.ones(4, np.float32)}
    p1 = client.push_pull(d, header_extra={"pod": "p", "window": 0})
    np.testing.assert_allclose(p1["w"], 1.0)
    # duplicate window: NOT re-applied, live params replied
    p2 = client.push_pull(d, header_extra={"pod": "p", "window": 0})
    np.testing.assert_allclose(p2["w"], 1.0)
    p3 = client.push_pull(d, header_extra={"pod": "p", "window": 1})
    np.testing.assert_allclose(p3["w"], 2.0)
    # per-shard apply logs carry no duplicates
    for s in servers:
        assert len(set(s.applied_log)) == len(s.applied_log)
    assert client.window_floor("p") == 1
    assert client.window_floor("other-pod") == -1
    client.close()


def test_windowless_push_unaffected_by_ledger(shards):
    _, addrs = shards
    client = ps.PSClient(addrs)
    client.init({"w": np.zeros(2, np.float32)}, ("sgd", {"learning_rate": 1.0}))
    g = {"w": np.ones(2, np.float32)}
    client.push_pull(g)
    out = client.push_pull(g)  # no pod/window headers: both apply
    np.testing.assert_allclose(out["w"], -2.0)
    client.close()


# --- the trainer: pure ICI tier ---------------------------------------


def test_pure_ici_converges_with_zero_readback():
    tracer = telemetry.get_tracer()
    tracer.clear()
    tr = hier_ps.HierTrainer(
        quad_loss, None, optimizer=("sgd", {"learning_rate": 0.05}),
        push_every=4,
    )
    tr.init({"w": np.zeros(4, np.float32)})
    for _ in range(150):
        out = tr.step(None)
    # THE hierarchical contract: the in-pod path never reads gradients
    # back to the host (the flat plane's measured 100x wall)
    assert tracer.count("grad_readback") == 0
    # the returned tree is device-resident
    assert isinstance(out["w"], jax.Array)
    np.testing.assert_allclose(np.asarray(out["w"]), TARGET, atol=1e-2)
    assert float(jax.device_get(tr.last_loss())) < 1e-4
    tr.stop()


def test_push_every_validated():
    with pytest.raises(ValueError):
        hier_ps.HierTrainer(quad_loss, None, push_every=0)
    with pytest.raises(ValueError):
        hier_ps.HierTrainer(quad_loss, None, members=(1, 2), member_id=0)


# --- the trainer: DCN tier --------------------------------------------


def test_dcn_tier_converges_and_server_tracks_local(shards):
    servers, addrs = shards
    tracer = telemetry.get_tracer()
    tracer.clear()
    tr = hier_ps.HierTrainer(
        quad_loss, addrs, optimizer=("sgd", {"learning_rate": 0.05}),
        push_every=2, codec="int8", reply_codec="same",
    )
    tr.init({"w": np.zeros(4, np.float32)})
    for _ in range(100):
        tr.step(None)
    out = jax.device_get(tr.drain())
    np.testing.assert_allclose(out["w"], TARGET, atol=1e-2)
    # the compressed-delta feedback loop keeps the global tier locked
    # to the local one (EF telescoping + reply correction)
    probe = ps.PSClient(addrs)
    probe.init({"w": np.zeros(4, np.float32)}, ("delta", {}))
    srv = probe.pull()
    probe.close()
    np.testing.assert_allclose(np.asarray(srv["w"]), out["w"], atol=1e-3)
    # exactly-once window applies, contiguous sequences, on EVERY shard
    for s in servers:
        assert len(set(s.applied_log)) == len(s.applied_log)
        seqs = sorted(w for _, w in s.applied_log)
        assert seqs == list(range(len(seqs)))
    # zero grad_readback even WITH the DCN tier active; the leader's
    # window readback traces under its own (cadence-amortized) name
    assert tracer.count("grad_readback") == 0
    assert tracer.count("hier.dcn_readback") > 0
    assert tracer.count("hier.dcn_push") > 0
    ledger = tr.dcn_epochs()[-1]
    assert ledger["pushed"] and ledger["pushed"] == ledger["acked"]
    assert ledger["pending"] == []
    tr.stop()


def test_dcn_bounded_staleness_window_count(shards):
    # push_every=5 over 20 steps -> exactly 4 windows, ids 0..3
    _, addrs = shards
    tr = hier_ps.HierTrainer(
        quad_loss, addrs, optimizer=("sgd", {"learning_rate": 0.05}),
        push_every=5,
    )
    tr.init({"w": np.zeros(4, np.float32)})
    for _ in range(20):
        tr.step(None)
    tr.drain()
    ledger = tr.dcn_epochs()[-1]
    assert ledger["pushed"] == [0, 1, 2, 3]
    assert ledger["acked"] == [0, 1, 2, 3]
    tr.stop()


def test_leader_failover_exactly_once_and_loss_parity(shards):
    servers, addrs = shards

    spent = []

    def fault(seq):
        if seq >= 3 and not spent:
            spent.append(seq)
            raise hier_ps.LeaderKilled("chaos kill at window %d" % seq)

    tr = hier_ps.HierTrainer(
        quad_loss, addrs, optimizer=("sgd", {"learning_rate": 0.05}),
        push_every=2, codec="int8", reply_codec="same",
        members=(0, 1), member_id=0, fault_fn=fault,
    )
    tr.init({"w": np.zeros(4, np.float32)})
    for _ in range(100):
        tr.step(None)
    out = jax.device_get(tr.drain())
    # loss parity: the kill cost re-pushes, not convergence
    np.testing.assert_allclose(out["w"], TARGET, atol=1e-2)
    epochs = tr.dcn_epochs()
    assert len(epochs) == 2, epochs
    dead, live = epochs
    assert dead["member"] == 0 and live["member"] == 1
    # the successor KEEPS pushing new windows after taking over (not
    # just the re-pushed backlog): the global tier must track the pod
    # through the failover, not freeze at the death point
    assert max(live["acked"]) > max(dead["pushed"])
    probe = ps.PSClient(addrs)
    probe.init({"w": np.zeros(4, np.float32)}, ("delta", {}))
    srv = probe.pull()
    probe.close()
    np.testing.assert_allclose(np.asarray(srv["w"]), out["w"], atol=1e-3)
    # the successor resumed AFTER the server's applied floor and
    # re-pushed the dead epoch's pending windows
    assert live["resumed_from"] >= 2
    assert live["pending"] == []
    # EF state is per-epoch: the successor's client started with a
    # clean residual (fresh connection, fresh ErrorFeedback)
    # exactly-once on every shard, no gaps
    for s in servers:
        assert len(set(s.applied_log)) == len(s.applied_log)
        seqs = sorted(w for _, w in s.applied_log)
        assert seqs == list(range(len(seqs)))
    tr.stop()


def test_failover_exhausted_members_reraises(shards):
    _, addrs = shards

    def fault(seq):
        raise hier_ps.LeaderKilled("always")

    tr = hier_ps.HierTrainer(
        quad_loss, addrs, optimizer=("sgd", {"learning_rate": 0.05}),
        push_every=1, members=(0,), member_id=0, fault_fn=fault,
    )
    tr.init({"w": np.zeros(4, np.float32)})
    with pytest.raises(hier_ps.LeaderKilled):
        for _ in range(20):
            tr.step(None)
        tr.drain()
    tr.stop()


def test_non_leader_drops_windows_but_keeps_state(shards):
    # a non-leader member computes the same local state but never
    # pushes; its base advances in lockstep so a takeover is clean
    servers, addrs = shards
    tr = hier_ps.HierTrainer(
        quad_loss, addrs, optimizer=("sgd", {"learning_rate": 0.05}),
        push_every=2, members=(0, 1), member_id=1,  # leader is 0, we are 1
    )
    tr.init({"w": np.zeros(4, np.float32)})
    for _ in range(100):
        out = tr.step(None)
    tr.drain()
    ledger = tr.dcn_epochs()[-1]
    assert ledger["pushed"] == []  # never pushed
    for s in servers:
        assert s.applied_log == []
    np.testing.assert_allclose(np.asarray(out["w"]), TARGET, atol=1e-2)
    tr.stop()


def test_leadership_gain_resyncs_window_floor(shards):
    # leader_fn flips mid-run: the member must resync its sequence
    # from the server ledger before its first push
    _, addrs = shards
    lead = {"id": 1}
    tr = hier_ps.HierTrainer(
        quad_loss, addrs, optimizer=("sgd", {"learning_rate": 0.05}),
        push_every=2, members=(0, 1), member_id=0,
        leader_fn=lambda: lead["id"],
    )
    tr.init({"w": np.zeros(4, np.float32)})
    for _ in range(10):
        tr.step(None)  # not leader: nothing pushed
    assert tr.dcn_epochs()[-1]["pushed"] == []
    lead["id"] = 0  # gained the duty
    for _ in range(10):
        tr.step(None)
    tr.drain()
    ledger = tr.dcn_epochs()[-1]
    assert ledger["pushed"] and ledger["pushed"][0] == 0  # floor was -1
    tr.stop()


# --- AsyncTrainer facade ----------------------------------------------


def test_async_trainer_hierarchical_topology(shards):
    _, addrs = shards
    tracer = telemetry.get_tracer()
    tracer.clear()
    w = ps.AsyncTrainer(
        quad_loss, addrs, optimizer=("sgd", {"learning_rate": 0.05}),
        topology="hierarchical", push_every=4, codec="int8",
        reply_codec="same",
    )
    p = w.init({"w": np.zeros(4, np.float32)})
    # 200 steps (not 120): under full-suite load the async push window
    # lands fewer effective updates and 120 left one coordinate just
    # past atol once — the quadratic converges geometrically, so the
    # extra steps buy margin without changing what's under test
    for _ in range(200):
        p = w.step(p, None)
    w.drain()
    np.testing.assert_allclose(
        np.asarray(jax.device_get(p)["w"]), TARGET, atol=1e-2
    )
    # the wire accounting surfaces through the same client attribute
    # the flat trainer exposes (bench relies on it)
    assert w.client.bytes_sent > 0
    assert w.client.bytes_recv > 0
    assert tracer.count("grad_readback") == 0
    w.stop()


def test_async_trainer_rejects_bad_topology():
    with pytest.raises(ValueError):
        ps.AsyncTrainer(quad_loss, [], topology="diagonal")


# --- feed-driven hierarchical loop ------------------------------------


class _ListFeed(object):
    def __init__(self, batches):
        self._batches = list(batches)
        self._i = 0

    def next_batch(self, batch_size):
        if self._i >= len(self._batches):
            return []
        b = self._batches[self._i]
        self._i += 1
        return b

    def should_stop(self):
        return self._i >= len(self._batches)


def test_train_on_feed_steps_and_stops(shards):
    _, addrs = shards
    rows = [{"x": np.float32(0.0)}] * 2
    feed = _ListFeed([list(rows)] * 12)
    tr = hier_ps.HierTrainer(
        quad_loss, addrs, optimizer=("sgd", {"learning_rate": 0.05}),
        push_every=3,
        mesh=build_mesh({AXIS_PS: 1}, devices=jax.devices()[:1]),
    )
    tr.init({"w": np.zeros(4, np.float32)})
    seen = []
    steps = tr.train_on_feed(
        feed, 2, max_steps=8, step_callback=seen.append,
    )
    assert steps == 8
    assert seen == list(range(8))
    ledger = tr.dcn_epochs()[-1]
    # 8 steps at push_every=3 -> windows 0,1 on cadence + the drain's
    # partial window
    assert ledger["pushed"] == [0, 1, 2]
    tr.stop()


# --- overlapped split step (ISSUE 12 satellite) -----------------------


def test_overlap_step_parity_vs_serial():
    # overlap=True splits the fused step into backward (+ICI psum
    # tail) and apply, dispatched without an intervening sync — the op
    # sequence is identical, so params must match the serial trainer's
    # step for step
    def run(overlap):
        tr = hier_ps.HierTrainer(
            quad_loss, None,
            optimizer=("adam", {"learning_rate": 0.05}),
            overlap=overlap,
        )
        tr.init({"w": np.zeros(4, np.float32)})
        for _ in range(200):
            tr.step(None)
        tr.drain()
        return np.asarray(tr.params["w"])

    serial = run(False)
    overlapped = run(True)
    np.testing.assert_allclose(overlapped, serial, atol=1e-6)
    np.testing.assert_allclose(overlapped, TARGET, atol=1e-2)


def test_overlap_spans_record_pipeline_overlap():
    # the telemetry contract: apply span N stays OPEN until grad N+1
    # has been dispatched — the recorded intervals overlap, which is
    # the span-asserted statement of the dispatch pipeline
    tracer = telemetry.get_tracer()
    tracer.clear()
    tr = hier_ps.HierTrainer(
        quad_loss, None, optimizer=("sgd", {"learning_rate": 0.05}),
        overlap=True,
    )
    tr.init({"w": np.zeros(4, np.float32)})
    n_steps = 8
    for _ in range(n_steps):
        tr.step(None)
    tr.drain()
    grads = sorted(
        tracer.spans("hier.overlap_grad"),
        key=lambda s: s["attrs"]["step"],
    )
    applies = sorted(
        tracer.spans("hier.overlap_apply"),
        key=lambda s: s["attrs"]["step"],
    )
    assert len(grads) == n_steps
    assert len(applies) == n_steps  # drain closed the last one
    for i in range(n_steps - 1):
        a = applies[i]
        g_next = grads[i + 1]
        # apply i opened before grad i+1 started...
        assert a["t0"] <= g_next["t0"]
        # ...and closed only after grad i+1 was dispatched: overlap
        assert a["t0"] + a["dur"] >= g_next["t0"] + g_next["dur"]
    # the overlapped path still never reads gradients back
    assert tracer.count("grad_readback") == 0


def test_overlap_composes_with_dcn_tier(shards):
    # the split step under a real DCN link: windows still ship, the
    # ledger still dedups, convergence holds
    servers, addrs = shards
    tr = hier_ps.HierTrainer(
        quad_loss, addrs, optimizer=("sgd", {"learning_rate": 0.05}),
        push_every=4, overlap=True,
    )
    tr.init({"w": np.zeros(4, np.float32)})
    for _ in range(80):
        tr.step(None)
    tr.drain()
    np.testing.assert_allclose(np.asarray(tr.params["w"]), TARGET,
                               atol=1e-2)
    led = tr.dcn_epochs()[-1]
    assert led["acked"], led
    tr.stop()
