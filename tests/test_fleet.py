"""Fleet serving plane tests (ISSUE 13 tentpole).

Covers the serving plane one level above the engine
(`tensorflowonspark_tpu/fleet/`): the ReplicaSet lifecycle and load
snapshots, the FleetRouter's dispatch policies (least-loaded /
prefix-affinity / weighted round-robin / random, plus the pluggable-
callable seam), fleet-level admission (spill to a sibling before any
single engine sheds), committed-token-safe re-dispatch on replica
death, slow-replica evict/probe/re-admit, and zero-downtime rolling
deploys with canary-burn halt — on fake decoders for the scheduler
logic and on the real tiny transformer for the token-identity and
acceptance e2e paths.
"""

import os
import time

import numpy as np
import pytest

from tensorflowonspark_tpu import serving, serving_engine, telemetry
from tensorflowonspark_tpu.fleet.deploy import RollingDeploy
from tensorflowonspark_tpu.fleet.replica import ReplicaSet
from tensorflowonspark_tpu.fleet.router import (
    FLEET_BUDGET_COL,
    FleetRouter,
)
from tensorflowonspark_tpu.telemetry import journal as journal_mod
from tensorflowonspark_tpu.testing import chaos

TINY = {
    "vocab_size": 64, "num_layers": 2, "num_heads": 2, "head_dim": 8,
    "embed_dim": 16, "mlp_dim": 32, "max_seq_len": 96, "dtype": "float32",
}


# ----------------------------------------------------------------------
# fakes: a deterministic greedy "model" with the full SlotDecoder
# surface — scheduler logic tests pay no compile time
# ----------------------------------------------------------------------


def _next_token(context):
    # greedy semantics: next token is a pure function of the context
    # so far — re-dispatching prompt+committed onto ANY replica must
    # continue the exact sequence (the committed-token invariant)
    return (sum(context) + len(context)) % 50


class FakeDecoder(object):
    eos_id = None
    cache_len = 4096

    def __init__(self, n, chunk=4, max_new=8, delay=0.0):
        self.num_slots = int(n)
        self.chunk_size = int(chunk)
        self.max_new_tokens = int(max_new)
        self.delay = float(delay)
        self.active = {}
        self.weight_generation = 0
        self.params = "v0"
        self.chunks = 0

    def free_slots(self):
        return [i for i in range(self.num_slots)
                if i not in self.active]

    def admit(self, slot, prompt):
        ctx = [int(t) for t in prompt]
        first = _next_token(ctx)
        self.active[slot] = ctx + [first]
        return first

    def step_chunk(self):
        self.chunks += 1
        if self.delay:
            time.sleep(self.delay)
        out = np.zeros((self.num_slots, self.chunk_size), np.int32)
        for slot, ctx in self.active.items():
            for j in range(self.chunk_size):
                t = _next_token(ctx)
                ctx.append(t)
                out[slot, j] = t
        return out

    def evict(self, slot):
        self.active.pop(slot, None)

    cancel = evict

    def reset(self):
        self.active.clear()

    # hot-swap surface (fleet/deploy.py drives it)
    def param_spec(self):
        return {"w": {"shape": [1], "dtype": "float32"}}

    def snapshot_weights(self):
        return self.params

    def swap_weights(self, params, draft=None):
        if params == "refuse":
            raise ValueError("shape mismatch at w")
        self.params = params
        self.weight_generation += 1

    def restore_weights(self, snapshot):
        self.params = snapshot
        self.weight_generation += 1

    def canary_check(self):
        return self.params != "burn"


class FakePredict(object):
    column_padding = {"tokens": 0}

    def __init__(self, chunk=4, max_new=8, delay=0.0):
        self._args = (chunk, max_new, delay)

    def make_slot_decoder(self, n, chunk=None):
        c, max_new, delay = self._args
        return FakeDecoder(
            n, chunk=chunk or c, max_new=max_new, delay=delay
        )


def _fake_router(n=2, slots=2, max_new=8, chunk=4, **kw):
    kw.setdefault("poll_sec", 0.01)
    return FleetRouter(
        None, {"prompt": "tokens"}, replicas=n, num_slots=slots,
        predict_factory=lambda: FakePredict(chunk=chunk,
                                            max_new=max_new),
        **kw
    )


def _fake_reference(rows, slots=2, max_new=8, chunk=4):
    """Single fake engine, the token-identity oracle."""
    eng = serving_engine.ServingEngine(
        FakePredict(chunk=chunk, max_new=max_new),
        {"prompt": "tokens"}, None, slots, on_error="record",
    )
    return list(eng.serve([dict(r) for r in rows]))


def _prompts(lens, vocab=50, seed=7):
    rng = np.random.RandomState(seed)
    return [{"prompt": rng.randint(1, vocab, (n,)).astype(np.int32)}
            for n in lens]


def _same_tokens(a, b):
    return np.array_equal(
        np.asarray(a["generated"]), np.asarray(b["generated"])
    )


# ----------------------------------------------------------------------
# engine load() snapshot (satellite)
# ----------------------------------------------------------------------


class TestLoadSnapshot:
    def test_load_fields_and_health_status_agree(self):
        eng = serving_engine.ServingEngine(
            FakePredict(), {"prompt": "tokens"}, None, 3,
            queue_depth=5,
        )
        snap = eng.load()
        assert snap == {
            "slots": 3, "free_slots": 3, "in_flight": 0, "queued": 0,
            "queue_depth": 5, "prefix_blocks": 0,
            "weight_generation": 0, "draining": False,
        }
        hs = eng.health_status()
        for key in snap:
            if key in hs:
                assert hs[key] == snap[key]
        # /status carries the router's placement fields per engine
        assert {"free_slots", "queued", "queue_depth",
                "prefix_blocks"} <= set(hs)

    def test_load_is_zero_telemetry_when_disabled(self):
        telemetry.set_enabled(False)
        try:
            eng = serving_engine.ServingEngine(
                FakePredict(), {"prompt": "tokens"}, None, 2,
            )
            before = telemetry.get_registry().snapshot()
            for _ in range(64):
                snap = eng.load()
            after = telemetry.get_registry().snapshot()
            # no metric allocated, no registry traffic; plain host
            # scalars only
            assert before == after
            assert all(
                isinstance(v, (int, bool)) for v in snap.values()
            )
        finally:
            telemetry.set_enabled(True)


# ----------------------------------------------------------------------
# dispatch policies
# ----------------------------------------------------------------------


class TestDispatchPolicies:
    def test_least_loaded_vs_rr_queue_depth_invariant(self):
        # a pluggable-callable wrapper records the router's assigned
        # depth at every send: NO replica may ever exceed its
        # capacity (slots + engine queue bound) under either policy
        rows = _prompts([5, 7, 3, 9, 4, 6, 8, 5, 7, 3, 9, 4, 6, 8, 5, 7])
        for name in ("least_loaded", "weighted_rr"):
            from tensorflowonspark_tpu.fleet.router import (
                DISPATCH_POLICIES,
            )

            seen = []

            def spy(router, req, candidates, _inner=DISPATCH_POLICIES[name]):
                pick = _inner(router, req, candidates)
                seen.append(
                    (pick.replica_id,
                     router._assigned_count(pick.replica_id),
                     pick.capacity())
                )
                return pick

            router = _fake_router(
                n=2, slots=2, dispatch=spy, policy="reject",
            )
            out = list(router.serve([dict(r) for r in rows]))
            router.close()
            assert len(out) == len(rows)
            assert all("error" not in r for r in out)
            assert seen, "policy never consulted"
            assert all(depth < cap for _rid, depth, cap in seen)
            # both replicas took real work
            per = router.stats["per_replica"]
            assert all(per[r]["admitted"] > 0 for r in per)

    def test_weighted_rr_respects_weights(self):
        # ample capacity -> the whole burst dispatches in one pass,
        # so smooth WRR counts are exact: 3:1
        rows = _prompts([4] * 16)
        router = _fake_router(
            n=2, slots=8, dispatch="weighted_rr", policy="reject",
            replica_queue_depth=16,
            replica_weights={0: 3.0, 1: 1.0},
        )
        out = list(router.serve([dict(r) for r in rows]))
        router.close()
        assert len(out) == 16
        per = router.stats["per_replica"]
        assert per[0]["admitted"] == 12
        assert per[1]["admitted"] == 4

    def test_prefix_affinity_routes_family_to_one_replica(self):
        # 2 families x 6 requests sharing 16-token heads: affinity
        # must keep each family on one replica (imbalance off)
        rng = np.random.RandomState(5)
        heads = [rng.randint(1, 50, (16,)) for _ in range(2)]
        rows = []
        fam = []
        for i in range(12):
            h = heads[i % 2]
            rows.append({"prompt": np.concatenate(
                [h, rng.randint(1, 50, (3,))]
            ).astype(np.int32)})
            fam.append(i % 2)
        picks = {}

        def spy(router, req, candidates):
            from tensorflowonspark_tpu.fleet.router import (
                DISPATCH_POLICIES,
            )

            pick = DISPATCH_POLICIES["prefix_affinity"](
                router, req, candidates
            )
            picks.setdefault(req["fingerprint"], set()).add(
                pick.replica_id
            )
            return pick

        # ample per-replica room: no capacity spill — pure affinity
        router = _fake_router(
            n=2, slots=2, dispatch=spy, policy="reject",
            replica_queue_depth=12, imbalance=10 ** 6,
        )
        out = list(router.serve([dict(r) for r in rows]))
        router.close()
        assert len(out) == 12
        assert len(picks) == 2  # two fingerprints
        for replicas_hit in picks.values():
            assert len(replicas_hit) == 1  # consistent routing
        assert router.stats["affinity_hits"] == 12

    def test_outputs_in_input_order_and_token_identical_fake(self):
        rows = _prompts([5, 9, 3, 7, 4, 8, 6, 5, 9, 3])
        ref = _fake_reference(rows)
        for name in ("least_loaded", "prefix_affinity",
                     "weighted_rr", "random"):
            router = _fake_router(n=3, slots=2, dispatch=name)
            out = list(router.serve([dict(r) for r in rows]))
            router.close()
            assert len(out) == len(rows)
            assert all(
                _same_tokens(a, b) for a, b in zip(ref, out)
            ), name

    def test_unknown_policy_named(self):
        with pytest.raises(ValueError, match="least_loaded"):
            _fake_router(dispatch="fastest_wins")


# ----------------------------------------------------------------------
# fleet admission: spill before shed, degrade budgets
# ----------------------------------------------------------------------


class TestFleetAdmission:
    def test_reject_sheds_typed_records_beyond_fleet_bound(self):
        # burst far beyond (fleet queue + replica capacity): the
        # overflow sheds with typed records at its input positions —
        # and NO replica engine ever shed (spill-before-shed)
        rows = _prompts([4] * 30)
        router = _fake_router(
            n=2, slots=2, replica_queue_depth=2, policy="reject",
            queue_depth=4,
        )
        out = list(router.serve([dict(r) for r in rows]))
        router.close()
        assert len(out) == 30
        shed = [r for r in out if "error" in r]
        assert shed and all(
            r["error"]["kind"] == "shed" for r in shed
        )
        assert all(
            "fleet admission queue" in r["error"]["message"]
            for r in shed
        )
        assert router.stats["shed"] == len(shed)
        # served + shed account for everything; positions line up
        for i, r in enumerate(out):
            if "error" in r:
                assert r["error"]["request_index"] == i
        # the engines themselves never invoked their shed policy
        per = router.stats["per_replica"]
        assert all(per[r]["shed"] == 0 for r in per)

    def test_degrade_shrinks_budgets_against_fleet_backlog(self):
        rows = _prompts([4] * 24)
        router = _fake_router(
            n=2, slots=2, replica_queue_depth=2, policy="degrade",
            queue_depth=4, max_new=8,
        )
        out = list(router.serve([dict(r) for r in rows]))
        router.close()
        assert len(out) == 24
        assert all("error" not in r for r in out)
        assert router.stats["degraded"] > 0
        lens = [int(r["generated_len"]) for r in out]
        assert min(lens) < 8  # someone got a shrunk budget
        assert max(lens) == 8  # early admits kept theirs

    def test_block_backpressures_source(self):
        pulled = []

        def source():
            for i, r in enumerate(_prompts([4] * 12)):
                pulled.append(i)
                yield r

        router = _fake_router(
            n=2, slots=2, replica_queue_depth=1, policy="block",
        )
        out = list(router.serve(source()))
        router.close()
        assert len(out) == 12 and len(pulled) == 12
        assert router.stats["shed"] == 0


# ----------------------------------------------------------------------
# replica death + slow replica (chaos satellites)
# ----------------------------------------------------------------------


class TestReplicaFaults:
    def test_kill_replica_redispatches_committed_tokens(self, tmp_path):
        rows = _prompts([6, 8, 5, 7, 9, 4, 6, 8, 5, 7, 9, 4])
        ref = _fake_reference(rows, max_new=12, chunk=2)
        plan = chaos.ChaosPlan().kill_replica(1, at_chunk=2)
        path = plan.save(str(tmp_path / "plan.json"))
        os.environ[chaos.TFOS_CHAOS_PLAN] = path
        j0 = len(journal_mod.get_journal().events(kind="replica_dead"))
        try:
            router = _fake_router(n=3, slots=2, max_new=12, chunk=2)
            out = list(router.serve([dict(r) for r in rows]))
            router.close()
        finally:
            del os.environ[chaos.TFOS_CHAOS_PLAN]
        # every request accounted for, token-identical to the
        # single-engine oracle (committed prefixes continued exactly)
        assert len(out) == len(rows)
        assert all("error" not in r for r in out)
        assert all(_same_tokens(a, b) for a, b in zip(ref, out))
        assert router.stats["replica_deaths"] == 1
        assert router.stats["redispatched"] >= 1
        assert not router.replicas[1].alive
        # death and re-dispatch are typed journal events
        j = journal_mod.get_journal()
        assert len(j.events(kind="replica_dead")) > j0
        assert j.events(kind="fleet_redispatch")

    def test_slow_replica_routed_around_then_readmitted(self, tmp_path):
        plan = chaos.ChaosPlan().slow_replica(
            0, per_chunk_sec=0.3, chunks=2
        )
        path = plan.save(str(tmp_path / "plan.json"))
        os.environ[chaos.TFOS_CHAOS_PLAN] = path
        try:
            # a small BASE chunk cost bounds the healthy replica's
            # throughput so the stream outlives the slow window —
            # probe traffic must exist after the straggler recovers;
            # a 1-deep replica queue keeps the straggler's backlog
            # (which must drain before clean probes) short
            router = FleetRouter(
                None, {"prompt": "tokens"}, replicas=2, num_slots=1,
                predict_factory=lambda: FakePredict(
                    chunk=4, max_new=4, delay=0.015
                ),
                replica_queue_depth=1, poll_sec=0.01,
                suspect_rounds=1, probe_every=2, readmit_rounds=2,
                min_slow_sec=0.1, slow_factor=3.0,
            )
            rows = _prompts([4] * 80)
            out = list(router.serve([dict(r) for r in rows]))
            router.close()
        finally:
            del os.environ[chaos.TFOS_CHAOS_PLAN]
        assert len(out) == 80
        assert all("error" not in r for r in out)
        assert router.stats["evicted"] >= 1
        assert router.stats["readmitted"] >= 1
        assert router.replicas[0].state == "live"  # re-admitted
        j = journal_mod.get_journal()
        assert j.events(kind="replica_evicted")
        assert j.events(kind="replica_readmitted")


# ----------------------------------------------------------------------
# rolling deploys (fake engines)
# ----------------------------------------------------------------------


class TestRollingDeployFake:
    def _run_with_deploy(self, router, rows, deploy_at=4, **deploy_kw):
        dep = None
        out = []
        for i, r in enumerate(router.serve(rows)):
            out.append(r)
            if i == deploy_at and dep is None:
                dep = router.start_rolling_deploy(**deploy_kw)
        return out, dep

    def test_rolling_deploy_all_replicas_zero_drop(self):
        # the commit gate needs LIVE traffic (a replica proves its
        # new generation on real requests): pace the source so the
        # stream spans all three drain->swap->gate rounds
        router = FleetRouter(
            None, {"prompt": "tokens"}, replicas=3, num_slots=2,
            predict_factory=lambda: FakePredict(
                chunk=4, max_new=8, delay=0.01
            ),
            engine_opts={"rollback_window": 1}, poll_sec=0.01,
        )

        def paced():
            for r in _prompts([4] * 120):
                time.sleep(0.01)
                yield dict(r)

        out, dep = self._run_with_deploy(
            router, paced(), params="v1", step=7, phase_timeout=30.0,
        )
        router.close()
        assert len(out) == 120
        assert all("error" not in r for r in out)  # swap_dropped == 0
        assert dep.status["state"] == "done"
        assert sorted(dep.status["replicas_done"]) == [0, 1, 2]
        assert all(
            g >= 1 for g in dep.status["generations"].values()
        )
        assert router.stats["swaps"] == 3
        assert router.stats["swap_commits"] == 3
        j = journal_mod.get_journal()
        assert j.events(kind="deploy_done")

    def test_canary_burn_halts_fleet_on_old_generation(self):
        # the canary's post-install canary_check fails ("burn"
        # params): the engine rolls ITSELF back, the rollout halts
        # fleet-wide, and replicas 1/2 never see a swap
        j0 = len(journal_mod.get_journal().events(kind="deploy_halted"))
        router = _fake_router(
            n=3, slots=2, engine_opts={"rollback_window": 1},
        )
        rows = [dict(r) for r in _prompts([4] * 30)]
        out, dep = self._run_with_deploy(
            router, rows, params="burn", step=9,
        )
        router.close()
        assert len(out) == 30
        assert all("error" not in r for r in out)
        assert dep.status["state"] == "halted"
        assert dep.status["halted"]["kind"] == "canary_failed"
        assert dep.status["halted"]["replica"] == 0
        assert dep.status["replicas_done"] == []
        # siblings untouched; the canary rolled back (its generation
        # moved through swap+restore but serves the OLD weights)
        assert router.replicas[0].engine.decoder.params == "v0"
        for rid in (1, 2):
            assert router.replicas[rid].stats["swaps"] == 0
        j = journal_mod.get_journal()
        assert len(j.events(kind="deploy_halted")) > j0

    def test_install_refusal_halts(self):
        router = _fake_router(
            n=2, slots=2, engine_opts={"rollback_window": 1},
        )
        rows = [dict(r) for r in _prompts([4] * 20)]
        out, dep = self._run_with_deploy(
            router, rows, params="refuse", step=3,
            refuse_grace=0.2, phase_timeout=20.0,
        )
        router.close()
        assert len(out) == 20
        assert dep.status["state"] == "halted"
        assert dep.status["halted"]["kind"] == "install_refused"
        assert router.replicas[1].stats["swaps"] == 0

    def test_exactly_one_deploy_at_a_time(self):
        router = _fake_router(n=2, slots=2)
        router.start_rolling_deploy(params="v1")
        with pytest.raises(RuntimeError, match="already in progress"):
            router.start_rolling_deploy(params="v2")
        router.close()

    def test_deploy_needs_exactly_one_weight_source(self):
        with pytest.raises(ValueError, match="exactly one"):
            RollingDeploy()
        with pytest.raises(ValueError, match="exactly one"):
            RollingDeploy(params="x", step_dir="/tmp/x")


# ----------------------------------------------------------------------
# real-model fleet: token identity, affinity hit rate, acceptance e2e
# ----------------------------------------------------------------------


def _gen_predict(max_new=6, extra=None):
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models import transformer as tr

    model = tr.Transformer(tr.TransformerConfig(**TINY))
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    cfg = dict(TINY, mode="generate", max_new_tokens=max_new,
               pad_multiple=16, **(extra or {}))
    predict = tr.serving_builder(
        jax.tree.map(np.asarray, params), cfg
    )
    return params, predict


@pytest.fixture(scope="module")
def shared_predicts():
    """One compiled predictor trio shared across the real-model fleet
    tests (make_replica per extra replica — each owns its decoder but
    the compile cost is paid once per module)."""
    _params, predict = _gen_predict(max_new=6, extra={"chunk_size": 2})
    return [predict, predict.make_replica(), predict.make_replica()]


def _shared_factory(predicts):
    it = iter(predicts)
    return lambda: next(it)


class TestRealFleet:
    def test_predict_rows_replicas_token_identical(self, shared_predicts):
        # the serving.predict_rows(replicas=N) surface end to end —
        # fleet outputs must match the single-engine run bit-for-bit
        predict = shared_predicts[0]
        rows = _prompts([5, 9, 14, 3, 8, 12, 7, 6], vocab=64, seed=13)
        ref = list(serving.predict_rows(
            predict, [dict(r) for r in rows], {"prompt": "tokens"},
            batch_size=2, schedule="continuous",
        ))
        stats = {}
        out = list(serving.predict_rows(
            predict, [dict(r) for r in rows], {"prompt": "tokens"},
            batch_size=2, schedule="continuous", replicas=2,
            stats=stats,
        ))
        assert len(out) == len(rows)
        assert all(_same_tokens(a, b) for a, b in zip(ref, out))
        assert stats["completed"] == len(rows)
        assert stats["replicas"] == 2

    def test_every_policy_token_identical_real(self, shared_predicts):
        predict = shared_predicts[0]
        rows = _prompts([5, 9, 14, 3, 8, 12, 7, 6, 11, 4],
                        vocab=64, seed=21)
        ref = list(serving.predict_rows(
            predict, [dict(r) for r in rows], {"prompt": "tokens"},
            batch_size=2, schedule="continuous",
        ))
        for name in ("least_loaded", "prefix_affinity",
                     "weighted_rr", "random"):
            router = FleetRouter(
                None, {"prompt": "tokens"}, replicas=3, num_slots=2,
                predict_factory=_shared_factory(shared_predicts),
                dispatch=name, poll_sec=0.01,
            )
            out = list(router.serve([dict(r) for r in rows]))
            router.close()
            assert len(out) == len(rows), name
            assert all(
                _same_tokens(a, b) for a, b in zip(ref, out)
            ), name

    def test_kill_replica_mid_decode_e2e(self, shared_predicts,
                                         tmp_path):
        # ACCEPTANCE: 3 in-process replicas at ~2x a single engine's
        # admission capacity, one kill_replica mid-stream — every
        # request accounted for, outputs token-identical to the
        # reference, death + re-dispatch visible as journal events
        predict = shared_predicts[0]
        # single engine: 2 slots + queue 4 -> capacity 6; offer 2x+
        rows = _prompts([6, 9, 5, 13, 8, 4, 7, 11, 6, 9, 5, 13],
                        vocab=64, seed=31)
        ref = list(serving.predict_rows(
            predict, [dict(r) for r in rows], {"prompt": "tokens"},
            batch_size=2, schedule="continuous",
        ))
        plan = chaos.ChaosPlan().kill_replica(2, at_chunk=1)
        os.environ[chaos.TFOS_CHAOS_PLAN] = plan.save(
            str(tmp_path / "plan.json")
        )
        j = journal_mod.get_journal()
        j0_dead = len(j.events(kind="replica_dead"))
        j0_red = len(j.events(kind="fleet_redispatch"))
        try:
            router = FleetRouter(
                None, {"prompt": "tokens"}, replicas=3, num_slots=2,
                predict_factory=_shared_factory(shared_predicts),
                poll_sec=0.01,
            )
            out = list(router.serve([dict(r) for r in rows]))
            router.close()
        finally:
            del os.environ[chaos.TFOS_CHAOS_PLAN]
        assert len(out) == len(rows)
        served = [r for r in out if "error" not in r]
        records = [r for r in out if "error" in r]
        assert len(served) + len(records) == len(rows)
        assert not records  # nothing shed at this load; all served
        assert all(_same_tokens(a, b) for a, b in zip(ref, out))
        assert router.stats["replica_deaths"] == 1
        assert router.stats["redispatched"] >= 1
        assert len(j.events(kind="replica_dead")) > j0_dead
        assert len(j.events(kind="fleet_redispatch")) > j0_red

    def test_affinity_hit_rate_beats_random(self):
        # 80%-shared workload: 4 of 5 requests extend one of 4 shared
        # 16-token heads.  Affinity keeps each family on one replica
        # (ONE cold admit per family); random splits families across
        # replicas and pays the cold admit per (family, replica).
        _params, p0 = _gen_predict(max_new=4, extra={
            "chunk_size": 2, "prefix_cache": True, "prefix_block": 8,
        })
        predicts = [p0, p0.make_replica()]
        rng = np.random.RandomState(11)
        heads = [rng.randint(1, 64, (16,)) for _ in range(4)]
        rows = []
        for i in range(30):
            if i % 5 == 4:
                rows.append({"prompt": rng.randint(
                    1, 64, (18,)
                ).astype(np.int32)})
            else:
                rows.append({"prompt": np.concatenate(
                    [heads[i % 4], rng.randint(1, 64, (2,))]
                ).astype(np.int32)})
        rates = {}
        for name in ("prefix_affinity", "random"):
            router = FleetRouter(
                None, {"prompt": "tokens"}, replicas=2, num_slots=2,
                predict_factory=_shared_factory(predicts),
                dispatch=name, poll_sec=0.01,
                # compare the POLICIES, not the replicas' start-up
                # timing: each replica compiles for its own device, and
                # whichever is later would be routed around (straggler
                # detector) or spilled away from (no room / imbalance)
                # while the other serves its families
                slow_factor=float("inf"), imbalance=10 ** 6,
                replica_queue_depth=len(rows),
            )
            out = list(router.serve([dict(r) for r in rows]))
            router.close()
            assert len(out) == 30
            hits = router.stats["prefix_hits"]
            admitted = router.stats["admitted"]
            rates[name] = hits / float(admitted)
            for pred in predicts:  # cold caches for the next policy
                dec = pred.make_slot_decoder(2)
                if dec.prefix_cache is not None:
                    dec.prefix_cache.clear()
        assert rates["prefix_affinity"] > rates["random"], rates

    def test_rolling_deploy_real_zero_drop(self, shared_predicts):
        import jax

        params, _ = _gen_predict()
        new_params = jax.tree.map(
            lambda a: np.asarray(a) * 1.01, params
        )
        router = FleetRouter(
            None, {"prompt": "tokens"}, replicas=3, num_slots=2,
            predict_factory=_shared_factory(shared_predicts),
            engine_opts={"rollback_window": 1}, poll_sec=0.01,
        )

        # the commit gate proves each replica's new generation on
        # LIVE requests — keep traffic flowing until the rollout
        # lands (bounded by the deploy phase_timeout + a hard cap)
        hold = {}
        base_rows = _prompts([6, 9, 5, 8] * 4, vocab=64, seed=41)

        def traffic():
            for i in range(1500):
                d = hold.get("dep")
                if d is not None and d.finished and i >= 8:
                    return
                time.sleep(0.02)
                yield dict(base_rows[i % len(base_rows)])

        out = []
        for i, r in enumerate(router.serve(traffic())):
            out.append(r)
            if i == 3 and "dep" not in hold:
                hold["dep"] = router.start_rolling_deploy(
                    params=new_params, step=11, phase_timeout=30.0,
                )
        dep = hold["dep"]
        router.close()
        assert len(out) >= 8
        assert all("error" not in r for r in out)  # swap_dropped == 0
        assert dep.status["state"] == "done", dep.status
        assert sorted(dep.status["replicas_done"]) == [0, 1, 2]
        assert router.stats["swaps"] == 3

    def test_corrupt_checkpoint_canary_halts_rollout(
            self, shared_predicts, tmp_path):
        # ACCEPTANCE: an injected corrupt_checkpoint on the canary
        # replica halts the rollout with the other replicas still on
        # the old generation (and the step quarantined)
        from tensorflowonspark_tpu import checkpoint as ckpt
        from tensorflowonspark_tpu import hot_swap

        params, _ = _gen_predict()
        root = str(tmp_path / "pub")
        step_dir = ckpt.publish_for_serving(root, 5, params)
        chaos.corrupt_checkpoint(step_dir, "shape_mismatch")
        router = FleetRouter(
            None, {"prompt": "tokens"}, replicas=3, num_slots=2,
            predict_factory=_shared_factory(shared_predicts),
            poll_sec=0.01,
        )
        rows = [dict(r) for r in
                _prompts([6, 9, 5, 8] * 8, vocab=64, seed=43)]
        dep = None
        out = []
        gens_before = [
            r.stats.get("weight_generation", 0)
            for r in router.replicas
        ]
        for i, r in enumerate(router.serve(rows)):
            out.append(r)
            if i == 2 and dep is None:
                dep = router.start_rolling_deploy(step_dir=step_dir)
        router.close()
        assert len(out) == 32
        assert all("error" not in r for r in out)
        assert dep.status["state"] == "halted"
        assert dep.status["halted"]["kind"] == "shape_mismatch"
        assert dep.status["replicas_done"] == []
        for r, g0 in zip(router.replicas, gens_before):
            assert r.stats["weight_generation"] == g0  # old gen
            assert r.stats["swaps"] == 0
        assert hot_swap.read_quarantine(step_dir)


# ----------------------------------------------------------------------
# surface guards
# ----------------------------------------------------------------------


class TestSurface:
    def test_static_schedule_rejects_replicas(self):
        with pytest.raises(ValueError, match="continuous"):
            list(serving.predict_rows(
                lambda b: b, [], {"c": "x"}, replicas=2,
            ))

    def test_fleet_rejects_single_engine_watcher_knobs(self):
        with pytest.raises(ValueError, match="rolling deploys"):
            list(serving.predict_rows(
                lambda b: b, [], {"c": "x"}, schedule="continuous",
                replicas=2, checkpoint_dir="/tmp/nope",
            ))

    def test_replicas_need_make_replica(self):
        class _Bare(FakePredict):
            pass

        bare = _Bare()
        with pytest.raises(ValueError, match="make_replica"):
            ReplicaSet(bare, 2, {"prompt": "tokens"})

    def test_engine_mapping_adds_internal_budget_column(self):
        router = _fake_router(n=1)
        try:
            m = router.engine_input_mapping()
            assert m[FLEET_BUDGET_COL] == serving_engine.BUDGET_INPUT
            # a user budget column wins; no internal column added
            m2 = router.engine_input_mapping(
                {"prompt": "tokens", "budget": "max_new"}
            )
            assert FLEET_BUDGET_COL not in m2
        finally:
            router.close()

    def test_user_budget_column_respected(self):
        rows = _prompts([4] * 6)
        for i, r in enumerate(rows):
            r["budget"] = 3 if i % 2 else 8
        router = _fake_router(n=2)
        # rebuild with a budget mapping: use a fresh router
        router.close()
        router = FleetRouter(
            None, {"prompt": "tokens", "budget": "max_new"},
            replicas=2, num_slots=2,
            predict_factory=lambda: FakePredict(max_new=8),
            poll_sec=0.01,
        )
        out = list(router.serve([dict(r) for r in rows]))
        router.close()
        lens = [int(r["generated_len"]) for r in out]
        assert lens == [8, 3, 8, 3, 8, 3]

    def test_replica_lifecycle_verbs(self):
        router = _fake_router(n=2)
        rs = router.replica_set
        rs.drain(1)
        assert router.replicas[1].state == "draining"
        rs.evict(1)
        assert router.replicas[1].state == "routed_around"
        rs.readmit(1)
        assert router.replicas[1].state == "live"
        snap = rs.load()
        assert [s["replica"] for s in snap] == [0, 1]
        assert all(
            {"free_slots", "queued", "in_flight"} <= set(s)
            for s in snap
        )
        router.close()


# ----------------------------------------------------------------------
# device-error quarantine containment (ISSUE 19 tentpole)
# ----------------------------------------------------------------------


class TestQuarantineFake:
    def test_device_error_quarantines_not_kills(self, tmp_path):
        # a device error is CONTAINED: the replica quarantines (state
        # "routed_around", engine rebuilt, probe traffic) instead of
        # dying, and the router re-dispatches committed-token-safe —
        # the stream stays token-identical to the single-engine oracle
        rows = _prompts([5, 7, 3, 9, 4, 6, 8, 5, 7, 3, 9, 4])
        ref = _fake_reference(rows, max_new=12, chunk=2)
        plan = chaos.ChaosPlan().device_error(0, at_chunk=2)
        path = plan.save(str(tmp_path / "plan.json"))
        os.environ[chaos.TFOS_CHAOS_PLAN] = path
        try:
            router = _fake_router(n=2, slots=2, max_new=12, chunk=2)
            out = list(router.serve([dict(r) for r in rows]))
            router.close()
        finally:
            del os.environ[chaos.TFOS_CHAOS_PLAN]
        assert len(out) == len(rows)
        assert all("error" not in r for r in out)
        assert all(_same_tokens(a, b) for a, b in zip(ref, out))
        assert router.stats["quarantined"] == 1
        assert router.stats["replica_deaths"] == 0
        rep = router.replicas[0]
        assert rep.alive
        assert rep.state in ("live", "routed_around")
        j = journal_mod.get_journal()
        ev = j.events(kind="replica_quarantined")
        assert ev and ev[-1].severity == "page"


# ----------------------------------------------------------------------
# gated re-admission (ISSUE 19 satellite: CleanRoundsSensor seam)
# ----------------------------------------------------------------------


class _StubGate(object):
    """The readmit_gate surface (poll/ready/streak/rounds) with a
    hand-operated valve — the router contract test; the real
    CleanRoundsSensor is covered in tests/test_health.py."""

    def __init__(self):
        self.open = False
        self.polls = 0
        self.rounds = 3

    @property
    def streak(self):
        return self.rounds if self.open else 0

    def poll(self):
        self.polls += 1

    def ready(self):
        return self.open


class TestReadmitGate:
    def _slow_router(self, gate):
        return FleetRouter(
            None, {"prompt": "tokens"}, replicas=2, num_slots=1,
            predict_factory=lambda: FakePredict(
                chunk=4, max_new=4, delay=0.015
            ),
            replica_queue_depth=1, poll_sec=0.01,
            suspect_rounds=1, probe_every=2, readmit_rounds=2,
            min_slow_sec=0.1, slow_factor=3.0, readmit_gate=gate,
        )

    def test_gate_holds_then_releases_readmission(self, tmp_path):
        plan = chaos.ChaosPlan().slow_replica(
            0, per_chunk_sec=0.3, chunks=2
        )
        path = plan.save(str(tmp_path / "plan.json"))
        os.environ[chaos.TFOS_CHAOS_PLAN] = path
        gate = _StubGate()
        try:
            router = self._slow_router(gate)
            # first stream: the straggler is evicted, probes clean,
            # but the CLOSED gate must hold the re-admission
            out1 = list(router.serve(
                [dict(r) for r in _prompts([4] * 80)]
            ))
            assert len(out1) == 80
            assert router.stats["evicted"] >= 1
            assert router.stats["readmitted"] == 0
            assert router.replicas[0].state == "routed_around"
            assert gate.polls >= 1
            j = journal_mod.get_journal()
            gated = j.events(kind="readmit_gated")
            assert gated
            attrs = gated[-1].attrs
            assert attrs["required_rounds"] == gate.rounds
            assert attrs["clean_health_rounds"] == 0
            # second stream over the SAME warm fleet (serve is
            # re-entrant): the gate is open now — clean probe rounds
            # re-admit the replica and journal the release
            gate.open = True
            out2 = list(router.serve(
                [dict(r) for r in _prompts([4] * 40, seed=11)]
            ))
            router.close()
        finally:
            del os.environ[chaos.TFOS_CHAOS_PLAN]
        assert len(out2) == 40
        assert router.stats["readmitted"] >= 1
        assert router.replicas[0].state == "live"
        assert journal_mod.get_journal().events(kind="readmit_cleared")
