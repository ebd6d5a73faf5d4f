import json
import os

import numpy as np
import pytest

from benchmarks import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
MIX_DIR = os.path.join(HERE, "..", "traffic")


def load(name):
    with open(os.path.join(MIX_DIR, name + ".json")) as f:
        return json.load(f)


def drain(plan, rounds=3):
    out = []
    for _ in range(rounds):
        for c in range(plan.clients):
            ids, answer = plan.next_request(c)
            out.append((c, ids.tobytes(), len(ids), answer))
    return out


def test_closed_loop_repeats_for_a_seed_and_differs_across_seeds():
    mix = load("decode-closed")
    a = drain(traffic.ClosedLoop(mix, 7, 32000))
    b = drain(traffic.ClosedLoop(mix, 7, 32000))
    c = drain(traffic.ClosedLoop(mix, 2 ** 31 + 11, 32000))
    assert a == b
    assert a != c
    # another seed: the same set of sizes, dealt in another order
    sizes = lambda rows: sorted((n, ans) for _, _, n, ans in rows)  # noqa
    assert sizes(a) == sizes(c)
    assert [r[2:] for r in a] != [r[2:] for r in c]


def test_lengths_stay_inside_the_mix():
    mix = load("decode-closed")
    plan = traffic.ClosedLoop(mix, 1, 32000)
    lo, hi = mix["prompt_tokens"]["lo"], mix["prompt_tokens"]["hi"]
    assert plan.prompt_len.min() >= lo and plan.prompt_len.max() <= hi
    assert plan.answer_len[:, 1:].min() >= mix["answer_tokens"]["lo"]
    assert plan.answer_len.max() <= mix["answer_tokens"]["hi"]
    assert plan.prompt_buckets(64)[-1] == 512


def test_prefix_sharing_is_data():
    mix = dict(load("decode-closed"), clients=3, requests_per_client=2,
               sharing={"kind": "prefix_pool", "pool": 1, "tokens": 100})
    plan = traffic.ClosedLoop(mix, 5, 32000)
    a, _ = plan.next_request(0)
    b, _ = plan.next_request(1)
    assert (a[:100] == b[:100]).all() and (a[100:110] != b[100:110]).any()


OPEN = {
    "loop": "open", "rate_per_s": 20.0, "schedule_seed": 3,
    "burst": {"every_s": 1.0, "size": 5},
    "prompt_tokens": {"dist": "lognormal", "median": 200, "sigma": 1.0,
                      "lo": 64, "hi": 2048},
    "answer_tokens": {"dist": "uniform", "lo": 32, "hi": 512},
}


def test_open_schedule_repeats_and_keeps_its_sizes_across_seeds():
    a = traffic.open_schedule(OPEN, 1, 1000, 4.0)
    b = traffic.open_schedule(OPEN, 1, 1000, 4.0)
    c = traffic.open_schedule(OPEN, 2, 1000, 4.0)
    key = lambda s: [(d, p.tobytes(), n) for d, p, n in s]  # noqa: E731
    assert key(a) == key(b) and key(a) != key(c)
    assert [d for d, _, _ in a] == [d for d, _, _ in c]
    assert sorted((len(p), n) for _, p, n in a) == sorted(
        (len(p), n) for _, p, n in c)
    # bursts: five extra arrivals at every whole second
    assert sum(1 for d, _, _ in a if d == 1.0) == 5
    assert all(64 <= len(p) <= 2048 and 32 <= n <= 512 for _, p, n in a)


def test_open_loop_source_stamps_due_time_and_its_own_lateness():
    now = [100.0]
    slept = []

    def sleep(s):
        slept.append(s)
        now[0] += s + 0.01        # the generator wakes 10 ms late

    sched = [(0.5, np.arange(3), 4), (0.6, np.arange(3), 4),
             (0.6, np.arange(3), 4)]
    src = traffic.OpenLoopSource(sched, lambda: now[0], sleep, t0=100.0)
    rows = list(src)
    assert len(rows) == 3 and rows[0]["max_new"] == 4
    assert src.due_at == [100.5, 100.6, 100.6]
    assert src.late_s == pytest.approx([0.01, 0.01, 0.01], abs=1e-9)
    assert slept == pytest.approx([0.5, 0.09])


def test_unknown_kinds_are_errors():
    with pytest.raises(ValueError):
        traffic.draw_lengths(np.random.default_rng(0), {"dist": "zipf"}, 1)
    with pytest.raises(ValueError):
        traffic.ClosedLoop(dict(OPEN), 1, 10)
