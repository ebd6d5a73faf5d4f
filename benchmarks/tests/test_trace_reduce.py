"""The trace reducer on a small trace recorded on a TPU v5e (three runs
of a four-matmul program named ``_chunk_impl`` under the benchmark's
two annotations; ``trace_small.json`` is ``load_xplane``'s output)."""

import json
import os

import pytest

from benchmarks import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(HERE, "trace_small.json")) as f:
        return json.load(f)


def test_busy_union_and_window(trace):
    s = tr.summary(trace)
    # three runs of 361.2 us each, back to back inside: busy is their
    # union, the window runs from the first event's start to the last's end
    assert s["busy_s"] == pytest.approx(3 * 361.2e-6, rel=1e-3)
    assert s["window_s"] == pytest.approx(
        (67951798 + 91055 - 50419528) / 1e9)
    assert s["busy_s_by_chip"] == {0: s["busy_s"]}
    assert tr.union_seconds([(0, 10), (5, 20), (30, 40)])[0] == 30e-9


def test_a_named_programs_time(trace):
    runs = tr.program_events(trace, r"^jit__chunk_impl")
    assert runs == pytest.approx([361.244e-6, 361.212e-6, 361.260e-6])
    assert tr.program_events(trace, r"^jit_no_such_program") == []


def test_a_kernels_events(trace):
    seconds, count = tr.op_seconds(trace, r"^fusion")
    assert count == 12
    assert seconds == pytest.approx(1083.6e-6, rel=1e-3)
    top = tr.top_device_ops(trace)
    assert top[0][0] == "fusion" and top[0][1] == pytest.approx(seconds)
    assert tr._short(
        '%custom-call.7 = bf16[8]{0} custom-call(bf16[8]{0} %x), '
        'custom_call_target="tpu_custom_call"'
    ) == "custom-call.7[tpu_custom_call]"


def test_a_gaps_attribution(trace):
    gaps = dict(tr.idle_gaps(trace))
    # between two runs the host is partly inside the benchmark's
    # annotations: their part of the gap bears their name
    assert set(gaps) <= {"unattributed", "bench.predict_rows",
                         "bench.source"}
    assert gaps["bench.predict_rows"] > 0
    idle = tr.summary(trace)["window_s"] - tr.summary(trace)["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)


def test_an_empty_trace_reads_as_nothing():
    empty = {"planes": [{"name": "/host:CPU", "lines": []}]}
    assert tr.summary(empty) is None
    assert tr.idle_gaps(empty) == [] and tr.top_device_ops(empty) == []
