"""``kv_read_share.serve`` on hand-made spans over the small recorded
trace (the fixtures of ``test_span_readers``): the mean share over the
window's ``engine.chunk`` spans, and nothing where the spans lack the
counters (a parent commit's)."""

import pytest

from benchmarks.runners.common import load_reader
from benchmarks.tests.test_span_readers import (  # noqa: F401 - fixtures
    ring,
    serving_spans,
    trace,
)


def test_absent_counters_read_as_nothing(trace, ring):  # noqa: F811
    serving_spans(ring)  # chunks with live/slots only, as before PR 27
    assert load_reader("kv_read_share.serve")(trace, {}, {}) is None


def test_mean_share_over_the_windows_chunks(trace, ring):  # noqa: F811
    serving_spans(ring)
    for start, read in ((50.44, 1024), (59.0, 2048), (67.7, 3072)):
        ring("engine.chunk", start, start + 0.4, trace="engine",
             attn="kernel", kv_read_tokens=read, kv_bank_tokens=4096)
    # a chunk before the traced window does not count
    ring("engine.chunk", 10.0, 10.4, trace="engine", attn="kernel",
         kv_read_tokens=4096, kv_bank_tokens=4096)
    got = load_reader("kv_read_share.serve")(trace, {}, {})
    assert got == pytest.approx(100.0 * 2048 / 4096)
