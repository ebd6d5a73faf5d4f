"""``correct`` has to be able to come out false.  The control (the
reference in int8) fails the served-token gap; and a run whose timed
path is broken underneath — a token altered where it is produced —
reports ``correct: false`` through the whole of a run."""

import json
import os
import time

import numpy as np
import pytest

from benchmarks import compare, run, weights
from benchmarks.reference import dense_gqa as ref
from benchmarks.runners import serve
from benchmarks.tests.conftest import ROOT, TINY_MODEL, TINY_SERVE

MODEL = dict(TINY_MODEL, rope_theta=10000.0, rms_norm_eps=1e-6)


def greedy_samples(seed, rows=4, prompt=24, new=40):
    """Greedy continuations by the float32 reference itself."""
    import jax.numpy as jnp

    params = weights.make_params(MODEL, seed, "bfloat16")
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(rows):
        ids = list(rng.integers(1, 256, prompt))
        for _ in range(new):
            logits = ref.forward(jnp.asarray([ids], jnp.int32), params, MODEL)
            ids.append(int(jnp.argmax(logits[0, -1])))
        out.append((np.asarray(ids[:prompt], np.int32),
                    np.asarray(ids[prompt:], np.int32)))
    return out


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_int8_control_fails_the_gap_and_greedy_tokens_pass(seed):
    samples = greedy_samples(seed)
    got = compare.served_gaps(MODEL, seed, samples, "bfloat16", control=True)
    assert got["tokens_compared"] == 4 * 40
    assert got["served_gap_max"] == 0.0
    # at this tiny size the control's gap is its own reading, far above
    # what exact greedy tokens read; the chip's limit is for the chip
    assert got["control_gap_max"] > 0.02


def tiny_spec(tmp_path, seed=3):
    spec = run.load_cell("mistral7b-decode-closed", ROOT)
    spec["config"].update(TINY_SERVE["config"])
    spec["traffic"].update(TINY_SERVE["traffic"])
    spec.update(seed=seed, seconds=2.0, trace=0, control=False,
                rehearse=True, t_start=time.time(),
                trace_dir=str(tmp_path / "trace"))
    return spec


def test_a_sound_run_is_correct(tmp_path):
    result = serve.run(tiny_spec(tmp_path))
    assert result["correct"] is True
    assert result["checks"]["served_gap_max"]["value"] < (
        result["checks"]["served_gap_max"]["limit"])


def test_a_token_altered_where_it_is_produced_is_not_correct(
        tmp_path, monkeypatch):
    from tensorflowonspark_tpu import serving_engine

    consume = serving_engine.ServingEngine._consume

    def altered(self, req, chunk_row):
        if chunk_row is not None and len(chunk_row):
            chunk_row = np.array(chunk_row, copy=True)
            chunk_row[0] = (int(chunk_row[0]) + 1) % 256
        return consume(self, req, chunk_row)

    monkeypatch.setattr(serving_engine.ServingEngine, "_consume", altered)
    result = serve.run(tiny_spec(tmp_path))
    assert result["correct"] is False
    c = result["checks"]["served_gap_max"]
    assert c["value"] > c["limit"]


def test_an_answer_cut_short_is_not_correct(tmp_path, monkeypatch):
    from tensorflowonspark_tpu import serving_engine

    finalize = serving_engine.ServingEngine._finalize

    def short(self, req, t_done):
        req["budget"] = max(1, req["budget"] - 1)
        return finalize(self, req, t_done)

    monkeypatch.setattr(serving_engine.ServingEngine, "_finalize", short)
    result = serve.run(tiny_spec(tmp_path))
    assert result["correct"] is False
    assert result["checks"]["answers_not_of_budget"]["value"] > 0
