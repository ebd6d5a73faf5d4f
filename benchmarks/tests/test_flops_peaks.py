import json
import os

import pytest

from benchmarks import flops, peaks

HERE = os.path.dirname(os.path.abspath(__file__))


def mistral(layers=1):
    path = os.path.join(HERE, "..", "configs", "mistral-7b-v0.1.serve.json")
    with open(path) as f:
        return dict(json.load(f), num_hidden_layers=layers)


def test_one_layer_by_hand():
    m = mistral(1)
    # q and out: 4096 x 4096 each; k and v: 4096 x 1024 each;
    # three MLP matrices of 4096 x 14336
    by_hand = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert by_hand == 218_103_808
    assert flops.layer_params(m) == by_hand
    assert flops.matmul_params(m) == by_hand + 4096 * 32000
    assert flops.total_params(m) == (
        by_hand + 2 * 4096 + 4096 + 2 * 4096 * 32000)


def test_forward_flops_one_token_and_window():
    m = mistral(1)
    dense = 2 * flops.matmul_params(m)
    # the first token sees one key: 4 * 128 * 32 operations of attention
    assert flops.forward_flops(m, 1) == dense + 4 * 128 * 32
    # a token at position 5000 sees only the window's 4096 keys
    one = flops.forward_flops(m, 5001, start=5000)
    assert one == dense + 4 * 128 * 32 * 4096
    assert flops.attention_pairs(8192, 4096) == sum(
        min(p + 1, 4096) for p in range(8192))
    assert flops.train_flops(m, 16) == 3 * flops.forward_flops(m, 16)


def test_decode_step_work_by_hand():
    m = mistral(16)
    ops, nbytes = flops.decode_step_work(m, [99, 199])
    live = 100 + 200
    assert nbytes == (
        flops.weight_bytes(m) + live * 16 * 2 * 8 * 128 * 2 + 2 * 4096 * 2)
    assert flops.kv_bytes_per_token(m) == 65536
    assert ops == 2 * flops.matmul_params(m) * 2 + 4 * 128 * 32 * 16 * live
    t, bound = flops.roofline_seconds(
        ops, nbytes, peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory" and t == nbytes / 819e9


def test_unknown_device_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("cpu")
