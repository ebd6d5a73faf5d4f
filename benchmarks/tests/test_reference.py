"""The plain reference against the program, at a tiny size on the CPU:
forward logits, and loss with gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import compare, weights
from benchmarks.reference import dense_gqa as ref
from benchmarks.tests.conftest import TINY_MODEL

MODEL = dict(TINY_MODEL, rope_theta=10000.0, rms_norm_eps=1e-6)


def program(dtype):
    from tensorflowonspark_tpu.models import transformer as tr

    return tr, tr.Transformer(tr.TransformerConfig(
        vocab_size=256, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, embed_dim=64, mlp_dim=128, max_seq_len=256,
        attention_window=48, dtype=dtype))


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(
        np.random.default_rng(0).integers(1, 256, (2, 96)), jnp.int32)


def test_forward_logits_agree(tokens):
    params = weights.make_params(MODEL, 5, "float32")
    _, model = program("float32")
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, tokens)
    want = ref.forward(tokens, params, MODEL)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-4
    # 96 tokens against a window of 48: the window does work
    no_window = ref.forward(tokens, params, dict(MODEL, sliding_window=0))
    assert float(jnp.max(jnp.abs(no_window - want))) > 1e-2


def test_loss_and_gradients_agree(tokens):
    params = weights.make_params(MODEL, 6, "float32")
    tr, model = program("float32")
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(tr.loss_fn(model))(
            params, {"tokens": tokens}, None)
    rloss, rgrads = jax.value_and_grad(ref.loss)(params, tokens, MODEL)
    assert abs(float(loss) - float(rloss)) < 1e-5
    for g, r in zip(jax.tree.leaves(grads), jax.tree.leaves(rgrads)):
        scale = float(jnp.max(jnp.abs(r))) + 1e-12
        assert float(jnp.max(jnp.abs(g - r))) / scale < 1e-3


def test_layerwise_reference_is_the_whole_reference(tokens):
    params = weights.make_params(MODEL, 9, "bfloat16")
    whole = ref.forward(tokens, params, MODEL)
    by_layer = compare.reference_logits(MODEL, 9, tokens, "bfloat16")
    assert float(jnp.max(jnp.abs(whole - by_layer))) < 1e-4


def test_int8_control_moves_the_logits(tokens):
    params = weights.make_params(MODEL, 9, "bfloat16")
    f32 = ref.forward(tokens, params, MODEL)
    int8 = ref.forward(tokens, params, MODEL, mode="int8")
    err = float(jnp.max(jnp.abs(f32 - int8)))
    assert 1e-3 < err < 1.0


def test_three_optimizer_steps_agree_with_the_program():
    """``train_reference`` (row by row, layer by layer, AdamW written
    out) against ``SyncTrainer`` with ``optax.adamw``, in float32."""
    import optax

    from benchmarks import traffic
    from tensorflowonspark_tpu.parallel import dp

    opt = {"learning_rate": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
           "weight_decay": 1e-4}
    mix = {"seq_len": 64, "bos_id": 1,
           "documents": {"dist": "uniform", "lo": 4, "hi": 40}}
    batches = [np.stack([traffic.packed_row(mix, 3, s * 4 + r, 256)
                         for r in range(4)]) for s in range(3)]
    tr, model = program("float32")
    params = weights.make_params(MODEL, 3, "float32")
    trainer = dp.SyncTrainer(
        tr.loss_fn(model),
        optax.adamw(opt["learning_rate"], b1=opt["b1"], b2=opt["b2"],
                    eps=opt["eps"], weight_decay=opt["weight_decay"]))
    state = trainer.create_state(params)
    losses, grad = [], None
    with jax.default_matmul_precision("highest"):
        for b in batches:
            state, m = trainer.step(state, {"tokens": b})
            losses.append(float(m["loss"]))
            if grad is None:
                grad = {k: v / 0.1 for k, v in compare.leaf_norms(
                    state.opt_state[0].mu).items()}
    change = compare.change_norms(state.params, MODEL, 3)
    want = compare.train_reference(MODEL, 3, batches, opt)
    checks, detail = compare.train_checks(
        losses, grad, change, want, 1e-5, 1e-3, 1e-3)
    assert all(c["value"] <= c["limit"] for c in checks.values()), checks
    # half of the batch left out reads far off on the first gradient
    half = compare.train_reference(MODEL, 3, batches, opt, rows=range(2))
    c, _ = compare.train_checks(
        half["losses"], half["grad_norms"], half["change_norms"], want,
        1e-5, 1e-3, 1e-3)
    assert c["grad_norm_gap_worst_leaf"]["value"] > 0.1
    # and so does the int8 control
    low = compare.train_reference(MODEL, 3, batches, opt, mode="int8")
    c, _ = compare.train_checks(
        low["losses"], low["grad_norms"], low["change_norms"], want,
        1e-5, 1e-3, 1e-3)
    assert c["grad_norm_gap_worst_leaf"]["value"] > 1e-3
