"""The grouped matmul's forward stripe (ISSUE 37).

``gmm_call`` walks ``(F // bf, T)`` with row tiles innermost, so a row
tile of ``x`` is read ``F / bf`` times.  Where the whole width's
double-buffered blocks and its float32 product fit the VMEM budget the
forward takes ``bf = F``: one stripe, each tile read once.  Elsewhere
it takes what it took, and the ``dx`` / ``dw`` kernels choose as they
did: the programs of the cells whose experts are too wide stay the
parent's, to the character.
"""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.models import moe
from tensorflowonspark_tpu.ops import gmm
from tensorflowonspark_tpu.ops import moe as moe_ops

#: a cell's experts: (D, F) of ``wi`` / ``wg``; ``wo`` is (F, D)
WIDTHS = {"mellum": (2304, 896), "glm": (6144, 2048),
          "moonlight": (2048, 1408)}
#: (cell, row tile) -> the forward's stripes of ``wi`` / ``wg`` and of
#: ``wo``.  Whole width for Mellum at both tiles; GLM's whole width is
#: 50 MB and Moonlight's at tiles of 256 is 15.0 + 1.4 MiB, so both
#: keep the parent's.  Moonlight's whole width at tiles of 16 is 11.3
#: MiB and fits: no cell runs it (its training span takes tiles of 256)
STRIPES = {
    ("mellum", 16): (896, 2304),
    ("mellum", 256): (896, 2304),
    ("glm", 16): (512, 1024),
    ("glm", 256): (256, 1024),
    ("moonlight", 16): (1408, 2048),
    ("moonlight", 256): (128, 1024),
}


def _grid(x, w, te, bm, **kw):
    jaxpr = jax.make_jaxpr(
        lambda x, w, te: gmm.gmm_call(x, w, te, bm=bm, **kw))(x, w, te)
    (eqn,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    return eqn.params["grid_mapping"].grid


@pytest.mark.parametrize("matrix", ["wi_wg", "wo"])
@pytest.mark.parametrize("cell,bm", sorted(STRIPES))
def test_the_forward_s_stripe_at_each_cell_s_widths(cell, bm, matrix):
    d, f = WIDTHS[cell]
    if matrix == "wo":
        d, f = f, d
    want = STRIPES[cell, bm][matrix == "wo"]
    assert gmm._forward_bf(bm, d, f) == want
    t = 5
    grid = _grid(jax.ShapeDtypeStruct((t * bm, d), jnp.bfloat16),
                 jax.ShapeDtypeStruct((8, d, f), jnp.bfloat16),
                 jax.ShapeDtypeStruct((t,), jnp.int32), bm)
    assert grid == (f // want, t)
    # a pinned divisor is still the caller's; the narrow picker is
    # untouched (the dw kernel's two blocks come from it)
    assert _grid(jax.ShapeDtypeStruct((t * bm, d), jnp.bfloat16),
                 jax.ShapeDtypeStruct((8, d, f), jnp.bfloat16),
                 jax.ShapeDtypeStruct((t,), jnp.int32), bm,
                 bf=128) == (f // 128, t)
    if cell == "mellum":
        assert gmm._pick_bf(bm, d, f) == (128 if matrix == "wi_wg" else 256)


@pytest.mark.parametrize("live", [None, 5])
@pytest.mark.parametrize("narrow", [64, 128])
def test_whole_width_is_the_narrow_stripes_to_the_bit(narrow, live):
    # 8 row tiles of 16 over 4 experts; with ``live`` the last three
    # are dead (they repeat the last live tile's expert, as the
    # layouts make them) and their rows are never read
    bm, e, d, f = 16, 4, 64, 256
    assert gmm._forward_bf(bm, d, f) == f
    assert gmm._pick_bf(bm, d, f) == 128
    ks = jax.random.split(jax.random.PRNGKey(37), 2)
    x = jax.random.normal(ks[0], (8 * bm, d)).astype(jnp.bfloat16)
    w = jax.random.normal(ks[1], (e, d, f)).astype(jnp.bfloat16)
    te = jnp.asarray([0, 0, 1, 2, 3, 3, 3, 3], jnp.int32)
    kw = {} if live is None else {"live_tiles": jnp.asarray([live],
                                                            jnp.int32)}
    whole = gmm.gmm_call(x, w, te, bm=bm, **kw)
    striped = gmm.gmm_call(x, w, te, bm=bm, bf=narrow, **kw)
    rows = (8 if live is None else live) * bm
    np.testing.assert_allclose(
        np.asarray(whole[:rows], np.float32),
        np.asarray(striped[:rows], np.float32), rtol=0, atol=0)
    # and both are the product (the reference rounds its float32 sums
    # to bfloat16 apart from the kernel's: an ulp here and there)
    np.testing.assert_allclose(
        np.asarray(whole[:rows], np.float32),
        np.asarray(gmm.gmm_reference(x, w, te, bm=bm)[:rows], np.float32),
        rtol=1e-2, atol=1e-3)


def _text(fn, *args):
    text = str(jax.make_jaxpr(fn)(*args))
    return re.sub(r" at [^\s\]]+:\d+", "", text)   # no source positions


def _sds(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _glm_decode_pass():
    # one chip's share of GLM-5.2's layer: 16 of 256 experts of 6144 x
    # 2048, top-8, one shared expert; 17 slots' rows, one decode pass
    layer = moe.SigmoidMoE(
        router_experts=256, num_experts=16, mlp_dim=2048, embed_dim=6144,
        k=8, scaling=2.5, shared_experts=1, dtype="bfloat16")
    x = _sds((17, 1, 6144))
    params = jax.tree.map(
        lambda a: _sds(a.shape), jax.eval_shape(
            lambda x: layer.init(jax.random.PRNGKey(0), x), x)["params"])

    def step(params, x):
        return layer.apply({"params": params}, x, mutable=["moe_stats"])

    return _text(step, params, x)


def _moonlight_span_grad():
    # one chip's share of Moonlight's layer: 8 of 64 experts of 2048 x
    # 1408, top-6; a span of 512 tokens in chunks as SigmoidMoE sizes
    # them, forward and backward through share_span
    g, k, held, d, f, bm = 512, 6, 8, 2048, 1408, 256
    rows = -(-g * k * held // 64 // bm) * bm + 2 * held * bm

    def loss(x, gates, w, experts):
        lay = moe_ops.span_layout(experts, 0, held, bm, rows)
        return jnp.sum(moe_ops.share_span(
            x, gates, w, lay, bm, rows).astype(jnp.float32))

    return _text(jax.grad(loss, argnums=(0, 1, 2)), _sds((g, d)),
                 _sds((g, k), jnp.float32),
                 (_sds((held, d, f)), _sds((held, d, f)),
                  _sds((held, f, d))),
                 _sds((g, k), jnp.int32))


#: sha256 of the traced programs, source positions stripped, recorded
#: from the parent commit (8d6520f): where the whole width does not fit
#: the forward's stripes, and the dx / dw kernels' blocks, are the
#: parent's, so these cells run the parent's arithmetic
PARENT_PROGRAMS = {
    "glm_decode_pass": (
        _glm_decode_pass,
        "132c6e5d135c910de67480add3c0128d01c9aa84ff318d9a692e6f095d166750"),
    "moonlight_span_grad": (
        _moonlight_span_grad,
        "d1c6e8c58ad8b41cf1aa5cb05c5205c7551dbea0aa0036ed196177ae486a9bf4"),
}


@pytest.mark.parametrize("name", sorted(PARENT_PROGRAMS))
def test_the_other_cells_programs_are_the_parent_s(name):
    build, want = PARENT_PROGRAMS[name]
    assert hashlib.sha256(build().encode()).hexdigest() == want
