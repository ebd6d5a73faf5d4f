"""The plain reference of the ``glm_moe_dsa`` block (GLM-5.2's
``config.json``; the sparse index as DeepSeek-V3.2 published it):
multi-head latent attention, a learned top-k index that some layers
own and the next ones share, a dense gated MLP on the leading layer
and sigmoid-routed experts with a shared expert on the rest — forward
only.  Straight ``jax.numpy`` in float32 at matmul precision
``highest``: no kernel, no cache, no absorbed form, nothing imported
from the program.  ``mode="int8"`` is the low-precision control, as in
``dense_gqa.py`` (whose matmul and norm this file uses).  ``mode="bf16"``
is the same equations at the PROGRAM's precision — every product's
inputs and every activation rounded to bfloat16, sums in float32 — and
says how far rounding alone moves the answer: it decides nothing, the
runner prints it beside the control (``--control``).

Layer equations (``x`` the normed input, one row of tokens):

- ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb`` -> heads of ``nope +
  rope``; ``[c_kv | k_r] = x W_kva``, ``c_kv = RMSNorm(c_kv)``; RoPE
  (interleaved pairs) on the rope part of ``q`` and on ``k_r``;
  ``[k_nope | v] = c_kv W_kvb`` per head; scores ``(q_nope · k_nope +
  q_rope · k_r) / sqrt(nope + rope)``, softmax over the SELECTED keys,
  ``o = (p v) W_o``.
- the index of a "full" layer: ``q_I = c_q W_Iq`` (heads ``j``),
  ``k_I = LayerNorm(x W_Ik)``, RoPE on the leading rope-width of
  each, ``w = x W_Iw / sqrt(j · index_head_dim)``; ``I[t, s] = sum_j
  w[t, j] relu(q_I[t, j] · k_I[s])`` for ``s <= t``; the selected set
  is the ``index_topk`` largest (all while there are fewer), ties to
  the lower position.  A "shared" layer attends over the set of the
  nearest "full" layer before it.
- sparse FFN: ``s = sigmoid(x W_r)``; the ``k`` experts with the
  largest ``s + b``; weights ``s_e / sum of the chosen s``, times the
  scaling factor; ``y = shared(x) + sum over chosen AND held e of w_e
  expert_e(x)`` — the share of the result that the experts this chip
  holds give (``weights_glm_dsa_moe.sizes``: ``first``, ``held``).

Departures from the published kernels, listed in the configuration's
``assumed``: no Hadamard rotation and no fp8 in the index, index keys
in the model's dtype, LayerNorm epsilon 1e-6.
"""

import jax
import jax.numpy as jnp

from benchmarks.reference import dense_gqa
from benchmarks.reference.dense_gqa import HIGHEST
from benchmarks.weights_glm_dsa_moe import layer_kinds, sizes

#: queries at a time, so float32 scores of 64 heads fit
Q_BLOCK = 256


def rounded(x, mode):
    """``x`` as the program holds it: rounded to bfloat16 under
    ``mode="bf16"``, untouched otherwise."""
    if mode != "bf16":
        return x
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def matmul(x, w, n_contract, mode):
    """``dense_gqa.matmul``; under ``mode="bf16"`` the activations
    rounded to bfloat16, the weights as they are stored (the cell
    stores bfloat16), a float32 sum and a bfloat16 result."""
    if mode != "bf16":
        return dense_gqa.matmul(x, w, n_contract, mode)
    return rounded(dense_gqa.matmul(
        rounded(x, mode), w, n_contract, "f32"), mode)


def rmsnorm(x, scale, eps, mode="f32"):
    return rounded(dense_gqa.rmsnorm(x, scale, eps), mode)


def rope_pairs(x, positions, theta):
    """Rotate interleaved pairs ``(2i, 2i + 1)`` of ``x[S, ..., D]``
    by ``positions[S]``."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32).reshape(
        (-1,) + (1,) * (x.ndim - 1)) * freq
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).reshape(x.shape)


def layernorm(x, scale, bias, eps=1e-6):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps)
            * scale.astype(jnp.float32) + bias.astype(jnp.float32))


def _rope_head(x, positions, theta, width):
    return jnp.concatenate(
        [rope_pairs(x[..., :width], positions, theta), x[..., width:]],
        axis=-1)


def select(scores, visible, k):
    """``[Q, S]`` bool: the ``k`` largest visible scores of each row
    (all the visible ones where fewer), ties to the lower index."""
    if scores.shape[-1] <= k:
        return visible
    # +0.0 and -0.0 are one value (a sort would tell them apart)
    masked = jnp.where(visible, jnp.where(scores == 0, 0.0, scores),
                       -jnp.inf)
    _, idx = jax.lax.top_k(masked, k)  # equal values: lower index first
    rows = jnp.arange(scores.shape[0])[:, None]
    chosen = jnp.zeros(scores.shape, bool).at[rows, idx].set(True)
    return jnp.logical_and(chosen, visible)


def attention(x, p, model, positions, mode, indexer, sel):
    """One row: ``x[S, d]`` -> ``(out[S, d], sel[S, S])``."""
    z = sizes(model)
    dn, dr, dv = z["dn"], z["dr"], z["dv"]
    theta = model["rope_parameters"]["rope_theta"]
    eps = model["rms_norm_eps"]
    c_q = rmsnorm(matmul(x, p["q_a"]["kernel"], 1, mode),
                  p["q_norm"]["scale"], eps, mode)
    q = matmul(c_q, p["q_b"], 1, mode)          # [S, H, dn+dr]
    q_nope = q[..., :dn]
    q_rope = rounded(rope_pairs(q[..., dn:], positions, theta), mode)
    kv_a = matmul(x, p["kv_a"]["kernel"], 1, mode)
    c_kv = rmsnorm(kv_a[:, :z["rkv"]], p["kv_norm"]["scale"], eps, mode)
    k_r = rounded(
        rope_pairs(kv_a[:, z["rkv"]:], positions, theta), mode)  # [S, dr]
    kv = matmul(c_kv, p["kv_b"], 1, mode)                  # [S, H, dn+dv]
    k_nope, v = kv[..., :dn], kv[..., dn:]
    s_len = x.shape[0]
    if indexer == "full":
        q_i = rounded(_rope_head(
            matmul(c_q, p["index_q"], 1, mode),
            positions, theta, dr), mode)                   # [S, J, di]
        k_i = rounded(_rope_head(
            layernorm(matmul(x, p["index_k"]["kernel"], 1, mode),
                      p["index_k_norm"]["scale"],
                      p["index_k_norm"]["bias"]),
            positions, theta, dr), mode)                   # [S, di]
        w_i = matmul(x, p["index_w"]["kernel"], 1, mode) * (
            z["j"] ** -0.5 * z["di"] ** -0.5)              # [S, J]
    scale = (dn + dr) ** -0.5
    block = Q_BLOCK if s_len % Q_BLOCK == 0 else s_len

    def one(q0):
        def cut(t):
            return jax.lax.dynamic_slice_in_dim(t, q0, block, axis=0)

        qpos = cut(positions)
        visible = positions[None, :] <= qpos[:, None]
        if indexer == "full":
            dots = jnp.einsum("qjd,sd->qjs", cut(q_i), k_i,
                              precision=HIGHEST)
            score = jnp.sum(
                jnp.maximum(dots, 0.0) * cut(w_i)[:, :, None], axis=1)
            chosen = select(score, visible, z["topk"])
        else:
            chosen = cut(sel)
        logits = (
            jnp.einsum("qhd,shd->hqs", cut(q_nope), k_nope,
                       precision=HIGHEST)
            + jnp.einsum("qhd,sd->hqs", cut(q_rope), k_r,
                         precision=HIGHEST)
        ) * scale
        probs = rounded(jax.nn.softmax(
            jnp.where(chosen[None], logits, -jnp.inf), axis=-1), mode)
        return rounded(jnp.einsum("hqs,shd->qhd", probs, v,
                                  precision=HIGHEST), mode), chosen

    ctx, chosen = jax.lax.map(one, jnp.arange(0, s_len, block))
    ctx = ctx.reshape((s_len,) + ctx.shape[2:])
    return (matmul(ctx, p["out"]["kernel"], 2, mode),
            chosen.reshape(s_len, s_len))


def gated(x, wi, wg, wo, mode):
    gate = jax.nn.silu(matmul(x, wg, 1, mode))
    return matmul(rounded(gate * matmul(x, wi, 1, mode), mode), wo, 1, mode)


def route(x, p, model, mode):
    """``[S, experts]`` weights: nought where an expert is not among
    the token's chosen ``k``."""
    z = sizes(model)
    # the program keeps the router's float32 sums unrounded
    score = jax.nn.sigmoid(dense_gqa.matmul(
        rounded(x, mode), p["router"], 1,
        "f32" if mode == "bf16" else mode))
    _, idx = jax.lax.top_k(
        score + p["router_bias"].astype(jnp.float32), z["k"])
    chosen = jnp.zeros(score.shape, bool).at[
        jnp.arange(score.shape[0])[:, None], idx].set(True)
    picked = jnp.where(chosen, score, 0.0)
    return (picked / jnp.sum(picked, axis=-1, keepdims=True)
            * model["routed_scaling_factor"])


def sparse_ffn(x, p, model, mode):
    z = sizes(model)
    weight = route(x, p, model, mode)
    y = gated(x, p["shared_wi"]["kernel"], p["shared_wg"]["kernel"],
              p["shared_wo"]["kernel"], mode)
    held = weight[:, z["first"]:z["first"] + z["held"]]

    def add(y, e):
        wi, wg, wo, w_e = e
        return rounded(
            y + w_e[:, None] * gated(x, wi, wg, wo, mode), mode), None

    y, _ = jax.lax.scan(add, y, (p["wi"], p["wg"], p["wo"], held.T))
    return y


def embed(tokens, outer):
    return outer["embedding"].astype(jnp.float32)[tokens]


def block(x, p, model, layer, positions, mode="f32", sel=None):
    """One row through layer ``layer`` (a static index: it picks the
    layer's kinds): ``(x[S, d], sel)``."""
    ffn, indexer = layer_kinds(model, layer)
    eps = model["rms_norm_eps"]
    att, sel = attention(
        rmsnorm(x, p["ln1"]["scale"], eps, mode), p["attn"], model,
        positions, mode, indexer, sel)
    x = rounded(x + att, mode)
    h = rmsnorm(x, p["ln2"]["scale"], eps, mode)
    if ffn == "dense":
        y = gated(h, p["mlp"]["wi"]["kernel"], p["mlp"]["wg"]["kernel"],
                  p["mlp"]["wo"]["kernel"], mode)
    else:
        y = sparse_ffn(h, p["moe"], model, mode)
    return rounded(x + y, mode), sel


def head(x, outer, model, mode="f32"):
    x = rmsnorm(x, outer["ln_f"]["scale"], model["rms_norm_eps"], mode)
    # the program's head gives float32 logits from bfloat16 inputs
    return dense_gqa.matmul(
        rounded(x, mode), outer["lm_head"]["kernel"], 1,
        "f32" if mode == "bf16" else mode)


def forward(tokens, params, model, mode="f32"):
    """Logits ``[S, vocab]`` of ONE row of tokens from a whole tree."""
    positions = jnp.arange(tokens.shape[0])
    x, sel = embed(tokens, params), None
    for i in range(model["num_hidden_layers"]):
        x, sel = block(
            x, params["block_%d" % i], model, i, positions, mode, sel)
    return head(x, params, model, mode)
