"""The plain reference of the ``mellum`` block (Mellum2-12B-A2.5B's
``config.json``): a pre-norm decoder whose layers are of two TYPES —
sliding-window and full attention — each with its own RoPE, and whose
every FFN is softmax-routed experts; forward only.  Straight
``jax.numpy`` in float32 at matmul precision ``highest``: no kernel,
no cache, no ring, no batching, nothing imported from the program; a
row at a time and a layer's weights at a time, so that it fits at the
cell's size.  ``mode="int8"`` is the low-precision control and
``mode="bf16"`` the same equations at the program's precision, both as
``glm_dsa_moe.py`` has them (whose ``matmul``, ``rmsnorm``, ``gated``
and ``rounded`` this file uses).

The layer's equations (``x`` one row of tokens, ``[S, hidden]``):

``h = x + Attn_l(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``; after the
last layer a final RMSNorm and the untied head.

*Attention.*  ``q, k, v = x W_q, x W_k, x W_v`` (no bias), shaped
``[S, H | Hkv | Hkv, head_dim]``; ``q`` and ``k`` each RMS-normed over
``head_dim`` with a learned scale; RoPE in split halves (pair ``i`` is
``(i, i + D/2)``) with the layer TYPE's frequencies:

- sliding (``rope_type`` default): ``inv_freq_i = theta ** (-2i / D)``;
- full (YaRN, as ``transformers``' ``_compute_yarn_parameters`` with
  ``truncate``): ``extrap_i = theta ** (-2i / D)``, ``interp_i =
  extrap_i / factor``, ``c(n) = D ln(original / (2 pi n)) / (2 ln
  theta)``, ``low = max(floor(c(beta_fast)), 0)``, ``high =
  min(ceil(c(beta_slow)), D - 1)``, ``ramp_i = clip((i - low) / (high
  - low), 0, 1)``, ``inv_freq_i = interp_i ramp_i + extrap_i (1 -
  ramp_i)``, and cos and sin both times ``attention_factor``;

scores ``q . k / sqrt(D)``; query ``i`` sees keys ``j <= i`` and, on a
sliding layer, ``j > i - sliding_window``; softmax in float32; each KV
head serves ``H / Hkv`` query heads; ``W_o``.

*Experts.*  ``p = softmax(x W_r)`` over all experts in float32, the
``num_experts_per_tok`` largest (ties to the lower id), ``w = p_chosen
/ sum p_chosen`` (``norm_topk_prob``), ``MoE(x) = sum_e w_e
W_down,e(silu(W_gate,e x) * W_up,e x)``.  No scaling factor, no bias,
no shared expert, never a drop.

Departures and assumptions (the configuration's ``assumed``): the
config has no key for the q/k norm — its keys are Qwen3-MoE's, whose
attention norms q and k per head, so it is built; the MTP head the
catalog's ``described_as`` mentions has no key and is not built; the
router's product is taken at the reference's precision (float32; under
``mode="int8"`` rounded like every other matmul).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import dense_gqa
from benchmarks.reference.dense_gqa import HIGHEST
from benchmarks.reference.glm_dsa_moe import gated, matmul, rmsnorm, rounded
from benchmarks.weights_swa_moe import sizes

#: queries at a time, so the float32 scores of 32 heads over a row of
#: ten thousand keys fit
Q_BLOCK = 256


def inv_freq(model, kind):
    """``([D/2] float32 inverse frequencies, factor on cos and sin)``
    of layer type ``kind``, from ``rope_parameters[kind]``."""
    p = model["rope_parameters"][kind]
    dim = model["head_dim"]
    theta = float(p["rope_theta"])
    i = np.arange(dim // 2, dtype=np.float64)
    extrap = theta ** (-2 * i / dim)
    if p["rope_type"] == "default":
        return extrap.astype(np.float32), 1.0
    if p["rope_type"] != "yarn":
        raise ValueError("rope_type %r" % (p["rope_type"],))
    interp = extrap / p["factor"]
    original = p["original_max_position_embeddings"]

    def c(n):
        return dim * math.log(original / (2 * math.pi * n)) / (
            2 * math.log(theta))

    low = max(math.floor(c(p["beta_fast"])), 0)
    high = min(math.ceil(c(p["beta_slow"])), dim - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return ((interp * ramp + extrap * (1 - ramp)).astype(np.float32),
            float(p["attention_factor"]))


def rope_halves(x, positions, freq, factor):
    """Rotate pairs ``(i, i + D/2)`` of ``x[S, H, D]`` by
    ``positions[S]``."""
    ang = positions.astype(jnp.float32)[:, None, None] * jnp.asarray(freq)
    sin, cos = jnp.sin(ang) * factor, jnp.cos(ang) * factor
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(x, p, model, kind, positions, mode):
    """One row: ``x[S, d] -> [S, d]`` through a layer of type
    ``kind``."""
    z = sizes(model)
    h, hkv, dh = z["h"], z["hkv"], z["dh"]
    eps = model["rms_norm_eps"]
    window = z["window"] if kind == "sliding_attention" else 0
    freq, factor = inv_freq(model, kind)
    q = rmsnorm(matmul(x, p["q"]["kernel"], 1, mode),
                p["q_norm"]["scale"], eps, mode)        # [S, H, D]
    k = rmsnorm(matmul(x, p["k"]["kernel"], 1, mode),
                p["k_norm"]["scale"], eps, mode)        # [S, Hkv, D]
    v = matmul(x, p["v"]["kernel"], 1, mode)
    q = rounded(rope_halves(q, positions, freq, factor), mode)
    k = rounded(rope_halves(k, positions, freq, factor), mode)
    s_len = x.shape[0]
    q = q.reshape(s_len, hkv, h // hkv, dh)
    block = Q_BLOCK if s_len % Q_BLOCK == 0 else s_len

    def one(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, block, axis=0)
        qpos = jax.lax.dynamic_slice_in_dim(positions, q0, block, axis=0)
        seen = positions[None, :] <= qpos[:, None]
        if window:
            seen &= positions[None, :] > qpos[:, None] - window
        scores = jnp.einsum(
            "qkgd,skd->kgqs", qb, k, precision=HIGHEST) * dh ** -0.5
        probs = rounded(jax.nn.softmax(
            jnp.where(seen[None, None], scores, -jnp.inf), axis=-1), mode)
        return rounded(jnp.einsum(
            "kgqs,skd->qkgd", probs, v, precision=HIGHEST), mode)

    ctx = jax.lax.map(one, jnp.arange(0, s_len, block))
    return matmul(ctx.reshape(s_len, h, dh), p["out"]["kernel"], 2, mode)


def route(x, p, model, mode):
    """``[S, experts]`` weights: nought where an expert is not among
    the token's chosen ``k``."""
    z = sizes(model)
    # the program keeps the router's float32 sums unrounded
    probs = jax.nn.softmax(dense_gqa.matmul(
        rounded(x, mode), p["router"], 1,
        "f32" if mode == "bf16" else mode), axis=-1)
    _, idx = jax.lax.top_k(probs, z["k"])  # equal values: lower id first
    chosen = jnp.zeros(probs.shape, bool).at[
        jnp.arange(probs.shape[0])[:, None], idx].set(True)
    picked = jnp.where(chosen, probs, 0.0)
    if model.get("norm_topk_prob", True):
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return picked


def experts(x, p, model, mode):
    weight = route(x, p, model, mode)

    def add(y, e):
        wi, wg, wo, w_e = e
        return rounded(
            y + w_e[:, None] * gated(x, wi, wg, wo, mode), mode), None

    y, _ = jax.lax.scan(
        add, jnp.zeros_like(x), (p["wi"], p["wg"], p["wo"], weight.T))
    return y


def embed(tokens, outer):
    return outer["embedding"].astype(jnp.float32)[tokens]


def block(x, p, model, kind, positions, mode="f32"):
    """One row through a layer of type ``kind`` (static)."""
    eps = model["rms_norm_eps"]
    x = rounded(x + attention(
        rmsnorm(x, p["ln1"]["scale"], eps, mode), p["attn"], model, kind,
        positions, mode), mode)
    return rounded(x + experts(
        rmsnorm(x, p["ln2"]["scale"], eps, mode), p["moe"], model, mode),
        mode)


def head(x, outer, model, mode="f32"):
    x = rmsnorm(x, outer["ln_f"]["scale"], model["rms_norm_eps"], mode)
    # the program's head gives float32 logits from bfloat16 inputs
    return dense_gqa.matmul(
        rounded(x, mode), outer["lm_head"]["kernel"], 1,
        "f32" if mode == "bf16" else mode)


def forward(tokens, params, model, mode="f32"):
    """Logits ``[S, vocab]`` of ONE row of tokens from a whole tree."""
    positions = jnp.arange(tokens.shape[0])
    x = embed(tokens, params)
    for i in range(model["num_hidden_layers"]):
        x = block(x, params["block_%d" % i], model,
                  model["layer_types"][i], positions, mode)
    return head(x, params, model, mode)
