"""The plain reference of the ``laguna`` block (Laguna-S-2.1's
``config.json``): a pre-norm decoder whose layers are of two TYPES —
sliding-window and full attention — each with its own query heads and
its own RoPE, a sigmoid gate on every head's output, a dense gated MLP
on the layers ``mlp_layer_types`` calls dense and softmax-routed
experts with a shared expert on the rest; forward only.  Straight
``jax.numpy`` in float32 at matmul precision ``highest``: no kernel,
no cache, no ring, no batching, nothing imported from the program; a
row at a time and a layer's weights at a time, so that it fits at the
cell's size.  ``mode="int8"`` is the low-precision control and
``mode="bf16"`` the same equations at the program's precision, both as
``glm_dsa_moe.py`` has them (whose ``matmul``, ``rmsnorm``, ``gated``
and ``rounded`` this file uses).

The layer's equations (``x`` one row of tokens, ``[S, hidden]``; layer
``l`` of type ``kappa(l)`` with ``H_l`` query heads,
``num_attention_heads_per_layer[l]``):

``h = x + Attn_l(RMSNorm(x))``, ``y = h + FFN_l(RMSNorm(h))``; after
the last layer a final RMSNorm and the untied head.

*Attention.*  ``q, k, v = x W_q, x W_k, x W_v`` (no bias), shaped
``[S, H_l | Hkv | Hkv, head_dim]``; ``q`` and ``k`` each RMS-normed
over ``head_dim`` with a learned scale; RoPE on the leading ``R =
head_dim * partial_rotary_factor`` dimensions of the layer TYPE, in
split halves of those (pair ``i`` is ``(i, i + R/2)``), the other
``head_dim - R`` passed through:

- sliding (``rope_type`` default): ``inv_freq_i = theta ** (-2i / R)``;
- full (YaRN, as ``transformers``' ``_compute_yarn_parameters`` with
  ``truncate``, over the ROTATED width ``R``): ``extrap_i = theta **
  (-2i / R)``, ``interp_i = extrap_i / factor``, ``c(n) = R ln(original
  / (2 pi n)) / (2 ln theta)``, ``low = max(floor(c(beta_fast)), 0)``,
  ``high = min(ceil(c(beta_slow)), R - 1)``, ``ramp_i = clip((i - low)
  / (high - low), 0, 1)``, ``inv_freq_i = interp_i ramp_i + extrap_i (1
  - ramp_i)``, and cos and sin of the rotated pairs both times
  ``attention_factor``;

scores ``q . k / sqrt(head_dim)``; query ``i`` sees keys ``j <= i``
and, on a sliding layer, ``j > i - sliding_window``; softmax in
float32; query head ``h`` reads key/value head ``floor(h / (H_l /
Hkv))``; the gate ``g = sigmoid(x W_g)``, ``W_g [hidden, H_l]``, one
scalar a head and token; ``o = sum_h (g_h a_h) W_o[h]``.

*FFN.*  Dense: ``W_down(silu(W_gate x) * W_up x)`` at
``intermediate_size``.  Sparse: ``p = softmax(x W_r)`` over all
``expert_share["of"]`` experts in float32, the ``num_experts_per_tok``
largest (ties to the lower id), ``w = routed_scaling * p_chosen / sum
p_chosen`` (``norm_topk_prob``), ``FFN(x) = shared(x) + sum over
chosen AND held e of w_e expert_e(x)`` — the share of the result that
the experts this chip holds give (``weights_gated_swa_moe.sizes``:
``first``, ``held``); every expert and the shared one a SwiGLU.

Departures and assumptions (the configuration's ``assumed``): the
config has no key for the q/k norm — its key names are Qwen3's family,
whose attention norms q and k per head, so it is built; the gate is
read as above (from the layer's normed input, a sigmoid, no bias, on
each head's output before ``W_o``); the shared expert has no gate of
its own (the config has no key for one); the router's product is taken
at the reference's precision (float32; under ``mode="int8"`` rounded
like every other matmul); the attention factor multiplies the rotated
pairs alone.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import dense_gqa
from benchmarks.reference.dense_gqa import HIGHEST
from benchmarks.reference.glm_dsa_moe import gated, matmul, rmsnorm, rounded
from benchmarks.weights_gated_swa_moe import heads, layer_kinds, sizes

#: queries at a time, so the float32 scores of 72 heads over a row of
#: twenty thousand keys fit
Q_BLOCK = 256


def rotary(model, kind):
    """Leading dimensions of a head that layer type ``kind`` rotates."""
    p = model["rope_parameters"][kind]
    return int(model["head_dim"] * float(p.get("partial_rotary_factor", 1)))


def inv_freq(model, kind):
    """``([R/2] float32 inverse frequencies, factor on cos and sin)``
    of layer type ``kind``, from ``rope_parameters[kind]``, over the
    rotated width ``R`` (:func:`rotary`)."""
    p = model["rope_parameters"][kind]
    dim = rotary(model, kind)
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


def rope_leading(x, positions, freq, factor):
    """Rotate pairs ``(i, i + R/2)`` of the leading ``R = 2 len(freq)``
    dimensions of ``x[S, H, D]`` by ``positions[S]``; the rest pass."""
    width = 2 * len(freq)
    ang = positions.astype(jnp.float32)[:, None, None] * jnp.asarray(freq)
    sin, cos = jnp.sin(ang) * factor, jnp.cos(ang) * factor
    half = width // 2
    x1, x2 = x[..., :half], x[..., half:width]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., width:]], axis=-1)


def attention(x, p, model, layer, positions, mode):
    """One row: ``x[S, d] -> [S, d]`` through layer ``layer``."""
    z = sizes(model)
    kind = layer_kinds(model, layer)[0]
    h, hkv, dh = heads(model, layer), z["hkv"], z["dh"]
    eps = model["rms_norm_eps"]
    window = z["window"] if kind == "sliding_attention" else 0
    freq, factor = inv_freq(model, kind)
    q = rmsnorm(matmul(x, p["q"]["kernel"], 1, mode),
                p["q_norm"]["scale"], eps, mode)        # [S, H, D]
    k = rmsnorm(matmul(x, p["k"]["kernel"], 1, mode),
                p["k_norm"]["scale"], eps, mode)        # [S, Hkv, D]
    v = matmul(x, p["v"]["kernel"], 1, mode)
    q = rounded(rope_leading(q, positions, freq, factor), mode)
    k = rounded(rope_leading(k, positions, freq, factor), mode)
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
    ctx = ctx.reshape(s_len, h, dh)
    if model.get("gating"):
        gate = jax.nn.sigmoid(matmul(x, p["gate"]["kernel"], 1, mode))
        ctx = rounded(ctx * gate[..., None], mode)               # [S, H, D]
    return matmul(ctx, p["out"]["kernel"], 2, mode)


def route(x, p, model, mode):
    """``[S, experts]`` weights over EVERY expert of the layer: nought
    where an expert is not among the token's chosen ``k``."""
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
    return picked * model["moe_routed_scaling_factor"]


def sparse_ffn(x, p, model, mode):
    """The shared expert and this share's held experts."""
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


def block(x, p, model, layer, positions, mode="f32"):
    """One row through layer ``layer`` (a static index: it picks the
    layer's kinds and heads)."""
    eps = model["rms_norm_eps"]
    x = rounded(x + attention(
        rmsnorm(x, p["ln1"]["scale"], eps, mode), p["attn"], model, layer,
        positions, mode), mode)
    h = rmsnorm(x, p["ln2"]["scale"], eps, mode)
    if layer_kinds(model, layer)[1] == "dense":
        y = gated(h, p["mlp"]["wi"]["kernel"], p["mlp"]["wg"]["kernel"],
                  p["mlp"]["wo"]["kernel"], mode)
    else:
        y = sparse_ffn(h, p["moe"], model, mode)
    return rounded(x + y, mode)


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
        x = block(x, params["block_%d" % i], model, i, positions, mode)
    return head(x, params, model, mode)
