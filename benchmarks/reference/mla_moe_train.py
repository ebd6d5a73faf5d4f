"""The plain reference of the ``deepseek_v3`` block as it is TRAINED
(Moonlight-16B-A3B's ``config.json``): multi-head latent attention
with no sparse index and, under ``q_lora_rank: null``, no query latent;
a dense gated MLP on the leading layers and sigmoid-routed experts
with shared experts on the rest — forward, the mean next-token loss and
its gradients.  Straight ``jax.numpy`` in float32 at matmul precision
``highest``: no kernel, no sort, no tiles, no absorbed form, nothing
imported from the program.  ``mode="int8"`` is the low-precision
control, as in ``dense_gqa.py`` (whose matmul and norm this file uses):
every weight matmul, the router's too, forward and backward.

Layer equations (``x`` the normed input, ONE row of tokens ``[S, d]``):

- ``q = x W_q`` -> heads of ``nope + rope`` (with a query latent:
  ``q = RMSNorm(x W_qa) W_qb``); ``[c_kv | k_r] = x W_kva``, ``c_kv =
  RMSNorm(c_kv)``; RoPE (interleaved pairs) on the rope part of ``q``
  and on ``k_r``, which every head shares; ``[k_nope | v] = c_kv
  W_kvb`` per head; scores ``(q_nope · k_nope + q_rope · k_r) /
  sqrt(nope + rope)``, causal softmax, ``o = (p v) W_o``.  A block of
  queries at a time, recomputed in the backward pass: the float32
  scores of 16 heads over 8192 x 8192 are 4.3 GB a row whole.
- sparse FFN: ``s = sigmoid(x W_r)``; the ``k`` experts with the
  largest ``s + b`` (``jax.lax.top_k``: ties to the lower id);
  weights ``s_e / sum of the chosen s``, times the scaling factor;
  ``y = shared(x) + sum over chosen AND held e of w_e expert_e(x)`` —
  a dense loop over the HELD experts, each over every token under the
  mask of who chose it: the share of the result that the experts this
  chip holds give (``weights_mla_moe_train.sizes``: ``first``,
  ``held``).  ``b`` moves the choice alone, so its gradient is nought.

``loss_and_grads`` goes a layer and a row at a time (each block's
forward is run again in its backward), so a model at published widths
fits one chip beside its float32 parameters; a caller that follows
optimizer steps takes each block's gradient as it is finished
(``on_grad``).
"""

import functools
import json

import jax
import jax.numpy as jnp

from benchmarks.reference.dense_gqa import HIGHEST, matmul, rmsnorm
from benchmarks.reference.glm_dsa_moe import rope_pairs
from benchmarks.weights_mla_moe_train import ffn_kind, sizes

#: queries at a time, so float32 scores of every head fit
Q_BLOCK = 1024


def _attend(q_nope, q_rope, k_nope, k_r, v, qpos, kpos, scale):
    """A block of queries over the keys up to its last one:
    ``q_* [Q, H, .]``, ``k_nope, v [K, H, .]``, ``k_r [K, dr]``."""
    logits = (
        jnp.einsum("qhd,khd->hqk", q_nope, k_nope, precision=HIGHEST)
        + jnp.einsum("qhd,kd->hqk", q_rope, k_r, precision=HIGHEST)
    ) * scale
    seen = kpos[None, :] <= qpos[:, None]
    probs = jax.nn.softmax(
        jnp.where(seen[None], logits, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST)


def attention(x, p, model, positions, mode):
    """One row: ``x[S, d]`` -> ``[S, d]``."""
    z = sizes(model)
    dn, rkv = z["dn"], z["rkv"]
    theta, eps = model["rope_theta"], model["rms_norm_eps"]
    if z["rq"]:
        c_q = rmsnorm(matmul(x, p["q_a"]["kernel"], 1, mode),
                      p["q_norm"]["scale"], eps)
        q = matmul(c_q, p["q_b"], 1, mode)
    else:
        q = matmul(x, p["q"], 1, mode)               # [S, H, dn+dr]
    q_nope, q_rope = q[..., :dn], rope_pairs(q[..., dn:], positions, theta)
    kv_a = matmul(x, p["kv_a"]["kernel"], 1, mode)
    c_kv = rmsnorm(kv_a[:, :rkv], p["kv_norm"]["scale"], eps)
    k_r = rope_pairs(kv_a[:, rkv:], positions, theta)    # [S, dr]
    kv = matmul(c_kv, p["kv_b"], 1, mode)                # [S, H, dn+dv]
    k_nope, v = kv[..., :dn], kv[..., dn:]
    scale = (dn + z["dr"]) ** -0.5
    s_len = x.shape[0]
    blocks = []
    for q0 in range(0, s_len, Q_BLOCK):
        q1 = min(s_len, q0 + Q_BLOCK)
        blocks.append(jax.checkpoint(
            functools.partial(_attend, scale=scale)
        )(q_nope[q0:q1], q_rope[q0:q1], k_nope[:q1], k_r[:q1], v[:q1],
          positions[q0:q1], positions[:q1]))
    ctx = jnp.concatenate(blocks, axis=0)
    return matmul(ctx, p["out"]["kernel"], 2, mode)


def gated(x, wi, wg, wo, mode):
    gate = jax.nn.silu(matmul(x, wg, 1, mode))
    return matmul(gate * matmul(x, wi, 1, mode), wo, 1, mode)


def route(x, p, model, mode):
    """``(weights [S, experts], chosen [S, experts] bool)``: a weight
    is nought where the expert is not among the token's ``k``."""
    z = sizes(model)
    score = jax.nn.sigmoid(matmul(x, p["router"], 1, mode))
    _, idx = jax.lax.top_k(
        score + p["router_bias"].astype(jnp.float32), z["k"])
    chosen = jnp.zeros(score.shape, bool).at[
        jnp.arange(score.shape[0])[:, None], idx].set(True)
    picked = jnp.where(chosen, score, 0.0)
    weights = (picked / jnp.sum(picked, axis=-1, keepdims=True)
               * model["routed_scaling_factor"])
    return weights, chosen


def sparse_ffn(x, p, model, mode):
    """``(y [S, d], choices that landed on the held experts)``."""
    z = sizes(model)
    weight, chosen = route(x, p, model, mode)
    y = gated(x, p["shared_wi"]["kernel"], p["shared_wg"]["kernel"],
              p["shared_wo"]["kernel"], mode)
    lo, hi = z["first"], z["first"] + z["held"]

    def add(y, e):
        wi, wg, wo, w_e = e
        return y + w_e[:, None] * gated(x, wi, wg, wo, mode), None

    y, _ = jax.lax.scan(
        add, y, (p["wi"], p["wg"], p["wo"], weight[:, lo:hi].T))
    return y, jnp.sum(chosen[:, lo:hi].astype(jnp.int32))


def embed(tokens, outer):
    return outer["embedding"].astype(jnp.float32)[tokens]


def block(x, p, model, kind, positions, mode="f32"):
    """One row through a layer of ``kind`` ("dense" | "sparse"):
    ``(x [S, d], local choices)``."""
    eps = model["rms_norm_eps"]
    x = x + attention(
        rmsnorm(x, p["ln1"]["scale"], eps), p["attn"], model, positions,
        mode)
    h = rmsnorm(x, p["ln2"]["scale"], eps)
    if kind == "dense":
        y = gated(h, p["mlp"]["wi"]["kernel"], p["mlp"]["wg"]["kernel"],
                  p["mlp"]["wo"]["kernel"], mode)
        return x + y, jnp.zeros((), jnp.int32)
    y, local = sparse_ffn(h, p["moe"], model, mode)
    return x + y, local


def head(x, outer, model, mode="f32"):
    x = rmsnorm(x, outer["ln_f"]["scale"], model["rms_norm_eps"])
    return matmul(x, outer["lm_head"]["kernel"], 1, mode)


def row_loss(x, ln_f, lm_head, tokens, model, mode):
    """Mean next-token cross-entropy of ONE row over the vocabulary
    rows held."""
    logits = head(
        x, {"ln_f": ln_f, "lm_head": lm_head}, model, mode)[:-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(
        logp, tokens[1:, None], axis=-1))


# ----------------------------------------------------------------------
# loss and gradients, a layer and a row at a time
# ----------------------------------------------------------------------


def _items(model):
    return json.dumps(
        {k: v for k, v in model.items()
         if k not in ("assumed", "deployment", "source", "program",
                      "optimizer", "published")},
        sort_keys=True)


@functools.partial(jax.jit, static_argnames=("items", "kind", "mode"))
def _fwd(x, p, items, kind, mode):
    return block(x, p, json.loads(items), kind,
                 jnp.arange(x.shape[0]), mode)


@functools.partial(jax.jit, static_argnames=("items", "kind", "mode"))
def _bwd(x, p, dy, items, kind, mode):
    model = json.loads(items)
    _, vjp = jax.vjp(
        lambda x, p: block(x, p, model, kind, jnp.arange(x.shape[0]),
                           mode)[0], x, p)
    return vjp(dy)


@functools.partial(jax.jit, static_argnames=("items", "mode"))
def _top(x, ln_f, lm_head, tokens, items, mode):
    model = json.loads(items)
    return jax.value_and_grad(
        lambda x, a, b: row_loss(x, a, b, tokens, model, mode),
        argnums=(0, 1, 2))(x, ln_f, lm_head)


@jax.jit
def _bottom(tokens, dx, embedding):
    return jnp.zeros(embedding.shape, jnp.float32).at[tokens].add(dx)


@functools.partial(jax.jit, donate_argnums=(0,))
def _add(a, b, w):
    return jax.tree.map(lambda x, y: x + w * y, a, b)


@jax.jit
def _scaled(tree, w):
    return jax.tree.map(lambda x: w * x, tree)


def loss_and_grads(params, tokens, model, mode="f32", rows=None,
                   on_grad=None):
    """Mean next-token loss of ``tokens [R, S]`` (of its ``rows`` alone
    where given: a planted fault) and the gradient of every leaf of
    ``params``, plus the choices that landed on the held experts,
    summed over rows and sparse layers.  Parts of the gradient are
    handed to ``on_grad(name, part)`` as they are finished — ``"head"``
    (``ln_f`` and ``lm_head``), ``"block_<i>"`` from the last block
    down, ``"embedding"`` — so that a caller can use and drop each
    (``params`` is read a part at a time, at the moment of use); with
    no ``on_grad`` the whole tree is returned:
    ``(loss, grads or None, local)``."""
    items = _items(model)
    n_layers = model["num_hidden_layers"]
    kinds = [ffn_kind(model, i) for i in range(n_layers)]
    use = list(range(len(tokens)) if rows is None else rows)
    w = 1.0 / len(use)
    grads = {}

    def give(name, part):
        if on_grad is not None:
            on_grad(name, part)
        elif name == "head":
            grads.update(part)
        else:
            grads[name] = part

    toks = [jnp.asarray(tokens[r], jnp.int32) for r in use]
    acts, local = [], 0
    for t in toks:
        xs = [embed(t, params)]
        for i in range(n_layers):
            x, n = _fwd(xs[-1], params["block_%d" % i], items, kinds[i],
                        mode)
            xs.append(x)
            local += int(n)
        acts.append(xs)
    loss, dxs, part = 0.0, [], None
    for t, xs in zip(toks, acts):
        value, (dx, dln, dhead) = _top(
            xs.pop(), params["ln_f"], params["lm_head"], t, items, mode)
        loss += float(value) * w
        dxs.append(dx)
        piece = {"ln_f": dln, "lm_head": dhead}
        part = _scaled(piece, w) if part is None else _add(part, piece, w)
    give("head", part)
    for i in reversed(range(n_layers)):
        name, part = "block_%d" % i, None
        for r, xs in enumerate(acts):
            dxs[r], dp = _bwd(
                xs.pop(), params[name], dxs[r], items, kinds[i], mode)
            part = _scaled(dp, w) if part is None else _add(part, dp, w)
        give(name, part)
    part = None
    for t, dx in zip(toks, dxs):
        piece = _bottom(t, dx, params["embedding"])
        part = _scaled(piece, w) if part is None else _add(part, piece, w)
    give("embedding", part)
    return loss, (grads if on_grad is None else None), local


def forward(tokens, params, model, mode="f32"):
    """Logits ``[S, vocab]`` of ONE row of tokens from a whole tree."""
    positions = jnp.arange(tokens.shape[0])
    x = embed(tokens, params)
    for i in range(model["num_hidden_layers"]):
        x, _ = block(x, params["block_%d" % i], model,
                     ffn_kind(model, i), positions, mode)
    return head(x, params, model, mode)

