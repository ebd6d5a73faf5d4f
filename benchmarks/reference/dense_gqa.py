"""The plain reference: a pre-norm decoder with grouped-query
attention, split-half RoPE, one sliding window and a gated (SwiGLU)
MLP, as Mistral-7B-v0.1's published description has it — forward, and
loss with gradients.  Straight ``jax.numpy`` in float32 at matmul
precision ``highest``: no kernel, no cache, no batching tricks, and
nothing imported from the program.

``mode`` selects the arithmetic of every matmul: ``"f32"`` is the
reference; ``"int8"`` is the control (the nearest precision below the
bf16 the configurations state): both operands rounded to a symmetric
int8 grid — weights per output channel, activations per token — before
an exact product, which is what an int8 MXU path computes, in the
forward pass and in the two products of the backward pass.

Weights arrive per layer (see ``benchmarks/weights.py``) so a caller
can run a model that does not fit whole: ``embed`` → ``block`` × L →
``head``.
"""

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _fake_int8(x, axis):
    """Round ``x`` to 255 levels, symmetric, one scale per slice along
    ``axis`` (the contraction axis)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.round(x / scale) * scale


def _dot(a, b):
    return jnp.dot(a, b, precision=HIGHEST,
                   preferred_element_type=jnp.float32)


@jax.custom_vjp
def _int8_dot(x, w):
    """``x[M, K] @ w[K, N]`` as an int8 path computes it: both operands
    rounded along the contraction before an exact product — in the
    forward pass and in both products of the backward pass."""
    return _dot(_fake_int8(x, 1), _fake_int8(w, 0))


def _int8_dot_fwd(x, w):
    return _int8_dot(x, w), (x, w)


def _int8_dot_bwd(saved, dy):
    x, w = saved
    dx = _dot(_fake_int8(dy, 1), _fake_int8(w, 1).T)
    dw = _dot(_fake_int8(x, 0).T, _fake_int8(dy, 0))
    return dx, dw


_int8_dot.defvjp(_int8_dot_fwd, _int8_dot_bwd)


def matmul(x, w, n_contract, mode):
    """``x[..., c1..cn] @ w[c1..cn, ...]`` in float32."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    lead, tail = x.shape[:x.ndim - n_contract], w.shape[n_contract:]
    x2 = x.reshape(-1, int(np.prod(w.shape[:n_contract])))
    w2 = w.reshape(x2.shape[1], -1)
    if mode == "int8":
        out = _int8_dot(x2, w2)
    elif mode == "f32":
        out = _dot(x2, w2)
    else:
        raise ValueError("mode must be 'f32' or 'int8', got %r" % (mode,))
    return out.reshape(lead + tail)


def rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def rope(x, positions, theta):
    """Rotate halves: ``x[B, S, H, D]``, pairs ``(i, i + D/2)``."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq
    sin, cos = jnp.sin(ang)[:, :, None], jnp.cos(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )


#: queries are taken this many at a time once a sequence is longer, so
#: that the float32 scores of a full-width layer fit on a chip
Q_BLOCK = 1024


def _attend(q, k, v, qpos, kpos, window):
    """Softmax attention of a block of queries over the keys given:
    ``q[B, Q, Hkv, G, D]``, ``k, v[B, S, Hkv, D]``."""
    scores = jnp.einsum(
        "bqkgd,bskd->bkgqs", q, k, precision=HIGHEST
    ) * q.shape[-1] ** -0.5
    seen = kpos[:, None, :] <= qpos[:, :, None]
    if window:
        seen &= kpos[:, None, :] > qpos[:, :, None] - window
    scores = jnp.where(seen[:, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bkgqs,bskd->bqkgd", probs, v, precision=HIGHEST)


def attention(x, p, model, positions, mode):
    hkv = p["k"]["kernel"].shape[1]
    h, dh = p["q"]["kernel"].shape[1:]
    window = model.get("sliding_window") or 0
    q = rope(matmul(x, p["q"]["kernel"], 1, mode), positions,
             model["rope_theta"])
    k = rope(matmul(x, p["k"]["kernel"], 1, mode), positions,
             model["rope_theta"])
    v = matmul(x, p["v"]["kernel"], 1, mode)
    b, s = x.shape[:2]
    q = q.reshape(b, s, hkv, h // hkv, dh)
    if s <= Q_BLOCK:
        ctx = _attend(q, k, v, positions, positions, window)
    else:
        # the same sums, a block of queries at a time; a block sees no
        # key after its last query nor before its first one's window
        # (positions run 0..S-1 in order), and is recomputed in the
        # backward pass instead of kept
        blocks = []
        for q0 in range(0, s, Q_BLOCK):
            q1 = min(s, q0 + Q_BLOCK)
            k0 = max(0, q0 - window + 1) if window else 0
            blocks.append(jax.checkpoint(
                lambda qb, kb, vb, qp, kp: _attend(qb, kb, vb, qp, kp, window)
            )(q[:, q0:q1], k[:, k0:q1], v[:, k0:q1],
              positions[:, q0:q1], positions[:, k0:q1]))
        ctx = jnp.concatenate(blocks, axis=1)
    return matmul(ctx.reshape(b, s, h, dh), p["out"]["kernel"], 2, mode)


def mlp(x, p, mode):
    gate = jax.nn.silu(matmul(x, p["wg"]["kernel"], 1, mode))
    up = matmul(x, p["wi"]["kernel"], 1, mode)
    return matmul(gate * up, p["wo"]["kernel"], 1, mode)


def embed(tokens, outer):
    return outer["embedding"].astype(jnp.float32)[tokens]


def block(x, p, model, positions, mode="f32"):
    eps = model["rms_norm_eps"]
    x = x + attention(
        rmsnorm(x, p["ln1"]["scale"], eps), p["attn"], model, positions,
        mode,
    )
    return x + mlp(rmsnorm(x, p["ln2"]["scale"], eps), p["mlp"], mode)


def head(x, outer, model, mode="f32"):
    x = rmsnorm(x, outer["ln_f"]["scale"], model["rms_norm_eps"])
    return matmul(x, outer["lm_head"]["kernel"], 1, mode)


def forward(tokens, params, model, mode="f32"):
    """Logits ``[B, S, vocab]`` of whole sequences from a whole tree."""
    positions = jnp.broadcast_to(
        jnp.arange(tokens.shape[1]), tokens.shape
    )
    x = embed(tokens, params)
    for i in range(model["num_hidden_layers"]):
        x = block(x, params["block_%d" % i], model, positions, mode)
    return head(x, params, model, mode)


def loss(params, tokens, model, mode="f32"):
    """Mean next-token cross-entropy over ``tokens[B, S]``."""
    logits = forward(tokens, params, model, mode)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return jnp.mean(nll)
