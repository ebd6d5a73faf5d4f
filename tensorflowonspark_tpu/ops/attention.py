"""Attention dispatcher.

One entry point, four implementations (SURVEY.md §2.3 build targets —
the reference has none of these, grep-verified SURVEY.md §5):

- ``dot``     — plain XLA einsum attention (always available; the
                numerics reference for every other impl's tests);
- ``flash``   — blockwise pallas TPU kernel, O(seq) memory
                (:mod:`tensorflowonspark_tpu.ops.flash_attention`);
- ``ring``    — sequence-parallel ring attention over the ``seq`` mesh
                axis (:mod:`tensorflowonspark_tpu.ops.ring_attention`);
- ``ulysses`` — all-to-all sequence↔head re-sharding
                (:mod:`tensorflowonspark_tpu.ops.ulysses`).

Shapes follow the ``[batch, seq, heads, head_dim]`` convention
throughout (the TPU-friendly layout: heads*head_dim contiguous for the
MXU, seq shardable for context parallelism).
"""

import jax
import jax.numpy as jnp

_IMPLS = ("dot", "flash", "ring", "ulysses")


def dot_attention(q, k, v, causal=True, scale=None, mask=None, window=0,
                  k_scale=None, v_scale=None):
    """Plain softmax attention via XLA einsums.

    Args:
      q: ``[B, Sq, H, D]``; k, v: ``[B, Sk, Hkv, D]`` where ``Hkv``
        divides ``H`` (grouped-query attention: each kv head serves
        ``H/Hkv`` query heads; ``Hkv == H`` is ordinary MHA).  The
        grouped einsums never materialize repeated k/v.
      causal: apply a causal mask (positions aligned at the end).
      mask: optional additive mask broadcastable to ``[B, H, Sq, Sk]``.
      window: ``> 0`` restricts each query to the last ``window``
        positions (sliding-window attention; requires ``causal``).
      k_scale, v_scale: optional per-position/per-head dequant scales
        ``[B, Sk, Hkv, 1]`` for int8 ``k``/``v`` banks (the quantized
        KV cache).  Instead of dequantizing the banks (which would
        materialize a full-width copy), the factored identities are
        used: ``q·(k*ks) == (q·k)*ks`` scales the LOGITS, and
        ``Σ p·(v*vs) == Σ (p*vs)·v`` folds into the probabilities —
        the int8 banks reach the einsums as pure converts, which XLA
        fuses into the operand read.
    Returns ``[B, Sq, H, D]`` in ``q.dtype``.
    """
    if window:
        if window < 0:
            raise ValueError(
                "window must be positive, got {0}".format(window)
            )
        if not causal:
            raise ValueError("window attention requires causal=True")
    orig_dtype = q.dtype
    # int8 (quantized-cache) banks convert up WITHOUT their scales —
    # a bare convert fuses into the dot; convert-multiply does not
    if k.dtype != orig_dtype:
        k = k.astype(orig_dtype)
    if v.dtype != orig_dtype:
        v = v.astype(orig_dtype)
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    h, hkv = q.shape[2], k.shape[2]
    if h % hkv != 0:
        raise ValueError(
            "query heads ({0}) must be a multiple of kv heads "
            "({1})".format(h, hkv)
        )
    g = h // hkv
    # [B, Sk, Hkv, 1] -> [B, Hkv, 1, Sk] (broadcast over queries)
    ks_t = (
        jnp.transpose(k_scale, (0, 2, 3, 1))
        if k_scale is not None else None
    )
    vs_t = (
        jnp.transpose(v_scale, (0, 2, 3, 1))
        if v_scale is not None else None
    )
    # accumulate logits/softmax in f32 for stability (bf16 inputs stay
    # bf16 through the matmuls — MXU native — but the reduction is f32)
    if g == 1:
        logits = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
        )
        if ks_t is not None:
            logits = logits * ks_t
    else:
        qg = q.reshape(q.shape[0], q.shape[1], hkv, g, q.shape[3])
        logits = jnp.einsum(
            "bqhgd,bkhd->bhgqk", qg, k,
            preferred_element_type=jnp.float32,
        )
        if ks_t is not None:
            logits = logits * ks_t[:, :, None]
        logits = logits.reshape(
            q.shape[0], h, q.shape[1], k.shape[1]
        )
    logits = logits * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        # queries occupy the LAST sq positions of the key timeline, which
        # makes the same mask correct for full self-attention (sq == sk)
        # and decode steps (sq == 1)
        qpos = jnp.arange(sq)[:, None] + (sk - sq)
        kpos = jnp.arange(sk)[None, :]
        visible = qpos >= kpos
        if window:
            visible = jnp.logical_and(visible, kpos > qpos - window)
        logits = jnp.where(visible, logits, -jnp.inf)
    if mask is not None:
        logits = logits + mask
    weights = jax.nn.softmax(logits, axis=-1)
    if g == 1:
        if vs_t is not None:
            weights = weights * vs_t
        out = jnp.einsum(
            "bhqk,bkhd->bqhd", weights.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        )
    else:
        wg = weights.reshape(
            q.shape[0], hkv, g, q.shape[1], k.shape[1]
        )
        if vs_t is not None:
            wg = wg * vs_t[:, :, None]
        out = jnp.einsum(
            "bhgqk,bkhd->bqhgd", wg.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        ).reshape(q.shape[0], q.shape[1], h, q.shape[3])
    return out.astype(orig_dtype)


def attention(q, k, v, impl="dot", causal=True, scale=None, mesh=None,
              seq_axis="seq", block_q=1024, block_k=1024,
              ring_impl="flash", window=0):
    """Dispatch to an attention implementation (see module docstring).

    ``ring``/``ulysses`` dispatch on ``mesh``: with ``mesh=None`` the
    inputs must be local shards and the call must already be inside
    ``shard_map``-decorated code where ``seq_axis`` is bound; with a mesh
    given, the inputs are *global* arrays and the op wraps itself in a
    ``shard_map`` over the mesh's ``seq`` axis (do NOT pass a mesh from
    code that is itself under ``shard_map``).  ``flash`` under a
    multi-device ``mesh`` wraps itself the same way over the batch and
    head axes (a model jitted over real chips MUST pass its mesh:
    GSPMD cannot partition a Mosaic kernel); it runs the pallas
    kernels in interpret mode off-TPU so the same model runs in CPU
    tests.  ``block_q``/``block_k`` bound the pallas tiles for both the
    ``flash`` impl and ``ring``'s flash inner step; ``ring_impl``
    selects ring's inner step (``"flash"`` or the dense einsum
    numerics reference).
    """
    if impl not in _IMPLS:
        raise ValueError("unknown attention impl {0!r}; one of {1}".format(impl, _IMPLS))
    if impl == "flash":
        from tensorflowonspark_tpu.ops.flash_attention import (
            flash_attention,
            flash_attention_sharded,
        )

        if mesh is not None and mesh.size > 1:
            return flash_attention_sharded(
                q, k, v, mesh, causal=causal, scale=scale,
                block_q=block_q, block_k=block_k, window=window,
            )
        return flash_attention(
            q, k, v, causal=causal, scale=scale, block_q=block_q,
            block_k=block_k, window=window,
        )
    if impl == "ring":
        from tensorflowonspark_tpu.ops.ring_attention import (
            ring_attention,
            ring_attention_sharded,
        )

        if mesh is not None:
            return ring_attention_sharded(
                q, k, v, mesh, causal=causal, scale=scale,
                axis_name=seq_axis, impl=ring_impl,
                block_q=block_q, block_k=block_k, window=window,
            )
        return ring_attention(
            q, k, v, causal=causal, scale=scale, axis_name=seq_axis,
            impl=ring_impl, block_q=block_q, block_k=block_k,
            window=window,
        )
    if impl == "ulysses":
        from tensorflowonspark_tpu.ops.ulysses import (
            ulysses_attention,
            ulysses_attention_sharded,
        )

        if mesh is not None:
            return ulysses_attention_sharded(
                q, k, v, mesh, causal=causal, scale=scale,
                axis_name=seq_axis, block_q=block_q, block_k=block_k,
                window=window,
            )
        return ulysses_attention(
            q, k, v, causal=causal, scale=scale, axis_name=seq_axis,
            block_q=block_q, block_k=block_k, window=window,
        )
    return dot_attention(q, k, v, causal=causal, scale=scale, window=window)
