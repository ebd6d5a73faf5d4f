"""Blockwise (flash) attention as a pallas TPU kernel.

New TPU-first capability with no reference analogue (the reference
delegated all compute to TensorFlow, SURVEY.md §2 'Native-code reality
check'; long-context support is absent there, SURVEY.md §5).  This is
the single-chip building block that :mod:`.ring_attention` composes into
sequence parallelism.

Algorithm: FlashAttention-2-style online softmax.  The forward kernel
streams key/value blocks through VMEM against a resident query block,
keeping a running max ``m``, normalizer ``l``, and accumulator — O(seq)
memory instead of the O(seq²) logits matrix.  The backward pass is two
more pallas kernels (dq, and dk/dv) that recompute probabilities from
the saved log-sum-exp rather than storing them.

TPU mapping:
- grid = (batch, heads, q-blocks, k-blocks): the K/V *blocks* stream
  through VMEM via the trailing (sequential, "arbitrary") grid
  dimension while running state lives in VMEM scratch — only
  O(block) memory per core, so sequence length is HBM-bound, not
  VMEM-bound (full-array K/V blocks capped usable seq at ~8k);
- causal q/k block pairs that are fully masked are skipped with
  ``pl.when`` (no wasted MXU work on the upper triangle);
- the matmuls hit the MXU with ``preferred_element_type=f32`` (bf16
  operands stay MXU-native — no f32 upcast; softmax state alone is
  f32); block sizes default to 1024×1024 (swept fastest on v5e at
  head_dim 128) — multiples of the (8,128) f32 / (16,128) bf16 tiles;
- lse/delta tensors carry a trailing singleton lane axis
  ``(B, H, S, 1)``: Mosaic requires the last two block dims to be
  (8k, 128k) or equal to the array's;
- off-TPU (CPU tests) the same kernels run under ``interpret=True`` so
  numerics are verified against :func:`..attention.dot_attention`
  without TPU hardware (mirrors the reference's shrink-don't-mock test
  stance, SURVEY.md §4).
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from tensorflowonspark_tpu import compat

NEG_INF = -1e30  # finite mask sentinel: keeps exp() at 0 without NaNs
#: names (``jax.ad_checkpoint.checkpoint_name``) of the differentiated
#: forward's output and log-sum-exp, the residuals the backward kernels
#: read besides q, k and v.  ``Transformer``'s ``remat_policy="block"``
#: keeps them, so a rematerialised block's backward makes q, k and v
#: again but runs no second forward kernel.  The primal-only path
#: (serving prefill) names nothing
FLASH_SAVED = ("flash_out", "flash_lse")


def _scratch(shape, dtype):
    """VMEM scratch allocation that also works in interpret mode."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)


def _compiler_params():
    """Grid semantics for Mosaic: batch/heads/outer-block dims are
    embarrassingly parallel; only the trailing streaming dim (the
    online-softmax / gradient accumulation) is order-dependent.
    Declaring this lets the compiler schedule/pipeline the parallel
    dims freely instead of assuming a fully sequential grid."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
    )


def _causal_mask(qi, kj, block_q, block_k, window=0, q_offset=0):
    """Causal mask for block (qi, kj); ``window > 0`` additionally
    drops keys more than ``window - 1`` positions behind the query
    (sliding-window / local attention).  ``q_offset`` shifts the query
    positions — ring attention uses it for visiting kv chunks from
    ``q_offset`` positions earlier in the global sequence (the offset
    is static per ring distance, so each distance gets its own
    specialized kernel)."""
    qpos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    kpos = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    mask = qpos >= kpos
    if window:
        mask = jnp.logical_and(mask, kpos > qpos - window)
    return mask


def _block_relevant(qi, kj, block_q, block_k, causal, window, q_offset=0):
    """Whether block (qi, kj) contributes anything: causal skips blocks
    strictly above the diagonal; a window additionally skips blocks
    entirely behind the horizon — the compute saving that makes local
    attention O(S*W) instead of O(S^2/2)."""
    relevant = True
    if causal:
        relevant = kj * block_k < (qi + 1) * block_q + q_offset
    if window:
        relevant = jnp.logical_and(
            relevant,
            (kj + 1) * block_k > qi * block_q + q_offset - window + 1,
        )
    return relevant


def _diag_block(qi, jj, block_q, block_k):
    """Banded kv walk: j-th step visits kv block (diagonal - j).  The
    ONE definition both the kernels and the BlockSpec index maps use —
    fetch and compute must address the same block."""
    return ((qi + 1) * block_q - 1) // block_k - jj


def _q_band_block(kj, jj, block_q, block_k):
    """Banded q walk for dk/dv: j-th step visits q block
    (first-on-or-after-diagonal + j)."""
    return kj * block_k // block_q + jj


def _band_steps(window, block_a, block_b, total_b):
    """Grid size of the trailing (streamed) dim when windowed: how many
    ``block_b``-wide blocks a ``block_a``-wide resident block can touch
    under a ``window`` horizon (plus the diagonal spill).  Falling back
    to the full count means banding is off (window >= seq)."""
    band = (block_a + window - 2) // block_b + 2
    return min(total_b, band)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, block_q, block_k, grid_steps,
                window=0, banded=False, q_offset=0):
    qi = pl.program_id(2)
    jj = pl.program_id(3)
    if banded:
        # only blocks inside the window band are ever fetched
        # (O(S*W) DMA, not O(S^2))
        kj = _diag_block(qi, jj, block_q, block_k)
        in_range = kj >= 0
    else:
        kj = jj
        in_range = True

    @pl.when(jj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    relevant = jnp.logical_and(
        in_range,
        _block_relevant(
            qi, kj, block_q, block_k, causal, window, q_offset
        ),
    )

    @pl.when(relevant)
    def _compute():
        # operands stay in their input dtype (bf16 on TPU): the MXU
        # multiplies bf16 natively with f32 accumulation via
        # preferred_element_type — upcasting to f32 first would run the
        # matmuls at the ~4x slower f32 rate.  Softmax state (m, l, p)
        # is f32 for stability; p is cast back to the operand dtype for
        # the PV matmul (FlashAttention-2's mixed-precision recipe).
        q = q_ref[0, 0]  # [block_q, d]
        k = k_ref[0, 0]  # [block_k, d]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [block_q, block_k] f32
        if causal:
            s = jnp.where(
                _causal_mask(qi, kj, block_q, block_k, window, q_offset),
                s, NEG_INF,
            )
        m_prev = m_scr[:, 0]
        l_prev = l_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        m_scr[:, 0] = m_new
        l_scr[:, 0] = l_prev * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(jj == grid_steps - 1)
    def _finalize():
        l_safe = jnp.maximum(l_scr[:, 0], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0, :, 0] = m_scr[:, 0] + jnp.log(l_safe)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, scale, causal, block_q, block_k, grid_steps,
               window=0, banded=False, q_offset=0):
    qi = pl.program_id(2)
    jj = pl.program_id(3)
    if banded:
        kj = _diag_block(qi, jj, block_q, block_k)
        in_range = kj >= 0
    else:
        kj = jj
        in_range = True

    @pl.when(jj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    relevant = jnp.logical_and(
        in_range,
        _block_relevant(
            qi, kj, block_q, block_k, causal, window, q_offset
        ),
    )

    @pl.when(relevant)
    def _compute():
        # operand-dtype matmuls (see _fwd_kernel note); p/ds are f32
        # intermediates cast to the operand dtype at the MXU boundary
        q = q_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0, :, 0]  # [block_q]
        delta = delta_ref[0, 0, :, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            s = jnp.where(
                _causal_mask(qi, kj, block_q, block_k, window, q_offset),
                s, NEG_INF,
            )
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta[:, None]) * scale
        dq_scr[...] = dq_scr[...] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(jj == grid_steps - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *,
                scale, causal, block_q, block_k, num_q_blocks,
                grid_steps, window=0, banded=False, q_offset=0):
    kj = pl.program_id(2)
    jj = pl.program_id(3)
    if banded:
        qi = _q_band_block(kj, jj, block_q, block_k)
        in_range = qi < num_q_blocks
    else:
        qi = jj
        in_range = True

    @pl.when(jj == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    relevant = jnp.logical_and(
        in_range,
        _block_relevant(
            qi, kj, block_q, block_k, causal, window, q_offset
        ),
    )

    @pl.when(relevant)
    def _compute():
        # operand-dtype matmuls (see _fwd_kernel note)
        k = k_ref[0, 0]  # [block_k, d]
        v = v_ref[0, 0]
        q = q_ref[0, 0]  # [block_q, d]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0, :, 0]
        delta = delta_ref[0, 0, :, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            s = jnp.where(
                _causal_mask(qi, kj, block_q, block_k, window, q_offset),
                s, NEG_INF,
            )
        p = jnp.exp(s - lse[:, None])  # [block_q, block_k] f32
        dv_scr[...] = dv_scr[...] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta[:, None]) * scale
        dk_scr[...] = dk_scr[...] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(jj == grid_steps - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _fit_block(requested, seq_len):
    """Largest lane-aligned block <= requested that divides seq_len
    (so raising the *default* block size never breaks a sequence length
    that worked before; S=1536 fits 768, not 1024)."""
    b = min(requested, seq_len)
    if seq_len % b == 0:
        return b
    b -= b % 128  # lane-aligned candidates only
    while b >= 128:
        if seq_len % b == 0:
            return b
        b -= 128
    return None


def flash_supported(scale, seq_len, block_q, block_k):
    """Whether the pallas kernels can run this shape/config: seq_len
    must tile by a lane-aligned block under both requested sizes, and
    scale must be concrete (custom_vjp nondiff args).  Head dim needs
    no gate — Mosaic compiles arbitrary D via relayout (verified on
    v5e down to D=20).  Shared by the ring and Ulysses fallbacks so
    the \"can flash run\" predicate lives in one place."""
    return (
        _fit_block(block_q, seq_len) is not None
        and _fit_block(block_k, seq_len) is not None
        and not isinstance(scale, jax.core.Tracer)
    )


def _block_sizes(seq_len, block_q, block_k):
    bq = _fit_block(block_q, seq_len)
    bk = _fit_block(block_k, seq_len)
    if bq is None or bk is None:
        raise ValueError(
            "flash attention needs seq_len {0} divisible by a "
            "lane-aligned block <= the requested sizes; pad the "
            "sequence or pass block_q/block_k".format(seq_len)
        )
    return bq, bk


def _fwd(q, k, v, scale, causal, block_q, block_k, window=0):
    # [B,S,H,D] -> [B,H,S,D]: heads become a grid dim, seq stays blocked
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    out_t, lse = _fwd_core(
        qt, kt, vt, scale, causal, block_q, block_k, window=window
    )
    out = jnp.swapaxes(out_t, 1, 2)
    return out, (q, k, v, out, lse)


def _fwd_core(qt, kt, vt, scale, causal, block_q, block_k, out_dtype=None,
              window=0, q_offset=0):
    """Forward on ``[B,H,S,D]`` (transposed) tensors; returns
    ``(out_t [B,H,S,D], lse [B,H,S,1])``.  Split out so callers that
    loop over kv chunks (ring attention) can keep everything in the
    kernel layout and transpose exactly once.  ``out_dtype`` lets such
    callers take the partial outputs in f32 straight from the kernel's
    f32 accumulator (one final downcast instead of one per chunk).

    Grouped-query attention: ``kt``/``vt`` may carry ``Hkv`` heads with
    ``H % Hkv == 0`` — the kv BlockSpec index maps divide the q-head
    grid index by the group size, so each kv head's blocks stream to
    its whole query group with no repeated-kv materialization."""
    (b, h, s, d), dv = qt.shape, vt.shape[-1]  # v's own head size
    g = h // kt.shape[1]
    bq, bk = _block_sizes(s, block_q, block_k)
    # windowed: stream only the band of kv blocks the horizon can
    # touch, descending from the diagonal — blocks outside the window
    # are never DMA'd (banding off when the band wouldn't shrink)
    # banding assumes the zero-offset diagonal walk; offset chunks
    # (ring hops) use the full grid with pl.when skipping
    steps = _band_steps(window, bq, bk, s // bk) if (
        causal and window and q_offset == 0
    ) else s // bk
    banded = steps < s // bk
    grid = (b, h, s // bq, steps)

    def _kv_idx(bi, hi, qi, jj, g=g):
        if banded:
            kj = _diag_block(qi, jj, bq, bk)
            return (bi, hi // g, jnp.maximum(kj, 0), 0)
        return (bi, hi // g, jj, 0)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=bq, block_k=bk, grid_steps=steps, window=window,
        banded=banded, q_offset=q_offset,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, kj: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bk, d), _kv_idx),
            pl.BlockSpec((1, 1, bk, dv), _kv_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, dv), lambda bi, hi, qi, kj: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda bi, hi, qi, kj: (bi, hi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, dv), out_dtype or qt.dtype),
            jax.ShapeDtypeStruct((b, h, s, 1), jnp.float32),
        ],
        scratch_shapes=[
            _scratch((bq, 1), jnp.float32),  # running max
            _scratch((bq, 1), jnp.float32),  # running normalizer
            _scratch((bq, dv), jnp.float32),  # output accumulator
        ],
        interpret=compat.pallas_interpret(),
        compiler_params=_compiler_params(),
    )(qt, kt, vt)
    return out, lse


def _bwd(scale, causal, block_q, block_k, window, residuals, dout):
    q, k, v, out, lse = residuals
    qt, kt, vt, ot, dot_ = (
        jnp.swapaxes(x, 1, 2) for x in (q, k, v, out, dout)
    )
    # delta_i = rowsum(dout * out): the softmax-jacobian correction term
    delta = jnp.sum(
        dot_.astype(jnp.float32) * ot.astype(jnp.float32), axis=-1
    )[..., None]  # [B,H,S,1] (lane axis; see lse layout note)
    dqt, dkt, dvt = _bwd_core(
        scale, causal, block_q, block_k, qt, kt, vt, dot_, lse, delta,
        window=window,
    )
    return (
        jnp.swapaxes(dqt, 1, 2),
        jnp.swapaxes(dkt, 1, 2),
        jnp.swapaxes(dvt, 1, 2),
    )


def _bwd_core(scale, causal, block_q, block_k, qt, kt, vt, dot_, lse,
              delta, window=0, q_offset=0):
    """Backward on ``[B,H,S,D]`` (transposed) tensors with the
    loop-invariant ``delta`` precomputed by the caller; returns
    ``(dqt, dkt, dvt)`` in the same layout (``dkt``/``dvt`` carry the
    kv head count).  Ring attention calls this once per visiting chunk,
    hoisting delta and the q/dout transposes out of its hop loop.

    GQA backward: dq uses the same ``hi // g`` kv index maps as the
    forward; dk/dv are computed PER QUERY HEAD (the q-head grid dim is
    parallel, so different group members must not write one kv block)
    and group-summed outside the kernel."""
    (b, h, s, d), dvh = qt.shape, vt.shape[-1]  # v, dout, dv: dvh wide
    hkv = kt.shape[1]
    g = h // hkv
    bq, bk = _block_sizes(s, block_q, block_k)
    # banded grids mirror the forward (see _fwd_core): dq streams kv
    # blocks down from the diagonal, dk/dv stream q blocks up from it
    band_ok = causal and window and q_offset == 0
    kv_steps = _band_steps(window, bq, bk, s // bk) if band_ok else s // bk
    kv_banded = kv_steps < s // bk
    q_steps = _band_steps(window, bk, bq, s // bq) if band_ok else s // bq
    q_banded = q_steps < s // bq

    def _kv_idx(bi, hi, qi, jj, g=g):
        if kv_banded:
            kj = _diag_block(qi, jj, bq, bk)
            return (bi, hi // g, jnp.maximum(kj, 0), 0)
        return (bi, hi // g, jj, 0)

    dq_kernel = functools.partial(
        _dq_kernel, scale=scale, causal=causal,
        block_q=bq, block_k=bk, grid_steps=kv_steps, window=window,
        banded=kv_banded, q_offset=q_offset,
    )
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b, h, s // bq, kv_steps),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, kj: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bk, d), _kv_idx),
            pl.BlockSpec((1, 1, bk, dvh), _kv_idx),
            pl.BlockSpec((1, 1, bq, dvh), lambda bi, hi, qi, kj: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda bi, hi, qi, kj: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda bi, hi, qi, kj: (bi, hi, qi, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, bq, d), lambda bi, hi, qi, kj: (bi, hi, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), qt.dtype),
        scratch_shapes=[_scratch((bq, d), jnp.float32)],
        interpret=compat.pallas_interpret(),
        compiler_params=_compiler_params(),
    )(qt, kt, vt, dot_, lse, delta)

    def _q_idx(bi, hi, kj, jj):
        if q_banded:
            qi = _q_band_block(kj, jj, bq, bk)
            return (bi, hi, jnp.minimum(qi, s // bq - 1), 0)
        return (bi, hi, jj, 0)

    dkv_kernel = functools.partial(
        _dkv_kernel, scale=scale, causal=causal,
        block_q=bq, block_k=bk, num_q_blocks=s // bq,
        grid_steps=q_steps, window=window, banded=q_banded,
        q_offset=q_offset,
    )
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b, h, s // bk, q_steps),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), _q_idx),
            pl.BlockSpec(
                (1, 1, bk, d),
                lambda bi, hi, kj, qi, g=g: (bi, hi // g, kj, 0),
            ),
            pl.BlockSpec(
                (1, 1, bk, dvh),
                lambda bi, hi, kj, qi, g=g: (bi, hi // g, kj, 0),
            ),
            pl.BlockSpec((1, 1, bq, dvh), _q_idx),
            pl.BlockSpec((1, 1, bq, 1), _q_idx),
            pl.BlockSpec((1, 1, bq, 1), _q_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, d), lambda bi, hi, kj, qi: (bi, hi, kj, 0)),
            pl.BlockSpec((1, 1, bk, dvh), lambda bi, hi, kj, qi: (bi, hi, kj, 0)),
        ],
        out_shape=[
            # per-q-head partials stay f32 when they will be
            # group-summed (casting each to bf16 first would round g
            # times; MHA keeps the operand dtype as before)
            jax.ShapeDtypeStruct(
                (b, h, s, d), jnp.float32 if g > 1 else kt.dtype
            ),
            jax.ShapeDtypeStruct(
                (b, h, s, dvh), jnp.float32 if g > 1 else vt.dtype
            ),
        ],
        scratch_shapes=[
            _scratch((bk, d), jnp.float32),
            _scratch((bk, dvh), jnp.float32),
        ],
        interpret=compat.pallas_interpret(),
        compiler_params=_compiler_params(),
    )(qt, kt, vt, dot_, lse, delta)

    if g > 1:
        # per-q-head f32 contributions -> kv heads, ONE final downcast
        dk = dk.reshape(b, hkv, g, s, d).sum(2).astype(kt.dtype)
        dv = dv.reshape(b, hkv, g, s, dvh).sum(2).astype(vt.dtype)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, block_q, block_k, window):
    out, _ = _fwd(q, k, v, scale, causal, block_q, block_k, window)
    return out


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, window):
    # the named output is both what the caller gets and the residual:
    # a rematerialised block that keeps FLASH_SAVED (the out-projection's
    # backward reads the same value) never runs this kernel again
    out, (*_, lse) = _fwd(q, k, v, scale, causal, block_q, block_k, window)
    out = checkpoint_name(out, FLASH_SAVED[0])
    lse = checkpoint_name(lse, FLASH_SAVED[1])
    return out, (q, k, v, out, lse)


_flash.defvjp(_flash_fwd, _bwd)


def flash_attention(q, k, v, causal=True, scale=None, block_q=1024,
                    block_k=1024, window=0):
    """Flash attention on ``[B, S, H, D]`` tensors (self-attention:
    q/k/v share the sequence length; ``v`` may be ``[B, S, Hkv, Dv]``).

    Grouped-query attention: k/v may carry ``Hkv`` heads with
    ``H % Hkv == 0`` (each kv head serves ``H/Hkv`` query heads) — the
    kernels stream each kv head's blocks to its whole query group, no
    repeated-kv materialization.

    ``window > 0`` is sliding-window (local) attention: position ``i``
    attends to ``[i-window+1, i]``; requires ``causal``.  Blocks
    entirely behind the horizon are skipped, so compute is O(S·window)
    instead of O(S²/2).

    Differentiable via custom pallas backward kernels.  ``seq_len`` must
    divide by the (clamped) block sizes — pad upstream if not.  The
    1024x1024 default blocks measured fastest on v5e at S=2048 (+9%
    over 512x512; 2048-wide blocks overflow VMEM).
    """
    if k.shape[:3] != v.shape[:3]:  # v's head size is its own
        raise ValueError(
            "k/v must match, got {0} {1}".format(k.shape, v.shape)
        )
    b, s, h, d = q.shape
    bk_, sk_, hkv, dk_ = k.shape
    if (b, s, d) != (bk_, sk_, dk_) or h % hkv != 0:
        raise ValueError(
            "flash attention is self-attention-shaped with grouped kv: "
            "q [B,S,H,D] vs k/v [B,S,Hkv,D], H % Hkv == 0; got q={0} "
            "k={1}".format(q.shape, k.shape)
        )
    if window:
        if window < 0:
            raise ValueError(
                "window must be positive, got {0}".format(window)
            )
        if not causal:
            raise ValueError("window attention requires causal=True")
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    return _flash(
        q, k, v, float(scale), bool(causal), block_q, block_k, int(window)
    )


def flash_attention_sharded(q, k, v, mesh, causal=True, scale=None,
                            block_q=1024, block_k=1024, window=0):
    """Global-array entry point for a model jitted over ``mesh``.

    Mosaic kernels cannot be partitioned by GSPMD (on real chips the
    jit raises ``Mosaic kernels cannot be automatically partitioned``;
    CPU meshes never see it because interpret mode lowers the kernel
    to plain XLA ops), so the kernel is wrapped in a ``shard_map``:
    batch over the ``data``/``fsdp`` axes, heads over ``model`` —
    attention is independent per (batch row, kv-head group), so each
    device runs the unchanged kernel on its shard with no collective.
    A dim the axes do not divide (a batch-1 ``model.init`` trace, a kv
    head count narrower than ``model``) stays replicated instead.
    """
    from jax.sharding import PartitionSpec as P

    from tensorflowonspark_tpu.parallel.mesh import (
        AXIS_DATA,
        AXIS_FSDP,
        AXIS_TENSOR,
        mesh_axis_size,
    )

    batch_axes = tuple(
        a for a in (AXIS_DATA, AXIS_FSDP) if mesh.shape.get(a, 1) > 1
    )
    if q.shape[0] % mesh_axis_size(mesh, *batch_axes) != 0:
        batch_axes = ()
    tp = mesh.shape.get(AXIS_TENSOR, 1)
    head_axis = (
        AXIS_TENSOR
        if tp > 1 and q.shape[2] % tp == 0 and k.shape[2] % tp == 0
        else None
    )
    spec = P(batch_axes or None, None, head_axis, None)

    def _local(ql, kl, vl):
        return flash_attention(
            ql, kl, vl, causal=causal, scale=scale, block_q=block_q,
            block_k=block_k, window=window,
        )

    return compat.shard_map(
        _local,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)
