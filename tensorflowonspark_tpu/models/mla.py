"""Multi-head latent attention with a learned sparse index.

The attention of ``TransformerConfig(attention_kind="mla")``:

- **Latent keys and values.**  A token's keys and values for every
  head are one row: ``c_kv = RMSNorm(x W_kva[:, :r])`` (``r`` =
  ``kv_lora_rank``) beside one rotary key ``k_r = RoPE(x W_kva[:, r:])``
  shared by all heads; ``[k_nope | v] = c_kv W_kvb`` per head.  Queries
  are low-rank too: ``q = RMSNorm(x W_qa) W_qb`` — or, with
  ``q_lora_rank == 0``, one projection ``q = x W_q`` — split into a
  part that meets ``k_nope`` and a rotary part that meets ``k_r``.  The
  decode cache holds the ``r + rope`` wide row a token — the latent
  bank ``[slots, L, r + rope]``, its rows padded out to whole lanes
  (:func:`bank_width`) — never per-head keys and values.
- **Two forms of one product.**  A span of tokens (training, prefill)
  expands ``k_nope`` and ``v`` once and attends per head (the
  *non-absorbed* form; a training span with no index takes it through
  the flash kernels, :func:`flash_span`).  A one-token decode step
  folds ``W_kvb`` into the query and the output instead (*absorbed*):
  the score is
  ``[q_nope W_k | q_rope] · [c_kv | k_r]`` straight against the bank,
  the context ``p · c_kv`` is expanded by ``W_v`` afterwards.
- **The index** (layers of kind ``"full"``).  A small scorer,
  ``I[t, s] = sum_j w[t, j] · relu(q_I[t, j] · k_I[s])`` over
  ``index_n_heads`` heads of ``index_head_dim`` (``q_I`` from the query
  latent, ``k_I = LayerNorm(x W_Ik)``, rotary on the leading
  ``qk_rope_head_dim`` of each), picks for every query the
  ``index_topk`` visible keys that score highest — all of them while
  there are fewer, ties to the lower position — and softmax runs over
  those alone.  Its keys live in a second bank ``[slots, L,
  index_head_dim]``.  A ``"shared"`` layer has no scorer and attends
  over the set of the nearest ``"full"`` layer before it, handed along
  as ``sel``: a boolean ``[B, queries, keys]``.

Selection is exact (:func:`topk_mask`).  The decode step reads the
rows of each slot's live span once, under that mask, and a prefill's
span attends block by block under it with its scores in VMEM, both
through ``ops/latent_attention.py`` (docs/serving.md "Latent and
index banks"); :func:`decode_block` and :func:`span_blocks` say when.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp

from tensorflowonspark_tpu.models.transformer import RMSNorm, rope

#: a span's queries go through in sub-blocks of Q_SUB (mapped) inside
#: super-blocks of Q_SUPER (unrolled; a super-block's keys end at its
#: last query), so no [S, S] score tensor per head is ever whole
Q_SUB = 128
Q_SUPER = 2048


def bank_width(cfg):
    """Columns of a latent bank's row: ``kv_lora_rank +
    qk_rope_head_dim`` rounded up to whole 128-lane tiles (576 -> 640,
    the tail zeros).  A width that is no multiple of the lane makes
    the TPU's default layout put POSITIONS on the lanes instead, and
    every program that appends a row or multiplies by the bank then
    copies the bank both ways."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // 128) * 128


def decode_block(cfg, bank_len):
    """Rows a grid step when the one-token step attends through the
    latent decode kernel (:mod:`..ops.latent_attention`), else None
    (two einsums over the whole bank under the mask).  From what the
    code can see, as ``transformer.decode_bank_block``: no mesh, and a
    block size that divides the bank (the slot decoder rounds its
    latent banks up to a multiple of 128 positions)."""
    if cfg.mesh is not None:
        return None
    from tensorflowonspark_tpu.ops.latent_attention import block_rows

    return block_rows(bank_len, bank_width(cfg))


def span_blocks(cfg, decode, span):
    """``(queries, keys)`` a grid step when a span's attention over its
    own tokens goes through the span kernel
    (:func:`..ops.latent_attention.latent_span_attention`), else None
    (:func:`span_attention`'s einsums a block of queries).  The twin
    of :func:`decode_block`, from what the code can see: the module is
    filling a cache with more than one token (``decode``: a prefill,
    through which no gradient is ever taken — the kernel is forward
    only), no mesh (GSPMD does not partition a Pallas call), and a
    span that the kernel's blocks divide (a multiple of 128: the slot
    decoder's prompt buckets of a long-prompt mix are)."""
    if not decode or span <= 1 or cfg.mesh is not None:
        return None
    from tensorflowonspark_tpu.ops import latent_attention

    return latent_attention.span_blocks(span)


def flash_span(cfg, indexer, decode, pad_start, span):
    """Whether a span's attention goes through the flash kernels
    (``ops/flash_attention.py``: forward AND backward, the scores never
    leave VMEM) as plain causal attention over ``nope + rope`` wide
    queries and keys and ``v_head_dim`` wide values.  From what the
    code can see, as :func:`span_blocks`: the configuration asks for
    flash, the span is no cache fill (training or a plain forward:
    every row runs positions 0.. with no pad region, which is what the
    kernels' causal mask assumes), the layer attends over every
    visible key (no index of its own, none shared), no mesh (GSPMD
    does not partition a Pallas call, and the sharded wrapper splits
    heads that the one rotary key is shared by) and a span the blocks
    divide.  Otherwise :func:`span_attention`'s einsum form."""
    if (cfg.attention_impl != "flash" or decode or indexer
            or pad_start is not None or cfg.mesh is not None):
        return False
    from tensorflowonspark_tpu.ops.flash_attention import flash_supported

    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    return flash_supported(scale, span, cfg.block_q, cfg.block_k)


def topk_mask(scores, visible, k):
    """Boolean mask of the ``k`` largest ``scores`` along the last
    axis among the ``visible`` entries (all of them where fewer are
    visible), ties to the lower index.  Exact: the k-th largest value
    is found bit by bit over the float's ordered integer image — 32
    counting passes, no sort — and entries equal to it are taken from
    the left until ``k`` are."""
    if scores.shape[-1] <= k:
        return visible
    # -0.0 and +0.0 are one value to a comparison, two to the bits
    x = jnp.where(scores == 0, 0.0, scores.astype(jnp.float32))
    x = jnp.where(visible, x, -jnp.inf)
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    # ordered image: negative floats reverse; then shift to unsigned
    ordered = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    key = jax.lax.bitcast_convert_type(ordered, jnp.uint32) ^ jnp.uint32(
        0x80000000)

    def grow(i, t):
        cand = t | jnp.left_shift(
            jnp.uint32(1), (31 - i).astype(jnp.uint32))
        enough = jnp.sum(
            (key >= cand[..., None]).astype(jnp.int32), axis=-1) >= k
        return jnp.where(enough, cand, t)

    kth = jax.lax.fori_loop(
        0, 32, grow, jnp.zeros(scores.shape[:-1], jnp.uint32))[..., None]
    above = key > kth
    equal = key == kth
    room = k - jnp.sum(above.astype(jnp.int32), axis=-1, keepdims=True)
    take = jnp.logical_and(
        equal, jnp.cumsum(equal.astype(jnp.int32), axis=-1) <= room)
    return jnp.logical_and(jnp.logical_or(above, take), visible)


def _visible(qpos, kpos, pad_start):
    """``[B, Q, K]``: key at ``kpos`` is at or before the query at
    ``qpos`` and outside the row's pad region; a query always sees
    itself, so a pad or idle row's softmax stays finite."""
    vis = kpos[None, None, :] <= qpos[:, :, None]
    if pad_start is not None:
        vis = jnp.logical_and(vis, kpos[None, None, :]
                              >= pad_start[:, None, None])
    return jnp.logical_or(vis, kpos[None, None, :] == qpos[:, :, None])


def index_scores(q_i, k_i, w_i):
    """``I[b, q, s] = sum_j w_i[b, q, j] · relu(q_i[b, q, j] · k_i[b,
    s])`` in float32."""
    dots = jnp.einsum(
        "bqjd,bsd->bqjs", q_i, k_i, preferred_element_type=jnp.float32)
    return jnp.sum(nn.relu(dots) * w_i[..., None], axis=2)


def _query_block(pos, pad_start, b, start, sub, k1):
    """The ``sub`` queries from row ``start`` of a span against its
    first ``k1`` keys: ``cut`` (takes their rows of a ``[B, S, ...]``
    tensor), their positions, and the keys they may see ``[B, sub,
    k1]``."""
    def cut(t):
        return jax.lax.dynamic_slice_in_dim(t, start, sub, axis=1)

    qpos = jax.lax.dynamic_slice_in_dim(pos, start, sub)
    vis = jnp.broadcast_to(
        _visible(qpos[None], pos[:k1], pad_start), (b, sub, k1))
    return cut, qpos, vis


def _selection(cut, c_q, qpos, vis, k1, index, sel, topk):
    """The keys ``[B, Q, k1]`` that a block of queries attends over,
    of the ``vis`` ible ones: the index's ``topk``, else the block's
    rows of ``sel``, else all of them.  ``cut`` takes the block's rows
    of a ``[B, S, ...]`` tensor."""
    if index is not None:
        index_queries, k_i, w_i = index
        with jax.named_scope("dsa.index"):
            scores = index_scores(
                index_queries(cut(c_q), qpos), k_i[:, :k1], cut(w_i))
        with jax.named_scope("dsa.select"):
            return topk_mask(scores, vis, topk)
    if sel is not None:
        return cut(sel)[:, :, :k1]
    return vis


def _query_blocks(s):
    """``(super-block, sub-block)`` of a span of ``s`` queries."""
    sup = Q_SUPER if s % Q_SUPER == 0 else s
    return sup, Q_SUB if sup % Q_SUB == 0 else sup


def span_attention(c_q, queries, c_kv, keys, k_r, pos, pad_start, scale,
                   index=None, sel=None, topk=0, blocks=None):
    """Non-absorbed attention of a span over its own tokens, queries in
    blocks: ``c_q [B, S, rq]`` the query latent, ``queries(c_q_block,
    pos_block)`` -> ``(q_nope [B, Q, H, dn], q_rope [B, Q, H, dr])``
    (a block's heads are made when the block is attended, never all
    at once), ``c_kv [B, S, r]`` the key/value latent, ``keys(c_kv
    [B, K, r])`` -> ``(k_nope [B, H, K, dn], v [B, H, K, dv])`` —
    HEAD-major, so that each head's keys are one contiguous matrix for
    the MXU (token-major, the per-head products ran at an eighth of
    the chip's rate: my chip run, PR 28); a super-block expands the
    keys up to its last query, so the longest expansion is alive
    alone — ``k_r [B, S, dr]``, ``pos [S]`` (the tokens' positions,
    ascending).
    ``index`` = ``(index_queries, k_i, w_i)`` selects ``topk`` keys a
    query; else ``sel`` ``[B, S, S]`` is the selection to attend over;
    else every visible key.  ``blocks`` (:func:`span_blocks`) sends
    the products and the softmax through the span kernel instead: the
    same selection, made first, then every head over keys expanded
    once (:func:`_kernel_span_attention`).  Returns the context ``[B,
    S, H, dv]`` and the selection ``[B, S, S]`` it attended over."""
    if blocks is not None:
        return _kernel_span_attention(
            c_q, queries, c_kv, keys, k_r, pos, pad_start, scale, index,
            sel, topk, blocks)
    b, s = c_q.shape[:2]
    sup, sub = _query_blocks(s)
    ctxs, masks = [], []
    for q0 in range(0, s, sup):
        k1 = q0 + sup  # no key after the super-block's last query
        k_nope, v = keys(c_kv[:, :k1])

        def one(i, q0=q0, k1=k1, k_nope=k_nope, v=v):
            cut, qpos, vis = _query_block(
                pos, pad_start, b, q0 + i * sub, sub, k1)
            q_nope, q_rope = queries(cut(c_q), qpos)
            mask = _selection(cut, c_q, qpos, vis, k1, index, sel, topk)
            logits = jnp.einsum(
                "bqhd,bhkd->bhqk", q_nope, k_nope,
                preferred_element_type=jnp.float32,
            ) + jnp.einsum(
                "bqhd,bkd->bhqk", q_rope, k_r[:, :k1],
                preferred_element_type=jnp.float32,
            )
            logits = jnp.where(mask[:, None], logits * scale, -jnp.inf)
            # jax.nn.softmax as it stands: a hand-written exp / sum /
            # normalise-the-context form ran the prefill 2.7 times
            # SLOWER (a bf16 -> f32 reduce over the keys at 23 ms a
            # block: my chip run, PR 28).  The [H, Q, K] float32 scores
            # cross HBM ~40 bytes a score here; that, not the MXU,
            # sets the prefill's time, and a blocked kernel is the fix
            probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
            ctx = jnp.einsum("bhqk,bhkd->bqhd", probs, v)
            return ctx, jnp.pad(mask, ((0, 0), (0, 0), (0, s - k1)))

        ctx, mask = jax.lax.map(one, jnp.arange(sup // sub))
        ctxs.append(jnp.moveaxis(ctx, 0, 1).reshape(
            (b, sup) + ctx.shape[3:]))
        masks.append(jnp.moveaxis(mask, 0, 1).reshape(b, sup, s))
    return jnp.concatenate(ctxs, axis=1), jnp.concatenate(masks, axis=1)


def _kernel_span_attention(c_q, queries, c_kv, keys, k_r, pos, pad_start,
                           scale, index, sel, topk, blocks):
    """:func:`span_attention` through the span kernel.  The selection
    comes first and is what the einsum form computes: the index's
    scores and the exact top-k a sub-block of queries over the keys up
    to its super-block's end, in XLA (a ``"shared"`` or index-less
    layer has nothing to compute).  Then ONE kernel call for the span:
    queries of every head ``[B, H, S, dn + dr]``, keys expanded once
    with the rotary key written beside every head's (one MXU product
    contracting 256 where ``k_nope`` and ``k_r`` apart are two, 192
    and 64 deep, at the cost of ``S x H x dr`` more key bytes), the
    selection as int8.  Key blocks after a query block, or wholly in
    the pad region, are skipped inside the kernel."""
    from tensorflowonspark_tpu.ops import latent_attention

    b, s = c_q.shape[:2]
    if index is None:
        mask = sel if sel is not None else jnp.broadcast_to(
            _visible(pos[None], pos, pad_start), (b, s, s))
    else:
        sup, sub = _query_blocks(s)
        masks = []
        for q0 in range(0, s, sup):
            k1 = q0 + sup

            def one(i, q0=q0, k1=k1):
                cut, qpos, vis = _query_block(
                    pos, pad_start, b, q0 + i * sub, sub, k1)
                return jnp.pad(
                    _selection(cut, c_q, qpos, vis, k1, index, None, topk),
                    ((0, 0), (0, 0), (0, s - k1)))

            masks.append(jnp.moveaxis(
                jax.lax.map(one, jnp.arange(sup // sub)), 0, 1,
            ).reshape(b, sup, s))
        mask = jnp.concatenate(masks, axis=1)
    q = jnp.swapaxes(jnp.concatenate(queries(c_q, pos), axis=-1), 1, 2)
    k_nope, v = keys(c_kv)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(
            k_r[:, None], k_nope.shape[:3] + k_r.shape[-1:])], axis=-1)
    # the first key outside the pad region, as a row of the span
    first = jnp.zeros((b,), jnp.int32) if pad_start is None else jnp.clip(
        pad_start - pos[0], 0, s - 1).astype(jnp.int32)
    ctx = latent_attention.latent_span_attention(
        q, k, v, mask.astype(jnp.int8), first, scale=scale, blocks=blocks)
    return jnp.swapaxes(ctx, 1, 2), mask


class MLAttention(nn.Module):
    cfg: object
    #: "full" | "shared" | "" (no index: every visible key)
    indexer: str = ""

    @nn.compact
    def __call__(self, x, positions, decode=False, pad_start=None,
                 per_slot=False, sel=None):
        """Returns ``(out [B, S, embed], sel)``: ``sel`` is the
        selection this layer attended over (None on a dense layer)."""
        cfg = self.cfg
        if self.indexer not in ("", "full", "shared"):
            raise ValueError(
                "indexer must be 'full', 'shared' or '', got %r"
                % (self.indexer,))
        if self.indexer == "shared" and sel is None:
            raise ValueError(
                "a 'shared' index layer needs a 'full' layer before it")
        h, dt = cfg.num_heads, cfg.jdtype
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        dc = cfg.kv_lora_rank
        scale = (dn + dr) ** -0.5
        b, s = x.shape[:2]

        def dense(name, feats):
            return nn.DenseGeneral(
                feats, axis=-1, use_bias=False, dtype=dt, name=name)

        def norm(name):
            return RMSNorm(eps=cfg.rms_norm_eps, name=name)

        def rot(t, pos=positions):
            return rope(t, pos, cfg.rope_theta, cfg.rope_interleave)

        def rot_head(t, pos=positions):
            # rotary on the leading dr of the last axis of [B, S, J, D]
            return jnp.concatenate(
                [rot(t[..., :dr], pos), t[..., dr:]], axis=-1)

        init = nn.initializers.variance_scaling(1.0, "fan_in", "normal")

        if self.indexer == "full" and not cfg.q_lora_rank:
            raise ValueError(
                "a layer that owns an index takes the index's queries "
                "from the query latent: q_lora_rank must be set")
        with jax.named_scope("mla"):
            if cfg.q_lora_rank:
                c_q = norm("q_norm")(dense("q_a", cfg.q_lora_rank)(x))
                w_qb = self.param(
                    "q_b", init, (cfg.q_lora_rank, h, dn + dr)).astype(dt)
            else:
                # no query latent (the published ``q_lora_rank: null``):
                # ONE projection ``q = x W_q``, no ``q_a``, no
                # ``q_norm``; what follows reads ``x`` where it read
                # the latent
                c_q = x
                w_qb = self.param(
                    "q", init, (cfg.embed_dim, h, dn + dr)).astype(dt)

            def queries(c_q, pos):
                # ``pos [S]`` (one row of positions for every batch
                # row) or ``[B, S]``
                pos = jnp.broadcast_to(pos, c_q.shape[:2])
                return (
                    jnp.einsum("bsr,rhd->bshd", c_q, w_qb[..., :dn]),
                    rot(jnp.einsum("bsr,rhd->bshd", c_q, w_qb[..., dn:]),
                        pos),
                )

            kv_a = dense("kv_a", dc + dr)(x)
            c_kv = norm("kv_norm")(kv_a[..., :dc])
            k_r = rot(kv_a[:, :, None, dc:])[:, :, 0]
            # the bank's row: [c_kv | k_r | zeros] out to whole lanes
            row = jnp.concatenate(
                [c_kv, k_r,
                 jnp.zeros((b, s, bank_width(cfg) - dc - dr), dt)],
                axis=-1)
            w_kvb = self.param("kv_b", init, (dc, h, dn + dv)).astype(dt)
        index = None
        if self.indexer == "full":
            with jax.named_scope("dsa.index"):
                j, di = cfg.index_n_heads, cfg.index_head_dim
                w_iq = self.param(
                    "index_q", init, (cfg.q_lora_rank, j, di)).astype(dt)

                def index_queries(c_q, pos):
                    return rot_head(
                        jnp.einsum("bsr,rjd->bsjd", c_q, w_iq),
                        jnp.broadcast_to(pos, c_q.shape[:2]))

                k_i = nn.LayerNorm(
                    epsilon=1e-6, dtype=dt, name="index_k_norm"
                )(dense("index_k", di)(x))
                k_i = rot_head(k_i[:, :, None])[:, :, 0]
                w_i = dense("index_w", j)(x).astype(jnp.float32) * (
                    j ** -0.5 * di ** -0.5)
                index = (index_queries, k_i, w_i)

        if decode:
            bank = self.variable(
                "cache", "latent", jnp.zeros,
                (b, cfg.max_seq_len, bank_width(cfg)), dt)
            ibank = self.variable(
                "cache", "index_key", jnp.zeros,
                (b, cfg.max_seq_len, cfg.index_head_dim), dt,
            ) if self.indexer == "full" else None
        if decode and s == 1:
            q_nope, q_rope = queries(c_q, positions)
            if index is not None:
                index = (index[0](c_q, positions),) + index[1:]
            ctx, sel = self._decode_step(
                bank, ibank, row, index, q_nope, q_rope, w_kvb,
                positions[:, 0], pad_start, sel, scale)
        else:
            if decode:
                if per_slot:
                    raise NotImplementedError(
                        "latent banks take a span only as a prefill "
                        "from the lane's start: a cached-prefix suffix "
                        "or a speculative verify block is not built")
                # a prefill from the lane's start: the span's own
                # tokens are all its keys; the bank only receives them
                at = (0, positions[0, 0], 0)
                bank.value = jax.lax.dynamic_update_slice(
                    bank.value, row.astype(dt), at)
                if ibank is not None:
                    ibank.value = jax.lax.dynamic_update_slice(
                        ibank.value, index[1].astype(dt), at)
            with jax.named_scope("mla"):
                def keys(c_kv):
                    return (
                        jnp.einsum("bsc,chd->bhsd", c_kv, w_kvb[..., :dn]),
                        jnp.einsum("bsc,chd->bhsd", c_kv, w_kvb[..., dn:]),
                    )

                if flash_span(cfg, self.indexer, decode, pad_start, s):
                    ctx, mask = self._flash_span(
                        c_q, w_qb, c_kv, w_kvb, k_r, rot, scale), None
                else:
                    ctx, mask = span_attention(
                        c_q, queries, c_kv, keys, k_r,
                        positions[0], pad_start, scale, index=index,
                        sel=sel if self.indexer == "shared" else None,
                        topk=cfg.index_topk,
                        blocks=span_blocks(cfg, decode, s),
                    )
            sel = mask if self.indexer else None
        out = nn.DenseGeneral(
            cfg.embed_dim, axis=(-2, -1), use_bias=False, dtype=dt,
            name="out",
        )(ctx)
        return out, sel

    def _flash_span(self, c_q, w_qb, c_kv, w_kvb, k_r, rot, scale):
        """The non-absorbed form of a training span as plain causal
        attention through the flash kernels, which have a backward:
        ``q = [q_nope | RoPE(q_rope)]`` and ``k = [k_nope | k_r]`` (the
        one rotary key written beside every head's) ``nope + rope``
        wide, ``v`` ``v_head_dim`` wide, token-major as the kernels
        take them.  Returns the context ``[B, S, H, dv]``."""
        from tensorflowonspark_tpu.ops.flash_attention import (
            flash_attention,
        )

        cfg = self.cfg
        dn = cfg.qk_nope_head_dim
        q = jnp.einsum("bsr,rhd->bshd", c_q, w_qb)
        q = jnp.concatenate([q[..., :dn], rot(q[..., dn:])], axis=-1)
        kv = jnp.einsum("bsc,chd->bshd", c_kv, w_kvb)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(
                k_r[:, :, None], kv.shape[:3] + k_r.shape[-1:])], axis=-1)
        return flash_attention(
            q, k, kv[..., dn:], causal=True, scale=scale,
            block_q=cfg.block_q, block_k=cfg.block_k)

    def _decode_step(self, bank, ibank, row, index, q_nope, q_rope, w_kvb,
                     pos, pad_start, sel, scale):
        """One token a row, absorbed: append the row (one scatter of B
        rows, as the K/V banks do), select on a "full" layer, attend
        over the whole latent bank under the selection."""
        cfg = self.cfg
        dn, dc, dv = cfg.qk_nope_head_dim, cfg.kv_lora_rank, cfg.v_head_dim
        b = row.shape[0]
        rows = jnp.arange(b)
        bank.value = bank.value.at[rows, pos].set(
            row[:, 0].astype(bank.value.dtype))
        kpos = jnp.arange(bank.value.shape[1])
        vis = _visible(pos[:, None], kpos, pad_start)  # [B, 1, L]
        if self.indexer == "full":
            q_i, k_i, w_i = index
            ibank.value = ibank.value.at[rows, pos].set(
                k_i[:, 0].astype(ibank.value.dtype))
            with jax.named_scope("dsa.index"):
                scores = index_scores(q_i, ibank.value, w_i)
            with jax.named_scope("dsa.select"):
                sel = topk_mask(scores, vis, cfg.index_topk)
        elif self.indexer == "":
            sel = vis
        with jax.named_scope("mla"):
            h, pad = q_nope.shape[2], bank.value.shape[-1] - dc
            q_lat = jnp.einsum(
                "bshd,chd->bshc", q_nope, w_kvb[..., :dn])
            q_full = jnp.concatenate(
                [q_lat, q_rope,
                 jnp.zeros((b, 1, h, pad - q_rope.shape[-1]), q_lat.dtype)],
                axis=-1)[:, 0]
            if decode_block(cfg, bank.value.shape[1]):
                # one pass over the rows between the slot's pad region
                # and its position, both products in one kernel
                from tensorflowonspark_tpu.ops import latent_attention

                first = pos if pad_start is None else jnp.minimum(
                    pad_start, pos)
                ctx_row = latent_attention.latent_decode_attention(
                    q_full, bank.value,
                    jnp.where(sel, 0.0, latent_attention.MASKED),
                    first, pos, scale=scale,
                )
            else:
                logits = jnp.einsum(
                    "bhc,blc->bhl", q_full, bank.value,
                    preferred_element_type=jnp.float32,
                )
                logits = jnp.where(sel, logits * scale, -jnp.inf)
                probs = jax.nn.softmax(logits, axis=-1).astype(row.dtype)
                ctx_row = jnp.einsum("bhl,blc->bhc", probs, bank.value)
            # p · the WHOLE row either way, and W_v with zero rows
            # under the columns that are not c_kv: a slice of the bank
            # down to c_kv would be a copy of the bank every step
            w_v = jnp.concatenate(
                [w_kvb[..., dn:],
                 jnp.zeros((pad,) + w_kvb.shape[1:2] + (dv,), w_kvb.dtype)],
                axis=0)
            ctx = jnp.einsum("bhc,chd->bhd", ctx_row, w_v)[:, None]
        return ctx, (sel if self.indexer else None)
