"""Decoder-only Transformer LM — the long-context flagship.

The reference has no transformer and no long-context support at all
(SURVEY.md §5 'Long-context / sequence parallelism: absent'); this model
is the vehicle for the new TP/SP/ring-attention capabilities.  Design is
TPU-first:

- bfloat16 activations/weights with f32 softmax/layernorm reductions —
  MXU-native matmuls, stable reductions;
- RoPE positions (no learned position table → no max-seq coupling, and
  rotations fuse into the surrounding elementwise ops);
- attention layout ``[B, S, H, D]`` so the ``seq`` dim shards for
  ring/Ulysses context parallelism and ``H`` shards for TP;
- static shapes everywhere; the whole forward is one traced jit region.

Logical sharding axes (consumed by
:func:`tensorflowonspark_tpu.parallel.sharding.param_specs` through
:func:`logical_axes`): ``vocab``, ``embed``, ``heads``, ``mlp``.
"""

import dataclasses
import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from tensorflowonspark_tpu.models import base
from tensorflowonspark_tpu.ops.attention import attention
from tensorflowonspark_tpu.parallel import sharding as sh


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    num_layers: int = 4
    num_heads: int = 8
    #: grouped-query attention: kv heads (0 = num_heads = MHA).  Must
    #: divide num_heads.  Shrinks kv projections, the decode cache, and
    #: ring attention's rotating kv shards by num_heads/num_kv_heads.
    num_kv_heads: int = 0
    head_dim: int = 64
    embed_dim: int = 512
    mlp_dim: int = 2048
    max_seq_len: int = 2048
    dtype: str = "bfloat16"
    attention_impl: str = "dot"  # dot | flash | ring | ulysses
    #: Mesh the model is jitted over, for attention impls that must
    #: wrap themselves in a shard_map on *global* arrays: ring/ulysses
    #: over ``seq_axis``, flash over the batch and head axes (a Mosaic
    #: kernel is opaque to GSPMD — on more than one real chip flash
    #: REQUIRES this).  Leave None on one device or when the whole
    #: model already runs under shard_map.
    mesh: object = None
    seq_axis: str = "seq"
    remat: bool = False  # jax.checkpoint each block (HBM for FLOPs)
    #: remat granularity when ``remat`` is set: ``"block"`` keeps the
    #: block's input, a sigmoid-routed span's routing
    #: (``moe.SPAN_SAVED``) and the flash kernels' output and lse
    #: (``flash_attention.FLASH_SAVED``) and recomputes the rest of the
    #: block in backward (max HBM savings, ~+1/3 step FLOPs);
    #: ``"dots"`` saves matmul outputs and recomputes only elementwise
    #: ops (checkpoint_policies.dots_with_no_batch_dims_saveable) — the
    #: MXU does no second pass, so MFU stays at the 6N accounting.
    remat_policy: str = "block"
    #: one fused [embed -> 3*heads*head_dim] projection instead of three
    #: separate q/k/v matmuls — fewer, larger MXU calls
    fused_qkv: bool = False
    #: pallas flash-attention block shape (attention_impl="flash")
    block_q: int = 1024
    block_k: int = 1024
    #: sliding-window (local) attention: each position sees the last
    #: ``attention_window`` tokens (0 = full causal).  Works with every
    #: attention impl: flash skips blocks behind the horizon (O(S·W)
    #: compute and DMA via banded grids); ring skips whole HOPS beyond
    #: the horizon (each ring distance gets a statically-specialized
    #: offset kernel); ulysses windows the full-sequence local kernel.
    attention_window: int = 0
    #: KV-cache storage dtype for decode: "bfloat16" (exact) or
    #: "int8" (symmetric per-position/per-head scales over head_dim —
    #: halves the cache HBM read that dominates long-generation decode;
    #: the dequant fuses into the attention einsum's operand read, same
    #: trick as quantize.py's weights)
    cache_dtype: str = "bfloat16"
    #: decode KV layout: "contiguous" (per-slot banks ``[B, L, Hkv,
    #: D]``) or "paged" — KV lives in ONE physical page pool per layer
    #: ``[kv_pages, kv_page_tokens, Hkv, D]`` addressed by per-slot
    #: block tables, attention runs the ops/paged_attention.py
    #: block-gather kernel, and cached admits install page INDICES
    #: instead of copying banks (the SlotDecoder sets the pool
    #: geometry via dataclasses.replace; see docs/serving.md "Paged
    #: KV & int4").  Decode-path only — training/prefill-from-scratch
    #: semantics are identical.
    kv_layout: str = "contiguous"
    #: paged-layout pool geometry (set by the SlotDecoder, not by hand)
    kv_pages: int = 0
    kv_page_tokens: int = 16
    #: block-table width: logical blocks per slot (ceil(bank/page))
    kv_slot_blocks: int = 0
    #: live bank span in tokens — multi-token paged attention slices
    #: its gathered banks to this width so einsum/mask shapes match the
    #: contiguous layout exactly (0 = the full table span)
    kv_span: int = 0
    #: single-token paged decode implementation: "kernel" (the pallas
    #: block-gather kernel — the TPU hot path; interpret-mode on CPU)
    #: or "gather" (XLA gather + dense attention — interpret-free, the
    #: right CPU serving choice; numerics match the multi-token path
    #: bit for bit).  Multi-token spans always use the gather path.
    paged_decode_impl: str = "kernel"
    #: set by the SlotDecoder, not by hand, where every span its
    #: contiguous banks see is a FRESH prompt at positions from 0 (no
    #: prefix continuation, no verify block, no pages, no mesh).  Then
    #: (a) a layer whose window is shorter than its bank keeps a RING
    #: of :func:`ring_rows` rows, written and read modulo its length;
    #: (b) a prompt attends over its own keys, not over the bank —
    #: through the flash forward kernel where :func:`prefill_flash`
    #: finds the shapes legal
    fresh_prompts: bool = False
    # MoE: num_experts > 0 swaps the dense MLP for an expert-parallel
    # MoE FFN (models/moe.py) in every block
    num_experts: int = 0
    expert_k: int = 2
    capacity_factor: float = 1.25
    #: "gather" (index dispatch, no permutation matmuls) | "einsum" |
    #: "dropless" (MoEMLP over row tiles of 256) | "share" (the
    #: never-a-drop share layer ``SigmoidMoE`` under softmax scores:
    #: a decode step over row tiles of 16, a prompt routed once)
    expert_dispatch: str = "gather"
    #: RMSNorm epsilon of every norm in the model
    rms_norm_eps: float = 1e-6
    #: RoPE base, and whether a rotated pair is (2i, 2i+1)
    #: (interleaved) or (i, i + D/2) (split halves)
    rope_theta: float = 10000.0
    rope_interleave: bool = False
    #: RMS-norm each head's query and key over ``head_dim`` with a
    #: learned scale, before the rotation (``attn/q_norm``, ``k_norm``)
    qk_norm: bool = False
    #: "" (none) | "per-head": each head's output times ``sigmoid(x
    #: W_g)``, one scalar a head and token from the layer's normed
    #: input (``attn/gate``, ``[embed, heads]``), before ``out``
    gating: str = ""
    # -- per-layer pattern --------------------------------------------
    #: attention of each layer, "sliding_attention" (sees the last
    #: ``sliding_window`` positions) | "full_attention" (full causal);
    #: empty: every layer alike, under ``attention_window``
    layer_types: tuple = ()
    sliding_window: int = 0
    #: RoPE of each layer TYPE, where the types differ: ``{type:
    #: {"rope_theta", and for YaRN "rope_type": "yarn", "factor",
    #: "original_max_position_embeddings", "beta_fast", "beta_slow",
    #: "attention_factor"; "partial_rotary_factor": the leading share
    #: of ``head_dim`` that rotates, 1 if absent}}`` (the published
    #: ``rope_parameters``; held as sorted item tuples).  Empty:
    #: ``rope_theta`` on every layer
    layer_rope: tuple = ()
    #: query heads of each layer, where they differ by layer (empty:
    #: ``num_heads`` on every layer); needs ``num_kv_heads``, which
    #: must divide each
    num_attention_heads_per_layer: tuple = ()
    #: FFN of each layer, "dense" | "sparse" (empty: every layer dense,
    #: or every layer the softmax MoE above when num_experts > 0)
    mlp_layer_types: tuple = ()
    #: sparse index of each latent-attention layer: "full" (owns an
    #: indexer and selects), "shared" (attends over the selection of
    #: the nearest "full" layer before it) or "" (dense); empty: all ""
    indexer_types: tuple = ()
    # -- latent attention (models/mla.py) ------------------------------
    #: "gqa" (Attention above) | "mla" (low-rank queries, a compressed
    #: key/value latent and one shared rotary key a token; the decode
    #: cache holds the latent row, not per-head keys and values)
    attention_kind: str = "gqa"
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    #: keys a query attends to on indexed layers (all while fewer)
    index_topk: int = 0
    # -- sigmoid-routed experts (models/moe.py SigmoidMoE) -------------
    #: "softmax" (MoEMLP) | "sigmoid" (scores sigmoid(x W_r); top-k of
    #: score + a selection-only bias; weights normalised over the
    #: chosen, times routed_scaling; a shared expert; never a drop)
    router_scoring: str = "softmax"
    #: experts the router scores (0 = num_experts).  num_experts is how
    #: many THIS program holds, ids expert_first .. expert_first +
    #: num_experts - 1: it routes over all and computes its own part
    router_experts: int = 0
    expert_first: int = 0
    shared_experts: int = 0
    routed_scaling: float = 1.0
    #: width of one routed (and one shared) expert (0 = mlp_dim)
    moe_mlp_dim: int = 0

    def __post_init__(self):
        # a config read from JSON brings lists; flax hashes the config
        rope_of = self.layer_rope
        if isinstance(rope_of, dict):
            rope_of = tuple(sorted(
                (kind, tuple(sorted(dict(v).items())))
                for kind, v in rope_of.items()))
        object.__setattr__(self, "layer_rope", tuple(rope_of or ()))
        for name in ("mlp_layer_types", "indexer_types", "layer_types",
                     "num_attention_heads_per_layer"):
            val = tuple(getattr(self, name) or ())
            object.__setattr__(self, name, val)
            if val and len(val) != self.num_layers:
                raise ValueError(
                    "{0} names {1} layers, num_layers is {2}".format(
                        name, len(val), self.num_layers))
        if self.num_attention_heads_per_layer and not self.num_kv_heads:
            # the banks hold num_kv_heads on every layer
            raise ValueError(
                "num_attention_heads_per_layer needs num_kv_heads")
        if self.gating not in ("", "per-head"):
            raise ValueError("gating %r is not built" % (self.gating,))

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    def ffn_kind(self, layer):
        """"dense", "moe" (MoEMLP: softmax scores, capacity or row
        tiles of 256) or "sigmoid_moe" (the never-a-drop share layer
        ``SigmoidMoE``, scored as ``router_scoring`` says: sigmoid
        always, softmax under ``expert_dispatch="share"``) of layer
        ``layer``."""
        sparse = (
            self.mlp_layer_types[layer] == "sparse"
            if self.mlp_layer_types else self.num_experts > 0
        )
        if not sparse:
            return "dense"
        share = (self.router_scoring == "sigmoid"
                 or self.expert_dispatch == "share")
        return "sigmoid_moe" if share else "moe"

    def window_of(self, layer):
        """Positions layer ``layer`` sees behind (and with) a query, 0
        = all: ``sliding_window`` on a "sliding_attention" layer of
        ``layer_types``, ``attention_window`` where no types are
        named."""
        if not self.layer_types:
            return self.attention_window
        sliding = self.layer_types[layer] == "sliding_attention"
        return self.sliding_window if sliding else 0

    def heads_of(self, layer):
        """Query heads of layer ``layer``."""
        if self.num_attention_heads_per_layer:
            return int(self.num_attention_heads_per_layer[layer])
        return self.num_heads

    def rotary_of(self, layer):
        """Leading dimensions of a head that layer ``layer`` rotates:
        ``head_dim`` times its type's ``partial_rotary_factor`` (all of
        ``head_dim`` where none is given); the rest pass unrotated."""
        kinds = dict(self.layer_rope)
        if not (self.layer_types and kinds):
            return self.head_dim
        share = dict(kinds[self.layer_types[layer]]).get(
            "partial_rotary_factor", 1)
        return int(self.head_dim * float(share))

    def rope_of(self, layer):
        """``(theta, inv_freq, factor)`` of layer ``layer``'s rotation:
        ``inv_freq`` None and ``factor`` 1 under the default RoPE of
        base ``theta``; under YaRN the layer type's blended
        frequencies over the rotated width (:func:`yarn_inv_freq`,
        :meth:`rotary_of`) and the factor that multiplies cos and
        sin."""
        kinds = dict(self.layer_rope)
        if not (self.layer_types and kinds):
            return self.rope_theta, None, 1.0
        p = dict(kinds[self.layer_types[layer]])
        theta = float(p["rope_theta"])
        if p.get("rope_type", "default") == "default":
            return theta, None, 1.0
        if p["rope_type"] != "yarn":
            raise ValueError("rope_type %r is not built" % (p["rope_type"],))
        return theta, yarn_inv_freq(
            self.rotary_of(layer), theta, float(p["factor"]),
            int(p["original_max_position_embeddings"]),
            float(p.get("beta_fast", 32)), float(p.get("beta_slow", 1)),
        ), float(p.get("attention_factor") or (
            0.1 * math.log(float(p["factor"])) + 1.0))


def yarn_inv_freq(dim, theta, factor, original, beta_fast, beta_slow):
    """YaRN's ``dim // 2`` inverse frequencies (float32 numpy), as
    ``transformers``' ``_compute_yarn_parameters`` with ``truncate``:
    pairs that turn more than ``beta_fast`` times over the ``original``
    context keep ``theta ** (-2i / dim)``, pairs that turn fewer than
    ``beta_slow`` times are slowed by ``factor``, a linear ramp
    between."""
    import numpy as np

    half = dim // 2
    extrap = theta ** (-np.arange(half, dtype=np.float64) * 2 / dim)
    interp = extrap / factor

    def turns_at(n):
        return dim * math.log(original / (n * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), dim - 1)
    ramp = np.clip(
        (np.arange(half, dtype=np.float64) - low) / max(high - low, 1e-3),
        0, 1)
    return (interp * ramp + extrap * (1 - ramp)).astype(np.float32)


def rope(x, positions, max_wavelength=10000.0, interleave=False,
         inv_freq=None, factor=1.0, rotary=None):
    """Rotary position embedding on ``[B, S, H, D]`` (D even): pair
    ``i`` is ``(i, i + D/2)``, or ``(2i, 2i + 1)`` with
    ``interleave``.  ``inv_freq`` (``[D/2]``) replaces the default
    ``max_wavelength ** (-2i / D)`` and ``factor`` multiplies cos and
    sin (YaRN: :meth:`TransformerConfig.rope_of`).  ``rotary`` (even,
    under ``D``): the leading ``rotary`` dimensions rotate as a head of
    that size would — its pairs and frequencies reckoned over
    ``rotary`` — and the rest pass through
    (:meth:`TransformerConfig.rotary_of`)."""
    if rotary is not None and rotary < x.shape[-1]:
        return jnp.concatenate([
            rope(x[..., :rotary], positions, max_wavelength, interleave,
                 inv_freq, factor),
            x[..., rotary:]], axis=-1)
    d = x.shape[-1]
    freq = max_wavelength ** (
        -jnp.arange(0, d // 2, dtype=jnp.float32) / (d // 2)
    ) if inv_freq is None else jnp.asarray(inv_freq, jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * freq  # [B,S,D/2]
    angles = angles[:, :, None, :]  # [B,S,1,D/2]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    if factor != 1.0:
        sin, cos = sin * factor, cos * factor
    x32 = x.astype(jnp.float32)
    if interleave:
        x1, x2 = x32[..., 0::2], x32[..., 1::2]
        out = jnp.stack(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
        ).reshape(x.shape)
        return out.astype(x.dtype)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps
        )
        return (normed * scale).astype(x.dtype)


def decode_bank_block(cfg, bank_len):
    """Tokens per block when single-token per-slot decode over
    contiguous banks of ``bank_len`` goes through the block-walking
    kernel (:func:`..ops.paged_attention.bank_attention`), else None
    (``dot_attention`` over the whole bank).  Read from what the code
    can see, no knob: a mesh rules the kernel out (GSPMD does not
    partition a Pallas call), and the geometry must be tile-legal —
    ``head_dim`` a multiple of 128 and a block size that divides the
    bank — which the tiny head sizes of CPU tests are not."""
    if (cfg.mesh is not None or cfg.kv_layout == "paged"
            or cfg.attention_kind == "mla"):
        # a latent row (one 576-wide row a token, every head's key) is
        # not the kernel's [Hkv, D] block: models/mla.py reads it
        return None
    from tensorflowonspark_tpu.ops.paged_attention import bank_block

    return bank_block(
        bank_len, cfg.head_dim,
        jnp.int8 if cfg.cache_dtype == "int8" else cfg.jdtype,
    )


def ring_rows(cfg, window):
    """Rows of the ring a layer of ``window`` keeps under
    ``cfg.fresh_prompts``: the window rounded out to whole blocks of
    the decode kernel plus one block — a step's query at ``p`` reads
    the blocks of ``[p - window + 1, p]``, up to ``window + block - 1``
    positions counted from the first block's start, and none of them
    may have been overwritten by ``p``'s own append (1280 for a window
    of 1024 in blocks of 256).  Where the head size is not tile-legal
    (CPU tests) there is no block to round to and the ring is the
    window itself: the append at ``p`` takes the row of ``p - window``,
    which has just left it."""
    from tensorflowonspark_tpu.ops import paged_attention as pa

    dtype = jnp.int8 if cfg.cache_dtype == "int8" else cfg.jdtype
    t = pa.BANK_BLOCKS[0]
    try:
        pa.check_tiles(t, cfg.head_dim, dtype)
    except pa.TileLegalityError:
        return int(window)
    return (-(-int(window) // t) + 1) * t


def bank_rows(cfg, layer, length):
    """Rows layer ``layer``'s key/value bank holds for a cache of
    ``length`` positions: a ring (:func:`ring_rows`) where the layer's
    window makes one shorter than the bank, else ``length``."""
    window = cfg.window_of(layer)
    if cfg.fresh_prompts and window and cfg.attention_kind != "mla":
        return min(ring_rows(cfg, window), length)
    return length


def prefill_flash(cfg, span):
    """Whether a fresh prompt of ``span`` (bucketed) tokens attends
    through the flash forward kernel (``ops/flash_attention.py``,
    banded under the layer's window) instead of masked
    ``dot_attention`` with float32 scores ``[H, span, keys]`` in HBM.
    Read from what the code can see, as :func:`decode_bank_block`: the
    decoder's spans are fresh prompts (``fresh_prompts``: the kernel's
    causal mask knows nothing of a bank's earlier rows), no mesh, the
    banks hold what the projections gave (an int8 bank's prefill reads
    its own rounding back), a head size of whole lanes, and a span the
    CONFIGURED blocks divide — a long-prompt mix's buckets of 1024 do;
    prompts shorter than a block keep the einsums they always had."""
    return bool(
        cfg.fresh_prompts and cfg.mesh is None
        and cfg.cache_dtype != "int8" and cfg.head_dim % 128 == 0
        and span >= cfg.block_q and span % cfg.block_q == 0
        and span % cfg.block_k == 0)


class Attention(nn.Module):
    cfg: TransformerConfig
    #: index of this layer in ``layer_types`` (its window, its RoPE)
    layer: int = 0

    def _prompt_attention(self, q, k, v, positions, pad_start, window):
        """A fresh prompt over its OWN keys ``[B, S, ...]`` (positions
        from 0, nothing in the bank before it): causal, the layer's
        window, never the left pad ``[0, pad_start)``.  Through flash
        (:func:`prefill_flash`) the rows are rotated left by
        ``pad_start`` first, so the prompt starts at row 0 and the pad
        rows come LAST: the kernel's causal mask then hides them from
        every real query with no mask of their own (RoPE is already in
        q and k, and the window is a difference of rows, which the
        rotation keeps), and the result is rotated back; what the pad
        queries read is never looked at."""
        cfg = self.cfg
        b, s = q.shape[0], q.shape[1]
        pad = (pad_start if pad_start is not None
               else jnp.zeros((b,), jnp.int32))
        if prefill_flash(cfg, s):
            from tensorflowonspark_tpu.ops.flash_attention import (
                flash_attention,
            )

            turn = lambda t, by: jax.vmap(  # noqa: E731
                lambda row, n: jnp.roll(row, n, axis=0))(t, by)
            out = flash_attention(
                turn(q, -pad), turn(k, -pad), turn(v, -pad), causal=True,
                block_q=cfg.block_q, block_k=cfg.block_k, window=window,
            )
            return turn(out, pad)
        from tensorflowonspark_tpu.ops.attention import dot_attention

        pos = positions[0]
        visible = pos[None, :] <= pos[:, None]
        if window:
            visible = jnp.logical_and(
                visible, pos[None, :] > pos[:, None] - window)
        # a pad query keeps itself, as under the bank-wide mask
        visible = jnp.logical_or(
            jnp.logical_and(
                visible[None], pos[None, None, :] >= pad[:, None, None]),
            (pos[None, :] == pos[:, None])[None],
        )
        return dot_attention(
            q, k, v, causal=False,
            mask=jnp.where(visible, 0.0, -jnp.inf)[:, None])

    @nn.compact
    def __call__(self, x, positions, decode=False, pad_start=None,
                 per_slot=False, block_tables=None, stream=None):
        """``stream``: the shardings of :func:`residual_stream`, where
        ``x`` is the token-split normed stream of a training step."""
        cfg = self.cfg
        window = cfg.window_of(self.layer)
        theta, inv_freq, rope_factor = cfg.rope_of(self.layer)
        rotary = cfg.rotary_of(self.layer)
        h, d = cfg.heads_of(self.layer), cfg.head_dim
        hkv = cfg.num_kv_heads or h
        if h % hkv != 0:
            raise ValueError(
                "num_kv_heads ({0}) must divide num_heads ({1})".format(
                    hkv, h
                )
            )
        dense = lambda name, feats: nn.DenseGeneral(  # noqa: E731
            feats, axis=-1, use_bias=False, dtype=cfg.jdtype, name=name
        )

        def out_proj(o):
            if cfg.gating:
                # one gate a head and token, from the layer's normed
                # input, on the head's output before it is mixed
                gate = jax.nn.sigmoid(
                    (dense("gate", h)(x) if stream is None
                     else gated).astype(jnp.float32))
                o = (o.astype(jnp.float32) * gate[..., None]).astype(o.dtype)
            return nn.DenseGeneral(
                cfg.embed_dim, axis=(-2, -1), use_bias=False,
                dtype=cfg.jdtype, name="out",
            )(o)

        if cfg.fused_qkv and hkv != h:
            raise ValueError(
                "fused_qkv requires equal q/kv head counts; use "
                "separate projections with num_kv_heads"
            )
        if stream is not None:
            # every product of the normed input in one all-gather
            shapes = ((("qkv", (3, h, d)),) if cfg.fused_qkv else (
                ("q", (h, d)), ("k", (hkv, d)), ("v", (hkv, d))))
            if cfg.gating:
                shapes += (("gate", (h,)),)
            outs = sh.gathered_products(x, tuple(
                Kernel(x.shape[-1], f, name=n)() for n, f in shapes),
                stream, cfg.jdtype)
            if cfg.fused_qkv:
                q, k, v = (outs[0][..., i, :, :] for i in range(3))
            else:
                q, k, v = outs[:3]
            gated = outs[-1] if cfg.gating else None
        elif cfg.fused_qkv:
            qkv = dense("qkv", (3, h, d))(x)  # [B,S,3,H,D]
            q, k, v = (qkv[..., i, :, :] for i in range(3))
        else:
            q = dense("q", (h, d))(x)
            k = dense("k", (hkv, d))(x)
            v = dense("v", (hkv, d))(x)
        if cfg.qk_norm:
            q = RMSNorm(eps=cfg.rms_norm_eps, name="q_norm")(q)
            k = RMSNorm(eps=cfg.rms_norm_eps, name="k_norm")(k)
        q = rope(q, positions, theta, cfg.rope_interleave, inv_freq,
                 rope_factor, rotary)
        k = rope(k, positions, theta, cfg.rope_interleave, inv_freq,
                 rope_factor, rotary)
        if decode and cfg.kv_layout == "paged":
            return out_proj(self._paged_decode(
                x, q, k, v, positions, block_tables, hkv, d, window
            ))
        if decode:
            # KV-cache autoregressive path: keys/values append at the
            # write pointer (cache stores POST-rope keys — RoPE is
            # absolute, so cached rotations stay valid).  Single-token
            # per-slot steps read only each slot's live blocks through
            # the block-walking decode kernel (decode_bank_block);
            # every other decode call — prefill into the cache,
            # multi-token spans, the static generate() batch, a mesh —
            # attends over the whole cache under an additive mask with
            # dot attention (never flash: at s=1..P query rows the
            # O(S²) logits the flash kernel avoids don't exist).
            # The write index IS positions[0, 0] (rows are identical by
            # construction) — no per-layer counter to keep in sync with
            # the model-level position variable.  Cache capacity comes
            # from the provided cache arrays' actual shape, so
            # init_cache can size it to the generation length instead
            # of cfg.max_seq_len and the per-step cache read shrinks
            # proportionally.
            b = x.shape[0]
            int8_cache = cfg.cache_dtype == "int8"
            bank_dtype = jnp.int8 if int8_cache else cfg.jdtype
            ck = self.variable(
                "cache", "cached_key", jnp.zeros,
                (b, cfg.max_seq_len, hkv, d), bank_dtype,
            )
            cv = self.variable(
                "cache", "cached_value", jnp.zeros,
                (b, cfg.max_seq_len, hkv, d), bank_dtype,
            )
            # a ring (``fresh_prompts``, ring_rows): position p lives
            # in row p % rows; the bank a decoder built says so by its
            # length
            rows = ck.value.shape[1]
            ring = bool(cfg.fresh_prompts and window
                        and rows == ring_rows(cfg, window))
            if ring and not per_slot:
                # a fresh prompt: its last ``rows`` positions, each to
                # its own row (one scatter, rows all different)
                def _write(bank, val):
                    keep = min(val.shape[1], rows)
                    at = (positions[0, val.shape[1] - keep:]) % rows
                    return bank.at[:, at].set(
                        val[:, val.shape[1] - keep:].astype(bank.dtype))
            elif per_slot:
                # continuous-batching slot mode: every batch lane is an
                # independent request with its OWN write pointer
                # (positions[:, 0]), so appends are per-row instead of
                # one batch-wide slice write: one token a slot is ONE
                # scatter of B rows; a longer span is a per-row
                # dynamic_update_slice (vmapped), which XLA:TPU runs
                # as a loop over the slots, one small copy at a time
                # (0.22 ms a bank at 64 slots, against 0.007 for the
                # scatter: PERF.md section 5)
                row_i = positions[:, 0] % rows if ring else positions[:, 0]

                def _write(bank, val):
                    if val.shape[1] == 1:
                        return bank.at[jnp.arange(b), row_i].set(
                            val[:, 0].astype(bank.dtype)
                        )
                    return jax.vmap(
                        lambda bank_r, val_r, i_r: jax.lax.dynamic_update_slice(
                            bank_r, val_r.astype(bank_r.dtype),
                            (i_r,) + (0,) * (val_r.ndim - 1),
                        )
                    )(bank, val, row_i)
            else:
                i = positions[0, 0]

                def _write(bank, val):
                    return jax.lax.dynamic_update_slice(
                        bank, val.astype(bank.dtype),
                        (0, i) + (0,) * (val.ndim - 2),
                    )
            if int8_cache:
                cks = self.variable(
                    "cache", "cached_key_scale", jnp.zeros,
                    (b, cfg.max_seq_len, hkv, 1), jnp.float32,
                )
                cvs = self.variable(
                    "cache", "cached_value_scale", jnp.zeros,
                    (b, cfg.max_seq_len, hkv, 1), jnp.float32,
                )

                from tensorflowonspark_tpu import quantize as qz

                kq, ks = qz.quantize_leaf(k, reduce_axes=(3,))
                vq, vs = qz.quantize_leaf(v, reduce_axes=(3,))
                ck.value = _write(ck.value, kq)
                cv.value = _write(cv.value, vq)
                cks.value = _write(cks.value, ks)
                cvs.value = _write(cvs.value, vs)
            else:
                ck.value = _write(ck.value, k)
                cv.value = _write(cv.value, v)
            if (cfg.fresh_prompts and not per_slot
                    and (ring or prefill_flash(cfg, x.shape[1]))):
                return out_proj(self._prompt_attention(
                    q, k, v, positions, pad_start, window))
            kpos = jnp.arange(ck.value.shape[1])
            qpos = positions[0]
            from tensorflowonspark_tpu.ops.attention import dot_attention

            if per_slot:
                ps = (
                    pad_start if pad_start is not None
                    else jnp.zeros((b,), jnp.int32)
                )
            if (per_slot and x.shape[1] == 1
                    and decode_bank_block(cfg, ck.value.shape[1])):
                # one token a slot: walk the blocks of each slot's live
                # span [pad_start, position] and nothing else.  The
                # same visibility as the mask below — causal, window,
                # pad region, self always — as block skips plus an
                # in-block mask.
                from tensorflowonspark_tpu.ops.paged_attention import (
                    bank_attention,
                )

                out = bank_attention(
                    q[:, 0], ck.value, cv.value, positions[:, 0], ps,
                    window=window,
                    k_scale=cks.value if int8_cache else None,
                    v_scale=cvs.value if int8_cache else None,
                    ring=ring,
                )
                return out_proj(out[:, None])
            if ring:
                # one token a slot over a ring too small for the
                # kernel's tiles: row r holds the newest position at or
                # before the query's that is congruent to r
                if not per_slot or x.shape[1] != 1:
                    raise ValueError(
                        "a ring bank takes one token a slot or a fresh "
                        "prompt, not a span of %d" % x.shape[1])
                qp = positions[:, :1]  # [B, 1]
                held = qp - (qp - jnp.arange(rows)[None, :]) % rows
                vis = jnp.logical_and(held >= 0, held > qp - window)
                vis = jnp.logical_or(
                    jnp.logical_and(vis, held >= ps[:, None]), held == qp)
                out = dot_attention(
                    q, ck.value, cv.value, causal=False,
                    mask=jnp.where(vis, 0.0, -jnp.inf)[:, None, None],
                    k_scale=cks.value if int8_cache else None,
                    v_scale=cvs.value if int8_cache else None,
                )
                return out_proj(out)
            if per_slot:
                # per-row query positions: each slot sees its own
                # causal horizon, window, and pad region.  Slots keep
                # self-visibility (kpos == qpos) so a fully-masked idle
                # slot's softmax stays finite (same NaN guard as the
                # ragged pad-row case below).
                qpos_r = positions  # [B, S]
                vis = kpos[None, None, :] <= qpos_r[:, :, None]
                if window:
                    vis = jnp.logical_and(
                        vis,
                        kpos[None, None, :]
                        > qpos_r[:, :, None] - window,
                    )
                vis = jnp.logical_or(
                    jnp.logical_and(
                        vis, kpos[None, None, :] >= ps[:, None, None]
                    ),
                    kpos[None, None, :] == qpos_r[:, :, None],
                )
                mask = jnp.where(vis, 0.0, -jnp.inf)[:, None]
                out = dot_attention(
                    q, ck.value, cv.value, causal=False, mask=mask,
                    k_scale=cks.value if int8_cache else None,
                    v_scale=cvs.value if int8_cache else None,
                )
                return out_proj(out)
            visible = kpos[None, :] <= qpos[:, None]
            if window:
                visible = jnp.logical_and(
                    visible,
                    kpos[None, :] > qpos[:, None] - window,
                )
            if pad_start is not None:
                # ragged LEFT-padded batch: row r's cache slots before
                # pad_start[r] hold pad K/V and are never attended.
                # RoPE scores depend only on position DIFFERENCES, so
                # keeping physical slot positions leaves each row's
                # numerics identical to its unpadded run.  Pad QUERY
                # rows keep their own slot visible — otherwise their
                # softmax sees only -inf and the resulting NaN output
                # poisons the pad K/V of the NEXT layer (0 * NaN); for
                # real rows self-visibility is already implied by the
                # causal+window mask, so this changes nothing there.
                visible = jnp.logical_or(
                    jnp.logical_and(
                        visible[None],
                        kpos[None, None, :] >= pad_start[:, None, None],
                    ),
                    (kpos[None, :] == qpos[:, None])[None],
                )
                mask = jnp.where(visible, 0.0, -jnp.inf)[:, None]
            else:
                mask = jnp.where(visible, 0.0, -jnp.inf)[None, None]
            out = dot_attention(
                q, ck.value, cv.value, causal=False, mask=mask,
                k_scale=cks.value if int8_cache else None,
                v_scale=cvs.value if int8_cache else None,
            )
        else:
            out = attention(
                q,
                k,
                v,
                impl=cfg.attention_impl,
                causal=True,
                mesh=cfg.mesh,
                seq_axis=cfg.seq_axis,
                block_q=cfg.block_q,
                block_k=cfg.block_k,
                window=window,
            )
        return out_proj(out)

    def _paged_decode(self, x, q, k, v, positions, block_tables, hkv, d,
                      window):
        """Paged-KV decode (``kv_layout="paged"``), up to the output
        projection, which the caller applies: the per-layer cache
        is ONE physical page pool ``[kv_pages, kv_page_tokens, Hkv,
        Dx]`` shared by every slot; ``block_tables [B, kv_slot_blocks]``
        maps each slot's logical blocks to physical pages.  New K/V
        scatter into the pool at ``pool[table[b, pos // T], pos % T]``
        (slots own their writable pages exclusively — the allocator
        guarantees it — so the batch scatter never collides on live
        pages; idle lanes' tables point at the reserved trash page).
        Attention reads the pool through the block table: the
        ops/paged_attention.py kernel for single-token steps (the hot
        loop), the gather fallback for multi-token spans (canonical
        suffix prefill, speculative verify).  Positions are CANONICAL
        (token ``i`` at cache position ``i``) — the paged engine
        admits every request through the canonical path, so there is
        no pad region to mask."""
        cfg = self.cfg
        p, t = cfg.kv_pages, cfg.kv_page_tokens
        if p < 1 or cfg.kv_slot_blocks < 1:
            raise ValueError(
                "kv_layout='paged' needs kv_pages/kv_slot_blocks set "
                "(the SlotDecoder computes them; got pages={0}, "
                "slot_blocks={1})".format(p, cfg.kv_slot_blocks)
            )
        b, s = x.shape[0], x.shape[1]
        if block_tables is None:
            # cache-shape init path (init_cache's eval_shape): address
            # everything through the reserved trash page
            block_tables = jnp.zeros((b, cfg.kv_slot_blocks), jnp.int32)
        int8_cache = cfg.cache_dtype == "int8"
        bank_dtype = jnp.int8 if int8_cache else cfg.jdtype
        ck = self.variable(
            "cache", "cached_key", jnp.zeros, (p, t, hkv, d), bank_dtype,
        )
        cv = self.variable(
            "cache", "cached_value", jnp.zeros, (p, t, hkv, d), bank_dtype,
        )
        pos = positions  # [B, S] absolute canonical positions
        page = jnp.take_along_axis(block_tables, pos // t, axis=1)
        flat = (page * t + pos % t).reshape(-1)

        def _write(bank, val):
            pf = bank.reshape((p * t,) + bank.shape[2:])
            pf = pf.at[flat].set(
                val.reshape((b * s,) + val.shape[2:]).astype(bank.dtype)
            )
            return pf.reshape(bank.shape)

        if int8_cache:
            cks = self.variable(
                "cache", "cached_key_scale", jnp.zeros,
                (p, t, hkv, 1), jnp.float32,
            )
            cvs = self.variable(
                "cache", "cached_value_scale", jnp.zeros,
                (p, t, hkv, 1), jnp.float32,
            )

            from tensorflowonspark_tpu import quantize as qz

            kq, ks = qz.quantize_leaf(k, reduce_axes=(3,))
            vq, vs = qz.quantize_leaf(v, reduce_axes=(3,))
            ck.value = _write(ck.value, kq)
            cv.value = _write(cv.value, vq)
            cks.value = _write(cks.value, ks)
            cvs.value = _write(cvs.value, vs)
        else:
            ck.value = _write(ck.value, k)
            cv.value = _write(cv.value, v)
        from tensorflowonspark_tpu.ops.paged_attention import (
            paged_attention,
            paged_gather_attention,
        )

        ksp = cks.value if int8_cache else None
        vsp = cvs.value if int8_cache else None
        if s == 1 and cfg.paged_decode_impl == "kernel":
            out = paged_attention(
                q[:, 0], ck.value, cv.value, block_tables,
                pos[:, 0] + 1, window=window,
                k_scale_pool=ksp, v_scale_pool=vsp,
            )[:, None]
        else:
            out = paged_gather_attention(
                q, ck.value, cv.value, block_tables, pos,
                span=cfg.kv_span or None,
                window=window,
                k_scale_pool=ksp, v_scale_pool=vsp,
            )
        return out


class Kernel(nn.Module):
    """The ``kernel`` an ``nn.DenseGeneral(features, axis=-1)`` of the
    same name holds, initialised alike, for products another function
    makes (:func:`~tensorflowonspark_tpu.parallel.sharding.
    gathered_products`)."""

    in_dim: int
    features: tuple

    @nn.compact
    def __call__(self):
        def init(rng, shape, dtype=jnp.float32):
            flat = (shape[0], math.prod(shape[1:]))
            return jnp.reshape(
                nn.initializers.lecun_normal()(rng, flat, dtype), shape)

        return self.param(
            "kernel", init, (self.in_dim,) + tuple(self.features))


class MLP(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, stream=None):
        """``stream`` as :class:`Attention` takes it."""
        cfg = self.cfg
        if stream is None:
            wi = nn.Dense(cfg.mlp_dim, use_bias=False, dtype=cfg.jdtype, name="wi")(x)
            wg = nn.Dense(cfg.mlp_dim, use_bias=False, dtype=cfg.jdtype, name="wg")(x)
        else:
            wi, wg = sh.gathered_products(x, tuple(
                Kernel(x.shape[-1], (cfg.mlp_dim,), name=n)()
                for n in ("wi", "wg")), stream, cfg.jdtype)
        return nn.Dense(
            cfg.embed_dim, use_bias=False, dtype=cfg.jdtype, name="wo"
        )(nn.silu(wg) * wi)


def residual_stream(cfg, x, decode):
    """The shardings :func:`~tensorflowonspark_tpu.parallel.sharding.
    residual_shardings` gives a training step's residual stream
    ``[batch, seq, embed]`` (batch and seq: ``x``'s first two dims) —
    tokens split over the ``model`` axis between blocks, gathered whole
    inside each projection — or ``None``: decoding, a mesh without a
    ``model`` axis the layout fits, or a model with latent attention or
    routed experts, whose projections read whole tokens as given."""
    if decode or cfg.attention_kind == "mla" or any(
            cfg.ffn_kind(i) != "dense" for i in range(cfg.num_layers)):
        return None
    return sh.residual_shardings(cfg.mesh, x.shape[0], x.shape[1])


class Block(nn.Module):
    cfg: TransformerConfig
    #: index of this layer in the model's per-layer pattern
    #: (``mlp_layer_types``, ``indexer_types``)
    layer: int = 0

    @nn.compact
    def __call__(self, x, positions, decode=False, pad_start=None,
                 per_slot=False, block_tables=None, sel=None):
        """One pre-norm layer.  Under ``attention_kind="mla"`` the
        layer takes and returns the sparse selection ``sel`` beside
        ``x`` (a "shared" layer attends over the set the last "full"
        layer chose): ``(x, sel)``; otherwise ``x`` alone."""
        cfg = self.cfg
        norm = lambda name: RMSNorm(  # noqa: E731
            eps=cfg.rms_norm_eps, name=name)
        # where the stream is split over tokens, norms and adds run on
        # this chip's tokens; the projections gather them whole and
        # hand back partial sums, summed into this chip's share
        stream = residual_stream(cfg, x, decode)
        add = lambda a: a if stream is None else sh.split_tokens(  # noqa: E731
            a, stream)
        if cfg.attention_kind == "mla":
            from tensorflowonspark_tpu.models.mla import MLAttention

            att, sel = MLAttention(
                cfg,
                indexer=(cfg.indexer_types[self.layer]
                         if cfg.indexer_types else ""),
                name="attn",
            )(norm("ln1")(x), positions, decode=decode,
              pad_start=pad_start, per_slot=per_slot, sel=sel)
            x = x + att
        else:
            x = x + add(Attention(cfg, layer=self.layer, name="attn")(
                norm("ln1")(x), positions, decode=decode,
                pad_start=pad_start, per_slot=per_slot,
                block_tables=block_tables, stream=stream,
            ))
        h = norm("ln2")(x)
        kind = cfg.ffn_kind(self.layer)
        if kind == "sigmoid_moe":
            from tensorflowonspark_tpu.models.moe import SigmoidMoE

            ff = SigmoidMoE(
                router_experts=cfg.router_experts or cfg.num_experts,
                expert_first=cfg.expert_first,
                num_experts=cfg.num_experts,
                mlp_dim=cfg.moe_mlp_dim or cfg.mlp_dim,
                embed_dim=cfg.embed_dim,
                k=cfg.expert_k,
                scaling=cfg.routed_scaling,
                shared_experts=cfg.shared_experts,
                dtype=cfg.dtype,
                scoring=cfg.router_scoring,
                name="moe",
            )(h, differentiable=not decode)
        elif kind == "moe":
            from tensorflowonspark_tpu.models.moe import MoEMLP

            axes = set(getattr(cfg.mesh, "axis_names", ()) or ())
            if cfg.expert_dispatch == "dropless" and axes & {
                "expert", "model"
            }:
                # the gmm pallas call is opaque to GSPMD: sharding the
                # expert weights on ANY axis the MoE rules map (expert
                # -> 'expert', expert_mlp -> 'model') would silently
                # all-gather the full [E, D, M] tensors onto every
                # device — exactly what EP/TP shard away
                raise ValueError(
                    "expert_dispatch='dropless' does not compose with "
                    "an expert- or model-sharded mesh; use 'gather'"
                )
            ff = MoEMLP(
                num_experts=cfg.num_experts,
                mlp_dim=cfg.mlp_dim,
                embed_dim=cfg.embed_dim,
                k=cfg.expert_k,
                capacity_factor=cfg.capacity_factor,
                dtype=cfg.dtype,
                dispatch=cfg.expert_dispatch,
                name="moe",
            )(h)
        else:
            ff = MLP(cfg, name="mlp")(h, stream=stream)
        x = x + add(ff)
        return (x, sel) if cfg.attention_kind == "mla" else x


class Transformer(nn.Module):
    """LM forward: ``tokens [B, S] int32 -> logits [B, S, vocab]``."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, decode=False, pad_start=None,
                 slot_positions=None, block_tables=None,
                 last_only=False):
        """``last_only``: logits of the last position alone,
        ``[B, 1, vocab]`` (a prefill samples from nothing else; the
        head over a 16k-token prompt is a gigabyte of logits)."""
        cfg = self.cfg
        if pad_start is not None and not decode:
            raise ValueError(
                "pad_start (ragged left-padded batches) is a decode-"
                "path feature; the training path has no pad masking"
            )
        if slot_positions is not None and not decode:
            raise ValueError(
                "slot_positions (continuous-batching slot decode) is a "
                "decode-path feature"
            )
        if block_tables is not None and not decode:
            raise ValueError(
                "block_tables (paged-KV slot decode) is a decode-path "
                "feature"
            )
        emb = self.param(
            "embedding",
            nn.initializers.normal(stddev=0.02),
            (cfg.vocab_size, cfg.embed_dim),
        )
        x = emb[tokens].astype(cfg.jdtype)
        stream = residual_stream(cfg, tokens, decode)
        if not decode:
            from tensorflowonspark_tpu import telemetry

            telemetry.get_registry().gauge("train.residual_shards").set(
                1 if stream is None else cfg.mesh.shape["model"])
        if stream is not None:
            x = sh.split_tokens(x, stream)
        if decode:
            # absolute positions continue from the cache write pointer
            # (one shared counter; the per-layer Attention counters
            # advance in lockstep with it).  In slot mode every batch
            # lane is an independent request: the caller owns per-slot
            # write pointers and passes them as ``slot_positions`` —
            # the shared counter is left untouched.
            pos_var = self.variable(
                "cache", "position", lambda: jnp.zeros((), jnp.int32)
            )
            if slot_positions is None:
                start = pos_var.value
                positions = jnp.broadcast_to(
                    start + jnp.arange(tokens.shape[1]), tokens.shape
                )
                pos_var.value = start + tokens.shape[1]
            else:
                positions = (
                    slot_positions[:, None]
                    + jnp.arange(tokens.shape[1])[None, :]
                )
        else:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1]), tokens.shape
            )
        mla = cfg.attention_kind == "mla"
        if cfg.remat and cfg.remat_policy not in ("block", "dots"):
            raise ValueError(
                "remat_policy must be 'block' or 'dots', got %r"
                % (cfg.remat_policy,)
            )
        if cfg.remat and not decode:
            # remat is a training trade (recompute in backward); decode
            # has no backward, and the wrapped call must not see the
            # python-bool flag (jax.checkpoint would try to trace it)
            # "block" keeps the block's input, of a sigmoid-routed span
            # its routing's integers, and of attention through the flash
            # kernels their output and lse (a block that names nothing
            # saves nothing, as under no policy at all)
            from tensorflowonspark_tpu.models.moe import SPAN_SAVED
            from tensorflowonspark_tpu.ops.flash_attention import (
                FLASH_SAVED,
            )

            policy = (
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                if cfg.remat_policy == "dots"
                else jax.checkpoint_policies.save_only_these_names(
                    *SPAN_SAVED, *FLASH_SAVED)
            )
            block = nn.remat(Block, static_argnums=(), policy=policy)
            sel = None
            for i in range(cfg.num_layers):
                out = block(cfg, layer=i, name="block_%d" % i)(
                    x, positions, sel=sel)
                x, sel = out if mla else (out, None)
        else:
            sel = None
            for i in range(cfg.num_layers):
                out = Block(cfg, layer=i, name="block_%d" % i)(
                    x, positions, decode, pad_start=pad_start,
                    per_slot=slot_positions is not None,
                    block_tables=block_tables, sel=sel,
                )
                x, sel = out if mla else (out, None)
        if last_only:
            x = x[:, -1:]
        x = RMSNorm(eps=cfg.rms_norm_eps, name="ln_f")(x)
        # tied output head would shard awkwardly under TP; a separate
        # vocab projection keeps the ``vocab`` logical axis clean
        if stream is not None and not last_only:
            # whole tokens out: the loss slices positions
            logits, = sh.gathered_products(x, (Kernel(
                cfg.embed_dim, (cfg.vocab_size,), name="lm_head")(),),
                stream, cfg.jdtype)
        else:
            logits = nn.Dense(
                cfg.vocab_size, use_bias=False, dtype=cfg.jdtype,
                name="lm_head")(x)
        return logits.astype(jnp.float32)


#: path-regex → logical axes (see models/base.annotate)
LOGICAL_AXES_RULES = (
    (r"embedding$", ("vocab", "embed")),
    (r"attn/(q|k|v)/kernel", ("embed", "heads", None)),
    (r"attn/qkv/kernel", ("embed", None, "heads", None)),
    (r"attn/out/kernel", ("heads", None, "embed")),
    (r"mlp/(wi|wg)/kernel", ("embed", "mlp")),
    (r"mlp/wo/kernel", ("mlp", "embed")),
    (r"lm_head/kernel", ("embed", "vocab")),
    (r"(ln1|ln2|ln_f)/scale", None),
    # MoE blocks (models/moe.py)
    (r"moe/router$", ("embed", None)),
    (r"moe/(wi|wg)$", ("expert", "embed", "expert_mlp")),
    (r"moe/wo$", ("expert", "expert_mlp", "embed")),
)


def logical_axes(params):
    return base.annotate(params, LOGICAL_AXES_RULES)


def loss_fn(model):
    """Next-token cross-entropy; batch = dict(tokens=[B,S])."""

    def _loss(params, batch, rng):
        tokens = batch["tokens"]
        logits = model.apply({"params": params}, tokens)
        targets = tokens[:, 1:]
        logits = logits[:, :-1]
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return jnp.mean(nll)

    return _loss


def init_cache(model, batch_size, cache_len=None):
    """A zeroed KV cache for ``batch_size`` sequences.

    ``cache_len`` (default ``cfg.max_seq_len``) sizes the per-layer
    key/value capacity.  The static :func:`generate` batch, a mesh and
    head sizes the lane does not tile read and mask the WHOLE cache
    every step (bandwidth-bound), so size it to the actual generation
    length; the slot decoder's single-token steps read only the blocks
    of each slot's live span where :func:`decode_bank_block` finds a
    block size (a bank length that 256 or 128 divides).
    Shapes come from ``jax.eval_shape`` — no parameters are
    materialized and no forward runs."""
    length = cache_len if cache_len is not None else model.cfg.max_seq_len
    stub = jnp.zeros((batch_size, 1), jnp.int32)
    # decode must stay a python bool (it selects trace-time structure),
    # so close over it instead of passing it through eval_shape's args
    shapes = jax.eval_shape(
        lambda k, s: model.init(k, s, decode=True),
        jax.random.PRNGKey(0), stub,
    )
    if model.cfg.kv_layout == "paged":
        # paged pools are [kv_pages, kv_page_tokens, H, Dx] — the
        # geometry comes from the config (the SlotDecoder sized it),
        # not from cache_len, and there is no batch dim to resize
        return jax.tree.map(
            lambda x: jnp.zeros(x.shape, x.dtype), shapes["cache"]
        )

    def _zero(x, rows=length):
        # [B, max_seq, ...] banks: keys and values [.., H, D], latent
        # rows and index keys [.., width]
        if x.ndim >= 3:
            return jnp.zeros((x.shape[0], rows) + x.shape[2:], x.dtype)
        return jnp.zeros(x.shape, x.dtype)

    if not model.cfg.fresh_prompts:
        return jax.tree.map(_zero, shapes["cache"])
    # a windowed layer's banks are rings where that is shorter
    out = {}
    for name, sub in shapes["cache"].items():
        rows = length
        if name.startswith("block_"):
            rows = bank_rows(model.cfg, int(name.rsplit("_", 1)[1]), length)
        out[name] = jax.tree.map(lambda x, rows=rows: _zero(x, rows), sub)
    return out


def sample_logits(logits, key, temperature=0.0, top_k=0, top_p=0.0):
    """One sampling step on ``[B, V]`` logits.

    ``temperature=0`` is greedy argmax; otherwise categorical after the
    optional filters: ``top_k`` keeps the k highest logits, ``top_p``
    keeps the smallest prefix of the probability-sorted vocabulary
    whose mass reaches p (nucleus sampling; the top token always
    survives).  Filters compose (top-k first, as usual).  All static
    shapes — sort/threshold, no dynamic vocab slicing."""
    if temperature <= 0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits.astype(jnp.float32) / temperature
    neg = jnp.float32(-1e30)
    use_k = bool(top_k) and 0 < top_k < logits.shape[-1]
    use_p = bool(top_p) and 0.0 < top_p < 1.0
    if use_k or use_p:
        # one descending sort serves both filters (the sort dominates
        # per-token sampling cost inside the decode scan)
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
    if use_k:
        kth = sorted_logits[:, top_k - 1][:, None]
        logits = jnp.where(logits >= kth, logits, neg)
        sorted_logits = jnp.where(
            jnp.arange(sorted_logits.shape[-1])[None, :] < top_k,
            sorted_logits, neg,
        )
    if use_p:
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep ranks whose PRECEDING mass is < p (top rank always kept)
        keep = jnp.concatenate(
            [jnp.ones_like(cum[:, :1], bool), cum[:, :-1] < top_p],
            axis=-1,
        )
        # threshold logit: the smallest kept value per row
        cutoff = jnp.min(
            jnp.where(keep, sorted_logits, jnp.inf), axis=-1
        )[:, None]
        logits = jnp.where(logits >= cutoff, logits, neg)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def generate(model, params, prompt, max_new_tokens, temperature=0.0,
             rng=None, top_k=0, top_p=0.0, pad_start=None, eos_id=None):
    """Autoregressive sampling with a KV cache.

    New TPU-first capability (the reference has no text generation of
    any kind).  Phase 1 prefills the cache with the whole prompt in one
    forward (MXU-efficient: one [B,P] pass, not P decode steps); phase
    2 is a ``lax.scan`` of single-token decode steps — static shapes,
    one compiled program for the entire loop, cache updated in place
    via ``dynamic_update_slice``.

    Args:
      model: a :class:`Transformer` (any attention_impl; decode always
        runs dot-on-cache).
      prompt: ``[B, P]`` int32; ``P + max_new_tokens`` must fit
        ``cfg.max_seq_len``.
      temperature: 0 = greedy argmax; otherwise categorical sampling
        (requires ``rng``), filtered by ``top_k``/``top_p`` (see
        :func:`sample_logits`).
      pad_start: optional ``[B]`` int32 — ragged multi-request
        batching: prompts LEFT-padded to a common ``P`` with
        ``pad_start[r]`` pad slots before row ``r``'s real tokens.
        Pad cache slots are masked out of every attention; RoPE scores
        depend only on position differences, so each row generates
        exactly what its unpadded prompt would (serving pads rows and
        derives this automatically — see serving_builder
        ``mode="generate"``).
      eos_id: optional stop token — once a row samples it, every later
        position emits ``eos_id`` again (per-row stop inside the one
        compiled scan).  Rows are returned UNTRIMMED at the full
        ``[B, max_new_tokens]`` shape — static shapes are the whole
        point of the compiled scan; the serving predictor reports a
        ``generated_len`` column (the first-eos position) alongside
        the untrimmed rows and the CONSUMER trims
        (``row[:generated_len]``).  Tested in
        tests/test_models.py::test_generated_len_matches_first_eos.
    Returns ``[B, max_new_tokens]`` sampled tokens.
    """
    b, p = prompt.shape
    total = p + max_new_tokens
    if total > model.cfg.max_seq_len:
        raise ValueError(
            "prompt ({0}) + max_new_tokens ({1}) exceeds the cache "
            "capacity max_seq_len={2}".format(
                p, max_new_tokens, model.cfg.max_seq_len
            )
        )
    if max_new_tokens <= 0:
        return jnp.zeros((b, 0), jnp.int32)
    if temperature > 0 and rng is None:
        raise ValueError("temperature sampling needs an rng key")
    rng = rng if rng is not None else jax.random.PRNGKey(0)

    from tensorflowonspark_tpu import quantize as qz

    qparams = params
    quantized = qz.is_quantized(params)
    if quantized:
        # prefill dequantizes once (it is compute-bound); each decode
        # step re-dequantizes under an optimization barrier so the
        # weights cross HBM as int8 every step (see quantize.py)
        params = qz.dequantize_tree(
            qparams, model.cfg.jdtype, barrier=False
        )

    def sample(logits, key):
        return sample_logits(
            logits, key, temperature=temperature, top_k=top_k, top_p=top_p
        )

    # cache sized to the live positions, not cfg.max_seq_len: every
    # decode step reads+masks the whole bank
    cache = init_cache(model, b, cache_len=total)
    logits, mut = model.apply(
        {"params": params, "cache": cache}, prompt, decode=True,
        mutable=["cache"], pad_start=pad_start,
    )
    rng, key = jax.random.split(rng)
    first = sample(logits[:, -1], key)
    done0 = (
        first == eos_id if eos_id is not None
        else jnp.zeros((b,), jnp.bool_)
    )

    def step(carry, key):
        cache, tok, done = carry
        p = (
            qz.dequantize_tree(qparams, model.cfg.jdtype, barrier=True)
            if quantized else params
        )
        logits, mut = model.apply(
            {"params": p, "cache": cache}, tok[:, None],
            decode=True, mutable=["cache"], pad_start=pad_start,
        )
        nxt = sample(logits[:, 0], key)
        if eos_id is not None:
            nxt = jnp.where(done, jnp.int32(eos_id), nxt)
            done = jnp.logical_or(done, nxt == eos_id)
        return (mut["cache"], nxt, done), nxt

    keys = jax.random.split(rng, max(0, max_new_tokens - 1))
    (_, _, _), rest = jax.lax.scan(
        step, (mut["cache"], first, done0), keys
    )
    return jnp.concatenate(
        [first[:, None], jnp.swapaxes(rest, 0, 1)], axis=1
    ) if max_new_tokens > 1 else first[:, None]


def generate_speculative(model, params, prompt, max_new_tokens,
                         draft_len=4, ngram=2, return_stats=False,
                         draft_model=None, draft_params=None, stats=None):
    """Greedy generation with speculative decoding.

    Decode is HBM-bound: one token per forward re-reads all weights.
    Speculation verifies ``draft_len`` guessed tokens in ONE forward
    (same weight read, ``draft_len+1`` query rows — nearly free on the
    MXU), so every accepted draft is a weight read saved.  Two draft
    sources:

    - **prompt lookup** (default, ``draft_model=None``): n-gram
      continuation — find the most recent earlier occurrence of the
      last ``ngram`` emitted/prompt tokens and copy what followed it.
      No extra model; highly effective on inputs with repeated
      structure (code, extraction, summarization).
    - **draft model** (``draft_model``/``draft_params``): a small
      :class:`Transformer` with the SAME vocabulary proposes
      ``draft_len`` tokens autoregressively through its own KV cache
      (prefilled on the prompt, write pointer rewound in lockstep with
      the flagship's after every verify round), and the flagship
      verifies all of them in one batched step.  Beats prompt lookup
      on free-form text, where n-grams rarely repeat; see
      docs/serving.md "Prefix cache & speculative decoding".

    Greedy-only and LOSSLESS either way: the verify forward recomputes
    the exact argmax chain, accepted tokens match :func:`generate`'s
    output token for token (tested) — draft quality only moves the
    accept rate, never the tokens.  Rejected verify rows leave stale
    cache entries BEYOND the accepted position; they are masked
    (decode attends ``kpos <= qpos``) and overwritten by the next
    round's writes before the write pointer reaches them.  Batch rows
    accept in lockstep (the cache write pointer is shared): the
    per-round acceptance is the minimum over rows, so speculation pays
    off most at small batch — exactly the bandwidth-bound serving
    regime.  Uniform-length prompts only: the batch is one ``[B, P]``
    array (ragged rows fail at stacking with a named error in
    ``serving.predict_rows``; see docs/inference.md).

    Returns ``[B, max_new_tokens]`` int32 (with ``return_stats=True``,
    a ``(tokens, rounds)`` pair — ``max_new_tokens/rounds`` is the
    mean tokens per verify forward; 1.0 means nothing accepted, ``1 +
    draft_len`` is the ceiling).  Pass a dict as ``stats`` to also get
    ``{"rounds", "proposed", "accepted", "accept_rate"}`` — the
    accept-rate accounting the serving engine and bench report.
    """
    b, p = prompt.shape
    k = int(draft_len)
    total = p + max_new_tokens
    if model.cfg.attention_kind == "mla":
        raise ValueError(
            "speculative decoding verifies a multi-token block against "
            "the cache; latent banks (attention_kind='mla') take a "
            "span only as a prefill from the start")
    if k < 1:
        raise ValueError("draft_len must be >= 1")
    if ngram < 1:
        # ngram=0 would make every history position a "match" and draft
        # from position 0 forever
        raise ValueError("ngram must be >= 1")
    if draft_model is not None:
        if draft_params is None:
            raise ValueError("draft_model needs draft_params")
        if draft_model.cfg.vocab_size != model.cfg.vocab_size:
            raise ValueError(
                "draft and flagship models must share a vocabulary; "
                "got draft vocab {0} vs flagship {1}".format(
                    draft_model.cfg.vocab_size, model.cfg.vocab_size
                )
            )
    if max_new_tokens <= 0:
        # mirror generate(): nothing to emit — skip cache alloc/prefill
        out = jnp.zeros((prompt.shape[0], 0), jnp.int32)
        if stats is not None:
            stats.update(rounds=0, proposed=0, accepted=0,
                         accept_rate=0.0)
        return (out, 0) if return_stats else out
    if total > model.cfg.max_seq_len:
        raise ValueError(
            "prompt ({0}) + max_new_tokens ({1}) exceeds "
            "max_seq_len={2}".format(
                p, max_new_tokens, model.cfg.max_seq_len
            )
        )
    from tensorflowonspark_tpu import quantize as qz

    qparams = params
    quantized = qz.is_quantized(params)
    if quantized:
        # same contract as generate(): prefill dequantizes once, each
        # verify round re-dequantizes under a barrier (weights cross
        # HBM as int8 — see quantize.py)
        params = qz.dequantize_tree(
            qparams, model.cfg.jdtype, barrier=False
        )
    if draft_model is not None and qz.is_quantized(draft_params):
        # the draft is small: dequantize once, no per-step barrier
        draft_params = qz.dequantize_tree(
            draft_params, draft_model.cfg.jdtype, barrier=False
        )
    # cache must hold the last verify block that crosses max_new
    cache = init_cache(model, b, cache_len=total + k + 1)
    logits, mut = model.apply(
        {"params": params, "cache": cache}, prompt, decode=True,
        mutable=["cache"],
    )
    first = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    dcache = None
    if draft_model is not None:
        # the draft keeps its own cache, prefilled on the same prompt;
        # its write pointer tracks the flagship's round for round
        dcache = init_cache(draft_model, b, cache_len=total + k + 1)
        _, dmut = draft_model.apply(
            {"params": draft_params, "cache": dcache}, prompt,
            decode=True, mutable=["cache"],
        )
        dcache = dict(dmut["cache"])

    hist_len = total + k + 1
    history = jnp.zeros((b, hist_len), jnp.int32).at[:, :p].set(prompt)
    history = history.at[:, p].set(first)
    emitted = jnp.zeros((b, max_new_tokens + k + 1), jnp.int32)
    emitted = emitted.at[:, 0].set(first)

    def find_drafts(hist, hist_n, last):
        """[hist_len] history with hist_n valid tokens -> [k] drafts
        (continuation of the latest earlier n-gram match; repeat of
        ``last`` when none)."""
        idx = jnp.arange(hist_len)
        suffix = jax.lax.dynamic_slice(hist, (hist_n - ngram,), (ngram,))
        windows = hist[
            jnp.minimum(idx[:, None] + jnp.arange(ngram)[None, :],
                        hist_len - 1)
        ]
        match = jnp.all(windows == suffix[None, :], axis=-1)
        valid = idx < hist_n - ngram  # strictly before the suffix itself
        j = jnp.max(jnp.where(match & valid, idx, -1))
        start = jnp.clip(j + ngram, 0, hist_len - k)
        cont = jax.lax.dynamic_slice(hist, (start,), (k,))
        # positions past the valid history would draft garbage zeros;
        # the repeat-last fallback at least keeps runs alive
        in_range = start + jnp.arange(k) < hist_n
        fallback = jnp.full((k,), last, jnp.int32)
        return jnp.where((j >= 0) & in_range, cont, fallback)

    def model_drafts(dcache, last):
        """k autoregressive draft-model steps (plus one extra feeding
        the final proposal, so ITS kv is banked too — when every draft
        is accepted the flagship pointer moves past it, and a hole
        there would poison all later draft rounds)."""
        def dstep(carry, _):
            dc, tok = carry
            dlogits, dmut = draft_model.apply(
                {"params": draft_params, "cache": dc}, tok[:, None],
                decode=True, mutable=["cache"],
            )
            nxt = jnp.argmax(dlogits[:, 0], axis=-1).astype(jnp.int32)
            return (dict(dmut["cache"]), nxt), nxt

        (dcache, _), douts = jax.lax.scan(
            dstep, (dcache, last), None, length=k + 1
        )
        return dcache, jnp.swapaxes(douts, 0, 1)[:, :k]  # [B, k]

    def round_(state):
        history, emitted, cache, dcache, n, last, rounds, acc = state
        if draft_model is not None:
            dcache, drafts = model_drafts(dcache, last)
        else:
            drafts = jax.vmap(find_drafts)(
                history, jnp.full((b,), p + n), last
            )  # [B, k]
        block = jnp.concatenate([last[:, None], drafts], axis=1)
        pr = (
            qz.dequantize_tree(qparams, model.cfg.jdtype, barrier=True)
            if quantized else params
        )
        logits, mut = model.apply(
            {"params": pr, "cache": cache}, block, decode=True,
            mutable=["cache"],
        )
        targets = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B,k+1]
        # row r accepts drafts while they match the model's chain
        ok = drafts == targets[:, :k]
        m = jnp.min(
            jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)
        )  # lockstep acceptance
        out_block = targets  # cols 0..m are valid for every row
        emitted = jax.lax.dynamic_update_slice(
            emitted, out_block, (0, n)
        )
        history = jax.lax.dynamic_update_slice(
            history, out_block, (0, p + n)
        )
        gained = m + 1
        cache = dict(mut["cache"])
        # rewind the write pointer to the newest ACCEPTED token's slot:
        # tokens e_0..e_{n'-1} are emitted, e_{n'-1}'s kv is not yet
        # written, so the pointer sits at its position p + n' - 1
        cache["position"] = jnp.asarray(
            p + n + gained - 1, jnp.int32
        )
        if draft_model is not None:
            # lockstep rewind: stale draft kv beyond the pointer is
            # causally masked and overwritten by the next round's
            # sequential feeds, exactly like the flagship's
            dcache = dict(dcache)
            dcache["position"] = jnp.asarray(
                p + n + gained - 1, jnp.int32
            )
        last = jnp.take_along_axis(targets, m[None].repeat(b)[:, None],
                                   axis=1)[:, 0]
        return (history, emitted, cache, dcache, n + gained, last,
                rounds + 1, acc + m)

    def cond(state):
        return state[4] < max_new_tokens

    # after prefill the pointer is already at p — `first`'s slot
    cache = dict(mut["cache"])
    state = (history, emitted, cache, dcache, jnp.int32(1), first,
             jnp.int32(0), jnp.int32(0))
    history, emitted, cache, dcache, n, last, rounds, acc = (
        jax.lax.while_loop(cond, round_, state)
    )
    tokens = emitted[:, :max_new_tokens]
    if stats is not None:
        r = int(rounds)
        a = int(acc)
        stats.update(
            rounds=r, proposed=r * k, accepted=a,
            accept_rate=(a / float(r * k)) if r else 0.0,
        )
    return (tokens, rounds) if return_stats else tokens


def _default_device():
    """The device uncommitted arrays land on right now: the ambient
    ``jax.default_device`` when one is set, else the first local
    device."""
    dev = jax.config.jax_default_device
    if dev is None:
        return jax.local_devices()[0]
    if isinstance(dev, str):  # a platform name
        return jax.local_devices(backend=dev)[0]
    return dev


class _BlockRef(object):
    """A prefix-cache block payload: a zero-copy VIEW into a donor
    extract-segment (``segment`` is the per-bank leaf tuple one
    ``SlotDecoder._extract_jit`` call produced; ``index`` is this
    block's position in it).  Storing views keeps insert free of
    device dispatches; the donor segment's buffers live until every
    block referencing them is evicted (bytes are accounted per block,
    so the amplification is bounded by one prompt's segment)."""

    __slots__ = ("segment", "index")

    def __init__(self, segment, index):
        self.segment = segment
        self.index = index


class SlotDecoder:
    """Slot-level KV-cache engine for CONTINUOUS in-flight batching.

    The static :func:`generate` path is batch-synchronous: every
    request in a batch pays the max-length decode.  This engine treats
    each batch lane as a SLOT — an independent request with its own
    cache region, write pointer, pad region, and eos flag — so the
    serving scheduler (:mod:`tensorflowonspark_tpu.serving`,
    ``schedule="continuous"``) can evict a finished request and admit
    a queued prompt into the freed lane *between* chunked decode
    scans, without touching the other lanes and without recompiling.

    Exactly TWO compiled programs run steady-state:

    - ``prefill``: one program per prompt-length BUCKET (lengths round
      up to ``pad_multiple``, the same bucketing the static path
      uses).  It slices one lane out of every cache bank
      (``dynamic_slice``), runs the ordinary batch-1 prefill forward
      with ``pad_start`` masking into that lane, writes the lane back
      (``dynamic_update_slice``), and samples the first token.  The
      slot index is a TRACED argument — admitting into lane 0 vs lane
      7 is the same program.
    - ``decode_chunk``: a ``lax.scan`` of ``chunk_size`` single-token
      steps over the whole slot batch with per-slot positions
      (``slot_positions`` decode mode — per-row cache appends and
      per-row causal/window/pad masks).  One program for the engine's
      lifetime.

    Numerics are identical to :func:`generate` per request (greedy):
    the lane sees exactly the same prefill forward and the same
    masked decode steps it would in a static batch — RoPE scores
    depend only on position differences and pad slots are masked, the
    invariant tests/test_models.py::test_ragged_generate_matches_per_row
    already pins down.  Composes with GQA, sliding-window attention,
    int8 weights (dequant-per-step under a barrier, as generate
    does), and the int8 KV cache (per-row quantized appends).

    Per-slot state (``positions`` — next write index, ``pad_start``,
    ``last_tok``, ``done``) lives ON DEVICE and is updated by the two
    compiled programs themselves, so ``admit`` is a single async
    dispatch (no host sync — a sync stalls the dispatch pipeline); the only synchronizing pull is the chunk's token block,
    which the scheduler needs anyway to make evict decisions.  The
    host keeps just the ``active`` scheduling mask.

    Two request-level reuse planes compose on top (ISSUE 6 /
    docs/serving.md "Prefix cache & speculative decoding"):

    - ``prefix_cache``: a
      :class:`~tensorflowonspark_tpu.prefix_cache.PrefixCache` turns
      admits CANONICAL (token ``i`` at cache position ``i``): the
      longest cached block-prefix installs into the lane with one
      segment write and only the uncached suffix prefills
      (:meth:`_prefill_canonical_impl`); finished prefills commit
      their blocks back.  Token-identical to cold admits (the RoPE
      position-difference invariant), asserted in
      tests/test_prefix_cache.py.
    - ``draft_model``/``draft_params``: chunks become per-slot
      SPECULATIVE rounds (:meth:`_chunk_spec_impl`) — the draft owns
      a second slot table at the same canonical positions, proposes
      ``draft_len`` tokens per slot, the flagship verifies them in
      one batched step, and every slot accepts independently.
      Greedy-only, lossless; accept counters surface through
      :meth:`reuse_stats`.

    All cache/state buffers are DONATED through the jitted programs
    (the handles are linear — consumed and reassigned every
    dispatch), so admits scatter one lane and chunks append one
    position per step genuinely in place instead of copying every
    bank every dispatch.
    """

    def __init__(self, model, params, num_slots, max_new_tokens, *,
                 cache_len=None, chunk_size=16, pad_multiple=64,
                 temperature=0.0, top_k=0, top_p=0.0, eos_id=None,
                 seed=0, prefix_cache=None, draft_model=None,
                 draft_params=None, draft_len=4,
                 kv_layout="contiguous", kv_pages=None, page_tokens=None,
                 paged_impl="kernel", mesh=None):
        import numpy as np

        from tensorflowonspark_tpu import quantize as qz

        # TP plane (docs/serving.md "Disaggregated prefill/decode & TP
        # sharding"): with a mesh, weights shard over the `model` axis
        # per RULES_TP and the KV banks/pools shard on their kv-head
        # dim; the jitted programs are unchanged — GSPMD partitions
        # them from the committed input shardings (the multichip
        # dryruns prove this token-exact for generate()).
        self.mesh = mesh
        if mesh is not None:
            from tensorflowonspark_tpu.parallel import mesh as pmesh

            self.tp_degree = int(
                pmesh.mesh_axis_size(mesh, pmesh.AXIS_TENSOR)
            )
        else:
            self.tp_degree = 1
        self.kv_layout = str(kv_layout)
        if self.kv_layout not in ("contiguous", "paged"):
            raise ValueError(
                "kv_layout must be 'contiguous' or 'paged', got "
                "{0!r}".format(kv_layout)
            )
        if model.cfg.kv_layout == "paged" and self.kv_layout != "paged":
            raise ValueError(
                "model is configured kv_layout='paged' but the decoder "
                "was asked for 'contiguous'; pass kv_layout='paged'"
            )
        self._paged = self.kv_layout == "paged"
        #: latent attention: the banks are latent rows and index keys
        #: (models/mla.py).  Built for the contiguous layout and whole
        #: prefills; what is left says so here, by name
        self._latent = model.cfg.attention_kind == "mla"
        if self._latent and (
                self._paged or prefix_cache is not None
                or draft_model is not None or mesh is not None):
            raise ValueError(
                "attention_kind='mla' serves from contiguous latent "
                "banks on one device: paged latent pages, prefix reuse "
                "(a suffix prefill over cached latent rows), "
                "draft-model speculation and a TP mesh are not built")
        #: sparse layers whose routing the chunk program counts
        self._moe_layers = sum(
            model.cfg.ffn_kind(i) == "sigmoid_moe"
            for i in range(model.cfg.num_layers))
        #: MoE counts of the last resolved chunk (None without such
        #: layers): assignments, those to held experts, and held
        #: experts hit, summed over the chunk's steps and layers
        self.last_chunk_counts = None
        self.model = model
        self.num_slots = int(num_slots)
        self.max_new_tokens = int(max_new_tokens)
        self.chunk_size = max(1, min(int(chunk_size), self.max_new_tokens))
        self.pad_multiple = max(1, int(pad_multiple))
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.eos_id = None if eos_id is None else int(eos_id)
        cap = model.cfg.max_seq_len if cache_len is None else int(cache_len)
        self.cache_len = min(cap, model.cfg.max_seq_len)
        if self.cache_len <= self.max_new_tokens:
            raise ValueError(
                "cache_len ({0}) must exceed max_new_tokens ({1}) to "
                "hold any prompt at all".format(
                    self.cache_len, self.max_new_tokens
                )
            )
        self.prefix_cache = prefix_cache
        self._use_prefix = prefix_cache is not None
        self.draft_model = draft_model
        self.draft_len = int(draft_len)
        self._spec = draft_model is not None
        if mesh is not None and self._spec:
            raise ValueError(
                "TP-sharded SlotDecoder does not compose with "
                "draft-model speculation yet (the draft's contiguous "
                "banks would need their own sharding story); drop "
                "draft_model or the mesh"
            )
        if self._spec:
            if self.temperature > 0:
                raise ValueError(
                    "draft-model speculative decoding is greedy-only "
                    "(temperature must be 0)"
                )
            if self.draft_len < 1:
                raise ValueError("draft_len must be >= 1")
            if draft_params is None:
                raise ValueError("draft_model needs draft_params")
            if draft_model.cfg.vocab_size != model.cfg.vocab_size:
                raise ValueError(
                    "draft and flagship models must share a "
                    "vocabulary; got {0} vs {1}".format(
                        draft_model.cfg.vocab_size, model.cfg.vocab_size
                    )
                )
        # bank slack past cache_len: a verify round writes the whole
        # [last, drafts] block at the current pointer, so the banks
        # keep draft_len+1 scratch positions the admission bound
        # (prompt + max_new <= cache_len) never hands out
        self._bank_len = self.cache_len + (
            self.draft_len + 1 if self._spec else 0
        )
        if self._latent:
            # whole blocks for the latent decode kernel; the admission
            # bound stays cache_len
            self._bank_len = -(-self._bank_len // 128) * 128
        if self._paged:
            if paged_impl not in ("kernel", "gather"):
                raise ValueError(
                    "paged_impl must be 'kernel' or 'gather', got "
                    "{0!r}".format(paged_impl)
                )
            if mesh is not None and paged_impl == "kernel":
                raise ValueError(
                    "paged_impl='kernel' does not compose with a TP "
                    "mesh: pallas calls are not partitioned by GSPMD, "
                    "so the kernel would see per-shard pools with "
                    "global tables; use paged_impl='gather' (the "
                    "XLA-native path — serving_builder defaults to it "
                    "under tp/mesh_shape)"
                )
            self.paged_impl = str(paged_impl)
            self._setup_paged(model, kv_pages, page_tokens, np)
        else:
            self.page_pool = None
            self.tables = None
            if mesh is not None and model.cfg.mesh is None:
                # the model reads the mesh from its own config when it
                # picks the decode attention (decode_bank_block): a
                # Pallas call is not partitioned by GSPMD
                import dataclasses as _dc

                self.model = Transformer(_dc.replace(model.cfg, mesh=mesh))
            if not (self._latent or self._use_prefix or self._spec
                    or mesh is not None):
                # every span these banks see is a fresh prompt: a
                # windowed layer may keep a ring, a long prompt may go
                # through flash (TransformerConfig.fresh_prompts)
                import dataclasses as _dc

                self.model = Transformer(
                    _dc.replace(model.cfg, fresh_prompts=True))
        #: rows of each layer's bank: a ring where the layer's window
        #: makes one shorter than ``_bank_len`` (the paged pool and the
        #: latent banks are not per-layer: ``_bank_len`` throughout)
        self._layer_rows = [
            bank_rows(self.model.cfg, i, self._bank_len)
            for i in range(self.model.cfg.num_layers)]
        #: what the decode chunk's flagship attention was built with:
        #: "kernel" (block-walking, reads live blocks only), "dot"
        #: (masked einsums over the whole span; a speculative chunk's
        #: verify block is a multi-token span, so always this) or
        #: "latent" (absorbed MLA under the index's selection, through
        #: the latent decode kernel over each slot's live blocks where
        #: ``_kv_block`` is set, else over the whole bank)
        if self._spec:
            self._kv_block = None
        elif self._latent:
            from tensorflowonspark_tpu.models.mla import decode_block

            self._kv_block = decode_block(self.model.cfg, self._bank_len)
        elif self._paged:
            self._kv_block = (
                self._page_tokens if self.paged_impl == "kernel" else None
            )
        else:
            self._kv_block = decode_bank_block(
                self.model.cfg, self._bank_len
            )
        #: tokens a copy of the decode kernel reads of each layer's
        #: bank (a ring's length has its own), None where that layer's
        #: step reads the bank whole
        self._layer_blocks = [
            self._kv_block if rows == self._bank_len
            else decode_bank_block(self.model.cfg, rows)
            for rows in self._layer_rows]
        self.attn_impl = (
            "latent" if self._latent
            else "kernel" if all(self._layer_blocks)
            else "dot" if not any(self._layer_blocks) else "mixed")
        self._np = np
        self._qz = qz
        self._rng = jax.random.PRNGKey(int(seed))
        self._n_keys = 0  # admissions + chunks, folds the rng stream
        self._quantized = qz.is_quantized(params)
        if mesh is not None and self._quantized:
            raise ValueError(
                "TP-sharded SlotDecoder needs float weights (the "
                "quantized trees' packed codes + per-group scales "
                "have no RULES_TP annotations yet); pass "
                "weights='float' or drop the mesh"
            )
        #: weight scheme ("int8" | "int4" | None) — hot-swap ingest
        #: re-quantizes with the SAME scheme the live decoder serves
        self._wq = qz.quantization_of(params)
        # unsharded decoders COMMIT their weights to the device they
        # are built for (the ambient default device — a fleet replica
        # builds under jax.default_device(its chip)): arrays left where
        # the caller made them (device 0) would be re-copied to the
        # replica's chip by every dispatch
        self._home = None if mesh is not None else _default_device()
        self._qparams = self._place(jax.tree.map(jnp.asarray, params))
        # prefill is compute-bound: dequantize once, no barrier (the
        # same trade generate() makes); the chunk path re-dequantizes
        # per step under a barrier so weights cross HBM as int8
        self._params = (
            qz.dequantize_tree(self._qparams, model.cfg.jdtype,
                               barrier=False)
            if self._quantized else self._qparams
        )
        if mesh is not None:
            self._params = self._shard_params(self._params, mesh)
            self._qparams = self._params
        # live-swap plane (hot_swap.py / docs/serving.md "Live weight
        # swap & rollback"): params are deliberately NOT donated
        # through the jitted programs (only cache/state are), so the
        # previous generation's buffers stay resident for rollback;
        # each install bumps this tag and the serving engine exports
        # it as the serving.weight_generation gauge
        self.weight_generation = 0
        self._canary_jit = None
        # self.model, not model: the paged layout rebuilt it with the
        # pool geometry in its config (same params)
        self.cache = self._place(init_cache(
            self.model, self.num_slots, cache_len=self._bank_len
        ))
        if mesh is not None:
            self.cache = self._shard_cache(self.cache, mesh)
        if self._spec:
            # the draft's own slot-table banks, at the SAME canonical
            # per-slot positions as the flagship's (one admit prefills
            # both in one compiled program); draft weights are small —
            # dequantize once if quantized, no per-step barrier
            self._dparams = self._place(
                jax.tree.map(jnp.asarray, draft_params)
            )
            if qz.is_quantized(self._dparams):
                self._dparams = qz.dequantize_tree(
                    self._dparams, draft_model.cfg.jdtype, barrier=False
                )
            self.draft_cache = self._place(init_cache(
                draft_model, self.num_slots, cache_len=self._bank_len
            ))
        else:
            self._dparams = None
            self.draft_cache = None
        # host-side accept accounting (resolved with each chunk block)
        self.spec_accepted = 0
        self.spec_proposed = 0
        self.state = self._idle_state()
        self.active = np.zeros((self.num_slots,), bool)
        # the cache/state buffers are linear: every program consumes
        # the previous value and the handle is immediately reassigned,
        # so DONATE them — XLA then updates the multi-MB banks in
        # place (admit scatters one lane, a chunk appends one position
        # per step) instead of copying every bank every dispatch
        self._prefill_jit = jax.jit(
            self._prefill_impl, donate_argnums=(2, 3, 4)
        )
        self._chunk_jit = jax.jit(
            self._chunk_spec_impl, donate_argnums=(2, 3, 4)
        ) if self._spec else jax.jit(
            self._chunk_impl, donate_argnums=(1, 2)
        )
        if self._paged:
            # the ONE admit program of the paged plane: cached pages
            # arrive as table indices (host bookkeeping, no install
            # dispatch) and the prompt's new pages are committed by the
            # prefill's own pool writes (no extract dispatch) — a
            # cached admit is a single fused dispatch
            self._prefill_paged_jit = jax.jit(
                self._prefill_paged_impl, donate_argnums=(2, 3, 4)
            )
            # disaggregated handoff (serving_disagg.PrefillWorker →
            # :meth:`adopt`): the decode-side half is a pure
            # [num_slots] state-vector scatter — donated, one
            # dispatch, never touches a KV bank
            self._adopt_jit = jax.jit(
                self._adopt_impl, donate_argnums=(0,)
            )
        elif self._use_prefix:
            self._prefill_canonical_jit = jax.jit(
                self._prefill_canonical_impl, donate_argnums=(2, 3, 4)
            )
            self._install_jit = jax.jit(
                self._install_segment_impl, donate_argnums=(0,)
            )
            # extract only READS the banks — nothing to donate
            self._extract_jit = jax.jit(
                self._extract_segment_impl, static_argnums=(3,)
            )

    def _setup_paged(self, model, kv_pages, page_tokens, np):
        """Build the paged-KV plane: pick the page geometry, size and
        allocate the :class:`~tensorflowonspark_tpu.prefix_cache.
        PagePool`, wire the radix cache (when attached) as the pool's
        eviction client, and rebuild the model with the pool geometry
        in its config (same params — the config only selects the cache
        layout; see docs/serving.md "Paged KV & int4")."""
        import dataclasses as _dc

        from tensorflowonspark_tpu.prefix_cache import PagePool

        cfg = model.cfg
        pc = self.prefix_cache
        t = int(page_tokens) if page_tokens else (
            pc.block_tokens if pc is not None else 16
        )
        if pc is not None and pc.block_tokens != t:
            raise ValueError(
                "paged layout needs page_tokens == the prefix cache's "
                "block_tokens; got {0} vs {1}".format(t, pc.block_tokens)
            )
        self._page_tokens = t
        span = -(-self._bank_len // t)  # blocks per slot table
        self._blocks_per_slot = span
        hkv = cfg.num_kv_heads or cfg.num_heads
        int8_cache = cfg.cache_dtype == "int8"
        itemsize = 1 if int8_cache else jnp.dtype(cfg.dtype).itemsize
        per_layer = 2 * t * hkv * cfg.head_dim * itemsize
        if int8_cache:
            per_layer += 2 * t * hkv * 4  # f32 scale pages
        #: device bytes one logical page costs across every layer's
        #: pools — what the radix cache's byte budget accounts per block
        self._page_nbytes = max(1, cfg.num_layers * per_layer)
        if kv_pages:
            num_pages = int(kv_pages)
        else:
            # every slot can always hold its full table span; shared
            # (radix-committed) pages ride in the extra headroom, capped
            # by the cache's byte budget so prefix_mem_mb keeps meaning
            # POOL sizing here (docs/serving.md "Paged KV & int4") —
            # bounded so a generous default budget doesn't preallocate
            # hundreds of MB the workload never touches
            extra = 0
            if pc is not None:
                budget_pages = pc.mem_budget_bytes // self._page_nbytes
                extra = int(min(
                    budget_pages, max(2 * self.num_slots * span, 64)
                ))
            num_pages = self.num_slots * span + extra + 1
        min_pages = self.num_slots * span + 1
        if num_pages < min_pages:
            raise ValueError(
                "kv_pages={0} cannot hold {1} slots x {2} blocks (+1 "
                "reserved trash page); need >= {3}".format(
                    num_pages, self.num_slots, span, min_pages
                )
            )
        self.page_pool = PagePool(num_pages, reserved=1)
        if pc is not None:
            # ONE pool per radix cache: page-index payloads are only
            # meaningful against the pool that allocated them
            owner = getattr(pc, "_paged_pool", None)
            if owner is not None and owner is not self.page_pool:
                raise ValueError(
                    "this PrefixCache is already bound to another "
                    "decoder's page pool; paged decoders need their "
                    "own radix cache (serving_builder builds one per "
                    "slot geometry)"
                )
            if len(pc):
                raise ValueError(
                    "paged layout needs an EMPTY PrefixCache at attach "
                    "(its payloads become page indices); got {0} "
                    "node(s)".format(len(pc))
                )
            pc._paged_pool = self.page_pool
            pool = self.page_pool
            pc._release_fn = lambda page: pool.release([page])
        # per-slot block tables (host mirror; shipped as one small
        # int32 array per dispatch) + the pages each slot holds.  All
        # rows start at the reserved trash page.
        self.tables = np.zeros((self.num_slots, span), np.int32)
        self._slot_pages = [[] for _ in range(self.num_slots)]
        self.model = Transformer(_dc.replace(
            cfg, kv_layout="paged", kv_pages=num_pages,
            kv_page_tokens=t, kv_slot_blocks=span,
            kv_span=self._bank_len, paged_decode_impl=self.paged_impl,
        ))

    def _place(self, tree):
        """Commit long-lived buffers (weights, KV banks, slot state) to
        this decoder's home device — explicitly, because they are also
        (re)built from threads that are not under the builder's
        ``jax.default_device`` (``reset`` between jobs, a quarantine
        rebuild).  Mesh decoders place through ``_shard_*`` instead."""
        if self._home is None:
            return tree
        return jax.device_put(tree, self._home)

    def _idle_state(self):
        b = self.num_slots
        return self._place({
            "positions": jnp.zeros((b,), jnp.int32),
            # idle slots mask everything but self: pad_start=cache_len
            "pad_start": jnp.full((b,), self.cache_len, jnp.int32),
            "last_tok": jnp.zeros((b,), jnp.int32),
            "done": jnp.ones((b,), jnp.bool_),
        })

    # -- compiled programs ---------------------------------------------

    def _sample(self, logits, key):
        return sample_logits(
            logits, key, temperature=self.temperature,
            top_k=self.top_k, top_p=self.top_p,
        )

    @staticmethod
    def _lane_of(cache, slot):
        """Slice lane ``slot`` out of every cache bank — ``[B, L, H,
        Dx]`` keys and values, ``[B, L, width]`` latent rows and index
        keys — (the shared position counter resets to 0: slot mode
        ignores it)."""
        def _lane(leaf):
            if getattr(leaf, "ndim", 0) >= 3:  # [B, L, ...] banks
                return jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=0)
            return jnp.zeros((), jnp.int32)

        return jax.tree.map(_lane, cache)

    @staticmethod
    def _merge_lane(cache, lane, slot):
        def _merge(full, lane_leaf):
            if getattr(full, "ndim", 0) >= 3:
                return jax.lax.dynamic_update_slice_in_dim(
                    full, lane_leaf.astype(full.dtype), slot, axis=0
                )
            return full  # shared position counter: slot mode ignores it

        return jax.tree.map(_merge, cache, lane)

    def _shard_params(self, params, mesh):
        """Commit the weights to ``mesh`` under the canonical TP rules
        (``parallel.sharding.RULES_TP`` through this model's
        :func:`logical_axes` annotations — attention heads, mlp and
        vocab dims split over the ``model`` axis; dims the mesh width
        does not divide stay replicated, ``apply_rules``'s shape-aware
        dropping).  The committed placements are what GSPMD propagates
        through the unchanged jitted programs."""
        from jax.sharding import NamedSharding

        from tensorflowonspark_tpu.parallel import sharding as sh

        specs = sh.param_specs(
            params, sh.RULES_TP, mesh=mesh,
            annotations=logical_axes(params),
        )
        return jax.tree.map(
            lambda p, s: jax.device_put(p, NamedSharding(mesh, s)),
            params, specs,
        )

    def _shard_cache(self, cache, mesh):
        """Commit the KV banks/pools to ``mesh``: every 4-dim leaf —
        contiguous ``[B, L, Hkv, D]`` banks and paged ``[P, T, Hkv,
        Dx]`` pools (scale pools included) — splits its kv-head dim
        over the ``model`` axis, matching the head sharding of the
        projections that write it; leaves whose head count the axis
        does not divide (and the scalar counters) replicate."""
        from jax.sharding import NamedSharding, PartitionSpec

        from tensorflowonspark_tpu.parallel.mesh import AXIS_TENSOR

        size = mesh.shape.get(AXIS_TENSOR, 1)

        def _place(leaf):
            shape = getattr(leaf, "shape", ())
            if (len(shape) == 4 and size > 1
                    and shape[2] % size == 0):
                spec = PartitionSpec(None, None, AXIS_TENSOR, None)
            else:
                spec = PartitionSpec()
            return jax.device_put(leaf, NamedSharding(mesh, spec))

        return jax.tree.map(_place, cache)

    def _prefill_impl(self, params, dparams, cache, dcache, state, slot,
                      tokens, pad, key):
        """Slot-scoped prefill: lane ``slot`` of every cache bank gets
        the bucketed prompt's KV, and the slot's state-vector entries
        (position, pad region, first token, eos flag) are scattered in
        place.  All shapes static per prompt bucket; ``slot`` is
        traced (no recompilation on admit).  With a draft model, the
        SAME program prefills the draft's lane on the same padded
        tokens — one dispatch, both banks, identical positions."""
        lane = self._lane_of(cache, slot)
        logits, mut = self.model.apply(
            {"params": params, "cache": lane}, tokens, decode=True,
            mutable=["cache"], pad_start=pad, last_only=True,
        )
        cache = self._merge_lane(cache, mut["cache"], slot)
        if self._spec:
            dlane = self._lane_of(dcache, slot)
            _, dmut = self.draft_model.apply(
                {"params": dparams, "cache": dlane}, tokens,
                decode=True, mutable=["cache"], pad_start=pad,
                last_only=True,
            )
            dcache = self._merge_lane(dcache, dmut["cache"], slot)
        first = self._sample(logits[:, -1], key)[0]
        state = {
            "positions": state["positions"].at[slot].set(tokens.shape[1]),
            "pad_start": state["pad_start"].at[slot].set(pad[0]),
            "last_tok": state["last_tok"].at[slot].set(first),
            "done": state["done"].at[slot].set(
                first == self.eos_id if self.eos_id is not None
                else False
            ),
        }
        return cache, dcache, state, first

    def _prefill_canonical_impl(self, params, dparams, cache, dcache,
                                state, slot, suffix, full, n, kpref, key):
        """Cached-prefix prefill at CANONICAL positions (token ``i`` of
        the prompt at cache position ``i`` — the layout the prefix
        cache's committed blocks are stored in, see
        :mod:`tensorflowonspark_tpu.prefix_cache`).

        The first ``kpref`` positions of the lane already hold the
        cached prefix KV (installed by :meth:`admit` before this
        dispatch); ``suffix`` is the uncached tail right-padded to its
        own bucket, prefilled as a multi-token decode step starting at
        position ``kpref`` (per-slot positions thread the same
        causal/window masking a chunked decode uses, so pad-tail query
        rows write scratch KV past ``n`` that the causal mask hides
        and decode overwrites).  The first token samples from the last
        REAL suffix row, ``n - kpref - 1``.  ``slot``, ``n`` and
        ``kpref`` are traced — one compiled program per suffix bucket,
        shared by hits of every depth including misses (kpref=0)."""
        lane = self._lane_of(cache, slot)
        logits, mut = self.model.apply(
            {"params": params, "cache": lane}, suffix, decode=True,
            mutable=["cache"], pad_start=jnp.zeros((1,), jnp.int32),
            slot_positions=kpref[None],
        )
        cache = self._merge_lane(cache, mut["cache"], slot)
        if self._spec:
            # the draft re-prefills the WHOLE prompt (its banks are not
            # prefix-cached; a stale-prefix draft would only cost
            # accept rate, but a cheap full prefill keeps it sharp)
            dlane = self._lane_of(dcache, slot)
            _, dmut = self.draft_model.apply(
                {"params": dparams, "cache": dlane}, full,
                decode=True, mutable=["cache"],
                pad_start=jnp.zeros((1,), jnp.int32),
                slot_positions=jnp.zeros((1,), jnp.int32),
            )
            dcache = self._merge_lane(dcache, dmut["cache"], slot)
        row = jax.lax.dynamic_slice_in_dim(
            logits, n - kpref - 1, 1, axis=1
        )[:, 0]
        first = self._sample(row, key)[0]
        state = {
            "positions": state["positions"].at[slot].set(n),
            "pad_start": state["pad_start"].at[slot].set(0),
            "last_tok": state["last_tok"].at[slot].set(first),
            "done": state["done"].at[slot].set(
                first == self.eos_id if self.eos_id is not None
                else False
            ),
        }
        return cache, dcache, state, first

    def _prefill_paged_impl(self, params, dparams, cache, dcache, state,
                            slot, suffix, full, n, kpref, tables, key):
        """Paged-KV canonical prefill — the ONE dispatch of a paged
        admit.  The cached prefix needs no install (the slot's block
        table already references the shared physical pages — host
        bookkeeping); the uncached ``suffix`` prefills at canonical
        positions WRITING STRAIGHT INTO THE POOL through the slot's
        table row, which also commits the prompt's new full blocks in
        place (no extract dispatch — the pages ARE the cache payload).
        ``slot``/``n``/``kpref`` are traced: one compiled program per
        suffix bucket, shared by hits of every depth."""
        trow = jax.lax.dynamic_slice_in_dim(tables, slot, 1, axis=0)
        logits, mut = self.model.apply(
            {"params": params, "cache": cache}, suffix, decode=True,
            mutable=["cache"], slot_positions=kpref[None],
            block_tables=trow,
        )
        cache = mut["cache"]
        if self._spec:
            # the draft keeps CONTIGUOUS per-slot banks (its cache is
            # slot-private — nothing to share) and re-prefills the
            # whole prompt, exactly like the contiguous canonical path
            dlane = self._lane_of(dcache, slot)
            _, dmut = self.draft_model.apply(
                {"params": dparams, "cache": dlane}, full,
                decode=True, mutable=["cache"],
                pad_start=jnp.zeros((1,), jnp.int32),
                slot_positions=jnp.zeros((1,), jnp.int32),
            )
            dcache = self._merge_lane(dcache, dmut["cache"], slot)
        row = jax.lax.dynamic_slice_in_dim(
            logits, n - kpref - 1, 1, axis=1
        )[:, 0]
        first = self._sample(row, key)[0]
        state = {
            "positions": state["positions"].at[slot].set(n),
            "pad_start": state["pad_start"].at[slot].set(0),
            "last_tok": state["last_tok"].at[slot].set(first),
            "done": state["done"].at[slot].set(
                first == self.eos_id if self.eos_id is not None
                else False
            ),
        }
        return cache, dcache, state, first

    def _adopt_impl(self, state, slot, n, first):
        """Decode-side half of a disaggregated prefill→decode handoff
        (:meth:`adopt`): scatter the request's entries into the
        ``[num_slots]`` state vectors — position ``n``, canonical pad
        (0), the prefill program's first token, the eos flag.  This
        program NEVER takes a KV bank operand: the prefill worker
        already wrote the KV into shared pool pages, and the decode
        side adopts them as table indices (host bookkeeping), which is
        what makes the handoff zero-copy across programs."""
        return {
            "positions": state["positions"].at[slot].set(n),
            "pad_start": state["pad_start"].at[slot].set(0),
            "last_tok": state["last_tok"].at[slot].set(first),
            "done": state["done"].at[slot].set(
                first == self.eos_id if self.eos_id is not None
                else False
            ),
        }

    def _install_segment_impl(self, cache, slot, segment):
        """Write a cached-prefix segment (per-bank ``[L_seg, ...]``
        leaves, flattened bank order) into lane ``slot`` at positions
        ``[0, L_seg)`` — prefix blocks always sit at canonical
        offset 0.  One dispatch per admit hit."""
        flat, treedef = jax.tree_util.tree_flatten(cache)
        it = iter(segment)
        out = []
        for leaf in flat:
            if getattr(leaf, "ndim", 0) >= 3:
                seg = next(it)
                out.append(jax.lax.dynamic_update_slice(
                    leaf, seg[None].astype(leaf.dtype),
                    (slot,) + (0,) * (leaf.ndim - 1),
                ))
            else:
                out.append(leaf)
        return jax.tree_util.tree_unflatten(treedef, out)

    def _extract_segment_impl(self, cache, slot, start, length):
        """Read ``[start, start+length)`` of lane ``slot`` from every
        bank (flattened order, matching :meth:`_install_segment_impl`)
        — the committed KV a finished prefill donates to the prefix
        cache.  ``length`` is static (it keys the program)."""
        flat, _ = jax.tree_util.tree_flatten(cache)
        out = []
        for leaf in flat:
            if getattr(leaf, "ndim", 0) >= 3:
                lane = jax.lax.dynamic_slice_in_dim(
                    leaf, slot, 1, axis=0
                )[0]
                out.append(jax.lax.dynamic_slice_in_dim(
                    lane, start, length, axis=0
                ))
        return tuple(out)

    def _chunk_impl(self, params, cache, state, active, tables, keys):
        """``chunk_size`` single-token decode steps over all slots with
        per-slot positions; done rows keep emitting ``eos_id`` (the
        static scan's contract), idle rows hold their pointer.  On the
        paged layout ``tables`` carries the per-slot block tables (the
        pool pages are pre-allocated for the whole span, so the scan
        never allocates — one fused dispatch per chunk either way)."""
        # a lane no request holds (evicted, never admitted) sees itself
        # alone, whatever span its last request left behind: its
        # output is thrown away, so its keys are not worth reading
        pad_start = jnp.where(
            active, state["pad_start"], jnp.int32(self.cache_len)
        )

        count_moe = self._moe_layers > 0
        cfg = self.model.cfg

        def step(carry, key):
            cache, pos, tok, done = carry
            p = (
                self._qz.dequantize_tree(
                    params, self.model.cfg.jdtype, barrier=True
                )
                if self._quantized else params
            )
            logits, mut = self.model.apply(
                {"params": p, "cache": cache}, tok[:, None], decode=True,
                mutable=["cache", "moe_stats"] if count_moe else ["cache"],
                pad_start=pad_start,
                slot_positions=pos, block_tables=tables,
            )
            counts = None
            if count_moe:
                # what the rows requests hold asked of the experts held
                # here: [layers, slots, held] -> three integers
                chose = jnp.stack([
                    c for c in jax.tree.leaves(mut["moe_stats"])
                ]).astype(jnp.int32) * active[None, :, None]
                counts = jnp.stack([
                    jnp.sum(active) * (cfg.expert_k * self._moe_layers),
                    jnp.sum(chose),
                    jnp.sum(jnp.any(chose > 0, axis=1)),
                ]).astype(jnp.int32)
            nxt = self._sample(logits[:, 0], key)
            if self.eos_id is not None:
                nxt = jnp.where(done, jnp.int32(self.eos_id), nxt)
                done = jnp.logical_or(done, nxt == self.eos_id)
            # active rows advance (clamped: a completed-but-not-yet-
            # evicted row must not run its pointer off the cache); idle
            # rows hold still
            pos = jnp.where(
                active, jnp.minimum(pos + 1, self.cache_len - 1), pos
            )
            return (mut["cache"], pos, nxt, done), (nxt, counts)

        (cache, positions, last_tok, done), (toks, counts) = jax.lax.scan(
            step,
            (cache, state["positions"], state["last_tok"], state["done"]),
            keys,
        )
        state = dict(state, positions=positions, last_tok=last_tok,
                     done=done)
        toks = jnp.swapaxes(toks, 0, 1)
        if count_moe:
            # the three integers ride back beside the tokens
            return cache, state, (toks, jnp.sum(counts, axis=0))
        return cache, state, toks

    def _chunk_spec_impl(self, params, dparams, cache, dcache, state,
                         active, tables, keys):
        """``chunk_size`` SPECULATIVE rounds over all slots: per round
        the draft model proposes ``draft_len`` tokens per slot (its own
        per-slot cache, one extra step to bank the final proposal's
        KV), the flagship verifies all of them in ONE batched
        ``draft_len+1``-token step, and each slot accepts
        INDEPENDENTLY (no lockstep minimum — per-slot positions make
        the batch rows autonomous, which is exactly what the shared
        write pointer forbids in :func:`generate_speculative`).

        Accepted tokens compact left into a per-slot output buffer
        (``buf``) with per-slot valid counts (``off``); rejected-tail
        KV beyond each slot's pointer is causally masked and
        overwritten by the next round's writes, the same stale-entry
        contract the static speculative path relies on.  Greedy only
        (enforced at construction).  Also returns per-slot
        accepted/proposed draft counters for the engine's accept-rate
        stats."""
        kd = self.draft_len
        eos = self.eos_id

        def round_(carry, _key):
            cache, dcache, pos, tok, done, buf, off, acc, prop = carry
            p = (
                self._qz.dequantize_tree(
                    params, self.model.cfg.jdtype, barrier=True
                )
                if self._quantized else params
            )

            def dstep(c, i):
                dc, t = c
                dlogits, dmut = self.draft_model.apply(
                    {"params": dparams, "cache": dc}, t[:, None],
                    decode=True, mutable=["cache"],
                    pad_start=state["pad_start"], slot_positions=pos + i,
                )
                nxt = jnp.argmax(
                    dlogits[:, 0], axis=-1
                ).astype(jnp.int32)
                return (dmut["cache"], nxt), nxt

            # kd+1 draft steps: kd proposals + one feed of the final
            # proposal so its KV is banked (a hole there would poison
            # every later round once the pointer moves past it)
            (dcache, _), douts = jax.lax.scan(
                dstep, (dcache, tok), jnp.arange(kd + 1)
            )
            drafts = jnp.swapaxes(douts, 0, 1)[:, :kd]  # [B, kd]
            block = jnp.concatenate([tok[:, None], drafts], axis=1)
            logits, mut = self.model.apply(
                {"params": p, "cache": cache}, block, decode=True,
                mutable=["cache"], pad_start=state["pad_start"],
                slot_positions=pos, block_tables=tables,
            )
            targets = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            ok = drafts == targets[:, :kd]
            m = jnp.sum(
                jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1
            )  # [B] — per-slot acceptance
            gained = m + 1
            out_block = targets
            if eos is not None:
                iseos = out_block == eos
                first_eos = jnp.where(
                    iseos.any(axis=1), iseos.argmax(axis=1),
                    jnp.int32(kd + 1),
                )
                newly_done = first_eos < gained
                gained = jnp.minimum(gained, first_eos + 1)
                # already-done rows keep emitting a full eos block (the
                # static scan's contract); the scheduler reads none of it
                out_block = jnp.where(
                    done[:, None], jnp.int32(eos), out_block
                )
                new_done = jnp.logical_or(done, newly_done)
            else:
                new_done = done
            gained = jnp.where(done, jnp.int32(kd + 1), gained)
            alive = jnp.logical_and(active, jnp.logical_not(done))
            acc = acc + jnp.where(alive, m, 0)
            prop = prop + jnp.where(alive, jnp.int32(kd), 0)
            buf = jax.vmap(
                lambda b_r, v_r, o_r: jax.lax.dynamic_update_slice(
                    b_r, v_r, (o_r,)
                )
            )(buf, out_block, off)
            off = off + gained
            last = jnp.take_along_axis(
                out_block, (gained - 1)[:, None], axis=1
            )[:, 0]
            pos = jnp.where(
                active,
                jnp.minimum(pos + gained, self.cache_len - 1), pos,
            )
            return (mut["cache"], dcache, pos, last, new_done, buf,
                    off, acc, prop), None

        b = self.num_slots
        cap = self.chunk_size * (kd + 1)
        buf0 = jnp.zeros((b, cap), jnp.int32)
        zero = jnp.zeros((b,), jnp.int32)
        (cache, dcache, positions, last_tok, done, buf, off, acc,
         prop), _ = jax.lax.scan(
            round_,
            (cache, dcache, state["positions"], state["last_tok"],
             state["done"], buf0, zero, zero, zero),
            keys,
        )
        state = dict(state, positions=positions, last_tok=last_tok,
                     done=done)
        return cache, dcache, state, buf, off, acc, prop

    # -- host-side slot operations -------------------------------------

    def _next_key(self, n=None):
        """One fresh key (``n=None``) or a ``[n, 2]`` stack (scan xs —
        ``n=1`` still stacks, so chunk_size=1 scans one step, not two
        key halves)."""
        key = jax.random.fold_in(self._rng, self._n_keys)
        self._n_keys += 1
        return key if n is None else jax.random.split(key, n)

    def bucket_len(self, prompt_len):
        """Prompt-length bucket: round up to ``pad_multiple``, capped
        so the bucket + max_new_tokens still fits the cache (the
        static path's pad_cap rule)."""
        m = self.pad_multiple
        b = ((int(prompt_len) + m - 1) // m) * m
        return max(int(prompt_len), min(b, self.cache_len
                                        - self.max_new_tokens))

    def prefill_attn(self, bucket):
        """What the prefill program of a ``bucket``-token prompt
        attends with, for the engine's ``prefill`` span (the decode
        chunk's is ``attn_impl``): ``"latent_span_kernel"`` where a
        latent span goes through the span kernel and ``"einsum"``
        where it keeps its einsums (``mla.span_blocks`` decides, the
        function the model itself asks); over K/V banks a span is
        ``"flash"`` where :func:`prefill_flash` sends a fresh prompt
        through the flash forward kernel and else ``"dot"`` (masked
        dot attention, over the bank or over the prompt's own keys),
        over pages ``"gather"``."""
        if self._latent:
            from tensorflowonspark_tpu.models.mla import span_blocks

            return ("latent_span_kernel"
                    if span_blocks(self.model.cfg, True, int(bucket))
                    else "einsum")
        if prefill_flash(self.model.cfg, int(bucket)):
            return "flash"
        return "gather" if self._paged else "dot"

    def _suffix_bucket(self, suffix_len, kpref):
        """Suffix-prefill bucket for a cached-prefix admit: round the
        uncached tail up to ``pad_multiple``, capped so the bucketed
        write ``[kpref, kpref + bucket)`` stays inside the banks (the
        scratch tail past the real tokens is causally masked and
        overwritten by decode)."""
        m = self.pad_multiple
        b = ((int(suffix_len) + m - 1) // m) * m
        return max(int(suffix_len), min(b, self._bank_len - int(kpref)))

    def free_slots(self):
        return [i for i in range(self.num_slots) if not self.active[i]]

    def admit(self, slot, prompt):
        """Prefill ``prompt`` (1-D int tokens) into lane ``slot`` and
        activate it.  Returns the first generated token as a DEVICE
        scalar (the request's first output) without synchronizing —
        the scheduler resolves it together with the next chunk's
        block.  Raises when the prompt cannot fit
        ``cache_len - max_new_tokens``.

        With a :class:`~tensorflowonspark_tpu.prefix_cache.PrefixCache`
        attached, admits run at CANONICAL positions: the longest cached
        block-prefix of the prompt is installed into the lane with one
        segment write, only the uncached suffix prefills
        (:meth:`_prefill_canonical_impl`), and the prompt's own full
        blocks are committed back to the cache — so the NEXT request
        sharing the prefix skips its prefill.  All dispatches stay
        async; outputs are token-identical to a cold admit
        (tests/test_prefix_cache.py)."""
        np = self._np
        prompt = np.asarray(prompt, np.int32).ravel()
        n = prompt.shape[0]
        if n == 0:
            raise ValueError("cannot admit an empty prompt")
        if n + self.max_new_tokens > self.cache_len:
            raise ValueError(
                "prompt ({0}) + max_new_tokens ({1}) exceeds the "
                "engine cache_len={2}".format(
                    n, self.max_new_tokens, self.cache_len
                )
            )
        if self.active[slot]:
            raise ValueError("slot {0} is still active".format(slot))
        if self._paged:
            first = self._admit_paged(slot, prompt, n)
        elif self._use_prefix:
            first = self._admit_canonical(slot, prompt, n)
        else:
            self.last_admit_cached_tokens = 0
            self.last_admit_dispatches = 1
            b = self.bucket_len(n)
            padded = np.zeros((1, b), np.int32)
            padded[0, b - n:] = prompt
            (self.cache, self.draft_cache, self.state,
             first) = self._prefill_jit(
                self._params, self._dparams, self.cache,
                self.draft_cache, self.state, jnp.int32(slot),
                jnp.asarray(padded), jnp.asarray([b - n], jnp.int32),
                self._next_key(),
            )
        self.active[slot] = True
        return first

    @staticmethod
    def _assemble_segment(payloads, blk):
        """Materialize a contiguous install segment from block
        payloads.  Payloads are :class:`_BlockRef` VIEWS into donor
        extract-segments (zero-copy at insert time); consecutive
        blocks from the same donor collapse into one slice — the
        common all-one-donor hit path materializes with zero
        dispatches (the donor segment IS the install segment)."""
        runs = []
        for p in payloads:
            if (runs and p.segment is runs[-1][-1].segment
                    and p.index == runs[-1][-1].index + 1):
                runs[-1].append(p)
            else:
                runs.append([p])
        out = []
        for li in range(len(payloads[0].segment)):
            pieces = []
            for run in runs:
                seg = run[0].segment[li]
                s = run[0].index * blk
                e = (run[-1].index + 1) * blk
                pieces.append(
                    seg if (s == 0 and e == seg.shape[0]) else seg[s:e]
                )
            out.append(
                pieces[0] if len(pieces) == 1
                else jnp.concatenate(pieces)
            )
        return tuple(out)

    def _alloc_pages(self, need):
        """``need`` free pages from the pool, evicting the radix
        cache's cold leaf blocks under pool pressure (each eviction
        releases that block's pool reference; a page only actually
        frees once no active slot's table references it)."""
        pool, pc = self.page_pool, self.prefix_cache
        while pool.available() < need:
            if pc is None or not pc.evict_blocks(1):
                lease_fn = getattr(pool, "lease_table", None)
                raise RuntimeError(
                    "page pool exhausted: need {0} pages, {1} free and "
                    "nothing left to evict (pool {2}; {3})".format(
                        need, pool.available(), pool.stats(),
                        lease_fn() if lease_fn is not None
                        else "no lease table",
                    )
                )
        return pool.alloc(need)

    def _admit_paged(self, slot, prompt, n):
        """The paged admit path (see :meth:`admit`): the cached prefix
        installs as PAGE INDICES into the slot's block table — pure
        host bookkeeping, ZERO physical KV copies (the contiguous
        layout's per-admit segment copy is the cost this layout
        exists to delete) — and the suffix prefill writes straight
        into the slot's freshly-allocated private pages, which also
        commits the prompt's new full blocks in place.  One device
        dispatch per admit, cached or cold."""
        np = self._np
        pc, pool = self.prefix_cache, self.page_pool
        blk = self._page_tokens
        if pc is not None:
            # at least one real token must prefill (first-token logits)
            lease = pc.acquire(prompt, limit_tokens=n - 1)
            kpref = lease.n_tokens
            cached_pages = [int(p) for p in lease.payloads()]
        else:
            lease, kpref, cached_pages = None, 0, []
        self.last_admit_cached_tokens = int(kpref)
        self.last_admit_dispatches = 1
        # the slot holds its own reference to every shared page (the
        # radix may evict the block while this slot still decodes on
        # it — the pool refcount keeps the physical page alive)
        pool.retain(cached_pages)
        if lease is not None:
            pc.release(lease)
        private = self._alloc_pages(self._blocks_per_slot
                                    - len(cached_pages))
        row = cached_pages + private
        self.tables[slot] = np.asarray(row, np.int32)
        self._slot_pages[slot] = row
        sb = self._suffix_bucket(n - kpref, kpref)
        suffix = np.zeros((1, sb), np.int32)
        suffix[0, :n - kpref] = prompt[kpref:]
        if self._spec:
            fb = self.bucket_len(n)
            full = np.zeros((1, fb), np.int32)
            full[0, :n] = prompt
            full = jnp.asarray(full)
        else:
            full = None
        (self.cache, self.draft_cache, self.state,
         first) = self._prefill_paged_jit(
            self._params, self._dparams, self.cache, self.draft_cache,
            self.state, jnp.int32(slot), jnp.asarray(suffix), full,
            jnp.int32(n), jnp.int32(kpref), jnp.asarray(self.tables),
            self._next_key(),
        )
        # commit the prompt's NEW full blocks: their pages already hold
        # the KV (the prefill wrote through the table) — recording the
        # indices in the radix IS the commit, zero copies, zero
        # dispatches.  The radix takes its own pool reference per
        # block it accepts (budget drops keep the page slot-private).
        if pc is not None:
            total_blocks = n // blk
            first_new = len(cached_pages)
            if total_blocks > first_new:
                committed = []
                pc.insert(
                    prompt, row[first_new:total_blocks], first_new,
                    self._page_nbytes, on_insert=committed.append,
                )
                pool.retain(committed)
        return first

    def adopt(self, slot, handoff):
        """Adopt a finished disaggregated prefill into lane ``slot``
        (the decode half of :class:`tensorflowonspark_tpu.
        serving_disagg.PrefillWorker`'s handoff protocol).

        ``handoff`` carries the page-index row the prefill program
        wrote the prompt's KV through (``pages``), the prompt length
        (``n_tokens``), the cached-prefix depth (``cached_tokens``)
        and the sampled first token (``first``, an unresolved device
        scalar).  Adoption is a BLOCK-TABLE EXCHANGE: the table row
        and page ownership move by host bookkeeping, and the one
        device dispatch (:meth:`_adopt_impl`) scatters only the
        ``[num_slots]`` state vectors — ``last_adopt_dispatches`` is
        pinned at 1 and no program on this path takes a KV bank
        operand, the zero-copy assertion the disagg tests check."""
        if not self._paged:
            raise ValueError(
                "adopt() needs kv_layout='paged' (the handoff IS a "
                "block-table exchange; contiguous banks would force "
                "a physical KV copy between programs)"
            )
        if self.active[slot]:
            raise ValueError("slot {0} is still active".format(slot))
        row = [int(p) for p in handoff.pages]
        if len(row) != self._blocks_per_slot:
            raise ValueError(
                "handoff row has {0} pages; this decoder's slots span "
                "{1} blocks".format(len(row), self._blocks_per_slot)
            )
        n = int(handoff.n_tokens)
        self.tables[slot] = self._np.asarray(row, self._np.int32)
        self._slot_pages[slot] = row
        self.last_admit_cached_tokens = int(handoff.cached_tokens)
        #: the admit-side program count for this request is the
        #: prefill worker's (1); the adopt itself adds exactly one
        #: state-scatter dispatch and zero KV programs
        self.last_admit_dispatches = 1
        self.last_adopt_dispatches = 1
        self.state = self._adopt_jit(
            self.state, jnp.int32(slot), jnp.int32(n), handoff.first
        )
        end = getattr(self.page_pool, "end_handoff", None)
        if end is not None:
            end(row)
        self.active[slot] = True
        return handoff.first

    def _admit_canonical(self, slot, prompt, n):
        """The cached-prefix admit path (see :meth:`admit`)."""
        np = self._np
        pc = self.prefix_cache
        blk = pc.block_tokens
        # at least one real token must prefill (first-token logits)
        lease = pc.acquire(prompt, limit_tokens=n - 1)
        kpref = lease.n_tokens
        #: telemetry label: how many prompt tokens this admit served
        #: from cache (the serving engine marks prefill spans
        #: prefix_hit with it — docs/observability.md)
        self.last_admit_cached_tokens = int(kpref)
        self.last_admit_dispatches = 1
        if kpref:
            segment = self._assemble_segment(lease.payloads(), blk)
            self.cache = self._install_jit(
                self.cache, jnp.int32(slot), segment
            )
            self.last_admit_dispatches += 1
        # install dispatches hold the block buffers; safe to unpin now
        pc.release(lease)
        sb = self._suffix_bucket(n - kpref, kpref)
        suffix = np.zeros((1, sb), np.int32)
        suffix[0, :n - kpref] = prompt[kpref:]
        if self._spec:
            fb = self.bucket_len(n)
            full = np.zeros((1, fb), np.int32)
            full[0, :n] = prompt
            full = jnp.asarray(full)
        else:
            full = None
        (self.cache, self.draft_cache, self.state,
         first) = self._prefill_canonical_jit(
            self._params, self._dparams, self.cache, self.draft_cache,
            self.state, jnp.int32(slot), jnp.asarray(suffix), full,
            jnp.int32(n), jnp.int32(kpref), self._next_key(),
        )
        # commit the prompt's NEW full blocks (the matched ones are
        # already cached) — ONE async segment read; the per-block
        # payloads are zero-copy views into it (_BlockRef), so insert
        # costs no device dispatches and a donor's whole segment
        # re-installs without re-assembly
        total_blocks = n // blk
        first_new = kpref // blk
        if total_blocks > first_new:
            n_new = total_blocks - first_new
            seg = self._extract_jit(
                self.cache, jnp.int32(slot), jnp.int32(first_new * blk),
                n_new * blk,
            )
            self.last_admit_dispatches += 1
            payloads = [_BlockRef(seg, i) for i in range(n_new)]
            nbytes = sum(int(leaf.nbytes) for leaf in seg) // n_new
            pc.insert(prompt, payloads, first_new, nbytes)
        return first

    def evict(self, slot):
        """Free lane ``slot`` (between chunks) — host bookkeeping
        only.  The lane's stale KV and state entries need no
        scrubbing: a future request's causal mask only ever reaches
        positions its own prefill/decode has re-written, and admit
        rewrites the state entries.  On the paged layout the slot's
        pool references release here (shared pages the radix still
        holds stay resident; the slot's private pages free) and its
        table row parks on the trash page so the lane's dead decode
        writes can never land in a live page."""
        self.active[slot] = False
        if self._paged and self._slot_pages[slot]:
            self.page_pool.release(self._slot_pages[slot])
            self._slot_pages[slot] = []
            self.tables[slot, :] = 0

    def cancel(self, slot):
        """CANCEL an in-flight lane between chunks (deadline expiry,
        client abort): identical to :meth:`evict` — the lane simply
        stops being scheduled, its neighbors keep decoding
        undisturbed, and nothing recompiles (the slot index was
        traced at admit).  A distinct name so the serving engine's
        cancellation contract is explicit and separately testable
        (tests/test_serving_engine.py asserts the compiled-program
        census is unchanged by cancellations)."""
        self.evict(slot)

    def reset(self):
        """Return every slot to idle (between serving jobs).  The
        cache banks stay as-is — stale KV is unreachable, see
        :meth:`evict` — so a reused engine keeps its compiled
        programs AND its device cache allocation (paged: the pool
        array AND the radix's committed pages survive; only the
        slots' own page references release)."""
        if self._paged:
            for slot in range(self.num_slots):
                if self._slot_pages[slot]:
                    self.page_pool.release(self._slot_pages[slot])
                    self._slot_pages[slot] = []
            self.tables[:, :] = 0
        self.state = self._idle_state()
        self.active[:] = False

    # -- live weight swap (hot_swap.py) --------------------------------

    def param_spec(self):
        """Per-leaf ``{path: {"shape", "dtype"}}`` census of the RAW
        ingest contract — what a published checkpoint must look like
        to swap into this decoder (shapes exact; the hot-swap
        validation plane treats dtype as kind-compatible, since
        :meth:`swap_weights` casts to the live dtype / re-quantizes).
        Quantized decoders census at the original float shapes."""
        from tensorflowonspark_tpu.checkpoint import param_manifest

        return param_manifest(self._params)

    def _check_swap_tree(self, raw_params):
        """Raise ``ValueError`` naming the first structural/shape
        incompatibility of ``raw_params`` vs the live weights — a
        mismatched tree silently retraces the jitted programs, so it
        must never reach the install."""
        from tensorflowonspark_tpu.checkpoint import param_manifest

        live = self.param_spec()
        new = param_manifest(raw_params)
        missing = sorted(set(live) - set(new))
        extra = sorted(set(new) - set(live))
        if missing or extra:
            raise ValueError(
                "swap params tree mismatch: missing leaves {0}, "
                "unexpected leaves {1}".format(missing[:4], extra[:4])
            )
        for path in sorted(live):
            if new[path]["shape"] != live[path]["shape"]:
                raise ValueError(
                    "swap params shape mismatch at {0}: live {1} vs "
                    "ingested {2}".format(
                        path, live[path]["shape"], new[path]["shape"]
                    )
                )

    def _ingest_params(self, raw_params):
        """Raw float checkpoint tree -> the ``(qparams, params)`` pair
        the compiled programs consume: re-quantized on ingest for
        quantized deployments, cast to the live dtype otherwise —
        always aval-identical to the previous generation, so the swap
        hits the SAME compiled programs (census-tested)."""
        qz = self._qz
        if self._quantized:
            # re-quantize with the SAME scheme the live decoder serves
            # (int4 deployments must stay int4 — avals would otherwise
            # change and force a retrace)
            qfn = (
                qz.quantize_tree_int4 if self._wq == "int4"
                else qz.quantize_tree
            )
            qparams = qfn(
                self._place(jax.tree.map(jnp.asarray, raw_params))
            )
            params = qz.dequantize_tree(
                qparams, self.model.cfg.jdtype, barrier=False
            )
            return qparams, params
        params = self._place(jax.tree.map(
            lambda new, old: jnp.asarray(new, old.dtype),
            raw_params, self._params,
        ))
        return params, params

    def snapshot_weights(self):
        """Opaque handle to the CURRENT weight generation (device
        buffers stay resident — params are never donated).  Hand it
        back to :meth:`restore_weights` to roll a swap back without
        re-ingesting."""
        return (self._qparams, self._params, self._dparams,
                self.weight_generation)

    def swap_weights(self, raw_params, draft_params=None):
        """Install a new weight generation between decode chunks.

        ``raw_params`` is a raw (float) checkpoint tree matching
        :meth:`param_spec`; it is re-quantized on ingest when this
        decoder serves int8 weights.  The slot table is NOT touched —
        the serving engine quiesces in-flight requests first (the
        watchdog teardown/re-admit path, reused for planned swaps).
        The attached prefix cache is flushed (its KV blocks were
        computed by the old weights — serving them under the new
        generation would be silent corruption).  Avals are identical
        by construction, so no compiled program retraces."""
        self._check_swap_tree(raw_params)
        self._qparams, self._params = self._ingest_params(raw_params)
        if self._spec and draft_params is not None:
            dparams = self._place(
                jax.tree.map(jnp.asarray, draft_params)
            )
            if self._qz.is_quantized(dparams):
                dparams = self._qz.dequantize_tree(
                    dparams, self.draft_model.cfg.jdtype, barrier=False
                )
            self._dparams = dparams
        if self._use_prefix:
            self.prefix_cache.clear()
        self.weight_generation += 1
        return self.weight_generation

    def restore_weights(self, snapshot):
        """Roll back to a :meth:`snapshot_weights` generation (no
        re-ingest, no requantization — the old buffers were kept
        resident).  Flushes the prefix cache like a forward swap."""
        self._qparams, self._params, self._dparams, gen = snapshot
        if self._use_prefix:
            self.prefix_cache.clear()
        self.weight_generation = int(gen)
        return self.weight_generation

    def canary_check(self, raw_params=None):
        """ONE forward pass through the model (a fixed 8-token prompt)
        with ``raw_params`` (default: the live weights), returning
        True when every logit is finite.  Compiled separately from
        the decode programs, so the first call never perturbs the
        serving census; the hot-swap plane runs it as the last
        validation stage — in the watcher's ingest thread (off the
        hot path) and/or right after a swap installs, where a failure
        triggers automatic rollback."""
        if self._canary_jit is None:
            self._canary_jit = jax.jit(
                lambda p, t: self.model.apply({"params": p}, t)
            )
        if raw_params is None:
            params = self._params
        else:
            # validate + ingest exactly as a swap would, so the canary
            # exercises the same (re-quantized, live-dtype) weights
            # that would serve
            self._check_swap_tree(raw_params)
            params = self._ingest_params(raw_params)[1]
        tokens = (
            jnp.arange(8, dtype=jnp.int32)[None, :]
            % self.model.cfg.vocab_size
        )
        logits = self._canary_jit(params, tokens)
        return bool(jnp.isfinite(jnp.asarray(logits)).all())

    def dispatch_chunk(self):
        """Dispatch one compiled decode chunk over every slot WITHOUT
        synchronizing: the cache/state futures are installed
        immediately and the token block comes back as unresolved
        device arrays.  Pair with :meth:`resolve_chunk`; the split
        lets the serving engine do host-side work (queue refill,
        deadline bookkeeping) while the chunk runs, and lets its
        watchdog bound only the synchronizing half."""
        keys = self._next_key(self.chunk_size)
        params = self._qparams if self._quantized else self._params
        tables = jnp.asarray(self.tables) if self._paged else None
        if self._spec:
            (self.cache, self.draft_cache, self.state, buf, off, acc,
             prop) = self._chunk_jit(
                params, self._dparams, self.cache, self.draft_cache,
                self.state, jnp.asarray(self.active), tables, keys,
            )
            return buf, off, acc, prop
        self.cache, self.state, toks = self._chunk_jit(
            params, self.cache, self.state, jnp.asarray(self.active),
            tables, keys,
        )
        return toks

    def resolve_chunk(self, pending):
        """Synchronize a :meth:`dispatch_chunk` block to host int32 as
        ``(tokens [B, T], valid [B])`` — row ``r``'s tokens are
        ``tokens[r, :valid[r]]`` (idle lanes hold garbage — the
        scheduler only reads active lanes' rows).  Plain chunks fill
        every row to ``chunk_size``; speculative chunks compact each
        slot's accepted tokens left, so ``valid`` varies per slot (up
        to ``chunk_size * (draft_len+1)``) and the per-slot
        accepted/proposed draft counters fold into
        :attr:`spec_accepted`/:attr:`spec_proposed`.  The ONLY
        synchronizing host pull in the engine — and therefore the
        call a wedged device dispatch hangs, which is why the serving
        watchdog wraps exactly this."""
        np = self._np
        if self._spec:
            buf, off, acc, prop = pending
            toks = np.asarray(buf)
            valid = np.asarray(off)
            # tfoslint: disable=TFOS002(resolve_chunk IS the one sanctioned sync point - see docstring; the watchdog wraps exactly this)
            self.spec_accepted += int(np.asarray(acc).sum())
            # tfoslint: disable=TFOS002(same sanctioned sync point as the line above)
            self.spec_proposed += int(np.asarray(prop).sum())
            return toks, valid
        if self._moe_layers:
            # one pull for both: the copies start together
            toks, counts = jax.device_get(pending)
            self.last_chunk_counts = dict(zip(
                ("moe_assignments", "moe_local_assignments",
                 "moe_experts_hit"), (int(c) for c in counts)))
        else:
            toks = np.asarray(pending)
        return toks, np.full((toks.shape[0],), toks.shape[1], np.int32)

    def step_chunk(self):
        """Dispatch + resolve one decode chunk (see
        :meth:`dispatch_chunk` / :meth:`resolve_chunk`)."""
        return self.resolve_chunk(self.dispatch_chunk())

    def _layer_reads(self, live):
        """Per layer, the key/value positions the next chunk's first
        decode step reads, from the scheduler's own record ``live`` of
        every request in flight (``(prompt_len, generated)`` since its
        admit; no device pull).  A layer whose step goes through the
        block-walking kernel reads, a slot, the blocks its live span
        touches — a left-padded admit's span starts past its pad
        region, the layer's OWN window cuts it from below, a ring holds
        the same blocks modulo its length — and one block of a lane
        nobody holds; a layer under masked einsums reads its bank
        whole, ring or not."""
        cfg = self.model.cfg
        canonical = self._paged or self._use_prefix
        spans = []
        for n, gen in live:
            first = 0 if canonical else self.bucket_len(n) - n
            spans.append((first, first + n + gen - 1))
        reads = {}  # (block, window) -> positions: layers repeat
        out = []
        for layer, (rows, t) in enumerate(
                zip(self._layer_rows, self._layer_blocks)):
            if not t:
                out.append(self.num_slots * (
                    self._blocks_per_slot * self._page_tokens
                    if self._paged else rows))
                continue
            window = cfg.window_of(layer)
            if (t, window) not in reads:
                read = (self.num_slots - len(live)) * t
                for first, last in spans:
                    if window:
                        first = max(first, last + 1 - window)
                    read += (last // t - first // t + 1) * t
                reads[t, window] = read
            out.append(reads[t, window])
        return out

    def kv_read_by_kind(self, live):
        """``{"ring", "whole"}``: the positions of :meth:`_layer_reads`
        summed over the layers that keep rings and over those whose
        banks are whole."""
        out = {"ring": 0, "whole": 0}
        for rows, read in zip(self._layer_rows, self._layer_reads(live)):
            out["whole" if rows == self._bank_len else "ring"] += read
        return out

    def prefill_pairs(self, bucket):
        """``{"window", "full"}``: the query-key pairs a prompt of
        ``bucket`` tokens attends over (causal, and on a windowed layer
        inside its window), times the layer's query heads, summed over
        the windowed layers and over the full ones."""
        cfg = self.model.cfg
        n = int(bucket)
        out = {"window": 0, "full": 0}
        for layer in range(cfg.num_layers):
            w = cfg.window_of(layer)
            m = min(n, w) if w else n
            pairs = m * (m + 1) // 2 + (n - m) * w
            out["window" if w else "full"] += pairs * cfg.heads_of(layer)
        return out

    def kv_read_tokens(self, live):
        """``(read, bank)``, a layer (the mean over the layers where
        they differ — rings beside whole banks): key/value positions
        the next chunk's first decode step reads (:meth:`_layer_reads`)
        and what the slots' banks hold."""
        layers = len(self._layer_rows)
        if self._paged:
            bank = self.num_slots * self._blocks_per_slot * self._page_tokens
        else:
            bank = self.num_slots * sum(self._layer_rows) // layers
        return sum(self._layer_reads(live)) // layers, bank

    def attn_read_tokens(self, live):
        """``(read, context)`` summed over the layers: the positions
        the next chunk's first decode step reads — key/value or latent
        rows on every layer, each by its own window and bank, and the
        index keys of the layers that own a sparse index — and the
        positions that are live (every request's prompt and answer so
        far, a layer; what dense attention over exactly the live keys
        would read).  ``live`` as for :meth:`_layer_reads`: no device
        pull."""
        cfg = self.model.cfg
        # the index scores every position of its bank
        index_layers = sum(t == "full" for t in cfg.indexer_types)
        context = sum(n + gen for n, gen in live)
        return (sum(self._layer_reads(live))
                + self.num_slots * self._bank_len * index_layers,
                context * cfg.num_layers)

    def kv_bank_bytes(self):
        """Bytes the contiguous key/value banks hold, by kind:
        ``{"ring", "whole"}`` — the windowed layers' rings and the
        banks of ``_bank_len`` rows — and ``"unringed"``, what whole
        banks on every layer would hold.  None for pages and latent
        rows (their sizes are the pool's and the latent banks')."""
        if self._paged or self._latent:
            return None
        row = sum(
            leaf.size // (leaf.shape[0] * leaf.shape[1]) * leaf.dtype.itemsize
            for leaf in jax.tree.leaves(self.cache["block_0"]))
        ring = sum(r for r in self._layer_rows if r != self._bank_len)
        whole = sum(r for r in self._layer_rows if r == self._bank_len)
        return {
            "ring": self.num_slots * ring * row,
            "whole": self.num_slots * whole * row,
            "unringed": self.num_slots * self._bank_len * row
            * len(self._layer_rows),
        }

    def reuse_stats(self):
        """Cross-request reuse counters: the prefix cache's
        cumulative stats (when attached) plus the speculative
        accept accounting.  The serving engine snapshots these at
        job start and reports per-job deltas."""
        out = {
            "spec_accepted": self.spec_accepted,
            "spec_proposed": self.spec_proposed,
        }
        if self._use_prefix:
            out.update(self.prefix_cache.stats())
        if self._paged:
            out.update(self.page_pool.stats())
        return out

    def compile_counts(self):
        """Compiled-program census: {"prefill": one per prompt bucket,
        "chunk": 1}.  Admit/evict must never grow these (asserted in
        tests/test_serving.py).  With a prefix cache the census adds
        the canonical-admit programs: one suffix-prefill per suffix
        bucket, one install per hit-segment length, one extract per
        commit-segment length — still admission-count-independent
        (tests/test_prefix_cache.py)."""
        out = {
            "prefill": int(self._prefill_jit._cache_size()),
            "chunk": int(self._chunk_jit._cache_size()),
        }
        if self._paged:
            # the paged plane's whole admit surface is ONE program
            # family (per suffix bucket) — no install, no extract
            out["prefill_paged"] = int(
                self._prefill_paged_jit._cache_size()
            )
        elif self._use_prefix:
            out["prefill_canonical"] = int(
                self._prefill_canonical_jit._cache_size()
            )
            out["install"] = int(self._install_jit._cache_size())
            out["extract"] = int(self._extract_jit._cache_size())
        return out


def serving_builder(params, config):
    """``model_ref`` target for serving exports: next-token logits for
    a ``tokens`` batch (see :mod:`tensorflowonspark_tpu.serving`).
    ``config`` carries TransformerConfig fields; distributed-attention
    settings (``ring``/``ulysses``, ``mesh``) are coerced to dense
    ``dot`` — serving is single-host batch inference and the kernels
    are numerically identical (tests/test_attention.py)."""
    import numpy as np

    # fleet serving needs fresh predictors (make_replica below):
    # capture the caller's params/config BEFORE the draft pop and
    # weight quantization rebind them
    _raw_params, _raw_config = params, dict(config)
    cfg_fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    # unknown-key preflight (ISSUE 18): a typo'd knob (kv_page_token)
    # used to fall through every config.get below and serve with the
    # default, no signal — raise the named error listing the valid
    # knob table instead
    from tensorflowonspark_tpu.planner import knobs as knob_registry

    knob_registry.validate_keys(config, cfg_fields)
    plan_summary = None
    if config.get("auto"):
        # config={"auto": True, ...}: the cost-model planner fills
        # every planner-owned knob the caller left unset; explicit
        # keys win, so each decision is individually overridable
        from tensorflowonspark_tpu.planner import auto_serving_config

        config, _plan = auto_serving_config(config)
        plan_summary = _plan.summary()
        # replicas rebuild from the RESOLVED config: one plan (and one
        # planner_decision journal event) per deployment, not per
        # replica
        _raw_config = dict(config)
    overrides = dict(config, attention_impl="dot", mesh=None)
    cfg = TransformerConfig(
        **{k: v for k, v in overrides.items() if k in cfg_fields}
    )
    model = Transformer(cfg)
    # draft-model speculative decoding: draft weights ride the export
    # as a "draft" sibling of "params" (save_for_serving({"params": ...,
    # "draft": ...})) or arrive in-process via config["draft_params"];
    # config["draft_config"] carries the draft's TransformerConfig
    # fields (defaults: the flagship's geometry)
    draft_params = config.get("draft_params")
    if isinstance(params, dict) and "draft" in params:
        params = dict(params)
        popped = params.pop("draft")
        if draft_params is None:
            draft_params = popped
    draft_model = None
    if config.get("draft_config") is not None:
        if draft_params is None:
            raise ValueError(
                "draft_config given but no draft weights: pass "
                "config['draft_params'] or export "
                "{'params': ..., 'draft': ...}"
            )
        dover = dict(
            config["draft_config"], attention_impl="dot", mesh=None
        )
        dover.setdefault("vocab_size", cfg.vocab_size)
        dcfg = TransformerConfig(
            **{k: v for k, v in dover.items() if k in cfg_fields}
        )
        draft_model = Transformer(dcfg)
        draft_params = jax.tree.map(jnp.asarray, draft_params)
    # weight quantization (quantize.py): "int8" halves the weight HBM
    # read, "int4" halves it AGAIN with group-wise scales (packed two
    # codes per byte; docs/serving.md "Paged KV & int4") — generate()
    # dequantizes per decode step under a barrier; the logits path
    # dequantizes once up front (batch logits are compute-bound).
    # ``weights`` is the canonical knob; ``quantize`` stays as the
    # pre-ISSUE-12 alias.
    weights = config.get("weights") or config.get("quantize")
    if weights in ("int8", "int4"):
        from tensorflowonspark_tpu import quantize as qz

        params = (
            qz.quantize_tree(params) if weights == "int8"
            else qz.quantize_tree_int4(
                params, group_size=int(config.get("int4_group", 64))
            )
        )
        if config.get("mode") != "generate":
            params = qz.dequantize_tree(
                params, cfg.jdtype, barrier=False
            )
    elif weights not in (None, "float", "none"):
        raise ValueError(
            "weights/quantize must be 'int8', 'int4', 'float' or "
            "unset; got {0!r}".format(weights)
        )
    if config.get("mode") == "generate":
        # generation serving: prompt batch in -> sampled continuations
        # out (KV-cache decode; see generate()).  config keys:
        # max_new_tokens (required), temperature, top_k, top_p, seed;
        # speculative=true switches the STATIC path to speculative
        # decoding (greedy-only, uniform-length batches; draft_len/
        # ngram tune it, draft_config+draft_params swap the n-gram
        # lookup for a draft model).  draft_config alone arms per-slot
        # speculation on the CONTINUOUS schedule; prefix_cache=true
        # arms cross-request KV reuse there (docs/serving.md "Prefix
        # cache & speculative decoding").
        max_new = int(config["max_new_tokens"])
        temperature = float(config.get("temperature", 0.0))
        top_k = int(config.get("top_k", 0))
        top_p = float(config.get("top_p", 0.0))
        rng = jax.random.PRNGKey(int(config.get("seed", 0)))
        speculative = bool(config.get("speculative", False))
        if (speculative or draft_model is not None) and temperature > 0:
            raise ValueError(
                "speculative generation serving is greedy-only "
                "(temperature must be 0)"
            )
        draft_len = int(config.get("draft_len", 4))
        ngram = int(config.get("ngram", 2))
        pad_id = int(config.get("pad_id", 0))
        eos_id = config.get("eos_id")
        eos_id = None if eos_id is None else int(eos_id)
        input_name = config.get("input_name", "tokens")
        variables = base.as_variables(params)

        if speculative:
            # uniform-length batches only (generate_speculative has no
            # ragged support; rows of unequal length fail at stacking
            # with a named ValueError from predict_rows — see
            # docs/inference.md "Speculative decoding")
            def predict_spec(batch):
                tokens = jnp.asarray(batch[input_name], jnp.int32)
                st = {}
                toks, _rounds = generate_speculative(
                    model, variables["params"], tokens, max_new,
                    draft_len=draft_len, ngram=ngram,
                    draft_model=draft_model, draft_params=draft_params,
                    return_stats=True, stats=st,
                )
                out = {"generated": np.asarray(toks, np.int32)}
                if draft_model is not None:
                    # per-batch accept rate as a per-row column (the
                    # bench / engine stats surface)
                    out["accept_rate"] = np.full(
                        (tokens.shape[0],), st["accept_rate"],
                        np.float32,
                    )
                predict_spec.last_spec_stats = st
                return out

            predict_spec.last_spec_stats = {}
            predict_spec.plan = plan_summary
            return predict_spec

        # ragged multi-request batching: predict_rows left-pads each
        # batch's prompts (predict.column_padding) and ships per-row
        # pad counts; generate() masks the pad slots and stops rows at
        # eos_id inside the one compiled scan
        jitted = jax.jit(
            lambda v, tokens, pads: generate(
                model, v["params"], tokens, max_new,
                temperature=temperature, rng=rng, top_k=top_k,
                top_p=top_p, pad_start=pads, eos_id=eos_id,
            )
        )

        def predict(batch):
            tokens = jnp.asarray(batch[input_name], jnp.int32)
            pads = batch.get(input_name + "_pad")
            pads = (
                jnp.zeros((tokens.shape[0],), jnp.int32)
                if pads is None else jnp.asarray(pads, jnp.int32)
            )
            out = np.asarray(jitted(variables, tokens, pads), np.int32)
            res = {"generated": out}
            if eos_id is not None:
                first_eos = np.where(
                    (out == eos_id).any(axis=1),
                    (out == eos_id).argmax(axis=1),
                    out.shape[1],
                ).astype(np.int32)
                res["generated_len"] = first_eos
            return res

        predict.column_padding = {input_name: pad_id}
        # bucket prompt lengths to multiples of 64 so the compiled
        # generate program is reused across batches (config:
        # pad_multiple)
        predict.pad_multiple = int(config.get("pad_multiple", 64))
        # bucketing must never push a fitting prompt past the cache:
        # cap the bucketed length at max_seq_len - max_new (ADVICE;
        # predict_rows honors this when left-padding)
        predict.pad_cap = max(1, cfg.max_seq_len - max_new)
        # continuous in-flight batching (predict_rows
        # schedule="continuous"): the scheduler builds a SlotDecoder
        # per job.  config keys: chunk_size (decode steps between
        # admit/evict points, default 16) and max_prompt_len (sizes
        # the slot cache to bucket(max_prompt_len) + max_new instead
        # of max_seq_len — device memory for more slots, and where
        # decode still reads the whole cache every step (a mesh,
        # untiled head sizes: decode_bank_block) bandwidth too).
        # Cross-request reuse knobs (docs/serving.md "Prefix cache &
        # speculative decoding"): prefix_cache=true attaches a
        # device-resident radix prefix cache over committed KV blocks
        # (prefix_block tokens per block, prefix_mem_mb HBM budget —
        # shared by every slot geometry of this predictor, so a warm
        # cache survives across jobs); speculative=true with a
        # draft_config runs per-slot draft-model speculative decode
        # chunks (greedy-only).
        # kv_layout="paged" (docs/serving.md "Paged KV & int4"): the
        # slot decoders keep KV in a shared physical page pool behind
        # per-slot block tables — cached admits install page indices
        # (zero-copy) and decode runs the ops/paged_attention.py
        # block-gather kernel.  kv_pages overrides the pool size;
        # kv_page_tokens the page width (defaults to prefix_block so
        # radix blocks and physical pages are the same granularity).
        kv_layout = str(config.get("kv_layout", "contiguous"))
        chunk_size = int(config.get("chunk_size", 16))
        max_prompt = config.get("max_prompt_len")
        # TP sharding knobs (docs/serving.md "Disaggregated
        # prefill/decode & TP sharding"): tp=N shards the slot
        # decoders' weights and KV pools over an N-wide `model` mesh
        # (mesh_shape overrides with an explicit {axis: size} dict).
        # The predictor surface is unchanged — fleet replicas built
        # through the engine_factory seam inherit the sharding from
        # the committed placements, zero router changes.
        smesh = None
        if config.get("tp") or config.get("mesh_shape"):
            from tensorflowonspark_tpu.parallel.mesh import serving_mesh

            smesh = serving_mesh(
                tp=config.get("tp"), mesh_shape=config.get("mesh_shape")
            )
        # under a mesh the pallas kernel is off the table (pallas
        # calls are not partitioned by GSPMD) — default to the
        # XLA-native gather path; an EXPLICIT paged_impl="kernel"
        # still reaches SlotDecoder's named error
        paged_impl = str(config.get(
            "paged_impl", "gather" if smesh is not None else "kernel"
        ))
        if kv_layout == "paged":
            # build-time Mosaic tile-legality preflight: fail paged
            # geometries destined for the TPU kernel HERE with a named
            # TileLegalityError instead of a Mosaic lowering failure
            # inside the first decode dispatch.  Off-TPU (interpret
            # mode) or on the gather path any geometry is legal, so
            # enforcement defaults off there; config["check_tiles"]
            # forces it either way.
            from tensorflowonspark_tpu import compat
            from tensorflowonspark_tpu.ops import paged_attention as pa

            enforce = config.get("check_tiles")
            if enforce is None:
                enforce = (
                    paged_impl == "kernel"
                    and not compat.pallas_interpret()
                )
            if enforce:
                pa.check_tiles(
                    int(config.get("kv_page_tokens")
                        or config.get("prefix_block") or 16),
                    cfg.head_dim,
                    "int8" if cfg.cache_dtype == "int8" else cfg.dtype,
                )
        slot_decoders = {}
        prefix_holder = []
        paged_caches = {}

        def _make_prefix_cache():
            from tensorflowonspark_tpu.prefix_cache import PrefixCache

            return PrefixCache(
                block_tokens=int(config.get("prefix_block", 16)),
                mem_budget_bytes=int(
                    float(config.get("prefix_mem_mb", 256.0))
                    * (1 << 20)
                ),
            )

        def _prefix_cache(key=None):
            if not config.get("prefix_cache", False):
                return None
            if kv_layout == "paged":
                # page-index payloads are only meaningful against the
                # pool that allocated them: one radix cache per slot
                # geometry (still warm across jobs — the decoder memo
                # below reuses it)
                if key not in paged_caches:
                    paged_caches[key] = _make_prefix_cache()
                return paged_caches[key]
            if not prefix_holder:
                prefix_holder.append(_make_prefix_cache())
            return prefix_holder[0]

        def make_slot_decoder(num_slots, chunk=None):
            # memoized per (slots, chunk): a SlotDecoder owns its
            # jitted programs, so a fresh instance per job would
            # recompile prefill+chunk every predict_rows call; a
            # reused one only resets its (host-side) slot table
            key = (
                int(num_slots),
                int(chunk) if chunk is not None else chunk_size,
            )
            dec = slot_decoders.get(key)
            if dec is not None:
                dec.reset()
                return dec
            cache_len = cfg.max_seq_len
            if max_prompt is not None:
                m = predict.pad_multiple
                b = ((int(max_prompt) + m - 1) // m) * m
                cache_len = min(cfg.max_seq_len, b + max_new)
            dec = SlotDecoder(
                model, variables["params"], key[0], max_new,
                cache_len=cache_len, chunk_size=key[1],
                pad_multiple=predict.pad_multiple,
                temperature=temperature, top_k=top_k, top_p=top_p,
                eos_id=eos_id, seed=int(config.get("seed", 0)),
                prefix_cache=_prefix_cache(key),
                draft_model=draft_model, draft_params=draft_params,
                draft_len=draft_len,
                kv_layout=kv_layout,
                kv_pages=config.get("kv_pages"),
                page_tokens=config.get(
                    "kv_page_tokens", config.get("prefix_block")
                ),
                paged_impl=paged_impl,
                mesh=smesh,
            )
            slot_decoders[key] = dec
            return dec

        predict.make_slot_decoder = make_slot_decoder
        predict.max_new_tokens = max_new
        predict.eos_id = eos_id
        #: the planner's decision record when config={"auto": ...}
        #: built this predictor (None otherwise) — predict_rows reads
        #: engine-side picks (batch_size) off plan["chosen"]
        predict.plan = plan_summary
        #: the serving mesh (None = unsharded) — fleet/replica.py skips
        #: its default-device pin for mesh predictors (the committed
        #: placements own the devices)
        predict.mesh = smesh
        # prefill/decode disaggregation: the ServingEngine reads this
        # attr (overridable per engine) and, when set, admits through a
        # serving_disagg.PrefillWorker — its own jitted program — with
        # the zero-copy block-table handoff into the chunked decoder.
        # Needs the paged layout (the handoff IS a table exchange).
        disagg = bool(config.get("disaggregate", False))
        if disagg and kv_layout != "paged":
            raise ValueError(
                "disaggregate=true needs kv_layout='paged' (the "
                "prefill→decode handoff is a block-table exchange)"
            )
        predict.disaggregate = disagg
        # fleet serving (docs/serving.md "Fleet routing & rolling
        # deploys"): every replica needs its OWN SlotDecoder (jitted
        # programs + slot state are single-threaded) and its own radix
        # cache (prefix affinity routes a shared prefix to the replica
        # whose cache already holds it) — a fresh predictor per
        # replica gives exactly that.  ReplicaSet calls this once per
        # replica beyond the first.
        predict.make_replica = lambda: serving_builder(
            _raw_params, dict(_raw_config)
        )
        if config.get("profile_dir"):
            # on-demand jax.profiler capture: the serving engine starts
            # the trace and counts decode chunks as steps
            # (tensorboard.start_profile — graceful no-op when the
            # build lacks the profiler)
            predict.profile = {
                "dir": str(config["profile_dir"]),
                "steps": int(config.get("profile_steps", 0)) or None,
            }
        return predict
    out = base.make_serving_predict(
        base.as_variables(params),
        lambda v, tokens: model.apply(v, jnp.asarray(tokens, jnp.int32)),
        config.get("input_name", "tokens"),
        lambda logits: {
            "logits": np.asarray(logits, np.float32),
            "next_token": np.asarray(jnp.argmax(logits[:, -1], axis=-1)),
        },
    )
    out.plan = plan_summary
    return out
