"""What a CPU mesh cannot show: the programs must lower and compile for
real chips.

On CPU every Pallas kernel runs interpreted — plain XLA ops that GSPMD
partitions silently — so a model that works on the 8-device CPU mesh can
still be refused by ``jax.jit`` on more than one TPU (``Mosaic kernels
cannot be automatically partitioned``), and a kernel whose blocks exceed
VMEM never meets the Mosaic allocator.  Two levers work without a chip:
``lower(lowering_platforms=("tpu",))`` runs the TPU lowering rules over a
CPU mesh, and ``jax.experimental.topologies`` hands out v5e devices to
AOT-compile for.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tensorflowonspark_tpu import compat
from tensorflowonspark_tpu.ops import gmm
from tensorflowonspark_tpu.ops.attention import attention

#: flagship attention geometry (bench/chip_smoke): B8 S2048 H8 Dh128
B, S, H, D = 8, 2048, 8, 128


@pytest.fixture
def mosaic(monkeypatch):
    """Lower the kernels as on a TPU (compiled by Mosaic, not
    interpreted) — the one switch every kernel reads."""
    monkeypatch.setattr(compat, "pallas_interpret", lambda: False)


def _lower_for_tpu(mesh, kv_heads, use_mesh):
    spec = P(("data",) if "data" in mesh.shape else None, None,
             "model" if "model" in mesh.shape else None, None)
    sharding = NamedSharding(mesh, spec)
    q = jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16, sharding=sharding)
    kv = jax.ShapeDtypeStruct(
        (B, S, kv_heads, D), jnp.bfloat16, sharding=sharding)

    def loss(q, k, v):
        return attention(
            q, k, v, impl="flash", mesh=mesh if use_mesh else None,
        ).astype(jnp.float32).sum()

    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    return step.trace(q, kv, kv).lower(lowering_platforms=("tpu",))


@pytest.mark.parametrize("axes,kv_heads", [
    ({"data": 4}, H),
    ({"data": 2, "model": 2}, 2),  # GQA: kv heads split over `model`
])
def test_sharded_flash_lowers_for_tpu_on_a_mesh(mosaic, axes, kv_heads):
    mesh = Mesh(
        np.array(jax.devices()[:4]).reshape(tuple(axes.values())),
        tuple(axes),
    )
    text = _lower_for_tpu(mesh, kv_heads, use_mesh=True).as_text()
    assert "tpu_custom_call" in text  # the Mosaic kernel, not interpreted
    # each device runs the kernel on ITS shard of batch and heads
    per_dev = "tensor<%dx%dx%dx%dxbf16>" % (
        B // axes["data"], H // axes.get("model", 1), S, D)
    assert per_dev in text


def test_unwrapped_flash_is_refused_on_a_mesh(mosaic):
    # the failure the shard_map wrap exists for — if a future JAX learns
    # to partition Mosaic calls this starts passing and the wrap can go
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    with pytest.raises(NotImplementedError, match="Mosaic kernels cannot"):
        _lower_for_tpu(mesh, H, use_mesh=False)


def test_gmm_float32_backward_compiles_for_v5e(mosaic):
    """E8 D1024 F4096 float32: the dw/dx/forward block pickers must count
    4-byte operands, or Mosaic runs out of its 16MB scoped VMEM."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu AOT in this env
        pytest.skip("no TPU AOT topology here: %s" % e)
    dev = SingleDeviceSharding(topo.devices[0])
    e, d, f, bm, t = 8, 1024, 4096, 256, 32

    def fwd_bwd(x, w, te, dy):
        out, vjp = jax.vjp(
            lambda x, w: gmm.grouped_matmul(x, w, te, bm), x, w)
        return (out,) + vjp(dy)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    for dtype in (jnp.float32, jnp.bfloat16):
        jax.jit(fwd_bwd).lower(
            arg((t * bm, d), dtype), arg((e, d, f), dtype),
            arg((t,), jnp.int32), arg((t * bm, f), dtype),
        ).compile()


@pytest.mark.parametrize("cache", ["bfloat16", "int8"])
def test_bank_decode_kernel_compiles_for_v5e_without_copying_a_bank(
        mosaic, cache):
    """The serving cell's geometry (64 slots, banks of 1536, 8 kv heads
    of 128, 4 query heads each): Mosaic must take the kernel, and the
    view of the banks the kernel reads through must stay a bitcast —
    a relayout would copy 200 MB a bank, a layer, a step."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from tensorflowonspark_tpu.ops import paged_attention as pa

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu AOT in this env
        pytest.skip("no TPU AOT topology here: %s" % e)
    dev = SingleDeviceSharding(topo.devices[0])
    b, s, h, hkv, d = 64, 1536, 32, 8, 128

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    bank = arg((b, s, hkv, d), jnp.dtype(cache))
    args = [arg((b, h, d), jnp.bfloat16), bank, bank,
            arg((b,), jnp.int32), arg((b,), jnp.int32)]
    if cache == "int8":
        args += [arg((b, s, hkv, 1), jnp.float32)] * 2

    def attend(q, k, v, positions, pad_start, ks=None, vs=None):
        return pa.bank_attention(
            q, k, v, positions, pad_start, window=4096,
            k_scale=ks, v_scale=vs,
        )

    compiled = jax.jit(attend).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # temporaries: nothing the size of a bank (the int8 scales' lane
    # view is a copy of 2 x 3 MB)
    bank_bytes = b * s * hkv * d * jnp.dtype(cache).itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < bank_bytes // 8


@pytest.mark.parametrize("held,d,f,rows,bm", [
    (16, 6144, 2048, 17 * 8, 16), (16, 6144, 2048, 1024 * 8, 256),
    (64, 2304, 896, 97 * 8, 16), (64, 2304, 896, 8192 * 8, 256),
])
def test_share_grouped_matmul_compiles_for_v5e_at_both_row_tiles(
        mosaic, held, d, f, rows, bm):
    """The routed experts at published widths: the latent-attention
    cell's (16 held experts, 6144 x 2048: a decode step's 136
    assignments in row tiles of 16, a prompt chunk's 8192 in tiles of
    256) and the window-and-full cell's (64 held, 2304 x 896: 97
    slots' 776 assignments, a prompt of 8192's 65,536), whose forward
    takes each product's whole width as one stripe — Mosaic must take
    the live-tile kernel at both."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu AOT in this env
        pytest.skip("no TPU AOT topology here: %s" % e)
    dev = SingleDeviceSharding(topo.devices[0])
    t = -(-rows // bm) + held

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    def experts(x, wi, wg, wo, te, live):
        def mm(a, w):
            return gmm.gmm_call(a, w, te, bm=bm, live_tiles=live)

        return mm(jax.nn.silu(mm(x, wg)) * mm(x, wi), wo)

    w_in = arg((held, d, f), jnp.bfloat16)
    compiled = jax.jit(experts).lower(
        arg((t * bm, d), jnp.bfloat16), w_in, w_in,
        arg((held, f, d), jnp.bfloat16), arg((t,), jnp.int32),
        arg((1,), jnp.int32),
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3


def test_latent_decode_step_compiles_for_v5e_without_copying_a_bank(mosaic):
    """One absorbed decode step of latent attention at the cell's
    geometry (17 slots, banks of 20480 positions, 64 heads, rows of
    512 + 64 padded to 640 lanes, an index of 32 heads x 128): Mosaic
    must take the latent decode kernel, and the row append, the index,
    the selection and the kernel's view must leave the bank where it
    is — a relayout or a slice down to the 512 latent columns is a
    copy of 446 MB a layer, a step."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from tensorflowonspark_tpu.models import mla
    from tensorflowonspark_tpu.models import transformer as tr

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu AOT in this env
        pytest.skip("no TPU AOT topology here: %s" % e)
    dev = SingleDeviceSharding(topo.devices[0])
    slots, length = 17, 20480
    cfg = tr.TransformerConfig(
        num_heads=64, embed_dim=6144, max_seq_len=length,
        attention_kind="mla", q_lora_rank=2048, kv_lora_rank=512,
        qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
        index_n_heads=32, index_head_dim=128, index_topk=2048,
        rope_theta=8e6, rope_interleave=True, rms_norm_eps=1e-5,
    )
    layer = mla.MLAttention(cfg, indexer="full")
    x = jnp.zeros((slots, 1, cfg.embed_dim), jnp.bfloat16)
    pos = jnp.zeros((slots, 1), jnp.int32)
    shapes = jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0), x, pos, decode=True))
    assert shapes["cache"]["latent"].shape == (slots, length, 640)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, jnp.bfloat16 if a.dtype == jnp.float32 else a.dtype,
            sharding=dev), tree)

    def step(params, cache, x, pos, pad):
        (out, sel), mut = layer.apply(
            {"params": params, "cache": cache}, x, pos, decode=True,
            pad_start=pad, per_slot=True, mutable=["cache"])
        return out, sel, mut["cache"]

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        on_chip(shapes["params"]), on_chip(shapes["cache"]),
        on_chip(x), on_chip(pos),
        jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=dev),
    ).compile()
    assert "latent_decode_attention" in compiled.as_text()
    bank_bytes = slots * length * 640 * 2
    assert compiled.memory_analysis().temp_size_in_bytes < bank_bytes // 2


def test_latent_prefill_layer_compiles_for_v5e_with_its_scores_in_vmem(
        mosaic):
    """One "full" latent-attention layer prefilling the cell's longest
    bucket (16384 tokens, 64 heads of 192 + 64 / 256, an index of 32
    heads x 128 picking 2048) into a lane of its banks: the span's
    attention must be the span kernel, no float32 ``[64, queries,
    keys]`` score tensor may be left in the program (the einsum form
    holds one of 128 x 16384 a block of queries), and the plan's
    temporaries stay under the 3.45 GB the whole prefill program of
    that bucket took with the einsums (PERF.md section 4)."""
    import re

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from tensorflowonspark_tpu.models import mla
    from tensorflowonspark_tpu.models import transformer as tr

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu AOT in this env
        pytest.skip("no TPU AOT topology here: %s" % e)
    dev = SingleDeviceSharding(topo.devices[0])
    span, length = 16384, 20480
    cfg = tr.TransformerConfig(
        num_heads=64, embed_dim=6144, max_seq_len=length,
        attention_kind="mla", q_lora_rank=2048, kv_lora_rank=512,
        qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
        index_n_heads=32, index_head_dim=128, index_topk=2048,
        rope_theta=8e6, rope_interleave=True, rms_norm_eps=1e-5,
    )
    assert mla.span_blocks(cfg, True, span) == (2048, 512)
    layer = mla.MLAttention(cfg, indexer="full")
    x = jnp.zeros((1, span, cfg.embed_dim), jnp.bfloat16)
    pos = jnp.zeros((1, span), jnp.int32)
    shapes = jax.eval_shape(lambda: layer.init(
        jax.random.PRNGKey(0), x[:, :1], pos[:, :1], decode=True))

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, jnp.bfloat16 if a.dtype == jnp.float32 else a.dtype,
            sharding=dev), tree)

    def prefill(params, cache, x, pos, pad):
        (out, sel), mut = layer.apply(
            {"params": params, "cache": cache}, x, pos, decode=True,
            pad_start=pad, mutable=["cache"])
        return out, sel, mut["cache"]

    compiled = jax.jit(prefill, donate_argnums=(1,)).lower(
        on_chip(shapes["params"]), on_chip(shapes["cache"]),
        on_chip(x), on_chip(pos),
        jax.ShapeDtypeStruct((1,), jnp.int32, sharding=dev),
    ).compile()
    text = compiled.as_text()
    assert "latent_span_attention" in text
    scores = [m for m in re.findall(r"f32\[(?:1,)?64,\d+,(\d+)\]", text)
              if int(m) >= 2048]
    assert scores == []
    assert compiled.memory_analysis().temp_size_in_bytes < 3.45e9


#: caps of the two routed-span programs below: what PR 35's builder
#: read on 2026-10-04 (jax 0.9.0 / jaxlib 0.9.0 / libtpu 0.0.34) + 5%
MOONLIGHT_LAYER_TEMP, MOONLIGHT_LAYER_CODE = 1.152e9, 26.0e6
GLM_LAYER_TEMP, GLM_LAYER_CODE = 1.2e9, 14.5e6


@pytest.mark.parametrize(
    "name,tokens,d,m,k,held,experts,shared,grad,temp_cap,code_cap", [
        # one sparse layer of the training cell: 2 rows of 8192, top-6
        # of 64 with 8 held, 2 shared; forward and backward under the
        # block's remat, which keeps the routing's integers.  Read:
        # temporaries 1.097 GB, generated code 24.75 MB (27.39 MB
        # where the block saved nothing and a chunk worked out its
        # own rows' pairs)
        ("moonlight-train", 16384, 2048, 1408, 6, 8, 64, 2, True,
         MOONLIGHT_LAYER_TEMP, MOONLIGHT_LAYER_CODE),
        # a sparse layer of the serving cell's 12288-token prefill
        # bucket: top-8 of 256, 16 held, 1 shared.  Read: temporaries
        # 1.141 GB, generated code 13.76 MB
        ("glm-prefill", 12288, 6144, 2048, 8, 16, 256, 1, False,
         GLM_LAYER_TEMP, GLM_LAYER_CODE),
    ])
def test_a_routed_span_compiles_for_v5e_small_and_with_no_row_of_every_pair(
        mosaic, name, tokens, d, m, k, held, experts, shared, grad,
        temp_cap, code_cap):
    """``SigmoidMoE``'s span (``ops.moe.span_layout`` +
    ``share_span``) at the two cells' widths: the loops over the live
    chunks and the three kernels are in the program, nothing with
    ``d`` or ``m`` columns has more rows than the span has tokens —
    only 1-D integers are sized for every (token, choice) pair landing
    here — and the executable stays small: ``setup_s`` loads it on
    every run, and PR 34's form of this span lost its gain to 43 MB
    more of a step's code (caps: what PR 35's builder read + 5%)."""
    import re

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from tensorflowonspark_tpu.models import moe

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu AOT in this env
        pytest.skip("no TPU AOT topology here: %s" % e)
    dev = SingleDeviceSharding(topo.devices[0])
    layer = moe.SigmoidMoE(
        router_experts=experts, num_experts=held, mlp_dim=m, embed_dim=d,
        k=k, scaling=2.5, shared_experts=shared, dtype="bfloat16")
    x = jax.ShapeDtypeStruct((1, tokens, d), jnp.bfloat16, sharding=dev)
    shapes = jax.eval_shape(lambda: layer.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8, d), jnp.bfloat16)))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype if grad else jnp.bfloat16, sharding=dev),
        shapes["params"])

    def forward(p, x):
        return layer.apply({"params": p}, x, differentiable=grad)

    if grad:
        block = jax.checkpoint(
            forward, policy=jax.checkpoint_policies.save_only_these_names(
                *moe.SPAN_SAVED))
        # the value too, as a step returns its loss: the forward
        # runs, then the block again for the backward
        step = jax.value_and_grad(lambda p, x: block(p, x).astype(
            jnp.float32).sum(), argnums=(0, 1))
    else:
        step = forward
    compiled = jax.jit(step).lower(params, x).compile()
    text = compiled.as_text()
    kernels = set(re.findall(
        r"%(grouped_matmul(?:_dx|_dw)?)(?:\.\d+)? = ", text))
    assert kernels == ({"grouped_matmul", "grouped_matmul_dx",
                        "grouped_matmul_dw"} if grad
                       else {"grouped_matmul"}), kernels
    assert " while(" in text
    wide = [int(rows) for rows, cols in re.findall(
        r"(?:bf16|f32)\[(\d+),(\d+)\]", text) if int(cols) in (d, m)]
    assert wide and max(wide) == tokens, max(wide)
    plan = compiled.memory_analysis()
    print("%s: temporaries %.3f GB, generated code %.2f MB" % (
        name, plan.temp_size_in_bytes / 1e9,
        plan.generated_code_size_in_bytes / 1e6))
    assert plan.temp_size_in_bytes < temp_cap
    assert plan.generated_code_size_in_bytes < code_cap


def _v5e():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu AOT in this env
        pytest.skip("no TPU AOT topology here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


def test_ring_decode_kernel_compiles_for_v5e_without_copying_a_ring(mosaic):
    """The window-and-full cell's sliding layers (97 slots, rings of
    1280 rows for a window of 1024, 4 kv heads of 128, 8 query heads
    each): Mosaic must take the kernel with the wrapped block lookup,
    and the ring stays where it is."""
    from tensorflowonspark_tpu.ops import paged_attention as pa

    dev = _v5e()
    b, rows, h, hkv, d = 97, 1280, 32, 4, 128

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    bank = arg((b, rows, hkv, d), jnp.bfloat16)
    compiled = jax.jit(lambda q, k, v, pos, pad: pa.bank_attention(
        q, k, v, pos, pad, window=1024, ring=True)).lower(
            arg((b, h, d), jnp.bfloat16), bank, bank,
            arg((b,), jnp.int32), arg((b,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ring_bytes = b * rows * hkv * d * 2
    assert compiled.memory_analysis().temp_size_in_bytes < ring_bytes // 8


@pytest.mark.parametrize("layer,rows", [(0, 1280), (3, 10240)])
def test_a_long_prompt_s_layer_compiles_for_v5e_with_its_scores_in_vmem(
        mosaic, layer, rows):
    """One attention layer of the window-and-full cell prefilling its
    longest bucket (8192 tokens, 32 query heads over 4 kv heads of 128)
    into one lane — a ring of 1280 on a sliding layer, a bank of 10240
    on a full one: the prompt goes through the flash kernel (banded on
    the sliding layer, YaRN on the full one), so no float32 score
    tensor ``[32, 8192, keys]`` (10.7 GB over the bank) is ever in HBM:
    the plan's temporaries stay under a gigabyte."""
    from tensorflowonspark_tpu.models import transformer as tr

    dev = _v5e()
    cfg = tr.TransformerConfig(
        num_heads=32, num_kv_heads=4, head_dim=128, embed_dim=2304,
        max_seq_len=131072, qk_norm=True, num_layers=4,
        layer_types=["sliding_attention"] * 3 + ["full_attention"],
        sliding_window=1024, fresh_prompts=True, layer_rope={
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 8192,
                "beta_fast": 32, "beta_slow": 1,
                "attention_factor": 1.2772588722239782},
            "sliding_attention": {
                "rope_type": "default", "rope_theta": 500000}})
    assert tr.bank_rows(cfg, layer, 10240) == rows
    assert tr.prefill_flash(cfg, 8192)
    attn = tr.Attention(cfg, layer=layer)
    x = jnp.zeros((1, 8192, cfg.embed_dim), jnp.bfloat16)
    pos = jnp.zeros((1, 8192), jnp.int32)
    shapes = jax.eval_shape(
        lambda: attn.init(jax.random.PRNGKey(0), x[:, :1], pos[:, :1]))

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, jnp.bfloat16 if a.dtype == jnp.float32 else a.dtype,
            sharding=dev), tree)

    bank = jax.ShapeDtypeStruct((1, rows, 4, 128), jnp.bfloat16, sharding=dev)
    cache = {"cached_key": bank, "cached_value": bank}

    def prefill(params, cache, x, pos, pad):
        return attn.apply({"params": params, "cache": cache}, x, pos,
                          decode=True, pad_start=pad, mutable=["cache"])

    compiled = jax.jit(prefill, donate_argnums=(1,)).lower(
        on_chip(shapes["params"]), cache, on_chip(x), on_chip(pos),
        jax.ShapeDtypeStruct((1,), jnp.int32, sharding=dev)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9


def test_a_tp_training_step_compiles_for_v5e_with_its_stream_split_over_tokens(
        mosaic):
    """The training cell's step on a 2x2 v5e (``data=2 x model=2``,
    flash at its blocks of 1024, narrow widths): between blocks the
    residual stream holds half the tokens, so every sum of a row-parallel
    projection's partial products over ``model`` is a reduce-scatter
    (XLA:TPU's ``all-reduce-scatter`` fusion: all-reduce and slice in
    one) and every column-parallel projection reads an all-gather — no
    all-reduce of the stream is left on its own.  The Mosaic kernel
    runs inside its ``shard_map`` on one chip's batch rows and heads."""
    import re

    from jax.experimental import topologies

    from tensorflowonspark_tpu.models import transformer as tr
    from tensorflowonspark_tpu.parallel import mesh as pmesh, sharding as sh

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu AOT in this env
        pytest.skip("no TPU AOT topology here: %s" % e)
    mesh = pmesh.build_mesh({"data": 2, "model": 2}, devices=topo.devices)
    rows, seq, d, layers = 4, 2048, 256, 2
    fields = dict(vocab_size=512, num_layers=layers, num_heads=4,
                  num_kv_heads=2, head_dim=128, embed_dim=d, mlp_dim=512,
                  max_seq_len=seq, block_q=1024, block_k=1024)
    params = jax.eval_shape(lambda: tr.Transformer(tr.TransformerConfig(
        **fields)).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    specs = sh.param_specs(params, sh.RULES_TP, mesh, tr.logical_axes(params))
    params = jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=NamedSharding(mesh, s)), params, specs)
    tokens = jax.ShapeDtypeStruct(
        (rows, seq), jnp.int32, sharding=NamedSharding(mesh, P("data")))
    loss = tr.loss_fn(tr.Transformer(tr.TransformerConfig(
        **fields, attention_impl="flash", mesh=mesh)))
    text = jax.jit(jax.value_and_grad(
        lambda p, t: loss(p, {"tokens": t}, None))).lower(
            params, tokens).compile().as_text()
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and " custom-call(" in line]
    assert kernels and all("[2,2,%d,128]" % seq in k or "[2,%d,2,128]" % seq
                           in k for k in kernels)
    stream = r"(bf16|f32)\[%d,%d,%d\]" % (rows // 2, seq, d)
    where, scattered, reduced, gathered = None, 0, [], 0
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.-]+) ", line)
        if head:
            where = head.group(1)
        elif re.search(r"= %s\S* all-reduce\(" % stream, line):
            reduced.append(where)
        elif re.search(r"= %s\S* all-gather\(" % stream.replace(
                "(bf16|f32)", "bf16"), line):
            assert "replica_groups=[2,2]<=[4]" in line
            gathered += 1
        if head and where.startswith("all-reduce-scatter"):
            scattered += 1
    # the embedding, and attention's out and the MLP's wo a layer, in
    # the forward; the projections' dx and the head's in the backward
    assert scattered >= 2 * (2 * layers + 1)
    assert reduced and all(w.startswith("all-reduce-scatter") for w in reduced)
    assert gathered >= 2 * layers + 1
