"""Logical-axis sharding rules → concrete ``PartitionSpec``/shardings.

Models annotate parameters with *logical* axis names ("embed", "mlp",
"heads", "batch", ...); strategies pick a rule set mapping logical names
to mesh axes.  This is the layer that makes one model definition run
under DP, FSDP, TP, or any combination — the reference had no analogue
(all sharding lived inside TF's strategies).

Rules are ordered ``(logical_axis, mesh_axis_or_None)`` pairs; the first
match wins.  A mesh axis already consumed for an earlier dimension of the
same spec is skipped (a mesh axis may shard at most one dimension of a
given array).
"""

import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

logger = logging.getLogger(__name__)

# Default rule sets per strategy (models use these logical names).
RULES_DP = (
    ("batch", ("data", "fsdp")),
)
RULES_FSDP = RULES_DP + (
    ("embed", "fsdp"),
    ("mlp", "fsdp"),
    ("vocab", "fsdp"),
)
RULES_TP = RULES_DP + (
    ("mlp", "model"),
    ("heads", "model"),
    ("kv_heads", "model"),
    ("vocab", "model"),
    ("expert", "expert"),
    ("expert_mlp", "model"),
)
RULES_TP_FSDP = RULES_DP + (
    ("mlp", "model"),
    ("heads", "model"),
    ("kv_heads", "model"),
    # vocab takes model AND fsdp: for the embedding table this puts all
    # sharding on the gather/scatter dim and leaves the embed dim
    # replicated (the t5x/maxtext layout).  Sharding embed on fsdp here
    # instead forces the partitioner to reshard the gather's output from
    # batch sharding to embed sharding in the backward scatter — an
    # "involuntary full rematerialization" at every step.  For matmul
    # params (lm_head) fsdp is already consumed by the embed dim by the
    # time vocab resolves, so their specs are unchanged.
    ("vocab", ("model", "fsdp")),
    ("embed", "fsdp"),
    ("expert", "expert"),
    ("expert_mlp", "model"),
)
RULES_SEQ = (
    ("batch", ("data", "fsdp")),
    ("seq", "seq"),
)
RULES_EP = RULES_DP + (
    ("expert", "expert"),
    ("expert_mlp", "model"),
)


def apply_rules(logical_spec, rules, mesh=None, shape=None):
    """Map a tuple of logical axis names (or ``None``) to a
    :class:`PartitionSpec` under ``rules``.

    Mesh axes absent from ``mesh`` (when given) resolve to ``None`` —
    this is what lets TP-annotated models run unmodified on a pure-DP
    mesh.  With ``shape`` given, mesh axes that would not divide the
    dimension are dropped (e.g. a single-head model under TP: ``heads``
    is size 1, so the ``model`` axis falls off rather than erroring in
    ``device_put``).
    """
    rule_map = dict(rules) if not isinstance(rules, dict) else rules
    used = set()
    out = []
    for i, logical in enumerate(logical_spec):
        mesh_axes = rule_map.get(logical) if logical is not None else None
        if mesh_axes is None:
            out.append(None)
            continue
        if isinstance(mesh_axes, str):
            mesh_axes = (mesh_axes,)
        dim = shape[i] if shape is not None and i < len(shape) else None
        width = 1
        picked = []
        for ax in mesh_axes:
            if ax in used:
                continue
            size = mesh.shape.get(ax, 1) if mesh is not None else 1
            if mesh is not None and size == 1:
                # absent/size-1 axis: harmless to include, but dropping it
                # keeps specs readable in logs
                continue
            if dim is not None and dim % (width * size) != 0:
                continue
            picked.append(ax)
            used.add(ax)
            width *= size
        if not picked:
            out.append(None)
        elif len(picked) == 1:
            out.append(picked[0])
        else:
            out.append(tuple(picked))
    # trailing Nones are implied
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def param_specs(abstract_params, rules, mesh=None, annotations=None):
    """Derive a ``PartitionSpec`` pytree for a parameter pytree.

    Args:
      abstract_params: pytree of arrays / ShapeDtypeStructs.
      rules: logical→mesh rules.
      annotations: optional matching pytree of logical-axis tuples (as
        produced by :func:`tensorflowonspark_tpu.models.base.logical_axes`).
        Leaves without annotation are sharded by a shape heuristic: the
        largest dimension divisible by the fsdp axis size goes on
        ``fsdp`` (zero-3 style) if an ``fsdp`` rule target exists,
        otherwise fully replicated.
    """
    fsdp_size = mesh.shape.get("fsdp", 1) if mesh is not None else 1

    def _spec_for(leaf, logical):
        if logical is not None:
            return apply_rules(
                logical, rules, mesh, shape=getattr(leaf, "shape", None)
            )
        shape = getattr(leaf, "shape", ())
        if fsdp_size > 1 and len(shape) >= 1:
            # shape heuristic for un-annotated params
            dims = sorted(
                range(len(shape)), key=lambda i: shape[i], reverse=True
            )
            for d in dims:
                if shape[d] % fsdp_size == 0 and shape[d] >= fsdp_size:
                    spec = [None] * len(shape)
                    spec[d] = "fsdp"
                    while spec and spec[-1] is None:
                        spec.pop()
                    return PartitionSpec(*spec)
        return PartitionSpec()

    if annotations is None:
        return jax.tree.map(lambda l: _spec_for(l, None), abstract_params)
    return jax.tree.map(
        _spec_for,
        abstract_params,
        annotations,
        is_leaf=lambda x: x is None,
    )


def shard_params(params, rules, mesh, annotations=None):
    """Place a parameter pytree onto the mesh per the rules.

    Always copies: ``device_put`` may alias the source buffer into a
    shard of the placed array, and trainers *donate* the placed state —
    aliased donation would silently delete the caller's original params
    (e.g. re-using the same init params for a second trainer).
    """
    specs = param_specs(params, rules, mesh, annotations)
    return jax.tree.map(
        lambda p, s: jax.device_put(
            jnp.array(p), NamedSharding(mesh, s)
        ),
        params,
        specs,
    )


def replicated(mesh):
    return NamedSharding(mesh, PartitionSpec())


def canonicalize_on_mesh(tree, mesh):
    """Ensure every leaf lives on ``mesh``.  Leaves XLA left on a single
    device (jit outputs with no input dependence — e.g. optax ``count``
    scalars) are re-placed replicated; mesh-sharded leaves pass through.
    A state that mixes single-device and mesh arrays fails at the next
    jitted step with 'incompatible devices', and checkpoint templates
    built from it restore to the same broken placement."""

    def _fix(x):
        s = getattr(x, "sharding", None)
        if s is None or not hasattr(x, "shape"):
            return x
        if isinstance(s, NamedSharding) and s.mesh == mesh:
            return x
        return jax.device_put(x, replicated(mesh))

    return jax.tree.map(_fix, tree)


def residual_shardings(mesh, batch, seq):
    """``(split, whole)``: the two layouts of a training step's residual
    stream ``[batch, seq, embed]`` under a ``model`` axis (Megatron's
    sequence parallelism).  ``split`` puts tokens over ``model``: the
    stream between blocks, where norms and residual adds run on each
    chip's share of the tokens.  ``whole`` is the layout a
    column-parallel projection reads (:func:`gathered_products`).
    Batch goes over the data axes as :func:`batch_sharding` places it.
    ``None`` where the layout does not engage: no mesh, a ``model`` axis
    of 1, a ``seq`` axis wider than 1 (context parallelism lays tokens
    out itself) or a sequence the ``model`` axis does not divide."""
    shape = mesh.shape if mesh is not None else {}
    tp = shape.get("model", 1)
    if tp < 2 or seq % tp or shape.get("seq", 1) > 1:
        return None
    axes = tuple(a for a in ("data", "fsdp") if shape.get(a, 1) > 1)
    if batch % int(np.prod([shape[a] for a in axes])):
        axes = ()
    return (NamedSharding(mesh, PartitionSpec(axes or None, "model", None)),
            NamedSharding(mesh, PartitionSpec(axes or None, None, None)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def split_tokens(x, shardings):
    """``x`` — partial sums on each chip: a row-parallel projection's
    (attention's ``out``, the MLP's ``wo``) or the vocabulary-parallel
    embedding's — summed into the ``split`` layout of
    :func:`residual_shardings`.  Summed whole and sliced, which XLA
    fuses into one reduce-scatter (constrained to ``split`` alone, GSPMD
    may gather the embedding's table instead).  The gradient goes back
    whole, an all-gather, before the projection's transpose reads it
    (left to itself, GSPMD would keep it split and gather the
    projection's weights)."""
    split, whole = shardings
    return jax.lax.with_sharding_constraint(
        jax.lax.with_sharding_constraint(x, whole), split)


def _split_tokens_fwd(x, shardings):
    return split_tokens(x, shardings), None


def _split_tokens_bwd(shardings, _, ct):
    return (jax.lax.with_sharding_constraint(ct, shardings[1]),)


split_tokens.defvjp(_split_tokens_fwd, _split_tokens_bwd)


def _contract_last(x, kernel):
    # x [..., E] . kernel [E, *features]: nn.Dense's and
    # nn.DenseGeneral(axis=-1)'s product
    return jax.lax.dot_general(x, kernel, (((x.ndim - 1,), (0,)), ((), ())))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def gathered_products(x, kernels, shardings, dtype):
    """``x`` in the ``split`` layout of :func:`residual_shardings`
    times each of ``kernels`` (``[E, *features]``, column-parallel:
    split over ``model`` on a feature dim) with tokens whole, the
    operands in ``dtype`` as ``nn.Dense(dtype=dtype)`` computes them.
    Forward, an all-gather of ``x``.  Backward, ``x`` is gathered AGAIN
    for the kernels' gradients rather than kept whole from the forward
    (the whole copy would cost a chip ``S x E`` per projection input
    for every layer), and ``dx`` — partial sums over ``model`` — is
    reduce-scattered back to ``split``."""
    whole = jax.lax.with_sharding_constraint(x, shardings[1]).astype(dtype)
    return tuple(_contract_last(whole, k.astype(dtype)) for k in kernels)


def _gathered_products_fwd(x, kernels, shardings, dtype):
    return gathered_products(x, kernels, shardings, dtype), (x, kernels)


def _gathered_products_bwd(shardings, dtype, res, cts):
    x, kernels = res
    split, whole = shardings
    # split on both sides of a barrier that waits for the cotangents:
    # else XLA's CSE, or the propagation of ``whole`` through the
    # barrier, turns this gather back into the forward's, kept alive
    x = jax.lax.with_sharding_constraint(x, split)
    x, cts = jax.lax.optimization_barrier((x, cts))
    x = jax.lax.with_sharding_constraint(x, split)
    xw = jax.lax.with_sharding_constraint(x, whole).astype(dtype)
    lead = tuple(range(x.ndim - 1))
    dks = tuple(
        jax.lax.dot_general(xw, ct, ((lead, lead), ((), ()))).astype(k.dtype)
        for k, ct in zip(kernels, cts))
    dx = None
    for k, ct in zip(kernels, cts):
        part = jax.lax.dot_general(
            ct, k.astype(dtype),
            ((tuple(range(x.ndim - 1, ct.ndim)), tuple(range(1, k.ndim))),
             ((), ())))
        dx = part if dx is None else jax.lax.add(dx, part)
    dx = split_tokens(dx, shardings)
    return dx.astype(x.dtype), dks


gathered_products.defvjp(_gathered_products_fwd, _gathered_products_bwd)


def batch_sharding(mesh, data_axes=("data", "fsdp")):
    """Sharding for a ``[batch, ...]`` array: batch dim split over the
    data-parallel axes (only the ones present on the mesh)."""
    present = tuple(a for a in data_axes if mesh.shape.get(a, 1) > 1)
    if not present:
        return NamedSharding(mesh, PartitionSpec())
    axes = present[0] if len(present) == 1 else present
    return NamedSharding(mesh, PartitionSpec(axes))


def shard_batch(batch, mesh, data_axes=("data", "fsdp"), leading_dims=0):
    """Place a host batch (pytree of np/jnp arrays, leading batch dim)
    onto the mesh, split over the data axes.

    Single-process: a straight ``device_put`` with the batch sharding.
    Multi-process: each host owns a slice of the global batch; assembled
    via ``make_array_from_process_local_data`` (the HBM landing zone of
    the reference's InputMode.SPARK feed path, SURVEY.md §2.3).

    Args:
      leading_dims: number of replicated axes *before* the batch dim —
        e.g. 1 for the ``[K, batch, ...]`` stacks that
        ``SyncTrainer.multi_step`` scans over.
    """
    base = batch_sharding(mesh, data_axes)
    spec = PartitionSpec(*(((None,) * leading_dims) + tuple(base.spec)))
    sharding = NamedSharding(mesh, spec)
    width = 1
    for a in data_axes:
        width *= mesh.shape.get(a, 1)

    def _check(x):
        ndim = getattr(x, "ndim", 0)
        n = x.shape[leading_dims] if ndim > leading_dims else 0
        if width > 1 and n % width != 0:
            raise ValueError(
                "batch dim {0} not divisible by data-parallel width {1}; "
                "pad or resize the batch (global batch must be a multiple "
                "of the data axes' product)".format(n, width)
            )
        return x

    if jax.process_count() == 1:
        return jax.tree.map(lambda x: jax.device_put(_check(x), sharding), batch)
    return jax.tree.map(
        lambda x: jax.make_array_from_process_local_data(
            sharding, np.asarray(_check(x))
        ),
        batch,
    )
