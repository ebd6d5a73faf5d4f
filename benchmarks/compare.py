"""The comparisons that decide ``correct``: what the timed path
produced, held against the plain reference run on the same inputs with
the same seeded weights (made again here, layer by layer — nothing the
program made is read)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import weights
from benchmarks.reference import dense_gqa as ref


def _pad_rows(samples, multiple):
    """Stack ``(prompt_ids, served_ids)`` pairs into ``tokens[R, L]``
    (zero-padded at the end, where causal attention cannot see it),
    ``served[R, L]`` holding the served id whose logits position ``t``
    predicts (-1 elsewhere)."""
    longest = max(len(p) + len(s) for p, s in samples)
    length = -(-longest // multiple) * multiple
    tokens = np.zeros((len(samples), length), np.int32)
    served = np.full((len(samples), length), -1, np.int32)
    for r, (p, s) in enumerate(samples):
        seq = np.concatenate([p, s]).astype(np.int32)
        tokens[r, :len(seq)] = seq
        served[r, len(p) - 1:len(seq) - 1] = s
    return tokens, served


@functools.partial(jax.jit, static_argnames=("model_items", "dtype", "mode"))
def _block_step(x, key, index, model_items, dtype, mode):
    model = dict(model_items)
    p = weights.block_params(model, key, index, jnp.dtype(dtype))
    positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
    return ref.block(x, p, model, positions, mode)


@functools.partial(jax.jit, static_argnames=("model_items", "dtype"))
def _embed(tokens, key, model_items, dtype):
    outer = weights.outer_params(dict(model_items), key, jnp.dtype(dtype))
    return ref.embed(tokens, outer)


@functools.partial(jax.jit, static_argnames=("model_items", "dtype", "mode"))
def _head(x, key, model_items, dtype, mode):
    model = dict(model_items)
    outer = weights.outer_params(model, key, jnp.dtype(dtype))
    return ref.head(x, outer, model, mode)


def _model_items(model):
    keep = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "num_hidden_layers", "vocab_size",
            "sliding_window", "rope_theta", "rms_norm_eps", "head_dim")
    return tuple(sorted((k, model[k]) for k in keep if k in model))


def reference_logits(model, seed, tokens, dtype, mode="f32"):
    """Logits ``[R, L, vocab]`` of the reference over ``tokens``, the
    weights drawn layer by layer from ``seed`` in ``dtype``."""
    items = _model_items(model)
    key = weights.seed_key(seed)
    x = _embed(jnp.asarray(tokens), key, items, dtype)
    for i in range(model["num_hidden_layers"]):
        x = _block_step(x, key, jnp.int32(i), items, dtype, mode)
    return _head(x, key, items, dtype, mode)


@jax.jit
def _gaps(logits, chosen, valid):
    """At each valid position, how far the chosen id's logit lies below
    the best one."""
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(
        logits, jnp.maximum(chosen, 0)[..., None], axis=-1)[..., 0]
    return jnp.where(valid, best - got, 0.0)


def served_gaps(model, seed, samples, dtype, control=False,
                rows_per_block=4, pad_multiple=128):
    """The widest gap, over every served token of ``samples``, by which
    the served token's reference logit lies below the reference's best
    — and, with ``control``, the same for the token that the reference
    computed in int8 puts first at the same positions.  Rows go through
    in blocks so the float32 activations fit."""
    tokens, served = _pad_rows(samples, pad_multiple)
    n = int((served >= 0).sum())
    out = {"tokens_compared": n, "served_gap_max": 0.0,
           "served_gap_mean": 0.0}
    if control:
        out.update(control_gap_max=0.0, control_gap_mean=0.0)
    for r0 in range(0, len(samples), rows_per_block):
        tok = tokens[r0:r0 + rows_per_block]
        srv = jnp.asarray(served[r0:r0 + rows_per_block])
        valid = srv >= 0
        logits = reference_logits(model, seed, tok, dtype)
        gap = _gaps(logits, srv, valid)
        out["served_gap_max"] = max(
            out["served_gap_max"], float(jnp.max(gap)))
        out["served_gap_mean"] += float(jnp.sum(gap)) / n
        if control:
            low = reference_logits(model, seed, tok, dtype, mode="int8")
            first = jnp.argmax(low, axis=-1).astype(jnp.int32)
            gap = _gaps(logits, first, valid)
            out["control_gap_max"] = max(
                out["control_gap_max"], float(jnp.max(gap)))
            out["control_gap_mean"] += float(jnp.sum(gap)) / n
    return out


# ----------------------------------------------------------------------
# training: losses, the first gradient, the parameters' change
# ----------------------------------------------------------------------


def leaf_paths(tree):
    """``[(name, leaf)]`` with names like ``block_0/attn/q/kernel``."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [
        ("/".join(str(getattr(k, "key", k)) for k in path), leaf)
        for path, leaf in flat
    ]


@jax.jit
def _norms(tree):
    return jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))),
        tree)


def leaf_norms(tree):
    """``{leaf name: Euclidean norm}`` as Python floats."""
    return {k: float(v) for k, v in leaf_paths(_norms(tree))}


def change_norms(params, model, seed, dtype="float32"):
    """Per leaf, the norm of ``params`` minus the seed's initial
    values, which are drawn again a block at a time (never kept: a
    model that fills its chips has no room for a second copy)."""
    dtype = jnp.dtype(dtype)
    key = weights.seed_key(seed)

    def gap(a, b):
        return jnp.sqrt(jnp.sum(jnp.square(
            a.astype(jnp.float32) - b.astype(jnp.float32))))

    @jax.jit
    def outer(part, key):
        return jax.tree.map(gap, part, weights.outer_params(model, key, dtype))

    @jax.jit
    def block(part, key, index):
        return jax.tree.map(
            gap, part, weights.block_params(model, key, index, dtype))

    out = outer({k: v for k, v in params.items()
                 if not k.startswith("block_")}, key)
    for i in range(model["num_hidden_layers"]):
        out["block_%d" % i] = block(params["block_%d" % i], key, jnp.int32(i))
    return {k: float(v) for k, v in leaf_paths(out)}


def worst_leaf_gap(got, want, skip=()):
    """The widest gap between the program's norm and the reference's,
    leaf by leaf, against the reference's norm of that leaf or of the
    median leaf, whichever is larger; and the leaf it sits on."""
    names = [k for k in want if k not in skip]
    median = float(np.median([want[k] for k in names]))
    worst, where = 0.0, None
    for k in names:
        gap = abs(got[k] - want[k]) / max(want[k], median, 1e-30)
        if not gap <= worst:      # a NaN is the worst there is
            worst, where = gap, k
    return worst, where


def still_leaves(grad_norms, share=1e-3):
    """Leaves whose reference gradient is nought to rounding — under a
    thousandth of the median leaf's — and so move under Adam by
    round-off alone: left out of the change comparison."""
    median = float(np.median(list(grad_norms.values())))
    return {k for k, v in grad_norms.items() if v < share * median}


def adamw_update(p, g, mu, nu, step, opt):
    """optax.adamw's arithmetic, written out (``step`` counts from 1)."""
    b1, b2 = opt["b1"], opt["b2"]
    mu = b1 * mu + (1 - b1) * g
    nu = b2 * nu + (1 - b2) * jnp.square(g)
    mhat = mu / (1 - b1 ** step)
    nhat = nu / (1 - b2 ** step)
    upd = mhat / (jnp.sqrt(nhat) + opt["eps"]) + opt["weight_decay"] * p
    return p - opt["learning_rate"] * upd, mu, nu


def reference_programs(model, mode, rows_sharding=None, spread=None):
    """The reference's four jitted pieces for a group of rows (laid out
    a row to a chip where ``rows_sharding`` is given): a block's
    forward, a block's backward (its forward run again inside), the
    head with the loss and its gradients, and the embedding's
    gradient."""
    def positions(x):
        return jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])

    def by_row(x):
        if rows_sharding is None:
            return x
        return jax.lax.with_sharding_constraint(x, rows_sharding)

    def stored(tree):
        # gradients leave a program laid out as they are stored
        if spread is None:
            return tree
        return jax.tree.map(
            lambda a: jax.lax.with_sharding_constraint(a, spread(a.shape)),
            tree)

    def one_block(x, p):
        return by_row(ref.block(by_row(x), p, model, positions(x), mode))

    fwd = jax.jit(one_block)

    @jax.jit
    def bwd(x, p, dy):
        _, vjp = jax.vjp(one_block, x, p)
        dx, dp = vjp(by_row(dy))
        return dx, stored(dp)

    @jax.jit
    def top(x, outer, tokens):
        def f(x, ln_f, lm_head):
            logits = ref.head(
                x, {"ln_f": ln_f, "lm_head": lm_head}, model, mode)[:, :-1]
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(
                logp, tokens[:, 1:, None], axis=-1)
            return jnp.mean(nll)

        loss, (dx, dln, dhead) = jax.value_and_grad(f, argnums=(0, 1, 2))(
            x, outer["ln_f"], outer["lm_head"])
        return loss, dx, stored(dln), stored(dhead)

    @jax.jit
    def bottom(tokens, dx, embedding):
        return stored(jnp.zeros_like(embedding).at[tokens].add(dx))

    return fwd, bwd, top, bottom


def train_reference(model, seed, batches, opt, mode="f32", rows=None,
                    devices=None):
    """Follow the program's first ``len(batches)`` optimizer steps with
    the plain reference: float32 parameters from the seed, the mean
    next-token loss over the step's rows, AdamW.  Layers go through one
    at a time (each block's forward is run again in its backward) and
    the rows a row to a chip over ``devices`` (one at a time where the
    chips do not divide them), with parameters, gradients and moments
    spread over the chips, so a full-width model fits.  ``rows`` limits
    every step to those rows of its batch (a planted fault).  Returns
    the losses, the first gradient's leaf norms and the leaf norms of
    the parameters' change."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    items = _model_items(model)
    model = dict(items)
    devices = list(devices or jax.devices()[:1])
    spread = spread_over(devices)
    rows_sharding = None
    if len(devices) > 1:
        rows_sharding = NamedSharding(
            Mesh(np.asarray(devices), ("x",)), PartitionSpec("x"))
    key = weights.seed_key(seed)
    n_layers = model["num_hidden_layers"]
    # everything stored is laid out over the chips from the start: no
    # array is ever whole on one
    params = weights.make_params(
        model, seed, jnp.float32,
        shardings=None if spread is None else (
            lambda shapes: jax.tree.map(lambda a: spread(a.shape), shapes)))

    def zeros(tree):
        return jax.tree.map(
            lambda x: jnp.zeros(x.shape, x.dtype, device=x.sharding), tree)

    mu, nu = zeros(params), zeros(params)
    programs = {
        True: reference_programs(model, mode, rows_sharding, spread),
        False: reference_programs(model, mode, None, spread),
    }
    add = jax.jit(
        lambda a, b, w: jax.tree.map(lambda x, y: x + w * y, a, b),
        donate_argnums=(0,))
    step_fn = jax.jit(
        lambda p, g, mu, nu, step: _adam_tree(p, g, mu, nu, step, opt))

    losses, grad_norms = [], None
    for step, batch in enumerate(batches, 1):
        batch = np.asarray(batch)
        use = list(range(len(batch)) if rows is None else rows)
        together = rows_sharding is not None and len(use) % len(devices) == 0
        groups = [use] if together else [[r] for r in use]
        fwd, bwd, top, bottom = programs[together]
        grads = zeros(params)
        loss_sum = 0.0
        for group in groups:
            w = len(group) / len(use)
            tokens = jnp.asarray(batch[group], jnp.int32)
            if together:
                tokens = jax.device_put(tokens, rows_sharding)
            xs = [ref.embed(tokens, params)]
            for i in range(n_layers):
                xs.append(fwd(xs[-1], params["block_%d" % i]))
            loss, dx, dln, dhead = top(xs.pop(), params, tokens)
            loss_sum += float(loss) * w
            # each piece is added to the stored gradient as it comes
            for name, piece in (("ln_f", dln), ("lm_head", dhead)):
                grads[name] = add(grads[name], piece, w)
            for i in reversed(range(n_layers)):
                name = "block_%d" % i
                dx, dp = bwd(xs.pop(), params[name], dx)
                grads[name] = add(grads[name], dp, w)
            grads["embedding"] = add(
                grads["embedding"],
                bottom(tokens, dx, params["embedding"]), w)
        losses.append(loss_sum)
        if grad_norms is None:
            grad_norms = leaf_norms(grads)
        params, mu, nu = step_fn(params, grads, mu, nu, float(step))
    change = change_norms(params, model, seed)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


def _adam_tree(p, g, mu, nu, step, opt):
    out = jax.tree.map(
        lambda p, g, mu, nu: adamw_update(p, g, mu, nu, step, opt),
        p, g, mu, nu)
    pick = lambda i: jax.tree.map(  # noqa: E731
        lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2)


def spread_over(devices):
    """Where the reference's stored arrays live: ``sharding(shape)``
    splits the first axis that the chips divide over all of them
    (replicated where none does), so that float32 parameters, gradients
    and AdamW moments of a model that one chip cannot hold fit on the
    host; None for one device."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    n = len(devices)
    if n == 1:
        return None
    mesh = Mesh(np.asarray(devices), ("x",))

    def sharding(shape):
        spec = [None] * len(shape)
        for axis, size in enumerate(shape):
            if size % n == 0:
                spec[axis] = "x"
                break
        return NamedSharding(mesh, PartitionSpec(*spec))

    return sharding


def train_checks(losses, grad_norms, change, want, loss_limit,
                 grad_limit, change_limit):
    """The numbers compared, each beside its limit, and where the worst
    leaves sit."""
    loss_gap = max(
        abs(a - b) / abs(b) for a, b in zip(losses, want["losses"]))
    grad_gap, grad_leaf = worst_leaf_gap(grad_norms, want["grad_norms"])
    skip = still_leaves(want["grad_norms"])
    change_gap, change_leaf = worst_leaf_gap(
        change, want["change_norms"], skip)
    checks = {
        "loss_gap_max": {"value": float(loss_gap), "limit": loss_limit},
        "grad_norm_gap_worst_leaf": {
            "value": float(grad_gap), "limit": grad_limit},
        "change_norm_gap_worst_leaf": {
            "value": float(change_gap), "limit": change_limit},
    }
    detail = {
        "losses": [float(x) for x in losses],
        "reference_losses": [float(x) for x in want["losses"]],
        "grad_worst_leaf": grad_leaf, "change_worst_leaf": change_leaf,
        "leaves_left_out_of_change": sorted(skip),
    }
    return checks, detail
