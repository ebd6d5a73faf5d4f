"""Pipeline-parallel LM training: GPipe / 1F1B / interleaved-1F1B.

No reference analogue — the reference has no pipeline parallelism
(SURVEY.md §2.3).  This app stacks a small decoder LM's blocks over the
``pipe`` mesh axis with :class:`tensorflowonspark_tpu.parallel.pp.
PipelineTrainer` and trains on synthetic next-token data under any of
the three schedules; ``--schedule interleaved`` runs Megatron's
virtual-stage schedule (each device owns ``--interleave`` chunks of the
depth, bubble ÷ v), whose handoff-buffer geometry is proven safe at
build time (``pp_schedule.analyze_program``).

Run (CPU, 8 virtual chips stand in for a pod slice):
    python examples/transformer/pipeline_tpu.py \
        --virtual_devices 8 --schedule interleaved --steps 5

On a real slice drop ``--virtual_devices``.
"""

import argparse
import os
import sys

sys.path.insert(
    0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
)


def synthetic_tokens(batch, seq, vocab, seed=0):
    """Deterministic learnable stream: next token = (token + 1) % vocab
    with a fixed random start per row."""
    import numpy as np

    r = np.random.RandomState(seed)
    start = r.randint(0, vocab, size=(batch, 1))
    ramp = np.arange(seq)[None, :]
    return ((start + ramp) % vocab).astype(np.int32)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--virtual_devices", type=int, default=0)
    p.add_argument("--schedule", default="1f1b",
                   choices=("gpipe", "1f1b", "interleaved"))
    p.add_argument("--interleave", type=int, default=2)
    p.add_argument("--pipe", type=int, default=4, help="pipeline stages")
    p.add_argument("--num_layers", type=int, default=8)
    p.add_argument("--embed_dim", type=int, default=64)
    p.add_argument("--mlp_dim", type=int, default=128)
    p.add_argument("--vocab", type=int, default=64)
    p.add_argument("--seq_len", type=int, default=32)
    p.add_argument("--batch_size", type=int, default=16)
    p.add_argument("--microbatches", type=int, default=4)
    p.add_argument("--steps", type=int, default=5)
    args = p.parse_args()

    if args.virtual_devices:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=%d"
            % args.virtual_devices
        )

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflowonspark_tpu.parallel import pp
    from tensorflowonspark_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(data=-1, pipe=args.pipe))
    D, F = args.embed_dim, args.mlp_dim
    rng = np.random.RandomState(0)

    def layer_fn(lp, h):
        # pre-norm MLP block (the repeated unit; attention-free keeps
        # the example small — PipelineTrainer only sees layer_fn)
        mu = jnp.mean(h, axis=-1, keepdims=True)
        var = jnp.mean((h - mu) ** 2, axis=-1, keepdims=True)
        n = (h - mu) * jax.lax.rsqrt(var + 1e-6)
        return h + jnp.tanh(n @ lp["wi"]) @ lp["wo"]

    layers = [
        {
            "wi": jnp.asarray(rng.randn(D, F).astype(np.float32) * 0.1),
            "wo": jnp.asarray(rng.randn(F, D).astype(np.float32) * 0.1),
        }
        for _ in range(args.num_layers)
    ]
    v = args.interleave if args.schedule == "interleaved" else 1
    params = {
        "stages": pp.stack_stage_params(layers, args.pipe, interleave=v),
        "first": {
            "emb": jnp.asarray(
                rng.randn(args.vocab, D).astype(np.float32) * 0.1
            )
        },
        "last": {
            "head": jnp.asarray(
                rng.randn(D, args.vocab).astype(np.float32) * 0.1
            )
        },
    }

    def first_fn(fp, batch):
        return fp["emb"][batch["tokens"]]

    def last_fn(lp, h, batch):
        logits = h[:, :-1] @ lp["head"]
        targets = batch["tokens"][:, 1:]
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
        loss = jnp.mean(nll)
        return loss, {"nll": loss}

    trainer = pp.PipelineTrainer(
        layer_fn, first_fn, last_fn, optax.adam(3e-3), mesh,
        num_microbatches=args.microbatches,
        schedule=args.schedule, interleave=args.interleave,
    )
    state = trainer.create_state(params)
    tokens = synthetic_tokens(args.batch_size, args.seq_len, args.vocab)
    for step in range(args.steps):
        state, metrics = trainer.step(state, {"tokens": tokens})
        print("step %d schedule=%s loss=%.4f"
              % (step, args.schedule, float(metrics["loss"])))


if __name__ == "__main__":
    main()
