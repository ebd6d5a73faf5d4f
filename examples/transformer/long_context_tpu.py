"""Long-context Transformer LM: sequence parallelism over the mesh.

No reference analogue — the reference predates long-context training
entirely (SURVEY.md §5 'Long-context / sequence parallelism: absent').
This app trains the framework's flagship Transformer with the sequence
axis sharded across devices, so each device holds ``seq/N`` of every
activation: ring attention rotates KV blocks over ICI (``ppermute``)
or Ulysses re-shards seq↔heads with all-to-alls — pick with
``--attention``.

Run (CPU, 8 virtual chips stand in for a pod slice):
    python examples/transformer/long_context_tpu.py \
        --virtual_devices 8 --seq_len 1024 --steps 5

On a real slice drop ``--virtual_devices``; the same mesh spec rides
ICI.
"""

import argparse
import os
import sys

sys.path.insert(
    0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--virtual_devices", type=int, default=0,
                   help="N virtual CPU devices (testing without a pod)")
    p.add_argument("--attention", choices=("ring", "ulysses", "flash", "dot"),
                   default="ring",
                   help="ring/ulysses shard the sequence across chips; "
                        "flash streams K/V blocks on one chip (pallas)")
    p.add_argument("--seq_len", type=int, default=1024)
    p.add_argument("--batch_size", type=int, default=2)
    p.add_argument("--num_layers", type=int, default=2)
    p.add_argument("--embed_dim", type=int, default=128)
    p.add_argument("--num_heads", type=int, default=8)
    p.add_argument("--steps", type=int, default=5)
    p.add_argument("--seq_parallel", type=int, default=0,
                   help="size of the seq mesh axis (default: all devices)")
    args = p.parse_args()

    if args.virtual_devices:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=%d" % args.virtual_devices
        )

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflowonspark_tpu.models import transformer as tr
    from tensorflowonspark_tpu.parallel import dp
    from tensorflowonspark_tpu.parallel.mesh import build_mesh

    import math

    n_dev = len(jax.devices())
    if args.attention in ("ring", "ulysses"):
        seq_par = args.seq_parallel or n_dev
        if n_dev % seq_par:
            sys.exit(
                "--seq_parallel {0} must divide the device count {1}".format(
                    seq_par, n_dev
                )
            )
        data_par = n_dev // seq_par
        if args.batch_size % data_par:
            sys.exit(
                "--batch_size {0} must divide by the data axis {1} "
                "(= devices {2} / seq_parallel {3}); raise batch_size or "
                "seq_parallel".format(
                    args.batch_size, data_par, n_dev, seq_par
                )
            )
    else:
        # flash/dot ignore the seq axis entirely: all devices go to data
        # parallelism, capped so the batch still divides the data axis
        if args.seq_parallel and args.seq_parallel != 1:
            sys.exit(
                "--seq_parallel only applies to ring/ulysses attention"
            )
        seq_par = 1
        data_par = math.gcd(args.batch_size, n_dev)
    used = data_par * seq_par
    if used < n_dev:
        print(
            "note: %d of %d devices idle (batch %d limits data "
            "parallelism to %d); raise --batch_size to use them"
            % (n_dev - used, n_dev, args.batch_size, data_par)
        )
    mesh = build_mesh(
        {"data": data_par, "seq": seq_par}, devices=jax.devices()[:used]
    )
    print("mesh:", dict(mesh.shape), "attention:", args.attention)

    cfg = tr.TransformerConfig(
        vocab_size=1024,
        num_layers=args.num_layers,
        num_heads=args.num_heads,
        head_dim=args.embed_dim // args.num_heads,
        embed_dim=args.embed_dim,
        mlp_dim=args.embed_dim * 4,
        max_seq_len=args.seq_len,
        dtype="float32" if args.virtual_devices else "bfloat16",
        attention_impl=args.attention,
        mesh=mesh if args.attention in ("ring", "ulysses") else None,
    )
    model = tr.Transformer(cfg)

    # synthetic next-token data with learnable structure (tok_{t+1} =
    # tok_t + 1 mod vocab) so loss visibly drops
    rng_np = np.random.RandomState(0)
    start = rng_np.randint(0, 1024, size=(args.batch_size, 1))
    tokens = (start + np.arange(args.seq_len)) % 1024

    params = model.init(
        jax.random.PRNGKey(0), jnp.asarray(tokens, jnp.int32)
    )["params"]
    trainer = dp.SyncTrainer(
        tr.loss_fn(model),
        optax.adam(1e-3),
        mesh=mesh,
        annotations=tr.logical_axes(params),
        data_axes=("data",),
    )
    state = trainer.create_state(params)

    import time

    for i in range(args.steps):
        t0 = time.perf_counter()
        state, metrics = trainer.step(
            state, {"tokens": tokens.astype(np.int32)}, jax.random.PRNGKey(i)
        )
        loss = float(metrics["loss"])
        print(
            "step %d loss %.4f (%.0f ms)"
            % (i, loss, 1e3 * (time.perf_counter() - t0))
        )
    if args.attention in ("ring", "ulysses"):
        print("done: seq_len=%d over %d-way sequence parallelism" % (
            args.seq_len, seq_par))
    else:
        print("done: seq_len=%d single-chip (%s attention)" % (
            args.seq_len, args.attention))


if __name__ == "__main__":
    main()
