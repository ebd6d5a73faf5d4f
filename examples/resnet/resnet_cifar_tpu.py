"""ResNet56 on CIFAR-10-shaped data: the throughput benchmark workload.

Reference-parity app for ``examples/resnet/resnet_cifar_spark.py`` +
``resnet_cifar_dist.py`` (reference: examples/resnet/resnet_cifar_dist.py:
33-35 batch 128 defaults, :218-225 MWMS wiring; throughput measured like
the official-models ``TimeHistory`` ``exp_per_second``, reference:
examples/resnet/common.py:175-246).  Synthetic-input mode mirrors
``common.py:315-363``.

Single-node it trains on the host's own chips; under
``--cluster_size N`` it runs through the cluster API with one mesh per
node (DP over each node's chips, the multi-host axis via
``jax.distributed``).

Run (CPU smoke):
    JAX_PLATFORMS=cpu python examples/resnet/resnet_cifar_tpu.py \
        --batch_size 32 --steps 5
"""

import argparse
import os
import sys
import time

sys.path.insert(
    0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
)


def main_fun(args, ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflowonspark_tpu.models import resnet
    from tensorflowonspark_tpu.parallel import dp
    from tensorflowonspark_tpu.parallel.mesh import build_mesh

    if ctx is not None:
        ctx.initialize_distributed()

    platform = jax.devices()[0].platform
    dtype = "bfloat16" if platform in ("tpu", "gpu") else "float32"
    # every arch-derived value set in one place
    if args.arch == "resnet50":
        # ImageNet-class workload (reference: resnet_imagenet_main.py)
        model = resnet.ResNet50(num_classes=1000, dtype=dtype)
        hw, num_classes, dataset_size = args.image_size, 1000, 1_281_167
        name = "resnet50"
    else:
        model = resnet.ResNetCIFAR(depth=args.depth, dtype=dtype)
        hw, num_classes, dataset_size = 32, 10, 50_000
        name = "resnet%d" % args.depth
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, hw, hw, 3)))

    # LR schedule shape follows the reference defaults (0.1 → /10 at
    # epoch boundaries 91/136, reference: resnet_cifar_dist.py:33-35);
    # epoch length tracks the modeled dataset (CIFAR 50k / ImageNet 1.28M)
    steps_per_epoch = max(1, dataset_size // args.batch_size)
    schedule = optax.piecewise_constant_schedule(
        0.1, {91 * steps_per_epoch: 0.1, 136 * steps_per_epoch: 0.1}
    )
    trainer = dp.SyncTrainer(
        resnet.loss_fn(model),
        optax.sgd(schedule, momentum=0.9),
        mesh=build_mesh(),
        has_model_state=True,
    )
    state = trainer.create_state(
        variables["params"], {"batch_stats": variables["batch_stats"]}
    )

    # synthetic image batch (reference: common.py:315-363)
    rng = np.random.RandomState(0)
    x = rng.rand(args.batch_size, hw, hw, 3).astype(np.float32)
    y = (np.arange(args.batch_size) % num_classes).astype(np.int32)

    warmup = min(3, args.steps)
    for i in range(warmup):
        state, metrics = trainer.step(state, (x, y), jax.random.PRNGKey(i))
    jax.block_until_ready(metrics["loss"])

    t0 = time.perf_counter()
    for i in range(args.steps):
        state, metrics = trainer.step(state, (x, y), jax.random.PRNGKey(i))
    jax.block_until_ready(metrics["loss"])
    dt = time.perf_counter() - t0
    ips = args.batch_size * args.steps / dt
    print(
        "%s %s: %d steps, %.1f images/sec, final loss %.4f"
        % (name, platform, args.steps, ips, float(metrics["loss"]))
    )
    return ips


def main():
    from tensorflowonspark_tpu import setup_logging

    setup_logging()
    p = argparse.ArgumentParser()
    p.add_argument("--cluster_size", type=int, default=0,
                   help="0 = run in-process; N = run through the cluster API")
    p.add_argument("--arch", choices=("cifar", "resnet50"), default="cifar")
    p.add_argument("--image_size", type=int, default=224,
                   help="input size for --arch resnet50")
    p.add_argument("--depth", type=int, default=56)
    p.add_argument("--batch_size", type=int, default=128)
    p.add_argument("--steps", type=int, default=30)
    args = p.parse_args()

    if args.cluster_size <= 0:
        main_fun(args, None)
        return

    from tensorflowonspark_tpu.cluster import cluster as tfcluster

    cluster = tfcluster.run(
        args.cluster_size,
        main_fun,
        args,
        num_executors=args.cluster_size,
        input_mode=tfcluster.InputMode.TENSORFLOW,
    )
    cluster.shutdown()


if __name__ == "__main__":
    main()
