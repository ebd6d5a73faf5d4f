"""Version/backend compatibility shims.

The reference's ``compat.py`` papered over TF 2.0/2.1 API drift
(``export_saved_model``, ``disable_auto_shard``, ``is_gpu_available`` —
reference: tensorflowonspark/compat.py:10-31).  This build targets ONE
installed JAX (0.9.x), so nothing here probes for API spellings: a
chief-aware export helper matching the reference's calling convention, an
accelerator probe, the pallas interpret switch, and a no-op kept for
source compatibility with code ported from the reference.
"""

import logging

logger = logging.getLogger(__name__)


def shard_map(f, *, mesh, in_specs, out_specs, **kwargs):
    """``jax.shard_map``: the one spelling every in-repo call site uses
    (ops/flash_attention.py, ops/ring_attention.py, ops/ulysses.py,
    parallel/pp.py, parallel/hier_ps.py)."""
    import jax

    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kwargs
    )


def axis_size(axis_name):
    """Static size of a bound mesh axis (inside ``shard_map``)."""
    from jax import lax

    return lax.axis_size(axis_name)


def pallas_interpret():
    """True off-TPU: the repo's pallas kernels (flash/gmm/paged
    attention) run under ``interpret=True`` on CPU so tier-1 exercises
    the real kernel path without TPU hardware.  THE single switch — a
    process that expected a chip must assert its platform itself
    (``chip_smoke.py`` does), because with ``JAX_PLATFORMS`` unset JAX
    falls back to CPU when TPU init fails and this then interprets."""
    import jax

    return jax.default_backend() != "tpu"


def export_saved_model(params, export_dir, is_chief=False, metadata=None):
    """Chief-only serving export (reference: compat.py:10-17 — chief
    exported, workers wrote to a dummy dir; here non-chiefs no-op)."""
    if not is_chief:
        logger.info("skipping export on non-chief node")
        return None
    from tensorflowonspark_tpu.checkpoint import save_for_serving

    return save_for_serving(export_dir, params, extra_metadata=metadata)


def disable_auto_shard(options):  # noqa: ARG001 - source-compat no-op
    """No-op: tf.data auto-sharding has no JAX analogue — feed sharding
    is explicit via partitions / DataFeed (reference: compat.py:20-24)."""
    return options


def is_accelerator_available():
    """True when a TPU/GPU backend is live (reference: compat.py:27-31
    ``is_gpu_available``)."""
    import jax

    try:
        return jax.devices()[0].platform in ("tpu", "gpu")
    except RuntimeError:
        return False


#: Reference-name alias (reference: compat.py:27)
is_gpu_available = is_accelerator_available
