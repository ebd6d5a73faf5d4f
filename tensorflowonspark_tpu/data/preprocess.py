"""On-device input preprocessing: the compute half of the narrow-dtype
data plane (docs/data_plane.md).

The wire half (:class:`~tensorflowonspark_tpu.data.columnar.WireSpec`,
the columnar feed, the shm ring) keeps image/int-like columns in their
STORAGE dtype — uint8 pixels stay uint8 from the Spark row to the HBM
DMA, cutting host↔device bytes up to 4x vs the old promote-to-float32-at-
ingest.  Something still has to widen them before the matmuls; doing it
on the host re-inflates the transfer, so this module builds a small
jit-traceable graph (cast / scale / offset / mean-sub / std-div,
optional center-crop and random flip) that runs fused IN FRONT of the
train or predict step — the cast happens in HBM ("TensorFlow: A system
for large-scale machine learning" attributes much of its input-pipeline
headroom to exactly this move).

Wired through:

- ``prefetch_to_device(..., preprocess=...)`` (data/feed.py) — applied
  on the device-resident batch after the async ``device_put``;
- ``SyncTrainer(device_preprocess=...)`` (parallel/dp.py) — traced into
  the jitted train step (and the fused multi-step scan body);
- ``serving.load_predictor(..., preprocess=...)`` /
  ``serving.with_preprocess`` — a jitted stage in front of the
  predictor, also resolvable from the serving export's metadata
  (``save_for_serving(..., extra_metadata={"preprocess": {...}})``);
- ``TFEstimator/TFModel`` ``setPreprocess`` params (pipeline.py).

Numerics contract: ``make_preprocess(dtype, scale, mean, std)`` on a
uint8 batch matches the host-side ``x.astype(np.float32) * scale``
path to float32 tolerance (parity-tested in tests/test_preprocess.py).
"""

import logging

import numpy as np

logger = logging.getLogger(__name__)

#: dtypes the default column selection treats as "narrow wire" inputs
#: that need widening (labels/indices are typically int32/int64 and are
#: left alone)
NARROW_DTYPES = ("uint8", "int8", "uint16", "int16")


def _is_narrow(a):
    try:
        return np.dtype(getattr(a, "dtype", None)).name in NARROW_DTYPES
    except TypeError:
        return False


def make_preprocess(
    columns=None,
    dtype="float32",
    scale=None,
    offset=None,
    mean=None,
    std=None,
    crop=None,
    flip=False,
):
    """Build a jit-traceable batch preprocess ``fn(batch[, rng])``.

    ``batch`` may be a single array, a tuple of columns, or a dict of
    named columns; the transform applies to the selected columns and
    passes the rest through untouched.

    Args:
      columns: which entries to transform — a list of names (dict
        batches) or indices (tuple batches).  Default ``None`` selects
        every column with a NARROW wire dtype (uint8/int8/uint16/int16)
        — the columns the wire plane deliberately did not widen; int32+
        label/index columns pass through.
      dtype: compute dtype the selected columns are cast to.
      scale / offset: ``x * scale + offset`` after the cast (e.g.
        ``scale=1/255`` for uint8 pixels).
      mean / std: ``(x - mean) / std`` after scale/offset (arrays
        broadcast, e.g. per-channel ImageNet stats).
      crop: ``(h, w)`` center crop of axes 1 and 2 (NHWC batches).
      flip: random horizontal flip (axis 2) per row — requires the
        ``rng`` argument at call time; with ``rng=None`` the flip is
        skipped (the deterministic eval/serving path).

    Returns ``fn(batch, rng=None) -> batch`` built from jax.numpy ops —
    trace it under ``jax.jit`` (the wiring points above do) so the
    widening runs on device.
    """
    import jax
    import jax.numpy as jnp

    out_dtype = jnp.dtype(dtype)

    def _one(x, rng):
        x = jnp.asarray(x)
        x = x.astype(out_dtype)
        if scale is not None:
            x = x * jnp.asarray(scale, out_dtype)
        if offset is not None:
            x = x + jnp.asarray(offset, out_dtype)
        if mean is not None:
            x = x - jnp.asarray(mean, out_dtype)
        if std is not None:
            x = x / jnp.asarray(std, out_dtype)
        if crop is not None:
            ch, cw = crop
            if x.ndim < 3:
                raise ValueError(
                    "crop needs [N, H, W, ...] batches; got shape %s"
                    % (x.shape,)
                )
            h0 = (x.shape[1] - ch) // 2
            w0 = (x.shape[2] - cw) // 2
            if h0 < 0 or w0 < 0:
                raise ValueError(
                    "crop %s larger than input %s" % (crop, x.shape)
                )
            x = x[:, h0:h0 + ch, w0:w0 + cw]
        if flip and rng is not None:
            if x.ndim < 3:
                raise ValueError(
                    "flip needs [N, H, W, ...] batches; got shape %s"
                    % (x.shape,)
                )
            coin = jax.random.bernoulli(rng, 0.5, (x.shape[0],))
            shape = (x.shape[0],) + (1,) * (x.ndim - 1)
            x = jnp.where(coin.reshape(shape), jnp.flip(x, axis=2), x)
        return x

    def _selected(key, value):
        if columns is not None:
            return key in columns
        return _is_narrow(value)

    def preprocess(batch, rng=None):
        if isinstance(batch, dict):
            return {
                k: _one(v, rng) if _selected(k, v) else v
                for k, v in batch.items()
            }
        if isinstance(batch, (tuple, list)):
            return tuple(
                _one(v, rng) if _selected(i, v) else v
                for i, v in enumerate(batch)
            )
        return _one(batch, rng)

    if flip:
        return preprocess

    # deterministic graph: expose a single-arg signature so rng-probing
    # wiring (SyncTrainer's takes_rng) never forks its step-rng chain
    # for a preprocess that cannot consume one
    def deterministic(batch):
        return preprocess(batch, None)

    return deterministic


def resolve_preprocess(spec):
    """Normalize a preprocess argument: a callable passes through, a
    dict becomes ``make_preprocess(**spec)`` (the form serving-export
    metadata and pipeline params carry — JSON-serializable), ``None``
    stays ``None``."""
    if spec is None or callable(spec):
        return spec
    if isinstance(spec, dict):
        return make_preprocess(**spec)
    raise TypeError(
        "preprocess must be a callable or a make_preprocess kwargs "
        "dict, got {0!r}".format(type(spec))
    )


def takes_rng(fn):
    """True when ``fn`` accepts a second (rng) argument — the contract
    probe the train-step wiring uses to decide whether to split its
    step rng for augmentation."""
    import inspect

    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        return False
    params = [
        p for p in sig.parameters.values()
        if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
    ]
    if any(
        p.kind == p.VAR_POSITIONAL for p in sig.parameters.values()
    ):
        return True
    return len(params) >= 2 or "rng" in sig.parameters
