"""Gradient compression codecs for the PS/DP communication plane.

The async-PS wire (``parallel/ps.py``) ships every gradient and every
parameter reply as raw float32 across the host↔device transfer and
the TCP fabric — the structural cost of the async path (a per-step
device->host gradient transfer).  This module attacks the *bytes* axis:

- :class:`Int8Codec` — per-tensor symmetric int8 quantization (4x
  fewer wire bytes than float32).  Lossy; pair with
  :class:`ErrorFeedback` so the quantization error is accumulated
  client-side and re-injected into the next step's gradient (the
  EF-SGD construction: the *running sum* of what crossed the wire
  tracks the running sum of the true gradients, which preserves
  convergence where naive quantization stalls).
- :class:`TopKCodec` — magnitude top-k sparsification; wire format is
  (indices, values) pairs.  Much higher compression (k/n of the
  values + index overhead); always run it under error feedback, the
  dropped (n-k) coordinates are *all* error.
- :class:`NoneCodec` — identity, so codec choice is uniform plumbing.

Codecs are numpy-only and deterministic: the PS server decodes with the
same arithmetic the client used to compute its residual, so the two
sides agree bit-for-bit on what crossed the wire (the delta-reply path
in ``parallel/ps.py`` relies on this to keep the server's per-connection
client view drift-free).

Wire integration: ``encode`` returns ``(parts, meta)`` where ``parts``
is a list of C-contiguous numpy arrays (the payloads laid on the
socket) and ``meta`` is a small JSON-able dict; ``decode(parts, meta)``
reconstructs the dense array.  ``parallel/ps.py`` frames these per
tensor (see ``send_msg``'s codec path).
"""

import numpy as np

__all__ = [
    "CODECS",
    "Codec",
    "ErrorFeedback",
    "Int8Codec",
    "NoneCodec",
    "TopKCodec",
    "dtype_str",
    "encoded_nbytes",
    "get_codec",
    "resolve_dtype",
]


def dtype_str(dt):
    """Wire-safe dtype spelling.  ``dtype.str`` round-trips for every
    builtin numpy dtype, but extension dtypes (``ml_dtypes.bfloat16``,
    the gradient dtype of bf16 training) stringify as an opaque void
    (``'<V2'``) that ``np.dtype()`` resolves to raw bytes — a silent
    corruption, not an error.  For those the registered NAME
    (``'bfloat16'``) is the round-trippable spelling."""
    dt = np.dtype(dt)
    s = dt.str
    try:
        if np.dtype(s) == dt:
            return s
    except TypeError:
        pass
    return dt.name


def resolve_dtype(s):
    """Inverse of :func:`dtype_str` (``np.dtype`` accepts both the
    ``.str`` and the registered-name spellings)."""
    return np.dtype(str(s))


class Codec(object):
    """Base codec: ``encode(arr) -> (parts, meta)``, ``decode`` inverts.

    ``parts`` arrays must be C-contiguous (they go straight onto the
    socket as memoryviews); ``meta`` must be JSON-able.
    """

    name = None

    def encode(self, arr):
        raise NotImplementedError

    def decode(self, parts, meta):
        raise NotImplementedError

    def spec(self):
        """JSON-able constructor spec, ``[name, kwargs]`` — what the
        client advertises when negotiating a reply codec."""
        return [self.name, {}]


class NoneCodec(Codec):
    """Identity codec: one part, the array itself."""

    name = "none"

    def encode(self, arr):
        arr = np.ascontiguousarray(arr)
        return [arr], {"dtype": dtype_str(arr.dtype), "shape": list(arr.shape)}

    def decode(self, parts, meta):
        return parts[0]


class Int8Codec(Codec):
    """Per-tensor symmetric int8 quantization.

    ``q = round(x / scale)`` with ``scale = max|x| / 127`` — zero maps
    to zero exactly (gradients are zero-heavy) and the dynamic range
    adapts per tensor per message.  float32 → int8 is a 4x wire-byte
    reduction; the scale rides in the JSON meta.
    """

    name = "int8"

    def encode(self, arr):
        arr = np.ascontiguousarray(arr)
        dtype = arr.dtype
        f = arr.astype(np.float32, copy=False)
        amax = float(np.max(np.abs(f))) if f.size else 0.0
        scale = amax / 127.0 if amax > 0 else 1.0
        q = np.clip(np.rint(f / scale), -127, 127).astype(np.int8)
        return [q], {
            "dtype": dtype_str(dtype),
            "shape": list(arr.shape),
            "scale": scale,
        }

    def decode(self, parts, meta):
        q = parts[0].reshape(meta["shape"])
        out = q.astype(np.float32) * np.float32(meta["scale"])
        return out.astype(resolve_dtype(meta["dtype"]), copy=False)


class TopKCodec(Codec):
    """Magnitude top-k sparsification: ship the k largest-|x| entries
    as (flat indices, values); the receiver scatters into zeros.

    Args:
      ratio: fraction of entries kept (``k = ceil(ratio * n)``, min 1).
      min_size: tensors with fewer elements ship dense (index overhead
        would exceed the savings on tiny biases).
    """

    name = "topk"

    def __init__(self, ratio=0.01, min_size=1024):
        if not 0.0 < ratio <= 1.0:
            raise ValueError("topk ratio must be in (0, 1], got %r" % ratio)
        self.ratio = float(ratio)
        self.min_size = int(min_size)

    def spec(self):
        return [self.name, {"ratio": self.ratio, "min_size": self.min_size}]

    def encode(self, arr):
        arr = np.ascontiguousarray(arr)
        dtype = arr.dtype
        flat = arr.reshape(-1).astype(np.float32, copy=False)
        n = flat.size
        if n <= self.min_size:
            dense = np.ascontiguousarray(arr)
            return [dense], {
                "dtype": dtype_str(dtype),
                "shape": list(arr.shape),
                "dense": True,
            }
        k = max(1, int(np.ceil(self.ratio * n)))
        # argpartition is O(n); indices sorted afterwards so the wire
        # format is canonical (equal inputs -> equal bytes)
        idx = np.argpartition(np.abs(flat), n - k)[n - k:]
        idx = np.sort(idx).astype(np.int64 if n > np.iinfo(np.int32).max
                                  else np.int32)
        vals = np.ascontiguousarray(flat[idx])
        idx = np.ascontiguousarray(idx)
        return [idx, vals], {
            "dtype": dtype_str(dtype),
            "shape": list(arr.shape),
            "k": int(k),
        }

    def decode(self, parts, meta):
        shape = meta["shape"]
        dtype = resolve_dtype(meta["dtype"])
        if meta.get("dense"):
            return parts[0].reshape(shape)
        idx, vals = parts
        out = np.zeros(int(np.prod(shape)) if shape else 1, np.float32)
        out[idx] = vals
        return out.reshape(shape).astype(dtype, copy=False)


CODECS = {c.name: c for c in (NoneCodec, Int8Codec, TopKCodec)}


def get_codec(spec):
    """Resolve a codec spec: an instance passes through; a name or a
    ``(name, kwargs)`` pair constructs from :data:`CODECS` (named specs
    only — never deserialized code, the same hardening rule as the PS
    optimizers)."""
    if spec is None:
        return None
    if isinstance(spec, Codec):
        return spec
    if isinstance(spec, str):
        name, kwargs = spec, {}
    else:
        name, kwargs = spec[0], (spec[1] if len(spec) > 1 else None) or {}
    if name not in CODECS:
        raise ValueError(
            "unknown codec {0!r}; supported: {1}".format(name, sorted(CODECS))
        )
    return CODECS[name](**kwargs)


def encoded_nbytes(parts):
    """Payload bytes a parts list lays on the wire (headers excluded)."""
    return sum(int(p.nbytes) for p in parts)


class ErrorFeedback(object):
    """Client-side error feedback around a lossy codec.

    Per tensor name, the residual ``r`` accumulates what compression
    dropped; each step encodes ``g + r`` and keeps the new remainder:

        e = encode(g + r);  r' = (g + r) - decode(e)

    so the sum of decoded messages telescopes to the sum of true
    gradients — quantization error is *delayed*, never lost (the
    memory-compensated SGD construction; convergence-parity is tested
    on a quadratic bowl in ``tests/test_compress.py`` and end-to-end
    against sync SGD in ``tests/test_ps.py``).

    Thread-safety: each name's residual is read and written by exactly
    one caller at a time (the PS client's shard workers partition the
    name space), which is the only discipline required.
    """

    def __init__(self, codec):
        self.codec = get_codec(codec)
        if self.codec is None or isinstance(self.codec, NoneCodec):
            raise ValueError("error feedback requires a lossy codec")
        self._residual = {}

    @property
    def name(self):
        return self.codec.name

    def spec(self):
        return self.codec.spec()

    def encode_named(self, name, arr):
        """Encode ``arr`` under the accumulated residual for ``name``."""
        arr = np.asarray(arr)
        f = arr.astype(np.float32, copy=True)
        r = self._residual.get(name)
        if r is not None and r.shape == f.shape:
            f += r
        parts, meta = self.codec.encode(f)
        approx = self.codec.decode(
            [p.copy() for p in parts], meta
        ).astype(np.float32, copy=False)
        # the residual MUST stay float32: a bf16 residual would round
        # away exactly the small corrections error feedback exists to
        # carry (tested in tests/test_compress.py::TestBfloat16)
        self._residual[name] = f - approx
        # the receiver reconstructs in the original dtype
        meta = dict(meta, dtype=dtype_str(arr.dtype))
        return parts, meta

    def decode(self, parts, meta):
        return self.codec.decode(parts, meta)

    def reset(self):
        self._residual.clear()
