"""Asynchronous parameter-server data parallelism.

The reference's async-DP mode delegated everything to TensorFlow's PS
runtime: ``num_ps`` executors ran ``tf.train.Server`` processes that the
framework kept pinned via a control-queue block, with
``ParameterServerStrategy`` in user code (reference:
TFSparkNode.py:409-426, TFCluster.py:186-194,
examples/mnist/estimator/mnist_spark_streaming.py:88).  TPUs have no
native PS runtime, so this module *is* the PS system (SURVEY.md §7
'Hard parts: Async PS on TPU'):

- **ParamServerShard** — a TCP service holding a shard of the model's
  leaves in host memory, applying updates with its own numpy optimizer
  (the parameter-host-over-DCN design: PS traffic rides the data-center
  network while each worker's compute stays on its chips).
- **PSClient** — worker-side: partitions a params pytree across shards
  (size-balanced), then ``push_pull(grads)`` ships gradients and
  returns fresh params in one round trip per shard (DistBelief-style
  async SGD; no barrier between workers, stale gradients by design).
- **run_server(ctx)** — what a ps-role node runs inside ``main_fun``
  (the ``server.join()`` analogue, reference: TFNode.py:120-129): binds
  the clusterspec's ps address and serves until STOP/teardown.
- **AsyncTrainer** — worker-side convenience wrapping grad computation
  (jit on the local chips) + push_pull.

Wire protocol: 4-byte BE header length + JSON header + raw tensor
bytes (no pickle — same hardening rationale as
:mod:`tensorflowonspark_tpu.cluster.reservation`).  Optimizers are
named specs (``("adam", {"learning_rate": 1e-3})``) resolved against
the server's own numpy implementations, never deserialized code.
Leafwise optimizers only (sgd/momentum/adagrad/adam): each shard
updates its leaves independently, which is exact for these rules.

Gradient-plane extensions (docs/communication.md):

- **Codecs** — a tensor entry may carry a ``codec`` name plus per-part
  metadata; payloads are the codec's encoded parts
  (:mod:`tensorflowonspark_tpu.compress`: int8 quantization, top-k
  sparsification) and ``recv_msg`` decodes back to dense arrays.  The
  client compresses gradient pushes (with error feedback); the server,
  once a connection negotiates a reply codec via the ``codec`` op,
  compresses push/pull replies as **deltas** against that connection's
  tracked client view instead of shipping ``dict(self._params)`` dense.
- **Zero-copy sends** — frames go out via ``socket.sendmsg``
  scatter-gather over memoryviews of the C-contiguous payloads; no
  ``tobytes()``/``b"".join`` materialization of the concatenated frame.
"""

import json
import logging
import socket
import struct
import threading
import time

import numpy as np

from tensorflowonspark_tpu import compress as compress_mod

logger = logging.getLogger(__name__)

_MAX_HEADER = 16 * 1024 * 1024


# ----------------------------------------------------------------------
# framing: JSON header + raw tensor payloads
# ----------------------------------------------------------------------


def _recv_exact(sock, n):
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(min(1 << 20, n - len(buf)))
        if not chunk:
            raise ConnectionError("socket closed mid-frame")
        buf.extend(chunk)
    return bytes(buf)


#: sendmsg iovec batch bound (Linux IOV_MAX is 1024; stay well under)
_IOV_MAX = 512


def _sendmsg_all(sock, views):
    """Scatter-gather send of a list of memoryviews; returns total
    bytes.  The zero-copy wire path: payload arrays are handed to the
    kernel in place instead of being concatenated into one big
    ``bytes`` (the old path copied every tensor per message).  Falls
    back to ``sendall`` where ``sendmsg`` is unavailable."""
    total = sum(v.nbytes for v in views)
    if not hasattr(sock, "sendmsg"):
        sock.sendall(b"".join(views))
        return total
    pending = [v for v in views if v.nbytes]
    while pending:
        sent = sock.sendmsg(pending[:_IOV_MAX])
        while sent > 0 and pending:
            v = pending[0]
            if sent >= v.nbytes:
                sent -= v.nbytes
                pending.pop(0)
            else:
                pending[0] = v[sent:]
                sent = 0
    return total


def _part_meta(p):
    # dtype_str, not .str: extension dtypes (bfloat16) stringify as an
    # opaque void that np.dtype() resolves to raw bytes
    return {"dtype": compress_mod.dtype_str(p.dtype),
            "shape": list(p.shape), "nbytes": int(p.nbytes)}


def _payload_view(p):
    """Byte view of a contiguous payload array.  Extension dtypes
    (bfloat16) refuse buffer export under their own format code, so
    fall back to a zero-copy uint8 reinterpret of the same memory."""
    try:
        return memoryview(p).cast("B")
    except (ValueError, TypeError):
        return memoryview(p.reshape(-1).view(np.uint8))


def _send_frame(sock, header, entries):
    """Lay one frame on the socket: ``entries`` is a list of
    ``(tensor_meta, [payload arrays])``; returns bytes sent."""
    meta = []
    payloads = []
    for m, parts in entries:
        parts = [np.ascontiguousarray(p) for p in parts]
        if m.get("codec"):
            m = dict(m, parts=[_part_meta(p) for p in parts])
        meta.append(m)
        payloads.extend(parts)
    hb = json.dumps(dict(header, tensors=meta)).encode("utf-8")
    views = [memoryview(struct.pack(">I", len(hb))), memoryview(hb)]
    views.extend(_payload_view(p) for p in payloads)
    return _sendmsg_all(sock, views)


def send_msg(sock, header, tensors=None, codec=None):
    """Send ``header`` (JSON-able dict) plus named numpy ``tensors``.

    With ``codec`` (a :class:`~tensorflowonspark_tpu.compress.Codec` or
    :class:`~tensorflowonspark_tpu.compress.ErrorFeedback`), each
    tensor ships as the codec's encoded parts and the per-tensor meta
    gains the codec header ``recv_msg`` decodes by.  Returns the total
    bytes laid on the wire (header + payloads) — the wire-traffic
    accounting the wire tests use.
    """
    tensors = tensors or {}
    entries = []
    for name, arr in tensors.items():
        if codec is not None and not isinstance(codec, compress_mod.NoneCodec):
            if hasattr(codec, "encode_named"):  # error-feedback wrapper
                parts, cmeta = codec.encode_named(name, arr)
            else:
                parts, cmeta = codec.encode(np.asarray(arr))
            entries.append(
                ({"name": name, "codec": codec.name, "meta": cmeta}, parts)
            )
        else:
            arr = np.ascontiguousarray(arr)
            entries.append((dict(_part_meta(arr), name=name), [arr]))
    return _send_frame(sock, header, entries)


def _recv_part(sock, m):
    """Receive one payload described by part-meta ``m``; malformed meta
    (nbytes disagreeing with dtype x shape — a corrupt or hostile
    frame) is rejected as ConnectionError before any allocation, the
    same posture as the tfrecord codec's corruption checks."""
    try:
        dtype = compress_mod.resolve_dtype(m["dtype"])
        shape = tuple(int(s) for s in m["shape"])
        nbytes = int(m["nbytes"])
    except (KeyError, TypeError, ValueError) as e:
        raise ConnectionError("bad tensor meta: {0}".format(e))
    expect = int(dtype.itemsize * int(np.prod(shape, dtype=np.int64)))
    if nbytes != expect or nbytes < 0 or any(s < 0 for s in shape):
        raise ConnectionError(
            "tensor meta nbytes {0} inconsistent with dtype/shape "
            "({1} expected)".format(nbytes, expect)
        )
    raw = _recv_exact(sock, nbytes)
    return np.frombuffer(raw, dtype=dtype).reshape(shape)


def recv_msg(sock):
    """Receive one message → ``(header, {name: np.ndarray})``.

    Codec-carrying tensors are decoded to dense arrays here, so every
    consumer (the shard's ``update()``, the client's unshard) sees
    plain numpy regardless of what crossed the wire.  Undecodable or
    inconsistent frames raise ``ConnectionError``.

    The returned header carries ``_recv_nbytes`` — the exact wire
    bytes this frame occupied (length prefix + header + payloads), the
    receive-side twin of ``send_msg``'s return value; anything the
    peer put under that key is overwritten after parse.
    """
    (hlen,) = struct.unpack(">I", _recv_exact(sock, 4))
    if hlen > _MAX_HEADER:
        raise ConnectionError("header length {0} exceeds limit".format(hlen))
    try:
        header = json.loads(_recv_exact(sock, hlen).decode("utf-8"))
    except (UnicodeDecodeError, ValueError) as e:
        raise ConnectionError("undecodable frame header: {0}".format(e))
    if not isinstance(header, dict):
        raise ConnectionError("frame header is not an object")
    nbytes = 4 + hlen
    tensors = {}
    for m in header.get("tensors", ()):
        if m.get("codec"):
            codec = compress_mod.get_codec(str(m["codec"]))
            parts = [_recv_part(sock, pm) for pm in m.get("parts", ())]
            nbytes += sum(int(p.nbytes) for p in parts)
            try:
                tensors[m["name"]] = codec.decode(parts, m.get("meta") or {})
            except (KeyError, TypeError, ValueError, IndexError) as e:
                raise ConnectionError(
                    "codec {0} decode failed: {1}".format(m["codec"], e)
                )
        else:
            part = _recv_part(sock, m)
            nbytes += int(part.nbytes)
            tensors[m["name"]] = part
    header["_recv_nbytes"] = nbytes
    return header, tensors


# ----------------------------------------------------------------------
# server-side numpy optimizers (leafwise; no code deserialization)
# ----------------------------------------------------------------------


class _SGD(object):
    def __init__(self, learning_rate=0.01, momentum=0.0):
        self.lr = learning_rate
        self.momentum = momentum
        self._vel = {}

    def update(self, name, param, grad):
        if self.momentum:
            v = self._vel.get(name)
            v = grad if v is None else self.momentum * v + grad
            self._vel[name] = v
            grad = v
        return param - self.lr * grad


class _Adagrad(object):
    def __init__(self, learning_rate=0.01, eps=1e-10):
        self.lr = learning_rate
        self.eps = eps
        self._acc = {}

    def update(self, name, param, grad):
        acc = self._acc.get(name, np.zeros_like(param)) + grad * grad
        self._acc[name] = acc
        return param - self.lr * grad / (np.sqrt(acc) + self.eps)


class _Adam(object):
    def __init__(self, learning_rate=1e-3, b1=0.9, b2=0.999, eps=1e-8):
        self.lr = learning_rate
        self.b1, self.b2, self.eps = b1, b2, eps
        self._m, self._v, self._t = {}, {}, {}

    def update(self, name, param, grad):
        t = self._t.get(name, 0) + 1
        m = self.b1 * self._m.get(name, np.zeros_like(param)) + (1 - self.b1) * grad
        v = self.b2 * self._v.get(name, np.zeros_like(param)) + (
            1 - self.b2
        ) * grad * grad
        self._m[name], self._v[name], self._t[name] = m, v, t
        mhat = m / (1 - self.b1**t)
        vhat = v / (1 - self.b2**t)
        return param - self.lr * mhat / (np.sqrt(vhat) + self.eps)


class _Delta(object):
    """Hierarchical-plane server rule: the pod leader ships parameter
    DELTAS (local progress since the last synced base, already the
    product of the pod's own on-device optimizer), and the server folds
    them straight in — ``param + scale * delta``.  ``scale`` < 1 damps
    the mixing when many pods push concurrently."""

    def __init__(self, scale=1.0):
        self.scale = scale

    def update(self, name, param, grad):
        return param + self.scale * grad


OPTIMIZERS = {"sgd": _SGD, "adagrad": _Adagrad, "adam": _Adam,
              "delta": _Delta}


def _build_optimizer(spec):
    name, kwargs = spec
    if name not in OPTIMIZERS:
        raise ValueError(
            "unknown PS optimizer {0!r}; supported: {1}".format(
                name, sorted(OPTIMIZERS)
            )
        )
    return OPTIMIZERS[name](**(kwargs or {}))


# ----------------------------------------------------------------------
# server
# ----------------------------------------------------------------------


class _ReplyCompressor(object):
    """Per-connection compressed-delta reply state.

    Once a connection negotiates a reply codec (the ``codec`` wire op),
    params replies stop shipping ``dict(self._params)`` dense: for each
    tensor the server tracks the *client view* — exactly what the
    client has reconstructed so far — and sends the lossy-encoded delta
    against it.  The view advances by the server's own decode of the
    encoded delta (bit-identical to the client's decode of the same
    bytes), so encoding error never drifts the two sides apart: any
    residual stays inside the next ``params - view`` delta — the
    downlink twin of client-side error feedback.

    First sight of a tensor name (or a shape change after an elastic
    restart) ships dense, establishing the base.
    """

    def __init__(self):
        self.codec = None
        self._view = {}

    def negotiate(self, spec):
        codec = compress_mod.get_codec(spec)
        if codec is not None and isinstance(codec, compress_mod.NoneCodec):
            codec = None
        self.codec = codec
        self._view.clear()

    def entries(self, tensors):
        """Frame entries for a params reply (see ``_send_frame``)."""
        entries = []
        for name, arr in tensors.items():
            arr = np.asarray(arr)
            view = self._view.get(name)
            if view is None or view.shape != arr.shape:
                self._view[name] = arr.astype(np.float32, copy=True)
                dense = np.ascontiguousarray(arr)
                entries.append((dict(_part_meta(dense), name=name), [dense]))
                continue
            delta = arr.astype(np.float32, copy=False) - view
            parts, meta = self.codec.encode(delta)
            approx = self.codec.decode(
                [p.copy() for p in parts], meta
            ).astype(np.float32, copy=False)
            self._view[name] = view + approx
            entries.append(
                (
                    {
                        "name": name,
                        "codec": self.codec.name,
                        "meta": meta,
                        "delta": True,
                        "pdtype": arr.dtype.str,
                    },
                    parts,
                )
            )
        return entries


class ParamServerShard(object):
    """One PS shard: parameter store + optimizer + TCP service.

    Thread-per-connection; updates serialized under a lock (each push is
    one atomic read-modify-write, the async-SGD consistency model).
    """

    def __init__(self):
        self._params = {}
        self._opt = None
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._sock = None
        self.addr = None
        #: hierarchical-plane window ledger: pod id -> last applied
        #: window sequence.  A push carrying ``pod``/``window`` header
        #: fields is applied AT MOST ONCE per (pod, window): a re-push
        #: after a leader failover (the new leader cannot know whether
        #: its predecessor's in-flight window landed) is answered
        #: idempotently with the live params instead of double-applying
        #: the gradient (tests/test_hier_ps.py asserts via applied_log).
        self.applied_windows = {}
        #: append-only (pod, window) apply log — test observability for
        #: the exactly-once contract; bounded by run length in tests.
        self.applied_log = []

    # -- ops -----------------------------------------------------------

    def _op_init(self, header, tensors):
        with self._lock:
            if self._opt is None:
                self._opt = _build_optimizer(header["optimizer"])
                self._params = {k: v.copy() for k, v in tensors.items()}
                logger.info(
                    "ps shard initialized: %d tensors, optimizer %s",
                    len(tensors),
                    header["optimizer"][0],
                )
            # idempotent: late initializers get the live params
            return {"op": "init_ok"}, dict(self._params)

    def _op_pull(self, header, tensors):
        with self._lock:
            return {"op": "pull_ok"}, dict(self._params)

    def _op_push(self, header, tensors):
        with self._lock:
            if self._opt is None:
                return {"op": "error", "error": "shard not initialized"}, {}
            pod, window = header.get("pod"), header.get("window")
            if pod is not None and window is not None:
                window = int(window)
                if window <= self.applied_windows.get(pod, -1):
                    # duplicate window (leader failover re-push): do NOT
                    # re-apply; reply with live params so the client
                    # still advances
                    return {"op": "push_ok", "dedup": True}, dict(
                        self._params
                    )
                self.applied_windows[pod] = window
                self.applied_log.append((pod, window))
            for name, grad in tensors.items():
                p = self._params.get(name)
                if p is None:
                    return {
                        "op": "error",
                        "error": "unknown tensor {0}".format(name),
                    }, {}
                self._params[name] = self._opt.update(
                    name, p, grad.astype(p.dtype, copy=False)
                )
            # piggyback fresh params: push+pull in one round trip
            return {"op": "push_ok"}, dict(self._params)

    def _op_window(self, header, tensors):
        """Last applied hierarchical window for ``pod`` (-1 when the
        pod never pushed) — what a freshly-elected pod leader resumes
        its sequence from (docs/communication.md)."""
        with self._lock:
            return {
                "op": "window_ok",
                "last": self.applied_windows.get(header.get("pod"), -1),
            }, {}

    # -- service loop --------------------------------------------------

    def start(self, host="", port=0):
        """Bind and serve in background threads; returns ``(host, port)``."""
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((host, port))
        self._sock.listen(64)
        self.addr = self._sock.getsockname()
        t = threading.Thread(target=self._accept_loop, daemon=True, name="ps-accept")
        t.start()
        return self.addr

    def _accept_loop(self):
        while not self._stop.is_set():
            try:
                conn, peer = self._sock.accept()
            except OSError:
                return  # socket closed
            threading.Thread(
                target=self._serve_conn, args=(conn,), daemon=True, name="ps-conn"
            ).start()

    def _serve_conn(self, conn):
        ops = {"init": self._op_init, "pull": self._op_pull,
               "push": self._op_push, "window": self._op_window}
        reply = _ReplyCompressor()
        try:
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            while not self._stop.is_set():
                try:
                    header, tensors = recv_msg(conn)
                except (ConnectionError, OSError, json.JSONDecodeError):
                    return
                op = header.get("op")
                if op == "stop":
                    send_msg(conn, {"op": "stop_ok"})
                    self.stop()
                    return
                if op == "codec":
                    # per-connection negotiation: subsequent params
                    # replies ship as compressed deltas vs this
                    # connection's tracked client view
                    try:
                        reply.negotiate(header.get("reply"))
                    except (ValueError, TypeError) as e:
                        send_msg(conn, {"op": "error", "error": str(e)})
                        continue
                    send_msg(
                        conn,
                        {
                            "op": "codec_ok",
                            "reply": reply.codec.name if reply.codec else None,
                        },
                    )
                    continue
                handler = ops.get(op)
                if handler is None:
                    send_msg(conn, {"op": "error", "error": "bad op " + repr(op)})
                    continue
                out_header, out_tensors = handler(header, tensors)
                if reply.codec is not None and out_tensors:
                    _send_frame(conn, out_header, reply.entries(out_tensors))
                else:
                    send_msg(conn, out_header, out_tensors)
        finally:
            conn.close()

    def stop(self):
        self._stop.set()
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass

    def join(self, timeout=None):
        """Block until the shard is stopped (the ``server.join()`` role,
        reference: TFNode.py:120-129)."""
        self._stop.wait(timeout)


def run_server(ctx, host=""):
    """Run this ps node's shard until STOP / process teardown.

    Called from ``main_fun`` when ``ctx.job_name == 'ps'`` — the
    reference-parity usage where user code dispatched ps roles to
    ``server.join()`` (reference: TFNode.py:120-129).  The shard binds
    the port the clusterspec advertises for this ps task, so workers
    find it at ``ctx.cluster_spec['ps'][task_index]``.
    """
    addr = ctx.cluster_spec["ps"][ctx.task_index]
    port = int(addr.rsplit(":", 1)[1])
    shard = ParamServerShard()
    shard.start(host, port)
    logger.info("ps shard %d serving at %s", ctx.task_index, shard.addr)
    shard.join()


# ----------------------------------------------------------------------
# client
# ----------------------------------------------------------------------


def _flatten(params):
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(params)
    return [np.asarray(x) for x in leaves], treedef


class _PushHandle(object):
    """In-flight push_pull: ``result()`` waits for every shard's reply
    and returns the unsharded params."""

    def __init__(self, client, boxes, events):
        self._client = client
        self._boxes = boxes
        self._events = events

    def result(self):
        return self._client._unshard(
            PSClient._collect(self._boxes, self._events)
        )


class PSClient(object):
    """Worker-side connection to every PS shard.

    Args:
      addresses: list of ``"host:port"`` (``ctx.cluster_spec['ps']``).
      timeout: per-socket timeout (secs).
      codec: optional gradient-push codec spec (``"int8"``,
        ``("topk", {"ratio": 0.01})``, or a
        :class:`~tensorflowonspark_tpu.compress.Codec`) — pushes ship
        compressed; ``init`` params always ship exact.
      error_feedback: wrap a lossy push codec in client-side
        :class:`~tensorflowonspark_tpu.compress.ErrorFeedback`
        (residual accumulation; keep the default unless measuring the
        uncompensated codec).
      reply_codec: optional reply codec spec negotiated with every
        shard (the ``codec`` wire op): params replies then arrive as
        compressed deltas against this client's last-known view instead
        of dense ``dict(params)``.  ``"same"`` reuses ``codec``'s spec.
        Old servers that reject the negotiation fall back to dense
        replies (logged).
    """

    def __init__(self, addresses, timeout=60, codec=None,
                 error_feedback=True, reply_codec=None):
        from tensorflowonspark_tpu.utils.retry import retry_call

        self.addresses = list(addresses)
        self._socks = []
        for a in self.addresses:
            host, _, port = a.rpartition(":")
            # Backoff-with-jitter under a hard deadline (utils/retry.py)
            # — workers race the ps shards' startup (the shard binds in
            # a background compute process after the rendezvous barrier
            # releases), and a whole fleet reconnecting to a restarted
            # shard must not stampede it in lockstep.
            s = retry_call(
                lambda h=host, p=int(port): socket.create_connection(
                    (h, p), timeout=max(1.0, timeout)
                ),
                "connect to ps shard at {0}".format(a),
                exceptions=(OSError,),
                deadline=timeout,
                base=0.2,
                max_delay=2.0,
            )
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self._socks.append(s)
        self._treedef = None
        self._assignment = None  # leaf index -> shard index
        self._shapes = None
        # gradient-push codec (client->server), optionally under error
        # feedback; and the negotiated reply codec (server->client
        # compressed deltas).  Residuals/views are keyed by wire tensor
        # name; each name is only ever touched by its shard's worker
        # thread, so no extra locking is needed.
        push = compress_mod.get_codec(codec)
        if push is not None and isinstance(push, compress_mod.NoneCodec):
            push = None
        if push is not None and error_feedback:
            push = compress_mod.ErrorFeedback(push)
        self._push_codec = push
        if reply_codec == "same":
            reply_codec = push.spec() if push is not None else None
        self._reply_views = [dict() for _ in self._socks]
        self._reply_active = False
        #: wire bytes this client laid on / pulled off each shard
        #: connection (headers + payloads, both directions; one writer
        #: per index) — initialized BEFORE the reply negotiation so its
        #: round trip is accounted too
        self._sent_bytes = [0] * len(self._socks)
        self._recv_bytes = [0] * len(self._socks)
        if reply_codec is not None:
            self._negotiate_reply(reply_codec)
        # fleet telemetry: the wire accounting that used to live only
        # in this object now also publishes into the process registry,
        # and push/pull round trips trace as spans (null singletons /
        # no-op spans when TFOS_TELEMETRY=0 — docs/observability.md)
        from tensorflowonspark_tpu import telemetry as _telemetry

        _reg = _telemetry.get_registry()
        self._m_bytes = _reg.counter("ps.bytes_sent")
        self._m_bytes_recv = _reg.counter("ps.bytes_recv")
        self._m_trips = _reg.counter("ps.round_trips")
        self._m_rt_hist = _reg.histogram("ps.round_trip_sec")
        self._tracer = _telemetry.get_tracer()
        # persistent per-shard request workers: a round trip costs two
        # queue handoffs instead of a thread spawn per shard per step
        # (measured: thread creation dominated small-model step time)
        import queue as _queue

        self._reqs = [_queue.Queue() for _ in self._socks]
        self._workers = []
        self._closed = False
        for i in range(len(self._socks)):
            t = threading.Thread(
                target=self._shard_worker, args=(i,), daemon=True
            )
            t.start()
            self._workers.append(t)

    def _negotiate_reply(self, spec):
        """Negotiate compressed-delta replies on every shard connection
        (runs before the workers start, so the sockets are free)."""
        spec = compress_mod.get_codec(spec).spec()
        ok = True
        for i, s in enumerate(self._socks):
            self._sent_bytes[i] += send_msg(s, {"op": "codec", "reply": spec})
            h, _ = recv_msg(s)
            self._recv_bytes[i] += h.get("_recv_nbytes", 0)
            if h.get("op") != "codec_ok":
                ok = False
        if not ok:
            # mixed/old ensemble: stay on dense replies everywhere
            # rather than tracking per-shard reply formats
            logger.warning(
                "reply codec %s rejected by a shard; dense replies", spec
            )
            for i, s in enumerate(self._socks):
                self._sent_bytes[i] += send_msg(
                    s, {"op": "codec", "reply": None}
                )
                h, _ = recv_msg(s)
                self._recv_bytes[i] += h.get("_recv_nbytes", 0)
        self._reply_active = ok

    @property
    def bytes_sent(self):
        """Total wire bytes laid on the shard connections by the worker
        round trips (headers + payloads, send side)."""
        return sum(self._sent_bytes)

    @property
    def bytes_recv(self):
        """Total wire bytes pulled OFF the shard connections (headers +
        payloads, receive side) — the reply/delta traffic ``bytes_sent``
        never saw.  Compressed delta replies shrink exactly this number
        (unit-tested against known payloads in tests/test_ps.py)."""
        return sum(self._recv_bytes)

    def _apply_reply(self, i, header, tensors):
        """Post-process one shard reply: delta-coded tensors are folded
        into this client's tracked view (float32, the same arithmetic
        the server's ``_ReplyCompressor`` ran on its copy — the two
        stay bit-identical); dense tensors refresh the view."""
        if not self._reply_active:
            return tensors
        view = self._reply_views[i]
        for m in header.get("tensors", ()):
            name = m.get("name")
            if name is None:
                continue
            if m.get("delta"):
                base = view.get(name)
                if base is None:
                    raise RuntimeError(
                        "shard {0} sent a delta for {1} without a dense "
                        "base".format(i, name)
                    )
                fresh = base + tensors[name].astype(np.float32, copy=False)
                view[name] = fresh
                tensors[name] = fresh.astype(
                    np.dtype(str(m.get("pdtype", "<f4"))), copy=False
                )
            else:
                view[name] = tensors[name].astype(np.float32, copy=True)
        return tensors

    def _shard_worker(self, i):
        sock = self._socks[i]
        q = self._reqs[i]
        while True:
            item = q.get()
            if item is None:
                return
            header, tensors, box, ev, codec = item
            try:
                op = header.get("op", "?")
                t0 = time.perf_counter()
                # "push" covers codec encode + the wire send; "pull"
                # the reply wait + decode — the two halves of the
                # training-step trace's PS leg
                with self._tracer.span(
                    "ps.push", trace="ps", shard=i, op=op
                ) as sp:
                    sent = send_msg(sock, header, tensors, codec=codec)
                    sp.set("bytes", sent)
                self._sent_bytes[i] += sent
                self._m_bytes.inc(sent)
                with self._tracer.span(
                    "ps.pull", trace="ps", shard=i, op=op
                ):
                    h, t = recv_msg(sock)
                self._recv_bytes[i] += h.get("_recv_nbytes", 0)
                self._m_bytes_recv.inc(h.get("_recv_nbytes", 0))
                self._m_trips.inc()
                self._m_rt_hist.observe(time.perf_counter() - t0)
                if h.get("op") == "error":
                    box[1] = RuntimeError(
                        "ps shard {0}: {1}".format(i, h["error"])
                    )
                else:
                    box[0] = self._apply_reply(i, h, t)
                    box[2] = h
            except Exception as e:  # noqa: BLE001 - delivered to caller
                box[1] = e
            ev.set()

    # -- sharding ------------------------------------------------------
    #
    # Two granularities (both DistBelief-style):
    # - small leaves go whole to one shard (size-balanced greedy);
    # - a leaf >= _CHUNK_BYTES with enough rows is split row-wise into
    #   one chunk per shard, so its wire bytes cross ALL shard
    #   connections concurrently instead of serializing through one.
    #   Exact for the leafwise numpy optimizers: every rule is
    #   elementwise, so updating row-chunks independently equals
    #   updating the whole leaf.

    _CHUNK_BYTES = 1 << 18  # 256KB: below this, chunking buys nothing

    def _assign(self, leaves):
        """Deterministic chunk plan: per leaf either ``shard_index`` or
        the list of shard indices its row-chunks land on."""
        n = len(self._socks)
        load = [0] * n
        plan = [None] * len(leaves)
        order = sorted(
            range(len(leaves)), key=lambda i: (-leaves[i].nbytes, i)
        )
        for i in order:
            leaf = leaves[i]
            if (
                n > 1
                and leaf.nbytes >= self._CHUNK_BYTES
                and getattr(leaf, "shape", ())
                and leaf.shape[0] >= n
            ):
                plan[i] = list(range(n))
                for s in range(n):
                    load[s] += leaf.nbytes // n
            else:
                shard = min(range(n), key=lambda s: (load[s], s))
                plan[i] = shard
                load[shard] += max(1, leaf.nbytes)
        return plan

    @staticmethod
    def _chunk_bounds(rows, k):
        """np.array_split's boundary rule, kept explicit so push and
        reassembly can never disagree."""
        base, extra = divmod(rows, k)
        bounds = [0]
        for j in range(k):
            bounds.append(bounds[-1] + base + (1 if j < extra else 0))
        return bounds

    def _shard_tensors(self, leaves):
        per_shard = [dict() for _ in self._socks]
        for i, leaf in enumerate(leaves):
            target = self._assignment[i]
            if isinstance(target, list):
                arr = np.asarray(leaf)
                bounds = self._chunk_bounds(arr.shape[0], len(target))
                for j, s in enumerate(target):
                    per_shard[s]["t{0}c{1}".format(i, j)] = arr[
                        bounds[j]:bounds[j + 1]
                    ]
            else:
                per_shard[target]["t{0}".format(i)] = leaf
        return per_shard

    def _unshard(self, replies):
        flat = {}
        for tensors in replies:
            flat.update(tensors)
        import jax

        leaves = []
        for i, target in enumerate(self._assignment):
            if isinstance(target, list):
                leaves.append(
                    np.concatenate(
                        [
                            flat["t{0}c{1}".format(i, j)]
                            for j in range(len(target))
                        ],
                        axis=0,
                    )
                )
            else:
                leaves.append(flat["t{0}".format(i)])
        return jax.tree_util.tree_unflatten(self._treedef, leaves)

    # -- round trips ---------------------------------------------------

    def _enqueue_all(self, headers, per_shard_tensors, codec=None):
        """Hand one request per shard to the persistent workers (all
        shards in flight concurrently); returns (boxes, events)."""
        if self._closed:
            # a request enqueued after close() would wait forever (the
            # workers are gone); fail fast instead
            raise RuntimeError("PSClient is closed")
        boxes = []
        events = []
        for i in range(len(self._socks)):
            box = [None, None, None]  # [reply, error, reply header]
            ev = threading.Event()
            boxes.append(box)
            events.append(ev)
            self._reqs[i].put(
                (headers[i], per_shard_tensors[i], box, ev, codec)
            )
        return boxes, events

    @staticmethod
    def _collect(boxes, events):
        for ev in events:
            ev.wait()
        errors = [
            (i, box[1]) for i, box in enumerate(boxes) if box[1] is not None
        ]
        if errors:
            raise RuntimeError(
                "PS round trip failed: "
                + "; ".join("shard {0}: {1}".format(i, e) for i, e in errors)
            )
        return [box[0] for box in boxes]

    def _roundtrip_all(self, headers, per_shard_tensors):
        return self._collect(*self._enqueue_all(headers, per_shard_tensors))

    def init(self, params, optimizer=("sgd", {"learning_rate": 0.01})):
        """Initialize (or join) the PS ensemble; returns the live params.

        Idempotent across workers: the first ``init`` seeds the shards,
        later ones receive the current values — the chief/worker race is
        harmless by construction.
        """
        leaves, self._treedef = _flatten(params)
        self._shapes = [x.shape for x in leaves]
        self._assignment = self._assign(leaves)
        per_shard = self._shard_tensors(leaves)
        headers = [
            {"op": "init", "optimizer": [optimizer[0], optimizer[1] or {}]}
            for _ in self._socks
        ]
        return self._unshard(self._roundtrip_all(headers, per_shard))

    def pull(self):
        """Fetch current params from all shards.  Requires a prior
        :meth:`init` on this client (it defines the pytree structure and
        leaf→shard assignment; init is idempotent, so calling it with a
        params template is the way to *join* a live ensemble)."""
        if self._assignment is None:
            raise RuntimeError(
                "call init(params_template, optimizer) before pull()/"
                "push_pull(): it defines the leaf->shard assignment "
                "(idempotent; the template does not overwrite live params)"
            )
        headers = [{"op": "pull"} for _ in self._socks]
        return self._unshard(self._roundtrip_all(headers, [{}] * len(self._socks)))

    def push_pull(self, grads, header_extra=None):
        """Ship gradients, get fresh params back (one async-SGD step)."""
        return self.push_pull_async(grads, header_extra=header_extra).result()

    def push_pull_async(self, grads, header_extra=None):
        """Enqueue the push on every shard worker and return a handle;
        ``handle.result()`` blocks for the replies and unshards.  The
        pipelined :class:`AsyncTrainer` uses this to overlap the round
        trip with the next gradient computation without an extra relay
        thread (each hop in the wakeup chain costs a context switch,
        and a pool-thread relay would sit between the shard workers and
        the caller).

        ``header_extra`` merges extra JSON-able fields into every
        shard's push header — the hierarchical plane stamps its
        ``pod``/``window`` ledger ids this way so the server can
        dedup leader-failover re-pushes."""
        if self._assignment is None:
            raise RuntimeError(
                "call init(params_template, optimizer) before pull()/"
                "push_pull(): it defines the leaf->shard assignment "
                "(idempotent; the template does not overwrite live params)"
            )
        leaves, _ = _flatten(grads)
        per_shard = self._shard_tensors(leaves)
        headers = [
            dict({"op": "push"}, **(header_extra or {}))
            for _ in self._socks
        ]
        return _PushHandle(
            self,
            *self._enqueue_all(headers, per_shard, codec=self._push_codec)
        )

    def window_floor(self, pod):
        """The highest window sequence EVERY shard has applied for
        ``pod`` (-1 when the pod never pushed) — where a newly-elected
        pod leader resumes its push sequence.  Taking the min over
        shards makes a partially-landed window (some shards applied it
        before the old leader died) get re-pushed everywhere; shards
        that already applied it dedup by the ledger, so each shard
        still applies each window exactly once."""
        headers = [{"op": "window", "pod": pod} for _ in self._socks]
        boxes, events = self._enqueue_all(headers, [{}] * len(self._socks))
        self._collect(boxes, events)
        return min(int((b[2] or {}).get("last", -1)) for b in boxes)

    def _join_workers(self):
        self._closed = True
        for q in self._reqs:
            q.put(None)
        for t in self._workers:
            t.join(timeout=5)
        self._workers = []

    def stop(self):
        """Stop every shard (end of training; the driver's control-queue
        teardown is the backstop, reference: TFCluster.py:186-194)."""
        self._join_workers()  # sockets must have no reader in flight
        for s in self._socks:
            try:
                send_msg(s, {"op": "stop"})
                recv_msg(s)
            except (ConnectionError, OSError):
                pass
        self.close()

    def close(self):
        if self._workers:
            self._join_workers()
        for s in self._socks:
            try:
                s.close()
            except OSError:
                pass


# ----------------------------------------------------------------------
# worker-side trainer
# ----------------------------------------------------------------------


class _GradDrain(object):
    """Background device→host gradient drain feeding the
    :class:`_PushHandle` pipeline.

    The dispatch thread hands over *device* gradient trees and keeps
    dispatching; this thread performs the device→host readback (the
    blocking ``device_get`` that used to sit on the training loop's
    critical path — the measured async-PS bottleneck) and enqueues the
    push on the shard workers.  Double-buffered: readback of window
    N+1 overlaps the wire round trip of window N (the previous handle
    is collected only after the next push is in flight).

    ``max_inflight`` is the bounded-staleness window: at most that many
    gradient windows may be queued-or-flying before ``submit`` blocks
    the dispatch thread, so a slow wire backpressures training
    instead of accumulating unbounded staleness.
    """

    _STOP = object()

    def __init__(self, client, max_inflight=2):
        import queue as _queue

        self._client = client
        self._slots = threading.Semaphore(max(1, int(max_inflight)))
        self._q = _queue.Queue()
        self._fresh_lock = threading.Lock()
        self._fresh = None
        self._error = None
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="ps-grad-drain"
        )
        self._thread.start()

    # test hook: tests assert every readback happens on THIS thread,
    # never on the dispatch thread (the non-blocking contract)
    def _to_host(self, tree):
        import jax

        from tensorflowonspark_tpu import telemetry

        # the async-PS plane's per-step readback gets its own
        # span + histogram so the step trace shows where the wall went
        tracer = telemetry.get_tracer()
        t0 = tracer.now()
        out = jax.device_get(tree)
        dur = tracer.now() - t0
        telemetry.get_registry().histogram(
            "ps.grad_readback_sec"
        ).observe(dur)
        tracer.add("grad_readback", t0, dur, trace="ps")
        return out

    def submit(self, device_grads):
        """Hand a device gradient tree to the drain; blocks only when
        the staleness window is full.  Raises any error a previous
        window hit (once)."""
        self._raise_pending()
        # tfoslint: disable=TFOS006(staleness-window semaphore: the _GradDrain thread releases it after the round trip - cross-thread handoff by design)
        self._slots.acquire()
        self._q.put(device_grads)

    def freshest(self):
        """Latest params any landed round trip returned (or None)."""
        self._raise_pending()
        with self._fresh_lock:
            return self._fresh

    def _raise_pending(self):
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def _land(self, handle):
        try:
            fresh = handle.result()
            with self._fresh_lock:
                self._fresh = fresh
        except Exception as e:  # noqa: BLE001 - surfaced on next submit
            if self._error is None:
                self._error = e
        finally:
            self._slots.release()

    def _loop(self):
        prev = None
        while True:
            item = self._q.get()
            if item is self._STOP:
                break
            if isinstance(item, threading.Event):  # flush marker
                if prev is not None:
                    self._land(prev)
                    prev = None
                item.set()
                continue
            try:
                host = self._to_host(item)
                handle = self._client.push_pull_async(host)
            except Exception as e:  # noqa: BLE001 - surfaced on submit
                if self._error is None:
                    self._error = e
                self._slots.release()
                continue
            # collect the PREVIOUS round trip only now: its wire time
            # overlapped this window's device→host readback
            if prev is not None:
                self._land(prev)
            prev = handle
        if prev is not None:
            self._land(prev)

    def flush(self):
        """Block until every submitted window has landed; returns the
        freshest params (or None if nothing ever landed)."""
        ev = threading.Event()
        self._q.put(ev)
        ev.wait()
        self._raise_pending()
        with self._fresh_lock:
            return self._fresh

    def stop(self):
        self._q.put(self._STOP)
        self._thread.join(timeout=10)


class AsyncTrainer(object):
    """Async-PS worker loop: local grads on this node's chips, updates on
    the parameter hosts.

    Args:
      loss_fn: ``loss_fn(params, batch) -> scalar``.
      ps_addresses: ``ctx.cluster_spec['ps']``.
      optimizer: named spec, e.g. ``("adam", {"learning_rate": 1e-3})``.
      pipeline: overlap the PS round trip with the next gradient
        computation (a background single-slot sender).  The params a
        step trains on are then one round trip staler than fully
        synchronous pulls — exactly the async-PS staleness model, one
        deeper — in exchange for hiding the TCP latency behind compute.
        The reference's between-graph PS mode had the same overlap
        implicitly (TF queued send ops against the next session.run).
      overlap: move the device→host gradient readback off the training
        loop entirely (:class:`_GradDrain`): ``step`` dispatches the
        next gradient computation while a background thread drains the
        previous window's grads and runs the push — the fix for the
        measured "per-step device->host grad transfer" bottleneck.
        Staleness is bounded by ``max_inflight`` windows.
      push_every: accumulate this many steps' gradients ON DEVICE
        (mean) per push — the wire sees 1/k the traffic and the PS
        applies the averaged gradient (local accumulation; exact for
        the leafwise optimizers up to the usual async staleness).
      max_inflight: bounded-staleness cap for ``overlap`` mode.
      codec / reply_codec / error_feedback: gradient-plane compression,
        forwarded to :class:`PSClient` (docs/communication.md).
      topology: ``"flat"`` (default — every step crosses the host/TCP
        wire, the DistBelief shape above) or ``"hierarchical"`` — the
        two-tier plane (docs/communication.md "Two-tier gradient
        plane"): per-step gradients aggregate over ICI collectives on
        the mesh and the PS apply runs as a jitted on-device program
        against device-resident shard state (NO host readback on the
        in-pod path); only the pod leader crosses DCN, pushing
        compressed window deltas at ``push_every`` cadence through
        this same wire with ``max_inflight`` bounding staleness.
        Delegates to
        :class:`tensorflowonspark_tpu.parallel.hier_ps.HierTrainer`;
        ``mesh``/``pod_id``/``members``/``member_id``/``leader_fn``
        are forwarded (``pipeline``/``overlap`` do not apply — the
        in-pod path has nothing to overlap, it never leaves the
        device).
    """

    def __init__(self, loss_fn, ps_addresses,
                 optimizer=("sgd", {"learning_rate": 0.01}),
                 pipeline=True, overlap=False, push_every=1,
                 max_inflight=2, codec=None, reply_codec=None,
                 error_feedback=True, topology="flat", mesh=None,
                 pod_id="pod0", members=None, member_id=0,
                 leader_fn=None):
        import jax

        if push_every < 1:
            raise ValueError(
                "push_every must be >= 1, got {0}".format(push_every)
            )
        if topology not in ("flat", "hierarchical"):
            raise ValueError(
                "topology must be 'flat' or 'hierarchical', got "
                "{0!r}".format(topology)
            )
        self.topology = topology
        if topology == "hierarchical":
            # lazy import: hier_ps imports this module for the wire
            from tensorflowonspark_tpu.parallel import hier_ps

            self._hier = hier_ps.HierTrainer(
                loss_fn, ps_addresses, optimizer=optimizer, mesh=mesh,
                push_every=push_every, max_inflight=max_inflight,
                codec=codec, reply_codec=reply_codec,
                error_feedback=error_feedback, pod_id=pod_id,
                members=members, member_id=member_id,
                leader_fn=leader_fn,
            )
            self._client = None
            self.optimizer = optimizer
            self.push_every = int(push_every)
            self.pipeline = False
            self.overlap = False
            self._drain = None
            return
        self._hier = None
        self._client = PSClient(
            ps_addresses, codec=codec, reply_codec=reply_codec,
            error_feedback=error_feedback,
        )
        self.optimizer = optimizer
        self.pipeline = pipeline
        self.overlap = bool(overlap)
        self.push_every = int(push_every)
        self._grad_fn = jax.jit(jax.grad(loss_fn))
        self._acc_fn = jax.jit(
            lambda a, b: jax.tree.map(lambda x, y: x + y, a, b)
        )
        self._inflight = None
        self._accum = None
        self._accum_n = 0
        self._drain = (
            _GradDrain(self.client, max_inflight=max_inflight)
            if self.overlap else None
        )

    @property
    def client(self):
        """The live :class:`PSClient` (wire accounting).  Hierarchical
        topology resolves through the CURRENT leader epoch's link — a
        failover swaps the underlying connection, and a captured
        reference would keep reading the dead epoch's counters."""
        if self._hier is not None:
            return self._hier.client
        return self._client

    def init(self, params):
        if self._hier is not None:
            return self._hier.init(params)
        return self.client.init(params, self.optimizer)

    _mean_cache = None

    def _mean_fn(self, n):
        # cached per window size: a fresh lambda per call would re-jit
        # every accumulation window
        import jax

        if self._mean_cache is None:
            self._mean_cache = {}
        fn = self._mean_cache.get(n)
        if fn is None:
            inv = 1.0 / float(n)
            fn = jax.jit(lambda t: jax.tree.map(lambda x: x * inv, t))
            self._mean_cache[n] = fn
        return fn

    def _accumulate(self, grads):
        """Fold one step's device grads into the local window; returns
        the (mean) window to ship, or None while the window fills.  All
        arithmetic is jitted on device — nothing crosses to host here."""
        if self.push_every == 1:
            return grads
        self._accum = (
            grads if self._accum is None
            else self._acc_fn(self._accum, grads)
        )
        self._accum_n += 1
        if self._accum_n < self.push_every:
            return None
        out = self._mean_fn(self._accum_n)(self._accum)
        self._accum, self._accum_n = None, 0
        return out

    def step(self, params, batch):
        """One async step; returns fresh params (stale-gradient model:
        grads computed at ``params`` may land after other workers').
        Hierarchical topology: the device-resident state is
        authoritative, ``params`` is ignored and the returned tree
        stays on device."""
        if self._hier is not None:
            return self._hier.step(batch)
        grads = self._grad_fn(params, batch)
        window = self._accumulate(grads)
        if window is None:
            return self._freshest(params)
        if self.overlap:
            # hand the DEVICE tree to the drain: the readback happens on
            # its thread, this one goes straight back to dispatching
            self._drain.submit(window)
            return self._freshest(params)
        if not self.pipeline:
            return self.client.push_pull(window)
        # enqueue this step's push directly on the shard workers, then
        # collect the PREVIOUS round trip — its wire time overlapped
        # this step's gradient computation.  The new handle replaces
        # _inflight BEFORE collecting the old one: if the old trip
        # failed, the error surfaces once and the next step collects
        # the fresh handle instead of re-raising a stale failure
        prev, self._inflight = self._inflight, self.client.push_pull_async(
            window
        )
        return prev.result() if prev is not None else params

    def _freshest(self, params):
        fresh = self._drain.freshest() if self._drain is not None else None
        return fresh if fresh is not None else params

    def drain(self):
        """Block until every in-flight round trip lands; returns the
        freshest params or None.  Call at epoch/export boundaries so
        checkpoints see every shipped gradient.  A partially-filled
        accumulation window is shipped (mean over its actual count)."""
        if self._hier is not None:
            return self._hier.drain()
        if self._accum is not None:
            window = self._mean_fn(self._accum_n)(self._accum)
            self._accum, self._accum_n = None, 0
            if self._drain is not None:
                self._drain.submit(window)
            else:
                prev, self._inflight = self._inflight, None
                if prev is not None:
                    prev.result()
                return self.client.push_pull(window)
        if self._drain is not None:
            return self._drain.flush()
        if self._inflight is None:
            return None
        fresh = self._inflight.result()
        self._inflight = None
        return fresh

    def stop(self, stop_servers=False):
        if self._hier is not None:
            return self._hier.stop(stop_servers=stop_servers)
        try:
            self.drain()
        except Exception:  # noqa: BLE001 - teardown must proceed
            pass
        if self._drain is not None:
            self._drain.stop()
        if stop_servers:
            self.client.stop()
        else:
            self.client.close()
