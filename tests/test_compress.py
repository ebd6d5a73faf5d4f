"""Gradient-plane tests: codecs, error feedback, the codec-aware wire
format, compressed delta replies, and the overlap drain.

Unit: codec round trips (property-style over shapes/dtypes), int8
error-feedback convergence on a quadratic bowl, top-k index
correctness, non-contiguous inputs, wire-byte accounting.
Wire: truncated/garbage frame rejection (mirroring the tfrecord
corruption tests), bytes-on-wire shrink under codecs, delta-reply
bit-consistency between the server's client view and the client's.
Overlap: the background drain keeps device dispatch non-blocking — no
readback ever runs on the training-loop thread.
"""

import socket
import struct
import threading

import numpy as np
import pytest

from tensorflowonspark_tpu import compress
from tensorflowonspark_tpu.parallel import ps


# --- codec round trips -------------------------------------------------


SHAPES = [(7,), (3, 5), (2, 3, 4), (1,), (128, 9)]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_int8_roundtrip_bounded_error(shape, dtype):
    rng = np.random.RandomState(hash((shape, str(dtype))) % 2**31)
    arr = (rng.randn(*shape) * 3).astype(dtype)
    codec = compress.Int8Codec()
    parts, meta = codec.encode(arr)
    out = codec.decode(parts, meta)
    assert out.shape == arr.shape and out.dtype == arr.dtype
    # symmetric quantization error is bounded by half a step
    step = np.abs(arr).max() / 127.0
    assert np.abs(out - arr).max() <= step * 0.5 + 1e-12


def test_int8_zero_tensor_and_wire_bytes():
    codec = compress.Int8Codec()
    arr = np.zeros((64, 64), np.float32)
    parts, meta = codec.encode(arr)
    np.testing.assert_array_equal(codec.decode(parts, meta), arr)
    # float32 -> int8: payload shrinks exactly 4x
    assert compress.encoded_nbytes(parts) * 4 == arr.nbytes


def test_topk_keeps_exactly_the_largest_magnitudes():
    rng = np.random.RandomState(0)
    arr = rng.randn(40, 50).astype(np.float32)
    codec = compress.TopKCodec(ratio=0.05, min_size=16)
    parts, meta = codec.encode(arr)
    out = codec.decode(parts, meta)
    k = meta["k"]
    assert k == int(np.ceil(0.05 * arr.size))
    nz = np.flatnonzero(out.ravel())
    assert len(nz) == k
    # the kept set IS the top-k by |value|, values exact
    expect = np.sort(np.argpartition(np.abs(arr.ravel()), arr.size - k)[
        arr.size - k:])
    np.testing.assert_array_equal(nz, expect)
    np.testing.assert_array_equal(out.ravel()[nz], arr.ravel()[nz])


def test_topk_small_tensor_ships_dense():
    codec = compress.TopKCodec(ratio=0.01, min_size=1024)
    arr = np.arange(10, dtype=np.float32)
    parts, meta = codec.encode(arr)
    assert meta.get("dense") is True
    np.testing.assert_array_equal(codec.decode(parts, meta), arr)


def test_topk_rejects_bad_ratio():
    with pytest.raises(ValueError):
        compress.TopKCodec(ratio=0.0)
    with pytest.raises(ValueError):
        compress.TopKCodec(ratio=1.5)


def test_codecs_accept_non_contiguous_input():
    base = np.asfortranarray(np.random.RandomState(1).randn(32, 16)
                             .astype(np.float32))
    view = base[::2]  # non-contiguous strided view
    assert not view.flags.c_contiguous
    for codec in (compress.Int8Codec(),
                  compress.TopKCodec(ratio=0.5, min_size=1),
                  compress.NoneCodec()):
        parts, meta = codec.encode(view)
        for p in parts:
            assert p.flags.c_contiguous  # wire payloads must be laid flat
        out = codec.decode(parts, meta)
        assert out.shape == view.shape
        if isinstance(codec, (compress.NoneCodec,)):
            np.testing.assert_array_equal(out, view)


def test_get_codec_specs():
    assert compress.get_codec(None) is None
    assert isinstance(compress.get_codec("int8"), compress.Int8Codec)
    tk = compress.get_codec(("topk", {"ratio": 0.1}))
    assert isinstance(tk, compress.TopKCodec) and tk.ratio == 0.1
    same = compress.get_codec(tk)
    assert same is tk
    with pytest.raises(ValueError):
        compress.get_codec("zstd-of-doom")


# --- error feedback ----------------------------------------------------


def test_error_feedback_requires_lossy_codec():
    with pytest.raises(ValueError):
        compress.ErrorFeedback("none")


@pytest.mark.parametrize("codec", ["int8", ("topk", {"ratio": 0.25,
                                                     "min_size": 1})])
def test_error_feedback_converges_quadratic_bowl(codec):
    # minimize ||w - t||^2 with only the DECODED (lossy) gradients
    # applied: with error feedback the residual re-injects what
    # compression dropped, so SGD still reaches the optimum — without
    # it, top-k permanently starves the small coordinates
    efb = compress.ErrorFeedback(codec)
    dec = compress.get_codec(codec)
    target = np.linspace(-3.0, 5.0, 16).astype(np.float32)
    w = np.zeros(16, np.float32)
    for _ in range(500):
        g = 2.0 * (w - target)
        parts, meta = efb.encode_named("g", g)
        w = w - 0.05 * dec.decode(parts, meta).astype(np.float32)
    assert np.abs(w - target).max() < 1e-2


def test_error_feedback_residual_tracks_sum_of_true_gradients():
    # telescoping invariant: sum(decoded) + residual == sum(true grads)
    efb = compress.ErrorFeedback("int8")
    rng = np.random.RandomState(3)
    true_sum = np.zeros(32, np.float32)
    sent_sum = np.zeros(32, np.float32)
    for _ in range(50):
        g = rng.randn(32).astype(np.float32)
        true_sum += g
        parts, meta = efb.encode_named("g", g)
        sent_sum += efb.codec.decode(parts, meta)
    np.testing.assert_allclose(
        sent_sum + efb._residual["g"], true_sum, atol=1e-3
    )


# --- wire format -------------------------------------------------------


def _xfer(tensors, codec=None, header=None):
    """One message across a socketpair with a concurrent reader;
    returns (bytes_sent, header, tensors)."""
    a, b = socket.socketpair()
    box = {}

    def rd():
        box["r"] = ps.recv_msg(b)

    t = threading.Thread(target=rd)
    t.start()
    n = ps.send_msg(a, header or {"op": "push"}, tensors, codec=codec)
    t.join(10)
    a.close()
    b.close()
    return n, box["r"][0], box["r"][1]


def test_wire_codec_roundtrip_int8_and_topk():
    rng = np.random.RandomState(0)
    tensors = {
        "w": rng.randn(300, 40).astype(np.float32),
        "b": rng.randn(17).astype(np.float32),
    }
    for codec in (compress.Int8Codec(),
                  compress.TopKCodec(ratio=0.1, min_size=8)):
        _, header, got = _xfer(tensors, codec=codec)
        assert set(got) == set(tensors)
        for m in header["tensors"]:
            assert m["codec"] == codec.name
        for k in tensors:
            assert got[k].shape == tensors[k].shape
            assert got[k].dtype == tensors[k].dtype


def test_wire_bytes_shrink_3x_under_int8_and_more_under_topk():
    # the acceptance gate: bytes-on-wire per push, same gradients
    grads = {"w": np.random.RandomState(0).randn(1000, 64)
             .astype(np.float32)}
    dense, _, _ = _xfer(grads)
    int8, _, _ = _xfer(grads, codec=compress.Int8Codec())
    topk, _, _ = _xfer(grads, codec=compress.TopKCodec(ratio=0.01))
    assert dense / int8 >= 3.0
    assert dense / topk > dense / int8  # top-k compresses further
    assert dense / topk >= 10.0


def test_recv_msg_rejects_truncated_frame():
    a, b = socket.socketpair()
    ps.send_msg(a, {"op": "push"}, {"x": np.ones(4, np.float32)})
    # re-send a truncated copy: read the valid frame, chop the payload
    full = b.recv(1 << 20)
    a.sendall(full[: len(full) - 8])
    a.close()  # EOF mid-payload
    with pytest.raises(ConnectionError):
        ps.recv_msg(b)
    b.close()


def test_recv_msg_rejects_garbage_header():
    a, b = socket.socketpair()
    junk = b"\x00\x00\x00\x10" + b"\xde\xad\xbe\xef" * 4
    a.sendall(junk)
    with pytest.raises(ConnectionError):
        ps.recv_msg(b)
    a.close()
    b.close()


def test_recv_msg_rejects_oversized_header():
    a, b = socket.socketpair()
    a.sendall(struct.pack(">I", (16 << 20) + 1))
    with pytest.raises(ConnectionError):
        ps.recv_msg(b)
    a.close()
    b.close()


def test_recv_msg_rejects_inconsistent_tensor_meta():
    # nbytes disagreeing with dtype*shape must be refused before any
    # allocation (a corrupt or hostile frame)
    a, b = socket.socketpair()
    import json

    hb = json.dumps({
        "op": "push",
        "tensors": [{"name": "x", "dtype": "<f4", "shape": [4],
                     "nbytes": 999}],
    }).encode()
    a.sendall(struct.pack(">I", len(hb)) + hb + b"\x00" * 16)
    with pytest.raises(ConnectionError):
        ps.recv_msg(b)
    a.close()
    b.close()


def test_recv_msg_rejects_unknown_codec():
    a, b = socket.socketpair()
    import json

    hb = json.dumps({
        "op": "push",
        "tensors": [{"name": "x", "codec": "evil", "meta": {},
                     "parts": []}],
    }).encode()
    a.sendall(struct.pack(">I", len(hb)) + hb)
    with pytest.raises(ValueError):
        ps.recv_msg(b)
    a.close()
    b.close()


# --- compressed delta replies -----------------------------------------


@pytest.fixture()
def shard_addr():
    shard = ps.ParamServerShard()
    _, port = shard.start("127.0.0.1", 0)
    yield "127.0.0.1:{0}".format(port)
    shard.stop()


def test_delta_replies_track_server_params(shard_addr):
    # push replies arrive as int8 deltas; after N async steps the
    # client's reconstructed view must agree with a fresh dense pull
    c = ps.PSClient([shard_addr], codec="int8", reply_codec="same")
    assert c._reply_active
    rng = np.random.RandomState(1)
    params = {"w": rng.randn(400, 30).astype(np.float32)}
    p = c.init(params, ("sgd", {"learning_rate": 0.05}))
    for _ in range(40):
        g = 2.0 * (np.asarray(p["w"]) - 1.0)
        p = c.push_pull({"w": g.astype(np.float32)})
    # ground truth: a separate dense client joining the live ensemble
    dense = ps.PSClient([shard_addr])
    dense.init({"w": np.zeros_like(params["w"])},
               ("sgd", {"learning_rate": 0.05}))
    truth = dense.pull()
    # the delta view may lag the true params by one quantization
    # residual of the (tiny) final delta — bounded, not drifting
    scale = np.abs(np.asarray(truth["w"])).max() / 127.0
    assert np.abs(np.asarray(p["w"]) - np.asarray(truth["w"])).max() \
        <= scale + 1e-5
    dense.close()
    c.stop()


def test_delta_reply_convergence_matches_dense(shard_addr):
    # same workload, delta-compressed replies vs dense replies: both
    # clients must drive the quadratic to its optimum
    for kwargs in ({}, {"codec": "int8", "reply_codec": "same"}):
        shard = ps.ParamServerShard()
        _, port = shard.start("127.0.0.1", 0)
        c = ps.PSClient(["127.0.0.1:{0}".format(port)], **kwargs)
        p = c.init({"w": np.zeros(64, np.float32)},
                   ("sgd", {"learning_rate": 0.05}))
        target = np.linspace(-2, 2, 64).astype(np.float32)
        for _ in range(200):
            g = 2.0 * (np.asarray(p["w"]) - target)
            p = c.push_pull({"w": g.astype(np.float32)})
        assert np.abs(np.asarray(p["w"]) - target).max() < 2e-2
        c.stop()
        shard.join(5)


def test_reply_codec_negotiation_falls_back_on_rejection(shard_addr,
                                                         monkeypatch):
    # an ensemble member that rejects the codec op must leave the
    # client on dense replies everywhere (mixed-version safety)
    real_recv = ps.recv_msg
    state = {"first": True}

    def flaky_recv(sock):
        h, t = real_recv(sock)
        if h.get("op") == "codec_ok" and state.pop("first", False):
            return {"op": "error", "error": "no codecs here"}, {}
        return h, t

    monkeypatch.setattr(ps, "recv_msg", flaky_recv)
    c = ps.PSClient([shard_addr], reply_codec="int8")
    assert not c._reply_active
    p = c.init({"w": np.zeros(8, np.float32)},
               ("sgd", {"learning_rate": 0.1}))
    p = c.push_pull({"w": np.ones(8, np.float32)})
    np.testing.assert_allclose(np.asarray(p["w"]), -0.1)
    c.close()


# --- overlap drain -----------------------------------------------------


@pytest.fixture()
def two_shards():
    shards = [ps.ParamServerShard() for _ in range(2)]
    addrs = []
    for s in shards:
        _, port = s.start("127.0.0.1", 0)
        addrs.append("127.0.0.1:{0}".format(port))
    yield addrs
    for s in shards:
        s.stop()


def test_overlap_drain_keeps_dispatch_thread_free(two_shards,
                                                  monkeypatch):
    # THE non-blocking contract: with overlap=True, every device→host
    # gradient readback runs on the drain thread — never on the thread
    # calling step() (where it would serialize transfer with dispatch)
    readback_threads = set()
    orig = ps._GradDrain._to_host

    def spy(self, tree):
        readback_threads.add(threading.current_thread().name)
        return orig(self, tree)

    monkeypatch.setattr(ps._GradDrain, "_to_host", spy)

    target = np.asarray([1.0, -2.0, 0.5, 3.0], np.float32)

    def loss_fn(params, batch):
        import jax.numpy as jnp

        del batch
        return jnp.sum((params["w"] - target) ** 2)

    tr = ps.AsyncTrainer(
        loss_fn, two_shards, optimizer=("sgd", {"learning_rate": 0.05}),
        overlap=True,
    )
    p = tr.init({"w": np.zeros(4, np.float32)})
    for _ in range(60):
        p = tr.step(p, None)
    drained = tr.drain()
    tr.stop()
    assert readback_threads == {"ps-grad-drain"}
    assert threading.current_thread().name not in readback_threads
    assert drained is not None


def test_overlap_with_push_every_converges(two_shards):
    # accumulation window k=4: the wire sees 1/4 the pushes, the PS
    # applies window means — convergence on the bowl must survive
    target = np.asarray([2.0, -1.0, 0.25, -3.0], np.float32)

    def loss_fn(params, batch):
        import jax.numpy as jnp

        del batch
        return jnp.sum((params["w"] - target) ** 2)

    tr = ps.AsyncTrainer(
        loss_fn, two_shards, optimizer=("sgd", {"learning_rate": 0.1}),
        overlap=True, push_every=4, codec="int8", reply_codec="same",
    )
    p = tr.init({"w": np.zeros(4, np.float32)})
    for _ in range(402):  # 2 extra: a partial window drain() must ship
        p = tr.step(p, None)
    drained = tr.drain()
    tr.stop(stop_servers=True)
    assert np.abs(np.asarray(drained["w"]) - target).max() < 2e-2


def test_overlap_push_count_is_one_per_window(two_shards):
    # push_every=k must cut pushes to ceil(steps/k) (+1 for the drain
    # of the trailing partial window)
    calls = []

    def loss_fn(params, batch):
        import jax.numpy as jnp

        del batch
        return jnp.sum(params["w"] ** 2)

    tr = ps.AsyncTrainer(
        loss_fn, two_shards, optimizer=("sgd", {"learning_rate": 0.01}),
        overlap=True, push_every=5,
    )
    orig = tr.client.push_pull_async
    tr.client.push_pull_async = lambda g: calls.append(1) or orig(g)
    tr.init({"w": np.ones(4, np.float32)})
    for _ in range(23):
        tr.step({"w": np.ones(4, np.float32)}, None)
    tr.drain()
    tr.stop(stop_servers=True)
    assert len(calls) == 5  # 4 full windows + the partial (3-step) one


def test_async_int8_error_feedback_matches_sync_final_loss(two_shards):
    """Convergence parity (acceptance gate): int8 error-feedback async
    PS vs plain sync SGD on the same quadratic — final loss within
    tolerance."""
    rng = np.random.RandomState(0)
    A = rng.randn(16, 8).astype(np.float32)
    y = rng.randn(16).astype(np.float32)

    def loss_np(w):
        r = A @ w - y
        return float(r @ r) / 16.0

    # sync reference: exact gradients, plain SGD
    w_sync = np.zeros(8, np.float32)
    for _ in range(300):
        g = 2.0 * A.T @ (A @ w_sync - y) / 16.0
        w_sync = w_sync - 0.05 * g

    def loss_fn(params, batch):
        import jax.numpy as jnp

        del batch
        r = jnp.dot(A, params["w"]) - y
        return jnp.dot(r, r) / 16.0

    tr = ps.AsyncTrainer(
        loss_fn, two_shards, optimizer=("sgd", {"learning_rate": 0.05}),
        codec="int8", reply_codec="same",
    )
    p = tr.init({"w": np.zeros(8, np.float32)})
    for _ in range(300):
        p = tr.step(p, None)
    drained = tr.drain()
    tr.stop(stop_servers=True)
    final = loss_np(np.asarray(drained["w"]))
    ref = loss_np(w_sync)
    assert abs(final - ref) < 1e-3, (final, ref)


def test_drain_surfaces_background_errors(two_shards):
    def loss_fn(params, batch):
        import jax.numpy as jnp

        del batch
        return jnp.sum(params["w"] ** 2)

    tr = ps.AsyncTrainer(
        loss_fn, two_shards, optimizer=("sgd", {"learning_rate": 0.01}),
        overlap=True,
    )
    tr.init({"w": np.ones(4, np.float32)})
    tr.step({"w": np.ones(4, np.float32)}, None)
    # kill the wire under the drain; the failure must surface on
    # drain()/step(), not vanish in the background thread
    tr.client.close()
    with pytest.raises(Exception):
        for _ in range(50):
            tr.step({"w": np.ones(4, np.float32)}, None)
        tr.drain()
    tr._drain.stop()


# --- bfloat16 gradients (the bf16-training wire, ISSUE 9) --------------


class TestBfloat16(object):
    """bf16 gradient round trips: the codecs were float32-centric, and
    ``dtype.str`` for the ml_dtypes extension type is an opaque void
    (``'<V2'``) that silently reinterprets as raw bytes — the wire now
    spells extension dtypes by their registered NAME."""

    def _bf16(self):
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)

    def test_dtype_str_roundtrips_bf16(self):
        bf = self._bf16()
        s = compress.dtype_str(bf)
        assert s == "bfloat16"  # NOT '<V2'
        assert compress.resolve_dtype(s) == bf
        # builtin dtypes keep the canonical .str spelling
        assert compress.dtype_str(np.float32) == np.dtype(np.float32).str

    @pytest.mark.parametrize("codec", [
        compress.NoneCodec(), compress.Int8Codec(),
        compress.TopKCodec(ratio=0.5, min_size=4),
    ])
    def test_codec_roundtrip_preserves_bf16_dtype(self, codec):
        bf = self._bf16()
        rng = np.random.RandomState(3)
        arr = (rng.randn(6, 5) * 2).astype(np.float32).astype(bf)
        parts, meta = codec.encode(arr)
        out = codec.decode(parts, meta)
        assert out.dtype == bf and out.shape == arr.shape
        if isinstance(codec, compress.TopKCodec):
            # the kept coordinates round-trip (the dropped half is the
            # codec's lossiness, not a dtype bug)
            nz = np.flatnonzero(out.astype(np.float32).ravel())
            np.testing.assert_allclose(
                out.astype(np.float32).ravel()[nz],
                arr.astype(np.float32).ravel()[nz],
                rtol=1e-2,
            )
        else:
            # quantization error stays bounded in float32 terms
            err = np.abs(
                out.astype(np.float32) - arr.astype(np.float32)
            ).max()
            assert err <= (
                np.abs(arr.astype(np.float32)).max() / 64.0 + 1e-6
            )

    def test_bf16_dense_wire_roundtrip(self):
        bf = self._bf16()
        a, b = socket.socketpair()
        try:
            g = np.array([1.5, -2.25, 0.125, 7.0], dtype=bf)
            sent = ps.send_msg(a, {"op": "push"}, {"g": g})
            header, got = ps.recv_msg(b)
            assert got["g"].dtype == bf
            np.testing.assert_array_equal(got["g"], g)
            # byte accounting symmetric across the two sides
            assert header["_recv_nbytes"] == sent
        finally:
            a.close()
            b.close()

    def test_bf16_codec_wire_roundtrip(self):
        bf = self._bf16()
        a, b = socket.socketpair()
        try:
            g = (np.arange(-16, 16, dtype=np.float32) / 4).astype(bf)
            ps.send_msg(a, {"op": "push"}, {"g": g},
                        codec=compress.Int8Codec())
            _, got = ps.recv_msg(b)
            assert got["g"].dtype == bf
            np.testing.assert_allclose(
                got["g"].astype(np.float32), g.astype(np.float32),
                atol=np.abs(g.astype(np.float32)).max() / 100.0,
            )
        finally:
            a.close()
            b.close()

    def test_ef_residual_accumulates_in_float32(self):
        # the EF residual MUST stay float32: a bf16 residual (8 mantissa
        # bits) would round away exactly the sub-quantization-step
        # corrections error feedback exists to carry
        bf = self._bf16()
        ef = compress.ErrorFeedback(compress.Int8Codec())
        rng = np.random.RandomState(4)
        g = (rng.randn(256) * 0.1).astype(np.float32).astype(bf)
        ef.encode_named("g", g)
        assert ef._residual["g"].dtype == np.float32

    def test_ef_telescoping_sum_survives_bf16_gradients(self):
        # sum of decoded messages tracks the sum of true grads at
        # FLOAT32 precision: the telescoping invariant, with bf16 on
        # the wire's edges and fp32 in the residual
        bf = self._bf16()
        ef = compress.ErrorFeedback(compress.Int8Codec())
        rng = np.random.RandomState(5)
        true_sum = np.zeros(128, np.float64)
        decoded_sum = np.zeros(128, np.float64)
        for _ in range(50):
            g = (rng.randn(128) * 0.03).astype(np.float32).astype(bf)
            parts, meta = ef.encode_named("g", g)
            # decode at the codec's float32 working precision: the
            # telescoping property is about what EF tracks, not about
            # the receiver's (bf16) storage rounding on top of it
            dec = ef.decode(
                [p.copy() for p in parts], dict(meta, dtype="<f4")
            )
            true_sum += g.astype(np.float64)
            decoded_sum += dec.astype(np.float64)
        # the gap IS the final residual (elementwise telescoping), up
        # to fp32 accumulation noise — NOT 50 steps of bf16 drift
        np.testing.assert_allclose(
            (true_sum - decoded_sum).astype(np.float32),
            ef._residual["g"], atol=5e-5,
        )

    def test_bf16_residual_would_break_the_invariant(self):
        # the failure mode the float32 rule prevents, demonstrated:
        # accumulating the SAME residuals in bf16 loses the small
        # corrections (documents WHY the dtype rule exists)
        bf = self._bf16()
        rng = np.random.RandomState(6)
        codec = compress.Int8Codec()
        r32 = np.zeros(128, np.float32)
        rbf = np.zeros(128, dtype=bf)
        drift32 = drift_bf = 0.0
        for _ in range(50):
            g = (rng.randn(128) * 0.03).astype(np.float32)
            for kind in ("f32", "bf16"):
                r = r32 if kind == "f32" else rbf.astype(np.float32)
                f = g + r
                parts, meta = codec.encode(f)
                dec = codec.decode([p.copy() for p in parts], meta)
                new_r = f - dec
                if kind == "f32":
                    r32 = new_r
                    drift32 = np.abs(new_r).max()
                else:
                    rbf = new_r.astype(bf)
                    drift_bf += np.abs(
                        new_r - rbf.astype(np.float32)
                    ).max()
        # the bf16 path leaks residual every step; fp32 does not
        assert drift_bf > 0.0
