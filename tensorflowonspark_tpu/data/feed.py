"""DataFeed: the compute-process side of the executor data plane.

Re-designed from the reference's ``TFNode.DataFeed`` (reference:
tensorflowonspark/TFNode.py:221-329).  Semantics preserved:

- ``next_batch(batch_size)`` blocks on the input queue and returns up to
  ``batch_size`` items; a ``None`` sentinel means end-of-feed
  (reference: TFNode.py:243-288), an ``EndPartition`` marker truncates
  the batch at a partition boundary (reference: TFNode.py:268-274).
- With ``input_mapping``, batches come back as a dict of named columns
  (reference: TFNode.py:276-288) — the natural layout for feeding a JAX
  step function.
- ``batch_results`` pushes inference results to the output queue
  (reference: TFNode.py:294-305).
- ``terminate`` sets the node state to ``'terminating'`` and drains the
  input queue so blocked feeders are released
  (reference: TFNode.py:307-329).

TPU-native additions (no reference analogue — SURVEY.md §7 step 3):

- ``batches(...)`` generator with numpy stacking, padding of the final
  short batch, and optional device placement,
- ``prefetch_to_device`` double-buffering so host→HBM transfer of batch
  N+1 overlaps compute on batch N (the InputMode.SPARK → HBM path).
"""

import logging
import queue as queue_mod

import numpy as np

from tensorflowonspark_tpu.cluster.marker import (
    Block,
    ColumnarBlock,
    EndPartition,
    decode_columnar_record,
    pack_columnar,
)


def _decode_ring_record(rec):
    """Decode one ring record to a PENDING element — a row list or a
    :class:`ColumnarBlock` (the two shapes ``_set_pending`` consumers
    index into).  Records are either the zero-pickle columnar wire
    format (magic-prefixed; decoded as zero-copy views over ``rec``) or
    a pickled Block/row-list fallback — a pickled ``Block`` must be
    unwrapped to its rows here (the queue path unwraps in the fetch
    loop; a raw Block is not subscriptable).  A zero-length record (the
    ring supports them) is an empty row list — ``pickle.loads(b"")``
    would raise EOFError."""
    if not rec:
        return []
    block = decode_columnar_record(rec)
    if block is not None:
        return block
    import pickle

    obj = pickle.loads(rec)
    return obj.items if isinstance(obj, Block) else obj

logger = logging.getLogger(__name__)


class DataFeed(object):
    """Consumes feed items from the executor queue manager inside the
    compute process (reference: TFNode.py:221)."""

    def __init__(
        self,
        mgr,
        train_mode=True,
        qname_in="input",
        qname_out="output",
        input_mapping=None,
    ):
        self.mgr = mgr
        self.train_mode = train_mode
        self.qname_in = qname_in
        self.qname_out = qname_out
        self.done_feeding = False
        # Sorted column order matches the driver's df.select(sorted(cols))
        # convention (reference: TFNode.py:239-241, pipeline.py:411-413).
        self.input_tensors = (
            sorted(input_mapping.keys()) if input_mapping is not None else None
        )
        #: rows unwrapped from a Block but not yet consumed by a batch
        self._pending = []
        self._pending_pos = 0
        #: queue proxies are cached: creating one is a full manager
        #: round trip (~100ms) and next_batch used to pay it per call
        self._qin = None
        self._qout = None
        #: shm feed ring (TFOS_SHM_FEED): attached lazily from the
        #: manager kv; None = queue-only feeding
        self._ring = None
        self._ring_checked = False
        self._ring_producer_warned = False  # one log line per death
        #: which source produced the last item ("ring" | "queue") —
        #: next_batch blocks on the hot source, polls the other
        self._hot_source = "ring"
        #: wire accounting (docs/data_plane.md): bytes/records/rows
        #: received over the feed plane.  Ring records count their
        #: exact wire length; queue blocks count their column/row
        #: payload bytes (pickle framing excluded — the payload is
        #: what dtype narrowing shrinks, and the number is comparable
        #: across transports).
        self.wire_bytes = 0
        self.wire_records = 0
        self.wire_rows = 0
        #: records that arrived through the shm ring (the rest came by
        #: manager queue) — how a caller proves the native ring carried
        #: the feed instead of the silent queue fallback
        self.ring_records = 0
        # fleet telemetry twins of the wire accounting (null
        # singletons when TFOS_TELEMETRY=0): the same numbers
        # wire_stats() reports, published into the process registry so
        # the driver's fleet view carries feed-plane throughput
        from tensorflowonspark_tpu import telemetry

        reg = telemetry.get_registry()
        self._m_bytes = reg.counter("feed.wire_bytes")
        self._m_records = reg.counter("feed.wire_records")
        self._m_rows = reg.counter("feed.wire_rows")

    _RING_SENTINEL = object()  # internal: ring produced a block

    def _account(self, nbytes, nrows):
        self.wire_bytes += int(nbytes)
        self.wire_records += 1
        self.wire_rows += int(nrows)
        self._m_bytes.inc(int(nbytes))
        self._m_records.inc()
        self._m_rows.inc(int(nrows))

    def _account_item(self, item):
        """Wire accounting for a queue-delivered element (Block /
        ColumnarBlock / bare row): payload bytes + row count."""
        if isinstance(item, ColumnarBlock):
            self._account(_columns_nbytes(item.columns), item.count)
        elif isinstance(item, Block):
            self._account(
                sum(_row_nbytes(r) for r in item.items), len(item.items)
            )
        else:
            self._account(_row_nbytes(item), 1)

    def wire_stats(self):
        """Cumulative feed-plane wire accounting: ``wire_bytes`` (ring
        records at exact wire length, queue blocks at payload bytes),
        ``records`` (``ring_records`` of them through the shm ring),
        ``rows``, and derived ``bytes_per_row`` — the
        number the narrow-dtype plane shrinks (docs/data_plane.md;
        asserted >= 3x smaller for uint8-vs-float32 image columns in
        tests/test_dataplane.py)."""
        return {
            "wire_bytes": self.wire_bytes,
            "records": self.wire_records,
            "ring_records": self.ring_records,
            "rows": self.wire_rows,
            "bytes_per_row": (
                self.wire_bytes / self.wire_rows if self.wire_rows else 0.0
            ),
        }

    def _fetch(self):
        """Block until the next feed element arrives; returns it.

        Ring elements are installed as pending directly and signalled
        with ``_RING_SENTINEL``; queue elements (rows, Blocks, markers,
        the ``None`` end-of-feed sentinel) are returned raw with
        ``task_done`` left to the caller's handling here.
        """
        if self._qin is None:
            self._qin = self.mgr.get_queue(self.qname_in)
        queue_in = self._qin
        if not self._ring_checked:
            self._attach_ring()
        while True:
            if self._ring is not None:
                # shm fast path: rows usually arrive through the ring,
                # but control sentinels (None / EndPartition) and
                # fallback Blocks (oversized rows, inference feeds) come
                # via the queue.  Poll both, blocking on whichever
                # produced LAST (the hot source) so either path runs at
                # full rate; switching sources costs one 50ms miss.  (A
                # fixed non-blocking queue poll throttled to 10/s capped
                # queue-fed rows at ~2.5k rows/s; blocking on the wrong source starved the
                # other.)
                if self._hot_source == "queue":
                    try:
                        return queue_in.get(block=True, timeout=0.05)
                    except queue_mod.Empty:
                        rec = self._ring_pop(0)
                        if rec is None:
                            continue
                        self._hot_source = "ring"
                        self._install_ring_record(rec)
                        return self._RING_SENTINEL
                else:
                    rec = self._ring_pop(0.05)
                    if rec is not None:
                        self._install_ring_record(rec)
                        return self._RING_SENTINEL
                    try:
                        item = queue_in.get(block=False)
                        self._hot_source = "queue"
                        return item
                    except queue_mod.Empty:
                        continue
            else:
                # Bounded block, retried: an UNbounded proxied get()
                # parks a thread inside the manager server holding the
                # queue's read lock; if this process then dies, that
                # zombie thread survives it and silently swallows the
                # next item (it only discovers the dead socket when it
                # tries to reply).  A 1s bound makes any zombie expire
                # within a second of the death — the supervisor's
                # queue-reset grace period relies on this constant.
                try:
                    return queue_in.get(block=True, timeout=1.0)
                except queue_mod.Empty:
                    continue

    def _install_ring_record(self, rec):
        """Decode one ring record, install it as pending, and account
        its EXACT wire length (the ring frame is the wire payload)."""
        self._set_pending(_decode_ring_record(rec))
        self._account(len(rec), self._pending_left())
        self.ring_records += 1

    def _ring_pop(self, timeout):
        """Ring pop with producer-liveness handling: a dead feeder
        (its pid is announced in the ring header, see
        :class:`~tensorflowonspark_tpu.data.shm_ring.ShmRing`) turns
        the would-be-infinite ring wait into a logged miss — the feed
        drops to the queue path, where control sentinels and the
        cluster's heartbeat/ledger recovery (PR 1) own the failure.
        A NEW feeder for a later partition re-announces itself, which
        re-arms the ring."""
        from tensorflowonspark_tpu.data import shm_ring

        try:
            return self._ring.pop(timeout=timeout)
        except shm_ring.ProducerDiedError as e:
            if not self._ring_producer_warned:
                self._ring_producer_warned = True
                logger.warning("%s; falling back to the queue path", e)
            return None

    def _set_pending(self, obj):
        """Install a ring/queue block as the pending element (a row list
        or a :class:`ColumnarBlock`)."""
        self._pending = obj
        self._pending_pos = 0

    def _pending_left(self):
        n = (
            self._pending.count
            if isinstance(self._pending, ColumnarBlock)
            else len(self._pending)
        )
        return n - self._pending_pos

    def _pending_rows(self):
        """Row-objects view of the pending element (converts a columnar
        block ONCE — the row-mode compat path)."""
        if isinstance(self._pending, ColumnarBlock):
            self._pending = self._pending.rows()
        return self._pending

    def next_batch(self, batch_size):
        """Gets a batch of items from the input queue.

        Blocks until items are available (or the ``None`` end-of-feed
        sentinel is seen).  Returns a list of items, or — when
        ``input_mapping`` was provided — a dict of named column lists
        (reference: TFNode.py:243-288).  Training loops should prefer
        :meth:`next_arrays`, which consumes columnar blocks with zero
        per-row Python.
        """
        queue_in = None
        tensors = [] if self.input_tensors is None else {
            tensor: [] for tensor in self.input_tensors
        }
        count = 0

        def _consume(item):
            if self.input_tensors is None:
                tensors.append(item)
            else:
                for i, tensor in enumerate(self.input_tensors):
                    tensors[tensor].append(item[i])

        while count < batch_size:
            if self._pending_left() > 0:
                rows = self._pending_rows()
                _consume(rows[self._pending_pos])
                self._pending_pos += 1
                count += 1
                continue
            if self.done_feeding:
                # calls after end-of-feed return what's left instead of
                # blocking on a drained queue (reference: TFNode.py:258
                # loops `while not done_feeding`)
                break
            item = self._fetch()
            if item is self._RING_SENTINEL:
                continue  # pending installed by _fetch
            queue_in = self._qin
            if item is None:
                # End-of-feed: mark done and stop (reference: TFNode.py:265-268)
                queue_in.task_done()
                self.done_feeding = True
                break
            elif isinstance(item, (Block, ColumnarBlock)):
                self._set_pending(
                    item.items if isinstance(item, Block) else item
                )
                self._account_item(item)
                queue_in.task_done()
            elif isinstance(item, EndPartition):
                # Truncate the batch at a partition boundary
                # (reference: TFNode.py:268-274)
                queue_in.task_done()
                if count > 0:
                    break
            else:
                _consume(item)
                self._account_item(item)
                count += 1
                queue_in.task_done()
        logger.debug("next_batch() returning %d items", count)
        return tensors

    def next_arrays(self, batch_size):
        """Columnar fast path: a batch as stacked numpy columns.

        Consumes :class:`ColumnarBlock` elements by SLICING — no
        per-row Python objects anywhere (the Spark→HBM staging layout;
        row Blocks interleaved in the stream are stacked as a fallback).

        Returns ``(columns, count)`` where ``columns`` is a tuple of
        arrays (tuple/field rows), a dict of arrays (dict rows or
        ``input_mapping``), or a single array (scalar rows); ``count``
        is the number of rows (< ``batch_size`` at a partition
        boundary; 0 with ``columns=None`` at end-of-feed).
        """
        pieces = []  # per-fragment column sets
        count = 0
        scalar = False
        while count < batch_size:
            left = self._pending_left()
            if left == 0 and self.done_feeding:
                break  # post-end-of-feed calls must not block
            if left > 0:
                if isinstance(self._pending, ColumnarBlock):
                    take = min(batch_size - count, left)
                    pos = self._pending_pos
                    cols = self._pending.columns
                    sl = (
                        {
                            k: v[pos : pos + take]
                            for k, v in cols.items()
                        }
                        if isinstance(cols, dict)
                        else tuple(c[pos : pos + take] for c in cols)
                    )
                    scalar = scalar or self._pending._scalar
                    pieces.append(sl)
                    self._pending_pos += take
                    count += take
                else:
                    # row fallback: stack the pending rows into columns
                    take = min(batch_size - count, left)
                    rows = self._pending[
                        self._pending_pos : self._pending_pos + take
                    ]
                    blk = pack_columnar(rows)
                    if blk is None:
                        raise TypeError(
                            "next_arrays() requires fixed-shape numeric "
                            "rows; use next_batch() for object rows"
                        )
                    scalar = scalar or blk._scalar
                    pieces.append(blk.columns)
                    self._pending_pos += take
                    count += take
                continue
            item = self._fetch()
            if item is self._RING_SENTINEL:
                continue
            queue_in = self._qin
            if item is None:
                queue_in.task_done()
                self.done_feeding = True
                break
            elif isinstance(item, ColumnarBlock):
                self._set_pending(item)
                self._account_item(item)
                queue_in.task_done()
            elif isinstance(item, Block):
                self._set_pending(item.items)
                self._account_item(item)
                queue_in.task_done()
            elif isinstance(item, EndPartition):
                queue_in.task_done()
                if count > 0:
                    break
            else:
                self._set_pending([item])
                self._account_item(item)
                queue_in.task_done()
        if count == 0:
            return None, 0
        cols = _concat_pieces(pieces)
        if self.input_tensors is not None:
            if isinstance(cols, dict):
                # dict rows: select + order by the mapping's sorted keys
                # (mirrors next_batch's sorted-column contract)
                cols = {k: cols[k] for k in self.input_tensors}
            else:
                seq = (cols,) if not isinstance(cols, tuple) else cols
                cols = dict(zip(self.input_tensors, seq))
        elif scalar and isinstance(cols, tuple) and len(cols) == 1:
            cols = cols[0]
        logger.debug("next_arrays() returning %d rows", count)
        return cols, count

    def _attach_ring(self):
        """Attach the node's shm feed ring if the runtime advertised one
        (TFOS_SHM_FEED; see cluster/node.py and data/shm_ring.py)."""
        self._ring_checked = True
        try:
            info = self.mgr.get("shm_ring")._getvalue()
        except Exception:  # noqa: BLE001 - kv read is best effort
            info = None
        if info:
            from tensorflowonspark_tpu.data import shm_ring

            ring = shm_ring.ShmRing(info["name"])
            # wire-format negotiation: the segment header tags the
            # record encoding its producer writes; a tag this build
            # doesn't know means frames would MIS-decode — stay on the
            # queue path (correct, just slower) instead
            tag = ring.format_tag()
            if tag not in shm_ring.KNOWN_FORMATS:
                logger.warning(
                    "shm ring %s carries unknown wire-format tag %d "
                    "(this build knows %s); staying on the queue path",
                    info["name"], tag, shm_ring.KNOWN_FORMATS,
                )
                ring.close(unlink=False)
                return
            self._ring = ring
            logger.info(
                "consuming from shm feed ring %s (wire format %d)",
                info["name"], tag,
            )

    def should_stop(self):
        """True once the feeder posted the end-of-feed sentinel
        (reference: TFNode.py:290-292)."""
        return self.done_feeding

    def commit_partitions(self):
        """Promote every *delivered* feed partition to *committed* in
        this node's :class:`~tensorflowonspark_tpu.cluster.manager.PartitionLedger`.

        Call immediately AFTER a checkpoint save has been made durable
        (``Checkpointer.save(..., wait=True)`` or
        ``wait_until_finished()``): a committed partition is one the
        elastic restart path will never requeue, so committing before
        durability would turn a crash into silent data loss.  The
        ``train_on_feed(checkpointer=...)`` resume hook sequences this
        correctly.  Returns the number of partitions promoted (0 when
        feeding isn't elastic — the ledger is simply empty)."""
        try:
            return int(self.mgr.ledger("commit")._getvalue())
        except Exception:  # noqa: BLE001 - pre-ledger manager (rolling
            logger.warning(  # upgrade): requeue stays conservative
                "partition-ledger commit failed; partitions stay "
                "requeue-eligible", exc_info=True,
            )
            return 0

    def batch_results(self, results):
        """Push a batch of inference results to the output queue
        (reference: TFNode.py:294-305).  Ships the whole batch as one
        Block — one manager RPC (the feed-side optimization, mirrored)."""
        if self._qout is None:
            self._qout = self.mgr.get_queue(self.qname_out)
        self._qout.put(Block(results), block=True)

    def terminate(self):
        """Terminate data feeding early: set node state to 'terminating'
        and drain the input queue so blocked feeders are released
        (reference: TFNode.py:307-329)."""
        logger.info("terminate() invoked")
        self.mgr.set("state", "terminating")

        from tensorflowonspark_tpu.cluster import manager

        if not self._ring_checked:
            self._attach_ring()
        if self._ring is not None:
            # release feeders blocked on a full ring: keep discarding
            # until the ring stays empty (an in-flight feeder refills it
            # as space frees) — the queue-path drain's shm twin
            import time as _time

            hard_end = _time.monotonic() + 30
            idle_end = _time.monotonic() + 2
            ring_count = 0
            while _time.monotonic() < min(hard_end, idle_end):
                if self._ring_pop(0.05) is None:
                    continue
                ring_count += 1
                idle_end = _time.monotonic() + 2
            logger.info("terminate() drained %d ring blocks", ring_count)
            # release this consumer's mapping — a feed outliving its
            # cluster run must not pin the (unlinked) segment in memory
            self._ring.close(unlink=False)
            self._ring = None
        if self._qin is None:
            self._qin = self.mgr.get_queue(self.qname_in)
        count = manager.drain(self._qin, timeout=5)
        logger.info("terminate() drained %d items from input queue", count)

    # ------------------------------------------------------------------
    # TPU-native batch pipeline (SURVEY.md §7 step 3)
    # ------------------------------------------------------------------

    def batches(self, batch_size, stack=True, pad_to_batch=False):
        """Generator of batches until end-of-feed.

        The JAX analogue of the reference examples' ``rdd_generator`` →
        ``tf.data.Dataset.from_generator`` idiom (reference:
        examples/mnist/keras/mnist_spark.py:33-47), folded into the
        framework so user code shrinks.

        Args:
          batch_size: items per batch.
          stack: stack each column into a single ``np.ndarray``.
          pad_to_batch: zero-pad the final short batch to ``batch_size``
            (static shapes keep XLA from recompiling the jitted step);
            yields ``(batch, n_valid)`` tuples when set.
        """
        while not self.should_stop():
            batch = self.next_batch(batch_size)
            n = _batch_len(batch)
            if n == 0:
                continue
            if stack:
                batch = _stack_batch(batch)
            if pad_to_batch:
                if n < batch_size:
                    batch = _pad_batch(batch, batch_size)
                yield batch, n
            else:
                yield batch


def _columns_nbytes(cols):
    vals = cols.values() if isinstance(cols, dict) else cols
    return sum(getattr(np.asarray(v), "nbytes", 0) for v in vals)


def _row_nbytes(row):
    """Cheap payload-byte estimate of one row object (arrays exact,
    bytes/str by length, everything else 8 — scalars and refs)."""
    vals = (
        row.values() if isinstance(row, dict)
        else row if isinstance(row, (tuple, list))
        else (row,)
    )
    total = 0
    try:
        for v in vals:
            n = getattr(v, "nbytes", None)
            if n is None:
                n = len(v) if isinstance(v, (bytes, str)) else 8
            total += n
    except TypeError:
        return 0
    return total


def _concat_pieces(pieces):
    """Join per-fragment column sets (single fragment: no copy)."""
    first = pieces[0]
    if len(pieces) == 1:
        return first
    if isinstance(first, dict):
        return {
            k: np.concatenate([p[k] for p in pieces]) for k in first
        }
    return tuple(
        np.concatenate([p[i] for p in pieces]) for i in range(len(first))
    )


def _batch_len(batch):
    if isinstance(batch, dict):
        return len(next(iter(batch.values()))) if batch else 0
    return len(batch)


def _stack_batch(batch):
    """Rows → columnar numpy arrays (host-side, ready for device_put).

    Fast path: homogeneous row lists stack in ONE ``np.asarray`` —
    the old ``np.stack([np.asarray(r) for r in batch])`` materialized
    every row twice (per-row array + the stacked copy).  Ragged or
    object rows fall back to the per-row path (whose ``np.stack``
    raises the same shape error it always did)."""
    if isinstance(batch, dict):
        return {k: np.asarray(v) for k, v in batch.items()}
    try:
        arr = np.asarray(batch)
    except ValueError:
        arr = None  # ragged rows: modern numpy refuses the single pass
    if arr is not None and arr.dtype != object:
        return arr
    rows = [np.asarray(r) for r in batch]
    return np.stack(rows)


def _pad_batch(batch, batch_size):
    def pad(a):
        n = batch_size - a.shape[0]
        if n <= 0:
            return a
        widths = [(0, n)] + [(0, 0)] * (a.ndim - 1)
        return np.pad(a, widths)

    if isinstance(batch, dict):
        return {k: pad(v) for k, v in batch.items()}
    return pad(batch)


def prefetch_to_device(
    iterator, size=2, sharding=None, preprocess=None, host_prefetch=False
):
    """Double-buffered host→device transfer.

    Keeps ``size`` batches in flight: batch N+1's ``jax.device_put`` (an
    async HBM DMA on TPU) overlaps the compute consuming batch N —
    the zero-copy staging the reference's JoinableQueue feed path lacks
    (SURVEY.md §7 'Hard parts: feed-path throughput').

    Args:
      iterator: yields pytrees of numpy arrays (or ``(batch, n)`` tuples
        from ``batches(pad_to_batch=True)`` — the batch is device-put,
        the valid-row count ``n`` STAYS a host int: shipping it to HBM
        made every consumer that reads the count pay a device→host sync
        per batch).
      size: number of in-flight device batches (>= 1).
      sharding: optional ``jax.sharding.Sharding`` for multi-chip
        placement of each batch (data-parallel feeding).
      preprocess: optional on-device preprocess — a callable or a
        :func:`~tensorflowonspark_tpu.data.preprocess.make_preprocess`
        kwargs dict — jitted and applied AFTER the ``device_put``, so
        narrow wire dtypes (uint8 pixels) cross the host→HBM link
        narrow and widen in HBM (docs/data_plane.md).  Deterministic
        only here (no rng); use ``SyncTrainer(device_preprocess=...)``
        for rng-bearing augmentation fused into the train step.
      host_prefetch: run the ITERATOR (host-side decode/stacking) plus
        the ``device_put`` dispatch on a background thread with a
        bounded ``size``-deep buffer, so host decode of batch N+1
        overlaps compute on batch N — the last stage of the
        decode→ring→device pipeline.  Order is preserved; iterator
        exceptions re-raise in the consumer.
    """
    import collections

    import jax

    if size < 1:
        raise ValueError(
            "prefetch_to_device size must be >= 1, got {0}".format(size)
        )

    pre = None
    if preprocess is not None:
        from tensorflowonspark_tpu.data import preprocess as pp_mod

        pre = jax.jit(pp_mod.resolve_preprocess(preprocess))

    def put_tree(tree):
        if sharding is not None:
            tree = jax.tree_util.tree_map(
                lambda x: jax.device_put(x, sharding), tree
            )
        else:
            tree = jax.tree_util.tree_map(jax.device_put, tree)
        return pre(tree) if pre is not None else tree

    def put(item):
        # (batch, n) from pad_to_batch: only the batch goes to device;
        # the host-side row count must never become a device scalar
        if (
            isinstance(item, tuple)
            and len(item) == 2
            and isinstance(item[1], (int, np.integer))
        ):
            return (put_tree(item[0]), int(item[1]))
        return put_tree(item)

    if host_prefetch:
        return _host_prefetch_gen(iterator, put, size)

    def _sync_gen():
        q = collections.deque()
        for item in iterator:
            q.append(put(item))
            if len(q) >= size:
                yield q.popleft()
        while q:
            yield q.popleft()

    return _sync_gen()


def _host_prefetch_gen(iterator, put, size):
    """Background-thread variant of prefetch_to_device: the worker
    drains the iterator and dispatches ``device_put`` into a bounded
    queue; the consumer generator yields in order.  The worker is a
    daemon and honors a stop flag, so abandoning the generator (or the
    consumer erroring out) cannot deadlock on a full buffer."""
    import queue as _q
    import threading

    out_q = _q.Queue(maxsize=size)
    stop = threading.Event()

    def worker():
        try:
            for item in iterator:
                msg = ("ok", put(item))
                while not stop.is_set():
                    try:
                        out_q.put(msg, timeout=0.1)
                        break
                    except _q.Full:
                        continue
                if stop.is_set():
                    return
            msg = ("end", None)
        except BaseException as e:  # noqa: BLE001 - forwarded to consumer
            msg = ("err", e)
        while not stop.is_set():
            try:
                out_q.put(msg, timeout=0.1)
                return
            except _q.Full:
                continue

    t = threading.Thread(
        target=worker, daemon=True, name="prefetch-host"
    )
    t.start()

    def gen():
        try:
            while True:
                kind, payload = out_q.get()
                if kind == "end":
                    return
                if kind == "err":
                    raise payload
                yield payload
        finally:
            stop.set()

    return gen()
