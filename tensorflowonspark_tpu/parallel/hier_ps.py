"""ICI-native hierarchical parameter server: the two-tier gradient plane.

The flat async PS (:mod:`tensorflowonspark_tpu.parallel.ps`) pays a
device→host gradient readback plus a TCP round trip on EVERY step,
and PR 3's codecs only shrank the wire, not the wall.
This module restructures the plane per the MPI-aggregation literature
(PAPERS.md: "Distributed TensorFlow with MPI", "CUDA-Aware MPI" —
ICI-aware here): keep aggregation on the interconnect, and cross the
host/network boundary only where topology forces it.

Two tiers:

- **Intra-pod (ICI)** — PS shard state (params + optimizer slots) is
  **device-resident**, replicated along the mesh's ``ps`` axis
  (:data:`~tensorflowonspark_tpu.parallel.mesh.AXIS_PS`).  Each step
  is ONE jitted program: grads psum over ICI (XLA inserts the
  collective for the replicated params / ps-sharded batch), the
  optimizer update applies on device, and the step's gradient folds
  into a device-resident accumulation window.  Nothing crosses to the
  host — the ``grad_readback`` telemetry span never fires on this
  path (asserted in tests/test_hier_ps.py).  :func:`ici_mean` /
  :func:`ici_reduce_scatter_mean` expose the explicit shard_map
  collectives for the aggregation math itself.
- **Cross-pod (DCN)** — every ``push_every`` steps the pod's
  accumulated mean gradient window ships to the global PS ensemble
  through the existing compressed wire (error-feedback codecs, delta
  replies — PR 3 intact), but only from the **pod leader**; the reply
  (the globally-mixed params) installs back into the device state
  between steps.  Staleness is bounded by ``max_inflight`` windows.

**Leader election & exactly-once windows.**  Every pod member holds the
identical device-resident state (the ICI tier replicates it), so any
member can take over the DCN duty: the leader is simply the lowest
live member id (:func:`elect_leader`; the supervisor re-elects on
elastic restarts and publishes to the node kv).  Each pushed window
carries a monotonically increasing ``(pod, window)`` id; the server's
ledger applies each id at most once, and a new leader resumes from
``PSClient.window_floor(pod) + 1``, re-pushing its predecessor's
unacknowledged windows — landed-but-unacked ones dedup server-side, so
no gradient is double-applied and none is silently dropped (the
kill-the-leader chaos e2e asserts both, tests/test_chaos.py).  Error
feedback is per-leader-epoch: a fresh leader starts with a clean
residual (its predecessor's residual died with it — bounded, like any
EF state on a crashed worker).

See docs/communication.md "Two-tier gradient plane" for the topology
diagram and tuning guidance.
"""

import logging
import queue as _queue
import threading

import numpy as np

from tensorflowonspark_tpu import compat
from tensorflowonspark_tpu.parallel.mesh import AXIS_DATA, AXIS_FSDP, AXIS_PS, build_mesh

logger = logging.getLogger(__name__)


class LeaderKilled(RuntimeError):
    """The pod leader's DCN duty was killed (chaos injection or a real
    wire death) — the signal the trainer's failover path catches to
    re-elect and resume."""


def elect_leader(members, dead=()):
    """The pod's DCN leader: the LOWEST live member id.

    Deterministic and coordination-free — every member computes the
    same answer from the same liveness view, which the heartbeat plane
    already agrees on (the supervisor's re-rendezvous barrier).  Raises
    when nobody is left alive.
    """
    live = sorted(m for m in members if m not in set(dead))
    if not live:
        raise RuntimeError(
            "no live members to elect a leader from: members={0} "
            "dead={1}".format(sorted(members), sorted(dead))
        )
    return live[0]


def current_leader(mgr, default=None):
    """The leader the supervisor published into the node manager kv
    (``hier_leader``), or ``default`` when unset/unreachable — how a
    compute process learns its pod's DCN duty without talking to the
    reservation server itself."""
    try:
        v = mgr.get("hier_leader")
        v = getattr(v, "_getvalue", lambda: v)()
        return default if v is None else int(v)
    except Exception:  # noqa: BLE001 - kv is observability-grade
        return default


# ----------------------------------------------------------------------
# on-device leafwise optimizers (jnp twins of ps.OPTIMIZERS)
# ----------------------------------------------------------------------


class DeviceOptimizer(object):
    """Jittable leafwise optimizer matching the PS server's numpy rules
    (``ps.OPTIMIZERS``) — the apply-update half of the device-resident
    shard.  ``init(params) -> state``; ``update(params, grads, state)
    -> (params, state)``; both pure, both traced into the trainer's
    fused step.  Parity with the numpy implementations is unit-tested
    (tests/test_hier_ps.py), which is what makes the hierarchical
    plane's local tier consistent with the global tier's arithmetic.
    """

    def __init__(self, name, kwargs):
        self.name = name
        self.kwargs = dict(kwargs or {})

    def spec(self):
        return [self.name, dict(self.kwargs)]

    def init(self, params):
        import jax
        import jax.numpy as jnp

        zeros = lambda: jax.tree.map(jnp.zeros_like, params)  # noqa: E731
        if self.name == "sgd":
            if self.kwargs.get("momentum"):
                return {"v": zeros()}
            return {}
        if self.name == "adagrad":
            return {"acc": zeros()}
        if self.name == "adam":
            return {"m": zeros(), "v": zeros(),
                    "t": jnp.zeros((), jnp.int32)}
        raise ValueError(
            "unknown device optimizer {0!r}; supported: "
            "['adagrad', 'adam', 'sgd']".format(self.name)
        )

    def update(self, params, grads, state):
        import jax
        import jax.numpy as jnp

        k = self.kwargs
        if self.name == "sgd":
            lr = k.get("learning_rate", 0.01)
            momentum = k.get("momentum", 0.0)
            if momentum:
                v = jax.tree.map(
                    lambda vv, g: momentum * vv + g, state["v"], grads
                )
                return (
                    jax.tree.map(lambda p, vv: p - lr * vv, params, v),
                    {"v": v},
                )
            return (
                jax.tree.map(lambda p, g: p - lr * g, params, grads),
                state,
            )
        if self.name == "adagrad":
            lr = k.get("learning_rate", 0.01)
            eps = k.get("eps", 1e-10)
            acc = jax.tree.map(
                lambda a, g: a + g * g, state["acc"], grads
            )
            return (
                jax.tree.map(
                    lambda p, g, a: p - lr * g / (jnp.sqrt(a) + eps),
                    params, grads, acc,
                ),
                {"acc": acc},
            )
        if self.name == "adam":
            lr = k.get("learning_rate", 1e-3)
            b1, b2 = k.get("b1", 0.9), k.get("b2", 0.999)
            eps = k.get("eps", 1e-8)
            t = state["t"] + 1
            m = jax.tree.map(
                lambda mm, g: b1 * mm + (1 - b1) * g, state["m"], grads
            )
            v = jax.tree.map(
                lambda vv, g: b2 * vv + (1 - b2) * g * g, state["v"], grads
            )
            tf = t.astype(jnp.float32)
            bc1 = 1 - b1 ** tf
            bc2 = 1 - b2 ** tf
            return (
                jax.tree.map(
                    lambda p, mm, vv: p - lr * (mm / bc1)
                    / (jnp.sqrt(vv / bc2) + eps),
                    params, m, v,
                ),
                {"m": m, "v": v, "t": t},
            )
        raise ValueError("unknown device optimizer {0!r}".format(self.name))


def build_device_optimizer(spec):
    """Resolve a named optimizer spec (the same grammar as the PS
    server's ``_build_optimizer`` — named specs only, never code)."""
    name, kwargs = spec
    return DeviceOptimizer(str(name), kwargs)


# ----------------------------------------------------------------------
# explicit ICI collectives (the aggregation math, shard_map form)
# ----------------------------------------------------------------------


def ici_mean(stacked, mesh, axis=AXIS_PS):
    """psum-mean a per-member gradient stack over the mesh's ``axis``.

    ``stacked`` is a pytree whose leaves carry a leading member dim of
    the axis' width, sharded (or shardable) along ``axis``; the result
    is the member-mean, replicated — one jitted shard_map program, the
    collective running on ICI.  Width-1 (or absent) axes short-circuit
    to a plain squeeze.  The implicit-GSPMD twin of this (replicated
    params + ps-sharded batch inside one jit) is what
    :class:`HierTrainer` rides; this explicit form is the unit-testable
    statement of the aggregation math.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    width = mesh.shape.get(axis, 1)
    if width == 1:
        return jax.tree.map(lambda x: jnp.squeeze(jnp.asarray(x), 0), stacked)

    def body(tree):
        return jax.tree.map(
            lambda x: jax.lax.psum(jnp.squeeze(x, 0), axis) / width, tree
        )

    fn = compat.shard_map(
        body, mesh=mesh, in_specs=P(axis), out_specs=P(),
        check_vma=False,
    )
    stacked = jax.tree.map(
        lambda x: jax.device_put(
            jnp.asarray(x), NamedSharding(mesh, P(axis))
        ),
        stacked,
    )
    return jax.jit(fn)(stacked)


def ici_reduce_scatter_mean(stacked, mesh, axis=AXIS_PS):
    """Reduce-scatter form of :func:`ici_mean`: each shard owns the
    summed 1/width slice of the member-mean (``lax.psum_scatter``
    tiled over the leading data dim), and the ``P(axis)``-stacked
    output reassembles the full mean — bandwidth-optimal when the
    apply-update is itself sharded along ``axis``.  Leaf dim 0 must be
    divisible by the axis width.  Numerically equal to
    :func:`ici_mean` (asserted in tests/test_hier_ps.py)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    width = mesh.shape.get(axis, 1)
    if width == 1:
        return jax.tree.map(lambda x: jnp.squeeze(jnp.asarray(x), 0), stacked)

    def body(tree):
        return jax.tree.map(
            lambda x: jax.lax.psum_scatter(
                jnp.squeeze(x, 0), axis, scatter_dimension=0, tiled=True
            ) / width,
            tree,
        )

    fn = compat.shard_map(
        body, mesh=mesh, in_specs=P(axis), out_specs=P(axis),
        check_vma=False,
    )
    stacked = jax.tree.map(
        lambda x: jax.device_put(
            jnp.asarray(x), NamedSharding(mesh, P(axis))
        ),
        stacked,
    )
    return jax.jit(fn)(stacked)


# ----------------------------------------------------------------------
# DCN tier: the pod leader's compressed window pusher
# ----------------------------------------------------------------------


class DcnLink(object):
    """One leader epoch's connection to the global PS ensemble.

    Wraps a :class:`~tensorflowonspark_tpu.parallel.ps.PSClient`
    (compressed pushes under error feedback, delta replies — the PR 3
    wire, untouched) behind a background pusher thread:

    - ``submit(delta, base)`` hands a DEVICE parameter-delta tree (and
      the local params it was measured at) over and returns
      immediately; the thread performs the device→host readback (span
      ``hier.dcn_readback`` — deliberately NOT ``grad_readback``: that
      span is the flat plane's per-step wall, and its absence is the
      hierarchical contract) and the wire round trip off the dispatch
      path.  At most ``max_inflight`` windows may be queued-or-flying
      (bounded staleness; ``submit`` blocks past that).
    - every window carries ``(pod, window_seq)``; the server ledger
      applies each at most once.  ``attach`` resumes the sequence from
      the server's :meth:`~tensorflowonspark_tpu.parallel.ps.PSClient.
      window_floor` — a failover leader continues numbering where the
      ensemble actually is, and re-pushes via :meth:`resubmit`.
    - ``fault_fn(seq)`` is the chaos hook
      (:func:`~tensorflowonspark_tpu.testing.chaos.hier_leader_fault_fn`):
      raising :class:`LeaderKilled` there is exactly what a leader
      death mid-push looks like to the trainer.
    """

    _STOP = object()

    def __init__(self, addresses, optimizer, pod_id="pod0", member_id=0,
                 codec=None, reply_codec=None, error_feedback=True,
                 max_inflight=2, fault_fn=None, timeout=60):
        from tensorflowonspark_tpu import telemetry
        from tensorflowonspark_tpu.parallel.ps import PSClient

        self.pod_id = str(pod_id)
        self.member_id = member_id
        self.optimizer = optimizer
        self.client = PSClient(
            addresses, timeout=timeout, codec=codec,
            reply_codec=reply_codec, error_feedback=error_feedback,
        )
        self._fault_fn = fault_fn
        self._slots = threading.Semaphore(max(1, int(max_inflight)))
        self._q = _queue.Queue()
        self._lock = threading.Lock()
        self._fresh = None
        self.error = None
        self._pushed = []
        self._acked = []
        self._pending = {}  # seq -> device window (submitted, unacked)
        self._next_seq = None
        self.resumed_from = None
        reg = telemetry.get_registry()
        self._m_windows = reg.counter("hier.dcn_windows")
        self._m_dedup = reg.counter("hier.dcn_dedup")
        self._m_rb_hist = reg.histogram("hier.dcn_readback_sec")
        self._m_push_hist = reg.histogram("hier.dcn_push_sec")
        self._tracer = telemetry.get_tracer()
        self._thread = threading.Thread(
            target=self._loop, daemon=True,
            name="hier-dcn-%s-m%s" % (self.pod_id, member_id),
        )
        self._thread.start()
        # fleet health plane: the DCN link's state rides /status
        # (telemetry/health.py; one slot per pod, latest link wins —
        # exactly the leader-epoch semantics).  Weakref-bound so a
        # retired leader epoch's link (and its PSClient sockets) is
        # never pinned by the provider registry
        import weakref

        from tensorflowonspark_tpu.telemetry import health as _health

        _ref = weakref.ref(self)

        def _link_status():
            link = _ref()
            return (
                {"retired": True} if link is None
                else link.health_status()
            )

        _health.register_status_provider(
            "hier_ps.%s" % self.pod_id, _link_status
        )

    def health_status(self):
        """Compact DCN-link state for the health plane's ``/status``:
        which member holds the leader duty, how far the window
        sequence has advanced, and the in-flight backlog."""
        with self._lock:
            pending = len(self._pending)
        return {
            "pod": self.pod_id,
            "member": self.member_id,
            "next_window": self._next_seq,
            "resumed_from": self.resumed_from,
            "pushed": len(self._pushed),
            "acked": len(self._acked),
            "inflight": pending,
            "error": str(self.error) if self.error else None,
        }

    # -- lifecycle -----------------------------------------------------

    def attach(self, params_template):
        """Join the global ensemble (idempotent PS init) and resume the
        window sequence from the server's applied floor; returns the
        live global params."""
        live = self.client.init(params_template, self.optimizer)
        self.resync()
        return live

    def resync(self):
        """Re-read the server's applied window floor and resume the
        sequence after it — what a member that just GAINED the leader
        duty does before its first push (its predecessor may have
        advanced the ledger since this link attached)."""
        floor = self.client.window_floor(self.pod_id)
        self._next_seq = floor + 1
        self.resumed_from = floor
        return floor

    def submit(self, delta, base):
        """Queue a device delta window under the next sequence id;
        ``base`` is the local params the delta was measured AT (the
        reply correction anchors on it).  Blocks only when
        ``max_inflight`` windows are already queued-or-flying.
        Returns the sequence assigned."""
        if self._next_seq is None:
            raise RuntimeError("DcnLink.attach() must run before submit()")
        # tfoslint: disable=TFOS006(staleness-window semaphore: the DCN pusher thread releases it when the window lands - cross-thread handoff by design)
        self._slots.acquire()
        seq, self._next_seq = self._next_seq, self._next_seq + 1
        with self._lock:
            self._pending[seq] = (delta, base)
        self._pushed.append(seq)
        self._q.put((seq, delta, base))
        return seq

    def resubmit(self, seq, delta, base):
        """Failover re-push: a predecessor's unacked window, sequence
        preserved — the server ledger dedups it if it actually
        landed."""
        # tfoslint: disable=TFOS006(same staleness-window semaphore handoff as submit)
        self._slots.acquire()
        with self._lock:
            self._pending[seq] = (delta, base)
        self._pushed.append(seq)
        self._q.put((seq, delta, base))

    def _loop(self):
        import jax

        while True:
            item = self._q.get()
            if item is self._STOP:
                return
            if isinstance(item, threading.Event):  # flush marker
                item.set()
                continue
            seq, delta, base = item
            try:
                if self.error is not None:
                    # leader already declared dead: leave the window
                    # pending for the successor instead of pushing on
                    # a broken epoch
                    continue
                t0 = self._tracer.now()
                host = jax.device_get(delta)
                dur = self._tracer.now() - t0
                self._m_rb_hist.observe(dur)
                self._tracer.add(
                    "hier.dcn_readback", t0, dur, trace="hier", window=seq
                )
                if self._fault_fn is not None:
                    self._fault_fn(seq)
                with self._tracer.span(
                    "hier.dcn_push", trace="hier", window=seq,
                    pod=self.pod_id,
                ):
                    fresh = self.client.push_pull(
                        host,
                        header_extra={"pod": self.pod_id, "window": seq},
                    )
                self._m_push_hist.observe(self._tracer.now() - t0)
                self._m_windows.inc()
                with self._lock:
                    self._fresh = (fresh, base)
                    self._pending.pop(seq, None)
                self._acked.append(seq)
            except Exception as e:  # noqa: BLE001 - surfaced to trainer
                if self.error is None:
                    self.error = e
            finally:
                self._slots.release()

    # -- observability -------------------------------------------------

    def fresh(self):
        """Latest landed reply as ``(global host params, base device
        params)`` — cleared on read.  Both states are CUMULATIVE, so
        the newest pair supersedes any skipped intermediates (the
        correction ``global - base`` is everything cross-pod the local
        state hasn't absorbed)."""
        with self._lock:
            fresh, self._fresh = self._fresh, None
        return fresh

    def unacked(self):
        """``{seq: (delta, base)}`` of submitted-but-unacknowledged
        device windows — what a successor re-pushes after failover."""
        with self._lock:
            return dict(self._pending)

    def ledger(self):
        """This epoch's push accounting (the chaos e2e asserts on it)."""
        return {
            "member": self.member_id,
            "pod": self.pod_id,
            "resumed_from": self.resumed_from,
            "pushed": list(self._pushed),
            "acked": list(self._acked),
            "pending": sorted(self.unacked()),
        }

    def flush(self):
        """Block until every queued window was processed (landed or
        parked pending on error)."""
        ev = threading.Event()
        self._q.put(ev)
        ev.wait()

    def stop(self, stop_servers=False):
        self._q.put(self._STOP)
        self._thread.join(timeout=10)
        if stop_servers:
            self.client.stop()
        else:
            self.client.close()


# ----------------------------------------------------------------------
# the hierarchical trainer
# ----------------------------------------------------------------------


class HierTrainer(object):
    """Two-tier async trainer: jitted on-device PS in the pod, compressed
    DCN windows across pods.

    Args:
      loss_fn: ``loss_fn(params, batch) -> scalar`` (the
        :class:`~tensorflowonspark_tpu.parallel.ps.AsyncTrainer`
        contract).
      ps_addresses: global PS shard addresses for the DCN tier, or
        None/empty for a single-pod (pure-ICI) run.
      optimizer: named spec for the LOCAL tier's on-device apply
        (:class:`DeviceOptimizer`).  The global tier runs the
        ``delta`` rule — it folds pod deltas in directly, since each
        delta is already the product of this optimizer.
      mesh: mesh carrying a ``ps`` axis (default: all local devices on
        ``ps``).  Params/optimizer state replicate; the batch shards
        along ``(ps, data, fsdp)`` and XLA's gradient psum IS the ICI
        aggregation.
      push_every: ICI steps per DCN window.  A window ships the pod's
        PARAMETER DELTA since the last synced base (``params - ref``);
        the reply's correction (``global - base``) folds the other
        pods' content back in without discarding local progress —
        single-pod runs see a near-zero correction and keep pure
        on-device speed.
      dcn_scale: the global ``delta`` rule's mixing factor (<1 damps
        concurrent many-pod pushes; default 1.0).
      max_inflight: bounded staleness of the DCN tier, in windows.
      codec / reply_codec / error_feedback: the PR 3 wire knobs,
        leader-side.
      pod_id: this pod's ledger namespace on the global shards.
      members / member_id / leader_fn: DCN-duty election.  ``members``
        lists the pod's candidate ids (default: just ``member_id``);
        ``leader_fn()`` overrides the internal lowest-live-member rule
        (production wires :func:`current_leader` over the supervisor's
        kv here).  A non-leader computes identical windows and drops
        them — its state stays bit-identical, which is what makes
        failover a pure bookkeeping step.
      fault_fn: chaos hook forwarded to the :class:`DcnLink`.
      overlap: split the fused step into TWO dispatches — backward
        (grad + the ICI psum XLA appends to it) and psum-consume +
        apply — handed to the runtime back to back WITHOUT a sync, so
        the collective tail of step N's backward overlaps the host's
        dispatch of step N+1 and the DCN readback thread (the
        CUDA-Aware-MPI overlap result, applied to ICI).  The gradient
        accumulators double-buffer: each backward writes fresh
        buffers while the previous step's apply consumes (and, via
        donation, recycles) the prior pair — the backward never
        stalls on an in-flight apply's memory.  Numerics are
        IDENTICAL to the fused step (same op sequence, parity-tested
        in tests/test_hier_ps.py); telemetry spans
        ``hier.overlap_grad`` / ``hier.overlap_apply`` record the
        dispatch pipeline, and the overlap is span-asserted (apply N
        stays open past grad N+1's dispatch).

    ``step(batch)`` returns the (device-resident) params after the
    fused ICI step; no host readback happens anywhere on that path.
    """

    def __init__(self, loss_fn, ps_addresses=None,
                 optimizer=("sgd", {"learning_rate": 0.01}),
                 mesh=None, push_every=8, max_inflight=2, codec=None,
                 reply_codec=None, error_feedback=True, pod_id="pod0",
                 members=None, member_id=0, leader_fn=None,
                 data_axes=(AXIS_PS, AXIS_DATA, AXIS_FSDP),
                 fault_fn=None, timeout=60, dcn_scale=1.0,
                 overlap=False):
        from tensorflowonspark_tpu import telemetry

        if push_every < 1:
            raise ValueError(
                "push_every must be >= 1, got {0}".format(push_every)
            )
        self.loss_fn = loss_fn
        self.optimizer = (optimizer[0], dict(optimizer[1] or {}))
        self.mesh = mesh if mesh is not None else build_mesh({AXIS_PS: -1})
        self.data_axes = data_axes
        self.push_every = int(push_every)
        self.max_inflight = int(max_inflight)
        self.pod_id = str(pod_id)
        self.member_id = member_id
        self.members = tuple(members) if members else (member_id,)
        if member_id not in self.members:
            raise ValueError(
                "member_id {0} not in members {1}".format(
                    member_id, self.members
                )
            )
        self._leader_fn = leader_fn
        self._dead = set()
        self._link_kwargs = dict(
            codec=codec, reply_codec=reply_codec,
            error_feedback=error_feedback, max_inflight=max_inflight,
            fault_fn=fault_fn, timeout=timeout,
        )
        self.dcn_optimizer = ("delta", {"scale": float(dcn_scale)})
        self.addresses = list(ps_addresses or [])
        self._opt = build_device_optimizer(self.optimizer)
        self._state = None      # (params, opt_state) device trees
        self._ref = None        # last synced base (device tree)
        self._window_steps = 0
        self._was_leader = False
        self._loss = None       # device scalar of the last step
        self._link = None
        self._epochs = []       # closed DcnLink ledgers (failover audit)
        self._step_fn = None
        self._sub_fn = None
        self._copy_fn = None
        self._corr_fn = None
        self.overlap = bool(overlap)
        self._grad_fn = None
        self._apply_fn = None
        self._apply_open = None  # (t0, step_idx) of the in-flight apply
        self._step_idx = 0
        reg = telemetry.get_registry()
        self._m_steps = reg.counter("hier.ici_steps")
        self._m_failover = reg.counter("hier.leader_failovers")
        self._g_leader = reg.gauge("hier.leader")
        self._tracer = telemetry.get_tracer()
        if self.addresses:
            self._open_link()

    # -- live retune ---------------------------------------------------

    def set_push_every(self, push_every):
        """Retune the ICI-steps-per-DCN-window cadence in place.

        Safe mid-training: ``push_every`` is read at every step's
        window check, so the new cadence takes effect at the next
        window boundary — no quiesce, no link rebuild.  This is the
        actuation seam the live re-planner drives when measured DCN
        RTT drifts off the planned cadence (push_every x step_time >
        RTT).  Returns the previous value.
        """
        push_every = int(push_every)
        if push_every < 1:
            raise ValueError(
                "push_every must be >= 1, got {0}".format(push_every)
            )
        old = self.push_every
        self.push_every = push_every
        if push_every != old:
            self._tracer.mark(
                "push_every_retune", trace="hier_ps",
                old=old, new=push_every, pod=self.pod_id,
            )
        return old

    # -- election ------------------------------------------------------

    def leader(self):
        """The current DCN leader's member id."""
        if self._leader_fn is not None:
            got = self._leader_fn()
            if got is not None:
                return got
        return elect_leader(self.members, self._dead)

    def acting_member(self):
        """The member identity this trainer's DCN duty currently acts
        as.  Normally ``member_id``; after an in-process failover
        (single-process pod: all candidate members live in this
        trainer) it is the successor epoch's id — the live link's."""
        return (
            self._link.member_id if self._link is not None
            else self.member_id
        )

    def is_leader(self):
        return self.leader() == self.acting_member()

    def _open_link(self, member_id=None):
        member_id = self.member_id if member_id is None else member_id
        self._link = DcnLink(
            self.addresses, self.dcn_optimizer, pod_id=self.pod_id,
            member_id=member_id, **self._link_kwargs
        )
        self._g_leader.set(member_id)
        self._tracer.mark(
            "leader_elected", trace="hier", pod=self.pod_id,
            member=member_id,
        )

    @property
    def client(self):
        """The DCN tier's PSClient (wire accounting lives there), or
        None on a pure-ICI run."""
        return self._link.client if self._link is not None else None

    def dcn_epochs(self):
        """Every leader epoch's ledger, oldest first, the live one
        last — the failover audit the chaos e2e asserts on."""
        out = list(self._epochs)
        if self._link is not None:
            out.append(self._link.ledger())
        return out

    # -- jitted programs -----------------------------------------------

    def _build_step(self):
        import jax

        loss_fn, opt = self.loss_fn, self._opt

        def fused(params, opt_state, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            new_params, new_opt = opt.update(params, grads, opt_state)
            return new_params, new_opt, loss

        # donation recycles the whole shard state in place: the apply-
        # update IS the on-device program, there is no host copy to
        # invalidate
        return jax.jit(fused, donate_argnums=(0, 1))

    def _build_split_step(self):
        """The overlapped pair (``overlap=True``): backward (whose
        tail is the ICI psum GSPMD appends for the replicated params)
        and psum-consume + apply, dispatched back to back with no
        intervening sync.  The grads tree is the double-buffered
        accumulator: each backward call produces a FRESH buffer pair
        while the previous pair is being consumed — and donated, so
        the runtime recycles it — by the in-flight apply."""
        import jax

        loss_fn, opt = self.loss_fn, self._opt
        grad_fn = jax.jit(jax.value_and_grad(loss_fn))

        def apply(params, opt_state, grads):
            return opt.update(params, grads, opt_state)

        apply_fn = jax.jit(apply, donate_argnums=(0, 1, 2))
        return grad_fn, apply_fn

    def _overlap_step(self, params, opt_state, batch):
        """One overlapped step: dispatch backward, close the PREVIOUS
        step's apply span (it was held open across this dispatch — the
        recorded overlap), dispatch apply, leave its span open."""
        with self._tracer.span(
            "hier.overlap_grad", trace="hier", step=self._step_idx,
        ):
            loss, grads = self._grad_fn(params, batch)
        if self._apply_open is not None:
            t0, idx = self._apply_open
            # the previous apply's pipeline interval ends only now —
            # AFTER this step's backward was dispatched: that ordering
            # is the overlap, and the span records it
            self._tracer.add(
                "hier.overlap_apply", t0, self._tracer.now() - t0,
                trace="hier", step=idx,
            )
        self._apply_open = (self._tracer.now(), self._step_idx)
        new_params, new_opt = self._apply_fn(params, opt_state, grads)
        self._step_idx += 1
        return new_params, new_opt, loss

    def _close_overlap_span(self):
        if self._apply_open is not None:
            t0, idx = self._apply_open
            self._apply_open = None
            self._tracer.add(
                "hier.overlap_apply", t0, self._tracer.now() - t0,
                trace="hier", step=idx,
            )

    def _build_helpers(self):
        import jax
        import jax.numpy as jnp

        # window close: delta vs the synced base, plus a fresh-buffer
        # copy of params (the live tree is DONATED into every step, so
        # the base must own its buffers)
        self._sub_fn = jax.jit(
            lambda a, b: jax.tree.map(lambda x, y: x - y, a, b)
        )
        self._copy_fn = jax.jit(
            lambda t: jax.tree.map(jnp.copy, t)
        )
        # reply install: fold the cross-pod correction (global - base)
        # into BOTH the live params and the base, preserving local
        # progress made while the window flew
        # no donation here: base/ref may alias across the two installs
        # (params and ref both correct against the same base tree)
        self._corr_fn = jax.jit(
            lambda p, g, b: jax.tree.map(
                lambda pp, gg, bb: pp + (gg - bb), p, g, b
            )
        )

    # -- lifecycle -----------------------------------------------------

    def init(self, params):
        """Place the PS shard state on device (params replicated over
        the mesh, optimizer slots alongside) and join the global
        ensemble when a DCN tier is configured; returns the device
        params."""
        import jax

        from tensorflowonspark_tpu.parallel import sharding as sh

        if self._link is not None:
            # seed/join the global tier first: a restarted pod adopts
            # the globally-live params instead of its init template
            params = self._link.attach(params)
        device_params = jax.tree.map(
            lambda p: jax.device_put(np.asarray(p), sh.replicated(self.mesh)),
            params,
        )
        opt_state = jax.jit(self._opt.init)(device_params)
        opt_state = sh.canonicalize_on_mesh(opt_state, self.mesh)
        self._state = (device_params, opt_state)
        if self._step_fn is None:
            self._step_fn = self._build_step()
            if self.overlap:
                self._grad_fn, self._apply_fn = self._build_split_step()
            self._build_helpers()
        # the synced base starts at the (globally-agreed) init params;
        # its buffers are its own — the live tree is donated every step
        self._ref = self._copy_fn(device_params)
        self._window_steps = 0
        self._was_leader = self.is_leader() if self._link else False
        return device_params

    @property
    def params(self):
        """The device-resident params (no copy, no readback)."""
        if self._state is None:
            raise RuntimeError("call init(params) first")
        return self._state[0]

    def last_loss(self):
        """Device scalar loss of the most recent step (pull it to host
        only when YOU want the sync)."""
        return self._loss

    # -- the step ------------------------------------------------------

    def step(self, batch):
        """One in-pod step: fused grad + ICI aggregation + on-device
        apply + window fold, one dispatch, zero host transfers.  At
        ``push_every`` cadence the leader ships the window to the DCN
        tier (background thread); a landed reply's global params
        install before the NEXT step (host→device only)."""
        import jax

        from tensorflowonspark_tpu.parallel import sharding as sh

        if self._state is None:
            raise RuntimeError("call init(params) first")
        self._check_link()
        self._install_fresh()
        if batch is not None:
            batch = sh.shard_batch(batch, self.mesh, self.data_axes)
        params, opt_state = self._state
        if self.overlap:
            params, opt_state, self._loss = self._overlap_step(
                params, opt_state, batch
            )
        else:
            params, opt_state, self._loss = self._step_fn(
                params, opt_state, batch
            )
        self._state = (params, opt_state)
        self._window_steps += 1
        self._m_steps.inc()
        if self._link is not None and self._window_steps >= self.push_every:
            self._close_window()
        return params

    def _close_window(self):
        lead = self.is_leader()
        if lead and not self._was_leader:
            # just GAINED the duty (supervisor re-election): resume the
            # window sequence from the server's ledger, not from this
            # link's stale attach-time floor
            self._link.resync()
        self._was_leader = lead
        params = self._state[0]
        if lead:
            delta = self._sub_fn(params, self._ref)
            base = self._copy_fn(params)
            self._ref = base
            self._link.submit(delta, base)
        else:
            # non-leaders advance the base identically (their window
            # would be the same ICI-aggregated tree — pushing it too
            # would double-count); keeping the base in lockstep is what
            # makes a takeover's first delta start from the right spot
            self._ref = self._copy_fn(params)
        self._window_steps = 0

    def _install_fresh(self):
        import jax

        from tensorflowonspark_tpu.parallel import sharding as sh

        if self._link is None:
            return
        fresh = self._link.fresh()
        if fresh is None:
            return
        if jax.process_count() > 1:
            # a multi-process pod must install the correction
            # identically on every process; only the leader holds the
            # reply, so the install rides the next re-rendezvous
            # instead (documented limitation — docs/communication.md)
            logger.warning(
                "skipping cross-pod correction install on a "
                "multi-process pod (leader-only reply)"
            )
            return
        global_host, base = fresh
        device_global = jax.tree.map(
            lambda p: jax.device_put(
                np.asarray(p), sh.replicated(self.mesh)
            ),
            global_host,
        )
        # fold (global - base) into the live params AND the synced
        # base: local progress made while the window flew is preserved,
        # and the next delta measures pure local content
        params, opt_state = self._state
        self._state = (
            self._corr_fn(params, device_global, base), opt_state
        )
        self._ref = self._corr_fn(self._ref, device_global, base)

    # -- failover ------------------------------------------------------

    def _check_link(self):
        if self._link is None or self._link.error is None:
            return
        err = self._link.error
        survivors = [
            m for m in self.members
            if m not in self._dead and m != self._link.member_id
        ]
        retriable = isinstance(
            err, (LeaderKilled, ConnectionError, OSError, RuntimeError)
        )
        if not survivors or not retriable:
            raise err
        # the leader epoch died: record it, elect the next member, and
        # hand the dead epoch's unacked windows to the successor (the
        # server ledger dedups any that actually landed).  This trainer
        # then ACTS as the successor — the single-process-pod model,
        # where every candidate member lives in this trainer.  In a
        # multi-process pod each process passes members=[own_id] plus a
        # supervisor-backed leader_fn, so a dead leader's duty moves to
        # another PROCESS (via re-election + resync) and this path
        # correctly re-raises instead of impersonating.
        dead_link = self._link
        self._dead.add(dead_link.member_id)
        self._m_failover.inc()
        # flight-recorder dump trigger (telemetry/blackbox.py): the
        # DCN leader died mid-push — exactly the incident the
        # forensics analyzer reconstructs from this process's rings
        self._tracer.mark(
            "leader_failover", trace="hier", severity="page",
            pod=self.pod_id, dead_member=dead_link.member_id,
            error=str(err),
        )
        logger.warning(
            "pod %s leader (member %s) died mid-push (%s); re-electing",
            self.pod_id, dead_link.member_id, err,
        )
        dead_link.flush()
        pending = dead_link.unacked()
        self._epochs.append(dead_link.ledger())
        dead_link.stop()
        new_leader = elect_leader(self.members, self._dead)
        self._open_link(member_id=new_leader)
        # attach with the CURRENT device params as template (idempotent
        # join — the live global values win, our template is ignored)
        import jax

        self._link.attach(jax.device_get(self._state[0]))
        self._was_leader = self.is_leader()
        floor = self._link.resumed_from
        resubmitted = 0
        for seq in sorted(pending):
            if seq > floor:
                delta, base = pending[seq]
                self._link.resubmit(seq, delta, base)
                resubmitted += 1
        # the successor continues numbering AFTER the retained windows
        self._link._next_seq = max(
            self._link._next_seq, (max(pending) + 1) if pending else 0
        )
        logger.info(
            "pod %s: member %s took over the DCN duty (floor %d, "
            "%d window(s) re-pushed)",
            self.pod_id, new_leader, floor, resubmitted,
        )

    # -- drain / feed / teardown ---------------------------------------

    def drain(self):
        """Ship a partial window (leader), wait for every in-flight DCN
        window to land, and install the final cross-pod correction;
        returns the device params.  Raises a non-retriable link error;
        a retriable one re-elects first."""
        self._close_overlap_span()
        if self._link is not None:
            self._check_link()
            if self._window_steps and self._state is not None:
                self._close_window()
            self._link.flush()
            self._check_link()
            self._link.flush()
            self._install_fresh()
        return self._state[0] if self._state is not None else None

    def train_on_feed(self, feed, batch_size, preprocess=None,
                      max_steps=None, columnar=False, step_callback=None,
                      log_every=100):
        """Feed-driven hierarchical training: pull globally-agreed
        batches (the same all-hosts barrier as
        :meth:`~tensorflowonspark_tpu.parallel.dp.SyncTrainer.
        train_on_feed` — every pod process steps the same count, so the
        ICI collective never strands a straggler) and run :meth:`step`
        per batch.  Returns the step count."""
        from tensorflowonspark_tpu.parallel import dp

        steps = 0
        while True:
            if max_steps is not None and steps >= max_steps:
                break
            group, stopped = dp.collect_ready_group(
                feed, batch_size, 1, columnar=columnar,
                preprocess=preprocess,
            )
            if not group:
                if stopped:
                    logger.info("global stop after %d steps", steps)
                break
            if step_callback is not None:
                step_callback(steps)
            self.step(group[0])
            steps += 1
            if log_every and steps % log_every == 0:
                logger.info("hier step %d", steps)
            if stopped:
                logger.info("global stop after %d steps", steps)
                break
        self.drain()
        return steps

    def stop(self, stop_servers=False):
        try:
            if self._link is not None:
                self.drain()
        except Exception:  # noqa: BLE001 - teardown must proceed
            pass
        if self._link is not None:
            self._epochs.append(self._link.ledger())
            self._link.stop(stop_servers=stop_servers)
            self._link = None
