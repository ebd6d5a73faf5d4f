"""Per-process flight recorder: always-on ring, fault-triggered dumps.

An aircraft black box records continuously and is read after the
crash.  Same contract here (ISSUE 11 tentpole): every instrumented
process keeps its recent spans, journal events, and metric state live
in bounded rings (the tracer, journal, and registry it already runs),
and the :class:`FlightRecorder` freezes them into a **dump bundle**
the instant a fault event lands — while the evidence is still in
memory, before a restart or teardown erases it.

Triggers ride the journal's listener bus (telemetry/journal.py): the
recorder subscribes once, and any event whose ``kind`` is in
:data:`DUMP_TRIGGERS` — or whose severity is ``page`` — produces a
dump.  Because every fault site already marks the tracer and marks
bridge into the journal, the trigger set covers, with zero new
call-site code:

- ``watchdog_fire`` — a wedged serving dispatch (serving_engine.py);
- ``swap_rollback`` — a weight generation rolled back (canary or
  probation-window failure);
- ``restart`` / ``executor_restart`` — a supervisor rebirth of a dead
  compute process (supervisor.py / cluster.py);
- ``executor_dead`` — the driver monitor declaring a node permanently
  dead (page severity);
- ``leader_failover`` — the hierarchical gradient plane re-electing a
  dead DCN leader (parallel/hier_ps.py);
- any ``page``-severity SLO alert (``alert_firing`` from the
  SloEngine).

A dump bundle is one JSON file: the trigger event, the journal rings,
the tracer's span ring (starts in Unix seconds, the journal events'
clock, so the forensics analyzer can align spans across executors
with the heartbeat-estimated clock offsets), the registry snapshot
and the delta since the recorder started, and process identity.  Dumps are rate-limited per
trigger kind and capped per process — a crash loop must not fill the
disk.

Driver-side collection: a recorder attached to a node kv
(:meth:`FlightRecorder.attach_kv`) publishes its dump index under
``blackbox_dumps``; ``TPUCluster.collect_dumps()`` reads every node's
index through the existing manager connections — no new wire protocol.

``install()`` is the one-call idempotent setup
(``_compute_process_main``, the node supervisor, ``ServingEngine``,
and ``HealthPlane`` all call it); ``TFOS_BLACKBOX=0`` disables the
whole module.
"""

import json
import logging
import os
import tempfile
import threading
import time

from tensorflowonspark_tpu.telemetry import journal as _journal
from tensorflowonspark_tpu.telemetry import registry as _registry
from tensorflowonspark_tpu.telemetry import tracing as _tracing

logger = logging.getLogger(__name__)

#: Env kill-switch for the recorder alone (the journal/tracer keep
#: running): TFOS_BLACKBOX=0.
BLACKBOX_ENV = "TFOS_BLACKBOX"

#: Where dumps land (env-tunable: TFOS_BLACKBOX_DIR); default
#: ``<tmp>/tfos_blackbox``.
DUMP_DIR_ENV = "TFOS_BLACKBOX_DIR"

#: Event kinds that trigger a dump regardless of severity (any
#: ``page``-severity event triggers too).
DUMP_TRIGGERS = frozenset({
    "watchdog_fire",
    "swap_rollback",
    "restart",
    "executor_restart",
    "restart_budget_exhausted",
    "executor_dead",
    "leader_failover",
})

#: Bundle format tag (the forensics analyzer's dispatch key).
BUNDLE_FORMAT = "tfos-blackbox-1"

#: Per-process dump cap and per-kind rate limit (seconds) — crash
#: loops must not fill the disk (env-tunable).
MAX_DUMPS = int(os.environ.get("TFOS_BLACKBOX_MAX_DUMPS", "16"))
MIN_INTERVAL = float(os.environ.get("TFOS_BLACKBOX_MIN_INTERVAL", "5.0"))


def _env_enabled():
    return os.environ.get(BLACKBOX_ENV, "1").lower() not in (
        "0", "false", "off", "no",
    )


class FlightRecorder(object):
    """Always-on recorder over one process's journal/tracer/registry.

    Args:
      journal, tracer, registry: the rings to freeze (defaults: the
        process-wide singletons).
      dump_dir: where bundles land (created on first dump).
      triggers: event kinds that dump (default :data:`DUMP_TRIGGERS`;
        ``page`` severity always triggers).
      max_dumps / min_interval: the disk-protection bounds (the cap is
        per recorder ≈ per process; the interval per trigger kind).
      clock: wall-clock source (injectable for tests).
    """

    def __init__(self, journal=None, tracer=None, registry=None,
                 dump_dir=None, triggers=None, max_dumps=None,
                 min_interval=None, clock=None):
        self.journal = journal or _journal.get_journal()
        self.tracer = tracer or _tracing.get_tracer()
        self.registry = registry or _registry.get_registry()
        self.dump_dir = os.fspath(
            dump_dir
            or os.environ.get(DUMP_DIR_ENV)
            or os.path.join(tempfile.gettempdir(), "tfos_blackbox")
        )
        self.triggers = (
            DUMP_TRIGGERS if triggers is None else frozenset(triggers)
        )
        self.max_dumps = MAX_DUMPS if max_dumps is None else int(max_dumps)
        self.min_interval = (
            MIN_INTERVAL if min_interval is None else float(min_interval)
        )
        self._clock = clock or time.time
        self._lock = threading.Lock()
        self._last_dump = {}   # kind -> wall time of its last dump
        self._seq = 0
        self._started = False
        self._mgr = None
        self._baseline = None
        self._m_dumps = self.registry.counter("blackbox.dumps")
        self._m_suppressed = self.registry.counter(
            "blackbox.dumps_suppressed"
        )
        #: dump records this recorder produced:
        #: ``{"path", "reason", "time", "trigger"}``
        self.dumps = []

    # -- lifecycle ------------------------------------------------------

    def start(self):
        """Subscribe the dump trigger to the journal (idempotent) and
        snapshot the metrics baseline the bundle deltas against."""
        with self._lock:
            if self._started:
                return self
            self._started = True
        self._baseline = self.registry.snapshot()
        self.journal.add_listener(self._on_event)
        return self

    def stop(self):
        with self._lock:
            self._started = False
        self.journal.remove_listener(self._on_event)

    def attach_kv(self, mgr):
        """Publish this recorder's dump index into a node manager kv
        (``blackbox_dumps``) after every dump, so the driver can
        collect bundles through its existing manager connections
        (``TPUCluster.collect_dumps``)."""
        self._mgr = mgr
        self._publish_index()
        return self

    # -- triggering -----------------------------------------------------

    def _on_event(self, ev):
        if ev.kind not in self.triggers and ev.severity != "page":
            return
        self.dump(ev.kind, trigger=ev)

    def dump(self, reason, trigger=None):
        """Freeze the rings into one bundle file; returns its path, or
        None when suppressed (cap / rate limit / disabled journal)."""
        now = self._clock()
        with self._lock:
            if len(self.dumps) >= self.max_dumps:
                self._m_suppressed.inc()
                return None
            last = self._last_dump.get(reason)
            if last is not None and now - last < self.min_interval:
                self._m_suppressed.inc()
                return None
            self._last_dump[reason] = now
            self._seq += 1
            seq = self._seq
        bundle = self.bundle(reason, trigger=trigger, now=now)
        try:
            os.makedirs(self.dump_dir, exist_ok=True)
            path = os.path.join(
                self.dump_dir,
                "blackbox-{0}-{1:03d}-{2}.json".format(
                    os.getpid(), seq, _safe(reason)
                ),
            )
            with open(path, "w") as f:
                json.dump(bundle, f)
        except (OSError, TypeError, ValueError):
            logger.warning(
                "flight recorder could not write a dump for %r",
                reason, exc_info=True,
            )
            return None
        rec = {
            "path": path, "reason": reason, "time": now,
            "executor": self.journal.executor,
        }
        with self._lock:
            self.dumps.append(rec)
        self._m_dumps.inc()
        logger.warning(
            "flight recorder: dumped %r bundle to %s", reason, path
        )
        self._publish_index()
        return path

    def bundle(self, reason, trigger=None, now=None):
        """The in-memory dump bundle (what :meth:`dump` serializes)."""
        now = self._clock() if now is None else now
        delta = None
        snap = self.registry.snapshot()
        if self._baseline is not None:
            try:
                delta = _registry.snapshot_delta(snap, self._baseline)
            except Exception:  # noqa: BLE001 - delta is advisory
                delta = None
        return {
            "format": BUNDLE_FORMAT,
            "reason": reason,
            "time": now,
            "pid": os.getpid(),
            "executor": self.journal.executor,
            "trigger": trigger.to_dict() if trigger is not None else None,
            "events": [e.to_dict() for e in self.journal.events()],
            "spans": self.tracer.spans(),
            "metrics": snap,
            "metrics_delta": delta,
        }

    def _publish_index(self):
        if self._mgr is None:
            return
        try:
            with self._lock:
                index = list(self.dumps)
            self._mgr.set("blackbox_dumps", index)
        except Exception:  # noqa: BLE001 - kv is best effort
            logger.warning(
                "flight recorder could not publish its dump index",
                exc_info=True,
            )


def _safe(name):
    return "".join(
        c if c.isalnum() or c in "-_" else "_" for c in str(name)
    )[:48]


def load_dump(path):
    """Read a dump bundle back; raises ValueError on a non-bundle."""
    with open(path) as f:
        data = json.load(f)
    if not isinstance(data, dict) or data.get("format") != BUNDLE_FORMAT:
        raise ValueError(
            "{0} is not a {1} bundle".format(path, BUNDLE_FORMAT)
        )
    return data


_GLOBAL = None
_GLOBAL_LOCK = threading.Lock()


def install(**kwargs):
    """Start (or return) the process-wide recorder.  Returns None when
    disabled (``TFOS_BLACKBOX=0`` or telemetry off) — callers treat
    the recorder as strictly optional."""
    global _GLOBAL
    if not _env_enabled() or not _registry.get_registry().enabled:
        return None
    if _GLOBAL is None:
        with _GLOBAL_LOCK:
            if _GLOBAL is None:
                _GLOBAL = FlightRecorder(**kwargs).start()
    return _GLOBAL


def get_recorder():
    """The installed process-wide recorder, or None."""
    return _GLOBAL
