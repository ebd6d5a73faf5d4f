"""Cluster bootstrap rendezvous: the framework's own coordination component.

Re-designed from the reference's ``reservation.py`` (reference:
tensorflowonspark/reservation.py) which implements a TCP server on the
driver that executors register with, plus a client-side barrier.  Design
changes for the TPU build:

- **Typed JSON frames instead of pickle** (reference used pickled python
  objects, reservation.py:68-97 — an RCE hazard on an open port).  Frames
  are 4-byte big-endian length + UTF-8 JSON.
- Node metadata carries TPU topology (chip count, coords, process index)
  instead of GPU info, so the driver can assemble a
  ``jax.distributed.initialize`` coordination plan and a logical mesh.
- Same message vocabulary as the reference: REG / QINFO / QUERY / STOP
  (reference: reservation.py:130-146) plus LOOKUP for keyed queries.
- **HEARTBEAT frames + liveness registry** (no reference analogue — the
  reference's only failure signal was the 600s feed timeout): every
  node sends a HEARTBEAT every ``HEARTBEAT_INTERVAL`` seconds carrying
  its executor id, rendezvous *generation*, and whether its compute
  process is alive; the server-side :class:`Liveness` registry marks an
  executor dead after ``HEARTBEAT_MISS_THRESHOLD`` missed intervals, so
  the driver's ClusterMonitor detects a dead worker in seconds.

The server survives in the TPU architecture as the component that produces
the coordinator address + topology and enforces the startup barrier
(SURVEY.md §5 'Distributed communication backend').
"""

import collections
import json
import logging
import os
import select
import socket
import struct
import threading
import time

from tensorflowonspark_tpu.utils.retry import Backoff

logger = logging.getLogger(__name__)

#: Seconds between HEARTBEAT frames (env-tunable: TFOS_HEARTBEAT_INTERVAL).
HEARTBEAT_INTERVAL = float(os.environ.get("TFOS_HEARTBEAT_INTERVAL", "1.0"))

#: Missed intervals before an executor is declared dead (env-tunable:
#: TFOS_HEARTBEAT_MISS_THRESHOLD).  3 intervals balances detection speed
#: against GC-pause / scheduler-jitter false positives.
HEARTBEAT_MISS_THRESHOLD = int(
    os.environ.get("TFOS_HEARTBEAT_MISS_THRESHOLD", "3")
)

#: Env overrides for multi-homed driver hosts
#: (reference: reservation.py:25-26 TFOS_SERVER_HOST/TFOS_SERVER_PORT).
TFOS_SERVER_HOST = "TFOS_SERVER_HOST"
TFOS_SERVER_PORT = "TFOS_SERVER_PORT"

BUFSIZE = 1024 * 1024

#: Upper bound on a single frame; a bogus length prefix (e.g. stray HTTP
#: bytes hitting the port) must not wedge the select() loop in a
#: gigabyte-sized blocking read.
MAX_FRAME = 16 * 1024 * 1024

#: Per-connection socket timeout on the server side, seconds.  A client that
#: stalls mid-frame gets dropped instead of blocking the single-threaded
#: event loop for everyone else.
SERVER_SOCKET_TIMEOUT = 10.0


class Reservations(object):
    """Thread-safe store of cluster reservations
    (reference: reservation.py:31-65)."""

    def __init__(self, required):
        self.required = required
        self._lock = threading.RLock()
        self._reservations = []

    def add(self, meta):
        """Add (or refresh) a reservation.

        Registration is idempotent per ``executor_id``: a client that lost
        the OK response and re-sent REG must not count twice, or the
        barrier would release before all real nodes registered (the
        reference detects duplicates late, at TFCluster.py:355-370; we
        dedup at the source).
        """
        with self._lock:
            key = meta.get("executor_id") if isinstance(meta, dict) else None
            if key is not None:
                for i, existing in enumerate(self._reservations):
                    if isinstance(existing, dict) and existing.get("executor_id") == key:
                        self._reservations[i] = meta
                        return
            self._reservations.append(meta)

    def done(self):
        with self._lock:
            return len(self._reservations) >= self.required

    def get(self):
        with self._lock:
            return list(self._reservations)

    def remaining(self):
        with self._lock:
            return self.required - len(self._reservations)


class Liveness(object):
    """Server-side heartbeat registry.

    Tracks the last heartbeat per executor id.  An executor is *dead*
    when its newest beat is older than ``interval * miss_threshold`` —
    i.e. it missed ``miss_threshold`` consecutive heartbeats — or when
    its node explicitly reported ``compute_alive=False`` (immediate,
    no waiting out the threshold).  Executors are only tracked once
    they have beaten at least once: a cluster that never enables
    heartbeats reports nobody dead, keeping the feature opt-in.
    """

    def __init__(self, interval=None, miss_threshold=None):
        self.interval = (
            HEARTBEAT_INTERVAL if interval is None else float(interval)
        )
        self.miss_threshold = (
            HEARTBEAT_MISS_THRESHOLD
            if miss_threshold is None
            else int(miss_threshold)
        )
        self._lock = threading.Lock()
        #: executor_id -> {"t": monotonic, "generation": int,
        #:                 "compute_alive": bool, "host": str}
        self._beats = {}

    @property
    def deadline(self):
        """Seconds of silence after which an executor is dead."""
        return self.interval * self.miss_threshold

    def beat(self, executor_id, generation=0, compute_alive=True, host=""):
        with self._lock:
            self._beats[int(executor_id)] = {
                "t": time.monotonic(),
                "generation": int(generation),
                "compute_alive": bool(compute_alive),
                "host": host,
            }

    def forget(self, executor_id):
        """Drop an executor from tracking (its node left on purpose)."""
        with self._lock:
            self._beats.pop(int(executor_id), None)

    def last_seen(self, executor_id):
        """Seconds since the executor's last beat; None if never seen."""
        with self._lock:
            rec = self._beats.get(int(executor_id))
        return None if rec is None else time.monotonic() - rec["t"]

    def generation(self, executor_id):
        with self._lock:
            rec = self._beats.get(int(executor_id))
        return 0 if rec is None else rec["generation"]

    def dead(self):
        """Return ``{executor_id: diagnosis}`` for every tracked executor
        currently considered dead.  Diagnosis dicts carry ``age`` (secs
        of silence), ``reason`` and the last known ``host``/``generation``
        so the driver can name the node in its failure."""
        now = time.monotonic()
        out = {}
        with self._lock:
            for eid, rec in self._beats.items():
                age = now - rec["t"]
                if not rec["compute_alive"]:
                    out[eid] = {
                        "age": age,
                        "reason": "node reported its compute process dead",
                        "host": rec["host"],
                        "generation": rec["generation"],
                    }
                elif age > self.deadline:
                    out[eid] = {
                        "age": age,
                        "silent": True,  # inferred, not reported
                        "reason": (
                            "no heartbeat for {0:.1f}s "
                            "(> {1} x {2:.1f}s interval)".format(
                                age, self.miss_threshold, self.interval
                            )
                        ),
                        "host": rec["host"],
                        "generation": rec["generation"],
                    }
        return out

    def health(self):
        """The ``/healthz`` summary of this registry (consumed by the
        fleet health plane's exposition surface,
        telemetry/exposition.py): healthy iff no tracked executor is
        currently dead.  Carries the dead set's reasons and the worst
        heartbeat age so a probe failure names its cause."""
        dead = self.dead()
        snap = self.snapshot()
        ages = [rec["age"] for rec in snap.values()]
        return {
            "healthy": not dead,
            "executors": len(snap),
            "dead": {str(eid): d["reason"] for eid, d in dead.items()},
            "max_heartbeat_age": round(max(ages), 3) if ages else None,
            "deadline": self.deadline,
        }

    def snapshot(self):
        """Last-seen ages + metadata for every tracked executor (the
        LIVENESS query payload)."""
        now = time.monotonic()
        with self._lock:
            return {
                str(eid): {
                    "age": now - rec["t"],
                    "generation": rec["generation"],
                    "compute_alive": rec["compute_alive"],
                    "host": rec["host"],
                }
                for eid, rec in self._beats.items()
            }


class MetricsStore(object):
    """Server-side store of the newest telemetry snapshot per executor
    (the cluster half of the fleet telemetry plane — see
    telemetry/aggregate.py).  Snapshots arrive piggybacked on
    HEARTBEAT frames and are answered back out through the METRICS
    wire op; each record keeps its arrival time so the driver can
    judge staleness."""

    def __init__(self):
        self._lock = threading.Lock()
        self._snaps = {}  # executor_id -> {"metrics": dict, "t": monotonic}

    def update(self, executor_id, snapshot):
        if not isinstance(snapshot, dict):
            return
        with self._lock:
            self._snaps[int(executor_id)] = {
                "metrics": snapshot,
                "t": time.monotonic(),
            }

    def forget(self, executor_id):
        with self._lock:
            self._snaps.pop(int(executor_id), None)

    def snapshot(self):
        """``{executor_id(str): {"metrics": dict, "age": secs}}`` (str
        keys — JSON wire format, matching the liveness snapshot)."""
        now = time.monotonic()
        with self._lock:
            return {
                str(eid): {
                    "metrics": rec["metrics"],
                    "age": now - rec["t"],
                }
                for eid, rec in self._snaps.items()
            }


class ClockSync(object):
    """Per-executor clock-offset estimation from heartbeat RTTs.

    NTP's client-side sample: the heartbeater records ``t0`` (its wall
    clock before the frame), the server's reply carries
    ``server_time``, and ``t1`` lands on receipt; assuming a symmetric
    path, ``offset = server_time - (t0 + t1) / 2`` with uncertainty
    bounded by ``rtt = t1 - t0``.  The node reports each sample on its
    next beat and this registry keeps, per executor, the sample with
    the SMALLEST rtt among the last :data:`CLOCK_WINDOW` — minimum-rtt
    selection is the standard defense against queueing-delay asymmetry
    (one cleanly-timed exchange beats an average of congested ones).

    ``offset(eid)`` is the seconds to ADD to that executor's local
    wall-clock timestamps to land them on the server (driver) clock —
    what the forensics analyzer and
    :func:`~tensorflowonspark_tpu.telemetry.tracing.merge_traces`
    align merged fleet timelines with (ISSUE 11 tentpole).
    """

    #: Samples retained per executor for the min-rtt pick.
    CLOCK_WINDOW = 8

    def __init__(self):
        self._lock = threading.Lock()
        self._samples = {}  # eid -> deque[(rtt, offset)]

    def update(self, executor_id, offset, rtt):
        try:
            offset, rtt = float(offset), float(rtt)
        except (TypeError, ValueError):
            return
        if rtt < 0:
            return
        with self._lock:
            dq = self._samples.setdefault(
                int(executor_id),
                collections.deque(maxlen=self.CLOCK_WINDOW),
            )
            dq.append((rtt, offset))

    def offset(self, executor_id):
        """Best (min-rtt) offset estimate in seconds, or None when the
        executor never reported a sample."""
        with self._lock:
            dq = self._samples.get(int(executor_id))
            if not dq:
                return None
            return min(dq, key=lambda s: s[0])[1]

    def snapshot(self):
        """``{executor_id(str): {"offset": secs, "rtt": secs}}`` for
        every tracked executor (string keys — JSON wire format)."""
        with self._lock:
            out = {}
            for eid, dq in self._samples.items():
                if not dq:
                    continue
                rtt, off = min(dq, key=lambda s: s[0])
                out[str(eid)] = {"offset": off, "rtt": rtt}
            return out


def estimate_offset(t0, server_time, t1):
    """One NTP-style sample: ``(offset, rtt)`` from a request sent at
    ``t0`` (client clock), answered with ``server_time`` (server
    clock), received at ``t1`` (client clock)."""
    return float(server_time) - (float(t0) + float(t1)) / 2.0, (
        float(t1) - float(t0)
    )


class EventStore(object):
    """Server-side fleet journal: the newest typed events per executor,
    shipped piggybacked on HEARTBEAT frames (the journal half of the
    telemetry piggyback path — see telemetry/journal.py).

    One bounded ring fleet-wide (env-tunable:
    TFOS_FLEET_JOURNAL_MAX).  Per-(executor, pid) seq high-water marks
    dedup re-sent frames: journal seqs are process-monotonic, so an
    event with ``seq <= seen[(eid, pid)]`` was already stored — and a
    RESTARTED compute process (new pid) starts a fresh watermark
    instead of being masked by its dead predecessor's.
    """

    MAX_EVENTS = int(os.environ.get("TFOS_FLEET_JOURNAL_MAX", "8192"))

    def __init__(self, max_events=None):
        self._lock = threading.Lock()
        self._events = collections.deque(
            maxlen=self.MAX_EVENTS if max_events is None else int(max_events)
        )
        self._seen = {}  # (eid, pid) -> max seq stored

    def extend(self, executor_id, events):
        if not events:
            return 0
        eid = int(executor_id)
        stored = 0
        with self._lock:
            for ev in events:
                if not isinstance(ev, dict):
                    continue
                key = (eid, ev.get("pid", 0))
                seq = ev.get("seq", 0)
                if seq and seq <= self._seen.get(key, 0):
                    continue
                self._seen[key] = max(self._seen.get(key, 0), seq)
                rec = dict(ev)
                rec.setdefault("executor", eid)
                self._events.append(rec)
                stored += 1
        return stored

    def snapshot(self, limit=None):
        """Time-ordered list of stored event dicts (newest last)."""
        with self._lock:
            out = list(self._events)
        out.sort(key=lambda e: e.get("ts", 0.0))
        if limit is not None:
            out = out[-int(limit):]
        return out


class MessageSocket(object):
    """Length-prefixed JSON framing over a TCP socket
    (reference: reservation.py:68-97, re-done without pickle)."""

    def receive(self, sock):
        header = self._recv_exact(sock, 4)
        if header is None:
            raise ConnectionError("connection closed while reading header")
        (length,) = struct.unpack(">I", header)
        if length > MAX_FRAME:
            raise ConnectionError(
                "frame length {0} exceeds limit; dropping connection".format(length)
            )
        payload = self._recv_exact(sock, length)
        if payload is None:
            raise ConnectionError("connection closed while reading payload")
        return json.loads(payload.decode("utf-8"))

    def send(self, sock, msg):
        payload = json.dumps(msg).encode("utf-8")
        sock.sendall(struct.pack(">I", len(payload)) + payload)

    @staticmethod
    def _recv_exact(sock, n):
        buf = b""
        while len(buf) < n:
            chunk = sock.recv(min(n - len(buf), BUFSIZE))
            if not chunk:
                return None
            buf += chunk
        return buf


class Server(MessageSocket):
    """Driver-side rendezvous server: single-thread ``select()`` loop
    (reference: reservation.py:100-199)."""

    def __init__(self, count, heartbeat_interval=None, miss_threshold=None):
        assert count > 0
        self.reservations = Reservations(count)
        self.liveness = Liveness(heartbeat_interval, miss_threshold)
        self.metrics = MetricsStore()
        #: fleet journal + per-executor clock offsets (ISSUE 11): both
        #: fed by HEARTBEAT frames, read back via the JOURNAL wire op
        self.events = EventStore()
        self.clocks = ClockSync()
        self.done = threading.Event()
        self._stop_requested = threading.Event()
        self._listener = None
        self._journal_listener = None
        #: elastic re-rendezvous generation — bumped by REBIRTH frames
        self._generation = 0
        self._gen_lock = threading.Lock()

    @property
    def generation(self):
        with self._gen_lock:
            return self._generation

    def next_generation(self, executor_id, old_generation):
        """Atomically claim the generation a reborn executor joins.

        Monotonic and race-safe for simultaneous deaths: the first
        rebirth bumps the cluster generation; a second executor dying in
        the same window *joins* that generation instead of bumping past
        it (its ``old_generation`` is still the pre-death value)."""
        with self._gen_lock:
            self._generation = max(self._generation, int(old_generation) + 1)
            gen = self._generation
        self.liveness.beat(executor_id, generation=gen)
        return gen

    @property
    def stop_requested(self):
        return self._stop_requested.is_set()

    def start(self):
        """Bind and start the background listener; returns ``(host, port)``.

        Env overrides for multi-NIC hosts (reference: reservation.py:190-199).
        """
        from tensorflowonspark_tpu.utils.net import get_ip_address

        host = os.environ.get(TFOS_SERVER_HOST, get_ip_address())
        port = int(os.environ.get(TFOS_SERVER_PORT, 0))

        server_sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        server_sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        server_sock.bind(("", port))
        server_sock.listen(64)
        self._listener = server_sock
        addr = (host, server_sock.getsockname()[1])
        self.addr = addr

        t = threading.Thread(target=self._serve, args=(server_sock,), daemon=True)
        t.start()
        logger.info("reservation server listening on %s", addr)
        return addr

    def _serve(self, server_sock):
        # select()-based single-thread event loop (reference: reservation.py:162-187)
        inputs = [server_sock]
        while not self.done.is_set():
            try:
                readable, _, exceptional = select.select(inputs, [], [], 1.0)
            except (OSError, ValueError):
                break
            for s in readable:
                if s is server_sock:
                    try:
                        conn, _ = server_sock.accept()
                        conn.settimeout(SERVER_SOCKET_TIMEOUT)
                        inputs.append(conn)
                    except OSError:
                        pass
                else:
                    try:
                        msg = self.receive(s)
                        self._handle(s, msg)
                    except (ConnectionError, OSError, json.JSONDecodeError):
                        inputs.remove(s)
                        s.close()
                    except Exception:  # noqa: BLE001
                        # A malformed-but-valid-JSON frame (wrong shape,
                        # missing keys) must not kill the serve thread —
                        # answer with an error and keep the rendezvous up.
                        logger.exception("error handling rendezvous message")
                        try:
                            self.send(s, {"type": "ERROR", "error": "bad request"})
                        except OSError:
                            inputs.remove(s)
                            s.close()
            for s in exceptional:
                if s in inputs:
                    inputs.remove(s)
                    s.close()
        for s in inputs:
            try:
                s.close()
            except OSError:
                pass

    def _handle(self, sock, msg):
        # message vocabulary (reference: reservation.py:130-146)
        mtype = msg.get("type")
        if mtype == "REG":
            data = msg["data"]
            self.reservations.add(data)
            # A REG carrying a generation > 0 is an elastic re-rendezvous:
            # the replacement node primes the liveness registry so the
            # monitor stops counting the old incarnation's silence.
            if isinstance(data, dict) and data.get("generation"):
                self.liveness.beat(
                    data.get("executor_id", -1),
                    generation=data.get("generation", 0),
                    host=data.get("host", ""),
                )
            self.send(sock, {"type": "OK"})
        elif mtype == "HEARTBEAT":
            self.liveness.beat(
                msg.get("executor_id", -1),
                generation=msg.get("generation", 0),
                compute_alive=msg.get("compute_alive", True),
                host=msg.get("host", ""),
            )
            # telemetry snapshots piggyback on beats (the node never
            # opens a second connection just for observability)
            if msg.get("metrics") is not None:
                self.metrics.update(
                    msg.get("executor_id", -1), msg["metrics"]
                )
            # journal events + the node's NTP-style clock sample ride
            # the same frame (ISSUE 11 — still one connection)
            if msg.get("events"):
                self.events.extend(
                    msg.get("executor_id", -1), msg["events"]
                )
            clk = msg.get("clock")
            if isinstance(clk, dict):
                self.clocks.update(
                    msg.get("executor_id", -1),
                    clk.get("offset"), clk.get("rtt"),
                )
            # stop flag + cluster generation piggyback on the reply, so
            # heartbeaters double as the survivors' rebirth signal;
            # server_time is the clock-sync sample the NEXT beat
            # reports back (estimate_offset)
            self.send(
                sock,
                {
                    "type": "OK",
                    "stop": self.stop_requested,
                    "generation": self.generation,
                    "server_time": time.time(),
                },
            )
        elif mtype == "FAREWELL":
            # orderly departure: stop tracking, so a node whose work
            # completed is never misread as dead-by-silence
            self.liveness.forget(msg.get("executor_id", -1))
            self.send(sock, {"type": "OK"})
        elif mtype == "REBIRTH":
            gen = self.next_generation(
                msg.get("executor_id", -1), msg.get("generation", 0)
            )
            self.send(sock, {"type": "REBIRTH_RESP", "generation": gen})
        elif mtype == "METRICS":
            # the fleet-telemetry pull: per-executor snapshots plus the
            # liveness fields the driver merges into its fleet view
            self.send(
                sock,
                {
                    "type": "METRICS_RESP",
                    "executors": self.metrics.snapshot(),
                    "liveness": self.liveness.snapshot(),
                    "clocks": self.clocks.snapshot(),
                    "generation": self.generation,
                },
            )
        elif mtype == "JOURNAL":
            # the forensics pull: the fleet's merged typed-event record
            # plus the clock offsets that align it (ISSUE 11)
            self.send(
                sock,
                {
                    "type": "JOURNAL_RESP",
                    "events": self.events.snapshot(
                        limit=msg.get("limit")
                    ),
                    "clocks": self.clocks.snapshot(),
                    "generation": self.generation,
                },
            )
        elif mtype == "LIVENESS":
            self.send(
                sock,
                {
                    "type": "LIVENESS_RESP",
                    "executors": self.liveness.snapshot(),
                    "dead": {
                        str(k): v for k, v in self.liveness.dead().items()
                    },
                    "generation": self.generation,
                },
            )
        elif mtype == "QUERY":
            self.send(
                sock,
                {
                    "type": "QUERY_RESP",
                    "done": self.reservations.done(),
                    "stop": self.stop_requested,
                },
            )
        elif mtype == "QINFO":
            self.send(
                sock,
                {"type": "QINFO_RESP", "reservations": self.reservations.get()},
            )
        elif mtype == "STOP":
            # request_stop: streaming shutdown / early termination
            # (reference: reservation.py:142-146, used by TFSparkNode.py:497)
            self._stop_requested.set()
            self.send(sock, {"type": "OK"})
        else:
            self.send(sock, {"type": "ERROR", "error": "unknown message %r" % mtype})

    def await_reservations(self, status=None, timeout=600):
        """Block until all nodes registered; abort on error status or timeout
        (reference: reservation.py:113-128)."""
        timespent = 0.0
        while not self.reservations.done():
            logger.info(
                "waiting for %d reservations", self.reservations.remaining()
            )
            if status is not None and status.get("error"):
                raise RuntimeError(
                    "cluster startup aborted: {0}".format(status["error"])
                )
            time.sleep(1)
            timespent += 1
            if timespent > timeout:
                raise RuntimeError("timed out waiting for cluster reservations")
        logger.info("all reservations completed")
        return self.reservations.get()

    def attach_local_journal(self, executor_id=-1):
        """Feed THIS process's journal into the fleet EventStore.

        The server lives in the driver, and driver-side fault events
        (the monitor's ``executor_dead`` verdict, requeue decisions)
        never ride a heartbeat — without this bridge the fleet record
        would lack exactly the driver's view of the incident.
        ``executor_id`` defaults to ``-1``, the driver sentinel.
        Idempotent; the listener detaches on :meth:`stop`."""
        if self._journal_listener is not None:
            return self
        from tensorflowonspark_tpu.telemetry import journal as _journal

        store, eid = self.events, int(executor_id)

        def _listener(ev):
            store.extend(eid, [ev.to_dict()])

        _journal.get_journal().add_listener(_listener)
        self._journal_listener = _listener
        return self

    def stop(self):
        self.done.set()
        if self._journal_listener is not None:
            from tensorflowonspark_tpu.telemetry import journal as _journal

            _journal.get_journal().remove_listener(self._journal_listener)
            self._journal_listener = None
        if self._listener is not None:
            try:
                self._listener.close()
            except OSError:
                pass


class Client(MessageSocket):
    """Executor-side rendezvous client (reference: reservation.py:206-273)."""

    def __init__(self, server_addr, retry_deadline=None):
        self.server_addr = tuple(server_addr)
        if retry_deadline is not None:
            # instance override of the class default (heartbeaters use a
            # ~1-interval budget: blocking 30s on a dead server would
            # defeat the liveness signal they exist to provide)
            self.RETRY_DEADLINE = float(retry_deadline)
        self.sock = self._connect(self.server_addr, self.RETRY_DEADLINE)

    #: Client-side socket timeout: a stalled server must surface as a
    #: retryable error, not an unbounded block that bypasses the polling
    #: timeout in ``await_reservations``.
    SOCKET_TIMEOUT = 30.0

    #: Wall-clock budget for connect / request retries.  Backoff with
    #: jitter under a HARD deadline (utils/retry.py) replaced the seed's
    #: fixed 1s/2s/3s sleeps: a restarting server sees a desynchronized
    #: trickle instead of a lockstep stampede, and exhaustion raises a
    #: ConnectionError that names the server address.
    RETRY_DEADLINE = 30.0

    @staticmethod
    def _connect(addr, deadline=None):
        bo = Backoff(
            deadline=Client.RETRY_DEADLINE if deadline is None else deadline,
            base=0.2,
            max_delay=3.0,
        )
        for attempt in bo:
            try:
                sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
                sock.settimeout(Client.SOCKET_TIMEOUT)
                sock.connect(addr)
                return sock
            except OSError as e:
                attempt.note(e)
                logger.warning(
                    "connect to reservation server at %s failed "
                    "(attempt %d): %s", addr, attempt.attempts, e,
                )
        raise ConnectionError(
            "unable to connect to reservation server at {0} within "
            "{1:.0f}s ({2} attempts): {3}".format(
                addr, bo.deadline, bo.attempts, bo.last_error
            )
        )

    def _request(self, msg):
        """Send with backoff + reconnect under a hard deadline
        (reference: reservation.py:228-241 used three fixed-sleep tries;
        see utils/retry.py for the replacement policy)."""
        bo = Backoff(deadline=self.RETRY_DEADLINE, base=0.2, max_delay=3.0)
        for attempt in bo:
            try:
                self.send(self.sock, msg)
                return self.receive(self.sock)
            except (ConnectionError, OSError) as e:
                attempt.note(e)
                logger.warning(
                    "lost connection to reservation server at %s "
                    "(attempt %d): %s — reconnecting",
                    self.server_addr, attempt.attempts, e,
                )
                try:
                    self.sock.close()
                except OSError:
                    pass
                # connect retries share the request's remaining budget
                self.sock = self._connect(self.server_addr,
                                          self.RETRY_DEADLINE)
        raise ConnectionError(
            "unable to reach reservation server at {0} within {1:.0f}s "
            "({2} attempts): {3}".format(
                self.server_addr, bo.deadline, bo.attempts, bo.last_error
            )
        )

    def register(self, reservation):
        resp = self._request({"type": "REG", "data": reservation})
        return resp

    def get_reservations(self):
        resp = self._request({"type": "QINFO"})
        return resp["reservations"]

    def await_reservations(self, timeout=600):
        """1s-poll barrier until the cluster is fully registered
        (reference: reservation.py:262-268)."""
        done = False
        timespent = 0.0
        while not done:
            resp = self._request({"type": "QUERY"})
            done = resp["done"]
            if not done:
                time.sleep(1)
                timespent += 1
                if timespent > timeout:
                    raise RuntimeError("timed out waiting for cluster reservations")
        return self.get_reservations()

    def request_stop(self):
        """Ask the server to set the cluster-wide stop flag
        (reference: reservation.py:270-273; examples/utils/stop_streaming.py)."""
        return self._request({"type": "STOP"})

    def heartbeat(self, executor_id, generation=0, compute_alive=True,
                  host="", metrics=None, events=None, clock=None):
        """Send one HEARTBEAT frame; returns the server's reply (which
        carries the cluster-wide ``stop`` flag, so heartbeaters double
        as stop-signal listeners).  ``metrics`` optionally piggybacks a
        telemetry registry snapshot (plain dict) for the server's
        :class:`MetricsStore`; ``events`` a list of journal event
        dicts for its :class:`EventStore`; ``clock`` the node's latest
        ``{"offset", "rtt"}`` NTP-style sample for its
        :class:`ClockSync`."""
        frame = {
            "type": "HEARTBEAT",
            "executor_id": int(executor_id),
            "generation": int(generation),
            "compute_alive": bool(compute_alive),
            "host": host,
        }
        if metrics is not None:
            frame["metrics"] = metrics
        if events:
            frame["events"] = list(events)
        if clock is not None:
            frame["clock"] = clock
        return self._request(frame)

    def get_metrics(self):
        """Fetch the server's per-executor telemetry snapshots:
        ``(executors, liveness)`` dicts keyed by executor id (string
        keys — JSON wire format).  Merge with
        :func:`tensorflowonspark_tpu.telemetry.aggregate.merge_snapshots`."""
        resp = self._request({"type": "METRICS"})
        return resp["executors"], resp.get("liveness", {})

    def get_journal(self, limit=None):
        """Fetch the fleet journal: ``(events, clocks)`` — the merged
        typed-event record (list of event dicts, time-ordered) and the
        per-executor clock offsets that align it (string executor
        keys — JSON wire format)."""
        frame = {"type": "JOURNAL"}
        if limit is not None:
            frame["limit"] = int(limit)
        resp = self._request(frame)
        return resp["events"], resp.get("clocks", {})

    def get_liveness(self):
        """Fetch the server's liveness snapshot: ``(executors, dead)``
        dicts keyed by executor id (string keys — JSON wire format)."""
        resp = self._request({"type": "LIVENESS"})
        return resp["executors"], resp["dead"]

    def farewell(self, executor_id):
        """Remove this executor from liveness tracking (orderly exit)."""
        return self._request(
            {"type": "FAREWELL", "executor_id": int(executor_id)}
        )

    def rebirth(self, executor_id, generation):
        """Claim the generation a reborn executor rejoins under (see
        ``Server.next_generation`` for the simultaneous-death rule)."""
        resp = self._request(
            {
                "type": "REBIRTH",
                "executor_id": int(executor_id),
                "generation": int(generation),
            }
        )
        return int(resp["generation"])

    def get_stop_requested(self):
        resp = self._request({"type": "QUERY"})
        return resp.get("stop", False)

    def close(self):
        try:
            self.sock.close()
        except OSError:
            pass


class Heartbeater(object):
    """Background thread pumping HEARTBEAT frames to the rendezvous
    server — the node-side half of the liveness plane.

    Args:
      server_addr: ``(host, port)`` of the rendezvous server.
      executor_id: this node's logical id.
      interval: seconds between beats (default ``HEARTBEAT_INTERVAL``).
      alive_fn: zero-arg callable polled each beat; its bool rides the
        frame as ``compute_alive`` so a node whose compute process died
        is reported *immediately* instead of after the miss threshold.
      generation_fn: zero-arg callable returning the node's current
        rendezvous generation (elastic restarts bump it).
      chaos_fn: optional zero-arg callable; truthy = drop this beat
        (the chaos harness's heartbeat-delay/drop injection point —
        dropping frames here exercises exactly the miss-threshold path
        a real network partition would).
      metrics_fn: optional zero-arg callable returning a telemetry
        registry snapshot (plain dict) to piggyback on the beat — the
        node half of the fleet telemetry plane (telemetry/aggregate.py).
        A None/falsy return or a raising fn simply ships a bare beat:
        liveness must never depend on observability.
      events_fn: optional zero-arg callable returning journal event
        dicts to piggyback (the node half of the fleet journal,
        ISSUE 11).  Events whose beat failed are RETAINED (bounded)
        and re-shipped on the next successful beat — the server-side
        EventStore dedups by (pid, seq), so a retry is safe and a
        fault record survives one dropped frame.

    A beat that cannot reach the server is logged and *dropped* — the
    next interval retries with a fresh connection.  Missing frames is
    precisely the failure signal the server-side registry measures, so
    the heartbeater must never block or die trying to be reliable.

    Every beat also takes one NTP-style clock sample: ``t0`` before
    the frame, the reply's ``server_time``, ``t1`` on receipt →
    ``estimate_offset``; the sample ships on the NEXT frame so the
    server's :class:`ClockSync` can align this node's timestamps.
    """

    #: Cap on retained-but-unshipped journal events (a long partition
    #: must not grow the backlog without bound; the newest survive).
    MAX_EVENT_BACKLOG = 512

    def __init__(self, server_addr, executor_id, interval=None,
                 alive_fn=None, generation_fn=None, host="", chaos_fn=None,
                 metrics_fn=None, events_fn=None):
        self.server_addr = tuple(server_addr)
        self.executor_id = int(executor_id)
        self.interval = (
            HEARTBEAT_INTERVAL if interval is None else float(interval)
        )
        self.alive_fn = alive_fn
        self.generation_fn = generation_fn
        self.host = host
        self.chaos_fn = chaos_fn
        self.metrics_fn = metrics_fn
        self.events_fn = events_fn
        self.stop_seen = False  # server's stop flag, piggybacked on beats
        #: newest cluster generation seen in a reply — supervisors poll
        #: this to learn a peer was reborn (their cue to park/respawn)
        self.cluster_generation = 0
        #: latest NTP-style sample of THIS node vs the server
        #: (``{"offset", "rtt"}``), shipped on the next beat
        self.clock = None
        self._event_backlog = []
        self._stop = threading.Event()
        self._client = None
        self._thread = None

    def start(self):
        self._thread = threading.Thread(
            target=self._run,
            daemon=True,
            name="heartbeat-%d" % self.executor_id,
        )
        self._thread.start()
        return self

    def beat_once(self):
        """Send a single beat synchronously (used to prime the registry
        at startup so death-by-silence is measured from 'now')."""
        self._send_beat()

    def _send_beat(self):
        alive = True if self.alive_fn is None else bool(self.alive_fn())
        gen = 0 if self.generation_fn is None else int(self.generation_fn())
        metrics = None
        if self.metrics_fn is not None:
            try:
                metrics = self.metrics_fn()
            except Exception:  # noqa: BLE001 - see metrics_fn docstring
                metrics = None
        events = list(self._event_backlog)
        if self.events_fn is not None:
            try:
                events.extend(self.events_fn() or ())
            except Exception:  # noqa: BLE001 - journal is best effort
                pass
        events = events[-self.MAX_EVENT_BACKLOG:]
        t0 = time.time()
        try:
            if self._client is None:
                self._client = Client(
                    self.server_addr,
                    retry_deadline=max(1.0, self.interval),
                )
            resp = self._client.heartbeat(
                self.executor_id, generation=gen, compute_alive=alive,
                host=self.host, metrics=metrics, events=events or None,
                clock=self.clock,
            )
        except Exception:
            # the beat is dropped by contract, but the journal events
            # it carried must not be: retain for the next beat (the
            # server dedups by (pid, seq) if some actually landed)
            self._event_backlog = events
            raise
        t1 = time.time()
        self._event_backlog = []
        if resp.get("server_time") is not None:
            offset, rtt = estimate_offset(t0, resp["server_time"], t1)
            self.clock = {
                "offset": round(offset, 6), "rtt": round(rtt, 6),
            }
        if resp.get("stop"):
            self.stop_seen = True
        self.cluster_generation = max(
            self.cluster_generation, int(resp.get("generation", 0))
        )

    def _run(self):
        while not self._stop.wait(self.interval):
            if self.chaos_fn is not None and self.chaos_fn():
                logger.debug(
                    "chaos: dropping heartbeat of executor %d",
                    self.executor_id,
                )
                continue
            try:
                self._send_beat()
            except Exception as e:  # noqa: BLE001 - see class docstring
                logger.warning(
                    "heartbeat of executor %d to %s failed: %s "
                    "(will retry next interval)",
                    self.executor_id, self.server_addr, e,
                )
                try:
                    if self._client is not None:
                        self._client.close()
                # tfoslint: disable=TFOS005(closing a socket the failed beat already killed; the retry path reopens it)
                except Exception:  # noqa: BLE001 - socket already gone
                    pass
                self._client = None

    def stop(self, farewell=True):
        """Stop beating; with ``farewell`` (default) tell the server to
        drop this executor from tracking — an orderly exit must not be
        misread as death-by-silence."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2 * self.interval)
        if farewell:
            try:
                if self._client is None:
                    self._client = Client(
                        self.server_addr,
                        retry_deadline=max(1.0, self.interval),
                    )
                self._client.farewell(self.executor_id)
            except Exception:  # noqa: BLE001 - server may already be down
                pass
        if self._client is not None:
            try:
                self._client.close()
            except Exception:  # noqa: BLE001 - socket already gone
                pass
            self._client = None
