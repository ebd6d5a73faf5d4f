"""Per-executor node runtime: role assignment, process launch, data plane.

Re-designed from the reference's ``TFSparkNode.py`` (reference:
tensorflowonspark/TFSparkNode.py).  Each executor runs ``_mapfn`` exactly
once at cluster startup (reference: TFSparkNode.py:126-431); it

1. claims its executor id (from the start-partition payload),
2. allocates local accelerator devices (TPU chips here; the reference
   probed nvidia-smi and set CUDA_VISIBLE_DEVICES,
   TFSparkNode.py:149-207),
3. derives its role (job_name, task_index) from the cluster template
   (reference: TFSparkNode.py:209-219),
4. starts the per-node :mod:`manager` with role-appropriate queues
   (reference: TFSparkNode.py:235-246),
5. registers with the rendezvous server and blocks on the startup
   barrier (reference: TFSparkNode.py:300-338),
6. assembles the cluster spec and the JAX coordination plan — the
   TPU-native replacement for the reference's TF_CONFIG export
   (reference: TFSparkNode.py:340-362), and
7. launches the user's ``main_fun(args, ctx)`` in foreground or
   background (reference: TFSparkNode.py:375-431).

The data-plane map functions (``train``/``inference``) reconnect to the
node's manager from whatever executor the feed task landed on (reference:
TFSparkNode.py:97-123) and preserve the reference's error-containment
contract: feeders poll the error queue each second and re-raise into the
engine task so retries still fail (reference: TFSparkNode.py:612-618).
Teardown is driver-direct — ``cluster.shutdown`` connects to each node
manager over TCP to kill tensorboard, post end-of-feed sentinels, and
check the error queue (no shutdown job on the executors).
"""

import atexit
import collections
import json
import logging
import multiprocessing
import os
import queue as _queue_mod
import socket
import time
import uuid

from tensorflowonspark_tpu.cluster import manager, reservation, tpu_info
from tensorflowonspark_tpu.cluster.marker import (
    Block,
    ColumnarBlock,
    EndPartition,
    PartitionStart,
    encode_columnar_parts,
    encode_rows_parts,
    pack_columnar,
)
from tensorflowonspark_tpu.utils import paths as path_utils
from tensorflowonspark_tpu.utils.net import get_ip_address

logger = logging.getLogger(__name__)

#: Rows per feed Block — one manager RPC ships this many rows
#: (SURVEY.md §7 'feed-path throughput'; override via env for tuning).
FEED_BLOCK_SIZE = int(os.environ.get("TFOS_FEED_BLOCK_SIZE", "256"))


class NodeContext(object):
    """Encapsulates cluster metadata for the user's ``main_fun``
    (reference: TFSparkNode.py:37-77 TFNodeContext).

    Attributes mirror the reference: ``executor_id``, ``job_name``,
    ``task_index``, ``cluster_spec``, ``num_workers``, ``default_fs``,
    ``working_dir``, ``mgr``.  TPU additions: ``coordinator`` (address
    for ``jax.distributed.initialize``), ``process_id`` / ``num_processes``
    (this node's rank among JAX worker processes), ``device_info``.
    """

    def __init__(
        self,
        executor_id=0,
        job_name="",
        task_index=0,
        cluster_spec=None,
        default_fs="file://",
        working_dir=".",
        mgr=None,
        coordinator=None,
        process_id=0,
        num_processes=1,
        device_info=None,
        manager_addr=None,
        manager_authkey=None,
        generation=0,
        plan=None,
    ):
        self.executor_id = executor_id
        self.job_name = job_name
        self.task_index = task_index
        self.cluster_spec = cluster_spec or {}
        self.default_fs = default_fs
        self.working_dir = working_dir
        self.mgr = mgr
        self.coordinator = coordinator
        self.process_id = process_id
        self.num_processes = num_processes
        self.device_info = device_info or {}
        #: (addr, authkey-hex) so a spawned compute process can rebind its
        #: manager proxy — BaseManager proxies don't survive pickling into
        #: a spawn-context child (the fork-context inheritance the
        #: reference relied on is a TPU hazard: a forked JAX runtime is
        #: undefined behavior, so we spawn and reconnect instead).
        self.manager_addr = manager_addr
        self.manager_authkey = manager_authkey
        #: the driver-side planner's decision record when the cluster
        #: was started with ``run(plan="auto")`` (docs/autotune.md) —
        #: ``plan["chosen"]`` carries the DCN cadence (push_every /
        #: max_inflight) the user fn hands to HierTrainer instead of
        #: hand-set knobs; None otherwise.
        self.plan = plan
        #: elastic re-rendezvous generation: 0 on the first launch, N
        #: after the Nth supervised restart — user code can log it or
        #: branch on "am I a restart" (checkpoint auto-resume needs
        #: neither: ``train_on_feed(checkpointer=...)`` restores
        #: whenever a checkpoint exists).
        self.generation = generation
        self.num_workers = sum(
            len(v)
            for k, v in self.cluster_spec.items()
            if k in ("worker", "chief", "master")
        )

    def absolute_path(self, path):
        """Convert a relative path into an absolute path on the default FS
        (reference: TFSparkNode.py:54-56, TFNode.py:29-64)."""
        return path_utils.resolve_path(path, self.default_fs, self.working_dir)

    def get_data_feed(
        self, train_mode=True, qname_in="input", qname_out="output", input_mapping=None
    ):
        """Return a :class:`~tensorflowonspark_tpu.data.feed.DataFeed` bound
        to this node's queues (reference: TFSparkNode.py:58-60)."""
        from tensorflowonspark_tpu.data.feed import DataFeed

        return DataFeed(self.mgr, train_mode, qname_in, qname_out, input_mapping)

    def initialize_distributed(self):
        """Initialize JAX multi-host coordination for this node.

        The TPU-native replacement for the reference's
        ``start_cluster_server`` / TF_CONFIG export (reference:
        TFNode.py:67-151, TFSparkNode.py:354-362): instead of booting a
        gRPC ``tf.train.Server``, a multi-host JAX node calls
        ``jax.distributed.initialize(coordinator, num_processes,
        process_id)`` and lets XLA run collectives over ICI/DCN.

        No-op for single-process clusters (workers colocated on one host
        already share a chip set) — returns ``jax`` either way.
        """
        import jax

        if self.num_processes > 1 and self.coordinator:
            jax.distributed.initialize(
                coordinator_address=self.coordinator,
                num_processes=self.num_processes,
                process_id=self.process_id,
            )
        return jax

    def mesh(self, axes=None):
        """Build a :class:`jax.sharding.Mesh` over this cluster's devices
        (SURVEY.md §7 step 5; see :mod:`tensorflowonspark_tpu.parallel.mesh`)."""
        from tensorflowonspark_tpu.parallel.mesh import build_mesh

        return build_mesh(axes)


def _cluster_template(num_executors, num_ps, master_node=None, eval_node=False):
    """Map job names to executor-id lists (reference: TFCluster.py:255-270).

    Layout (by executor id): ps nodes first, then optional master/chief,
    then optional evaluator, then workers.
    """
    template = {}
    idx = 0
    if num_ps > 0:
        template["ps"] = list(range(idx, idx + num_ps))
        idx += num_ps
    if master_node:
        template[master_node] = [idx]
        idx += 1
    if eval_node:
        template["evaluator"] = [idx]
        idx += 1
    if idx < num_executors:
        template["worker"] = list(range(idx, num_executors))
    return template


def _role_for(template, executor_id):
    for job_name, ids in template.items():
        if executor_id in ids:
            return job_name, ids.index(executor_id)
    raise ValueError(
        "executor_id {0} not present in cluster template {1}".format(
            executor_id, template
        )
    )


#: Module-level keepalive for this executor's queue manager.  BaseManager
#: installs a finalizer that shuts the server down when the last local
#: reference is collected — if the start task's ``mgr`` went out of scope
#: when ``_mapfn`` returned, the data plane would vanish with it.  The
#: reference kept the same process-lifetime singleton
#: (reference: TFSparkNode.py:90-95).
#:
#: NOTE: must be mutated via :func:`_register_local_manager`, never via a
#: ``global`` statement inside ``_mapfn`` — the start task's map function
#: travels to executors as a cloudpickled closure whose ``__globals__`` is
#: a reconstructed dict that dies with the function object, not this
#: module's real namespace.
_LOCAL_MANAGERS = []


def _register_local_manager(mgr):
    _LOCAL_MANAGERS.append(mgr)


#: Keepalive for shm feed rings created by this executor, as
#: ``(cluster_id, ring)`` pairs (segment dies with its creating process;
#: see TFOS_SHM_FEED in run()).  Rings from *prior* cluster runs are
#: unlinked when a new run starts — a long-lived executor would
#: otherwise accumulate one shm segment per cluster run.
_LOCAL_RINGS = []


def _evict_stale_rings(current_cluster_id):
    kept = []
    for cluster_id, ring in _LOCAL_RINGS:
        if cluster_id == current_cluster_id:
            kept.append((cluster_id, ring))
            continue
        try:
            ring.close(unlink=True)
            logger.info("unlinked stale shm ring from run %s", cluster_id)
        except Exception:  # noqa: BLE001 - cleanup is best effort
            logger.warning("failed to unlink stale shm ring", exc_info=True)
    _LOCAL_RINGS[:] = kept


@atexit.register
def _unlink_local_rings():
    """The FINAL run's ring has no successor run to evict it: unlink at
    executor exit or the resource tracker reports a leaked segment."""
    _evict_stale_rings(current_cluster_id=object())  # matches nothing


_MANAGER_FILE = "tfos_manager.json"


def _write_manager_info(workdir, info):
    with open(os.path.join(workdir, _MANAGER_FILE), "w") as f:
        json.dump(info, f)


def _read_manager_info(workdir):
    p = os.path.join(workdir, _MANAGER_FILE)
    if not os.path.exists(p):
        return None
    with open(p) as f:
        return json.load(f)


#: Cached manager connections, keyed by (addr, authkey).  Executor
#: processes persist across feed tasks, and a fresh connect + queue
#: proxy setup costs ~100ms — at reference scale that tax is per
#: partition (the reference reconnected every task,
#: TFSparkNode.py:97-123; caching is a deliberate improvement).
#: LRU-bounded so a long-lived executor serving many sequential cluster
#: runs (each with a fresh addr/authkey) cannot grow it monotonically.
_MANAGER_CONNS = collections.OrderedDict()
_MANAGER_CONNS_MAX = 8


def _get_manager(cluster_info, executor_id):
    """Connect (cached) to the manager of the node hosting
    ``executor_id`` (reference: TFSparkNode.py:97-123; lookup is by
    executor id — the advertised manager address already encodes the
    host)."""
    for node in cluster_info:
        if node["executor_id"] == executor_id:
            addr = tuple(node["addr"])
            key = (addr, node["authkey"])
            m = _MANAGER_CONNS.get(key)
            if m is not None:
                # Bounded liveness probe: BaseManager clients open a
                # FRESH connection per registered-method call (there is
                # no persistent socket on the cached object to wedge or
                # to close on eviction — dropping the reference is the
                # whole cleanup), so a short-timeout TCP connect to the
                # server is the right check and cannot block the feed
                # task for a kernel TCP timeout the way an unbounded
                # probe RPC could.
                try:
                    socket.create_connection(addr, timeout=2.0).close()
                    _MANAGER_CONNS.move_to_end(key)
                    return m
                except OSError:  # stale/unreachable: reconnect below
                    _MANAGER_CONNS.pop(key, None)
            authkey = bytes.fromhex(node["authkey"])
            m = manager.connect(addr, authkey)
            _MANAGER_CONNS[key] = m
            while len(_MANAGER_CONNS) > _MANAGER_CONNS_MAX:
                _MANAGER_CONNS.popitem(last=False)
            logger.debug(
                "connected to manager of executor %d at %s", executor_id, addr
            )
            return m
    raise RuntimeError(
        "no node with executor_id {0} in cluster_info".format(executor_id)
    )


def _manager_first_call(cluster_info, executor_id, call):
    """First manager RPC with one evict+reconnect retry.

    The cached-connection probe in :func:`_get_manager` is a bare TCP
    connect, which a wedged manager process — or an unrelated server
    that reused the port after a restart — passes; the first
    registered-method call is the authoritative liveness/authkey check.
    On its failure the cached entry is evicted and the connection
    rebuilt once, so a stale cache costs one retry instead of failing
    the feed task mid-partition."""
    from multiprocessing import AuthenticationError

    mgr = _get_manager(cluster_info, executor_id)
    try:
        return mgr, call(mgr)
    except (OSError, EOFError, AuthenticationError) as e:
        logger.warning(
            "cached manager connection failed first RPC (%s); "
            "reconnecting", e,
        )
        for node in cluster_info:
            if node["executor_id"] == executor_id:
                _MANAGER_CONNS.pop(
                    (tuple(node["addr"]), node["authkey"]), None
                )
        mgr = _get_manager(cluster_info, executor_id)
        return mgr, call(mgr)


def _route_around_hold(cluster_info, executor_id, mgr, state, probe):
    """Pick a live, un-held COMPUTE peer's manager for this feed task.

    The data-plane half of a remediation hold (ISSUE 16): a held node
    keeps its heartbeats and registrations but drains nothing, so its
    share of the feed must flow to the survivors of the elastic
    shrink.  Falls back to the local manager when every peer is
    held/terminating/unreachable — the normal feed_timeout + elastic
    requeue path then applies."""
    for node in sorted(cluster_info, key=lambda n: n["executor_id"]):
        peer = node["executor_id"]
        if peer == executor_id or node.get("job_name") in ("ps", "eval"):
            continue
        try:
            m2, (st2, cs2) = _manager_first_call(
                cluster_info, peer, probe
            )
        except Exception:  # noqa: BLE001 - peer mid-restart: next one
            continue
        if cs2 != "held" and st2 != "terminating":
            logger.info(
                "executor %d is held by remediation; forwarding this "
                "partition to executor %d", executor_id, peer,
            )
            return m2, st2
    logger.warning(
        "executor %d is held and no live peer accepts its feed; "
        "feeding locally (the elastic requeue will recover it)",
        executor_id,
    )
    return mgr, state


def _local_executor_workdir():
    from tensorflowonspark_tpu.engine import TFOS_EXECUTOR_WORKDIR

    return os.environ.get(TFOS_EXECUTOR_WORKDIR, os.getcwd())


def _local_executor_id():
    """The executor id claimed by this executor's start task, persisted in
    its working dir (reference: util.py:77-85 read_executor_id)."""
    from tensorflowonspark_tpu.utils.env import read_executor_id

    return read_executor_id(_local_executor_workdir())


def _compute_process_main(fn_bytes, args, ctx):
    """Entry point of the background compute process: rebind the manager
    proxy, run the user fn, ship any traceback home via the node's error
    queue (reference: TFSparkNode.py:391-397 wrapper_fn_background)."""
    import traceback

    try:
        import cloudpickle as _cp
    except ImportError:  # pragma: no cover
        import pickle as _cp

    from tensorflowonspark_tpu.utils.compile_cache import (
        ensure_compile_cache,
    )
    from tensorflowonspark_tpu.utils.retry import retry_call

    # this process owns the node's chips: give its compiles the
    # program-wide persistent cache before the user fn's first jit (a
    # cluster start — or a supervisor respawn — otherwise compiles cold)
    ensure_compile_cache()
    authkey = bytes.fromhex(ctx.manager_authkey)
    multiprocessing.current_process().authkey = authkey
    # a freshly spawned (or supervisor-respawned) compute process can
    # race its executor's manager: backoff briefly instead of dying on
    # one refused connect
    ctx.mgr = retry_call(
        lambda: manager.connect(tuple(ctx.manager_addr), authkey),
        "connect to node manager at {0}".format(tuple(ctx.manager_addr)),
        exceptions=(OSError, EOFError),
        deadline=30.0,
        base=0.1,
    )
    # fleet telemetry: ship this process's registry snapshot into the
    # manager kv so the supervisor's heartbeats carry it to the driver
    # (telemetry/aggregate.py; returns None when TFOS_TELEMETRY=0)
    from tensorflowonspark_tpu import telemetry as _telemetry

    _publisher = _telemetry.start_node_publisher(ctx.mgr)
    # incident forensics (ISSUE 11): stamp this process's journal with
    # its executor id and arm the flight recorder — fault events
    # (watchdog fires, swap rollbacks, ...) freeze the recent rings
    # into a dump bundle, indexed into the node kv so the driver's
    # collect_dumps() finds them (telemetry/blackbox.py; install()
    # returns None when disabled)
    _telemetry.get_journal().set_identity(ctx.executor_id)
    from tensorflowonspark_tpu.telemetry import blackbox as _blackbox

    _recorder = _blackbox.install()
    if _recorder is not None:
        _recorder.attach_kv(ctx.mgr)
    # on-demand device profiling: TFOS_PROFILE_DIR / TFOS_PROFILE_STEPS
    # start a jax.profiler trace for this compute process (graceful
    # no-op when the build lacks the profiler — see tensorboard.py)
    from tensorflowonspark_tpu import tensorboard as _tb

    _profile = _tb.maybe_start_profile_from_env()
    try:
        fn = _cp.loads(fn_bytes)
        fn(args, ctx)
    except Exception:  # noqa: BLE001 - process boundary, traceback shipped home
        tb = traceback.format_exc()
        logger.error("compute process failed:\n%s", tb)
        try:
            ctx.mgr.get_queue("error").put(tb)
            ctx.mgr.set("compute_state", "failed")
        except Exception:  # noqa: BLE001 - best effort error reporting
            logger.exception("unable to report error to manager")
        raise
    finally:
        if _profile is not None:
            _profile.stop()
        if _publisher is not None:
            _publisher.stop()
    # Completion signal: shutdown() polls this instead of the reference's
    # blind grace_secs sleep (TFCluster.py:125), so the chief's post-feed
    # export always finishes before teardown.  Outside the user-fn try: a
    # failure to *signal* must not be reported as a compute failure.
    try:
        ctx.mgr.set("compute_state", "finished")
    except Exception:  # noqa: BLE001 - shutdown falls back to its window
        logger.exception("unable to report completion to manager")


def run(fn, args, cluster_meta, input_mode, log_dir=None, tensorboard=False):
    """Build the start-job map function executed once per executor
    (reference: TFSparkNode.py:126-431).

    Args:
      fn: user ``main_fun(args, ctx)``.
      args: opaque user args (argparse Namespace or list).
      cluster_meta: dict from the driver — ``id``, ``cluster_template``,
        ``num_executors``, ``default_fs``, ``server_addr``,
        ``reservation_timeout``, ``queues``.
      input_mode: ``InputMode.SPARK`` feeds data through the engine;
        ``InputMode.TENSORFLOW`` (kept name for API parity) means the
        user fn reads its own data and runs in the foreground.
      log_dir: directory for event logs / tensorboard.
      tensorboard: launch a managed tensorboard subprocess on chief/worker:0
        (reference: TFSparkNode.py:260-297).
    """

    def _mapfn(iterator):
        from tensorflowonspark_tpu.cluster.cluster import InputMode
        from tensorflowonspark_tpu.utils.env import write_executor_id

        # 1. claim executor id from the start partition payload
        executor_id = None
        for item in iterator:
            executor_id = item
        assert executor_id is not None, "empty start partition"
        workdir = _local_executor_workdir()
        write_executor_id(executor_id, workdir)

        template = cluster_meta["cluster_template"]
        job_name, task_index = _role_for(template, executor_id)
        logger.info(
            "executor_id=%d assigned role %s:%d", executor_id, job_name, task_index
        )

        # 2. duplicate / retry detection (reference: TFSparkNode.py:227-233):
        # if this executor already hosts a *running* manager for this
        # cluster, the engine re-ran the start task — fail fast so the
        # retry lands elsewhere instead of double-starting a TPU owner.
        existing = _read_manager_info(workdir)
        if existing is not None and existing.get("cluster_id") == cluster_meta["id"]:
            try:
                m = manager.connect(
                    tuple(existing["addr"]), bytes.fromhex(existing["authkey"])
                )
                state = str(m.get("state")._getvalue())
            except (ConnectionError, OSError):
                # The previous incarnation died with its manager: this is a
                # legitimate retry — start fresh.
                state = "dead"
            if state == "running":
                # Still a poison-fail — but under elastic this is now
                # the rare true-duplicate case only: a retry after the
                # node died finds a dead manager and starts fresh
                # (above), and an in-place compute death never fails
                # the start task at all — the Supervisor respawns the
                # compute process locally (cluster/supervisor.py),
                # which is what replaced the reference's
                # always-poison-the-retry recovery story.
                raise RuntimeError(
                    "TFOS node already running on executor {0}; "
                    "duplicate start task".format(executor_id)
                )

        # 3. start the per-node queue manager (reference: TFSparkNode.py:235-246)
        authkey = uuid.uuid4().bytes
        is_service_node = job_name in ("ps", "evaluator")
        if is_service_node:
            queues = ["control", "error"]
        else:
            queues = list(cluster_meta.get("queues", ["input", "output", "error"]))
            if "error" not in queues:
                queues.append("error")
        # All managers bind 'remote' (all interfaces + HMAC authkey) so the
        # driver can reach every node directly for shutdown/error-check —
        # the reference could only reach ps/evaluator managers and had to
        # run a racy per-executor job to shut workers down
        # (reference: TFManager.py:60-63, TFCluster.py:174-194).
        mgr, addr = manager.start(authkey, queues, mode="remote")
        _register_local_manager(mgr)  # keepalive for the executor lifetime
        mgr.set("state", "running")
        # Optional shared-memory feed ring (TFOS_SHM_FEED=1): feeders
        # push row-Blocks through shm instead of manager RPCs — the
        # SURVEY.md §7 'C++ ring buffer' staging path.  Created here so
        # it lives as long as the executor process; feeders and the
        # compute process attach by name via the manager kv.
        # "force" additionally pins every block to the ring, bypassing
        # the feeder's small-row queue policy (see train()._use_ring).
        if (
            not is_service_node
            and input_mode == InputMode.SPARK  # only the feed path uses it
            and os.environ.get("TFOS_SHM_FEED") in ("1", "force")
        ):
            from tensorflowonspark_tpu.data import shm_ring

            if shm_ring.available():
                ring_name = "tfos_{0}_{1}".format(
                    cluster_meta["id"][-8:], executor_id
                )
                ring_cap = int(
                    os.environ.get(
                        "TFOS_SHM_FEED_BYTES", shm_ring.DEFAULT_CAPACITY
                    )
                )
                # All ring-registry access goes through the MODULE, not
                # bare globals: this closure ships to the executor by
                # value (cloudpickle), so its captured globals are
                # per-function COPIES; appending to the copy would pin
                # the ring only until this function object is GC'd, and
                # the segment would vanish mid-run (observed as the r2
                # BufferError-at-GC + leaked-segment pair).  Module-level
                # functions like _evict_stale_rings DO pickle by
                # reference and see the real registry, but routing them
                # the same way keeps the invariant visible.
                from tensorflowonspark_tpu.cluster import node as _node

                _node._evict_stale_rings(cluster_meta["id"])
                ring = shm_ring.ShmRing(ring_name, ring_cap, create=True)
                # dtype-tagged segments: record the wire format the
                # feeders will write so consumers can verify at attach
                # (shm_ring.FORMAT_COLUMNAR_V1 — columnar records with
                # self-describing per-column dtypes, pickle fallback)
                ring.set_format(shm_ring.FORMAT_COLUMNAR_V1)
                _node._LOCAL_RINGS.append((cluster_meta["id"], ring))
                mgr.set(
                    "shm_ring", {"name": ring_name, "capacity": ring_cap}
                )
                logger.info(
                    "shm feed ring %s (%d MB) enabled",
                    ring_name,
                    ring_cap // (1 << 20),
                )
            else:
                logger.warning(
                    "TFOS_SHM_FEED=1 but native ring unavailable; "
                    "falling back to queue feeding"
                )
        host = get_ip_address()
        adv_addr = (host, addr[1])
        _write_manager_info(
            workdir,
            {
                "cluster_id": cluster_meta["id"],
                "addr": list(adv_addr),
                "authkey": authkey.hex(),
            },
        )

        # 5. reserve a port for this node's coordination plane (the
        # moral equivalent of the reference's TF gRPC port,
        # TFSparkNode.py:330-335): bound now so it can't be stolen
        # between registration and jax.distributed.initialize.
        # ... and a second one for libtpu's own slice-builder, used when
        # co-hosted compute processes split a host's chips into one
        # slice (tpu_info.set_visible_chips)
        held_socks = []
        for _ in range(2):
            sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            sock.bind(("", 0))
            held_socks.append(sock)
        coord_port, tpu_port = (
            sock.getsockname()[1] for sock in held_socks
        )

        # tensorboard on exactly one node: the chief when one exists, else
        # worker:0 (reference: TFSparkNode.py:260-297; the reference's
        # condition could double-launch when both chief and worker:0 exist)
        tb_pid, tb_port = 0, 0
        has_chief = any(j in template for j in ("chief", "master"))
        is_tb_node = (
            job_name in ("chief", "master")
            if has_chief
            else (job_name == "worker" and task_index == 0)
        )
        if tensorboard and is_tb_node:
            from tensorflowonspark_tpu.tensorboard import start_tensorboard

            tb_proc, tb_port = start_tensorboard(log_dir)
            tb_pid = tb_proc.pid if tb_proc is not None else 0

        # 6. rendezvous registration + startup barrier
        # (reference: TFSparkNode.py:300-338)
        node_meta = {
            "executor_id": executor_id,
            "host": host,
            "job_name": job_name,
            "task_index": task_index,
            "addr": list(adv_addr),
            "authkey": authkey.hex(),
            "port": coord_port,
            "tpu_port": tpu_port,
            "tb_pid": tb_pid,
            "tb_port": tb_port,
            "device_info": _safe_device_info(),
        }
        client = reservation.Client(cluster_meta["server_addr"])
        client.register(node_meta)
        cluster_info = client.await_reservations(
            timeout=cluster_meta.get("reservation_timeout", 600)
        )
        client.close()

        # 7. cluster spec sorted by executor id (reference: TFSparkNode.py:340-352)
        spec, coordinator, process_ranks = build_cluster_spec(cluster_info)
        # driver-hosted ps shards join the spec by address (reference:
        # TFCluster.py:296-314 driver_ps_nodes)
        if cluster_meta.get("driver_ps_addrs"):
            spec = dict(spec, ps=list(cluster_meta["driver_ps_addrs"]))

        # accelerator allocation by HOST-LOCAL rank: co-located nodes must
        # land on disjoint chip windows, so the index comes from this
        # node's position among same-host nodes, not the global task_index
        # (reference: TFSparkNode.py:149-207 + gpu_info.py:74-86).
        # Visibility env vars are set before the compute process spawns.
        # A layout whose processes would fight over the chips is refused
        # here, by name, on every node (the driver raises the same error
        # from the same cluster_info — see cluster.run).
        num_chips = cluster_meta.get("num_chips_per_node")
        tpu_info.check_chip_layout(cluster_info, num_chips)
        if num_chips and not is_service_node:
            cohosted = sorted(
                (n for n in cluster_info
                 if n["host"] == host
                 and n["job_name"] in ("chief", "master", "worker")),
                key=lambda n: n["executor_id"],
            )
            local_rank = [n["executor_id"] for n in cohosted].index(
                executor_id
            )
            tpu_info.set_visible_chips(
                tpu_info.get_chips(num_chips, worker_index=local_rank),
                process_index=local_rank,
                process_ports=(
                    [n["tpu_port"] for n in cohosted]
                    if len(cohosted) > 1 else None
                ),
            )

        # The ports were held only across the registration barrier so
        # no co-located node could grab them; release them now —
        # jax.distributed.initialize / libtpu (or a user server) must be
        # able to bind them from the compute process.
        for sock in held_socks:
            sock.close()

        ctx = NodeContext(
            executor_id=executor_id,
            job_name=job_name,
            task_index=task_index,
            cluster_spec=spec,
            default_fs=cluster_meta.get("default_fs", "file://"),
            working_dir=workdir,
            mgr=None,  # compute process rebinds via manager_addr
            coordinator=coordinator,
            process_id=process_ranks.get(executor_id, 0),
            num_processes=len(process_ranks) or 1,
            device_info=node_meta["device_info"],
            manager_addr=list(adv_addr),
            manager_authkey=authkey.hex(),
            plan=cluster_meta.get("plan"),
        )

        # 8. launch user fn (reference: TFSparkNode.py:375-431)
        background = (input_mode == InputMode.SPARK) or is_service_node
        if background:
            try:
                import cloudpickle as _cp
            except ImportError:  # pragma: no cover
                import pickle as _cp

            if is_service_node:
                # The compute process owns the TPU chips; exactly one
                # per node (SURVEY.md §7 'Spark process model vs TPU
                # ownership').  Service nodes are not supervised: their
                # loss is not recoverable by checkpoint resume.
                proc = multiprocessing.get_context("spawn").Process(
                    target=_compute_process_main,
                    args=(_cp.dumps(fn), args, ctx),
                    daemon=True,
                    name="compute-%s-%d" % (job_name, task_index),
                )
                proc.start()
                mgr.set("compute_pid", proc.pid)
                # ps/evaluator executors block on the control queue until
                # the driver posts None (reference: TFSparkNode.py:409-426),
                # pinning the executor slot so no feed task lands here.
                control = mgr.get_queue("control")
                while True:
                    msg = control.get(block=True)
                    control.task_done()
                    if msg is None:
                        break
                _check_error_queue(mgr)
                proc.terminate()
                mgr.set("state", "stopped")
            else:
                # Compute workers run under a Supervisor: it spawns the
                # compute process, pumps heartbeats to the rendezvous
                # server (dead-node detection in seconds instead of the
                # 600s feed timeout), and — with elastic=True — wraps
                # the process in the rebirth/re-rendezvous restart loop
                # (cluster/supervisor.py).
                from tensorflowonspark_tpu.cluster import (
                    supervisor as _supervisor,
                )
                from tensorflowonspark_tpu.testing import chaos as _chaos

                compute_eids = [
                    n["executor_id"]
                    for n in cluster_info
                    if n["job_name"] in ("chief", "master", "worker")
                ]
                sup = _supervisor.Supervisor(
                    _cp.dumps(fn),
                    args,
                    ctx,
                    mgr,
                    cluster_meta,
                    compute_eids,
                    node_meta,
                    chaos_fn=_chaos.heartbeat_chaos_fn(executor_id),
                )
                sup.start()
                _supervisor.register_local_supervisor(sup)
            # SPARK-mode workers return immediately, freeing the executor
            # for feed tasks; the compute process keeps running.
        else:
            # TENSORFLOW input mode: user fn reads its own data; run in
            # the foreground, pinning this executor for the duration
            # (reference: TFSparkNode.py:427-431).  A heartbeater runs
            # for the duration so the driver monitor sees this node too.
            ctx.mgr = mgr
            from tensorflowonspark_tpu import telemetry as _telemetry

            _events_fn = None
            if _telemetry.enabled():
                # forensics plane (ISSUE 11): same contract as the
                # supervisor path — journal identity, fault-triggered
                # flight recorder with its kv dump index, and journal
                # events shipped on the beats
                _telemetry.get_journal().set_identity(executor_id)
                from tensorflowonspark_tpu.telemetry import (
                    blackbox as _blackbox,
                )

                _fg_recorder = _blackbox.install()
                if _fg_recorder is not None:
                    _fg_recorder.attach_kv(mgr)

                def _events_fn():
                    return [
                        e.to_dict()
                        for e in _telemetry.get_journal()
                        .drain_unshipped(64)
                    ]

            hb = reservation.Heartbeater(
                cluster_meta["server_addr"],
                executor_id,
                interval=cluster_meta.get("heartbeat_interval"),
                host=host,
                # foreground mode: the user fn runs IN this process, so
                # its registry snapshot ships directly on the beats
                metrics_fn=(
                    _telemetry.get_registry().snapshot
                    if _telemetry.enabled() else None
                ),
                events_fn=_events_fn,
            ).start()
            try:
                fn(args, ctx)
            except Exception:
                import traceback

                mgr.get_queue("error").put(traceback.format_exc())
                mgr.set("state", "stopped")
                raise
            finally:
                hb.stop()
            mgr.set("state", "stopped")
        return []

    return _mapfn


def _safe_device_info():
    """Device info without forcing JAX backend init in the executor task
    process (only the compute process may own TPU chips)."""
    try:
        return tpu_info.get_device_info_lazy()
    except Exception:  # noqa: BLE001 - absent accelerators are fine
        return {"platform": "unknown", "num_devices": 0}


def build_cluster_spec(cluster_info):
    """Assemble ``{job: ["host:port", ...]}`` sorted by executor id, plus
    the JAX coordination plan (reference: TFSparkNode.py:340-362 built the
    TF clusterspec + TF_CONFIG; the TPU plan is a coordinator address and
    a dense process rank per compute node).

    Returns ``(spec, coordinator, process_ranks)`` where ``process_ranks``
    maps executor_id → JAX process index over the *compute* nodes
    (chief/master/worker — ps and evaluator are not part of the mesh).
    """
    ordered = sorted(cluster_info, key=lambda n: n["executor_id"])
    spec = {}
    for node in ordered:
        spec.setdefault(node["job_name"], []).append(
            "{0}:{1}".format(node["host"], node["port"])
        )
    compute = [
        n for n in ordered if n["job_name"] in ("chief", "master", "worker")
    ]
    process_ranks = {n["executor_id"]: i for i, n in enumerate(compute)}
    coordinator = (
        "{0}:{1}".format(compute[0]["host"], compute[0]["port"]) if compute else None
    )
    return spec, coordinator, process_ranks


# ----------------------------------------------------------------------
# Data-plane map functions (feed jobs)
# ----------------------------------------------------------------------


def _queue_put_retry(queue, obj):
    """``queue.put`` with one reconnect-retry.

    Manager proxies share one socket per (address, thread); a GC pass
    in the feeder thread can run ``BaseProxy._decref`` for an unrelated
    dead proxy and close that shared connection while this put is
    mid-``send`` (``TypeError: 'NoneType' ...`` from the nulled handle,
    or ``OSError`` on a partially-written frame).  Either way the
    request never completed server-side, and the next proxy call
    transparently opens a fresh connection — so one retry is safe
    (no duplicate put) and a genuinely dead manager still raises."""
    try:
        queue.put(obj, block=True)
    except (OSError, TypeError):
        logger.warning(
            "feed queue put hit a closed manager connection; "
            "retrying once on a fresh connection", exc_info=True,
        )
        queue.put(obj, block=True)


class _PipelinedShipper(object):
    """Feeder-side decode pipeline (the 'pipelined decode' stage of the
    narrow-dtype data plane, docs/data_plane.md): a small worker pool
    runs the CPU-bound encode — columnar pack, wire encode,
    ``pickle.dumps`` — for block N+1 while the caller's iterator
    deserializes block N+2 and the single pusher (the submitting
    thread) writes block N into the shm ring.  Submission order is
    preserved (results drain FIFO), and all pushes stay on one thread,
    so the ring's single-producer contract holds.

    Errors from encode workers re-raise in the submitting thread at the
    next ``ship``/``close``; the feeder's error contract is unchanged.
    """

    def __init__(self, encode, push, workers=2, depth=4):
        import collections
        from concurrent.futures import ThreadPoolExecutor

        self._encode = encode
        self._push = push
        self._pool = ThreadPoolExecutor(
            max_workers=max(1, workers),
            thread_name_prefix="feed-encode",
        )
        self._depth = max(1, depth)
        self._pending = collections.deque()

    def ship(self, rows, use_ring):
        # bound the in-flight window, then opportunistically drain
        # completed heads so pushes interleave with in-flight encodes
        while len(self._pending) >= self._depth:
            self._drain_one()
        self._pending.append(
            self._pool.submit(self._encode, rows, use_ring)
        )
        while self._pending and self._pending[0].done():
            self._drain_one()

    def _drain_one(self):
        fut = self._pending.popleft()
        for action in fut.result():
            self._push(action)

    def close(self):
        """Flush every queued block in order, then stop the pool."""
        try:
            while self._pending:
                self._drain_one()
        finally:
            self._pool.shutdown(wait=True)

    def abort(self):
        """Error-path teardown: drop queued work, stop the pool (its
        threads are non-daemon — leaving them running would pin the
        executor process past the failing task)."""
        self._pending.clear()
        self._pool.shutdown(wait=True, cancel_futures=True)


def train(cluster_info, cluster_meta, feed_timeout=600, qname="input"):
    """Build the feeder map function for training data
    (reference: TFSparkNode.py:436-503)."""

    def _train(iterator):
        import itertools

        # elastic partitions lead with a PartitionStart marker carrying
        # the driver's partition id — strip it and open a ledger record
        # so the driver can requeue this partition if the consumer dies
        # before a checkpoint commits it (at-least-once delivery)
        iterator = iter(iterator)
        first = next(iterator, None)
        pid = None
        if isinstance(first, PartitionStart):
            pid = first.pid
        elif first is not None:
            iterator = itertools.chain([first], iterator)
        def _node_probe(m):
            st = str(m.get("state")._getvalue())
            try:
                cs = m.get("compute_state")._getvalue()
            except Exception:  # noqa: BLE001 - kv is best effort
                cs = None
            return st, cs

        local_eid = _local_executor_id()
        mgr, (state, cstate) = _manager_first_call(
            cluster_info, local_eid, _node_probe,
        )
        logger.info("connected to node manager, state=%s", state)
        if cstate == "held" and state != "terminating":
            # remediation hold (ISSUE 16): this node's compute is
            # deliberately quiesced (elastic shrink), so nothing will
            # ever drain its queue — route the partition to a live
            # peer instead of wedging until feed_timeout
            mgr, state = _route_around_hold(
                cluster_info, local_eid, mgr, state, _node_probe
            )
        if pid is not None and state != "terminating":
            mgr.ledger("begin", pid)
        terminating = state == "terminating"
        queue = mgr.get_queue(qname)
        if terminating:
            # Compute is done: discard remaining partitions quickly and
            # tell the driver to stop scheduling feed jobs
            # (reference: TFSparkNode.py:458-499).
            logger.info("node terminating; skipping partition")
            count = sum(1 for _ in iterator)
            logger.debug("skipped %d items", count)
            try:
                client = reservation.Client(cluster_meta["server_addr"])
                client.request_stop()
                client.close()
            except (ConnectionError, OSError) as e:
                logger.debug("unable to reach reservation server: %s", e)
            return []
        err_q = mgr.get_queue("error")
        ring = _attach_feed_ring(mgr)
        count = 0
        block = []
        # Columnar packing (default on): a block of fixed-shape numeric
        # rows ships as stacked numpy columns — serialization is a few
        # buffer copies instead of N object pickles, and the consumer
        # slices batches out with zero per-row Python
        # (DataFeed.next_arrays).  Ragged/object rows fall back to row
        # Blocks transparently.
        columnar_ok = os.environ.get("TFOS_COLUMNAR_FEED", "1") != "0"

        def _pack(rows):
            if columnar_ok:
                packed = pack_columnar(rows)
                if packed is not None:
                    return packed
            return Block(rows)

        # largest record one ring frame can carry: the frame length
        # field is u32, so a multi-GiB ring still caps records below
        # 4GiB — oversize blocks must take the split path, not a fatal
        # push error
        wire_cap = min(ring.capacity, (1 << 32) - 4) if ring else 0

        def _row_vals(first):
            return (
                first.values() if isinstance(first, dict)
                else first if isinstance(first, (tuple, list))
                else (first,)
            )

        def _row_is_large(first):
            """Cheap first-row probe: the per-row scatter-gather encode
            only pays off when a row carries a >=64KB array (images);
            kilobyte rows ship faster as one stacked-column copy, and
            this probe avoids running the O(rows) encode just to
            discard it."""
            try:
                return any(
                    getattr(v, "nbytes", 0) >= 65536 for v in _row_vals(first)
                )
            except TypeError:
                return False

        def _row_bytes(first):
            total = 0
            try:
                for v in _row_vals(first):
                    n = getattr(v, "nbytes", None)
                    if n is None:
                        n = len(v) if isinstance(v, (bytes, str)) else 8
                    total += n
            except TypeError:
                return 0
            return total

        # Ring-vs-queue policy: at image-scale rows the shm ring's
        # zero-copy path carries the feed, but at kilobyte rows the e2e
        # pipeline is consumer-bound and the ring's extra encode/decode
        # buys nothing — so blocks whose rows are below the threshold ship
        # via the queue even when the ring is up.  TFOS_SHM_FEED=force
        # pins the ring for every block (benchmarks; threshold tuning).
        ring_min_row = int(
            os.environ.get("TFOS_SHM_RING_MIN_ROW_BYTES", "4096")
        )
        ring_forced = os.environ.get("TFOS_SHM_FEED") == "force"
        ring_choice = []  # decided at the first block, sticky per task

        def _use_ring(rows):
            if ring is None:
                return False
            if ring_forced:
                return True
            if not ring_choice:
                use = _row_bytes(rows[0]) >= ring_min_row
                ring_choice.append(use)
                if not use:
                    logger.info(
                        "rows ~%dB < TFOS_SHM_RING_MIN_ROW_BYTES=%d: "
                        "shipping via queue (ring idle for this task)",
                        _row_bytes(rows[0]), ring_min_row,
                    )
            return ring_choice[0]

        def _encode_into(rows, use_ring, actions):
            """Encode one block into ordered ship actions —
            ``('pushv', parts, nbytes)`` / ``('push', payload, nbytes)``
            / ``('queue', obj)`` — splitting blocks that exceed a ring
            frame.  Pure CPU work (pack / wire encode / pickle): safe
            on the shipper's worker pool, no manager or ring calls."""
            if not use_ring:
                actions.append(("queue", _pack(rows)))
                return
            if columnar_ok and _row_is_large(rows[0]):
                # zero-copy fast path: per-row buffers scatter-gather
                # straight into the ring — the contiguous record write
                # IS the column stack (no pack, no pickle)
                enc = encode_rows_parts(rows)
                if enc is not None:
                    header, bufs, total = enc
                    if total + 8 < wire_cap:
                        actions.append(("pushv", [header] + bufs, total))
                        return
                    # known oversize from the exact wire total: split
                    # now instead of materializing a full stacked copy
                    # below just to re-measure it
                    if len(rows) > 1:
                        mid = len(rows) // 2
                        _encode_into(rows[:mid], use_ring, actions)
                        _encode_into(rows[mid:], use_ring, actions)
                        return
                    # single row bigger than a ring frame: the queue
                    # path never had a size cap
                    actions.append(("queue", Block(rows)))
                    return
            packed = _pack(rows)
            if isinstance(packed, ColumnarBlock):
                # stacked-columns path (small or scalar rows): still
                # zero-pickle — one copy instead of three.  None = not
                # wire-encodable (non-string dict keys); such blocks
                # ship pickled below.
                enc2 = encode_columnar_parts(packed)
                if enc2 is not None:
                    header, bufs = enc2
                    total = len(header) + sum(b.nbytes for b in bufs)
                    if total + 8 < wire_cap:
                        actions.append(("pushv", [header] + bufs, total))
                        return
                    if len(rows) > 1:
                        mid = len(rows) // 2
                        _encode_into(rows[:mid], use_ring, actions)
                        _encode_into(rows[mid:], use_ring, actions)
                        return
            import pickle as _p

            payload = _p.dumps(packed, protocol=5)
            # a block that outgrows a ring frame is split, not fatal —
            # the queue path never had a size cap; a single giant row
            # falls back to the queue
            if len(payload) + 8 >= wire_cap:
                if len(rows) == 1:
                    actions.append(("queue", Block(rows)))
                    return
                mid = len(rows) // 2
                _encode_into(rows[:mid], use_ring, actions)
                _encode_into(rows[mid:], use_ring, actions)
                return
            actions.append(("push", payload, len(payload)))

        def _encode(rows, use_ring):
            actions = []
            _encode_into(rows, use_ring, actions)
            return actions

        wire_sent = [0]  # ring wire bytes shipped (narrowing telemetry)

        def _push_action(action):
            """Perform one ship action — ALWAYS on the feeder's main
            thread (the ring is SPSC: one producer)."""
            kind = action[0]
            if kind == "queue":
                _queue_put_retry(queue, action[1])
                return
            if kind == "pushv":
                ring.pushv(
                    action[1],
                    timeout=feed_timeout,
                    error_check=lambda: _check_error_queue(mgr, err_q),
                )
            else:
                ring.push(
                    action[1],
                    timeout=feed_timeout,
                    error_check=lambda: _check_error_queue(mgr, err_q),
                )
            wire_sent[0] += action[2]

        # Pipelined decode (docs/data_plane.md): encode block N+1 on a
        # small worker pool while block N pushes and the engine iterator
        # deserializes N+2.  TFOS_FEED_PIPELINE=0 restores the serial
        # path (debugging / single-core executors).
        shipper = None
        if os.environ.get("TFOS_FEED_PIPELINE", "1") != "0":
            shipper = _PipelinedShipper(
                _encode,
                _push_action,
                workers=int(
                    os.environ.get("TFOS_FEED_PIPELINE_WORKERS", "2")
                ),
                depth=int(os.environ.get("TFOS_FEED_PIPELINE_DEPTH", "4")),
            )

        def _ship(rows):
            use_ring = _use_ring(rows)  # sticky choice: main thread only
            if shipper is not None:
                shipper.ship(rows, use_ring)
            else:
                for action in _encode(rows, use_ring):
                    _push_action(action)

        try:
            for item in iterator:
                count += 1
                block.append(item)
                if len(block) >= FEED_BLOCK_SIZE:
                    _ship(block)
                    block = []
            if block:
                _ship(block)
            if shipper is not None:
                shipper.close()  # flush queued encodes, in order
        except BaseException:
            if shipper is not None:
                shipper.abort()
            raise
        # wait for consumption, surfacing compute errors promptly
        # (reference: TFSparkNode.py:472-483).  Wall-clock deadline —
        # decrementing a counter by the nominal sleep would inflate the
        # effective feed_timeout by the manager-RPC latency of each
        # error poll; the error queue is polled at ~1/s (each poll is a
        # manager RPC, and a 10/s rate per in-flight feed task is real
        # load at reference scale) while the wakeup stays at 0.1s.
        def _check_held():
            # remediation hold: a held executor's compute process is
            # parked in the rendezvous barrier and will never drain
            # rows that were already in flight when the hold landed —
            # fail fast so the elastic requeue re-feeds this partition
            # to a live peer instead of wedging until feed_timeout
            try:
                cs = mgr.get("compute_state")._getvalue()
            except Exception:  # noqa: BLE001 - kv is best effort
                return
            if cs == "held":
                raise RuntimeError(
                    "executor held by remediation while batches were "
                    "in flight; failing fast so the partition requeues"
                )

        deadline = time.monotonic() + feed_timeout
        next_err_poll = 0.0
        if ring is not None:
            while True:
                sz = ring.size()
                if sz < 0:
                    raise RuntimeError(
                        "feed ring segment corrupt during drain wait"
                    )
                if sz == 0:
                    break
                if time.monotonic() >= next_err_poll:
                    _check_error_queue(mgr, err_q)
                    _check_held()
                    next_err_poll = time.monotonic() + 1.0
                time.sleep(0.05)
                if time.monotonic() >= deadline:
                    raise RuntimeError(
                        "timed out waiting for ring consumption "
                        "(feed_timeout exceeded)"
                    )
        joinThr = _JoinWatcher(queue)
        while not joinThr.wait(0.1):
            if time.monotonic() >= next_err_poll:
                _check_error_queue(mgr, err_q)
                _check_held()
                next_err_poll = time.monotonic() + 1.0
            if time.monotonic() >= deadline:
                raise RuntimeError(
                    "timed out waiting for consumption of all batches "
                    "(feed_timeout exceeded)"
                )
        _check_error_queue(mgr, err_q)
        if pid is not None:
            # every row was consumed (join + ring drain both completed):
            # the partition is DELIVERED — it becomes durable (committed)
            # only when the compute process checkpoints past it
            mgr.ledger("deliver", pid)
        logger.info(
            "fed %d items (%.2f MB ring wire)", count, wire_sent[0] / 1e6
        )
        return []

    return _train


#: feeder-side ring attachments, one per (process, ring name)
_ATTACHED_RINGS = {}


def _attach_feed_ring(mgr):
    """Attach to this node's shm feed ring if one was advertised."""
    try:
        info = mgr.get("shm_ring")._getvalue()
    except Exception:  # noqa: BLE001 - kv read is best effort
        info = None
    if not info:
        return None
    name = info["name"]
    if name not in _ATTACHED_RINGS:
        from tensorflowonspark_tpu.data import shm_ring

        # evict attachments from finished cluster runs: an unlinked
        # segment stays resident while mapped, so long-lived executor
        # processes would otherwise pin one dead ring per run
        for stale in list(_ATTACHED_RINGS):
            _ATTACHED_RINGS.pop(stale).close(unlink=False)
        _ATTACHED_RINGS[name] = shm_ring.ShmRing(name)
    # announce this process as the ring's producer so a consumer
    # waiting on the ring detects a feeder death instead of hanging
    # (shm_ring.ProducerDiedError; the pid lands in the ring header)
    _ATTACHED_RINGS[name].announce_producer()
    return _ATTACHED_RINGS[name]


def inference(cluster_info, cluster_meta, feed_timeout=600, qname="input"):
    """Build the inference map function: feed a partition, then drain
    exactly as many results (reference: TFSparkNode.py:506-565)."""

    def _inference(iterator):
        mgr, queue_in = _manager_first_call(
            cluster_info,
            _local_executor_id(),
            lambda m: m.get_queue(qname),
        )
        count = 0
        block = []
        for item in iterator:
            count += 1
            block.append(item)
            if len(block) >= FEED_BLOCK_SIZE:
                _queue_put_retry(queue_in, Block(block))
                block = []
        if block:
            _queue_put_retry(queue_in, Block(block))
        _queue_put_retry(queue_in, EndPartition())
        if count == 0:
            return []
        err_q = mgr.get_queue("error")
        joinThr = _JoinWatcher(queue_in)
        timeout = feed_timeout
        while not joinThr.wait(0.1):
            _check_error_queue(mgr, err_q)
            timeout -= 0.1
            if timeout <= 0:
                raise RuntimeError("timed out waiting for inference consumption")
        _check_error_queue(mgr, err_q)
        queue_out = mgr.get_queue("output")
        results = []
        while count > 0:
            item = queue_out.get(block=True)
            queue_out.task_done()
            if isinstance(item, Block):
                results.extend(item.items)
                count -= len(item.items)
            else:
                results.append(item)
                count -= 1
        logger.info("returning %d inference results", len(results))
        return results

    return _inference


# NOTE: the reference had a per-executor shutdown map function
# (TFSparkNode.py:570-622); this build's shutdown is driver-direct —
# every node manager is reachable over TCP, so TPUCluster.shutdown posts
# the sentinels and peeks the error queues itself (cluster.py).


def _check_error_queue(mgr, err_queue=None):
    """Raise if the node's compute process posted an error; the error is
    re-queued first so later tasks (and shutdown) see it too
    (reference: TFSparkNode.py:476-479,612-618).

    Pass a cached ``err_queue`` proxy from polling loops — creating a
    proxy is a full manager round trip.
    """
    q = err_queue if err_queue is not None else mgr.get_queue("error")
    try:
        error = q.get(block=False)
        q.task_done()
        q.put(error)
        raise RuntimeError("compute process failed:\n{0}".format(error))
    except _queue_mod.Empty:
        pass


class _JoinWatcher(object):
    """Runs ``queue.join()`` on a daemon thread so the caller can poll
    with a timeout + error checks (reference: TFSparkNode.py:472-475)."""

    def __init__(self, queue):
        import threading

        self._t = threading.Thread(target=queue.join, daemon=True)
        self._t.start()

    def is_alive(self):
        return self._t.is_alive()

    def wait(self, timeout):
        """Block up to ``timeout`` for the join to finish; True when the
        queue fully drained.  Event-based — a fast consumer releases the
        feeder in milliseconds, where a fixed 1s poll made EVERY feed
        task pay a full second (8 small partitions = 8s of pure wait)."""
        self._t.join(timeout)
        return not self._t.is_alive()
