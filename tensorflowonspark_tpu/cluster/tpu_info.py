"""TPU device discovery and per-process chip allocation.

TPU-native replacement for the reference's ``gpu_info.py`` (reference:
tensorflowonspark/gpu_info.py), which shelled out to ``nvidia-smi`` to find
free GPUs and exported ``CUDA_VISIBLE_DEVICES``.  On TPU the equivalents
are:

- discovery: ``jax.devices()`` / ``jax.local_devices()`` with platform
  probing (no subprocess needed);
- topology: each TPU device exposes ``coords`` (its position in the ICI
  torus) and ``core_on_chip``;
- per-process visibility: the ``TPU_VISIBLE_CHIPS`` /
  ``TPU_PROCESS_BOUNDS`` / ``TPU_CHIPS_PER_PROCESS_BOUNDS`` env vars
  (plus ``TPU_PROCESS_ADDRESSES`` / ``TPU_PROCESS_PORT`` /
  ``CLOUD_TPU_TASK_ID`` when co-hosted processes form one slice),
  which must be set *before* JAX initializes — the moral twin of
  ``CUDA_VISIBLE_DEVICES`` (reference: gpu_info.py:87-94).

Like the reference's deterministic by-worker-index placement
(reference: gpu_info.py:74-86), ``get_chips`` assigns chips by local
worker index so co-located workers don't collide.
"""

import logging
import os

logger = logging.getLogger(__name__)

MAX_RETRIES = 3  # (reference: gpu_info.py:18 MAX_RETRIES)


def is_tpu_available():
    """True if this host has TPU devices JAX can see
    (reference analogue: gpu_info.py:22-28 is_gpu_available)."""
    try:
        import jax

        return any(d.platform == "tpu" for d in jax.devices())
    except Exception:  # noqa: BLE001 - any backend init failure means "no"
        return False


def get_device_info():
    """Describe local accelerator topology for the reservation payload.

    Returns a JSON-able dict: platform, device count, per-device coords.
    This is what executors register with the rendezvous server so the
    driver can build the global mesh (SURVEY.md §7 step 1).
    """
    import jax

    devices = jax.local_devices()
    info = {
        "platform": devices[0].platform if devices else "none",
        "num_devices": len(devices),
        "devices": [],
    }
    for d in devices:
        entry = {"id": d.id, "process_index": d.process_index}
        coords = getattr(d, "coords", None)
        if coords is not None:
            entry["coords"] = list(coords)
        core = getattr(d, "core_on_chip", None)
        if core is not None:
            entry["core_on_chip"] = core
        info["devices"].append(entry)
    return info


class ChipLayoutError(RuntimeError):
    """A chips-per-process layout that cannot run on this host: raised
    before any compute process starts, instead of letting co-hosted
    processes fight over libtpu's lockfile (the loser dies with
    ``Internal error when accessing libtpu multi-process lockfile`` and
    the winner waits on its peers until a timeout)."""


#: chips of the host the slice tables below cover (a 2x2 v5e/v6e host)
_HOST_CHIPS = 4

#: libtpu bounds strings for splitting ONE 2x2 host (the v5e/v6e
#: four-chip host) between co-hosted processes, keyed by chips per
#: process: ``(TPU_CHIPS_PER_PROCESS_BOUNDS, TPU_PROCESS_BOUNDS)``.
#: The same table JAX's own multi-process TPU tests use; verified on a
#: v5e 2x2 host (CHANGES.md PR 21).
_SLICE_BOUNDS_2X2 = {
    1: ("1,1,1", "2,2,1"),
    2: ("1,2,1", "2,1,1"),
    4: ("2,2,1", "1,1,1"),
}


def set_visible_chips(chip_ids, process_index=0, process_ports=None):
    """Restrict this process to a subset of local TPU chips.

    Must run before JAX backend initialization (the TPU twin of the
    ``CUDA_VISIBLE_DEVICES`` export, reference: gpu_info.py:87-94 /
    TFSparkNode.py:364-366).  ``TPU_VISIBLE_CHIPS`` alone is not
    enough: libtpu also needs the process's chip bounds, or a second
    process on the host dies on the libtpu lockfile.

    Args:
      chip_ids: local chip indices this process may use (1, 2 or 4).
      process_index / process_ports: set when the co-hosted processes
        form ONE slice (``cluster.run`` workers that will join through
        ``ctx.initialize_distributed()``): this process's rank among
        them and every rank's reserved libtpu port.  ``None`` means an
        independent process (``parallel_run``, ``single_node_env``)
        that shares the host but not a mesh.
    """
    chip_ids = list(chip_ids)
    bounds = _SLICE_BOUNDS_2X2.get(len(chip_ids))
    if bounds is None:
        raise ChipLayoutError(
            "cannot give a process {0} chip(s) of a 2x2 host; supported "
            "chips per process: {1}".format(
                len(chip_ids), sorted(_SLICE_BOUNDS_2X2)
            )
        )
    env = {
        "TPU_VISIBLE_CHIPS": ",".join(str(c) for c in chip_ids),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": bounds[0],
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }
    if process_ports is not None:
        if len(process_ports) * len(chip_ids) != _HOST_CHIPS:
            raise ChipLayoutError(
                "{0} co-hosted processes x {1} chip(s) do not tile a "
                "2x2 host; a slice of co-hosted processes must use all "
                "four chips".format(len(process_ports), len(chip_ids))
            )
        env.update({
            "TPU_PROCESS_BOUNDS": bounds[1],
            "TPU_PROCESS_ADDRESSES": ",".join(
                "localhost:{0}".format(p) for p in process_ports
            ),
            "TPU_PROCESS_PORT": str(process_ports[process_index]),
            "CLOUD_TPU_TASK_ID": str(process_index),
        })
    os.environ.update(env)
    logger.info("TPU process env: %s", env)


def check_chip_layout(cluster_info, num_chips_per_node):
    """Refuse, by name, a cluster layout whose compute processes would
    fight over a host's chips.

    ``cluster_info`` is the rendezvous result (each node's ``host``,
    ``job_name`` and lazy ``device_info``), so the driver and every
    node reach the same verdict from the same data before any compute
    process is spawned.  Co-hosted compute nodes on a TPU host need
    ``num_chips_per_node``: without it each process claims every chip.
    """
    by_host = {}
    for n in cluster_info:
        if n.get("job_name") in ("chief", "master", "worker"):
            by_host.setdefault(n["host"], []).append(n)
    for host, nodes in sorted(by_host.items()):
        if len(nodes) < 2 or not any(
            (n.get("device_info") or {}).get("platform") == "tpu"
            for n in nodes
        ):
            continue
        if not num_chips_per_node:
            raise ChipLayoutError(
                "{0} compute executors share TPU host {1} but "
                "num_chips_per_node is unset: every compute process "
                "would claim all of the host's chips and all but one "
                "would die on the libtpu lockfile.  Pass "
                "num_chips_per_node (executors x chips must tile the "
                "host) or run one executor per host.".format(
                    len(nodes), host
                )
            )
        if len(nodes) * num_chips_per_node != _HOST_CHIPS:
            raise ChipLayoutError(
                "{0} compute executors x num_chips_per_node={1} do not "
                "tile the four chips of TPU host {2}".format(
                    len(nodes), num_chips_per_node, host
                )
            )


def get_chips(num_chips, worker_index=-1, total_chips=None):
    """Allocate ``num_chips`` local chip ids for this worker.

    Deterministic placement by local worker index, mirroring the
    reference's by-index GPU placement so multiple workers on one host
    land on disjoint chips (reference: gpu_info.py:74-86).
    """
    if total_chips is None:
        total_chips = int(os.environ.get("TPU_HOST_CHIPS", "4"))
    if num_chips > total_chips:
        raise RuntimeError(
            "requested {0} chips but host has {1}".format(num_chips, total_chips)
        )
    if worker_index < 0:
        start = 0
    else:
        # No modulo wrap: a wrapped window would silently alias another
        # worker's chips, and two JAX runtimes contending for a chip is
        # fatal — oversubscription must fail loudly.
        start = worker_index * num_chips
        if start + num_chips > total_chips:
            raise RuntimeError(
                "worker {0} needs chips [{1},{2}) but the host has only "
                "{3}; use fewer chips per worker or fewer workers per "
                "host".format(worker_index, start, start + num_chips, total_chips)
            )
    return list(range(start, start + num_chips))


def get_device_info_lazy():
    """Device info WITHOUT initializing a JAX backend.

    The executor task process must never claim TPU chips (exactly one
    process per host may own a chip set — the compute process); this
    reads env/topology hints only.  ``get_device_info`` (above) is the
    full probe for use inside the compute process.  ``JAX_PLATFORMS``
    wins when set (its first entry is the platform JAX will use — a
    CPU-pinned test on a TPU VM is a CPU cluster); otherwise the TPU
    VM's own variables say a chip is there.
    """
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms:
        platform = platforms.split(",")[0]
    elif any(
        os.environ.get(k)
        for k in ("TPU_ACCELERATOR_TYPE", "TPU_SKIP_MDS_QUERY",
                  "TPU_VISIBLE_CHIPS")
    ):
        platform = "tpu"
    else:
        platform = "unknown"
    visible = os.environ.get("TPU_VISIBLE_CHIPS")
    if visible:
        num = len([c for c in visible.split(",") if c.strip()])
    else:
        num = int(os.environ.get("TPU_HOST_CHIPS", "0"))
    return {"platform": platform, "num_devices": num, "devices": []}
