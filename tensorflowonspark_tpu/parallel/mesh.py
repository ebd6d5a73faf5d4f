"""Device-mesh construction over ICI/DCN.

The TPU-native replacement for the reference's cluster-spec/TF_CONFIG
machinery (reference: tensorflowonspark/TFSparkNode.py:340-362): instead
of wiring gRPC servers by job name, parallelism is expressed as named
axes of a :class:`jax.sharding.Mesh`, and XLA lowers collectives onto
ICI (intra-slice) / DCN (inter-slice) links.

Canonical axis names (used by every strategy module and the models):

========  =====================================================
axis      meaning
========  =====================================================
``data``  pure data parallelism (batch split, grads psum'd)
``ps``    device-resident PS aggregation (hierarchical gradient
          plane: in-pod grads psum/reduce-scatter along this axis,
          see :mod:`tensorflowonspark_tpu.parallel.hier_ps`)
``fsdp``  data parallelism with fully-sharded params (zero-3)
``model`` tensor parallelism (matmul column/row sharding)
``pipe``  pipeline stages (microbatched ppermute loop)
``seq``   sequence/context parallelism (ring attention, Ulysses)
``expert`` expert parallelism (MoE all-to-all dispatch)
========  =====================================================

Mesh-order convention follows the scaling playbook: slowest-varying axis
first = the axis that may span DCN (data), fastest-varying axes last =
the ones needing the tightest ICI locality (model/seq).
"""

import logging
import math

logger = logging.getLogger(__name__)

AXIS_DATA = "data"
AXIS_PS = "ps"
AXIS_FSDP = "fsdp"
AXIS_TENSOR = "model"
AXIS_PIPELINE = "pipe"
AXIS_SEQ = "seq"
AXIS_EXPERT = "expert"

#: All known axes in canonical mesh order (DCN-friendly → ICI-hungry).
#: ``ps`` sits right after ``data``: the in-pod aggregation axis wants
#: ICI locality but never spans DCN (the hierarchical plane's whole
#: point is that only a pod leader crosses it).
CANONICAL_ORDER = (
    AXIS_PIPELINE,
    AXIS_DATA,
    AXIS_PS,
    AXIS_FSDP,
    AXIS_EXPERT,
    AXIS_SEQ,
    AXIS_TENSOR,
)


def distributed_init_from_env(environ=None):
    """``jax.distributed`` bootstrap for pod-launched hosts.

    ``scripts/tpu_pod.py run`` exports ``TFOS_COORDINATOR``
    (host:port of worker 0) and ``TFOS_PROCESS_ID`` on every host of
    the slice; this reads them and initializes the process group
    (num_processes from ``TFOS_NUM_PROCESSES`` when set, otherwise the
    TPU backend infers it from the slice metadata).  No-op when the
    variables are absent (single-host runs) or when jax.distributed is
    already initialized.  The Spark/LocalEngine path wires the same
    thing from the reservation server instead
    (``cluster.node.NodeContext.initialize_distributed``).

    Returns True when initialization ran.
    """
    import os

    env = os.environ if environ is None else environ
    coord = env.get("TFOS_COORDINATOR")
    if not coord:
        return False
    import jax

    if jax.distributed.is_initialized():
        return False
    kwargs = {"coordinator_address": coord}
    if env.get("TFOS_PROCESS_ID") is not None:
        kwargs["process_id"] = int(env["TFOS_PROCESS_ID"])
    if env.get("TFOS_NUM_PROCESSES") is not None:
        kwargs["num_processes"] = int(env["TFOS_NUM_PROCESSES"])
    jax.distributed.initialize(**kwargs)
    logger.info(
        "jax.distributed initialized from env: %s process %s",
        coord, env.get("TFOS_PROCESS_ID"),
    )
    return True


class MeshSpec(object):
    """Declarative mesh shape: ordered ``(axis_name, size)`` pairs.

    ``size == -1`` on at most one axis means "absorb all remaining
    devices".  Example::

        MeshSpec(data=-1, model=2)        # 8 devices -> data=4, model=2
        MeshSpec.from_axes([("pipe", 2), ("data", -1)])
    """

    def __init__(self, **axes):
        # preserve canonical order for kwargs; explicit list via from_axes
        ordered = [(n, axes.pop(n)) for n in CANONICAL_ORDER if n in axes]
        if axes:
            # unknown axis names are allowed (user-defined), appended last
            ordered.extend(sorted(axes.items()))
        self.axes = ordered

    @classmethod
    def from_axes(cls, axes):
        spec = cls()
        spec.axes = [(str(n), int(s)) for n, s in axes]
        return spec

    def resolve(self, num_devices):
        """Concretize ``-1`` and validate the factorization."""
        names = [n for n, _ in self.axes]
        if len(set(names)) != len(names):
            raise ValueError("duplicate axis names in {0}".format(names))
        sizes = [s for _, s in self.axes]
        wild = [i for i, s in enumerate(sizes) if s == -1]
        if len(wild) > 1:
            raise ValueError("at most one axis may have size -1")
        fixed = math.prod(s for s in sizes if s != -1)
        if wild:
            if num_devices % fixed != 0:
                raise ValueError(
                    "fixed axes {0} do not divide device count {1}".format(
                        fixed, num_devices
                    )
                )
            sizes[wild[0]] = num_devices // fixed
        elif fixed != num_devices:
            raise ValueError(
                "mesh {0} needs {1} devices, have {2}".format(
                    self.axes, fixed, num_devices
                )
            )
        return list(zip(names, sizes))


def build_mesh(axes=None, devices=None, allow_split_physical=True):
    """Build a :class:`jax.sharding.Mesh`.

    Args:
      axes: ``None`` (all devices on ``data``), a :class:`MeshSpec`, a
        dict ``{axis: size}``, or an ordered list of ``(axis, size)``
        pairs; ``-1`` absorbs remaining devices.
      devices: override the device list (default ``jax.devices()``).
      allow_split_physical: fall back to a plain reshape when
        ``mesh_utils.create_device_mesh`` rejects the shape (e.g. virtual
        CPU devices with no physical topology).

    The device order is delegated to ``jax.experimental.mesh_utils`` so
    ICI-adjacent chips land adjacent on the fastest-varying axes.
    """
    # Pod-launched hosts (scripts/tpu_pod.py run) carry the rendezvous
    # in env vars; joining the process group must precede the first
    # device query, and every program path funnels through build_mesh —
    # a no-op unless TFOS_COORDINATOR is set and not yet initialized.
    distributed_init_from_env()

    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    n = len(devices)

    if axes is None:
        axes = MeshSpec(**{AXIS_DATA: -1})
    elif isinstance(axes, dict):
        axes = MeshSpec(**axes)
    elif isinstance(axes, (list, tuple)):
        axes = MeshSpec.from_axes(axes)

    resolved = axes.resolve(n)
    names = tuple(name for name, _ in resolved)
    shape = tuple(size for _, size in resolved)

    try:
        from jax.experimental import mesh_utils

        device_array = mesh_utils.create_device_mesh(shape, devices=devices)
    except (ValueError, AssertionError, NotImplementedError) as e:
        if not allow_split_physical:
            raise
        # a plain reshape ignores ICI adjacency: on real chips the
        # fastest-varying axes may then cross the slowest links
        logger.warning(
            "mesh_utils rejected shape %s (%s); falling back to a plain "
            "reshape of the device list", shape, e,
        )
        import numpy as np

        device_array = np.asarray(devices).reshape(shape)

    mesh = Mesh(device_array, names)
    logger.info("built mesh %s over %d devices", dict(resolved), n)
    return mesh


def serving_mesh(tp=None, mesh_shape=None, devices=None):
    """The SERVING stack's mesh (``serving_builder`` ``tp`` /
    ``mesh_shape`` knobs, docs/serving.md "Disaggregated
    prefill/decode & TP sharding").

    ``tp=N`` is the shorthand: a 1-axis ``model=N`` mesh over the
    first N devices — the tensor-parallel degree the SlotDecoder
    shards its weights and KV page pools over.  ``mesh_shape`` (a
    ``{axis: size}`` dict, ``-1`` wildcard allowed) overrides it for
    explicit topologies (e.g. ``{"data": 2, "model": 2}``).  Returns
    ``None`` when neither asks for more than one device — the caller
    then keeps the unsharded single-program path, so the knobs are
    strictly additive.
    """
    if mesh_shape:
        return build_mesh(dict(mesh_shape), devices=devices)
    t = int(tp or 0)
    if t <= 1:
        return None
    import jax

    devs = list(devices) if devices is not None else list(jax.devices())
    if len(devs) < t:
        raise ValueError(
            "tp={0} needs {0} devices, have {1}".format(t, len(devs))
        )
    return build_mesh(MeshSpec(**{AXIS_TENSOR: t}), devices=devs[:t])


def mesh_axis_size(mesh, *axis_names):
    """Product of the named axes' sizes (1 for absent axes) — the standard
    way strategies ask "how wide is my parallelism" without caring which
    axes exist on this particular mesh."""
    size = 1
    for name in axis_names:
        size *= mesh.shape.get(name, 1)
    return size


def local_batch_size(mesh, global_batch_size, data_axes=(AXIS_DATA, AXIS_FSDP)):
    """Per-process batch share for a multi-host mesh (the reference's
    equivalent knob was implicit in RDD partitioning)."""
    width = mesh_axis_size(mesh, *data_axes)
    if global_batch_size % width != 0:
        raise ValueError(
            "global batch {0} not divisible by data-parallel width {1}".format(
                global_batch_size, width
            )
        )
    import jax

    return global_batch_size // jax.process_count()
