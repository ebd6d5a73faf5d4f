"""Pod bring-up script (scripts/tpu_pod.py) — config/command rendering.

The reference's deployment tooling (scripts/spark_ec2.py) was never
exercised in its CI either; what IS testable without GCP credentials is
that every action renders complete, correctly-quoted gcloud commands
and that the rendezvous env the `run` action exports matches what
``parallel.mesh.distributed_init_from_env`` consumes.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "scripts", "tpu_pod.py")

sys.path.insert(0, os.path.join(REPO, "scripts"))
import tpu_pod  # noqa: E402


CFG = tpu_pod.PodConfig(name="tfos-pod", zone="us-east5-a")


def test_create_renders_accelerator_and_zone():
    (cmd,) = tpu_pod.render_create(CFG)
    assert cmd[:5] == ["gcloud", "compute", "tpus", "tpu-vm", "create"]
    assert "tfos-pod" in cmd
    assert cmd[cmd.index("--zone") + 1] == "us-east5-a"
    assert cmd[cmd.index("--accelerator-type") + 1] == "v5litepod-16"


def test_delete_is_quiet():
    (cmd,) = tpu_pod.render_delete(CFG)
    assert "delete" in cmd and "--quiet" in cmd


def test_bootstrap_clones_and_builds_native():
    (cmd,) = tpu_pod.render_bootstrap(
        CFG, "https://example.com/r.git", ref="v1.0"
    )
    assert "--worker=all" in cmd  # every host of the slice
    remote = cmd[cmd.index("--command") + 1]
    assert "git clone" in remote and "v1.0" in remote
    assert "make -C ~/tfos-tpu/native" in remote


def test_run_exports_rendezvous_env():
    (cmd,) = tpu_pod.render_run(
        CFG, ["python", "examples/mnist/mnist_spark.py", "--cluster_size", "4"]
    )
    remote = cmd[cmd.index("--command") + 1]
    # the exported variables are exactly what
    # mesh.distributed_init_from_env consumes — including the explicit
    # process count (initialize() with only process_id raises on hosts
    # where JAX's cluster auto-detect finds nothing)
    assert "TFOS_COORDINATOR=$COORD:%d" % tpu_pod.COORDINATOR_PORT in remote
    assert "TFOS_PROCESS_ID=$WID" in remote
    assert "TFOS_NUM_PROCESSES=$NPROC" in remote
    assert "examples/mnist/mnist_spark.py" in remote


def test_cli_dry_run_prints_without_executing(tmp_path):
    out = subprocess.run(
        [
            sys.executable, SCRIPT, "run", "--name", "p", "--zone", "z",
            "--dry-run", "--", "python", "x.py",
        ],
        stdout=subprocess.PIPE, text=True, check=True,
    ).stdout
    assert out.startswith("gcloud ")
    assert "x.py" in out


def test_distributed_init_env_contract():
    from tensorflowonspark_tpu.parallel import mesh

    # absent vars -> no-op (single host)
    assert mesh.distributed_init_from_env(environ={}) is False


def test_pod_env_rendezvous_forms_process_group(tmp_path):
    """The launcher's exported env actually forms a multi-process JAX
    group: two subprocesses with TFOS_COORDINATOR/TFOS_PROCESS_ID (what
    `tpu_pod.py run` exports on every host) call nothing but
    build_mesh() and end up in ONE 2-process Gloo mesh computing a
    global sum — the pod path's analogue of test_distributed.py."""
    import socket
    import time

    child = tmp_path / "pod_child.py"
    child.write_text(
        "import os\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import numpy as np\n"
        "import jax.numpy as jnp\n"
        "from jax.sharding import NamedSharding, PartitionSpec as P\n"
        "from tensorflowonspark_tpu.parallel.mesh import MeshSpec, "
        "build_mesh\n"
        "mesh = build_mesh(MeshSpec(data=-1))\n"
        "x = jax.make_array_from_process_local_data(\n"
        "    NamedSharding(mesh, P('data')),\n"
        "    np.ones((1,), np.float32),\n"
        "    global_shape=(jax.process_count(),),\n"
        ")\n"
        "s = jax.jit(lambda a: jnp.sum(a),\n"
        "            out_shardings=NamedSharding(mesh, P()))(x)\n"
        "print('RESULT', jax.process_count(), float(s), flush=True)\n"
    )
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env_base = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        TFOS_COORDINATOR="127.0.0.1:%d" % port,
        TFOS_NUM_PROCESSES="2",
        PYTHONPATH=os.pathsep.join([REPO] + sys.path),
        # one CPU device per process (the conftest's 8-device forcing
        # would make a 16-device global mesh)
        XLA_FLAGS=" ".join(
            f for f in os.environ.get("XLA_FLAGS", "").split()
            if not f.startswith("--xla_force_host_platform_device_count")
        ),
    )
    procs = [
        subprocess.Popen(
            [sys.executable, str(child)],
            env=dict(env_base, TFOS_PROCESS_ID=str(i)),
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
        for i in range(2)
    ]
    deadline = time.time() + 180
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=max(1, deadline - time.time()))
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
        outs.append(out)
    for out in outs:
        assert "RESULT 2 2.0" in out, outs
