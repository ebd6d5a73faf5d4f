import os
import unittest
from unittest import mock

from tensorflowonspark_tpu.cluster import tpu_info


class DeviceInfoTest(unittest.TestCase):
    def test_get_device_info_cpu(self):
        info = tpu_info.get_device_info()
        self.assertEqual(info["platform"], "cpu")
        self.assertEqual(info["num_devices"], 8)  # conftest virtual devices
        self.assertEqual(len(info["devices"]), 8)

    def test_chip_allocation_deterministic(self):
        with mock.patch.dict(os.environ, {"TPU_HOST_CHIPS": "4"}):
            self.assertEqual(tpu_info.get_chips(1, worker_index=0), [0])
            self.assertEqual(tpu_info.get_chips(1, worker_index=1), [1])
            self.assertEqual(tpu_info.get_chips(2, worker_index=1), [2, 3])
            self.assertEqual(tpu_info.get_chips(4, worker_index=0), [0, 1, 2, 3])

    def test_chip_allocation_overflow(self):
        with mock.patch.dict(os.environ, {"TPU_HOST_CHIPS": "4"}):
            with self.assertRaises(RuntimeError):
                tpu_info.get_chips(8, worker_index=0)

    def test_chip_allocation_wrap_collision_raises(self):
        # a wrapped window would collide with worker 0's chips -> loud failure
        with mock.patch.dict(os.environ, {"TPU_HOST_CHIPS": "4"}):
            with self.assertRaises(RuntimeError):
                tpu_info.get_chips(3, worker_index=1)

    def test_set_visible_chips_independent_process(self):
        # TPU_VISIBLE_CHIPS alone is not enough for libtpu: a second
        # process on the host dies on the lockfile without the bounds
        with mock.patch.dict(os.environ, {}, clear=False):
            tpu_info.set_visible_chips([0, 2])
            self.assertEqual(os.environ["TPU_VISIBLE_CHIPS"], "0,2")
            self.assertEqual(
                os.environ["TPU_CHIPS_PER_PROCESS_BOUNDS"], "1,2,1")
            self.assertEqual(os.environ["TPU_PROCESS_BOUNDS"], "1,1,1")
            self.assertNotIn("TPU_PROCESS_ADDRESSES", os.environ)

    def test_set_visible_chips_cohosted_slice(self):
        # four one-chip processes forming ONE slice of the 2x2 host (the
        # layout verified on a v5e host, CHANGES.md PR 21)
        ports = [7001, 7002, 7003, 7004]
        with mock.patch.dict(os.environ, {}, clear=False):
            tpu_info.set_visible_chips(
                [2], process_index=2, process_ports=ports)
            self.assertEqual(os.environ["TPU_VISIBLE_CHIPS"], "2")
            self.assertEqual(
                os.environ["TPU_CHIPS_PER_PROCESS_BOUNDS"], "1,1,1")
            self.assertEqual(os.environ["TPU_PROCESS_BOUNDS"], "2,2,1")
            self.assertEqual(
                os.environ["TPU_PROCESS_ADDRESSES"],
                "localhost:7001,localhost:7002,localhost:7003,"
                "localhost:7004",
            )
            self.assertEqual(os.environ["TPU_PROCESS_PORT"], "7003")
            self.assertEqual(os.environ["CLOUD_TPU_TASK_ID"], "2")

    def test_set_visible_chips_refuses_untileable_layouts(self):
        with mock.patch.dict(os.environ, {}, clear=False):
            with self.assertRaises(tpu_info.ChipLayoutError):
                tpu_info.set_visible_chips([0, 1, 2])
            with self.assertRaises(tpu_info.ChipLayoutError):
                tpu_info.set_visible_chips(
                    [0], process_index=0, process_ports=[1, 2, 3])


def _nodes(n, platform, host="10.0.0.1", job="worker"):
    return [
        {"executor_id": i, "host": host, "job_name": job,
         "device_info": {"platform": platform}}
        for i in range(n)
    ]


class ChipLayoutTest(unittest.TestCase):
    def test_cohosted_tpu_executors_need_chips_per_node(self):
        # the README quick-start shape on one TPU host: refused by name
        with self.assertRaisesRegex(
                tpu_info.ChipLayoutError, "num_chips_per_node is unset"):
            tpu_info.check_chip_layout(_nodes(4, "tpu"), None)
        tpu_info.check_chip_layout(_nodes(4, "tpu"), 1)
        tpu_info.check_chip_layout(_nodes(2, "tpu"), 2)
        with self.assertRaisesRegex(
                tpu_info.ChipLayoutError, "do not tile"):
            tpu_info.check_chip_layout(_nodes(3, "tpu"), 1)

    def test_layouts_that_cannot_collide_pass(self):
        tpu_info.check_chip_layout(_nodes(4, "cpu"), None)  # CPU tests
        tpu_info.check_chip_layout(_nodes(1, "tpu"), None)  # 1 per host
        spread = _nodes(1, "tpu", host="a") + _nodes(1, "tpu", host="b")
        tpu_info.check_chip_layout(spread, None)
        # service nodes own no chips
        tpu_info.check_chip_layout(
            _nodes(1, "tpu") + _nodes(3, "tpu", job="ps"), None)

    def test_lazy_platform_follows_jax_platforms(self):
        tpu_vm = {"TPU_SKIP_MDS_QUERY": "true",
                  "TPU_ACCELERATOR_TYPE": "v5litepod-4"}
        with mock.patch.dict(os.environ, tpu_vm):
            with mock.patch.dict(os.environ, {"JAX_PLATFORMS": "cpu"}):
                self.assertEqual(
                    tpu_info.get_device_info_lazy()["platform"], "cpu")
            with mock.patch.dict(os.environ, {"JAX_PLATFORMS": "tpu,cpu"}):
                self.assertEqual(
                    tpu_info.get_device_info_lazy()["platform"], "tpu")
            env = dict(os.environ)
            env.pop("JAX_PLATFORMS", None)
            with mock.patch.dict(os.environ, env, clear=True):
                self.assertEqual(
                    tpu_info.get_device_info_lazy()["platform"], "tpu")




def _never_runs(args, ctx):  # pragma: no cover - the layout is refused
    raise AssertionError("a compute process was spawned")


def test_cluster_run_refuses_cohosted_tpu_executors_fast():
    """End to end: executors that REPORT a TPU platform (the lazy probe
    reads the environment; nothing here touches a backend) and share a
    host with num_chips_per_node unset are refused by name right after
    the rendezvous — no compute process is spawned, nothing waits for a
    timeout."""
    import time

    import pytest

    from tensorflowonspark_tpu.cluster import cluster as tpu_cluster
    from tensorflowonspark_tpu.engine import LocalEngine

    engine = LocalEngine(2, env={"JAX_PLATFORMS": "tpu"})
    t0 = time.monotonic()
    try:
        with pytest.raises(tpu_info.ChipLayoutError, match="share TPU host"):
            tpu_cluster.run(
                engine, _never_runs, num_executors=2,
                input_mode=tpu_cluster.InputMode.SPARK,
                reservation_timeout=60,
            )
    finally:
        engine.stop()
    assert time.monotonic() - t0 < 30


if __name__ == "__main__":
    unittest.main()
