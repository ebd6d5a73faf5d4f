"""Remote-filesystem record IO.

The reference read/wrote TFRecords on HDFS through the Hadoop
InputFormat jar (reference: dfutil.py:39,63); here any ``scheme://``
URI routes through fsspec with the same framing.  ``memory://`` stands
in for ``gs://``/``hdfs://`` — same fsspec code path, no network.
"""

import pytest

fsspec = pytest.importorskip("fsspec")

from tensorflowonspark_tpu.data import interchange, tfrecord as tfr  # noqa: E402
from tensorflowonspark_tpu.utils import fs as fs_utils  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_memory_fs():
    fs = fsspec.filesystem("memory")
    try:
        fs.rm("/", recursive=True)
    except FileNotFoundError:
        pass
    yield


def test_scheme_split_and_remote_detection():
    assert fs_utils.split_scheme("gs://bucket/key") == ("gs", "bucket/key")
    assert fs_utils.split_scheme("/a/b") == ("", "/a/b")
    assert fs_utils.is_remote("memory://x")
    assert not fs_utils.is_remote("/tmp/x")
    assert not fs_utils.is_remote("file:///tmp/x")
    assert fs_utils.local_path("file:///tmp/x") == "/tmp/x"


def test_raw_records_roundtrip_memory_uri():
    uri = "memory://bench/records.tfr"
    recs = [b"alpha", b"beta", b"\x00" * 64]
    assert tfr.write_records(uri, recs) == 3
    assert list(tfr.read_records(uri)) == recs


def test_corruption_detected_on_remote_uri():
    uri = "memory://bench/corrupt.tfr"
    tfr.write_records(uri, [b"payload"])
    fs = fsspec.filesystem("memory")
    raw = bytearray(fs.cat("/bench/corrupt.tfr"))
    raw[14] ^= 0xFF  # flip a data byte
    with fs.open("/bench/corrupt.tfr", "wb") as f:
        f.write(bytes(raw))
    with pytest.raises(tfr.CorruptRecordError):
        list(tfr.read_records(uri))


def test_interchange_roundtrip_memory_dir():
    rows = [
        {"x": float(i), "label": i % 3, "name": "row-{0}".format(i)}
        for i in range(20)
    ]
    uri = "memory://data/train"
    n = interchange.save_as_tfrecords(rows, uri, num_shards=3)
    assert n == 20
    files = fs_utils.list_files(uri)
    assert len(files) == 3 and all(f.startswith("memory://") for f in files)
    loaded, schema = interchange.load_tfrecords(uri)
    assert len(loaded) == 20
    names = {r["name"] for r in loaded}
    assert names == {"row-{0}".format(i) for i in range(20)}


def test_serving_cli_remote_input_and_output(tmp_path):
    """The serving CLI reads TFRecords from and writes its JSONL results
    to remote URIs (reference: Inference.scala read/wrote HDFS)."""
    import json

    import numpy as np

    from tensorflowonspark_tpu import serving
    from tensorflowonspark_tpu.checkpoint import save_for_serving

    export_dir = str(tmp_path / "export")
    save_for_serving(
        export_dir,
        {"w": np.array([3.14, 1.618], np.float32), "b": np.float32(0.5)},
        extra_metadata={
            "model_config": {"input_name": "features"},
            "model_ref": "tensorflowonspark_tpu.models.linear:serving_builder",
        },
    )
    in_uri = "memory://serve/in"
    rows = [{"x": [float(i), 1.0]} for i in range(6)]
    interchange.save_as_tfrecords(rows, in_uri, num_shards=2)

    out_uri = "memory://serve/out"
    count = serving.main(
        [
            "--export_dir", export_dir,
            "--input", in_uri,
            "--schema_hint", "struct<x:array<float>>",
            "--input_mapping", "x=features",
            "--output_mapping", "prediction=pred",
            "--output", out_uri,
            "--batch_size", "4",
        ]
    )
    assert count == 6
    fs = fsspec.filesystem("memory")
    lines = fs.cat("/serve/out/part-00000.jsonl").decode().strip().splitlines()
    preds = sorted(
        float(np.ravel(json.loads(ln)["pred"])[0]) for ln in lines
    )
    expected = sorted(3.14 * i + 1.618 + 0.5 for i in range(6))
    assert np.allclose(preds, expected, atol=1e-3)
