"""Every repo path a document cites exists.

README.md and docs/*.md name source files in back-ticks
(`models/transformer.py`, `tests/test_fleet.py`, `ops/gmm.py:62`); a
document must not go on citing a file after the file has gone.  No JAX:
it reads text and walks the tree.
"""

import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: what building, testing and chip runs leave behind (see .gitignore)
NOT_SOURCE = {".git", "_checkout", "chiprun_out", ".jax_cache",
              "__pycache__", ".pytest_cache", ".hypothesis"}

#: files of the reference project (TensorFlowOnSpark), cited where the
#: documents map its layout onto this repo's
UPSTREAM = {
    "TFCluster.py", "TFManager.py", "TFParallel.py", "TFSparkNode.py",
    "dfutil.py", "scripts/spark_ec2.py", "examples/utils/stop_streaming.py",
}

SPAN = re.compile(r"`([^`\n]+)`")
PATH = re.compile(r"[A-Za-z0-9_./-]*[A-Za-z0-9_]\.py\b")

DOCS = ["README.md"] + sorted(
    "docs/" + name for name in os.listdir(os.path.join(ROOT, "docs"))
    if name.endswith(".md")
)


@pytest.fixture(scope="module")
def python_files():
    # "/"-rooted at the checkout, so that one suffix test finds a
    # citation relative to the root, to the package or to any directory
    # of the tree (`runners/train.py`, a bare `serving.py`)
    found = []
    for where, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in NOT_SOURCE]
        found += [
            "/" + os.path.relpath(os.path.join(where, name), ROOT)
            for name in files if name.endswith(".py")
        ]
    return found


@pytest.mark.parametrize("doc", DOCS)
def test_cited_python_files_exist(doc, python_files):
    with open(os.path.join(ROOT, doc)) as f:
        text = f.read()
    cited = set()
    for span in SPAN.findall(text):
        cited.update(PATH.findall(span))
    missing = sorted(
        path for path in cited - UPSTREAM
        if not any(have.endswith("/" + path) for have in python_files)
    )
    assert not missing, "%s cites files that do not exist: %s" % (doc, missing)
