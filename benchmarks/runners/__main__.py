"""``python -m benchmarks.runners <spec.json>``: the chip-owning child.
The spec names the runner by the configuration's ``path`` key."""

import importlib
import json
import sys


def main(argv):
    with open(argv[1]) as f:
        spec = json.load(f)
    path = spec["config"]["path"]
    if not path.isidentifier():
        raise ValueError("bad runner name %r" % (path,))
    runner = importlib.import_module("benchmarks.runners." + path)
    from benchmarks.runners.common import emit

    emit(runner.run(spec))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
