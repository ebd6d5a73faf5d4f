#!/usr/bin/env bash
# Reproduce the CI `spark` job locally and keep the log as evidence.
#
# The development image for this repo cannot host pyspark (no package
# installs), so the real-Spark suite (tests/test_spark_real.py — a
# local-cluster[2,1,1024] run mirroring the reference's 2-worker
# Standalone posture, /root/reference/test/run_tests.sh:16-27) only
# executes where pyspark + a JDK are present: CI, or any dev machine
# via this script.  CI uploads the produced ci_logs/spark_*.log as
# `spark-e2e-log`.
#
# Usage: scripts/run_spark_suite.sh   (from the repo root)
set -euo pipefail

python -c "import pyspark" 2>/dev/null || {
  echo "pyspark is not installed; run where the CI spark job's deps" \
       "are available (pip install pyspark + JDK 17)" >&2
  exit 2
}

mkdir -p ci_logs
log="ci_logs/spark_$(date +%Y%m%d_%H%M%S).log"
set -o pipefail
python -m pytest tests/test_spark_real.py -m spark -x -q -rs | tee "$log"
python - "$log" <<'EOF'
import re
import sys

txt = open(sys.argv[1]).read()
m = re.search(r"(\d+) passed", txt)
assert m and int(m.group(1)) >= 5, (
    "spark e2e suite passed %s tests; expected >= 5" % (m and m.group(1))
)
print("spark suite green; evidence at", sys.argv[1])
EOF
