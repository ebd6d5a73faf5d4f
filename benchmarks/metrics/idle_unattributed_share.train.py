"""Share of the busiest chip's idle time that no span of the program
covers: the serving cell's reader, on the training cell's trace (one
quantity, two names, because the cells report different end-to-end
metrics)."""

from benchmarks.runners.common import load_reader

reduce = load_reader("idle_unattributed_share.serve")
