"""Share of the traced window in which no operation ran on the busiest
chip: the serving cell's reader, on the training cell's trace (one
quantity, two names, because the cells report different end-to-end
metrics)."""

from benchmarks.runners.common import load_reader

reduce = load_reader("device_idle_share.serve")
