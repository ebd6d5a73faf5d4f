"""Token shards of the residual stream between blocks in the step the
run traced: the program's ``train.residual_shards`` gauge, which the
model sets when it is traced for training — the ``model`` axis's size
where the stream is split over it, 1 where every chip holds it whole.
Read in the process that traced the step.  Nothing where the program
has no such gauge, as on a parent commit, or with telemetry off."""


def reduce(trace, counters, cell):
    from tensorflowonspark_tpu import telemetry

    return telemetry.get_registry().snapshot()["gauges"].get(
        "train.residual_shards")
