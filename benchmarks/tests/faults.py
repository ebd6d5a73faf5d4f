"""Faults a test plants UNDER the timed path, in the process that runs
it, to see ``correct`` come out false.  Reached only through a test's
rehearsal (``run.main(..., rehearse={"fault": name})``); the command
the driver runs has no way to name one."""


def state_unchanged():
    """Every step computes its loss and returns the state it was
    given."""
    from tensorflowonspark_tpu.parallel import dp

    step_on_device = dp.SyncTrainer.step_on_device

    def stuck(self, state, device_batch, rng):
        import jax

        keep = jax.tree.map(lambda x: x.copy(), state)
        _, metrics = step_on_device(self, state, device_batch, rng)
        return keep, metrics

    dp.SyncTrainer.step_on_device = stuck


def half_batch():
    """Half of every batch is left out and the mean taken over the
    rest.  Under GSPMD the gradient exchange over ``data`` is no call
    of the program's that a test could take out; seen from the first
    data replica, leaving it out is exactly this fault — the mean over
    its own half of the rows."""
    from tensorflowonspark_tpu.models import transformer as tr

    loss_fn = tr.loss_fn

    def halved(model):
        inner = loss_fn(model)

        def loss(params, batch, rng):
            tokens = batch["tokens"]
            return inner(
                params, {"tokens": tokens[: tokens.shape[0] // 2]}, rng)

        return loss

    tr.loss_fn = halved


FAULTS = {
    "state_unchanged": state_unchanged,
    "half_batch": half_batch,
    "no_gradient_exchange": half_batch,
}


def plant(name):
    FAULTS[name]()
