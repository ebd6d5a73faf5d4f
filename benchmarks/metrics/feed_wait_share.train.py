"""Share of the window the trainer's host spent waiting on the feed's
``next``: growth of the ``train.feed_wait_sec`` histogram's sum inside
the window over the window."""


def reduce(trace, counters, cell):
    if "feed_wait_s" not in counters or not counters.get("window_s"):
        return None
    return 100.0 * counters["feed_wait_s"] / counters["window_s"]
