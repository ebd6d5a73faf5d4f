"""From the benchmark's ``cluster.run`` call to ``main_fun`` entered in
the compute process: reservation, executor start, rendezvous."""


def reduce(trace, counters, cell):
    return counters.get("cluster_start_s")
