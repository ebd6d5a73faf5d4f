"""Bytes the key/value banks HOLD over what whole banks on every layer
would hold: the engine's ``serving.kv_bank_bytes_ring`` and ``_whole``
gauges over ``_unringed`` (the runner hands them on as
``counters["kv_bank_bytes"]``).  100 where no layer's window is
shorter than its bank; a layer that keeps a ring of its window brings
it down.  Nothing where the program has no such gauges, as on a parent
commit."""


def reduce(trace, counters, cell):
    held = counters.get("kv_bank_bytes") or {}
    if not held.get("unringed") or held.get("whole") is None:
        return None
    return 100.0 * (held.get("ring", 0) + held["whole"]) / held["unringed"]
