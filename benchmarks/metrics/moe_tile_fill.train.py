"""How full the row tiles that the grouped matmuls multiply are: the
routed rows that landed on the held experts over the rows of the live
tiles (live tiles x tile rows), both the step's own counters summed
over the window's steps.  What is missing from 100% is what rounding
every expert's run up to whole tiles costs, routing pass by routing
pass."""


def reduce(trace, counters, cell):
    rows = counters.get("moe_rows_multiplied")
    local = counters.get("moe_local_assignments")
    if not rows or local is None:
        return None
    return 100.0 * local / rows
