"""Device time per collective application, averaged over the chips, inside
the loop of each whole program execution in the traced window that no op
covers: the in-loop gaps that ``idle_share.collectives`` counts as busy.
An execution is an outermost op that holds others (the loop's ``while``)."""

from chipbench.programs import loop_idle_us


def read(record):
    trace = record.get("trace")
    if trace is None or not record.get("attempted"):
        return None
    return loop_idle_us(trace, record["applications"] // record["attempted"])
