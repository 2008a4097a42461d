"""Device time of the collective operations (all-reduce, reduce-scatter,
collective-permute, all-gather, all-to-all) per collective application in
the traced window, averaged over the chips.  The adds and selects that the
software schedules run between their permutes are not counted."""

from chipbench.trace import device_seconds


def read(record):
    trace = record.get("trace")
    if trace is None or not record.get("applications"):
        return None
    seconds = device_seconds(trace, record["is_collective"])
    return 1e6 * seconds / record["applications"] if seconds > 0 else None
