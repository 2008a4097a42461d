"""Host time per collective application from the start of each program call
(``bench.call``) in the traced window to the first program launch
(``DoEnqueueProgram``, on any chip) inside it: the host's work before the
chips are handed the program, averaged over the calls.  Both ends are read
on the host's clock, so no device-to-host clock offset enters it."""

import bisect
import statistics

from chipbench.programs import CALL_SPAN


def read(record):
    trace = record.get("trace")
    if trace is None or not trace.get("launches") or not record.get("attempted"):
        return None
    w0, w1 = trace["window"]
    starts = [launch[0] for launch in trace["launches"]]
    waits = []
    for name, s, d in trace["host"]:
        if name != CALL_SPAN or s < w0 or s + d > w1:
            continue
        k = bisect.bisect_left(starts, s)
        if k < len(starts) and starts[k] < s + d:
            waits.append(starts[k] - s)
    if not waits:
        return None
    per_call = record["applications"] // record["attempted"]
    return 1e-3 * statistics.fmean(waits) / per_call
