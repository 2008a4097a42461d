"""From the profiler's trace to the numbers the per-layer metrics read.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
only what the reduction needs, as plain data: the operations each TPU ran
(line ``XLA Ops`` of each ``/device:TPU:<n>`` plane), the harness's own
host spans (``bench.*``, see ``clock.span``) and the runtime's program
launches on the host (``DoEnqueueProgram``).  ``reduce`` turns that into
busy time, the idle share, the operations that took the most device time and
the idle gaps named by the innermost harness span open in them.  The
reduction works on the plain data, so a small recorded trace tests it.
"""

from __future__ import annotations

import bisect
import collections
import pathlib
import re

from chipbench.clock import SPAN_PREFIX

WINDOW_SPAN = SPAN_PREFIX + "window"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
LAUNCH_EVENT = "DoEnqueueProgram"
NO_SPAN = "no harness span"


_LAYOUT = re.compile(r"\{[^{}]*\}")
_HLO = re.compile(r"^%?(\S+) = (.*?) ?([a-z][a-z0-9\-]*)\(")


def op_name(event_name: str) -> str:
    """``kind name type`` from the HLO text a TPU trace names an op by,
    without layouts: ``fusion fusion.623 (f32[2,16,4096], f32[...])``."""
    m = _HLO.match(_LAYOUT.sub("", event_name))
    if not m:
        return event_name[:120]
    name, typ, kind = m.groups()
    return f"{kind} {name} {typ}"[:120]


def extract(trace_dir: str | pathlib.Path) -> dict:
    from jax.profiler import ProfileData

    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    devices, host, launches = {}, [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            dev = plane.name[len(DEVICE_PLANE):]
            ops = [[op_name(e.name), float(e.start_ns), float(e.duration_ns)]
                   for line in plane.lines if line.name == OPS_LINE for e in line.events]
            devices[dev] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        host.append([e.name, float(e.start_ns), float(e.duration_ns)])
                    elif e.name == LAUNCH_EVENT:
                        launches.append(_launch(e))
    windows = [h for h in host if h[0] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    w = windows[-1]
    return {"window": [w[1], w[1] + w[2]], "devices": devices, "host": host,
            "launches": sorted(launches, key=lambda launch: launch[0])}


def _launch(event) -> list:
    """[start, duration, run_id, device_ordinal] of a program launch; an id
    the event's stats do not give is None."""
    stats = dict(event.stats)
    ids = [None if stats.get(k) is None else int(stats[k]) for k in ("run_id", "device_ordinal")]
    return [float(event.start_ns), float(event.duration_ns), *ids]


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(ops):
    """Each op's duration less that of the ops nested in it (a ``while``
    holds its body's ops on the same line)."""
    out, stack = [], []  # stack of [end, index into out]
    for name, s, d in sorted(ops, key=lambda op: (op[1], -op[2])):
        while stack and stack[-1][0] < s + d:  # not inside this one
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= d
        out.append([name, d, s])
        stack.append([s + d, len(out) - 1])
    return out


def busy_intervals(ops, window):
    t0, t1 = window
    clipped = ((max(s, t0), min(s + d, t1)) for _, s, d in ops)
    return _merge([s, e] for s, e in clipped if e > s)


def span_segments(host):
    """Cuts the time the harness spans cover into pieces, each named by the
    innermost (shortest) span open there: (starts, ends, names), sorted."""
    spans = [(s, s + d, d, name) for name, s, d in host if name != WINDOW_SPAN]
    cuts = sorted({t for s, e, _, _ in spans for t in (s, e)})
    starts, ends, names = [], [], []
    by_start = sorted(spans)
    active, i = [], 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(by_start) and by_start[i][0] <= a:
            active.append(by_start[i])
            i += 1
        active = [sp for sp in active if sp[1] > a]
        if active:
            starts.append(a)
            ends.append(b)
            names.append(min(active, key=lambda sp: sp[2])[3])
    return starts, ends, names


def _name_at(segments, t):
    starts, ends, names = segments
    k = bisect.bisect_right(starts, t) - 1
    return names[k] if k >= 0 and t < ends[k] else NO_SPAN


def reduce(trace: dict, top: int = 10) -> dict:
    """busy_s and window_s (averaged over the chips), idle_share, and the
    breakdown: device ops by their own seconds per chip (nested ops taken
    out), idle gaps by the span open in them."""
    window = trace["window"]
    window_s = (window[1] - window[0]) * 1e-9
    devices = trace["devices"]
    if not devices:
        raise ValueError("the trace holds no TPU plane")
    n = len(devices)
    busy_ns = 0.0
    op_time = collections.Counter()
    gap_time = collections.Counter()
    segments = span_segments(trace["host"])
    for ops in devices.values():
        busy = busy_intervals(ops, window)
        busy_ns += sum(e - s for s, e in busy)
        for name, d, s in self_times(ops):
            if window[0] <= s < window[1]:
                op_time[name] += d * 1e-9 / n
        edges = [window[0]] + [t for iv in busy for t in iv] + [window[1]]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gap_time[_name_at(segments, (s + e) / 2)] += (e - s) * 1e-9 / n
    busy_s = busy_ns * 1e-9 / n
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "breakdown": {
            "device_ops": [[k, v] for k, v in op_time.most_common(top)],
            "idle_gaps": [[k, v] for k, v in gap_time.most_common(top)],
        },
    }


def device_seconds(trace: dict, match) -> float:
    """Seconds per chip of the operations whose name ``match`` accepts,
    inside the window."""
    window = trace["window"]
    n = len(trace["devices"])
    total = 0.0
    for ops in trace["devices"].values():
        total += sum(e - s for s, e in busy_intervals(
            [op for op in ops if match(op[0])], window))
    return total * 1e-9 / n
