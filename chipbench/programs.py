"""Inside and around each program execution of a TPU profiler trace.

``trace.extract`` keeps each chip's ops, named by their HLO, and the
harness's host spans.  ``read`` takes three things more from the same
``.xplane.pb``, as plain data in the same nanoseconds:

- ``scopes``: the ``schedules.<op>.<schedule>`` scope of each op in
  ``devices``, as a parallel list of indices into one table of scope names
  (index 0, the empty name, is no scope).  The scope is the outermost such
  component of the op's HLO ``op_name``, which the trace keeps as the
  ``tf_op`` stat of the op's metadata.  ``jax.profiler.ProfileData`` does not
  show metadata stats, so that part reads the file's protocol buffer itself.
- ``programs``: each chip's program executions, [start, end], from the
  plane's ``XLA Modules`` line.
- ``clock``: per chip, the offset that puts device times on the host's
  clock (host = device + offset), with its error bound.  The host's launch
  (``DoEnqueueProgram``) and completion (``CompleteCallbacks``) events name
  the execution they belong to by ``run_id``; an execution cannot start
  before its launch began nor end after its completion began, so each one
  bounds the offset to [launch - start, completion - end].  The intersection
  over all executions is the estimate, its width the error; where it is
  empty the offset reads None, with a note.

The readers below work on the plain data, so hand-worked traces and a
recorded one test them.
"""

from __future__ import annotations

import bisect
import pathlib
import re
import statistics

from chipbench.clock import SPAN_PREFIX
from chipbench.trace import DEVICE_PLANE, OPS_LINE, _merge, op_name

MODULES_LINE = "XLA Modules"
LAUNCH_EVENT = "DoEnqueueProgram"
DONE_EVENT = "CompleteCallbacks"
CALL_SPAN = SPAN_PREFIX + "call"
SCOPE_STAT = "tf_op"
_SCOPE = re.compile(r"(?:^|/)(schedules\.\w+\.\w+)(?=/|:|$)")


def scope_of(hlo_op_name: str) -> str:
    """The outermost ``schedules.<op>.<schedule>`` component of an HLO
    ``op_name``, or '' where it has none."""
    m = _SCOPE.search(hlo_op_name)
    return m.group(1) if m else ""


def _xspace_class():
    """The message class of the part of ``XSpace`` (tsl/profiler/protobuf/
    xplane.proto) that ``op_scopes`` reads; the wire format lets it skip
    every other field.  Map fields are read as their repeated entries."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    f = descriptor_pb2.FieldDescriptorProto
    one, many = f.LABEL_OPTIONAL, f.LABEL_REPEATED
    fd = descriptor_pb2.FileDescriptorProto(name="chipbench_xplane.proto",
                                            package="chipbench_xplane", syntax="proto3")

    def message(name, *fields):
        m = fd.message_type.add(name=name)
        for field, number, kind, label, of in fields:
            entry = m.field.add(name=field, number=number, type=kind, label=label)
            if of:
                entry.type_name = f".chipbench_xplane.{of}"

    message("XSpace", ("planes", 1, f.TYPE_MESSAGE, many, "XPlane"))
    message("XPlane", ("name", 2, f.TYPE_STRING, one, None),
            ("lines", 3, f.TYPE_MESSAGE, many, "XLine"),
            ("event_metadata", 4, f.TYPE_MESSAGE, many, "EventMetadataEntry"),
            ("stat_metadata", 5, f.TYPE_MESSAGE, many, "StatMetadataEntry"))
    message("EventMetadataEntry", ("key", 1, f.TYPE_INT64, one, None),
            ("value", 2, f.TYPE_MESSAGE, one, "XEventMetadata"))
    message("StatMetadataEntry", ("key", 1, f.TYPE_INT64, one, None),
            ("value", 2, f.TYPE_MESSAGE, one, "XStatMetadata"))
    message("XLine", ("name", 2, f.TYPE_STRING, one, None),
            ("events", 4, f.TYPE_MESSAGE, many, "XEvent"))
    message("XEvent", ("metadata_id", 1, f.TYPE_INT64, one, None))
    message("XStat", ("metadata_id", 1, f.TYPE_INT64, one, None),
            ("str_value", 5, f.TYPE_STRING, one, None),
            ("ref_value", 7, f.TYPE_UINT64, one, None))
    message("XEventMetadata", ("name", 2, f.TYPE_STRING, one, None),
            ("stats", 5, f.TYPE_MESSAGE, many, "XStat"))
    message("XStatMetadata", ("name", 2, f.TYPE_STRING, one, None))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("chipbench_xplane.XSpace"))


def op_scopes(xspace: bytes, devices: dict) -> dict:
    """``scopes`` of the ops ``trace.extract`` kept in ``devices``, read from
    the serialized ``XSpace`` they came from.  Raises ValueError where the
    file's ops are not, one for one, the ops in ``devices``."""
    space = _xspace_class().FromString(xspace)
    names, index, ops = [""], {"": 0}, {}
    for plane in space.planes:
        chip = plane.name[len(DEVICE_PLANE):]
        if not plane.name.startswith(DEVICE_PLANE) or chip not in devices:
            continue
        meta = {e.key: e.value for e in plane.event_metadata}
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        by_meta = {}  # metadata id -> (op name as extract gives it, scope index)
        for key, m in meta.items():
            scope = ""
            for s in m.stats:
                if stat_names.get(s.metadata_id) == SCOPE_STAT:
                    scope = scope_of(s.str_value or stat_names.get(s.ref_value, ""))
            by_meta[key] = (op_name(m.name), index.setdefault(scope, len(index)))
        events = [e.metadata_id for line in plane.lines if line.name == OPS_LINE
                  for e in line.events]
        kept = devices[chip]
        if len(events) != len(kept):
            raise ValueError(f"chip {chip}: {len(events)} ops in the file, {len(kept)} kept")
        row = []
        for k, (meta_id, op) in enumerate(zip(events, kept)):
            name, scope = by_meta[meta_id]
            if name != op[0]:
                raise ValueError(f"chip {chip}, op {k}: {name!r} in the file, {op[0]!r} kept")
            row.append(scope)
        ops[chip] = row
    names += [n for n in sorted(index, key=index.get) if n]
    return {"names": names, "ops": ops}


def offset_bounds(pairs) -> dict:
    """The offset (host = device + offset) that every (launch, start, done,
    end) of ``pairs`` allows: launch <= start + offset, end + offset <= done."""
    pairs = list(pairs)
    if not pairs:
        return {"pairs": 0, "lo_ns": None, "hi_ns": None, "offset_ns": None, "error_ns": None,
                "note": "no execution is named by both a launch and a completion event"}
    lo = max(launch - start for launch, start, _, _ in pairs)
    hi = min(done - end for _, _, done, end in pairs)
    out = {"pairs": len(pairs), "lo_ns": lo, "hi_ns": hi, "offset_ns": None, "error_ns": None,
           "note": ""}
    if lo > hi:
        out["note"] = (f"the {len(pairs)} executions allow no common offset: a launch "
                       f"bounds it from below at {lo} ns, a completion from above at {hi} ns")
    else:
        out["offset_ns"], out["error_ns"] = (lo + hi) / 2, hi - lo
    return out


def programs_and_clock(profile) -> tuple[dict, dict]:
    """``programs`` and ``clock`` from a ``jax.profiler.ProfileData``."""
    programs, runs, launched, done = {}, {}, {}, {}
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE):
            chip = plane.name[len(DEVICE_PLANE):]
            programs[chip], runs[chip] = [], {}
            for line in plane.lines:
                if line.name != MODULES_LINE:
                    continue
                for e in line.events:
                    start, end = float(e.start_ns), float(e.start_ns) + float(e.duration_ns)
                    programs[chip].append([start, end])
                    run = dict(e.stats).get("run_id")
                    if run is not None:
                        runs[chip][int(run)] = (start, end)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name not in (LAUNCH_EVENT, DONE_EVENT):
                        continue
                    stats = dict(e.stats)
                    if "run_id" not in stats or "device_ordinal" not in stats:
                        continue
                    key = (str(int(stats["device_ordinal"])), int(stats["run_id"]))
                    into = launched if e.name == LAUNCH_EVENT else done
                    into[key] = min(into.get(key, float("inf")), float(e.start_ns))
    clock = {}
    for chip, by_run in runs.items():
        clock[chip] = offset_bounds(
            (launched[(chip, r)], start, done[(chip, r)], end)
            for r, (start, end) in sorted(by_run.items())
            if (chip, r) in launched and (chip, r) in done)
    for chip in programs:
        programs[chip].sort()
    return programs, clock


def read(trace_dir, devices: dict) -> dict:
    """``scopes``, ``programs`` and ``clock`` of the trace under
    ``trace_dir``, for the ``devices`` that ``trace.extract`` kept from it."""
    from jax.profiler import ProfileData

    path = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))[-1]  # the file extract reads
    programs, clock = programs_and_clock(ProfileData.from_file(str(path)))
    return {"scopes": op_scopes(path.read_bytes(), devices), "programs": programs,
            "clock": clock}


# -- readers of the plain data ------------------------------------------------


def nesting(ops) -> tuple[list, list]:
    """One chip's ops split into the outermost ops that hold others (a
    ``while`` holds its body on the same line) and the leaf ops, which hold
    none: two lists of [start, end], sorted."""
    order = sorted(([s, s + d] for _, s, d in ops), key=lambda iv: (iv[0], -iv[1]))
    outer, leaves, stack = [], [], []  # stack of [interval, depth, holds another]
    for iv in order:
        while stack and stack[-1][0][1] < iv[1]:  # not inside the top one
            _close(stack.pop(), outer, leaves)
        if stack:
            stack[-1][2] = True
        stack.append([iv, len(stack), False])
    while stack:
        _close(stack.pop(), outer, leaves)
    return sorted(outer), sorted(leaves)


def _close(entry, outer, leaves):
    iv, depth, holds = entry
    if not holds:
        leaves.append(iv)
    elif depth == 0:
        outer.append(iv)


def inside(spans, window) -> list:
    """The spans that lie wholly inside ``window``."""
    return [[s, e] for s, e in spans if window[0] <= s and e <= window[1]]


def covered_ns(intervals) -> float:
    return sum(e - s for s, e in _merge(intervals))


def uncovered_ns(span, leaves) -> float:
    """The part of ``span`` that no leaf op (sorted by start) covers."""
    s, e = span
    k = bisect.bisect_left(leaves, [s])
    within = []
    while k < len(leaves) and leaves[k][0] < e:
        within.append([leaves[k][0], min(leaves[k][1], e)])
        k += 1
    return (e - s) - covered_ns(within)


def loop_idle_us(trace: dict, per_program: int):
    """Device time per application, averaged over the chips, that no leaf op
    covers inside the outermost op holding others (the loop's ``while``) of
    each whole program execution in the window: the in-loop gaps, which the
    busy time of ``trace.reduce`` counts as busy."""
    readings = []
    for ops in trace["devices"].values():
        outer, leaves = nesting(ops)
        loops = inside(outer, trace["window"])
        if loops:
            idle = sum(uncovered_ns(loop, leaves) for loop in loops)
            readings.append(1e-3 * idle / (len(loops) * per_program))
    return statistics.fmean(readings) if readings else None


def scope_readings(trace: dict, scopes: dict, programs: dict, per_program: int,
                   is_collective) -> dict:
    """Per scope, averaged over the chips: device us per application of
    the programs that run it (union of its ops' intervals), and collective
    ops per application; under ``"*"``, every scoped op per application of
    every whole program in the window.  A program runs the scope of its
    first scoped op."""
    per_chip = []
    for chip, ops in trace["devices"].items():
        spans = inside(programs.get(chip, []), trace["window"])
        if not spans:
            continue
        starts = [s for s, _ in spans]
        held = [[] for _ in spans]  # each program's scoped ops
        for (name, s, d), k in zip(ops, scopes["ops"][chip]):
            p = bisect.bisect_right(starts, s) - 1
            if k and p >= 0 and s + d <= spans[p][1]:
                held[p].append((name, s, s + d, scopes["names"][k]))
        runs, busy, collectives, every = {}, {}, {}, []
        for scoped in filter(None, held):
            scope = scoped[0][3]
            runs[scope] = runs.get(scope, 0) + 1
            for name, s, e, sc in scoped:
                busy.setdefault(sc, []).append([s, e])
                every.append([s, e])
                collectives[sc] = collectives.get(sc, 0) + bool(is_collective(name))
        row = {sc: {"device_us": 1e-3 * covered_ns(busy.get(sc, [])) / (n * per_program),
                    "collective_ops": collectives.get(sc, 0) / (n * per_program)}
               for sc, n in runs.items()}
        row["*"] = {"device_us": 1e-3 * covered_ns(every) / (len(spans) * per_program)}
        per_chip.append(row)
    out = {}
    for sc in sorted({sc for row in per_chip for sc in row}):
        rows = [row[sc] for row in per_chip if sc in row]
        out[sc] = {k: statistics.fmean(r[k] for r in rows) for k in rows[0]}
    return out


def schedule_device_us(trace: dict, scopes: dict, programs: dict, per_program: int):
    """Device us per application, averaged over the chips, of the union of
    the ops under any ``schedules.*`` scope in whole programs in the window;
    None where no op carries a scope (a program built without them)."""
    if len(scopes["names"]) < 2:
        return None
    reading = scope_readings(trace, scopes, programs, per_program, lambda _: False).get("*")
    return None if reading is None else reading["device_us"]


def call_split(trace: dict, programs: dict, clock: dict):
    """Each ``bench.call`` on the aligned clock: launch (host start to its
    program's aligned start), program, return (aligned end to host end) and
    between calls (the window's host time outside every call, per call),
    in us, averaged over the calls and chips; ``error_us`` is the widest
    chip's offset bound.  None where no chip has an offset."""
    calls = sorted((s, s + d) for name, s, d in trace["host"] if name == CALL_SPAN)
    if not calls:
        return None
    starts = [s for s, _ in calls]
    w0, w1 = trace["window"]
    between = ((w1 - w0) - sum(e - s for s, e in calls)) / len(calls)
    parts, errors = {"launch": [], "program": [], "return": []}, []
    for chip, spans in programs.items():
        off = clock.get(chip, {}).get("offset_ns")
        if off is None:
            continue
        errors.append(clock[chip]["error_ns"])
        for s, e in spans:
            a, b = s + off, e + off
            k = bisect.bisect_right(starts, (a + b) / 2) - 1
            if k < 0 or (a + b) / 2 > calls[k][1]:
                continue
            parts["launch"].append(a - calls[k][0])
            parts["program"].append(b - a)
            parts["return"].append(calls[k][1] - b)
    if not parts["launch"]:
        return None
    out = {k: 1e-3 * statistics.fmean(v) for k, v in parts.items()}
    out.update(between=1e-3 * between, calls=len(calls), matched=len(parts["launch"]),
               error_us=1e-3 * max(errors))
    return out
