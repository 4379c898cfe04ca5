"""xplane -> device busy / idle, time per XLA module, and the device's
idle time by batcher stage.

``load`` flattens a ``jax.profiler`` ``.xplane.pb`` into plain rows
``[plane, line, name, start_ns, duration_ns]``; an ``emqx.match.<stage>``
event carries a sixth element, its stats (``seq``, ``n``, ``t_ns``).
``reduce`` works on such rows alone, so it is tested on small lists kept
beside the tests.  Device planes are ``/device:TPU:<n>``; on each, the
``XLA Ops`` line holds the operations (busy = the union of their
intervals) and the ``XLA Modules`` line one event per executed program.

The idle time is named on the HOST's clock, by what the batcher was doing
(``split_idle``): the program's ``emqx.match.encode / dispatch / readback
/ epilogue`` events and the time between them cut the slice into pieces,
and every gap between device operations is shared out over the pieces it
overlaps.  The device plane's clock is off the host planes' by a
millisecond or so, differently each session (PERF.md §3), so it is
measured first (``clock_shift``) from what has to hold: a served program
runs after its dispatch began (after the runtime's launch inside it, where
the trace has that event) and before its readback ended.  Where that
cannot be done the ten longest gaps are named as before, and a note says
so."""

from __future__ import annotations

import re
from bisect import bisect_left, bisect_right
from statistics import median

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
STAGE_PREFIX = "emqx.match."
# the runtime's own host event around the launch of a program (PJRT, at
# host_tracer_level 2): inside the dispatch stage, after the operands' puts
LAUNCH = "tpu::System::Execute"
NO_BATCH = "no_batch"
# what the time BETWEEN two consecutive stage events is called (a batch
# is encode, dispatch [, encode, dispatch ...], readback, epilogue,
# epilogue); any other succession is "other"
BETWEEN = {
    ("epilogue", "encode"): "window",       # batching window + hop out
    ("encode", "dispatch"): "encode",       # one worker call
    ("dispatch", "encode"): "dispatch",     # the next depth group
    ("dispatch", "readback"): "hops",       # hop back, gate, hop out
    ("readback", "epilogue"): "hop_back",
    ("epilogue", "epilogue"): "epilogue",   # rows stitched -> hints minted
}
SHIFT_SEARCH_NS = 20_000_000    # a dispatch this near a program may be its own
IN_PLACE_MIN = 0.95


def load(path: str):
    from jax.profiler import ProfileData

    rows = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                row = [plane.name, line.name, ev.name,
                       int(ev.start_ns), int(ev.duration_ns)]
                if ev.name.startswith(STAGE_PREFIX):
                    row.append({k: int(v) for k, v in ev.stats
                                if isinstance(v, (int, float))})
                rows.append(row)
    return rows


def union(intervals):
    """Merge ``(start, end, name)`` intervals; returns the merged list of
    ``[start, end, name_of_last_member]`` sorted by start."""
    out = []
    for s, e, name in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1], out[-1][2] = e, name
        else:
            out.append([s, e, name])
    return out


def _host_name(host_rows, s, e):
    """The host event that overlaps the gap ``[s, e)`` longest without
    spanning far beyond it; None where the host plane shows nothing."""
    best, best_ov = None, 0
    for name, hs, he in host_rows:
        ov = min(e, he) - max(s, hs)
        if ov > best_ov and (he - hs) <= 4 * (e - s):
            best, best_ov = name, ov
    return best


def longest_gaps(merged_planes, host_rows, top: int):
    """The ``top`` longest gaps between operations, each under the host
    event that overlaps it longest, on unshifted clocks: the outliers, not
    the idle time (the naming before PR 34, kept for a trace that
    ``split_idle`` cannot read)."""
    gaps = [(b[0] - a[1], a[1], b[0], a[2]) for merged in merged_planes
            for a, b in zip(merged, merged[1:])]
    named = {}
    for length, s, e, prev in sorted(gaps, reverse=True)[:top]:
        host = _host_name(host_rows, s, e)
        key = f"host:{host}" if host else f"after:{prev}"
        named[key] = named.get(key, 0) + length
    return named


def timeline(stages):
    """The host's time cut into named pieces.  ``stages`` are ``(start,
    end, stage)`` events; each keeps its own name and the time between two
    takes ``BETWEEN``'s.  Returns ``(bounds, names)``: piece ``i`` is
    ``[bounds[i], bounds[i + 1])``.  Where two events overlap (the
    pipelined loop) the earlier keeps its time."""
    bounds, names = [], []
    end = prev = None
    for s, e, stage in sorted(stages):
        if end is not None:
            if s > end:
                bounds.append(end)
                names.append(BETWEEN.get((prev, stage), "other"))
            s = max(s, end)
        if e <= s:
            continue
        bounds.append(s)
        names.append(stage)
        end, prev = e, stage
    bounds.append(end)
    return bounds, names


def clock_shift(modules, calls):
    """How far the device plane's clock is off the host planes', in ns
    (device reading minus host reading; negative: the device's stamps are
    early), and the share of ``modules`` that lie inside a call once it is
    taken off.

    ``modules`` are ``(start, end)`` of the served programs on the device's
    clock, ``calls`` ``(launch, readback end)`` on the host's.  A program
    runs inside its call, so each pair that can belong together allows an
    interval of shifts; the shift most programs allow names each program's
    call, and the answer is the median of those intervals' midpoints.  It
    is exact to the interval's half width less the launch's delay and the
    fetch (a few tenths of a millisecond), no more.  Returns ``(None,
    0.0)`` where nothing pairs."""
    starts = [d for d, _r in calls]
    allowed = []                                # (low, high, module)
    for i, (ms, me) in enumerate(modules):
        a = bisect_left(starts, ms - SHIFT_SEARCH_NS)
        b = bisect_right(starts, ms + SHIFT_SEARCH_NS)
        for d, r in calls[a:b]:
            if me - r <= ms - d:
                allowed.append((me - r, ms - d, i))
    if not allowed:
        return None, 0.0
    # sweep: the stretch of shifts inside the most intervals; of equals
    # the one nearest no shift at all
    marks = sorted([(lo, 0) for lo, _hi, _i in allowed]
                   + [(hi, 1) for _lo, hi, _i in allowed])
    depth, best, at = 0, (0, 0.0), 0.0
    for k, (x, closes) in enumerate(marks):
        if closes:
            depth -= 1
            continue
        depth += 1
        mid = (x + marks[k + 1][0]) / 2         # a close always follows
        if (depth, -abs(mid)) > best:
            best, at = (depth, -abs(mid)), mid
    shift = median((lo + hi) / 2 for lo, hi, _i in allowed if lo <= at <= hi)
    in_place = {i for lo, hi, i in allowed if lo <= shift <= hi}
    return shift, len(in_place) / len(modules)


def split_idle(merged_planes, bounds, names, lo, hi):
    """Every nanosecond of ``[lo, hi)`` in which a plane ran no operation,
    summed by the name of the timeline piece it falls in (``NO_BATCH``
    outside the timeline), as a mean over the planes.  All on one clock."""
    out = {}
    last = len(names)

    def share(a, b):
        i = bisect_right(bounds, a) - 1         # the piece that holds a
        while a < b:
            if i < 0:
                end, name = min(b, bounds[0]), NO_BATCH
            elif i >= last:
                end, name = b, NO_BATCH
            else:
                end, name = min(b, bounds[i + 1]), names[i]
            out[name] = out.get(name, 0) + end - a
            a, i = end, i + 1

    for merged in merged_planes:
        at = lo
        for s, e, _name in merged:
            if e <= at:
                continue
            if s >= hi:
                break
            if s > at:
                share(at, s)
            at = e
        if at < hi:
            share(at, hi)
    return {k: v / len(merged_planes) for k, v in out.items()}


def idle_by_stage(merged_planes, stages, launches, modules, slice_ns):
    """``({"stage:<name>": ns}, shift_ns, share in place, None)``, or
    ``(None, shift_ns, share, why not)``.  ``stages`` are the host planes'
    ``(start, end, stage, stats)``, ``launches`` the starts of their
    ``LAUNCH`` events, ``modules`` the served programs' ``(start, end)``
    on the device's clock, ``slice_ns`` the traced slice's ``(start,
    end)`` on the clock of the stats' ``t_ns`` (None: from the first event
    or operation to the last)."""
    if not stages:
        return None, None, 0.0, (
            f"no {STAGE_PREFIX}* event on the host planes")
    stages = sorted(stages, key=lambda st: st[:2])
    fetched = [(s, e) for s, e, stage, _st in stages if stage == "readback"]
    fetch_starts = [s for s, _e in fetched]
    launches = sorted(launches)
    calls = []
    for s, e, stage, _st in stages:
        if stage == "dispatch":
            k = bisect_left(fetch_starts, e)    # the readback that follows
            if k < len(fetched):
                j = bisect_right(launches, e) - 1       # the launch inside
                if j >= 0 and launches[j] >= s:
                    s = launches[j]
                calls.append((s, fetched[k][1]))
    shift, in_place = clock_shift(sorted(modules), calls)
    if shift is None:
        return None, None, 0.0, "no served program lies near a dispatch"
    if in_place < IN_PLACE_MIN:
        return None, shift, in_place, (
            f"only {100 * in_place:.1f} % of the served programs lie "
            "between their dispatch and the end of their readback after "
            f"a shift of {shift / 1e6:.3f} ms")
    bounds, names = timeline([st[:3] for st in stages])
    anchors = [s - st["t_ns"] for s, _e, _n, st in stages
               if st and "t_ns" in st]
    if slice_ns is not None and anchors:
        off = median(anchors)                   # trace clock - t_ns clock
        lo, hi = slice_ns[0] + off, slice_ns[1] + off
    else:
        ops = [x for merged in merged_planes for x in (
            merged[0][0] - shift, merged[-1][1] - shift)]
        lo, hi = min(ops + bounds[:1]), max(ops + bounds[-1:])
    # onto the device's clock: device reading = host reading + shift
    by_name = split_idle(merged_planes, [b + shift for b in bounds], names,
                         lo + shift, hi + shift)
    return ({f"stage:{k}": v for k, v in by_name.items()},
            shift, in_place, None)


def reduce(rows, window_s: float, top: int = 10, served=None,
           slice_ns=None):
    """Returns None where no device plane holds an operation, else a dict:
    ``busy_s`` (mean over device planes of the union of op intervals),
    ``window_s``, ``idle_pct``, ``modules`` {name: [seconds, calls]} summed
    over planes, ``device_ops`` (top list) and ``idle_gaps``: the whole
    idle time of the slice by batcher stage (``stage:*``; with ``busy_s``
    they add up to the slice), or, where the trace does not let that be
    done, the longest gaps by host event (``host:*`` / ``after:*``) with
    the reason under ``idle_note``.  ``device_clock_shift_ms`` and
    ``modules_in_place_pct`` say what ``clock_shift`` found.

    ``served`` lists the words by which a module is known as a served
    program (None: every module is one); ``slice_ns`` as in
    ``idle_by_stage``."""
    planes = {}
    host_rows, stages, launches = [], [], []
    for plane, line, name, start, dur, *stats in rows:
        if DEVICE_PLANE.match(plane):
            planes.setdefault(plane, {}).setdefault(line, []).append(
                (start, start + dur, name))
        elif plane.startswith("/host:") and dur > 0:
            host_rows.append((name, start, start + dur))
            if name.startswith(STAGE_PREFIX):
                stages.append((start, start + dur, name[len(STAGE_PREFIX):],
                               stats[0] if stats else None))
            elif name == LAUNCH:
                launches.append(start)
    busy, modules, ops = [], {}, {}
    merged_planes, served_runs = [], []
    for lines in planes.values():
        op_rows = lines.get(OPS_LINE) or [
            r for ln, rs in lines.items() if ln != MODULES_LINE for r in rs]
        if not op_rows:
            continue
        merged = union(op_rows)
        merged_planes.append(merged)
        busy.append(sum(e - s for s, e, _n in merged))
        for s, e, name in op_rows:
            ops[name] = ops.get(name, 0) + (e - s)
        for s, e, name in lines.get(MODULES_LINE, []):
            m = modules.setdefault(name, [0, 0])
            m[0] += e - s
            m[1] += 1
            if served is None or any(word in name for word in served):
                served_runs.append((s, e))
    if not busy or sum(busy) <= 0:
        return None
    busy_s = sum(busy) / len(busy) / 1e9
    named, shift, in_place, why_not = idle_by_stage(
        merged_planes, stages, launches, served_runs, slice_ns)
    if named is None:
        named = longest_gaps(merged_planes, host_rows, top)
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_pct": 100.0 * (1.0 - busy_s / window_s),
        "chips": len(busy),
        "modules": {k: [v[0] / 1e9 / len(busy), v[1]]
                    for k, v in modules.items()},
        "device_ops": [[k[:96], v / 1e9] for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v / 1e9] for k, v in sorted(
            named.items(), key=lambda kv: -kv[1])[:top]],
        "idle_note": why_not,
        "device_clock_shift_ms": None if shift is None else shift / 1e6,
        "modules_in_place_pct": 100.0 * in_place,
    }


def module_seconds(reduced, pattern: str):
    """Seconds (per chip) and calls of the modules whose name contains
    ``pattern``; (0.0, 0) where none ran."""
    secs = calls = 0
    for name, (s, c) in reduced["modules"].items():
        if pattern in name:
            secs, calls = secs + s, calls + c
    return secs, calls
