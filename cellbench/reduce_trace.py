"""xplane -> device busy / idle, time per XLA module, the longest gaps.

``load`` flattens a ``jax.profiler`` ``.xplane.pb`` into plain rows
``[plane, line, name, start_ns, duration_ns]``; ``reduce`` works on such
rows alone, so it is tested on a small recorded list kept beside the
tests.  Device planes are ``/device:TPU:<n>``; on each, the ``XLA Ops``
line holds the operations (busy = the union of their intervals) and the
``XLA Modules`` line one event per executed program."""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def load(path: str):
    from jax.profiler import ProfileData

    rows = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                rows.append([plane.name, line.name, ev.name,
                             int(ev.start_ns), int(ev.duration_ns)])
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


def reduce(rows, window_s: float, top: int = 10):
    """Returns None where no device plane holds an operation, else a dict:
    ``busy_s`` (mean over device planes of the union of op intervals),
    ``window_s``, ``idle_pct``, ``modules`` {name: [seconds, calls]} summed
    over planes, ``device_ops`` and ``idle_gaps`` (top lists)."""
    planes = {}
    host_rows = []
    for plane, line, name, start, dur in rows:
        if DEVICE_PLANE.match(plane):
            planes.setdefault(plane, {}).setdefault(line, []).append(
                (start, start + dur, name))
        elif plane.startswith("/host:") and dur > 0:
            host_rows.append((name, start, start + dur))
    busy, modules, ops, gaps = [], {}, {}, []
    for lines in planes.values():
        op_rows = lines.get(OPS_LINE) or [
            r for ln, rs in lines.items() if ln != MODULES_LINE for r in rs]
        if not op_rows:
            continue
        merged = union(op_rows)
        busy.append(sum(e - s for s, e, _n in merged))
        for s, e, name in op_rows:
            ops[name] = ops.get(name, 0) + (e - s)
        for s, e, name in lines.get(MODULES_LINE, []):
            m = modules.setdefault(name, [0, 0])
            m[0] += e - s
            m[1] += 1
        for a, b in zip(merged, merged[1:]):
            gaps.append((b[0] - a[1], a[1], b[0], a[2]))
    if not busy or sum(busy) <= 0:
        return None
    busy_s = sum(busy) / len(busy) / 1e9
    gaps.sort(reverse=True)
    named = {}
    for length, s, e, prev in gaps[:top]:
        host = _host_name(host_rows, s, e)
        key = f"host:{host}" if host else f"after:{prev}"
        named[key] = named.get(key, 0) + length
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
    }


def module_seconds(reduced, pattern: str):
    """Seconds (per chip) and calls of the modules whose name contains
    ``pattern``; (0.0, 0) where none ran."""
    secs = calls = 0
    for name, (s, c) in reduced["modules"].items():
        if pattern in name:
            secs, calls = secs + s, calls + c
    return secs, calls

