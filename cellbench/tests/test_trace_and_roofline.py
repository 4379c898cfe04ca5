"""reduce_trace.py on a small trace recorded on the chip (PR 25, the
first 6 ``nfa_match`` programs of a wild1m slice), and the roofline byte
count on a three-level hand example."""

import json
import os

from cellbench import reduce_trace as RT
from cellbench import rooflines as ROOF

HERE = os.path.dirname(os.path.abspath(__file__))


def rows():
    with open(os.path.join(HERE, "trace_rows.json")) as f:
        return json.load(f)


def test_recorded_trace():
    r = rows()
    ops = [(x[3], x[3] + x[4]) for x in r
           if x[0] == "/device:TPU:0" and x[1] == "XLA Ops"]
    # busy by brute force: every nanosecond boundary, counted once
    marks = sorted({t for iv in ops for t in iv})
    busy = sum(b - a for a, b in zip(marks, marks[1:])
               if any(s <= a and b <= e for s, e in ops))
    span = (max(e for _s, e in ops) - min(s for s, _e in ops)) / 1e9
    out = RT.reduce(r, window_s=2.0)
    assert abs(out["busy_s"] - busy / 1e9) < 1e-9
    assert 0 < out["busy_s"] <= span < 2.0
    assert abs(out["idle_pct"] - 100 * (1 - out["busy_s"] / 2.0)) < 1e-9
    secs, calls = RT.module_seconds(out, "nfa_match")
    assert calls == 6 and 0.015 < secs < 0.025
    assert RT.module_seconds(out, "no_such_program") == (0, 0)
    assert len(out["device_ops"]) == 10 and out["idle_gaps"]
    assert all(len(name) <= 96 for name, _s in out["device_ops"])


def test_no_device_plane_reads_nothing():
    host_only = [x for x in rows() if x[0].startswith("/host:")]
    assert RT.reduce(host_only, window_s=1.0) is None


def test_union():
    assert RT.union([(0, 10, "a"), (5, 12, "b"), (20, 30, "c"),
                     (22, 25, "d")]) == [[0, 12, "b"], [20, 30, "c"]]


def test_level_walk_bytes_hand_example():
    # one topic of three levels, 16 active slots: at each level every slot
    # reads one 16 B node row and probes two 32 B edge buckets
    assert ROOF.level_walk_bytes(["a/b/c"], 16) == 3 * 16 * (16 + 2 * 32)
    assert ROOF.level_walk_bytes(["a", "a/b"], 4) == (1 + 2) * 4 * 80
    peak = {"hbm_bytes_per_s": 819e9}
    # 819 MB needed in 10 ms = a tenth of what the chip could move
    assert abs(ROOF.roofline_pct(819_000_000, 0.010, peak) - 10.0) < 1e-9
    assert ROOF.roofline_pct(0, 0.010, peak) is None
    assert ROOF.roofline_pct(100, 0.0, peak) is None
