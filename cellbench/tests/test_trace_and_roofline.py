"""reduce_trace.py on a small trace recorded on the chip (PR 25, the
first 6 ``nfa_match`` programs of a wild1m slice), and the roofline byte
count on a three-level hand example."""

import json
import os

import pytest

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


# ---------------------------------------------------------------------------
# the idle time by batcher stage, on rows built by hand
# ---------------------------------------------------------------------------

US = 1_000
HOST, LOOP, WORKER = "/host:CPU", "python3", "asyncio_0"
T_NS_OFF = 7_000_000_000_000    # the stats' t_ns clock minus the trace's


def served_rows(shift_ns, batches=60, astray=0, seed=5, stage_events=True):
    """``batches`` cycles of the batcher in ``trace_rows.json``'s format:
    window (varies), encode 200 us, dispatch 640, hops 300, readback 940,
    hop back 180, epilogue 250 + 50 + 150.  Each cycle's program runs 310
    us (two operations, 10 us apart) in the middle of launch (300 us into
    the dispatch) -> readback end on the host's clock; the device plane
    reads ``shift_ns`` more.  The first ``astray`` programs come 3 ms late.  Returns the rows
    and what the host was doing while the device stood idle, in ns."""
    import random

    rnd = random.Random(seed)
    rows, idle = [], {}
    t = 1_000_000

    def host(stage, start, dur, seq, line=WORKER):
        if stage_events:
            rows.append([HOST, line, f"emqx.match.{stage}", start, dur,
                         {"seq": seq, "n": 7, "t_ns": start + T_NS_OFF}])
        return start + dur

    def add(name, ns):
        idle[name] = idle.get(name, 0) + ns

    end = None
    for seq in range(batches):
        if end is not None:
            window = rnd.randrange(1_500, 6_000) * US
            add("window", window)
            t = end + window
        enc_end = host("encode", t, 200 * US, seq)
        d0 = enc_end + 2 * US
        add("encode", 202 * US)
        d1 = host("dispatch", d0, 640 * US, seq)
        r0 = d1 + 300 * US
        r1 = host("readback", r0, 940 * US, seq)
        e0 = r1 + 180 * US
        add("hop_back", 180 * US)
        e1 = host("epilogue", e0, 250 * US, seq, LOOP)
        end = host("epilogue", e1 + 50 * US, 150 * US, seq, LOOP)
        add("epilogue", 450 * US)
        # the program, centred in [launch, r1): 935 us after d0, so it
        # covers the last 5 us of the hops and the first 305 of the
        # readback (the 10 us between its two operations lie in there)
        launch = d0 + 300 * US
        m0 = launch + (r1 - launch - 310 * US) // 2
        assert m0 == d0 + 935 * US
        if seq < astray:
            m0 += 3_000 * US
        else:
            add("dispatch", 640 * US)
            add("hops", 295 * US)
            add("readback", (940 - 305 + 10) * US)
        dev = m0 + shift_ns
        rows.append(["/device:TPU:0", "XLA Modules",
                     f"jit__nfa_match_packed({seq})", dev, 310 * US])
        rows.append(["/device:TPU:0", "XLA Ops", "%fusion.1", dev,
                     150 * US])
        rows.append(["/device:TPU:0", "XLA Ops", "%fusion.2",
                     dev + 160 * US, 150 * US])
        rows.append([HOST, WORKER, "tpu::System::Execute", launch,
                     200 * US])
    return rows, idle


@pytest.mark.parametrize("shift_ms", [-1.2, 0.04])
def test_idle_time_is_split_by_stage_on_a_measured_clock(shift_ms):
    rows, idle = served_rows(int(shift_ms * 1e6))
    stage = [r for r in rows if r[2].startswith("emqx.match.")]
    first, last = stage[0][3], stage[-1][3] + stage[-1][4]
    # the slice: 5 ms before the first batch to 4 ms after the last
    lo, hi = first - 5_000 * US, last + 4_000 * US
    out = RT.reduce(rows, (hi - lo) / 1e9, served=["nfa_match"],
                    slice_ns=(lo + T_NS_OFF, hi + T_NS_OFF))
    assert out["idle_note"] is None
    assert abs(out["device_clock_shift_ms"] - shift_ms) < 0.1
    assert out["modules_in_place_pct"] == 100.0
    got = dict(out["idle_gaps"])
    assert set(got) == {f"stage:{k}" for k in idle} | {"stage:no_batch"}
    assert out["idle_gaps"][0][0] == "stage:window"
    for name, ns in idle.items():
        assert abs(got[f"stage:{name}"] - ns / 1e9) < 1e-6, name
    assert abs(got["stage:no_batch"] - 9e-3) < 1e-9
    assert abs(sum(got.values()) + out["busy_s"] - out["window_s"]) < 1e-9
    # what the reducer read before, it reads the same
    assert abs(out["busy_s"] - 60 * 300e-6) < 1e-12
    secs, calls = RT.module_seconds(out, "nfa_match")
    assert calls == 60 and abs(secs - 60 * 310e-6) < 1e-12


def test_without_a_slice_the_split_runs_from_first_to_last_event():
    rows, idle = served_rows(-1_200_000)
    out = RT.reduce(rows, 1.0)          # every module is a served one
    got = dict(out["idle_gaps"])
    assert "stage:no_batch" not in got
    assert abs(sum(got.values()) - sum(idle.values()) / 1e9) < 1e-6
    assert abs(out["device_clock_shift_ms"] + 1.2) < 0.1


def test_without_the_runtimes_launch_event_the_dispatch_start_stands_in():
    rows = [r for r in served_rows(-1_200_000)[0] if r[2] != RT.LAUNCH]
    out = RT.reduce(rows, 1.0, served=["nfa_match"])
    # the allowed interval opens 300 us earlier, its middle lies 150 lower
    assert abs(out["device_clock_shift_ms"] - (-1.2 + 0.15)) < 1e-6
    assert out["idle_note"] is None and out["modules_in_place_pct"] == 100.0


@pytest.mark.parametrize("trace, says", [
    (served_rows(-1_200_000, astray=6)[0], "90.0 % of the served programs"),
    (served_rows(-1_200_000, stage_events=False)[0], "no emqx.match.* event"),
    (rows(), "no emqx.match.* event"),
    ([r for r in served_rows(0)[0] if r[1] != "XLA Modules"],
     "no served program"),
], ids=["a_tenth_astray", "no_stage_events", "recorded_before_the_spans",
        "no_module"])
def test_a_trace_that_cannot_be_split_falls_back_and_says_so(trace, says):
    out = RT.reduce(trace, 2.0, served=["nfa_match"])
    assert says in out["idle_note"]
    assert out["idle_gaps"] and all(
        name.startswith(("host:", "after:")) for name, _s in out["idle_gaps"])
    if "90.0" in says:
        # the shift is still what most programs agree on
        assert abs(out["device_clock_shift_ms"] + 1.2) < 0.1
    else:
        assert out["device_clock_shift_ms"] is None


def test_timeline_names_the_time_between_stage_events():
    bounds, names = RT.timeline([
        (50, 60, "epilogue"), (0, 10, "encode"), (12, 20, "dispatch"),
        (30, 40, "readback"), (44, 50, "epilogue"), (70, 80, "encode"),
        (75, 90, "dispatch"), (95, 99, "epilogue")])
    assert bounds == [0, 10, 12, 20, 30, 40, 44, 50, 60, 70, 80, 90, 95, 99]
    assert names == ["encode", "encode", "dispatch", "hops", "readback",
                     "hop_back", "epilogue", "epilogue", "window", "encode",
                     "dispatch", "other", "epilogue"]


def test_clock_shift_prefers_the_shift_most_programs_allow():
    # calls every 10 ms but one; programs 1 ms long, stamped 4 ms early
    calls = [(t, t + 3_000_000) for t in range(0, 90_000_000, 10_000_000)
             if t != 30_000_000]
    modules = [(d + 1_000_000 - 4_000_000, d + 2_000_000 - 4_000_000)
               for d, _r in calls]
    shift, in_place = RT.clock_shift(modules, calls)
    assert (shift, in_place) == (-4_000_000, 1.0)
    assert RT.clock_shift(modules, []) == (None, 0.0)
