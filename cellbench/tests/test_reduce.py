"""The percentile and rate arithmetic on hand-made arrays."""

import numpy as np

from cellbench import reduce as R


def window(stall_at=None):
    """1,000 publishes over 10 s, one subscriber each, 5 ms latency; with
    ``stall_at`` the broker stops for 2 s and then delivers the backlog."""
    n, t0 = 1000, 10**12
    due = t0 + np.arange(n) * 10_000_000
    recv = due + 5_000_000
    if stall_at is not None:
        a, b = t0 + int(stall_at * 1e9), t0 + int((stall_at + 2) * 1e9)
        held = (due >= a) & (due < b)
        recv[held] = b + 1_000_000
    seq = np.arange(n)
    lat, rcv, n_exp, missing, extra = R.join_deliveries(
        seq, due, [[0]] * n, np.zeros(n, np.int64), seq, recv)
    return R.end_to_end(lat, rcv, n_exp, t0, t0 + 10 * 10**9, 60 * 10**9), \
        missing, extra


def test_steady_window():
    e, missing, extra = window()
    assert (missing, extra) == (0, 0)
    assert e["e2e_p50_ms"] == 5.0 and e["e2e_p95_ms"] == 5.0
    assert e["delivered_msgs_per_s"] == 100.0


def test_stall_in_the_window_moves_tail_and_leaves_median():
    e, _m, _x = window(stall_at=3.0)
    assert e["e2e_p50_ms"] == 5.0
    assert e["e2e_p95_ms"] > 1000.0


def test_stall_at_the_end_moves_the_rate():
    e, _m, _x = window(stall_at=8.5)
    assert e["delivered_msgs_per_s"] < 90.0


def test_missing_is_beyond_every_percentile_and_counted():
    n, t0 = 100, 10**12
    due = t0 + np.arange(n) * 10_000_000
    seq = np.arange(n)
    got = seq[:90]                      # the last ten never arrive
    lat, rcv, n_exp, missing, extra = R.join_deliveries(
        seq, due, [[0]] * n, np.zeros(90, np.int64), got, due[:90] + 10**6)
    assert (n_exp, missing, extra) == (100, 10, 0)
    e = R.end_to_end(lat, rcv, n_exp, t0, t0 + 10**9, 60 * 10**9)
    assert e["e2e_p50_ms"] == 1.0
    assert e["e2e_p95_ms"] == 60000.0


def test_second_copy_and_stranger_are_extra():
    due = np.asarray([10**12, 10**12 + 1])
    lat, _r, n_exp, missing, extra = R.join_deliveries(
        np.arange(2), due, [[0], []],
        np.asarray([0, 0, 1]), np.asarray([0, 0, 1]),
        np.asarray([due[0] + 5, due[0] + 6, due[1] + 5]))
    assert (n_exp, missing, extra, len(lat)) == (1, 0, 2, 1)


def test_hist_delta_is_the_windows_own_samples():
    before = [0] * 688
    after = [0] * 688
    # bucket 16*k+.. : 1 ms = 1e6 ns lies in octave 19 (524288..1048575)
    idx_1ms = ((19 - 4) << 4) + (1_000_000 >> 15)
    before[idx_1ms] = 50                # samples from before the window
    after[idx_1ms] = 50
    idx_8ms = ((22 - 4) << 4) + (8_000_000 >> 18)
    after[idx_8ms] = 100
    p50 = R.hist_delta_stat(before, after, "p50")
    lower, width = R.bucket_bounds(idx_8ms)
    assert lower <= p50 * 1e6 <= lower + width
    assert lower <= 8_000_000 < lower + width
    assert R.hist_delta_stat(before, before, "p50") is None
