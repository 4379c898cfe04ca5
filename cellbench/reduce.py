"""From raw arrays to numbers: the benchmark's own arithmetic.

The generator returns per-publish ``due``/``sent``/``acked`` and
per-delivery ``(subscriber, seq, received)``; the node's histograms are
read as bucket counts before and after the window.  Everything that
turns those into a metric is here, so that no change to the program can
change how a number is computed; the one thing taken from the program is
the histograms' bucket layout, the format of its counts."""

from __future__ import annotations

import math

import numpy as np

# The bucket layout has one owner, the module that writes the counts (no
# JAX behind this import).  tests/test_stage_spans.py holds the export to
# 16 sub-buckets an octave and 688 buckets, and reads it as ``_SUB_BITS``
# and ``bucket_bounds`` from here.
from emqx_tpu.observe.hist import SUB_BITS as _SUB_BITS     # noqa: F401
from emqx_tpu.observe.hist import bucket_bounds


def percentile(arrived_sorted, n_total: int, q: float, missing: float):
    """Nearest-rank percentile ``q`` over ``n_total`` samples of which
    ``arrived_sorted`` came in; the rest are beyond every percentile and
    read ``missing``."""
    if n_total <= 0:
        return None
    rank = max(0, math.ceil(q / 100.0 * n_total) - 1)
    if rank < len(arrived_sorted):
        return float(arrived_sorted[rank])
    return float(missing)


def join_deliveries(seq, due_abs, expected, d_sub, d_seq, d_recv):
    """Match deliveries to what the reference expects.

    ``expected[i]`` lists the subscribers due a copy of publish ``i``
    (``seq[i]``).  Returns the latency (ns, from the due time) of every
    expected delivery that arrived, its receive time, and the counts of
    expected deliveries that never came and of deliveries nobody was due
    (a second copy counts as extra)."""
    index = {int(s): i for i, s in enumerate(seq)}
    want = {}
    for i, subs in enumerate(expected):
        for s in subs:
            want[(i, s)] = want.get((i, s), 0) + 1
    n_expected = sum(want.values())
    lat, recv = [], []
    extra = 0
    for s, q, t in zip(d_sub.tolist(), d_seq.tolist(), d_recv.tolist()):
        i = index.get(q)
        key = (i, s)
        left = want.get(key, 0)
        if i is None or left <= 0:
            extra += 1
            continue
        want[key] = left - 1
        lat.append(t - int(due_abs[i]))
        recv.append(t)
    missing = sum(want.values())
    return (np.asarray(lat, np.int64), np.asarray(recv, np.int64),
            n_expected, missing, extra)


def end_to_end(lat_ns, recv_ns, n_expected, t0, t1, missing_ns):
    """The end-to-end numbers of one window ``[t0, t1)`` (ns)."""
    ms = np.sort(lat_ns) / 1e6
    miss = missing_ns / 1e6
    in_window = int(((recv_ns >= t0) & (recv_ns < t1)).sum())
    return {
        "e2e_p50_ms": percentile(ms, n_expected, 50, miss),
        "e2e_p95_ms": percentile(ms, n_expected, 95, miss),
        "e2e_p99_ms": percentile(ms, n_expected, 99, miss),
        "delivered_msgs_per_s": in_window / ((t1 - t0) / 1e9),
    }


def series_stat(values_ns, n_total, stat: str, missing_ns):
    """``p50``/``p95``/``p99`` (ms) of a per-publish series in which
    ``len(values_ns)`` of ``n_total`` have a reading."""
    return percentile(np.sort(values_ns) / 1e6, n_total,
                      float(stat[1:]), missing_ns / 1e6)


# -- the node's fixed-bucket histograms (observe/hist.py layout) ---------
# The percentile of a DELTA of two snapshots is computed here: the
# program's own reader has no delta.


def hist_delta_stat(before, after, stat: str):
    """Percentile (ms) of the samples recorded between two snapshots of
    one histogram's counts, or None where there is none."""
    counts = [a - b for a, b in zip(after, before)]
    total = sum(counts)
    if total <= 0:
        return None
    rank = float(stat[1:]) / 100.0 * (total - 1)
    cum = 0
    for idx, c in enumerate(counts):
        if c <= 0:
            continue
        if cum + c > rank:
            lower, width = bucket_bounds(idx)
            frac = min(max((rank - cum + 0.5) / c, 0.0), 1.0)
            return (lower + width * frac) / 1e6
        cum += c
    return None
