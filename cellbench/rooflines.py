"""Bytes the work NEEDS, whatever kernel does it, and the share of the
chip's peak that moving them in the measured time comes to.

The wildcard match of one topic is a level walk over the NFA table: at
each of the topic's levels every active state (at most
``tpu.active_slots`` wide) reads its node row and probes the edge hash
table, two buckets a probe.  The rows are the table's own layout
(``node_tab`` 4 x int32 = 16 B a row, ``edge_tab`` 8 x int32 = 32 B a
bucket, PERF.md §6 PR 22), so

    bytes(topic) = levels(topic) x active_slots x (16 + 2 x 32)

counted for the REAL topics of the traced slice (a padded batch row
needs nothing).  The match is bound by memory, not by arithmetic, so the
roofline is bytes over the HBM peak."""

from __future__ import annotations

NODE_ROW_BYTES = 16
EDGE_BUCKET_BYTES = 32
BUCKETS_PER_PROBE = 2


def level_walk_bytes(topics, active_slots: int) -> int:
    per_level = active_slots * (NODE_ROW_BYTES
                                + BUCKETS_PER_PROBE * EDGE_BUCKET_BYTES)
    return sum(t.count("/") + 1 for t in topics) * per_level


def roofline_pct(needed_bytes: int, seconds: float, peak: dict):
    """Least time the chip could take over the time it took, in %; None
    where no time was measured (never 0 for a share of a peak)."""
    if seconds <= 0 or needed_bytes <= 0:
        return None
    return 100.0 * (needed_bytes / peak["hbm_bytes_per_s"]) / seconds
