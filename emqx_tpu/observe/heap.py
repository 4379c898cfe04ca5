"""The subscription table leaves the cyclic collector's reach.

A broker with a million subscriptions holds millions of containers that
live as long as the node: the router's maps and destination sets, the
trie, the sessions' subscription maps, the mirror's books.  CPython's
full collection walks every one of them each time the old generation has
grown by a quarter, and stops the node's only event loop for seconds
while it does (PERF.md §2, §6 PR 35), to find no garbage: the trie and
the router hold no back pointers, so an unsubscribed filter's nodes die
by reference count.

``gc.freeze()`` moves everything the collector tracks into its permanent
generation, which no pass walks.  Reference counting frees a frozen
object exactly as before; only the cyclic pass stops looking at it.  The
two moments the code can observe at which what is alive is table, not
traffic:

* :func:`grown` — the router reports, where a filter is new, how many
  routes its table holds and how many it held when it last froze; each
  time the table stands ``GROWTH_STEP`` routes above that mark, freeze,
  so that a bulk load never meets a full pass (a freeze also zeroes the
  generations' counts, and the third threshold is then not met before
  the next step).  The mark is the ROUTER's and only rises:
  subscriptions that come and go never reach a step, and a table
  freezes ``peak // GROWTH_STEP`` times in its life at most;
* :func:`settled` — a whole table has been uploaded to the device by
  ``MatchService._sync_loop`` (the first sync, a growth re-upload).

**The full pass keeps its cadence; only its length changes.**  CPython
runs one when the old generation has grown by a quarter of what survived
the last one; after a freeze that is a quarter of the UNFROZEN heap only,
which would trade one long stop for a row of short ones.  So a settle
sets the third threshold to ``frozen // (4 * t0 * t1)``: one full pass a
quarter-heap of net new containers, the frozen ones counted, as
CPython's own rule had it before the freeze.  (A growth freeze does not:
reading the frozen count is itself a walk over the frozen objects, and
while a table grows step by step each freeze zeroes the counts long
before any third threshold is met.)  A heap of under 280,000 frozen
objects keeps CPython's 10.  The two young thresholds are passed back as
read.  Neither half stands alone, and this module is the one place
either call appears.

What a freeze costs: a CYCLE that is alive when it happens and dies
later is never collected — a connection open at a freeze and closed
afterwards leaves its protocol ↔ channel cycle behind (41 containers,
≈ 5 KB: CPU, PR 35; the other 75 of its objects die by reference count).
The bound is the connections alive at a freeze, times the freezes, and
the freezes are bounded by the table: ``peak // GROWTH_STEP`` growth
freezes and one settle a pow2 shape of the device's table.  That is why
the compaction swap (``match.segments.enable``, a whole table every
``compact_interval`` under churn) and the shard rebuild do NOT settle:
they recur for as long as the node runs.  ``runtime.gc.frozen_objects``
and the two counters show it.  The collector and its thresholds belong
to the process, so the counts here do too: every node of a process
reports the same.

**The collector's account.**  One ``gc.callbacks`` entry, appended the
first time a node keeps the account (:func:`keep_account`) and kept for
the life of the process, times every collection on ``perf_counter_ns``:
running totals ``runtime.gc.pause_ns`` / ``runtime.gc.collections``
(``set`` into the table of every node that keeps the account; readers
take deltas) and one ``obs.stage.gc_pause`` sample each.  Collections
never overlap, so the callback is the only writer of both at any moment,
whichever thread collects; that is also why it feeds no flight-recorder
ring (a ring has one writer thread).
"""

from __future__ import annotations

import gc
import logging
import time
from typing import Any, Dict, Optional

__all__ = ["GROWTH_STEP", "grown", "settled", "report", "keep_account",
           "drop_account"]

log = logging.getLogger(__name__)

#: routes between two freezes while the table grows.  At 8,192 the
#: cell's 1.6M-subscribe set-up met no generation-2 pass at all, at
#: 65,536 it met 73 short ones (CPU copy runs, PERF.md §6 PR 35).
GROWTH_STEP = 8192

_freezes = {"growth": 0, "settled": 0}
_frozen = 0         # the permanent generation as the last settle counted it

# the collector's account: the process's totals since the callback went
# in, and the (metrics, histogram) of every node keeping it (a tuple,
# rebound whole, so the callback reads it in one load on any thread)
_pause_ns = 0
_collections = 0
_gc_start = 0
_accounts: tuple = ()


def _on_gc(phase: str, _info: Dict[str, int]) -> None:
    global _gc_start, _pause_ns, _collections
    if phase == "start":
        _gc_start = time.perf_counter_ns()
        return
    dur = time.perf_counter_ns() - _gc_start
    _pause_ns += dur
    _collections += 1
    for metrics, hist in _accounts:
        metrics.set("runtime.gc.pause_ns", _pause_ns)
        metrics.set("runtime.gc.collections", _collections)
        if hist is not None:
            hist.record(dur)


def keep_account(metrics: Any, hist: Optional[Any] = None) -> None:
    """From now on every collection of the process is written into
    ``metrics`` (the two totals) and ``hist`` (one pause each, where a
    histogram is given), until :func:`drop_account`."""
    global _accounts
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    metrics.set("runtime.gc.pause_ns", _pause_ns)
    metrics.set("runtime.gc.collections", _collections)
    _accounts = _accounts + ((metrics, hist),)


def drop_account(metrics: Any) -> None:
    global _accounts
    _accounts = tuple(a for a in _accounts if a[0] is not metrics)


def grown(size: int, mark: int) -> int:
    """A table holds ``size`` routes and held ``mark`` when it last
    froze (0: never).  Returns the mark to keep."""
    if size - mark < GROWTH_STEP:
        return mark
    gc.freeze()
    _freezes["growth"] += 1
    return size


def settled(why: str) -> None:
    """A whole table has landed on the device: what is alive now lives
    as long as the table does."""
    global _frozen
    gc.freeze()
    _freezes["settled"] += 1
    _frozen = gc.get_freeze_count()
    t0, t1, _ = gc.get_threshold()
    gc.set_threshold(t0, t1, max(10, _frozen // max(1, 4 * t0 * t1)))
    log.debug("heap settled (%s): %d objects frozen, thresholds %s",
              why, _frozen, gc.get_threshold())


def report(metrics: Any = None) -> Dict[str, Any]:
    """The process's counts, for ``BrokerNode.info()``; written into
    ``metrics`` as well where a table is given (housekeeping samples
    them every second, so ``frozen_objects`` is the count the last
    settle read, not a new walk)."""
    if metrics is not None:
        metrics.set("runtime.gc.freezes.growth", _freezes["growth"])
        metrics.set("runtime.gc.freezes.settled", _freezes["settled"])
        metrics.set("runtime.gc.frozen_objects", _frozen)
    return {"freezes": dict(_freezes), "frozen_objects": _frozen,
            "thresholds": list(gc.get_threshold())}
