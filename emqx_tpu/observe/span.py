"""One way to record a stage span: a handle over the stage's histogram
and its plane's flight-recorder ring, fed from one pair of stamps.

A recording site used to stamp ``perf_counter_ns()`` twice and then
call ``hist.record(dur)`` and ``ring.push(sid, start, dur, ...)`` side
by side, each behind its own ``is not None`` test.  :func:`stage_span`
resolves both sinks ONCE at set-up from the stage's name (``STAGES``
gives the ring's packed id, ``obs.stage.<stage>`` the histogram) and
the site keeps the one handle::

    self._sp_x = stage_span("match_x", hists, ring)     # set-up
    ...
    sp = self._sp_x                                     # hot path
    if sp is not None:
        sp.rec(t0, t1, batch=n, gen=gen, seq=seq)

The zero-cost-when-off idiom holds: with no histogram set
(``obs.hist.enable = false``) and no ring, :func:`stage_span` returns
``None`` and the site pays one identity test; a per-batch site then
feeds the always-on ring alone.  A stage that is recorded per publish
(or per waiter) feeds no ring, so its site needs no second sink and
holds the histogram itself (``hists.hist("obs.stage.x")``,
``h.record(t1 - t0)``): one call less, a dozen times a publish, on a
loop where that is measurable (PERF.md §6, PR 26).

Both stamps are ``time.perf_counter_ns()``: the clock of every ring
event, of the ``t_ns`` the ``emqx.match.*`` profiler annotations carry,
and of the ``clock`` entry of a flight-recorder dump.

Single-writer discipline is the caller's, as for the sinks themselves:
one thread per histogram instance and per ring.
"""

from __future__ import annotations

from typing import Optional

from .flightrec import STAGES, Ring
from .hist import HistSet, LatencyHistogram

__all__ = ["Span", "stage_span"]


class Span:
    """A stage's histogram and/or ring behind one ``rec`` call."""

    __slots__ = ("hist", "ring", "sid")

    def __init__(self, hist: Optional[LatencyHistogram],
                 ring: Optional[Ring], sid: int) -> None:
        self.hist = hist
        self.ring = ring
        self.sid = sid

    def rec(self, start_ns: int, end_ns: int, batch: int = 0,
            gen: int = 0, seq: int = 0) -> None:
        dur = end_ns - start_ns
        if self.hist is not None:
            self.hist.record(dur)
        if self.ring is not None:
            self.ring.push(self.sid, start_ns, dur, batch, gen, seq)


def stage_span(stage: str, hists: Optional[HistSet] = None,
               ring: Optional[Ring] = None) -> Optional[Span]:
    """The handle of ``stage`` over ``hists`` and ``ring``, ``None``
    where both are off.  An unknown stage raises here, at set-up (and
    the staticcheck ``registry-drift`` rule checks the literal)."""
    sid = STAGES.index(stage)
    if hists is None and ring is None:
        return None
    hist = hists.hist("obs.stage." + stage) if hists is not None else None
    return Span(hist, ring, sid)
