"""The one general traffic generator: a mix is a data file of parameters
(``cellbench/traffic/<mix>.json``), this turns it into a schedule.

    arrivals        "poisson"   a Poisson process of the mix's rate with
                                the COUNT fixed to rate x seconds (sorted
                                uniform due times), so that every seed
                                offers the same amount of work
    publisher_pick  "uniform"   each publish on a uniformly drawn
                                connection
    topic_draw      "fresh"     a topic never published before in the run

A mix that names another value is refused: the PR that brings such a mix
brings the draw with it.
"""

from __future__ import annotations

import numpy as np


class Schedule:
    """``due`` (ns from the phase's start, sorted), ``pub`` (publisher),
    ``topics`` (distinct list) and ``topic`` (index into it)."""

    def __init__(self, due, pub, topics, topic) -> None:
        self.due, self.pub, self.topics, self.topic = due, pub, topics, topic

    def __len__(self) -> int:
        return len(self.due)

    def topic_of(self, i: int) -> str:
        return self.topics[self.topic[i]]


def _known(mix: dict) -> None:
    for key, only in (("arrivals", "poisson"), ("publisher_pick", "uniform"),
                      ("topic_draw", "fresh")):
        if mix[key] != only:
            raise ValueError(f"unknown {key} {mix[key]!r}")


def fresh_topics(rng, table, n: int, used: set):
    """``n`` distinct topics of the table's tree, none in ``used``;
    ``used`` is extended."""
    out = []
    while len(out) < n:
        for t in table.draw_topics(rng, max(1024, 2 * (n - len(out)))):
            if t not in used:
                used.add(t)
                out.append(t)
                if len(out) == n:
                    break
    return out


def volley(rng, mix: dict, table, size: int, n_pub: int,
           used: set) -> Schedule:
    """``size`` publishes due at time 0, one on each of ``size``
    connections, every topic one the device has to be asked about."""
    _known(mix)
    pub = (np.arange(size) % n_pub).astype(np.int32)
    topics = fresh_topics(rng, table, size, used)
    return Schedule(np.zeros(size, np.int64), pub, topics,
                    np.arange(size, dtype=np.int32))


def schedule(rng, mix: dict, table, rate: float, seconds: float,
             n_pub: int, used: set) -> Schedule:
    _known(mix)
    n = int(round(rate * seconds))
    t = np.sort(rng.random(n)) * seconds
    pub = rng.integers(0, n_pub, size=n).astype(np.int32)
    topics = fresh_topics(rng, table, n, used)
    return Schedule((t * 1e9).astype(np.int64), pub, topics,
                    rng.permutation(n).astype(np.int32))
