"""Two-tier hot/cold match table: routing
correctness and merged-answer parity vs the host oracle, with the
pallas tier in interpret mode on the CPU mesh."""

import numpy as np
import pytest

from emqx_tpu import topic as T
from emqx_tpu.ops.tiered import (
    TieredMatcher, build_tiered, pick_hot_roots, route, split_filters,
)


def oracle(topics, filters):
    return [sorted(f for f in set(filters) if T.match(t, f))
            for t in topics]


FILTERS = [
    "hot1/+", "hot1/a/#", "hot1/x/y", "hot1/+/z",
    "hot2/devices/+/temp", "hot2/#",
    "cold1/a", "cold1/+/b", "cold2/#", "cold3/deep/+/x",
    "+/status", "#",                       # root wildcards: both tiers
    "$SYS/broker/uptime",
]
TOPICS = [
    "hot1/a", "hot1/a/b/c", "hot1/x/y", "hot1/q/z",
    "hot2/devices/d9/temp", "hot2/anything",
    "cold1/a", "cold1/x/b", "cold2/what/ever", "cold3/deep/k/x",
    "misc/status", "unrelated/topic",
    "$SYS/broker/uptime",
]


def test_split_filters_replicates_root_wildcards():
    hot, cold = split_filters(FILTERS, {"hot1", "hot2"})
    assert "+/status" in hot and "+/status" in cold
    assert "#" in hot and "#" in cold
    assert "hot1/+" in hot and "hot1/+" not in cold
    assert "cold1/a" in cold and "cold1/a" not in hot


def test_route_by_root():
    hot_idx, cold_idx = route(TOPICS, frozenset({"hot1", "hot2"}))
    assert sorted(hot_idx + cold_idx) == list(range(len(TOPICS)))
    assert all(TOPICS[i].split("/")[0] in ("hot1", "hot2")
               for i in hot_idx)


def test_pick_hot_roots_traffic_driven():
    counts = {"hot1": 100_000, "hot2": 50_000, "cold1": 3}
    picked = pick_hot_roots(FILTERS, counts)
    assert picked[:2] == ["hot1", "hot2"]
    # zero-traffic roots are not admitted
    assert "cold2" not in picked and "cold3" not in picked


def test_pick_hot_roots_respects_budget():
    counts = {"hot1": 100, "hot2": 50}
    picked = pick_hot_roots(FILTERS, counts, vmem_budget_bytes=16 * 10)
    # tiny budget: at most one root fits
    assert len(picked) <= 1


def test_tiered_matches_oracle_interpret():
    tiered = build_tiered(FILTERS, {"hot1", "hot2"}, depth=8)
    assert tiered.hot is not None
    tm = TieredMatcher(tiered, depth=8, interpret=True)
    got = tm.match(TOPICS)
    want = oracle(TOPICS, FILTERS)
    for t, g, w in zip(TOPICS, got, want):
        assert sorted(g) == w, (t, sorted(g), w)
    # routing actually split the work
    assert tm.hot_topics > 0 and tm.cold_topics > 0


def test_tiered_randomized_parity():
    rng = np.random.default_rng(9)
    roots = [f"r{i}" for i in range(12)]
    filters = sorted({
        rng.choice(roots + ["+"]) + "/"
        + "/".join(("+" if rng.random() < 0.3 else f"w{rng.integers(6)}")
                   for _ in range(rng.integers(1, 4)))
        + ("/#" if rng.random() < 0.25 else "")
        for _ in range(160)
    })
    counts = {r: (1000 if i < 4 else 0) for i, r in enumerate(roots)}
    hot_roots = pick_hot_roots(filters, counts, depth=8)
    assert hot_roots, "expected some hot roots"
    tiered = build_tiered(filters, hot_roots, depth=8)
    tm = TieredMatcher(tiered, depth=8, interpret=True)
    topics = [
        f"{rng.choice(roots)}/" + "/".join(
            f"w{rng.integers(6)}" for _ in range(rng.integers(1, 5)))
        for _ in range(64)
    ]
    got = tm.match(topics)
    want = oracle(topics, filters)
    for t, g, w in zip(topics, got, want):
        assert sorted(g) == w, (t, sorted(g), w)


def test_no_hot_roots_degenerates_to_cold_only():
    tiered = build_tiered(FILTERS, (), depth=8)
    assert tiered.hot is None
    tm = TieredMatcher(tiered, depth=8)
    got = tm.match(TOPICS)
    want = oracle(TOPICS, FILTERS)
    for g, w in zip(got, want):
        assert sorted(g) == w
    assert tm.hot_topics == 0


def test_build_demotes_until_vmem_fits(monkeypatch):
    """If the compiled hot tier exceeds the VMEM budget, roots demote
    until it fits (the pick estimate is advisory, the compile decides)."""
    import emqx_tpu.ops.pallas_match as pm

    calls = []
    real = pm.supports_table

    def tight(node_tab, edge_tab):
        calls.append(node_tab.shape[0])
        # reject anything holding both hot roots' filters
        return (node_tab.nbytes + edge_tab.nbytes) < 10_000 \
            and len(calls) > 1

    monkeypatch.setattr(pm, "supports_table", tight)
    tiered = build_tiered(FILTERS, ["hot1", "hot2"], depth=8)
    assert len(tiered.hot_roots) < 2
    # every filter is still matchable somewhere
    all_placed = set()
    if tiered.hot is not None:
        all_placed |= {f for f in tiered.hot.accept_filters if f}
    all_placed |= {f for f in tiered.cold.accept_filters if f}
    assert set(FILTERS) <= all_placed
