"""Table-lifetime objects leave the cyclic collector's reach
(``observe/heap.py``): when the freezes fire, what they leave for a full
pass to walk, what they cost in leaks, and the cadence the scaled third
threshold keeps.  Counts only; no timing is asserted.

``conftest.py`` thaws the heap and puts the thresholds back after every
test, so each case starts from CPython's own state.
"""

import asyncio
import gc
import weakref

import pytest

from emqx_tpu.broker import Broker, trie as trie_mod
from emqx_tpu.broker.match_service import MatchService
from emqx_tpu.broker.router import Router
from emqx_tpu.config import Config
from emqx_tpu.node import BrokerNode
from emqx_tpu.observe import heap
from emqx_tpu.observe.metrics import RUNTIME_METRIC_NAMES, Metrics


def freezes():
    return heap.report()["freezes"]


@pytest.fixture
def counted(monkeypatch):
    """``gc.freeze`` and ``gc.set_threshold`` as the module calls them."""
    calls = {"freeze": 0, "set_threshold": []}
    real_freeze, real_set = gc.freeze, gc.set_threshold

    def freeze():
        calls["freeze"] += 1
        real_freeze()

    def set_threshold(*a):
        calls["set_threshold"].append(a)
        real_set(*a)

    monkeypatch.setattr(heap.gc, "freeze", freeze)
    monkeypatch.setattr(heap.gc, "set_threshold", set_threshold)
    return calls


# -- call site 1: the router reports new routes ---------------------------

@pytest.mark.parametrize("step,routes", [(8, 8), (8, 30), (50, 49),
                                         (50, 500)])
def test_one_freeze_per_growth_step_of_new_routes(monkeypatch, counted,
                                                  step, routes):
    monkeypatch.setattr(heap, "GROWTH_STEP", step)
    r = Router()
    before = freezes()["growth"]
    for i in range(routes):
        r.add_route(f"t/{i}/+" if i % 2 else f"t/{i}", "d0")
    assert counted["freeze"] == routes // step
    assert freezes()["growth"] - before == routes // step


def test_resubscribes_of_a_known_filter_never_freeze(monkeypatch, counted):
    monkeypatch.setattr(heap, "GROWTH_STEP", 8)
    r = Router()
    for i in range(7):
        r.add_route(f"t/{i}/#", "d0")
    for d in range(200):            # new (filter, dest) pairs, no new filter
        assert r.add_route("t/3/#", f"d{d + 1}")
        assert not r.add_route("t/3/#", "d0")
    assert counted["freeze"] == 0
    r.add_route("t/7/#", "d0")      # the eighth NEW filter
    assert counted["freeze"] == 1


def test_routes_that_come_and_go_never_reach_a_step(monkeypatch, counted):
    """Growth is the table's size over the router's own mark, which only
    rises: what bounds the freezes is the table's peak, not its churn."""
    monkeypatch.setattr(heap, "GROWTH_STEP", 8)
    r = Router()
    for i in range(6):
        r.add_route(f"t/{i}/#", "d0")
    for i in range(500):            # a client's own filter, per session
        r.add_route(f"c/{i}/+", "d1")
        r.delete_route(f"c/{i}/+", "d1")
    assert counted["freeze"] == 0
    for i in range(6, 20):
        r.add_route(f"t/{i}/#", "d0")
    assert counted["freeze"] == 2   # at 8 and at 16
    for i in range(20):             # the table empties and comes back
        r.delete_route(f"t/{i}/#", "d0")
    for i in range(23):
        r.add_route(f"u/{i}/#", "d0")
    assert counted["freeze"] == 2   # 23 < the mark (16) + 8
    r.add_route("u/23/#", "d0")
    assert counted["freeze"] == 3


def test_each_router_counts_from_its_own_mark(monkeypatch, counted):
    """A router dropped with its routes in it (a node stopped, another
    started in the same process) leaves nothing behind in the module."""
    monkeypatch.setattr(heap, "GROWTH_STEP", 8)
    for life in range(3):
        r = Router()
        for i in range(7):
            r.add_route(f"t/{life}/{i}/#", "d0")
        assert counted["freeze"] == life
        r.add_route(f"t/{life}/7/#", "d0")
        assert counted["freeze"] == life + 1


def test_a_small_node_never_reaches_a_growth_step(counted):
    node = BrokerNode(Config())
    b = node.broker
    b.open_session("c")
    for i in range(2000):
        b.subscribe("c", f"site/{i}/+/temp")
    assert counted["freeze"] == 0
    assert gc.get_freeze_count() == 0
    assert gc.get_threshold()[2] == 10


# -- call site 2: a whole table has landed --------------------------------

def make_node(**extra):
    cfg = Config(file_text='listeners.tcp.default.bind = "127.0.0.1:0"\n')
    cfg.put("tpu.enable", True)     # the env layer turns it off for tests
    cfg.put("tpu.mirror_refresh_interval", 0.01)
    cfg.put("tpu.bypass_rate", 0.0)
    for k, v in extra.items():
        cfg.put(k, v)
    return BrokerNode(cfg)


async def settle(pred, timeout=60.0):
    end = asyncio.get_running_loop().time() + timeout
    while not pred() and asyncio.get_running_loop().time() < end:
        await asyncio.sleep(0.02)
    return pred()


def synced(node):
    ms = node.match_service
    return (ms is not None and ms.ready
            and ms._synced_epoch == node.broker.router.epoch
            and ms.dev.epoch == ms.inc.epoch)


def test_full_uploads_settle_once_each_and_a_delta_does_not(counted):
    async def main():
        node = make_node()
        b, m = node.broker, node.observed.metrics
        b.open_session("c")
        for i in range(20):
            b.subscribe("c", f"room/{i}/+")
        s0 = freezes()["settled"]
        await node.start()
        try:
            assert await settle(lambda: synced(node))
            # the first upload: one settle, made before ``ready``
            assert freezes()["settled"] - s0 == 1
            assert m.get("tpu.mirror.recompile") == 1
            assert gc.get_freeze_count() > 0

            b.subscribe("c", "room/one/more/+")
            assert await settle(
                lambda: synced(node)
                and m.get("tpu.mirror.delta_applied") >= 1)
            assert m.get("tpu.mirror.recompile") == 1
            assert freezes()["settled"] - s0 == 1     # a delta: none

            # grow past the table's shape: whole re-uploads
            for i in range(6000):
                b.subscribe("c", f"grow/{i}/+/x/#")
            assert await settle(
                lambda: synced(node)
                and m.get("tpu.mirror.recompile") >= 2)
            assert (freezes()["settled"] - s0
                    == m.get("tpu.mirror.recompile"))
            assert counted["freeze"] == freezes()["settled"] - s0
        finally:
            await node.stop()

    asyncio.run(main())


def test_compaction_swaps_do_not_freeze(tmp_path, counted):
    """A segment swap recurs for as long as subscriptions churn
    (``match.segments.enable``): it settles nothing, so clients that
    come and go leave the frozen count where the first upload put it."""
    async def main():
        b = Broker()
        b.open_session("c")
        for i in range(30):
            b.subscribe("c", f"room/+/k{i}")
        ms = MatchService(b, depth=8, table="python", bypass_rate=0.0,
                          segments=True, segments_dir=str(tmp_path),
                          compact_interval_s=0.05, compact_min_mutations=1,
                          metrics=Metrics())
        await ms.start()
        try:
            assert await settle(lambda: ms.ready)
            assert counted["freeze"] == 1           # the first upload
            frozen = gc.get_freeze_count()
            for life in range(3):
                gen = ms._table_gen
                b.open_session(f"r{life}")
                b.subscribe(f"r{life}", f"own/{life}/#")
                assert await settle(lambda: ms._table_gen > gen
                                    and ms.ready)
                b.close_session(f"r{life}", discard=True)
            assert ms.metrics.get("tpu.table.compact_runs") >= 3
            assert counted["freeze"] == 1
            assert gc.get_freeze_count() <= frozen  # some died by count
        finally:
            await ms.stop()

    asyncio.run(main())


def test_a_full_pass_after_the_settle_walks_none_of_the_table():
    def unfrozen_after(n):
        node = BrokerNode(Config())
        b = node.broker
        for s in range(10):
            b.open_session(f"bulk{s}")
        for i in range(n):
            b.subscribe(f"bulk{i % 10}", f"site/{i % 977}/dev{i}/+/temp")
        heap.settled("test")
        assert gc.collect() >= 0
        # one destination set and at least one trie node a filter
        assert gc.get_freeze_count() > 2 * n
        left = len(gc.get_objects())    # generations 0-2, not the frozen
        gc.unfreeze()
        return left, node

    small, _n1 = unfrozen_after(10_000)
    large, _n2 = unfrozen_after(50_000)
    # 40,000 more filters are > 80,000 more containers; none is unfrozen
    assert abs(large - small) < 1_000


# -- what a freeze costs --------------------------------------------------

def test_an_unsubscribed_filters_nodes_die_by_reference_count(monkeypatch):
    class Node(trie_mod._Node):
        __slots__ = ("__weakref__",)

    monkeypatch.setattr(trie_mod, "_Node", Node)
    node = BrokerNode(Config())
    b = node.broker
    b.open_session("c")
    b.subscribe("c", "keep/+/x")
    b.subscribe("c", "gone/+/deep/er/#")
    r = b.router
    refs = [weakref.ref(r._wild["gone/+/deep/er/#"])]
    n = r._trie._root.children["gone"]
    while True:
        refs.append(weakref.ref(n))
        if not n.children:
            break
        n = next(iter(n.children.values()))
    del n
    assert len(refs) == 6
    heap.settled("test")
    gc.disable()
    try:
        b.unsubscribe("c", "gone/+/deep/er/#")
        assert [ref() for ref in refs] == [None] * 6
        assert r._trie.match("keep/a/x") == ["keep/+/x"]
    finally:
        gc.enable()


def test_a_cycle_alive_at_a_freeze_is_the_leak():
    """The cost, stated: a cycle frozen alive is not collected when it
    dies later; one made after the freeze is."""
    class Conn:
        pass

    def pair():
        a, b = Conn(), Conn()
        a.peer, b.peer = b, a
        return weakref.ref(a)

    before = pair()
    heap.settled("test")
    after = pair()
    gc.collect()
    assert before() is not None
    assert after() is None
    gc.unfreeze()
    gc.collect()
    assert before() is None


# -- the cadence ----------------------------------------------------------

class Gen2:
    """Generation-2 passes, and the most the collector's three
    generations held at the end of one (the frozen are in none)."""

    def __init__(self):
        self.passes = 0
        self.walked_max = 0

    def __call__(self, phase, info):
        if phase == "stop" and info["generation"] == 2:
            self.passes += 1
            self.walked_max = max(self.walked_max, len(gc.get_objects()))


def arrivals(n):
    """``n`` long-lived tracked containers, passes counted meanwhile."""
    seen = Gen2()
    gc.callbacks.append(seen)
    try:
        kept = [[i] for i in range(n)]
    finally:
        gc.callbacks.remove(seen)
    return seen, kept


@pytest.mark.parametrize("young", [(700, 10), (100, 5)])
def test_half_a_heap_of_arrivals_meets_at_most_one_full_pass(young):
    n = 300_000
    table = [[i] for i in range(n)]
    gc.set_threshold(*young, 10)
    heap.settled("test")
    t0, t1, t2 = gc.get_threshold()
    assert (t0, t1) == young
    assert t2 == max(10, gc.get_freeze_count() // (4 * t0 * t1))
    gc.collect()        # as the cell's set-up does: the rule's base anew
    seen, kept = arrivals(n // 2)
    assert seen.passes <= 1
    # the pass saw the arrivals at most, never the frozen table
    assert seen.walked_max < n // 2 + 20_000
    assert len(table) == n and len(kept) == n // 2


def test_freezing_without_scaling_is_a_row_of_short_passes():
    """The control: the same heap frozen by hand, the third threshold
    left at CPython's."""
    n = 300_000
    table = [[i] for i in range(n)]
    gc.set_threshold(100, 5, 10)
    gc.freeze()
    gc.collect()
    seen, kept = arrivals(n // 2)
    assert seen.passes >= 3
    assert len(table) == n and len(kept) == n // 2


@pytest.mark.parametrize("young", [(700, 10), (350, 7), (1000, 20)])
def test_the_two_young_thresholds_are_passed_back_as_read(
        monkeypatch, counted, young):
    monkeypatch.setattr(heap, "GROWTH_STEP", 4)
    gc.set_threshold(*young, 10)
    counted["set_threshold"].clear()
    mark = heap.grown(4, 0)
    assert mark == 4 and not counted["set_threshold"]   # growth: no scaling
    heap.settled("test")
    mark = heap.grown(7, mark)
    assert mark == 4
    assert heap.grown(8, mark) == 8
    assert counted["freeze"] == 3
    assert len(counted["set_threshold"]) == 1           # the settle's
    assert all(a[:2] == young and a[2] >= 10
               for a in counted["set_threshold"])
    assert gc.get_threshold()[:2] == young


def test_a_small_heap_keeps_cpythons_third_threshold():
    heap.settled("test")
    assert gc.get_freeze_count() < 280_000 * 4      # this process's heap
    assert gc.get_threshold()[2] == max(
        10, gc.get_freeze_count() // 28_000)


# -- the registry ---------------------------------------------------------

@pytest.mark.parametrize("name", RUNTIME_METRIC_NAMES)
def test_runtime_names_are_registered(name):
    assert name.startswith(("runtime.gc.", "runtime.loop."))
    assert Metrics().get(name) == 0


def test_the_counts_move_in_the_table_and_in_info(monkeypatch):
    monkeypatch.setattr(heap, "GROWTH_STEP", 16)
    node = BrokerNode(Config())
    m = node.observed.metrics
    heap.report(m)
    g0 = m.get("runtime.gc.freezes.growth")
    s0 = m.get("runtime.gc.freezes.settled")
    b = node.broker
    b.open_session("c")
    for i in range(40):
        b.subscribe("c", f"a/{i}/#")
    heap.settled("test")
    info = node.info()["gc"]            # info() samples into the table
    assert m.get("runtime.gc.freezes.growth") - g0 == 2
    assert m.get("runtime.gc.freezes.settled") - s0 == 1
    # the gauge is the count the settle's scaling read, not a new walk
    assert (m.get("runtime.gc.frozen_objects") == info["frozen_objects"]
            == gc.get_freeze_count() > 0)
    assert info["freezes"] == freezes()
    assert info["thresholds"][:2] == [700, 10]
