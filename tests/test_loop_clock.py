"""The event loop's own account: the lag probe's clock on the loop's
selector (busy and idle time, long runs), the collector's account in
``observe/heap.py``, a subscriber's acks on the connection's worker, and
the benchmark's readers of all three."""

import asyncio
import gc
import json
import os
import selectors
import socket
import time

import pytest

from cellbench import run as RUN
from emqx_tpu.broker.olp import LONG_RUN_NS, LoopClock, LoopLagProbe, Olp
from emqx_tpu.client import Client
from emqx_tpu.config import Config
from emqx_tpu.node import BrokerNode
from emqx_tpu.observe import heap
from emqx_tpu.observe.flightrec import STAGES, Ring
from emqx_tpu.observe.hist import HistSet, LatencyHistogram
from emqx_tpu.observe.metrics import Metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYER_DIR = os.path.join(REPO, "cellbench", "layer_metrics")
NEW = ("loop_busy_pct", "loop_busy_us_per_publish", "gc_pause_us_per_publish",
       "loop_run_max_ms", "ack_in_p50_ms")
BUSY, IDLE = "runtime.loop.busy_ns", "runtime.loop.idle_ns"
COUNTERS = (BUSY, IDLE, "runtime.gc.pause_ns", "runtime.gc.collections")


# ---------------------------------------------------------------------------
# the clock on the selector
# ---------------------------------------------------------------------------

def test_the_clock_keeps_the_selectors_methods():
    inner = selectors.DefaultSelector()
    clock = LoopClock(inner, Metrics())
    a, b = socket.socketpair()
    try:
        key = clock.register(a, selectors.EVENT_READ, "data")
        assert clock.get_key(a) is key and a in clock.get_map()
        clock.modify(a, selectors.EVENT_READ | selectors.EVENT_WRITE, "d2")
        assert inner.get_key(a).data == "d2"
        b.send(b"x")
        events = clock.select(1.0)
        assert [(k.fileobj, k.data) for k, _ in events] == [(a, "d2")]
        clock.unregister(a)
        with pytest.raises(KeyError):
            inner.get_key(a)
        assert clock.select(0) == []
        # anything else is the selector's own
        assert clock.selector is inner and clock.__class__ is LoopClock
    finally:
        a.close()
        b.close()
        clock.close()


def _probe(metrics=None, hist=None, ring=None):
    return LoopLagProbe(Olp(max_loop_lag=60.0), metrics=metrics,
                        interval=0.01, hist=hist, ring=ring)


async def _running(probe):
    """Start ``probe.run`` and wait until its clock is in place."""
    task = asyncio.ensure_future(probe.run())
    loop = asyncio.get_running_loop()
    for _ in range(100):
        await asyncio.sleep(0.005)
        if isinstance(loop._selector, LoopClock):
            return task
    raise AssertionError("the probe never put its clock on the loop")


async def _stop(task):
    task.cancel()
    await asyncio.gather(task, return_exceptions=True)


def test_busy_and_idle_tile_the_wall_time():
    m = Metrics()

    async def main():
        task = await _running(_probe(m))
        b0, i0, t0 = m.get(BUSY), m.get(IDLE), time.perf_counter_ns()
        for _ in range(50):
            sum(range(20_000))          # some busy time
            await asyncio.sleep(0.01)   # some idle time
        b1, i1, t1 = m.get(BUSY), m.get(IDLE), time.perf_counter_ns()
        await _stop(task)
        return b1 - b0, i1 - i0, t1 - t0

    busy, idle, wall = asyncio.run(main())
    assert busy > 0 and idle > 0
    assert abs((busy + idle) - wall) <= 0.05 * wall, (busy, idle, wall)


def test_a_blocking_callback_is_one_long_run_and_one_ring_event():
    hist, ring = LatencyHistogram(), Ring("loop", depth=64)

    async def main():
        task = await _running(_probe(Metrics(), hist, ring))
        c0, n0 = hist.snapshot(), ring.idx
        t_planted = time.perf_counter_ns()
        asyncio.get_running_loop().call_soon(time.sleep, 0.03)
        await asyncio.sleep(0.02)
        await asyncio.sleep(0.02)
        await _stop(task)
        snap = ring.snapshot()
        return t_planted, [a - b for a, b in zip(hist.snapshot(), c0)], \
            snap[len(snap) - (ring.idx - n0):]

    t_planted, counts, events = asyncio.run(main())
    new = LatencyHistogram()
    new.counts = counts
    assert new.count >= 2 and new.max_ms() >= 30.0
    long_runs = [e for e in events if e[2] >= 30_000_000]
    assert len(long_runs) == 1, events
    sid, start, dur = long_runs[0][:3]
    # the run after the one that planted it: the callback's own
    assert STAGES[sid] == "loop_run" and start >= t_planted
    # only a long run goes into the ring
    assert all(e[2] >= LONG_RUN_NS for e in events)


def test_a_node_wraps_the_loop_once_and_restores_its_selector():
    async def main():
        loop = asyncio.get_running_loop()
        original = loop._selector
        cfg = 'listeners.tcp.default.bind = "127.0.0.1:0"\n'
        a, b = BrokerNode(Config(file_text=cfg)), BrokerNode(
            Config(file_text=cfg))
        await a.start()
        try:
            for _ in range(100):
                if isinstance(loop._selector, LoopClock):
                    break
                await asyncio.sleep(0.005)
            clock = loop._selector
            assert isinstance(clock, LoopClock) and clock.selector is original
            await b.start()             # the same loop: no second clock
            await asyncio.sleep(0.05)
            assert loop._selector is clock
            port = a.listeners.all()[0].port
            sub, pub = Client(clientid="s", port=port), Client(
                clientid="p", port=port)
            await sub.connect()
            await sub.subscribe("t/#", qos=1)
            await pub.connect()
            await pub.publish("t/1", b"x", qos=1)
            assert (await sub.recv(5.0)).topic == "t/1"
            await sub.close()
            await pub.close()
            m = a.observed.metrics
            assert m.get(BUSY) > 0 and m.get(IDLE) > 0
            assert b.observed.metrics.get(BUSY) == 0
        finally:
            await b.stop()
            await a.stop()
        assert loop._selector is original

    asyncio.run(main())


def test_a_loop_without_a_selector_runs_the_probe_without_a_clock(caplog):
    class NoSelector:
        pass

    probe = _probe(Metrics())
    with caplog.at_level("INFO", logger="emqx_tpu.broker.olp"):
        assert probe._wrap(NoSelector()) is None
    # the OLP contract stands: a drift sample is reported as before
    assert probe.observe(0.2) == pytest.approx(0.2)
    assert probe.metrics.get("broker.olp.loop_lag_us") == 200_000


# ---------------------------------------------------------------------------
# the collector's account
# ---------------------------------------------------------------------------

def test_a_forced_collection_moves_the_collectors_account():
    m = Metrics()
    h = HistSet("main").hist("obs.stage.gc_pause")
    other = Metrics()
    heap.keep_account(m, h)
    heap.keep_account(other)
    try:
        assert gc.callbacks.count(heap._on_gc) == 1     # once a process
        p0, c0 = m.get("runtime.gc.pause_ns"), m.get("runtime.gc.collections")
        gc.collect()
        p1, c1 = m.get("runtime.gc.pause_ns"), m.get("runtime.gc.collections")
        assert c1 >= c0 + 1 and p1 > p0
        assert h.count == c1 - c0
        assert other.get("runtime.gc.collections") == c1
    finally:
        heap.drop_account(m)
        heap.drop_account(other)
    gc.collect()
    assert m.get("runtime.gc.collections") == c1 and h.count == c1 - c0


# ---------------------------------------------------------------------------
# a subscriber's acks on the connection's worker
# ---------------------------------------------------------------------------

async def _intercept_node(extra=""):
    node = BrokerNode(Config(file_text=(
        'listeners.tcp.default.bind = "127.0.0.1:0"\n' + extra)))
    node._wants_intercept = lambda: True    # the worker queue, no device
    await node.start()
    return node, node.listeners.all()[0].port


def test_an_intercept_node_records_one_ack_in_per_puback():
    n = 12

    async def main():
        node, port = await _intercept_node()
        try:
            sub, pub = Client(clientid="s", port=port), Client(
                clientid="p", port=port)
            await sub.connect()
            await sub.subscribe("a/#", qos=1)
            await pub.connect()
            h = node.hists.hist("obs.stage.ack_in")
            c0 = h.count
            for i in range(n):
                await pub.publish(f"a/{i}", b"x", qos=1)
            for _ in range(n):
                await sub.recv(5.0)
            for _ in range(200):
                if h.count - c0 >= n:
                    break
                await asyncio.sleep(0.01)
            await asyncio.sleep(0.05)
            got = h.count - c0
            assert all(c._queue is not None for c in node._all_conns)
            await sub.close()
            await pub.close()
            return got
        finally:
            await node.stop()

    assert asyncio.run(main()) == n


def test_with_histograms_off_the_new_sites_are_none(monkeypatch):
    calls = []
    monkeypatch.setattr(LatencyHistogram, "record",
                        lambda self, ns: calls.append(ns))

    async def main():
        node, port = await _intercept_node("obs.hist.enable = false\n")
        try:
            assert node.lag_probe.hist is None
            assert node.lag_probe.ring is not None     # the ring is always on
            sub = Client(clientid="s", port=port)
            await sub.connect()
            await sub.subscribe("a/#", qos=1)
            await sub.publish("a/1", b"x", qos=1)
            await sub.recv(5.0)
            for conn in node._all_conns:
                assert conn._h_ack is None
            gc.collect()
            await asyncio.sleep(0.05)
            await sub.close()
            assert node.observed.metrics.get(BUSY) > 0
        finally:
            await node.stop()

    asyncio.run(main())
    assert calls == []


# ---------------------------------------------------------------------------
# the benchmark's readers of them
# ---------------------------------------------------------------------------

def _spec(name):
    with open(os.path.join(LAYER_DIR, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_reads_here_and_is_absent_on_a_tree_without_it(name):
    spec = _spec(name)
    m = Metrics()
    hs = HistSet("main")
    h0, c0 = {n: hs.hist(n).snapshot() for n in hs.names()}, m.all()
    m.inc(BUSY, 600_000)
    m.inc(IDLE, 400_000)
    m.set("runtime.gc.pause_ns", 20_000)
    m.set("runtime.gc.collections", 3)
    for v in (1_000_000, 2_000_000, 45_000_000):
        hs.hist("obs.stage.loop_run").record(v)
        hs.hist("obs.stage.ack_in").record(v // 10)
    h1, c1 = {n: hs.hist(n).snapshot() for n in hs.names()}, m.all()
    delta = {k: c1[k] - c0[k] for k in c1}
    older = {k: v for k, v in delta.items() if k not in COUNTERS}
    h_old = {k: v for k, v in h0.items() if not k.endswith(
        ("loop_run", "gc_pause", "ack_in"))}
    if spec["kind"] == "counter_ratio":
        got = RUN.counter_value(spec, delta, 1000)
        absent = RUN.counter_value(spec, older, 1000)
    else:
        assert spec["kind"] == "hist_delta"
        got = RUN.hist_value(spec, h0, h1)
        absent = RUN.hist_value(spec, h_old, h_old)
    assert absent is RUN.ABSENT
    want = {"loop_busy_pct": 60.0, "loop_busy_us_per_publish": 0.6,
            "gc_pause_us_per_publish": 0.02, "loop_run_max_ms": 45.0,
            "ack_in_p50_ms": 0.2}[name]
    assert got == pytest.approx(want, rel=0.04)
