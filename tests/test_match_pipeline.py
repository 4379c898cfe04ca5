"""Overlapped serve pipeline (ISSUE 11): double-buffered
encode/dispatch/readback.  Both loops dispatch the same served program
and read the same one packed array (ISSUE 32): the switch means overlap
and nothing else.

Flag off (``match.pipeline.enable = false``, the default) the pipeline
machinery is inert — asserted here by the inertness + parity tests; the
pre-existing tests/test_match_service.py suite keeps passing unchanged
on top.
"""

import asyncio
import threading

import pytest

from emqx_tpu import faultinject
from emqx_tpu.broker import Broker, SubOpts
from emqx_tpu.broker.match_service import MatchService
from emqx_tpu.faultinject import FaultInjector
from emqx_tpu.observe.metrics import Metrics
from emqx_tpu.ops.match_kernel import SERVE_FLAT_MULT

# what one served group ships at the smallest batch bucket: the packed
# array, row_meta then the flat ids
PACKED_BYTES_64 = 4 * (64 + SERVE_FLAT_MULT * 64)


def run(coro):
    return asyncio.run(coro)


async def settle(pred, timeout=30.0, interval=0.01):
    deadline = asyncio.get_running_loop().time() + timeout
    while asyncio.get_running_loop().time() < deadline:
        if pred():
            return True
        await asyncio.sleep(interval)
    return pred()


def make_service(broker, **kw):
    kw.setdefault("depth", 8)
    kw.setdefault("table", "python")
    kw.setdefault("bypass_rate", 0.0)
    kw.setdefault("metrics", Metrics())
    return MatchService(broker, **kw)


def subscribe_many(b, filters, sessions=8):
    for i, flt in enumerate(filters):
        cid = f"s{i % sessions}"
        if cid not in b.sessions:
            b.open_session(cid)
        b.subscribe(cid, flt, SubOpts())


async def synced(ms, b):
    return await settle(
        lambda: ms.ready and ms._seen_epoch == b.router.epoch
        and ms.dev.epoch == ms.inc.epoch)


# ---------------------------------------------------------------------------
# flag off: the pipeline machinery is inert, the serial path serves
# ---------------------------------------------------------------------------

def test_flag_off_pipeline_inert_and_slab_readback():
    async def main():
        b = Broker()
        subscribe_many(b, [f"room/+/k{i}" for i in range(6)])
        m = Metrics()
        ms = make_service(b, metrics=m)
        assert not ms.pipeline
        await ms.start()
        assert ms._inflight_q is None     # no queue, no readback child
        assert await synced(ms, b)
        await ms.prefetch("room/1/k1")
        hint = ms.hint_routes("room/1/k1")
        want = b.router.match_routes("room/1/k1")
        assert hint is not None
        assert sorted(map(tuple, hint)) == sorted(map(tuple, want))
        # flag off reads the one packed array, one buffer a batch
        batches = m.get("tpu.match.batches")
        assert batches >= 1
        assert m.get("tpu.match.readback_bytes") == \
            PACKED_BYTES_64 * batches
        assert m.get("tpu.match.readback_roundtrips") == batches
        assert m.get("broker.match.pipeline_inflight") == 0
        await ms.stop()

    run(main())


def test_flag_onoff_hints_identical():
    """The pipelined chain must mint byte-identical hints to the
    serial path for the same table + batch (flag-off parity)."""
    async def hints_with(pipeline):
        b = Broker()
        subscribe_many(b, [f"room/+/k{i}" for i in range(8)] + ["deep/#"])
        ms = make_service(b, pipeline=pipeline)
        await ms.start()
        assert await synced(ms, b)
        topics = [f"room/{i}/k{i % 8}" for i in range(20)] + ["deep/a/b"]
        await ms.prefetch_many({t: 1 for t in topics})
        out = {}
        for t in topics:
            hint = ms._hints.get(t)
            assert hint is not None, (pipeline, t)
            out[t] = (sorted(hint[2]), sorted(hint[3]))
        await ms.stop()
        return out

    async def main():
        serial = await hints_with(False)
        piped = await hints_with(True)
        assert serial == piped

    run(main())


# ---------------------------------------------------------------------------
# pipelined serving: parity, readback bytes, metrics
# ---------------------------------------------------------------------------

def test_pipeline_serves_with_parity_and_proportional_bytes():
    async def main():
        b = Broker()
        subscribe_many(b, [f"room/+/k{i}" for i in range(8)])
        m = Metrics()
        ms = make_service(b, pipeline=True, metrics=m)
        await ms.start()
        assert ms._inflight_q is not None
        assert await synced(ms, b)
        topics = [f"room/{i}/k{i % 8}" for i in range(32)]
        await ms.prefetch_many({t: 1 for t in topics})
        for t in topics:
            hint = ms.hint_routes(t)
            want = b.router.match_routes(t)
            assert hint is not None, t
            assert sorted(map(tuple, hint)) == sorted(map(tuple, want))
        # the same one packed array the serial loop reads, one buffer
        # a batch; the batch was 32 topics padded to 64
        batches = m.get("tpu.match.batches")
        assert m.get("tpu.match.readback_bytes") == \
            PACKED_BYTES_64 * batches
        assert m.get("tpu.match.readback_roundtrips") == batches
        # quiesced: no slots left in flight, metric reads 0
        assert ms._inflight_n == 0
        assert m.get("broker.match.pipeline_inflight") == 0
        assert m.get("tpu.match.batches") >= 1   # device really served
        await ms.stop()

    run(main())


# ---------------------------------------------------------------------------
# satellite bugfix: encode runs OFF the event loop in BOTH modes
# ---------------------------------------------------------------------------

def test_encode_runs_off_loop_flag_off(monkeypatch):
    async def main():
        b = Broker()
        subscribe_many(b, [f"room/+/k{i}" for i in range(4)])
        ms = make_service(b)          # flag OFF — the serial path
        await ms.start()
        assert await synced(ms, b)
        loop_thread = threading.get_ident()
        seen = []
        import emqx_tpu.ops as ops
        orig = ops.encode_batch

        def spy(*a, **kw):
            seen.append(threading.get_ident())
            return orig(*a, **kw)

        monkeypatch.setattr(ops, "encode_batch", spy)
        await ms.prefetch("room/1/k1")
        hint = ms.hint_routes("room/1/k1")
        assert hint is not None
        want = b.router.match_routes("room/1/k1")
        assert sorted(map(tuple, hint)) == sorted(map(tuple, want))
        # the serve-path encode ran in a worker thread, not on the loop
        # (the ~2.3 ms/dispatch loop stall the satellite bugfix kills)
        assert seen and all(t != loop_thread for t in seen)
        await ms.stop()

    run(main())


# ---------------------------------------------------------------------------
# per-slot staleness guards: swap / aid reuse discard exactly one slot
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mutate", ["gen", "reuse"])
def test_inflight_slot_swap_or_reuse_discards_via_guards(mutate):
    async def main():
        b = Broker()
        subscribe_many(b, [f"a/+/k{i}" for i in range(6)])
        m = Metrics()
        ms = make_service(b, pipeline=True, deadline=True, metrics=m)
        await ms.start()
        assert await synced(ms, b)
        topics = ["a/1/k1", "a/2/k2"]
        loop = asyncio.get_running_loop()
        pending = [(t, loop.create_future(), loop.time() + 1.0)
                   for t in topics]
        groups = [(list(range(len(topics))), ms.depth)]
        handles, enc_ns, disp_ns = ms._encode_dispatch(
            ms.inc, ms.dev, topics, groups)
        slot = (pending, topics, groups, handles, ms.inc, ms.dev,
                ms.inc.aid_reuses, ms._table_gen, ms._synced_epoch,
                ms._synced_rule_gen, loop.time(), True,
                enc_ns + disp_ns, None)
        # the swap/reuse lands while the slot is in flight
        if mutate == "gen":
            ms._table_gen += 1
        else:
            ms.inc.aid_reuses += 1
        await ms._finish_slot(slot)
        # every waiter resolved NOW, answers minted via the CPU tables,
        # and no breaker strike (the device is healthy)
        for _t, fut, _d in pending:
            assert fut.done()
        for t in topics:
            hint = ms._hints.get(t)
            assert hint is not None, t
            want = b.router.match_routes(t)
            got = ms.router.routes_with_wild(t, hint[2])
            assert sorted(map(tuple, got)) == sorted(map(tuple, want))
        assert ms._breaker_failures == 0
        assert m.get("broker.match.cpu_fallback") >= len(topics)
        await ms.stop()

    run(main())


# ---------------------------------------------------------------------------
# match.readback chaos seam + failover
# ---------------------------------------------------------------------------

def test_readback_fault_raise_falls_to_cpu_promptly():
    async def main():
        b = Broker()
        subscribe_many(b, [f"room/+/k{i}" for i in range(4)])
        m = Metrics()
        ms = make_service(b, pipeline=True, metrics=m)
        await ms.start()
        assert await synced(ms, b)
        faultinject.install(FaultInjector([
            {"point": "match.readback", "action": "raise", "times": 1},
        ]))
        try:
            t0 = asyncio.get_running_loop().time()
            await ms.prefetch("room/1/k1")
            waited = asyncio.get_running_loop().time() - t0
            # the faulted slot answers from the CPU tables in one hop,
            # far under the prefetch timeout
            assert waited < ms.prefetch_timeout_s * 0.9
            hint = ms.hint_routes("room/1/k1")
            assert hint is not None
            want = b.router.match_routes("room/1/k1")
            assert sorted(map(tuple, hint)) == sorted(map(tuple, want))
            assert m.get("broker.match.cpu_fallback") >= 1
            # fixed-window mode: a readback fault is not a breaker
            # strike (only the deadline loop feeds the breaker)
            assert not ms._breaker_open
        finally:
            faultinject.uninstall()
        # the seam is one-shot: the next batch rides the device again
        await ms.prefetch("room/2/k2")
        assert ms.hint_routes("room/2/k2") is not None
        await ms.stop()

    run(main())


def test_readback_fault_in_flag_off_path_shared_seam():
    """The match.readback seam also covers the serial (flag-off)
    loop's d2h boundary — both loops share one chaos surface."""
    async def main():
        b = Broker()
        subscribe_many(b, ["room/+/x"])
        m = Metrics()
        ms = make_service(b, metrics=m)    # flag OFF
        await ms.start()
        assert await synced(ms, b)
        inj = faultinject.install(FaultInjector([
            {"point": "match.readback", "action": "raise", "times": 1},
        ]))
        try:
            await ms.prefetch("room/9/x")
            assert inj.fired.get("match.readback") == 1
            # failure path: waiter resolved, host trie serves (the
            # serial loop resolves the batch empty-handed)
            assert b.router.match_routes("room/9/x")
        finally:
            faultinject.uninstall()
        await ms.stop()

    run(main())


def test_stop_resolves_inflight_slot_waiters():
    async def main():
        b = Broker()
        subscribe_many(b, ["t/+"])
        m = Metrics()
        ms = make_service(b, pipeline=True, metrics=m)
        await ms.start()
        assert await synced(ms, b)
        # park a fake in-flight slot, then stop: the readback child's
        # failover must resolve the waiter immediately
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        # hang the readback child so the slot stays queued
        faultinject.install(FaultInjector([
            {"point": "match.readback", "action": "hang", "times": 1},
        ]))
        try:
            await ms.prefetch("t/1")      # consumes the hang
        finally:
            faultinject.uninstall()
        ms._inflight_q.put_nowait(([("t/2", fut)], ["t/2"], [], [],
                                   ms.inc, ms.dev, 0, 0, 0, 0, 0.0,
                                   False))
        await ms.stop()
        await asyncio.sleep(0.01)
        assert fut.done()
        assert ms._inflight_n == 0

    run(main())


# ---------------------------------------------------------------------------
# composition with the deadline loop
# ---------------------------------------------------------------------------

def test_pipeline_composes_with_deadline_breaker():
    """Pipelined readback failures FEED the deadline-mode breaker:
    persistent faults trip CPU-serve mode exactly like dispatch
    failures do."""
    async def main():
        b = Broker()
        subscribe_many(b, [f"room/+/k{i}" for i in range(4)])
        m = Metrics()
        ms = make_service(b, pipeline=True, deadline=True,
                          breaker_threshold=3, metrics=m)
        await ms.start()
        assert await synced(ms, b)
        faultinject.install(FaultInjector([
            {"point": "match.readback", "action": "raise", "times": 3},
        ]))
        try:
            for i in range(3):
                await ms.prefetch(f"room/{i}/k{i}")
            assert await settle(lambda: ms._breaker_open, timeout=5)
        finally:
            faultinject.uninstall()
        # breaker open: prefetches short-circuit to the CPU path
        await ms.prefetch("room/9/k1")
        assert m.get("broker.match.cpu_fallback") >= 1
        await ms.stop()

    run(main())
