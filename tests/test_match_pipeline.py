"""Overlapped serve pipeline (ISSUE 11): double-buffered
encode/dispatch/readback with match-proportional two-phase d2h.

Flag off (``match.pipeline.enable = false``, the default) the serial
serve path is byte-identical to the PR-10 shape — asserted here by the
inertness + parity tests; the pre-existing tests/test_match_service.py
suite keeps passing unchanged on top.
"""

import asyncio
import threading

import pytest

from emqx_tpu import faultinject
from emqx_tpu.broker import Broker, SubOpts
from emqx_tpu.broker.match_service import MatchService, _StaleRace
from emqx_tpu.faultinject import FaultInjector
from emqx_tpu.observe.metrics import Metrics


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

def test_flag_off_pipeline_inert_and_slab_readback(monkeypatch):
    async def main():
        b = Broker()
        subscribe_many(b, [f"room/+/k{i}" for i in range(6)])
        ms = make_service(b)
        assert not ms.pipeline
        calls = {"twophase": 0, "slab": 0}
        orig = MatchService._readback_rows
        monkeypatch.setattr(
            MatchService, "_readback_rows",
            staticmethod(lambda res, n, k: (
                calls.__setitem__("slab", calls["slab"] + 1)
                or orig(res, n, k))))
        monkeypatch.setattr(
            MatchService, "_readback_rows_twophase",
            staticmethod(lambda res, n, k, mode="chunked": (
                calls.__setitem__("twophase", calls["twophase"] + 1))))
        await ms.start()
        assert ms._inflight_q is None     # no queue, no readback child
        assert await synced(ms, b)
        await ms.prefetch("room/1/k1")
        assert ms.hint_routes("room/1/k1") is not None
        # flag off reads the FULL slab exactly as PR 10 did — the
        # two-phase path never runs
        assert calls["slab"] >= 1
        assert calls["twophase"] == 0
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
        # two-phase d2h: bytes shipped are meta + ids, never the
        # FLAT_MULT·B slab; the batch was 32 topics padded to 64
        nbytes = m.get("tpu.match.readback_bytes")
        assert 0 < nbytes
        slab = 4 * (ms.FLAT_MULT * 64 + 3 * 64)
        assert nbytes < slab, (nbytes, slab)
        # quiesced: no slots left in flight, metric reads 0
        assert ms._inflight_n == 0
        assert m.get("broker.match.pipeline_inflight") == 0
        assert m.get("tpu.match.batches") >= 1   # device really served
        await ms.stop()

    run(main())


def test_two_phase_readback_exact_bytes_and_row_parity():
    """Spy-level contract: the two-phase readback ships EXACTLY
    4·(B + sum(counts)) bytes — counts vector first, then the dense
    ids — and decodes the same rows as the full-slab path."""
    async def main():
        b = Broker()
        subscribe_many(b, [f"a/+/k{i}" for i in range(6)] + ["a/#"])
        ms = make_service(b, pipeline=True)
        await ms.start()
        assert await synced(ms, b)
        topics = [f"a/{i}/k{i % 6}" for i in range(24)]
        handles, _enc_ns, _disp_ns = ms._encode_dispatch(
            ms.inc, ms.dev, topics,
            [(list(range(len(topics))), ms.depth)], False)
        (res, n) = handles[0]
        import jax
        import numpy as np

        B = int(res.row_meta.shape[0])
        counts_raw = int(np.asarray(
            jax.device_get(res.n_matches))[:n].sum())
        rows2, sp2, nbytes, trips = ms._readback_rows_twophase(
            res, n, ms.dev.max_matches)
        rows1, sp1 = ms._readback_rows(res, n, ms.dev.max_matches)
        assert rows2 == rows1
        assert sp2 == sp1
        # exact: 4·B meta + 4·Σ min(counts, K) ids — within the ISSUE
        # bound of 4·(B + sum(counts)), vs the 4·FLAT_MULT·B slab
        total = sum(len(r) for r in rows2)
        assert nbytes == 4 * (B + total)
        assert nbytes <= 4 * (B + counts_raw)
        assert nbytes < 4 * ms.FLAT_MULT * B
        # chunked trips: the meta fetch + one per pow2 chunk
        assert trips == 1 + bin(total).count("1")
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
            ms.inc, ms.dev, topics, groups, True)
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


# ---------------------------------------------------------------------------
# one-round-trip serve (ISSUE 17): ragged single-transfer readback
# ---------------------------------------------------------------------------

def _dispatch_one(ms, topics):
    """Encode + dispatch one batch through the real device path and
    hand back its (res, n) handle for direct readback assertions."""
    handles, _enc_ns, _disp_ns = ms._encode_dispatch(
        ms.inc, ms.dev, topics,
        [(list(range(len(topics))), ms.depth)], False)
    return handles[0]


def _count_device_gets(monkeypatch):
    """Spy on jax.device_get — every d2h round trip of the readback
    path funnels through it."""
    import jax

    calls = {"n": 0}
    orig = jax.device_get

    def spy(x):
        calls["n"] += 1
        return orig(x)

    monkeypatch.setattr(jax, "device_get", spy)
    return calls


def test_ragged_readback_two_transfers_and_bit_parity(monkeypatch):
    """The tentpole contract: ragged mode reads a batch in EXACTLY two
    d2h round trips (4·B meta + one padded payload) and decodes rows
    bit-identical to the chunked decomposition AND the full slab."""
    async def main():
        b = Broker()
        subscribe_many(b, [f"a/+/k{i}" for i in range(6)] + ["a/#"])
        ms = make_service(b, pipeline=True)
        await ms.start()
        assert await synced(ms, b)
        res, n = _dispatch_one(ms, [f"a/{i}/k{i % 6}" for i in range(24)])
        k = ms.dev.max_matches
        rows_c, sp_c, nb_c, tr_c = ms._readback_rows_twophase(
            res, n, k, mode="chunked")
        rows_s, sp_s = ms._readback_rows(res, n, k)
        calls = _count_device_gets(monkeypatch)
        rows_r, sp_r, nb_r, tr_r = ms._readback_rows_twophase(
            res, n, k, mode="ragged")
        assert rows_r == rows_c == rows_s
        assert sp_r == sp_c == sp_s
        # the spy-level bound: TWO device_get round trips, agreeing
        # with the trip count the metrics pipeline reports
        assert tr_r <= 2
        assert calls["n"] == tr_r == 2
        total = sum(len(r) for r in rows_r)
        # chunked pays popcount(total) payload trips for exact bytes;
        # ragged pays ≤ 2x bytes for exactly one payload trip
        assert tr_c == 1 + bin(total).count("1")
        from emqx_tpu.ops.match_kernel import ragged_capacity

        B = int(res.row_meta.shape[0])
        cap = ragged_capacity(total, int(res.matches.shape[0]))
        assert nb_r == 4 * (B + cap)
        assert nb_c == 4 * (B + total)
        assert nb_r <= 4 * B + 8 * max(4 * total, 4)
        await ms.stop()

    run(main())


def test_ragged_readback_meta_only_when_no_matches(monkeypatch):
    """Σcounts == 0: phase 2 vanishes — ONE d2h (the meta vector),
    every row empty, in both ragged and auto modes."""
    async def main():
        b = Broker()
        subscribe_many(b, [f"room/+/k{i}" for i in range(4)])
        ms = make_service(b, pipeline=True)
        await ms.start()
        assert await synced(ms, b)
        res, n = _dispatch_one(ms, ["zzz/1", "zzz/2", "zzz/3"])
        for mode in ("ragged", "auto"):
            calls = _count_device_gets(monkeypatch)
            rows, sp, nbytes, trips = ms._readback_rows_twophase(
                res, n, ms.dev.max_matches, mode=mode)
            assert rows == [[], [], []]
            assert sp == []
            assert trips == 1
            assert calls["n"] == 1
            assert nbytes == 4 * int(res.row_meta.shape[0])
        await ms.stop()

    run(main())


def test_ragged_readback_all_spill_batch():
    """Every row overflowing K stays fail-open through the ragged
    contract: counts clamp to K, every row lands in the spilled set,
    and the two-transfer bound holds."""
    async def main():
        b = Broker()
        # 8 overlapping filters vs max_matches=4: every topic spills
        subscribe_many(b, [f"s/+/k{i}" for i in range(4)]
                       + ["s/#", "s/+/#", "#", "+/+/+"])
        ms = make_service(b, pipeline=True, max_matches=4)
        await ms.start()
        assert await synced(ms, b)
        topics = [f"s/{i}/k{i % 4}" for i in range(6)]
        res, n = _dispatch_one(ms, topics)
        rows_r, sp_r, _nb, trips = ms._readback_rows_twophase(
            res, n, ms.dev.max_matches, mode="ragged")
        rows_c, sp_c, _nb2, _t2 = ms._readback_rows_twophase(
            res, n, ms.dev.max_matches, mode="chunked")
        assert sp_r == sp_c == list(range(len(topics)))
        assert rows_r == rows_c
        assert all(len(r) == 4 for r in rows_r)  # clamped to K
        assert trips <= 2
        await ms.stop()

    run(main())


def test_ragged_capacity_class_boundary_matches_chunked():
    """total == its capacity class (exact pow2): ragged pads nothing,
    bytes equal chunked exactly, and auto picks the chunked shape (a
    pow2 total is one chunk either way — same bytes AND trips)."""
    async def main():
        b = Broker()
        # disjoint single-wildcard filters: each topic matches exactly
        # one (literal filters answer off-device via the exact dict)
        subscribe_many(b, [f"p{i}/+" for i in range(4)])
        ms = make_service(b, pipeline=True)
        await ms.start()
        assert await synced(ms, b)
        res, n = _dispatch_one(ms, [f"p{i}/x" for i in range(4)])
        k = ms.dev.max_matches
        rows_r, _sp, nb_r, tr_r = ms._readback_rows_twophase(
            res, n, k, mode="ragged")
        rows_c, _sp2, nb_c, tr_c = ms._readback_rows_twophase(
            res, n, k, mode="chunked")
        _rows_a, _sp3, nb_a, tr_a = ms._readback_rows_twophase(
            res, n, k, mode="auto")
        total = sum(len(r) for r in rows_r)
        assert total == 4 and total & (total - 1) == 0
        assert rows_r == rows_c
        # pow2 boundary: capacity class == total, zero padding bytes
        assert nb_r == nb_c == nb_a
        assert tr_r == tr_c == tr_a == 2
        await ms.stop()

    run(main())


def test_midflight_swap_discards_ragged_slot():
    """A table swap landing while a ragged slot is in flight discards
    exactly that slot: waiters answer from the CPU tables, no breaker
    strike (same _StaleRace fail-open as the chunked path)."""
    async def main():
        b = Broker()
        subscribe_many(b, [f"a/+/k{i}" for i in range(6)])
        m = Metrics()
        ms = make_service(b, pipeline=True, deadline=True, metrics=m,
                          readback_mode="ragged")
        await ms.start()
        assert await synced(ms, b)
        topics = ["a/1/k1", "a/2/k2"]
        loop = asyncio.get_running_loop()
        pending = [(t, loop.create_future(), loop.time() + 1.0)
                   for t in topics]
        groups = [(list(range(len(topics))), ms.depth)]
        handles, enc_ns, disp_ns = ms._encode_dispatch(
            ms.inc, ms.dev, topics, groups, True)
        slot = (pending, topics, groups, handles, ms.inc, ms.dev,
                ms.inc.aid_reuses, ms._table_gen, ms._synced_epoch,
                ms._synced_rule_gen, loop.time(), True,
                enc_ns + disp_ns, None)
        ms._table_gen += 1          # the swap lands mid-flight
        await ms._finish_slot(slot)
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


def test_readback_mode_flag_off_byte_identity(monkeypatch):
    """``match.readback.mode = chunked`` (the default) leaves BOTH
    serve loops byte-identical to the PR-16 shape: the serial path
    reads the slab, the pipelined path runs the chunked two-phase —
    fetch_flat_ragged never executes (spy-asserted)."""
    async def main():
        from emqx_tpu.ops import match_kernel

        def boom(*a, **kw):  # pragma: no cover - must never run
            raise AssertionError("ragged fetch ran with the flag off")

        monkeypatch.setattr(match_kernel, "fetch_flat_ragged", boom)
        for pipeline in (False, True):
            b = Broker()
            subscribe_many(b, [f"room/+/k{i}" for i in range(6)])
            ms = make_service(b, pipeline=pipeline)
            assert ms.readback_mode == "chunked"
            await ms.start()
            assert await synced(ms, b)
            await ms.prefetch_many(
                {f"room/{i}/k{i % 6}": 1 for i in range(12)})
            for i in range(12):
                t = f"room/{i}/k{i % 6}"
                hint = ms.hint_routes(t)
                want = b.router.match_routes(t)
                assert hint is not None, t
                assert sorted(map(tuple, hint)) == \
                    sorted(map(tuple, want))
            await ms.stop()

    run(main())


def test_ragged_serve_parity_and_roundtrip_metric():
    """End-to-end through BOTH serve loops with the flag on: hints
    match the CPU router, and ``tpu.match.readback_roundtrips`` stays
    ≤ 2 per served batch."""
    async def main():
        for pipeline in (False, True):
            b = Broker()
            subscribe_many(b,
                           [f"room/+/k{i}" for i in range(8)] + ["deep/#"])
            m = Metrics()
            ms = make_service(b, pipeline=pipeline, metrics=m,
                              readback_mode="ragged")
            await ms.start()
            assert await synced(ms, b)
            topics = [f"room/{i}/k{i % 8}" for i in range(20)] \
                + ["deep/a/b"]
            await ms.prefetch_many({t: 1 for t in topics})
            for t in topics:
                hint = ms.hint_routes(t)
                want = b.router.match_routes(t)
                assert hint is not None, (pipeline, t)
                assert sorted(map(tuple, hint)) == \
                    sorted(map(tuple, want))
            batches = m.get("tpu.match.batches")
            trips = m.get("tpu.match.readback_roundtrips")
            assert batches >= 1
            assert 0 < trips <= 2 * batches, (trips, batches)
            await ms.stop()

    run(main())


def test_ragged_faultinject_readback_seam_covered():
    """The ``match.readback`` chaos seam sits upstream of the mode
    switch: a raise faults the ragged path exactly like chunked and
    the slot fails over to the CPU tables."""
    async def main():
        b = Broker()
        subscribe_many(b, [f"room/+/k{i}" for i in range(4)])
        m = Metrics()
        ms = make_service(b, pipeline=True, metrics=m,
                          readback_mode="ragged")
        await ms.start()
        assert await synced(ms, b)
        inj = FaultInjector([
            {"point": "match.readback", "action": "raise", "times": 1},
        ])
        faultinject.install(inj)
        try:
            await ms.prefetch("room/1/k1")
            assert inj.fired.get("match.readback") == 1
            hint = ms.hint_routes("room/1/k1")
            want = b.router.match_routes("room/1/k1")
            assert hint is not None
            assert sorted(map(tuple, hint)) == sorted(map(tuple, want))
            assert m.get("broker.match.cpu_fallback") >= 1
        finally:
            faultinject.uninstall()
        await ms.stop()

    run(main())
