"""Multichip serve backend (ISSUE 15): the match table sharded by
topic-prefix over the virtual 8-device CPU mesh, serving real publish
traffic through MatchService.

Covers: compact-contract parity against the host tables and the
single-chip flat path (bit-for-bit), per-shard truncation psum
fail-open, delta churn + growth restacks, per-shard segment
persistence with the epoch/checksum guards, kernel-cache mesh keys
(CompileMiss + prewarm), shard-kill / ``match.shard`` fault chaos with
delivery held at 1.0 via CPU failover, and the flag-off spy (the
single-chip path is byte-identical — no matcher is even constructed).
"""

import asyncio
import itertools
import json
import os

import numpy as np
import pytest

from emqx_tpu import faultinject
from emqx_tpu import topic as T
from emqx_tpu.client import Client
from emqx_tpu.config import Config
from emqx_tpu.faultinject import FaultInjector
from emqx_tpu.node import BrokerNode
from emqx_tpu.observe.metrics import Metrics
from emqx_tpu.ops.incremental import IncrementalNfa
from emqx_tpu.parallel import multichip_serve as mcs_mod
from emqx_tpu.parallel.multichip_serve import (
    MultichipMatcher, ShardDead, is_micro_filter, serve_mesh_shape,
    shard_of_filter,
)

FILTERS = ["a/+", "a/#", "+/b", "#", "x/y/z", "x/+/z", "$SYS/#",
           "rooms/+/temp", "rooms/1/#", "b/c", "deep/+/q/+", "m/n"]


def run(coro):
    return asyncio.run(coro)


async def settle(pred, timeout=60.0, interval=0.02):
    deadline = asyncio.get_running_loop().time() + timeout
    while asyncio.get_running_loop().time() < deadline:
        if pred():
            return True
        await asyncio.sleep(interval)
    return pred()


def make_node(**extra):
    cfg = Config(file_text='listeners.tcp.default.bind = "127.0.0.1:0"\n')
    cfg.put("tpu.enable", True)
    cfg.put("tpu.mirror_refresh_interval", 0.01)
    cfg.put("tpu.bypass_rate", 0.0)
    cfg.put("match.multichip.enable", True)
    for k, v in extra.items():
        cfg.put(k, v)
    return BrokerNode(cfg)


def build_pair(filters=FILTERS, depth=8, **mc_kw):
    """(service table, matcher with the same aid space, pairs)."""
    inc = IncrementalNfa(depth=depth)
    pairs = []
    for f in filters:
        inc.add(f)
        pairs.append((f, inc.aid_of(f)))
    mc = MultichipMatcher(depth=depth, **mc_kw)
    mc.rebuild(pairs)
    assert mc.apply_pending()
    return inc, mc, pairs


def topics_for(n, seed=5):
    rng = np.random.default_rng(seed)
    words = ["a", "b", "x", "y", "z", "rooms", "1", "temp", "m", "n",
             "deep", "q"]
    return ["/".join(rng.choice(words, size=rng.integers(1, 5)))
            for _ in range(n)]


def mesh_rows(mc, topics, batch=64, depth=None):
    enc = mc.encode(topics, batch=batch, depth=depth)
    return mc.readback(mc.dispatch(enc), len(topics))


# ---------------------------------------------------------------------------
# partition + parity (CPU mesh)
# ---------------------------------------------------------------------------

def test_mesh_shape_and_partition_determinism():
    assert serve_mesh_shape(8) == {"dp": 2, "tp": 4}
    assert serve_mesh_shape(8, tp=2) == {"dp": 4, "tp": 2}
    assert serve_mesh_shape(1) == {"dp": 1, "tp": 1}
    for f in FILTERS:
        t = shard_of_filter(f, 4)
        assert 0 <= t < 4
        assert t == shard_of_filter(f, 4)  # deterministic
    # the partition spreads the whole table over the shards; the
    # wildcard-root filters live in the replicated micro-table instead
    # of crc32-hashing to one arbitrary shard (ISSUE 16)
    _inc, mc, _pairs = build_pair()
    per_shard = [sub.n_filters for sub in mc._subs]
    n_micro = sum(1 for f in FILTERS if is_micro_filter(f))
    assert n_micro >= 2            # corpus keeps the micro path honest
    assert len(mc._micro_filters) == n_micro
    assert sum(per_shard) == len(FILTERS) - n_micro
    assert mc.dp * mc.tp == 8


def test_compact_rows_bit_for_bit_vs_host_and_single_chip():
    """The dense compact contract off the mesh must reproduce the
    single-chip serve path's rows bit-for-bit (same service accept
    ids) and agree with the host walk on every topic."""
    from emqx_tpu.ops import encode_batch
    from emqx_tpu.ops.device_table import DeviceNfa
    from emqx_tpu.ops.match_kernel import decode_packed

    inc, mc, _pairs = build_pair()
    dev = DeviceNfa(inc, active_slots=8, max_matches=16)
    topics = topics_for(64)
    rows8, sp8, nbytes = mesh_rows(mc, topics)
    assert nbytes > 0
    enc = encode_batch(inc, topics, batch=64)
    rows1, sp1 = decode_packed(dev.serve(*enc), len(topics), 16)
    assert not sp8 and not sp1
    for t, r8, r1 in zip(topics, rows8, rows1):
        assert sorted(r8) == sorted(r1) == sorted(inc.match_host(t)), t


def test_delta_churn_and_growth_restack_parity():
    """note_add/note_del ride the drain/apply cycle; enough adds to
    cross a pow2 boundary force a restack (gen bump) and parity must
    hold through both regimes."""
    inc, mc, _pairs = build_pair()
    gen0 = mc.gen
    # small delta: scatters, no restack
    for f in ("live/+/one", "live/two"):
        inc.add(f)
        mc.note_add(f, inc.aid_of(f))
    inc.remove("a/+")
    mc.note_del("a/+")
    assert mc.apply_pending()
    topics = topics_for(32) + ["live/x/one", "live/two", "a/q"]
    rows, sp, _ = mesh_rows(mc, topics)
    for t, r in zip(topics, rows):
        if topics.index(t) in sp:
            continue
        assert sorted(r) == sorted(inc.match_host(t)), t
    # bulk growth: resized deltas restack the stacked tables
    for i in range(400):
        f = f"grow/{i}/+"
        inc.add(f)
        mc.note_add(f, inc.aid_of(f))
    assert mc.apply_pending()
    assert mc.gen > gen0
    rows, sp, _ = mesh_rows(mc, ["grow/7/z", "grow/399/z", "m/n"])
    assert not sp
    for t, r in zip(["grow/7/z", "grow/399/z", "m/n"], rows):
        assert sorted(r) == sorted(inc.match_host(t)), t


def test_truncation_psum_fail_open():
    """Per-shard truncation: every row the psum'd overflow did NOT
    flag must be COMPLETE (the flag may over-approximate — the host
    re-runs flagged rows — but never under-approximate)."""
    inc, mc, _pairs = build_pair(max_matches=1, ep_micro_matches=1)
    # shard segments truncate ("x/y/z" matches x/y/z + x/+/z on the
    # "x" shard) AND the micro segment truncates ("a/b" matches the
    # wildcard-root "+/b" + "#" past the 1-slot micro cap)
    topics = ["a/b", "a/b/c", "x/y/z", "m/n", "b/c"]
    rows, sp, _ = mesh_rows(mc, topics)
    spset = set(sp)
    assert spset, "expected at least one truncated row"
    for i, t in enumerate(topics):
        if i not in spset:
            assert sorted(rows[i]) == sorted(inc.match_host(t)), t


# ---------------------------------------------------------------------------
# chaos: dead shards + the match.shard seam (matcher level)
# ---------------------------------------------------------------------------

def test_shard_kill_raises_and_counts_failover():
    inc, mc, _pairs = build_pair()
    enc = mc.encode(["a/b"], batch=64)
    mc.dispatch(enc)
    mc.kill_shard(2)
    with pytest.raises(ShardDead):
        mc.dispatch(enc)
    assert mc.failovers == 1
    mc.revive_shard(2)
    rows, _, _ = mesh_rows(mc, ["a/b"])
    assert sorted(rows[0]) == sorted(inc.match_host("a/b"))


def test_match_shard_fault_injection_point():
    inc, mc, _pairs = build_pair()
    enc = mc.encode(["a/b"], batch=64)
    faultinject.install(FaultInjector([
        {"point": "match.shard", "action": "raise", "times": 1},
    ]))
    try:
        with pytest.raises(faultinject.InjectedFault):
            mc.dispatch(enc)
        assert mc.failovers == 1
        mc.dispatch(enc)   # rule exhausted: healthy again
    finally:
        faultinject.uninstall()


# ---------------------------------------------------------------------------
# per-shard segment persistence
# ---------------------------------------------------------------------------

def test_segments_roundtrip_epoch_and_checksum_guards(tmp_path):
    inc, mc, _pairs = build_pair()
    d = str(tmp_path)
    mc.save_segments(d, epoch=inc.epoch)
    topics = topics_for(16)
    want, _, _ = mesh_rows(mc, topics)

    # epoch mismatch -> repartition serves
    mc2 = MultichipMatcher(depth=8)
    assert not mc2.load_segments(d, expect_epoch=inc.epoch + 1)
    # matching epoch -> seeded, restacked at the next apply, parity
    mc3 = MultichipMatcher(depth=8)
    assert mc3.load_segments(d, expect_epoch=inc.epoch)
    assert mc3.dirty and not mc3.ready
    assert mc3.apply_pending()
    got, _, _ = mesh_rows(mc3, topics)
    assert [sorted(r) for r in got] == [sorted(r) for r in want]
    assert mc3.seeded_from_segments

    # tampered aid maps -> checksum reject
    mpath = os.path.join(d, "multichip", "aid_maps.npz")
    maps = dict(np.load(mpath))
    maps["m0"] = np.asarray(maps["m0"], np.int32) + 1
    np.savez(mpath, **maps)
    mc4 = MultichipMatcher(depth=8)
    assert not mc4.load_segments(d, expect_epoch=inc.epoch)

    # wrong tp layout -> rejected before any array is trusted
    mc5 = MultichipMatcher(depth=8, tp=2)
    assert not mc5.load_segments(d, expect_epoch=inc.epoch)


# ---------------------------------------------------------------------------
# kernel-cache mesh dimension
# ---------------------------------------------------------------------------

def test_kernel_cache_mesh_keys_compile_miss_and_prewarm():
    from emqx_tpu.ops.kernel_cache import CompileMiss, MatchKernelCache

    kc = MatchKernelCache()
    inc, mc, _pairs = build_pair(kernel_cache=kc)
    enc = mc.encode(["a/b"], batch=64)
    # non-blocking cold shape: the serving contract (CPU answers NOW)
    with pytest.raises(CompileMiss):
        mc.dispatch(enc, block_compile=False)
    # blocking compile, then a hit
    rows, _, _ = mc.readback(mc.dispatch(enc, block_compile=True), 1)
    assert sorted(rows[0]) == sorted(inc.match_host("a/b"))
    h0 = kc.hits
    mc.dispatch(enc)
    assert kc.hits > h0
    # prewarm replays the MESH combo against the next pow2 table shape
    smax, hbmax = mc._stacked_shape[0], mc._stacked_shape[1]
    assert not kc.shape_covered(2 * smax, hbmax)
    n = kc.prewarm_shape(2 * smax, hbmax)
    assert n >= 1
    assert kc.shape_covered(2 * smax, hbmax)


# ---------------------------------------------------------------------------
# MatchService integration (full node on the CPU mesh)
# ---------------------------------------------------------------------------

def test_node_multichip_serves_then_shard_kill_holds_delivery():
    """Real traffic through the sharded table: hints ride the mesh
    with parity; a killed shard degrades like any device failure —
    the CPU trie answers and delivery stays 1.0 (tier-1 chaos)."""

    async def main():
        node = make_node()
        await node.start()
        ms = node.match_service
        assert ms is not None and ms.mc is not None
        assert ms.mc.n_devices == 8
        port = node.listeners.all()[0].port
        try:
            subs, filters = [], []
            for i in range(4):
                c = Client(clientid=f"s{i}", port=port)
                await c.connect()
                flt = f"room/+/kind{i % 2}"
                await c.subscribe(flt, qos=0)
                subs.append(c)
                filters.append(flt)
            assert await settle(lambda: ms.ready and ms.mc.ready)
            d0 = ms.mc.dispatches
            pub = Client(clientid="p", port=port)
            await pub.connect()
            topics = [f"room/{i}/kind{i % 2}" for i in range(20)]
            for t in topics:
                await pub.publish(t, b"x", qos=0)
            want = sum(1 for t in topics for f in filters
                       if T.match(t, f))
            assert await settle(
                lambda: sum(s.messages.qsize() for s in subs) >= want)
            m = node.observed.metrics
            assert ms.mc.dispatches > d0, "batches did not ride the mesh"
            assert m.get("tpu.match.shard_dispatches") >= 1
            assert m.get("tpu.match.shard_devices") == 8
            assert m.get("tpu.match.batches") >= 1

            # chaos: dead shard -> CPU failover, delivery_ratio 1.0
            ms.mc.kill_shard(1)
            topics2 = [f"room/{100 + i}/kind{i % 2}" for i in range(20)]
            for t in topics2:
                await pub.publish(t, b"y", qos=0)
            want2 = want + sum(1 for t in topics2 for f in filters
                               if T.match(t, f))
            assert await settle(
                lambda: sum(s.messages.qsize() for s in subs) >= want2)
            assert m.get("tpu.match.shard_failover") >= 1
            info = ms.info()["multichip"]
            assert info["dead_shards"] == [1]
            for s in subs:
                await s.disconnect()
            await pub.disconnect()
        finally:
            await node.stop()

    run(main())


def test_node_match_shard_fault_failover_and_recovery():
    """An injected ``match.shard`` raise behaves like a device
    failure: the batch falls to the CPU trie (hints still answer),
    and once the rule is exhausted the mesh serves again."""

    async def main():
        node = make_node()
        await node.start()
        ms = node.match_service
        assert ms is not None and ms.mc is not None
        try:
            b = node.broker
            if "c1" not in b.sessions:
                b.open_session("c1")
            b.subscribe("c1", "f/+")
            assert await settle(lambda: ms.ready and ms.mc.ready)
            faultinject.install(FaultInjector([
                {"point": "match.shard", "action": "raise", "times": 2},
            ]))
            await ms.prefetch("f/one")
            # device path refused; the publish path still answers via
            # the host trie (no fresh hint was minted)
            inj = faultinject.get()
            assert inj is not None
            assert inj.fired.get("match.shard", 0) >= 1
            faultinject.uninstall()
            d0 = ms.mc.dispatches
            await ms.prefetch("f/two")
            assert await settle(lambda: ms.mc.dispatches > d0)
            assert ms.hint_routes("f/two") is not None
        finally:
            faultinject.uninstall()
            await node.stop()

    run(main())


def test_compaction_swap_repartitions_and_serves():
    """A compacted-table swap reassigns EVERY aid: the shard
    partition rebuilds from the fresh space (mc.gen bumps) and serving
    parity holds on the new table."""

    async def main():
        import tempfile

        seg = tempfile.mkdtemp()
        node = make_node(**{
            "match.segments.enable": True,
            "match.segments.dir": seg,
            "match.segments.compact_interval": 0.2,
            "match.segments.compact_min_mutations": 1,
        })
        await node.start()
        ms = node.match_service
        assert ms is not None and ms.mc is not None
        try:
            b = node.broker
            if "c1" not in b.sessions:
                b.open_session("c1")
            for i in range(8):
                b.subscribe("c1", f"swap/{i}/+")
            assert await settle(lambda: ms.ready and ms.mc.ready)
            gen0 = ms.mc.gen
            assert await settle(lambda: ms._table_gen >= 1, timeout=30)
            # the repartition lands on the next sync pass; the service
            # is ready again once its step shapes are warm (until then
            # the host trie serves, not the one-chip mirror)
            assert await settle(
                lambda: ms.ready and ms.mc.gen > gen0, timeout=30)
            await ms.prefetch("swap/3/x")
            routes = ms.hint_routes("swap/3/x")
            assert routes is not None
            # per-shard segments persisted next to the main segment
            assert os.path.exists(
                os.path.join(seg, "multichip", "manifest.json"))
            with open(os.path.join(seg, "multichip",
                                   "manifest.json")) as f:
                assert json.load(f)["tp"] == ms.mc.tp
        finally:
            await node.stop()

    run(main())


def test_flag_off_is_byte_identical_single_chip_path(monkeypatch):
    """match.multichip.enable off: no matcher is constructed (spy),
    the serve plane dispatches through the single-chip DeviceNfa, and
    the shard metrics stay zero."""
    calls = []
    real = mcs_mod.MultichipMatcher

    class Spy(real):
        def __init__(self, *a, **kw):
            calls.append(1)
            super().__init__(*a, **kw)

    monkeypatch.setattr(mcs_mod, "MultichipMatcher", Spy)

    async def main():
        cfg = Config(
            file_text='listeners.tcp.default.bind = "127.0.0.1:0"\n')
        cfg.put("tpu.enable", True)
        cfg.put("tpu.mirror_refresh_interval", 0.01)
        cfg.put("tpu.bypass_rate", 0.0)
        node = BrokerNode(cfg)
        await node.start()
        ms = node.match_service
        try:
            assert ms is not None
            assert ms.mc is None
            b = node.broker
            if "c1" not in b.sessions:
                b.open_session("c1")
            b.subscribe("c1", "off/+")
            assert await settle(lambda: ms.ready)
            await ms.prefetch("off/x")
            assert ms.hint_routes("off/x") is not None
            m = node.observed.metrics
            assert m.get("tpu.match.batches") >= 1
            assert m.get("tpu.match.shard_dispatches") == 0
            assert m.get("tpu.match.shard_devices") == 0
            assert not calls, "flag off must not construct a matcher"
            assert ms.info()["multichip"] is None
        finally:
            await node.stop()

    run(main())

# ---------------------------------------------------------------------------
# prefix-EP routed front end (ISSUE 16)
# ---------------------------------------------------------------------------

def build_ep_pair(filters=FILTERS, depth=8, **mc_kw):
    inc = IncrementalNfa(depth=depth)
    pairs = []
    for f in filters:
        inc.add(f)
        pairs.append((f, inc.aid_of(f)))
    mc = MultichipMatcher(depth=depth, ep=True, **mc_kw)
    mc.rebuild(pairs)
    assert mc.apply_pending()
    return inc, mc, pairs


def test_ep_routed_parity_vs_replicated_mixed_roots():
    """Routed bit-parity: a mixed literal/wildcard-root corpus served
    through the EP front end must reproduce the replicated-batch
    backend's rows (and the host walk) exactly — the owner's merged
    own+micro segment covers everything the fanned batch saw."""
    inc, mc_rep, pairs = build_pair()
    mc_ep = MultichipMatcher(depth=8, ep=True, ep_slack=4.0)
    mc_ep.rebuild(pairs)
    assert mc_ep.apply_pending()
    topics = topics_for(48)
    rows_r, sp_r, _ = mesh_rows(mc_rep, topics)
    rows_e, sp_e, _ = mesh_rows(mc_ep, topics)
    assert mc_ep.ep_dispatches == 1 and mc_rep.ep_dispatches == 0
    assert not sp_r and not sp_e
    for t, rr, re_ in zip(topics, rows_r, rows_e):
        assert sorted(re_) == sorted(rr) == sorted(inc.match_host(t)), t


def test_ep_bucket_overflow_fails_open():
    """A hot root skewing every row of a source slice to ONE owner
    overflows the (source, owner) bucket: overflowed rows are flagged
    for the CPU trie (never silently dropped), unflagged rows stay
    complete."""
    inc, mc, _pairs = build_ep_pair(ep_slack=1.0)
    # every topic under x/: all 8 rows of each source slice route to
    # the "x" owner, capacity ceil(1.0*8/4) = 2 -> 6 overflow/source
    topics = [f"x/{i}/z" for i in range(24)] + ["x/y/z"] * 8
    rows, sp, _ = mc.readback(
        mc.dispatch(mc.encode(topics, batch=64)), len(topics))
    assert sp, "expected bucket overflow on the skewed corpus"
    spset = set(sp)
    assert len(spset) < len(topics), "slack must keep some rows routed"
    for i, t in enumerate(topics):
        if i not in spset:
            assert sorted(rows[i]) == sorted(inc.match_host(t)), t


def test_ep_micro_table_completeness_unknown_roots():
    """Wildcard-root filters live in the replicated micro-table: a
    topic whose root was NEVER interned (word id 0, owner shard 0)
    still collects its full wildcard answer set on the routed path."""
    inc, mc, _pairs = build_ep_pair(ep_slack=4.0)
    topics = ["zzz/b", "unknown/word/here", "qqq"]
    rows, sp, _ = mc.readback(
        mc.dispatch(mc.encode(topics, batch=64)), len(topics))
    assert not sp
    for t, r in zip(topics, rows):
        want = sorted(inc.match_host(t))
        assert sorted(r) == want, (t, r, want)
        assert want, f"corpus must exercise the micro path for {t}"


def test_ep_micro_table_tracks_churn():
    """note_add/note_del of wildcard-root filters mutate the micro
    partition (not a crc32 shard) and serve on the next apply."""
    inc, mc, _pairs = build_ep_pair(ep_slack=4.0)
    inc.add("+/added")
    mc.note_add("+/added", inc.aid_of("+/added"))
    inc.remove("#")
    mc.note_del("#")
    assert mc.apply_pending()
    assert "+/added" in mc._micro_filters
    assert "#" not in mc._micro_filters
    topics = ["q/added", "zz/yy"]
    rows, sp, _ = mc.readback(
        mc.dispatch(mc.encode(topics, batch=64)), len(topics))
    assert not sp
    for t, r in zip(topics, rows):
        assert sorted(r) == sorted(inc.match_host(t)), t


def test_ep_route_fault_injection_point():
    """The routed front end's own seam: an injected ep.route raise
    refuses the dispatch (failover counted) without touching the
    replicated path."""
    inc, mc, _pairs = build_ep_pair(ep_slack=4.0)
    enc = mc.encode(["a/b"], batch=64)
    faultinject.install(FaultInjector([
        {"point": "ep.route", "action": "raise", "times": 1},
    ]))
    try:
        with pytest.raises(faultinject.InjectedFault):
            mc.dispatch(enc)
        assert mc.failovers == 1
        rows, _, _ = mc.readback(mc.dispatch(enc), 1)
        assert sorted(rows[0]) == sorted(inc.match_host("a/b"))
    finally:
        faultinject.uninstall()


def test_ep_shard_kill_raises_before_routing():
    inc, mc, _pairs = build_ep_pair(ep_slack=4.0)
    enc = mc.encode(["a/b"], batch=64)
    mc.dispatch(enc)
    mc.kill_shard(3)
    with pytest.raises(ShardDead):
        mc.dispatch(enc)
    assert mc.failovers == 1


def test_ep_metrics_width_gate_and_odd_batches_fall_back():
    """Routed dispatches publish the per-shard width tp*C (the
    gate_shard_width_le_batch_over_tp numerator) and the analytic ICI
    bill; batch shapes that don't split into tp source slices fall
    back to the replicated step for that dispatch."""
    from emqx_tpu.observe.metrics import Metrics

    m = Metrics()
    inc, mc, pairs = build_ep_pair(metrics=m)
    b = 64
    rows, _, _ = mc.readback(
        mc.dispatch(mc.encode(["a/b"], batch=b)), 1)
    assert sorted(rows[0]) == sorted(inc.match_host("a/b"))
    assert m.get("tpu.match.ep_dispatches") == 1
    width = m.get("tpu.match.ep_shard_width")
    assert width == mc.tp * mc.ep_capacity(b)
    import math
    assert width <= math.ceil(mc.ep_slack * (b // mc.dp) / mc.tp)
    assert m.get("tpu.match.ep_ici_bytes") > 0
    # 4-row batch: 4 % (dp*tp) != 0 -> replicated fallback, parity holds
    rows2, _, _ = mc.readback(
        mc.dispatch(mc.encode(["a/b"], batch=4)), 1)
    assert sorted(rows2[0]) == sorted(inc.match_host("a/b"))
    assert m.get("tpu.match.ep_dispatches") == 1  # unchanged


def test_node_ep_routed_serves_and_shard_kill_holds_delivery():
    """The full node with match.multichip.ep.enable: real publishes
    ride the routed step (ep metrics move), and a killed shard on the
    ROUTED path still degrades to the CPU trie at delivery 1.0."""

    async def main():
        node = make_node(**{"match.multichip.ep.enable": True})
        await node.start()
        ms = node.match_service
        assert ms is not None and ms.mc is not None and ms.mc.ep
        port = node.listeners.all()[0].port
        try:
            subs, filters = [], []
            for i in range(4):
                c = Client(clientid=f"s{i}", port=port)
                await c.connect()
                flt = f"room/+/kind{i % 2}"
                await c.subscribe(flt, qos=0)
                subs.append(c)
                filters.append(flt)
            assert await settle(lambda: ms.ready and ms.mc.ready)
            pub = Client(clientid="p", port=port)
            await pub.connect()
            topics = [f"room/{i}/kind{i % 2}" for i in range(20)]
            for t in topics:
                await pub.publish(t, b"x", qos=0)
            want = sum(1 for t in topics for f in filters
                       if T.match(t, f))
            assert await settle(
                lambda: sum(s.messages.qsize() for s in subs) >= want)
            m = node.observed.metrics
            assert await settle(
                lambda: m.get("tpu.match.ep_dispatches") >= 1)
            assert m.get("tpu.match.ep_shard_width") >= 1

            ms.mc.kill_shard(2)
            topics2 = [f"room/{100 + i}/kind{i % 2}" for i in range(20)]
            for t in topics2:
                await pub.publish(t, b"y", qos=0)
            want2 = want + sum(1 for t in topics2 for f in filters
                               if T.match(t, f))
            assert await settle(
                lambda: sum(s.messages.qsize() for s in subs) >= want2)
            assert m.get("tpu.match.shard_failover") >= 1
            for s in subs:
                await s.disconnect()
            await pub.disconnect()
        finally:
            await node.stop()

    run(main())


# ---------------------------------------------------------------------------
# count-compacted routed readback (ISSUE 17)
# ---------------------------------------------------------------------------

def test_ep_compact_parity_and_bytes_reduction():
    """``ep_compact`` selects nothing any more: every routed step
    collapses its per-owner segments on the mesh and answers with one
    packed array, so rows are bit-equal with the key on and off (and to
    the replicated contract and the host walk), and the routed d2h bytes
    are the packed array's, far under the replicated step's five."""
    inc, mc_rep, pairs = build_pair()
    mc_ep = MultichipMatcher(depth=8, ep=True, ep_slack=4.0)
    mc_ep.rebuild(pairs)
    assert mc_ep.apply_pending()
    mc_c = MultichipMatcher(depth=8, ep=True, ep_slack=4.0,
                            ep_compact=True)
    mc_c.rebuild(pairs)
    assert mc_c.apply_pending()
    assert mc_c.info()["ep_compact"] is True
    assert mc_ep.info()["ep_compact"] is False
    topics = topics_for(48)
    rows_r, sp_r, nb_r = mesh_rows(mc_rep, topics)
    rows_e, sp_e, nb_e = mesh_rows(mc_ep, topics)
    rows_c, sp_c, nb_c = mesh_rows(mc_c, topics)
    assert mc_c.ep_dispatches == 1 and mc_ep.ep_dispatches == 1
    assert not sp_r and not sp_e and not sp_c
    for t, rr, re_, rc in zip(topics, rows_r, rows_e, rows_c):
        assert sorted(rc) == sorted(re_) == sorted(rr) \
            == sorted(inc.match_host(t)), t
    # one (B + SERVE_FLAT_MULT·B,) int32 array, the key on or off
    from emqx_tpu.ops.match_kernel import SERVE_FLAT_MULT

    assert nb_c == nb_e == 4 * (64 + SERVE_FLAT_MULT * 64)
    assert nb_e <= nb_r // 4, (nb_e, nb_r)


def test_ep_compact_overflow_fails_open():
    """Bucket overflow under the compact contract keeps the fail-open
    discipline: psum carries every shard's overflow flag into the
    collapsed row, so skewed rows are flagged for the CPU trie and
    unflagged rows stay complete."""
    inc, mc, _pairs = build_ep_pair(ep_slack=1.0, ep_compact=True)
    topics = [f"x/{i}/z" for i in range(24)] + ["x/y/z"] * 8
    rows, sp, _ = mc.readback(
        mc.dispatch(mc.encode(topics, batch=64)), len(topics))
    assert sp, "expected bucket overflow on the skewed corpus"
    spset = set(sp)
    assert len(spset) < len(topics), "slack must keep some rows routed"
    for i, t in enumerate(topics):
        if i not in spset:
            assert sorted(rows[i]) == sorted(inc.match_host(t)), t


def test_ep_compact_odd_batch_falls_back_replicated():
    """Batch shapes that can't split into tp source slices fall back
    to the replicated step under ep_compact too — same fallback gate,
    parity holds."""
    inc, mc, _pairs = build_ep_pair(ep_slack=4.0, ep_compact=True)
    rows, _, _ = mc.readback(
        mc.dispatch(mc.encode(["a/b"], batch=4)), 1)
    assert sorted(rows[0]) == sorted(inc.match_host("a/b"))
    assert mc.ep_dispatches == 0   # replicated fallback served


# ---------------------------------------------------------------------------
# degraded mesh: scoped failover, health ladder, online rebuild (ISSUE 18)
# ---------------------------------------------------------------------------

def fill_parity(inc, mc, topics, rows, sp, fill=None):
    """The scoped-failover delivery contract: every non-spilled row,
    credited with the CPU fill of the dead shards' aids, reproduces
    the host walk exactly."""
    fill = mc.dead_aids() if fill is None else fill
    spset = set(sp)
    for i, t in enumerate(topics):
        if i in spset:
            continue
        host = set(inc.match_host(t))
        assert set(rows[i]) | (host & fill) == host, t


def test_degraded_flag_off_whole_plane_failover_unchanged():
    """Flag OFF: a dead shard refuses every dispatch (the PR 17
    whole-plane CPU failover, byte-identical) and the step cache keys
    carry no micro_owner extension."""
    inc, mc, _pairs = build_pair()
    assert mc.degraded is False
    mc.kill_shard(0)
    assert not mc.degraded_serving
    assert mc.mesh_state() == 2
    with pytest.raises(ShardDead):
        mc.dispatch(mc.encode(["a/b"], batch=64))
    assert mc.failovers == 1 and mc.degraded_batches == 0
    mc.revive_shard(0)
    assert mc.mesh_state() == 0
    rows, _, _ = mesh_rows(mc, ["a/b"])
    assert sorted(rows[0]) == sorted(inc.match_host("a/b"))
    # PR 17 key shape verbatim: (batch, depth, kind) — no owner element
    assert all(len(k) == 3 for k in mc._steps)


def test_degraded_replicated_mask_and_micro_owner_migration():
    """Replicated scoped failover: the dead shard's answer segment is
    masked (rows decode exactly the LIVE shards' answers — the service
    CPU-fills the rest), micro filters never enter the fill set, and
    killing shard 0 migrates the micro merge point to the lowest live
    shard so wildcard-root answers stay on-device."""
    met = Metrics()
    inc, mc, _pairs = build_pair(degraded=True, metrics=met)
    topics = topics_for(24) + ["m/n", "q/b"]
    want = [set(inc.match_host(t)) for t in topics]
    mc.kill_shard(0)     # owns "m/n" AND the default micro merge point
    assert mc.degraded_serving and mc.mesh_state() == 1
    dead = mc.dead_aids()
    assert dead, "victim shard must own part of the corpus"
    micro_aids = set(mc._micro_filters.values())
    assert micro_aids and not (micro_aids & dead)
    rows, sp, _ = mesh_rows(mc, topics)
    assert not sp
    for t, r, w in zip(topics, rows, want):
        assert set(r) == w - dead, t
    # "q/b" matches only wildcard-root (micro) filters: fully on-device
    # through the MIGRATED merge owner
    i = topics.index("q/b")
    assert set(rows[i]) == want[i]
    assert mc.degraded_batches >= 1
    assert met.get("tpu.mesh.degraded_batches") >= 1
    assert met.get("tpu.mesh.state") == 1
    mc.revive_shard(0)
    rows2, _, _ = mesh_rows(mc, topics)
    assert [set(r) for r in rows2] == want


def test_degraded_ep_scoped_failover_row_accounting():
    """EP-routed degraded serving: EXACTLY the rows whose crc32-root
    owner is dead divert to the CPU trie; the other (tp-1)/tp of an
    owner-balanced batch stays on-device with bit-exact host parity
    (the dead shard's literal filters share no root with a live-owned
    row), and the divert set is counted on ``cpu_filled_rows``."""
    met = Metrics()
    tp = serve_mesh_shape(8)["tp"]
    roots: dict = {t: [] for t in range(tp)}
    i = 0
    while any(len(v) < 2 for v in roots.values()):
        r = f"r{i}"
        i += 1
        roots[shard_of_filter(f"{r}/a/+", tp)].append(r)
    inc = IncrementalNfa(depth=8)
    pairs = []
    for t in range(tp):
        for r in roots[t][:2]:
            for f in (f"{r}/a/+", f"{r}/b/#"):
                inc.add(f)
                pairs.append((f, inc.aid_of(f)))
    inc.add("+/m/#")
    pairs.append(("+/m/#", inc.aid_of("+/m/#")))
    mc = MultichipMatcher(depth=8, ep=True, ep_slack=4.0,
                          degraded=True, metrics=met)
    mc.rebuild(pairs)
    assert mc.apply_pending()
    batch = 64
    topics = [f"{roots[k % tp][(k // tp) % 2]}/a/x" for k in range(batch)]
    rows0, sp0, _ = mesh_rows(mc, topics, batch=batch)
    assert not sp0 and mc.ep_dispatches == 1
    victim = 1
    mc.kill_shard(victim)
    assert mc.degraded_serving
    rows, sp, _ = mesh_rows(mc, topics, batch=batch)
    dead_rows = {k for k, t in enumerate(topics)
                 if shard_of_filter(t, tp) == victim}
    assert set(sp) == dead_rows
    assert len(sp) == batch // tp          # owner-balanced: exactly 1/tp
    for k, t in enumerate(topics):
        if k not in dead_rows:
            assert sorted(rows[k]) == sorted(inc.match_host(t)), t
    assert mc.cpu_filled_rows == len(dead_rows)
    assert met.get("tpu.mesh.cpu_filled_rows") == len(dead_rows)
    assert met.get("tpu.mesh.degraded_batches") >= 1


def test_degraded_double_kill_cpu_only_then_staged_readmit():
    """The double-kill rung: two dead shards drop the plane to
    cpu-only (every dispatch refused), and the staged re-admit climbs
    back — lowest shard rebuilt + canaried first (serving resumes
    degraded around the remaining dead shard), then the second, back
    to healthy with bit parity."""
    inc, mc, pairs = build_pair(degraded=True)
    topics = topics_for(24) + ["m/n", "b/c"]
    mc.kill_shard(0)
    assert mc.degraded_serving and mc.mesh_state() == 1
    mc.kill_shard(1)
    assert not mc.degraded_serving and mc.mesh_state() == 2
    with pytest.raises(ShardDead):
        mc.dispatch(mc.encode(topics, batch=64))
    for t in (0, 1):
        assert mc.rebuild_shard(t, pairs) >= 0.0
        ctop = mc.canary_topics(t)
        assert ctop, "victim shards own filters in this corpus"
        crows, csp = mc.canary_rows(ctop, 64, t)
        fill_parity(inc, mc, ctop, crows, csp,
                    fill=mc.dead_aids(exclude=t))
        mc.revive_shard(t)
        assert mc.mesh_state() == (1 if t == 0 else 0)
        if t == 0:
            # middle rung: degraded(S) serving around shard 1
            rows, sp, _ = mesh_rows(mc, topics)
            assert not sp
            fill_parity(inc, mc, topics, rows, sp)
    rows, sp, _ = mesh_rows(mc, topics)
    assert not sp
    for t_, r in zip(topics, rows):
        assert sorted(r) == sorted(inc.match_host(t_)), t_
    assert mc.rebuilds == 2


def test_rebuild_shard_delta_tail_replay_and_readmit_zero_stale():
    """Online rebuild converges on the LIVE filter state: a filter
    added while its owner shard was dead is replayed from the service
    pairs into the fresh subtable, the canary proves bit parity, and
    after re-admission the delta filter serves on-device (zero-stale
    re-admission)."""
    inc, mc, pairs = build_pair(degraded=True)
    f = "delta/x/+"
    t = shard_of_filter(f, mc.tp)
    mc.kill_shard(t)
    inc.add(f)
    pairs.append((f, inc.aid_of(f)))      # the delta lands while dead
    assert mc.rebuild_shard(t, pairs) >= 0.0
    ctop = mc.canary_topics(t)
    assert any(c.startswith("delta/") for c in ctop)
    crows, csp = mc.canary_rows(ctop, 64, t)
    csps = set(csp)
    for i, topic in enumerate(ctop):
        if i in csps:
            continue
        assert sorted(crows[i]) == sorted(inc.match_host(topic)), topic
    mc.revive_shard(t)
    assert mc.mesh_state() == 0
    rows, sp, _ = mesh_rows(mc, ["delta/x/y"])
    assert not sp
    assert inc.aid_of(f) in rows[0]
    assert sorted(rows[0]) == sorted(inc.match_host("delta/x/y"))


def test_shard_kill_races_apply_pending_restack():
    """Satellite chaos: a shard dies WHILE ``apply_pending`` is
    mid-restack (inside the maintenance lock).  The swap completes on
    the full grid, degraded serving picks the death up afterwards with
    the fill contract intact, and the online rebuild re-admits it with
    parity — maintenance and the health ladder never tear the table."""
    inc, mc, pairs = build_pair(degraded=True)
    victim = 1
    real = mc._restack

    def racy():
        mc.kill_shard(victim)     # death lands mid-maintenance
        real()

    mc._restack = racy
    try:
        for f in ("race/a/+", "race/b/#"):
            inc.add(f)
            pairs.append((f, inc.aid_of(f)))
        mc.rebuild(pairs)          # the full-restack (swap) path
        assert mc.apply_pending()
    finally:
        mc._restack = real
    assert mc.dead_shards == [victim] and mc.degraded_serving
    topics = topics_for(16) + ["race/a/x", "b/c"]
    rows, sp, _ = mesh_rows(mc, topics)
    assert not sp
    fill_parity(inc, mc, topics, rows, sp)
    assert mc.rebuild_shard(victim, pairs) >= 0.0
    mc.revive_shard(victim)
    rows2, sp2, _ = mesh_rows(mc, topics)
    assert not sp2
    for t, r in zip(topics, rows2):
        assert sorted(r) == sorted(inc.match_host(t)), t


def test_node_shard_kill_races_compaction_swap_then_readmits():
    """Satellite chaos at node level: kill a shard in the compaction
    swap window (the service just bumped ``_table_gen``; the mesh
    repartition hasn't landed).  The swap completes, the health ladder
    raises the degraded alarm, and the supervised rebuild re-admits
    the shard through the canary — serving never stops."""

    async def main():
        import tempfile

        seg = tempfile.mkdtemp()
        node = make_node(**{
            "match.segments.enable": True,
            "match.segments.dir": seg,
            "match.segments.compact_interval": 0.2,
            "match.segments.compact_min_mutations": 1,
            "match.multichip.degraded.enable": True,
            "supervisor.backoff_base": 0.005,
            "supervisor.backoff_max": 0.05,
        })
        await node.start()
        ms = node.match_service
        assert ms is not None and ms.mc is not None and ms.mc.degraded
        try:
            b = node.broker
            if "c1" not in b.sessions:
                b.open_session("c1")
            for i in range(8):
                b.subscribe("c1", f"swap/{i}/+")
            assert await settle(lambda: ms.ready and ms.mc.ready)
            gen0 = ms.mc.gen
            assert await settle(lambda: ms._table_gen >= 1, timeout=30)
            ms.mc.kill_shard(1)            # mid-swap-window death
            assert await settle(
                lambda: ms.mc.ready and ms.mc.gen > gen0, timeout=30)
            # the supervised rebuild re-admits it (canary-gated)
            assert await settle(lambda: not ms.mc.dead_shards,
                                timeout=60)
            assert ms.mc.rebuilds >= 1
            assert await settle(
                lambda: not node.observed.alarms.is_active(
                    "mesh_degraded"), timeout=30)
            await ms.prefetch("swap/3/x")
            assert ms.hint_routes("swap/3/x") is not None
            assert node.observed.metrics.get("tpu.mesh.state") == 0
        finally:
            await node.stop()

    run(main())


def test_node_canary_failure_blocks_readmit_until_parity():
    """A failing bit-parity canary keeps the rebuilt shard OUT:
    ``tpu.mesh.readmit_canary_fails`` counts the refusals, the
    degraded alarm stays up, and the moment parity is restored the
    shard re-admits and the alarm clears."""

    async def main():
        node = make_node(**{
            "match.multichip.degraded.enable": True,
            "supervisor.backoff_base": 0.005,
            "supervisor.backoff_max": 0.05,
        })
        await node.start()
        ms = node.match_service
        assert ms is not None and ms.mc is not None
        try:
            b = node.broker
            if "c1" not in b.sessions:
                b.open_session("c1")
            for i in range(6):
                b.subscribe("c1", f"cn/{i}/+")
            assert await settle(lambda: ms.ready and ms.mc.ready)

            async def failing(t):
                return False

            ms._mesh_canary = failing     # parity probe refuses
            ms.mc.kill_shard(0)
            await ms.prefetch("cn/0/x")   # serve pass trips the watch
            m = node.observed.metrics
            assert await settle(
                lambda: m.get("tpu.mesh.readmit_canary_fails") >= 2,
                timeout=30)
            assert ms.mc.dead_shards == [0]      # stays OUT
            assert node.observed.alarms.is_active("mesh_degraded")
            info = ms.mesh_info()
            assert info["alarmed"] and info["rebuilding"]
            del ms._mesh_canary           # parity restored
            assert await settle(lambda: not ms.mc.dead_shards,
                                timeout=60)
            assert await settle(
                lambda: not node.observed.alarms.is_active(
                    "mesh_degraded"), timeout=30)
            assert ms.mc.readmit_canary_fails >= 2
            assert ms.mc.rebuilds >= 1
        finally:
            await node.stop()

    run(main())

# ---------------------------------------------------------------------------
# load-adaptive plane (ISSUE 20): capacity auto-resize + popularity
# placement
# ---------------------------------------------------------------------------

def _colliding_roots(tp, n, prefix="h"):
    """First ``n`` synthetic roots that crc32-hash to ONE shard — the
    skew every popularity test needs."""
    out, i = [], 0
    while len(out) < n:
        r = f"{prefix}{i}"
        if shard_of_filter(r, tp) == shard_of_filter(f"{prefix}0", tp):
            out.append(r)
        i += 1
    return out


def test_greedy_balance_pure_strict_improvement():
    """The pure core: every move is strictly improving (hottest root
    whose load fits inside the hi-lo gap), the worst shard's load
    drops, budget 0 is a no-op, and a balanced input stays put."""
    from emqx_tpu.parallel.prefix_ep import greedy_balance

    loads = {"h0": 100.0, "h1": 90.0, "h2": 80.0, "h3": 70.0,
             "c0": 1.0}
    owners = {"h0": 0, "h1": 0, "h2": 0, "h3": 0, "c0": 1}

    def worst(o):
        per = [0.0] * 4
        for w, t in o.items():
            per[t] += loads[w]
        return max(per)

    new, moved = greedy_balance(loads, owners, 4, 64)
    assert moved >= 3
    assert worst(new) < worst(owners)
    assert worst(new) <= 100.0          # no shard above the hottest root
    assert set(new) == set(owners)      # no root invented or dropped
    assert all(0 <= t < 4 for t in new.values())
    # budget 0: identity
    same, n0 = greedy_balance(loads, owners, 4, 0)
    assert n0 == 0 and same == owners
    # already balanced: strict improvement finds nothing to move
    flat = {f"r{i}": 10.0 for i in range(4)}
    fown = {f"r{i}": i for i in range(4)}
    kept, nk = greedy_balance(flat, fown, 4, 64)
    assert nk == 0 and kept == fown


def test_autotune_flag_off_byte_identical():
    """Flag off (the default ctor): no load is noted, no resize ever
    triggers, the placement map stays empty, ``shard_of`` is the pure
    crc32 hash, and rows are bit-identical to an autotune-on matcher
    that never crossed a threshold."""
    inc, mc_off, pairs = build_ep_pair(ep_slack=4.0)
    mc_on = MultichipMatcher(depth=8, ep=True, ep_slack=4.0,
                             ep_autotune=True)
    mc_on.rebuild(pairs)
    assert mc_on.apply_pending()
    topics = topics_for(48)
    rows_off, sp_off, _ = mesh_rows(mc_off, topics)
    rows_on, sp_on, _ = mesh_rows(mc_on, topics)
    assert sp_off == sp_on
    assert [sorted(r) for r in rows_off] == [sorted(r) for r in rows_on]
    assert not mc_off.ep_autotune
    assert mc_off._cap_class == 0 and mc_off._placement == {}
    assert mc_off.ep_resizes == 0 and not mc_off._root_load.any()
    assert mc_off.plan_rebalance() == 0     # flag off: a no-op
    assert mc_off._placement_next is None
    for f in FILTERS:
        assert mc_off.shard_of(f) == shard_of_filter(f, mc_off.tp)
    assert mc_off.ep_capacity(64) == mc_on.ep_capacity(64)
    # autotune on but idle: still byte-identical state
    assert mc_on._cap_class == 0 and mc_on._placement == {}


def test_overflow_ewma_grow_rearms_warn_latch_rows_complete(caplog):
    """EWMA-triggered grow: a hot root overflowing every source slice
    crosses the grow threshold, the grid grows on a background thread
    while EVERY row of every batch stays complete (fail-open, zero
    failover strikes), the grow zeroes the EWMA and re-arms the
    warn-once latch, and the SECOND regression at the grown class
    warns again (satellite: the latch must reset on grow)."""
    import logging
    import time

    # grow_threshold ABOVE the warn threshold so each grow happens
    # after the warn fired: warn/grow at class 0, re-warn/grow at 1
    inc, mc, _pairs = build_ep_pair(
        ep_slack=0.5, ep_autotune=True, ep_grow_threshold=0.6)
    assert mc.ep_autotune and mc._cap_class == 0
    topics = [f"x/{i}/z" for i in range(56)] + ["x/y/z"] * 8
    cap0 = mc.ep_capacity(64)
    with caplog.at_level(logging.WARNING,
                         logger="emqx_tpu.parallel.multichip_serve"):
        deadline = time.monotonic() + 120.0
        complete = True
        while mc.ep_resizes < 2 and time.monotonic() < deadline:
            rows, sp, _ = mc.readback(
                mc.dispatch(mc.encode(topics, batch=64)), len(topics))
            spset = set(sp)
            complete = complete and all(
                (sorted(inc.match_host(t)) if k in spset
                 else sorted(rows[k])) == sorted(inc.match_host(t))
                for k, t in enumerate(topics))
        while mc._resize_busy and time.monotonic() < deadline:
            time.sleep(0.01)
    assert mc.ep_resizes >= 2, "EWMA never triggered the grow"
    assert mc._cap_class >= 2
    assert complete, "rows dropped during the compile window"
    assert mc.failovers == 0            # zero breaker strikes
    assert mc.ep_capacity(64) > cap0
    warns = [r for r in caplog.records if "overflow EWMA" in r.message]
    assert len(warns) >= 2, "grow must re-arm the warn-once latch"
    # the flip reset the measurement state for the new grid
    grows = [r for r in caplog.records if "grew to capacity" in r.message]
    assert len(grows) >= 2
    # post-grow serve on the wider grid still bit-complete
    rows, sp, _ = mc.readback(
        mc.dispatch(mc.encode(topics, batch=64)), len(topics))
    spset = set(sp)
    for k, t in enumerate(topics):
        if k not in spset:
            assert sorted(rows[k]) == sorted(inc.match_host(t)), t
    # the last readback may have kicked one more grow: drain it so the
    # compile thread can't leak CPU into the rest of the suite
    assert mc.drain_resize(120.0)


def test_kernel_cache_grow_compiles_ahead_no_dispatch_parks():
    """With a kernel cache attached the resize worker compiles the
    grown grid THROUGH the cache before flipping: a post-flip
    dispatch with ``block_compile=False`` hits — never a CompileMiss,
    so no serve dispatch ever parks behind XLA."""
    import time

    from emqx_tpu.ops.kernel_cache import MatchKernelCache

    kc = MatchKernelCache()
    inc, mc, _pairs = build_ep_pair(
        ep_slack=0.5, ep_autotune=True, ep_grow_threshold=0.05,
        kernel_cache=kc)
    topics = [f"x/{i}/z" for i in range(64)]
    enc = mc.encode(topics, batch=64)
    mc.readback(mc.dispatch(enc, block_compile=True), len(topics))
    deadline = time.monotonic() + 120.0
    while mc.ep_resizes < 1 and time.monotonic() < deadline:
        mc.readback(mc.dispatch(enc, block_compile=True), len(topics))
    while mc._resize_busy and time.monotonic() < deadline:
        time.sleep(0.01)
    assert mc.ep_resizes >= 1 and mc._cap_class >= 1
    # the serving contract: the grown-grid step is already cached
    rows, sp, _ = mc.readback(
        mc.dispatch(enc, block_compile=False), len(topics))
    spset = set(sp)
    for k, t in enumerate(topics):
        if k not in spset:
            assert sorted(rows[k]) == sorted(inc.match_host(t)), t
    assert mc.drain_resize(120.0)


def test_plan_rebalance_stages_and_rebuild_applies_with_parity():
    """The popularity pass STAGES; only the next rebuild applies: the
    override map is invisible to serving until the repartition swap,
    then the moved hot roots spread across shards, ``_word_owner``
    routes to the new owners, and rows stay bit-parity with the host
    oracle and the replicated backend."""
    mc = MultichipMatcher(depth=8, ep=True, ep_slack=4.0,
                          ep_autotune=True, balance_budget=64)
    hot = _colliding_roots(mc.tp, 4)
    home = shard_of_filter(f"{hot[0]}/a/+", mc.tp)
    inc = IncrementalNfa(depth=8)
    pairs = []
    for r in hot:
        for f in (f"{r}/a/+", f"{r}/b/#"):
            inc.add(f)
            pairs.append((f, inc.aid_of(f)))
    mc.rebuild(pairs)
    assert mc.apply_pending()
    assert all(mc.shard_of(f"{r}/a/x") == home for r in hot)
    topics = [f"{hot[k % 4]}/a/x" for k in range(64)]
    for _ in range(3):                      # accumulate the load slab
        mesh_rows(mc, topics)
    assert mc._root_load.any()
    moved = mc.plan_rebalance()
    assert moved >= 1
    # staged, not applied: serving still routes to the crc32 home
    assert mc._placement == {} and mc._placement_next
    assert all(mc.shard_of(f"{r}/a/x") == home for r in hot)
    rows0, sp0, _ = mesh_rows(mc, topics)
    assert not sp0
    # the next rebuild (the compaction-swap cadence) applies the map
    mc.rebuild(pairs)
    assert mc.apply_pending()
    assert mc._placement and mc._placement_next is None
    owners = {mc.shard_of(f"{r}/a/x") for r in hot}
    assert len(owners) > 1, "hot roots must spread after the remap"
    for r in hot:                            # device routing agrees
        wid = mc.vocab[r]
        assert int(mc._word_owner[wid]) == mc.shard_of(f"{r}/a/x")
    mc_rep = MultichipMatcher(depth=8)
    mc_rep.rebuild(pairs)
    assert mc_rep.apply_pending()
    rows_e, sp_e, _ = mesh_rows(mc, topics)
    rows_r, sp_r, _ = mesh_rows(mc_rep, topics)
    assert not sp_e and not sp_r
    for t, re_, rr, r0 in zip(topics, rows_e, rows_r, rows0):
        want = sorted(inc.match_host(t))
        assert sorted(re_) == sorted(rr) == sorted(r0) == want, t
    assert mc.ep_rebalances == 1 and mc.moved_roots == moved


def test_placement_segments_roundtrip_v3_and_skew_rejection(tmp_path):
    """The override map rides the v3 segment set: a cold start
    restores placement bit-identical BEFORE the restack (the restored
    partition and its shard_of agree); a placement tampered after the
    save fails the per-segment placement_crc guard even with a
    recomputed manifest checksum (torn-save mixed generations); a v2
    manifest is rejected outright."""
    mc = MultichipMatcher(depth=8, ep=True, ep_slack=4.0,
                          ep_autotune=True, balance_budget=64)
    hot = _colliding_roots(mc.tp, 4)
    inc = IncrementalNfa(depth=8)
    pairs = []
    for r in hot:
        for f in (f"{r}/a/+", f"{r}/b/#"):
            inc.add(f)
            pairs.append((f, inc.aid_of(f)))
    mc.rebuild(pairs)
    assert mc.apply_pending()
    topics = [f"{hot[k % 4]}/a/x" for k in range(64)]
    for _ in range(3):
        mesh_rows(mc, topics)
    assert mc.plan_rebalance() >= 1
    mc.rebuild(pairs)
    assert mc.apply_pending()
    assert mc._placement
    d = str(tmp_path)
    mc.save_segments(d, epoch=inc.epoch)
    want, _, _ = mesh_rows(mc, topics)

    mc2 = MultichipMatcher(depth=8, ep=True, ep_slack=4.0,
                           ep_autotune=True)
    assert mc2.load_segments(d, expect_epoch=inc.epoch)
    assert mc2._placement == mc._placement
    assert mc2.apply_pending()
    assert all(mc2.shard_of(f"{r}/a/x") == mc.shard_of(f"{r}/a/x")
               for r in hot)
    got, sp, _ = mesh_rows(mc2, topics)
    assert not sp
    assert [sorted(r) for r in got] == [sorted(r) for r in want]

    # tamper the persisted owners + recompute the manifest checksum:
    # the per-segment placement_crc (cut under the ORIGINAL map) must
    # reject the mixed generation
    mpath = os.path.join(d, "multichip", "aid_maps.npz")
    maps = dict(np.load(mpath))
    assert len(maps["ps"]), "round trip must persist real overrides"
    ps = np.asarray(maps["ps"], np.int32)
    ps[0] = (ps[0] + 1) % mc.tp
    maps["ps"] = ps
    np.savez(mpath, **maps)
    manp = os.path.join(d, "multichip", "manifest.json")
    with open(manp) as f:
        meta = json.load(f)
    core = {k: meta[k] for k in
            ("version", "epoch", "tp", "depth", "native")}
    meta["checksum"] = MultichipMatcher._manifest_checksum(core, maps)
    with open(manp, "w") as f:
        json.dump(meta, f, sort_keys=True)
    mc3 = MultichipMatcher(depth=8, ep=True, ep_autotune=True)
    assert not mc3.load_segments(d, expect_epoch=inc.epoch)

    # a v2 manifest (pre-placement format) is rejected by version
    meta["version"] = 2
    with open(manp, "w") as f:
        json.dump(meta, f, sort_keys=True)
    mc4 = MultichipMatcher(depth=8, ep=True, ep_autotune=True)
    assert not mc4.load_segments(d, expect_epoch=inc.epoch)


def test_rebalance_defers_while_degraded_then_readmit_post_remap():
    """Rebalance racing the degraded mesh: while ANY shard is dead the
    balance pass stages NOTHING (roots never remap onto a dead owner);
    after re-admission the pass stages and applies, and a shard killed
    POST-remap rebuilds + canaries against the remapped placement (the
    canary judges the placement the rebuild was built against)."""
    mc = MultichipMatcher(depth=8, ep=True, ep_slack=4.0,
                          ep_autotune=True, balance_budget=64,
                          degraded=True)
    hot = _colliding_roots(mc.tp, 4)
    home = shard_of_filter(f"{hot[0]}/a/+", mc.tp)
    inc = IncrementalNfa(depth=8)
    pairs = []
    for r in hot:
        for f in (f"{r}/a/+", f"{r}/b/#"):
            inc.add(f)
            pairs.append((f, inc.aid_of(f)))
    mc.rebuild(pairs)
    assert mc.apply_pending()
    topics = [f"{hot[k % 4]}/a/x" for k in range(64)]
    for _ in range(3):
        mesh_rows(mc, topics)
    # dead shard: the pass defers outright
    mc.kill_shard(home)
    assert mc.plan_rebalance() == 0
    assert mc._placement_next is None and mc.ep_rebalances == 0
    rows_d, sp_d, _ = mesh_rows(mc, topics)   # scoped failover serves
    spset = set(sp_d)
    assert spset, "hot rows owned by the dead shard must divert"
    for k, t in enumerate(topics):
        if k not in spset:
            assert sorted(rows_d[k]) == sorted(inc.match_host(t)), t
    # readmit, then the pass stages and the rebuild applies
    assert mc.rebuild_shard(home, pairs) >= 0.0
    mc.revive_shard(home)
    assert mc.plan_rebalance() >= 1
    mc.rebuild(pairs)
    assert mc.apply_pending()
    moved = [r for r in hot
             if mc.shard_of(f"{r}/a/x") != home]
    assert moved, "the remap must have moved a hot root off home"
    # post-remap kill of a MOVED root's new owner: the online rebuild
    # partitions by the live (overridden) placement and the canary
    # proves parity against exactly that placement
    t2 = mc.shard_of(f"{moved[0]}/a/x")
    mc.kill_shard(t2)
    assert mc.plan_rebalance() == 0           # still defers while dead
    assert mc.rebuild_shard(t2, pairs) >= 0.0
    ctop = mc.canary_topics(t2)
    assert any(c.startswith(f"{moved[0]}/") for c in ctop)
    crows, csp = mc.canary_rows(ctop, 64, t2)
    csps = set(csp)
    for i, topic in enumerate(ctop):
        if i not in csps:
            assert sorted(crows[i]) == sorted(inc.match_host(topic)), \
                topic
    mc.revive_shard(t2)
    rows_p, sp_p, _ = mesh_rows(mc, topics)
    assert not sp_p
    for t, r in zip(topics, rows_p):
        assert sorted(r) == sorted(inc.match_host(t)), t


def test_ep_rebalance_fault_injection_noop():
    """An injected ``ep.rebalance`` fault raises BEFORE anything is
    staged (kill mid-rebalance = no-op): placement unchanged, nothing
    pending, and the next un-faulted pass stages normally."""
    mc = MultichipMatcher(depth=8, ep=True, ep_slack=4.0,
                          ep_autotune=True, balance_budget=64)
    hot = _colliding_roots(mc.tp, 4)
    inc = IncrementalNfa(depth=8)
    pairs = []
    for r in hot:
        inc.add(f"{r}/a/+")
        pairs.append((f"{r}/a/+", inc.aid_of(f"{r}/a/+")))
    mc.rebuild(pairs)
    assert mc.apply_pending()
    topics = [f"{hot[k % 4]}/a/x" for k in range(64)]
    for _ in range(3):
        mesh_rows(mc, topics)
    faultinject.install(FaultInjector([
        {"point": "ep.rebalance", "action": "raise", "times": 1},
    ]))
    try:
        with pytest.raises(faultinject.InjectedFault):
            mc.plan_rebalance()
        assert mc._placement == {} and mc._placement_next is None
        assert mc.ep_rebalances == 0
        rows, sp, _ = mesh_rows(mc, topics)   # delivery holds
        assert not sp
        for t, r in zip(topics, rows):
            assert sorted(r) == sorted(inc.match_host(t)), t
        assert mc.plan_rebalance() >= 1       # un-faulted: stages
        assert mc._placement_next
    finally:
        faultinject.uninstall()


# ---------------------------------------------------------------------------
# the step's one packed operand, placed where the step reads it (ISSUE 37)
# ---------------------------------------------------------------------------

MESHES = {"dp1xtp4": (4, 4), "dp2xtp2": (2, 4),    # (tp, devices)
          "dp2xtp4": (4, 8)}
MODES = {"replicated": {}, "routed": {"ep": True},
         "compact": {"ep": True, "ep_compact": True}}
# sha1 of json.dumps([sorted rows, spilled]) of ``seeded_case`` as the
# tree BEFORE the packed operand answered it (commit 27c9d81: three
# ``jnp.asarray`` operands, a twelve-argument step), per mode and mesh;
# where tp is 2 no bucket overflows and the three modes agree.  The
# routed answers of that tree were five arrays decoded segment by
# segment, with ``ep_compact`` off (routed) and on (compact)
_ALL = "a7623736c8b0a5d4eaf713fc4f3bea833ce94159"
_SPILL8 = "ec1985d2df9881d30b19feccbd7786b12101e83b"
_SPILL_DP2 = "b6f8e0aaaf679ddec17bc593c199a4ac8ff8e7cd"
PARENT_DIGESTS = {
    ("replicated", "dp1xtp4"): _ALL, ("replicated", "dp2xtp2"): _ALL,
    ("replicated", "dp2xtp4"): _ALL,
    ("routed", "dp1xtp4"): _SPILL8, ("routed", "dp2xtp2"): _ALL,
    ("routed", "dp2xtp4"): _SPILL_DP2,
    ("compact", "dp1xtp4"): _SPILL8, ("compact", "dp2xtp2"): _ALL,
    ("compact", "dp2xtp4"): _SPILL_DP2,
}


def seeded_case(seed=3700000042, n_filters=400, n_topics=56):
    rng = np.random.default_rng(seed)
    roots = [f"r{i}" for i in range(12)]
    words = [f"w{i}" for i in range(6)]

    def path(lo, hi):
        return [str(rng.choice(roots))] + [
            str(rng.choice(words)) for _ in range(rng.integers(lo, hi))]

    filters = set()
    while len(filters) < n_filters:
        ws = path(1, 5)
        kind = rng.random()
        if kind < 0.45:
            ws[int(rng.integers(0, len(ws)))] = "+"
        elif kind < 0.75:
            ws = ws[:int(rng.integers(1, len(ws)))] + ["#"]
        filters.add("/".join(ws))
    topics = ["/".join(path(1, 5)) for _ in range(n_topics - 24)]
    # a volley on one root: over a routed bucket's capacity at tp 4, so
    # the spill set is not empty there
    topics += ["/".join(["r3"] + path(1, 4)[1:]) for _ in range(24)]
    return sorted(filters), topics


def mesh_matcher(mesh, filters=FILTERS, **mc_kw):
    import jax

    tp, n = MESHES[mesh]
    return build_pair(filters=filters, tp=tp, devices=jax.devices()[:n],
                      **mc_kw)


def decoded(mc, res, n):
    rows, spilled, _ = mc.readback(res, n)
    return [sorted(r) for r in rows], list(spilled)


@pytest.mark.parametrize("mesh", MESHES)
@pytest.mark.parametrize("routed", [True, False],
                         ids=["routed", "replicated"])
def test_the_operand_is_placed_in_the_steps_own_input_sharding(mesh, routed):
    """What ``_put_operands`` returns is committed, held by every device
    of the mesh, and equivalent to what the compiled step says it reads
    for that argument: the call has nothing to reshard.  Both forms of
    the step: the kernel cache's AOT executable and the jitted one."""
    import jax

    from emqx_tpu.ops.kernel_cache import MatchKernelCache

    tp, n = MESHES[mesh]
    for kc in (MatchKernelCache(), None):
        _inc, mc, _pairs = mesh_matcher(mesh, ep=routed, kernel_cache=kc)
        assert (mc.dp, mc.tp) == (n // tp, tp)
        enc = mc.encode(topics_for(40), batch=64)
        assert mc._routed_for(64) is routed
        step = mc._step_for((64, 8), routed=routed)
        packed = mc._put_operands(enc)
        assert packed.committed
        assert packed.shape == (64, 8 + 2) and packed.dtype == np.int32
        assert packed.sharding.device_set == set(jax.devices()[:n])
        compiled = step if kc is not None else \
            step.lower(packed, *mc._arrs).compile()
        reads = compiled.input_shardings[0]
        assert len(reads) == 1 + len(mc._arrs)
        assert packed.sharding.is_equivalent_to(reads[0], packed.ndim)
        for arr, sh in zip(mc._arrs, reads[1:]):
            # (None: an argument this step does not read, as the
            # replicated one does not read ``word_owner``)
            assert sh is None or arr.sharding.is_equivalent_to(
                sh, arr.ndim)
        # and a row block lives where ``dp`` puts it: whole on every
        # device at dp 1, halves at dp 2
        assert {s.data.shape for s in packed.addressable_shards} == {
            (64 // mc.dp, 10)}


@pytest.mark.parametrize("depth", [8, 16])
def test_pack_and_unpack_round_trip_the_encoded_batch(depth):
    _inc, mc, _pairs = build_pair(depth=depth)
    topics = topics_for(37) + ["$SYS/brokers/x", "a/" * (depth + 3) + "z"]
    words, lens, is_sys = mc.encode(topics, batch=64, depth=depth)
    words, lens, is_sys = map(np.asarray, (words, lens, is_sys))
    # the batch has what the format must carry: pad rows under the
    # D + 2 sentinel, an over-deep topic's D + 1, a $-topic's flag
    assert (lens == depth + 2).sum() == 64 - len(topics)
    assert lens[len(topics) - 1] == depth + 1
    assert is_sys[len(topics) - 2] and not is_sys[0]
    packed = mcs_mod.pack_operands(words, lens, is_sys)
    assert packed.shape == (64, depth + 2) and packed.dtype == np.int32
    w2, l2, s2 = mcs_mod.unpack_operands(packed)
    assert np.array_equal(w2, words) and w2.dtype == words.dtype
    assert np.array_equal(l2, lens)
    assert s2.dtype == np.bool_ and np.array_equal(s2, is_sys)


@pytest.mark.parametrize("mesh", MESHES)
@pytest.mark.parametrize("mode", MODES)
def test_the_packed_step_answers_as_the_three_operand_call_did(mode, mesh):
    """Rows and spilled indices of a seeded table and batch: equal to
    the three-operand form of the call (kept here, not in the package:
    three ``jnp.asarray`` operands on the default device, taken by a
    step of the old signature) and to what the tree before the packed
    operand answered (``PARENT_DIGESTS``)."""
    import hashlib

    import jax
    import jax.numpy as jnp

    filters, topics = seeded_case()
    inc, mc, _pairs = mesh_matcher(mesh, filters=filters, **MODES[mode])
    enc = mc.encode(topics, batch=64)
    got = decoded(mc, mc.dispatch(enc), len(topics))
    assert mc.ep_dispatches == (0 if mode == "replicated" else 1)
    step = mc._step_for((64, 8), routed=mode != "replicated")

    @jax.jit
    def three_operand_step(words, lens, is_sys, *tables):
        return step(jnp.concatenate(
            [words, lens[:, None], is_sys[:, None].astype(jnp.int32)],
            axis=1), *tables)

    res = three_operand_step(*(jnp.asarray(a) for a in enc), *mc._arrs)
    if mode != "replicated":
        mc._routed_live.add(id(res))    # as dispatch marks a routed handle
    assert decoded(mc, res, len(topics)) == got
    blob = json.dumps([got[0], got[1]]).encode()
    assert hashlib.sha1(blob).hexdigest() == PARENT_DIGESTS[mode, mesh]
    rows, spilled = got
    assert bool(spilled) == (PARENT_DIGESTS[mode, mesh] != _ALL)
    for i, (t, row) in enumerate(zip(topics, rows)):
        if i not in spilled:
            assert row == sorted(inc.match_host(t)), t


# ---------------------------------------------------------------------------
# the routed step's answer: one packed array in the served format
# ---------------------------------------------------------------------------

def deep_fanin(n_topics=24):
    """Topics of one root that each match 23 filters (every literal or
    ``+`` mask of its four levels, and ``#`` behind each prefix), so 24
    of them hold 552 ids: past the flat buffer of a 64-row block (512)
    and of a 32-row one (256)."""
    filters, topics = set(), []
    for i in range(n_topics):
        ws = ["deep", f"a{i}", f"b{i}", f"c{i}"]
        topics.append("/".join(ws))
        for k in range(1, 5):
            for mask in itertools.product((False, True), repeat=k - 1):
                lv = [ws[0]] + ["+" if m else w
                                for m, w in zip(mask, ws[1:k])]
                filters.add("/".join(lv + ["#"]))
                if k == 4:
                    filters.add("/".join(lv))
    return sorted(filters), topics


def routed_case(case):
    """``(filters, topics, matcher kwargs, shard killed)``: a volley on
    one root past its buckets at ``ep_slack`` 1.0, scoped failover around
    a dead shard, and a batch past the flat buffer."""
    if case == "flat_cap":
        return (*deep_fanin(), {"ep_slack": 4.0}, None)
    filters, topics = seeded_case()
    if case == "slack1":
        return filters, topics, {"ep_slack": 1.0}, None
    return filters, topics, {"ep_slack": 4.0, "degraded": True}, 1


# ``[sorted rows, spilled]`` digests of the routed cases as the tree
# before the packed answer decoded them (five arrays, segment by
# segment; commit 69c279d), ``ep_compact`` off and on alike; the
# flat-cap case has none: that tree had no flat buffer to run past
PARENT_ROUTED = {
    ("slack1", "dp1xtp4"): "3f8039ba3efd39fc81cddd7b6af4ac52cef6ff06",
    ("slack1", "dp2xtp4"): "351bc923a242a03c7cf7910f35ef61688730e6a7",
    ("degraded", "dp1xtp4"): "7eb54faa7c00278b0ce7097c227533c4a461c3fa",
    ("degraded", "dp2xtp4"): "7eb54faa7c00278b0ce7097c227533c4a461c3fa",
}


@pytest.mark.parametrize("mesh", ["dp1xtp4", "dp2xtp4"])
@pytest.mark.parametrize("case", ["slack1", "degraded", "flat_cap"])
def test_the_routed_answer_is_one_packed_array_that_decodes_as_before(
        case, mesh):
    """The routed step answers with ONE int32 array in ``decode_packed``'s
    format, a block a dp group, fetched as one buffer a block
    (``tpu.mesh.answer_buffers``).  Its rows and spill set are the
    parent tree's (with ``ep_compact`` off and on), every row it does
    not spill is the host trie's and the replicated step's, and a row
    the flat buffer cannot hold is spilled, never cut short."""
    import hashlib

    from emqx_tpu.ops.match_kernel import (SERVE_FLAT_MULT, decode_flat,
                                           decode_row_meta)

    filters, topics, kw, kill = routed_case(case)
    n = len(topics)
    got = []
    for compact in (False, True):
        met = Metrics()
        inc, mc, pairs = mesh_matcher(mesh, filters=filters, ep=True,
                                      ep_compact=compact, metrics=met, **kw)
        if kill is not None:
            mc.kill_shard(kill)
        res = mc.dispatch(mc.encode(topics, batch=64))
        raw = np.asarray(res)
        got.append(decoded(mc, res, n))
        assert met.get("tpu.mesh.answer_buffers") == mc.dp
        assert met.get("tpu.match.shard_dispatches") == 1
    assert got[0] == got[1]
    rows, spilled = got[0]
    if case in ("slack1", "degraded"):
        blob = json.dumps([rows, spilled]).encode()
        assert hashlib.sha1(blob).hexdigest() == PARENT_ROUTED[case, mesh]
    want = [sorted(inc.match_host(t)) for t in topics]
    dead = {r for r, t in enumerate(topics)
            if kill is not None and shard_of_filter(t, mc.tp) == kill}
    assert dead <= set(spilled) and (kill is None) == (not dead)

    # the array, block by block: row_meta, then the flat ids back to
    # back in row order and -1 behind them; the spill bit is the
    # device's fail-open set (the dead owner's rows join on the host)
    bl = 64 // mc.dp
    cap = SERVE_FLAT_MULT * bl
    assert raw.dtype == np.int32 and raw.shape == (mc.dp * (bl + cap),)
    on_device = []
    for j, blk in enumerate(raw.reshape(mc.dp, bl + cap)):
        nk, sp = decode_row_meta(blk[:bl])
        flat = blk[bl:]
        assert (flat[min(int(nk.sum()), cap):] == -1).all()
        offs = np.cumsum(nk) - nk
        for i, seg in enumerate(decode_flat(flat, nk, 1 << 16)):
            r = j * bl + i
            if r >= n:      # a pad row (flagged as any row past the cap)
                assert nk[i] == 0 and bool(sp[i]) == (offs[i] > cap)
                continue
            if sp[i]:
                on_device.append(r)
            elif r not in dead:
                assert sorted(seg.tolist()) == want[r], topics[r]
            # a row past the flat buffer is spilled, and only such a row
            # of this corpus is
            assert (offs[i] + nk[i] > cap) <= bool(sp[i])
            if case == "flat_cap":
                assert (offs[i] + nk[i] > cap) == bool(sp[i])
                assert nk[i] == len(want[r]) == 23
    assert sorted(set(on_device) | dead) == spilled
    assert spilled and len(spilled) < n
    # the replicated step and the host trie agree on every row kept
    rep_met = Metrics()
    _inc, rep, _pairs = mesh_matcher(mesh, filters=filters, metrics=rep_met)
    rows_r, sp_r = decoded(rep, rep.dispatch(rep.encode(topics, batch=64)),
                           n)
    assert rep.ep_dispatches == 0 and not sp_r
    assert rows_r == want
    for r in range(n):
        if r not in spilled:
            assert rows[r] == want[r], topics[r]
    # five arrays: ids and counts a block of (dp, tp) each, three vectors
    assert rep_met.get("tpu.mesh.answer_buffers") == \
        2 * rep.dp * rep.tp + 3 * rep.dp
