"""Streaming table lifecycle (ISSUE 9): persistent compacted segments,
background delta compaction with atomic swap, dirty-region device
upload, and the padded-shape kernel compile cache.

Flag off (``match.segments.enable = false``, the default), every
structure is inert and the serve path is the PR-8 lifecycle — asserted
here and covered by the pre-existing match suites, which this PR keeps
passing unchanged.
"""

import asyncio
import os

import numpy as np
import pytest

from emqx_tpu.broker import Broker, SubOpts
from emqx_tpu.broker.match_service import MatchService
from emqx_tpu.observe.metrics import Metrics
from emqx_tpu.ops.device_table import DeviceNfa
from emqx_tpu.ops.incremental import IncrementalNfa
from emqx_tpu.ops.kernel_cache import CompileMiss, MatchKernelCache
from emqx_tpu.ops.match_kernel import decode_packed
from emqx_tpu.storage.segments import (
    SegmentError, load_segment, restore_incremental, save_segment,
)


def run(coro):
    return asyncio.run(coro)


async def settle(pred, timeout=30.0, interval=0.01):
    deadline = asyncio.get_running_loop().time() + timeout
    while asyncio.get_running_loop().time() < deadline:
        if pred():
            return True
        await asyncio.sleep(interval)
    return pred()


def make_service(broker, seg_dir, **kw):
    kw.setdefault("depth", 8)
    kw.setdefault("table", "python")
    kw.setdefault("bypass_rate", 0.0)
    kw.setdefault("segments", True)
    kw.setdefault("segments_dir", str(seg_dir))
    kw.setdefault("compact_interval_s", 0.05)
    kw.setdefault("compact_min_mutations", 1)
    kw.setdefault("metrics", Metrics())
    return MatchService(broker, **kw)


def subscribe_many(b, filters, sessions=16):
    for i, flt in enumerate(filters):
        cid = f"s{i % sessions}"
        if cid not in b.sessions:
            b.open_session(cid)
        b.subscribe(cid, flt, SubOpts())


# ---------------------------------------------------------------------------
# segment round trip (load(save(T)) parity, aliases/aids stable)
# ---------------------------------------------------------------------------

def test_segment_round_trip_parity(tmp_path):
    inc = IncrementalNfa(depth=4)
    filters = [f"a/{i}/+" for i in range(500)] + ["x/#", "+/y", "only"]
    for f in filters:
        inc.add(f)
    inc.remove("a/7/+")           # free-list holes survive
    inc.remove("a/9/+")
    deep = {"d/e/e/p/x/y/z/+": inc.alloc_alias("d/e/e/p/x/y/z/+")}
    routing = {aid for aid, f in enumerate(inc.accept_filters)
               if f is not None}
    p = str(tmp_path / "seg.npz")
    save_segment(p, inc, deep=deep, routing_aids=routing)
    seg = load_segment(p)
    assert seg.kind == "state"
    inc2 = restore_incremental(seg)
    # arrays byte-identical => device matches byte-identical
    assert np.array_equal(inc.node_tab, inc2.node_tab)
    assert np.array_equal(inc.edge_tab, inc2.edge_tab)
    assert np.array_equal(inc.seeds, inc2.seeds)
    assert inc.vocab == inc2.vocab
    assert list(inc.accept_filters) == list(inc2.accept_filters)
    assert inc._alias_aids == inc2._alias_aids
    assert list(inc._free_aids) == list(inc2._free_aids)
    assert set(inc._free_sids) == set(inc2._free_sids)
    assert (inc.n_states, inc.n_edges, inc.n_filters) == \
        (inc2.n_states, inc2.n_edges, inc2.n_filters)
    # aids stable; host matches identical (incl. hole topics)
    for f in ("a/5/+", "x/#", "+/y", "only"):
        assert inc.aid_of(f) == inc2.aid_of(f)
    for t in ("a/5/k", "x/q/r", "z/y", "a/7/k", "only"):
        assert sorted(inc.match_host(t)) == sorted(inc2.match_host(t)), t
    # the restored table stays fully mutable
    assert inc2.add("fresh/+") and inc2.remove("a/11/+")
    assert not inc2.flush().empty


def test_segment_device_serve_parity(tmp_path):
    inc = IncrementalNfa(depth=4)
    for i in range(200):
        inc.add(f"r/{i}/+")
    p = str(tmp_path / "seg.npz")
    save_segment(p, inc, deep={}, routing_aids=set())
    inc2 = restore_incremental(load_segment(p))
    d1 = DeviceNfa(inc, active_slots=8, max_matches=16)
    d2 = DeviceNfa(inc2, active_slots=8, max_matches=16)
    from emqx_tpu.ops import encode_batch

    topics = [f"r/{i}/k" for i in range(20)]
    e1 = encode_batch(inc, topics, batch=32)
    e2 = encode_batch(inc2, topics, batch=32)
    r1 = d1.match(*e1)
    r2 = d2.match(*e2)
    assert np.array_equal(np.asarray(r1.matches), np.asarray(r2.matches))
    assert np.array_equal(np.asarray(r1.n_matches),
                          np.asarray(r2.n_matches))


def test_segment_checksum_reject_and_version_skew(tmp_path):
    inc = IncrementalNfa(depth=4)
    inc.add("a/+")
    p = str(tmp_path / "seg.npz")
    save_segment(p, inc, deep={}, routing_aids=set())
    raw = open(p, "rb").read()
    mid = len(raw) // 2
    with open(p, "wb") as f:
        f.write(raw[:mid] + bytes([raw[mid] ^ 0xFF]) + raw[mid + 1:])
    with pytest.raises(SegmentError):
        load_segment(p)
    with pytest.raises(SegmentError):
        load_segment(str(tmp_path / "missing.npz"))


def test_segment_lazy_hydration_defers_trie_relink(tmp_path):
    inc = IncrementalNfa(depth=4)
    for i in range(50):
        inc.add(f"a/{i}/+")
    p = str(tmp_path / "seg.npz")
    save_segment(p, inc, deep={}, routing_aids=set())
    inc2 = restore_incremental(load_segment(p))
    assert inc2._pending_trie is not None and inc2.root is None
    # any mutation/walk entry point hydrates on demand
    assert sorted(inc2.match_host("a/3/k")) == sorted(
        inc.match_host("a/3/k"))
    assert inc2._pending_trie is None and inc2.root is not None


# ---------------------------------------------------------------------------
# dirty-region device upload (grow-in-place instead of full re-upload)
# ---------------------------------------------------------------------------

def test_dirty_region_grow_in_place_skips_full_upload():
    inc = IncrementalNfa(depth=8, state_bucket=64)
    inc.track_regions = True
    dev = DeviceNfa(inc, active_slots=8, max_matches=16)
    dev.dirty_regions = True
    dev.dirty_full_threshold = 1.0   # threshold behavior tested below
    uploads0 = dev.uploads
    # grow the node table past 64 states with a bounded dirty set
    for i in range(120):
        inc.add(f"g/{i}/x/y")
    dev.sync()
    assert dev.uploads == uploads0, "resize paid a full upload"
    assert dev.grow_applies >= 1
    assert dev.dirty_rows_uploaded > 0
    node, edge, _ = (np.asarray(a) for a in dev.arrays())
    assert np.array_equal(node, inc.node_tab)
    assert np.array_equal(edge, inc.edge_tab)


def test_dirty_region_threshold_falls_back_to_full_upload():
    inc = IncrementalNfa(depth=8, state_bucket=64)
    inc.track_regions = True
    dev = DeviceNfa(inc, active_slots=8, max_matches=16)
    dev.dirty_regions = True
    dev.dirty_full_threshold = 0.0001   # everything is "too dirty"
    uploads0 = dev.uploads
    for i in range(120):
        inc.add(f"g/{i}/x/y")
    dev.sync()
    assert dev.uploads > uploads0      # full upload won, correctly
    assert dev.grow_applies == 0
    node, _, _ = (np.asarray(a) for a in dev.arrays())
    assert np.array_equal(node, inc.node_tab)


def test_dirty_region_off_keeps_legacy_full_upload():
    inc = IncrementalNfa(depth=8, state_bucket=64)
    dev = DeviceNfa(inc, active_slots=8, max_matches=16)
    uploads0 = dev.uploads
    for i in range(120):
        inc.add(f"g/{i}/x/y")
    dev.sync()
    assert dev.uploads > uploads0      # flag-off path byte-identical
    assert dev.grow_applies == 0


def test_compact_forces_full_upload_even_in_region_mode():
    inc = IncrementalNfa(depth=8, state_bucket=64)
    inc.track_regions = True
    dev = DeviceNfa(inc, active_slots=8, max_matches=16)
    dev.dirty_regions = True
    for i in range(50):
        inc.add(f"c/{i}/+")
    dev.sync()
    uploads0 = dev.uploads
    inc.compact()                       # wholesale rebuild: rows moved
    dev.sync()
    assert dev.uploads > uploads0
    node, edge, _ = (np.asarray(a) for a in dev.arrays())
    assert np.array_equal(node, inc.node_tab)
    assert np.array_equal(edge, inc.edge_tab)


# ---------------------------------------------------------------------------
# padded-shape kernel cache (pow2 resize served without a recompile)
# ---------------------------------------------------------------------------

def test_prewarmed_resize_serves_with_zero_compiles():
    """The compile-counter spy of the acceptance criteria: pre-warm the
    next pow2 shape, grow the table across the boundary, and the resize
    dispatch must be a pure cache hit — zero new compiles."""
    from emqx_tpu.ops import encode_batch

    inc = IncrementalNfa(depth=8, state_bucket=64, edge_bucket=1024)
    inc.track_regions = True
    dev = DeviceNfa(inc, active_slots=8, max_matches=16)
    dev.dirty_regions = True
    kc = MatchKernelCache()
    dev.kernel_cache = kc
    for i in range(20):
        inc.add(f"a/{i}/+")
    dev.sync()
    enc = encode_batch(inc, ["a/3/k"], batch=64)
    np.asarray(dev.serve(*enc))          # observe combo
    s, hb, _d = inc.shape_key()
    kc.prewarm_shape(2 * s, hb)         # the next pow2 state shape
    compiles0 = kc.compiles
    hits0 = kc.hits
    for i in range(20):                 # cross the 64-state boundary
        inc.add(f"b/{i}/x")
    dev.sync()
    assert inc.shape_key() == (2 * s, hb, 8)
    enc = encode_batch(inc, ["b/5/x"], batch=64)
    rows, _sp = decode_packed(dev.serve(*enc, block_compile=False), 1, 16)
    assert rows == [[inc.aid_of("b/5/x")]]
    assert kc.compiles == compiles0, "resize serve paid a compile"
    assert kc.hits > hits0


def test_compile_miss_raises_instead_of_stalling():
    from emqx_tpu.ops import encode_batch

    inc = IncrementalNfa(depth=8)
    inc.add("a/+")
    dev = DeviceNfa(inc, active_slots=8, max_matches=16)
    kc = MatchKernelCache()
    dev.kernel_cache = kc
    enc = encode_batch(inc, ["a/k"], batch=64)
    with pytest.raises(CompileMiss):
        dev.serve(*enc, block_compile=False)
    # the miss kicked a background compile: the same key eventually hits
    import time

    for _ in range(400):
        if kc.info()["entries"]:
            break
        time.sleep(0.02)
    rows, _sp = decode_packed(dev.serve(*enc, block_compile=False), 1, 16)
    assert rows == [[inc.aid_of("a/+")]]
    assert kc.hits >= 1


# ---------------------------------------------------------------------------
# service lifecycle: cold start, compaction swap, churn-under-serve
# ---------------------------------------------------------------------------

def test_cold_start_from_segment_with_delta_tail(tmp_path):
    async def main():
        b = Broker()
        filters = [f"room/+/k{i}" for i in range(60)]
        subscribe_many(b, filters)
        ms = make_service(b, tmp_path)
        await ms.start()
        assert await settle(lambda: ms._table_gen >= 1)
        await ms.stop()
        # mutate AFTER the segment was written: the delta tail
        b.open_session("late")
        b.subscribe("late", "late/+/f", SubOpts())
        b.unsubscribe("s0", "room/+/k0")
        m2 = Metrics()
        ms2 = make_service(b, tmp_path, metrics=m2,
                           compact_interval_s=30.0,
                           compact_min_mutations=10**9)
        await ms2.start()
        assert ms2._segment_loaded
        assert m2.get("tpu.table.segment_load_s") > 0
        assert await settle(lambda: ms2.ready)
        for t, flt in (("late/1/f", "late/+/f"),
                       ("room/1/k1", "room/+/k1")):
            await ms2.prefetch(t)
            hint = ms2.hint_routes(t)
            want = b.router.match_routes(t)
            assert hint is not None
            assert sorted(map(tuple, hint)) == sorted(map(tuple, want))
        # the unsubscribed filter is gone from the restored table
        assert ms2.inc.aid_of("room/+/k0") < 0
        await ms2.stop()

    run(main())


def test_hint_freshness_preserved_across_segment_swap(tmp_path):
    async def main():
        b = Broker()
        subscribe_many(b, [f"room/+/k{i}" for i in range(30)])
        ms = make_service(b, tmp_path)
        await ms.start()
        assert await settle(lambda: ms.ready)
        topics = [f"room/{i}/k{i % 30}" for i in range(12)]
        await asyncio.gather(*[ms.prefetch(t) for t in topics])
        for t in topics:
            assert ms._hint_fresh(t, ms._hints[t][0])
        gen0 = ms._table_gen
        assert await settle(lambda: ms._table_gen > gen0, timeout=30)
        # hints carry router epochs + filter STRINGS, never aids: the
        # swap must not invalidate a single one
        for t in topics:
            assert t in ms._hints
            assert ms._hint_fresh(t, ms._hints[t][0]), t
            hint = ms.hint_routes(t)
            want = b.router.match_routes(t)
            assert hint is not None
            assert sorted(map(tuple, hint)) == sorted(map(tuple, want))
        await ms.stop()

    run(main())


def test_churn_under_serve_across_swaps_zero_stalls(tmp_path):
    """Sustained add/remove while the deadline loop serves prefetches:
    waiters never resolve past the prefetch budget, segment swaps land
    mid-churn, and every hint consumed has routing parity."""
    async def main():
        b = Broker()
        subscribe_many(b, [f"base/+/k{i}" for i in range(50)])
        ms = make_service(b, tmp_path, deadline=True, deadline_s=0.1)
        await ms.start()
        assert await settle(lambda: ms.ready)
        import time as _time

        waits = []
        for i in range(120):
            cid = f"c{i % 8}"
            if cid not in b.sessions:
                b.open_session(cid)
            if i % 2 == 0:
                b.subscribe(cid, f"churn/{i}/+", SubOpts())
            elif i > 2:
                b.unsubscribe(f"c{(i - 2) % 8}", f"churn/{i - 2}/+")
            t0 = _time.perf_counter()
            await ms.prefetch(f"serve/{i}/x")
            waits.append(_time.perf_counter() - t0)
        assert ms._table_gen >= 1, "no swap landed during the churn"
        budget = ms.prefetch_timeout_s * 0.9
        assert max(waits) < budget, (max(waits), budget)
        # post-churn parity through the swapped table
        await ms.prefetch("base/9/k9")
        hint = ms.hint_routes("base/9/k9")
        want = b.router.match_routes("base/9/k9")
        assert hint is not None
        assert sorted(map(tuple, hint)) == sorted(map(tuple, want))
        await ms.stop()

    run(main())


def test_swap_discards_inflight_batch_via_gen_guard(tmp_path):
    async def main():
        b = Broker()
        subscribe_many(b, [f"a/+/k{i}" for i in range(10)])
        ms = make_service(b, tmp_path, compact_interval_s=30.0)
        await ms.start()
        assert await settle(lambda: ms.ready)
        from emqx_tpu.broker.match_service import _StaleRace

        fut = asyncio.ensure_future(ms._device_serve(["a/1/k1"]))
        await asyncio.sleep(0)         # let it capture gen0
        ms._table_gen += 1             # a swap landed mid-flight
        with pytest.raises(_StaleRace):
            await fut
        await ms.stop()

    run(main())


def test_rules_remap_across_swap(tmp_path):
    async def main():
        b = Broker()
        subscribe_many(b, [f"r/+/k{i}" for i in range(10)])
        ms = make_service(b, tmp_path, compact_interval_s=30.0,
                          compact_min_mutations=1)
        await ms.start()
        assert await settle(lambda: ms.ready)
        ms.register_rule("rule1", ["rule/+/from"])
        assert await settle(lambda: ms._seen_epoch == b.router.epoch)
        ok = await ms._compact_once()
        assert ok and ms._table_gen == 1
        # the rule's aid was remapped into the fresh table's id space
        aid = ms.inc.aid_of("rule/+/from")
        assert aid >= 0 and ms._aid_rules.get(aid) == {"rule1"}
        await ms.prefetch("rule/9/from")
        assert ms.hint_rules("rule/9/from") == ["rule1"]
        await ms.stop()

    run(main())


def test_flag_off_structures_inert():
    b = Broker()
    ms = MatchService(b, depth=8, table="python")
    assert not ms.segments
    assert ms.kcache is None
    assert not ms.dev.dirty_regions
    assert not getattr(ms.inc, "track_regions", False)
    # no compact/hydrate/prewarm machinery arms without the flag
    assert ms._table_gen == 0 and ms._mut_count == 0


@pytest.mark.parametrize("env_dir,cwd", [
    ("/some/dir", None),     # placed from outside: code sets NO directory
    (None, None),            # unset: the fixed path inside the checkout
    (None, "elsewhere"),     # ... from a different cwd too
])
def test_xla_cache_placed_by_env_else_fixed_path(tmp_path, monkeypatch,
                                                 env_dir, cwd):
    """enable_xla_cache(): JAX_COMPILATION_CACHE_DIR set -> JAX reads
    it and no code path updates jax_compilation_cache_dir; unset -> one
    fixed <repo root>/.jax_cache whatever the cwd (a cache directory
    that moves never hits)."""
    import jax

    import emqx_tpu.node as node_mod

    repo_root = os.path.dirname(os.path.dirname(
        os.path.abspath(node_mod.__file__)))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    if cwd is not None:
        os.makedirs(os.path.join(str(tmp_path), cwd))
        monkeypatch.chdir(os.path.join(str(tmp_path), cwd))
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda k, v: (updates.append((k, v)), real_update(k, v))[1])
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert node_mod.enable_xla_cache()
        dirs = [v for k, v in updates if k == "jax_compilation_cache_dir"]
        if env_dir is not None:
            assert dirs == []
            assert jax.config.jax_compilation_cache_dir == prev
        else:
            want = os.path.join(repo_root, ".jax_cache")
            assert dirs == [want]
            assert jax.config.jax_compilation_cache_dir == want
            assert os.path.isdir(want)
        # the two min-size/min-time knobs apply either way
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
        assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1
    finally:
        real_update("jax_compilation_cache_dir", prev)


def test_xla_cache_config_key_registered():
    from emqx_tpu.config import SCHEMA

    field = SCHEMA["match.segments.xla_cache"]
    assert field.default is True


@pytest.mark.parametrize("overrides,called", [
    # tpu.enable off: nothing runs (the early return)
    ({"tpu.enable": False, "match.segments.enable": True}, False),
    # every tpu.enable start turns the cache on, segments or not
    # (the key is spelled out: tests/conftest.py's env layer turns its
    # default-true off for the suite)
    ({"tpu.enable": True, "match.segments.enable": False,
      "match.segments.xla_cache": True}, True),
    ({"tpu.enable": True, "match.segments.enable": True,
      "match.segments.xla_cache": True,
      "match.segments.dir": "/tmp/segdir"}, True),
    # the key (name and default kept) still switches it off
    ({"tpu.enable": True, "match.segments.enable": False,
      "match.segments.xla_cache": False}, False),
    ({"tpu.enable": True, "match.segments.enable": True,
      "match.segments.xla_cache": False,
      "match.segments.dir": "/tmp/segdir"}, False),
])
def test_node_wires_xla_cache_on_every_tpu_start(monkeypatch, overrides,
                                                 called):
    """The node start path calls enable_xla_cache() (no path argument:
    the cache is never derived from the segments dir) iff tpu.enable
    and match.segments.xla_cache are on."""
    import emqx_tpu.node as node_mod
    from emqx_tpu.config import Config

    calls = []
    monkeypatch.setattr(node_mod, "enable_xla_cache",
                        lambda *a: calls.append(a) or True)
    defaults = Config()

    class _Cfg:
        def get(self, key):
            if key == "tpu.start_timeout":
                return 0.001
            return overrides.get(key, defaults.get(key))

    n = node_mod.BrokerNode.__new__(node_mod.BrokerNode)
    n.config = _Cfg()
    run(n._start_match_service())
    assert calls == ([()] if called else [])
