"""Relational-join match backend (ISSUE 13): sorted edge relations +
searchsorted-intersection level steps as an alternate kernel family
behind the kernel-cache seam, with per-shape autotuned routing.

The load-bearing property is BIT-FOR-BIT parity with the hash kernel —
matches, counts, ``row_meta``, and both overflow vectors — across every
corpus shape the serve plane sees, because the cache routes per shape
and a divergent answer would be a correctness bug, not a perf delta.
Flag off (``match.backend = hash``, the default), every join structure
stays unbuilt.
"""

import asyncio
import json
import os
import random

import numpy as np
import pytest

from emqx_tpu.broker import Broker, SubOpts
from emqx_tpu.broker.match_service import MatchService
from emqx_tpu.observe.metrics import Metrics
from emqx_tpu.ops import encode_batch
from emqx_tpu.ops.device_table import DeviceNfa
from emqx_tpu.ops.incremental import IncrementalNfa
from emqx_tpu.ops.join_match import (
    OVERLAY_CAP, BackendAutotuner, JoinRelation, OverlayFull,
    relation_capacity,
)
from emqx_tpu.ops.kernel_cache import CompileMiss, MatchKernelCache
from emqx_tpu.ops.match_kernel import decode_packed


def run(coro):
    return asyncio.run(coro)


RESULT_FIELDS = ("matches", "n_matches", "active_overflow",
                 "match_overflow")


def assert_result_parity(rh, rj, ctx=""):
    for f in RESULT_FIELDS:
        a, b = np.asarray(getattr(rh, f)), np.asarray(getattr(rj, f))
        assert np.array_equal(a, b), (ctx, f, a, b)
    if rh.row_meta is not None or rj.row_meta is not None:
        assert np.array_equal(np.asarray(rh.row_meta),
                              np.asarray(rj.row_meta)), ctx


def both(dev, enc, **kw):
    return (dev.match(*enc, backend="hash", **kw),
            dev.match(*enc, backend="join", **kw))


# ---------------------------------------------------------------------------
# kernel parity
# ---------------------------------------------------------------------------

CORPUS = [
    # wildcard spread
    "a/b/c", "a/+/c", "a/#", "+/b/#", "+/+/+", "#", "x/y",
    # $SYS / $share-style (the router strips $share before the table
    # sees the filter — the kernel-level corpus is the plain filter)
    "$SYS/broker/clients/+", "$SYS/#", "queue/jobs/+",
    # deep-ish literals
    "d1/d2/d3/d4/d5/d6", "d1/d2/d3/d4/+/d6",
]

TOPICS = [
    "a/b/c", "a/z/c", "a/b", "x/y", "q/w/e",
    "$SYS/broker/clients/c1", "$SYS/broker/uptime", "$delayed/x",
    "queue/jobs/7", "d1/d2/d3/d4/d5/d6", "d1/d2/d3/d4/zz/d6",
    "a", "", "a/b/c/d/e/f/g/h",
]


def _table(filters, depth=8, **kw):
    inc = IncrementalNfa(depth=depth, **kw)
    for f in filters:
        inc.add(f)
    return inc


def test_kernel_parity_across_corpus():
    inc = _table(CORPUS)
    dev = DeviceNfa(inc, active_slots=8, max_matches=16)
    dev.enable_join()
    enc = encode_batch(inc, TOPICS, batch=16)
    assert_result_parity(*both(dev, enc), "compact")
    assert_result_parity(*both(dev, enc, flat_cap=8 * 16), "flat")
    # and both agree with the host oracle
    rh = dev.match(*enc, backend="join")
    m = np.asarray(rh.matches)
    for r, t in enumerate(TOPICS):
        got = sorted(x for x in m[r] if x >= 0)
        assert got == sorted(inc.match_host(t)), (t, got)


def test_kernel_parity_empty_frontier_and_empty_batch():
    inc = _table(["only/this"])
    dev = DeviceNfa(inc, active_slots=8, max_matches=8)
    dev.enable_join()
    # topics that die at step 0/1 + padding-only batch
    enc = encode_batch(inc, ["zz/zz/zz", "$SYS/x"], batch=8)
    assert_result_parity(*both(dev, enc), "dead frontier")
    enc = encode_batch(inc, [], batch=8)
    assert_result_parity(*both(dev, enc, flat_cap=64), "empty batch")


def test_kernel_parity_overflow_rows():
    # tiny active set + tiny K: force BOTH spill kinds and assert the
    # fail-open flags agree bit-for-bit (the host re-run set must be
    # THE SAME rows whichever backend served).  "a/3/x" forks into 3
    # live states at step 2 (a→+, +→3, +→+) > A=2 → active spill; the
    # '#'+wildcards push counts past K=2 → match spill.
    filters = ["+/+/#", "a/+/#", "+/3/#", "#"] \
        + [f"+/{i}/#" for i in range(6)]
    inc = _table(filters)
    dev = DeviceNfa(inc, active_slots=2, max_matches=2)
    dev.enable_join()
    enc = encode_batch(inc, ["a/3/x", "a/5/y/z", "q/1/w"], batch=4)
    rh, rj = both(dev, enc)
    assert_result_parity(rh, rj, "overflow")
    assert np.asarray(rh.active_overflow).sum() > 0
    assert np.asarray(rh.match_overflow).sum() > 0
    enc2 = encode_batch(inc, ["a/3/x"], batch=4)
    assert_result_parity(*both(dev, enc2, flat_cap=8), "overflow flat")


@pytest.mark.slow
def test_kernel_parity_random_churn_soak():
    rng = random.Random(71)
    inc = IncrementalNfa(depth=6, state_bucket=32, edge_bucket=64)
    inc.track_regions = True
    dev = DeviceNfa(inc, active_slots=8, max_matches=16)
    dev.dirty_regions = True
    dev.enable_join()
    pool = [f"l{i}/m{j}" + ("/+" if (i + j) % 3 == 0 else f"/n{j}")
            for i in range(40) for j in range(8)]
    present = set()
    for step in range(60):
        for _ in range(31):
            f = rng.choice(pool)
            if f in present:
                inc.remove(f)
                present.discard(f)
            else:
                inc.add(f)
                present.add(f)
        dev.sync()
        names = [t.replace("+", "qq") for t in rng.sample(pool, 8)]
        enc = encode_batch(inc, names, batch=8)
        assert_result_parity(*both(dev, enc), f"step {step}")


# ---------------------------------------------------------------------------
# relation maintenance
# ---------------------------------------------------------------------------

def test_relation_lookup_matches_edge_table():
    inc = _table(CORPUS)
    rel = JoinRelation(inc.S, inc.edge_tab)
    flat = inc.edge_tab.reshape(-1, 4)
    for s, w, n, _pad in flat[flat[:, 0] >= 0].tolist():
        assert rel.lookup(s, w) == n
    assert rel.lookup(0, 999999) == -1
    assert rel.cap == relation_capacity(inc.Hb)


def test_relation_delta_tombstone_revive_and_overlay():
    inc = _table(["a/b", "a/c"])
    rel = JoinRelation(inc.S, inc.edge_tab)
    inc.flush()  # clear dirt from the build
    inc.remove("a/c")            # tombstone
    inc.add("a/d")               # fresh edge -> overlay
    d = inc.flush()
    mpos, mval, opos, orows = rel.apply_bucket_delta(
        d.bucket_idx, d.bucket_rows)
    assert len(mpos) >= 1 and (mval == -1).any()    # tombstone written
    assert len(opos) >= 1                           # overlay append
    assert rel.lookup(0, inc.vocab["a"]) >= 0
    # revive: re-add the tombstoned filter — must land back in the CSR
    inc.add("a/c")
    d = inc.flush()
    mpos, mval, opos, orows = rel.apply_bucket_delta(
        d.bucket_idx, d.bucket_rows)
    assert (mval >= 0).any()
    # every live edge answers; the removed one is dead
    flat = inc.edge_tab.reshape(-1, 4)
    for s, w, n, _pad in flat[flat[:, 0] >= 0].tolist():
        assert rel.lookup(s, w) == n


def test_relation_overlay_overflow_raises_then_rebuild_serves():
    # table shapes large enough that nothing resizes mid-test: the
    # overflow must come from the overlay cap, not a rehash
    inc = _table(["seed/x"], state_bucket=4096, edge_bucket=4096)
    rel = JoinRelation(inc.S, inc.edge_tab)
    inc.flush()
    with pytest.raises(OverlayFull):
        added = 0
        while added < OVERLAY_CAP + 50:
            inc.add(f"o{added}/p{added}")
            added += 2  # two fresh edges per filter
            d = inc.flush()
            rel.apply_bucket_delta(d.bucket_idx, d.bucket_rows)
    assert inc.shape_key() == (4096, 4096, 8)
    # the shadow is already current: a rebuild alone restores service
    rel.rebuild(inc.S)
    flat = inc.edge_tab.reshape(-1, 4)
    for s, w, n, _pad in flat[flat[:, 0] >= 0].tolist():
        assert rel.lookup(s, w) == n


def test_device_overlay_overflow_rebuilds_and_keeps_parity():
    inc = IncrementalNfa(depth=6, state_bucket=4096, edge_bucket=4096)
    for i in range(4):
        inc.add(f"warm/{i}")
    dev = DeviceNfa(inc, active_slots=8, max_matches=8)
    dev.enable_join()
    rebuilds0 = dev.join_rebuilds
    # far more fresh edges than OVERLAY_CAP in one delta, with table
    # shapes big enough that nothing resizes: the overflow path, not
    # the rehash path, must absorb it
    for i in range(OVERLAY_CAP):
        inc.add(f"g{i}/h{i}")
    dev.sync()
    assert inc.shape_key() == (4096, 4096, 6)   # no resize happened
    assert dev.join_rebuilds > rebuilds0
    enc = encode_batch(inc, ["g7/h7", "warm/2", "nope/x"], batch=4)
    assert_result_parity(*both(dev, enc), "post-rebuild")


def test_grow_in_place_rehash_ships_fresh_seeds_regression():
    """The bug the join parity suite surfaced: a cuckoo rehash on the
    grow-in-place path shipped the rehashed edge table WITHOUT its
    fresh seeds, so the hash kernel probed with a stale pair and every
    lookup missed.  The relation is seed-free, which is why the join
    backend kept answering."""
    inc = IncrementalNfa(depth=6, state_bucket=16)
    inc.track_regions = True
    for f in ["a/b", "c/#"]:
        inc.add(f)
    dev = DeviceNfa(inc, active_slots=8, max_matches=8)
    dev.dirty_regions = True
    dev.enable_join()
    for i in range(200):    # forces node growth AND edge rehashes
        inc.add(f"g{i}/h{i}/+")
        if i % 17 == 0:
            dev.sync()
    dev.sync()
    assert dev.grow_applies > 0
    topics = [f"g{i}/h{i}/zz" for i in range(0, 200, 13)] + ["a/b"]
    enc = encode_batch(inc, topics, batch=32)
    rh, rj = both(dev, enc)
    assert_result_parity(rh, rj, "post-rehash")
    m = np.asarray(rh.matches)
    for r, t in enumerate(topics):
        assert sorted(x for x in m[r] if x >= 0) == \
            sorted(inc.match_host(t)), t


def test_flag_off_join_structures_inert():
    inc = _table(CORPUS)
    dev = DeviceNfa(inc, active_slots=8, max_matches=8)
    assert dev._join is None and dev._jarrs is None
    inc.add("later/+")
    dev.sync()
    assert dev._join is None and dev._jarrs is None
    # backend="join" without the mirror silently serves hash (identical
    # answers) instead of failing the batch
    enc = encode_batch(inc, ["a/b/c"], batch=4)
    r = dev.match(*enc, backend="join")
    assert sorted(x for x in np.asarray(r.matches)[0] if x >= 0) == \
        sorted(inc.match_host("a/b/c"))
    b = Broker()
    ms = MatchService(b, table="python")     # backend defaults to hash
    assert ms.backend == "hash" and ms.tuner is None
    assert ms.dev.join_enabled is False


# ---------------------------------------------------------------------------
# kernel cache: backend dimension, prewarm-both bugfix, CompileMiss
# ---------------------------------------------------------------------------

def test_compile_miss_raised_for_uncompiled_join_shape():
    inc = _table(["a/+"])
    dev = DeviceNfa(inc, active_slots=8, max_matches=16)
    dev.enable_join()
    kc = MatchKernelCache()
    dev.kernel_cache = kc
    enc = encode_batch(inc, ["a/k"], batch=64)
    with pytest.raises(CompileMiss):
        dev.serve(*enc, block_compile=False, backend="join")
    import time

    for _ in range(400):
        if kc.info()["entries"]:
            break
        time.sleep(0.02)
    rows, _sp = decode_packed(
        dev.serve(*enc, block_compile=False, backend="join"), 1, 16)
    assert rows == [[inc.aid_of("a/+")]]
    assert kc.hits >= 1


def test_prewarm_covers_both_backends_under_auto_zero_compile():
    """ISSUE 13 bugfix, spy-asserted: with auto routing the observed
    combos are hash-first, so prewarm_shape must cross-product them
    with BOTH kernel families — after prewarming the next shape, an
    auto-routed JOIN dispatch on it is a pure cache hit."""
    inc = IncrementalNfa(depth=8, state_bucket=64, edge_bucket=1024)
    inc.track_regions = True
    dev = DeviceNfa(inc, active_slots=8, max_matches=16)
    dev.dirty_regions = True
    dev.enable_join()
    kc = MatchKernelCache()
    kc.auto_backends = ("hash", "join")
    dev.kernel_cache = kc
    for i in range(20):
        inc.add(f"a/{i}/+")
    dev.sync()
    enc = encode_batch(inc, ["a/3/k"], batch=64)
    # observe the combo via the HASH backend only (the auto cold path)
    np.asarray(dev.serve(*enc, backend="hash"))
    s, hb, _d = inc.shape_key()
    kc.prewarm_shape(2 * s, hb)
    assert kc.shape_covered(2 * s, hb)
    compiles0 = kc.compiles
    for i in range(20):                 # cross the boundary
        inc.add(f"b/{i}/x")
    dev.sync()
    assert inc.shape_key() == (2 * s, hb, 8)
    enc = encode_batch(inc, ["b/5/x"], batch=64)
    # the first JOIN dispatch on the fresh shape: zero compiles
    rows, _sp = decode_packed(
        dev.serve(*enc, block_compile=False, backend="join"), 1, 16)
    assert rows == [[inc.aid_of("b/5/x")]]
    assert kc.compiles == compiles0, \
        "auto-routed join dispatch on a prewarmed shape paid a compile"


def test_prewarm_single_backend_unchanged_without_auto():
    """Without auto_backends the prewarm set is exactly the observed
    combos — no join executables are built behind a hash-only config."""
    inc = _table(["a/+"], state_bucket=64)
    dev = DeviceNfa(inc, active_slots=8, max_matches=16)
    kc = MatchKernelCache()
    dev.kernel_cache = kc
    enc = encode_batch(inc, ["a/k"], batch=64)
    np.asarray(dev.serve(*enc))
    n = kc.prewarm_shape(128, inc.Hb)
    assert n == 1       # one combo, one backend, one fresh shape
    assert all(k[8] == "hash" for k in kc._compiled)


# ---------------------------------------------------------------------------
# segments: the sorted relations survive save/load/compact
# ---------------------------------------------------------------------------

def test_segment_round_trip_preserves_join_relation(tmp_path):
    from emqx_tpu.storage.segments import load_segment, save_segment

    inc = _table(CORPUS)
    path = str(tmp_path / "seg.npz")
    save_segment(path, inc, deep={}, routing_aids=set(),
                 join_relation=True)
    seg = load_segment(path)
    assert seg.join_start is not None
    rel = JoinRelation(inc.S, inc.edge_tab)   # fresh build = oracle
    assert np.array_equal(seg.join_start, rel.state_start)
    assert np.array_equal(seg.join_word, rel.edge_word)
    assert np.array_equal(seg.join_next, rel.edge_next)
    # and a relation seeded from the persisted arrays serves verbatim
    seeded = JoinRelation(inc.S, inc.edge_tab,
                          arrays=(seg.join_start, seg.join_word,
                                  seg.join_next))
    flat = inc.edge_tab.reshape(-1, 4)
    for s, w, n, _pad in flat[flat[:, 0] >= 0].tolist():
        assert seeded.lookup(s, w) == n


def test_segment_without_join_arrays_still_loads(tmp_path):
    from emqx_tpu.storage.segments import load_segment, save_segment

    inc = _table(["a/+"])
    path = str(tmp_path / "seg.npz")
    save_segment(path, inc, deep={}, routing_aids=set())
    seg = load_segment(path)
    assert seg.join_start is None


def test_cold_start_seeds_join_mirror_without_resort(tmp_path,
                                                    monkeypatch):
    """A segment-restored service with the join backend skips the
    build sort at first sync: the persisted arrays seed the mirror
    (epoch-guarded), spy-asserted on JoinRelation._build."""
    seg_dir = str(tmp_path)

    async def first_node():
        b = Broker()
        b.open_session("sub")
        for i in range(30):
            b.subscribe("sub", f"t/{i}/+", SubOpts())
        ms = MatchService(b, table="python", debounce_s=0.01,
                          bypass_rate=0.0, segments=True,
                          segments_dir=seg_dir,
                          compact_interval_s=0.05,
                          compact_min_mutations=1, backend="join")
        await ms.start()
        for _ in range(400):
            if ms._table_gen >= 1:
                break
            await asyncio.sleep(0.02)
        assert ms._table_gen >= 1
        await ms.stop()

    run(first_node())
    builds = []
    monkeypatch.setattr(
        JoinRelation, "_build",
        (lambda orig: lambda self, s: (builds.append(s),
                                       orig(self, s))[1])(
            JoinRelation._build))

    async def second_node():
        b2 = Broker()
        b2.open_session("sub")
        for i in range(30):
            b2.subscribe("sub", f"t/{i}/+", SubOpts())
        ms2 = MatchService(b2, table="python", debounce_s=0.01,
                           bypass_rate=0.0, segments=True,
                           segments_dir=seg_dir, backend="join")
        await ms2.start()
        for _ in range(400):
            if ms2.ready:
                break
            await asyncio.sleep(0.02)
        assert ms2.ready
        assert ms2._segment_loaded
        assert builds == [], "segment cold start re-paid the build sort"
        assert ms2.dev._jarrs is not None
        # and the seeded mirror answers with full parity
        enc = encode_batch(ms2.inc, ["t/3/x", "t/9/y"], batch=4)
        assert_result_parity(*both(ms2.dev, enc), "seeded mirror")
        await ms2.stop()

    run(second_node())


# ---------------------------------------------------------------------------
# autotuner
# ---------------------------------------------------------------------------

def test_autotuner_measure_records_and_persists(tmp_path):
    path = str(tmp_path / "autotune.json")
    t = BackendAutotuner(path=path, reps=2)
    calls = {"hash": 0, "join": 0}

    def mk(name, cost):
        def go():
            calls[name] += 1
            import time
            time.sleep(cost)
        return go

    sig = t.sig(256, 8, 1024, 64)
    pick = t.measure(sig, {"hash": mk("hash", 0.004),
                           "join": mk("join", 0.0)})
    assert pick == "join"
    assert calls["hash"] == 3 and calls["join"] == 3  # warmup + 2 reps
    assert t.pick(sig) == "join"
    # round-trips through the checksummed file
    t2 = BackendAutotuner(path=path)
    assert t2.pick(sig) == "join"
    assert not t2.rejected


def test_autotuner_corrupt_file_rejected(tmp_path):
    """The segment-checksum idiom: a torn/tampered pick table must be
    REJECTED (defaults serve, measuring restarts) — never trusted."""
    path = str(tmp_path / "autotune.json")
    t = BackendAutotuner(path=path, reps=1)
    t.record(t.sig(256, 8, 1024, 64), "join")
    doc = json.loads(open(path).read())
    doc["picks"]["b256:d8:s1024:h64"] = "hash"   # tamper, stale checksum
    open(path, "w").write(json.dumps(doc))
    t2 = BackendAutotuner(path=path)
    assert t2.rejected and t2.picks == {}
    # garbage bytes are equally rejected
    open(path, "w").write("{not json")
    t3 = BackendAutotuner(path=path)
    assert t3.rejected and t3.picks == {}
    # a bogus backend value is structurally rejected too
    open(path, "w").write(json.dumps({
        "version": 1, "checksum": "x", "picks": {"a": "pallas"}}))
    t4 = BackendAutotuner(path=path)
    assert t4.rejected and t4.picks == {}


# ---------------------------------------------------------------------------
# service-level routing
# ---------------------------------------------------------------------------

async def _serve_storm(ms, b, n=48, base=0):
    for i in range(n):
        await ms.prefetch_many({f"t/{base + i}/x": 1})


def test_service_join_backend_serves_and_counts(tmp_path):
    """backend=join: every device dispatch rides the join kernel
    (metric-asserted) and hints are BIT-FOR-BIT what a hash-backend
    service mints for the same router state and traffic."""
    async def serve(backend):
        b = Broker()
        m = Metrics()
        b.open_session("sub")
        for i in range(24):
            b.subscribe("sub", f"t/{i}/+", SubOpts())
        b.subscribe("sub", "t/#", SubOpts())
        b.subscribe("sub", "$share/g1/t/+/x", SubOpts())   # share strips
        b.subscribe("sub", "$SYS/deep/1/2/3/4/5/6/7/8/9/#", SubOpts())
        ms = MatchService(b, metrics=m, table="python",
                          debounce_s=0.01, bypass_rate=0.0,
                          backend=backend)
        await ms.start()
        for _ in range(400):
            if ms.ready:
                break
            await asyncio.sleep(0.02)
        topics = [f"t/{i}/x" for i in range(24)] + ["t/zz/q/deep"]
        await ms.prefetch_many({t: 1 for t in topics})
        hints = {t: ms._hints[t][2:] for t in topics if t in ms._hints}
        joins = m.get("tpu.match.backend_join_dispatches")
        await ms.stop()
        return hints, joins

    hints_h, joins_h = run(serve("hash"))
    hints_j, joins_j = run(serve("join"))
    assert joins_h == 0
    assert joins_j > 0
    assert hints_h == hints_j       # filter strings + rule ids equal
    assert len(hints_j) >= 20


def test_service_auto_measures_then_routes(tmp_path):
    async def main():
        b = Broker()
        m = Metrics()
        b.open_session("sub")
        for i in range(16):
            b.subscribe("sub", f"t/{i}/+", SubOpts())
        ms = MatchService(b, metrics=m, table="python",
                          debounce_s=0.01, bypass_rate=0.0,
                          backend="auto", autotune_reps=1)
        await ms.start()
        for _ in range(400):
            if ms.ready:
                break
            await asyncio.sleep(0.02)
        assert ms.tuner is not None
        for r in range(8):
            await _serve_storm(ms, b, n=16, base=100 * r)
            if ms.tuner.picks:
                break
        for _ in range(300):
            if ms.tuner.picks:
                break
            await asyncio.sleep(0.02)
        assert ms.tuner.picks, "no shape was ever measured"
        assert m.get("tpu.match.autotune_picks") >= 1
        info = ms.info()
        assert info["backend"] == "auto"
        assert info["autotune"]["measured_shapes"] >= 1
        # serve once more: the routed backend is the measured pick
        await _serve_storm(ms, b, n=16, base=9000)
        pick = next(iter(ms.tuner.picks.values()))
        joins = m.get("tpu.match.backend_join_dispatches")
        if pick == "join":
            assert joins > 0
        await ms.stop()

    run(main())


def test_service_auto_with_segments_persists_picks(tmp_path):
    seg_dir = str(tmp_path)

    async def main():
        b = Broker()
        b.open_session("sub")
        for i in range(8):
            b.subscribe("sub", f"t/{i}/+", SubOpts())
        ms = MatchService(b, table="python", debounce_s=0.01,
                          bypass_rate=0.0, segments=True,
                          segments_dir=seg_dir, backend="auto",
                          autotune_reps=1)
        assert ms.kcache is not None
        assert ms.kcache.auto_backends == ("hash", "join")
        await ms.start()
        for _ in range(400):
            if ms.ready:
                break
            await asyncio.sleep(0.02)
        for r in range(8):
            await _serve_storm(ms, b, n=16, base=100 * r)
            if ms.tuner.picks:
                break
        for _ in range(300):
            if ms.tuner.picks:
                break
            await asyncio.sleep(0.02)
        await ms.stop()
        assert os.path.exists(os.path.join(seg_dir, "autotune.json"))
        reloaded = BackendAutotuner(
            path=os.path.join(seg_dir, "autotune.json"))
        assert reloaded.picks == ms.tuner.picks and reloaded.picks

    run(main())


def test_autotuner_family_pick_generalizes_across_pow2_shapes():
    """ROADMAP join residual (d): a pick measured at one pow2 (S, Hb)
    shape serves the whole (B, D) family — a growth step inherits the
    family consensus instead of re-measuring cold."""
    t = BackendAutotuner(reps=1)
    t.record(t.sig(256, 8, 1024, 64), "join")
    t.record(t.sig(256, 8, 2048, 64), "join")
    # exact hit stays exact
    assert t.pick_for(256, 8, 1024, 64) == "join"
    assert t.family_hits == 0
    # unmeasured grown shape inherits the family consensus
    assert t.pick_for(256, 8, 4096, 128) == "join"
    assert t.family_hits == 1
    # a different (B, D) family has no pick
    assert t.pick_for(512, 8, 4096, 128) is None
    assert t.pick_for(256, 4, 4096, 128) is None


def test_autotuner_family_split_measures_exact():
    """A family whose measured shapes DISAGREE returns no consensus:
    the exact shape measures as before (a wrong inherited pick is only
    slow, but a split family is real signal)."""
    t = BackendAutotuner(reps=1)
    t.record(t.sig(256, 8, 1024, 64), "join")
    t.record(t.sig(256, 8, 2048, 128), "hash")
    assert t.pick_for(256, 8, 4096, 256) is None
    assert t.family_hits == 0
    # persisted format stays the versioned checksummed JSON
    assert "family_hits" in t.info()


def test_sorted_overlay_bit_parity_vs_linear_scan():
    """ISSUE 16 satellite: the sorted-overlay lower-bound search must
    be bit-identical to the historical dense overlay compare — same
    matches, counts, and overflow vectors — with the overlay well
    populated and a tombstoned CSR edge in play."""
    from emqx_tpu.ops.join_match import OVERLAY_EMPTY, join_match

    inc = _table(CORPUS, state_bucket=1024, edge_bucket=1024)
    rel = JoinRelation(inc.S, inc.edge_tab)
    inc.flush()
    # fresh edges land in the overlay; a removal tombstones the CSR
    for i in range(40):
        inc.add(f"ov{i}/+/leaf{i}")
    inc.remove("a/+/c")
    d = inc.flush()
    rel.grow_states(inc.S)
    mpos, mval, opos, orows = rel.apply_bucket_delta(
        d.bucket_idx, d.bucket_rows)
    assert (mval == -1).any()          # the tombstone
    assert len(opos) == OVERLAY_CAP    # overlay ships whole, sorted
    # sortedness invariant: live rows ascending, sentinels at the end
    ov = rel.overlay
    live = ov[ov[:, 0] != OVERLAY_EMPTY]
    assert len(live) >= 40
    keys = [tuple(r[:2]) for r in live.tolist()]
    assert keys == sorted(keys)
    assert (ov[len(live):, 0] == OVERLAY_EMPTY).all()

    topics = ["ov3/q/leaf3", "a/b/c", "ov39/x/leaf39", "a/z/c",
              "nope/x", "d1/d2/d3/d4/d5/d6"]
    enc = encode_batch(inc, topics, batch=8)
    kw = dict(active_slots=8, max_matches=16)
    r_sorted = join_match(*enc, inc.node_tab, *rel.arrays(), **kw)
    r_linear = join_match(*enc, inc.node_tab, *rel.arrays(),
                          linear_overlay=True, **kw)
    assert_result_parity(r_sorted, r_linear, "overlay search")
    r_sorted_f = join_match(*enc, inc.node_tab, *rel.arrays(),
                            flat_cap=8 * 16, **kw)
    r_linear_f = join_match(*enc, inc.node_tab, *rel.arrays(),
                            flat_cap=8 * 16, linear_overlay=True, **kw)
    assert_result_parity(r_sorted_f, r_linear_f, "overlay search flat")
    # and the host walk agrees (the overlay answers are REAL edges)
    m = np.asarray(r_sorted.matches)
    for r, t in enumerate(topics):
        got = sorted(x for x in m[r] if x >= 0)
        assert got == sorted(inc.match_host(t)), (t, got)
