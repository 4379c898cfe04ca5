"""Pallas small-table fast path: parity vs nfa_match in interpret mode
(SURVEY.md §7.4 experiment; Mosaic lowering A/B'd on real hardware via
ops.pallas_match.bench_pallas_small)."""

import numpy as np
import jax.numpy as jnp
import pytest

from emqx_tpu import topic as T
from emqx_tpu.ops import compile_filters, encode_topics, nfa_match
from emqx_tpu.ops.pallas_match import (
    TILE_B, pallas_small_match, supports_table,
)

FILTERS = ["a/b/c", "a/+/c", "a/#", "#", "+", "+/b", "a/b", "b",
           "$SYS/#", "x//y", "+/+/+", "deep/1/2/3/4/5/6/#"]
TOPICS = (["a/b/c", "a/b", "a", "b", "x//y", "$SYS/broker",
           "deep/1/2/3/4/5/6/7", "nomatch/z", "a/q/c", "/"] * 26)[:256]


def test_pallas_parity_interpret():
    t = compile_filters(FILTERS, depth=8, state_bucket=8)
    assert supports_table(*t.device_arrays()[:2])
    words, lens, is_sys = encode_topics(t, TOPICS, batch=256)
    args = (jnp.asarray(words), jnp.asarray(lens), jnp.asarray(is_sys),
            *[jnp.asarray(a) for a in t.device_arrays()])
    ref = nfa_match(*args, active_slots=8, compact_output=False)
    acc, aover = pallas_small_match(*args, depth=8, active_slots=8,
                                    interpret=True)
    ra, pa = np.asarray(ref.matches), np.asarray(acc)
    assert ra.shape == pa.shape
    # same accept-id multiset per row (slot layout is shared)
    assert (np.sort(np.where(ra < 0, -1, ra), axis=1)
            == np.sort(np.where(pa < 0, -1, pa), axis=1)).all()
    assert (np.asarray(ref.active_overflow) == np.asarray(aover)).all()
    # spot-check against the oracle too
    counts = np.asarray(ref.n_matches)
    for i, name in enumerate(TOPICS[:32]):
        want = {f for f in FILTERS if T.match(name, f)}
        got = {t.accept_filters[a] for a in pa[i] if a >= 0}
        assert got == want or counts[i] > len(got)


def test_pallas_flat_epilogue_parity_interpret():
    """The SHARED flat compaction epilogue rides the pallas walk too
    (ISSUE 11): pallas_small_match_flat produces the same dense flat
    buffer + packed row_meta as nfa_match(flat_cap=...), so both
    backends honor one readback contract."""
    from emqx_tpu.ops.match_kernel import decode_flat, decode_row_meta
    from emqx_tpu.ops.pallas_match import pallas_small_match_flat

    t = compile_filters(FILTERS, depth=8, state_bucket=8)
    words, lens, is_sys = encode_topics(t, TOPICS, batch=256)
    args = (jnp.asarray(words), jnp.asarray(lens), jnp.asarray(is_sys),
            *[jnp.asarray(a) for a in t.device_arrays()])
    K = 8
    cap = 8 * 256
    ref = nfa_match(*args, active_slots=8, max_matches=K, flat_cap=cap)
    got = pallas_small_match_flat(*args, depth=8, active_slots=8,
                                  max_matches=K, flat_cap=cap,
                                  interpret=True)
    np.testing.assert_array_equal(np.asarray(ref.n_matches),
                                  np.asarray(got.n_matches))
    np.testing.assert_array_equal(np.asarray(ref.row_meta),
                                  np.asarray(got.row_meta))
    # same per-row id SETS (slot order within a row may differ between
    # backends; the epilogue's compaction is order-preserving per input
    # layout, so compare decoded sets)
    n1 = np.asarray(ref.n_matches)
    rows_ref = decode_flat(np.asarray(ref.matches), n1, K)
    rows_got = decode_flat(np.asarray(got.matches),
                           np.asarray(got.n_matches), K)
    nk, sp = decode_row_meta(np.asarray(got.row_meta))
    for i in range(len(TOPICS)):
        if not sp[i]:
            assert set(rows_ref[i]) == set(rows_got[i]), i


def test_pallas_rejects_ragged_batch():
    t = compile_filters(FILTERS, depth=8, state_bucket=8)
    words, lens, is_sys = encode_topics(t, TOPICS[:100], batch=100)
    with pytest.raises(ValueError):
        pallas_small_match(
            jnp.asarray(words), jnp.asarray(lens), jnp.asarray(is_sys),
            *[jnp.asarray(a) for a in t.device_arrays()],
            depth=8, interpret=True)
    assert TILE_B == 256


# ---------------------------------------------------------------------------
# fused join walk (ISSUE 17): the CSR join relation composed on-chip
# ---------------------------------------------------------------------------

JOIN_CORPUS = [
    "a/b/c", "a/+/c", "a/#", "+/b/#", "+/+/+", "#", "x/y",
    "$SYS/broker/clients/+", "$SYS/#", "queue/jobs/+",
    "d1/d2/d3/d4/d5/d6", "d1/d2/d3/d4/+/d6",
]
JOIN_TOPICS = [
    "a/b/c", "a/z/c", "a/b", "x/y", "q/w/e",
    "$SYS/broker/clients/c1", "$SYS/broker/uptime", "$delayed/x",
    "queue/jobs/7", "d1/d2/d3/d4/d5/d6", "d1/d2/d3/d4/zz/d6",
    "a", "", "a/b/c/d/e/f/g/h",
]


def _join_dev(filters, depth=8, active_slots=8, max_matches=16, **kw):
    from emqx_tpu.ops.device_table import DeviceNfa
    from emqx_tpu.ops.incremental import IncrementalNfa

    inc = IncrementalNfa(depth=depth, **kw)
    for f in filters:
        inc.add(f)
    dev = DeviceNfa(inc, active_slots=active_slots,
                    max_matches=max_matches)
    dev.enable_join()
    return inc, dev


def _assert_flat_parity(rj, rp, ctx=""):
    for f in ("matches", "n_matches", "active_overflow",
              "match_overflow", "row_meta"):
        a = np.asarray(getattr(rj, f))
        b = np.asarray(getattr(rp, f))
        assert np.array_equal(a, b), (ctx, f, a, b)


def test_pallas_join_parity_corpus_interpret():
    """Bit-parity gate: the fused Pallas join walk returns the SAME
    flat buffer, counts, packed row_meta, and fail-open flags as the
    lax join kernel over the full corpus suite — and both agree with
    the host oracle."""
    from emqx_tpu.ops import encode_batch
    from emqx_tpu.ops.match_kernel import decode_row_meta
    from emqx_tpu.ops.pallas_match import supports_join_table

    inc, dev = _join_dev(JOIN_CORPUS)
    assert supports_join_table(dev.arrays()[0], *dev._jarrs)
    enc = encode_batch(inc, JOIN_TOPICS, batch=16)
    cap = 8 * 16
    rj = dev.match(*enc, backend="join", flat_cap=cap)
    rp = dev.match(*enc, backend="join-pallas", flat_cap=cap)
    _assert_flat_parity(rj, rp, "corpus flat")
    nk, sp = decode_row_meta(np.asarray(rp.row_meta))
    flat = np.asarray(rp.matches)
    offs = np.cumsum(nk) - nk
    for i, t in enumerate(JOIN_TOPICS):
        if sp[i]:
            continue
        got = sorted(flat[offs[i]:offs[i] + nk[i]].tolist())
        assert got == sorted(inc.match_host(t)), (t, got)


def test_pallas_join_parity_overflow_rows_interpret():
    """Both spill kinds (active-set and match-count) flag the same
    rows bit-for-bit — the fail-open host re-run set is identical
    whichever join backend served."""
    from emqx_tpu.ops import encode_batch

    filters = ["+/+/#", "a/+/#", "+/3/#", "#"] \
        + [f"+/{i}/#" for i in range(6)]
    inc, dev = _join_dev(filters, active_slots=2, max_matches=2)
    enc = encode_batch(inc, ["a/3/x", "a/5/y/z", "q/1/w"], batch=4)
    rj = dev.match(*enc, backend="join", flat_cap=8)
    rp = dev.match(*enc, backend="join-pallas", flat_cap=8)
    _assert_flat_parity(rj, rp, "overflow flat")
    assert np.asarray(rj.active_overflow).sum() > 0
    assert np.asarray(rj.match_overflow).sum() > 0


def test_pallas_join_parity_dead_frontier_and_empty_batch():
    from emqx_tpu.ops import encode_batch

    inc, dev = _join_dev(["only/this"])
    enc = encode_batch(inc, ["zz/zz/zz", "$SYS/x"], batch=8)
    _assert_flat_parity(dev.match(*enc, backend="join", flat_cap=64),
                        dev.match(*enc, backend="join-pallas",
                                  flat_cap=64), "dead frontier")
    enc = encode_batch(inc, [], batch=8)
    _assert_flat_parity(dev.match(*enc, backend="join", flat_cap=64),
                        dev.match(*enc, backend="join-pallas",
                                  flat_cap=64), "empty batch")


def test_pallas_join_fallback_paths(monkeypatch):
    """join-pallas degrades without erroring: compact output falls to
    the lax join (the fused walk is flat-only), a non-tile-divisible
    batch falls to the lax join, and a table without the join relation
    falls to hash — spy-asserted (the Pallas entry never runs)."""
    from emqx_tpu.ops import encode_batch, pallas_match
    from emqx_tpu.ops.device_table import DeviceNfa
    from emqx_tpu.ops.incremental import IncrementalNfa

    def boom(*a, **kw):  # pragma: no cover - must never run
        raise AssertionError("pallas join ran on a fallback shape")

    inc, dev = _join_dev(JOIN_CORPUS)
    enc = encode_batch(inc, JOIN_TOPICS, batch=16)
    want = dev.match(*enc, backend="join")
    monkeypatch.setattr(pallas_match, "pallas_join_match_flat", boom)
    got = dev.match(*enc, backend="join-pallas")   # compact → lax join
    for f in ("matches", "n_matches", "active_overflow",
              "match_overflow"):
        assert np.array_equal(np.asarray(getattr(want, f)),
                              np.asarray(getattr(got, f))), f
    # batch not divisible by the 256-lane tile → lax join, same answer
    enc2 = encode_batch(inc, JOIN_TOPICS, batch=384)
    wf = dev.match(*enc2, backend="join", flat_cap=8 * 384)
    gf = dev.match(*enc2, backend="join-pallas", flat_cap=8 * 384)
    _assert_flat_parity(wf, gf, "non-tile batch")
    # no join relation → hash serves
    inc2 = IncrementalNfa(depth=8)
    inc2.add("a/+")
    dev2 = DeviceNfa(inc2, active_slots=8, max_matches=8)
    assert dev2._jarrs is None
    enc3 = encode_batch(inc2, ["a/k"], batch=8)
    r = dev2.match(*enc3, backend="join-pallas", flat_cap=64)
    np.testing.assert_array_equal(
        np.asarray(r.n_matches),
        np.asarray(dev2.match(*enc3, backend="hash",
                              flat_cap=64).n_matches))


def test_pallas_join_kernel_cache_backend():
    """The join-pallas backend is a first-class kernel-cache citizen:
    a cached served dispatch compiles once, hits after, and returns the
    lax join's exact bits as the one packed array; lowering it without a flat cap is a contract
    error (flat-output only)."""
    import pytest as _pytest

    from emqx_tpu.ops import encode_batch
    from emqx_tpu.ops.kernel_cache import MatchKernelCache

    inc, dev = _join_dev(JOIN_CORPUS)
    kc = MatchKernelCache()
    dev.kernel_cache = kc
    enc = encode_batch(inc, JOIN_TOPICS, batch=16)
    want = dev.match(*enc, backend="join", flat_cap=8 * 16)
    want = np.concatenate([np.asarray(want.row_meta),
                           np.asarray(want.matches)])
    rp = dev.serve(*enc, backend="join-pallas")
    np.testing.assert_array_equal(want, np.asarray(rp), "cache first")
    compiles = kc.compiles
    rp2 = dev.serve(*enc, backend="join-pallas")
    np.testing.assert_array_equal(want, np.asarray(rp2), "cache hit")
    assert kc.compiles == compiles    # pure hit, no recompile
    assert kc.hits >= 1
    s, hb, _d = inc.shape_key()
    with _pytest.raises(ValueError):
        kc._lower((16, 8, s, hb, 8, 16, True, 0, "join-pallas", None))


def test_pallas_join_excluded_from_auto_prewarm_cross():
    """``auto`` prewarm crosses hash×join only — the Pallas family
    compiles on first explicit dispatch, never speculatively (VMEM
    budget gating is per-table, not per-shape)."""
    from emqx_tpu.ops.kernel_cache import MatchKernelCache

    kc = MatchKernelCache()
    assert "join-pallas" not in kc.auto_backends
