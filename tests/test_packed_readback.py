"""One answer from the device (ISSUES 31, 32): every single-chip serve
path asks for a program whose WHOLE answer is one packed array
(``row_meta`` then the flat ids), ``match_kernel.decode_packed`` is the
one host decode of it, and the counters say how many device buffers a
batch really fetched.  The five-output ``nfa_match`` / ``join_match``
stay as the reference these tests compare it against."""

import asyncio
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from emqx_tpu import topic as T
from emqx_tpu.broker import Broker, SubOpts
from emqx_tpu.broker.match_service import MatchService
from emqx_tpu.observe.metrics import Metrics
from emqx_tpu.ops import (
    compile_filters, encode_batch, encode_topics, nfa_match,
)
from emqx_tpu.ops.match_kernel import (
    SERVE_FLAT_MULT, decode_flat, decode_packed, nfa_match_packed,
)

K = 12          # max_matches: above SERVE_FLAT_MULT, so a batch of
A = 4           # full rows runs past flat_cap = 8·B; active_slots 4
DEPTH = 10
TAIL = "1/2/3/4/5/6/7"


def _rooted(root):
    """12 filters that match ``<root>/1/2/3/4/5/6/7`` with at most four
    states active: the 8-long ``#`` chain, the exact filter and three
    one-``+`` variants."""
    levels = [root] + TAIL.split("/")
    chain = ["/".join(levels[:i] + ["#"]) for i in range(1, 9)]
    plus = ["/".join(levels[:i] + ["+"] + levels[i + 1:])
            for i in (1, 2, 3)]
    return chain + ["/".join(levels)] + plus


FILTERS = (
    _rooted("k")                        # k/<TAIL>: n = K exactly
    + _rooted("m") + ["m/+/#"]          # m/<TAIL>: n = K + 1
    # s/a/b/z: six states live at level 3, two more than A holds
    + ["s/a/b/z", "s/a/+/z", "s/+/b/z", "s/+/+/z", "+/a/b/z", "+/a/+/z"]
)
FULL, OVER, SPILL = f"k/{TAIL}", f"m/{TAIL}", "s/a/b/z"
ONE = "x/a/c/z"                         # matches +/a/+/z alone


def _every4(every4, rest):
    """``every4`` on every fourth row (None: no such row), ``rest`` on
    the rows between."""
    return lambda i, bucket: (every4 if every4 and i % 4 == 0
                              else rest.format(i=i))


def _sum_counts(off):
    """One id a row on the first ``bucket // 2 + off`` rows, none after:
    the batch's Σcounts sits on a power of two or one beside it."""
    return lambda i, bucket: ONE if i < bucket // 2 + off else f"zero/{i}"


# case → row i's topic in a batch padded to ``bucket``.  "past_flat_cap"
# fills every row with K ids, 12·n > 8·B; "all_spill" flags every row
# (n > K on every fourth, the active set on the rest) under the cap
CASES = {
    "zero_matches": _every4(None, "zero/{i}"),
    "n_eq_k": _every4(FULL, "zero/{i}"),
    "n_gt_k": _every4(OVER, "zero/{i}"),
    "active_spill": _every4(SPILL, "zero/{i}"),
    "past_flat_cap": _every4(FULL, FULL),
    "all_spill": _every4(OVER, SPILL),
    "sum_pow2_less_1": _sum_counts(-1),
    "sum_pow2": _sum_counts(0),
    "sum_pow2_plus_1": _sum_counts(1),
}
BUCKETS = (64, 128, 256, 512, 1024, 2048)


@lru_cache(maxsize=None)
def _table():
    t = compile_filters(FILTERS, depth=DEPTH, state_bucket=8)
    return t, tuple(jnp.asarray(a) for a in t.device_arrays())


def _match(fn, names, bucket):
    t, tabs = _table()
    words, lens, is_sys = encode_topics(t, names, batch=bucket)
    return fn(jnp.asarray(words), jnp.asarray(lens), jnp.asarray(is_sys),
              *tabs, active_slots=A, max_matches=K,
              flat_cap=SERVE_FLAT_MULT * bucket)


def _four_array_decode(res, n, k):
    """The reference decode: the five-output program's flat ids, counts
    and both overflow vectors, fetched and ORed on the host."""
    matches, nk, aover, mover = jax.device_get(
        (res.matches, res.n_matches, res.active_overflow,
         res.match_overflow))
    sp = (aover > 0) | (mover > 0)
    rows = [seg.tolist() for seg in decode_flat(matches, nk, k)[:n]]
    return rows, np.flatnonzero(sp[:n]).tolist()


def _count_arrays_fetched(monkeypatch):
    """A d2h trip is one ARRAY fetched, however many a call names."""
    seen = {"n": 0}
    orig = jax.device_get

    def spy(x):
        seen["n"] += len(jax.tree_util.tree_leaves(x))
        return orig(x)

    monkeypatch.setattr(jax, "device_get", spy)
    return seen


@pytest.mark.parametrize("bucket", BUCKETS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_packed_readback_parity_with_four_array_decode(
        case, bucket, monkeypatch):
    n = bucket - 3                      # the last rows are padding
    names = [CASES[case](i, bucket) for i in range(n)]
    res = _match(nfa_match, names, bucket)
    packed = _match(nfa_match_packed, names, bucket)
    assert packed.shape == (bucket + SERVE_FLAT_MULT * bucket,)
    assert packed.dtype == jnp.int32
    np.testing.assert_array_equal(
        np.asarray(packed),
        np.concatenate([np.asarray(res.row_meta),
                        np.asarray(res.matches)]))

    seen = _count_arrays_fetched(monkeypatch)
    rows, spilled = decode_packed(packed, n, K)
    assert seen["n"] == 1
    assert (rows, spilled) == _four_array_decode(res, n, K)

    # the batch is the case it says it is, and unspilled rows are exact
    t, _tabs = _table()
    for i in set(range(0, n, max(1, n // 16))) - set(spilled):
        assert {t.accept_filters[a] for a in rows[i]} == \
            {f for f in FILTERS if T.match(names[i], f)}, (i, names[i])
    if case == "zero_matches":
        assert not any(rows) and spilled == []
    elif case == "n_eq_k":
        assert len(rows[0]) == K and spilled == []
    elif case == "n_gt_k":
        assert len(rows[0]) == K and spilled == list(range(0, n, 4))
    elif case == "active_spill":
        assert spilled == list(range(0, n, 4))
    elif case == "all_spill":
        assert spilled == list(range(n))
        assert all(len(r) == K for r in rows[::4])
    elif case.startswith("sum_pow2"):
        off = {"sum_pow2_less_1": -1, "sum_pow2": 0,
               "sum_pow2_plus_1": 1}[case]
        assert sum(map(len, rows)) == bucket // 2 + off
        assert spilled == []
    else:
        whole = SERVE_FLAT_MULT * bucket // K   # rows wholly under the cap
        assert spilled == list(range(whole, n))
        assert all(len(r) == K for r in rows[:whole])
        assert len(rows[whole]) == SERVE_FLAT_MULT * bucket - whole * K
        assert not any(rows[whole + 1:])


def test_twins_keep_their_kernel_name_for_the_trace():
    """``cellbench``'s ``nfa_match_roofline`` sums the device time of the
    XLA modules whose name contains "nfa_match": the twin's module is
    named after its function and has to stay among them."""
    from emqx_tpu.ops.join_match import join_match_packed

    assert nfa_match_packed.__name__ == "_nfa_match_packed"
    assert join_match_packed.__name__ == "_join_match_packed"
    lowered = nfa_match_packed.lower(
        *(jax.ShapeDtypeStruct(s, d) for s, d in (
            ((64, 8), jnp.int32), ((64,), jnp.int32), ((64,), jnp.bool_),
            ((128, 4), jnp.int32), ((128, 8), jnp.int32),
            ((2,), jnp.int32))),
        active_slots=A, max_matches=K, flat_cap=SERVE_FLAT_MULT * 64)
    assert "module @jit__nfa_match_packed" in lowered.as_text()[:200]


# ---------------------------------------------------------------------------
# through a real MatchService, on the serial (flag-off) path
# ---------------------------------------------------------------------------

SUBS = [f"room/+/k{i}" for i in range(8)] + ["deep/#"]
TOPICS = [f"room/{i}/k{i % 8}" for i in range(20)] + ["deep/a/b"]


async def _settle(pred, timeout=30.0):
    deadline = asyncio.get_running_loop().time() + timeout
    while not pred() and asyncio.get_running_loop().time() < deadline:
        await asyncio.sleep(0.01)
    return pred()


class _Service:
    """A started MatchService over SUBS (serial path unless ``kw`` says
    otherwise), synced and warm."""

    def __init__(self, **kw):
        self.kw = kw

    async def __aenter__(self):
        b = Broker()
        for i, flt in enumerate(SUBS):
            cid = f"s{i % 4}"
            if cid not in b.sessions:
                b.open_session(cid)
            b.subscribe(cid, flt, SubOpts())
        self.m = Metrics()
        self.ms = ms = MatchService(b, depth=8, table="python",
                                    bypass_rate=0.0, metrics=self.m,
                                    **self.kw)
        await ms.start()
        assert await _settle(
            lambda: ms.ready and ms._seen_epoch == b.router.epoch
            and ms.dev.epoch == ms.inc.epoch)
        return self

    async def __aexit__(self, *exc):
        await self.ms.stop()

    async def serve(self, topics):
        """One batch; → its minted hints and what the counters added."""
        names = ("tpu.match.batches", "tpu.match.readback_roundtrips",
                 "tpu.match.readback_bytes", "broker.match.cpu_fallback")
        before = [self.m.get(k) for k in names]
        await self.ms.prefetch_many({t: 1 for t in topics})
        hints = {t: (sorted(self.ms._hints[t][2]),
                     sorted(self.ms._hints[t][3])) for t in topics}
        return hints, {k: self.m.get(k) - v
                       for k, v in zip(names, before)}


@pytest.mark.parametrize("backend", ["hash", "join"])
@pytest.mark.parametrize("mode", ["serial", "pipeline"])
def test_serial_batch_counts_one_trip_and_the_packed_bytes(mode, backend):
    """Both serve loops read the same one answer: 1 buffer and
    4·(B + flat_cap) bytes a group."""
    async def main():
        async with _Service(backend=backend,
                            pipeline=(mode == "pipeline")) as s:
            assert backend == "hash" or await _settle(
                lambda: s.ms.dev._jarrs is not None)
            hints, added = await s.serve(TOPICS)
            assert s.m.get("tpu.match.backend_join_dispatches") == \
                (backend == "join")
            assert added == {
                "tpu.match.batches": 1,
                "tpu.match.readback_roundtrips": 1,
                "tpu.match.readback_bytes":
                    4 * (64 + SERVE_FLAT_MULT * 64),
                "broker.match.cpu_fallback": 0,
            }
            assert hints["deep/a/b"][0] == ["deep/#"]
            assert hints["room/3/k3"][0] == ["room/+/k3"]

    asyncio.run(main())


def test_serial_batch_serves_inside_a_profiler_session(tmp_path):
    """The packed trip has to serve while ``jax.profiler`` is running:
    the same batch mints the same hints and nothing falls to the host
    trie (PR 28's packed path failed for as long as the profiler ran)."""
    async def main():
        async with _Service() as s:
            plain, _ = await s.serve(TOPICS)
            s.ms._hints.clear()
            with jax.profiler.trace(str(tmp_path)):
                traced, added = await s.serve(TOPICS)
            assert traced == plain
            assert added["tpu.match.batches"] == 1
            assert added["tpu.match.readback_roundtrips"] == 1
            assert added["broker.match.cpu_fallback"] == 0

    asyncio.run(main())


def test_kernel_cache_serves_the_packed_array_one_buffer(tmp_path):
    """Through a kernel cache (``match.segments.enable``) the AOT
    executable of a served shape is the one-output program: the batch
    fetches one buffer and mints the host trie's hints."""
    async def main():
        async with _Service(segments=True,
                            segments_dir=str(tmp_path)) as s:
            kc = s.ms.dev.kernel_cache
            assert kc is not None
            enc = encode_batch(s.ms.inc, TOPICS, batch=64, depth=s.ms.depth)
            hits = kc.hits
            packed = s.ms.dev.serve(*enc, block_compile=False)
            assert kc.hits == hits + 1      # warmed: no CompileMiss
            assert packed.shape == (64 + SERVE_FLAT_MULT * 64,)
            plain_hints = {t: [f for f in SUBS if T.match(t, f)]
                           for t in TOPICS}
            hints, added = await s.serve(TOPICS)
            assert {t: h[0] for t, h in hints.items()} == plain_hints
            assert added["tpu.match.readback_roundtrips"] == \
                added["tpu.match.batches"] > 0
            assert added["tpu.match.readback_bytes"] == \
                4 * (64 + SERVE_FLAT_MULT * 64) * added["tpu.match.batches"]
            assert added["broker.match.cpu_fallback"] == 0

    asyncio.run(main())
