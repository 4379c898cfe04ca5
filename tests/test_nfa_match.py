"""NFA compiler + match kernel parity vs the host oracle/trie.

The contract (SURVEY.md §7 stage 4): for any wildcard filter set and any
topic batch, kernel matches ≡ FilterTrie.match ≡ {f | topic.match(n, f)}.
"""

import numpy as np
import pytest
from _optional import given, settings, st

from emqx_tpu import topic as T
from emqx_tpu.broker import FilterTrie
from emqx_tpu.ops import compile_filters, encode_topics, match_topics, nfa_match

import jax.numpy as jnp


FILTERS = [
    "a/b/c", "a/+/c", "a/#", "#", "+", "+/b", "a/b", "b",
    "$SYS/#", "$SYS/+/x", "x//y", "+/+/+", "a/+/+", "deep/1/2/3/4/5/6/#",
]
TOPICS = [
    "a/b/c", "a/b", "a", "b", "x//y", "x/y", "$SYS/broker", "$SYS/a/x",
    "deep/1/2/3/4/5/6/7/8/9", "nomatch/zzz", "a/q/c", "/", "a/b/c/d",
]


def oracle(name, filters):
    return {f for f in filters if T.match(name, f)}


def test_compile_basic_shapes():
    t = compile_filters(FILTERS, depth=16, state_bucket=8)
    assert t.n_states <= t.S
    assert t.n_accepts == len(set(FILTERS))
    # host-side probe agrees with trie structure: root literal 'a'
    aid = t.vocab["a"]
    assert t.lookup_literal(0, aid) > 0
    assert t.lookup_literal(0, 0) == -1  # UNKNOWN has no edges


def test_compile_rejects_too_deep():
    with pytest.raises(ValueError):
        compile_filters(["a/b/c"], depth=2)


def test_match_kernel_explicit():
    t = compile_filters(FILTERS, depth=16, state_bucket=8)
    got = match_topics(t, TOPICS)
    for name, matched in zip(TOPICS, got):
        assert set(matched) == oracle(name, FILTERS), name


def test_match_kernel_against_trie():
    tr = FilterTrie()
    for f in FILTERS:
        tr.insert(f)
    t = compile_filters(FILTERS, depth=16, state_bucket=8)
    got = match_topics(t, TOPICS)
    for name, matched in zip(TOPICS, got):
        assert set(matched) == set(tr.match(name)), name


def test_batch_padding_rows_inert():
    t = compile_filters(["#", "+", "a/#"], depth=8, state_bucket=8)
    words, lens, is_sys = encode_topics(t, ["a/b"], batch=4)
    res = nfa_match(
        jnp.asarray(words), jnp.asarray(lens), jnp.asarray(is_sys),
        *[jnp.asarray(a) for a in t.device_arrays()],
    )
    n = np.asarray(res.n_matches)
    assert n[0] == 2  # '#', 'a/#'
    assert (n[1:] == 0).all()  # padding matches nothing


def test_empty_filter_set():
    t = compile_filters([], depth=8, state_bucket=8)
    assert match_topics(t, ["a/b", "x"]) == [[], []]


def test_unknown_words_still_match_wildcards():
    t = compile_filters(["+/+", "a/#"], depth=8, state_bucket=8)
    got = match_topics(t, ["zz/ww", "a/zz"])
    assert set(got[0]) == {"+/+"}
    assert set(got[1]) == {"a/#", "+/+"}


def test_match_overflow_reported():
    # 100 filters all matching one topic, K=16 → overflow
    filters = [f"a/{i}/#" for i in range(100)] + ["a/+/+"]
    t = compile_filters(filters, depth=8, state_bucket=8)
    names = [f"a/{i}/x" for i in range(8)]
    words, lens, is_sys = encode_topics(t, names)
    res = nfa_match(
        jnp.asarray(words), jnp.asarray(lens), jnp.asarray(is_sys),
        *[jnp.asarray(a) for a in t.device_arrays()],
        max_matches=2,
    )
    # each topic matches a/<i>/# and a/+/+ = 2 matches → no overflow at K=2
    assert int(np.sum(res.match_overflow)) == 0
    res2 = nfa_match(
        jnp.asarray(words), jnp.asarray(lens), jnp.asarray(is_sys),
        *[jnp.asarray(a) for a in t.device_arrays()],
        max_matches=1,
    )
    # per-row overflow: every one of the 8 rows spilled, flagged exactly
    assert np.asarray(res2.match_overflow)[:8].tolist() == [1] * 8
    assert np.asarray(res2.spilled_rows())[:8].all()
    assert (np.asarray(res2.n_matches)[:8] == 2).all()  # exact beyond K


def test_active_overflow_reported():
    # force active-set spill with tiny A: filters +/+/.../+ at all depths
    filters = []
    for d in range(1, 7):
        for combo in range(2 ** d):
            ws = [("+" if (combo >> i) & 1 else "w") for i in range(d)]
            filters.append(T.join(ws))
    filters = list(set(filters))
    t = compile_filters(filters, depth=8, state_bucket=8)
    words, lens, is_sys = encode_topics(t, ["w/w/w/w/w/w"])
    res = nfa_match(
        jnp.asarray(words), jnp.asarray(lens), jnp.asarray(is_sys),
        *[jnp.asarray(a) for a in t.device_arrays()],
        active_slots=4,
    )
    # the overloaded row is flagged; per-row so the host can fail open
    assert int(np.asarray(res.active_overflow)[0]) > 0
    assert bool(np.asarray(res.spilled_rows())[0])
    with pytest.raises(OverflowError):
        match_topics(t, ["w/w/w/w/w/w"], active_slots=4)


# ---------------------------------------------------------------------------
# property: kernel ≡ oracle on random tables/batches
# ---------------------------------------------------------------------------

word_st = st.sampled_from(["a", "b", "c", "", "d1"])
name_st = st.lists(
    st.one_of(word_st, st.just("$s")), min_size=1, max_size=6
).map(T.join)
filter_st = st.lists(
    st.one_of(word_st, st.just("+")), min_size=1, max_size=6
).flatmap(lambda ws: st.sampled_from([ws, ws + ["#"], ["#"]])).map(T.join)


@settings(max_examples=30, deadline=None)
@given(
    st.lists(filter_st, min_size=0, max_size=25),
    st.lists(name_st, min_size=1, max_size=12),
)
def test_kernel_equals_oracle_random(filters, names):
    t = compile_filters(filters, depth=8, state_bucket=8)
    got = match_topics(t, names, active_slots=64, max_matches=64)
    for name, matched in zip(names, got):
        assert set(matched) == oracle(name, set(filters)), (name, filters)


def test_flat_output_parity_and_truncation():
    """Flat mode: globally compacted ids decode to the same per-row sets
    as compact mode; rows truncated by K or the global cap are flagged."""
    from emqx_tpu.ops.match_kernel import decode_flat

    t = compile_filters(FILTERS, depth=16, state_bucket=8)
    words, lens, is_sys = encode_topics(t, TOPICS)
    K = 8
    cap = 128
    r = nfa_match(
        jnp.asarray(words), jnp.asarray(lens), jnp.asarray(is_sys),
        *[jnp.asarray(a) for a in t.device_arrays()],
        active_slots=16, max_matches=K, flat_cap=cap,
    )
    flat = np.asarray(r.matches)
    assert flat.shape == (cap,)
    n = np.asarray(r.n_matches)
    spilled = np.asarray(r.spilled_rows())
    rows = decode_flat(flat, n, K)
    for i, name in enumerate(TOPICS):
        want = oracle(name, FILTERS)
        got = {t.accept_filters[a] for a in rows[i]}
        if not spilled[i]:
            assert got == want, (name, got, want)
        else:
            assert got <= want

    # tiny global cap: every row past the cap must be flagged
    r2 = nfa_match(
        jnp.asarray(words), jnp.asarray(lens), jnp.asarray(is_sys),
        *[jnp.asarray(a) for a in t.device_arrays()],
        active_slots=16, max_matches=K, flat_cap=4,
    )
    n2 = np.asarray(r2.n_matches)
    sp2 = np.asarray(r2.spilled_rows())
    nk = np.minimum(n2, K)
    offs = np.cumsum(nk) - nk
    for i in range(len(TOPICS)):
        if offs[i] + nk[i] > 4:
            assert sp2[i], i
    # un-truncated prefix rows still decode correctly
    rows2 = decode_flat(np.asarray(r2.matches), n2, K)
    for i in range(len(TOPICS)):
        if not sp2[i]:
            got = {t.accept_filters[a] for a in rows2[i]}
            assert got == oracle(TOPICS[i], FILTERS)


def test_row_meta_packs_counts_and_spill_flags():
    """Flat mode's packed (B,) row_meta vector (ISSUE 11): low 16 bits
    = min(n, K), bit 16 = the fail-open flag — everything the host
    needs to split the flat ids; non-flat modes carry None."""
    from emqx_tpu.ops.match_kernel import decode_row_meta

    t = compile_filters(FILTERS, depth=16, state_bucket=8)
    words, lens, is_sys = encode_topics(t, TOPICS)
    K = 8
    args = (jnp.asarray(words), jnp.asarray(lens), jnp.asarray(is_sys),
            *[jnp.asarray(a) for a in t.device_arrays()])
    r = nfa_match(*args, active_slots=16, max_matches=K, flat_cap=128)
    meta = np.asarray(r.row_meta)
    nk, sp = decode_row_meta(meta)
    np.testing.assert_array_equal(
        nk, np.minimum(np.asarray(r.n_matches), K))
    np.testing.assert_array_equal(sp, np.asarray(r.spilled_rows()))
    # truncation by a tiny global cap lands in the packed flag too
    r2 = nfa_match(*args, active_slots=16, max_matches=K, flat_cap=4)
    _, sp2 = decode_row_meta(np.asarray(r2.row_meta))
    np.testing.assert_array_equal(sp2, np.asarray(r2.spilled_rows()))
    # non-flat modes: no meta output
    assert nfa_match(*args, active_slots=16, max_matches=K
                     ).row_meta is None
