"""Native topic encoder ≡ pure-Python fallback, byte for byte.

The encoder is the serving-path front; parity
here is what lets the native path replace the Python loop safely.
"""

import numpy as np
from _optional import given, settings, st

from emqx_tpu.ops import TopicEncoder, compile_filters, encode_batch
from emqx_tpu.ops import encode as E


def _python_encode(enc, names, depth, batch=None):
    h, enc._h = enc._h, None
    try:
        return enc.encode(names, depth, batch=batch)
    finally:
        enc._h = h


def test_native_available():
    """The image ships g++; the native path must actually build."""
    assert E._native() is not None


def test_parity_basic():
    tbl = compile_filters(["a/+/c", "a/b/#", "x/y", "$SYS/#", "a//c"])
    names = [
        "a/b/c", "x/y", "$SYS/broker/x", "a//c", "", "unseen/words/here",
        "a", "very/deep/topic/a/b/c/d/e/f/g/h",
    ]
    enc = TopicEncoder(tbl.vocab)
    w1, l1, s1 = enc.encode(names, tbl.depth, batch=16)
    w2, l2, s2 = _python_encode(enc, names, tbl.depth, batch=16)
    np.testing.assert_array_equal(w1, w2)
    np.testing.assert_array_equal(l1, l2)
    np.testing.assert_array_equal(s1, s2)


topic_st = st.lists(
    st.text(
        alphabet=st.characters(
            blacklist_characters="\x00",
            blacklist_categories=("Cs",),
        ),
        max_size=6,
    ).map(lambda s: s.replace("/", "_")),
    min_size=1,
    max_size=10,
).map("/".join)


@settings(max_examples=50, deadline=None)
@given(st.lists(topic_st, min_size=0, max_size=20))
def test_parity_property(names):
    vocab = {}
    for n in names[: len(names) // 2]:  # half the words are known
        for w in n.split("/"):
            vocab.setdefault(w, len(vocab) + 1)
    enc = TopicEncoder(vocab)
    w1, l1, s1 = enc.encode(names, 8)
    w2, l2, s2 = _python_encode(enc, names, 8)
    np.testing.assert_array_equal(w1, w2)
    np.testing.assert_array_equal(l1, l2)
    np.testing.assert_array_equal(s1, s2)


def test_incremental_vocab_push():
    vocab = {"a": 1}
    enc = TopicEncoder(vocab)
    w, _, _ = enc.encode(["a/b"], 4)
    assert w[0, 0] == 1 and w[0, 1] == 0
    vocab["b"] = 2  # interned later, as IncrementalNfa does
    w, _, _ = enc.encode(["a/b"], 4)
    assert w[0, 1] == 2


def test_nul_topic_falls_back():
    tbl = compile_filters(["a/b"])
    names = ["a/b", "bad\x00topic"]
    w, l, s = encode_batch(tbl, names, batch=4)
    # fallback still encodes row 0 correctly
    assert l[0] == 2 and bool(s[0]) is False


def test_nul_topic_must_not_row_shift_neighbors():
    """A NUL-smuggling topic in the MIDDLE of a batch must not shift the
    encodings of the innocent topics after it (native path rejects the
    whole batch; Python fallback encodes per-topic)."""
    tbl = compile_filters(["a/b", "x/y/z"])
    names = ["ok/first", "bad\x00topic", "x/y/z"]
    w, l, s = encode_batch(tbl, names, batch=4)
    enc = TopicEncoder(tbl.vocab)
    w2, l2, s2 = _python_encode(enc, names, tbl.depth, batch=4)
    np.testing.assert_array_equal(w, w2)
    np.testing.assert_array_equal(l, l2)
    # the innocent last topic keeps its true encoding
    assert l[2] == 3
    assert w[2, 0] == tbl.vocab["x"] and w[2, 2] == tbl.vocab["z"]


def test_padding_rows_inert():
    tbl = compile_filters(["a/b"])
    w, l, s = encode_batch(tbl, ["a/b"], batch=8)
    assert (l[1:] == tbl.depth + 2).all()
    assert s[1:].all()
    assert (w[1:] == 0).all()
