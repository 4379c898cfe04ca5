"""Batch topic encoding for the match kernel — the serving-path front.

Round 1 measured the pure-Python per-word dict loop at ~82% of the
per-batch budget; this module replaces it with
the native C++ tokenizer/interner (``emqx_tpu/native/encoder.cpp``,
loaded via ctypes) and keeps the Python loop as a fallback with
identical output.

An encoder instance is cached per vocab *object* (the vocab is
append-only between compactions, so new words are pushed incrementally;
a compaction swaps the dict instance, which drops the cache entry).
"""

from __future__ import annotations

import ctypes
import logging
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from .. import topic as T

log = logging.getLogger(__name__)

__all__ = ["TopicEncoder", "encode_batch"]

_lib = None
_lib_tried = False


def _native():
    global _lib, _lib_tried
    if not _lib_tried:
        _lib_tried = True
        from ..native import load_library

        lib = load_library("encoder")
        if lib is not None:
            lib.enc_new.restype = ctypes.c_void_p
            lib.enc_free.argtypes = [ctypes.c_void_p]
            lib.enc_add_words.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_int32,
            ]
            lib.enc_vocab_size.argtypes = [ctypes.c_void_p]
            lib.enc_vocab_size.restype = ctypes.c_int64
            lib.enc_encode.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
                ctypes.c_int32, ctypes.c_int32,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ]
            lib.enc_encode.restype = ctypes.c_int32
        _lib = lib
    return _lib


class TopicEncoder:
    """Vocab-bound encoder; push-incremental, native when available."""

    def __init__(self, vocab: Dict[str, int]) -> None:
        self.vocab = vocab
        self._pushed = 0
        self._h = None
        lib = _native()
        if lib is not None:
            self._h = ctypes.c_void_p(lib.enc_new())

    def __del__(self):  # pragma: no cover - interpreter teardown order
        lib = _lib
        if lib is not None and self._h:
            try:
                lib.enc_free(self._h)
            except Exception:
                pass

    def _push_new_words(self) -> None:
        """Ship vocab entries added since the last call (dict preserves
        insertion order; interning only appends)."""
        n = len(self.vocab)
        if n == self._pushed:
            return
        items = list(self.vocab.items())[self._pushed:]
        buf = b"\x00".join(w.encode("utf-8") for w, _ in items)
        ids = np.fromiter((i for _, i in items), np.int32, len(items))
        _lib.enc_add_words(
            self._h, buf, len(buf),
            ids.ctypes.data_as(ctypes.c_void_p), len(items),
        )
        self._pushed = n

    def encode(
        self, names: Sequence[str], depth: int, batch: Optional[int] = None
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Mirror of the round-1 ``encode_topics`` contract: returns
        ``(words (B,D) int32, lens (B,) int32, is_sys (B,) bool)`` with
        inert padding rows (len sentinel D+2, is_sys True, UNKNOWN words).
        """
        D = depth
        B = batch if batch is not None else len(names)
        n = len(names)
        if n > B:
            raise ValueError(f"{n} topics > batch {B}")
        words = np.zeros((B, D), np.int32)
        lens = np.full(B, D + 2, np.int32)
        is_sys = np.ones(B, bool)
        if n == 0:
            return words, lens, is_sys
        if self._h is not None:
            self._push_new_words()
            joined = "\x00".join(names).encode("utf-8")
            sys8 = np.zeros(n, np.uint8)
            done = _lib.enc_encode(
                self._h, joined, len(joined), n, D,
                words.ctypes.data_as(ctypes.c_void_p),
                lens.ctypes.data_as(ctypes.c_void_p),
                sys8.ctypes.data_as(ctypes.c_void_p),
            )
            if done == n:
                is_sys[:n] = sys8.astype(bool)
                return words, lens, is_sys
            # a topic smuggled a NUL (forbidden in MQTT): the segment
            # count diverged, which would row-shift other topics'
            # answers — fall back for the whole batch
            log.warning("native encode rejected batch (%d); falling back",
                        done)
            words[:n] = 0
            lens[:n] = D + 2
        vocab = self.vocab
        for r, name in enumerate(names):
            ws = T.words(name)
            lens[r] = min(len(ws), D + 1)
            is_sys[r] = name.startswith("$")
            for i, w in enumerate(ws[:D]):
                words[r, i] = vocab.get(w, 0)
        return words, lens, is_sys


def encode_batch(
    table, names: Sequence[str], batch: Optional[int] = None,
    depth: Optional[int] = None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Encode against any table-like with ``.vocab`` and ``.depth``
    (NfaTable, IncrementalNfa).  The encoder rides on the table object
    itself and is rebuilt when the vocab instance is swapped
    (compaction), so its lifetime exactly tracks the table's."""
    enc = getattr(table, "_topic_encoder", None)
    if enc is None or enc.vocab is not table.vocab:
        enc = TopicEncoder(table.vocab)
        try:
            object.__setattr__(table, "_topic_encoder", enc)
        except (AttributeError, TypeError):
            pass  # slotted/frozen table: encoder lives for this call only
    return enc.encode(names, depth if depth is not None
                      else table.depth, batch=batch)
