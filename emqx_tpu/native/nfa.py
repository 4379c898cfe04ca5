"""ctypes wrapper for the native incremental NFA (``nfa.cpp``).

``NativeNfa`` mirrors the mutation/drain surface of
:class:`emqx_tpu.ops.incremental.IncrementalNfa` (the semantics oracle;
property-tested equivalent in tests/test_native_nfa.py) at 10M-filter
scale: bulk build in seconds, O(filter) add/remove, dirty-row delta
drain for the device twin, host-side authoritative match for fail-open.

Falls back to ``None`` when the toolchain can't build the .so — callers
use the Python IncrementalNfa below ~1M filters.
"""

from __future__ import annotations

import ctypes
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .build import load_library

# int32s per cuckoo bucket row — MUST match nfa.cpp's BUCKET_SLOTS*4
# and ops/compiler.py's BUCKET_SLOTS; drift would size the fill buffers
# wrong and let the C side write past them (verified at construction,
# see NativeNfa.__init__)
_ROW = 8


def _check_row() -> None:
    from ..ops.compiler import BUCKET_SLOTS

    if _ROW != 4 * BUCKET_SLOTS:
        raise RuntimeError(
            f"native/nfa.py _ROW={_ROW} out of sync with "
            f"compiler.BUCKET_SLOTS={BUCKET_SLOTS} (expected "
            f"{4 * BUCKET_SLOTS}); update BOTH plus nfa.cpp")

__all__ = ["NativeNfa", "available"]

_lib = None
_checked = False


def _load():
    global _lib, _checked
    if _checked:
        return _lib
    _checked = True
    lib = load_library("nfa")
    if lib is None:
        return None
    lib.nfa_new.restype = ctypes.c_void_p
    lib.nfa_new.argtypes = [ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
                            ctypes.c_uint64]
    lib.nfa_free.argtypes = [ctypes.c_void_p]
    lib.nfa_add.restype = ctypes.c_int32
    lib.nfa_add.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int32]
    lib.nfa_remove.restype = ctypes.c_int32
    lib.nfa_remove.argtypes = lib.nfa_add.argtypes
    lib.nfa_bulk_add.restype = ctypes.c_int64
    lib.nfa_bulk_add.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                 ctypes.c_int64]
    lib.nfa_intern.restype = ctypes.c_int32
    lib.nfa_intern.argtypes = lib.nfa_add.argtypes
    lib.nfa_bulk_intern.restype = ctypes.c_int64
    lib.nfa_bulk_intern.argtypes = lib.nfa_bulk_add.argtypes
    lib.nfa_grow_edges_to.restype = ctypes.c_int64
    lib.nfa_grow_edges_to.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.nfa_aid_of.restype = ctypes.c_int32
    lib.nfa_aid_of.argtypes = lib.nfa_add.argtypes
    lib.nfa_alloc_alias.restype = ctypes.c_int32
    lib.nfa_alloc_alias.argtypes = lib.nfa_add.argtypes
    lib.nfa_free_alias.restype = ctypes.c_int32
    lib.nfa_free_alias.argtypes = [ctypes.c_void_p, ctypes.c_int32]
    lib.nfa_match_topic.restype = ctypes.c_int32
    lib.nfa_match_topic.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int32,
    ]
    lib.nfa_sizes.argtypes = [ctypes.c_void_p,
                              ctypes.POINTER(ctypes.c_int64)]
    lib.nfa_fill_tables.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
    ]
    lib.nfa_vocab_fill.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.nfa_accept_get.restype = ctypes.c_int32
    lib.nfa_accept_get.argtypes = [ctypes.c_void_p, ctypes.c_int32,
                                   ctypes.c_char_p, ctypes.c_int32]
    lib.nfa_set_device_epoch.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.nfa_mark_resized.argtypes = [ctypes.c_void_p]
    lib.nfa_delta_sizes.argtypes = lib.nfa_sizes.argtypes
    lib.nfa_delta_fill.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32),
    ]
    _lib = lib
    return lib


def available() -> bool:
    return _load() is not None


def _i32p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


class _AcceptView:
    """aid→filter sequence view over the native accepts vector."""

    __slots__ = ("_nfa",)

    def __init__(self, nfa: "NativeNfa") -> None:
        self._nfa = nfa

    def __getitem__(self, aid: int) -> Optional[str]:
        if aid < 0 or aid >= len(self):
            # a real IndexError: sequence semantics (including the
            # legacy iteration protocol) must terminate
            raise IndexError(aid)
        return self._nfa.accept_get(aid)

    def __len__(self) -> int:
        return int(self._nfa._sizes()[4])


class NativeNfa:
    """Handle-owning wrapper; see module docstring."""

    def __init__(self, depth: int = 8, state_bucket: int = 1024,
                 edge_bucket: int = 64, seed: int = 0xE709) -> None:
        _check_row()
        lib = _load()
        if lib is None:
            raise RuntimeError("native nfa library unavailable")
        self._lib = lib
        self._h = ctypes.c_void_p(lib.nfa_new(depth, state_bucket,
                                              edge_bucket, seed))
        self.depth = depth
        # live vocab view: same dict OBJECT updated in place (append-only,
        # id order) so encode_batch's per-table encoder cache and its
        # push-incremental interning both work unchanged
        self._vocab: Dict[str, int] = {}

    def close(self) -> None:
        if self._h:
            self._lib.nfa_free(self._h)
            self._h = None

    def __del__(self):  # pragma: no cover
        try:
            self.close()
        except Exception:
            pass

    # -- mutation ----------------------------------------------------------

    def add(self, flt: str) -> bool:
        b = flt.encode()
        r = self._lib.nfa_add(self._h, b, len(b))
        if r < 0:
            raise ValueError(
                f"filter {flt!r} invalid (deeper than table depth, or "
                "'#' not in final position)"
            )
        return bool(r)

    def remove(self, flt: str) -> bool:
        b = flt.encode()
        return bool(self._lib.nfa_remove(self._h, b, len(b)))

    def bulk_add(self, filters: Sequence[str]) -> int:
        """Add many filters in one native call (the 10M-scale build path).
        Invalid lines ('#' not final / too deep) are skipped, not
        truncate-inserted; the return counts filters actually added.
        Filters containing '\\n' (legal in MQTT) can't ride the
        newline-framed bulk path and fall back to individual adds."""
        plain = [f for f in filters if "\n" not in f]
        odd = [f for f in filters if "\n" in f]
        blob = "\n".join(plain).encode()
        n = int(self._lib.nfa_bulk_add(self._h, blob, len(blob)))
        for f in odd:
            try:
                n += 1 if self.add(f) else 0
            except ValueError:
                pass
        # warm probe: the first few mutations after a large bulk absorb a
        # one-off allocator consolidation stall (measured ~200 ms at 2M
        # filters); pay it here, not on a live subscribe
        for i in range(4):
            probe = f"\x01warm/{i}".encode()
            self._lib.nfa_add(self._h, probe, len(probe))
            self._lib.nfa_remove(self._h, probe, len(probe))
        if n > 100_000:
            # absorb the one-off post-bulk allocator stall (~200 ms of
            # glibc consolidation measured at 2M filters) here rather
            # than on the first live delta: exercise the flush path AND
            # a few heap allocations of delta-buffer size, then re-flag
            # resized so any attached consumer still performs the full
            # upload the bulk requires
            self.flush()
            for _ in range(4):
                np.empty((4096, _ROW), np.int32)
                np.empty((4096, 4), np.int32)
            self._lib.nfa_mark_resized(self._h)
        return n

    def intern(self, word: str) -> int:
        """Intern ``word`` into the native vocab WITHOUT adding a
        filter; returns its id.  Ids assign append-only (size+1), so
        replaying one word sequence into several tables keeps their
        vocabs identical — the multichip shard subtables share an
        encode vocab this way."""
        b = word.encode()
        wid = int(self._lib.nfa_intern(self._h, b, len(b)))
        # keep the live dict view in lockstep (append-only invariant)
        if word not in self._vocab:
            self._vocab[word] = wid
        return wid

    def bulk_intern(self, words: Sequence[str]) -> int:
        """Intern many words in id order with one native call (the
        segment-restore path; NUL framing — words may contain '\\n',
        never NUL)."""
        blob = "\x00".join(words).encode()
        n = int(self._lib.nfa_bulk_intern(self._h, blob, len(blob)))
        for w in words:
            if w not in self._vocab:
                self._vocab[w] = len(self._vocab) + 1
        return n

    def grow_edges_to(self, hb_target: int) -> int:
        """Grow the cuckoo edge table until Hb >= ``hb_target`` (the
        multichip common-Hb restack: lookups probe modulo the table
        size, so stacked shards must share one real bucket count).
        Marks the table resized — the consumer re-uploads in full."""
        return int(self._lib.nfa_grow_edges_to(self._h, int(hb_target)))

    # -- introspection -----------------------------------------------------

    def _sizes(self) -> np.ndarray:
        out = np.zeros(11, np.int64)
        self._lib.nfa_sizes(self._h, out.ctypes.data_as(
            ctypes.POINTER(ctypes.c_int64)))
        return out

    @property
    def n_filters(self) -> int:
        return int(self._sizes()[5])

    @property
    def n_states(self) -> int:
        return int(self._sizes()[2])

    @property
    def epoch(self) -> int:
        return int(self._sizes()[8])

    @property
    def aid_reuses(self) -> int:
        return int(self._sizes()[10])

    def shape_key(self) -> Tuple[int, int, int]:
        s = self._sizes()
        return (int(s[0]), int(s[1]), self.depth)

    def memory_bytes(self) -> Dict[str, int]:
        """Device-array footprint (the HBM math)."""
        s = self._sizes()
        return {
            "node_tab": int(s[0]) * 4 * 4,
            "edge_tab": int(s[1]) * _ROW * 4,
            "n_states": int(s[2]),
            "n_edges": int(s[3]),
        }

    # -- table export ------------------------------------------------------

    def tables(self):
        """Current arrays in kernel order: (node_tab, edge_tab, seeds)."""
        s = self._sizes()
        node_tab = np.empty((int(s[0]), 4), np.int32)
        edge_tab = np.empty((int(s[1]), _ROW), np.int32)
        seeds = np.empty(2, np.int32)
        self._lib.nfa_fill_tables(self._h, _i32p(node_tab), _i32p(edge_tab),
                                  _i32p(seeds))
        return node_tab, edge_tab, seeds

    @property
    def vocab(self) -> Dict[str, int]:
        """Word → id map (id 0 reserved UNKNOWN).  The native vocab is
        append-only; this refreshes the SAME dict in place when it grew."""
        s = self._sizes()
        n = int(s[6])
        if len(self._vocab) != n:
            buf = ctypes.create_string_buffer(int(s[7]) + 1)
            self._lib.nfa_vocab_fill(self._h, buf)
            # NUL-separated: words may legally contain '\n' but never NUL
            words = buf.raw[: max(0, int(s[7]) - 1)].decode().split("\x00")
            for i in range(len(self._vocab), n):
                self._vocab[words[i]] = i + 1
        return self._vocab

    def accept_get(self, aid: int) -> Optional[str]:
        buf = ctypes.create_string_buffer(1024)
        n = self._lib.nfa_accept_get(self._h, aid, buf, 1024)
        return buf.raw[:n].decode() if n >= 0 else None

    def aid_of(self, flt: str) -> int:
        b = flt.encode()
        return int(self._lib.nfa_aid_of(self._h, b, len(b)))

    def alloc_alias(self, flt: str) -> int:
        """Accept id with no trie states (too-deep filters) — same
        contract as IncrementalNfa.alloc_alias."""
        b = flt.encode()
        return int(self._lib.nfa_alloc_alias(self._h, b, len(b)))

    def free_alias(self, aid: int) -> None:
        self._lib.nfa_free_alias(self._h, aid)

    @property
    def accept_filters(self) -> "_AcceptView":
        """Read-only aid→filter view (len/indexing); backed by the
        native accepts vector, so no 10M-string Python list."""
        return _AcceptView(self)

    def match_host(self, topic: str, cap: int = 4096) -> List[int]:
        b = topic.encode()
        out = np.empty(cap, np.int32)
        n = self._lib.nfa_match_topic(self._h, b, len(b), _i32p(out), cap)
        if n > cap:  # extremely wide match: retry with exact size
            out = np.empty(n, np.int32)
            n = self._lib.nfa_match_topic(self._h, b, len(b), _i32p(out), n)
        return out[:n].tolist()

    # -- device delta feed -------------------------------------------------

    def set_device_epoch(self, epoch: int) -> None:
        self._lib.nfa_set_device_epoch(self._h, epoch)
        self._device_epoch = epoch

    # attribute-style twin of IncrementalNfa.device_epoch so DeviceNfa
    # drives either table implementation unchanged
    @property
    def device_epoch(self) -> Optional[int]:
        return getattr(self, "_device_epoch", None)

    @device_epoch.setter
    def device_epoch(self, epoch: Optional[int]) -> None:
        # None = no consumer (-2); -1 = attached, nothing acked yet
        self.set_device_epoch(-2 if epoch is None else int(epoch))

    def flush(self):
        """Drain dirty rows as an ``NfaDelta`` (same contract as the
        Python IncrementalNfa.flush: after a resize the consumer must
        re-upload full tables)."""
        from ..ops.incremental import NfaDelta

        hdr = np.zeros(4, np.int64)
        self._lib.nfa_delta_sizes(self._h, hdr.ctypes.data_as(
            ctypes.POINTER(ctypes.c_int64)))
        ns, nb, resized, epoch = (int(x) for x in hdr)
        state_idx = np.empty(ns, np.int32)
        state_rows = np.empty((ns, 4), np.int32)
        bucket_idx = np.empty(nb, np.int32)
        bucket_rows = np.empty((nb, _ROW), np.int32)
        self._lib.nfa_delta_fill(self._h, _i32p(state_idx), _i32p(state_rows),
                                 _i32p(bucket_idx), _i32p(bucket_rows))
        return NfaDelta(
            epoch=epoch, resized=bool(resized),
            state_idx=state_idx, state_rows=state_rows,
            bucket_idx=bucket_idx, bucket_rows=bucket_rows,
        )
