"""Relational-join match backend — the TrieJax recast of the NFA walk.

The hash backend (:mod:`~emqx_tpu.ops.match_kernel`) resolves each
literal transition with two wide cuckoo-bucket gathers: 2 × 16 int32
per (row, active-slot) regardless of how many land, and the bucket
table itself carries ≥25% padding by the growth rule.  TrieJax
(PAPERS.md, arxiv 1905.08021) shows the same trie-walk workload recast
as a worst-case-optimal relational join vectorizes without either
cost: wildcard match IS a level-by-level join of the (level, token)
topic relation against the (state, token, next) edge relation.

This module stores the edge relation **sorted** and answers each level
step with a vectorized ``searchsorted`` intersection instead of hash
probes:

* ``state_start (S+1,) int32`` — CSR offsets: state ``s``'s edges live
  at rows ``[state_start[s], state_start[s+1])`` of the relation;
* ``edge_word (E,) int32`` — the edge tokens, sorted within each state
  segment (the relation is lexicographically sorted by (state, word));
* ``edge_next (E,) int32`` — the target state per row, ``-1`` for a
  TOMBSTONE (a deleted edge whose row is kept so sortedness — and the
  device copy — survive without a rebuild);
* ``overlay (OVERLAY_CAP, 3) int32`` — rows ``[state, word, next]`` of
  edges added since the last rebuild: insertions cannot keep a packed
  CSR sorted in place, so they land here until the next compaction
  folds them in.  The overlay itself is SORTED by (state, word) with
  ``INT32_MAX`` sentinel rows packed at the end, so the kernel
  resolves it with a second unrolled lower-bound search —
  ``log2(OVERLAY_CAP)`` two-int32 gathers per (row, slot) instead of
  the former dense 256-wide compare (ROADMAP maintenance (c): that
  compare was O(B·A·256) on EVERY dispatch, paid even with an empty
  overlay).  The host re-sorts on mutation and ships the overlay
  whole (3 KB) — mutations are rare, dispatches are not.

The lookup per (row, slot) is one CSR-offset gather plus an unrolled
lower-bound binary search over the state's own segment — ``log2(E)``
single-int32 gathers worst case, and the relation rows are exactly the
live edges (no bucket padding, no probe loops, no seeds).  The walk,
accepts, ``$``-topic masking and the flat/`row_meta`` epilogue are the
SHARED :func:`~emqx_tpu.ops.match_kernel.nfa_walk`, so hint/match
parity with the hash backend is structural.

**Maintenance** (:class:`JoinRelation`): the host keeps a shadow copy
of the cuckoo table and diffs each drained delta's dirty buckets
against it — deletions tombstone in place (one scatter), re-additions
revive their tombstone, fresh edges append to the overlay; a cuckoo
kick chain (the same edge relocating between buckets) cancels out of
the diff entirely, and a cuckoo RESEED doesn't touch the relation at
all (it is keyed by (state, word), not by bucket).  When the overlay
fills, the relation rebuilds from the shadow (one ``lexsort``, the
same cost class as the edge-table growth that usually triggered it).
Table compaction always rebuilds clean (overlay empty), which is when
segments persist the arrays (storage/segments.py format v2).

**Routing** (:class:`BackendAutotuner`): neither backend wins every
shape — the hash probe is two bulk gathers (good when the frontier is
wide and the table small), the join search is ``log2(segment)`` steps
(good when buckets are padded and fanout is skewed).  The autotuner
times both per (B, D, S, Hb) shape on representative topics, persists
its pick table as checksummed JSON next to the XLA disk cache, and
:class:`~emqx_tpu.ops.kernel_cache.MatchKernelCache` serves whichever
kernel won that shape.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import threading
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from .compiler import BUCKET_SLOTS

log = logging.getLogger(__name__)

__all__ = ["OVERLAY_CAP", "OVERLAY_EMPTY", "JoinRelation", "OverlayFull",
           "join_match", "join_match_packed", "relation_capacity",
           "BackendAutotuner"]

#: overlay rows available between rebuilds.  Small on purpose: the
#: kernel binary-searches the overlay per (row, slot), so its size
#: rides every dispatch (log2(CAP) steps); a full overlay just means
#: one rebuild (a lexsort over live edges — cheaper than the cuckoo
#: growth path that lands in the same sync).
OVERLAY_CAP = 256

#: sentinel state/word for unused overlay rows: sorts AFTER every live
#: (state, word) pair, so the lower-bound search never lands on one
#: (and no live state or word id can ever equal it)
OVERLAY_EMPTY = np.int32(2**31 - 1)


def relation_capacity(hb: int) -> int:
    """Relation row capacity for a cuckoo table of ``hb`` buckets.

    Slaved to the hash table's slot capacity so the two backends'
    shape keys stay one (S, Hb) pair: the cuckoo holds at most
    ``hb * BUCKET_SLOTS`` edges, so a relation this size can always
    absorb a rebuild, and it doubles exactly when Hb doubles."""
    return hb * BUCKET_SLOTS


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------


def _join_edge_lookup(state, word, state_start, edge_word, edge_next,
                      overlay, linear_overlay: bool = False):
    """Literal-edge lookup for (B, w) (state, word) pairs against the
    sorted relation: CSR segment bounds (2 gathers) + an unrolled
    lower-bound binary search (1 int32 gather/step), then the overlay
    intersection — a second unrolled lower bound over the sorted
    (state, word) overlay rows (2 int32 gathers/step, log2(CAP)
    steps).  Misses and tombstones both resolve to -1.

    ``linear_overlay`` keeps the pre-ISSUE-16 dense O(CAP) overlay
    compare compilable as the parity oracle for the sorted search."""
    import jax.numpy as jnp

    E = int(edge_word.shape[0])
    steps = max(1, E.bit_length())          # ceil(log2(E)) + 1 margin
    sa = jnp.maximum(state, 0)              # safe gather index
    lo = state_start[sa]
    hi0 = state_start[sa + 1]
    hi = hi0
    for _ in range(steps):
        act = lo < hi
        mid = (lo + hi) >> 1
        wm = edge_word[jnp.clip(mid, 0, E - 1)]
        right = act & (wm < word)
        lo = jnp.where(right, mid + 1, lo)
        hi = jnp.where(act & ~right, mid, hi)
    pos = jnp.clip(lo, 0, E - 1)
    hit = (lo < hi0) & (edge_word[pos] == word)
    nxt = jnp.where(hit, edge_next[pos], -1)
    # overlay intersection: edges added since the last rebuild
    o_state = overlay[:, 0]
    o_word = overlay[:, 1]
    o_next = overlay[:, 2]
    if linear_overlay:
        # dense compare, (B, w, OVERLAY_CAP) int32: the historical
        # path, kept as the bit-parity oracle (sentinel rows never
        # equal a live query, and their next = -1 never wins the max)
        eq = (state[..., None] == o_state[None, None, :]) & (
            word[..., None] == o_word[None, None, :])
        nxt_o = jnp.max(
            jnp.where(eq, o_next[None, None, :], -1), axis=-1)
        return jnp.maximum(nxt, nxt_o)
    # sorted overlay: lower-bound search on the lexicographic
    # (state, word) order; OVERLAY_EMPTY sentinel rows pack at the
    # end and compare greater than every live pair, so the search
    # never resolves to one.  Inactive slots query state = -1, which
    # compares less than every live row — lo lands at 0 and the
    # equality check misses.
    cap = int(o_state.shape[0])
    osteps = max(1, cap.bit_length())
    olo = jnp.zeros_like(state)
    ohi = jnp.full_like(state, cap)
    for _ in range(osteps):
        act = olo < ohi
        mid = (olo + ohi) >> 1
        midc = jnp.clip(mid, 0, cap - 1)
        ms = o_state[midc]
        mw = o_word[midc]
        right = act & ((ms < state) | ((ms == state) & (mw < word)))
        olo = jnp.where(right, mid + 1, olo)
        ohi = jnp.where(act & ~right, mid, ohi)
    opos = jnp.clip(olo, 0, cap - 1)
    ohit = ((olo < cap) & (o_state[opos] == state)
            & (o_word[opos] == word))
    nxt_o = jnp.where(ohit, o_next[opos], -1)
    return jnp.maximum(nxt, nxt_o)


def _join_match(
    words,        # (B, D) int32
    lens,         # (B,) int32
    is_sys,       # (B,) bool
    node_tab,     # (S, 4) int32 — same node table as the hash backend
    state_start,  # (S+1,) int32 CSR offsets
    edge_word,    # (E,) int32 sorted within each state segment
    edge_next,    # (E,) int32, -1 = tombstone
    overlay,      # (OVERLAY_CAP, 3) int32 [state, word, next]
    *,
    active_slots: int = 16,
    max_matches: int = 32,
    compact_output: bool = True,
    flat_cap: int = 0,
    linear_overlay: bool = False,
):
    from .match_kernel import nfa_walk

    return nfa_walk(
        words, lens, is_sys, node_tab,
        lambda st, w: _join_edge_lookup(
            st, w, state_start, edge_word, edge_next, overlay,
            linear_overlay=linear_overlay),
        active_slots=active_slots, max_matches=max_matches,
        compact_output=compact_output, flat_cap=flat_cap,
    )


def _jit_twins():
    import jax

    from .match_kernel import _MATCH_STATIC, packed_twin

    statics = tuple(_MATCH_STATIC) + ("linear_overlay",)
    fn = jax.jit(_join_match, static_argnames=statics)
    # the served twin: the flat answer as ONE array
    fn_p = jax.jit(packed_twin(_join_match), static_argnames=statics)
    return fn, fn_p


join_match, join_match_packed = _jit_twins()


# ---------------------------------------------------------------------------
# host-side relation maintenance
# ---------------------------------------------------------------------------


class OverlayFull(RuntimeError):
    """The overlay ran out of rows: the caller rebuilds the relation
    from the shadow table (one lexsort) and re-uploads it whole."""


class JoinRelation:
    """Host twin of the device relation arrays.

    Owns the numpy state plus a SHADOW copy of the cuckoo edge table;
    :meth:`apply_bucket_delta` diffs drained dirty buckets against the
    shadow and returns exactly the scatter updates the device copy
    needs (tombstones/revivals on ``edge_next``, overlay row writes) —
    O(dirty buckets), never a rebuild, until the overlay fills."""

    def __init__(self, s: int, edge_tab: np.ndarray,
                 arrays: Optional[Tuple[np.ndarray, np.ndarray,
                                        np.ndarray]] = None) -> None:
        self.shadow = np.array(edge_tab, np.int32, copy=True)
        hb = int(edge_tab.shape[0])
        self.cap = relation_capacity(hb)
        # overlay edges keyed (state, word); the materialized array is
        # kept SORTED (sentinel rows at the end) so the kernel's
        # lower-bound search stays valid — any mutation re-sorts and
        # ships the whole 3 KB array
        self.overlay = np.empty((OVERLAY_CAP, 3), np.int32)
        self._o_map: Dict[Tuple[int, int], int] = {}
        self._materialize_overlay()
        if arrays is not None:
            start, word, nxt = arrays
            self.state_start = np.array(start, np.int32, copy=True)
            self.edge_word = np.array(word, np.int32, copy=True)
            self.edge_next = np.array(nxt, np.int32, copy=True)
            if (len(self.state_start) != s + 1
                    or len(self.edge_word) != self.cap
                    or len(self.edge_next) != self.cap):
                raise ValueError("seed relation shape mismatch")
        else:
            self._build(s)

    def _build(self, s: int) -> None:
        flat = self.shadow.reshape(-1, 4)
        live = flat[flat[:, 0] >= 0]
        order = np.lexsort((live[:, 1], live[:, 0]))
        sw = live[order]
        n = len(sw)
        if n > self.cap:  # structurally impossible (cap = slot count)
            raise ValueError(f"{n} edges > relation capacity {self.cap}")
        word = np.zeros(self.cap, np.int32)
        nxt = np.full(self.cap, -1, np.int32)
        word[:n] = sw[:, 1]
        nxt[:n] = sw[:, 2]
        counts = np.bincount(sw[:, 0], minlength=s) if n else \
            np.zeros(s, np.int64)
        start = np.zeros(s + 1, np.int32)
        start[1:] = np.cumsum(counts[:s])
        self.state_start = start
        self.edge_word = word
        self.edge_next = nxt
        self._o_map = {}
        self._materialize_overlay()

    def _materialize_overlay(self) -> None:
        """Re-sort the overlay rows by (state, word); unused rows pack
        at the end as OVERLAY_EMPTY sentinels (they must compare
        GREATER than every live pair for the device lower bound)."""
        self.overlay[:, 0] = OVERLAY_EMPTY
        self.overlay[:, 1] = OVERLAY_EMPTY
        self.overlay[:, 2] = -1
        if self._o_map:
            rows = [(s, w, n) for (s, w), n in sorted(self._o_map.items())]
            self.overlay[:len(rows)] = np.asarray(rows, np.int32)

    # -- queries -----------------------------------------------------------

    def arrays(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                              np.ndarray]:
        return (self.state_start, self.edge_word, self.edge_next,
                self.overlay)

    def lookup(self, s: int, w: int) -> int:
        """Host-side oracle of the kernel lookup (tests)."""
        pos = self._csr_find(s, w)
        if pos is not None and self.edge_next[pos] >= 0:
            return int(self.edge_next[pos])
        return self._o_map.get((s, w), -1)

    def _csr_find(self, s: int, w: int) -> Optional[int]:
        start = self.state_start
        if s + 1 >= len(start):
            return None
        lo, hi = int(start[s]), int(start[s + 1])
        i = lo + int(np.searchsorted(self.edge_word[lo:hi], w))
        if i < hi and self.edge_word[i] == w:
            return i
        return None

    # -- maintenance -------------------------------------------------------

    @staticmethod
    def _bucket_edges(row: np.ndarray) -> Dict[Tuple[int, int], int]:
        out: Dict[Tuple[int, int], int] = {}
        r = row.tolist()
        for i in range(0, len(r), 4):
            if r[i] >= 0:
                out[(r[i], r[i + 1])] = r[i + 2]
        return out

    def apply_bucket_delta(self, bucket_idx: np.ndarray,
                           bucket_rows: np.ndarray):
        """Diff dirty buckets against the shadow → device scatter ops.

        Returns ``(main_pos, main_val, olay_pos, olay_rows)`` numpy
        arrays (possibly empty): ``edge_next[main_pos] = main_val`` and
        ``overlay[olay_pos] = olay_rows``.  Any overlay mutation
        re-sorts and returns the WHOLE overlay (sortedness is the
        device search's invariant; 3 KB per rare mutation beats 256
        compares per dispatch).  Raises :class:`OverlayFull` when an
        insertion finds no overlay slot — the caller rebuilds (the
        shadow is ALREADY updated, so ``rebuild()`` is enough)."""
        if len(bucket_idx) and int(bucket_idx.max()) >= len(self.shadow):
            # shadow shape drift (a resize the caller didn't route
            # through rebuild()): force the rebuild path rather than
            # corrupting the relation
            raise OverlayFull("dirty bucket beyond shadow shape")
        removed: Dict[Tuple[int, int], int] = {}
        added: Dict[Tuple[int, int], int] = {}
        for b, new in zip(bucket_idx.tolist(), bucket_rows):
            old_e = self._bucket_edges(self.shadow[b])
            new_e = self._bucket_edges(new)
            for k, v in old_e.items():
                if k not in new_e:
                    removed[k] = v
            for k, v in new_e.items():
                if k not in old_e or old_e[k] != v:
                    added[k] = v
            self.shadow[b] = new
        # a cuckoo kick relocates an edge between buckets: it shows as
        # removed in one bucket and added in another — net no-op (same
        # next), or an in-place next update (child re-created)
        for k in [k for k in removed if k in added]:
            if removed[k] == added[k]:
                del added[k]
            del removed[k]
        main_pos: List[int] = []
        main_val: List[int] = []
        o_dirty = False
        for (s, w) in removed:
            if self._o_map.pop((s, w), None) is not None:
                o_dirty = True
                continue
            pos = self._csr_find(s, w)
            if pos is None:  # shadow/relation drift: force a rebuild
                raise OverlayFull(f"edge ({s},{w}) missing from relation")
            self.edge_next[pos] = -1
            main_pos.append(pos)
            main_val.append(-1)
        for (s, w), nv in added.items():
            pos = self._csr_find(s, w)
            if pos is not None:   # revive the tombstone in place
                self.edge_next[pos] = nv
                main_pos.append(pos)
                main_val.append(nv)
                continue
            if (s, w) not in self._o_map and \
                    len(self._o_map) >= OVERLAY_CAP:
                raise OverlayFull(f"overlay full ({OVERLAY_CAP} rows)")
            if self._o_map.get((s, w)) != nv:
                self._o_map[(s, w)] = nv
                o_dirty = True
        if o_dirty:
            self._materialize_overlay()
            olay_pos = np.arange(OVERLAY_CAP, dtype=np.int32)
            olay_rows = self.overlay.copy()
        else:
            olay_pos = np.empty(0, np.int32)
            olay_rows = np.empty((0, 3), np.int32)
        return (
            np.asarray(main_pos, np.int32),
            np.asarray(main_val, np.int32),
            olay_pos,
            olay_rows,
        )

    def grow_states(self, new_s: int) -> None:
        """Node-table growth: new states have no CSR segment (their
        edges arrive through the overlay), so the offsets just extend
        with the terminal value."""
        cur = len(self.state_start) - 1
        if new_s <= cur:
            return
        self.state_start = np.concatenate([
            self.state_start,
            np.full(new_s - cur, self.state_start[-1], np.int32),
        ])

    def rebuild(self, s: int,
                edge_tab: Optional[np.ndarray] = None) -> None:
        """Re-sort from ``edge_tab`` (or the current shadow): the
        overlay-full / rehash / compaction path.  O(E log E)."""
        if edge_tab is not None:
            self.shadow = np.array(edge_tab, np.int32, copy=True)
            self.cap = relation_capacity(int(edge_tab.shape[0]))
        self._build(s)


# ---------------------------------------------------------------------------
# per-shape backend autotuner
# ---------------------------------------------------------------------------


class BackendAutotuner:
    """Measured hash-vs-join pick per kernel shape, persisted as
    checksummed JSON (the segment-checksum idiom: a corrupt or
    tampered pick table is REJECTED and the default serves — a wrong
    pick is only slow, but a torn file must never poison routing).

    Thread model: ``pick()`` is a dict read (serve path, GIL-atomic);
    ``record()``/``save()`` run from measurement threads under one
    lock."""

    VERSION = 1

    def __init__(self, path: Optional[str] = None, reps: int = 3) -> None:
        self.path = path
        self.reps = max(1, int(reps))
        self.picks: Dict[str, str] = {}
        self.measured: Dict[str, Dict[str, float]] = {}
        self.rejected = False
        self.family_hits = 0
        self._lock = threading.Lock()
        if path:
            self._load()

    @staticmethod
    def sig(b: int, d: int, s: int, hb: int) -> str:
        return f"b{b}:d{d}:s{s}:h{hb}"

    @staticmethod
    def family(sig: str) -> str:
        """The pow2 (S, Hb) family a sig belongs to: the (batch,
        depth) prefix — table shapes are padded pow2s, so every
        growth step lands in the same family."""
        return sig.split(":s", 1)[0]

    def pick(self, sig: str) -> Optional[str]:
        return self.picks.get(sig)

    def pick_for(self, b: int, d: int, s: int, hb: int) -> Optional[str]:
        """The serving pick for a shape: the exact measured sig, else
        the (B, D)-family CONSENSUS across pow2 (S, Hb) shapes — the
        pick rarely flips within a family (ROADMAP join residual (d)),
        so a growth step inherits the family's answer instead of
        re-measuring cold.  A split family (measured shapes disagree)
        returns None and the exact shape measures as before."""
        sig = self.sig(b, d, s, hb)
        p = self.picks.get(sig)
        if p is not None:
            return p
        fam = self.family(sig)
        seen = {v for k, v in self.picks.items()
                if self.family(k) == fam}
        if len(seen) == 1:
            self.family_hits += 1
            return next(iter(seen))
        return None

    # -- measurement -------------------------------------------------------

    def measure(self, sig: str,
                runners: Dict[str, Callable[[], None]]) -> str:
        """Time each runner (one warmup call outside the clock — the
        first call may compile), record the per-rep minimum, pick the
        fastest, persist.  Returns the winning backend name."""
        import time

        times: Dict[str, float] = {}
        for name, run in runners.items():
            run()                       # warmup / compile, untimed
            best = float("inf")
            for _ in range(self.reps):
                t0 = time.perf_counter()
                run()
                best = min(best, time.perf_counter() - t0)
            times[name] = best
        winner = min(times, key=lambda n: times[n])
        self.record(sig, winner, times)
        return winner

    def record(self, sig: str, backend: str,
               times: Optional[Dict[str, float]] = None) -> None:
        with self._lock:
            self.picks[sig] = backend
            if times:
                self.measured[sig] = {
                    k: round(v * 1e6, 2) for k, v in times.items()}
            self._save_locked()

    # -- persistence -------------------------------------------------------

    @staticmethod
    def _checksum(picks: Dict[str, str]) -> str:
        return hashlib.sha1(
            json.dumps(picks, sort_keys=True).encode()).hexdigest()

    def _load(self) -> None:
        try:
            with open(self.path) as f:
                doc = json.load(f)
            if doc.get("version") != self.VERSION:
                raise ValueError(f"version {doc.get('version')!r}")
            picks = doc.get("picks")
            if not isinstance(picks, dict) or any(
                    v not in ("hash", "join", "join-pallas")
                    for v in picks.values()):
                raise ValueError("malformed picks")
            if doc.get("checksum") != self._checksum(picks):
                raise ValueError("checksum mismatch")
            self.picks = dict(picks)
            self.measured = dict(doc.get("measured") or {})
        except FileNotFoundError:
            pass
        except Exception as e:  # corrupt table: defaults serve
            self.rejected = True
            log.warning("autotune pick table %s rejected (%s); "
                        "measuring fresh", self.path, e)

    def _save_locked(self) -> None:
        if not self.path:
            return
        doc = {
            "version": self.VERSION,
            "checksum": self._checksum(self.picks),
            "picks": self.picks,
            "measured": self.measured,
        }
        try:
            os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
            tmp = self.path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)
        except OSError:
            log.warning("autotune pick table %s not persisted",
                        self.path, exc_info=True)

    def info(self) -> dict:
        return {
            "picks": dict(self.picks),
            "measured_shapes": len(self.measured),
            "family_hits": self.family_hits,
            "rejected_file": self.rejected,
            "path": self.path,
        }
