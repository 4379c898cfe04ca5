"""Device twin of :class:`~emqx_tpu.ops.incremental.IncrementalNfa`.

The mria-replicant side of the mirror (SURVEY.md §2.2, §5.4): the host
table is authoritative; this class keeps the device copy fresh by
scatter-applying drained :class:`NfaDelta` batches **in place** (buffer
donation ⇒ no reallocation, no host↔device reshipping of the table) and
re-uploads only when shapes changed (table growth — rare, amortized).

Every delta ships as fixed-size scatter chunks so steady-state serving
reuses ONE compiled scatter per table shape (pre-warmed at upload) —
XLA recompiles are the p99 killer (SURVEY.md §7).

Threading model (for the asyncio serving path): host mutations and
``drain()`` happen on the owner (event-loop) thread; ``apply_pending``,
``serve`` and ``match`` may run on worker threads.  A lock serializes
device-op *dispatch* (donation invalidates the old buffers, so an
unserialized late dispatch could touch a deleted array); result readback happens
outside the lock.  ``arrays()`` returns one atomically-read tuple so a
reader never sees a half-applied (node, edge) pair.
"""

from __future__ import annotations

import threading
from functools import partial
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .incremental import IncrementalNfa, NfaDelta
from .match_kernel import (
    SERVE_FLAT_MULT, MatchResult, nfa_match, nfa_match_packed,
)

__all__ = ["DeviceNfa", "PendingSync", "SCATTER_CHUNK"]


@partial(jax.jit, donate_argnums=(0,))
def _scatter_rows(tab, idx, rows):
    """tab[idx] = rows, in place (donated)."""
    return tab.at[idx].set(rows, mode="drop", unique_indices=False)


@partial(jax.jit, donate_argnums=(0,))
def _scatter_vals(arr, idx, vals):
    """arr[idx] = vals for 1-D arrays, in place (donated) — the join
    relation's tombstone/revival path."""
    return arr.at[idx].set(vals, mode="drop", unique_indices=False)


# fixed scatter chunk: every delta ships as ceil(n/CHUNK) scatters of
# exactly CHUNK rows (padding repeats row 0 — same index, same contents,
# an idempotent no-op scatter).
SCATTER_CHUNK = 1024


def _chunks(idx: np.ndarray, rows: np.ndarray):
    n = len(idx)
    for lo in range(0, n, SCATTER_CHUNK):
        ci = idx[lo:lo + SCATTER_CHUNK]
        cr = rows[lo:lo + SCATTER_CHUNK]
        if len(ci) < SCATTER_CHUNK:
            pad = SCATTER_CHUNK - len(ci)
            ci = np.concatenate([ci, np.full(pad, ci[0], ci.dtype)])
            cr = np.concatenate([cr, np.tile(cr[0], (pad, 1))])
        yield ci, cr


def _chunks1(idx: np.ndarray, vals: np.ndarray):
    """1-D twin of :func:`_chunks` (join-relation value scatters):
    fixed-size chunks, padding repeats entry 0 (idempotent)."""
    n = len(idx)
    for lo in range(0, n, SCATTER_CHUNK):
        ci = idx[lo:lo + SCATTER_CHUNK]
        cv = vals[lo:lo + SCATTER_CHUNK]
        if len(ci) < SCATTER_CHUNK:
            pad = SCATTER_CHUNK - len(ci)
            ci = np.concatenate([ci, np.full(pad, ci[0], ci.dtype)])
            cv = np.concatenate([cv, np.full(pad, cv[0], cv.dtype)])
        yield ci, cv


class PendingSync(NamedTuple):
    """Drained host state, safe to apply from any thread: the arrays are
    stable copies, never aliases of the live mutable table."""

    delta: Optional[NfaDelta]          # in-place scatter path
    full: Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]]  # re-upload
    shape_key: Tuple[int, int, int]
    epoch: int
    # dirty-region grow path (``dirty_regions`` mode): a resized delta
    # whose node prefix is still valid on device ships only the grown
    # region + dirty rows; when the edge table was rehashed its full
    # contents ride here (node still grows in place).  A rehash also
    # drew FRESH seeds — they must ship with the table, or the device
    # keeps mixing with the old pair and every lookup misses (found by
    # the join backend's parity suite: the relation is seed-free, so
    # it kept answering while the hash kernel went dark).
    edge_full: Optional[np.ndarray] = None
    seeds_full: Optional[np.ndarray] = None

    @property
    def empty(self) -> bool:
        return self.full is None and (self.delta is None or self.delta.empty)


class DeviceNfa:
    """Live device mirror: ``sync()`` after host mutations, ``serve()``
    to answer a batch (``match()`` is its reference).  Single-chip
    twin; the sharded path wraps the same arrays via
    ``parallel.sharded_match``."""

    def __init__(
        self,
        inc: "IncrementalNfa",
        active_slots: int = 16,
        max_matches: int = 32,
        device: Optional[jax.Device] = None,
        lazy: bool = False,
        compact_output: bool = True,
    ) -> None:
        # `inc` is any host table with the IncrementalNfa mutation/drain
        # surface — the Python IncrementalNfa or the native C++ NativeNfa
        # (emqx_tpu.native.nfa; exposes tables() instead of raw arrays)
        self.inc = inc
        self.active_slots = active_slots
        self.max_matches = max_matches
        self.compact_output = compact_output
        self.device = device
        self.epoch = -1
        self.uploads = 0        # full table uploads (growth / first sync)
        self.delta_applies = 0  # in-place scatter batches
        # dirty-region mode (streaming table lifecycle, opt-in): a table
        # resize grows the device buffers in place (pad + scatter the
        # tracked dirty rows) instead of re-shipping everything; above
        # dirty_full_threshold (dirty rows / total rows) the one
        # contiguous device_put wins and drain() falls back to it.
        # Requires a host table with track_regions (the Python
        # IncrementalNfa); the native table keeps the full-upload path.
        self.dirty_regions = False
        self.dirty_full_threshold = 0.5
        self.grow_applies = 0           # in-place grow resizes applied
        self.dirty_rows_uploaded = 0    # rows shipped by scatter/grow
        # optional shape-keyed AOT compile cache (ops/kernel_cache.py):
        # when set, serve() dispatches through pre-compiled executables
        # so a table resize never stalls a serve batch on an XLA compile
        self.kernel_cache = None
        # relational-join backend (ops/join_match.py, opt-in): when
        # enabled the device ALSO mirrors the sorted edge relation so
        # serve(backend="join") can answer; maintenance rides the same
        # drain/apply cycle (tombstone/overlay scatters per delta, one
        # rebuild on rehash/compact/overlay-overflow)
        self.join_enabled = False
        self._join = None                 # host JoinRelation
        self._jarrs = None                # device relation arrays
        self._join_seed = None            # (epoch, shape_key, arrays)
        self.join_rebuilds = 0            # full relation re-uploads
        self._shape_key = None
        self._arrs: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None
        self._lock = threading.Lock()
        # activate deferred accept-id reuse: freed aids stay tombstoned
        # until we ack the epoch that cleared their device rows
        inc.device_epoch = -1
        if not lazy:
            self.sync(full=True)

    # -- mirror maintenance ------------------------------------------------

    def _put(self, arr: np.ndarray) -> jax.Array:
        return (
            jax.device_put(arr, self.device)
            if self.device is not None
            else jnp.asarray(arr)
        )

    def arrays(self) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """(node_tab, edge_tab, seeds) — one consistent epoch's view."""
        arrs = self._arrs
        if arrs is None:
            raise RuntimeError("DeviceNfa not synced yet (lazy init)")
        return arrs

    # expose the individual arrays for introspection / graft entry
    @property
    def node_tab(self):
        return self.arrays()[0]

    @property
    def edge_tab(self):
        return self.arrays()[1]

    @property
    def seeds(self):
        return self.arrays()[2]

    def drain(self, full: bool = False) -> PendingSync:
        """OWNER-THREAD step: flush host dirty state into a stable,
        thread-safe :class:`PendingSync`.  O(delta) except when a full
        upload is needed (first sync / growth), which copies the table.
        In ``dirty_regions`` mode a growth resize whose dirty sets
        survived (track_regions host table) ships as a grow-in-place
        sync instead — O(dirty) + the rehashed edge table at most."""
        delta = self.inc.flush()
        if not full and delta.resized and self._grow_ok(delta):
            key = self.inc.shape_key()
            rehash = delta.edges_rehashed or key[1] != self._shape_key[1]
            return PendingSync(
                delta=delta, full=None, shape_key=key, epoch=delta.epoch,
                edge_full=self.inc.edge_tab.copy() if rehash else None,
                seeds_full=self.inc.seeds.copy() if rehash else None,
            )
        if full or delta.resized or self._shape_key != self.inc.shape_key():
            if hasattr(self.inc, "tables"):  # native table: one export
                tabs = self.inc.tables()
            else:
                tabs = (
                    self.inc.node_tab.copy(),
                    self.inc.edge_tab.copy(),
                    self.inc.seeds.copy(),
                )
            return PendingSync(
                delta=None,
                full=tabs,
                shape_key=self.inc.shape_key(),
                epoch=self.inc.epoch,
            )
        return PendingSync(
            delta=delta, full=None,
            shape_key=self.inc.shape_key(), epoch=delta.epoch,
        )

    def _grow_ok(self, delta: NfaDelta) -> bool:
        """May this resized delta ride the grow-in-place path?  Needs the
        mode on, a synced device twin whose node prefix matches the
        delta's valid-prefix marker, an unchanged depth, and a dirty
        fraction below the measured full-upload crossover."""
        if not self.dirty_regions or self._shape_key is None \
                or self._arrs is None:
            return False
        if delta.node_grown_from < 0 \
                or delta.node_grown_from != self._shape_key[0]:
            return False
        key = self.inc.shape_key()
        if key[2] != self._shape_key[2]:
            return False
        n_dirty = len(delta.state_idx) + len(delta.bucket_idx)
        return n_dirty <= self.dirty_full_threshold * (key[0] + key[1])

    def apply_pending(self, p: PendingSync) -> bool:
        """ANY-THREAD step: ship a drained sync to the device.

        On ANY failure the mirror is poisoned (``_arrs`` dropped,
        shape key cleared): a partial apply may have donated-away live
        buffers, and the drained delta is already lost from the host
        dirty sets — the next ``drain()`` therefore returns a full
        re-upload, and matches until then fail fast to the host path."""
        with self._lock:
            try:
                return self._apply_locked(p)
            except Exception:
                self._arrs = None
                self._shape_key = None  # force full re-upload next drain
                self._join = None       # relation rebuilt with the table
                self._jarrs = None
                raise

    def _apply_locked(self, p: PendingSync) -> bool:
        if p.full is not None:
            node = self._put(p.full[0])
            edge = self._put(p.full[1])
            seeds = self._put(p.full[2])
            self._shape_key = p.shape_key
            self.uploads += 1
            node, edge = self._warm_scatter(node, edge, p.full)
            self._arrs = (node, edge, seeds)
            if self.join_enabled:
                self._join_full(p)
            self.epoch = p.epoch
            self.inc.device_epoch = p.epoch
            return True
        if p.delta is None or p.delta.empty:
            self.epoch = max(self.epoch, p.epoch)
            self.inc.device_epoch = max(
                self.inc.device_epoch or -1, p.epoch
            )
            return False
        if p.delta.resized:
            return self._apply_grow(p)
        node, edge, seeds = self._arrs
        for idx, rows in _chunks(p.delta.state_idx, p.delta.state_rows):
            node = _scatter_rows(node, self._put(idx), self._put(rows))
        for idx, rows in _chunks(p.delta.bucket_idx, p.delta.bucket_rows):
            edge = _scatter_rows(edge, self._put(idx), self._put(rows))
        self._arrs = (node, edge, seeds)
        if self.join_enabled and self._join is not None:
            self._join_delta(p.delta)
        self.epoch = p.delta.epoch
        self.inc.device_epoch = p.delta.epoch
        self.delta_applies += 1
        self.dirty_rows_uploaded += (
            len(p.delta.state_idx) + len(p.delta.bucket_idx))
        return True

    def _apply_grow(self, p: PendingSync) -> bool:
        """Grow-in-place resize: pad the node table device-side to the
        new S (no h2d traffic for the surviving prefix), swap in the
        rehashed edge table when it moved, then scatter the tracked
        dirty rows — replacing the whole-table ``device_put`` the old
        resize path paid (25 s at 10M filters, BENCH_r05)."""
        node, edge, seeds = self._arrs
        target_s, target_hb, _d = p.shape_key
        if int(node.shape[0]) != p.delta.node_grown_from:
            # base mismatch (missed sync): poison via the caller's
            # except path — the next drain ships full tables
            raise RuntimeError(
                f"grow-in-place base mismatch: device S={node.shape[0]} "
                f"!= host prefix {p.delta.node_grown_from}")
        grow = target_s - int(node.shape[0])
        if grow > 0:
            pad = jnp.broadcast_to(
                jnp.asarray([-1, -1, -1, 0], jnp.int32), (grow, 4))
            node = jnp.concatenate([node, pad], axis=0)
        if p.edge_full is not None:
            edge = self._put(p.edge_full)
            if p.seeds_full is not None:
                seeds = self._put(p.seeds_full)
        elif int(edge.shape[0]) != target_hb:
            raise RuntimeError(
                f"grow-in-place edge mismatch: device Hb={edge.shape[0]} "
                f"!= host {target_hb} with no rehashed table shipped")
        for idx, rows in _chunks(p.delta.state_idx, p.delta.state_rows):
            node = _scatter_rows(node, self._put(idx), self._put(rows))
        for idx, rows in _chunks(p.delta.bucket_idx, p.delta.bucket_rows):
            edge = _scatter_rows(edge, self._put(idx), self._put(rows))
        self._shape_key = p.shape_key
        self._arrs = (node, edge, seeds)
        if self.join_enabled and self._join is not None:
            if p.edge_full is not None:
                # cuckoo rehash: the relation's CAPACITY moved with Hb,
                # so rebuild from the shipped table (note the edge SET
                # often barely changed — the rebuild is the capacity
                # resize, same amortized class as the rehash itself)
                self._join.rebuild(target_s, p.edge_full)
                self._put_join()
            else:
                self._join.grow_states(target_s)
                ss, ew, en, ov = self._jarrs
                grow_ss = (target_s + 1) - int(ss.shape[0])
                if grow_ss > 0:
                    # new states have no CSR segment: pad the offsets
                    # device-side with the terminal value (no h2d for
                    # the surviving prefix — the grow-in-place idiom)
                    ss = jnp.concatenate(
                        [ss, jnp.broadcast_to(ss[-1:], (grow_ss,))])
                self._jarrs = (ss, ew, en, ov)
                self._join_delta(p.delta)
        self.epoch = p.delta.epoch
        self.inc.device_epoch = p.delta.epoch
        self.grow_applies += 1
        self.dirty_rows_uploaded += (
            len(p.delta.state_idx) + len(p.delta.bucket_idx))
        return True

    def sync(self, full: bool = False) -> bool:
        """Single-threaded convenience: drain + apply in one call."""
        return self.apply_pending(self.drain(full=full))

    # -- join-relation mirror (ops/join_match.py, opt-in) ------------------

    def enable_join(self, seed=None) -> None:
        """Turn the sorted-relation mirror on.  ``seed`` is an optional
        ``(epoch, shape_key, (state_start, edge_word, edge_next))``
        tuple from a persisted segment — used at the next full upload
        iff the epoch still matches (skips the build sort).  On an
        ALREADY-synced twin the relation builds now, from the device
        copy of the edge table (the truth the kernels see)."""
        self.join_enabled = True
        self._join_seed = seed
        if self._arrs is not None and self._jarrs is None:
            from .join_match import JoinRelation

            node, edge, _seeds = self._arrs
            self._join = JoinRelation(
                int(node.shape[0]), np.asarray(jax.device_get(edge)))
            self._put_join()

    def _join_full(self, p: PendingSync) -> None:
        """Full-upload half of the relation mirror: seed from a
        persisted segment when provably fresh, else one lexsort."""
        from .join_match import JoinRelation

        s = int(p.full[0].shape[0])
        seed = self._join_seed
        self._join_seed = None
        self._join = None
        if seed is not None and seed[0] == p.epoch \
                and tuple(seed[1]) == tuple(p.shape_key):
            try:
                self._join = JoinRelation(s, p.full[1], arrays=seed[2])
            except ValueError:
                self._join = None  # malformed seed: sort fresh below
        if self._join is None:
            self._join = JoinRelation(s, p.full[1])
        self._put_join()

    def _put_join(self) -> None:
        """Ship the whole relation + warm its scatter shapes (the same
        pre-pay idiom as ``_warm_scatter``)."""
        start, word, nxt, overlay = self._join.arrays()
        ss = self._put(start)
        ew = self._put(word)
        en = self._put(nxt)
        ov = self._put(overlay)
        z = self._put(np.zeros(SCATTER_CHUNK, np.int32))
        en = _scatter_vals(
            en, z, self._put(np.full(SCATTER_CHUNK, nxt[0], np.int32)))
        ov = _scatter_rows(
            ov, z, self._put(np.tile(overlay[0], (SCATTER_CHUNK, 1))))
        self._jarrs = (ss, ew, en, ov)
        self.join_rebuilds += 1

    def _join_delta(self, delta: NfaDelta) -> None:
        """Delta half: tombstone/revival scatters on ``edge_next`` +
        overlay row writes — O(changed edges) d2h, zero for the node
        side.  Overlay overflow (or shadow drift) rebuilds from the
        already-updated shadow."""
        from .join_match import OverlayFull

        try:
            mpos, mval, opos, orows = self._join.apply_bucket_delta(
                delta.bucket_idx, delta.bucket_rows)
        except OverlayFull:
            self._join.rebuild(len(self._join.state_start) - 1)
            self._put_join()
            return
        ss, ew, en, ov = self._jarrs
        for idx, vals in _chunks1(mpos, mval):
            en = _scatter_vals(en, self._put(idx), self._put(vals))
        for idx, rows in _chunks(opos, orows):
            ov = _scatter_rows(ov, self._put(idx), self._put(rows))
        self._jarrs = (ss, ew, en, ov)
        self.dirty_rows_uploaded += len(mpos) + len(opos)

    def _warm_scatter(self, node, edge, full):
        """Pre-pay the scatter compiles for the current shapes so the
        first real delta lands at steady-state latency.  The warm writes
        are idempotent (row 0 rewritten with its own contents)."""
        z = np.zeros(SCATTER_CHUNK, np.int32)
        node = _scatter_rows(
            node, self._put(z),
            self._put(np.tile(full[0][0], (SCATTER_CHUNK, 1))),
        )
        edge = _scatter_rows(
            edge, self._put(z),
            self._put(np.tile(full[1][0], (SCATTER_CHUNK, 1))),
        )
        return node, edge

    # -- serving -----------------------------------------------------------

    def _backend(self, backend: Optional[str], words, flat: bool) -> str:
        """The kernel that will answer: "hash" unless the relation is
        mirrored; "join-pallas" only for a flat batch that fits its
        tile, else "join" (every kernel answers identically)."""
        be = backend or "hash"
        if be in ("join", "join-pallas") and self._jarrs is None:
            return "hash"
        if be == "join-pallas":
            from .pallas_match import TILE_B

            b = int(words.shape[0])
            if not flat or b % min(TILE_B, b):
                return "join"
        return be

    def _static(self, flat_cap: int) -> dict:
        """The kernels' static arguments for this table's knobs."""
        return dict(active_slots=self.active_slots,
                    max_matches=self.max_matches,
                    compact_output=self.compact_output,
                    flat_cap=flat_cap)

    def _pallas_flat(self, words, lens, is_sys, node, flat_cap: int,
                     packed: bool = False):
        from . import pallas_match

        fn = pallas_match.pallas_join_match_packed if packed \
            else pallas_match.pallas_join_match_flat
        return fn(
            words, lens, is_sys, node, *self._jarrs,
            depth=int(words.shape[1]),
            active_slots=self.active_slots,
            max_matches=self.max_matches,
            flat_cap=flat_cap,
            interpret=(jax.default_backend() != "tpu"),
        )

    def match(self, words, lens, is_sys, *, flat_cap: int = 0,
              backend: Optional[str] = None) -> MatchResult:
        """The REFERENCE walk on already-encoded operands: every field
        of the kernel's answer, as lazy device arrays (``flat_cap`` > 0
        selects the flat compacted output, match_kernel.decode_flat).
        What parity tests and measurements compare :meth:`serve`
        against; nothing on a serve path calls it.  ``backend`` as in
        :meth:`serve`."""
        with self._lock:
            node, edge, seeds = self.arrays()
            be = self._backend(backend, words, flat_cap > 0)
            if be == "join-pallas":
                return self._pallas_flat(words, lens, is_sys, node,
                                         flat_cap)
            if be == "join":
                from .join_match import join_match

                return join_match(words, lens, is_sys, node,
                                  *self._jarrs, **self._static(flat_cap))
            return nfa_match(words, lens, is_sys, node, edge, seeds,
                             **self._static(flat_cap))

    def serve(self, words, lens, is_sys, *, block_compile: bool = True,
              backend: Optional[str] = None) -> jax.Array:
        """Dispatch one SERVED batch: the answer is ONE lazy
        ``(B + flat_cap,)`` int32 array, ``flat_cap`` =
        ``SERVE_FLAT_MULT``·B, from a program with that one output;
        :func:`~emqx_tpu.ops.match_kernel.decode_packed` fetches and
        splits it, outside any lock (dispatch alone holds the device
        lock).  With a kernel cache attached and ``block_compile=False``,
        an uncompiled shape raises
        :class:`~emqx_tpu.ops.kernel_cache.CompileMiss` instead of
        stalling the caller behind XLA (serving fail-open contract).
        ``backend`` selects the edge-structure kernel ("hash" default;
        "join" rides the sorted-relation mirror and silently falls back
        to hash while the relation is not yet mirrored; "join-pallas"
        walks the same relation with the fused Pallas kernel and falls
        back to "join" when the batch is not a multiple of its tile)."""
        flat_cap = SERVE_FLAT_MULT * int(words.shape[0])
        with self._lock:
            node, edge, seeds = self.arrays()
            be = self._backend(backend, words, True)
            tabs = (node, edge, seeds) if be == "hash" \
                else (node,) + tuple(self._jarrs)
            static = self._static(flat_cap)
            kc = self.kernel_cache
            if kc is not None and self.device is None:
                fn = kc.executable(
                    tuple(words.shape), int(node.shape[0]),
                    int(edge.shape[0]), backend=be,
                    block=block_compile, **static)
                return fn(words, lens, is_sys, *tabs)
            if be == "join-pallas":
                return self._pallas_flat(words, lens, is_sys, node,
                                         flat_cap, packed=True)
            from .join_match import join_match_packed

            fn = nfa_match_packed if be == "hash" else join_match_packed
            return fn(words, lens, is_sys, *tabs, **static)

    def match_names(self, names: Sequence[str], batch: Optional[int] = None):
        """Encode + match a batch of topic names (encode must run on the
        owner thread — it reads the live vocab)."""
        from .encode import encode_batch

        words, lens, is_sys = encode_batch(self.inc, names, batch=batch)
        return self.match(
            self._put(words), self._put(lens), self._put(is_sys)
        )
