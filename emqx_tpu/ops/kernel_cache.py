"""Padded-shape kernel compile cache — resizes never stall on XLA.

The match kernel (:func:`~emqx_tpu.ops.match_kernel.nfa_match`) compiles
one executable per ``(B, D, S, Hb, A, K, flat_cap, compact)`` bucket;
table shapes are padded to powers of two exactly so growth RARELY
changes them — but when growth does cross a pow2 boundary, the next
dispatch stalls for seconds on an XLA compile (3–11 s per shape at a
1M-filter table for the v5e compiler, tests/test_chip_compile.py) and
the serve plane browns out to the host path for the whole window.

This cache closes that window two ways:

* **AOT executables** — keys compile via ``jit(...).lower(...).
  compile()`` (the served one-output program for a flat key, the
  ``MatchResult`` one for a compact key) against
  :class:`jax.ShapeDtypeStruct` operands (no dummy
  arrays materialized, no device upload paid just to warm a shape) and
  the resulting ``Compiled`` is what serving dispatches through, so the
  compile-or-hit decision is explicit and countable (the compile-counter
  spy in tests/test_match_segments.py);
* **next-pow2 prewarm** — the serving layer watches table occupancy and
  calls :meth:`prewarm_shape` for the next ``shape_key`` *before* growth
  reaches it, for every (batch, depth, output-mode) combo observed so
  far; the resize is then served entirely from the cache.

Thread model: ``executable()`` may be called from serve worker threads
and ``prewarm_shape`` from a background thread.  A per-key in-flight set
under one lock makes concurrent compiles of the same key collapse into
one; the dict lookup on the hit path is one lock acquisition.
"""

from __future__ import annotations

import logging
import threading
from typing import Any, Dict, Optional, Set, Tuple

log = logging.getLogger(__name__)

__all__ = ["MatchKernelCache", "CompileMiss"]

#: (B, D, S, Hb, active_slots, max_matches, compact, flat_cap, backend,
#: mesh).  ``flat_cap`` > 0 names a SERVED shape: its executable is the
#: one-output program whose answer ``match_kernel.decode_packed`` reads
#: (the Pallas walk's is still a ``MatchResult``, which
#: ``DeviceNfa.serve`` packs).  ``backend`` selects the kernel family:
#: "hash" is the cuckoo-probe nfa_match, "join" the sorted-relation kernel
#: (ops/join_match.py) whose edge-structure shapes DERIVE from the same
#: (S, Hb) pair (relation capacity = Hb * BUCKET_SLOTS), so one shape
#: key covers both families; "join-pallas" is the same join relation
#: walked by the fused Pallas kernel (ops/pallas_match.py) — identical
#: operand shapes, flat-output only.  ``mesh`` is None for single-device keys;
#: the multichip serve backend (parallel/multichip_serve.py) keys its
#: shard_map executables with ``(dp, tp, acap, kind, cap, ...)`` —
#: note the routed bucket CAPACITY is part of the key, so the EP
#: capacity auto-resize pre-compiles its target grid through this
#: cache (block=True off the serve path) and the post-flip dispatch
#: hits without ever parking behind XLA — and installs a
#: ``mesh_lower`` hook the cache delegates those keys to; the same
#: prewarm/CompileMiss contract then covers the mesh step.
Key = Tuple[int, int, int, int, int, int, bool, int, str,
            Optional[Tuple[int, ...]]]


class CompileMiss(RuntimeError):
    """Raised by a non-blocking executable() miss: the caller serves the
    batch from the CPU tables NOW (never a breaker strike — the device
    is healthy) while the key compiles in the background."""


class MatchKernelCache:
    """Shape-keyed AOT compile cache for the match kernel."""

    def __init__(self) -> None:
        self._compiled: Dict[Key, Any] = {}
        self._inflight: Set[Key] = set()
        self._lock = threading.Lock()
        self._done = threading.Condition(self._lock)
        # every (B, D, A, K, compact, flat_cap, backend, mesh) combo
        # ever requested: what prewarm_shape replays against the NEXT
        # table shape
        self._combos: Set[Tuple[int, int, int, int, bool, int, str,
                                Optional[Tuple[int, ...]]]] = set()
        # mesh-key lowering hook, installed by the multichip matcher
        # that owns the mesh (the cache itself stays mesh-agnostic)
        self.mesh_lower: Any = None
        # backends prewarm_shape covers for EVERY combo regardless of
        # which backend the combo was first requested under: with
        # match.backend=auto the first requests route hash (the cold
        # default), so a combo-only replay would leave the join variant
        # uncompiled and the first auto-routed join dispatch on a fresh
        # shape would eat a CompileMiss → CPU hop (ISSUE 13 bugfix)
        self.auto_backends: Tuple[str, ...] = ()
        self.compiles = 0
        self.hits = 0
        self.misses = 0

    # ------------------------------------------------------------------

    @staticmethod
    def key(batch_shape: Tuple[int, int], s: int, hb: int, *,
            active_slots: int, max_matches: int,
            compact_output: bool, flat_cap: int,
            backend: str = "hash",
            mesh: Optional[Tuple[int, ...]] = None) -> Key:
        b, d = batch_shape
        return (b, d, s, hb, active_slots, max_matches,
                bool(compact_output), flat_cap, backend, mesh)

    def executable(self, batch_shape: Tuple[int, int], s: int, hb: int, *,
                   active_slots: int, max_matches: int,
                   compact_output: bool, flat_cap: int,
                   backend: str = "hash",
                   mesh: Optional[Tuple[int, ...]] = None,
                   block: bool = True):
        """The compiled executable for these operand shapes — cached, or
        compiled NOW (blocking; counted, so a resize that was prewarmed
        shows zero compiles on the serve path).  With ``block=False`` a
        miss kicks a background compile and raises :class:`CompileMiss`
        instead — the serving contract: a prefetch is NEVER parked
        behind XLA, the CPU trie answers while the shape warms."""
        k = self.key(batch_shape, s, hb, active_slots=active_slots,
                     max_matches=max_matches,
                     compact_output=compact_output, flat_cap=flat_cap,
                     backend=backend, mesh=mesh)
        with self._lock:
            self._combos.add((k[0], k[1]) + k[4:])
            fn = self._compiled.get(k)
            if fn is not None:
                self.hits += 1
                return fn
            self.misses += 1
            if not block:
                if k not in self._inflight:
                    self._inflight.add(k)
                    # non-daemon: a daemon compile thread racing XLA
                    # teardown at interpreter exit segfaults; exit
                    # instead waits out the in-flight compile
                    threading.Thread(
                        target=self._compile_bg, args=(k,),
                        name="match-kernel-compile",
                    ).start()
                raise CompileMiss(str(k))
        return self._compile(k)

    def _compile_bg(self, k: Key) -> None:
        """Background half of a non-blocking miss: the key was already
        marked in-flight by the caller under the lock."""
        try:
            fn = self._lower(k)
            with self._lock:
                self._compiled[k] = fn
                self.compiles += 1
        except Exception:  # pragma: no cover - XLA failure surfaces on
            log.exception("background kernel compile failed for %s", k)
        finally:
            with self._lock:
                self._inflight.discard(k)
                self._done.notify_all()

    def warmed(self, batch_shape: Tuple[int, int], s: int, hb: int, *,
               active_slots: int, max_matches: int,
               compact_output: bool, flat_cap: int,
               backend: str = "hash",
               mesh: Optional[Tuple[int, ...]] = None) -> bool:
        k = self.key(batch_shape, s, hb, active_slots=active_slots,
                     max_matches=max_matches,
                     compact_output=compact_output, flat_cap=flat_cap,
                     backend=backend, mesh=mesh)
        with self._lock:
            return k in self._compiled

    def _expanded_combos(self) -> list:
        """Observed combos crossed with ``auto_backends``: under
        per-shape routing every covered shape must hold BOTH kernel
        families, or the autotuner's first re-route eats a miss.
        Mesh combos stay on their own backend — the shard_map step has
        no join twin."""
        with self._lock:
            combos = list(self._combos)
            extra = tuple(self.auto_backends)
        out = []
        seen = set()
        for combo in combos:
            backends = (combo[6],) if combo[7] is not None \
                else (combo[6],) + extra
            for be in backends:
                c = combo[:6] + (be,) + combo[7:]
                if c not in seen:
                    seen.add(c)
                    out.append(c)
        return out

    def shape_covered(self, s: int, hb: int) -> bool:
        """Every observed batch combo (crossed with the auto-routing
        backends) already compiled for (s, hb)?"""
        combos = self._expanded_combos()
        with self._lock:
            return bool(combos) and all(
                (b, d, s, hb, a, m, c, f, be, mesh) in self._compiled
                for (b, d, a, m, c, f, be, mesh) in combos
            )

    def prewarm_shape(self, s: int, hb: int) -> int:
        """Compile every observed batch combo against table shape
        ``(s, hb)`` — the background step that makes the NEXT pow2
        resize free — for every backend ``auto`` may route to.
        Returns the number of fresh compiles."""
        n = 0
        for (b, d, a, m, c, f, be, mesh) in self._expanded_combos():
            k = (b, d, s, hb, a, m, c, f, be, mesh)
            with self._lock:
                if k in self._compiled:
                    continue
            self._compile(k)
            n += 1
        return n

    # ------------------------------------------------------------------

    def _compile(self, k: Key):
        with self._lock:
            while k in self._inflight:
                self._done.wait()
            fn = self._compiled.get(k)
            if fn is not None:
                return fn
            self._inflight.add(k)
        try:
            fn = self._lower(k)
            with self._lock:
                self._compiled[k] = fn
                self.compiles += 1
                return fn
        finally:
            with self._lock:
                self._inflight.discard(k)
                self._done.notify_all()

    def _lower(self, k: Key):
        if k[9] is not None:
            if self.mesh_lower is None:
                raise RuntimeError(
                    "mesh-keyed compile requested but no mesh_lower "
                    "hook is installed")
            return self.mesh_lower(k)
        fn, args, static = self.lowering(k)
        return fn.lower(*args, **static).compile()

    @staticmethod
    def lowering(k: Key, sharding: Any = None):
        """``(jitted fn, operand ShapeDtypeStructs, static kwargs)`` of
        the single-device executable ``k`` names — what :meth:`_lower`
        compiles: for a served shape (``flat_cap`` > 0) the program
        whose one output is the packed array.  ``sharding`` places every
        operand (the chip compile rehearsal in tests/test_chip_compile.py
        passes a described device, so it compiles exactly the served
        program)."""
        import jax
        import jax.numpy as jnp

        from .compiler import BUCKET_SLOTS
        from .match_kernel import nfa_match, nfa_match_packed

        b, d, s, hb, a, m, compact, flat_cap, backend, _mesh = k
        i32 = jnp.int32

        def sd(shape, dtype=i32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

        batch = (
            sd((b, d)),                           # words
            sd((b,)),                             # lens
            sd((b,), jnp.bool_),                  # is_sys
            sd((s, 4)),                           # node_tab
        )
        static = dict(active_slots=a, max_matches=m,
                      compact_output=compact, flat_cap=flat_cap)
        if backend in ("join", "join-pallas"):
            from .join_match import OVERLAY_CAP, relation_capacity

            e_cap = relation_capacity(hb)
            relation = (
                sd((s + 1,)),                     # state_start
                sd((e_cap,)),                     # edge_word
                sd((e_cap,)),                     # edge_next
                sd((OVERLAY_CAP, 3)),             # overlay
            )
            if backend == "join":
                from .join_match import join_match, join_match_packed

                fn = join_match_packed if flat_cap > 0 else join_match
                return fn, batch + relation, static
            from .pallas_match import pallas_join_match_packed

            if flat_cap <= 0:
                raise ValueError(
                    "join-pallas backend is flat-output only "
                    "(flat_cap > 0 required)")
            del static["compact_output"]
            static.update(depth=d,
                          interpret=(jax.default_backend() != "tpu"))
            return pallas_join_match_packed, batch + relation, static
        fn = nfa_match_packed if flat_cap > 0 else nfa_match
        return fn, batch + (
            sd((hb, BUCKET_SLOTS * 4)),           # edge_tab
            sd((2,)),                             # seeds
        ), static

    def info(self) -> dict:
        with self._lock:
            return {
                "entries": len(self._compiled),
                "combos": len(self._combos),
                "compiles": self.compiles,
                "hits": self.hits,
                "misses": self.misses,
            }
