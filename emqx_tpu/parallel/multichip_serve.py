"""Multichip serve backend: the match TABLE sharded by topic-prefix
over the mesh, serving real publish traffic (ISSUE 15, extended to the
100M-filter regime in ISSUE 16).

Every 8-device configuration in MULTICHIP_r05 passed dry runs with
parity checks, but the serving path was capped at one chip's table.
This module is the on-device analog of the reference's cluster routing
(PAPER.md: ekka/mria replicated route tables): instead of replicating
the NFA everywhere and sharding the *subscriber bitmap*
(:func:`~emqx_tpu.parallel.sharded_match.build_sharded_matcher_compact`),
the **table itself shards** — each ``tp`` shard owns the filters whose
root token hashes to it, so 8 chips hold 8× the filters:

* ``dp`` — publish-batch rows (each chip matches its slice, zero comms);
* ``tp`` — table shards.  In the default **replicated** mode the batch
  is fanned over this axis and every shard walks its OWN subtable; in
  **EP-routed** mode (``match.multichip.ep.enable``, the
  ``prefix_ep.py`` dryrun promoted to serving) each row is bucketed by
  its ROOT-token owner and ``all_to_all``-routed only to the one shard
  that can match it — per-shard batch width drops from ``B/dp`` to
  ``slack·B/(dp·tp)`` for literal-rooted tables, and ICI traffic with
  it.  Bucket overflow (a hot root skewing one owner) fails open to
  the CPU trie exactly like the dead-shard path;
* **wildcard-root micro-table** — ``+``/``#``-first filters would
  crc32-hash to one arbitrary shard and break single-owner routing;
  they live instead in a small table replicated to every device and
  merged into the owning shard's answer segment (shard 0's in
  replicated mode), so EP answers stay complete and a hot wildcard
  set can't skew one shard;
* per-shard matches map through a local→service accept-id table.  A
  ROUTED answer leaves the mesh as ONE int32 array in the one-chip
  served format (``row_meta`` then the flat ids, built by
  :func:`~emqx_tpu.ops.match_kernel.packed_answer`, read by
  :func:`~emqx_tpu.ops.match_kernel.decode_packed`), a block a ``dp``
  group; the replicated step's leaves as the **dense compact
  contract** (:class:`~emqx_tpu.parallel.sharded_match.
  CompactFanoutResult`): per-row id segments in disjoint per-shard
  order, concat-no-dedup, decoded by :func:`decode_compact_rows`.
  Either way what crosses the wire is proportional to MATCHES, never
  to table width (ROADMAP dispatch-tax residual (d));
* per-row truncation/active-set/bucket-overflow spills are ``psum``'d
  over ``tp`` (the fail-open set — the host re-runs exactly those rows
  on the CPU trie, the single-chip spill contract unchanged); a routed
  row the flat buffer cannot hold joins that set.

Shard subtables are **native** (``native/nfa.cpp``) when the toolchain
built the .so — per-shard capacity then matches the single-chip native
table (10M filters, BENCH_r05), putting ``tp × 10M`` within one
mesh.  Every subtable (and the micro-table) interns the SAME word
sequence, so all vocabs stay identical to the shared encode vocab by
construction (ids assign append-only).  The Python ``IncrementalNfa``
path remains as the no-toolchain fallback (one literally shared dict).

Maintenance rides the existing drain/apply cycle: the service's
``_table_add``/``_table_del`` seams note filter mutations here, the
sync loop applies them off the event loop (per-shard host subtables →
``flush()`` deltas → scatters into the stacked device arrays, full
restack only on a resize — the DeviceNfa discipline), and a compaction
swap rebuilds the whole partition from the fresh aid space.

Failure semantics: a dead (``kill_shard``) or fault-injected
(``match.shard`` point; ``ep.route`` for the routed front end) shard
raises at dispatch — the affected batch fails over to the CPU trie
through the serve plane's existing device-failure paths (breaker
strike in deadline mode, probe recovery, stale-slot discards stay
strike-free), exactly like any other device failure.

Degraded-mesh mode (ISSUE 18, opt-in ``match.multichip.degraded.
enable``) scopes that failover to the dead shard alone: EP-routed
rows owned by a dead shard divert to the CPU trie (host-side
``word_owner`` lookup — the device grid still runs, the dead owner's
answers are discarded), replicated dispatches mask the dead shard's
answer segment and the service CPU-fills only ``shard_of_filter(flt)
== dead`` filters, and the replicated micro-table's merge point
migrates to the lowest LIVE shard when shard 0 dies.  Per-shard
consecutive-failure counters (injected ``match.shard`` faults
attribute round-robin over the live shards) drive the health ladder
healthy → degraded(S) → cpu-only; ``rebuild_shard`` reconstructs a
lost subtable (epoch-guarded per-shard segment + delta-tail replay
from the service filter state) and the service re-admits it only
after a bit-parity canary passes.  Flag off, every path above is
byte-identical to the whole-plane failover.

Load-adaptive plane (ISSUE 20, opt-in ``match.multichip.ep.autotune.
enable``): two feedback loops close the ROADMAP 100M residuals (b)/(c)
on the PR 18 measurement plumbing.  (1) **EP capacity auto-resize** —
when the routed overflow EWMA crosses ``grow_threshold`` the bucket
grid rebuilds at the next pow2 capacity class (hysteresis band +
cooldown for shrink) on a background thread: the new-capacity step
compiles through the kernel cache / a local warm exec FIRST and the
class flips under the lock afterwards, so no dispatch ever parks
behind XLA and overflow rows keep failing open to the CPU trie
throughout the window.  A successful grow re-arms the overflow-warn
log-once latch and zeroes the EWMA so it measures the NEW grid.
(2) **Popularity-aware placement** — routed dispatches bump a per-root
popularity slab (numpy, the admission-plane feature-row idiom);
:meth:`MultichipMatcher.plan_rebalance` (the service's ``table.
compact`` worker cadence) greedily reassigns the hottest roots off the
most-loaded shard within a max-moved-roots budget and stages a small
``root → shard`` override map that :meth:`MultichipMatcher.shard_of`
consults before the crc32 default.  The staged map swaps in at the
next ``rebuild()`` apply — aid spans remap during that restack, and
in-flight slots discard via the service's table-gen guard exactly like
any compaction swap.  The map persists in the per-shard segment
manifest (format v3; checksum-rejected on skew) so cold start restores
placement.  A rebalance proposed while any shard is dead/rebuilding
defers — roots never remap onto a dead owner.  Flag off, every path
above is byte-identical: class stays 0, the override map stays empty.
"""

from __future__ import annotations

import json
import logging
import math
import os
import threading
import time
import zlib
from functools import lru_cache, partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import faultinject as _fi
from .. import topic as T
from ..ops.match_kernel import (SERVE_FLAT_MULT, decode_packed, nfa_match,
                                packed_answer)
from .sharded_match import CompactFanoutResult, decode_compact_rows

log = logging.getLogger(__name__)
_now_ns = time.perf_counter_ns      # the clock of every stage span

__all__ = ["MultichipMatcher", "ShardDead", "build_multichip_step",
           "serve_mesh_shape", "shard_of_filter", "is_micro_filter",
           "pack_operands", "unpack_operands"]


class ShardDead(RuntimeError):
    """A mesh shard is down: the dispatch cannot produce a trustworthy
    answer for ANY row (every shard owns part of the table).  Treated
    by the serve plane as a device failure — CPU trie serves the
    batch, breaker accounting applies."""


def serve_mesh_shape(n_devices: int, tp: int = 0) -> Dict[str, int]:
    """Mesh factorization for the serve backend: ``tp`` table shards
    (0 = the widest pow2 ≤ 4 that divides the device count — the
    :func:`~emqx_tpu.parallel.mesh.pick_shape` default), rest ``dp``
    batch rows."""
    from .mesh import pick_shape

    return pick_shape(n_devices, tp if tp > 0 else None)


def shard_of_filter(flt: str, tp: int) -> int:
    """Topic-prefix partition: a filter lives on the shard its ROOT
    token hashes to.  Wildcard roots (``+``/``#``) hash their literal
    token here too (deterministic), but the matcher diverts them to
    the replicated micro-table (:func:`is_micro_filter`) — a filter
    every topic can match has no single owner under EP routing.

    This is the DEFAULT placement only: the load-adaptive matcher
    consults its popularity override map first
    (:meth:`MultichipMatcher.shard_of`); use that instance method
    wherever a live matcher is in hand."""
    root = flt.split("/", 1)[0]
    return zlib.crc32(root.encode("utf-8")) % tp


def is_micro_filter(flt: str) -> bool:
    """Wildcard-root filters (``+``/``#`` first token) match topics
    with ANY root — they live in the replicated micro-table, merged
    into the owning shard's answer segment."""
    return flt.split("/", 1)[0] in ("+", "#")


def pack_operands(words, lens, is_sys) -> np.ndarray:
    """The mesh step's ONE batch operand, on the host: ``(B, D + 2)``
    int32, the ``D`` word ids of a row, then its ``lens``, then
    ``is_sys`` as 0 / 1.  This function and :func:`unpack_operands` own
    the column order; nothing else knows it.  One array because every
    host array a dispatch places is a transfer to every chip (one
    array 0.70 ms of host time on the four attached chips, three
    1.85: PERF.md §6, PR 37)."""
    words = np.asarray(words)
    d = words.shape[1]
    out = np.empty((words.shape[0], d + 2), np.int32)
    out[:, :d] = words
    out[:, d] = lens
    out[:, d + 1] = is_sys
    return out


def unpack_operands(packed):
    """``(words, lens, is_sys)`` of a :func:`pack_operands` array, by
    static slices: the step's first lines (a traced array) and the
    tests (a numpy one) both read the format here."""
    d = packed.shape[1] - 2
    return packed[:, :d], packed[:, d], packed[:, d + 1] != 0


@lru_cache(maxsize=64)
def _distinct_blocks(sharding, shape) -> int:
    return len({tuple((i.start, i.stop) for i in idx) for idx in
                sharding.devices_indices_map(shape).values()})


def _blocks(arr) -> int:
    """Device buffers a ``jax.device_get`` of ``arr`` copies: one a
    distinct block (a block replicated over ``tp`` is fetched once)."""
    return _distinct_blocks(arr.sharding, arr.shape)


@partial(jax.jit, donate_argnums=(0,))
def _scatter_stacked(tab, tvec, idx, rows):
    """stacked[t, idx] = rows, in place (donated) — the per-shard
    delta scatter into the (tp, ...) stacked table.  Callers hold the
    matcher lock across the scatter AND every dispatch-side read of
    ``_arrs``, so a donated-away buffer is never re-dispatched."""
    return tab.at[tvec, idx].set(rows, mode="drop", unique_indices=False)


def build_multichip_step(mesh, active_slots: int = 16,
                         max_matches: int = 32, micro_matches: int = 8,
                         routed: bool = False, capacity: int = 0,
                         micro_owner: int = 0):
    """Return a jitted ``step(packed, node_stk, edge_stk, seeds_stk,
    aid_stk, micro_node, micro_edge, micro_seeds, micro_amap,
    word_owner)``: a :class:`CompactFanoutResult` from the replicated
    step, ONE packed int32 array from the routed one.

    Input layouts: the batch's one operand ``packed (B, D + 2)``
    (:func:`pack_operands`: words, lens, is_sys) sharded over ``dp``
    (replicated — *fanned* — over ``tp``); the stacked per-shard tables
    ``node_stk (tp, S, 4)``, ``edge_stk (tp, Hb, slots·4)``,
    ``seeds_stk (tp, 2)`` and the local→service accept-id map
    ``aid_stk (tp, A)`` sharded over ``tp``; the wildcard-root
    micro-table arrays and the root-token ``word_owner`` routing map
    fully replicated.  The replicated step's output ``ids`` is the
    dense compact contract: (B, tp·(K+Km)) service accept ids, -1
    padded, per-shard segments disjoint by partition construction;
    ``counts`` (B, tp); the spill vectors psum over ``tp``.

    ``routed=True`` compiles the EP front end: each ``tp`` instance
    takes its 1/tp source slice of the dp-local batch, buckets rows
    by ``word_owner[root]`` into a (tp, ``capacity``) grid, and one
    ``all_to_all`` lands every row on the single shard that owns its
    root.  The owner merges its own + micro answers into ITS segment
    (other segments stay count-0 for that row), so no return
    ``all_to_all`` is needed.  Rows past ``capacity`` fail open
    (match_overflow) at the source.  Exactly one owner writes each row,
    so ONE ``psum`` over ``tp`` of the +1-biased ids (with the count
    and the spill flags beside them) collapses the per-owner segments
    into one (Bl, K+Km) row plane, and
    :func:`~emqx_tpu.ops.match_kernel.packed_answer` lays it out in the
    one-chip served format: the step's whole answer is one
    ``(dp·(Bl + SERVE_FLAT_MULT·Bl),)`` int32 array, a block a ``dp``
    group (``P("dp")``, replicated over ``tp``) that
    :func:`~emqx_tpu.ops.match_kernel.decode_packed` splits.  Every
    buffer a call returns is one more the runtime allocates on each
    device and one more the readback fetches (PERF.md §6).

    ``micro_owner`` names the shard that merges the replicated
    micro-table's answers in replicated mode (default 0; the degraded
    mesh migrates it to the lowest LIVE shard when shard 0 dies, so
    wildcard-root answers never go dark with their merge point)."""
    K = max_matches
    Km = micro_matches
    W = K + Km
    tp = mesh.shape["tp"]
    C = capacity
    seg_spec = P("dp", "tp")

    def merge_micro(gids, cnt_own, mg, mcnt):
        """Pack ``mcnt`` micro ids behind each row's ``cnt_own`` own
        ids — decode_compact_rows prefix-takes ``count`` entries per
        segment, so the merged segment must be contiguous from 0."""
        R = gids.shape[0]
        out = jnp.full((R, W), -1, jnp.int32).at[:, :K].set(gids)
        pos = cnt_own[:, None] + jnp.arange(Km, dtype=jnp.int32)[None, :]
        pos = jnp.where(
            jnp.arange(Km, dtype=jnp.int32)[None, :] < mcnt[:, None],
            pos, W)
        out = out.at[jnp.arange(R)[:, None], pos].set(mg, mode="drop")
        return out, cnt_own + mcnt

    def route(words, lens, is_sys, word_owner):
        """EP front end of one ``tp`` instance: bucket MY source slice
        of the dp-local batch by root-token owner into a (tp, C) grid
        and ``all_to_all`` it, so that every row lands on the one shard
        that owns its root."""
        Bl, D = words.shape
        i = jax.lax.axis_index("tp")
        Bs = Bl // tp
        start = i * Bs
        myw = jax.lax.dynamic_slice_in_dim(words, start, Bs)
        myl = jax.lax.dynamic_slice_in_dim(lens, start, Bs)
        mys = jax.lax.dynamic_slice_in_dim(is_sys, start, Bs)
        root = jnp.clip(myw[:, 0], 0, word_owner.shape[0] - 1)
        owner = word_owner[root]                            # (Bs,) in [0,tp)
        routable = myl <= D          # encode pads with the D+2 sentinel
        # rank within each owner group (cumsum compaction, prefix_ep)
        onehot = ((owner[:, None] == jnp.arange(tp)[None, :])
                  & routable[:, None])
        rank = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1
        my_rank = jnp.take_along_axis(rank, owner[:, None], axis=1)[:, 0]
        keep = routable & (my_rank < C)
        bucket_ov = (routable & (my_rank >= C)).astype(jnp.int32)
        # overflowed/pad rows must scatter NOWHERE (an in-range dummy
        # slot would clobber a legitimate row): route them out of range
        # and let mode="drop" discard the write
        owner_idx = jnp.where(keep, owner, tp)
        slot = jnp.where(keep, my_rank, 0)
        grid_w = jnp.zeros((tp, C, D), jnp.int32).at[owner_idx, slot].set(
            myw, mode="drop")
        grid_l = jnp.full((tp, C), D + 2, jnp.int32).at[
            owner_idx, slot].set(myl, mode="drop")
        grid_s = jnp.ones((tp, C), bool).at[owner_idx, slot].set(
            mys, mode="drop")
        grid_src = jnp.full((tp, C), -1, jnp.int32).at[
            owner_idx, slot].set(
                jnp.arange(Bs, dtype=jnp.int32), mode="drop")

        # ragged all-to-all: (owner, C, ...) leaves, (source, C, ...)
        # lands — each shard now holds exactly the rows it owns
        w2 = jax.lax.all_to_all(grid_w, "tp", 0, 0, tiled=False)
        l2 = jax.lax.all_to_all(grid_l, "tp", 0, 0, tiled=False)
        s2 = jax.lax.all_to_all(grid_s, "tp", 0, 0, tiled=False)
        src2 = jax.lax.all_to_all(grid_src, "tp", 0, 0, tiled=False)
        return w2, l2, s2, src2, bucket_ov, start, Bs

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P("dp", None),        # packed: words | lens | is_sys
            P("tp", None, None),  # node_stk
            P("tp", None, None),  # edge_stk
            P("tp", None),        # seeds_stk
            P("tp", None),        # aid_stk
            P(None, None),        # micro_node (replicated)
            P(None, None),        # micro_edge
            P(None),              # micro_seeds
            P(None),              # micro_amap
            P(None),              # word_owner
        ),
        out_specs=P("dp") if routed else CompactFanoutResult(
            ids=seg_spec,
            counts=seg_spec,
            overflow=seg_spec,
            n_matches=P("dp"),
            active_overflow=P("dp"),
            match_overflow=P("dp"),
        ),
        check_vma=False,
    )
    def mesh_match(packed, node_stk, edge_stk, seeds_stk, aid_stk,
                   micro_node, micro_edge, micro_seeds, micro_amap,
                   word_owner):
        # the function's name is the XLA module's (``jit_mesh_match``),
        # and the ``mesh.*`` scopes name its phases in a device trace
        # beside the ``nfa.*`` scopes of the level walk (PERF.md §3)
        words, lens, is_sys = unpack_operands(packed)
        node, edge, seeds, amap = (
            node_stk[0], edge_stk[0], seeds_stk[0], aid_stk[0])

        def match_both(w, l, s):
            with jax.named_scope("mesh.walk"):
                res = nfa_match(
                    w, l, s, node, edge, seeds,
                    active_slots=active_slots, max_matches=K,
                )
                gids = jnp.where(
                    res.matches >= 0, amap[jnp.maximum(res.matches, 0)],
                    -1)
            with jax.named_scope("mesh.micro"):
                mres = nfa_match(
                    w, l, s, micro_node, micro_edge, micro_seeds,
                    active_slots=active_slots, max_matches=Km,
                )
                mg = jnp.where(
                    mres.matches >= 0,
                    micro_amap[jnp.maximum(mres.matches, 0)], -1)
            return res, gids, mres, mg

        if not routed:
            res, gids, mres, mg = match_both(words, lens, is_sys)
            with jax.named_scope("mesh.compact"):
                # segments must stay DISJOINT per row: exactly one
                # shard (the micro owner — shard 0 unless the degraded
                # mesh migrated the merge point) merges the replicated
                # micro answers
                is0 = jax.lax.axis_index("tp") == micro_owner
                mcnt = jnp.where(is0, jnp.minimum(mres.n_matches, Km), 0)
                ids, cnt = merge_micro(
                    gids, jnp.minimum(res.n_matches, K), mg, mcnt)
                seg_ov = (res.match_overflow
                          + jnp.where(is0, mres.match_overflow, 0))
                return CompactFanoutResult(
                    ids=ids,
                    counts=cnt[:, None],
                    overflow=seg_ov[:, None],
                    n_matches=jax.lax.psum(
                        res.n_matches + jnp.where(is0, mres.n_matches, 0),
                        "tp"),
                    active_overflow=jax.lax.psum(
                        res.active_overflow
                        + jnp.where(is0, mres.active_overflow, 0), "tp"),
                    match_overflow=jax.lax.psum(seg_ov, "tp"),
                )

        # -- EP-routed front end ----------------------------------------
        with jax.named_scope("mesh.route"):
            w2, l2, s2, src2, bucket_ov, start, Bs = route(
                words, lens, is_sys, word_owner)
        Bl, D = words.shape
        R = tp * C
        res, gids, mres, mg = match_both(
            w2.reshape(R, D), l2.reshape(R), s2.reshape(R))
        # the owner is the ONLY shard seeing this row: merge micro here
        with jax.named_scope("mesh.micro"):
            merged, merged_cnt = merge_micro(
                gids, jnp.minimum(res.n_matches, K),
                mg, jnp.minimum(mres.n_matches, Km))

        with jax.named_scope("mesh.compact"):
            # scatter into MY output segment at the row's dp-local position
            # (source j's slice starts at j*Bs); no return all_to_all —
            # other shards' segments stay count-0 for rows they don't own
            flat_src = src2.reshape(R)
            pos = (jnp.arange(tp, dtype=jnp.int32)[:, None] * Bs
                   + src2).reshape(R)
            safe = jnp.where(flat_src >= 0, pos, Bl)
            ids_out = jnp.full((Bl, W), -1, jnp.int32).at[safe].set(
                merged, mode="drop")
            cnt_out = jnp.zeros((Bl,), jnp.int32).at[safe].set(
                merged_cnt, mode="drop")
            # a row's fail-open causes on its owner: active-set spill,
            # truncation past K or Km
            own_ov = jnp.zeros((Bl,), jnp.int32).at[safe].set(
                res.active_overflow + mres.active_overflow
                + res.match_overflow + mres.match_overflow, mode="drop")
            # source-side bucket overflow flags MY slice's rows: the psum
            # folds them into the fail-open set beside the owner's
            src_ov = jax.lax.dynamic_update_slice(
                jnp.zeros((Bl,), jnp.int32), bucket_ov, (start,))
            # exactly ONE owner wrote each row (the partition makes
            # segments disjoint; non-owners left -1 / 0), so one psum of
            # the +1-biased ids, the count and the flags collapses tp
            # segments into one (Bl, W) plane: the contiguous-from-0
            # owner segment survives verbatim
            both = jax.lax.psum(jnp.concatenate(
                [jnp.where(ids_out >= 0, ids_out + 1, 0),
                 cnt_out[:, None], (own_ov + src_ov)[:, None]], axis=1),
                "tp")
            return packed_answer(both[:, :W] - 1, both[:, W],
                                 both[:, W + 1] > 0, SERVE_FLAT_MULT * Bl)

    return jax.jit(mesh_match)


class MultichipMatcher:
    """Host side of the multichip serve backend: per-shard subtables
    (identical vocabs, one encode serves every shard), the wildcard
    micro-table, the stacked device twin, and the mesh-compiled step
    cache.

    Threading model (the MatchService discipline): ``note_add``/
    ``note_del``/``rebuild`` run on the event loop and only append to a
    pending op list; ``apply_pending`` runs in the sync loop's worker
    thread and is the single writer of the subtables + stacked arrays;
    ``dispatch`` runs in the serve plane's encode worker thread and
    captures one consistent (arrays, aid map) snapshot under the lock.
    """

    # v3 (ISSUE 20): the manifest's aid_maps.npz additionally carries
    # the popularity placement override map (NUL-framed roots + int32
    # owners, covered by the same sha1) so cold start restores
    # placement; v2 manifests are version-rejected (one repartition
    # serves after upgrade — same contract as any manifest skew)
    MANIFEST_VERSION = 3
    #: serve-plane dispatch routing marker (MatchService checks this
    #: instead of importing the class on its hot path)
    is_multichip = True
    #: smoothing factor for the per-dispatch routed overflow-rate EWMA
    EP_OVERFLOW_ALPHA = 0.1
    #: routed readbacks that must land at the current capacity class
    #: before a shrink is considered — the EWMA zeroes on every flip,
    #: so an immediate shrink-back would thrash the grid
    EP_SHRINK_COOLDOWN = 64

    def __init__(
        self,
        depth: int = 8,
        tp: int = 0,
        devices: Optional[Sequence[Any]] = None,
        active_slots: int = 16,
        max_matches: int = 32,
        metrics: Any = None,
        kernel_cache: Any = None,
        native: bool = True,
        ep: bool = False,
        ep_slack: float = 2.0,
        ep_micro_matches: int = 8,
        ep_compact: bool = False,
        degraded: bool = False,
        degraded_fail_threshold: int = 3,
        ep_overflow_warn: float = 0.5,
        ep_autotune: bool = False,
        ep_grow_threshold: float = 0.05,
        ep_shrink_threshold: float = 0.01,
        ep_max_cap_class: int = 3,
        balance_budget: int = 64,
        warm_depths: Tuple[int, ...] = (),
        spans: Tuple[Any, Any, Any, Any] = (None, None, None, None),
    ) -> None:
        from .mesh import make_mesh

        # topic depths whose batch-64 step a whole repartition compiles
        # before it is published (:meth:`_restack`): ``ready`` then
        # never turns true over a cold serve shape
        self.warm_depths = tuple(warm_depths)
        # stage spans of a served batch (observe/span.py handles, None
        # where histograms and the flight recorder are off):
        # ``mesh_put`` then ``mesh_launch`` tile :meth:`dispatch` (the
        # dispatch worker's ring), ``mesh_fetch`` then ``mesh_decode``
        # tile :meth:`readback` (the readback worker's)
        (self._sp_put, self._sp_launch,
         self._sp_fetch, self._sp_decode) = spans

        devs = list(devices if devices is not None else jax.devices())
        shape = serve_mesh_shape(len(devs), tp)
        self.mesh = make_mesh(shape, devs)
        # where the step reads its batch operand (``in_specs[0]``):
        # built once per mesh, :meth:`_put_operands` places into it
        self._operand_sharding = NamedSharding(self.mesh, P("dp", None))
        self.dp = shape["dp"]
        self.tp = shape["tp"]
        self.n_devices = self.dp * self.tp
        self.depth = depth
        self.active_slots = active_slots
        self.max_matches = max_matches
        self.metrics = metrics
        self.kernel_cache = kernel_cache
        self.ep = bool(ep)
        self.ep_slack = float(ep_slack)
        self.ep_micro_matches = int(ep_micro_matches)
        # selects nothing: the routed answer is always collapsed on the
        # mesh and packed (build_multichip_step); kept so that a
        # configuration naming match.multichip.ep.compact still loads
        self.ep_compact = bool(ep_compact)
        # degraded-mesh serving (ISSUE 18): scoped shard failover +
        # the health ladder; flag off every dead shard fails the
        # whole plane over (the PR 17 contract, byte-identical)
        self.degraded = bool(degraded)
        self.fail_threshold = max(1, int(degraded_fail_threshold))
        self.ep_overflow_warn = float(ep_overflow_warn)
        # load-adaptive plane (ISSUE 20, module docstring): capacity
        # auto-resize + popularity-aware placement; flag off every
        # structure below stays inert (class 0, empty override map)
        self.ep_autotune = bool(ep_autotune)
        self.ep_grow_threshold = float(ep_grow_threshold)
        self.ep_shrink_threshold = float(ep_shrink_threshold)
        self.ep_max_cap_class = max(0, int(ep_max_cap_class))
        self.balance_budget = max(0, int(balance_budget))
        self._cap_class = 0            # live pow2 capacity exponent
        self._class_readbacks = 0      # routed readbacks at this class
        self._resize_busy = False      # one background resize at a time
        self._resize_thread: Optional[threading.Thread] = None
        self._ep_shapes: set = set()   # observed routed (B, D) shapes
        # popularity placement: override map consulted before the crc32
        # default, the staged map the next rebuild swaps in, and the
        # per-root load slab (indexed by root word id, lock-free stats
        # — a dropped bump under a concurrent aging pass is benign)
        self._placement: Dict[str, int] = {}
        self._placement_next: Optional[Dict[str, int]] = None
        self._root_load = np.zeros(1024, np.float64)
        self.ep_resizes = 0
        self.ep_rebalances = 0
        self.moved_roots = 0
        if native:
            from ..native.nfa import available

            native = available()
            if not native:
                log.warning("native nfa unavailable; multichip shard "
                            "subtables fall back to IncrementalNfa")
        self.native = bool(native)
        if kernel_cache is not None:
            # mesh-keyed executables compile through the shared cache
            # (CompileMiss semantics, zero-compile prewarm spies)
            kernel_cache.mesh_lower = self._lower_step

        self.vocab: Dict[str, int] = {}
        self._subs: List[Any] = []
        self._aid_maps: List[np.ndarray] = []
        self._filters: List[Dict[str, int]] = []
        self._micro: Any = None
        self._micro_amap: np.ndarray = np.full(8, -1, np.int32)
        self._micro_filters: Dict[str, int] = {}
        self._word_owner = np.zeros(1024, np.int32)
        self._word_owner_n = 0
        self._reset_subs()

        self._lock = threading.Lock()
        # serializes table maintenance (apply_pending / save_segments /
        # rebuild_shard) — the rebuild child's worker hop must not race
        # the sync loop's
        self._maint_lock = threading.Lock()
        self._pending: List[Tuple[str, str, int]] = []  # (op, flt, aid)
        self._rebuild_pairs: Optional[List[Tuple[str, int]]] = None
        self._restack_due = False      # segment restore awaiting upload
        self._arrs: Optional[Tuple[Any, ...]] = None
        self._stacked_shape: Optional[Tuple[int, ...]] = None
        self._steps: Dict[Tuple[int, ...], Any] = {}
        self._routed_live: set = set()  # id(res) of in-flight EP handles
        self._dead: set = set()
        # degraded-mesh state: per-dispatch failover metadata keyed by
        # id(res), per-shard consecutive-failure strikes, and the
        # round-robin cursor that attributes anonymous match.shard
        # faults to a live shard
        self._degraded_meta: Dict[int, Tuple[Any, ...]] = {}
        self._fail_counts: Dict[int, int] = {}
        self._fault_rr = 0
        self.degraded_batches = 0
        self.cpu_filled_rows = 0
        self.rebuilds = 0
        self.readmit_canary_fails = 0
        # satellite: routed overflow-rate EWMA (the bucket-grid resize
        # input) + its log-once warning latch
        self._ov_ewma = 0.0
        self._ov_warned = False
        self.gen = 0                    # bumped on every restack
        self.dispatches = 0
        self.ep_dispatches = 0
        self.failovers = 0
        self.applies = 0
        self.restacks = 0
        self.seeded_from_segments = False
        self._persist_due = False
        if metrics is not None:
            metrics.set("tpu.match.shard_devices", self.n_devices)

    # ------------------------------------------------------------------
    # partition maintenance (event loop: enqueue; worker thread: apply)
    # ------------------------------------------------------------------

    def _new_sub(self):
        if self.native:
            from ..native.nfa import NativeNfa

            return NativeNfa(depth=self.depth)
        from ..ops.incremental import IncrementalNfa

        sub = IncrementalNfa(depth=self.depth)
        # one vocab dict shared by every subtable: a single encode
        # pass serves all shards (interning appends consistently)
        sub.vocab = self.vocab
        return sub

    def _reset_subs(self) -> None:
        self.vocab = {}
        self._subs = []
        self._aid_maps = []
        self._filters = []
        self._word_owner = np.zeros(1024, np.int32)
        self._word_owner_n = 0
        for _ in range(self.tp):
            self._subs.append(self._new_sub())
            self._aid_maps.append(np.full(64, -1, np.int32))
            self._filters.append({})
        self._micro = self._new_sub()
        self._micro_amap = np.full(8, -1, np.int32)
        self._micro_filters = {}

    def _all_tables(self) -> List[Any]:
        return [*self._subs, self._micro]

    def shard_of(self, flt: str) -> int:
        """Placement-aware :func:`shard_of_filter`: the popularity
        override map (root → shard, staged by :meth:`plan_rebalance`
        and swapped in at a rebuild) is consulted before the crc32
        default.  Empty map (flag off, or nothing hot enough to move)
        → byte-identical to the pure hash."""
        if self._placement:
            o = self._placement.get(flt.split("/", 1)[0])
            if o is not None:
                return int(o)
        return shard_of_filter(flt, self.tp)

    def note_add(self, flt: str, service_aid: int) -> None:
        with self._lock:
            self._pending.append(("add", flt, service_aid))

    def note_del(self, flt: str) -> None:
        with self._lock:
            self._pending.append(("del", flt, -1))

    def rebuild(self, pairs: List[Tuple[str, int]]) -> None:
        """Full repartition (cold start, compaction swap — the service
        aid space was reassigned wholesale).  Cheap on the loop: the
        build itself happens at the next ``apply_pending``; until that
        has landed and warmed its serve shapes ``ready`` is False (the
        service is then not ready either: the host trie serves)."""
        with self._lock:
            self._rebuild_pairs = list(pairs)
            self._pending = []
            self._restack_due = False
            self._arrs = None
            self._steps = {}

    @property
    def ready(self) -> bool:
        return self._arrs is not None

    @property
    def dirty(self) -> bool:
        return (bool(self._pending) or self._rebuild_pairs is not None
                or self._restack_due)

    def _intern_filter_words(self, flt: str) -> None:
        """Intern the filter's literal words into the shared encode
        vocab AND every subtable (native vocabs are per-table; ids
        assign append-only, so replaying one word sequence everywhere
        keeps them all identical — the EP word_owner map and the
        stacked edge tables then agree with encode_batch)."""
        for w in T.words(flt):
            if w in ("+", "#") or w in self.vocab:
                continue
            wid = len(self.vocab) + 1
            self.vocab[w] = wid
            if self.native:
                for tbl in self._all_tables():
                    tbl.intern(w)

    def _host_add(self, flt: str, service_aid: int) -> None:
        self._intern_filter_words(flt)
        if is_micro_filter(flt):
            sub = self._micro
            sub.add(flt)
            laid = sub.aid_of(flt)
            amap = self._micro_amap
            if laid >= len(amap):
                grown = np.full(max(2 * len(amap), laid + 1), -1, np.int32)
                grown[:len(amap)] = amap
                amap = self._micro_amap = grown
            amap[laid] = service_aid
            self._micro_filters[flt] = service_aid
            return
        t = self.shard_of(flt)
        sub = self._subs[t]
        sub.add(flt)
        laid = sub.aid_of(flt)
        amap = self._aid_maps[t]
        if laid >= len(amap):
            grown = np.full(max(2 * len(amap), laid + 1), -1, np.int32)
            grown[:len(amap)] = amap
            amap = self._aid_maps[t] = grown
        amap[laid] = service_aid
        self._filters[t][flt] = service_aid

    def _host_del(self, flt: str) -> None:
        if is_micro_filter(flt):
            laid = self._micro.aid_of(flt)
            if laid < 0:
                return
            self._micro_amap[laid] = -1
            self._micro.remove(flt)
            self._micro_filters.pop(flt, None)
            return
        t = self.shard_of(flt)
        sub = self._subs[t]
        laid = sub.aid_of(flt)
        if laid < 0:
            return
        self._aid_maps[t][laid] = -1
        sub.remove(flt)
        self._filters[t].pop(flt, None)

    def _sync_word_owner(self) -> bool:
        """Fill routing owners (the device twin of :meth:`shard_of` —
        placement override first, crc32(word) % tp default) for vocab
        words interned since the last sync; pow2 growth.  Returns True
        when entries changed."""
        n = len(self.vocab)
        if self._word_owner_n >= n:
            return False
        cap = len(self._word_owner)
        if n + 1 > cap:
            while cap < n + 1:
                cap *= 2
            grown = np.zeros(cap, np.int32)
            grown[:len(self._word_owner)] = self._word_owner
            self._word_owner = grown
        place = self._placement
        for w, wid in list(self.vocab.items())[self._word_owner_n:]:
            o = place.get(w) if place else None
            self._word_owner[wid] = (
                zlib.crc32(w.encode("utf-8")) % self.tp
                if o is None else int(o))
        self._word_owner_n = n
        return True

    def apply_pending(self) -> bool:
        """WORKER-THREAD step (the sync loop's ``to_thread`` hop):
        drain the queued mutations into the per-shard subtables, then
        ship the result — per-shard ``flush()`` deltas scatter into the
        stacked arrays in place; any resize/repartition restacks (the
        DeviceNfa full-upload analog).  Returns True when the device
        state changed."""
        with self._maint_lock:
            return self._apply_locked()

    def _apply_locked(self) -> bool:
        with self._lock:
            ops, self._pending = self._pending, []
            rebuild, self._rebuild_pairs = self._rebuild_pairs, None
            restack_due, self._restack_due = self._restack_due, False
        if rebuild is not None:
            with self._lock:
                staged, self._placement_next = self._placement_next, None
            if staged is not None:
                # a full repartition rebuilds every aid span anyway —
                # the staged override map swaps in HERE so the restack
                # below remaps spans and word_owner in the same pass
                # (in-flight slots discard via the service table-gen
                # guard, like any compaction swap)
                self._placement = staged
                self._persist_due = True
                log.info("EP placement override map applied: %d "
                         "root(s) off their crc32 shard", len(staged))
            self._reset_subs()
            if self.native:
                # pre-intern the whole word sequence with one native
                # call per table (the bulk-build fast path; per-filter
                # interning would pay tp+1 ctypes hops per word)
                words: List[str] = []
                for flt, _aid in rebuild:
                    for w in T.words(flt):
                        if w not in ("+", "#") and w not in self.vocab:
                            self.vocab[w] = len(self.vocab) + 1
                            words.append(w)
                for tbl in self._all_tables():
                    tbl.bulk_intern(words)
            for flt, aid in rebuild:
                self._host_add(flt, aid)
            # notes enqueued AFTER the rebuild request (rebuild()
            # clears the pending log, so every drained op postdates
            # it) apply on top — dropping them would serve a partition
            # missing live mutations
            for op, flt, aid in ops:
                if op == "add":
                    self._host_add(flt, aid)
                else:
                    self._host_del(flt)
            for tbl in self._all_tables():
                tbl.flush()     # clear dirty sets; restack ships all
            self._restack()
            self._persist_due = True
            return True
        if not ops:
            if self._arrs is None and restack_due:
                # segment restore: the subtables are populated but the
                # stacked device twin was never shipped
                self._restack()
                return True
            return False
        for op, flt, aid in ops:
            if op == "add":
                self._host_add(flt, aid)
            else:
                self._host_del(flt)
        deltas = [sub.flush() for sub in self._subs]
        mdelta = self._micro.flush()
        wo_changed = self._sync_word_owner()
        shape = self._required_shape()
        if (self._arrs is None or self._stacked_shape != shape
                or any(d.resized for d in deltas) or mdelta.resized):
            self._restack()
            return True
        from ..ops.device_table import _chunks

        # the scatters DONATE the stacked buffers: the lock must span
        # the whole read-modify-publish so a concurrent dispatch never
        # captures a donated-away array
        with self._lock:
            (node_stk, edge_stk, seeds_stk, _aid_stk,
             micro_node, micro_edge, micro_seeds, micro_amap,
             word_owner) = self._arrs
            for t, d in enumerate(deltas):
                if d.empty:
                    continue
                for idx, rows in _chunks(d.state_idx, d.state_rows):
                    node_stk = _scatter_stacked(
                        node_stk, jnp.full(idx.shape, t, jnp.int32),
                        jnp.asarray(idx), jnp.asarray(rows))
                for idx, rows in _chunks(d.bucket_idx, d.bucket_rows):
                    edge_stk = _scatter_stacked(
                        edge_stk, jnp.full(idx.shape, t, jnp.int32),
                        jnp.asarray(idx), jnp.asarray(rows))
            # (the donated scatters alias their operand, so node_stk /
            # edge_stk keep the placement _restack gave them)
            aid_stk = self._put_shards(self._stacked_aid_maps(shape[2]))
            if not mdelta.empty:
                # the micro-table is small and replicated: a dirty
                # micro ships as a full (fresh-array) upload
                mn, me, ms = self._table_arrays(self._micro)
                micro_node = self._put_replicated(mn)
                micro_edge = self._put_replicated(me)
                micro_seeds = self._put_replicated(ms)
            if not mdelta.empty or wo_changed:
                micro_amap = self._put_replicated(
                    self._padded_micro_amap(shape[5]))
                word_owner = self._put_replicated(self._word_owner)
            self._arrs = (node_stk, edge_stk, seeds_stk, aid_stk,
                          micro_node, micro_edge, micro_seeds,
                          micro_amap, word_owner)
        self.applies += 1
        return True

    def _put_shards(self, stacked):
        """Upload a stacked ``(tp, ...)`` host array with shard ``t``
        on the mesh's ``tp``-th device column — the layout the step's
        ``in_specs`` name.  A bare ``jnp.asarray`` lands the whole
        stack on device 0 and leaves every dispatch to re-shard it
        (no error anywhere; chip_smoke.py --chips 4 asserts four
        holders)."""
        spec = P("tp", *([None] * (np.ndim(stacked) - 1)))
        return jax.device_put(stacked, NamedSharding(self.mesh, spec))

    def _put_replicated(self, arr):
        """Upload a small table every mesh device reads (micro-table,
        ``word_owner``): one copy per device, placed once."""
        return jax.device_put(arr, NamedSharding(self.mesh, P()))

    def _put_operands(self, enc):
        """The one way a batch reaches a step (serve, warm, canary):
        :func:`pack_operands` of the encoded ``(words, lens, is_sys)``,
        put ONCE from the host straight into the step's own input
        sharding.  Every chip gets its copy in one batched call and the
        committed array already matches what the step reads, so the
        call reshards nothing (three uncommitted arrays on the default
        device cost a dispatch 1.7 ms more on four chips: PERF.md §6,
        PR 37).  ``tpu.mesh.operand_puts`` counts the host arrays
        placed: one a dispatch."""
        packed = jax.device_put(pack_operands(*enc),
                                self._operand_sharding)
        if self.metrics is not None:
            self.metrics.inc("tpu.mesh.operand_puts")
        return packed

    @staticmethod
    def _table_shape(sub) -> Tuple[int, int]:
        """(S, Hb) for either table implementation."""
        if hasattr(sub, "node_tab"):
            return int(sub.S), int(sub.Hb)
        s, hb, _depth = sub.shape_key()
        return int(s), int(hb)

    @staticmethod
    def _table_arrays(sub):
        """(node_tab, edge_tab, seeds) for either table implementation."""
        if hasattr(sub, "node_tab"):
            return sub.node_tab, sub.edge_tab, sub.seeds
        return sub.tables()

    def _required_shape(self) -> Tuple[int, int, int, int, int, int, int]:
        """Common stacked (S, Hb, A_cap) plus the replicated shapes
        (micro S, micro Hb, micro A_cap, word_owner cap): node tables
        pad (states index directly — pad rows are unreachable), edge
        tables must SHARE a real bucket count (lookups hash modulo
        Hb), aid maps pad."""
        smax = max(self._table_shape(sub)[0] for sub in self._subs)
        hbmax = max(self._table_shape(sub)[1] for sub in self._subs)
        acap = 64
        for amap in self._aid_maps:
            while acap < len(amap):
                acap *= 2
        sm, hbm = self._table_shape(self._micro)
        am = 8
        while am < len(self._micro_amap):
            am *= 2
        return (smax, hbmax, acap, sm, hbm, am, len(self._word_owner))

    def _stacked_aid_maps(self, acap: int) -> np.ndarray:
        out = np.full((self.tp, acap), -1, np.int32)
        for t, amap in enumerate(self._aid_maps):
            out[t, :len(amap)] = amap
        return out

    def _padded_micro_amap(self, am: int) -> np.ndarray:
        out = np.full(am, -1, np.int32)
        out[:len(self._micro_amap)] = self._micro_amap
        return out

    def _restack(self) -> None:
        """Full re-upload of the stacked per-shard tables (+ the
        replicated micro/word_owner arrays).  Smaller shards grow
        their edge table to the common Hb (hash-correct — a padded
        edge table would probe modulo the wrong size), node tables pad
        with inert rows."""
        hbmax = max(self._table_shape(sub)[1] for sub in self._subs)
        for sub in self._subs:
            if hasattr(sub, "grow_edges_to"):
                sub.grow_edges_to(hbmax)
            else:
                while sub.Hb < hbmax:
                    sub._grow_edges()
            sub.flush()     # growth marked dirty; the restack ships all
        self._micro.flush()
        self._sync_word_owner()
        shape = self._required_shape()
        smax, hbmax, acap, _sm, _hbm, am, _wcap = shape
        nodes, edges, seeds = [], [], []
        for sub in self._subs:
            node, edge, sd = self._table_arrays(sub)
            tab = np.full((smax, 4), -1, np.int32)
            tab[:, 3] = 0
            tab[:node.shape[0]] = node
            nodes.append(tab)
            edges.append(edge)
            seeds.append(sd)
        node_stk = self._put_shards(np.stack(nodes))
        edge_stk = self._put_shards(np.stack(edges))
        seeds_stk = self._put_shards(np.stack(seeds))
        aid_stk = self._put_shards(self._stacked_aid_maps(acap))
        mn, me, ms = self._table_arrays(self._micro)
        arrs = (node_stk, edge_stk, seeds_stk, aid_stk,
                self._put_replicated(mn), self._put_replicated(me),
                self._put_replicated(ms),
                self._put_replicated(self._padded_micro_amap(am)),
                self._put_replicated(self._word_owner))
        self._stacked_shape = shape     # the step's cache key reads it
        if self._arrs is None:
            # a whole repartition (or the first upload): pay the serve
            # shapes' compiles on the staged arrays, and publish last
            for d in self.warm_depths:
                step = self._step_for((64, d), self._routed_for(64))
                jax.block_until_ready(step(
                    self._put_operands(self.encode([], batch=64, depth=d)),
                    *arrs))
        with self._lock:
            self._arrs = arrs
        self.gen += 1
        self.applies += 1
        self.restacks += 1
        if self.metrics is not None:
            self.metrics.set("tpu.match.shard_restacks", self.restacks)

    # ------------------------------------------------------------------
    # serving (encode worker thread)
    # ------------------------------------------------------------------

    def encode(self, topics: Sequence[str], batch: int,
               depth: Optional[int] = None):
        """Encode against the SHARED shard vocab (one pass serves every
        shard) — the service's table vocab assigns different word ids,
        so multichip-routed groups must encode here."""
        from ..ops.encode import encode_batch

        return encode_batch(self, topics, batch=batch, depth=depth)

    def kill_shard(self, t: int) -> None:
        """Chaos surface: mark shard ``t`` dead.  Flag off, every
        subsequent dispatch raises :class:`ShardDead` until
        ``revive_shard`` (whole-plane failover); degraded mode keeps
        serving on the survivors and diverts only the dead shard's
        share of the answers to the CPU trie (scoped failover)."""
        self._dead.add(int(t))
        self._fail_counts.pop(int(t), None)
        self._set_state_metric()

    def revive_shard(self, t: int) -> None:
        self._dead.discard(int(t))
        self._fail_counts.pop(int(t), None)
        self._set_state_metric()

    # -- health ladder -------------------------------------------------

    def mesh_state(self) -> int:
        """Health-ladder rung: 0 healthy, 1 degraded(S) (scoped
        failover serving on the survivors around ONE dead shard), 2
        cpu-only (every dispatch refused: two or more shards dead —
        the double-kill rung — or any dead shard with the flag off)."""
        if not self._dead:
            return 0
        if self.degraded_serving:
            return 1
        return 2

    @property
    def dead_shards(self) -> List[int]:
        return sorted(self._dead)

    @property
    def degraded_serving(self) -> bool:
        """True while scoped failover is answering on the survivors.
        Scoped failover covers exactly ONE dead shard (degraded(S));
        a second death drops the plane to cpu-only until the staged
        re-admit climbs back through degraded(S) to healthy."""
        return bool(self.degraded and len(self._dead) == 1
                    and self.tp > 1)

    def note_shard_failure(self, t: int) -> bool:
        """One consecutive-failure strike against shard ``t`` (the
        health ladder's input); at ``fail_threshold`` strikes the
        shard is marked dead.  Returns True when this strike killed
        it."""
        t = int(t)
        if t in self._dead:
            return False
        c = self._fail_counts.get(t, 0) + 1
        self._fail_counts[t] = c
        if c < self.fail_threshold:
            return False
        self._fail_counts.pop(t, None)
        self._dead.add(t)
        log.warning("mesh shard %d dead after %d consecutive failures",
                    t, c)
        self._set_state_metric()
        return True

    def _note_fault_failure(self) -> None:
        """An injected ``match.shard`` fault names no shard: attribute
        it round-robin over the LIVE shards so a sustained fault storm
        marches the ladder one shard at a time toward cpu-only."""
        live = [t for t in range(self.tp) if t not in self._dead]
        if not live:
            return
        t = live[self._fault_rr % len(live)]
        self._fault_rr += 1
        self.note_shard_failure(t)

    def _set_state_metric(self) -> None:
        if self.degraded and self.metrics is not None:
            self.metrics.set("tpu.mesh.state", self.mesh_state())

    def dead_aids(self, exclude: Optional[int] = None) -> frozenset:
        """Service accept ids owned by dead shards — the replicated
        scoped-failover CPU-fill set (host-known: ``shard_of_filter``
        is a pure function of the filter)."""
        out: set = set()
        for t in self._dead:
            if exclude is not None and int(t) == int(exclude):
                continue
            out.update(self._filters[t].values())
        return frozenset(out)

    def _gate(self) -> None:
        if self._dead:
            if not self.degraded_serving:
                self._note_failover()
                raise ShardDead(
                    f"mesh shard(s) {sorted(self._dead)} dead")
        if _fi._injector is not None:
            act = _fi._injector.act("match.shard")
            if act == "raise":
                self._note_failover()
                if self.degraded:
                    self._note_fault_failure()
                raise _fi.InjectedFault("match.shard")
            if act == "delay":
                # sync seam (worker thread): a plain blocking sleep,
                # the match.compile idiom
                import time

                time.sleep(_fi._injector.last_delay)

    def _gate_ep(self) -> None:
        """The routed front end's own chaos seam: an injected
        ``ep.route`` fault refuses the dispatch (CPU trie serves the
        batch) without taking the whole mesh down."""
        if _fi._injector is not None:
            act = _fi._injector.act("ep.route")
            if act == "raise":
                self._note_failover()
                raise _fi.InjectedFault("ep.route")
            if act == "delay":
                import time

                time.sleep(_fi._injector.last_delay)

    def _note_failover(self) -> None:
        self.failovers += 1
        if self.metrics is not None:
            self.metrics.inc("tpu.match.shard_failover")

    def ep_capacity(self, batch: int) -> int:
        """Per-(source, owner) bucket size for a routed batch: the
        uniform share ``Bs/tp`` with ``ep_slack`` headroom.  Per-shard
        processed width is ``tp * C <= ceil(slack * Bl / tp)`` — the
        ``gate_shard_width_le_batch_over_tp`` contract.  The autotune
        capacity class scales this by pow2 steps, ceilinged at the
        full source-slice width (where bucket overflow is impossible);
        class 0 — flag off, or never grown — is byte-identical."""
        bs = (batch // self.dp) // self.tp
        base = max(1, int(math.ceil(self.ep_slack * bs / self.tp)))
        if self._cap_class:
            base = min(max(bs, 1), base << self._cap_class)
        return base

    def _capacity_at(self, batch: int, cap_class: int) -> int:
        """:meth:`ep_capacity` at an explicit class — what the resize
        worker compiles for before flipping ``_cap_class``."""
        bs = (batch // self.dp) // self.tp
        base = max(1, int(math.ceil(self.ep_slack * bs / self.tp)))
        if cap_class:
            base = min(max(bs, 1), base << cap_class)
        return base

    def _routed_for(self, batch: int) -> bool:
        """EP routing serves a batch iff the dp-local slice splits
        evenly into tp source slices; anything else (odd warm shapes)
        falls back to the replicated step for that dispatch."""
        return (self.ep and self.tp > 1
                and batch % (self.dp * self.tp) == 0
                and (batch // self.dp) >= self.tp)

    def dispatch(self, enc, *, block_compile: bool = True, n: int = 0,
                 seq: Optional[int] = None, gen: int = 0):
        """One mesh dispatch of an already-encoded batch; returns the
        lazy :class:`CompactFanoutResult` handle (readback blocks
        later, outside any lock).  Raises :class:`ShardDead` /
        :class:`~emqx_tpu.faultinject.InjectedFault` at the
        ``match.shard`` / ``ep.route`` seams, :class:`CompileMiss` on
        a cold mesh shape when a kernel cache is attached.  A served
        batch hands in its ``seq`` (with its ``n`` topics and table
        ``gen``) and gets one ``mesh_put`` and one ``mesh_launch``
        sample; probes, warm calls and canaries do not."""
        self._gate()
        words, lens, is_sys = enc
        b, d = int(words.shape[0]), int(words.shape[1])
        routed = self._routed_for(b)
        if routed:
            self._gate_ep()
        dead = (frozenset(int(x) for x in self._dead)
                if self.degraded_serving else None)
        owner = 0
        dead_rows: List[int] = []
        if dead is not None:
            if routed:
                # scoped EP failover: the rows whose crc32-root owner
                # is dead divert to the CPU trie at readback (the
                # device grid still runs; the dead owner's segment is
                # discarded with them)
                dead_rows = self._dead_row_indices(words, lens, d, dead)
            else:
                # replicated micro-merge owner migrates to the lowest
                # live shard when its default owner (shard 0) is dead
                owner = min(x for x in range(self.tp) if x not in dead)
        step = self._step_for((b, d), routed=routed, micro_owner=owner,
                              block_compile=block_compile)
        t0 = _now_ns()
        packed = self._put_operands(enc)
        t1 = _now_ns()
        with self._lock:
            if self._arrs is None:
                raise RuntimeError("multichip mirror not synced yet")
            res = step(packed, *self._arrs)
        if seq is not None and self._sp_put is not None:
            # both handles or neither (one stage_span rule for the two)
            self._sp_put.rec(t0, t1, n, gen, seq)
            self._sp_launch.rec(t1, _now_ns(), n, gen, seq)
        if dead is not None:
            self._degraded_meta[id(res)] = (dead, dead_rows)
            self.degraded_batches += 1
            if self.metrics is not None:
                self.metrics.inc("tpu.mesh.degraded_batches")
                self.metrics.set("tpu.mesh.state", self.mesh_state())
        if self.degraded and self._fail_counts:
            # a dispatch that made it out clears the CONSECUTIVE
            # failure strikes on the still-live shards
            self._fail_counts.clear()
        self.dispatches += 1
        if self.metrics is not None:
            self.metrics.inc("tpu.match.shard_dispatches")
        if routed:
            self.ep_dispatches += 1
            self._routed_live.add(id(res))
            if self.ep_autotune:
                self._ep_shapes.add((b, d))
                self._note_root_load(words, lens, d)
            if self.metrics is not None:
                cap = self.ep_capacity(b)
                self.metrics.inc("tpu.match.ep_dispatches")
                self.metrics.set("tpu.match.ep_shard_width",
                                 self.tp * cap)
                # analytic ICI bill for the routing all_to_all: each
                # instance ships (tp-1)/tp of its (tp, C) grid — words
                # + lens + is_sys + src per slot
                self.metrics.inc(
                    "tpu.match.ep_ici_bytes",
                    self.dp * self.tp * (self.tp - 1) * cap
                    * (d + 3) * 4)
        return res

    def readback(self, res, n: int, seq: Optional[int] = None,
                 gen: int = 0):
        """Block on the answer and decode it to per-topic SERVICE
        accept-id rows: a routed handle is ONE packed array
        (:meth:`_decode`), a replicated one the dense compact contract,
        whose per-shard segments concatenate (the partition makes them
        disjoint — no dedup); rows flagged by the spill bits go back to
        the host tables.  Degraded serving masks the dead shards'
        replicated answer segments and appends the dead-owned routed
        rows to the spill set (the scoped CPU-fill contract).  Returns
        ``(rows, spilled row indices, d2h bytes)``;
        ``tpu.mesh.answer_buffers`` counts the device buffers fetched.
        A served batch hands in its ``seq`` (and table ``gen``) and gets
        one ``mesh_fetch`` and one ``mesh_decode`` sample; probes and
        canaries do not."""
        t0 = _now_ns()
        routed = id(res) in self._routed_live
        self._routed_live.discard(id(res))
        meta = self._degraded_meta.pop(id(res), None)
        answer = self._answer_arrays(res, routed)
        host = jax.device_get(answer)
        t1 = _now_ns()
        out, spilled = self._decode(
            host, n, routed,
            meta[0] if meta is not None and not routed else None)
        if routed and spilled and self.metrics is not None:
            # the routed fail-open set: bucket overflow + truncation
            # rows the CPU trie re-runs
            self.metrics.inc("tpu.match.ep_overflow_rows", len(spilled))
        if routed and n:
            # overflow-rate EWMA over the psum'd flags (the input the
            # bucket-grid resize will key on), warn once on crossing
            frac = len(spilled) / n
            self._ov_ewma += self.EP_OVERFLOW_ALPHA * (
                frac - self._ov_ewma)
            if self.metrics is not None:
                self.metrics.set("tpu.match.ep_overflow_ewma",
                                 round(self._ov_ewma, 6))
            if self._ov_ewma >= self.ep_overflow_warn > 0:
                if not self._ov_warned:
                    self._ov_warned = True
                    log.warning(
                        "EP bucket overflow EWMA %.3f crossed %.3f: "
                        "a hot root is skewing one owner shard "
                        "(rows fail open to the CPU trie)",
                        self._ov_ewma, self.ep_overflow_warn)
            else:
                self._ov_warned = False
            self._class_readbacks += 1
            if self.ep_autotune:
                self._maybe_resize()
        if meta is not None and routed:
            sp = set(spilled)
            extra = [r for r in meta[1] if r < n and r not in sp]
            if extra:
                self.cpu_filled_rows += len(extra)
                if self.metrics is not None:
                    self.metrics.inc("tpu.mesh.cpu_filled_rows",
                                     len(extra))
                spilled = sorted(sp.union(extra))
        nbytes = 4 * sum(int(a.size) for a in host)
        if self.metrics is not None:
            self.metrics.inc("tpu.mesh.answer_buffers",
                             sum(_blocks(a) for a in answer))
        if seq is not None and self._sp_fetch is not None:
            # both handles or neither (one stage_span rule for the two)
            self._sp_fetch.rec(t0, t1, n, gen, seq)
            self._sp_decode.rec(t1, _now_ns(), n, gen, seq)
        return out, spilled, nbytes

    @staticmethod
    def _answer_arrays(res, routed: bool) -> Tuple[Any, ...]:
        """What a readback fetches of a step's answer: the routed one
        packed array, or the replicated step's five."""
        if routed:
            return (res,)
        return (res.ids, res.counts, res.n_matches, res.active_overflow,
                res.match_overflow)

    def _decode(self, host, n: int, routed: bool,
                dead: Optional[frozenset] = None):
        """``(rows, spilled row indices)`` of the first ``n`` rows of a
        fetched answer.  Routed: one ``decode_packed`` a ``dp`` block,
        block ``j`` holding rows ``j·Bl …``.  Replicated: the dense
        compact segments, the ``dead`` shards' counts zeroed so that
        their stale segments decode empty."""
        if routed:
            (packed,) = host
            blk = packed.size // self.dp
            bl = blk // (1 + SERVE_FLAT_MULT)
            w = self.max_matches + self.ep_micro_matches
            rows: List[List[int]] = []
            spilled: List[int] = []
            for j in range(min(self.dp, -(-n // bl))):
                r, sp = decode_packed(packed[j * blk:(j + 1) * blk],
                                      min(bl, n - j * bl), w)
                rows += r
                spilled += [j * bl + i for i in sp]
            return rows, spilled
        ids, counts, _nm, ao, mo = host
        if dead:
            counts = np.array(counts)
            counts[:, sorted(dead)] = 0
        cap_row = ids.shape[1] // counts.shape[1]
        rows = decode_compact_rows(ids, counts, cap_row)[:n]
        out = [[int(a) for a in row if a >= 0] for row in rows]
        sp = (ao > 0) | (mo > 0)
        return out, np.flatnonzero(sp[:n]).tolist()

    def _dead_row_indices(self, words, lens, depth: int,
                          dead: frozenset) -> List[int]:
        """Routable rows whose crc32-root owner shard is dead, from
        the HOST ``word_owner`` map (the same array the device routes
        by) — the scoped EP failover's CPU divert set."""
        wo = self._word_owner
        roots = np.clip(np.asarray(words)[:, 0], 0, len(wo) - 1)
        owners = wo[roots]
        routable = np.asarray(lens) <= depth
        return np.flatnonzero(
            routable & np.isin(owners, sorted(dead))).tolist()

    def _step_for(self, batch_shape: Tuple[int, int], routed: bool, *,
                  micro_owner: int = 0, block_compile: bool = True):
        cap = self.ep_capacity(batch_shape[0]) if routed else 0
        # mesh-key ``kind``: 0 = replicated, 1 = routed (2 named the
        # count-compact routed output, which every routed step has had
        # since its answer is packed)
        kind = 1 if routed else 0
        kc = self.kernel_cache
        if kc is not None and self._stacked_shape is not None:
            smax, hbmax, acap, sm, hbm, am, wcap = self._stacked_shape
            mesh_key = (self.dp, self.tp, acap, kind, cap,
                        sm, hbm, am, wcap, self.ep_micro_matches)
            if micro_owner:
                # degraded-only key extension: flag off (or owner 0)
                # the cache keys stay the PR 17 shape verbatim
                mesh_key += (int(micro_owner),)
            return kc.executable(
                batch_shape, smax, hbmax,
                active_slots=self.active_slots,
                max_matches=self.max_matches,
                compact_output=True, flat_cap=0,
                mesh=mesh_key,
                block=block_compile,
            )
        key: Tuple[int, ...] = (
            int(batch_shape[0]), int(batch_shape[1]), kind)
        if self.ep_autotune:
            # autotune-only key extension: a class flip must select a
            # freshly built grid, never silently reuse the old one;
            # flag off the keys stay the PR 17 shape verbatim
            key += (cap,)
        if micro_owner:
            key += (int(micro_owner),)
        fn = self._steps.get(key)
        if fn is None:
            fn = self._steps[key] = build_multichip_step(
                self.mesh, self.active_slots, self.max_matches,
                micro_matches=self.ep_micro_matches,
                routed=routed, capacity=cap,
                micro_owner=int(micro_owner))
        return fn

    def _lower_step(self, key):
        """Mesh half of the kernel cache's ``_lower``: AOT-compile the
        shard_map step for one (B, D, S, Hb, ..., (dp, tp, acap, kind,
        C, Sm, Hbm, Am, Wcap, Km[, micro_owner])) key (proven on the
        CPU mesh — jit(shard_map).lower(ShapeDtypeStruct...) works)."""
        from ..ops.compiler import BUCKET_SLOTS

        b, d, s, hb = key[0], key[1], key[2], key[3]
        mk = key[9]
        _dp, _tp, acap, kind, cap, sm, hbm, am, wcap, km = mk[:10]
        owner = int(mk[10]) if len(mk) > 10 else 0
        step = build_multichip_step(
            self.mesh, key[4], key[5], micro_matches=km,
            routed=kind >= 1, capacity=cap, micro_owner=owner)
        sd = jax.ShapeDtypeStruct
        i32 = jnp.int32
        return step.lower(
            sd((b, d + 2), i32),            # pack_operands' one array
            sd((self.tp, s, 4), i32),
            sd((self.tp, hb, BUCKET_SLOTS * 4), i32),
            sd((self.tp, 2), i32),
            sd((self.tp, acap), i32),
            sd((sm, 4), i32),
            sd((hbm, BUCKET_SLOTS * 4), i32),
            sd((2,), i32),
            sd((am,), i32),
            sd((wcap,), i32),
        ).compile()

    # ------------------------------------------------------------------
    # load-adaptive plane: capacity auto-resize + popularity placement
    # (ISSUE 20, opt-in match.multichip.ep.autotune.enable)
    # ------------------------------------------------------------------

    def _note_root_load(self, words, lens, depth: int) -> None:
        """Per-root popularity counters (numpy slab indexed by root
        word id — the admission-plane feature-row idiom): every
        routable row of a routed dispatch bumps its root.  The slab
        ages by halving at each balance pass, so it behaves as an EWMA
        at compaction cadence.  Lock-free: a bump lost under a
        concurrent aging pass skews a statistic, never an answer."""
        w = np.asarray(words)[:, 0]
        routable = (np.asarray(lens) <= depth) & (w > 0)
        if not routable.any():
            return
        if len(self._root_load) < len(self._word_owner):
            grown = np.zeros(len(self._word_owner), np.float64)
            grown[:len(self._root_load)] = self._root_load
            self._root_load = grown
        roots = np.clip(w[routable], 0, len(self._root_load) - 1)
        np.add.at(self._root_load, roots, 1.0)

    def _maybe_resize(self) -> None:
        """Capacity-class trigger (routed readback, worker thread):
        grow one pow2 class when the overflow EWMA crosses the grow
        threshold; shrink one class inside the hysteresis band after
        ``EP_SHRINK_COOLDOWN`` readbacks at the current class.  The
        rebuild runs on a background thread — dispatches keep serving
        the old grid (overflow failing open to the CPU trie) until the
        new step is compiled.  Deferred entirely while any shard is
        dead: the degraded mesh owns the plane then."""
        if self._resize_busy or self._dead:
            return
        target = None
        if (self._ov_ewma >= self.ep_grow_threshold
                and self._cap_class < self.ep_max_cap_class):
            target = self._cap_class + 1
            shapes = list(self._ep_shapes)
            if shapes and all(
                    self.ep_capacity(b) >= max(1, (b // self.dp)
                                               // self.tp)
                    for b, _d in shapes):
                return   # already at the source-slice ceiling
        elif (self._cap_class > 0
              and self._class_readbacks >= self.EP_SHRINK_COOLDOWN
              and self._ov_ewma <= self.ep_shrink_threshold):
            target = self._cap_class - 1
        if target is None:
            return
        self._resize_busy = True
        self._resize_thread = threading.Thread(
            target=self._resize_worker, args=(target,),
            name="mc-ep-resize", daemon=True)
        self._resize_thread.start()

    def drain_resize(self, timeout: Optional[float] = None) -> bool:
        """Teardown drain: join the in-flight capacity rebuild.  The
        worker is a daemon thread, but daemon only helps at interpreter
        exit — a compile left churning after the matcher's owner stops
        keeps XLA on every host core, stealing CPU from whatever the
        process runs next.  Returns True when no resize is in flight."""
        t = self._resize_thread
        if t is not None and t.is_alive():
            t.join(timeout)
        return not self._resize_busy

    def _resize_worker(self, target: int) -> None:
        """Background capacity rebuild: compile the routed step at the
        target class for every observed serve shape FIRST (kernel
        cache AOT when attached — the prewarm machinery — else a local
        warm exec), then flip ``_cap_class`` under the lock.  The flip
        is a key swap, so no dispatch ever parks behind XLA; rows keep
        failing open throughout the compile window.  A successful GROW
        re-arms the overflow-warn latch and zeroes the EWMA (satellite
        bugfix: it must measure the new grid, and a later regression
        must warn again)."""
        grew = target > self._cap_class
        try:
            for b, d in sorted(self._ep_shapes):
                self._warm_capacity((b, d), target)
            with self._lock:
                self._cap_class = target
                self._class_readbacks = 0
                if grew:
                    self._ov_ewma = 0.0
                    self._ov_warned = False
            self.ep_resizes += 1
            if self.metrics is not None:
                self.metrics.set("tpu.match.ep_cap_class", target)
                self.metrics.inc("tpu.match.ep_resizes")
            log.warning("EP bucket grid %s to capacity class %d "
                        "(overflow EWMA keyed)",
                        "grew" if grew else "shrank", target)
        except Exception:
            log.warning("EP capacity resize to class %d failed; grid "
                        "unchanged", target, exc_info=True)
        finally:
            self._resize_busy = False

    def _warm_capacity(self, batch_shape: Tuple[int, int],
                       cap_class: int) -> None:
        """Compile the routed step for ``batch_shape`` at an explicit
        capacity class WITHOUT flipping the live class.  With a kernel
        cache the compile lands in the shared cache (a post-flip
        dispatch with ``block=False`` hits, never a CompileMiss); the
        no-cache path warm-executes the local step once so its jit
        cache is hot."""
        b, d = int(batch_shape[0]), int(batch_shape[1])
        cap = self._capacity_at(b, cap_class)
        kind = 1
        kc = self.kernel_cache
        if kc is not None and self._stacked_shape is not None:
            smax, hbmax, acap, sm, hbm, am, wcap = self._stacked_shape
            mesh_key = (self.dp, self.tp, acap, kind, cap,
                        sm, hbm, am, wcap, self.ep_micro_matches)
            kc.executable(
                (b, d), smax, hbmax,
                active_slots=self.active_slots,
                max_matches=self.max_matches,
                compact_output=True, flat_cap=0,
                mesh=mesh_key, block=True)
            return
        key = (b, d, kind, cap)
        if key in self._steps:
            return
        fn = build_multichip_step(
            self.mesh, self.active_slots, self.max_matches,
            micro_matches=self.ep_micro_matches,
            routed=True, capacity=cap)
        with self._lock:
            arrs = self._arrs
        if arrs is not None:
            try:
                res = fn(self._put_operands(
                    self.encode([], batch=b, depth=d)), *arrs)
                jax.block_until_ready(res)
            except Exception:
                # a concurrent apply donated the snapshot away: the
                # compile simply happens at the first dispatch instead
                # (the pre-existing no-cache contract)
                log.debug("EP capacity warm exec lost the snapshot "
                          "race", exc_info=True)
        self._steps[key] = fn

    def plan_rebalance(self) -> int:
        """WORKER-THREAD step (the service's ``table.compact`` worker
        cadence): greedy hot-root reassignment off the popularity
        slab.  Moves the hottest improving root from the most- to the
        least-loaded shard, at most ``balance_budget`` times, and
        stages the result as a ``root → shard`` override map that the
        NEXT ``rebuild()`` apply swaps in (aid spans remap during that
        restack).  Defers — stages nothing, returns 0 — while any
        shard is dead or rebuilding: roots never remap onto a dead
        owner, and the readmit canary must judge the placement it was
        built against.  An injected ``ep.rebalance`` fault raises
        BEFORE anything is staged (kill mid-rebalance = no-op).
        Returns the number of roots moved."""
        if not self.ep_autotune or self.tp < 2 or self.balance_budget <= 0:
            return 0
        if _fi._injector is not None:
            act = _fi._injector.act("ep.rebalance")
            if act == "raise":
                raise _fi.InjectedFault("ep.rebalance")
            if act == "delay":
                import time

                time.sleep(_fi._injector.last_delay)
        if self._dead:
            return 0
        with self._lock:
            load = self._root_load.copy()
            placement = dict(self._placement)
            vocab_items = list(self.vocab.items())
        self._root_load *= 0.5   # age: EWMA at compaction cadence
        cand = [(w, wid) for w, wid in vocab_items
                if 0 < wid < len(load) and load[wid] > 0.0]
        if not cand:
            return 0
        owners: Dict[str, int] = {}
        loads: Dict[str, float] = {}
        for w, wid in cand:
            o = placement.get(w)
            if o is None:
                o = zlib.crc32(w.encode("utf-8")) % self.tp
            owners[w] = int(o)
            loads[w] = float(load[wid])
        from .prefix_ep import greedy_balance

        owners, moved = greedy_balance(
            loads, owners, self.tp, self.balance_budget)
        # the override map keeps only roots off their crc32 default;
        # overrides for roots with no observed load this round persist
        # (their filters still live on the overridden shard)
        new_place = {
            w: o for w, o in owners.items()
            if o != zlib.crc32(w.encode("utf-8")) % self.tp}
        for w, o in placement.items():
            if w not in owners:
                new_place.setdefault(w, o)
        if new_place == placement:
            return 0
        with self._lock:
            self._placement_next = new_place
        self.ep_rebalances += 1
        self.moved_roots = moved
        if self.metrics is not None:
            self.metrics.inc("tpu.match.ep_rebalances")
            self.metrics.set("tpu.match.ep_moved_roots", moved)
        log.info("EP balance pass staged %d root move(s) (%d "
                 "override(s) total); the next rebuild applies",
                 moved, len(new_place))
        return moved

    # ------------------------------------------------------------------
    # online shard rebuild + canary re-admit (degraded mesh, ISSUE 18)
    # ------------------------------------------------------------------

    def canary_topics(self, t: int, cap: int = 64) -> List[str]:
        """Concrete topics derived from shard ``t``'s own filter set
        (each wildcard level degraded to a literal token), so the
        re-admit canary batch exercises exactly the rebuilt subtable."""
        out = []
        for flt in list(self._filters[int(t)])[:cap]:
            out.append("/".join(
                w if w not in ("+", "#") else "c" for w in T.words(flt)))
        return out

    def canary_rows(self, topics: Sequence[str], batch: int,
                    readmit: int) -> Tuple[List[List[int]], List[int]]:
        """Dispatch a canary batch with shard ``readmit`` treated LIVE
        (any OTHER dead shard stays masked/diverted) — the bit-parity
        probe that gates re-admission.  Serving counters and the
        failure ladder are untouched; gates are bypassed on purpose
        (the probe must run while the shard is still marked dead)."""
        enc = self.encode(topics, batch=batch)
        words, lens, is_sys = enc
        b, d = int(words.shape[0]), int(words.shape[1])
        routed = self._routed_for(b)
        dead = frozenset(int(x) for x in self._dead
                         if int(x) != int(readmit))
        owner = 0
        dead_rows: List[int] = []
        if dead:
            if routed:
                dead_rows = self._dead_row_indices(words, lens, d, dead)
            else:
                owner = min(x for x in range(self.tp) if x not in dead)
        step = self._step_for((b, d), routed=routed, micro_owner=owner,
                              block_compile=True)
        packed = self._put_operands(enc)
        with self._lock:
            if self._arrs is None:
                raise RuntimeError("multichip mirror not synced yet")
            res = step(packed, *self._arrs)
        n = len(topics)
        out, spilled = self._decode(
            jax.device_get(self._answer_arrays(res, routed)), n, routed,
            None if routed else dead)
        return out, sorted(set(spilled).union(
            r for r in dead_rows if r < n))

    def rebuild_shard(self, t: int, pairs: List[Tuple[str, int]],
                      segments_dir: Optional[str] = None,
                      expect_epoch: Optional[int] = None) -> float:
        """WORKER-THREAD step (the supervised ``mesh.rebuild`` child's
        ``to_thread`` hop): reconstruct shard ``t``'s subtable — seeded
        from its epoch-guarded per-shard segment when one matches, then
        a delta-tail replay from the service-level ``pairs`` converges
        it on the live filter state — and restack/re-upload the stacked
        twin.  Does NOT re-admit: the caller runs the bit-parity canary
        first.  Returns the rebuild wall seconds; an injected
        ``mesh.rebuild`` fault raises (the supervised child restarts
        and retries)."""
        import time as _time

        if _fi._injector is not None:
            act = _fi._injector.act("mesh.rebuild")
            if act == "raise":
                raise _fi.InjectedFault("mesh.rebuild")
            if act == "delay":
                _time.sleep(_fi._injector.last_delay)
        t = int(t)
        t0 = _time.perf_counter()
        want = {flt: aid for flt, aid in pairs
                if not is_micro_filter(flt)
                and self.shard_of(flt) == t}
        with self._maint_lock:
            seeded = self._seg_seed_filters(t, segments_dir,
                                            expect_epoch)
            sub = self._new_sub()
            seed_flts = [f for f in (seeded or ())]
            if self.native:
                # replay the live shared vocab in id order first so the
                # fresh native table assigns identical word ids
                sub.bulk_intern(
                    [w for w, _i in sorted(self.vocab.items(),
                                           key=lambda kv: kv[1])])
                sub.bulk_add(seed_flts)
            else:
                for f in seed_flts:
                    sub.add(f)
            # delta-tail replay: adds since the snapshot, then removes
            # of filters the service no longer holds
            for f in want:
                if seeded is None or f not in seeded:
                    sub.add(f)
            for f in seed_flts:
                if f not in want:
                    sub.remove(f)
            if self.native:
                self._adopt_vocab_tail(sub)
            amap = np.full(max(64, sub.n_filters + 1), -1, np.int32)
            for flt, aid in want.items():
                laid = sub.aid_of(flt)
                if laid < 0:
                    raise RuntimeError(
                        f"rebuilt filter missing: {flt!r}")
                if laid >= len(amap):
                    grown = np.full(max(2 * len(amap), laid + 1), -1,
                                    np.int32)
                    grown[:len(amap)] = amap
                    amap = grown
                amap[laid] = aid
            self._subs[t] = sub
            self._aid_maps[t] = amap
            self._filters[t] = dict(want)
            self._restack()
        dt = _time.perf_counter() - t0
        self.rebuilds += 1
        if self.metrics is not None:
            self.metrics.set("tpu.mesh.rebuild_s", round(dt, 6))
        log.warning("mesh shard %d rebuilt (%d filters, %s seed) in "
                    "%.3fs — canary gates re-admission", t, len(want),
                    "segment" if seeded is not None else "full", dt)
        return dt

    def _adopt_vocab_tail(self, sub) -> None:
        """``bulk_add``'s warm probe may intern sentinel words past the
        replayed shared sequence: append them to the shared vocab and
        every OTHER table too (ids assign append-only from the same
        prefix, so all vocabs stay identical)."""
        extra = [(w, i) for w, i in sub.vocab.items()
                 if w not in self.vocab]
        for w, _i in sorted(extra, key=lambda kv: kv[1]):
            self.vocab[w] = len(self.vocab) + 1
            for tbl in self._all_tables():
                if tbl is not sub:
                    tbl.intern(w)

    def _seg_seed_filters(self, t: int, segments_dir: Optional[str],
                          expect_epoch: Optional[int],
                          ) -> Optional[Dict[str, int]]:
        """Shard ``t``'s persisted (filter → service aid) snapshot iff
        the manifest's epoch/shape/checksum still match — the rebuild
        seed.  None → the rebuild runs from the live pairs alone."""
        if segments_dir is None or expect_epoch is None:
            return None
        from ..storage.segments import load_segment

        d = self._seg_dir(segments_dir)
        try:
            with open(os.path.join(d, "manifest.json")) as f:
                meta = json.load(f)
            if (meta.get("version") != self.MANIFEST_VERSION
                    or meta.get("tp") != self.tp
                    or meta.get("depth") != self.depth
                    or meta.get("native") != bool(self.native)
                    or meta.get("epoch") != int(expect_epoch)):
                return None
            npz = np.load(os.path.join(d, "aid_maps.npz"))
            arrays = {k: npz[k] for k in npz.files}
            meta_core = {k: meta[k] for k in
                         ("version", "epoch", "tp", "depth", "native")}
            if meta.get("checksum") != self._manifest_checksum(
                    meta_core, arrays):
                return None
            seg = load_segment(os.path.join(d, f"shard{t}.seg.npz"))
            if seg.depth != self.depth:
                return None
            if seg.meta.get("placement_crc") != self._place_crc(
                    self._placement):
                # the segment was cut under a different placement: its
                # filter set is not this shard's under the LIVE map —
                # the full rebuild from service pairs serves instead
                return None
            if seg.kind == "filters":
                sa = np.asarray(arrays[f"sa{t}"], np.int32)
                if len(sa) != len(seg.filters):
                    return None
                return dict(zip(seg.filters, sa.tolist()))
            amap = np.asarray(arrays[f"m{t}"], np.int32)
            return {f: int(amap[aid]) for aid, f in
                    enumerate(seg.accept_filters or [])
                    if f is not None and aid < len(amap)
                    and amap[aid] >= 0}
        except Exception:
            log.warning("mesh rebuild segment seed unavailable; full "
                        "rebuild from service state", exc_info=True)
            return None

    # ------------------------------------------------------------------
    # per-shard segment persistence (opt-in via match.segments.enable)
    # ------------------------------------------------------------------

    @staticmethod
    def _seg_dir(segments_dir: str) -> str:
        return os.path.join(segments_dir, "multichip")

    @staticmethod
    def _place_crc(place: Dict[str, int]) -> int:
        """Canonical crc32 of a placement override map — stamped into
        every per-shard segment's (checksummed) meta so a shard file
        cut under a DIFFERENT placement than the manifest restores is
        rejected (a torn save can leave mixed generations; the epoch
        guard alone can't see a placement-only swap)."""
        return zlib.crc32(json.dumps(
            sorted(place.items()),
            separators=(",", ":")).encode("utf-8"))

    def save_segments(self, segments_dir: str, epoch: int) -> None:
        """WORKER-THREAD step: persist every shard subtable + the
        micro-table (native tables ride the NUL-framed "filters"
        segment kind, Python tables the full "state" kind) plus a
        checksummed manifest carrying the service-table epoch, the
        shared vocab in id order, per-filter service aids, and the
        local→service aid maps.  Cold start seeds from these iff the
        epoch still matches (the ``_seg_join_seed`` idiom)."""
        with self._maint_lock:
            self._save_segments_locked(segments_dir, epoch)

    def _save_segments_locked(self, segments_dir: str, epoch: int) -> None:
        from ..storage.segments import save_segment

        d = self._seg_dir(segments_dir)
        os.makedirs(d, exist_ok=True)
        pcrc = self._place_crc(self._placement)
        arrays: Dict[str, np.ndarray] = {}
        for t, sub in enumerate(self._subs):
            flts = list(self._filters[t])
            save_segment(os.path.join(d, f"shard{t}.seg.npz"), sub,
                         deep={}, routing_aids=set(), filters=flts,
                         extra_meta={"placement_crc": pcrc})
            arrays[f"m{t}"] = np.asarray(self._aid_maps[t], np.int32)
            arrays[f"sa{t}"] = np.asarray(
                [self._filters[t][f] for f in flts], np.int32)
        mflts = list(self._micro_filters)
        save_segment(os.path.join(d, "micro.seg.npz"), self._micro,
                     deep={}, routing_aids=set(), filters=mflts,
                     extra_meta={"placement_crc": pcrc})
        arrays["mm"] = np.asarray(self._micro_amap, np.int32)
        arrays["sam"] = np.asarray(
            [self._micro_filters[f] for f in mflts], np.int32)
        # the shared vocab in id order (NUL-framed: words may contain
        # '\n', never NUL) — the restore replays it FIRST so every
        # fresh native vocab assigns the same ids
        words = [w for w, _i in sorted(self.vocab.items(),
                                       key=lambda kv: kv[1])]
        arrays["vw"] = np.frombuffer(
            "\x00".join(words).encode("utf-8"), np.uint8).copy()
        # v3: the popularity placement override map (NUL-framed roots
        # + parallel int32 owners, deterministic order) — cold start
        # restores placement BEFORE the restack, so the restored
        # partition and the shard_of it will serve under agree
        proots = sorted(self._placement)
        arrays["pr"] = (np.frombuffer(
            "\x00".join(proots).encode("utf-8"), np.uint8).copy()
            if proots else np.zeros(0, np.uint8))
        arrays["ps"] = np.asarray(
            [self._placement[w] for w in proots], np.int32)
        meta = {"version": self.MANIFEST_VERSION, "epoch": int(epoch),
                "tp": self.tp, "depth": self.depth,
                "native": bool(self.native)}
        digest = self._manifest_checksum(meta, arrays)
        np.savez(os.path.join(d, "aid_maps.npz"), **arrays)
        # the manifest lands LAST (atomic replace = the commit point):
        # a crash mid-save leaves either the old manifest or none
        tmp = os.path.join(d, "manifest.json.tmp")
        with open(tmp, "w") as f:
            json.dump({**meta, "checksum": digest}, f, sort_keys=True)
        os.replace(tmp, os.path.join(d, "manifest.json"))
        self._persist_due = False

    @staticmethod
    def _manifest_checksum(meta: dict, maps: Dict[str, np.ndarray]) -> str:
        import hashlib

        h = hashlib.sha1(json.dumps(meta, sort_keys=True).encode())
        for k in sorted(maps):
            h.update(k.encode())
            h.update(np.ascontiguousarray(maps[k]).tobytes())
        return h.hexdigest()

    def _restore_sub(self, seg, arrays, sa_key: str):
        """One subtable + its (filter → service aid) dict from a
        segment: native replays the NUL/newline filter blob through
        ``bulk_add`` and rebuilds aids via ``aid_of`` (robust to
        bulk-order drift); Python restores the full state."""
        from ..storage.segments import restore_incremental

        if seg.kind == "filters":
            if not self.native:
                raise ValueError("filters-kind segment without native")
            sub = self._new_sub()
            sub.bulk_intern(self._restored_words)
            flts = list(seg.filters)
            sub.bulk_add(flts)
            sa = np.asarray(arrays[sa_key], np.int32)
            if len(sa) != len(flts):
                raise ValueError("service-aid array length mismatch")
            amap = np.full(max(64, sub.n_filters + 1), -1, np.int32)
            fdict: Dict[str, int] = {}
            for f, service_aid in zip(flts, sa.tolist()):
                laid = sub.aid_of(f)
                if laid < 0:
                    raise ValueError(f"restored filter missing: {f!r}")
                if laid >= len(amap):
                    grown = np.full(
                        max(2 * len(amap), laid + 1), -1, np.int32)
                    grown[:len(amap)] = amap
                    amap = grown
                amap[laid] = service_aid
                fdict[f] = service_aid
            return sub, amap, fdict
        if seg.kind != "state" or self.native:
            raise ValueError(f"unexpected segment kind {seg.kind!r}")
        sub = restore_incremental(seg)
        amap_key = "m" + sa_key[2:] if sa_key.startswith("sa") else "mm"
        amap = np.asarray(arrays[amap_key], np.int32)
        fdict = {}
        for f in sub.filters():
            laid = sub.aid_of(f)
            if 0 <= laid < len(amap) and amap[laid] >= 0:
                fdict[f] = int(amap[laid])
        return sub, amap, fdict

    def load_segments(self, segments_dir: str, expect_epoch: int) -> bool:
        """Cold start: restore the shard partition from the persisted
        per-shard segments iff the manifest's service epoch matches the
        just-restored main table (no drift since the save) — else the
        caller rebuilds the partition from the live service state.
        Returns True when seeded."""
        from ..storage.segments import load_segment

        d = self._seg_dir(segments_dir)
        try:
            with open(os.path.join(d, "manifest.json")) as f:
                meta = json.load(f)
            if meta.get("version") != self.MANIFEST_VERSION \
                    or meta.get("tp") != self.tp \
                    or meta.get("depth") != self.depth \
                    or meta.get("native") != bool(self.native) \
                    or meta.get("epoch") != int(expect_epoch):
                return False
            npz = np.load(os.path.join(d, "aid_maps.npz"))
            arrays = {k: npz[k] for k in npz.files}
            want = meta.get("checksum")
            meta_core = {k: meta[k] for k in
                         ("version", "epoch", "tp", "depth", "native")}
            if want != self._manifest_checksum(meta_core, arrays):
                log.warning("multichip manifest checksum mismatch; "
                            "repartition serves")
                return False
            self._restored_words = (
                bytes(np.asarray(arrays["vw"], np.uint8))
                .decode("utf-8").split("\x00")
                if len(arrays.get("vw", ())) else [])
            place: Dict[str, int] = {}
            if len(arrays.get("pr", ())):
                proots = (bytes(np.asarray(arrays["pr"], np.uint8))
                          .decode("utf-8").split("\x00"))
                powners = np.asarray(arrays["ps"], np.int32).tolist()
                if len(proots) != len(powners) or any(
                        not 0 <= o < self.tp for o in powners):
                    log.warning("multichip placement map malformed; "
                                "repartition serves")
                    return False
                place = dict(zip(proots, powners))
            pcrc = self._place_crc(place)
            subs, amaps, fdicts = [], [], []
            for t in range(self.tp):
                seg = load_segment(os.path.join(d, f"shard{t}.seg.npz"))
                if seg.depth != self.depth:
                    return False
                if seg.meta.get("placement_crc") != pcrc:
                    # a torn save left this shard file cut under a
                    # different placement than the manifest restores
                    log.warning("multichip shard %d segment placement "
                                "skew; repartition serves", t)
                    return False
                sub, amap, fdict = self._restore_sub(
                    seg, arrays, f"sa{t}")
                subs.append(sub)
                amaps.append(amap)
                fdicts.append(fdict)
            mseg = load_segment(os.path.join(d, "micro.seg.npz"))
            if mseg.depth != self.depth:
                return False
            if mseg.meta.get("placement_crc") != pcrc:
                log.warning("multichip micro segment placement skew; "
                            "repartition serves")
                return False
            micro, micro_amap, micro_fdict = self._restore_sub(
                mseg, arrays, "sam")
        except FileNotFoundError:
            return False
        except Exception:
            log.warning("multichip segment load failed; repartition "
                        "serves", exc_info=True)
            return False
        if self.native:
            # bulk_add's warm probe interns a few sentinel words past
            # the persisted list; every table replayed the identical
            # sequence, so adopt one table's (refreshed) vocab as the
            # shared encode vocab and guard that they all agree —
            # otherwise the next live intern would assign drifting ids
            vocab = dict(subs[0].vocab)
            for tbl in [*subs[1:], micro]:
                if tbl.vocab != vocab:
                    log.warning("multichip shard vocabs diverged; "
                                "repartition serves")
                    return False
        else:
            # every shard persisted the SAME shared vocab — rebind
            # them to one dict instance so future interning stays
            # consistent
            vocab = subs[0].vocab
            for tbl in [*subs[1:], micro]:
                if tbl.vocab != vocab:
                    log.warning("multichip shard vocabs diverged; "
                                "repartition serves")
                    return False
                tbl.vocab = vocab
        with self._lock:
            self.vocab = vocab
            self._subs = subs
            self._aid_maps = amaps
            self._filters = fdicts
            self._micro = micro
            self._micro_amap = micro_amap
            self._micro_filters = micro_fdict
            # placement restores FIRST relative to the word_owner
            # resync the pending restack performs — the restored
            # partition was saved under exactly this map
            self._placement = place
            self._placement_next = None
            self._word_owner = np.zeros(1024, np.int32)
            self._word_owner_n = 0
            self._pending = []
            self._rebuild_pairs = None
            self._restack_due = True
            self._arrs = None
        self.seeded_from_segments = True
        return True

    def info(self) -> dict:
        return {
            "devices": self.n_devices,
            "mesh": {"dp": self.dp, "tp": self.tp},
            "ready": self.ready,
            "native": self.native,
            "ep": self.ep,
            "ep_compact": self.ep_compact,
            "gen": self.gen,
            "dispatches": self.dispatches,
            "ep_dispatches": self.ep_dispatches,
            "failovers": self.failovers,
            "applies": self.applies,
            "restacks": self.restacks,
            "dead_shards": sorted(self._dead),
            "shard_filters": [sub.n_filters for sub in self._subs],
            "micro_filters": len(self._micro_filters),
            "seeded_from_segments": self.seeded_from_segments,
            "degraded": self.degraded,
            "mesh_state": ("healthy", "degraded",
                           "cpu-only")[self.mesh_state()],
            "fail_counts": {str(t): c for t, c in
                            sorted(self._fail_counts.items())},
            "degraded_batches": self.degraded_batches,
            "cpu_filled_rows": self.cpu_filled_rows,
            "rebuilds": self.rebuilds,
            "readmit_canary_fails": self.readmit_canary_fails,
            "ep_overflow_ewma": round(self._ov_ewma, 6),
            "ep_autotune": self.ep_autotune,
            "ep_cap_class": self._cap_class,
            "ep_resizes": self.ep_resizes,
            "ep_rebalances": self.ep_rebalances,
            "placement_overrides": len(self._placement),
        }
