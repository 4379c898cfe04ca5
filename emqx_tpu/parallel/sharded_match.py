"""Multi-chip publish step: DP-sharded NFA match + TP-sharded subscriber
bitmaps with ICI reductions.

This is the TPU-native counterpart of the reference's cluster fan-out
(``emqx_broker:publish`` → route → ``gen_rpc`` forward → per-node dispatch,
SURVEY.md §3.4), restructured for a device mesh (§2.5):

* the NFA tables are **replicated** on every chip (they are the "model");
* the topic batch is sharded over ``dp`` — each chip matches its rows with
  zero communication;
* the accept→subscriber bitmap matrix is sharded **column-wise** over
  ``tp`` — each chip OR-assembles its slice of every matched row locally,
  and per-topic totals (e.g. shared-group member counts) are ``psum``'d
  over ``tp`` (BASELINE config 4's "$share fan-out with subscriber-bitmap
  reduction").

Everything runs inside one ``shard_map`` so XLA sees the whole step and
schedules the collectives on ICI.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.compiler import NfaTable
from ..ops.match_kernel import nfa_match

__all__ = ["CompactFanoutResult", "FanoutResult",
           "build_sharded_matcher", "build_sharded_matcher_compact",
           "compact_bitmap_ids", "decode_compact_rows",
           "make_accept_bitmap", "or_accept_rows"]


class FanoutResult(NamedTuple):
    bitmap: jax.Array       # (B, W) uint32 — per-topic subscriber bitmap
    n_subscribers: jax.Array  # (B,) int32 — popcount over the full row
    n_matches: jax.Array    # (B,) int32 — matched filter count
    active_overflow: jax.Array  # (B,) int32 per-row spills (fail-open set)
    match_overflow: jax.Array   # (B,) int32 per-row 1 where count > K


class CompactFanoutResult(NamedTuple):
    """Dense-id fan-out (shard-locally compacted): what leaves the mesh
    is proportional to MATCHES, not table width.  ``ids`` holds GLOBAL
    subscriber ids (-1 padded) — each tp shard compacts its own bitmap
    columns with the same popcount + prefix-scan gather the match
    kernel's flat epilogue uses, and tp shards own disjoint subscriber
    ranges, so the per-row union across tp segments is a plain
    concatenation (no dedup pass)."""

    ids: jax.Array          # (B, tp·cap_row) int32, ascending per segment
    counts: jax.Array       # (B, tp) int32 — ids per tp segment
    overflow: jax.Array     # (B, tp) int32 — 1 where a segment truncated
    n_matches: jax.Array    # (B,) int32
    active_overflow: jax.Array  # (B,) int32 (fail-open set)
    match_overflow: jax.Array   # (B,) int32


def make_accept_bitmap(
    table: NfaTable, subscribers_of, n_subs: int, tp: int = 1
) -> np.ndarray:
    """Build the accept-id → subscriber-bitmap matrix (F+1, W) uint32.

    ``subscribers_of(filter) -> iterable[int]`` maps each accept filter to
    subscriber ids in [0, n_subs).  Row F (last) is all-zero and is indexed
    by invalid match slots.  W is padded so tp divides it.
    """
    words = (n_subs + 31) // 32
    if words % tp:
        words += tp - (words % tp)
    F = table.n_accepts
    bm = np.zeros((F + 1, words), np.uint32)
    for aid, flt in enumerate(table.accept_filters):
        for sub in subscribers_of(flt):
            if not 0 <= sub < n_subs:
                raise ValueError(f"subscriber id {sub} out of range")
            bm[aid, sub >> 5] |= np.uint32(1) << np.uint32(sub & 31)
    return bm


def or_accept_rows(accept_bitmap: jax.Array, matches: jax.Array) -> jax.Array:
    """(F+1, W) accept bitmap × (B, K) match ids → (B, W) OR-assembled
    subscriber rows.  Invalid slots (-1) index the all-zero sentinel
    row F.  Shared by every fan-out layout (TP, ring, Ulysses)."""
    F = accept_bitmap.shape[0] - 1
    idx = jnp.where(matches >= 0, matches, F)        # (B, K)
    rows = accept_bitmap[idx]                        # (B, K, W)
    return jax.lax.reduce(
        rows, np.uint32(0), jax.lax.bitwise_or, (1,)
    )


def compact_bitmap_ids(bitmap: jax.Array, cap_row: int,
                       id_base=0) -> Tuple[jax.Array, jax.Array,
                                           jax.Array]:
    """Shard-local bitmap compaction: (B, W) uint32 → dense per-row
    subscriber-id lists, entirely on device.

    The same popcount + prefix-scan gather shape as the match kernel's
    flat epilogue: expand set bits, cumsum positions within the row,
    compare-scatter into a (B, cap_row) buffer (-1 padded, ascending).
    ``id_base`` offsets local bit positions into the GLOBAL subscriber
    id space (a tp shard passes its column offset).  Returns
    ``(ids, counts, overflow)`` with overflow = 1 where a row's
    popcount exceeded ``cap_row`` (fail-open set — the host re-runs
    those rows against the full bitmap)."""
    B, W = bitmap.shape
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = ((bitmap[:, :, None] >> shifts) & jnp.uint32(1)) \
        .astype(jnp.int32).reshape(B, W * 32)               # (B, W·32)
    sub = id_base + jnp.arange(W * 32, dtype=jnp.int32)     # global ids
    n = jnp.sum(bits, axis=1)
    pos = jnp.cumsum(bits, axis=1) - 1
    pos = jnp.where(bits > 0, pos, cap_row)                 # OOB-drop
    rows = jnp.broadcast_to(jnp.arange(B)[:, None], pos.shape)
    out = jnp.full((B, cap_row), -1, jnp.int32)
    ids = out.at[rows, pos].set(
        jnp.broadcast_to(sub[None, :], pos.shape), mode="drop")
    overflow = (n > cap_row).astype(jnp.int32)
    return ids, n, overflow


def decode_compact_rows(ids: np.ndarray, counts: np.ndarray,
                        cap_row: int):
    """Host decode of a :class:`CompactFanoutResult`: per-topic global
    subscriber-id arrays, tp segments concatenated.  ``ids`` is
    (B, tp·cap_row), ``counts`` (B, tp); segments are disjoint by
    construction so no dedup is needed.  Truncated segments (overflow)
    decode to their surviving prefix — callers re-run flagged rows."""
    B, tp = counts.shape
    out = []
    for r in range(B):
        segs = [ids[r, t * cap_row:t * cap_row
                    + min(int(counts[r, t]), cap_row)]
                for t in range(tp)]
        out.append(np.concatenate(segs) if segs else
                   np.empty(0, np.int32))
    return out


def build_sharded_matcher_compact(
    mesh: Mesh,
    cap_row: int = 64,
    active_slots: int = 16,
    max_matches: int = 32,
):
    """Dense-id twin of :func:`build_sharded_matcher`: each (dp, tp)
    shard OR-assembles its bitmap slice locally, then COMPACTS it on
    shard — the cross-chip output is per-topic dense global subscriber
    ids + counts (4·(tp·cap_row + tp) bytes/topic, matches-proportional
    with cap_row sized to the fan-out tail) instead of the full (B, W)
    bitmap tile (W words/topic ≈ 1.2 MB/topic at 10M filters)."""
    repl = P()

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P("dp", None),  # words
            P("dp"),        # lens
            P("dp"),        # is_sys
            repl, repl, repl,  # NFA arrays
            P(None, "tp"),  # accept_bitmap columns
        ),
        out_specs=CompactFanoutResult(
            ids=P("dp", "tp"),
            counts=P("dp", "tp"),
            overflow=P("dp", "tp"),
            n_matches=P("dp"),
            active_overflow=P("dp"),
            match_overflow=P("dp"),
        ),
        check_vma=False,
    )
    def step(words, lens, is_sys, node_tab, edge_tab, seeds,
             accept_bitmap):
        res = nfa_match(
            words, lens, is_sys, node_tab, edge_tab, seeds,
            active_slots=active_slots, max_matches=max_matches,
        )
        bitmap = or_accept_rows(accept_bitmap, res.matches)  # (Bl, Wl)
        # local columns → global subscriber ids: tp shard t owns words
        # [t·Wl, (t+1)·Wl) of the padded bitmap row
        base = jax.lax.axis_index("tp") * bitmap.shape[1] * 32
        ids, n, over = compact_bitmap_ids(bitmap, cap_row, id_base=base)
        return CompactFanoutResult(
            ids=ids,
            counts=n[:, None],
            overflow=over[:, None],
            n_matches=res.n_matches,
            active_overflow=res.active_overflow,
            match_overflow=res.match_overflow,
        )

    return jax.jit(step)


def build_sharded_matcher(
    mesh: Mesh,
    active_slots: int = 16,   # keep in lockstep with nfa_match defaults so
    max_matches: int = 32,    # sharded/unsharded paths agree on truncation
):
    """Return a jitted ``step(words, lens, is_sys, *nfa_arrays, accept_bitmap)
    -> FanoutResult`` sharded over the mesh.

    Input layouts: batch arrays sharded over ``dp``; NFA arrays replicated;
    ``accept_bitmap`` (F+1, W) sharded over ``tp`` columns.  Output bitmap
    is (dp, tp)-sharded; counts are dp-sharded (psum'd over tp).
    """
    repl = P()

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P("dp", None),  # words
            P("dp"),        # lens
            P("dp"),        # is_sys
            repl, repl, repl,  # NFA arrays (node_tab, edge_tab, seeds)
            P(None, "tp"),  # accept_bitmap columns
        ),
        out_specs=FanoutResult(
            bitmap=P("dp", "tp"),
            n_subscribers=P("dp"),
            n_matches=P("dp"),
            active_overflow=P("dp"),
            match_overflow=P("dp"),
        ),
        check_vma=False,
    )
    def step(words, lens, is_sys, node_tab, edge_tab, seeds, accept_bitmap):
        res = nfa_match(
            words, lens, is_sys, node_tab, edge_tab, seeds,
            active_slots=active_slots, max_matches=max_matches,
        )
        bitmap = or_accept_rows(accept_bitmap, res.matches)  # (Bl, Wl)
        # per-topic total subscribers: popcount local slice, psum over tp
        local = jnp.sum(
            jax.lax.population_count(bitmap).astype(jnp.int32), axis=1
        )
        total = jax.lax.psum(local, "tp")
        # per-row overflow rides the dp sharding like the other outputs —
        # the host re-runs exactly the spilled rows on the trie
        return FanoutResult(
            bitmap=bitmap,
            n_subscribers=total,
            n_matches=res.n_matches,
            active_overflow=res.active_overflow,
            match_overflow=res.match_overflow,
        )

    return jax.jit(step)
