"""Batched NFA wildcard-match kernel — the device hot path.

Replaces the per-publish ``emqx_trie:match/1`` walk (reference hot loop #1,
SURVEY.md §3.4) with ONE unrolled NFA evaluation over a whole topic batch:

* carry: ``active`` (B, A) int32 — the NFA active-state set per topic,
  -1 padded.  Active sets are **duplicate-free by construction**: a trie
  node is reachable from the root by exactly one label path, so at step t
  each matching depth-t node appears at most once.  Compaction is a
  ``top_k`` (valids first), no dedup pass.
* per step t ∈ [0, D]:

  - ``#``-accepts fire for every active state (a ``#`` child matches the
    zero remaining levels too, which is why the walk runs D+1 steps);
  - end-accepts fire when t == topic length;
  - transitions fetch the literal edge from the bucketed cuckoo
    table (TWO wide row-gathers — the TPU-friendly access pattern; see
    compiler docstring) plus the ``+`` edge from the packed per-state
    node table (ONE wide gather), masked for t ≥ length and for the
    root-level-wildcard-vs-$-topic rule at t == 0.

The walk is fully unrolled: D is small and static, XLA fuses across
steps, and no dynamic loop means no per-iteration host round trips on
remote-attached backends.

Outputs per topic: up to K matched accept ids (valids first, -1
padded), the exact match count, plus PER-ROW overflow counters
(active-set spill beyond A, match spill beyond K): a spilled row's
answer is possibly truncated and the host re-runs exactly those rows on
the authoritative trie (fail-open, SURVEY.md §5.3 — implemented in the
serving engines).

Everything is int32, static shapes, no data-dependent control flow — one
XLA compilation per (D, A, K, B, S, Hb) bucket.
"""

from __future__ import annotations

from functools import partial
from typing import List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .compiler import BUCKET_SLOTS, NfaTable, encode_topics

__all__ = ["MatchResult", "SERVE_FLAT_MULT", "build_matcher",
           "decode_flat", "decode_packed", "decode_row_meta",
           "match_topics", "nfa_match", "nfa_match_packed", "nfa_walk",
           "packed_answer", "packed_twin"]

# serving flat-output capacity per padded batch row (ids/topic): shared
# by every serving engine so the fan-out tuning cannot drift between
# the in-process MatchService, the exhook sidecar, and bench.py.
# Round-5 10M measurement: at mult 6 / K=32 the fan-out tail spilled
# 11-12% of topics to ~60 us host re-runs; mult 8 / K=128 keeps the
# tail on device (spills 186k -> 84 per window, serving p99 353 ->
# 133 ms) for ~33% more readback bytes.
SERVE_FLAT_MULT = 8


#: ``row_meta`` packing: low 16 bits = per-row flat-buffer entry count
#: (min(n, K)); bit 16 = the row's fail-open flag (active-set OR match
#: overflow).  One (B,) vector carries everything the host needs to
#: split the flat id buffer into rows.
ROW_META_COUNT_MASK = 0xFFFF
ROW_META_SPILL_SHIFT = 16


def decode_row_meta(meta: np.ndarray):
    """(B,) packed row_meta → (per-row flat entry counts, spilled rows
    bool)."""
    return (meta & ROW_META_COUNT_MASK), (meta >> ROW_META_SPILL_SHIFT) > 0


def decode_packed(packed, n: int, k: int):
    """The ONE host decode of a served batch's answer: ``(rows, spilled
    row indices)`` of its first ``n`` rows.

    The served format, owned by this module: one ``(B + flat_cap,)``
    int32 array, ``flat_cap`` = ``SERVE_FLAT_MULT``·B — the (B,)
    ``row_meta`` vector, then the flat ids, each row's ``min(n, K)``
    back to back in row order (-1 behind the last).  :func:`packed_twin`
    builds it on the device; ``DeviceNfa.serve`` is the one way to ask
    for it; this function fetches it (ONE device buffer) and splits it.
    The mesh step's routed answer is the same array a ``dp`` group
    (:func:`packed_answer`), split here block by block.
    Spilled rows carry truncated segments: callers re-run those on the
    host trie (fail-open)."""
    packed = jax.device_get(packed)
    B = packed.size // (1 + SERVE_FLAT_MULT)
    nk, sp = decode_row_meta(packed[:B])
    rows = [seg.tolist() for seg in decode_flat(packed[B:], nk, k)[:n]]
    return rows, np.flatnonzero(sp[:n]).tolist()


class MatchResult(NamedTuple):
    matches: jax.Array     # (B, K) int32 accept ids, valids first, -1 pad
                           # flat mode: (flat_cap,) globally compacted ids
    n_matches: jax.Array   # (B,) int32 exact count (may exceed K)
    active_overflow: jax.Array  # (B,) int32 — per-row active-set spills
    match_overflow: jax.Array   # (B,) int32 — 1 where count > K (flat
                           # mode: also rows truncated by the global cap)
    # flat mode only: packed per-row metadata (see decode_row_meta);
    # None otherwise
    row_meta: Optional[jax.Array] = None

    def spilled_rows(self):
        """Bool (B,) — rows whose answer may be truncated (fail-open set)."""
        return (self.active_overflow > 0) | (self.match_overflow > 0)


def decode_flat(matches: np.ndarray, n_matches: np.ndarray,
                max_matches: int) -> List[np.ndarray]:
    """Split a flat-mode ``matches`` buffer into per-row id arrays.

    Rows flagged by ``spilled_rows()`` carry truncated segments — callers
    re-run those on the host (fail-open), same as compact mode.
    """
    nk = np.minimum(n_matches, max_matches)
    offs = np.cumsum(nk) - nk
    return [matches[o:o + c] for o, c in zip(offs, nk)]


def _bucket_hash(state: jax.Array, word: jax.Array, seed: jax.Array, mask: int):
    """Device twin of compiler._bucket_hash — identical uint32 mixing."""
    h = (
        state.astype(jnp.uint32) * jnp.uint32(2654435761)
        + word.astype(jnp.uint32) * jnp.uint32(2246822519)
        + seed.astype(jnp.uint32)
    )
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(3266489917)
    h = h ^ (h >> jnp.uint32(13))
    return (h & jnp.uint32(mask)).astype(jnp.int32)


def _edge_lookup(state, word, edge_tab, seeds):
    """Literal-edge lookup for (B, A) (state, word): 2 wide row-gathers.

    Each gathered row holds BUCKET_SLOTS slots of [state, word, next, 0];
    at most one slot matches (keys are unique), so a max-reduce extracts
    the hit (-1 elsewhere)."""
    Hb = edge_tab.shape[0]
    mask = Hb - 1
    B, A = state.shape
    hits = []
    for k in range(2):
        b = _bucket_hash(state, word, seeds[k], mask)      # (B, A)
        rows = edge_tab[b].reshape(B, A, BUCKET_SLOTS, 4)  # wide gather
        hit = (rows[..., 0] == state[..., None]) & (
            rows[..., 1] == word[..., None]
        )
        hits.append(jnp.max(jnp.where(hit, rows[..., 2], -1), axis=-1))
    return jnp.maximum(hits[0], hits[1])                   # (B, A)


def _compact(cand: jax.Array, width: int) -> jax.Array:
    """Valids-first compaction of (B, C) → (B, width) via cumsum +
    compare-scatter — no sort.  Any valids beyond ``width`` are dropped
    (the caller counts them as spill)."""
    valid = cand >= 0
    pos = jnp.cumsum(valid.astype(jnp.int32), axis=1) - 1
    pos = jnp.where(valid, pos, width)
    onehot = pos[..., None] == jnp.arange(width)[None, None, :]
    return jnp.max(jnp.where(onehot, cand[..., None], -1), axis=1)


def flat_scatter(rows, nk, flat_cap: int):
    """The served format's id half, on the device: each row's first
    ``nk`` entries of ``rows`` (B, w), valids first, laid back to back
    in row order by a GLOBAL cumsum offset into one ``(flat_cap,)``
    buffer, -1 behind the last.  Returns ``(flat, offs)``: a row with
    ``offs + nk > flat_cap`` ran past the cap and was cut (fail-open)."""
    offs = jnp.cumsum(nk) - nk                         # (B,)
    col = jnp.arange(rows.shape[1], dtype=jnp.int32)[None, :]
    valid = col < nk[:, None]
    idx = jnp.where(valid, offs[:, None] + col, flat_cap)
    out = jnp.full((flat_cap,), -1, jnp.int32)
    flat = out.at[idx.reshape(-1)].set(
        rows.reshape(-1), mode="drop")                 # OOB dropped
    return flat, offs


def packed_answer(rows, nk, spill, flat_cap: int):
    """A WHOLE served answer built on the device from rows another
    program compacted (the mesh step's collapsed owner segments): the
    ``(B + flat_cap,)`` array :func:`decode_packed` reads.  ``spill``
    (B,) bool is the caller's fail-open set; a row cut by the cap joins
    it."""
    flat, offs = flat_scatter(rows, nk, flat_cap)
    spilled = (spill | (offs + nk > flat_cap)).astype(jnp.int32)
    return jnp.concatenate([nk | (spilled << ROW_META_SPILL_SHIFT), flat])


def flat_epilogue(flat, n, aover, max_matches: int, flat_cap: int):
    """The fused on-device compaction epilogue for flat serving mode:
    per-row top-K compaction, a GLOBAL cumsum-offset scatter into one
    ``(flat_cap,)`` buffer, and the packed ``row_meta`` vector — the
    dense (row, accept-id) list is produced entirely on device.  Shared
    by :func:`nfa_match` and the pallas walk
    (:func:`~emqx_tpu.ops.pallas_match.pallas_small_match_flat`) so
    both backends honor one readback contract.  Returns ``(matches,
    mover, row_meta)``."""
    K = max_matches
    per_row = _compact(flat, K)                        # (B, K)
    nk = jnp.minimum(n, K)
    matches, offs = flat_scatter(per_row, nk, flat_cap)
    # truncated rows: count exceeded K, or the segment ran past the
    # global cap — both land in the fail-open set
    mover = ((n > K) | (offs + nk > flat_cap)).astype(jnp.int32)
    spilled = ((aover > 0) | (mover > 0)).astype(jnp.int32)
    row_meta = nk | (spilled << ROW_META_SPILL_SHIFT)
    return matches, mover, row_meta


def nfa_walk(
    words,        # (B, D) int32
    lens,         # (B,) int32
    is_sys,       # (B,) bool
    node_tab,     # (S, 4) int32: [plus_child, hash_accept, accept, 0]
    edge_lookup,  # (state (B,w), word (B,w)) -> next (B,w), -1 on miss
    *,
    active_slots: int = 16,
    max_matches: int = 32,
    compact_output: bool = True,
    flat_cap: int = 0,
) -> MatchResult:
    """The backend-agnostic level walk: accepts, ``+`` transitions and
    the epilogue are identical for every edge-structure backend — only
    the literal-edge lookup is pluggable (the cuckoo hash probe here,
    the sorted-relation ``searchsorted`` join step in
    :mod:`~emqx_tpu.ops.join_match`), so hint/match parity between
    backends is structural, not re-implemented."""
    B, D = words.shape
    A = active_slots
    K = max_matches

    # Per-step active width: a trie has at most 2^t nodes at depth t
    # reachable from the root under one topic (each state forks into at
    # most literal+plus children), so early steps run narrow — step 0 is
    # a single column.  This cuts gather traffic by ~40% at D=8, A=8 and
    # removes the compaction entirely until 2·width exceeds the cap
    # (measured 1.6× end-to-end vs the fixed-width round-2 kernel).
    active = jnp.zeros((B, 1), jnp.int32)                  # {root}
    accept_cols = []
    spills = []
    # Phases carry ``jax.named_scope`` names (metadata only: the compiled
    # program is the same) so that a profiler trace's device ops can be
    # summed by phase: ``nfa.level<t>`` with ``node_gather``,
    # ``edge_lookup`` and ``topk`` inside it, then ``nfa.epilogue``.
    for t in range(D + 1):
        with jax.named_scope(f"nfa.level{t}"):
            valid = active >= 0
            sa = jnp.maximum(active, 0)        # safe gather index
            with jax.named_scope("node_gather"):
                node = node_tab[sa]            # (B, w_t, 4) wide gather
            plus_child = node[..., 0]
            hash_accept = node[..., 1]
            end_accept = node[..., 2]

            # --- fire accepts ---------------------------------------------
            hacc = jnp.where(valid, hash_accept, -1)
            if t == 0:
                # root-level wildcard suppression for $-topics
                # (active == {root})
                hacc = jnp.where(is_sys[:, None], -1, hacc)
            at_end = (t == lens)[:, None]
            eacc = jnp.where(valid & at_end, end_accept, -1)
            accept_cols.append(jnp.concatenate([hacc, eacc], axis=1))

            if t == D:
                break

            # --- transition -----------------------------------------------
            w = jnp.broadcast_to(words[:, t][:, None], active.shape)
            with jax.named_scope("edge_lookup"):
                lit = edge_lookup(active, w)
            lit = jnp.where(valid, lit, -1)
            plus = jnp.where(valid, plus_child, -1)
            if t == 0:
                plus = jnp.where(is_sys[:, None], -1, plus)
            cand = jnp.concatenate([lit, plus], axis=1)    # (B, 2·w_t)
            cand = jnp.where((t < lens)[:, None], cand, -1)
            w_next = min(cand.shape[1], A)
            if cand.shape[1] <= A:
                active = cand              # lossless: no compaction needed
            else:
                with jax.named_scope("topk"):
                    active, _ = jax.lax.top_k(cand, w_next)  # valids first
                n_cand = jnp.sum((cand >= 0).astype(jnp.int32), axis=1)
                n_kept = jnp.sum((active >= 0).astype(jnp.int32), axis=1)
                spills.append(n_cand - n_kept)             # (B,) per row

    with jax.named_scope("nfa.epilogue"):
        flat = jnp.concatenate(accept_cols, axis=1)        # (B, Σ 2·w_t)
        n = jnp.sum((flat >= 0).astype(jnp.int32), axis=1)
        aover = (
            jnp.sum(jnp.stack(spills), axis=0) if spills
            else jnp.zeros((B,), jnp.int32)
        )
        row_meta = None
        if flat_cap:
            # flat mode: the fused compaction epilogue, so that the
            # served answer is one small array (decode_packed)
            matches, mover, row_meta = flat_epilogue(
                flat, n, aover, K, flat_cap)
        elif compact_output:
            matches = _compact(flat, K)                    # valids first
            mover = (n > K).astype(jnp.int32)
        else:
            # raw mode: all Σ2·w_t accept slots, valids scattered (-1
            # holes).  Structurally nothing truncates (the walk cannot
            # fire more accepts than it has slots), so only active-set
            # spill remains a fail-open cause — the right mode for
            # high-fan-out tables where a fixed K would overflow (hosts
            # mask row >= 0 to decode).
            matches = flat
            mover = jnp.zeros((B,), jnp.int32)
    return MatchResult(
        matches=matches,
        n_matches=n,
        active_overflow=aover,
        match_overflow=mover,
        row_meta=row_meta,
    )


def _nfa_match(
    words,        # (B, D) int32
    lens,         # (B,) int32
    is_sys,       # (B,) bool
    node_tab,     # (S, 4) int32: [plus_child, hash_accept, accept, 0]
    edge_tab,     # (Hb, BUCKET_SLOTS*4) int32 cuckoo buckets
    seeds,        # (2,) int32
    *,
    active_slots: int = 16,
    max_matches: int = 32,
    compact_output: bool = True,
    flat_cap: int = 0,
) -> MatchResult:
    return nfa_walk(
        words, lens, is_sys, node_tab,
        lambda st, w: _edge_lookup(st, w, edge_tab, seeds),
        active_slots=active_slots, max_matches=max_matches,
        compact_output=compact_output, flat_cap=flat_cap,
    )


_MATCH_STATIC = ("active_slots", "max_matches", "compact_output",
                 "flat_cap")

#: the reference entry point (``MatchResult``) — one compilation per
#: shape bucket
nfa_match = jax.jit(_nfa_match, static_argnames=_MATCH_STATIC)


def packed_twin(match):
    """The SERVED twin of a flat-mode match function: its WHOLE answer
    is one ``(B + flat_cap,)`` int32 array, ``row_meta`` then the flat
    ids (the format :func:`decode_packed` reads).  One output, not five:
    on the attached chip every buffer a call takes or returns costs
    0.06–0.09 ms of its dispatch (the runtime allocates each) and a
    little of its fetch, whatever its size (PERF.md §6, PR 31).  The
    other fields are dead code to that program; XLA drops them."""
    def packed(*operands, **static):
        res = match(*operands, **static)
        return jnp.concatenate([res.row_meta, res.matches])
    # the XLA module is named after the function: a trace still finds
    # the twin among the "nfa_match" / "join_match" programs
    packed.__name__ = packed.__qualname__ = match.__name__ + "_packed"
    return packed


#: the served hash program (flat mode only: ``flat_cap`` > 0)
nfa_match_packed = jax.jit(packed_twin(_nfa_match),
                           static_argnames=_MATCH_STATIC)


def build_matcher(active_slots: int = 16, max_matches: int = 32):
    """Bind the static kernel knobs; returned fn takes (words, lens,
    is_sys, *table.device_arrays())."""

    def match(words, lens, is_sys, node_tab, edge_tab, seeds):
        return nfa_match(
            words, lens, is_sys, node_tab, edge_tab, seeds,
            active_slots=active_slots, max_matches=max_matches,
        )

    return match


def match_topics(
    table: NfaTable,
    names: Sequence[str],
    active_slots: int = 16,
    max_matches: int = 32,
) -> List[List[str]]:
    """Convenience end-to-end: encode → kernel → decode to filter strings.

    Raises if the active set overflowed (callers wanting fail-open handle
    MatchResult directly)."""
    words, lens, is_sys = encode_topics(table, names)
    res = nfa_match(
        jnp.asarray(words), jnp.asarray(lens), jnp.asarray(is_sys),
        *[jnp.asarray(a) for a in table.device_arrays()],
        active_slots=active_slots, max_matches=max_matches,
    )
    if int(jnp.sum(res.active_overflow)) or int(jnp.sum(res.match_overflow)):
        raise OverflowError(
            f"match overflow: active={int(jnp.sum(res.active_overflow))} "
            f"rows>{max_matches}={int(jnp.sum(res.match_overflow))}"
        )
    matches = np.asarray(res.matches)
    counts = np.asarray(res.n_matches)
    out: List[List[str]] = []
    for r in range(len(names)):
        out.append([table.accept_filters[a] for a in matches[r, : counts[r]]])
    return out
