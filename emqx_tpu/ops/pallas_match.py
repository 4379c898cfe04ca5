"""Pallas fast path for SMALL (VMEM-resident) match tables — the
SURVEY.md §7.4 "pallas kernel for the hot op" experiment, with the
honest applicability analysis.

**Where pallas can win here.**  The shipping ``nfa_match`` is
HBM-random-gather bound at scale (the edge+node gathers dominate
kernel time; the table has ~1.0 literal edges per state, so the
2-choice cuckoo probe is already byte-minimal).  XLA's native gather is
the right tool for those HBM-scale lookups: a pallas kernel would have
to issue one DMA per probed bucket (B·A·2 small DMAs per step — DMA issue overhead alone
exceeds the gather cost), so pallas is NOT attempted for the 1M–10M
filter regime.

For tables that FIT IN VMEM (≲100k edges ≈ 6.4 MB edge table + node
table), the calculus inverts: the whole 8-step walk can run in ONE
kernel with every probe hitting VMEM — no per-step HBM round trips, no
intermediate materialization.  That is this module: a fused
walk-and-match kernel for the small/medium broker (≤~50k wildcard
filters), grid over batch tiles, tables broadcast to every tile.

**Status.**  Parity-tested against ``nfa_match`` in interpret mode (the
CPU-mesh suite) and nowhere else: the v5e compiler (jax 0.9.0 / libtpu
0.0.34) REFUSES both kernels at lowering, at a table inside their own
VMEM budget, with ``ValueError: Shape mismatch in input, indices and
output`` — the vectorized in-VMEM table gathers (``node_tab[sa]``,
``edge_tab[b]``, ``state_start[sa]``).  tests/test_chip_compile.py pins
that verdict as strict xfails, so a repair announces itself.  Until
then nothing routes here on a chip: callers serve ``nfa_match`` /
``join_match`` (same table layout — the fallback is a function swap).
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .compiler import BUCKET_SLOTS

__all__ = ["pallas_small_match", "pallas_small_match_flat",
           "pallas_join_match", "pallas_join_match_flat",
           "pallas_join_match_packed",
           "supports_table", "supports_join_table",
           "bench_pallas_small"]

VMEM_BUDGET_BYTES = 8 << 20   # tables beyond this stay on nfa_match
TILE_B = 256                  # batch rows per grid step


def supports_table(node_tab: np.ndarray, edge_tab: np.ndarray) -> bool:
    return (node_tab.nbytes + edge_tab.nbytes) <= VMEM_BUDGET_BYTES


def supports_join_table(node_tab, state_start, edge_word,
                        edge_next, overlay) -> bool:
    """VMEM fit check for the join-relation walk: node table + CSR
    offsets + both relation columns + the overlay must co-reside."""
    total = sum(int(np.asarray(a).nbytes)
                for a in (node_tab, state_start, edge_word, edge_next,
                          overlay))
    return total <= VMEM_BUDGET_BYTES


def _hash(state, word, seed, mask):
    h = (state.astype(jnp.uint32) * jnp.uint32(2654435761)
         + word.astype(jnp.uint32) * jnp.uint32(2246822519)
         + seed.astype(jnp.uint32))
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(3266489917)
    h = h ^ (h >> jnp.uint32(13))
    return (h & jnp.uint32(mask)).astype(jnp.int32)


def _walk_tile(words, lens, is_sys, node_tab, lit_lookup,
               acc_ref, aover_ref, *, depth: int, active_slots: int):
    """One batch tile: the full D-step walk with VMEM-resident tables,
    the literal-edge lookup pluggable (the ``nfa_walk`` factoring).

    Mirrors ``nfa_match`` exactly (same per-step widths, same accept
    slot layout) so parity is bit-for-bit and callers can decode with
    the same host code."""
    B = words.shape[0]
    A = active_slots

    active = jnp.zeros((B, 1), jnp.int32)
    aover = jnp.zeros((B,), jnp.int32)
    col = 0
    for t in range(depth + 1):
        valid = active >= 0
        sa = jnp.maximum(active, 0)
        node = node_tab[sa]                  # (B, w, 4) VMEM gather
        hacc = jnp.where(valid, node[..., 1], -1)
        if t == 0:
            hacc = jnp.where(is_sys[:, None], -1, hacc)
        eacc = jnp.where(valid & (t == lens)[:, None], node[..., 2], -1)
        w_cols = hacc.shape[1]
        acc_ref[:, col:col + w_cols] = hacc
        acc_ref[:, col + w_cols:col + 2 * w_cols] = eacc
        col += 2 * w_cols
        if t == depth:
            break
        w = jnp.broadcast_to(words[:, t][:, None], active.shape)
        lit = jnp.where(valid, lit_lookup(active, w), -1)
        plus = jnp.where(valid, node[..., 0], -1)
        if t == 0:
            plus = jnp.where(is_sys[:, None], -1, plus)
        cand = jnp.concatenate([lit, plus], axis=1)
        cand = jnp.where((t < lens)[:, None], cand, -1)
        if cand.shape[1] <= A:
            active = cand
        else:
            active, _ = jax.lax.top_k(cand, A)
            n_cand = jnp.sum((cand >= 0).astype(jnp.int32), axis=1)
            n_kept = jnp.sum((active >= 0).astype(jnp.int32), axis=1)
            aover = aover + (n_cand - n_kept)
    aover_ref[...] = aover


def _kernel(words_ref, lens_ref, issys_ref, node_ref, edge_ref, seeds_ref,
            acc_ref, aover_ref, *, depth: int, active_slots: int):
    """Hash-backend tile: the cuckoo 2-choice probe as the literal
    lookup, every probe hitting VMEM."""
    edge_tab = edge_ref[...]
    seeds = seeds_ref[...]
    Hb = edge_tab.shape[0]
    mask = Hb - 1
    B = words_ref.shape[0]

    def lookup(active, w):
        hits = []
        for k in range(2):
            b = _hash(active, w, seeds[k], mask)
            rows = edge_tab[b].reshape(B, active.shape[1],
                                       BUCKET_SLOTS, 4)
            hit = (rows[..., 0] == active[..., None]) & (
                rows[..., 1] == w[..., None])
            hits.append(jnp.max(jnp.where(hit, rows[..., 2], -1),
                                axis=-1))
        return jnp.maximum(hits[0], hits[1])

    _walk_tile(words_ref[...], lens_ref[...], issys_ref[...],
               node_ref[...], lookup, acc_ref, aover_ref,
               depth=depth, active_slots=active_slots)


def _join_kernel(words_ref, lens_ref, issys_ref, node_ref, start_ref,
                 eword_ref, enext_ref, overlay_ref, acc_ref, aover_ref,
                 *, depth: int, active_slots: int):
    """Join-backend tile: the whole sorted-relation lower-bound walk
    (``ops/join_match._join_edge_lookup`` ported verbatim — CSR
    segment bounds + unrolled binary search, then the sorted-overlay
    lower bound) runs on-chip, so the seed-free join backend composes
    with the VMEM walk end-to-end — no per-step HBM round trips, no
    host bounce for the search steps."""
    state_start = start_ref[...]
    edge_word = eword_ref[...]
    edge_next = enext_ref[...]
    overlay = overlay_ref[...]
    E = int(edge_word.shape[0])
    steps = max(1, E.bit_length())          # ceil(log2(E)) + 1 margin
    o_state = overlay[:, 0]
    o_word = overlay[:, 1]
    o_next = overlay[:, 2]
    cap = int(o_state.shape[0])
    osteps = max(1, cap.bit_length())

    def lookup(active, word):
        sa = jnp.maximum(active, 0)          # safe gather index
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
        # sorted overlay: lexicographic (state, word) lower bound
        olo = jnp.zeros_like(active)
        ohi = jnp.full_like(active, cap)
        for _ in range(osteps):
            act = olo < ohi
            mid = (olo + ohi) >> 1
            midc = jnp.clip(mid, 0, cap - 1)
            ms = o_state[midc]
            mw = o_word[midc]
            right = act & ((ms < active) | ((ms == active) & (mw < word)))
            olo = jnp.where(right, mid + 1, olo)
            ohi = jnp.where(act & ~right, mid, ohi)
        opos = jnp.clip(olo, 0, cap - 1)
        ohit = ((olo < cap) & (o_state[opos] == active)
                & (o_word[opos] == word))
        nxt_o = jnp.where(ohit, o_next[opos], -1)
        return jnp.maximum(nxt, nxt_o)

    _walk_tile(words_ref[...], lens_ref[...], issys_ref[...],
               node_ref[...], lookup, acc_ref, aover_ref,
               depth=depth, active_slots=active_slots)


def _accept_cols(depth: int, active_slots: int) -> int:
    cols = 0
    w = 1
    for t in range(depth + 1):
        cols += 2 * w
        w = min(2 * w, active_slots)
    return cols


@partial(jax.jit, static_argnames=("depth", "active_slots", "interpret"))
def pallas_small_match(words, lens, is_sys, node_tab, edge_tab, seeds,
                       *, depth: int, active_slots: int = 8,
                       interpret: bool = False) -> Tuple[jax.Array,
                                                         jax.Array]:
    """-> (raw accept slots (B, C), active_overflow (B,)) — the same
    raw-mode layout as ``nfa_match(compact_output=False)``; reuse its
    host decode / XLA compaction."""
    from jax.experimental import pallas as pl

    B, D = words.shape
    assert D == depth, (D, depth)
    if B % TILE_B:
        raise ValueError(f"batch {B} must be a multiple of {TILE_B}")
    C = _accept_cols(depth, active_slots)
    kernel = partial(_kernel, depth=depth, active_slots=active_slots)
    grid = (B // TILE_B,)
    acc, aover = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((B, C), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE_B, D), lambda i: (i, 0)),
            pl.BlockSpec((TILE_B,), lambda i: (i,)),
            pl.BlockSpec((TILE_B,), lambda i: (i,)),
            pl.BlockSpec(node_tab.shape, lambda i: (0, 0)),
            pl.BlockSpec(edge_tab.shape, lambda i: (0, 0)),
            pl.BlockSpec(seeds.shape, lambda i: (0,)),
        ],
        out_specs=(
            pl.BlockSpec((TILE_B, C), lambda i: (i, 0)),
            pl.BlockSpec((TILE_B,), lambda i: (i,)),
        ),
        interpret=interpret,
    )(words, lens, is_sys, node_tab, edge_tab, seeds)
    return acc, aover


@partial(jax.jit, static_argnames=("depth", "active_slots",
                                   "max_matches", "flat_cap",
                                   "interpret"))
def pallas_small_match_flat(words, lens, is_sys, node_tab, edge_tab,
                            seeds, *, depth: int, active_slots: int = 8,
                            max_matches: int = 32, flat_cap: int,
                            interpret: bool = False):
    """Pallas walk + the SHARED flat compaction epilogue
    (:func:`~emqx_tpu.ops.match_kernel.flat_epilogue`): the dense
    (row, accept-id) list and the packed ``row_meta`` vector are
    produced on device, so one readback contract holds for both
    kernel backends — the VMEM walk fuses straight into the
    cumsum-offset scatter under one jit.
    Returns the same :class:`~emqx_tpu.ops.match_kernel.MatchResult`
    layout as ``nfa_match(flat_cap=...)``."""
    from .match_kernel import MatchResult, flat_epilogue

    acc, aover = pallas_small_match(
        words, lens, is_sys, node_tab, edge_tab, seeds, depth=depth,
        active_slots=active_slots, interpret=interpret)
    n = jnp.sum((acc >= 0).astype(jnp.int32), axis=1)
    matches, mover, row_meta = flat_epilogue(
        acc, n, aover, max_matches, flat_cap)
    return MatchResult(matches=matches, n_matches=n,
                       active_overflow=aover, match_overflow=mover,
                       row_meta=row_meta)


@partial(jax.jit, static_argnames=("depth", "active_slots", "interpret"))
def pallas_join_match(words, lens, is_sys, node_tab, state_start,
                      edge_word, edge_next, overlay, *, depth: int,
                      active_slots: int = 8,
                      interpret: bool = False) -> Tuple[jax.Array,
                                                        jax.Array]:
    """Join-relation twin of :func:`pallas_small_match`: the unrolled
    lower-bound walk (``join-pallas`` backend) over VMEM-resident CSR
    relation arrays.  -> (raw accept slots (B, C), active_overflow
    (B,)) — the same raw-mode layout as ``nfa_match
    (compact_output=False)``.  Tiles adapt down to the batch (pow2
    serve buckets below ``TILE_B`` run as one tile), so the warm
    shapes (B=64) compile without padding."""
    from jax.experimental import pallas as pl

    B, D = words.shape
    assert D == depth, (D, depth)
    tile = min(TILE_B, B)
    if B % tile:
        raise ValueError(f"batch {B} must be a multiple of {tile}")
    C = _accept_cols(depth, active_slots)
    kernel = partial(_join_kernel, depth=depth,
                     active_slots=active_slots)
    grid = (B // tile,)
    acc, aover = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((B, C), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile, D), lambda i: (i, 0)),
            pl.BlockSpec((tile,), lambda i: (i,)),
            pl.BlockSpec((tile,), lambda i: (i,)),
            pl.BlockSpec(node_tab.shape, lambda i: (0, 0)),
            pl.BlockSpec(state_start.shape, lambda i: (0,)),
            pl.BlockSpec(edge_word.shape, lambda i: (0,)),
            pl.BlockSpec(edge_next.shape, lambda i: (0,)),
            pl.BlockSpec(overlay.shape, lambda i: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((tile, C), lambda i: (i, 0)),
            pl.BlockSpec((tile,), lambda i: (i,)),
        ),
        interpret=interpret,
    )(words, lens, is_sys, node_tab, state_start, edge_word,
      edge_next, overlay)
    return acc, aover


_JOIN_FLAT_STATIC = ("depth", "active_slots", "max_matches", "flat_cap",
                     "interpret")


def _pallas_join_match_flat(words, lens, is_sys, node_tab, state_start,
                            edge_word, edge_next, overlay, *,
                            depth: int, active_slots: int = 8,
                            max_matches: int = 32, flat_cap: int,
                            interpret: bool = False):
    from .match_kernel import MatchResult, flat_epilogue

    acc, aover = pallas_join_match(
        words, lens, is_sys, node_tab, state_start, edge_word,
        edge_next, overlay, depth=depth, active_slots=active_slots,
        interpret=interpret)
    n = jnp.sum((acc >= 0).astype(jnp.int32), axis=1)
    matches, mover, row_meta = flat_epilogue(
        acc, n, aover, max_matches, flat_cap)
    return MatchResult(matches=matches, n_matches=n,
                       active_overflow=aover, match_overflow=mover,
                       row_meta=row_meta)


#: Pallas join walk + the SHARED flat compaction epilogue — the same
#: readback contract as ``nfa_match(flat_cap=...)`` / ``join_match``,
#: so the host decode is backend-agnostic.
pallas_join_match_flat = jax.jit(
    _pallas_join_match_flat, static_argnames=_JOIN_FLAT_STATIC)


def _pallas_join_match_packed(*operands, **static):
    r = _pallas_join_match_flat(*operands, **static)
    return jnp.concatenate([r.row_meta, r.matches])


#: The same walk with the SERVED answer as its one output: ``row_meta``
#: then the flat ids (``match_kernel.decode_packed``).
pallas_join_match_packed = jax.jit(
    _pallas_join_match_packed, static_argnames=_JOIN_FLAT_STATIC)


def bench_pallas_small(n_filters: int = 50_000, batch: int = 8192,
                       iters: int = 20, depth: int = 8) -> dict:
    """Real-chip A/B: fused pallas walk vs nfa_match on a VMEM-sized
    table.  Mosaic refuses the walk today (module docstring), so on a
    chip this records ``pallas_error`` beside the XLA time."""
    import time

    from .compiler import compile_filters, encode_topics
    from .match_kernel import nfa_match

    rng = np.random.default_rng(3)
    filters = [f"s/{rng.integers(1000)}/+/d{i % 97}/#"[: 64]
               for i in range(n_filters)]
    table = compile_filters(sorted(set(filters)), depth=depth)
    topics = [f"s/{rng.integers(1000)}/x/d{i % 97}/leaf"
              for i in range(batch)]
    words, lens, is_sys = encode_topics(table, topics, batch=batch)
    args = (jnp.asarray(words), jnp.asarray(lens), jnp.asarray(is_sys),
            *[jnp.asarray(a) for a in table.device_arrays()])
    out = {"n_states": table.n_states,
           "table_bytes": int(sum(a.nbytes for a in
                                  table.device_arrays()[:2]))}
    r = nfa_match(*args, active_slots=8, compact_output=False)
    np.asarray(r.matches)
    t0 = time.perf_counter()
    for _ in range(iters):
        r = nfa_match(*args, active_slots=8, compact_output=False)
    np.asarray(r.matches)
    out["xla_ms_per_batch"] = round(
        (time.perf_counter() - t0) / iters * 1e3, 2)
    try:
        acc, aover = pallas_small_match(
            *args, depth=depth, active_slots=8)
        np.asarray(acc)
        t0 = time.perf_counter()
        for _ in range(iters):
            acc, aover = pallas_small_match(
                *args, depth=depth, active_slots=8)
        np.asarray(acc)
        out["pallas_ms_per_batch"] = round(
            (time.perf_counter() - t0) / iters * 1e3, 2)
    except Exception as e:  # noqa: BLE001 — record the lowering verdict
        out["pallas_error"] = f"{type(e).__name__}: {e}"[:500]
    return out


if __name__ == "__main__":
    print(bench_pallas_small())
