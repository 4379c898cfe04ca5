"""Config-5 device stage: ring-tiled accept-bitmap OR-reduction.

When the accept→subscriber bitmap outgrows one chip's HBM (BASELINE
config 5: 100k retained × 1M wildcard subs ⇒ multi-GB of bitmap rows),
its ROWS (accept ids) shard over a ``ring`` mesh axis.  Every shard OR-
assembles the contribution of the accept ids it owns, then partial
per-topic bitmaps rotate around the ring with ``ppermute`` accumulating
bitwise-OR — the ring-attention blockwise schedule with OR in place of
softmax-weighted sums (SURVEY.md §2.5 "Ring/blockwise bitmap tiles",
§5.7).  After ``ring-1`` hops every shard holds the full reduction, so
the result leaves the mesh dp-sharded and ring-replicated with no
all-gather.

Comms cost per batch: (ring-1) hops × (B/dp × W) words over ICI —
bandwidth-optimal for a reduction whose operand never fits one chip.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["build_ring_fanout", "build_ring_fanout_compact",
           "shard_bitmap_rows"]


def shard_bitmap_rows(bitmap: np.ndarray, ring: int) -> np.ndarray:
    """Pad the (F+1, W) accept bitmap so ``ring`` divides the row count
    (pad rows are all-zero ⇒ OR-inert).  The LAST row must stay the
    all-zero invalid-slot row within its shard — instead of relying on
    position we simply require callers to index invalid slots to the
    global padded last row, which is zero by construction."""
    rows, w = bitmap.shape
    pad = (-rows) % ring
    if pad:
        bitmap = np.concatenate(
            [bitmap, np.zeros((pad, w), bitmap.dtype)], axis=0
        )
    return bitmap


def build_ring_fanout(mesh: Mesh, active_slots: int = 16,
                      max_matches: int = 32):
    """Returns jitted ``step(words, lens, is_sys, node, edge, seeds,
    bitmap_rows) -> (B, W) uint32`` with:

    * batch arrays sharded ``(dp,)`` and replicated over ``ring``;
    * NFA arrays replicated (the match runs identically on every ring
      shard — cheaper than broadcasting matches, and the tables are the
      small operand in config 5);
    * ``bitmap_rows`` (F_pad, W) sharded ``(ring, None)`` — the operand
      that doesn't fit one chip.
    """
    from ..ops.match_kernel import nfa_match

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P("dp", None), P("dp"), P("dp"),
            P(), P(), P(),
            P("ring", None),
        ),
        out_specs=P("dp", None),
        check_vma=False,
    )
    def step(words, lens, is_sys, node_tab, edge_tab, seeds, rows_local):
        res = nfa_match(
            words, lens, is_sys, node_tab, edge_tab, seeds,
            active_slots=active_slots, max_matches=max_matches,
        )
        ring_idx = jax.lax.axis_index("ring")
        f_local = rows_local.shape[0]
        lo = ring_idx * f_local
        m = res.matches                                    # (Bl, K) global aids
        local = m - lo
        valid = (m >= 0) & (local >= 0) & (local < f_local)
        safe = jnp.where(valid, local, 0)
        gathered = rows_local[safe]                        # (Bl, K, W)
        gathered = jnp.where(valid[:, :, None], gathered, jnp.uint32(0))
        partial_or = jax.lax.reduce(
            gathered, np.uint32(0), jax.lax.bitwise_or, (1,)
        )                                                  # (Bl, W)

        # ring accumulate: rotate partials, OR as they come around
        nring = mesh.shape["ring"]
        perm = [(j, (j + 1) % nring) for j in range(nring)]
        acc = partial_or
        chunk = partial_or
        for _ in range(nring - 1):
            chunk = jax.lax.ppermute(chunk, "ring", perm)
            acc = acc | chunk
        return acc

    return jax.jit(step)


def build_ring_fanout_compact(mesh: Mesh, cap_row: int = 64,
                              active_slots: int = 16,
                              max_matches: int = 32):
    """Dense-id ring: same contract as :func:`build_ring_fanout`
    (returns the fully-reduced ``(B, W) uint32`` bitmap, plus a
    ``(B,) int32`` truncation flag), but what ROTATES on the ring is
    each shard's compacted per-topic subscriber-id list — (Bl, cap_row)
    ints per hop instead of the (Bl, W) bitmap tile, so ICI traffic is
    proportional to matches, not table width (W words/topic at config-5
    scale vs tens of matches).  Each hop scatters the incoming dense
    ids back into the local accumulator bitmap (scatter-add into a
    zero tile, then OR — ids are unique within a row, so add ≡ OR),
    which also dedups subscribers reached via filters owned by
    different ring shards.  A row whose LOCAL partial popcount exceeds
    ``cap_row`` is flagged truncated (psum over the ring) — the
    fail-open set callers re-run on the host."""
    from ..ops.match_kernel import nfa_match
    from .sharded_match import compact_bitmap_ids

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P("dp", None), P("dp"), P("dp"),
            P(), P(), P(),
            P("ring", None),
        ),
        out_specs=(P("dp", None), P("dp")),
        check_vma=False,
    )
    def step(words, lens, is_sys, node_tab, edge_tab, seeds, rows_local):
        res = nfa_match(
            words, lens, is_sys, node_tab, edge_tab, seeds,
            active_slots=active_slots, max_matches=max_matches,
        )
        ring_idx = jax.lax.axis_index("ring")
        f_local = rows_local.shape[0]
        lo = ring_idx * f_local
        m = res.matches
        local = m - lo
        valid = (m >= 0) & (local >= 0) & (local < f_local)
        safe = jnp.where(valid, local, 0)
        gathered = rows_local[safe]
        gathered = jnp.where(valid[:, :, None], gathered, jnp.uint32(0))
        partial_or = jax.lax.reduce(
            gathered, np.uint32(0), jax.lax.bitwise_or, (1,)
        )                                                  # (Bl, W)
        Bl, W = partial_or.shape
        ids, n, over = compact_bitmap_ids(partial_or, cap_row)

        def bits_of(chunk_ids):
            """Dense (Bl, cap_row) ids → (Bl, W) bitmap tile: scatter
            1<<bit into a zero tile (unique bits per row ⇒ add ≡ OR);
            -1 pads drop via an out-of-bounds word index."""
            ok = chunk_ids >= 0
            word = jnp.where(ok, chunk_ids >> 5, W)
            bit = jnp.where(
                ok,
                jnp.uint32(1) << (chunk_ids & 31).astype(jnp.uint32),
                jnp.uint32(0))
            rows = jnp.broadcast_to(
                jnp.arange(Bl)[:, None], chunk_ids.shape)
            z = jnp.zeros((Bl, W), jnp.uint32)
            return z.at[rows, word].add(bit, mode="drop")

        # ring accumulate: rotate the DENSE id lists, re-expand each
        # incoming chunk into the local accumulator
        nring = mesh.shape["ring"]
        perm = [(j, (j + 1) % nring) for j in range(nring)]
        acc = partial_or
        chunk = ids
        for _ in range(nring - 1):
            chunk = jax.lax.ppermute(chunk, "ring", perm)
            acc = acc | bits_of(chunk)
        truncated = jax.lax.psum(over, "ring")
        return acc, truncated

    return jax.jit(step)
