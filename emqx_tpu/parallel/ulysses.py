"""Ulysses-style ingest→dispatch reshard: one ``all_to_all`` flipping
the sharded axis between the two natural layouts of the publish step.

SURVEY.md §2.5 mandates this row explicitly: the reference has no
sequence parallelism, but its per-node dispatch (`emqx_broker:dispatch`
after `gen_rpc` forwarding, SURVEY.md §3.4 [U]) is the role this
collective fills on a mesh.  The two layouts:

* **ingest layout** — the topic BATCH axis is sharded (each device
  matches B/U topics end-to-end and assembles full-width subscriber
  bitmap rows for them).  This is where publishes arrive: whichever
  device's host fed the batch owns those rows.
* **dispatch layout** — the SUBSCRIBER axis is sharded (each device
  owns a column slice of the bitmap over the WHOLE batch).  This is
  what delivery wants: a device (≙ broker node) owns a range of
  sessions and must see every message destined to them.

Ulysses in sequence-parallel attention flips seq-sharded ↔ head-sharded
with one ``all_to_all`` per layer; here the same single collective flips
batch-sharded ↔ subscriber-sharded per publish batch:

    (B/U, W) per device  --all_to_all(split cols, concat rows)-->  (B, W/U)

versus the TP fan-out in :mod:`sharded_match` (which keeps rows sharded
and psums counts), this moves each message's bits to the device that
will deliver them — the collective IS the cluster forward hop, riding
ICI instead of gen_rpc.

The inverse reshard (dispatch→ingest) carries per-subscriber delivery
outcomes (acks, inflight counts) back to the ingest owners.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.match_kernel import nfa_match
from .sharded_match import or_accept_rows

__all__ = [
    "UlyssesResult",
    "build_reshard",
    "build_unreshard",
    "build_ulysses_step",
]


class UlyssesResult(NamedTuple):
    dispatch_bitmap: jax.Array   # (B, W) — column ("u")-sharded: each
    #                              device holds its subscriber slice of
    #                              EVERY message in the batch
    sub_deliveries: jax.Array    # (W*32,) int32 — per-subscriber message
    #                              counts, sharded over "u" like the cols
    n_matches: jax.Array         # (B,) int32 — ingest ("u")-row sharded
    active_overflow: jax.Array   # (B,) int32 — fail-open rows (ingest)


def build_reshard(mesh: Mesh, axis: str = "u"):
    """Jitted ingest→dispatch reshard: rows sharded over ``axis`` in,
    columns sharded over ``axis`` out.  One tiled ``all_to_all``."""

    @partial(shard_map, mesh=mesh,
             in_specs=P(axis, None), out_specs=P(None, axis))
    def reshard(block):            # (B/U, W) local
        return jax.lax.all_to_all(
            block, axis, split_axis=1, concat_axis=0, tiled=True)

    return jax.jit(reshard)


def build_unreshard(mesh: Mesh, axis: str = "u"):
    """Inverse (dispatch→ingest): columns sharded in, rows sharded out —
    the ack/backpressure return path."""

    @partial(shard_map, mesh=mesh,
             in_specs=P(None, axis), out_specs=P(axis, None))
    def unreshard(block):          # (B, W/U) local
        return jax.lax.all_to_all(
            block, axis, split_axis=0, concat_axis=1, tiled=True)

    return jax.jit(unreshard)


def build_ulysses_step(mesh: Mesh, axis: str = "u",
                       active_slots: int = 16, max_matches: int = 32):
    """Full ingest→match→reshard→dispatch step as ONE jitted program.

    ``step(words, lens, is_sys, node_tab, edge_tab, seeds, accept_bitmap)
    -> UlyssesResult``.  Batch arrays arrive row-sharded over ``axis``;
    NFA tables and the accept bitmap are replicated (the ingest side
    assembles full-width rows — that replication is what the single
    all_to_all then amortizes, exactly the Ulysses trade).  The dispatch
    side computes per-subscriber delivery counts for its slice: the
    device-resident work list a delivering node consumes.
    """

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis), P(axis),
                  P(), P(), P(), P()),
        out_specs=UlyssesResult(
            dispatch_bitmap=P(None, axis),
            sub_deliveries=P(axis),
            n_matches=P(axis),
            active_overflow=P(axis),
        ),
        check_vma=False,
    )
    def step(words, lens, is_sys, node_tab, edge_tab, seeds, accept_bitmap):
        res = nfa_match(
            words, lens, is_sys, node_tab, edge_tab, seeds,
            active_slots=active_slots, max_matches=max_matches,
        )
        ingest_bm = or_accept_rows(accept_bitmap, res.matches)  # (Bl, W)
        # THE reshard: batch-sharded full rows → subscriber-sharded
        # full batch, one tiled all_to_all on the wire
        disp = jax.lax.all_to_all(
            ingest_bm, axis, split_axis=1, concat_axis=0, tiled=True)
        # dispatch-side work list: how many messages hit each of MY
        # subscribers (bit b of word w = subscriber w*32+b)
        bits = (disp[:, :, None] >> jnp.arange(32, dtype=jnp.uint32)) \
            & jnp.uint32(1)                                  # (B, Wl, 32)
        per_sub = jnp.sum(bits.astype(jnp.int32), axis=0).reshape(-1)
        return UlyssesResult(
            dispatch_bitmap=disp,
            sub_deliveries=per_sub,
            n_matches=res.n_matches,
            active_overflow=res.active_overflow,
        )

    return jax.jit(step)
