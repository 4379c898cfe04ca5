"""Config-4 device stage: ``$share`` group member selection on-chip.

The reference picks one member per shared group per message on the host
(``emqx_shared_sub:dispatch`` strategies, SURVEY.md §2.1).  At BASELINE
config-4 scale the candidate sets live in the TP-sharded subscriber
bitmap, so selection runs where the bits already are:

* inputs (inside the same mesh as the fan-out step): the per-topic
  subscriber bitmap (B, W) sharded ``(dp, tp)``, per-group membership
  masks (G, W) sharded ``(None, tp)``, and a per-topic selector hash
  (the ``hash_topic``/``random`` strategy seed) sharded ``(dp,)``;
* per (topic, group): candidates = row ∧ mask, member counts psum over
  ``tp``, the hash picks an ordinal, and the one shard holding that
  ordinal extracts the subscriber id (cumsum-popcount word walk + 32-way
  bit probe) — combined across ``tp`` with a max-reduce.

Output: (B, G) int32 subscriber id, -1 where the group has no member
with a matching subscription — exactly the host strategy's pick for
``hash_topic``-style selection, provable in parity tests.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["build_shared_selector", "make_group_masks", "host_pick"]


def make_group_masks(groups, n_subs: int, words: int) -> np.ndarray:
    """(G, words) uint32 membership masks from ``groups``: iterable of
    iterables of subscriber ids."""
    g = len(groups)
    bm = np.zeros((g, words), np.uint32)
    for gi, members in enumerate(groups):
        for sub in members:
            if not 0 <= sub < n_subs:
                raise ValueError(f"subscriber id {sub} out of range")
            bm[gi, sub >> 5] |= np.uint32(1) << np.uint32(sub & 31)
    return bm


def host_pick(row_bitmap: np.ndarray, mask: np.ndarray, sel_hash: int) -> int:
    """Reference pick: the ``(hash % n_members)``-th live member in
    subscriber-id order (-1 when empty) — the parity oracle."""
    cand = row_bitmap & mask
    ids = []
    for w in range(len(cand)):
        v = int(cand[w])
        while v:
            b = (v & -v).bit_length() - 1
            ids.append(w * 32 + b)
            v &= v - 1
    if not ids:
        return -1
    return ids[sel_hash % len(ids)]


def _nth_set_bit(word, n):
    """n-th (0-based) set bit index of a uint32 via 32-step probe;
    word/n are (..,) arrays.  Caller guarantees n < popcount(word)."""
    idx = jnp.full(word.shape, -1, jnp.int32)
    seen = jnp.zeros(word.shape, jnp.int32)
    for b in range(32):
        bit = (word >> jnp.uint32(b)) & jnp.uint32(1)
        hit = (bit == 1) & (seen == n) & (idx < 0)
        idx = jnp.where(hit, b, idx)
        seen = seen + bit.astype(jnp.int32)
    return idx


def build_shared_selector(mesh: Mesh):
    """Returns jitted ``select(bitmap, masks, sel_hash) -> (B, G) int32``.

    ``bitmap`` (B, W) uint32 sharded (dp, tp); ``masks`` (G, W) uint32
    sharded (None, tp); ``sel_hash`` (B,) int32 sharded (dp,)."""

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P("dp", "tp"), P(None, "tp"), P("dp")),
        out_specs=P("dp", None),
        check_vma=False,
    )
    def select(bitmap, masks, sel_hash):
        # candidates per (topic, group): (Bl, G, Wl)
        cand = bitmap[:, None, :] & masks[None, :, :]
        wc = jax.lax.population_count(cand).astype(jnp.int32)
        count_l = jnp.sum(wc, axis=-1)                      # (Bl, G)
        total = jax.lax.psum(count_l, "tp")                 # (Bl, G)
        # exclusive prefix of counts across tp shards
        tp_idx = jax.lax.axis_index("tp")
        ntp = mesh.shape["tp"]
        all_counts = jax.lax.all_gather(count_l, "tp")      # (ntp, Bl, G)
        before = jnp.sum(
            jnp.where(jnp.arange(ntp)[:, None, None] < tp_idx,
                      all_counts, 0),
            axis=0,
        )                                                   # (Bl, G)
        sel = sel_hash[:, None] % jnp.maximum(total, 1)     # (Bl, G)
        local_ord = sel - before
        mine = (local_ord >= 0) & (local_ord < count_l) & (total > 0)
        # word holding the local ordinal: cumsum-popcount walk
        cum = jnp.cumsum(wc, axis=-1) - wc                  # exclusive (Bl,G,Wl)
        o = jnp.where(mine, local_ord, 0)[:, :, None]
        in_word = (o >= cum) & (o < cum + wc)
        word_idx = jnp.argmax(in_word, axis=-1)             # (Bl, G)
        word = jnp.take_along_axis(cand, word_idx[:, :, None],
                                   axis=-1)[:, :, 0]
        rem = (o[:, :, 0] - jnp.take_along_axis(
            cum, word_idx[:, :, None], axis=-1)[:, :, 0])
        bit = _nth_set_bit(word, rem)                       # (Bl, G)
        Wl = bitmap.shape[1]
        sub_id = (tp_idx * Wl + word_idx) * 32 + bit
        picked = jnp.where(mine, sub_id, -1)
        # exactly one shard claims each (topic, group) with members
        return jax.lax.pmax(picked, "tp")

    return jax.jit(select)
