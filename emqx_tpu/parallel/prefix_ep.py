"""Expert-parallel trie partition with all-to-all topic routing.

SURVEY.md §2.5's last two rows: the reference partitions routes by
owning node ("EP" analog) and our mandated counterpart shards the TRIE
by top-level topic word, routing each topic of the batch to the shard
owning its root prefix with a **ragged all-to-all** (the Ulysses-style
ingest→dispatch reshard).  Worth it when one chip's HBM can't hold the
whole table, or hot prefixes need isolation.

Pipeline (one `shard_map` over an ``ep`` axis):

1. ingest: topics arrive sharded arbitrarily over ``ep`` (B/E each);
2. each shard buckets its topics by owner (= root word id % E —
   device-computable and identical to the host partition rule) into an
   (E, C) capacity grid via the cumsum-compaction trick; bucket
   overflow is COUNTED and those topics fail open to the host trie;
3. ``all_to_all`` flips source↔owner: each shard now holds every topic
   it owns;
4. the local (per-partition) NFA matches them — root-level ``+``/``#``
   filters are replicated into every partition, so single-shard
   answers are complete;
5. results ``all_to_all`` back and scatter into ingest order.

Tables are built per partition with SHARED shapes and a SHARED vocab
(so one encode serves all shards) by :func:`build_partitions`.

This module remains the standalone dryrun (bench ``prefix_ep``,
MULTICHIP_r03+: parts=4, overflow=0).  The SERVING implementation of
the same router lives in :mod:`.multichip_serve` (ISSUE 16,
``match.multichip.ep.enable``): there the bucket/route step rides the
serve backend's dp×tp mesh, the owner merges a replicated
wildcard-root micro-table into its answer segment instead of
replicating root wildcards into every partition, and overflow joins
the serve plane's CPU-trie fail-open set.

:func:`greedy_balance` is the partition-balancing core the serving
plane's popularity-aware placement (ISSUE 20,
``match.multichip.ep.autotune.enable``) runs at compaction cadence: a
pure strict-improvement greedy over observed per-root loads, so the
same function is unit-testable here and auditable against the dryrun's
uniform ``owner_of`` rule it overrides.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .. import topic as T
from ..ops.incremental import IncrementalNfa

__all__ = ["EpTables", "build_partitions", "build_ep_matcher",
           "owner_of", "greedy_balance"]


def owner_of(flt_or_topic: str, vocab: Dict[str, int], n_parts: int) -> int:
    """Partition rule: root word's vocab id mod E (UNKNOWN → 0)."""
    root = flt_or_topic.split("/", 1)[0]
    return vocab.get(root, 0) % n_parts


def greedy_balance(loads: Dict[str, float], owners: Dict[str, int],
                   n_parts: int, budget: int,
                   ) -> Tuple[Dict[str, int], int]:
    """Greedy hot-root reassignment: repeatedly move the hottest
    strictly-improving root from the most- to the least-loaded
    partition, at most ``budget`` times.  A root heavier than the
    hi−lo gap never moves (it would only swap which partition is hot),
    so every move shrinks the spread and the loop terminates early
    when no improving move remains.  Pure: returns ``(new owners,
    moves made)`` without touching the inputs."""
    owners = dict(owners)
    shard_load = np.zeros(max(1, n_parts), np.float64)
    for w, o in owners.items():
        shard_load[o] += loads.get(w, 0.0)
    moved = 0
    for _ in range(max(0, budget)):
        hi = int(np.argmax(shard_load))
        lo = int(np.argmin(shard_load))
        gap = float(shard_load[hi] - shard_load[lo])
        best = None
        best_load = 0.0
        for w, o in owners.items():
            lw = loads.get(w, 0.0)
            if o == hi and 0.0 < lw < gap and lw > best_load:
                best, best_load = w, lw
        if best is None:
            break
        owners[best] = lo
        shard_load[hi] -= best_load
        shard_load[lo] += best_load
        moved += 1
    return owners, moved


class EpTables(NamedTuple):
    node_tabs: np.ndarray     # (E, S, 4) int32
    edge_tabs: np.ndarray     # (E, Hb, 16) int32
    seeds: np.ndarray         # (E, 2) int32
    vocab: Dict[str, int]     # SHARED across partitions
    accept_filters: List[List[str]]  # per-partition aid -> filter
    depth: int

    @property
    def n_parts(self) -> int:
        return int(self.node_tabs.shape[0])


def build_partitions(filters: Sequence[str], n_parts: int,
                     depth: int = 8) -> EpTables:
    """Partition ``filters`` by root word into ``n_parts`` NFA tables
    with uniform shapes + one shared vocab.  Root-level wildcards
    (``+``/``#`` first word) replicate into every partition."""
    # shared vocab: intern every literal word once, in a stable order
    vocab: Dict[str, int] = {}
    for f in sorted(set(filters)):
        for w in T.words(f):
            if w not in ("+", "#") and w not in vocab:
                vocab[w] = len(vocab) + 1

    parts: List[List[str]] = [[] for _ in range(n_parts)]
    for f in sorted(set(filters)):
        root = f.split("/", 1)[0]
        if root in ("+", "#"):
            for p in parts:
                p.append(f)
        else:
            parts[owner_of(f, vocab, n_parts)].append(f)

    incs = []
    for p in parts:
        inc = IncrementalNfa(depth=depth)
        inc.vocab = vocab  # shared interning (append-only, single thread)
        for f in p:
            inc.add(f)
        incs.append(inc)
    S = max(inc.S for inc in incs)
    Hb = max(inc.Hb for inc in incs)
    # re-home any undersized tables onto the common shapes
    rebuilt = []
    for inc, p in zip(incs, parts):
        if inc.S != S or inc.Hb != Hb:
            fresh = IncrementalNfa(depth=depth, state_bucket=S,
                                   edge_bucket=Hb)
            fresh.vocab = vocab
            for f in p:
                fresh.add(f)
            assert fresh.S == S and fresh.Hb == Hb, "table grew past max"
            inc = fresh
        rebuilt.append(inc)
    return EpTables(
        node_tabs=np.stack([i.node_tab for i in rebuilt]),
        edge_tabs=np.stack([i.edge_tab for i in rebuilt]),
        seeds=np.stack([i.seeds for i in rebuilt]),
        vocab=vocab,
        accept_filters=[list(i.accept_filters) for i in rebuilt],
        depth=depth,
    )


class EpResult(NamedTuple):
    matches: jax.Array      # (B, K) int32 PER-PARTITION accept ids
    owners: jax.Array       # (B,) int32 owning partition of each topic
    n_matches: jax.Array    # (B,) int32
    overflow: jax.Array     # (B,) int32 1 = bucket overflowed (host re-run)


def build_ep_matcher(mesh: Mesh, capacity: int, active_slots: int = 16,
                     max_matches: int = 32):
    """Jitted ``step(words, lens, is_sys, node_tabs, edge_tabs, seeds)
    -> EpResult`` over the ``ep`` axis.  ``capacity`` is the per-
    (source, owner) bucket size; overflowing topics are flagged for the
    host path (fail open, same discipline as kernel spills)."""
    from ..ops.match_kernel import nfa_match

    E = mesh.shape["ep"]
    C = capacity

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P("ep", None), P("ep"), P("ep"),
                  P("ep", None, None), P("ep", None, None), P("ep", None)),
        out_specs=EpResult(P("ep", None), P("ep"), P("ep"), P("ep")),
        check_vma=False,
    )
    def step(words, lens, is_sys, node_tab, edge_tab, seeds):
        Bl, D = words.shape
        # one table per shard, or the device routing rule (% E) and the
        # host partition rule (% n_parts) silently disagree
        assert node_tab.shape[0] == 1, (
            f"tables built for {node_tab.shape[0] * E} partitions but the "
            f"mesh has ep={E}; build_partitions(n_parts) must match"
        )
        node_tab = node_tab[0]
        edge_tab = edge_tab[0]
        seeds = seeds[0]
        owner = words[:, 0] % E                             # (Bl,)
        # bucket my topics by owner: rank within each owner group
        onehot_owner = owner[:, None] == jnp.arange(E)[None, :]  # (Bl, E)
        rank = jnp.cumsum(onehot_owner, axis=0) - 1         # (Bl, E)
        my_rank = jnp.take_along_axis(
            rank, owner[:, None], axis=1)[:, 0]             # (Bl,)
        overflow = (my_rank >= C).astype(jnp.int32)
        keep = overflow == 0
        # overflowed rows must scatter NOWHERE (an in-range dummy slot
        # would clobber a legitimate topic): route them out of range and
        # let mode="drop" discard the write
        owner_idx = jnp.where(keep, owner, E)
        slot = jnp.where(keep, my_rank, 0)
        # scatter topics into the (E, C) grid
        grid_w = jnp.zeros((E, C, D), jnp.int32)
        grid_l = jnp.full((E, C), D + 2, jnp.int32)         # inert pad
        grid_s = jnp.ones((E, C), bool)
        src = jnp.arange(Bl)
        grid_w = grid_w.at[owner_idx, slot].set(words, mode="drop")
        grid_l = grid_l.at[owner_idx, slot].set(lens, mode="drop")
        grid_s = grid_s.at[owner_idx, slot].set(is_sys, mode="drop")
        # remember which source row filled each bucket slot
        grid_src = jnp.full((E, C), -1, jnp.int32).at[owner_idx, slot].set(
            src, mode="drop")

        # ragged all-to-all: (owner, C, ...) leaves, (source, C, ...) lands
        w2 = jax.lax.all_to_all(grid_w, "ep", 0, 0, tiled=False)
        l2 = jax.lax.all_to_all(grid_l, "ep", 0, 0, tiled=False)
        s2 = jax.lax.all_to_all(grid_s, "ep", 0, 0, tiled=False)

        res = nfa_match(
            w2.reshape(E * C, D), l2.reshape(E * C), s2.reshape(E * C),
            node_tab, edge_tab, seeds,
            active_slots=active_slots, max_matches=max_matches,
        )
        K = res.matches.shape[1]
        m_back = jax.lax.all_to_all(
            res.matches.reshape(E, C, K), "ep", 0, 0)       # (E, C, K)
        n_back = jax.lax.all_to_all(
            res.n_matches.reshape(E, C), "ep", 0, 0)        # (E, C)

        # scatter results into ingest order via the remembered sources
        out_m = jnp.full((Bl, K), -1, jnp.int32)
        out_n = jnp.zeros((Bl,), jnp.int32)
        flat_src = grid_src.reshape(E * C)
        safe = jnp.where(flat_src >= 0, flat_src, Bl)       # Bl = dropped
        out_m = out_m.at[safe].set(m_back.reshape(E * C, K), mode="drop")
        out_n = out_n.at[safe].set(n_back.reshape(E * C), mode="drop")
        return EpResult(out_m, owner, out_n, overflow)

    return jax.jit(step)
