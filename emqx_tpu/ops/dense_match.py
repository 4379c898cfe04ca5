"""Dense matmul NFA walk — the MXU-native small-table match engine.

**Why this exists.**  Round-5 silicon run of the pallas VMEM kernel
(``pallas_match.py``) hit Mosaic's gather lowering limits: TPU Mosaic
supports only ``take_along_axis``-shaped 2D gathers (input/indices/
output the same shape), so arbitrary table lookups — the heart of the
walk — cannot lower (``ValueError: Shape mismatch in input, indices and
output``, pinned by tests/test_chip_compile.py).  Rather than fight
the gather unit, this module removes gathers entirely: for a small
table the trie walk IS dense linear algebra, and the MXU is the fastest unit on the chip.

**The reformulation.**  Active-state sets become multi-hot rows
``active (B, S)`` instead of id lists, and one step of the walk is:

* literal edges: every state has exactly ONE incoming literal edge
  (its trie parent), so ``L[parent, child] = 1`` is a 0/1 matrix with
  at most one nonzero per column and ``active @ L`` lands each parent's
  activation on its children — exact in bf16, no accumulation happens.
  A child survives only if the topic word at this level equals its edge
  label: a broadcast compare against ``label (S,)``, no hash probes.
* ``+`` edges: same construction with ``P[state, plus_child] = 1``.
* accepts are bitmaps: ``ever-active ∧ has-hash-accept`` and
  ``active-at-len ∧ has-end-accept``, compacted to id lists on device.

No cuckoo probes, no ``top_k``, **no active-set cap and therefore no
spill**: the multi-hot row holds every reachable state, so this engine
is exact where the gather kernel fails open (``aover ≡ 0``).  Cost is
``2·D·B·S²`` bf16 MACs — pure MXU work that beats the HBM
random-gather kernel while ``S`` stays small (the hot tier of
``ops.tiered``); the gather kernel keeps the 1M–10M regime where S²
explodes.  Matrices ship once per epoch like every other table.

Semantics mirror ``nfa_match`` exactly (same accept rules, $-topic
root suppression, UNKNOWN word id 0 having no literal edges by
construction) and parity is tested against the host oracle AND the
gather kernel.  Reference behavior: ``emqx_trie:match/1`` [U]
(SURVEY.md §3.4).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .compiler import NfaTable
from .match_kernel import MatchResult, _compact

__all__ = ["DenseTable", "build_dense", "dense_match", "supports_dense",
           "bench_dense", "DENSE_STATE_CAP"]

# above this many states the S^2 matmuls lose to the gather kernel ON
# THE SAME SMALL TABLE.  Measured on v5e (bench_dense sweep, B=4096,
# 2026-07-30): S=256 → 1.75x, S=512 → 1.36x, S=2048 → 0.70x, S=4096 →
# 0.31x (FLOP-bound: 16 (B,S)x(S,S) bf16 matmuls at ~50% MXU
# efficiency).  Either engine on a small hot table beats the monolithic
# 150k-filter table's gather walk ~4x/topic (8.2 → 1.9-2.4 µs) — the
# tier win is mostly table smallness; dense adds exactness (no spill)
# and the extra 1.4-1.8x under this cap (a remote attach that is gone;
# not measured on the attached chip yet).
DENSE_STATE_CAP = 512
_LABEL_NONE = -7            # never equals a word id (those are >= 0)


class DenseTable(NamedTuple):
    """Device operands of the dense walk (host numpy until shipped)."""

    lmat: np.ndarray        # (S, S) f32 0/1 — literal edge parent→child
    pmat: np.ndarray        # (S, S) f32 0/1 — plus edge parent→child
    label: np.ndarray       # (S,) i32 — incoming literal word id, -7 none
    hacc: np.ndarray        # (S,) i32 — hash-accept id, -1 none
    eacc: np.ndarray        # (S,) i32 — end-accept id, -1 none

    @property
    def S(self) -> int:
        return int(self.label.shape[0])

    def device_arrays(self):
        return (self.lmat, self.pmat, self.label, self.hacc, self.eacc)


def supports_dense(table: NfaTable,
                   state_cap: int = DENSE_STATE_CAP) -> bool:
    return table.n_states <= state_cap


def build_dense(table: NfaTable, min_s: int = 128) -> DenseTable:
    """Dense operands from the compiled table; S is padded to a power
    of two ≥ live states (NOT ``table.S`` — the cuckoo layout pads far
    wider than the matmul wants to pay for)."""
    n = max(table.n_states, 1)
    S = min_s
    while S < n:
        S <<= 1
    lmat = np.zeros((S, S), np.float32)
    pmat = np.zeros((S, S), np.float32)
    label = np.full((S,), _LABEL_NONE, np.int32)
    hacc = np.full((S,), -1, np.int32)
    eacc = np.full((S,), -1, np.int32)
    node = table.node_tab
    hacc[:min(S, node.shape[0])] = node[:min(S, node.shape[0]), 1]
    eacc[:min(S, node.shape[0])] = node[:min(S, node.shape[0]), 2]
    plus = node[:n, 0]
    src = np.nonzero(plus >= 0)[0]
    pmat[src, plus[src]] = 1.0
    slots = table.edge_tab.reshape(-1, 4)
    live = slots[slots[:, 2] >= 0]          # [state, word, next, 0]
    lmat[live[:, 0], live[:, 2]] = 1.0
    label[live[:, 2]] = live[:, 1]
    return DenseTable(lmat, pmat, label, hacc, eacc)


@partial(jax.jit, static_argnames=("max_matches",))
def dense_match(
    words,      # (B, D) int32
    lens,       # (B,) int32
    is_sys,     # (B,) bool
    lmat,       # (S, S) f32/bf16
    pmat,       # (S, S) f32/bf16
    label,      # (S,) i32
    hacc,       # (S,) i32
    eacc,       # (S,) i32
    *,
    max_matches: int = 32,
) -> MatchResult:
    B, D = words.shape
    S = label.shape[0]
    dt = jnp.bfloat16
    lmat = lmat.astype(dt)
    pmat = pmat.astype(dt)

    root = jnp.zeros((B, S), dt).at[:, 0].set(1.0)
    active = root
    acc_h = jnp.zeros((B, S), bool)
    acc_e = jnp.zeros((B, S), bool)
    for t in range(D + 1):
        a = active > 0.5
        fire = a if t else a & ~is_sys[:, None]   # $-topics: no root fire
        acc_h = acc_h | fire
        acc_e = acc_e | (a & (t == lens)[:, None])
        if t == D:
            break
        lit_in = active @ lmat                     # (B, S) — exact: one
        plus_src = active if t else active * (~is_sys[:, None]).astype(dt)
        plus_in = plus_src @ pmat                  # nonzero per column
        wmatch = words[:, t][:, None] == label[None, :]
        nxt = jnp.where(wmatch, lit_in, 0) + plus_in
        alive = (t < lens)[:, None]
        active = (alive & (nxt > 0.5)).astype(dt)

    cand = jnp.concatenate(
        [jnp.where(acc_h & (hacc >= 0)[None, :], hacc[None, :], -1),
         jnp.where(acc_e & (eacc >= 0)[None, :], eacc[None, :], -1)],
        axis=1)                                    # (B, 2S)
    n = jnp.sum((cand >= 0).astype(jnp.int32), axis=1)
    matches = _compact(cand, max_matches)
    return MatchResult(
        matches=matches,
        n_matches=n,
        active_overflow=jnp.zeros((B,), jnp.int32),  # exact by design
        match_overflow=(n > max_matches).astype(jnp.int32),
    )


def bench_dense(n_filters: int = 420, batch: int = 4096,
                iters: int = 20, depth: int = 8) -> dict:
    """On-chip A/B: dense matmul walk vs the HBM gather kernel on the
    SAME small table — the hot-tier engine decision measurement.
    Default sized to land near DENSE_STATE_CAP states (the regime the
    tier actually runs in; S=4096 measured 0.31x and set the cap)."""
    import time

    from .compiler import compile_filters, encode_topics
    from .match_kernel import nfa_match

    rng = np.random.default_rng(11)
    filters = sorted({
        f"r{rng.integers(40)}/"
        + "/".join(("+" if rng.random() < 0.3 else f"w{rng.integers(30)}")
                   for _ in range(rng.integers(1, depth - 2)))
        + ("/#" if rng.random() < 0.2 else "")
        for _ in range(n_filters)})
    table = compile_filters(filters, depth=depth)
    dense = build_dense(table)
    topics = [f"r{rng.integers(40)}/" +
              "/".join(f"w{rng.integers(30)}"
                       for _ in range(rng.integers(1, depth - 1)))
              for _ in range(batch)]
    words, lens, is_sys = encode_topics(table, topics, batch=batch)
    jargs = (jnp.asarray(words), jnp.asarray(lens), jnp.asarray(is_sys))
    gargs = tuple(jnp.asarray(a) for a in table.device_arrays())
    dargs = tuple(jnp.asarray(a) for a in dense.device_arrays())
    out = {"n_filters": len(filters), "n_states": table.n_states,
           "dense_S": dense.S, "batch": batch}

    r = nfa_match(*jargs, *gargs, active_slots=8, compact_output=True,
                  max_matches=64)
    np.asarray(r.matches)
    t0 = time.perf_counter()
    for _ in range(iters):
        r = nfa_match(*jargs, *gargs, active_slots=8, compact_output=True,
                      max_matches=64)
    np.asarray(r.matches)
    out["gather_ms"] = round((time.perf_counter() - t0) / iters * 1e3, 2)

    d = dense_match(*jargs, *dargs, max_matches=64)
    np.asarray(d.matches)
    t0 = time.perf_counter()
    for _ in range(iters):
        d = dense_match(*jargs, *dargs, max_matches=64)
    np.asarray(d.matches)
    out["dense_ms"] = round((time.perf_counter() - t0) / iters * 1e3, 2)
    out["dense_topics_per_s"] = int(batch / (out["dense_ms"] / 1e3))
    out["speedup_vs_gather"] = round(out["gather_ms"] / out["dense_ms"], 2)

    # parity on the measured batch (sets; gather rows that spilled are
    # excluded — dense cannot spill)
    ga = np.asarray(r.matches)
    da = np.asarray(d.matches)
    skip = np.asarray(r.spilled_rows()) | (np.asarray(d.match_overflow) > 0)
    mism = sum(
        1 for i in range(len(topics))
        if not skip[i]
        and set(ga[i][ga[i] >= 0]) != set(da[i][da[i] >= 0]))
    out["parity_mismatches"] = mism
    return out


if __name__ == "__main__":
    print(bench_dense())
