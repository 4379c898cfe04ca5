"""Wildcard-heavy filter table over a Zipfian topic tree.

A copy of ``bench.build_workload`` (BASELINE.json config 2/3 shape: 45 %
``+`` somewhere, 30 % ``#`` tail, 25 % plain; depth-``depth`` tree, 2 to
``depth`` levels, Zipf weights per level) with one change: level ``d`` has
``max(min_words, 2**(d+2))`` words, so 16 root words instead of 4 at
``min_words`` 16 (PERF.md §6, PR 22: four roots hash onto two of four
shards).  The original stays in ``bench.py``
(PERF.md §7).  Everything is drawn from the ``rng`` handed in."""

from __future__ import annotations

import numpy as np


class Table:
    def __init__(self, rng, params: dict) -> None:
        self.depth = depth = int(params["depth"])
        words = max(int(params.get("min_words", 4)), 4)
        self.vocab = [
            [f"L{d}w{i}" for i in range(max(words, 2 ** (d + 2)))]
            for d in range(depth)]
        self.zipf = []
        for d in range(depth):
            w = 1.0 / np.arange(1, len(self.vocab[d]) + 1)
            self.zipf.append(w / w.sum())
        self.filters = self._filters(rng, int(params["ask"]))
        self.n_wildcard = sum(1 for f in self.filters
                              if "+" in f or "#" in f)
        need = int(params.get("need_wildcard", 0))
        if self.n_wildcard < need:
            raise ValueError(f"{self.n_wildcard} distinct wildcard filters "
                             f"< {need} needed: raise table.params.ask")
        # one TCP subscriber per root word: every publish has exactly one
        # delivery to a socket
        self.tcp_filters = [f"{w}/#" for w in self.vocab[0]]

    def _paths(self, rng, count: int):
        depth, vocab = self.depth, self.vocab
        depths = rng.integers(2, depth + 1, size=count)
        cols = [rng.choice(len(vocab[d]), size=count, p=self.zipf[d])
                for d in range(depth)]
        return [[vocab[i][cols[i][r]] for i in range(depths[r])]
                for r in range(count)]

    def _filters(self, rng, n_filters: int):
        depth = self.depth
        filters = set()
        while len(filters) < n_filters:
            need = int((n_filters - len(filters)) * 1.3) + 16
            kinds, plus_pos, hash_cut = (rng.random(need) for _ in range(3))
            for ws, kind, pp, hc in zip(self._paths(rng, need), kinds,
                                        plus_pos, hash_cut):
                if kind < 0.45:         # '+' somewhere
                    ws[int(pp * len(ws))] = "+"
                elif kind < 0.75:       # '#' tail (replaces >= 1 level)
                    ws = ws[: max(1, int(hc * (len(ws) - 1)) + 1) - 1] \
                        or ws[:1]
                    ws = ws + ["#"]
                    if len(ws) > depth:
                        ws = ws[: depth - 1] + ["#"]
                filters.add("/".join(ws))
                if len(filters) >= n_filters:
                    break
        return sorted(filters)

    def draw_topics(self, rng, n: int):
        """``n`` i.i.d. topics from the tree; repeats are kept."""
        return ["/".join(ws) for ws in self._paths(rng, n)]


def build(rng, params: dict, n_publishers: int) -> Table:
    return Table(rng, params)
