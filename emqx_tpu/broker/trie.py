"""Host-side subscription tries — the authoritative wildcard indexes.

Behavioral reference: ``apps/emqx/src/emqx_trie.erl`` (``insert/1``,
``match/1``, ``delete/1``) and ``emqx_topic_index.erl`` [U] — reference
mount empty this round, see SURVEY.md provenance header.

Two directions of the same problem:

* :class:`FilterTrie` — indexes **wildcard filters**, answers
  "which filters match this concrete topic?" (the publish hot path;
  this is what gets compiled to the flattened NFA on device).
* :class:`TopicTrie` — indexes **concrete topics**, answers
  "which stored topics match this wildcard filter?" (the retained-message
  replay path on subscribe).

Both are refcounted: inserting the same key twice needs two deletes before
edges disappear (mirrors emqx_trie's edge counting so concurrent routes
sharing prefixes survive unrelated deletes).

These are also the **CPU baseline**, the benchmark's denominator: match
throughput here is what the TPU kernel is judged against.
"""

from __future__ import annotations

from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from .. import topic as T

__all__ = ["FilterTrie", "TopicTrie"]


class _Node:
    __slots__ = ("children", "end_count")

    def __init__(self) -> None:
        self.children: Dict[str, "_Node"] = {}
        self.end_count: int = 0  # number of live inserts terminating here


class _TrieBase:
    """Shared insert/delete machinery over word paths."""

    def __init__(self) -> None:
        self._root = _Node()
        self._keys: Dict[str, int] = {}  # key -> refcount (live inserts)

    # -- mutation ----------------------------------------------------------

    def insert(self, key: str) -> bool:
        """Insert one reference to ``key``.  Returns True if it is new."""
        ws = T.words(key)
        node = self._root
        for w in ws:
            nxt = node.children.get(w)
            if nxt is None:
                nxt = node.children[w] = _Node()
            node = nxt
        node.end_count += 1
        new = key not in self._keys
        self._keys[key] = self._keys.get(key, 0) + 1
        return new

    def delete(self, key: str) -> bool:
        """Drop one reference to ``key``.  Returns True if it is now gone.

        Unknown keys are a no-op (mirrors emqx_trie:delete of absent
        filters).
        """
        if key not in self._keys:
            return False
        ws = T.words(key)
        # walk down recording the path so empty branches can be pruned
        path: List[_Node] = [self._root]
        node = self._root
        for w in ws:
            node = node.children[w]
            path.append(node)
        node.end_count -= 1
        self._keys[key] -= 1
        gone = self._keys[key] == 0
        if gone:
            del self._keys[key]
        # prune: remove child edges whose subtree is dead
        for i in range(len(ws) - 1, -1, -1):
            child = path[i + 1]
            if child.end_count == 0 and not child.children:
                del path[i].children[ws[i]]
            else:
                break
        return gone

    # -- introspection -----------------------------------------------------

    def __contains__(self, key: str) -> bool:
        return key in self._keys

    def __len__(self) -> int:
        return len(self._keys)

    def keys(self) -> Iterator[str]:
        return iter(self._keys)

    def refcount(self, key: str) -> int:
        return self._keys.get(key, 0)

    def is_empty(self) -> bool:
        return not self._keys

    def node_count(self) -> int:
        """Number of trie nodes (excluding root) — sizing input for the
        NFA compiler."""
        n = 0
        stack = [self._root]
        while stack:
            node = stack.pop()
            for c in node.children.values():
                n += 1
                stack.append(c)
        return n


class FilterTrie(_TrieBase):
    """Wildcard filters indexed; match a concrete topic against them.

    ``$share`` prefixes must be stripped by the caller before insert
    (the broker layer owns share-group bookkeeping).
    """

    def match(self, name) -> List[str]:
        """All inserted filters matching concrete topic ``name``.

        Semantics identical to the oracle ``topic.match`` over every key —
        property-tested to agree.
        """
        nw = T.words(name) if isinstance(name, str) else list(name)
        if not nw:
            return []
        out: List[str] = []
        sys_topic = nw[0].startswith("$")
        # iterative DFS (valid filters can be tens of thousands of levels
        # deep — Python recursion would blow the stack on the hot path)
        stack: List[Tuple[_Node, int, Tuple[str, ...]]] = [(self._root, 0, ())]
        while stack:
            node, i, acc = stack.pop()
            # '#' child matches the rest (incl. zero levels), unless it is
            # a root-level wildcard on a $-topic.
            hashc = node.children.get("#")
            if hashc is not None and not (i == 0 and sys_topic):
                if hashc.end_count > 0:
                    out.append(T.join(acc + ("#",)))
            if i == len(nw):
                if node.end_count > 0:
                    out.append(T.join(acc))
                continue
            w = nw[i]
            lit = node.children.get(w)
            if lit is not None:
                stack.append((lit, i + 1, acc + (w,)))
            # '+' is a distinct edge from a literal '+' level;
            # root-level '+' is disabled for $-topics.
            if w != "+":
                plus = node.children.get("+")
                if plus is not None and not (i == 0 and sys_topic):
                    stack.append((plus, i + 1, acc + ("+",)))
        return out

    def match_many(self, names: Sequence[str]) -> Dict[str, List[str]]:
        """Batch :meth:`match` with duplicate-topic dedup — the CPU
        fallback path of the deadline serve loop answers a whole failed
        dispatch batch here, and publish storms repeat topics heavily
        (one trie walk per UNIQUE topic, not per waiter)."""
        out: Dict[str, List[str]] = {}
        for name in names:
            if name not in out:
                out[name] = self.match(name)
        return out


class TopicTrie(_TrieBase):
    """Concrete topics indexed; match a wildcard filter against them
    (retained-message replay direction)."""

    def match(self, flt) -> List[str]:
        fw = T.words(flt) if isinstance(flt, str) else list(flt)
        if not fw:
            return []
        out: List[str] = []
        # iterative DFS; entries are (node, filter_pos, topic_acc).
        # filter_pos == len(fw) with a trailing '#' means "collect subtree".
        COLLECT = -1
        stack: List[Tuple[_Node, int, Tuple[str, ...]]] = [(self._root, 0, ())]
        while stack:
            node, i, acc = stack.pop()
            if i == COLLECT:
                if node.end_count > 0 and acc:
                    out.append(T.join(acc))
                for cw, child in node.children.items():
                    stack.append((child, COLLECT, acc + (cw,)))
                continue
            if i == len(fw):
                if node.end_count > 0:
                    out.append(T.join(acc))
                continue
            w = fw[i]
            if w == "#":
                # everything at or below this node — except $-topics at root
                if node.end_count > 0 and acc:
                    out.append(T.join(acc))
                for cw, child in node.children.items():
                    if i == 0 and cw.startswith("$"):
                        continue
                    stack.append((child, COLLECT, acc + (cw,)))
                continue
            if w == "+":
                for cw, child in node.children.items():
                    if i == 0 and cw.startswith("$"):
                        continue
                    stack.append((child, i + 1, acc + (cw,)))
                continue
            child = node.children.get(w)
            if child is not None:
                stack.append((child, i + 1, acc + (w,)))
        return out
