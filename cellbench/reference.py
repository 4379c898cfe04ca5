"""The plain reference: MQTT topic matching and what follows from it.

Independent of the program under test: it imports nothing of it and is
fed only what the benchmark itself made from the seed (the filter list,
the subscribers' filters, the publishes).  Two equivalent forms of the
same rule, each the obvious one for its use:

* ``match(topic, flt)`` — the level-by-level rule of MQTT 3.1.1 §4.7;
* ``matching(topic, filters)`` — every filter of a set that matches a
  topic, found by writing out all filters that CAN match the topic
  (each level kept or replaced by ``+``, every prefix closed by ``#``)
  and looking each up in the set: no trie, no automaton.

The controls at the bottom are the reference with one stated guarantee
broken; ``run.py --control <name>`` puts one in the program's place and
the comparison has to come out as not correct.
"""

from __future__ import annotations

from itertools import product


def match(topic: str, flt: str) -> bool:
    tl, fl = topic.split("/"), flt.split("/")
    if topic.startswith("$") and fl[0] in ("+", "#"):
        return False
    for i, f in enumerate(fl):
        if f == "#":
            return True                 # also matches the parent level
        if i >= len(tl) or (f != "+" and f != tl[i]):
            return False
    return len(fl) == len(tl)


def candidates(topic: str):
    """Every filter that matches ``topic``."""
    tl = topic.split("/")
    sys_topic = topic.startswith("$")
    for k in range(len(tl) + 1):
        for mask in product((False, True), repeat=k):
            if sys_topic and (k == 0 or mask[0]):
                continue
            head = ["+" if m else w for m, w in zip(mask, tl)]
            yield "/".join(head + ["#"])
            if k == len(tl):
                yield "/".join(head)


def matching(topic: str, filters) -> set:
    """The filters of ``filters`` (a set or dict) that match ``topic``."""
    return {c for c in candidates(topic) if c in filters}


def expected_deliveries(topics, tcp_filters):
    """For each publish the list of TCP subscribers (indices into
    ``tcp_filters``) that must receive it, one delivery per matching
    subscriber.  ``tcp_filters[i]`` is subscriber ``i``'s one filter."""
    if len(tcp_filters) <= 64:
        subs = list(enumerate(tcp_filters))
        memo = {}
        out = []
        for t in topics:
            hit = memo.get(t)
            if hit is None:
                hit = memo[t] = [i for i, f in subs if match(t, f)]
            out.append(hit)
        return out
    by_filter = {}
    for i, f in enumerate(tcp_filters):
        by_filter.setdefault(f, []).append(i)
    memo = {}
    out = []
    for t in topics:
        hit = memo.get(t)
        if hit is None:
            hit = memo[t] = sorted(
                i for f in matching(t, by_filter) for i in by_filter[f])
        out.append(hit)
    return out


# -- controls: the reference with one guarantee broken -------------------

def matching_truncated(topic: str, filters, keep: int = 2) -> set:
    """An approximate answer where the configuration states an exact one:
    at most ``keep`` matches per topic, the rest dropped (what a match
    buffer that overflows without the host re-run would answer)."""
    return set(sorted(matching(topic, filters))[:keep])


CONTROLS = {
    # at-most-once in place of QoS 1: the subscriber side loses one
    # delivery in N and nothing redelivers it (planted in loadgen)
    "qos0_loss": {"drop_delivery_every": 1009},
    # approximate match set in place of the exact one
    "approx_match": {"device_answer": matching_truncated},
}
