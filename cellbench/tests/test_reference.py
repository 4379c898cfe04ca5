"""The plain reference agrees with itself in its two forms, and each
control departs from it."""

import numpy as np

from cellbench import reference as REF
from cellbench.tables import zipf_tree


def test_match_rules():
    assert REF.match("a/b", "a/b") and not REF.match("a/b", "a")
    assert REF.match("a/b", "a/+") and not REF.match("a/b/c", "a/+")
    assert REF.match("a", "a/#") and REF.match("a/b/c", "a/#")
    assert REF.match("a/b", "#") and not REF.match("$SYS/x", "#")
    assert not REF.match("$SYS/x", "+/x") and REF.match("$SYS/x", "$SYS/+")
    assert not REF.match("a", "a/+")


def test_candidates_are_exactly_the_matching_filters():
    rng = np.random.default_rng(7)
    table = zipf_tree.Table(rng, {"depth": 5, "min_words": 4, "ask": 3000})
    filters = set(table.filters) | set(table.tcp_filters)
    for topic in table.draw_topics(rng, 200) + ["$SYS/a", "L0w0"]:
        brute = {f for f in filters if REF.match(topic, f)}
        assert REF.matching(topic, filters) == brute


def test_expected_deliveries_both_paths_agree():
    topics = [f"bench/{i % 70}" for i in range(200)] + ["bench/1/x"]
    few = [f"bench/{i}" for i in range(10)]
    many = [f"bench/{i}" for i in range(70)]
    a = REF.expected_deliveries(topics, few)
    b = REF.expected_deliveries(topics, many)
    assert a[3] == [3] and a[15] == [] and a[-1] == []
    assert b[15] == [15] and b[-1] == []


def test_controls_break_something():
    filters = {"a/#", "a/+", "a/b", "+/b", "#"}
    assert REF.matching("a/b", filters) == filters
    assert len(REF.matching_truncated("a/b", filters)) == 2
    assert REF.CONTROLS["qos0_loss"]["drop_delivery_every"] > 0
