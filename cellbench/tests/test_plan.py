"""The one traffic generator: every seed offers the same amount of work."""

import numpy as np
import pytest

from cellbench import plan as P
from cellbench.tables import zipf_tree

POISSON = {"arrivals": "poisson", "publisher_pick": "uniform",
           "topic_draw": "fresh"}


def table():
    return zipf_tree.Table(np.random.default_rng(1),
                           {"depth": 6, "min_words": 8, "ask": 500})


def test_poisson_has_a_fixed_count_and_fresh_topics():
    t, used = table(), set()
    a = P.schedule(np.random.default_rng(1), POISSON, t, 500, 4.0, 20,
                   used)
    b = P.schedule(np.random.default_rng(2), POISSON, t, 500, 4.0, 20,
                   used)
    assert len(a) == len(b) == 2000
    assert (np.diff(a.due) >= 0).all() and a.due[-1] < 4e9
    topics = [a.topic_of(i) for i in range(2000)] + \
        [b.topic_of(i) for i in range(2000)]
    assert len(set(topics)) == 4000


def test_a_draw_the_generator_does_not_have_is_refused():
    for key in ("arrivals", "publisher_pick", "topic_draw"):
        with pytest.raises(ValueError, match=key):
            P.schedule(np.random.default_rng(3), dict(POISSON, **{key: "x"}),
                       table(), 100, 1.0, 20, set())


def test_volley_is_one_instant_on_distinct_connections():
    v = P.volley(np.random.default_rng(5), POISSON, table(), 50, 80, set())
    assert (v.due == 0).all() and len(set(v.pub.tolist())) == 50
