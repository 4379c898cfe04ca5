"""In-process TPU match service: the broker's own publish path rides the
device kernel.

Covers: router-delta mirror sync, hint production/consumption, fail-open
on staleness, rule co-batching, and an e2e TCP publish storm where
dispatch demonstrably used the kernel (tpu.* metrics) with parity.
"""

import asyncio

import pytest

from emqx_tpu import topic as T
from emqx_tpu.client import Client
from emqx_tpu.config import Config
from emqx_tpu.node import BrokerNode


def run(coro):
    return asyncio.run(coro)


async def settle(pred, timeout=30.0, interval=0.02):
    deadline = asyncio.get_running_loop().time() + timeout
    while asyncio.get_running_loop().time() < deadline:
        if pred():
            return True
        await asyncio.sleep(interval)
    return pred()


def make_node(**extra):
    cfg = Config(file_text='listeners.tcp.default.bind = "127.0.0.1:0"\n')
    cfg.put("tpu.enable", True)  # env layer disables it for other tests
    cfg.put("tpu.mirror_refresh_interval", 0.01)
    cfg.put("tpu.bypass_rate", 0.0)  # pin the device path on for tests
    for k, v in extra.items():
        cfg.put(k, v)
    return BrokerNode(cfg)


def sub(b, cid, flt):
    if cid not in b.sessions:
        b.open_session(cid)
    b.subscribe(cid, flt)


def ms_synced(node):
    ms = node.match_service
    return (
        ms is not None and ms.ready
        and ms._seen_epoch == node.broker.router.epoch
        and ms.dev.epoch == ms.inc.epoch
    )


def test_publish_storm_uses_kernel_with_parity():
    async def main():
        node = make_node()
        await node.start()
        port = node.listeners.all()[0].port
        try:
            subs = []
            filters = []
            for i in range(6):
                c = Client(clientid=f"s{i}", port=port)
                await c.connect()
                flt = f"room/+/kind{i % 3}"
                await c.subscribe(flt, qos=0)
                subs.append(c)
                filters.append(flt)
            assert await settle(lambda: ms_synced(node))

            pub = Client(clientid="p", port=port)
            await pub.connect()
            topics = [f"room/{i}/kind{i % 3}" for i in range(30)]
            for t in topics:
                await pub.publish(t, b"x", qos=0)

            # every subscriber with a matching filter got every message
            async def got_all():
                want = sum(
                    1 for t in topics for f in filters if T.match(t, f)
                )
                have = sum(s.messages.qsize() for s in subs)
                return have >= want

            ok = False
            for _ in range(100):
                if await got_all():
                    ok = True
                    break
                await asyncio.sleep(0.05)
            assert ok, "deliveries missing"

            m = node.observed.metrics
            assert m.get("tpu.match.batches") >= 1
            assert m.get("tpu.match.topics") >= len(topics)
            assert m.get("tpu.mirror.refresh") >= 1
            for s in subs:
                await s.disconnect()
            await pub.disconnect()
        finally:
            await node.stop()

    run(main())


def test_scoped_hint_invalidation():
    """Round-3 churn semantics: a router mutation only kills the hints it
    can actually make wrong.  Exact adds and any deletes resolve live via
    routes_with_wild; only a NEW wildcard filter matching the topic
    invalidates."""

    async def main():
        node = make_node()
        await node.start()
        try:
            b = node.broker
            ms = node.match_service
            sub(b, "c1", "a/+")
            assert await settle(lambda: ms_synced(node))
            await ms.prefetch("a/x")
            assert ms.hint_routes("a/x") is not None

            # exact-filter add: the hint SURVIVES and already includes
            # the new route (exact map is read live)
            sub(b, "c2", "a/x")
            hint = ms.hint_routes("a/x")
            assert hint is not None
            assert sorted(map(tuple, hint)) == sorted(
                map(tuple, b.router.match_routes("a/x"))
            )

            # non-matching wildcard add: hint survives too
            sub(b, "c3", "zzz/+")
            assert ms.hint_routes("a/x") is not None

            # unsubscribe (delete): hint survives, route drops out live
            b.unsubscribe("c2", "a/x")
            hint = ms.hint_routes("a/x")
            assert hint is not None
            assert sorted(map(tuple, hint)) == sorted(
                map(tuple, b.router.match_routes("a/x"))
            )

            # a MATCHING wildcard add is the one poison case
            sub(b, "c4", "a/#")
            assert ms.hint_routes("a/x") is None
            assert node.observed.metrics.get("tpu.match.hint_stale") >= 1

            # after resync + re-prefetch the device path serves again
            assert await settle(lambda: ms_synced(node))
            await ms.prefetch("a/x")
            hint = ms.hint_routes("a/x")
            assert hint is not None
            assert sorted(map(tuple, hint)) == sorted(
                map(tuple, b.router.match_routes("a/x"))
            )
        finally:
            await node.stop()

    run(main())


def test_churn_keeps_device_duty_cycle():
    """Continuous subscribe/unsubscribe churn elsewhere in the topic
    space must not collapse the device path to host serving: duty cycle
    (hints served / publishes) stays >50% with full parity."""

    async def main():
        node = make_node()
        await node.start()
        try:
            b = node.broker
            ms = node.match_service
            for i in range(8):
                sub(b, f"s{i}", f"room/+/k{i}")
            assert await settle(lambda: ms_synced(node))

            m = node.observed.metrics
            topics = [f"room/{i}/k{i % 8}" for i in range(16)]
            served = 0
            total = 0
            for round_ in range(12):
                # churn: unrelated wildcard subs come and go every round
                sub(b, "churn", f"churnspace/{round_}/+")
                if round_ > 0:
                    b.unsubscribe("churn", f"churnspace/{round_ - 1}/+")
                for t in topics:
                    await ms.prefetch(t)
                    total += 1
                    hint = ms.hint_routes(t)
                    if hint is not None:
                        served += 1
                        want = b.router.match_routes(t)
                        assert sorted(map(tuple, hint)) == sorted(
                            map(tuple, want)
                        ), t
                await asyncio.sleep(0.005)
            duty = served / total
            assert duty > 0.5, f"device duty cycle {duty:.2f} under churn"
            assert m.get("tpu.match.hint_served") >= served
        finally:
            await node.stop()

    run(main())


def test_adaptive_bypass_low_concurrency():
    """With bypass enabled and a trickle of publishes, prefetch skips
    the device batching window entirely (host trie is faster at one-
    client load) and delivery still works via the host path."""

    async def main():
        node = make_node(**{"tpu.bypass_rate": 1e9})
        await node.start()
        try:
            b = node.broker
            ms = node.match_service
            sub(b, "c1", "a/+")
            assert await settle(lambda: ms_synced(node))
            await ms.prefetch("a/x")
            assert node.observed.metrics.get("tpu.match.bypass") >= 1
            assert ms.hint_routes("a/x") is None  # no hint minted
            # broker delivery falls back to the host trie transparently
            from emqx_tpu.broker.message import make_message

            res = b.publish(make_message("p", "a/x", b"!"))
            assert res.matched >= 1
        finally:
            await node.stop()

    run(main())


def test_hint_routes_match_host_routes():
    async def main():
        node = make_node()
        await node.start()
        try:
            b = node.broker
            ms = node.match_service
            flts = ["s/+/t", "s/#", "exact/topic", "+/b", "deep/a/b/c/d/e/f/+/x"]
            for i, f in enumerate(flts):
                sub(b, f"c{i}", f)
            assert await settle(lambda: ms_synced(node))
            for topic in ["s/1/t", "s/9", "exact/topic", "q/b", "none",
                          "deep/a/b/c/d/e/f/q/x"]:
                await ms.prefetch(topic)
                hint = ms.hint_routes(topic)
                assert hint is not None, topic
                want = b.router.match_routes(topic)
                assert sorted(map(tuple, hint)) == sorted(map(tuple, want)), topic
        finally:
            await node.stop()

    run(main())


def test_rule_cobatch_selected_by_hint():
    async def main():
        node = make_node()
        await node.start()
        try:
            b = node.broker
            ms = node.match_service
            hits = []
            node.rule_engine.create_rule(
                "r1", 'SELECT topic FROM "evt/+/fire"',
                actions=[lambda out, cols: hits.append(out["topic"])],
            )
            node.rule_engine.create_rule(
                "r2", 'SELECT topic FROM "other/#"', actions=[],
            )
            sub(b, "c1", "evt/#")
            assert await settle(lambda: ms_synced(node))
            await ms.prefetch("evt/z1/fire")
            assert ms.hint_rules("evt/z1/fire") == ["r1"]
            from emqx_tpu.broker.message import make_message

            b.publish(make_message("c9", "evt/z1/fire", b"!"))
            assert hits == ["evt/z1/fire"]
            # unregister: a stale hint may still NAME the dead rule (the
            # safe direction — the engine skips unknown ids), but the
            # rule must never fire again
            node.rule_engine.delete_rule("r1")
            assert await settle(lambda: ms_synced(node))
            await ms.prefetch("evt/z1/fire")
            b.publish(make_message("c9", "evt/z1/fire", b"!"))
            assert hits == ["evt/z1/fire"]  # unchanged: r1 never refired
        finally:
            await node.stop()

    run(main())


def test_bootstrap_refcounts_multiple_dests():
    """ADVICE r2 high 1: a filter bootstrapped with several live routes
    must survive the deletion of all but one of them."""

    async def main():
        node = make_node()
        b = node.broker
        sub(b, "c1", "m/+")
        sub(b, "c2", "m/+")
        await node.start()  # bootstrap sees 2 routes for m/+
        try:
            ms = node.match_service
            assert await settle(lambda: ms_synced(node))
            b.unsubscribe("c1", "m/+")
            assert await settle(lambda: ms_synced(node))
            assert ms.inc.n_filters == 1, "filter dropped while still routed"
            await ms.prefetch("m/1")
            hint = ms.hint_routes("m/1")
            assert hint is not None and len(hint) == 1
        finally:
            await node.stop()

    run(main())


def test_rule_registration_invalidates_hints():
    """ADVICE r2 medium: rule changes don't bump the router epoch; a
    hint minted before a rule registration must not claim 'no rules'."""

    async def main():
        node = make_node()
        await node.start()
        try:
            b = node.broker
            ms = node.match_service
            sub(b, "c1", "evt/#")
            assert await settle(lambda: ms_synced(node))
            await ms.prefetch("evt/x")
            assert ms.hint_rules("evt/x") == []
            hits = []
            node.rule_engine.create_rule(
                "r1", 'SELECT topic FROM "evt/+"',
                actions=[lambda out, cols: hits.append(out["topic"])],
            )
            # stale in the rules dimension now → engine host-matches
            assert ms.hint_rules("evt/x") is None
            from emqx_tpu.broker.message import make_message

            b.publish(make_message("p", "evt/x", b"!"))
            assert hits == ["evt/x"]
        finally:
            await node.stop()

    run(main())


def test_unsubscribe_prunes_mirror():
    async def main():
        node = make_node()
        await node.start()
        try:
            b = node.broker
            ms = node.match_service
            sub(b, "c1", "x/+")
            assert await settle(lambda: ms_synced(node))
            assert ms.inc.n_filters == 1
            b.unsubscribe("c1", "x/+")
            assert await settle(
                lambda: ms_synced(node) and ms.inc.n_filters == 0
            )
        finally:
            await node.stop()

    run(main())


def test_table_kind_selection_and_python_parity():
    """tpu.table=auto picks the native C++ table when buildable; the
    python twin passes the same storm (both serve identical hints)."""
    async def main():
        node_native = make_node()
        await node_native.start()
        try:
            ms = node_native.match_service
            assert ms is not None
            # this environment has the toolchain: auto => native
            assert ms.table_kind == "native"
        finally:
            await node_native.stop()

        node_py = make_node(**{"tpu.table": "python"})
        await node_py.start()
        try:
            ms = node_py.match_service
            assert ms is not None and ms.table_kind == "python"
            port = node_py.listeners.all()[0].port
            sub = Client(clientid="s", port=port)
            await sub.connect()
            await sub.subscribe("k/+/x")
            await settle(lambda: ms.dev.epoch == ms.inc.epoch)
            pub = Client(clientid="p", port=port)
            await pub.connect()
            await pub.publish("k/1/x", b"v")
            got = await sub.recv(timeout=5)
            assert (got.topic, got.payload) == ("k/1/x", b"v")
            await pub.disconnect()
            await sub.disconnect()
        finally:
            await node_py.stop()

    run(main())


def test_depth_bucketed_batch_parity():
    """A mixed-depth batch split across the shallow and full kernels
    produces the same hints as the host trie (split_min=1 pins the
    split on)."""
    async def main():
        node = make_node(**{"tpu.split_min": 1, "tpu.batch_size": 512})
        await node.start()
        try:
            ms = node.match_service
            assert ms is not None
            port = node.listeners.all()[0].port
            sub = Client(clientid="s", port=port)
            await sub.connect()
            for flt in ("a/+", "a/+/c/+/e", "deep/+/x/+/z/+/q", "#"):
                await sub.subscribe(flt)
            await settle(lambda: ms.dev.epoch == ms.inc.epoch)

            assert await settle(lambda: ms.ready, timeout=120)
            topics = ["a/b", "a/b/c/d/e", "deep/1/x/2/z/3/q", "nah",
                      "a/q", "deep/only"]
            # push one batch through the device loop directly
            futs = []
            loop = asyncio.get_running_loop()
            for t in topics:
                f = loop.create_future()
                futs.append(f)
                ms._pending.append((t, f))
            ms._batch_wake.set()
            # first compiles of BOTH kernel shapes can take a while on CPU
            assert await settle(
                lambda: all(f.done() for f in futs), timeout=180)
            from emqx_tpu import topic as T

            missing = 0
            for t in topics:
                hint = ms._hints.get(t)
                want = sorted(
                    f for f in ("a/+", "a/+/c/+/e", "deep/+/x/+/z/+/q", "#")
                    if T.match(t, f)
                )
                if hint is None:
                    missing += 1
                    continue
                assert sorted(hint[2]) == want, (t, hint[2], want)
            assert missing == 0, f"{missing} topics got no hint"
            # the split actually happened: 2 kernel batches for 1 wake
            assert node.observed.metrics.all().get(
                "tpu.match.batches", 0) >= 2
        finally:
            await node.stop()

    run(main())


def test_hint_cache_lru_eviction_no_thrash():
    """A working set just over hint_cap must not
    flip the cache between full and empty.  Eviction takes only the
    least-recently-served entries, so the hot head of a Zipf working
    set keeps its hints (and its device duty cycle) while the cold
    tail cycles through."""

    async def main():
        node = make_node()
        await node.start()
        try:
            b = node.broker
            ms = node.match_service
            ms.hint_cap = 24  # scaled-down 64k working-set scenario
            sub(b, "s", "room/+/k")
            assert await settle(lambda: ms_synced(node))

            hot = [f"room/h{i}/k" for i in range(8)]
            # warm the hot set and mark it served (moves to LRU tail)
            for t in hot:
                await ms.prefetch(t)
            for t in hot:
                assert ms.hint_routes(t) is not None

            served_hot = 0
            total_hot = 0
            for round_ in range(6):
                # a cold tail larger than the remaining capacity arrives,
                # interleaved with hot serves (Zipf: the hot head is hit
                # far more often than any one cold topic)
                cold = [f"room/c{round_}_{i}/k" for i in range(20)]
                for ci, t in enumerate(cold):
                    await ms.prefetch(t)
                    if ci % 4 == 3:
                        for h in hot:
                            total_hot += 1
                            if ms.hint_routes(h) is not None:
                                served_hot += 1
                # the cache never exceeds cap and never empties
                assert len(ms._hints) <= ms.hint_cap
                assert len(ms._hints) >= 8
            duty = served_hot / total_hot
            assert duty > 0.9, f"hot-set duty cycle {duty:.2f} thrashed"
            m = node.observed.metrics
            assert m.get("tpu.match.hint_evicted") >= 1
        finally:
            await node.stop()

    run(main())

def test_rules_only_hot_set_survives_lru_eviction():
    """`hint_rules` hits must refresh LRU recency
    exactly like `hint_routes` does — a rules-only working set (topics
    matched by rule FROM-filters but with no subscribers) is hot, and
    must not age out of the cache under a cold tail."""

    async def main():
        node = make_node()
        await node.start()
        try:
            b = node.broker
            ms = node.match_service
            ms.hint_cap = 24
            node.rule_engine.create_rule(
                "r1", 'SELECT topic FROM "room/+/k"', actions=[],
            )
            # a subscription on an unrelated branch keeps the table
            # non-empty without routing the hot topics
            sub(b, "s", "other/+")
            assert await settle(lambda: ms_synced(node))

            hot = [f"room/h{i}/k" for i in range(8)]
            for t in hot:
                await ms.prefetch(t)
            for t in hot:
                assert ms.hint_rules(t) == ["r1"]

            served_hot = 0
            total_hot = 0
            for round_ in range(6):
                cold = [f"room/c{round_}_{i}/k" for i in range(20)]
                for ci, t in enumerate(cold):
                    await ms.prefetch(t)
                    if ci % 4 == 3:
                        for h in hot:
                            total_hot += 1
                            if ms.hint_rules(h) is not None:
                                served_hot += 1
                assert len(ms._hints) <= ms.hint_cap
            duty = served_hot / total_hot
            assert duty > 0.9, \
                f"rules-only hot set duty cycle {duty:.2f} thrashed"
        finally:
            await node.stop()

    run(main())
