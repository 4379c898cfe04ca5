"""Multi-node cluster tests on one host — the reference's CT
slave/peer-node pattern (SURVEY.md §4): several broker nodes over
loopback with real route replication, forwarding, takeover, and
nodedown handling."""

import asyncio

import pytest

from emqx_tpu.client import Client
from emqx_tpu.config import Config
from emqx_tpu.node import BrokerNode


def run(coro):
    return asyncio.run(coro)


async def start_cluster_node(name, seeds="", extra="", **over):
    cfg = Config(
        file_text=(
            f'node.name = "{name}"\n'
            'listeners.tcp.default.bind = "127.0.0.1:0"\n'
            'cluster.enable = true\n'
            'cluster.listen = "127.0.0.1:0"\n'
            f'cluster.seeds = "{seeds}"\n'
            'cluster.heartbeat_interval = 200ms\n'
            'cluster.node_timeout = 1500ms\n'
            + extra
        )
    )
    node = BrokerNode(cfg)
    await node.start()
    # speed the delta sync for tests
    node.cluster.SYNC_INTERVAL = 0.02
    node.cluster.RECONNECT_INTERVAL = 0.3
    node.cluster.durable.SYNC_INTERVAL = 0.05
    return node


def mqtt_port(node):
    return node.listeners.all()[0].port


def cluster_addr(node):
    return f"127.0.0.1:{node.cluster.listen_port}"


async def settle(pred, timeout=5.0, interval=0.02):
    deadline = asyncio.get_running_loop().time() + timeout
    while asyncio.get_running_loop().time() < deadline:
        if pred():
            return True
        await asyncio.sleep(interval)
    return pred()


async def peered(a, b):
    return await settle(
        lambda: b.cluster.name in a.cluster.peers
        and a.cluster.peers[b.cluster.name].up
        and a.cluster.name in b.cluster.peers
        and b.cluster.peers[a.cluster.name].up
    )


# ---------------------------------------------------------------------------


def test_two_node_route_replication_and_forwarding():
    async def main():
        n1 = await start_cluster_node("n1@test")
        n2 = await start_cluster_node("n2@test", seeds=cluster_addr(n1))
        try:
            assert await peered(n1, n2)

            sub = Client(clientid="s1", port=mqtt_port(n1))
            await sub.connect()
            await sub.subscribe("t/+/x", qos=1)
            # the wildcard route must replicate to n2
            assert await settle(
                lambda: n2.broker.router.has_route("t/+/x", "n1@test")
            )

            pub = Client(clientid="p1", port=mqtt_port(n2))
            await pub.connect()
            await pub.publish("t/a/x", b"cross", qos=1)
            msg = await sub.recv()
            assert (msg.topic, msg.payload) == ("t/a/x", b"cross")

            # unsubscribe removes the replicated route
            await sub.unsubscribe("t/+/x")
            assert await settle(
                lambda: not n2.broker.router.has_route("t/+/x", "n1@test")
            )
            await pub.disconnect()
            await sub.disconnect()
        finally:
            await n2.stop()
            await n1.stop()

    run(main())


def test_late_join_bootstraps_routes():
    async def main():
        n1 = await start_cluster_node("n1@test")
        sub = Client(clientid="s1", port=mqtt_port(n1))
        await sub.connect()
        await sub.subscribe("pre/existing/#", qos=0)
        # n2 joins AFTER the subscription exists: snapshot bootstrap
        n2 = await start_cluster_node("n2@test", seeds=cluster_addr(n1))
        try:
            assert await peered(n1, n2)
            assert await settle(
                lambda: n2.broker.router.has_route("pre/existing/#", "n1@test")
            )
            pub = Client(clientid="p1", port=mqtt_port(n2))
            await pub.connect()
            await pub.publish("pre/existing/topic", b"boot")
            msg = await sub.recv()
            assert msg.payload == b"boot"
            await pub.disconnect()
            await sub.disconnect()
        finally:
            await n2.stop()
            await n1.stop()

    run(main())


def test_shared_subscription_across_nodes():
    async def main():
        n1 = await start_cluster_node("n1@test")
        n2 = await start_cluster_node("n2@test", seeds=cluster_addr(n1))
        try:
            assert await peered(n1, n2)
            a = Client(clientid="a", port=mqtt_port(n1))
            b = Client(clientid="b", port=mqtt_port(n2))
            await a.connect()
            await b.connect()
            await a.subscribe("$share/g/load/t", qos=0)
            await b.subscribe("$share/g/load/t", qos=0)
            assert await settle(
                lambda: n1.broker.router.has_route("load/t", ("g", "n2@test"))
                and n2.broker.router.has_route("load/t", ("g", "n1@test"))
            )
            pub = Client(clientid="p", port=mqtt_port(n1))
            await pub.connect()
            n = 20
            for i in range(n):
                await pub.publish("load/t", f"m{i}".encode())
            # every message delivered exactly once across the group
            got = []

            async def drain(c):
                try:
                    while True:
                        got.append((await c.recv(timeout=0.5)).payload)
                except asyncio.TimeoutError:
                    pass

            await drain(a)
            await drain(b)
            assert sorted(got) == sorted(f"m{i}".encode() for i in range(n))
            await pub.disconnect()
            await a.disconnect()
            await b.disconnect()
        finally:
            await n2.stop()
            await n1.stop()

    run(main())


def test_session_takeover_across_nodes():
    async def main():
        n1 = await start_cluster_node("n1@test")
        n2 = await start_cluster_node("n2@test", seeds=cluster_addr(n1))
        try:
            assert await peered(n1, n2)
            c1 = Client(clientid="roam", port=mqtt_port(n1), proto_ver=5,
                        clean_start=False,
                        properties={"Session-Expiry-Interval": 300})
            await c1.connect()
            await c1.subscribe("offline/q", qos=1)
            await c1.disconnect()
            # registry replicated: n2 knows n1 owns 'roam'
            assert await settle(
                lambda: n2.cluster.owner_of("roam") == "n1@test"
            )
            # a message lands while the client is away → queued on n1
            pub = Client(clientid="p", port=mqtt_port(n1))
            await pub.connect()
            await pub.publish("offline/q", b"while-away", qos=1)
            await pub.disconnect()

            # reconnect on the OTHER node with clean_start=False
            c2 = Client(clientid="roam", port=mqtt_port(n2), proto_ver=5,
                        clean_start=False)
            ack = await c2.connect()
            assert ack.session_present
            msg = await c2.recv()
            assert msg.payload == b"while-away"
            # session now lives on n2; old node dropped it
            assert await settle(lambda: "roam" not in n1.broker.sessions)
            assert "roam" in n2.broker.sessions
            # replication is eventually consistent: wait for n1 to learn
            # the migrated route before publishing through it
            assert await settle(
                lambda: n1.broker.router.has_route("offline/q", "n2@test")
            )
            pub2 = Client(clientid="p2", port=mqtt_port(n1))
            await pub2.connect()
            await pub2.publish("offline/q", b"after-move", qos=1)
            msg = await c2.recv()
            assert msg.payload == b"after-move"
            await pub2.disconnect()
            await c2.disconnect()
        finally:
            await n2.stop()
            await n1.stop()

    run(main())


def test_nodedown_purges_routes():
    async def main():
        n1 = await start_cluster_node("n1@test")
        n2 = await start_cluster_node("n2@test", seeds=cluster_addr(n1))
        try:
            assert await peered(n1, n2)
            sub = Client(clientid="s1", port=mqtt_port(n2))
            await sub.connect()
            await sub.subscribe("dying/#", qos=0)
            assert await settle(
                lambda: n1.broker.router.has_route("dying/#", "n2@test")
            )
            # hard-stop n2 (no Leave: simulates a crash) → n1 times it out
            n2.cluster._running = False
            for t in n2.cluster._tasks:
                t.cancel()
            for peer in n2.cluster.peers.values():
                if peer.conn is not None:
                    peer.conn.close()
            await n2.cluster._server.stop()
            assert await settle(
                lambda: not n1.broker.router.has_route("dying/#", "n2@test"),
                timeout=8.0,
            )
            # publishing on n1 must not crash with the peer gone
            pub = Client(clientid="p", port=mqtt_port(n1))
            await pub.connect()
            await pub.publish("dying/t", b"x")
            await pub.disconnect()
        finally:
            await n2.stop()
            await n1.stop()

    run(main())


def test_hello_rejected_on_name_conflict():
    async def main():
        n1 = await start_cluster_node("same@test")
        n2 = await start_cluster_node("same@test", seeds=cluster_addr(n1))
        try:
            await asyncio.sleep(0.5)
            assert "same@test" not in n1.cluster.peers
            assert not any(p.up for p in n2.cluster.peers.values())
        finally:
            await n2.stop()
            await n1.stop()

    run(main())


def test_cluster_config_sync_two_phase():
    """emqx_conf analog: a validated config put on node A applies on
    node B; a joiner adopts runtime overrides from the snapshot; local
    validation failure broadcasts nothing."""
    async def main():
        n1 = await start_cluster_node("cs1@test")
        n2 = await start_cluster_node("cs2@test", seeds=cluster_addr(n1))
        try:
            assert await peered(n1, n2)

            n1.config.put("mqtt.max_inflight", 7)
            assert await settle(
                lambda: n2.config.get("mqtt.max_inflight") == 7)

            # B -> A direction too
            n2.config.put("flapping_detect.max_count", 42)
            assert await settle(
                lambda: n1.config.get("flapping_detect.max_count") == 42)

            # invalid value: rejected locally, nothing broadcast
            with pytest.raises(Exception):
                n1.config.put("mqtt.max_inflight", "not-a-number")
            await asyncio.sleep(0.1)
            assert n2.config.get("mqtt.max_inflight") == 7

            # a NEW joiner adopts the overrides via snapshot bootstrap
            n3 = await start_cluster_node("cs3@test",
                                          seeds=cluster_addr(n1))
            try:
                assert await settle(
                    lambda: n3.config.get("mqtt.max_inflight") == 7
                    and n3.config.get("flapping_detect.max_count") == 42)
            finally:
                await n3.stop()
        finally:
            await n2.stop()
            await n1.stop()

    run(main())


def test_config_sync_survives_origin_restart():
    """A restarted node's config updates must not be discarded by peers
    holding the previous life's txn high-water mark."""
    async def main():
        n1 = await start_cluster_node("cr1@test")
        n2 = await start_cluster_node("cr2@test", seeds=cluster_addr(n1))
        try:
            assert await peered(n1, n2)
            for i in range(3):
                n1.config.put("mqtt.max_inflight", 10 + i)
            assert await settle(
                lambda: n2.config.get("mqtt.max_inflight") == 12)

            name = "cr1@test"
            await n1.stop()
            # same node name rejoins with a fresh Cluster instance
            n1b = await start_cluster_node(name, seeds=cluster_addr(n2))
            try:
                assert await peered(n1b, n2)
                n1b.config.put("mqtt.max_inflight", 99)
                assert await settle(
                    lambda: n2.config.get("mqtt.max_inflight") == 99)
            finally:
                await n1b.stop()
        finally:
            await n2.stop()

    run(main())


def test_retained_replicates_and_survives_node_loss():
    """Retained half: a retained message stored on
    node A is replicated into B's OWN retainer (emqx_retainer_mnesia
    replicated-table semantics) and still serves subscribe-replay on B
    after A dies."""

    async def main():
        n1 = await start_cluster_node("n1@test")
        n2 = await start_cluster_node("n2@test", seeds=cluster_addr(n1))
        try:
            assert await peered(n1, n2)
            pub = Client(clientid="rp", port=mqtt_port(n1))
            await pub.connect()
            await pub.publish("cfg/device/9", b"retained-cfg", retain=True)
            await pub.disconnect()
            # live replication into n2's local retainer
            assert await settle(
                lambda: n2.retainer.get("cfg/device/9") is not None
            )
            await n1.stop()     # A dies

            sub = Client(clientid="rs", port=mqtt_port(n2))
            await sub.connect()
            await sub.subscribe("cfg/+/9")
            msg = await sub.recv()
            assert (msg.topic, msg.payload, msg.retain) == \
                ("cfg/device/9", b"retained-cfg", True)
            await sub.disconnect()
        finally:
            await n2.stop()
            try:
                await n1.stop()
            except Exception:
                pass

    run(main())


def test_retained_delete_propagates_tombstone():
    """An empty-payload retained delete on A removes the topic from B's
    replica and a tombstone blocks resurrection via snapshot merge."""

    async def main():
        n1 = await start_cluster_node("n1@test")
        n2 = await start_cluster_node("n2@test", seeds=cluster_addr(n1))
        try:
            assert await peered(n1, n2)
            pub = Client(clientid="rp", port=mqtt_port(n1))
            await pub.connect()
            await pub.publish("gone/soon", b"x", retain=True)
            assert await settle(
                lambda: n2.retainer.get("gone/soon") is not None)
            await pub.publish("gone/soon", b"", retain=True)  # delete
            assert await settle(lambda: n2.retainer.get("gone/soon") is None)
            assert n2.cluster.durable._retain_tombstones.get("gone/soon")
            await pub.disconnect()
        finally:
            await n2.stop()
            await n1.stop()

    run(main())


def test_durable_session_promoted_after_node_loss():
    """Session half: a persistent session created on
    A — subscriptions and queued QoS1 messages — is promoted from B's
    replica when A dies and the client reconnects to B."""

    async def main():
        n1 = await start_cluster_node("n1@test")
        n2 = await start_cluster_node("n2@test", seeds=cluster_addr(n1))
        try:
            assert await peered(n1, n2)
            c1 = Client(clientid="phoenix", port=mqtt_port(n1), proto_ver=5,
                        clean_start=False,
                        properties={"Session-Expiry-Interval": 300})
            await c1.connect()
            await c1.subscribe("dr/q", qos=1)
            await c1.disconnect()

            # wait for the route so a publish via n2 forwards to n1
            assert await settle(
                lambda: n2.broker.router.has_route("dr/q", "n1@test"))
            # a message lands while the client is away -> queued on n1
            pub = Client(clientid="p", port=mqtt_port(n2))
            await pub.connect()
            await pub.publish("dr/q", b"while-away", qos=1)
            await pub.disconnect()
            # the replica on n2 must include the queued message
            assert await settle(
                lambda: "phoenix" in n2.cluster.durable.session_replicas
                and (n2.cluster.durable.session_replicas["phoenix"][1]
                     .get("pending"))
            )
            await n1.stop()     # owner dies

            c2 = Client(clientid="phoenix", port=mqtt_port(n2), proto_ver=5,
                        clean_start=False)
            ack = await c2.connect()
            assert ack.session_present, "replica promotion lost the session"
            msg = await c2.recv()
            assert msg.payload == b"while-away"
            assert n2.cluster.durable.promotions == 1
            # the promoted session is live on n2: new publishes deliver
            pub2 = Client(clientid="p2", port=mqtt_port(n2))
            await pub2.connect()
            await pub2.publish("dr/q", b"after-failover", qos=1)
            msg = await c2.recv()
            assert msg.payload == b"after-failover"
            await pub2.disconnect()
            await c2.disconnect()
        finally:
            await n2.stop()
            try:
                await n1.stop()
            except Exception:
                pass

    run(main())


def test_clean_start_discards_replica():
    """A clean-start reconnect after owner death discards the replica
    instead of resurrecting old state."""

    async def main():
        n1 = await start_cluster_node("n1@test")
        n2 = await start_cluster_node("n2@test", seeds=cluster_addr(n1))
        try:
            assert await peered(n1, n2)
            c1 = Client(clientid="fresh", port=mqtt_port(n1), proto_ver=5,
                        clean_start=False,
                        properties={"Session-Expiry-Interval": 300})
            await c1.connect()
            await c1.subscribe("cs/q", qos=1)
            await c1.disconnect()
            assert await settle(
                lambda: "fresh" in n2.cluster.durable.session_replicas)
            await n1.stop()

            c2 = Client(clientid="fresh", port=mqtt_port(n2), proto_ver=5,
                        clean_start=True)
            ack = await c2.connect()
            assert not ack.session_present
            assert "fresh" not in n2.cluster.durable.session_replicas
            assert n2.cluster.durable.promotions == 0
            await c2.disconnect()
        finally:
            await n2.stop()
            try:
                await n1.stop()
            except Exception:
                pass

    run(main())


def test_replica_promotion_survives_full_restart(tmp_path):
    """The replica table is persisted: B restarts AFTER A died and can
    STILL promote A's durable session from its disk copy."""

    async def main():
        n1 = await start_cluster_node("n1@test")
        n2 = await start_cluster_node(
            "n2@test", seeds=cluster_addr(n1),
            extra=f'node.data_dir = "{tmp_path}/n2"\n')
        try:
            assert await peered(n1, n2)
            c1 = Client(clientid="lazarus", port=mqtt_port(n1), proto_ver=5,
                        clean_start=False,
                        properties={"Session-Expiry-Interval": 300})
            await c1.connect()
            await c1.subscribe("fr/q", qos=1)
            await c1.disconnect()
            assert await settle(
                lambda: "lazarus" in n2.cluster.durable.session_replicas)
            await n1.stop()
            await n2.stop()    # flushes session_replicas to disk

            n2b = await start_cluster_node(
                "n2@test",
                extra=f'node.data_dir = "{tmp_path}/n2"\n')
            try:
                assert "lazarus" in n2b.cluster.durable.session_replicas
                c2 = Client(clientid="lazarus", port=mqtt_port(n2b),
                            proto_ver=5, clean_start=False)
                ack = await c2.connect()
                assert ack.session_present
                assert "fr/q" in n2b.broker.sessions["lazarus"].subscriptions
                await c2.disconnect()
            finally:
                await n2b.stop()
        finally:
            try:
                await n1.stop()
            except Exception:
                pass

    run(main())


def test_reuseport_shared_port_across_cluster_nodes():
    """SO_REUSEPORT connection-plane scale-out: two
    clustered broker nodes bind the SAME MQTT port; the kernel spreads
    accepted connections across them and cross-node routing makes
    placement transparent to clients."""

    async def main():
        extra = 'listeners.tcp.default.reuse_port = true\n'
        n1 = await start_cluster_node("n1@test", extra=extra)
        port = mqtt_port(n1)
        n2 = await start_cluster_node(
            "n2@test", seeds=cluster_addr(n1),
            extra=extra + f'listeners.tcp.default.bind = "127.0.0.1:{port}"\n')
        try:
            assert await peered(n1, n2)
            assert mqtt_port(n2) == port
            # enough clients that the kernel hash lands on both sockets
            clients = []
            for i in range(24):
                c = Client(clientid=f"rp{i}", port=port)
                await c.connect()
                clients.append(c)
            placed1 = len(n1.connections)
            placed2 = len(n2.connections)
            assert placed1 + placed2 == 24
            assert placed1 > 0 and placed2 > 0, (
                f"kernel placed all connections on one node "
                f"({placed1}/{placed2}); reuse_port not balancing")
            # pub/sub across whatever placement happened: wait until the
            # NON-owning node learns the route toward rp0's actual home
            owner = "n1@test" if "rp0" in n1.connections else "n2@test"
            other = n2 if owner == "n1@test" else n1
            await clients[0].subscribe("rp/t", qos=1)
            assert await settle(
                lambda: other.broker.router.has_route("rp/t", owner))
            # publish from every other client: all must arrive
            for i in range(1, 24):
                await clients[i].publish("rp/t", f"m{i}".encode(), qos=1)
            got = set()
            for _ in range(23):
                got.add((await clients[0].recv(timeout=5)).payload)
            assert got == {f"m{i}".encode() for i in range(1, 24)}
            for c in clients:
                await c.disconnect()
        finally:
            await n2.stop()
            await n1.stop()

    run(main())
