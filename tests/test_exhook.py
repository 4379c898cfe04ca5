"""ExHook boundary tests: gRPC HookProvider round trips against a live
broker, and the TPU match sidecar's mirror/batch paths.

Mirrors the reference's exhook suite shape (SURVEY.md §4: fake gRPC
HookProvider servers inside the suite — ``apps/emqx_exhook/test/`` runs
a demo provider the same way [U])."""

import asyncio

import grpc
import grpc.aio
import pytest

from emqx_tpu import topic as T
from emqx_tpu.client import Client
from emqx_tpu.config import Config
from emqx_tpu.exhook.rpc import (
    HookProviderStub,
    MirrorSyncStub,
    add_hook_provider_to_server,
    add_mirror_sync_to_server,
    pb,
)
from emqx_tpu.exhook.server import TpuMatchSidecar
from emqx_tpu.mqtt import packet as P
from emqx_tpu.node import BrokerNode


def run(coro):
    return asyncio.run(coro)


# ---------------------------------------------------------------------------
# harness
# ---------------------------------------------------------------------------


class DemoProvider:
    """Scriptable HookProvider: deny-lists + message rewrite + event log."""

    def __init__(
        self,
        hooks=("client.authenticate", "client.authorize", "message.publish",
               "session.subscribed", "session.unsubscribed",
               "client.connected", "client.disconnected"),
        deny_clientids=(),
        deny_topics=(),
        rewrite=None,  # (from_topic, to_topic)
        fail_methods=(),
    ):
        self.hooks = list(hooks)
        self.deny_clientids = set(deny_clientids)
        self.deny_topics = set(deny_topics)
        self.rewrite = rewrite
        self.fail_methods = set(fail_methods)
        self.events = []

    async def OnProviderLoaded(self, request, context):
        self.events.append(("loaded", request.meta.node))
        return pb.LoadedResponse(hooks=[pb.HookSpec(name=h) for h in self.hooks])

    async def OnProviderUnloaded(self, request, context):
        self.events.append(("unloaded",))
        return pb.EmptySuccess()

    async def OnClientAuthenticate(self, request, context):
        if "OnClientAuthenticate" in self.fail_methods:
            raise RuntimeError("scripted failure")
        deny = request.clientinfo.clientid in self.deny_clientids
        self.events.append(("auth", request.clientinfo.clientid, not deny))
        if deny:
            return pb.ValuedResponse(
                type=pb.ValuedResponse.STOP_AND_RETURN, bool_result=False
            )
        return pb.ValuedResponse(type=pb.ValuedResponse.CONTINUE)

    async def OnClientAuthorize(self, request, context):
        deny = request.topic in self.deny_topics
        self.events.append(
            ("authz", request.clientinfo.clientid, request.type,
             request.topic, not deny)
        )
        if deny:
            return pb.ValuedResponse(
                type=pb.ValuedResponse.STOP_AND_RETURN, bool_result=False
            )
        return pb.ValuedResponse(type=pb.ValuedResponse.CONTINUE)

    async def OnMessagePublish(self, request, context):
        self.events.append(("publish", request.message.topic))
        if self.rewrite and request.message.topic == self.rewrite[0]:
            m = pb.Message()
            m.CopyFrom(request.message)
            m.topic = self.rewrite[1]
            return pb.ValuedResponse(
                type=pb.ValuedResponse.STOP_AND_RETURN, message=m
            )
        return pb.ValuedResponse(type=pb.ValuedResponse.CONTINUE)

    async def OnClientConnected(self, request, context):
        self.events.append(("connected", request.clientinfo.clientid))
        return pb.EmptySuccess()

    async def OnClientDisconnected(self, request, context):
        self.events.append(("disconnected", request.clientinfo.clientid))
        return pb.EmptySuccess()

    async def OnSessionSubscribed(self, request, context):
        self.events.append(("subscribed", request.clientinfo.clientid,
                            request.topic))
        return pb.EmptySuccess()

    async def OnSessionUnsubscribed(self, request, context):
        self.events.append(("unsubscribed", request.clientinfo.clientid,
                            request.topic))
        return pb.EmptySuccess()


async def start_provider(servicer):
    server = grpc.aio.server()
    add_hook_provider_to_server(servicer, server)
    port = server.add_insecure_port("127.0.0.1:0")
    await server.start()
    return server, port


async def start_node_with_exhook(port, failure_action="ignore"):
    cfg = Config(
        file_text=(
            'listeners.tcp.default.bind = "127.0.0.1:0"\n'
            f'exhook.servers = "default=127.0.0.1:{port}"\n'
            'exhook.request_timeout = 2s\n'
            f'exhook.failure_action = {failure_action}\n'
        )
    )
    node = BrokerNode(cfg)
    await node.start()
    return node


def node_port(node):
    return node.listeners.all()[0].port


async def settle(pred, timeout=5.0, interval=0.02):
    """Await an eventually-true condition (async notify queues drain)."""
    deadline = asyncio.get_running_loop().time() + timeout
    while asyncio.get_running_loop().time() < deadline:
        if pred():
            return True
        await asyncio.sleep(interval)
    return pred()


def synced(sidecar):
    """Sidecar device mirror is serving AND caught up with the host
    table (answers reflect every mutation so far, not a stale prefix)."""
    return (
        sidecar._engine is not None
        and not sidecar._dirty.is_set()
        and sidecar._eng.dev.epoch == sidecar._eng.inc.epoch
    )


# ---------------------------------------------------------------------------
# broker-side manager: advisory verdicts
# ---------------------------------------------------------------------------


def test_authenticate_deny_refuses_connect():
    async def main():
        provider = DemoProvider(deny_clientids={"evil"})
        server, port = await start_provider(provider)
        node = await start_node_with_exhook(port)
        try:
            ok = Client(clientid="good", port=node_port(node))
            await ok.connect()
            await ok.disconnect()

            bad = Client(clientid="evil", port=node_port(node))
            with pytest.raises(Exception):
                await bad.connect()
        finally:
            await node.stop()
            await server.stop(None)

    run(main())


def test_authorize_deny_publish_and_subscribe():
    async def main():
        provider = DemoProvider(deny_topics={"forbidden/t"})
        server, port = await start_provider(provider)
        node = await start_node_with_exhook(port)
        try:
            sub = Client(clientid="s1", port=node_port(node), proto_ver=5)
            await sub.connect()
            # subscribe deny → SUBACK 0x87 for that filter only
            codes = await sub.subscribe("forbidden/t", qos=1)
            assert codes == [P.RC.NOT_AUTHORIZED]
            codes = await sub.subscribe("allowed/t", qos=1)
            assert codes == [1]

            pub = Client(clientid="p1", port=node_port(node), proto_ver=5)
            await pub.connect()
            # publish deny → PUBACK 0x87, message not routed
            rc = await pub.publish("forbidden/t", b"x", qos=1)
            assert rc == P.RC.NOT_AUTHORIZED
            await pub.publish("allowed/t", b"y", qos=1)
            msg = await sub.recv()
            assert (msg.topic, msg.payload) == ("allowed/t", b"y")
            await pub.disconnect()
            await sub.disconnect()
        finally:
            await node.stop()
            await server.stop(None)

    run(main())


def test_message_publish_rewrite():
    async def main():
        provider = DemoProvider(rewrite=("in/t", "out/t"))
        server, port = await start_provider(provider)
        node = await start_node_with_exhook(port)
        try:
            sub = Client(clientid="s1", port=node_port(node))
            await sub.connect()
            await sub.subscribe("out/#", qos=0)
            pub = Client(clientid="p1", port=node_port(node))
            await pub.connect()
            await pub.publish("in/t", b"m", qos=1)
            msg = await sub.recv()
            assert msg.topic == "out/t"
            await pub.disconnect()
            await sub.disconnect()
        finally:
            await node.stop()
            await server.stop(None)

    run(main())


def test_notification_events_stream():
    async def main():
        provider = DemoProvider()
        server, port = await start_provider(provider)
        node = await start_node_with_exhook(port)
        try:
            c = Client(clientid="c1", port=node_port(node))
            await c.connect()
            await c.subscribe("a/b", qos=0)
            await c.unsubscribe("a/b")
            await c.disconnect()
            assert await settle(
                lambda: ("connected", "c1") in provider.events
                and ("subscribed", "c1", "a/b") in provider.events
                and ("unsubscribed", "c1", "a/b") in provider.events
                and ("disconnected", "c1") in provider.events
            ), provider.events
        finally:
            await node.stop()
            await server.stop(None)

    run(main())


def test_failure_action_deny_vs_ignore():
    async def main():
        provider = DemoProvider(fail_methods={"OnClientAuthenticate"})
        server, port = await start_provider(provider)
        # ignore → fail-open, clients still connect
        node = await start_node_with_exhook(port, failure_action="ignore")
        try:
            c = Client(clientid="c1", port=node_port(node))
            await c.connect()
            await c.disconnect()
        finally:
            await node.stop()
        # deny → fail-closed, connect refused
        node = await start_node_with_exhook(port, failure_action="deny")
        try:
            c = Client(clientid="c2", port=node_port(node))
            with pytest.raises(Exception):
                await c.connect()
        finally:
            await node.stop()
            await server.stop(None)

    run(main())


def test_server_down_fails_open():
    async def main():
        # nothing listening on the port: load fails, broker runs normally
        node = await start_node_with_exhook(1)  # port 1: connection refused
        try:
            c = Client(clientid="c1", port=node_port(node))
            await c.connect()
            await c.subscribe("x", qos=0)
            await c.disconnect()
        finally:
            await node.stop()

    run(main())


def test_server_down_deny_policy_fails_closed_then_recovers():
    """failure_action=deny + unreachable server: advisory ops refused
    until the reconnect loop restores the server."""
    from emqx_tpu.exhook.manager import ExHookManager

    async def main():
        old = ExHookManager.RECONNECT_INTERVAL
        ExHookManager.RECONNECT_INTERVAL = 0.1
        provider = DemoProvider()
        # reserve a port, then kill the server so load fails
        server, port = await start_provider(provider)
        await server.stop(None)
        node = await start_node_with_exhook(port, failure_action="deny")
        try:
            c = Client(clientid="c1", port=node_port(node))
            with pytest.raises(Exception):
                await c.connect()  # fail-closed while server is down
            # bring a provider back on the same port; reconnect loop heals
            server2 = grpc.aio.server()
            add_hook_provider_to_server(provider, server2)
            assert server2.add_insecure_port(f"127.0.0.1:{port}") == port
            await server2.start()
            assert await settle(
                lambda: node.exhook.servers[0].stub is not None
            )
            c2 = Client(clientid="c2", port=node_port(node))
            await c2.connect()
            await c2.disconnect()
            await server2.stop(None)
        finally:
            ExHookManager.RECONNECT_INTERVAL = old
            await node.stop()

    run(main())


# ---------------------------------------------------------------------------
# TPU sidecar: mirror + batched device match
# ---------------------------------------------------------------------------


async def start_sidecar(**kw):
    sidecar = TpuMatchSidecar(**kw)
    server = grpc.aio.server()
    add_hook_provider_to_server(sidecar, server)
    add_mirror_sync_to_server(sidecar, server)
    port = server.add_insecure_port("127.0.0.1:0")
    await sidecar.start()
    await server.start()
    return server, sidecar, port


FILTERS = ["s/+/t", "s/#", "a/b", "+/b", "$SYS/x", "deep/+/x/#"]
TOPICS = ["s/1/t", "s/9/zz", "a/b", "$SYS/x", "nomatch/q", "deep/k/x/y/z"]


def test_sidecar_delta_feed_and_match_batch():
    async def main():
        server, sidecar, port = await start_sidecar(
            rebuild_debounce_s=0.01, batch_window_ms=1.0
        )
        chan = grpc.aio.insecure_channel(f"127.0.0.1:{port}")
        hooks = HookProviderStub(chan)
        mirror = MirrorSyncStub(chan)
        try:
            resp = await hooks.OnProviderLoaded(
                pb.ProviderLoadedRequest(meta=pb.RequestMeta(node="n1"))
            )
            names = [h.name for h in resp.hooks]
            assert "session.subscribed" in names and "message.publish" in names

            for flt in FILTERS:
                await hooks.OnSessionSubscribed(
                    pb.SessionSubscribedRequest(
                        clientinfo=pb.ClientInfo(clientid="c1"), topic=flt
                    )
                )
            assert await settle(lambda: synced(sidecar))

            resp = await mirror.MatchBatch(
                pb.MatchBatchRequest(topics=TOPICS)
            )
            # id resolution over the wire, as an external broker would
            ft = await mirror.FilterTable(pb.FilterTableRequest())
            assert ft.table_version == resp.table_version
            assert list(ft.filters) == sidecar.filter_table()
            table = list(ft.filters)
            for topic, row in zip(TOPICS, resp.results):
                got = sorted(table[i] for i in row.filter_ids)
                want = sorted(f for f in FILTERS if T.match(topic, f))
                assert got == want, (topic, got, want)

            # unsubscribe drops the filter from the mirror
            await hooks.OnSessionUnsubscribed(
                pb.SessionUnsubscribedRequest(
                    clientinfo=pb.ClientInfo(clientid="c1"), topic="a/b"
                )
            )
            assert await settle(
                lambda: "a/b" not in sidecar.filter_table()
            )

            stats = await mirror.Stats(pb.StatsRequest())
            assert stats.n_filters == len(FILTERS) - 1
            assert stats.batches >= 1
        finally:
            await chan.close()
            await sidecar.stop()
            await server.stop(None)

    run(main())


def test_sidecar_snapshot_install_and_publish_hook():
    async def main():
        server, sidecar, port = await start_sidecar(
            rebuild_debounce_s=0.01, annotate=True
        )
        chan = grpc.aio.insecure_channel(f"127.0.0.1:{port}")
        hooks = HookProviderStub(chan)
        mirror = MirrorSyncStub(chan)
        try:
            async def chunks():
                yield pb.SnapshotChunk(
                    epoch=7, filters=FILTERS[:3], refcounts=[1, 2, 1]
                )
                yield pb.SnapshotChunk(
                    epoch=7, filters=FILTERS[3:], refcounts=[1] * 3, last=True
                )

            ack = await mirror.InstallSnapshot(chunks())
            assert ack.epoch == 7 and ack.n_filters == len(FILTERS)
            assert await settle(lambda: synced(sidecar))

            resp = await hooks.OnMessagePublish(
                pb.MessagePublishRequest(
                    message=pb.Message(topic="s/1/t", payload=b"x")
                )
            )
            assert resp.type == pb.ValuedResponse.STOP_AND_RETURN
            want = len([f for f in FILTERS if T.match("s/1/t", f)])
            assert resp.message.headers["matched_filters"] == str(want)
        finally:
            await chan.close()
            await sidecar.stop()
            await server.stop(None)

    run(main())


def test_v311_suback_deny_uses_0x80():
    """3.1.1 only knows granted-QoS and 0x80 failure (spec §3.9.3)."""

    async def main():
        provider = DemoProvider(deny_topics={"forbidden/t"})
        server, port = await start_provider(provider)
        node = await start_node_with_exhook(port)
        try:
            c = Client(clientid="v3", port=node_port(node), proto_ver=4)
            await c.connect()
            codes = await c.subscribe("forbidden/t", qos=1)
            assert codes == [0x80]
            await c.disconnect()
        finally:
            await node.stop()
            await server.stop(None)

    run(main())


def test_topic_alias_cannot_bypass_authorize():
    """A denied aliased publish must not leak through via alias-only
    retries (the alias never registers because the packet never reaches
    the channel)."""

    async def main():
        provider = DemoProvider(deny_topics={"forbidden/t"})
        server, port = await start_provider(provider)
        node = await start_node_with_exhook(port)
        try:
            spy = Client(clientid="spy", port=node_port(node), proto_ver=5)
            await spy.connect()
            await spy.subscribe("#", qos=0)

            pub = Client(clientid="p1", port=node_port(node), proto_ver=5)
            await pub.connect()
            rc = await pub.publish(
                "forbidden/t", b"x", qos=1,
                properties={"Topic-Alias": 1},
            )
            assert rc == P.RC.NOT_AUTHORIZED
            # alias-only retry: unknown alias → channel drops the conn,
            # and nothing ever reaches the subscriber
            try:
                await pub.publish(
                    "", b"y", qos=1, properties={"Topic-Alias": 1},
                    timeout=2.0,
                )
            except Exception:
                pass
            with pytest.raises(asyncio.TimeoutError):
                await spy.recv(timeout=0.5)
            await spy.disconnect()
        finally:
            await node.stop()
            await server.stop(None)

    run(main())


def test_shared_sub_filter_stripped_for_mirror():
    """session.subscribed events carry the routing filter — $share/<g>/
    stripped — so the sidecar mirror can actually match topics."""

    async def main():
        server, sidecar, port = await start_sidecar(rebuild_debounce_s=0.01)
        node = await start_node_with_exhook(port)
        try:
            c = Client(clientid="c1", port=node_port(node), proto_ver=5)
            await c.connect()
            await c.subscribe("$share/g1/room/+/temp", qos=0)
            assert await settle(
                lambda: "room/+/temp" in sidecar.filter_table()
            ), sidecar.filter_table()
            await c.unsubscribe("$share/g1/room/+/temp")
            assert await settle(
                lambda: "room/+/temp" not in sidecar.filter_table()
            )
            await c.disconnect()
        finally:
            await node.stop()
            await sidecar.stop()
            await server.stop(None)

    run(main())


def test_sidecar_deep_filters_merge_host_side():
    """Filters deeper than the device table depth still match (served
    from the host trie and merged into device results)."""

    async def main():
        server, sidecar, port = await start_sidecar(
            rebuild_debounce_s=0.01, depth=4
        )
        chan = grpc.aio.insecure_channel(f"127.0.0.1:{port}")
        hooks = HookProviderStub(chan)
        mirror = MirrorSyncStub(chan)
        try:
            deep = "a/b/c/d/e/+/g"          # 7 levels > depth 4
            shallow = "a/#"
            for flt in (deep, shallow):
                await hooks.OnSessionSubscribed(
                    pb.SessionSubscribedRequest(
                        clientinfo=pb.ClientInfo(clientid="c1"), topic=flt
                    )
                )
            assert await settle(lambda: synced(sidecar))
            topics = ["a/b/c/d/e/f/g", "a/x"]
            resp = await mirror.MatchBatch(pb.MatchBatchRequest(topics=topics))
            table = sidecar.filter_table()
            got = [sorted(table[i] for i in r.filter_ids)
                   for r in resp.results]
            assert got[0] == sorted([deep, shallow]), got
            assert got[1] == [shallow], got
        finally:
            await chan.close()
            await sidecar.stop()
            await server.stop(None)

    run(main())


def test_broker_feeds_sidecar_mirror_end_to_end():
    """BrokerNode → exhook → sidecar: real subscribe events populate the
    mirror; OnMessagePublish rides the micro-batch loop."""

    async def main():
        server, sidecar, port = await start_sidecar(
            rebuild_debounce_s=0.01, batch_window_ms=0.5
        )
        node = await start_node_with_exhook(port)
        try:
            c = Client(clientid="c1", port=node_port(node))
            await c.connect()
            await c.subscribe("room/+/temp", qos=0)
            assert await settle(
                lambda: "room/+/temp" in sidecar.filter_table()
            )
            # wait for the device engine so the publish rides the counted
            # micro-batch path, not the host fail-open fallback
            assert await settle(lambda: synced(sidecar))
            await c.publish("room/7/temp", b"21.5")
            msg = await c.recv()
            assert msg.payload == b"21.5"
            assert await settle(lambda: sidecar.topics_matched >= 1)
            await c.disconnect()
        finally:
            await node.stop()
            await sidecar.stop()
            await server.stop(None)

    run(main())


def test_sidecar_overflow_fails_open_to_host_trie():
    """Force active-set overflow (A=2, heavy '+' fan-in) and match-count
    overflow (K=4): spilled rows must be re-run on the host trie so the
    combined answer is exactly the oracle's."""

    async def main():
        server, sidecar, port = await start_sidecar(
            rebuild_debounce_s=0.01, active_slots=2, max_matches=4
        )
        chan = grpc.aio.insecure_channel(f"127.0.0.1:{port}")
        hooks = HookProviderStub(chan)
        mirror = MirrorSyncStub(chan)
        try:
            # 8 filters all matching a/b/c with distinct prefixes ⇒ the
            # active set needs >2 slots and the row matches >4 filters
            flts = (
                ["a/b/c", "+/b/c", "a/+/c", "a/b/+", "+/+/c", "a/+/+",
                 "+/b/+", "+/+/+", "a/#", "#"]
            )
            for flt in flts:
                await hooks.OnSessionSubscribed(
                    pb.SessionSubscribedRequest(
                        clientinfo=pb.ClientInfo(clientid="c1"), topic=flt
                    )
                )
            assert await settle(lambda: synced(sidecar))
            topics = ["a/b/c", "z/b/c", "none"]
            resp = await mirror.MatchBatch(pb.MatchBatchRequest(topics=topics))
            table = sidecar.filter_table()
            for topic, row in zip(topics, resp.results):
                got = sorted(table[i] for i in row.filter_ids)
                want = sorted(f for f in flts if T.match(topic, f))
                assert got == want, (topic, got, want)
            assert sidecar.spill_fallbacks >= 1  # the fail-open path ran
            stats = await mirror.Stats(pb.StatsRequest())
            assert int(stats.extra["spill_fallbacks"]) >= 1
        finally:
            await chan.close()
            await sidecar.stop()
            await server.stop(None)

    run(main())


def test_sidecar_incremental_no_reupload_under_churn():
    """Steady-state filter churn must ride the delta path: no device
    re-uploads, no table rebuilds."""

    async def main():
        server, sidecar, port = await start_sidecar(rebuild_debounce_s=0.005)
        chan = grpc.aio.insecure_channel(f"127.0.0.1:{port}")
        hooks = HookProviderStub(chan)
        mirror = MirrorSyncStub(chan)
        try:
            for i in range(64):
                await hooks.OnSessionSubscribed(
                    pb.SessionSubscribedRequest(
                        clientinfo=pb.ClientInfo(clientid="c"),
                        topic=f"base/{i}/+",
                    )
                )
            assert await settle(lambda: synced(sidecar))
            uploads0 = sidecar._eng.dev.uploads
            for i in range(40):
                await hooks.OnSessionSubscribed(
                    pb.SessionSubscribedRequest(
                        clientinfo=pb.ClientInfo(clientid="c"),
                        topic=f"churn/{i}",
                    )
                )
                if i % 2:
                    await hooks.OnSessionUnsubscribed(
                        pb.SessionUnsubscribedRequest(
                            clientinfo=pb.ClientInfo(clientid="c"),
                            topic=f"churn/{i}",
                        )
                    )
            assert await settle(
                lambda: not sidecar._dirty.is_set()
                and sidecar._eng.dev.epoch == sidecar._eng.inc.epoch
            )
            assert sidecar._eng.dev.uploads == uploads0
            assert sidecar._eng.dev.delta_applies >= 1
            resp = await mirror.MatchBatch(
                pb.MatchBatchRequest(topics=["churn/2", "base/3/x"])
            )
            table = sidecar.filter_table()
            assert sorted(
                table[i] for i in resp.results[0].filter_ids
            ) == ["churn/2"]
            assert sorted(
                table[i] for i in resp.results[1].filter_ids
            ) == ["base/3/+"]
        finally:
            await chan.close()
            await sidecar.stop()
            await server.stop(None)

    run(main())
