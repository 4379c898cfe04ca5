"""Durable storage tests: store crash-tolerance, node restart
persistence, data export/import, NFA checkpoint parity
(SURVEY.md §5.4)."""

import asyncio
import base64
import json
import os

import pytest

from emqx_tpu import topic as T
from emqx_tpu.client import Client
from emqx_tpu.config import Config
from emqx_tpu.node import BrokerNode
from emqx_tpu.storage import (
    Store,
    export_data,
    import_data,
    load_table,
    save_table,
)


def run(coro):
    return asyncio.run(coro)


# ---------------------------------------------------------------------------
# store engine
# ---------------------------------------------------------------------------


def test_table_put_delete_reload(tmp_path):
    s = Store(str(tmp_path))
    t = s.table("t1")
    t.put("a", {"x": 1})
    t.put("b", {"y": [1, 2]})
    t.delete("a")
    s.close()

    s2 = Store(str(tmp_path))
    t2 = s2.table("t1")
    assert t2.get("a") is None
    assert t2.get("b") == {"y": [1, 2]}
    assert len(t2) == 1
    s2.close()


def test_table_survives_torn_tail_write(tmp_path):
    s = Store(str(tmp_path))
    t = s.table("t1")
    for i in range(5):
        t.put(f"k{i}", i)
    # simulate a crash mid-append: garbage tail in the wal
    wal = os.path.join(str(tmp_path), "t1", "wal.jsonl")
    with open(wal, "a") as f:
        f.write('{"op":"put","k":"k9","v"')  # torn record
    s2 = Store(str(tmp_path))
    t2 = s2.table("t1")
    assert t2.get("k4") == 4 and "k9" not in t2
    s2.close()


def test_wal_kill9_recovers_acked_writes(tmp_path):
    """Durability bound: with per-append fsync, every
    write acknowledged before a SIGKILL must survive recovery."""
    import subprocess
    import sys

    prog = (
        "import os, sys\n"
        "from emqx_tpu.storage.store import Table\n"
        "t = Table(sys.argv[1])\n"
        "for i in range(50):\n"
        "    t.put(f'k{i}', i)\n"
        "    print(f'k{i}', flush=True)\n"
        "    if i == 37:\n"
        "        os.kill(os.getpid(), 9)\n"
    )
    p = subprocess.run(
        [sys.executable, "-c", prog, str(tmp_path / "tbl")],
        capture_output=True, text=True, timeout=60,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    acked = [ln for ln in p.stdout.split() if ln]
    assert p.returncode != 0 and len(acked) >= 1  # died by SIGKILL
    from emqx_tpu.storage.store import Table

    t2 = Table(str(tmp_path / "tbl"))
    for k in acked:
        assert k in t2, f"acked write {k} lost after kill -9"


def test_table_compaction(tmp_path):
    s = Store(str(tmp_path))
    t = s.table("t1")
    for i in range(500):
        t.put("hot", i)  # same key: wal grows, data stays size 1
    assert t._wal_records < 500  # compaction kicked in
    assert t.get("hot") == 499
    s.close()
    s2 = Store(str(tmp_path))
    assert s2.table("t1").get("hot") == 499
    s2.close()


# ---------------------------------------------------------------------------
# node persistence across restart
# ---------------------------------------------------------------------------


async def start_node(tmp_path, extra=""):
    cfg = Config(
        file_text=(
            'listeners.tcp.default.bind = "127.0.0.1:0"\n'
            f'node.data_dir = "{tmp_path}/data"\n'
            'durable_storage.sync_interval = 100ms\n'
            + extra
        )
    )
    node = BrokerNode(cfg)
    await node.start()
    return node


def mqtt_port(node):
    return node.listeners.all()[0].port


def test_node_restart_restores_state(tmp_path):
    async def main():
        node = await start_node(tmp_path)
        c = Client(clientid="keeper", port=mqtt_port(node), proto_ver=5,
                   clean_start=False,
                   properties={"Session-Expiry-Interval": 600})
        await c.connect()
        await c.subscribe("stay/+", qos=1)
        await c.disconnect()
        pub = Client(clientid="p", port=mqtt_port(node))
        await pub.connect()
        await pub.publish("retain/me", b"sticky", qos=1, retain=True)
        # queued while away
        await pub.publish("stay/x", b"queued", qos=1)
        await pub.disconnect()
        node.banned.add("clientid", "villain", reason="test")
        await node.stop()  # final sync

        node2 = await start_node(tmp_path)
        try:
            # banned + retained survive
            assert any(e.who == "villain" for e in node2.banned.list())
            assert node2.retainer.match("retain/me")[0].payload == b"sticky"
            # session + subscriptions + queued message survive
            sess = node2.broker.sessions.get("keeper")
            assert sess is not None and "stay/+" in sess.subscriptions
            c2 = Client(clientid="keeper", port=mqtt_port(node2),
                        proto_ver=5, clean_start=False)
            ack = await c2.connect()
            assert ack.session_present
            msg = await c2.recv()
            assert msg.payload == b"queued"
            await c2.disconnect()
        finally:
            await node2.stop()

    run(main())


def test_delayed_messages_survive_restart(tmp_path):
    async def main():
        node = await start_node(tmp_path)
        sub_cfg_port = mqtt_port(node)
        pub = Client(clientid="p", port=sub_cfg_port)
        await pub.connect()
        await pub.publish("$delayed/2/later/t", b"tick", qos=1)
        await pub.disconnect()
        assert len(node.delayed) == 1
        await node.stop()

        node2 = await start_node(tmp_path)
        try:
            assert len(node2.delayed) == 1
            sub = Client(clientid="s", port=mqtt_port(node2))
            await sub.connect()
            await sub.subscribe("later/t", qos=0)
            msg = await sub.recv(timeout=5.0)
            assert msg.payload == b"tick"
            await sub.disconnect()
        finally:
            await node2.stop()

    run(main())


def test_v311_persistent_session_not_swept(tmp_path):
    """3.1.1 clean_session=0 sessions have no expiry on the wire; the
    configured default applies, not immediate expiry."""

    async def main():
        node = await start_node(tmp_path)
        try:
            c = Client(clientid="v3keep", port=mqtt_port(node),
                       proto_ver=4, clean_start=False)
            await c.connect()
            await c.subscribe("v3/t", qos=1)
            await c.disconnect()
            sess = node.broker.sessions["v3keep"]
            assert sess.expiry_interval == 7200.0  # configured default
            await asyncio.sleep(1.5)  # past a sweep cycle
            assert "v3keep" in node.broker.sessions
        finally:
            await node.stop()

    run(main())


def test_kick_evicts_offline_durable_session(tmp_path):
    async def main():
        node = await start_node(tmp_path)
        try:
            c = Client(clientid="ghost", port=mqtt_port(node), proto_ver=5,
                       clean_start=False,
                       properties={"Session-Expiry-Interval": 600})
            await c.connect()
            await c.disconnect()
            assert "ghost" in node.broker.sessions
            assert node.kick_client("ghost") is True
            assert "ghost" not in node.broker.sessions
            assert node.kick_client("ghost") is False
        finally:
            await node.stop()

    run(main())


# ---------------------------------------------------------------------------
# export / import
# ---------------------------------------------------------------------------


def test_export_import_roundtrip(tmp_path):
    async def main():
        node = await start_node(tmp_path)
        c = Client(clientid="keeper", port=mqtt_port(node), proto_ver=5,
                   clean_start=False,
                   properties={"Session-Expiry-Interval": 600})
        await c.connect()
        await c.subscribe("exp/+", qos=1)
        await c.disconnect()
        pub = Client(clientid="p", port=mqtt_port(node))
        await pub.connect()
        await pub.publish("keep/this", b"r", qos=1, retain=True)
        await pub.disconnect()
        node.banned.add("clientid", "bad", reason="t")
        node.rule_engine.create_rule("r1", 'SELECT * FROM "a/#"')
        archive = export_data(node)
        await node.stop()

        # import into a FRESH node (different data dir)
        node2 = await start_node(str(tmp_path) + "/other")
        try:
            counts = import_data(node2, archive)
            assert counts["sessions"] == 1
            assert counts["retained"] == 1
            assert counts["banned"] == 1
            assert counts["rules"] == 1
            assert "keeper" in node2.broker.sessions
            assert node2.retainer.match("keep/this")
            assert "r1" in node2.rule_engine.rules
        finally:
            await node2.stop()

    run(main())


def test_export_via_rest(tmp_path):
    async def main():
        node = await start_node(
            tmp_path,
            'dashboard.enable = true\ndashboard.auth = false\n'
            'dashboard.listen = "127.0.0.1:0"\n',
        )
        try:
            pub = Client(clientid="p", port=mqtt_port(node))
            await pub.connect()
            await pub.publish("keep/this", b"r", qos=1, retain=True)
            await pub.disconnect()
            mport = node.mgmt_server.port
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", mport
            )
            writer.write(
                b"POST /api/v5/data/export HTTP/1.1\r\nHost: x\r\n"
                b"Content-Length: 0\r\nConnection: close\r\n\r\n"
            )
            await writer.drain()
            resp = await reader.read()
            writer.close()
            head, _, payload = resp.partition(b"\r\n\r\n")
            assert b"200" in head.split(b"\r\n")[0]
            assert payload[:2] == b"\x1f\x8b"  # gzip magic
        finally:
            await node.stop()

    run(main())


# ---------------------------------------------------------------------------
# NFA checkpoint
# ---------------------------------------------------------------------------


def test_nfa_checkpoint_roundtrip(tmp_path):
    from emqx_tpu.ops import compile_filters, match_topics

    filters = ["a/+/c", "a/#", "x/y", "$SYS/up", "+/b/#"]
    table = compile_filters(filters, depth=8)
    path = str(tmp_path / "nfa.npz")
    save_table(table, path)
    loaded = load_table(path)
    assert loaded is not None
    assert loaded.n_states == table.n_states
    assert loaded.accept_filters == table.accept_filters
    topics = ["a/q/c", "a/deep/er", "x/y", "$SYS/up", "q/b/z", "none"]
    for topic in topics:
        got = sorted(match_topics(loaded, [topic])[0])
        want = sorted(f for f in filters if T.match(topic, f))
        assert got == want, (topic, got, want)


def test_sidecar_checkpoint_restore(tmp_path):
    import grpc.aio

    from emqx_tpu.exhook.rpc import (
        HookProviderStub,
        MirrorSyncStub,
        add_hook_provider_to_server,
        add_mirror_sync_to_server,
        pb,
    )
    from emqx_tpu.exhook.server import TpuMatchSidecar

    async def settle(pred, timeout=5.0):
        deadline = asyncio.get_running_loop().time() + timeout
        while asyncio.get_running_loop().time() < deadline:
            if pred():
                return True
            await asyncio.sleep(0.02)
        return pred()

    ckpt = str(tmp_path / "sidecar.npz")

    async def phase1():
        sidecar = TpuMatchSidecar(
            rebuild_debounce_s=0.01, checkpoint_path=ckpt
        )
        server = grpc.aio.server()
        add_hook_provider_to_server(sidecar, server)
        port = server.add_insecure_port("127.0.0.1:0")
        await sidecar.start()
        await server.start()
        chan = grpc.aio.insecure_channel(f"127.0.0.1:{port}")
        hooks = HookProviderStub(chan)
        for flt in ("ck/+/a", "ck/#"):
            await hooks.OnSessionSubscribed(
                pb.SessionSubscribedRequest(
                    clientinfo=pb.ClientInfo(clientid="c"), topic=flt
                )
            )
        assert await settle(lambda: os.path.exists(ckpt))
        await chan.close()
        await sidecar.stop()
        await server.stop(None)

    async def phase2():
        # fresh sidecar restores the compiled table from the checkpoint
        sidecar = TpuMatchSidecar(checkpoint_path=ckpt)
        server = grpc.aio.server()
        add_mirror_sync_to_server(sidecar, server)
        port = server.add_insecure_port("127.0.0.1:0")
        await sidecar.start()
        await server.start()
        assert sidecar._engine is not None  # no rebuild needed
        chan = grpc.aio.insecure_channel(f"127.0.0.1:{port}")
        mirror = MirrorSyncStub(chan)
        resp = await mirror.MatchBatch(
            pb.MatchBatchRequest(topics=["ck/1/a", "nope"])
        )
        table = sidecar.filter_table()
        got = sorted(table[i] for i in resp.results[0].filter_ids)
        assert got == ["ck/#", "ck/+/a"]
        assert list(resp.results[1].filter_ids) == []
        await chan.close()
        await sidecar.stop()
        await server.stop(None)

    run(phase1())
    run(phase2())
