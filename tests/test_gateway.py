"""Gateway integration: real STOMP-over-TCP and MQTT-SN-over-UDP clients
against a live node (the emqx CT style — no protocol mocks), proving
gateway sessions ride the normal broker (routing, retained, MQTT
interop, auth)."""

import asyncio
import json
import socket
import struct

import pytest

from emqx_tpu.client import Client
from emqx_tpu.config import Config
from emqx_tpu.gateway.stomp import StompFrame, parse_frames, serialize_frame
from emqx_tpu.node import BrokerNode


def run(coro):
    return asyncio.run(coro)


async def start_node(extra_cfg: str = "", **node_kw):
    cfg = Config(
        file_text='listeners.tcp.default.bind = "127.0.0.1:0"\n'
                  'gateway.stomp.enable = true\n'
                  'gateway.stomp.bind = "127.0.0.1:0"\n'
                  'gateway.mqttsn.enable = true\n'
                  'gateway.mqttsn.bind = "127.0.0.1:0"\n' + extra_cfg
    )
    node = BrokerNode(cfg, **node_kw)
    await node.start()
    return node


def mqtt_port(node):
    return node.listeners.all()[0].port


class StompClient:
    """Minimal test STOMP client over asyncio streams."""

    def __init__(self):
        self.buf = bytearray()

    async def connect(self, port, headers=None):
        self.reader, self.writer = await asyncio.open_connection(
            "127.0.0.1", port)
        await self.send("CONNECT", {"accept-version": "1.2",
                                    **(headers or {})})
        f = await self.recv()
        return f

    async def send(self, command, headers, body=b""):
        self.writer.write(serialize_frame(StompFrame(command, headers, body)))
        await self.writer.drain()

    async def recv(self, timeout=5.0):
        while True:
            for f in parse_frames(self.buf):
                return f
            data = await asyncio.wait_for(self.reader.read(65536), timeout)
            if not data:
                raise ConnectionError("closed")
            self.buf.extend(data)

    async def close(self):
        self.writer.close()


def test_stomp_connect_sub_send_roundtrip():
    async def main():
        node = await start_node()
        try:
            port = node.gateways.gateways["stomp"].port
            c = StompClient()
            f = await c.connect(port)
            assert f.command == "CONNECTED"
            assert f.headers["version"] == "1.2"

            await c.send("SUBSCRIBE", {"id": "0", "destination": "car/+/speed",
                                       "receipt": "r1"})
            r = await c.recv()
            assert (r.command, r.headers["receipt-id"]) == ("RECEIPT", "r1")

            await c.send("SEND", {"destination": "car/42/speed"}, b"88")
            m = await c.recv()
            assert m.command == "MESSAGE"
            assert m.headers["destination"] == "car/42/speed"
            assert m.headers["subscription"] == "0"
            assert m.body == b"88"
            await c.close()
        finally:
            await node.stop()

    run(main())


def test_stomp_mqtt_interop_and_retained():
    """MQTT publishes reach STOMP subscribers and vice versa; a STOMP
    subscriber receives retained replay through the normal broker."""
    async def main():
        node = await start_node()
        try:
            sport = node.gateways.gateways["stomp"].port
            mq = Client(clientid="m1", port=mqtt_port(node))
            await mq.connect()
            await mq.publish("news/hot", b"retained!", retain=True)
            await mq.subscribe("from_stomp/#")

            c = StompClient()
            await c.connect(sport)
            await c.send("SUBSCRIBE", {"id": "7", "destination": "news/#"})
            m = await c.recv()
            assert (m.body, m.headers["destination"]) == (
                b"retained!", "news/hot")

            await c.send("SEND", {"destination": "from_stomp/x"}, b"hi mqtt")
            got = await mq.recv(timeout=5)
            assert (got.topic, got.payload) == ("from_stomp/x", b"hi mqtt")
            await c.close()
            await mq.disconnect()
        finally:
            await node.stop()

    run(main())


def test_stomp_client_ack_qos1_flow():
    async def main():
        node = await start_node()
        try:
            sport = node.gateways.gateways["stomp"].port
            c = StompClient()
            await c.connect(sport)
            await c.send("SUBSCRIBE", {"id": "1", "destination": "q/1",
                                       "ack": "client-individual"})
            mq = Client(clientid="m1", port=mqtt_port(node))
            await mq.connect()
            await mq.publish("q/1", b"needs-ack", qos=1)
            m = await c.recv()
            assert m.headers.get("ack")  # ack-able delivery
            sess = node.broker.sessions[
                node.gateways.gateways["stomp"].clients and
                list(node.gateways.gateways["stomp"].clients.values())[0]
                .clientid]
            assert len(sess.inflight) == 1  # unacked
            await c.send("ACK", {"id": m.headers["ack"]})
            for _ in range(50):
                if len(sess.inflight) == 0:
                    break
                await asyncio.sleep(0.01)
            assert len(sess.inflight) == 0
            await c.close()
            await mq.disconnect()
        finally:
            await node.stop()

    run(main())


# ---------------------------------------------------------------------------
# MQTT-SN over UDP
# ---------------------------------------------------------------------------

class SnClient:
    """Minimal MQTT-SN test client over a UDP socket."""

    def __init__(self, port):
        self.sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        self.sock.settimeout(5.0)
        self.addr = ("127.0.0.1", port)

    def send(self, msgtype, body=b""):
        n = len(body) + 2
        self.sock.sendto(bytes([n, msgtype]) + body, self.addr)

    def recv(self):
        data, _ = self.sock.recvfrom(2048)
        return data[1], data[2:data[0]]

    def connect(self, clientid, keepalive=60, clean=True):
        flags = 0x04 if clean else 0
        self.send(0x04, bytes([flags, 0x01])
                  + struct.pack(">H", keepalive) + clientid.encode())
        t, body = self.recv()
        assert t == 0x05 and body[0] == 0, (t, body)

    def close(self):
        self.sock.close()


def test_mqttsn_connect_register_publish_subscribe():
    async def main():
        node = await start_node()
        try:
            port = node.gateways.gateways["mqttsn"].port
            mq = Client(clientid="m1", port=mqtt_port(node))
            await mq.connect()
            await mq.subscribe("sn/up")

            def sn_flow():
                sn = SnClient(port)
                sn.connect("sn-dev-1")
                # REGISTER sn/up -> tid
                sn.send(0x0A, struct.pack(">HH", 0, 1) + b"sn/up")
                t, body = sn.recv()
                assert t == 0x0B and body[4] == 0
                tid = struct.unpack(">H", body[0:2])[0]
                # PUBLISH qos0 via registered tid
                sn.send(0x0C, bytes([0x00]) + struct.pack(">H", tid)
                        + struct.pack(">H", 0) + b"from-sn")
                # SUBSCRIBE to a concrete name -> SUBACK carries its tid
                sn.send(0x12, bytes([0x00]) + struct.pack(">H", 2)
                        + b"sn/down")
                t, body = sn.recv()
                assert t == 0x13 and body[-1] == 0
                down_tid = struct.unpack(">H", body[1:3])[0]
                assert down_tid != 0
                # SUBSCRIBE to a wildcard -> tid 0 (deliveries REGISTER)
                sn.send(0x12, bytes([0x00]) + struct.pack(">H", 3)
                        + b"snw/#")
                t, body = sn.recv()
                assert t == 0x13 and body[-1] == 0
                assert struct.unpack(">H", body[1:3])[0] == 0
                return sn, down_tid

            sn, down_tid = await asyncio.to_thread(sn_flow)
            got = await mq.recv(timeout=5)
            assert (got.topic, got.payload) == ("sn/up", b"from-sn")

            # concrete-name sub: delivery rides the SUBACK-assigned tid
            await mq.publish("sn/down", b"to-sn")

            def sn_recv_direct():
                t, body = sn.recv()
                assert t == 0x0C, (t, body)
                assert struct.unpack(">H", body[1:3])[0] == down_tid
                return body[5:]

            assert await asyncio.to_thread(sn_recv_direct) == b"to-sn"

            # wildcard sub: unknown topic => gateway REGISTERs first and
            # holds the delivery until REGACK
            await mq.publish("snw/t1", b"via-reg")

            def sn_recv_registered():
                t, body = sn.recv()
                assert t == 0x0A, (t, body)  # REGISTER from gateway
                tid = struct.unpack(">H", body[0:2])[0]
                mid = struct.unpack(">H", body[2:4])[0]
                assert body[4:] == b"snw/t1"
                sn.send(0x0B, struct.pack(">HH", tid, mid) + b"\x00")
                t, body = sn.recv()
                assert t == 0x0C
                assert struct.unpack(">H", body[1:3])[0] == tid
                return body[5:]

            assert await asyncio.to_thread(sn_recv_registered) == b"via-reg"
            sn.close()
            await mq.disconnect()
        finally:
            await node.stop()

    run(main())


def test_mqttsn_short_topic_and_ping():
    async def main():
        node = await start_node()
        try:
            port = node.gateways.gateways["mqttsn"].port
            mq = Client(clientid="m1", port=mqtt_port(node))
            await mq.connect()
            await mq.subscribe("ab")

            def flow():
                sn = SnClient(port)
                sn.connect("sn-short")
                # short topic 'ab', qos0
                sn.send(0x0C, bytes([0x02]) + b"ab"
                        + struct.pack(">H", 0) + b"short!")
                sn.send(0x16)  # PINGREQ
                t, _ = sn.recv()
                assert t == 0x17  # PINGRESP
                sn.send(0x18)  # DISCONNECT
                t, _ = sn.recv()
                assert t == 0x18
                sn.close()

            await asyncio.to_thread(flow)
            got = await mq.recv(timeout=5)
            assert (got.topic, got.payload) == ("ab", b"short!")
            await mq.disconnect()
        finally:
            await node.stop()

    run(main())


def test_gateway_rest_listing():
    async def main():
        import json

        from emqx_tpu.bridge import httpc

        node = await start_node('dashboard.enable = true\n'
                                'dashboard.auth = false\n'
                                'dashboard.listen = "127.0.0.1:0"\n')
        try:
            base = f"http://127.0.0.1:{node.mgmt_server.port}/api/v5"
            r = await httpc.request("GET", f"{base}/gateways")
            names = {g["name"] for g in json.loads(r.body)}
            assert names == {"stomp", "mqttsn"}
        finally:
            await node.stop()

    run(main())


# ---------------------------------------------------------------------------
# CoAP over UDP
# ---------------------------------------------------------------------------

class CoapTestClient:
    def __init__(self, port):
        self.sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        self.sock.settimeout(5.0)
        self.addr = ("127.0.0.1", port)
        self.mid = 0

    def request(self, code, path, query=(), payload=b"", observe=None,
                token=b"\x01", con=True):
        from emqx_tpu.gateway import coap as C

        self.mid += 1
        opts = []
        if observe is not None:
            opts.append((C.OPT_OBSERVE,
                         observe.to_bytes(1, "big") if observe else b""))
        for seg in path.split("/"):
            opts.append((C.OPT_URI_PATH, seg.encode()))
        for q in query:
            opts.append((C.OPT_URI_QUERY, q.encode()))
        msg = C.CoapMessage(C.CON if con else C.NON, code, self.mid,
                            token, opts, payload)
        self.send_raw(C.encode(msg))

    def send_raw(self, data):
        self.sock.sendto(data, self.addr)

    def recv_raw(self):
        data, _ = self.sock.recvfrom(2048)
        return data

    def recv(self):
        from emqx_tpu.gateway import coap as C

        return C.decode(self.recv_raw())

    def close(self):
        self.sock.close()


class DtlsCoapTestClient(CoapTestClient):
    """CoAP test client wrapped in a DTLS 1.2 PSK session."""

    def __init__(self, port, identity, key):
        super().__init__(port)
        from emqx_tpu.transport.dtls import DtlsConnection

        self.conn = DtlsConnection("client", psk_identity=identity, psk=key)
        self._flush()
        while not self.conn.complete:
            data, _ = self.sock.recvfrom(4096)
            self.conn.receive(data)
            self._flush()

    def _flush(self):
        for dg in self.conn.take_outgoing():
            self.sock.sendto(dg, self.addr)

    def send_raw(self, data):
        self.conn.send(data)
        self._flush()

    def recv_raw(self):
        while True:
            data, _ = self.sock.recvfrom(4096)
            plains = self.conn.receive(data)
            self._flush()
            if plains:
                return plains[0]


def coap_node_cfg():
    return ('gateway.coap.enable = true\n'
            'gateway.coap.bind = "127.0.0.1:0"\n')


def test_coap_publish_observe_and_retained():
    async def main():
        from emqx_tpu.gateway import coap as C

        node = await start_node(coap_node_cfg())
        try:
            cport = node.gateways.gateways["coap"].port
            mq = Client(clientid="m1", port=mqtt_port(node))
            await mq.connect()
            await mq.subscribe("sensors/#")

            c = CoapTestClient(cport)
            # publish via PUT -> 2.04, reaches MQTT subscriber
            def put_flow():
                c.request(C.PUT, "ps/sensors/t1", ("c=coap1",), b"23.5")
                r = c.recv()
                assert r.code == C.CHANGED and r.type == C.ACK
            await asyncio.to_thread(put_flow)
            got = await mq.recv(timeout=5)
            assert (got.topic, got.payload) == ("sensors/t1", b"23.5")

            # observe (subscribe): MQTT publish pushes a notification
            def obs_flow():
                c.request(C.GET, "ps/alerts/a", ("c=coap1",), observe=0,
                          token=b"\x77")
                r = c.recv()
                assert r.code == C.CONTENT
            await asyncio.to_thread(obs_flow)
            await mq.publish("alerts/a", b"fire!")

            def notif_flow():
                n = c.recv()
                assert n.code == C.CONTENT and n.token == b"\x77"
                assert n.payload == b"fire!"
                obs = n.opt(C.OPT_OBSERVE)
                assert obs is not None
            await asyncio.to_thread(notif_flow)

            # retained read via plain GET (qos1 so the store is settled)
            await mq.publish("cfg/v", b"42", retain=True, qos=1)
            for _ in range(100):
                if node.retainer.match("cfg/v"):
                    break
                await asyncio.sleep(0.01)
            def get_flow():
                c.request(C.GET, "ps/cfg/v", ("c=coap1",))
                r = c.recv()
                assert r.code == C.CONTENT and r.payload == b"42"
                c.request(C.GET, "ps/cfg/missing", ("c=coap1",))
                assert c.recv().code == C.NOT_FOUND
            await asyncio.to_thread(get_flow)

            # unobserve stops notifications
            def unobs_flow():
                c.request(C.GET, "ps/alerts/a", ("c=coap1",), observe=1)
                assert c.recv().code == C.CONTENT
            await asyncio.to_thread(unobs_flow)
            await mq.publish("alerts/a", b"again")
            def silent_flow():
                c.sock.settimeout(0.4)
                try:
                    c.recv()
                    return False
                except socket.timeout:
                    return True
            assert await asyncio.to_thread(silent_flow)
            c.close()
            await mq.disconnect()
        finally:
            await node.stop()

    run(main())


def test_coap_codec_roundtrip():
    from emqx_tpu.gateway import coap as C

    msg = C.CoapMessage(C.CON, C.PUT, 4242, b"\xab\xcd", [
        (C.OPT_OBSERVE, b"\x00"),
        (C.OPT_URI_PATH, b"ps"),
        (C.OPT_URI_PATH, b"some-long-topic-segment-exceeding-12-bytes"),
        (C.OPT_CONTENT_FORMAT, b"\x00"),
        (C.OPT_URI_QUERY, b"c=client1"),
    ], b"payload")
    out = C.decode(C.encode(msg))
    assert out is not None
    assert (out.type, out.code, out.mid, out.token) == (
        C.CON, C.PUT, 4242, b"\xab\xcd")
    assert out.opt_all(C.OPT_URI_PATH) == [
        b"ps", b"some-long-topic-segment-exceeding-12-bytes"]
    assert out.opt_all(C.OPT_URI_QUERY) == [b"c=client1"]
    assert out.payload == b"payload"
    # malformed inputs don't crash
    assert C.decode(b"") is None
    assert C.decode(b"\x00\x00\x00") is None
    assert C.decode(b"\xff\xff\xff\xff\xff") is None


# ---------------------------------------------------------------------------
# LwM2M over UDP (register + device management ops)
# ---------------------------------------------------------------------------

class FakeLwm2mDevice:
    """A device: registers, answers Read/Write, emits Observe notifies."""

    def __init__(self, port):
        from emqx_tpu.gateway import coap as C

        self.C = C
        self.sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        self.sock.settimeout(5.0)
        self.addr = ("127.0.0.1", port)
        self.resources = {"/3/0/0": "emqx-tpu-dev"}
        self.location = None
        self.observe_tokens = {}

    def register(self, ep, lifetime=120):
        C = self.C
        opts = [(C.OPT_URI_PATH, b"rd"),
                (C.OPT_URI_QUERY, f"ep={ep}".encode()),
                (C.OPT_URI_QUERY, f"lt={lifetime}".encode())]
        msg = C.CoapMessage(C.CON, C.POST, 77, b"\x09", opts,
                            b"</3/0>,</4/0>")
        self.sock.sendto(C.encode(msg), self.addr)
        r = self.recv()
        assert r.code == C.code(2, 1), r.code
        segs = r.opt_all(8)  # Location-Path (RFC 7252 option 8)
        assert segs[0] == b"rd"
        self.location = segs[1].decode()

    def recv(self):
        data, _ = self.sock.recvfrom(2048)
        return self.C.decode(data)

    def serve_one(self):
        """Answer ONE incoming management request."""
        C = self.C
        req = self.recv()
        path = "/" + "/".join(v.decode() for v in req.opt_all(C.OPT_URI_PATH))
        obs = req.opt(C.OPT_OBSERVE)
        if req.code == C.GET and obs is not None and obs == b"":
            self.observe_tokens[path] = req.token
            val = self.resources.get(path, "")
            resp = C.CoapMessage(C.ACK, C.CONTENT, req.mid, req.token,
                                 [(C.OPT_OBSERVE, b"\x01")], val.encode())
        elif req.code == C.GET:
            val = self.resources.get(path)
            if val is None:
                resp = C.CoapMessage(C.ACK, C.NOT_FOUND, req.mid, req.token)
            else:
                resp = C.CoapMessage(C.ACK, C.CONTENT, req.mid, req.token,
                                     [], val.encode())
        elif req.code == C.PUT:
            self.resources[path] = req.payload.decode()
            resp = C.CoapMessage(C.ACK, C.code(2, 4), req.mid, req.token)
        else:
            resp = C.CoapMessage(C.ACK, C.code(4, 5), req.mid, req.token)
        self.sock.sendto(C.encode(resp), self.addr)

    def notify(self, path, value, seq=5):
        C = self.C
        tok = self.observe_tokens[path]
        self.sock.sendto(C.encode(C.CoapMessage(
            C.NON, C.CONTENT, 99, tok,
            [(C.OPT_OBSERVE, bytes([seq]))], value.encode())), self.addr)

    def close(self):
        self.sock.close()


def test_lwm2m_register_read_write_observe():
    async def main():
        node = await start_node('gateway.lwm2m.enable = true\n'
                                'gateway.lwm2m.bind = "127.0.0.1:0"\n')
        try:
            lport = node.gateways.gateways["lwm2m"].port
            mq = Client(clientid="m1", port=mqtt_port(node))
            await mq.connect()
            await mq.subscribe("lwm2m/dev7/up/#")

            dev = FakeLwm2mDevice(lport)
            await asyncio.to_thread(dev.register, "dev7")

            reg = await mq.recv(timeout=5)
            assert reg.topic == "lwm2m/dev7/up/register"
            doc = json.loads(reg.payload)
            assert doc["op"] == "register" and "</3/0>" in \
                ",".join(doc["objects"]) or doc["objects"]

            # downlink READ -> device answers -> uplink resp
            await mq.publish("lwm2m/dev7/dn/cmd", json.dumps({
                "reqid": "r1", "op": "read", "path": "/3/0/0"}).encode())
            await asyncio.to_thread(dev.serve_one)
            resp = await mq.recv(timeout=5)
            assert resp.topic == "lwm2m/dev7/up/resp"
            rdoc = json.loads(resp.payload)
            assert (rdoc["reqid"], rdoc["code"], rdoc["value"]) == \
                ("r1", "2.05", "emqx-tpu-dev")

            # downlink WRITE
            await mq.publish("lwm2m/dev7/dn/cmd", json.dumps({
                "reqid": "r2", "op": "write", "path": "/3/0/14",
                "value": "+02:00"}).encode())
            await asyncio.to_thread(dev.serve_one)
            rdoc = json.loads((await mq.recv(timeout=5)).payload)
            assert (rdoc["reqid"], rdoc["code"]) == ("r2", "2.04")
            assert dev.resources["/3/0/14"] == "+02:00"

            # OBSERVE + device notification
            await mq.publish("lwm2m/dev7/dn/cmd", json.dumps({
                "reqid": "r3", "op": "observe", "path": "/3/0/0"}).encode())
            await asyncio.to_thread(dev.serve_one)
            rdoc = json.loads((await mq.recv(timeout=5)).payload)
            assert rdoc["reqid"] == "r3" and rdoc["code"] == "2.05"
            await asyncio.to_thread(dev.notify, "/3/0/0", "changed!")
            note = await mq.recv(timeout=5)
            assert note.topic == "lwm2m/dev7/up/notify"
            ndoc = json.loads(note.payload)
            assert ndoc["value"] == "changed!" and ndoc["path"] == "/3/0/0"

            # deregister
            def dereg():
                C = dev.C
                msg = C.CoapMessage(C.CON, C.DELETE, 88, b"\x0a",
                                    [(C.OPT_URI_PATH, b"rd"),
                                     (C.OPT_URI_PATH,
                                      dev.location.encode())])
                dev.sock.sendto(C.encode(msg), dev.addr)
                assert dev.recv().code == C.DELETED
            await asyncio.to_thread(dereg)
            rdoc = json.loads((await mq.recv(timeout=5)).payload)
            assert rdoc["op"] == "deregister"
            assert "dev7" not in node.gateways.gateways["lwm2m"].by_ep
            dev.close()
            await mq.disconnect()
        finally:
            await node.stop()

    run(main())


def test_gateway_qos1_retry_redelivers_unacked():
    """An unacked client-ack STOMP delivery is re-sent by the gateway
    retry loop (gateway sessions have no MQTT channel timer)."""
    async def main():
        node = await start_node()
        try:
            gwm = node.gateways
            gwm.RETRY_INTERVAL = 0.2
            # restart the retry loop at test cadence
            if gwm._retry_task is not None:
                gwm._retry_task.cancel()
                gwm._retry_task = asyncio.ensure_future(gwm._retry_loop())
            sport = gwm.gateways["stomp"].port
            c = StompClient()
            await c.connect(sport)
            await c.send("SUBSCRIBE", {"id": "1", "destination": "rt/1",
                                       "ack": "client"})
            sess_cid = list(gwm.gateways["stomp"].clients.values())[0] \
                .clientid
            sess = node.broker.sessions[sess_cid]
            sess.retry_interval = 0.2

            mq = Client(clientid="m1", port=mqtt_port(node))
            await mq.connect()
            await mq.publish("rt/1", b"persist-me", qos=1)

            m1 = await c.recv()
            assert m1.body == b"persist-me"
            # do NOT ack: the retry loop must re-send it
            m2 = await c.recv(timeout=5)
            assert m2.body == b"persist-me"
            assert m2.headers["ack"] != m1.headers["ack"]
            # ack the redelivery clears the inflight window
            await c.send("ACK", {"id": m2.headers["ack"]})
            for _ in range(100):
                if len(sess.inflight) == 0:
                    break
                await asyncio.sleep(0.02)
            assert len(sess.inflight) == 0
            await c.close()
            await mq.disconnect()
        finally:
            await node.stop()

    run(main())


def test_stomp_transactions_commit_and_abort():
    async def main():
        node = await start_node()
        try:
            sport = node.gateways.gateways["stomp"].port
            mq = Client(clientid="m1", port=mqtt_port(node))
            await mq.connect()
            await mq.subscribe("txt/#")

            c = StompClient()
            await c.connect(sport)
            await c.send("BEGIN", {"transaction": "t1", "receipt": "b1"})
            assert (await c.recv()).headers["receipt-id"] == "b1"
            await c.send("SEND", {"destination": "txt/a",
                                  "transaction": "t1"}, b"one")
            await c.send("SEND", {"destination": "txt/b",
                                  "transaction": "t1"}, b"two")
            # nothing delivered before COMMIT
            with pytest.raises(asyncio.TimeoutError):
                await mq.recv(timeout=0.3)
            await c.send("COMMIT", {"transaction": "t1", "receipt": "c1"})
            got = {(await mq.recv(timeout=5)).payload for _ in range(2)}
            assert got == {b"one", b"two"}

            # aborted tx delivers nothing
            await c.send("BEGIN", {"transaction": "t2"})
            await c.send("SEND", {"destination": "txt/c",
                                  "transaction": "t2"}, b"nope")
            await c.send("ABORT", {"transaction": "t2"})
            with pytest.raises(asyncio.TimeoutError):
                await mq.recv(timeout=0.3)

            # unknown tx errors
            await c.send("SEND", {"destination": "txt/d",
                                  "transaction": "ghost"}, b"x")
            # drain frames until the ERROR arrives (receipts may precede)
            for _ in range(5):
                fr = await c.recv()
                if fr.command == "ERROR":
                    break
            assert fr.command == "ERROR"
            await c.close()
            await mq.disconnect()
        finally:
            await node.stop()

    run(main())


def test_mqttsn_qos_minus1_connectionless_publish():
    async def main():
        node = await start_node(
            'gateway.mqttsn.enable = true\n')  # predefined via manager conf
        try:
            gw = node.gateways.gateways["mqttsn"]
            gw.predefined[7] = "sn/minus1"
            mq = Client(clientid="m1", port=mqtt_port(node))
            await mq.connect()
            await mq.subscribe("sn/minus1")

            def fire():
                import struct as _s
                s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
                # PUBLISH, flags qos=0b11 + predefined, tid=7, mid=0
                body = bytes([0x61]) + _s.pack(">H", 7) + _s.pack(">H", 0) \
                    + b"fire-and-forget"
                s.sendto(bytes([len(body) + 2, 0x0C]) + body,
                         ("127.0.0.1", gw.port))
                s.close()

            await asyncio.to_thread(fire)
            got = await mq.recv(timeout=5)
            assert (got.topic, got.payload) == ("sn/minus1",
                                                b"fire-and-forget")
            # no session/connection was created for the anonymous peer
            assert not any(cid.startswith("sn-anon")
                           for cid in node.broker.sessions)
            await mq.disconnect()
        finally:
            await node.stop()

    run(main())


def test_mqttsn_sleeping_client_buffers_and_flushes():
    """DISCONNECT(duration) -> ASLEEP: deliveries buffer; PINGREQ
    flushes them; CONNECT wakes (MQTT-SN §6.14)."""
    async def main():
        node = await start_node()
        try:
            port = node.gateways.gateways["mqttsn"].port
            mq = Client(clientid="m1", port=mqtt_port(node))
            await mq.connect()

            def setup():
                sn = SnClient(port)
                sn.connect("sleepy", clean=True)
                sn.send(0x12, bytes([0x00]) + struct.pack(">H", 2)
                        + b"zzz/t")
                t, body = sn.recv()
                assert t == 0x13 and body[-1] == 0
                # DISCONNECT with duration -> ASLEEP ack'd by DISCONNECT
                sn.send(0x18, struct.pack(">H", 60))
                t, _ = sn.recv()
                assert t == 0x18
                return sn

            sn = await asyncio.to_thread(setup)
            # published while asleep: buffered, not lost, not delivered
            await mq.publish("zzz/t", b"while-asleep", qos=1)
            await asyncio.sleep(0.1)

            def wake_and_collect():
                sn.send(0x16, b"sleepy")  # PINGREQ with clientid
                frames = []
                for _ in range(3):
                    t, body = sn.recv()
                    frames.append((t, body))
                    if t == 0x17:   # PINGRESP ends the listen window
                        break
                return frames

            frames = await asyncio.to_thread(wake_and_collect)
            types = [t for t, _ in frames]
            assert 0x17 in types
            pubs = [b for t, b in frames if t == 0x0C]
            regs = [b for t, b in frames if t == 0x0A]
            # the topic was registered pre-sleep (concrete sub) so the
            # buffered message arrives as a direct PUBLISH
            assert pubs and pubs[0][5:] == b"while-asleep", (pubs, regs)
            sn.close()
            await mq.disconnect()
        finally:
            await node.stop()

    run(main())


def test_mqttsn_will_fires_on_keepalive_loss_not_clean_disconnect():
    async def main():
        node = await start_node()
        try:
            gw = node.gateways.gateways["mqttsn"]
            port = gw.port
            mq = Client(clientid="m1", port=mqtt_port(node))
            await mq.connect()
            await mq.subscribe("wills/#")

            def connect_with_will(cid, keepalive):
                sn = SnClient(port)
                flags = 0x04 | 0x08  # clean + will
                sn.send(0x04, bytes([flags, 0x01])
                        + struct.pack(">H", keepalive) + cid.encode())
                t, _ = sn.recv()
                assert t == 0x06  # WILLTOPICREQ
                sn.send(0x07, bytes([0x00]) + f"wills/{cid}".encode())
                t, _ = sn.recv()
                assert t == 0x08  # WILLMSGREQ
                sn.send(0x09, b"gone!")
                t, body = sn.recv()
                assert t == 0x05 and body[0] == 0  # CONNACK
                return sn

            # clean disconnect: will must NOT fire
            sn1 = await asyncio.to_thread(connect_with_will, "w1", 60)
            def clean_dc():
                sn1.send(0x18)
                assert sn1.recv()[0] == 0x18
            await asyncio.to_thread(clean_dc)
            with pytest.raises(asyncio.TimeoutError):
                await mq.recv(timeout=0.3)
            sn1.close()

            # keepalive loss: will fires
            sn2 = await asyncio.to_thread(connect_with_will, "w2", 1)
            client = next(c for c in gw.by_addr.values()
                          if c.clientid == "w2")
            client.last_seen -= 10  # simulate silence past 1.5x keepalive
            got = await mq.recv(timeout=10)
            assert (got.topic, got.payload) == ("wills/w2", b"gone!")
            sn2.close()
            await mq.disconnect()
        finally:
            await node.stop()

    run(main())


def test_gateway_runtime_load_unload_via_rest():
    async def main():
        from emqx_tpu.bridge import httpc

        node = await start_node(
            'dashboard.enable = true\n'
            'dashboard.auth = false\n'
            'dashboard.listen = "127.0.0.1:0"\n'
            'gateway.coap.bind = "127.0.0.1:0"\n')
        try:
            base = f"http://127.0.0.1:{node.mgmt_server.port}/api/v5"
            assert "coap" not in node.gateways.gateways
            r = await httpc.request(
                "PUT", f"{base}/gateways/coap/enable/true", body=b"")
            assert r.status == 201
            assert "coap" in node.gateways.gateways
            r = await httpc.request(
                "PUT", f"{base}/gateways/coap/enable/false", body=b"")
            assert r.status == 204
            assert "coap" not in node.gateways.gateways
            r = await httpc.request(
                "PUT", f"{base}/gateways/nope/enable/true", body=b"")
            assert r.status == 400  # unknown gateway kind -> ValueError
        finally:
            await node.stop()

    run(main())


# ---------------------------------------------------------------------------
# codec round-trip fuzz (property-style, seeded)
# ---------------------------------------------------------------------------

def test_stomp_frame_codec_fuzz_roundtrip():
    import random as _r

    rng = _r.Random(99)
    specials = ["plain", "with:colon", "with\nnewline", "with\\back",
                "with\rcr", "", "unicode-é中"]
    for _ in range(200):
        cmd = rng.choice(["SEND", "MESSAGE", "SUBSCRIBE", "RECEIPT"])
        headers = {}
        for _ in range(rng.randint(0, 5)):
            headers.setdefault(rng.choice(specials) or "k",
                               rng.choice(specials))
        body = bytes(rng.randrange(256) for _ in range(rng.randint(0, 64)))
        buf = bytearray(serialize_frame(StompFrame(cmd, headers, body)))
        out = next(parse_frames(buf))
        assert out.command == cmd
        assert out.body == body
        for k, v in headers.items():
            assert out.headers[k] == v
    # incremental parse across arbitrary chunk boundaries
    frames = [StompFrame("SEND", {"destination": f"d/{i}"},
                         f"b{i}".encode()) for i in range(10)]
    stream = b"".join(serialize_frame(f) for f in frames)
    buf = bytearray()
    got = []
    for i in range(0, len(stream), 7):
        buf.extend(stream[i:i + 7])
        got.extend(parse_frames(buf))
    assert [f.body for f in got] == [f.body for f in frames]


def test_coap_codec_fuzz_roundtrip_and_garbage():
    import random as _r

    from emqx_tpu.gateway import coap as Cc

    rng = _r.Random(7)
    for _ in range(200):
        opts = []
        nums = sorted(rng.sample([1, 3, 6, 8, 11, 12, 15, 17, 35, 300,
                                  2000], rng.randint(0, 5)))
        for n in nums:
            opts.append((n, bytes(rng.randrange(256)
                                  for _ in range(rng.randint(0, 20)))))
        msg = Cc.CoapMessage(
            rng.randrange(4), rng.randrange(1, 256), rng.randrange(65536),
            bytes(rng.randrange(256) for _ in range(rng.randint(0, 8))),
            opts, bytes(rng.randrange(256)
                        for _ in range(rng.randint(0, 32))))
        out = Cc.decode(Cc.encode(msg))
        assert out is not None
        assert (out.type, out.code, out.mid, out.token) == \
            (msg.type, msg.code, msg.mid, msg.token)
        assert sorted(out.options) == sorted(msg.options)
        assert out.payload == msg.payload
    # random garbage never crashes the decoder
    for _ in range(500):
        blob = bytes(rng.randrange(256) for _ in range(rng.randint(0, 40)))
        Cc.decode(blob)  # may return None or a message; must not raise


def test_mqttsn_unpack_garbage_never_crashes():
    import random as _r

    from emqx_tpu.gateway.mqttsn import _pack, _unpack

    rng = _r.Random(3)
    for _ in range(500):
        blob = bytes(rng.randrange(256) for _ in range(rng.randint(0, 40)))
        _unpack(blob)  # None or (type, body); must not raise
    for _ in range(100):
        t = rng.randrange(256)
        body = bytes(rng.randrange(256) for _ in range(rng.randint(0, 300)))
        out = _unpack(_pack(t, body))
        assert out == (t, body)


def test_lwm2m_bootstrap_interface():
    """LwM2M 1.0 §5.2 bootstrap: POST /bs?ep= -> 2.04, then the server
    pushes the configured Writes and Bootstrap-Finish."""
    async def main():
        node = await start_node('gateway.lwm2m.enable = true\n'
                                'gateway.lwm2m.bind = "127.0.0.1:0"\n')
        try:
            gw = node.gateways.gateways["lwm2m"]
            gw.conf["bootstrap"] = {"writes": [
                {"path": "/0/0/0", "value": "coap://srv:5783"},
                {"path": "/1/0/1", "value": "300"},
            ]}
            lport = gw.port
            mq = Client(clientid="mb", port=mqtt_port(node))
            await mq.connect()
            await mq.subscribe("lwm2m/bdev/up/#")

            dev = FakeLwm2mDevice(lport)

            def run_bootstrap():
                C = dev.C
                dev.sock.sendto(C.encode(C.CoapMessage(
                    C.CON, C.POST, 901, b"\x0b",
                    [(C.OPT_URI_PATH, b"bs"),
                     (C.OPT_URI_QUERY, b"ep=bdev")])), dev.addr)
                ack = dev.recv()
                assert ack.code == C.code(2, 4), ack.code
                finish = False
                for _ in range(3):        # 2 writes + finish
                    req = dev.recv()
                    path = "/" + "/".join(
                        v.decode() for v in req.opt_all(C.OPT_URI_PATH))
                    if req.code == C.PUT:
                        dev.resources[path] = req.payload.decode()
                    elif req.code == C.POST and path == "/bs":
                        finish = True
                    dev.sock.sendto(C.encode(C.CoapMessage(
                        C.ACK, C.code(2, 4), req.mid, req.token)),
                        dev.addr)
                return finish

            finish = await asyncio.to_thread(run_bootstrap)
            assert finish, "no Bootstrap-Finish"
            assert dev.resources["/0/0/0"] == "coap://srv:5783"
            assert dev.resources["/1/0/1"] == "300"

            ev = await mq.recv(timeout=5)
            assert ev.topic == "lwm2m/bdev/up/bootstrap"
            assert json.loads(ev.payload)["writes"] == 2

            # bad endpoint names are rejected
            def bad_ep():
                C = dev.C
                dev.sock.sendto(C.encode(C.CoapMessage(
                    C.CON, C.POST, 902, b"\x0c",
                    [(C.OPT_URI_PATH, b"bs"),
                     (C.OPT_URI_QUERY, b"ep=a/b")])), dev.addr)
                return dev.recv().code

            assert await asyncio.to_thread(bad_ep) == dev.C.BAD_REQUEST
            dev.close()
        finally:
            await node.stop()

    run(main())


# ---------------------------------------------------------------------------
# CoAP over DTLS 1.2 PSK
# ---------------------------------------------------------------------------

DTLS_KEY = "6d792073686172656420736563726574"   # "my shared secret"


def dtls_coap_cfg():
    return ('gateway.coap.enable = true\n'
            'gateway.coap.bind = "127.0.0.1:0"\n'
            'gateway.coap.dtls.enable = true\n'
            f'gateway.coap.dtls.psk = "dev1:{DTLS_KEY}"\n')


def test_coap_gateway_over_dtls_psk():
    pytest.importorskip("cryptography")  # DTLS PSK transport needs it
    """Full CoAP pub/sub round-trip through the DTLS
    1.2 PSK transport — publish encrypted, MQTT subscriber receives,
    observe notification comes back encrypted."""

    async def main():
        from emqx_tpu.gateway import coap as C

        node = await start_node(dtls_coap_cfg())
        try:
            gw = node.gateways.gateways["coap"]
            assert gw.dtls is not None
            assert gw.info()["transport"] == "udp+dtls"
            mq = Client(clientid="m1", port=mqtt_port(node))
            await mq.connect()
            await mq.subscribe("sensors/#")

            c = await asyncio.to_thread(
                DtlsCoapTestClient, gw.port, "dev1",
                bytes.fromhex(DTLS_KEY))
            assert gw.dtls.handshakes == 1

            def put_flow():
                c.request(C.PUT, "ps/sensors/t9", ("c=dev1",), b"42.0")
                r = c.recv()
                assert r.code == C.CHANGED and r.type == C.ACK
            await asyncio.to_thread(put_flow)
            got = await mq.recv(timeout=5)
            assert (got.topic, got.payload) == ("sensors/t9", b"42.0")

            # observe over DTLS: server-initiated notify is encrypted too
            def obs_flow():
                c.request(C.GET, "ps/alerts/d", ("c=dev1",), observe=0,
                          token=b"\x55")
                r = c.recv()
                assert r.code == C.CONTENT
            await asyncio.to_thread(obs_flow)
            await mq.publish("alerts/d", b"dtls-notify")

            def notif_flow():
                n = c.recv()
                assert n.token == b"\x55" and n.payload == b"dtls-notify"
            await asyncio.to_thread(notif_flow)
            c.close()
            await mq.disconnect()
        finally:
            await node.stop()

    run(main())


def test_dtls_gateway_rejects_unknown_identity():
    pytest.importorskip("cryptography")  # DTLS PSK transport needs it
    async def main():
        node = await start_node(dtls_coap_cfg())
        try:
            gw = node.gateways.gateways["coap"]

            def bad_handshake():
                with pytest.raises(socket.timeout):
                    c = CoapTestClient(gw.port)
                    c.sock.settimeout(1.0)
                    from emqx_tpu.transport.dtls import DtlsConnection

                    conn = DtlsConnection("client", psk_identity="intruder",
                                          psk=b"wrong-key")
                    for dg in conn.take_outgoing():
                        c.sock.sendto(dg, c.addr)
                    while not conn.complete:
                        data, _ = c.sock.recvfrom(4096)
                        conn.receive(data)
                        for dg in conn.take_outgoing():
                            c.sock.sendto(dg, c.addr)
            await asyncio.to_thread(bad_handshake)
            assert gw.dtls.handshakes == 0
        finally:
            await node.stop()

    run(main())


def test_stomp_ack_run_batches_through_session_with_fanout_enabled():
    """With the batched-stack opt-in on, a run of ACK frames arriving
    in one TCP read releases the whole window through ONE
    session.puback_batch cycle (receipts still answered per frame);
    with it off the per-frame path is unchanged — both drain the
    inflight window completely."""
    async def main():
        for flag in (True, False):
            node = await start_node(
                'broker.fanout.enable = true\n' if flag else '')
            try:
                sport = node.gateways.gateways["stomp"].port
                c = StompClient()
                await c.connect(sport)
                await c.send("SUBSCRIBE", {"id": "1", "destination": "q/#",
                                           "ack": "client-individual"})
                mq = Client(clientid="m1", port=mqtt_port(node))
                await mq.connect()
                for i in range(4):
                    await mq.publish(f"q/{i}", b"m%d" % i, qos=1)
                acks = []
                for _ in range(4):
                    m = await c.recv()
                    assert m.command == "MESSAGE"
                    acks.append(m.headers["ack"])
                conn = list(
                    node.gateways.gateways["stomp"].clients.values())[0]
                assert conn.batched is flag
                sess = node.broker.sessions[conn.clientid]
                assert len(sess.inflight) == 4
                # all four ACKs (with receipts) land in ONE write
                frames = b"".join(
                    serialize_frame(StompFrame(
                        "ACK", {"id": a, "receipt": f"r-{a}"}))
                    for a in acks)
                c.writer.write(frames)
                await c.writer.drain()
                receipts = set()
                for _ in range(4):
                    f = await c.recv()
                    assert f.command == "RECEIPT"
                    receipts.add(f.headers["receipt-id"])
                assert receipts == {f"r-{a}" for a in acks}
                for _ in range(50):
                    if len(sess.inflight) == 0:
                        break
                    await asyncio.sleep(0.01)
                assert len(sess.inflight) == 0
                await c.close()
                await mq.disconnect()
            finally:
                await node.stop()

    run(main())
