"""DTLS 1.2 PSK transport: sans-IO handshake/record tests plus the
endpoint's stateless-cookie and sweep behavior (the esockd-dtls analog
for the UDP gateways)."""

import pytest

pytest.importorskip("cryptography")

from emqx_tpu.transport.dtls import (
    DtlsConnection, DtlsEndpoint, PskStore,
)

KEY = b"sixteen-byte-key"
STORE = PskStore({"dev1": KEY}, hint="emqx")


def pump(a, b, limit=20):
    """Shuttle datagrams between two sans-IO connections; returns all
    plaintext chunks surfaced on each side."""
    got_a, got_b = [], []
    for _ in range(limit):
        moved = False
        for src, dst, sink in ((a, b, got_b), (b, a, got_a)):
            for dg in src.take_outgoing():
                moved = True
                sink.extend(dst.receive(dg))
        if not moved:
            return got_a, got_b
    raise AssertionError("handshake did not settle")


def new_pair(identity="dev1", key=KEY):
    client = DtlsConnection("client", psk_identity=identity, psk=key)
    server = DtlsConnection("server", psk_store=STORE, peer=("1.2.3.4", 5))
    return client, server


def test_handshake_and_bidirectional_data():
    client, server = new_pair()
    pump(client, server)
    assert client.complete and server.complete
    assert server.psk_identity == b"dev1"
    client.send(b"up " * 100)
    server.send(b"down")
    got_client, got_server = pump(client, server)
    assert got_server == [b"up " * 100]
    assert got_client == [b"down"]


def test_wrong_psk_fails_finished():
    client, server = new_pair(key=b"the-wrong-key-!!")
    pump(client, server)
    # server drops the bad Finished; neither side completes
    assert not server.complete and not client.complete


def test_unknown_identity_rejected():
    client, server = new_pair(identity="who-dis")
    pump(client, server)
    assert not server.complete
    with pytest.raises(Exception):
        client.send(b"x")


def test_tampered_record_dropped():
    client, server = new_pair()
    pump(client, server)
    client.send(b"genuine")
    (dg,) = client.take_outgoing()
    bad = dg[:-1] + bytes([dg[-1] ^ 0xFF])
    assert server.receive(bad) == []        # auth tag fails: dropped
    # the channel stays usable for intact records
    client.send(b"second")
    (dg2,) = client.take_outgoing()
    assert server.receive(dg2) == [b"second"]


def test_application_data_needs_handshake():
    client, _ = new_pair()
    with pytest.raises(Exception):
        client.send(b"too-early")


class _FakeTransport:
    def __init__(self):
        self.sent = []

    def sendto(self, data, addr):
        self.sent.append((data, addr))

    def close(self):
        pass

    def get_extra_info(self, name, default=None):
        return default


def test_endpoint_stateless_before_cookie():
    """The pre-cookie first flight must not allocate per-address state
    (RFC 6347 §4.2.1 DoS posture): only a cookie'd ClientHello earns a
    session slot."""
    plain = []
    ep = DtlsEndpoint(_FakeTransport(), lambda d, a: plain.append((d, a)),
                      STORE)
    client = DtlsConnection("client", psk_identity="dev1", psk=KEY)
    addr = ("9.9.9.9", 1234)
    (ch0,) = client.take_outgoing()
    ep.datagram_received(ch0, addr)
    assert ep.sessions == {}               # HVR sent, nothing retained
    assert len(ep.transport.sent) == 1
    # replay the HVR into the client, complete the handshake
    for dg, _ in list(ep.transport.sent):
        client.receive(dg)
    for dg in client.take_outgoing():      # cookie'd CH
        ep.datagram_received(dg, addr)
    assert addr in ep.sessions             # address verified: retained
    for _round in range(4):
        for dg, _ in ep.transport.sent[1:]:
            client.receive(dg)
        ep.transport.sent[1:] = []
        for dg in client.take_outgoing():
            ep.datagram_received(dg, addr)
        if client.complete and ep.handshakes:
            break
    assert client.complete and ep.handshakes == 1
    client.send(b"app")
    for dg in client.take_outgoing():
        ep.datagram_received(dg, addr)
    assert plain == [(b"app", addr)]


def test_endpoint_sweep_drops_idle_sessions():
    ep = DtlsEndpoint(_FakeTransport(), lambda d, a: None, STORE,
                      idle_timeout=0.5)
    client = DtlsConnection("client", psk_identity="dev1", psk=KEY)
    addr = ("8.8.8.8", 42)
    for dg in client.take_outgoing():
        ep.datagram_received(dg, addr)
    for dg, _ in list(ep.transport.sent):
        client.receive(dg)
    for dg in client.take_outgoing():
        ep.datagram_received(dg, addr)
    assert addr in ep.sessions
    now = ep.sessions[addr].last_seen
    assert ep.sweep(now + 0.4) == 0
    assert ep.sweep(now + 1.0) == 1
    assert ep.sessions == {}
