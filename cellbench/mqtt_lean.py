"""Just enough MQTT 3.1.1 for the load generator: four encoders and one
frame scanner.  Imports nothing of the program under test and nothing of
JAX, so no later change to the broker's own codec can alter the traffic
the benchmark offers (the scanner is a copy of the inline one in
``emqx_tpu/bench_client.py`` ``LeanSub.drain``, see PERF.md §7)."""

from __future__ import annotations

import struct

CONNACK, PUBLISH, PUBACK, SUBACK = 0x20, 0x30, 0x40, 0x90


def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | 0x80 if n else b)
        if not n:
            return bytes(out)


def _str(s: str) -> bytes:
    b = s.encode()
    return struct.pack(">H", len(b)) + b


def connect(clientid: str) -> bytes:
    """CONNECT, protocol level 4, clean session, keepalive 0."""
    body = _str("MQTT") + b"\x04\x02\x00\x00" + _str(clientid)
    return b"\x10" + varint(len(body)) + body


def subscribe(packet_id: int, flt: str, qos: int) -> bytes:
    body = struct.pack(">H", packet_id) + _str(flt) + bytes([qos])
    return b"\x82" + varint(len(body)) + body


def publish_head(topic: str, qos: int, payload_bytes: int) -> bytes:
    """Fixed header + topic of a PUBLISH whose body will be completed by
    ``packet id (2 B, qos > 0) + payload``: built once per topic."""
    t = _str(topic)
    rl = len(t) + (2 if qos else 0) + payload_bytes
    return bytes([PUBLISH | (qos << 1)]) + varint(rl) + t


DISCONNECT = b"\xe0\x00"


def scan(buf: bytes):
    """Yield ``(first_byte, body_start, body_end)`` for every complete
    frame in ``buf`` and finally ``(None, consumed, consumed)``."""
    i, n = 0, len(buf)
    while n - i >= 2:
        rl = buf[i + 1]
        j = i + 2
        if rl & 0x80:                   # multi-byte remaining length
            rl &= 0x7F
            shift = 7
            while True:
                if j >= n:
                    rl = -1
                    break
                b = buf[j]
                j += 1
                rl |= (b & 0x7F) << shift
                if not b & 0x80:
                    break
                shift += 7
            if rl < 0:
                break
        if j + rl > n:
            break
        yield buf[i], j, j + rl
        i = j + rl
    yield None, i, i
