"""Load generator worker: real MQTT clients over TCP, open loop.

One worker is one process with one event loop.  It never imports JAX and
nothing of the program under test.  The parent (``run.py``) hands it a
plan directory and drives it over stdin/stdout:

    child  -> "connected"            every connection is up, subscribed
    parent -> "warm <W0>"            warm-up schedule runs from W0
    child  -> "p <sent> <acked>"     every 0.2 s of the warm-up
    parent -> "go <T0>"              warm-up stops at T0, the window's
                                     schedule starts at T0
    child  -> "done"                 drained, results written
    parent -> end of input           disconnect and exit

``W0``/``T0`` are ``time.monotonic_ns()`` readings: one clock for every
process of a host.  A publish is sent when it is due, acknowledged or
not (open loop); the worker returns raw arrays and reduces nothing:

    sent_ns, acked_ns      per window publish of this worker (0 = never)
    d_sub, d_seq, d_recv   per delivery of a window publish
"""

from __future__ import annotations

import asyncio
import json
import os
import resource
import struct
import sys
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import mqtt_lean as M  # noqa: E402  (sibling file, no package import)

WARM_BIT = 1 << 62
now_ns = time.monotonic_ns


def raise_nofile() -> None:
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    if soft != hard:
        resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))


class Conn(asyncio.Protocol):
    """One client connection; ``on_frame(first_byte, buf, start, end)``
    is called for every complete inbound frame."""

    def __init__(self) -> None:
        self.transport = None
        self.buf = b""
        self.waiting = {}          # packet type -> future (handshake)
        self.closed = False

    def connection_made(self, transport) -> None:
        self.transport = transport

    def connection_lost(self, exc) -> None:
        self.closed = True
        for fut in self.waiting.values():
            if not fut.done():
                fut.set_exception(ConnectionError("closed"))

    def expect(self, ptype: int) -> asyncio.Future:
        fut = asyncio.get_running_loop().create_future()
        self.waiting[ptype] = fut
        return fut

    def data_received(self, data: bytes) -> None:
        buf = self.buf + data if self.buf else data
        t = now_ns()
        for b1, s, e in M.scan(buf):
            if b1 is None:
                self.buf = buf[s:] if s < len(buf) else b""
                break
            self.on_frame(b1, buf, s, e, t)
        self.flush()

    def on_frame(self, b1, buf, s, e, t) -> None:
        fut = self.waiting.pop(b1 & 0xF0, None)
        if fut is not None and not fut.done():
            fut.set_result(buf[s:e])

    def flush(self) -> None:
        pass


class Pub(Conn):
    def __init__(self, rec) -> None:
        super().__init__()
        self.rec = rec
        self.pid = 0
        self.inflight = {}         # packet id -> window index or -1

    def send(self, head: bytes, body: bytes, idx: int) -> None:
        self.pid = self.pid % 65535 + 1
        self.inflight[self.pid] = idx
        self.transport.write(head + struct.pack(">H", self.pid) + body)

    def on_frame(self, b1, buf, s, e, t) -> None:
        if b1 & 0xF0 == M.PUBACK:
            idx = self.inflight.pop((buf[s] << 8) | buf[s + 1], None)
            if idx is None:
                return
            if idx >= 0:
                self.rec.acked[idx] = t
            else:
                self.rec.warm_acked += 1
        else:
            super().on_frame(b1, buf, s, e, t)


class Sub(Conn):
    def __init__(self, rec, gid: int) -> None:
        super().__init__()
        self.rec = rec
        self.gid = gid
        self.acks = bytearray()

    def on_frame(self, b1, buf, s, e, t) -> None:
        if b1 & 0xF0 != M.PUBLISH:
            super().on_frame(b1, buf, s, e, t)
            return
        off = s + 2 + ((buf[s] << 8) | buf[s + 1])
        if b1 & 0x06:                   # qos > 0: ack what was granted
            self.acks += b"\x40\x02" + buf[off:off + 2]
            off += 2
        (seq,) = struct.unpack_from(">Q", buf, off)
        rec = self.rec
        if seq & WARM_BIT:
            rec.warm_received += 1
            return
        rec.n_window += 1
        if rec.drop_every and rec.n_window % rec.drop_every == 0:
            return                      # the planted at-most-once loss
        if b1 & 0x08:
            rec.dup_flagged += 1
        rec.d_sub.append(self.gid)
        rec.d_seq.append(seq)
        rec.d_recv.append(t)

    def flush(self) -> None:
        if self.acks:
            self.transport.write(bytes(self.acks))
            self.acks = bytearray()


class Record:
    def __init__(self, n_window: int, drop_every: int) -> None:
        self.sent = np.zeros(n_window, np.int64)
        self.acked = np.zeros(n_window, np.int64)
        self.d_sub, self.d_seq, self.d_recv = [], [], []
        self.n_window = 0
        self.dup_flagged = 0
        self.warm_sent = self.warm_acked = self.warm_received = 0
        self.drop_every = drop_every


class Phase:
    def __init__(self, plan_dir: str, arrays, name: str, meta) -> None:
        self.due = arrays[f"{name}_due"]
        self.pub = arrays[f"{name}_pub"]
        self.seq = arrays[f"{name}_seq"]
        self.topic = arrays[f"{name}_topic"]
        with open(os.path.join(plan_dir, f"{name}_topics.txt")) as f:
            topics = f.read().split("\n") if len(self.due) else []
        self.heads = [M.publish_head(t, meta["qos"], meta["payload_bytes"])
                      for t in topics]


async def send_phase(ph: Phase, t0: int, stop, pubs, rec, pad: bytes,
                     window: bool) -> None:
    """Send every publish of ``ph`` at ``t0 + due``; ``stop()`` (warm-up
    only) gives the time after which nothing more is sent, or None."""
    due, pidx, seqs, tix, heads = ph.due, ph.pub, ph.seq, ph.topic, ph.heads
    flag = 0 if window else WARM_BIT
    i, n = 0, len(due)
    while i < n:
        now = now_ns()
        end = stop()
        if end is not None and now >= end:
            return
        while i < n and t0 + due[i] <= now:
            if end is not None and t0 + due[i] >= end:
                return
            pubs[pidx[i]].send(heads[tix[i]],
                               struct.pack(">Q", int(seqs[i]) | flag) + pad,
                               i if window else -1)
            if window:
                rec.sent[i] = now_ns()
            else:
                rec.warm_sent += 1
            i += 1
        if i < n:
            wait = (t0 + int(due[i]) - now_ns()) / 1e9
            if end is not None:
                wait = min(wait, max(0.0, (end - now_ns()) / 1e9))
            await asyncio.sleep(max(wait, 0.0))


async def connect_all(meta, rec):
    loop = asyncio.get_running_loop()
    host, port = meta["host"], meta["port"]

    async def one(factory, clientid):
        _tr, proto = await loop.create_connection(factory, host, port)
        ack = proto.expect(M.CONNACK)
        proto.transport.write(M.connect(clientid))
        body = await asyncio.wait_for(ack, 60.0)
        if body[1] != 0:
            raise ConnectionError(f"CONNACK refused rc={body[1]}")
        return proto

    async def one_sub(gid, flt, qos):
        p = await one(lambda: Sub(rec, gid), f"cb-sub-{gid}")
        ack = p.expect(M.SUBACK)
        p.transport.write(M.subscribe(1, flt, qos))
        body = await asyncio.wait_for(ack, 60.0)
        if body[2] >= 0x80:
            raise ConnectionError(f"SUBACK refused {flt!r}")
        return p

    async def in_chunks(coros, size=128):
        out = []
        for k in range(0, len(coros), size):
            out += await asyncio.gather(*coros[k:k + size])
        return out

    subs = await in_chunks([one_sub(g, f, q)
                            for g, f, q in meta["subscribers"]])
    pubs = await in_chunks([one(lambda: Pub(rec), f"cb-pub-{g}")
                            for g in meta["publishers"]])
    return subs, pubs


async def main(plan_dir: str) -> int:
    raise_nofile()
    with open(os.path.join(plan_dir, "plan.json")) as f:
        meta = json.load(f)
    arrays = np.load(os.path.join(plan_dir, "plan.npz"))
    warm = Phase(plan_dir, arrays, "warm", meta)
    win = Phase(plan_dir, arrays, "win", meta)
    rec = Record(len(win.due), int(meta.get("drop_delivery_every", 0)))
    pad = b"x" * (meta["payload_bytes"] - 8)

    loop = asyncio.get_running_loop()
    cmds: asyncio.Queue = asyncio.Queue()

    def read_stdin() -> None:
        for line in sys.stdin:
            loop.call_soon_threadsafe(cmds.put_nowait, line.split())
        loop.call_soon_threadsafe(cmds.put_nowait, ["eof"])

    threading.Thread(target=read_stdin, daemon=True).start()

    subs, pubs = await connect_all(meta, rec)
    print("connected", flush=True)

    async def progress() -> None:
        """How far the broker is behind, for the parent's warm-up rule."""
        while True:
            await asyncio.sleep(0.2)
            sent = rec.warm_sent + int((rec.sent != 0).sum())
            acked = rec.warm_acked + int((rec.acked != 0).sum())
            print("p", sent, acked, flush=True)

    prog = asyncio.ensure_future(progress())

    go_at = [None]
    cmd = await cmds.get()
    if cmd[0] != "warm":
        return 1
    warm_task = asyncio.ensure_future(send_phase(
        warm, int(cmd[1]), lambda: go_at[0], pubs, rec, pad, False))
    cmd = await cmds.get()
    if cmd[0] != "go":
        warm_task.cancel()
        return 1
    t0 = go_at[0] = int(cmd[1])
    await warm_task
    prog.cancel()
    await send_phase(win, t0, lambda: None, pubs, rec, pad, True)

    # drain: every window publish acknowledged and every expected
    # delivery in, then a little longer for anything extra; an answer
    # that comes late is late, so wait up to drain_max_s for it
    close = now_ns()
    cap = close + int(meta["drain_max_s"] * 1e9)
    want = meta["expect_window"]
    while now_ns() < cap:
        if len(rec.d_seq) >= want and (rec.acked != 0).all():
            break
        await asyncio.sleep(0.02)
    await asyncio.sleep(meta["linger_s"])
    lost = sum(1 for c in subs + pubs if c.closed)
    np.savez(os.path.join(plan_dir, "result.npz"),
             seq=win.seq, sent=rec.sent, acked=rec.acked,
             d_sub=np.asarray(rec.d_sub, np.int64),
             d_seq=np.asarray(rec.d_seq, np.int64),
             d_recv=np.asarray(rec.d_recv, np.int64),
             counts=np.asarray([rec.warm_sent, rec.warm_acked,
                                rec.warm_received, rec.dup_flagged,
                                lost, close], np.int64))
    print("done", flush=True)
    await cmds.get()            # stay subscribed until the parent has looked
    for c in subs + pubs:
        if not c.closed:
            c.transport.write(M.DISCONNECT)
            c.transport.close()
    await asyncio.sleep(0.05)
    return 0


if __name__ == "__main__":
    sys.exit(asyncio.run(main(sys.argv[1])))
