"""Operator tracing: per-clientid/topic/IP event capture to files.

Behavioral reference: ``emqx_trace.erl`` / ``emqx_trace_handler.erl``
[U] (SURVEY.md §2.1, §5.1): an operator creates a named trace with a
filter (clientid | topic | ip_address) and a time window; while active,
matching broker events (connect/disconnect, subscribe/unsubscribe,
publish, deliver, drop) append structured lines to the trace's file,
which REST serves for download.  Traces auto-stop at ``end_at`` and are
bounded in size.

TPU addition: when the in-process match service is live, publish events
record which path answered (``device`` | ``host``) so operators can see
the device duty cycle per client.
"""

from __future__ import annotations

import json
import logging
import os
import time
from typing import Any, Dict, List, Optional

from .. import topic as T

log = logging.getLogger(__name__)

__all__ = ["Trace", "TraceManager"]

MAX_TRACE_BYTES = 16 * 1024 * 1024


class Trace:
    def __init__(self, name: str, type_: str, value: str, path: str,
                 start_at: float, end_at: float) -> None:
        if type_ not in ("clientid", "topic", "ip_address"):
            raise ValueError(f"bad trace type {type_!r}")
        if type_ == "topic":
            T.validate(value, "filter")
        self.name = name
        self.type = type_
        self.value = value
        self.path = path
        self.start_at = start_at
        self.end_at = end_at
        self.stopped = False
        self.bytes = 0
        self.events = 0
        self._fh = None

    def active(self, now: Optional[float] = None) -> bool:
        now = time.time() if now is None else now
        return (not self.stopped and self.start_at <= now < self.end_at
                and self.bytes < MAX_TRACE_BYTES)

    def matches(self, clientid: Optional[str], topic: Optional[str],
                peerhost: Optional[str]) -> bool:
        if self.type == "clientid":
            return clientid == self.value
        if self.type == "topic":
            return topic is not None and T.match(topic, self.value)
        return peerhost == self.value

    def emit(self, event: str, fields: Dict[str, Any]) -> None:
        if self._fh is None:
            self._fh = open(self.path, "a", encoding="utf-8")
        line = json.dumps(
            {"ts": round(time.time(), 6), "event": event, **fields},
            separators=(",", ":"), default=str,
        )
        self._fh.write(line + "\n")
        self._fh.flush()
        self.bytes += len(line) + 1
        self.events += 1

    def stop(self) -> None:
        self.stopped = True
        if self._fh is not None:
            self._fh.close()
            self._fh = None

    def info(self) -> Dict[str, Any]:
        now = time.time()
        return {
            "name": self.name,
            "type": self.type,
            self.type: self.value,
            "status": "running" if self.active(now)
            else ("waiting" if now < self.start_at and not self.stopped
                  else "stopped"),
            "start_at": self.start_at,
            "end_at": self.end_at,
            "events": self.events,
            "bytes": self.bytes,
        }


class TraceManager:
    """Holds traces + the broker hook taps that feed them."""

    def __init__(self, node: Any, trace_dir: Optional[str] = None) -> None:
        self.node = node
        data_dir = (node.config.get("node.data_dir") or "").strip() or "."
        self.dir = trace_dir or os.path.join(data_dir, "trace")
        self.traces: Dict[str, Trace] = {}
        self._message_taps_on = False
        self._attach(node.broker)

    # -- lifecycle ---------------------------------------------------------

    def create(self, name: str, type_: str, value: str,
               duration_s: float = 600.0,
               start_at: Optional[float] = None,
               end_at: Optional[float] = None) -> Trace:
        if name in self.traces:
            raise ValueError(f"trace {name!r} exists")
        os.makedirs(self.dir, exist_ok=True)
        # strict charset: the name lands in a filesystem path AND a
        # Content-Disposition header (CR/LF/quote would split the header)
        if not name or not all(
            c.isalnum() or c in "-_." for c in name
        ) or name.startswith("."):
            raise ValueError("bad trace name (use [A-Za-z0-9._-], "
                             "no leading dot)")
        start = float(start_at) if start_at is not None else time.time()
        end = float(end_at) if end_at is not None else start + duration_s
        tr = Trace(name, type_, value,
                   os.path.join(self.dir, f"{name}.jsonl"), start, end)
        self.traces[name] = tr
        self._sync_message_taps()
        return tr

    def stop(self, name: str) -> bool:
        tr = self.traces.get(name)
        if tr is None:
            return False
        tr.stop()
        return True

    def delete(self, name: str) -> bool:
        tr = self.traces.pop(name, None)
        if tr is None:
            return False
        tr.stop()
        self._sync_message_taps()
        try:
            os.unlink(tr.path)
        except OSError:
            pass
        return True

    def read(self, name: str) -> bytes:
        tr = self.traces.get(name)
        if tr is None:
            raise KeyError(name)
        try:
            with open(tr.path, "rb") as f:
                return f.read()
        except OSError:
            return b""

    def list(self) -> List[Dict[str, Any]]:
        return [t.info() for t in self.traces.values()]

    # -- event taps --------------------------------------------------------

    def _fanout(self, event: str, clientid: Optional[str],
                topic: Optional[str], peerhost: Optional[str],
                fields: Dict[str, Any]) -> None:
        if not self.traces:
            return
        now = time.time()
        for tr in self.traces.values():
            if tr.active(now) and tr.matches(clientid, topic, peerhost):
                try:
                    tr.emit(event, fields)
                except OSError:
                    log.exception("trace %s write failed", tr.name)
                    tr.stop()

    def _attach(self, broker: Any) -> None:
        hooks = broker.hooks
        usernames = getattr(broker, "usernames", {})

        def peer_of(conninfo) -> Optional[str]:
            if isinstance(conninfo, dict):
                peer = conninfo.get("peername") or conninfo.get("peerhost")
                if isinstance(peer, tuple):
                    return peer[0]
                return peer
            return None

        hooks.add("client.connected", lambda cid, conninfo: self._fanout(
            "client.connected", cid, None, peer_of(conninfo),
            {"clientid": cid}), priority=-99, name="trace.connected")
        hooks.add("client.disconnected", lambda cid, reason: self._fanout(
            "client.disconnected", cid, None, None,
            {"clientid": cid, "reason": str(reason)}),
            priority=-99, name="trace.disconnected")
        hooks.add("session.subscribed",
                  lambda cid, flt, opts, is_new: self._fanout(
                      "subscribe", cid, flt, None,
                      {"clientid": cid, "topic": flt, "qos": opts.qos}),
                  priority=-99, name="trace.subscribed")
        hooks.add("session.unsubscribed", lambda cid, flt: self._fanout(
            "unsubscribe", cid, flt, None,
            {"clientid": cid, "topic": flt}),
            priority=-99, name="trace.unsubscribed")
        self._usernames = usernames

    def _on_publish_tap(self, msg):
        if msg is None:
            return msg
        fields = {
            "clientid": msg.sender,
            "topic": msg.topic,
            "qos": msg.qos,
            "retain": msg.retain,
            "payload_size": len(msg.payload),
            "username": self._usernames.get(msg.sender),
        }
        ms = getattr(self.node, "match_service", None)
        if ms is not None:
            # device duty-cycle visibility;
            # non-consuming peek so broker metrics stay untouched
            fields["match_path"] = (
                "device" if ms.hint_available(msg.topic) else "host"
            )
        self._fanout("publish", msg.sender, msg.topic, None, fields)
        return msg

    def _on_delivered_tap(self, cid, msg):
        self._fanout("deliver", cid, msg.topic, None,
                     {"clientid": cid, "topic": msg.topic,
                      "from": msg.sender})

    def _on_dropped_tap(self, msg, reason):
        self._fanout("drop", getattr(msg, "sender", None),
                     getattr(msg, "topic", None), None,
                     {"topic": getattr(msg, "topic", None),
                      "reason": str(reason)})

    def _sync_message_taps(self) -> None:
        """The per-message taps ride the publish→deliver hot path, so
        they exist only while at least one trace does — an idle broker
        pays a single empty-chain dict lookup per event, not a lambda +
        fields dict per delivered leg."""
        hooks = self.node.broker.hooks
        if self.traces and not self._message_taps_on:
            hooks.add("message.publish", self._on_publish_tap,
                      priority=-99, name="trace.publish")
            hooks.add("message.delivered", self._on_delivered_tap,
                      priority=-99, name="trace.delivered")
            hooks.add("message.dropped", self._on_dropped_tap,
                      priority=-99, name="trace.dropped")
            self._message_taps_on = True
        elif not self.traces and self._message_taps_on:
            hooks.delete("message.publish", "trace.publish")
            hooks.delete("message.delivered", "trace.delivered")
            hooks.delete("message.dropped", "trace.dropped")
            self._message_taps_on = False
