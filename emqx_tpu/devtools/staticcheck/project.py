"""Project policy for the rules: what is structurally exempt and why.

Two exemption mechanisms exist, with different lifetimes:

* **Allowlists here** are *structural*: the site is correct by design
  (request-scoped task that dies with its connection, bench harness,
  one-shot event) and stays correct until the design changes.  Every
  entry carries its reason and is reviewed like code.
* **Waivers** (``waivers.py``) are *temporary*: a known finding someone
  chose to defer.  They expire; an expired waiver resurfaces as its own
  finding.

Adding to an allowlist is a design statement; adding a waiver is debt.
"""

from __future__ import annotations

from typing import Dict, FrozenSet, Optional, Tuple

__all__ = [
    "ALLOWED_TASK_SITES", "DELIVERY_PATH_PREFIXES", "SUPERVISE_MODULE",
    "AFFINITY_SEEDS", "AFFINITY_BARRIERS", "AFFINITY_LOCKS",
    "MAIN_ONLY_CLASSES", "LOCKED_FIELDS", "ATTR_TYPES",
    "SHARD_ATTR_TYPES", "VARNAME_HINTS", "AFFINITY_ALLOWED_SITES",
    "INVARIANT_GROUPS", "TORN_READ_ALLOWED_SITES",
    "HOST_SYNC_ALLOWED_SITES", "DONATE_ALLOWED_SITES",
    "LOCK_ORDER_ALLOWED", "barrier_fact", "site_exemption",
]

#: Module allowed to create raw tasks: the supervision tree itself.
SUPERVISE_MODULE = "emqx_tpu/supervise.py"

#: (repo-relative path, enclosing qualname) → reason.  These sites may
#: call ``asyncio.create_task``/``ensure_future`` directly because the
#: task is request/connection-scoped (it dies with the socket or event
#: that spawned it — ROADMAP: "per-connection tasks stay unsupervised by
#: design") or belongs to client/bench tooling that runs outside the
#: broker's supervision tree.  Long-lived node loops do NOT belong here;
#: they register with the supervisor (supervised-with-fallback sites are
#: exempted structurally, not listed).
ALLOWED_TASK_SITES: Dict[Tuple[str, str], str] = {
    ("emqx_tpu/client.py", "Client.connect"):
        "MQTT client library: read/ping loops die with the connection",
    ("emqx_tpu/bench_client.py", "LeanPub.run"):
        "bench harness: ack loop scoped to one bench run",
    ("emqx_tpu/bench_client.py", "run_scenario"):
        "bench harness: drain tasks scoped to one bench run",
    ("bench.py", "bench_adversarial.run_one"):
        "bench harness: attacker/storm loops scoped to one A/B run, "
        "cancelled + gathered before the node stops",
    ("emqx_tpu/gateway/exproto.py", "ExProtoConn.send_deliveries"):
        "per-event gRPC notify; errors surface via the handler channel",
    ("emqx_tpu/gateway/stomp.py", "StompConn.on_connect"):
        "per-connection heartbeat, cancelled on close",
    ("emqx_tpu/transport/connection.py", "Connection.run"):
        "per-connection writer/tick loops, joined by the conn handler",
    ("emqx_tpu/transport/proto_conn.py", "MqttProtocol.connection_made"):
        "per-connection worker loop, cancelled in connection_lost",
    ("emqx_tpu/transport/quic/connection.py",
     "QuicEndpoint.datagram_received"):
        "per-connection stream handler (the accept path)",
    ("emqx_tpu/cluster/transport.py", "PeerConn.start"):
        "per-peer-socket recv loop, cancelled on conn close",
    ("emqx_tpu/cluster/durable.py", "DurableReplicator.apply_deltas"):
        "one-shot re-bootstrap on seq gap; re-armed on next gap",
    ("emqx_tpu/cluster/cluster.py", "Cluster._peer_up"):
        "one-shot bootstrap per peer-up event",
    ("emqx_tpu/cluster/cluster.py", "Cluster._apply_route_deltas"):
        "one-shot re-bootstrap on seq gap; re-armed on next gap",
    ("emqx_tpu/storage/backup.py", "import_data"):
        "one-shot worker start during restore (worker loops themselves "
        "register with the supervisor)",
}

#: Path prefixes (repo-relative) where a silently-swallowed exception is
#: a delivery bug, not a style nit — the no-swallowed-exceptions rule
#: only fires here.
DELIVERY_PATH_PREFIXES: Tuple[str, ...] = (
    "emqx_tpu/broker/",
    "emqx_tpu/bridge/",
    "emqx_tpu/gateway/",
    "emqx_tpu/transport/",
    "emqx_tpu/cluster/",
    "emqx_tpu/exhook/",
    "emqx_tpu/mqtt/",
    "emqx_tpu/node.py",
    "emqx_tpu/supervise.py",
)

#: Modules added since PR 4 that MUST be inside the delivery-path scope
#: (asserted by tests/test_staticcheck.py so a prefix refactor cannot
#: silently drop them): transport/shards.py, transport/timerwheel.py,
#: broker/match_service.py, broker/olp.py — all covered by the
#: ``emqx_tpu/transport/`` and ``emqx_tpu/broker/`` prefixes above.
DELIVERY_PATH_REQUIRED_MODULES: Tuple[str, ...] = (
    "emqx_tpu/transport/shards.py",
    "emqx_tpu/transport/timerwheel.py",
    "emqx_tpu/broker/match_service.py",
    "emqx_tpu/broker/olp.py",
)


# ---------------------------------------------------------------------------
# shard-affinity ownership facts (PR 8)
# ---------------------------------------------------------------------------
# The connection-plane sharding (transport/shards.py) rests on prose
# invariants: broker state is main-loop-only, session state is touched
# from shards only under the channel RLock (``Session.mutex`` is the
# same object), shard-affine helpers never touch the main loop.  These
# tables turn that prose into facts the affinity analysis propagates
# and CHECKS — editing them is a design statement, reviewed like code.

#: Affinity seeds: qualname suffix → (context, mutex-held-on-entry).
#: Contexts: "main" (the broker event loop), "shard" (a shard worker's
#: own event loop), "thread" (plain worker thread, no running loop).
#: A seed with locked=True records that every real entry into the
#: function takes the channel RLock first (e.g. Channel ack handlers
#: are only shard-reachable through the ShardChannel wrappers / the
#: marshal path, both of which hold the mutex).
AFFINITY_SEEDS: Dict[str, Tuple[str, bool]] = {
    # shard-loop surfaces (transport/shards.py)
    "ShardChannel.handle_in": ("shard", False),
    "ShardChannel.handle_ack_run": ("shard", False),
    "ShardChannel.handle_puback_batch": ("shard", False),
    "ShardChannel.handle_publish_run": ("shard", False),
    "ShardChannel.check_keepalive": ("shard", False),
    "ShardChannel.retry_deliveries": ("shard", False),
    "ShardChannel.retry_wire_batch": ("shard", False),
    "ShardChannel.retry_commit": ("shard", False),
    "ShardChannel.handle_close": ("shard", False),
    "ShardChannel.marshal_done": ("shard", False),
    # dispatched from ShardChannel.handle_in under the mutex (the
    # _fast_pub gate, not _SHARD_LOCAL — so it stays a hand seed)
    "ShardChannel._handle_publish": ("shard", True),
    # NOTE: the Channel._handle_puback/_handle_pubrec/_handle_pubrel/
    # _handle_pubcomp seeds are no longer hand-kept here — pass 2
    # GENERATES them by joining the `_SHARD_LOCAL` packet-type set
    # (transport/shards.py) with the `handle_in` dispatch-dict facts
    # (AffinityAnalysis._generated_seeds), so adding a packet type to
    # _SHARD_LOCAL automatically seeds its dispatch handler.
    "Shard._consume_inbox": ("shard", False),
    "_ShardProtocol.data_received": ("shard", False),
    # serve-pipeline worker stages (broker/match_service.py, PR 11):
    # the encode/dispatch stage and the readback stage are
    # entered via asyncio.to_thread (auto-seeded too — these facts
    # write the contract down): PURE COMPUTE against captured
    # arguments.  MatchService is MAIN_ONLY, so any state write (or a
    # Broker touch) from either worker trips shard-affinity — hint
    # minting, metrics, and breaker notes stay on the event loop in
    # the match.batch / match.readback children.
    "MatchService._encode_dispatch": ("thread", False),
    "MatchService._readback_groups": ("thread", False),
    # multichip mesh worker surfaces (ISSUE 15): the sync loop's
    # partition apply (MatchService._mc_apply via to_thread) and the
    # matcher methods it reaches.  The contract mirrors the pipeline
    # workers: MultichipMatcher owns its OWN state under its lock
    # (single writer = the sync worker; dispatch snapshots under the
    # same lock), and NOTHING in these workers may touch Broker /
    # MatchService state — MatchService is MAIN_ONLY, so a write from
    # here trips shard-affinity (fixture pair
    # trip/ok_affinity_mesh.py).
    "MatchService._mc_apply": ("thread", False),
    "MultichipMatcher.apply_pending": ("thread", False),
    "MultichipMatcher.dispatch": ("thread", False),
    "MultichipMatcher.readback": ("thread", False),
    # main-loop surfaces of the same file (the marshal consumers)
    "ShardPool._consume": ("main", False),
    "ShardPool._publish_batch": ("main", False),
    "ShardPool._main_handle": ("main", False),
    "ShardPool._takeover": ("main", False),
    "ShardPool._main_close": ("main", False),
    "ShardPool._main_conn_closed": ("main", False),
    "ShardPool.start": ("main", False),
    "ShardPool.stop": ("main", False),
}

#: Dispatch barriers: propagation stops at these functions because
#: their fan-out depends on runtime packet types; the shard-reachable
#: subset of their dispatch targets is seeded explicitly above.
#: (``Channel.handle_in`` dispatches CONNECT/SUBSCRIBE/... which only
#: ever run marshaled on the main loop — seeding the ack handlers and
#: barring the dispatcher encodes exactly that contract.)
#:
#: An entry is either a qualname suffix (absorbs EVERY plane — the
#: over-broad form) or ``(suffix, planes)`` absorbing only the named
#: planes: a per-context absorb fact.  ``barrier_fact`` normalizes.
AFFINITY_BARRIERS: Tuple[object, ...] = (
    "Channel.handle_in",
    # converted from the over-broad all-plane form: the close path's
    # packet-type fan-out is only dispatch-opaque on the SHARD plane
    # (ShardChannel.handle_close marshals the broker-touching half);
    # main/thread paths through Channel.handle_close propagate and
    # stay checked instead of being absorbed with it
    ("Channel.handle_close", ("shard",)),
)

_ALL_PLANES: Tuple[str, ...] = ("main", "shard", "thread")


def barrier_fact(entry: object) -> Tuple[str, Tuple[str, ...]]:
    """Normalize an ``AFFINITY_BARRIERS`` entry to
    ``(suffix, planes-it-absorbs)``."""
    if isinstance(entry, str):
        return entry, _ALL_PLANES
    suffix, planes = entry
    return suffix, tuple(planes)

#: Lock names that satisfy the "channel RLock held" requirement at a
#: call/write site (``Session.mutex`` is the same object as the
#: channel's RLock by construction — see transport/shards.py).
AFFINITY_LOCKS: FrozenSet[str] = frozenset({"mutex"})

#: Classes (by basename) whose attribute state belongs to the MAIN
#: loop outright: ANY write reachable from shard-affine code is a race,
#: locked or not — shards must marshal instead.
MAIN_ONLY_CLASSES: FrozenSet[str] = frozenset({
    "Broker", "Router", "MatchService", "FanoutPipeline", "Retainer",
    "SharedSub",
})

#: Classes with a documented RLock-protected field set: shard-affine
#: writes to the listed fields are legal **with the mutex held**;
#: writes to any OTHER field of the class remain main-loop-only even
#: under the lock (the lock protects the QoS window, not the session's
#: identity/registry fields).
LOCKED_FIELDS: Dict[str, FrozenSet[str]] = {
    "Session": frozenset({
        "inflight", "mqueue", "awaiting_rel", "_next_pid", "mutex",
    }),
    "Channel": frozenset({
        # connection-local packet-processing state: only ever touched
        # while handling that connection's packets, which on shards
        # happens under the channel mutex (ShardChannel wrappers)
        "last_rx", "_retry_pending", "_aliases",
    }),
}

#: Declarative attribute typing (ownership facts): attribute name →
#: project class basename, used when ``self.attr = Cls(...)`` inference
#: has nothing to say.  Keep this table small and obvious.
ATTR_TYPES: Dict[str, str] = {
    "session": "Session",
    "channel": "Channel",
    "broker": "Broker",
    "router": "Router",
    "inflight": "Inflight",
    "mqueue": "MQueue",
    "pool": "ShardPool",
    "handoff": "Handoff",
}

#: Shard-view attribute typing: under a shard/thread context these
#: override ``ATTR_TYPES`` — on a shard loop the protocol's channel IS
#: a ShardChannel (node.make_shard_protocol builds nothing else), so
#: propagation walks through the mutex-taking overrides.
SHARD_ATTR_TYPES: Dict[str, str] = {
    "channel": "ShardChannel",
    "chan": "ShardChannel",
}

#: Variable-name → class basename hints for non-self receivers
#: (``sess.puback_batch(...)``), same spirit as ATTR_TYPES.
VARNAME_HINTS: Dict[str, str] = {
    "sess": "Session",
    "session": "Session",
    "chan": "Channel",
    "channel": "Channel",
    "broker": "Broker",
    "router": "Router",
}

#: (repo-relative path, enclosing qualname) → exemption.  Structural
#: exemptions for the shard-affinity rule: sites the analysis flags but
#: that are correct by design (same lifetime rules as
#: ALLOWED_TASK_SITES — a reasoned allowlist, not a waiver).
#:
#: With the context-sensitive lattice these are **per-context facts**:
#: the value is either a bare reason string (exempts EVERY path — the
#: old, over-broad form, kept for sites that really are safe from
#: everywhere) or ``(reason, plane, entry-suffix)`` exempting only
#: paths on ``plane`` whose entry point matches ``entry-suffix``
#: (either may be None to wildcard it).  A site safe when reached
#: locked-from-main no longer absorbs the unlocked-from-shard path.
AFFINITY_ALLOWED_SITES: Dict[Tuple[str, str], object] = {
}


def site_exemption(table: Dict[Tuple[str, str], object], relpath: str,
                   qualname: str, plane: str,
                   entry: str) -> Optional[str]:
    """Reason when ``(relpath, qualname)`` is exempt for a path on
    ``plane`` entered at ``entry``, else None.  Shared by the
    shard-affinity and torn-read rules."""
    val = table.get((relpath, qualname))
    if val is None:
        return None
    if isinstance(val, str):
        return val
    reason, p, ent = val
    if p is not None and p != plane:
        return None
    if ent is not None and entry != ent \
            and not entry.endswith("." + ent):
        return None
    return reason


# ---------------------------------------------------------------------------
# read-set model: declarative multi-field invariants (torn-read rule)
# ---------------------------------------------------------------------------

#: group name → (owner class basename, the fields whose combination is
#: an invariant, the lock that must be held ACROSS any multi-field
#: read, why).  A function that reads ≥2 of a group's fields from
#: shard/thread context without the lock held over one contiguous
#: critical section observes a torn invariant — the reader-side race
#: the write-only detector can't see.
INVARIANT_GROUPS: Dict[str, Tuple[str, FrozenSet[str], str, str]] = {
    "session-window": (
        "Session", frozenset({"inflight", "mqueue"}), "mutex",
        "window admission/refill reads the inflight map and the mqueue "
        "together; a torn view double-admits past the window or "
        "strands queued messages until the next ack"),
    "session-qos2": (
        "Session", frozenset({"inflight", "awaiting_rel"}), "mutex",
        "the exactly-once handshake pairs sender inflight state with "
        "receiver awaiting_rel state; a torn view re-delivers or "
        "drops a release"),
    "inflight-expiry": (
        "Inflight", frozenset({"_d", "_exp"}), "mutex",
        "the lazy expiry heap mirrors the pid map; a torn view "
        "resurrects acked pids into the retry scan or skips a due "
        "retry"),
}

#: (repo-relative path, enclosing qualname) → exemption for the
#: torn-read rule; same value forms and per-context semantics as
#: AFFINITY_ALLOWED_SITES.
TORN_READ_ALLOWED_SITES: Dict[Tuple[str, str], object] = {
}

#: (repo-relative path, enclosing qualname) → exemption for the
#: host-sync-in-loop rule; same value forms and per-context semantics
#: as AFFINITY_ALLOWED_SITES.  An entry here states that a device
#: synchronization on a loop-affine path is acceptable — a strong
#: claim, so each reason must say why the stall is bounded (startup
#: one-shot, shutdown drain, cold path behind a breaker, ...).
HOST_SYNC_ALLOWED_SITES: Dict[Tuple[str, str], object] = {
}

#: (repo-relative path, enclosing qualname) → reason for the
#: use-after-donate rule.  Donation legality does not vary by plane,
#: so the value is always a bare reason string.  Should stay EMPTY:
#: a use-after-donate is a memory-safety bug on real devices (the CPU
#: backend hides it by copying), and the rebind idiom
#: ``x = fn_donated(x, ...)`` is already clean by construction.
DONATE_ALLOWED_SITES: Dict[Tuple[str, str], str] = {
}

#: Reasoned exemptions for the lock-order rule, keyed by the sorted
#: tuple of the cycle's lock NODE names — object-qualified
#: (``Pair.a_lock``) when the acquire sites typed, plain otherwise —
#: e.g. a pair of locks proven never to contend despite the ordering
#: edges.
LOCK_ORDER_ALLOWED: Dict[Tuple[str, ...], str] = {
}
