"""The pubsub core: subscribe/unsubscribe/publish/dispatch.

Behavioral reference: ``apps/emqx/src/emqx_broker.erl`` (``publish/1``,
``subscribe/3``, ``dispatch/2``), ``emqx_broker_helper.erl`` and the
publish call stack of SURVEY.md §3.4 [U].

Responsibilities kept from the reference:

* subscriber table: filter → {clientid → SubOpts} (the ETS
  ``emqx_subscriber`` analog), shared groups delegated to
  :class:`SharedSub`;
* route table updates on first/last subscriber of a filter
  (``emqx_router:do_add_route`` / ``do_delete_route``);
* publish pipeline: ``'message.publish'`` hook fold → route match →
  per-subscriber QoS cap → session delivery → ``message.delivered`` /
  ``message.dropped`` hooks;
* ``$SYS`` messages never match root wildcards (enforced by the match
  oracle/trie/kernel);
* No-Local (MQTT5 ``nl``) suppression.

The broker is single-node here; ``dest`` in the router is either this
node's name (non-shared) or ``(group, node)`` (shared) so that the
multi-node forwarding layer (``emqx_tpu.cluster``) can ship deliveries
across nodes using the same tables.  The device NFA mirror subscribes to
``router.deltas_since`` (SURVEY.md §3.3 note).
"""

from __future__ import annotations

import logging
import time
from dataclasses import replace
from typing import Any, Dict, List, Optional, Tuple

from .. import topic as T
from .hooks import Hooks, HOOK_POINTS, OK, STOP
from .message import Message, make_message
from .mqueue import MQueue
from .router import Router
from .session import Publish, Session, SubOpts
from .shared_sub import SharedSub

log = logging.getLogger(__name__)

__all__ = ["Broker", "DeliverResult"]


class DeliverResult:
    """Per-publish outcome: connection-layer sendouts + accounting."""

    __slots__ = ("publishes", "dropped", "matched", "no_subscribers")

    def __init__(self) -> None:
        self.publishes: Dict[str, List[Publish]] = {}  # clientid -> sends
        self.dropped: List[Tuple[str, Message]] = []   # (clientid, msg)
        self.matched: int = 0
        self.no_subscribers: bool = False


class Broker:
    def __init__(
        self,
        node: str = "local",
        hooks: Optional[Hooks] = None,
        shared_strategy: str = "random",
        session_defaults: Optional[dict] = None,
    ) -> None:
        self.node = node
        self.hooks = hooks if hooks is not None else Hooks()
        # MQTT 5 enhanced auth providers: method name -> provider
        # (start/continue_auth contract — see auth/scram.py)
        self.enhanced_auth: Dict[str, Any] = {}
        self.router = Router()
        self.shared = SharedSub(shared_strategy)
        self.sessions: Dict[str, Session] = {}
        # filter -> {clientid -> SubOpts}; non-shared local subscribers
        self.subscribers: Dict[str, Dict[str, SubOpts]] = {}
        # clientid -> username, maintained by the channel on CONNECT; lets
        # services (topic rewrite %u, ACL templates) resolve usernames
        self.usernames: Dict[str, Optional[str]] = {}
        self.session_defaults = session_defaults or {}
        # out-of-band deliveries (retained replay, delayed publish): the
        # serving layer sets on_deliver to push straight to connections;
        # otherwise they accumulate in outbox for take_outbox().
        self.on_deliver = None  # Optional[Callable[[str, List[Publish]], None]]
        self.outbox: Dict[str, List[Publish]] = {}
        # cluster forwarding seams (emqx_broker_proto_v1:forward analog):
        # set by emqx_tpu.cluster when this node joins a cluster
        self.on_forward = None         # (node, flt, msg) -> None
        self.on_forward_shared = None  # (node, group, flt, msg) -> None
        # device match seam: set by the node's MatchService — returns a
        # precomputed routes list for a topic when a fresh (same-epoch)
        # device answer exists, None otherwise (host trie then serves)
        self.device_match = None       # (topic) -> Optional[List[Route]]
        # batched publish→deliver pipeline (broker/fanout.py): set by the
        # node when broker.fanout.enable is on; the channel offers hot-path
        # publishes here and falls back to the sync publish() when refused
        self.fanout = None             # Optional[FanoutPipeline]
        # batched admission plane (broker/admission.py): set by
        # Admission.attach when admission.enable is on.  None keeps
        # every admission seam at one attr load + identity test.
        self.admission = None          # Optional[Admission]
        # counter table, set by observe(); broker-internal drop accounting
        # (outbox overflow) lands here when present
        self.metrics = None
        self._outbox_warned: set = set()  # clients already logged for drops
        # stage-level latency observatory (observe/hist.py): direct
        # histogram references for the PER-MESSAGE sync publish path —
        # None = zero-call recording sites.  The batched fanout drain
        # records its own spans; without these, traffic that bypasses
        # the pipeline (shape gate, fanout off, direct publish callers)
        # is invisible in the deliver/e2e histograms (ISSUE 13
        # observability follow-on (b)).  Same main-loop writer thread
        # as the fanout drain, so the single-writer discipline holds.
        self.hists = None
        self._h_deliver = None
        self._h_flush = None
        self._h_e2e = None

    # ------------------------------------------------------------------
    # session lifecycle (emqx_cm:open_session semantics, simplified here;
    # full takeover lives in emqx_tpu.broker.cm)
    # ------------------------------------------------------------------

    def open_session(
        self, clientid: str, clean_start: bool = True, **kw
    ) -> Tuple[Session, bool]:
        """Returns (session, session_present)."""
        old = self.sessions.get(clientid)
        if old is not None and not clean_start:
            # a resuming client renegotiates flow-control/expiry knobs
            if "max_inflight" in kw:
                old.inflight.max_size = kw["max_inflight"]
            if "expiry_interval" in kw:
                old.expiry_interval = kw["expiry_interval"]
            old.connected = True
            self.hooks.run("session.resumed", (clientid,))
            return old, True
        if old is not None:
            self._drop_session_state(old)
            self.hooks.run("session.discarded", (clientid,))
        opts = {**self.session_defaults, **kw}
        sess = Session(clientid, clean_start=clean_start, **opts)
        sess.metrics = self.metrics
        self.sessions[clientid] = sess
        self.hooks.run("session.created", (clientid,))
        return sess, False

    def close_session(self, clientid: str, discard: bool = False) -> None:
        sess = self.sessions.get(clientid)
        if sess is None:
            return
        if discard or sess.clean_start:
            self._drop_session_state(sess)
            del self.sessions[clientid]
            self.outbox.pop(clientid, None)
            self._outbox_warned.discard(clientid)
            self.usernames.pop(clientid, None)
            self.hooks.run("session.terminated", (clientid,))
        else:
            sess.connected = False  # deliveries queue until resume

    def _drop_session_state(self, sess: Session) -> None:
        for flt in list(sess.subscriptions):
            self._do_unsubscribe(sess.clientid, flt, sess.subscriptions[flt])

    # ------------------------------------------------------------------
    # subscribe / unsubscribe (SURVEY.md §3.3)
    # ------------------------------------------------------------------

    def subscribe(self, clientid: str, raw_filter: str, opts: SubOpts = SubOpts()) -> bool:
        T.validate(raw_filter, "filter")
        sess = self.sessions.get(clientid)
        if sess is None:
            raise KeyError(f"no session for {clientid!r}")
        share = T.parse_share(raw_filter)
        if share is not None:
            group, flt = share
            opts = replace(opts, share=group)
        else:
            group, flt = None, raw_filter
        is_new = sess.subscribe(raw_filter, opts)
        if group is not None:
            self.shared.subscribe(group, flt, clientid, self.node)
            self.router.add_route(flt, (group, self.node))
        else:
            subs = self.subscribers.setdefault(flt, {})
            first = not subs
            subs[clientid] = opts
            if first:
                self.router.add_route(flt, self.node)
        self.hooks.run("session.subscribed", (clientid, raw_filter, opts, is_new))
        return True

    def unsubscribe(self, clientid: str, raw_filter: str) -> bool:
        sess = self.sessions.get(clientid)
        if sess is None:
            return False
        opts = sess.subscriptions.get(raw_filter)
        if opts is None:
            return False
        sess.unsubscribe(raw_filter)
        self._do_unsubscribe(clientid, raw_filter, opts)
        self.hooks.run("session.unsubscribed", (clientid, raw_filter))
        return True

    def _do_unsubscribe(self, clientid: str, raw_filter: str, opts: SubOpts) -> None:
        share = T.parse_share(raw_filter)
        if share is not None:
            group, flt = share
            self.shared.unsubscribe(group, flt, clientid, self.node)
            if not self.shared.members(group, flt):
                self.router.delete_route(flt, (group, self.node))
        else:
            flt = raw_filter
            subs = self.subscribers.get(flt)
            if subs and clientid in subs:
                del subs[clientid]
                if not subs:
                    del self.subscribers[flt]
                    self.router.delete_route(flt, self.node)

    # ------------------------------------------------------------------
    # publish / dispatch (SURVEY.md §3.4 — THE hot path)
    # ------------------------------------------------------------------

    def publish(self, msg: Message) -> DeliverResult:
        T.validate(msg.topic, "name")
        res = DeliverResult()
        adm = self.admission
        if adm is not None and msg.qos == 0 \
                and adm.shed_qos0(msg.sender):
            # quarantined sender: QoS0 is best-effort by contract, so
            # the shed happens BEFORE the publish fold (no retainer /
            # delayed side effects for dropped attack traffic); QoS1/2
            # ride the throttled token bucket instead of a drop path
            res.no_subscribers = True
            self.hooks.run("message.dropped", (msg, "admission_shed"))
            return res
        msg = self.hooks.run_fold("message.publish", (), msg)
        if msg is None or msg.headers.get("allow_publish") is False:
            res.no_subscribers = True
            return res
        return self._publish_folded(msg, res)

    def publish_folded(self, msg: Message) -> DeliverResult:
        """Dispatch a message whose ``'message.publish'`` fold ALREADY ran
        (fanout-pipeline fallback after stage 1) — re-running the fold
        here would fire retainer/delayed/rewrite side effects twice."""
        return self._publish_folded(msg, DeliverResult())

    def attach_hists(self, hists) -> None:
        """Wire the sync publish path's span recording sites (node
        startup; no-op cost when never called)."""
        self.hists = hists
        self._h_deliver = hists.hist("obs.stage.deliver")
        self._h_flush = hists.hist("obs.stage.flush")
        self._h_e2e = hists.hist("obs.e2e.publish_deliver")

    def _publish_folded(self, msg: Message, res: DeliverResult) -> DeliverResult:
        # the TPU hot path (SURVEY.md §3.4): a fresh micro-batched device
        # answer replaces the per-publish host trie walk; stale/absent
        # hints fall back so correctness never depends on the device
        t0 = time.perf_counter_ns() if self._h_deliver is not None else 0
        routes = None
        if self.device_match is not None:
            routes = self.device_match(msg.topic)
        if routes is None:
            routes = self.router.match_routes(msg.topic)
        if not routes:
            res.no_subscribers = True
            self.hooks.run("message.dropped", (msg, "no_subscribers"))
            return res
        seen_shared: set = set()
        for flt, dest in routes:
            if isinstance(dest, tuple):  # (group, node) shared route
                group, _node = dest
                if (group, flt) in seen_shared:
                    continue
                seen_shared.add((group, flt))
                self._dispatch_shared(group, flt, msg, res)
            elif dest == self.node:
                self._dispatch(flt, msg, res)
            elif self.on_forward is not None:
                # remote node owns subscribers of flt: ship the delivery
                if self.on_forward(dest, flt, msg):
                    res.matched += 1
        # push the fan-out to the connection layer (or the outbox when no
        # serving layer is attached — unit tests read res.publishes instead)
        t1 = time.perf_counter_ns() if self._h_deliver is not None else 0
        for clientid, pubs in res.publishes.items():
            self.emit(clientid, pubs)
        if self._h_deliver is not None:
            # per-message spans for bypass traffic: match+deliver as one
            # deliver span, the emit fan-out as flush, plus the e2e
            # publish→deliver sample when anything was delivered — the
            # same three histograms the batched drain writes, so bypass
            # rates climbing no longer hollow out the distributions
            t2 = time.perf_counter_ns()
            self._h_deliver.record(t1 - t0)
            self._h_flush.record(t2 - t1)
            if res.matched and self._h_e2e is not None:
                self._h_e2e.record_s(time.time() - msg.timestamp)
        return res

    def _dispatch(self, flt: str, msg: Message, res: DeliverResult) -> None:
        for clientid, opts in self.subscribers.get(flt, {}).items():
            if opts.nl and msg.sender == clientid:
                continue  # MQTT5 No-Local
            self._deliver_to(clientid, opts, msg, res)

    def _shared_try_deliver(
        self, group: str, flt: str, msg: Message, res: DeliverResult
    ):
        """The per-member acceptance probe shared by single-message and
        batched $share dispatch (ack-aware redispatch calls it until a
        member accepts)."""
        def try_deliver(member: Tuple[str, str]) -> bool:
            clientid, node = member
            if node != self.node:
                if self.on_forward_shared is not None:
                    # remote candidate: that node's shared table picks the
                    # concrete member (two-level cluster dispatch).  A
                    # False return (peer down) lets dispatch_with_ack try
                    # the next member; remote acceptance after a
                    # successful send is optimistic (async cast, like the
                    # reference's gen_rpc async dispatch).
                    if self.on_forward_shared(node, group, flt, msg):
                        res.matched += 1
                        return True
                return False
            sess = self.sessions.get(clientid)
            if sess is None:
                return False
            # $queue/... sessions store the raw legacy key, not $share form
            opts = sess.subscriptions.get(T.make_share(group, flt))
            if opts is None and group == T.QUEUE_PREFIX:
                opts = sess.subscriptions.get(f"{T.QUEUE_PREFIX}/{flt}")
            if opts is None:
                return False
            return self._deliver_to(clientid, opts, msg, res)

        return try_deliver

    def _dispatch_shared(
        self, group: str, flt: str, msg: Message, res: DeliverResult
    ) -> None:
        try_deliver = self._shared_try_deliver(group, flt, msg, res)
        extra = []
        if self.on_forward_shared is not None:
            # remote nodes holding members of this group, from the route
            # table's (group, node) dests — ("", node) candidate markers
            extra = [
                ("", d[1]) for d in self.router.routes_of(flt)
                if isinstance(d, tuple) and d[0] == group and d[1] != self.node
            ]
        member = self.shared.dispatch_with_ack(
            group, flt, msg.topic, try_deliver, msg.sender, self.node,
            extra=extra,
        )
        if member is None:
            self.hooks.run("message.dropped", (msg, "shared_no_available"))

    def _dispatch_shared_batch(
        self, group: str, flt: str, msgs: List[Message], res: DeliverResult
    ) -> None:
        """Batched $share dispatch (fanout pipeline): ONE ``pick_batch``
        call assigns a member per message — advancing round-robin/
        sticky/hash state exactly as per-message picks would — then all
        messages picked onto one member deliver through a single
        ``Session.deliver``.  Anything the batch cannot keep faithful
        (cluster candidates for this group, a member that nacks) falls
        back to the per-message ack-aware redispatch for the affected
        messages only."""
        if self.on_forward_shared is not None and any(
            isinstance(d, tuple) and d[0] == group and d[1] != self.node
            for d in self.router.routes_of(flt)
        ):
            # remote members exist: keep the two-level cluster pick
            for m in msgs:
                self._dispatch_shared(group, flt, m, res)
            return
        picks = self.shared.pick_batch(
            group, flt,
            [(m.topic, m.sender) for m in msgs], self.node,
        )
        by_member: Dict[Tuple[str, str], List[Message]] = {}
        for m, member in zip(msgs, picks):
            if member is None:
                self.hooks.run("message.dropped", (m, "shared_no_available"))
                continue
            bucket = by_member.get(member)
            if bucket is None:
                bucket = by_member[member] = []
            bucket.append(m)
        hooks = self.hooks
        for member, mlist in by_member.items():
            clientid, node = member
            sess = self.sessions.get(clientid) if node == self.node else None
            opts = None
            if sess is not None:
                opts = sess.subscriptions.get(T.make_share(group, flt))
                if opts is None and group == T.QUEUE_PREFIX:
                    opts = sess.subscriptions.get(f"{T.QUEUE_PREFIX}/{flt}")
            if sess is None or opts is None:
                # picked member can't take it (gone / unsubscribed /
                # remote): redispatch each message excluding it
                for m in mlist:
                    self._redispatch_shared(group, flt, m, res, member)
                continue
            effs = [self._effective(m, opts) for m in mlist]
            mu = sess.mutex
            if mu is None:
                sends, dropped = sess.deliver(effs)
            else:
                with mu:
                    sends, dropped = sess.deliver(effs)
            if sends:
                res.matched += len(sends)
                if self.metrics is not None:
                    self.metrics.inc("messages.delivered", len(sends))
                res.publishes.setdefault(clientid, []).extend(sends)
                if hooks.has("message.delivered"):
                    for p in sends:
                        hooks.run("message.delivered", (clientid, p.msg))
            if not dropped:
                continue
            dropped_ids = set()
            for d in dropped:
                dropped_ids.add(d.id)
                res.dropped.append((clientid, d))
                hooks.run("message.dropped", (d, "queue_full"))
            # a message of THIS batch whose delivery was dropped (queue
            # rejection, or eviction by a later message of the same
            # batch) was never sent → redispatch it to another member;
            # victims from earlier batches just count as drops, like the
            # per-message path
            for m, eff in zip(mlist, effs):
                if eff.id in dropped_ids:
                    self._redispatch_shared(group, flt, m, res, member)

    def _redispatch_shared(
        self,
        group: str,
        flt: str,
        msg: Message,
        res: DeliverResult,
        nacked: Tuple[str, str],
    ) -> None:
        """Ack-aware redispatch of one message after ``nacked`` refused
        it (the batch-path analog of dispatch_with_ack's retry loop)."""
        member = self.shared.dispatch_with_ack(
            group, flt, msg.topic,
            self._shared_try_deliver(group, flt, msg, res),
            msg.sender, self.node, exclude=(nacked,),
        )
        if member is None:
            self.hooks.run("message.dropped", (msg, "shared_no_available"))

    @staticmethod
    def _effective(msg: Message, opts: SubOpts) -> Message:
        """The per-subscription view of a routed message: QoS capped at
        the granted QoS, Retain-As-Published, Subscription-Identifier.
        Returns ``msg`` itself when no transform applies, so a fan-out
        shares one Message (and its payload) across subscribers."""
        eff = msg.with_qos(min(msg.qos, opts.qos))
        if not opts.rap:
            # Retain-As-Published off → clear retain flag on forward
            eff = eff.clone(retain=False) if eff.retain else eff
        if opts.subid is not None:
            # MQTT5 §3.3.4: echo the Subscription-Identifier with deliveries
            eff = eff.clone(
                properties={**eff.properties, "Subscription-Identifier": opts.subid}
            )
        return eff

    def _deliver_to(
        self, clientid: str, opts: SubOpts, msg: Message, res: DeliverResult
    ) -> bool:
        """Returns True iff *this* message was accepted (sent or queued) —
        a queue eviction of an older message is not a nack."""
        sess = self.sessions.get(clientid)
        if sess is None:
            return False
        eff = self._effective(msg, opts)
        mu = sess.mutex
        if mu is None:
            sends, dropped = sess.deliver([eff])
        else:
            # shard-owned session: exclude the owning shard loop's ack
            # handling for the duration of the window admission
            with mu:
                sends, dropped = sess.deliver([eff])
        if sends:
            res.matched += 1
            res.publishes.setdefault(clientid, []).extend(sends)
            if self.metrics is not None:
                self.metrics.inc("messages.delivered")
            self.hooks.run("message.delivered", (clientid, eff))
        for d in dropped:
            res.dropped.append((clientid, d))
            self.hooks.run("message.dropped", (d, "queue_full"))
        return all(d.id != eff.id for d in dropped)

    # ------------------------------------------------------------------
    # cluster ingress (receiving side of on_forward / on_forward_shared)
    # ------------------------------------------------------------------

    def dispatch_remote(self, flt: str, msg: Message) -> int:
        """Dispatch a delivery forwarded from another node to local
        subscribers of ``flt`` (emqx_broker:dispatch on the receiving
        node).  Returns the number of sessions that accepted."""
        res = DeliverResult()
        self._dispatch(flt, msg, res)
        for clientid, pubs in res.publishes.items():
            self.emit(clientid, pubs)
        return res.matched

    def dispatch_shared_remote(self, group: str, flt: str, msg: Message) -> bool:
        """Second level of cross-node shared dispatch: pick among LOCAL
        members only (the sender already chose this node)."""
        res = DeliverResult()

        def try_deliver(member: Tuple[str, str]) -> bool:
            clientid, node = member
            if node != self.node:
                return False
            sess = self.sessions.get(clientid)
            if sess is None:
                return False
            opts = sess.subscriptions.get(T.make_share(group, flt))
            if opts is None and group == T.QUEUE_PREFIX:
                opts = sess.subscriptions.get(f"{T.QUEUE_PREFIX}/{flt}")
            if opts is None:
                return False
            return self._deliver_to(clientid, opts, msg, res)

        member = self.shared.dispatch_with_ack(
            group, flt, msg.topic, try_deliver, msg.sender, self.node
        )
        for clientid, pubs in res.publishes.items():
            self.emit(clientid, pubs)
        if member is None:
            self.hooks.run("message.dropped", (msg, "shared_no_available"))
        return member is not None

    # ------------------------------------------------------------------
    # out-of-band delivery (retained replay, delayed publish, ...)
    # ------------------------------------------------------------------

    def deliver_direct(self, clientid: str, opts: SubOpts, msgs: List[Message]) -> None:
        """Deliver ``msgs`` to one session outside a publish fan-out and
        emit the resulting sends to the connection layer."""
        sess = self.sessions.get(clientid)
        if sess is None:
            return
        effs = [m.with_qos(min(m.qos, opts.qos)) for m in msgs]
        mu = sess.mutex
        if mu is None:
            sends, dropped = sess.deliver(effs)
        else:
            with mu:
                sends, dropped = sess.deliver(effs)
        for d in dropped:
            self.hooks.run("message.dropped", (d, "queue_full"))
        if sends:
            if self.metrics is not None:
                self.metrics.inc("messages.delivered", len(sends))
            for pub in sends:   # only actually-sent messages, not queued
                self.hooks.run("message.delivered", (clientid, pub.msg))
            self.emit(clientid, sends)

    OUTBOX_MAX = 1000  # per client; oldest dropped beyond this

    def emit(self, clientid: str, pubs: List[Publish]) -> None:
        if self.on_deliver is not None:
            self.on_deliver(clientid, pubs)
        else:
            self.outbox_put(clientid, pubs)

    def outbox_put(self, clientid: str, pubs: List[Publish]) -> None:
        """Capped outbox append — the single fallback path for deliveries
        with no live connection.  Overflow evicts oldest-first, counted
        in ``broker.outbox.dropped`` and logged once per client (a silent
        drop here cost a round of debugging)."""
        box = self.outbox.setdefault(clientid, [])
        box.extend(pubs)
        over = len(box) - self.OUTBOX_MAX
        if over > 0:
            del box[:over]
            if self.metrics is not None:
                self.metrics.inc("broker.outbox.dropped", over)
            if clientid not in self._outbox_warned:
                self._outbox_warned.add(clientid)
                log.warning(
                    "outbox overflow for %r: dropped %d oldest "
                    "(cap %d; further drops counted in "
                    "broker.outbox.dropped, logged once per client)",
                    clientid, over, self.OUTBOX_MAX,
                )

    def take_outbox(self, clientid: str) -> List[Publish]:
        return self.outbox.pop(clientid, [])

    # ------------------------------------------------------------------

    def match_filters(self, topic: str) -> List[str]:
        """All filters (wildcard + exact) with local state matching topic —
        parity surface for the device mirror."""
        return [flt for flt, _ in self.router.match_routes(topic)]

    def stats(self) -> Dict[str, int]:
        return {
            "sessions.count": len(self.sessions),
            "subscriptions.count": sum(
                len(s.subscriptions) for s in self.sessions.values()
            ),
            "subscribers.count": sum(len(v) for v in self.subscribers.values()),
            "routes.count": self.router.route_count(),
            "shared_groups.count": len(self.shared.groups()),
        }
