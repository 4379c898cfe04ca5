"""Batched publish→deliver fanout pipeline — the broker-side analog of
the kernel's micro-batching.

The device matcher sustains ~428k topics/s, but the per-message publish
path (``Broker.publish`` → ``_dispatch`` → ``_deliver_to`` →
``Session.deliver`` → ``emit``) walks 6+ Python frames *per subscriber
per message*, which caps broker e2e throughput two orders of magnitude
below the kernel (BENCH_r05 ``config1_broker_e2e`` vs ``tpu.topics_per_s``
— exactly the broker-side processing overhead MQTT+ (arXiv:1810.00773)
measures as dominant in enhanced brokers).  This pipeline amortizes that
walk over micro-batches:

* the channel **offers** hot-path publishes here (acks immediately —
  PUBACK means "broker took responsibility", not "delivered", so this is
  spec-faithful) and falls back to the per-message ``Broker.publish``
  whenever the pipeline refuses (disabled, low-rate bypass, overload);
* a drain loop collects up to ``max_batch`` messages per deadline
  window and resolves **all** routes for the batch in one
  :meth:`MatchService.prefetch_many` call — one kernel dispatch instead
  of one hint lookup per message — with the host trie serving per unique
  topic (not per message) on fallback;
* deliveries are grouped ``session → [messages]`` so ``Session.deliver``
  runs once per session per batch with amortized ``Publish``
  construction, sharing one zero-copy :class:`Message` (payload and all)
  across subscribers whenever no per-subscription transform applies;
* per-client sends flush in bulk: ONE ``emit``/``outbox_put`` per client
  per batch instead of one per message;
* shared-subscription routes batch per ``(group, filter)`` slice
  through :meth:`SharedSub.pick_batch` + ``Broker._dispatch_shared_batch``
  — ONE strategy call assigns members for the whole slice, producing
  the identical pick sequence (round-robin, sticky, ...) the
  per-message path would, with ack-aware per-message redispatch only
  when a picked member nacks.

**Shape-aware gate** (``shape_routes``): the chunk delivery stage feeds
an EWMA of observed fan-out legs per message back to ``offer()``.  When
the workload is ~1:1 (paired clients, no fan-out to amortize) the offer
refuses while idle — the per-message path with instant synchronous
delivery is as fast or faster there — and a probe message is admitted
every ``shape_probe_s`` so the estimate tracks workload changes.

**Adaptive serve-batch sizing** (BENCH_r05: batch 2048 → p99 105 ms vs
398 ms at 8192 at similar capacity): the batch bound follows the
observed arrival rate — a batch covers at most ``adapt_window_s`` of
arrivals, capped at ``max_batch`` — using the same windowed-rate
estimator as ``MatchService``'s adaptive bypass.  Below ``bypass_rate``
msg/s the pipeline refuses outright and the per-message path serves, so
single-client latency never pays the batching window.

Ordering per (client, topic) is preserved: the queue is FIFO, batches
process in order, and per-session grouping appends in message order.
The low-rate bypass only engages while the queue is empty and no batch
is in flight, so a bypassed message can never overtake a queued one.
The only exception is queue overload (``queue_cap``): refusal there
hands messages to the sync path ahead of the backlog — survival over
ordering, counted in ``broker.fanout.overflow``.

**Supervision + overload** (PR 3): with a :class:`~emqx_tpu.supervise.
Supervisor` attached, the drain loop runs as a permanent child — a
crash or injected kill restarts it (backoff + restart-intensity
escalation) instead of silently stopping delivery, and an un-drained
queue re-publishes through the sync path on supervised shutdown.  With
an :class:`~emqx_tpu.broker.olp.Olp` attached, sustained overload sheds
per policy at ``offer()``: QoS0 drops first (``broker.olp.shed_qos0``),
retained/delayed publishes defer until the overload clears
(``broker.olp.deferred``), QoS1/2 keep riding the inflight-window
backpressure — queues never grow unboundedly.

Fault containment: an accepted publish is never lost.  A raising
publish hook, route-planning failure, or delivery/emit callback error
falls back to the per-message path for the affected messages (fold-
skipping via ``Broker.publish_folded`` once the ``message.publish``
fold has run, so retainer/delayed/rewrite side effects never fire
twice) and the drain loop stays alive.  On ``stop()``, a batch
cancelled at an await point re-queues its unprocessed remainder so the
shutdown drain republishes it in order.  The delivery-stage fallback is
at-least-once: a leg already delivered before the error may duplicate.
"""

from __future__ import annotations

import asyncio
import logging
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional

from .. import faultinject as _fi
from .. import topic as T
from ..observe.span import stage_span
from .broker import DeliverResult
from .message import Message

log = logging.getLogger(__name__)

__all__ = ["FanoutPipeline"]



class FanoutPipeline:
    def __init__(
        self,
        broker: Any,
        metrics: Any = None,
        match_service: Any = None,
        max_batch: int = 2048,
        min_batch: int = 8,
        window_s: float = 0.0005,
        adapt_window_s: float = 0.05,
        bypass_rate: float = 0.0,
        queue_cap: int = 65536,
        shape_routes: float = 0.0,
        shape_probe_s: float = 0.25,
        supervisor: Any = None,
        olp: Any = None,
        deferred_cap: int = 4096,
        hists: Any = None,
        e2e_per_leg_sample: int = 0,
        flightrec: Any = None,
    ) -> None:
        self.broker = broker
        self.metrics = metrics
        self.match_service = match_service
        self.supervisor = supervisor
        self.olp = olp
        self.deferred_cap = deferred_cap
        self.max_batch = max_batch
        self.min_batch = min_batch
        self.window_s = window_s
        self.adapt_window_s = adapt_window_s
        self.bypass_rate = bypass_rate
        self.queue_cap = queue_cap
        self.shape_routes = shape_routes
        self.shape_probe_s = shape_probe_s

        self._q: Deque[Message] = deque()
        # sender → count of their messages currently in pipeline
        # custody (queued, deferred, or mid-batch).  MQTT's ordering
        # guarantee is per publisher connection per topic, so a message
        # whose SENDER has nothing in flight can safely bypass to the
        # synchronous path even while other senders' messages are
        # queued — the key that lets the shape gate keep working under
        # sustained ~1:1 load (config1) instead of only while idle.
        self._pending_senders: Dict[Any, int] = {}
        # overload-deferred retained/delayed publishes: parked while the
        # Olp reports overload, re-queued when it clears (shed policy:
        # QoS0 drops first, retained/delayed defer, QoS1/2 ride the
        # window backpressure)
        self._deferred: Deque[Message] = deque()
        self._wake = asyncio.Event()
        self._task: Optional[asyncio.Task] = None
        self._child = None           # supervise.Child when supervised
        self._running = False
        self._busy = False  # a batch is mid-flight (prefetch await point)
        # arrival-rate window (mirrors MatchService._note_arrival)
        self._win_start = time.monotonic()
        self._win_count = 0
        self._last_rate = 0.0
        # shape gate state: EWMA of observed fan-out legs per message
        # (None until the first batch is measured) and the next probe
        # deadline that keeps the estimate fresh while bypassing
        self._avg_routes: Optional[float] = None
        self._shape_probe_at = 0.0
        # lifetime accounting (also mirrored into metrics when attached)
        self.batches = 0
        self.msgs = 0
        # stage-level latency observatory: the three stage spans
        # (observe/span.py: histogram + the "fanout" ring of the flight
        # recorder) and two e2e histograms (observe/hist.py), None =
        # zero-call recording sites.  These stages follow obs.hist
        # .enable, ring and all: their stamps ride the message path.
        # All are written by the drain loop (main plane, one writer).
        self.hists = hists
        self.flightrec = flightrec
        self._sp_queue = self._sp_deliver = self._sp_flush = None
        self._h_e2e = self._h_e2e_leg = None
        # per-leg e2e sampling knob (obs.hist.e2e_per_leg_sample):
        # 0 = off (the leg histogram's recording site is zero-call,
        # spy-asserted), N = record every Nth delivery leg — the
        # per-subscriber skew signal without the per-delivery cost
        self.e2e_per_leg_sample = int(e2e_per_leg_sample)
        self._leg_ctr = 0
        ring = flightrec.ring("fanout") if flightrec is not None else None
        if hists is not None:
            self._sp_queue = stage_span("fanout_queue", hists, ring)
            self._sp_deliver = stage_span("deliver", hists, ring)
            self._sp_flush = stage_span("flush", hists, ring)
            self._h_e2e = hists.hist("obs.e2e.publish_deliver")
            if self.e2e_per_leg_sample > 0:
                self._h_e2e_leg = hists.hist("obs.e2e.publish_deliver_leg")
        # queue-head arrival stamp for the fanout_queue span: set when
        # a message lands in an EMPTY queue, re-armed at each batch pop
        # — per-batch oldest-wait without a parallel timestamp deque
        # (deferred re-queues and cancel-requeues stay approximate)
        self._q_head_ns = 0

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    async def start(self) -> None:
        self._running = True
        if self.supervisor is not None:
            # supervised: a crashed/killed drain loop restarts per
            # policy instead of silently stopping delivery; the drain
            # callback preserves the "accepted publishes never drop"
            # guarantee if the SUPERVISOR stops us (node shutdown)
            self._child = self.supervisor.start_child(
                "broker.fanout", self._run, restart="permanent",
                drain=self._drain_queue)
        else:
            self._task = asyncio.ensure_future(self._run())

    async def stop(self) -> None:
        """Stop draining; leftover queued messages take the per-message
        correctness path so shutdown never loses accepted publishes."""
        self._running = False
        if self._child is not None:
            await self._child.stop()   # runs _drain_queue after the task
            self._child = None
            return
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except (asyncio.CancelledError, Exception):
                log.debug("fanout drain task exit", exc_info=True)
            self._task = None
        self._drain_queue()

    def _drain_queue(self) -> None:
        """Republish everything still queued (and overload-deferred)
        through the synchronous per-message path.  Idempotent."""
        while self._deferred:
            self._q.append(self._deferred.popleft())
        while self._q:
            msg = self._q.popleft()
            self._untrack([msg])
            try:
                self.broker.publish(msg)
            except Exception:
                log.exception("fanout drain-on-stop publish failed")

    # ------------------------------------------------------------------
    # ingress
    # ------------------------------------------------------------------

    def _note_arrival(self) -> None:
        now = time.monotonic()
        dt = now - self._win_start
        if dt >= 0.05:
            self._last_rate = self._win_count / dt
            self._win_start = now
            self._win_count = 0
        self._win_count += 1

    def offer(self, msg: Message) -> bool:
        """Accept ``msg`` for batched fanout.  False → the caller must
        deliver via the per-message path (``Broker.publish``)."""
        if not self._running:
            return False
        T.validate(msg.topic, "name")  # parity with Broker.publish
        adm = self.broker.admission
        if adm is not None and msg.qos == 0 \
                and adm.shed_qos0(msg.sender):
            # admission quarantine (broker/admission.py): the batched
            # twin of the Broker.publish shed — consumed by policy,
            # never queued, mirroring the olp QoS0 shed below
            self.broker.hooks.run("message.dropped",
                                  (msg, "admission_shed"))
            return True
        self._note_arrival()
        olp = self.olp
        if olp is not None and olp.overloaded():
            # sustained overload (emqx_olp policy): shed QoS0 first,
            # defer retained/delayed, and let QoS1/2 ride the normal
            # queue — their backpressure is the inflight window
            # (InflightFullError → mqueue) rather than queue growth.
            if msg.retain or msg.topic.startswith("$delayed/"):
                if len(self._deferred) < self.deferred_cap:
                    self._deferred.append(msg)
                    self._track(msg)
                    if self.metrics is not None:
                        self.metrics.inc("broker.olp.deferred")
                    return True
                return False  # deferral full: sync path decides
            if msg.qos == 0:
                if self.metrics is not None:
                    self.metrics.inc("broker.olp.shed_qos0")
                self.broker.hooks.run("message.dropped", (msg, "olp_shed"))
                return True   # consumed: dropped by policy, not queued
        if len(self._q) >= self.queue_cap:
            # overload: shed to the sync path rather than grow unbounded
            if self.metrics is not None:
                self.metrics.inc("broker.fanout.overflow")
            return False
        if (
            self.bypass_rate > 0
            and not self._q
            and not self._busy
            and self._last_rate < self.bypass_rate
        ):
            # single-digit-rate publisher: the batching window would cost
            # more latency than it amortizes (same logic as the match
            # service's device bypass).  Safe for ordering: nothing is
            # queued or in flight that this message could overtake.
            if self.metrics is not None:
                self.metrics.inc("broker.fanout.bypass")
            return False
        if (
            self.shape_routes > 0
            and self._avg_routes is not None
            and self._avg_routes <= self.shape_routes
            and msg.sender not in self._pending_senders
        ):
            # shape gate: batching amortizes per-message cost across
            # fan-out legs; on ~1:1 paired-client shapes there is
            # nothing to amortize and the per-message path's instant
            # synchronous delivery wins.  Safe whenever this SENDER has
            # nothing in pipeline custody — MQTT orders per publisher
            # per topic, so other senders' queued messages cannot be
            # overtaken in any way the spec (or a subscriber) can
            # observe.  A probe message is still admitted every
            # shape_probe_s so the estimate notices when the workload
            # grows fan-out again.
            now2 = time.monotonic()
            if now2 >= self._shape_probe_at:
                self._shape_probe_at = now2 + self.shape_probe_s
            else:
                if self.metrics is not None:
                    self.metrics.inc("broker.fanout.shape_bypass")
                return False
        if self._sp_queue is not None and not self._q:
            self._q_head_ns = time.perf_counter_ns()
        self._q.append(msg)
        self._track(msg)
        self._wake.set()
        return True

    def _track(self, msg: Message) -> None:
        d = self._pending_senders
        s = msg.sender
        d[s] = d.get(s, 0) + 1

    def _untrack(self, msgs: List[Message]) -> None:
        d = self._pending_senders
        for m in msgs:
            s = m.sender
            v = d.get(s)
            if v is not None:
                if v <= 1:
                    del d[s]
                else:
                    d[s] = v - 1

    def will_accept(self, headroom: int = 1) -> bool:
        """Side-effect-free preflight of :meth:`offer` for the
        publish-run ingest fast path: True only when the next
        ``headroom`` QoS1/2 offers are GUARANTEED to be accepted (and
        none would consume gate state like the shape probe).  False in
        every ambiguous case, so a bailing caller reproduces the
        per-message path byte-for-byte.  Only valid from the pipeline's
        own loop with no awaits between the check and the offers."""
        if not self._running:
            return False
        if self.olp is not None and self.olp.overloaded():
            return False
        if len(self._q) + headroom > self.queue_cap:
            return False
        idle = not self._q and not self._busy
        if self.bypass_rate > 0 and idle \
                and self._last_rate < self.bypass_rate:
            return False
        if self.shape_routes > 0 \
                and self._avg_routes is not None \
                and self._avg_routes <= self.shape_routes:
            # the shape gate may bypass per-sender at any queue depth
            return False
        return True

    def _batch_bound(self) -> int:
        """Arrival-rate-adaptive batch bound: one batch covers at most
        ``adapt_window_s`` of offered traffic, so flush time (and with it
        delivery p99) tracks load instead of the static cap."""
        by_rate = int(self._last_rate * self.adapt_window_s)
        return max(self.min_batch, min(self.max_batch, by_rate))

    # ------------------------------------------------------------------
    # drain loop
    # ------------------------------------------------------------------

    async def _run(self) -> None:
        if self._q or self._deferred:
            # supervisor restart mid-backlog: the previous run's wake
            # may have been consumed — never stall on a non-empty queue
            self._wake.set()
        while True:
            await self._wake.wait()
            self._wake.clear()
            if _fi._injector is not None:
                # chaos seam: BEFORE the batch pops, so a raise kills
                # the drain task without stranding popped messages
                act = _fi._injector.act("fanout.drain")
                if act == "raise":
                    raise _fi.InjectedFault("fanout.drain")
                if act == "delay":
                    await _fi._injector.pause()
            if self.olp is not None:
                self.olp.report(queue_depth=len(self._q))
                if self._deferred and not self.olp.overloaded():
                    # overload cleared: deferred retained/delayed
                    # publishes rejoin the batch queue
                    while self._deferred and len(self._q) < self.queue_cap:
                        self._q.append(self._deferred.popleft())
            if not self._q:
                continue
            if self.window_s > 0:
                # deadline batching: let concurrent publishes pile in
                await asyncio.sleep(self.window_s)
            bound = self._batch_bound()
            n = min(len(self._q), bound)
            popleft = self._q.popleft
            batch = [popleft() for _ in range(n)]
            if self._sp_queue is not None:
                # fanout_queue span: oldest queue wait for this batch
                # (head stamp → pop), re-armed for the remaining queue
                now_ns = time.perf_counter_ns()
                head = self._q_head_ns
                if head:
                    self._sp_queue.rec(head, now_ns, n)
                self._q_head_ns = now_ns if self._q else 0
            if self._q:
                self._wake.set()
            self._busy = True
            t0 = time.perf_counter()
            try:
                await self._process(batch)
            except asyncio.CancelledError:
                raise
            except Exception:
                # belt-and-braces: _process guards each stage itself, but
                # a bug here must never kill the drain task — offer()
                # would keep accepting (and the channel PUBACK-ing)
                # publishes that are never delivered
                log.exception("fanout batch processing failed")
                if self.metrics is not None:
                    self.metrics.inc("broker.fanout.errors")
            finally:
                self._busy = False
            if self.metrics is not None:
                m = self.metrics
                m.inc("broker.fanout.batches")
                m.inc("broker.fanout.msgs", n)
                m.set("broker.fanout.batch_size", n)
                m.set("broker.fanout.depth", len(self._q))
                m.inc(
                    "broker.fanout.flush_us",
                    int((time.perf_counter() - t0) * 1e6),
                )
            self.batches += 1
            self.msgs += n
            if self._deferred and (
                    self.olp is None or not self.olp.overloaded()):
                self._wake.set()   # re-queue deferred next iteration

    # loop-fairness bound: at most this many messages fan out per
    # synchronous stretch; between chunks the drain loop yields so
    # connection IO (reads, acks, other sessions' writes) keeps flowing
    # under large batches.  Grouping amortization saturates well below
    # this, so the chunking costs ~nothing.
    CHUNK = 256

    async def _process(self, batch: List[Message]) -> None:
        done = 0
        try:
            # batch-resolve device hints up front: ONE prefetch_many
            # kernel dispatch covers every unique topic in the batch, so
            # stage 2's device_match serves from fresh hints instead of
            # one per-publish prefetch (bounded by the service's
            # prefetch_timeout_s; failure → host trie serves)
            if self.match_service is not None:
                try:
                    # {topic: max qos} — the mapping iterates as the
                    # topic set AND carries the QoS the deadline serve
                    # plane's brownout stage-2 shed keys on
                    topic_qos: Dict[str, int] = {}
                    for m in batch:
                        q = topic_qos.get(m.topic)
                        if q is None or m.qos > q:
                            topic_qos[m.topic] = m.qos
                    await self.match_service.prefetch_many(topic_qos)
                except asyncio.CancelledError:
                    raise
                except Exception:
                    log.exception(
                        "fanout prefetch_many failed (host trie serves)")
            for i in range(0, len(batch), self.CHUNK):
                self._process_chunk(batch[i:i + self.CHUNK])
                done = i + self.CHUNK
                if done < len(batch):
                    await asyncio.sleep(0)
        except asyncio.CancelledError:
            # stop() cancelled us at an await point.  Chunks are
            # synchronous, so everything from `done` on is untouched —
            # hand it back to the queue front (order preserved) for
            # stop()'s drain, honoring "accepted publishes never drop"
            self._q.extendleft(reversed(batch[done:]))
            raise

    def _plan_routes(self, topics) -> Dict[str, list]:
        broker = self.broker
        routes_of: Dict[str, list] = {}
        device_match = broker.device_match
        match_routes = broker.router.match_routes
        for t in topics:
            routes = device_match(t) if device_match is not None else None
            routes_of[t] = routes if routes is not None else match_routes(t)
        return routes_of

    def _fallback(self, msgs: List[Message], folded: bool) -> None:
        """Per-message fallback for a failed pipeline stage.  ``folded``
        selects ``publish_folded`` so messages whose ``message.publish``
        fold already ran don't fire retainer/delayed/rewrite twice."""
        broker = self.broker
        if self.metrics is not None:
            self.metrics.inc("broker.fanout.fallback", len(msgs))
        publish = broker.publish_folded if folded else broker.publish
        for m in msgs:
            try:
                publish(m)
            except Exception:
                log.exception("fanout fallback publish failed")

    def _process_chunk(self, batch: List[Message]) -> None:
        try:
            self._process_chunk_inner(batch)
        finally:
            # the chunk left pipeline custody (delivered, dropped or
            # fallen back) — its senders may shape-bypass again
            self._untrack(batch)

    def _process_chunk_inner(self, batch: List[Message]) -> None:
        broker = self.broker
        hooks = broker.hooks
        # -- stage 1: publish hooks (retainer/rewrite/delayed ride this
        # fold) — per message, identical to Broker.publish.  A raising
        # hook sends THAT message down the sync path (its fold re-runs,
        # same exposure as any sync retry); the rest stay batched.
        msgs: List[Message] = []
        for msg in batch:
            try:
                m = hooks.run_fold("message.publish", (), msg)
            except Exception:
                log.exception("publish fold failed; message falls back "
                              "to the per-message path")
                self._fallback([msg], folded=False)
                continue
            if m is None or m.headers.get("allow_publish") is False:
                continue
            msgs.append(m)
        if not msgs:
            return
        # -- stage 2: route resolution once per UNIQUE topic (device
        # hints parked by prefetch_many serve here; host trie
        # otherwise), not once per message.  Nothing is delivered yet
        # and every fold already ran, so failure falls back fold-skipping
        # per message — no duplicates, no double hook side effects.
        try:
            routes_of = self._plan_routes({m.topic for m in msgs})
        except Exception:
            log.exception("fanout planning failed; chunk falls back to "
                          "the per-message path")
            self._fallback(msgs, folded=True)
            return
        try:
            self._deliver_chunk(msgs, routes_of)
        except Exception:
            # stages 3–5 touch callbacks the broker doesn't guard
            # (session.deliver, shared picks, delivered/dropped taps,
            # emit).  Partial delivery may have happened, so the
            # fold-skipping re-dispatch can duplicate a leg (at-least-
            # once on this error path) — but accepted publishes are
            # never lost and the drain loop survives.
            log.exception("fanout delivery failed; chunk falls back to "
                          "the per-message path")
            self._fallback(msgs, folded=True)

    def _deliver_chunk(self, msgs: List[Message], routes_of: Dict[str, list]) -> None:
        broker = self.broker
        hooks = broker.hooks
        # -- stage 3: group (session → [messages]) and ($share group →
        # [messages]); cluster forwards keep per-message semantics
        plan: Dict[str, List[Message]] = {}
        shared_slices: Dict[Any, List[Message]] = {}  # (group, flt) → msgs
        fwd_legs = 0
        res = DeliverResult()  # shared-path sends + accounting
        effective = broker._effective
        subscribers = broker.subscribers
        node = broker.node
        for m in msgs:
            routes = routes_of[m.topic]
            if not routes:
                hooks.run("message.dropped", (m, "no_subscribers"))
                continue
            seen_shared = None
            for flt, dest in routes:
                if isinstance(dest, tuple):  # (group, node) shared route
                    group, _node = dest
                    if seen_shared is None:
                        seen_shared = set()
                    elif (group, flt) in seen_shared:
                        continue
                    seen_shared.add((group, flt))
                    bucket = shared_slices.get((group, flt))
                    if bucket is None:
                        bucket = shared_slices[(group, flt)] = []
                    bucket.append(m)
                elif dest == node:
                    sender = m.sender
                    eff_cache: Dict[Any, Message] = {}
                    for clientid, opts in subscribers.get(flt, {}).items():
                        if opts.nl and sender == clientid:
                            continue  # MQTT5 No-Local
                        # subscribers sharing identical SubOpts (the
                        # normal fan-out) share ONE effective message —
                        # one clone per distinct transform, not per leg
                        eff = eff_cache.get(opts)
                        if eff is None:
                            eff = eff_cache[opts] = effective(m, opts)
                        bucket = plan.get(clientid)
                        if bucket is None:
                            bucket = plan[clientid] = []
                        bucket.append(eff)
                elif broker.on_forward is not None:
                    if broker.on_forward(dest, flt, m):
                        res.matched += 1
                        fwd_legs += 1
        # -- stage 3.5: batched shared dispatch — ONE pick_batch per
        # ($share group, filter) covers its whole batch slice, with
        # per-message ack-aware redispatch only on nack
        for (group, flt), ms in shared_slices.items():
            broker._dispatch_shared_batch(group, flt, ms, res)
        # shape signal for the offer() gate: observed fan-out legs per
        # message this chunk (EWMA)
        self._note_shape(
            len(msgs),
            sum(len(b) for b in plan.values())
            + sum(len(b) for b in shared_slices.values())
            + fwd_legs,
        )
        # -- stage 4: one Session.deliver per session per batch
        out = res.publishes
        sessions = broker.sessions
        delivered_taps = hooks.has("message.delivered")
        bmetrics = broker.metrics
        h_e2e = self._h_e2e
        h_leg = self._h_e2e_leg
        t4 = time.perf_counter_ns() if self._sp_deliver is not None else 0
        now_wall = (time.time()
                    if h_e2e is not None or h_leg is not None else 0.0)
        for clientid, effs in plan.items():
            sess = sessions.get(clientid)
            if sess is None:
                continue
            mu = sess.mutex
            if mu is None:
                sends, dropped = sess.deliver(effs)
            else:
                # shard-owned session (transport/shards.py): exclude
                # the owning shard loop's ack handling for the window
                # admission
                with mu:
                    sends, dropped = sess.deliver(effs)
            if sends:
                n_sends = len(sends)
                res.matched += n_sends
                if bmetrics is not None:
                    bmetrics.inc("messages.delivered", n_sends)
                if h_e2e is not None:
                    # publish→deliver e2e, SAMPLED once per session per
                    # chunk on the oldest leg (the legs of one deliver
                    # share a batch window, so per-leg recording would
                    # pay per-message cost for sub-window resolution);
                    # SlowSubs records per leg when enabled
                    h_e2e.record_s(now_wall - sends[0].msg.timestamp)
                if h_leg is not None:
                    # per-LEG variant, every Nth leg across chunks (the
                    # counter persists, so skewed fan-outs can't dodge
                    # the sampler by staying under N legs per session)
                    step = self.e2e_per_leg_sample
                    for p in sends:
                        self._leg_ctr += 1
                        if self._leg_ctr >= step:
                            self._leg_ctr = 0
                            h_leg.record_s(now_wall - p.msg.timestamp)
                bucket = out.get(clientid)
                if bucket is None:
                    out[clientid] = sends
                else:
                    bucket.extend(sends)
                if delivered_taps:
                    for p in sends:
                        hooks.run("message.delivered", (clientid, p.msg))
            for d in dropped:
                hooks.run("message.dropped", (d, "queue_full"))
        # -- stage 5: bulk flush — ONE emit per client per batch
        t5 = time.perf_counter_ns() if self._sp_deliver is not None else 0
        emit = broker.emit
        for clientid, pubs in out.items():
            emit(clientid, pubs)
        if self._sp_deliver is not None:
            t6 = time.perf_counter_ns()
            self._sp_deliver.rec(t4, t5, len(msgs))
            self._sp_flush.rec(t5, t6, len(out))

    # ------------------------------------------------------------------

    def _note_shape(self, n_msgs: int, n_legs: int) -> None:
        if n_msgs <= 0:
            return
        r = n_legs / n_msgs
        a = self._avg_routes
        self._avg_routes = r if a is None else a * 0.8 + r * 0.2

    def depth(self) -> int:
        return len(self._q)

    def info(self) -> Dict[str, Any]:
        return {
            "running": self._running,
            "depth": len(self._q),
            "deferred": len(self._deferred),
            "batches": self.batches,
            "msgs": self.msgs,
            "batch_bound": self._batch_bound(),
            "last_rate": round(self._last_rate, 1),
            "avg_routes": (round(self._avg_routes, 2)
                           if self._avg_routes is not None else None),
        }
