"""Fixed counter set — the ``emqx_metrics`` analog.

Behavioral reference: ``apps/emqx/src/emqx_metrics.erl`` [U] (SURVEY.md
§5.5): a fixed, atomics-backed counter table created at boot; modules
``inc/1`` by name; REST/Prometheus read the whole table.  We keep the
reference's metric names verbatim (bytes/packets/messages/delivery/client/
session/authorization groups) and extend with a ``tpu.*`` group for the
device match path (batch sizes, kernel latency, mirror staleness) —
additions, never renames, so dashboards diff cleanly.

Python ints under a single writer (asyncio event loop / GIL) play the
role of atomics; `inc` is a dict add, no locks.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional

__all__ = [
    "Metrics", "METRIC_NAMES", "TPU_METRIC_NAMES", "FANOUT_METRIC_NAMES",
    "ROBUSTNESS_METRIC_NAMES", "CONNPLANE_METRIC_NAMES",
    "MATCH_SERVE_METRIC_NAMES", "MULTICHIP_METRIC_NAMES",
    "MESH_METRIC_NAMES", "TABLE_METRIC_NAMES",
    "OBS_METRIC_NAMES", "ADMISSION_METRIC_NAMES",
    "RUNTIME_METRIC_NAMES",
]

# -- the reference's fixed counter names, grouped as in emqx_metrics.erl [U]
METRIC_NAMES: List[str] = [
    # bytes
    "bytes.received", "bytes.sent",
    # packets
    "packets.received", "packets.sent",
    "packets.connect.received", "packets.connack.sent",
    "packets.publish.received", "packets.publish.sent",
    "packets.publish.error", "packets.publish.auth_error",
    "packets.publish.dropped",
    "packets.puback.received", "packets.puback.sent",
    "packets.puback.inuse", "packets.puback.missed",
    "packets.pubrec.received", "packets.pubrec.sent",
    "packets.pubrec.inuse", "packets.pubrec.missed",
    "packets.pubrel.received", "packets.pubrel.sent",
    "packets.pubrel.missed",
    "packets.pubcomp.received", "packets.pubcomp.sent",
    "packets.pubcomp.inuse", "packets.pubcomp.missed",
    "packets.subscribe.received", "packets.suback.sent",
    "packets.subscribe.error", "packets.subscribe.auth_error",
    "packets.unsubscribe.received", "packets.unsuback.sent",
    "packets.unsubscribe.error",
    "packets.pingreq.received", "packets.pingresp.sent",
    "packets.disconnect.received", "packets.disconnect.sent",
    "packets.auth.received", "packets.auth.sent",
    "packets.connack.error", "packets.connack.auth_error",
    # messages
    "messages.received", "messages.sent",
    "messages.qos0.received", "messages.qos0.sent",
    "messages.qos1.received", "messages.qos1.sent",
    "messages.qos2.received", "messages.qos2.sent",
    "messages.publish", "messages.dropped",
    "messages.dropped.no_subscribers", "messages.dropped.await_pubrel_timeout",
    "messages.dropped.receive_maximum", "messages.dropped.expired",
    "messages.dropped.queue_full", "messages.dropped.too_large",
    # detail counters for drop reasons our delivery stack emits beyond
    # the reference set (registry-drift: inc_msg_dropped silently skips
    # unregistered detail keys — these two under-counted before PR 4)
    "messages.dropped.olp_shed", "messages.dropped.forward_no_peer",
    "messages.forward", "messages.delayed", "messages.delivered",
    "messages.acked", "messages.retained",
    # delivery
    "delivery.dropped", "delivery.dropped.no_local",
    "delivery.dropped.too_large", "delivery.dropped.qos0_msg",
    "delivery.dropped.queue_full", "delivery.dropped.expired",
    # client lifecycle
    "client.connect", "client.connack", "client.connected",
    "client.authenticate", "client.auth.anonymous", "client.authorize",
    "client.subscribe", "client.unsubscribe", "client.disconnected",
    # session lifecycle
    "session.created", "session.resumed", "session.takenover",
    "session.discarded", "session.terminated",
    # authorization
    "authorization.allow", "authorization.deny",
    "authorization.cache_hit", "authorization.cache_miss",
    "authorization.superuser", "authorization.nomatch",
    # overload protection
    "olp.delay.ok", "olp.delay.timeout", "olp.hbn", "olp.gc",
    "olp.new_conn",
]

# -- TPU-native additions (SURVEY.md §5.5 "add match-kernel metrics")
TPU_METRIC_NAMES: List[str] = [
    "tpu.match.batches", "tpu.match.topics",
    "tpu.match.active_overflow", "tpu.match.match_overflow",
    "tpu.match.fallback_host", "tpu.mirror.refresh",
    "tpu.mirror.delta_applied", "tpu.mirror.recompile",
    "tpu.match.hint_served", "tpu.match.hint_stale", "tpu.match.bypass",
    "tpu.match.hint_evicted",
    # waiters whose prefetch outlasted tpu.prefetch_timeout (a compile
    # or a stalled device): those publishes walked the host trie
    "tpu.match.prefetch_timeout",
]

# -- batched fanout pipeline (broker/fanout.py) + broker drop accounting.
# batch_size/depth are last-observed values (set), the rest accumulate
# (inc); avg batch = fanout.msgs / fanout.batches, avg flush =
# fanout.flush_us / fanout.batches.
FANOUT_METRIC_NAMES: List[str] = [
    "broker.fanout.batches", "broker.fanout.msgs",
    "broker.fanout.batch_size", "broker.fanout.flush_us",
    "broker.fanout.depth", "broker.fanout.bypass",
    "broker.fanout.overflow", "broker.fanout.fallback",
    "broker.fanout.errors", "broker.fanout.shape_bypass",
    "broker.outbox.dropped",
    # acknowledged-delivery stack (PR 2): bulk QoS1/2 window admissions
    # and ack/write flushes that merged >1 packet into one write
    "broker.inflight.batch_admitted", "broker.ack.coalesced_writes",
    # batched ingest (PR 5): ack runs recognized by the parser fast
    # path (one inc per packed run) and QoS2 state transitions that
    # covered >1 packet in one session call
    "broker.ack.run_parsed", "broker.qos2.batch",
]

# -- connection plane (transport/shards.py + transport/timerwheel.py).
# shards is the live worker-loop count (set), wheel_conns the aggregate
# timers resident in the hashed wheels (set, sampled by housekeeping),
# publish_runs accumulates one inc per packed same-client QoS1/2
# PUBLISH run the ingest fast path consumed.
CONNPLANE_METRIC_NAMES: List[str] = [
    "broker.conn.shards", "broker.timer.wheel_conns",
    "broker.ingest.publish_runs",
]

# -- supervision tree (supervise.py) + overload shedding on the batched
# delivery path (broker/olp.py wired into broker/fanout.py).  restarts
# accumulates; degraded is the CURRENT degraded-child count (set).
ROBUSTNESS_METRIC_NAMES: List[str] = [
    "broker.supervisor.restarts", "broker.supervisor.degraded",
    "broker.olp.shed_qos0", "broker.olp.deferred",
    # event-loop lag (sleep-drift sampler, broker/olp.py LoopLagProbe):
    # last observed drift in µs (set) — the CPU-saturation overload
    # signal that fires even when no queue grows
    "broker.olp.loop_lag_us",
]

# -- deadline-aware serve plane (broker/match_service.py, opt-in via
# match.deadline.enable).  deadline_dispatch counts partial batches the
# loop flushed because the oldest waiter's budget was about to expire;
# cpu_fallback counts waiters served from the CPU trie instead of the
# device (dispatch timeout/failure, breaker open, brownout shed, loop
# death); deadline_miss counts waiters resolved after their budget had
# already elapsed; breaker_state is the live circuit-breaker state
# (set: 0 closed, 1 open, 2 probing) and brownout_level the live olp
# brownout stage (set: 0-3).  pipeline_inflight is the live count of
# pipelined batches past dispatch awaiting readback (set, opt-in via
# match.pipeline.enable); readback_bytes accumulates the d2h bytes the
# match readback path actually shipped (inc): the served program's one
# packed array, 4·(B + SERVE_FLAT_MULT·B) per batch group in both serve
# modes; the mesh's routed step the same format, a block a dp group
# (its replicated step the dense compact rows).  backend_join_dispatches
# counts kernel dispatches served by the relational-join backend (inc,
# one per depth group; opt-in via match.backend) and autotune_picks the
# per-shape hash-vs-join measurements the autotuner recorded (inc, one
# per freshly measured shape).  readback_roundtrips accumulates the device
# buffers the readback path fetched (inc, by amount per batch group),
# each a d2h transfer of its own however many one device_get call
# names (that call starts them together: not one latency each, PERF.md
# §6): 1 a batch group, whatever the plane (the mesh counts its
# own buffers in tpu.mesh.answer_buffers).  Over tpu.match.batches it
# reads 1.0 where no batch split into depth groups.
MATCH_SERVE_METRIC_NAMES: List[str] = [
    "broker.match.deadline_dispatch", "broker.match.cpu_fallback",
    "broker.match.deadline_miss", "broker.match.breaker_state",
    "broker.match.brownout_level", "broker.match.pipeline_inflight",
    "tpu.match.readback_bytes", "tpu.match.readback_roundtrips",
    "tpu.match.backend_join_dispatches", "tpu.match.autotune_picks",
    # the serial serve paths' books on a batch (inc, by amount, once a
    # cycle): cycle_ns sums the obs.stage.match_cycle spans,
    # cycle_spanned_ns what of them lay inside a stage span (window,
    # hops, encode, dispatch, readback, epilogue) — the ratio guards
    # against a new unspanned hole in the cycle
    "tpu.match.cycle_ns", "tpu.match.cycle_spanned_ns",
]

# -- multichip serve backend (parallel/multichip_serve.py, opt-in via
# match.multichip.enable).  shard_devices is the mesh size dp*tp (set
# at construction); shard_dispatches counts publish batches served
# from the sharded table (inc, one per depth group); shard_failover
# counts dispatches refused at the match.shard seam — dead or
# fault-injected shard, the batch fell over to the CPU trie (inc);
# shard_restacks is the accumulated full re-upload count of the
# stacked per-shard tables (set).
#
# The ep_* names cover the prefix-EP routed front end (opt-in via
# match.multichip.ep.enable): ep_dispatches counts batches served
# through the routed step (inc); ep_overflow_rows accumulates rows the
# routed path failed open to the CPU trie — bucket overflow plus
# truncation (inc, by amount); ep_shard_width is the per-shard
# processed batch width tp*C of the last routed dispatch (set — the
# gate_shard_width_le_batch_over_tp numerator); ep_ici_bytes
# accumulates the analytic interconnect bill of the routing
# all_to_all (inc, by amount).
MULTICHIP_METRIC_NAMES: List[str] = [
    "tpu.match.shard_devices", "tpu.match.shard_dispatches",
    "tpu.match.shard_failover", "tpu.match.shard_restacks",
    "tpu.match.ep_dispatches", "tpu.match.ep_overflow_rows",
    "tpu.match.ep_shard_width", "tpu.match.ep_ici_bytes",
    # routed overflow-rate EWMA (set, 0..1): the smoothed fraction of
    # each routed batch that failed open via the psum'd overflow flags
    # — the input the capacity auto-resize keys on; a log-once warning
    # fires when it crosses match.multichip.ep.overflow_warn (the
    # latch re-arms after a successful capacity grow)
    "tpu.match.ep_overflow_ewma",
    # load-adaptive EP plane (opt-in via match.multichip.ep.autotune.
    # enable).  ep_cap_class is the live pow2 capacity-class exponent
    # (set on every flip; absent/0 = the static grid); ep_resizes
    # counts completed background capacity-class flips (inc);
    # ep_rebalances counts balance passes that staged a placement
    # override map (inc); ep_moved_roots is the number of roots the
    # LAST balance pass moved off their crc32 shard (set)
    "tpu.match.ep_cap_class", "tpu.match.ep_resizes",
    "tpu.match.ep_rebalances", "tpu.match.ep_moved_roots",
]

# -- degraded-mesh serving (parallel/multichip_serve.py +
# broker/match_service.py, opt-in via match.multichip.degraded.enable).
# state is the live health-ladder rung (set: 0 healthy, 1 degraded(S)
# — scoped failover serving on the survivors, 2 cpu-only);
# degraded_batches counts dispatches served while at least one shard
# was dead (inc); cpu_filled_rows accumulates the rows (EP-routed:
# whole rows owned by a dead shard; replicated: rows whose dead-owned
# filters were host-filled) the CPU trie answered under scoped
# failover (inc, by amount); rebuild_s is the last online shard
# rebuild's wall seconds (set); readmit_canary_fails counts re-admit
# attempts refused because the bit-parity canary batch disagreed with
# the CPU trie (inc) — the shard stays out.  apply_failed counts the
# times a CONFIGURED mesh did not come up (match.multichip.enable: the
# matcher's constructor or a partition apply raised; inc) — the service
# is not ready until a later sync pass succeeds, the host trie serves.
# operand_puts counts the host arrays a step's batch operands were
# placed from (inc; one a dispatch since the packed operand, so
# operand_puts / tpu.match.shard_dispatches reads 1.0 over a served
# window: warm calls and canaries place one each and dispatch nothing).
# answer_buffers counts the device buffers a mesh readback fetched (inc,
# by amount; a routed answer is one packed array, one buffer a dp group,
# so answer_buffers / tpu.match.shard_dispatches reads 1.0 at dp 1 over
# a served window, where the five-array answer before it read 11 at
# tp 4; canaries fetch outside readback and count nothing).
MESH_METRIC_NAMES: List[str] = [
    "tpu.mesh.state", "tpu.mesh.degraded_batches",
    "tpu.mesh.cpu_filled_rows", "tpu.mesh.rebuild_s",
    "tpu.mesh.readmit_canary_fails", "tpu.mesh.apply_failed",
    "tpu.mesh.operand_puts", "tpu.mesh.answer_buffers",
]

# -- streaming table lifecycle (broker/match_service.py, opt-in via
# match.segments.enable).  segment_load_s is the last cold-start
# segment load+reconcile time in seconds (set); compact_runs counts
# background compaction swaps (inc); dirty_rows_uploaded is the
# accumulated row count shipped by the scatter/grow-in-place paths
# (set, sampled from DeviceNfa each sync); compile_cache_hits is the
# kernel-cache hit count (set, sampled each sync).
TABLE_METRIC_NAMES: List[str] = [
    "tpu.table.segment_load_s", "tpu.table.compact_runs",
    "tpu.table.dirty_rows_uploaded", "tpu.table.compile_cache_hits",
]

# -- stage-level latency observatory (observe/hist.py + flightrec.py).
# dumps counts flight-recorder trace files written (inc, one per
# trigger: breaker trip, brownout escalation, supervisor_degraded,
# manual).  The latency histograms themselves live in HIST_NAMES
# (observe/hist.py), not here — they are distributions, not counters.
OBS_METRIC_NAMES: List[str] = [
    "obs.flightrec.dumps",
]

# -- batched admission plane (broker/admission.py, opt-in via
# admission.enable).  tracked_clients is the live feature-row count
# (set each tick — the reconnect-churn memory bound); throttled /
# quarantined are the CURRENT ladder populations at level >= 1 / >= 2
# (set); banned accumulates level-3 temp-bans issued (inc); shed_qos0
# accumulates QoS0 publishes dropped for quarantined senders (inc);
# fail_open counts scorer crash/kill/fault events that cleared every
# standing decision and raised admission_degraded (inc).  The derived
# drop detail messages.dropped.admission_shed rides the main list's
# inc_msg_dropped discipline.
ADMISSION_METRIC_NAMES: List[str] = [
    "broker.admission.tracked_clients", "broker.admission.throttled",
    "broker.admission.quarantined", "broker.admission.banned",
    "broker.admission.shed_qos0", "broker.admission.fail_open",
    "messages.dropped.admission_shed",
]

# -- host runtime (observe/heap.py): the cyclic collector's permanent
# generation.  freezes.growth counts freezes made while a route table
# grew (one each time it stood heap.GROWTH_STEP routes above where it
# last froze; the mark is the router's and only rises), freezes.settled
# those made once _sync_loop had landed a whole table on the device
# (first sync, growth re-upload; NOT a compaction swap or a shard
# rebuild, which recur); frozen_objects is gc.get_freeze_count() as the
# last settle read it (the call walks what it counts): what no full
# pass walks any more.  All three are the PROCESS's (the collector is),
# sampled into the table by the node's housekeeping (set).
# loop.busy_ns / loop.idle_ns: the node's event loop, as the lag probe's
# clock on its selector reads it (broker/olp.py LoopClock; inc, by the
# loop thread alone): idle is the time inside select(), busy the time
# from one select() to the next, one run of ready callbacks.
# gc.pause_ns / gc.collections: running totals of the process's
# collections (observe/heap.py; set, from the collector's callback, on
# whichever thread collected).  All four are read as deltas.
RUNTIME_METRIC_NAMES: List[str] = [
    "runtime.gc.freezes.growth", "runtime.gc.freezes.settled",
    "runtime.gc.frozen_objects",
    "runtime.loop.busy_ns", "runtime.loop.idle_ns",
    "runtime.gc.pause_ns", "runtime.gc.collections",
]


class Metrics:
    """A counter table with the reference's fixed name set.

    ``inc``/``get``/``all``; unknown names raise (mirroring the
    reference's fixed-at-boot table, which catches typos at call sites).
    """

    __slots__ = ("_c",)

    def __init__(self, extra: Optional[Iterable[str]] = None) -> None:
        self._c: Dict[str, int] = {n: 0 for n in METRIC_NAMES}
        self._c.update({n: 0 for n in TPU_METRIC_NAMES})
        self._c.update({n: 0 for n in FANOUT_METRIC_NAMES})
        self._c.update({n: 0 for n in ROBUSTNESS_METRIC_NAMES})
        self._c.update({n: 0 for n in CONNPLANE_METRIC_NAMES})
        self._c.update({n: 0 for n in MATCH_SERVE_METRIC_NAMES})
        self._c.update({n: 0 for n in MULTICHIP_METRIC_NAMES})
        self._c.update({n: 0 for n in MESH_METRIC_NAMES})
        self._c.update({n: 0 for n in TABLE_METRIC_NAMES})
        self._c.update({n: 0 for n in OBS_METRIC_NAMES})
        self._c.update({n: 0 for n in ADMISSION_METRIC_NAMES})
        self._c.update({n: 0 for n in RUNTIME_METRIC_NAMES})
        if extra:
            self._c.update({n: 0 for n in extra})

    def inc(self, name: str, n: int = 1) -> None:
        self._c[name] += n

    def dec(self, name: str, n: int = 1) -> None:
        self._c[name] -= n

    def set(self, name: str, v: int) -> None:
        """Last-observed-value metrics (batch_size, queue depth) share
        the fixed table; unknown names still raise like inc."""
        if name not in self._c:
            raise KeyError(name)
        self._c[name] = v

    def get(self, name: str) -> int:
        return self._c[name]

    def all(self) -> Dict[str, int]:
        return dict(self._c)

    def reset(self) -> None:
        for k in self._c:
            self._c[k] = 0

    # -- convenience aggregations used by the v3-compat REST shape --------
    def received_msgs(self) -> int:
        return self._c["messages.received"]

    def sent_msgs(self) -> int:
        return self._c["messages.sent"]

    def inc_recv_packet(self, ptype: str, nbytes: int = 0) -> None:
        """Bump the packets.<type>.received family (+ totals + bytes)."""
        self._c["packets.received"] += 1
        if nbytes:
            self._c["bytes.received"] += nbytes
        key = f"packets.{ptype}.received"
        if key in self._c:
            self._c[key] += 1

    def inc_sent_packet(self, ptype: str, nbytes: int = 0) -> None:
        self._c["packets.sent"] += 1
        if nbytes:
            self._c["bytes.sent"] += nbytes
        key = f"packets.{ptype}.sent"
        if key in self._c:
            self._c[key] += 1

    def inc_msg_received(self, qos: int) -> None:
        self._c["messages.received"] += 1
        self._c[f"messages.qos{min(qos, 2)}.received"] += 1

    def inc_msg_sent(self, qos: int) -> None:
        self._c["messages.sent"] += 1
        self._c[f"messages.qos{min(qos, 2)}.sent"] += 1

    def inc_msg_dropped(self, reason: str) -> None:
        self._c["messages.dropped"] += 1
        key = f"messages.dropped.{reason}"
        if key in self._c:
            self._c[key] += 1
