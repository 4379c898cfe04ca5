"""In-process device matcher for THIS broker's own publish path.

Round 1 left the TPU matcher reachable only through the external exhook
sidecar; the broker's own ``Broker.publish`` always walked the host
trie.  This service closes that gap:

* it mirrors the :class:`~emqx_tpu.broker.router.Router`'s **wildcard**
  filters into an :class:`IncrementalNfa`/:class:`DeviceNfa` pair by
  consuming the router's delta log (``deltas_since`` — the mria
  bootstrap-then-rlog pattern; a log gap triggers a full resnapshot),
  exact filters stay in the router's O(1) hash map;
* concurrent publishes are **micro-batched**: the connection layer's
  async intercept stage awaits :meth:`prefetch`, which rides a deadline
  batching loop into ONE kernel call, and parks the answer in an
  epoch-validated hint cache;
* the synchronous ``Broker.publish`` then consumes the hint via
  :meth:`hint_routes` (``Broker.device_match``) — if the hint can't be
  proven fresh or is absent, publish falls back to the host trie
  unchanged, so correctness never depends on the device;
* per-row kernel spills fail open to the router's own trie
  (SURVEY.md §5.3), counted in ``tpu.match.fallback_host``.

**Churn-resilient serving** (round-3 rework): hints
are no longer wholesale-invalidated by router mutations.  A hint is
stamped with the router epoch its table reflected; at consume time the
router's delta log since that epoch is checked and the hint stays valid
unless a *newly added wildcard filter* matches the topic.  Deletions are
inherently safe — :meth:`Router.routes_with_wild` resolves destinations
live, so removed filters/destinations drop out of the answer without
invalidation.  The same scheme covers rule co-batching via a rule
mutation log.  Under continuous subscribe/unsubscribe churn the device
path therefore keeps serving (duty cycle asserted in
tests/test_match_service.py) instead of collapsing to the host trie.

At low publish concurrency the batching window costs more than the host
trie answers (~12 µs); an **adaptive bypass** skips the device when the
recent arrival rate is below ``bypass_rate`` so single-client latency
stays at host-path levels.

Also co-batches the **rule engine**'s FROM filters (BASELINE config 3):
rules register their topic filters here under a separate id namespace,
and matched rule ids ride the same kernel call (see ``rule_filters``).

**Deadline-aware serve plane** (opt-in, ``match.deadline.enable``): the
fixed-window batch loop is replaced by a continuous-batching loop in
which every prefetch carries a latency *budget* (``match.deadline_ms``,
default = the measured CPU-iso serve p99) and latency is enforced, not
emergent:

* the loop dispatches a **partial batch** the moment the oldest waiter's
  budget (minus the EWMA-estimated dispatch time) is about to expire —
  ``broker.match.deadline_dispatch`` counts these forced flushes;
* the batch bound **adapts to the arrival rate** (EWMA, the fanout-gate
  estimator shape): a batch covers at most the budget's worth of
  arrivals, so batch size tracks load instead of pinning p99 to the
  worst-case fill time (BENCH_r05: batch 8192 → p99 398 ms, 2048 →
  105 ms);
* the short/long dual-lane depth split gets **per-lane caps** derived
  from the observed short-topic fraction, so a deep-topic flood cannot
  starve the cheap shallow kernel's latency;
* every device dispatch runs under a **per-dispatch timeout** with
  immediate CPU fallback: the host NFA + deep-filter trie answer the
  whole batch and mint hints (``broker.match.cpu_fallback``), so a hung
  kernel costs one timeout, not ``prefetch_timeout_s`` per waiter;
* consecutive dispatch failures trip a **circuit breaker**
  (``match.breaker.threshold``) into CPU-serve mode with the
  ``match_degraded`` alarm raised; a supervised recovery child
  (``match.probe``) re-dispatches a canary batch every
  ``match.breaker.probe_interval`` and closes the breaker (and clears
  the alarm) when the device answers again;
* sustained overload walks the :class:`~emqx_tpu.broker.olp.Olp`
  **brownout ladder**: stage 1 shrinks the adaptive batch caps, stage 2
  sheds QoS0 prefetches to the CPU trie, stage 3 is full CPU serve —
  degradation is latency-first, never queue-depth-first.

**Streaming table lifecycle** (opt-in, ``match.segments.enable``): the
delta path is promoted to the PRIMARY lifecycle — the service never
rebuilds or recompiles on the hot path:

* **persistent compacted segments** (``storage/segments.py``): cold
  start loads the flattened table from a versioned, checksummed segment
  file and replays only the diff against the live router, instead of
  re-adding every filter (64 s at 10M, BENCH_r05); a corrupt
  segment is rejected by checksum and falls back to the full rebuild;
* **background delta compaction**: a supervised ``table.compact`` child
  periodically builds a compacted replacement table + device twin OFF
  the event loop, writes the next segment, and swaps both in atomically
  on the loop (``table.swap`` chaos seam fires BEFORE any state
  mutates, so a mid-swap kill is a no-op and the supervised restart
  resumes).  Mutations landing during the build are tracked in a dirty
  set and fixed up at swap; in-flight device batches spanning the swap
  are discarded via the ``_table_gen`` guard (same ``_StaleRace``
  fail-open as aid reuse).  Hints survive the swap untouched — they
  carry router epochs and filter STRINGS, never aids;
* **dirty-region device upload** (``DeviceNfa.dirty_regions``): a table
  resize pads the device buffers in place and scatters only the tracked
  dirty rows (the rehashed edge table ships whole when it moved),
  replacing the whole-table ``device_put`` on growth;
* **padded-shape kernel cache** (``ops/kernel_cache.py``): serve
  dispatches ride AOT-compiled executables keyed on padded shapes, and
  the NEXT pow2 shape pre-warms in the background (``table.prewarm``)
  before growth reaches it — a resize is served from the cache instead
  of stalling a prefetch on an XLA compile.

**Overlapped serve pipeline** (opt-in, ``match.pipeline.enable``): the
dispatch tax BENCH_r05 measured (match kernel ~17 ms p99 vs 398 ms
served at batch 8192 — the gap is host-side encode and serialized
dispatch) is attacked by overlapping the three serve stages, the way
the FPGA XML-filtering architecture streams documents through match
units while I/O overlaps compute (on the attached chip it tied the
serial loop on the one cell, PERF.md §5, PR 27; the switch means
overlap and nothing else):

* **encode off the loop, overlapped**: ``encode_batch`` for batch N+1
  runs in a worker thread while batch N computes on device;
* **double-buffered dispatch**: up to ``match.pipeline.depth``
  (default 2) batches sit past dispatch awaiting readback
  (``broker.match.pipeline_inflight``); the serve loop goes back to
  batching the moment a dispatch lands, instead of parking on the
  round trip;
* **readback in a supervised ``match.readback`` child**: the same one
  packed array the serial path reads (``DeviceNfa.serve`` →
  ``match_kernel.decode_packed``), fetched off the serve loop;
* **per-slot staleness guards**: every in-flight slot carries the
  table generation + aid-reuse counters it dispatched against; a
  segment swap or aid reuse landing mid-flight discards exactly the
  stale slot (CPU trie answers it, no breaker strike) while fresher
  slots keep their device answers;
* the ``match.readback`` chaos seam (raise / delay / hang) sits at the
  d2h boundary of BOTH the pipelined child and the flag-off path; a
  killed readback child resolves its in-flight slots immediately
  (waiters fail over to the CPU trie) and the supervised restart
  resumes consuming.

**Multichip serve backend** (opt-in, ``match.multichip.enable``): the
match TABLE shards by topic-prefix over a dp×tp device mesh
(``parallel/multichip_serve.py``) and real publish traffic serves from
ALL chips — the on-device analog of the reference's cluster routing
(ekka/mria replicated route tables), and the dryrun→serve step for
every MULTICHIP_r05 configuration:

* each ``tp`` shard owns the filters whose root token hashes to it
  (8 chips hold 8× the filters — the path past 10M toward 100M);
  publish batches are fanned over ``tp`` and sharded over ``dp``;
* per-shard matches translate through a local→service accept-id map ON
  DEVICE and leave the mesh as the dense compact contract
  (``CompactFanoutResult``: per-row disjoint id segments,
  concat-no-dedup), so ring/ICI + d2h traffic is proportional to
  MATCHES, never table width (ROADMAP dispatch-tax residual (d));
* maintenance rides the SAME drain/apply cycle: ``_table_add``/
  ``_table_del`` note mutations into per-shard host subtables, the
  sync loop applies deltas off the event loop, a compaction swap
  repartitions from the fresh aid space (the service is NOT ready
  and the host trie serves while the partition rebuilds);
* readiness means the configured plane: with the flag on, ``ready``
  (and ``info()["ready"]``) is true only once the mesh has been
  applied and its step shapes are warm.  A constructor or an apply
  that fails is counted (``tpu.mesh.apply_failed``), logged once per
  distinct error and retried by the sync loop (1, 2 … 32 s apart);
  until it lands the host trie serves, as before the first sync.  The
  one-chip mirror (still built, on the first device) never stands in
  for a mesh that was asked for, so no line of a four-chip run can
  come from it;
* per-shard segments persist next to the main segment with an
  epoch-guarded, checksummed manifest — a cold start only seeds from
  them when the service epoch still matches, else it repartitions;
* failure semantics compose unchanged: a dead (``kill_shard``) or
  fault-injected (``match.shard``) shard raises at dispatch and the
  batch fails over to the CPU trie exactly like any other device
  failure (breaker strike in deadline mode, probe recovery through
  the mesh, ``_StaleRace``/stale-slot discards stay strike-free).

Flag off, the pre-deadline fixed-window loop serves byte-identically.
In BOTH modes a killed/crashed serve loop fails its in-flight waiters
over to the CPU path immediately (and re-arms on supervised restart)
instead of parking them for the full prefetch timeout.
"""

from __future__ import annotations

import asyncio
import logging
import os
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Set, Tuple

from jax.profiler import TraceAnnotation as _Annot

from .. import faultinject as _fi
from .. import topic as T
from ..observe import heap
from ..observe.span import stage_span
from ..ops.kernel_cache import CompileMiss
from ..ops.match_kernel import decode_packed
from .trie import FilterTrie

log = logging.getLogger(__name__)

__all__ = ["MatchService"]


_now_ns = time.perf_counter_ns      # the clock of every stage span


class _StaleRace(RuntimeError):
    """A benign serving race (aid reused mid-flight): the batch answer
    can't be trusted, but the device itself is healthy — falls back to
    the CPU path WITHOUT counting against the circuit breaker."""


class _Cycle:
    """The books of one popped batch.  ``seq`` is shared by every span
    of the batch on every plane (ring events' ``seq``, the profiler
    annotations' ``seq``).  The serial serve paths close the books:
    ``t0`` is the cycle's start, ``spanned`` the running sum of the
    stage spans recorded inside it, ``t_in``/``t_out`` the stamps of
    the worker function's first and last line (written by the worker:
    the loop is parked on its ``await`` meanwhile), ``t_ep`` where the
    loop-side epilogue began, ``t_mint`` where ``_mint_hints``
    returned.  Every waiter's future resolves to its batch's cycle, so
    that ``prefetch`` can time its own resumption from ``t_mint``."""

    __slots__ = ("seq", "n", "gen", "t0", "spanned", "t_in", "t_out",
                 "t_ep", "t_mint")

    def __init__(self, seq: int, n: int, gen: int, t0: int) -> None:
        self.seq, self.n, self.gen, self.t0 = seq, n, gen, t0
        self.spanned = self.t_in = self.t_out = 0
        self.t_ep = self.t_mint = 0


def _bucket(n: int, minimum: int = 64) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


def _fresh_add(fresh: Any, new_deep: Dict[str, int], flt: str) -> None:
    """Add ``flt`` to a compaction build's fresh table (the stateless
    twin of ``MatchService._table_add``)."""
    try:
        fresh.add(flt)
    except ValueError:
        if flt not in new_deep:
            new_deep[flt] = fresh.alloc_alias(flt)


def _build_compacted(table_kind: str, depth: int, filters: List[str],
                     deep_filters: List[str], routing: Set[str],
                     active_slots: int, max_matches: int,
                     compact_output: bool, kcache: Any,
                     dirty_threshold: float, segment_path: str,
                     join: bool = False):
    """Worker-thread half of a compaction cycle: build the fresh
    compacted table + device twin from the snapshot, write the next
    segment, and pre-pay the kernel compiles for the fresh shapes.
    Pure with respect to the service — every write lands on objects
    created here; the event-loop swap step publishes them."""
    from ..ops.compiler import _bucket as pow2
    from ..ops.device_table import DeviceNfa
    from ..storage.segments import save_segment

    if table_kind == "native":
        from ..native.nfa import NativeNfa

        fresh = NativeNfa(depth=depth)
        fresh.bulk_add(filters)
    else:
        from ..ops import IncrementalNfa

        fresh = IncrementalNfa(
            depth=depth,
            state_bucket=pow2(max(2 * len(filters), 8), 1024),
            # ~50% post-build edge load: the swapped-in table keeps
            # enough headroom that live churn doesn't hit a growth
            # boundary (and its compile-miss window) right after a swap
            edge_bucket=pow2(max(len(filters), 8), 64))
        for flt in filters:
            fresh.add(flt)
        fresh.track_regions = True
    new_deep = {flt: fresh.alloc_alias(flt) for flt in deep_filters}
    new_routing: Set[int] = set()
    for flt in routing:
        aid = new_deep.get(flt)
        if aid is None:
            aid = fresh.aid_of(flt)
        if aid >= 0:
            new_routing.add(aid)
    # the next segment lands BEFORE the swap: a crash after this point
    # leaves a valid fresh segment on disk and the old table serving.
    # With the join backend on, the relation persists too (built clean
    # from the fresh table — the full-rebuild-on-compact contract).
    save_segment(segment_path, fresh, deep=new_deep,
                 routing_aids=new_routing, filters=filters,
                 join_relation=join)
    newdev = DeviceNfa(
        fresh, active_slots=active_slots, max_matches=max_matches,
        compact_output=compact_output, lazy=True,
    )
    newdev.kernel_cache = kcache
    newdev.dirty_full_threshold = dirty_threshold
    newdev.dirty_regions = hasattr(fresh, "track_regions")
    if join:
        newdev.join_enabled = True
    newdev.sync(full=True)
    if kcache is not None:
        s, hb, _d = fresh.shape_key()
        kcache.prewarm_shape(s, hb)
    return fresh, newdev, new_deep, new_routing


class MatchService:
    """Device-backed topic matching for the broker's hot path."""

    def __init__(
        self,
        broker: Any,
        metrics: Any = None,
        depth: int = 8,
        batch_window_s: float = 0.0002,
        # 2048 is the measured serving sweet spot (BENCH_r05
        # serve_device_quarter_batch: p99 105 ms vs 398 ms at 8192 at
        # similar capacity) — the default when no override is given
        max_batch: int = 2048,
        debounce_s: float = 0.05,
        active_slots: int = 16,
        max_matches: int = 32,
        hint_cap: int = 65536,
        max_stale_deltas: int = 256,
        bypass_rate: float = 0.0,
        prefetch_timeout_s: float = 0.5,
        table: str = "auto",   # auto | native | python
        short_depth: int = 4,
        split_min: int = 256,
        deadline: bool = False,
        deadline_s: float = 0.041,
        pipeline: bool = False,
        pipeline_depth: int = 2,
        breaker_threshold: int = 5,
        breaker_probe_interval_s: float = 1.0,
        dispatch_timeout_s: Optional[float] = None,
        alarms: Any = None,
        olp: Any = None,
        segments: bool = False,
        segments_dir: str = "",
        compact_interval_s: float = 30.0,
        compact_min_mutations: int = 1024,
        dirty_threshold: float = 0.5,
        prewarm: bool = True,
        backend: str = "hash",
        autotune: bool = True,
        autotune_reps: int = 3,
        multichip: bool = False,
        multichip_tp: int = 0,
        multichip_native: bool = True,
        multichip_ep: bool = False,
        multichip_ep_slack: float = 2.0,
        multichip_ep_micro: int = 8,
        multichip_ep_compact: bool = False,
        multichip_degraded: bool = False,
        multichip_degraded_threshold: int = 3,
        multichip_ep_overflow_warn: float = 0.5,
        multichip_ep_autotune: bool = False,
        multichip_ep_grow_threshold: float = 0.05,
        multichip_ep_shrink_threshold: float = 0.01,
        multichip_ep_max_cap_class: int = 3,
        multichip_balance_budget: int = 64,
        hists: Any = None,
        flightrec: Any = None,
    ) -> None:
        from ..ops import IncrementalNfa
        from ..ops.device_table import DeviceNfa

        self.broker = broker
        self.router = broker.router
        self.metrics = metrics
        self.depth = depth
        self.batch_window_s = batch_window_s
        self.max_batch = max_batch
        self.debounce_s = debounce_s
        self.hint_cap = hint_cap
        # serving tolerates up to this many un-synced router deltas; the
        # per-topic freshness proof scans at most this many log entries
        self.max_stale_deltas = max_stale_deltas
        # publishes/s below which prefetch skips the device entirely
        # (0 disables bypassing — tests pin the device path on)
        self.bypass_rate = bypass_rate
        self.prefetch_timeout_s = prefetch_timeout_s
        # depth bucketing: topics with <= short_depth levels ride a
        # shallower kernel (~40% fewer gathers on Zipf traffic); the
        # split only happens when BOTH groups clear split_min, because a
        # second kernel dispatch has a fixed cost that must amortize
        self.short_depth = short_depth
        self.split_min = split_min
        # deadline-aware serve plane (module docstring).  Off = the
        # fixed-window loop, byte-identical to the pre-deadline path.
        self.deadline = bool(deadline)
        self.deadline_s = deadline_s
        # overlapped serve pipeline (module docstring).  Off = the
        # serial dispatch→readback round trip, byte-identical to PR 10.
        self.pipeline = bool(pipeline)
        self.pipeline_depth = max(1, int(pipeline_depth))
        self._inflight_q: Optional[asyncio.Queue] = None
        self._inflight_n = 0
        self.breaker_threshold = breaker_threshold
        self.breaker_probe_interval_s = breaker_probe_interval_s
        # per-dispatch bound: well under the waiter timeout so a hung
        # kernel degrades to ONE CPU-served batch, not a stalled queue
        self.dispatch_timeout_s = (
            dispatch_timeout_s if dispatch_timeout_s is not None
            else min(max(4.0 * deadline_s, 0.1),
                     max(prefetch_timeout_s * 0.8, 0.05)))
        self.alarms = alarms
        self.olp = olp

        # host table: the C++ incremental NFA when available (seconds at
        # 10M filters, Python-object-free), else the Python twin —
        # identical mutation/drain surface, property-tested equivalent
        self.inc = None
        self.table_kind = "python"
        if table in ("auto", "native"):
            try:
                from ..native.nfa import NativeNfa

                self.inc = NativeNfa(depth=depth)
                self.table_kind = "native"
            except Exception:
                if table == "native":
                    raise
                log.warning(
                    "native NFA table unavailable; python table serves "
                    "(fine below ~1M filters)", exc_info=True,
                )
        if self.inc is None:
            self.inc = IncrementalNfa(depth=depth)
        self.dev = DeviceNfa(
            self.inc, active_slots=active_slots, max_matches=max_matches,
            lazy=True,
        )
        # streaming table lifecycle (module docstring; opt-in, flag off
        # keeps every structure below inert and the serve path unchanged)
        self.segments = bool(segments) and bool(segments_dir)
        self.segments_dir = segments_dir
        self.compact_interval_s = compact_interval_s
        self.compact_min_mutations = compact_min_mutations
        self.prewarm = bool(prewarm)
        self.kcache = None
        self._table_gen = 0            # bumped by every segment swap
        self._mut_count = 0            # table mutations since last segment
        self._compact_dirty: Set[str] = set()   # filters touched mid-build
        self._compact_recording = False
        self._compact_abandoned = 0
        self._segment_loaded = False
        self._segment_tried = False
        self._prewarm_busy = False
        self._hydrate_child: Any = None
        if self.segments:
            from ..ops.kernel_cache import MatchKernelCache

            self.kcache = MatchKernelCache()
            self.dev.kernel_cache = self.kcache
            self.dev.dirty_full_threshold = dirty_threshold
            if hasattr(self.inc, "track_regions"):
                self.inc.track_regions = True
                self.dev.dirty_regions = True
        # kernel backend routing (ISSUE 13): "hash" = the cuckoo-probe
        # kernel (default, byte-identical to the pre-join path),
        # "join" = the sorted-relation kernel, "auto" = per-shape picks
        # from a measured, persisted autotuner.  join/auto turn the
        # DeviceNfa relation mirror on; flag off every join structure
        # stays unbuilt.
        self.backend = backend
        self.tuner = None
        self._tuning: Set[str] = set()
        self._seg_join_seed = None   # (epoch, shape_key, arrays)
        # reservoir of recently SERVED topics: what autotune measures
        # with, so picks reflect real traffic shape, not dummy batches
        self._topic_sample: Deque[str] = deque(maxlen=256)
        if backend in ("join", "join-pallas", "auto"):
            self.dev.enable_join()
        if backend == "auto" and autotune:
            from ..ops.join_match import BackendAutotuner

            self.tuner = BackendAutotuner(
                path=(os.path.join(segments_dir, "autotune.json")
                      if self.segments else None),
                reps=autotune_reps)
        if self.kcache is not None and backend == "auto":
            # prewarm must cover BOTH kernel families, or the first
            # auto-routed join dispatch on a fresh shape eats a
            # CompileMiss → CPU hop (ISSUE 13 bugfix)
            self.kcache.auto_backends = ("hash", "join")
        # degraded-mesh service state (inert unless the mc degraded
        # flag is on): the mesh_degraded alarm latch and the supervised
        # mesh.rebuild child's running flag
        self._mesh_alarmed = False
        self._mesh_rebuilding = False
        self._ref: Dict[str, int] = {}     # wildcard filter -> route count
        self._deep: Dict[str, int] = {}    # too-deep filter -> alias aid
        self._deep_trie = FilterTrie()     # host match for too-deep filters
        # rule filters compile as REAL NFA filters tagged by aid; a filter
        # used by both routing and rules shares one aid.  Maps aid->sets:
        self._aid_rules: Dict[int, Set[str]] = {}   # aid -> rule ids
        self._rule_refs: Dict[str, Dict[str, int]] = {}  # rule_id -> {flt: 1}
        self._routing_aids: Set[int] = set()

        # rule mutation log: (gen, filters-added) — unregisters append an
        # empty entry so gen coverage stays contiguous (deleted rules are
        # harmless in stale hints: the engine skips unknown ids)
        self._rule_gen = 0
        self._rule_log: Deque[Tuple[int, Tuple[str, ...]]] = deque(maxlen=512)

        self._mirror_ready = False    # behind the ``ready`` property
        self._seen_epoch = 0          # router delta-log position (drained)
        self._synced_epoch = 0        # router epoch the DEVICE table reflects
        self._synced_rule_gen = 0     # rule gen the device table reflects
        self._dirty = asyncio.Event()
        self._pending: List[Tuple[str, asyncio.Future]] = []
        self._batch_wake = asyncio.Event()
        # topic -> (router_epoch, rule_gen, wild filters, rule ids)
        self._hints: Dict[str, Tuple[int, int, List[str], List[str]]] = {}
        self._tasks: List[asyncio.Task] = []
        self._running = False
        # arrival-rate window for the adaptive bypass
        self._win_start = time.monotonic()
        self._win_count = 0
        self._last_rate = 0.0
        # deadline-mode serving state: EWMA arrival rate + short-lane
        # fraction (per-lane caps), EWMA dispatch latency (partial-flush
        # trigger), circuit breaker, brownout cache
        self._rate_ewma: Optional[float] = None
        self._short_frac: Optional[float] = None
        self._win_short = 0
        self._est_dispatch_s = 0.005
        # split dispatch-vs-readback estimate (ROADMAP dispatch-tax
        # residual (c)): the combined EWMA above times the WHOLE
        # t0→resolve span, which in pipeline mode includes time a slot
        # sits queued for readback — queue-wait polluting the
        # partial-flush trigger.  The split components are fed from the
        # stage timers where each stage actually runs (encode+dispatch
        # in the worker thread, readback in the readback worker), so
        # their sum is the true device round trip.  The combined
        # estimate stays as the fallback while the split is cold.
        self._est_disp_s = 0.004
        self._est_rb_s = 0.001
        self._est_split_samples = 0
        self._breaker_failures = 0
        self._breaker_open = False
        self._warned: Set[str] = set()   # _warn_device_failure latch
        self._probe_child: Any = None
        self._last_brownout = 0

        # stage spans (observe/span.py): one handle per per-batch stage
        # over its histogram (observe/hist.py, None with obs.hist.enable
        # off) and its writer's ring of the always-on flight recorder
        # (observe/flightrec.py, which also takes the breaker/brownout
        # dump triggers); a handle is None where both are off and its
        # site is one identity test.  One writer per handle: encode,
        # dispatch and readback are written by the (single in-flight)
        # worker-thread stages, everything else by the serve loop.
        self.hists = hists
        self.flightrec = flightrec
        ring_loop = ring_disp = ring_rb = None
        if flightrec is not None:
            ring_loop = flightrec.ring("match.serve")
            ring_disp = flightrec.ring("match.encode")
            ring_rb = flightrec.ring("match.readback")
        # per waiter, so the histogram itself is the handle: match_wait
        # (it also gates the enqueue stamp that rides the waiter
        # tuples; one ring event a batch beside it) and match_resume
        self._h_wait = self._h_resume = None
        if hists is not None:
            self._h_wait = hists.hist("obs.stage.match_wait")
            self._h_resume = hists.hist("obs.stage.match_resume")
        self._sp_wait_batch = stage_span("match_wait", None, ring_loop)
        self._sp_encode = stage_span("match_encode", hists, ring_disp)
        self._sp_dispatch = stage_span("match_dispatch", hists, ring_disp)
        self._sp_readback = stage_span("match_readback", hists, ring_rb)
        # the cycle's books (serial serve paths), all on the loop
        self._sp_cycle = stage_span("match_cycle", hists, ring_loop)
        self._sp_window = stage_span("match_window", hists, ring_loop)
        self._sp_hop_out = stage_span("match_hop_out", hists, ring_loop)
        self._sp_hop_back = stage_span("match_hop_back", hists, ring_loop)
        self._sp_epilogue = stage_span("match_epilogue", hists, ring_loop)
        self._seq = 0       # batch sequence number: one per popped batch
        # multichip serve backend (module docstring; opt-in, flag off
        # leaves self.mc None and every seam below one None-test so the
        # single-chip path is byte-identical — spy-asserted).  Flag on,
        # the mesh is the plane that was CONFIGURED: ``ready`` means it
        # is up, and a constructor that fails is retried by the sync
        # loop (``_mc_apply``) with the host trie serving meanwhile.
        self.mc = None
        self._mc_wanted = bool(multichip)
        self._mc_args = dict(
            depth=depth, tp=multichip_tp,
            active_slots=active_slots, max_matches=max_matches,
            metrics=metrics, kernel_cache=self.kcache,
            native=multichip_native, ep=multichip_ep,
            ep_slack=multichip_ep_slack,
            ep_micro_matches=multichip_ep_micro,
            ep_compact=multichip_ep_compact,
            degraded=multichip_degraded,
            degraded_fail_threshold=multichip_degraded_threshold,
            ep_overflow_warn=multichip_ep_overflow_warn,
            ep_autotune=multichip_ep_autotune,
            ep_grow_threshold=multichip_ep_grow_threshold,
            ep_shrink_threshold=multichip_ep_shrink_threshold,
            ep_max_cap_class=multichip_ep_max_cap_class,
            balance_budget=multichip_balance_budget,
            # the serve shapes a whole repartition compiles BEFORE the
            # matcher says ready (the _warm twin; the short lane too)
            warm_depths=((self.short_depth, depth)
                         if self.short_depth and self.short_depth < depth
                         else (depth,)),
            # what the mesh adds inside match_dispatch and inside
            # match_readback, each pair written by that stage's own
            # (single in-flight) worker
            spans=(stage_span("mesh_put", hists, ring_disp),
                   stage_span("mesh_launch", hists, ring_disp),
                   stage_span("mesh_fetch", hists, ring_rb),
                   stage_span("mesh_decode", hists, ring_rb)),
        ) if multichip else None
        if multichip:
            self._mc_build()

        self.router.listeners.append(self._on_router_mutation)

    @property
    def ready(self) -> bool:
        """The CONFIGURED device plane may serve: the one-chip mirror is
        synced and, with ``match.multichip.enable``, the mesh is applied
        and warm (``mc.ready``).  A mesh that was asked for and is
        absent (its constructor failed) or not applied (an apply failed,
        a compaction swap queued a repartition) leaves this false and
        the host trie serving, as before the first sync: the one-chip
        mirror never stands in for it silently."""
        return self._mirror_ready and (
            not self._mc_wanted or self._mc_active() is not None)

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    async def start(self) -> None:
        self._running = True
        self._bootstrap()
        if self.mc is not None:
            self._mc_seed()
        serve_loop = self._deadline_loop if self.deadline \
            else self._batch_loop
        if self.pipeline:
            # in-flight slot queue: maxsize bounds batches QUEUED for
            # readback; with one more in the readback child itself, at
            # most pipeline_depth batches sit past dispatch (depth 2 =
            # classic double buffering)
            self._inflight_q = asyncio.Queue(
                maxsize=max(1, self.pipeline_depth - 1))
        sup = getattr(self, "supervisor", None)
        if sup is not None:
            # supervised (node sets .supervisor before start): a crashed
            # mirror-sync or batch loop restarts instead of freezing
            # hint freshness / prefetch waiters until broker restart
            self._tasks = [
                sup.start_child("match.sync", self._sync_loop),
                sup.start_child("match.batch", serve_loop),
            ]
            if self.pipeline:
                self._tasks.append(
                    sup.start_child("match.readback", self._readback_loop))
            if self.segments:
                self._tasks.append(
                    sup.start_child("table.compact", self._compact_loop))
        else:
            self._tasks = [
                asyncio.ensure_future(self._sync_loop()),
                asyncio.ensure_future(serve_loop()),
            ]
            if self.pipeline:
                self._tasks.append(
                    asyncio.ensure_future(self._readback_loop()))
            if self.segments:
                self._tasks.append(
                    asyncio.ensure_future(self._compact_loop()))
        if self._segment_loaded and getattr(
                self.inc, "_pending_trie", None) is not None:
            # hydrate the restored trie in the background so the first
            # live mutation doesn't pay the relink on the event loop
            if sup is not None:
                self._hydrate_child = sup.start_child(
                    "table.hydrate", self._hydrate_loop,
                    restart="temporary")
            else:
                self._hydrate_child = asyncio.ensure_future(
                    self._hydrate_loop())
        self._dirty.set()

    async def _hydrate_loop(self) -> None:
        await asyncio.to_thread(self.inc._hydrate)

    async def stop(self) -> None:
        self._running = False
        if self._probe_child is not None:
            self._probe_child.cancel()
            self._probe_child = None
        for t in self._tasks:
            t.cancel()
        self._tasks = []
        try:
            self.router.listeners.remove(self._on_router_mutation)
        except ValueError:
            pass  # already unhooked (double stop is legal)
        if self.mc is not None and getattr(self.mc, "ep_autotune",
                                           False):
            # a capacity-rebuild compile must not outlive the service:
            # left running it keeps XLA on every host core after stop
            await asyncio.to_thread(self.mc.drain_resize, 60.0)

    # ------------------------------------------------------------------
    # mirror maintenance (event loop)
    # ------------------------------------------------------------------

    def _on_router_mutation(self, epoch: int) -> None:
        # NO hint invalidation here: freshness is proven per-topic at
        # consume time against the delta log (see _hint_fresh)
        self._dirty.set()

    def _add(self, flt: str) -> None:
        n = self._ref.get(flt, 0)
        self._ref[flt] = n + 1
        if n == 0:
            self._table_add(flt, routing=True)

    def _del(self, flt: str) -> None:
        n = self._ref.get(flt, 0)
        if n <= 1:
            self._ref.pop(flt, None)
            if n == 1:
                self._table_del(flt, routing=True)
        else:
            self._ref[flt] = n - 1

    def _table_add(self, flt: str, routing: bool) -> None:
        try:
            self.inc.add(flt)
            aid = self.inc.aid_of(flt)
            if self.mc is not None:
                # mirror the mutation into the shard partition (deep
                # aliases stay host-only — the deep trie serves them)
                self.mc.note_add(flt, aid)
        except ValueError:
            if flt in self._deep:
                aid = self._deep[flt]
            else:
                aid = self.inc.alloc_alias(flt)
                self._deep[flt] = aid
                self._deep_trie.insert(flt)
        if routing:
            self._routing_aids.add(aid)
        self._note_mutation(flt)

    def _table_del(self, flt: str, routing: bool) -> None:
        aid = self._deep.get(flt)
        if aid is None:
            aid = self.inc.aid_of(flt)
        if aid < 0:
            return
        if routing:
            self._routing_aids.discard(aid)
        if aid in self._aid_rules and self._aid_rules[aid]:
            return  # rules still reference this filter
        if flt in self._deep:
            del self._deep[flt]
            self._deep_trie.delete(flt)
            self.inc.free_alias(aid)
        else:
            self.inc.remove(flt)
            if self.mc is not None:
                self.mc.note_del(flt)
        self._note_mutation(flt)

    def _note_mutation(self, flt: str) -> None:
        if not self.segments:
            return
        self._mut_count += 1
        if self._compact_recording:
            # a compaction build is in flight: remember the touched
            # filter so the swap fixes up exactly the changed set
            self._compact_dirty.add(flt)

    def _bootstrap(self) -> None:
        """Full resnapshot from the router (cold start / delta-log gap).
        Refcounts seed from the router's live destination count — a
        filter restored with multiple routes must survive the deletion
        of all but one of them (ADVICE.md round-2 high item 1).

        With segments enabled, the FIRST bootstrap tries the on-disk
        segment instead: load the compacted table, then replay only the
        diff against the live router (the delta-log tail) — a corrupt
        or rejected segment falls through to the full rebuild below."""
        if self.segments and not self._segment_tried:
            self._segment_tried = True
            if self._load_segment():
                return
        self._ref = {}
        for flt in self.router.wildcard_filters():
            self._ref[flt] = max(1, len(self.router.routes_of(flt)))
            if self.inc.aid_of(flt) < 0 and flt not in self._deep:
                self._table_add(flt, routing=True)
            else:
                self._routing_aids.add(
                    self._deep.get(flt, self.inc.aid_of(flt))
                )
        self._seen_epoch = self.router.epoch

    # ------------------------------------------------------------------
    # streaming table lifecycle (opt-in, match.segments.enable)
    # ------------------------------------------------------------------

    @property
    def _segment_path(self) -> str:
        return os.path.join(self.segments_dir, "match_table.seg.npz")

    def _load_segment(self) -> bool:
        """Cold-start from the persisted segment: restore the table +
        id-space bookkeeping, then reconcile against the live router.
        Returns False (full rebuild serves) on ANY defect — missing
        file, checksum reject, injected ``table.load`` fault."""
        from ..storage.segments import (
            SegmentError, load_segment, restore_incremental,
        )

        path = self._segment_path
        if not os.path.exists(path):
            return False
        t0 = time.perf_counter()
        try:
            if _fi._injector is not None:
                # chaos seam: a load fault behaves exactly like a
                # corrupt segment — reject and rebuild from the router
                if _fi._injector.act("table.load") == "raise":
                    raise SegmentError("injected table.load fault")
            seg = load_segment(path)
            if seg.depth != self.depth:
                raise SegmentError(
                    f"segment depth {seg.depth} != table depth "
                    f"{self.depth}")
            if seg.kind == "state" and self.table_kind == "python":
                inc = restore_incremental(seg)
                self.inc = inc
                if self.segments:
                    inc.track_regions = True
                self._deep = dict(seg.deep)
                self._routing_aids = set(seg.routing_aids)
                if seg.join_start is not None:
                    # persisted sorted relation: seeds the join mirror
                    # at the first full sync iff the epoch still
                    # matches (no drift since the segment was written)
                    self._seg_join_seed = (
                        seg.epoch,
                        (int(seg.node_tab.shape[0]),
                         int(seg.edge_tab.shape[0]), seg.depth),
                        (seg.join_start, seg.join_word, seg.join_next),
                    )
            else:
                # native table (or a kind mismatch): replay the filter
                # blob through the bulk path — one native call, not one
                # ctypes round trip per filter
                if hasattr(self.inc, "bulk_add"):
                    self.inc.bulk_add(seg.filters)
                else:
                    for flt in seg.filters:
                        self.inc.add(flt)
                self._deep = {}
                self._routing_aids = set()
                for flt in seg.deep:
                    self._table_add(flt, routing=False)
            self._deep_trie = FilterTrie()
            for flt in self._deep:
                self._deep_trie.insert(flt)
            # the restored table replaces self.inc: rebind the device
            # twin so drains read the live arrays
            self._rebind_dev(self.inc)
            self._reconcile_with_router(
                set(seg.filters) | set(seg.deep),
                aids_valid=(seg.kind == "state"
                            and self.table_kind == "python"))
        except SegmentError:
            log.warning("segment %s rejected; full rebuild serves",
                        path, exc_info=True)
            return False
        except Exception:
            log.exception("segment load failed; full rebuild serves")
            return False
        self._segment_loaded = True
        self._mut_count = 0
        if self.metrics is not None:
            self.metrics.set("tpu.table.segment_load_s",
                             round(time.perf_counter() - t0, 4))
        log.info("match table cold-started from segment %s "
                 "(%d filters, %.1f ms)", path, self.inc.n_filters,
                 (time.perf_counter() - t0) * 1e3)
        return True

    def _rebind_dev(self, inc) -> None:
        from ..ops.device_table import DeviceNfa

        dev = DeviceNfa(
            inc, active_slots=self.dev.active_slots,
            max_matches=self.dev.max_matches,
            compact_output=self.dev.compact_output, lazy=True,
        )
        dev.kernel_cache = self.kcache
        dev.dirty_full_threshold = self.dev.dirty_full_threshold
        dev.dirty_regions = (self.segments
                             and hasattr(inc, "track_regions"))
        if self.backend in ("join", "join-pallas", "auto"):
            seed, self._seg_join_seed = self._seg_join_seed, None
            dev.enable_join(seed=seed)
        self.dev = dev

    def _reconcile_with_router(self, table_set: Set[str],
                               aids_valid: bool) -> None:
        """Replay the delta tail: diff the restored table against the
        live router so only CHANGED filters pay table mutations."""
        routed = self.router.wildcard_filters()
        routed_set = set(routed)
        self._ref = {
            flt: max(1, len(self.router.routes_of(flt)))
            for flt in routed
        }
        if not aids_valid:
            # fresh aid space (native bulk reload): derive the routing
            # aids for the surviving set — native aid_of is a C walk
            for flt in routed_set & table_set:
                aid = self._deep.get(flt, self.inc.aid_of(flt))
                if aid >= 0:
                    self._routing_aids.add(aid)
        for flt in routed_set - table_set:
            self._table_add(flt, routing=True)
        for flt in table_set - routed_set:
            # no rules exist at cold start: anything unrouted goes (a
            # segment-persisted rule filter re-adds at register_rule)
            self._table_del(flt, routing=True)
        self._seen_epoch = self.router.epoch

    def _drain_router(self) -> None:
        deltas = self.router.deltas_since(self._seen_epoch)
        if deltas is None:
            log.info("router delta log gap: full mirror resnapshot")
            # drop filters no longer routed, then re-add from scratch
            for flt in list(self._ref):
                self._table_del(flt, routing=True)
            self._bootstrap()
            return
        for d in deltas:
            if not T.wildcard(d.filter):
                continue  # exact filters stay in the router's hash map
            if d.op == "add":
                self._add(d.filter)
            else:
                self._del(d.filter)
        self._seen_epoch = self.router.epoch

    async def _sync_loop(self) -> None:
        mesh_retry_s = 1.0      # doubles while a configured mesh is down
        while True:
            await self._dirty.wait()
            await asyncio.sleep(self.debounce_s)
            self._dirty.clear()
            try:
                first = not self._mirror_ready
                self._drain_router()
                # epochs the device table will reflect once this sync lands
                router_epoch = self._seen_epoch
                rule_gen = self._rule_gen
                pending = self.dev.drain(full=first)
                if pending.full is not None:
                    # a full re-upload changes table shapes ⇒ the match
                    # jit recompiles; drop readiness so publishes take the
                    # host path instead of stalling on the compile
                    # (ADVICE.md round-2 high item 2)
                    self._mirror_ready = False
                await asyncio.to_thread(self.dev.apply_pending, pending)
                whole = first or pending.full is not None
                if whole:
                    await asyncio.to_thread(self._warm)
                if self._mc_wanted and self.mc is None:
                    # the constructor failed: try it again, for as
                    # long as the mesh is absent
                    self._mc_build()
                    if self.mc is not None:
                        self._mc_seed()
                if self.mc is not None and self.mc.dirty \
                        and not await asyncio.to_thread(self._mc_apply):
                    # shard partition applies in lockstep with the
                    # device twin so both reflect _synced_epoch below.
                    # What a failed apply had drained is lost: queue a
                    # whole repartition from the live aid space
                    # (mc.ready drops until it lands).  The pairs are
                    # read HERE, as at every other rebuild: the loop
                    # owns the books register_rule and the swap write
                    self.mc.rebuild(self._mc_pairs())
                if self.mc is not None:
                    self._mesh_watch()
                if whole:
                    # router, sessions' maps, inc's tables and the
                    # mirror's books are long-lived from here on
                    heap.settled("full upload")
                self._mirror_ready = True
                self._synced_epoch = router_epoch
                self._synced_rule_gen = rule_gen
                if self.metrics is not None:
                    self.metrics.inc("tpu.mirror.refresh")
                    if pending.full is not None:
                        self.metrics.inc("tpu.mirror.recompile")
                    elif pending.delta is not None and not pending.delta.empty:
                        self.metrics.inc("tpu.mirror.delta_applied")
                if self.segments:
                    if self.metrics is not None:
                        self.metrics.set("tpu.table.dirty_rows_uploaded",
                                         self.dev.dirty_rows_uploaded)
                        if self.kcache is not None:
                            self.metrics.set(
                                "tpu.table.compile_cache_hits",
                                self.kcache.hits)
                    self._maybe_prewarm()
                if self._mc_wanted and not self.ready:
                    # the mesh did not come up (counted and logged in
                    # _mesh_failed): try again, twice as far off each
                    # time (a retried apply repartitions the whole
                    # table); the host trie serves
                    await asyncio.sleep(mesh_retry_s)
                    mesh_retry_s = min(2.0 * mesh_retry_s, 32.0)
                    self._dirty.set()
                else:
                    mesh_retry_s = 1.0
            except Exception:
                log.exception("match-service sync failed; host path serves")
                await asyncio.sleep(1.0)
                self._dirty.set()

    def _warm(self) -> None:
        from ..ops import encode_batch

        if _fi._injector is not None:
            # chaos seam: the compile/warm step is where growth
            # re-uploads and cold starts stall — a raise here rides the
            # _sync_loop's existing failure path (host trie serves,
            # retry after 1 s); runs inside to_thread, so a delay is a
            # plain blocking sleep
            act = _fi._injector.act("match.compile")
            if act == "raise":
                raise _fi.InjectedFault("match.compile")
            if act == "delay":
                time.sleep(_fi._injector.last_delay)
        # warm the program the serve path dispatches (``dev.serve``),
        # or the first live batch would still stall on an XLA compile.
        # Under backend routing every family auto can pick must warm, or
        # the first re-routed batch stalls exactly like an unwarmed shape.
        backends = (("hash", "join") if self.backend == "auto"
                    else (self.backend,))
        for be in backends:
            self.dev.serve(*encode_batch(self.inc, [], batch=64),
                           backend=be)
            if self.short_depth and self.short_depth < self.depth:
                # pre-pay the short-depth kernel shape too, or the
                # first split batch stalls the loop on an XLA compile
                self.dev.serve(
                    *encode_batch(self.inc, [], batch=64,
                                  depth=self.short_depth), backend=be)

    # ------------------------------------------------------------------
    # multichip serve backend (opt-in, match.multichip.enable)
    # ------------------------------------------------------------------

    def _mc_pairs(self) -> List[Tuple[str, int]]:
        """(filter, service aid) for every NFA-resident filter (routing
        + rules; deep aliases excluded — the host trie serves them):
        the full repartition input for cold start / compaction swap."""
        ruled = {f for refs in self._rule_refs.values() for f in refs}
        out: List[Tuple[str, int]] = []
        for flt in set(self._ref) | ruled:
            if flt in self._deep:
                continue
            aid = self.inc.aid_of(flt)
            if aid >= 0:
                out.append((flt, aid))
        return out

    def _mc_build(self) -> None:
        """Construct the mesh matcher: in ``__init__``, and again by
        the sync loop after a constructor that failed."""
        try:
            from ..parallel.multichip_serve import MultichipMatcher

            self.mc = MultichipMatcher(**self._mc_args)
        except Exception as e:
            self._mesh_failed("multichip serve backend constructor", e)

    def _mc_seed(self) -> None:
        """Seed the shard partition: per-shard segments when the main
        table cold-started from ITS segment and the epochs still
        agree, else a full repartition from the live aid space
        (note_add events before it are superseded — rebuild clears the
        pending log)."""
        if not (self.segments and self._segment_loaded
                and self.mc.load_segments(self.segments_dir,
                                          self.inc.epoch)):
            self.mc.rebuild(self._mc_pairs())

    def _mesh_failed(self, what: str, e: BaseException) -> None:
        """The configured mesh did not come up: counted, logged once
        per distinct error, and ``ready`` stays false (the host trie
        serves and the sync loop tries again)."""
        if self.metrics is not None:
            self.metrics.inc("tpu.mesh.apply_failed")
        self._warn_device_failure(what, e)

    def _mc_apply(self) -> bool:
        """WORKER-THREAD step: fold the noted mutations (or a queued
        repartition) into the shard subtables + stacked device arrays.
        False where it failed (``_mesh_failed``): the service is then
        NOT ready, and the sync loop queues the partition again."""
        mc = self.mc
        try:
            mc.apply_pending()
            if self.segments and mc._persist_due:
                mc.save_segments(self.segments_dir, self.inc.epoch)
        except Exception as e:
            self._mesh_failed("multichip apply", e)
            return False
        return True

    def _mc_active(self):
        """The multichip matcher when it may serve the next dispatch,
        else None.  One attribute test on the flag-off path."""
        mc = self.mc
        return mc if mc is not None and mc.ready else None

    def _serving_dev(self):
        """The plane the next dispatch goes to: the mesh where one is
        configured, else the one-chip mirror.  ``_usable`` has the
        same rule; this is for a mesh that dropped out since (a
        compaction swap landed between the two)."""
        if not self._mc_wanted:
            return self.dev
        mc = self._mc_active()
        if mc is None:
            raise _StaleRace("configured mesh not ready")
        return mc

    # ------------------------------------------------------------------
    # degraded mesh: health ladder + online shard rebuild
    # (opt-in, match.multichip.degraded.enable)
    # ------------------------------------------------------------------

    def _mesh_watch(self) -> None:
        """Reconcile the mesh health ladder with the service's alarm /
        flight-recorder / rebuild machinery.  Called from the serve
        paths after a shard failure surfaces and from the sync loop;
        cheap when healthy (one attribute walk, no allocation)."""
        mc = self.mc
        if mc is None or not getattr(mc, "degraded", False):
            return
        dead = mc.dead_shards
        if self.metrics is not None:
            self.metrics.set("tpu.mesh.state", mc.mesh_state())
        if dead and not self._mesh_alarmed:
            self._mesh_alarmed = True
            if self.alarms is not None:
                self.alarms.activate(
                    "mesh_degraded",
                    {"dead_shards": list(dead), "tp": mc.tp},
                    "mesh shard(s) dead; degraded serving with CPU fill",
                )
            if self.flightrec is not None:
                # the forensic payoff: what the serve path was doing
                # for the last few hundred batches before the shard
                # died
                self.flightrec.dump("mesh_degraded")
        elif not dead and self._mesh_alarmed:
            self._mesh_alarmed = False
            if self.alarms is not None:
                self.alarms.deactivate("mesh_degraded")
        if dead and not self._mesh_rebuilding:
            self._mesh_rebuilding = True
            sup = getattr(self, "supervisor", None)
            if sup is not None:
                # supervised rebuild child: a crashing rebuild restarts
                # per policy instead of leaving the shard out forever
                sup.start_child("mesh.rebuild", self._mesh_rebuild_loop,
                                restart="transient")
            else:
                try:
                    asyncio.ensure_future(self._mesh_rebuild_loop())
                except RuntimeError:
                    # no running loop (sync-context caller, e.g. a
                    # direct-call test): the next loop-side watch
                    # starts the rebuild
                    self._mesh_rebuilding = False

    async def _mesh_rebuild_loop(self) -> None:
        """Online shard rebuild (transient supervised child): lowest
        dead shard first, reconstruct its subtable OFF the serve path
        (degraded serving continues on the survivors), canary the
        rebuilt shard against the CPU trie, re-admit only on bit
        parity.  A crash — including an injected ``mesh.rebuild``
        fault — restarts the child per supervisor policy and the
        rebuild starts over; a clean return means every shard is live
        again.  ``_mesh_rebuilding`` stays True across crash-restarts
        so ``_mesh_watch`` never starts a second child."""
        mc = self.mc
        while self._running and mc is not None and mc.dead_shards:
            t = mc.dead_shards[0]
            await asyncio.to_thread(
                mc.rebuild_shard, t, self._mc_pairs(),
                self.segments_dir if self.segments else None,
                self.inc.epoch)
            if not await self._mesh_canary(t):
                mc.readmit_canary_fails += 1
                if self.metrics is not None:
                    self.metrics.inc("tpu.mesh.readmit_canary_fails")
                log.error("mesh shard %d rebuild canary FAILED; shard "
                          "stays out", t)
                await asyncio.sleep(0.05)
                continue
            mc.revive_shard(t)
            # in-flight slots dispatched against the degraded plane
            # discard via the table-generation guard — no breaker
            # strike; those publishes re-serve from the CPU trie
            self._table_gen += 1
            log.warning("mesh shard %d rebuilt and re-admitted "
                        "(canary passed)", t)
        self._mesh_rebuilding = False
        self._mesh_watch()

    async def _mesh_canary(self, t: int) -> bool:
        """Bit-parity canary gating shard ``t``'s re-admission: push
        the rebuilt shard's own filters' topics through the mesh with
        ``t`` treated as live (other dead shards stay masked) and
        compare every on-device row against the CPU trie.  Aids the
        degraded plane CPU-fills anyway (other dead shards') are
        credited on the device side, same as the serve path.  True
        only when at least one row was actually checked and every
        checked row matched."""
        mc = self.mc
        topics = mc.canary_topics(t)
        if not topics:
            return True     # shard owns nothing: vacuous pass
        try:
            rows, spilled = await asyncio.to_thread(
                mc.canary_rows, topics, _bucket(len(topics)), t)
        except Exception:
            log.exception("mesh canary dispatch for shard %d failed", t)
            return False
        fill = mc.dead_aids(exclude=t)
        sp = set(spilled)
        checked = 0
        for i, topic in enumerate(topics):
            if i in sp:
                continue
            host = set(self._host_ids(topic))
            if set(rows[i]) | (host & fill) != host:
                log.error("mesh canary mismatch on %r (shard %d)",
                          topic, t)
                return False
            checked += 1
        return checked > 0

    def mesh_info(self) -> Optional[Dict[str, Any]]:
        """Mesh health snapshot for ``ctl mesh`` / ``GET /api/v5/mesh``
        — None when the multichip backend is off."""
        mc = self.mc
        if mc is None:
            return None
        out = mc.info()
        out["alarmed"] = self._mesh_alarmed
        out["rebuilding"] = self._mesh_rebuilding
        return out

    # ------------------------------------------------------------------
    # kernel backend routing (opt-in, match.backend)
    # ------------------------------------------------------------------

    def _backend_for(self, b: int, d: int) -> str:
        """Which kernel family serves a (batch, depth) group: the pinned
        backend, or — under ``auto`` — the autotuner's measured pick for
        the current table shape.  An unmeasured shape serves hash (the
        known-good default) and schedules a background measurement; the
        dispatch path never waits on one."""
        if self.backend != "auto":
            return self.backend
        t = self.tuner
        if t is None:
            return "hash"
        s, hb, _depth = self.inc.shape_key()
        # exact pick, else the pow2 (S, Hb)-family consensus: a growth
        # step inherits the family's measured answer instead of
        # re-measuring cold (ROADMAP join residual (d))
        pick = t.pick_for(b, d, s, hb)
        if pick is not None:
            return pick
        sig = t.sig(b, d, s, hb)
        if sig not in self._tuning and self._topic_sample:
            self._tuning.add(sig)
            # non-daemon, like the kernel cache's background compile: a
            # daemon thread racing XLA teardown at exit segfaults
            import threading

            threading.Thread(
                target=self._autotune_measure, args=(sig, b, d),
                name="match-autotune",
            ).start()
        return "hash"

    def _autotune_measure(self, sig: str, b: int, d: int) -> None:
        """Measurement thread: time hash vs join on the reservoir of
        recently served topics at exactly the dispatch shape, record
        the pick (persisted when segments are on).  Failures leave the
        default routing — a lost measurement is retried on a later
        dispatch of the same shape."""
        import jax

        from ..ops import encode_batch

        try:
            topics = list(self._topic_sample)
            if not topics or self.tuner is None:
                return
            names = (topics * (b // len(topics) + 1))[:b]
            inc, dev = self.inc, self.dev

            def runner(be):
                def go():
                    enc = encode_batch(inc, names, batch=b, depth=d)
                    jax.block_until_ready(dev.serve(*enc, backend=be))
                return go

            # no join-pallas candidate: Mosaic refuses its in-VMEM
            # table gathers (tests/test_chip_compile.py), and one
            # runner raising aborts the whole measurement
            runners = {"hash": runner("hash"), "join": runner("join")}
            self.tuner.measure(sig, runners)
            if self.metrics is not None:
                self.metrics.inc("tpu.match.autotune_picks")
        except Exception:
            log.warning("autotune measurement for %s failed; the shape "
                        "keeps serving hash", sig, exc_info=True)
        finally:
            self._tuning.discard(sig)

    async def _compact_loop(self) -> None:
        """Supervised ``table.compact`` child: periodically folds the
        accumulated mutations into a fresh compacted segment OFF the
        event loop and swaps it in atomically — serving never blocks on
        compaction (same supervise idiom as ``match.probe``)."""
        while True:
            await asyncio.sleep(self.compact_interval_s)
            if not self.ready:
                continue
            if self._mut_count < self.compact_min_mutations \
                    and os.path.exists(self._segment_path):
                continue
            try:
                await self._compact_once()
            except asyncio.CancelledError:
                raise
            except Exception:
                # leave the live table serving; the supervised child
                # retries next interval (an injected table.swap fault
                # lands here when unsupervised)
                log.exception("table compaction failed; retrying next "
                              "interval")

    def _snapshot_filters(self) -> Tuple[List[str], List[str], Set[str]]:
        """(nfa filters, deep filters, routing filter strings) — all
        service-level state, no table iteration."""
        ruled = {f for refs in self._rule_refs.values() for f in refs}
        deep = set(self._deep)
        nfa = sorted((set(self._ref) | ruled) - deep)
        return nfa, sorted(deep), set(self._ref)

    async def _compact_once(self) -> bool:
        """One compaction cycle: snapshot → background build + segment
        write → fixup + atomic swap.  Returns False when abandoned
        (too much churn landed mid-build; retried next interval)."""
        filters, deep_filters, routing = self._snapshot_filters()
        self._compact_dirty = set()
        self._compact_recording = True
        try:
            built = await asyncio.to_thread(
                _build_compacted, self.table_kind, self.depth,
                filters, deep_filters, routing,
                self.dev.active_slots, self.dev.max_matches,
                self.dev.compact_output, self.kcache,
                self.dev.dirty_full_threshold, self._segment_path,
                self.backend in ("join", "join-pallas", "auto"),
            )
        finally:
            self._compact_recording = False
        if len(self._compact_dirty) > 4096:
            # churn outran the build: abandon (the live table is
            # correct; only the compaction is stale) and retry
            self._compact_abandoned += 1
            log.warning("table compaction abandoned: %d filters "
                        "changed mid-build", len(self._compact_dirty))
            return False
        mc = self.mc
        if mc is not None and getattr(mc, "ep_autotune", False):
            # popularity balance pass rides the compaction worker
            # cadence: it STAGES a placement override map that the
            # repartition triggered by _swap_in below applies — so a
            # remap always lands with a fresh aid space and the
            # table-gen guard discarding in-flight slots.  A failure
            # (including an injected ep.rebalance fault) is a no-op:
            # the old placement keeps serving.
            try:
                await asyncio.to_thread(mc.plan_rebalance)
            except Exception:
                log.warning("EP balance pass failed; placement "
                            "unchanged", exc_info=True)
        self._swap_in(built)
        return True

    def _swap_in(self, built: Tuple[Any, ...]) -> None:
        """Atomic (single event-loop step) swap of the compacted table +
        device twin.  The chaos seam fires FIRST: a kill mid-swap
        mutates nothing, serving continues on the old table, and the
        supervised restart simply compacts again."""
        if _fi._injector is not None:
            if _fi._injector.act("table.swap") == "raise":
                raise _fi.InjectedFault("table.swap")
        fresh, newdev, new_deep, new_routing = built
        # fix up filters that changed while the build ran
        for flt in self._compact_dirty:
            routed = flt in self._ref
            ruled = any(flt in refs for refs in self._rule_refs.values())
            have = flt in new_deep or fresh.aid_of(flt) >= 0
            if (routed or ruled) and not have:
                _fresh_add(fresh, new_deep, flt)
            elif not (routed or ruled) and have:
                if flt in new_deep:
                    fresh.free_alias(new_deep.pop(flt))
                else:
                    fresh.remove(flt)
                continue
            aid = new_deep.get(flt, fresh.aid_of(flt))
            if aid >= 0:
                (new_routing.add if routed
                 else new_routing.discard)(aid)
        # remap rule ids into the fresh aid space from the live registry
        new_aid_rules: Dict[int, Set[str]] = {}
        for rule_id, refs in self._rule_refs.items():
            for flt in refs:
                aid = new_deep.get(flt, fresh.aid_of(flt))
                if aid >= 0:
                    new_aid_rules.setdefault(aid, set()).add(rule_id)
        new_trie = FilterTrie()
        for flt in new_deep:
            new_trie.insert(flt)
        self.inc = fresh
        self.dev = newdev
        self._deep = new_deep
        self._deep_trie = new_trie
        self._routing_aids = new_routing
        self._aid_rules = new_aid_rules
        # the fresh table reflects every drained delta + the fixups:
        # hints stay valid (they carry router epochs + filter strings,
        # never aids), in-flight device batches discard via the gen guard
        self._table_gen += 1
        self._synced_epoch = self._seen_epoch
        self._synced_rule_gen = self._rule_gen
        self._mut_count = len(self._compact_dirty)
        self._compact_dirty = set()
        self._mirror_ready = True
        if self.mc is not None:
            # the fresh table reassigned EVERY aid: repartition the
            # shard subtables from the new space; mc.ready drops and
            # the single-chip path serves until the rebuild applies
            self.mc.rebuild(self._mc_pairs())
            self._dirty.set()
        if self.metrics is not None:
            self.metrics.inc("tpu.table.compact_runs")
        log.info("compacted table swapped in (gen %d, %d filters)",
                 self._table_gen, fresh.n_filters)
        self._maybe_prewarm()   # cover the fresh table's next shapes

    def _maybe_prewarm(self) -> None:
        """Pre-pay the NEXT pow2 shapes' kernel compiles in the
        background once occupancy nears a growth boundary, so the
        resize is served from the cache (module docstring)."""
        if self.kcache is None or not self.prewarm or self._prewarm_busy:
            return
        nxt = self._next_shapes()
        if not nxt:
            return
        targets = [t for t in nxt if not self.kcache.shape_covered(*t)]
        if not targets:
            return
        self._prewarm_busy = True

        async def prewarm() -> None:
            try:
                for s, hb in targets:
                    await asyncio.to_thread(
                        self.kcache.prewarm_shape, s, hb)
            finally:
                self._prewarm_busy = False

        sup = getattr(self, "supervisor", None)
        if sup is not None:
            sup.start_child("table.prewarm", prewarm,
                            restart="temporary")
        else:
            asyncio.ensure_future(prewarm())

    def _next_shapes(self) -> List[Tuple[int, int]]:
        from ..ops.compiler import BUCKET_SLOTS

        s, hb, _d = self.inc.shape_key()
        n_states = int(self.inc.n_states)
        n_edges = getattr(self.inc, "n_edges", None)
        if n_edges is None:
            n_edges = self.inc.memory_bytes()["n_edges"]
        out: List[Tuple[int, int]] = []
        near_s = (s - n_states) <= max(s // 4, 8)
        # edge growth triggers at 3/4 load; start warming at ~55%
        near_hb = n_edges >= (hb * BUCKET_SLOTS * 11) // 20
        if near_s:
            out.append((2 * s, hb))
        if near_hb:
            out.append((s, 2 * hb))
        if near_s and near_hb:
            out.append((2 * s, 2 * hb))
        return out

    # ------------------------------------------------------------------
    # rule-engine co-batching (BASELINE config 3)
    # ------------------------------------------------------------------

    def register_rule(self, rule_id: str, from_filters: List[str]) -> None:
        """Co-batch a rule's FROM filters into the device table."""
        self.unregister_rule(rule_id)
        refs: Dict[str, int] = {}
        for flt in from_filters:
            refs[flt] = 1
            self._table_add(flt, routing=False)
            aid = self._deep.get(flt, self.inc.aid_of(flt))
            self._aid_rules.setdefault(aid, set()).add(rule_id)
        self._rule_refs[rule_id] = refs
        self._rule_gen += 1
        self._rule_log.append((self._rule_gen, tuple(from_filters)))
        self._dirty.set()

    def unregister_rule(self, rule_id: str) -> None:
        refs = self._rule_refs.pop(rule_id, None)
        if not refs:
            return
        for flt in refs:
            aid = self._deep.get(flt, self.inc.aid_of(flt))
            rules = self._aid_rules.get(aid)
            if rules is not None:
                rules.discard(rule_id)
                if not rules:
                    del self._aid_rules[aid]
            # drop the filter from the table unless routing still needs it
            if aid not in self._routing_aids and aid not in self._aid_rules:
                if flt in self._deep:
                    del self._deep[flt]
                    self._deep_trie.delete(flt)
                    self.inc.free_alias(aid)
                else:
                    self.inc.remove(flt)
        # removal-only entry: stale hints that still name the rule are
        # harmless (the engine skips ids not in its live rule map)
        self._rule_gen += 1
        self._rule_log.append((self._rule_gen, ()))
        self._dirty.set()

    # ------------------------------------------------------------------
    # serving
    # ------------------------------------------------------------------

    def _usable(self) -> bool:
        return (
            self.ready
            and self.router.epoch - self._synced_epoch <= self.max_stale_deltas
        )

    def _hint_fresh(self, topic: str, hint_epoch: int) -> bool:
        """Prove a hint still answers correctly for ``topic``.

        Deletions never need invalidation (destinations resolve live in
        ``routes_with_wild``); only a wildcard filter ADDED after the
        hint's table epoch can make the answer incomplete."""
        if hint_epoch == self.router.epoch:
            return True
        if self.router.epoch - hint_epoch > self.max_stale_deltas:
            return False  # bound the proof before materializing deltas
        deltas = self.router.deltas_since(hint_epoch)
        if deltas is None:
            return False
        for d in deltas:
            if d.op == "add" and T.wildcard(d.filter) \
                    and T.match(topic, d.filter):
                return False
        return True

    def _rules_fresh(self, topic: str, hint_gen: int) -> bool:
        """Rule-side freshness: a rule registered after the hint whose
        FROM filter matches the topic invalidates it (ADVICE.md round-2
        medium item: rule changes don't bump the router epoch)."""
        if hint_gen == self._rule_gen:
            return True
        if self._rule_log and self._rule_log[0][0] > hint_gen + 1:
            return False  # log trimmed past the hint's gen
        for gen, filters in self._rule_log:
            if gen > hint_gen and any(T.match(topic, f) for f in filters):
                return False
        return True

    def _note_arrival(self, topic: Optional[str] = None) -> None:
        now = time.monotonic()
        dt = now - self._win_start
        if dt >= 0.05:
            self._last_rate = self._win_count / dt
            if self.deadline:
                # EWMA-smooth the windowed rate (the fanout-gate
                # estimator shape) for the adaptive batch bound, and
                # track the short-lane traffic fraction for per-lane caps
                a = 0.5
                self._rate_ewma = (
                    self._last_rate if self._rate_ewma is None
                    else self._rate_ewma * (1.0 - a) + self._last_rate * a)
                frac = self._win_short / max(1, self._win_count)
                self._short_frac = (
                    frac if self._short_frac is None
                    else self._short_frac * (1.0 - a) + frac * a)
                self._win_short = 0
            self._win_start = now
            self._win_count = 0
        self._win_count += 1
        if topic is not None and self._is_short(topic):
            self._win_short += 1

    def _is_short(self, topic: str) -> bool:
        return topic.count("/") < self.short_depth

    def _should_bypass(self) -> bool:
        if self.bypass_rate <= 0:
            return False
        return not self._pending and self._last_rate < self.bypass_rate

    async def prefetch(self, topic: str, qos: int = 0) -> None:
        """Async stage (connection intercept): micro-batch this topic
        through the kernel and park the answer in the hint cache.
        Bounded by ``prefetch_timeout_s`` — a stalled device (compile,
        growth re-upload) degrades to the host path, never blocks
        publishes indefinitely.  In deadline mode the waiter carries its
        latency budget, and breaker-open / brownout states short-circuit
        straight to the CPU path (``qos`` feeds the stage-2 QoS0 shed)."""
        if not self.deadline:
            self._note_arrival()
            if not self._usable():
                return
            hint = self._hints.get(topic)
            if hint is not None and self._hint_fresh(topic, hint[0]) \
                    and self._rules_fresh(topic, hint[1]):
                return
            if self._should_bypass():
                if self.metrics is not None:
                    self.metrics.inc("tpu.match.bypass")
                return
            fut: asyncio.Future = asyncio.get_running_loop().create_future()
            # the match_wait stamp rides as the LAST element (only when
            # histograms are on — entries stay 2-tuples otherwise);
            # every consumer indexes from the front, and the deadline
            # accounting below is mode-gated, so the extra element is
            # invisible outside the histogram record
            if self._h_wait is not None:
                self._pending.append((topic, fut, _now_ns()))
            else:
                self._pending.append((topic, fut))
            self._batch_wake.set()
            try:
                cyc = await asyncio.wait_for(fut, self.prefetch_timeout_s)
            except Exception:
                # timeout/cancel: publish falls back to the host path
                self._note_prefetch_timeout(1)
                log.debug("prefetch for %r timed out", topic, exc_info=True)
                return
            if cyc is not None and self._h_resume is not None:
                # match_resume: the batch's hints were minted → this
                # waiter runs again (a waiter resolved empty-handed
                # got None and records nothing)
                self._h_resume.record(_now_ns() - cyc.t_mint)
            return
        self._note_arrival(topic)
        if not self._usable():
            return
        hint = self._hints.get(topic)
        if hint is not None and self._hint_fresh(topic, hint[0]) \
                and self._rules_fresh(topic, hint[1]):
            return
        if self._should_bypass():
            if self.metrics is not None:
                self.metrics.inc("tpu.match.bypass")
            return
        lvl = self._brownout()
        if self._breaker_open or lvl >= 3 or (lvl >= 2 and qos == 0):
            # CPU serve: no enqueue, no waiting — Broker.publish walks
            # the host trie when no fresh hint exists
            if self.metrics is not None:
                self.metrics.inc("broker.match.cpu_fallback")
            return
        loop = asyncio.get_running_loop()
        fut2: asyncio.Future = loop.create_future()
        if self._h_wait is not None:
            self._pending.append((topic, fut2,
                                  loop.time() + self.deadline_s,
                                  _now_ns()))
        else:
            self._pending.append(
                (topic, fut2, loop.time() + self.deadline_s))
        self._batch_wake.set()
        try:
            cyc = await asyncio.wait_for(fut2, self.prefetch_timeout_s)
        except Exception:
            self._note_prefetch_timeout(1)
            log.debug("prefetch for %r timed out", topic, exc_info=True)
            return
        if cyc is not None and self._h_resume is not None:
            self._h_resume.record(_now_ns() - cyc.t_mint)

    def _note_prefetch_timeout(self, n: int) -> None:
        if n and self.metrics is not None:
            self.metrics.inc("tpu.match.prefetch_timeout", n)

    async def prefetch_many(self, topics, qos_of=None) -> None:
        """Batched prefetch for the fanout pipeline: every topic missing
        a fresh hint is enqueued in the SAME event-loop tick, so the
        whole set rides one batching window — one kernel call for the
        batch instead of one ``prefetch`` await per message.  Bounded by
        ``prefetch_timeout_s`` like the single-topic path.

        ``topics`` may be a ``{topic: max_qos}`` mapping (the fanout
        pipeline passes one), which doubles as ``qos_of`` for the
        deadline-mode brownout stage-2 QoS0 shed."""
        if not self._usable():
            return
        if qos_of is None and isinstance(topics, dict):
            qos_of = topics
        deadline = self.deadline
        lvl = self._brownout() if deadline else 0
        if deadline and (self._breaker_open or lvl >= 3):
            # full CPU serve: the whole batch falls to the host trie
            if self.metrics is not None:
                self.metrics.inc("broker.match.cpu_fallback", len(topics))
            return
        waits: List[asyncio.Future] = []
        loop = asyncio.get_running_loop()
        deadline_t = loop.time() + self.deadline_s if deadline else 0.0
        shed = 0
        for topic in topics:
            self._note_arrival(topic if deadline else None)
            hint = self._hints.get(topic)
            if hint is not None and self._hint_fresh(topic, hint[0]) \
                    and self._rules_fresh(topic, hint[1]):
                continue
            if deadline and lvl >= 2 and qos_of is not None \
                    and qos_of.get(topic, 1) == 0:
                shed += 1   # brownout stage 2: QoS0 rides the CPU trie
                continue
            fut = loop.create_future()
            if self._h_wait is not None:
                ts = _now_ns()
                self._pending.append(
                    (topic, fut, deadline_t, ts) if deadline
                    else (topic, fut, ts))
            elif deadline:
                self._pending.append((topic, fut, deadline_t))
            else:
                self._pending.append((topic, fut))
            waits.append(fut)
        if shed and self.metrics is not None:
            self.metrics.inc("broker.match.cpu_fallback", shed)
        if not waits:
            return
        self._batch_wake.set()
        try:
            await asyncio.wait_for(
                asyncio.gather(*waits), self.prefetch_timeout_s
            )
        except Exception:
            # timeout/cancel: those topics fall back to the host trie
            self._note_prefetch_timeout(
                sum(1 for f in waits if not f.done() or f.cancelled()))
            log.debug("prefetch_many (%d topics) timed out", len(waits),
                      exc_info=True)

    def hint_available(self, topic: str) -> bool:
        """Non-consuming freshness peek (observability/tracing): True iff
        a device hint would serve this topic right now.  No metrics, no
        cache mutation — safe to call from taps."""
        hint = self._hints.get(topic)
        return hint is not None and self._hint_fresh(topic, hint[0])

    def hint_routes(self, topic: str):
        """Sync stage (Broker.publish): provably-fresh hint → routes,
        else None (host trie serves)."""
        hint = self._hints.get(topic)
        if hint is None:
            return None
        if not self._hint_fresh(topic, hint[0]):
            self._hints.pop(topic, None)
            if self.metrics is not None:
                self.metrics.inc("tpu.match.hint_stale")
            return None
        if self.metrics is not None:
            self.metrics.inc("tpu.match.hint_served")
        # move-to-end: a served hint is recent; eviction takes from the
        # other end of the dict (insertion order doubles as LRU order)
        self._hints[topic] = self._hints.pop(topic)
        return self.router.routes_with_wild(topic, hint[2])

    def hint_rules(self, topic: str) -> Optional[List[str]]:
        """Matched rule ids for a fresh hint, else None (rule engine then
        falls back to its per-rule host matching)."""
        hint = self._hints.get(topic)
        if hint is None:
            return None
        if not self._rules_fresh(topic, hint[1]):
            self._hints.pop(topic, None)
            if self.metrics is not None:
                self.metrics.inc("tpu.match.hint_stale")
            return None
        # a rules-only working set is just as hot as a routing one:
        # refresh LRU recency so it survives eviction (see hint_routes)
        self._hints[topic] = self._hints.pop(topic)
        return hint[3]

    def _deep_ids(self, topic: str) -> List[int]:
        if not self._deep:
            return []
        return [self._deep[f] for f in self._deep_trie.match(topic)]

    def _host_ids(self, topic: str) -> List[int]:
        return self.inc.match_host(topic) + self._deep_ids(topic)

    def _split_row(self, row: List[int]) -> Tuple[List[str], List[str]]:
        """aid row → (routing wildcard filters, rule ids)."""
        filters: List[str] = []
        rules: Set[str] = set()
        table = self.inc.accept_filters
        for aid in row:
            if aid in self._routing_aids:
                f = table[aid]
                if f is not None:
                    filters.append(f)
            r = self._aid_rules.get(aid)
            if r:
                rules.update(r)
        return filters, sorted(rules)

    def _encode_dispatch(self, inc, dev, topics, groups, cyc=None):
        """WORKER-THREAD stage: encode every depth group and dispatch
        its kernel — both OFF the event loop (the encode of a 2048
        batch held the loop ~2.3 ms per dispatch; vocab dict reads are
        GIL-atomic, and any concurrently-landed mutation is caught by
        the per-flight aid-reuse/table-gen guards or the hint freshness
        proof).  Dispatch only holds the device lock; the returned
        handles are lazy device results, so group 2 executes while
        group 1's answers stream back and — in pipeline mode — batch
        N+1 encodes while batch N computes.  ``cyc`` is the batch's
        books (``seq`` for the spans; this function's first- and
        last-line stamps for the loop's hop spans)."""
        t_in = _now_ns()
        from ..ops import encode_batch

        handles = []
        enc_ns = disp_ns = 0
        gen = self._table_gen
        seq = 0
        if cyc is not None:
            cyc.t_in, seq = t_in, cyc.seq
        multichip = getattr(dev, "is_multichip", False)
        # autotune reservoir: a slice of what this dispatch actually
        # serves (deque append is GIL-atomic; readers tolerate skew)
        self._topic_sample.extend(topics[:8])
        for idx, d in groups:
            be = "hash" if multichip else \
                self._backend_for(_bucket(len(idx)), d)
            n = len(idx)
            # each stage also goes into a running profiler trace under
            # its own start stamp (t_ns): the anchors that join the
            # spans' clock to the device timeline
            t0 = _now_ns()
            with _Annot("emqx.match.encode", seq=seq, n=n, t_ns=t0):
                if multichip:
                    # the shard partition's SHARED vocab assigns
                    # different word ids than the service table — encode
                    # there, then fan the batch over the mesh (rows come
                    # back already translated to service accept ids)
                    enc = dev.encode([topics[i] for i in idx],
                                     batch=_bucket(n), depth=d)
                else:
                    enc = encode_batch(inc, [topics[i] for i in idx],
                                       batch=_bucket(n), depth=d)
            t1 = _now_ns()
            with _Annot("emqx.match.dispatch", seq=seq, n=n, t_ns=t1):
                if multichip:
                    # one packed operand put into the step's own
                    # sharding, then the launch (mesh_put, mesh_launch)
                    res = dev.dispatch(
                        enc, block_compile=(dev.kernel_cache is None),
                        n=n, seq=seq, gen=gen)
                else:
                    res = dev.serve(
                        *enc,
                        # serving never parks behind XLA: an uncompiled
                        # shape raises CompileMiss (CPU trie answers,
                        # shape warms in the background) instead of
                        # stalling
                        block_compile=(dev.kernel_cache is None),
                        backend=be)
            t2 = _now_ns()
            if be in ("join", "join-pallas") and self.metrics is not None:
                # this worker is the single in-flight encode stage, so
                # the counter has one writer (same as the histograms)
                self.metrics.inc("tpu.match.backend_join_dispatches")
            enc_ns += t1 - t0
            disp_ns += t2 - t1
            # stage spans: this worker is the single in-flight encode
            # stage, so it is the sole writer of these two histograms
            # and its flight-recorder ring (both handles or neither)
            if self._sp_encode is not None:
                self._sp_encode.rec(t0, t1, n, gen, seq)
                self._sp_dispatch.rec(t1, t2, n, gen, seq)
            handles.append((res, n))
        if cyc is not None:
            cyc.t_out = _now_ns()
        return handles, enc_ns, disp_ns

    def _readback_groups(self, handles, dev, cyc=None):
        """WORKER-THREAD stage: block on every group's d2h, in BOTH
        serve modes.  A single-chip group's answer is the one packed
        array ``dev.serve`` returned: 4·(B + flat_cap) bytes, ONE device
        buffer (what a buffer costs on the attached chip, and why not
        fewer bytes in more fetches: PERF.md §6, PR 31); the mesh ships
        its own dense compact rows in one call.  Returns ``([(rows,
        spilled)...], total d2h bytes, readback ns, device buffers
        fetched)``.  ``cyc`` as in :meth:`_encode_dispatch`."""
        t0 = _now_ns()
        out = []
        nbytes = 0
        total = sum(n for _res, n in handles)
        seq = 0
        if cyc is not None:
            cyc.t_in, seq = t0, cyc.seq
        multichip = getattr(dev, "is_multichip", False)
        with _Annot("emqx.match.readback", seq=seq, n=total, t_ns=t0):
            for res, n in handles:
                if multichip:
                    # dense compact contract off the mesh, one
                    # device_get round trip (mesh_fetch, mesh_decode)
                    rows, sp, b = dev.readback(res, n, seq,
                                               self._table_gen)
                else:
                    rows, sp = decode_packed(res, n, dev.max_matches)
                    b = 4 * int(res.size)
                nbytes += b
                out.append((rows, sp))
        t1 = _now_ns()
        # single writer: the flag-off serve loop's to_thread hop OR the
        # pipelined readback child — never both in one mode
        if self._sp_readback is not None:
            self._sp_readback.rec(t0, t1, total, self._table_gen, seq)
        if cyc is not None:
            cyc.t_out = _now_ns()
        return out, nbytes, t1 - t0, len(handles)

    def _depth_groups(self, topics: List[str]) -> List[Tuple[List[int], int]]:
        """Partition batch indices into (indices, kernel_depth) groups.
        Kernel depth bounds TOPIC length, not filter depth, so short
        topics are exact through a shallow walk of the same table."""
        sd = self.short_depth
        everything = [(list(range(len(topics))), self.depth)]
        if not sd or sd >= self.depth:
            return everything
        short = [i for i, t in enumerate(topics) if t.count("/") < sd]
        if len(short) < self.split_min or \
                len(topics) - len(short) < self.split_min:
            return everything
        sset = set(short)
        long_ = [i for i in range(len(topics)) if i not in sset]
        return [(short, sd), (long_, self.depth)]

    async def _batch_loop(self) -> None:
        """The pre-deadline fixed-window serve loop (default): wake,
        sleep the batching window, pop up to ``max_batch`` waiters, one
        kernel dispatch.  Byte-identical to the PR-6 path except for the
        waiter-failover fix shared with the deadline loop: a killed or
        crashed run resolves its in-flight waiters immediately (CPU path
        serves) and a restart re-arms the wake on a non-empty queue."""
        try:
            if self._pending:
                # supervisor restart mid-backlog: the dead run consumed
                # the wake — never stall waiters on a non-empty queue
                # (mirrors the fanout _run re-arm fix from PR 3)
                self._batch_wake.set()
            while True:
                await self._batch_wake.wait()
                self._batch_wake.clear()
                if not self._pending:
                    continue
                t_wake = _now_ns()      # the cycle starts: woken with work
                await asyncio.sleep(self.batch_window_s)
                pending, self._pending = self._pending[: self.max_batch], \
                    self._pending[self.max_batch:]
                if self._pending:
                    self._batch_wake.set()
                await self._serve_batch(pending, t_wake)
        finally:
            self._fail_over_waiters()

    def _open_cycle(self, pending: List[Any], t_wake: int) -> _Cycle:
        """A batch was popped: give it its ``seq``, record its waiters'
        queue waits and, where the serve loop stamped its wake-up
        (``t_wake``), the batching window ``t_wake`` → now."""
        now = _now_ns()
        self._seq += 1
        cyc = _Cycle(self._seq, len(pending), self._table_gen,
                     t_wake or now)
        if self._h_wait is not None:
            self._rec_wait(pending, now, cyc)
        if t_wake:
            cyc.spanned = now - t_wake
            if self._sp_window is not None:
                self._sp_window.rec(t_wake, now, cyc.n, cyc.gen, cyc.seq)
        return cyc

    def _close_cycle(self, cyc: _Cycle) -> None:
        """The serial paths' last word on a batch, whichever way it
        went: the loop-side epilogue (where the batch got that far), the
        whole cycle, and the two counters whose ratio says how much of
        the cycle lay inside a stage span."""
        end = cyc.t_mint or _now_ns()
        if cyc.t_ep:
            cyc.spanned += end - cyc.t_ep
        if self._sp_cycle is not None:      # the loop's handles: all or none
            if cyc.t_ep:
                self._sp_epilogue.rec(cyc.t_ep, end, cyc.n, cyc.gen, cyc.seq)
            self._sp_cycle.rec(cyc.t0, end, cyc.n, cyc.gen, cyc.seq)
        if self.metrics is not None:
            self.metrics.inc("tpu.match.cycle_ns", end - cyc.t0)
            self.metrics.inc("tpu.match.cycle_spanned_ns", cyc.spanned)

    def _rec_wait(self, pending: List[Any], now_ns: int,
                  cyc: _Cycle) -> None:
        """Record each popped waiter's queue wait (enqueue → its batch
        is popped) + one flight-recorder event per batch.  Only reachable
        with histograms on — the stamps ride the waiter tuples' tail."""
        rec = self._h_wait.record
        oldest = now_ns
        n = 0
        for p in pending:
            ts = p[-1]
            # the stamp is an int (perf_counter_ns); a deadline tail is
            # a float and a bare test-injected waiter ends in a future —
            # neither is a stamp, and recording must never be the thing
            # that kills the serve loop
            if type(ts) is not int:
                continue
            rec(now_ns - ts)
            n += 1
            if ts < oldest:
                oldest = ts
        if n and self._sp_wait_batch is not None:
            self._sp_wait_batch.rec(oldest, now_ns, n, cyc.gen, cyc.seq)

    async def _serve_batch(self, pending: List[Any],
                           t_wake: int = 0) -> None:
        """Fixed-window dispatch: device rows → hints, any failure
        resolves the waiters empty-handed (host trie serves)."""
        cyc = self._open_cycle(pending, t_wake)
        if self.pipeline:
            await self._pipeline_dispatch(pending, False, cyc)
            return
        topics = [p[0] for p in pending]
        # the hint's provenance is the epoch the DEVICE table
        # reflects (not the live router epoch — the table may lag;
        # freshness is then proven forward from here at consume time)
        epoch = self._synced_epoch
        rule_gen = self._synced_rule_gen
        try:
            if not self._usable():
                # ordinary churn (the mirror lags the router past the
                # staleness bound): counted below, not a device fault
                raise _StaleRace("mirror stale")
            rows = await self._dispatch_guarded(topics, cyc)
            self._mint_hints(pending, rows, epoch, rule_gen, cyc)
        except Exception as e:
            if not isinstance(e, (_StaleRace, CompileMiss)):
                self._warn_device_failure("device batch", e)
            n = 0
            for p in pending:
                if not p[1].done():
                    p[1].set_result(None)
                    n += 1
            # the waiters resolve empty-handed and Broker.publish walks
            # the host trie: the same accounting as _fail_over_waiters
            if n and self.metrics is not None:
                self.metrics.inc("broker.match.cpu_fallback", n)
        finally:
            self._close_cycle(cyc)

    def _warn_device_failure(self, what: str, e: BaseException) -> None:
        """The serve plane is fail-open by design — the host trie
        answers — but never silently: a real device error is logged at
        WARNING, once per distinct error so a kernel that fails on
        every batch does not flood the log."""
        sig = f"{what}: {type(e).__name__}: {e}"[:200]
        if sig in self._warned or len(self._warned) >= 64:
            return      # bounded: messages may embed varying values
        self._warned.add(sig)
        log.warning("%s failed; the host trie serves these publishes "
                    "(logged once per distinct error)", what,
                    exc_info=(type(e), e, e.__traceback__))

    async def _fault_gate(self) -> None:
        """The ``match.dispatch`` chaos seam, shared by both serve loops
        and the breaker's recovery probe.  ``hang`` parks until the
        caller's per-dispatch timeout (or cancellation) rescues it."""
        if _fi._injector is not None:
            act = _fi._injector.act("match.dispatch")
            if act == "raise":
                raise _fi.InjectedFault("match.dispatch")
            if act == "delay":
                await _fi._injector.pause()
            elif act == "hang":
                await _fi._injector.hang()

    async def _readback_gate(self) -> None:
        """The ``match.readback`` chaos seam at the d2h boundary,
        shared by the flag-off serve path and the pipelined
        ``match.readback`` child.  ``hang`` parks until the pipelined
        per-slot timeout (or the waiters' prefetch timeout on the
        flag-off path) rescues it."""
        if _fi._injector is not None:
            act = _fi._injector.act("match.readback")
            if act == "raise":
                raise _fi.InjectedFault("match.readback")
            if act == "delay":
                await _fi._injector.pause()
            elif act == "hang":
                await _fi._injector.hang()

    async def _dispatch_guarded(self, topics: List[str],
                                cyc: Optional[_Cycle] = None) -> List[Any]:
        await self._fault_gate()
        return await self._device_serve(topics, cyc)

    async def _device_serve(self, topics: List[str],
                            cyc: Optional[_Cycle] = None) -> List[Any]:
        """Encode + kernel dispatch + readback + spill/deep merge for one
        batch; returns one aid row per topic.  Raises :class:`_StaleRace`
        when a freed accept id was handed out mid-flight (benign — the
        answer is untrusted but the device is healthy).

        The batch's books (``cyc``) get the two thread hops each way —
        ``to_thread`` called → the worker's first line, its last line →
        this coroutine runs again (the first hop back holds the readback
        chaos gate) — and the start of the loop-side epilogue, which the
        caller's ``_close_cycle`` ends."""
        # aid-reuse guard: if a freed accept id is handed out
        # again while this batch is in flight, the device rows
        # may name it under its OLD filter — translating through
        # the live accept_filters would be wrong at any epoch.
        # The table-gen guard is the segment-swap twin: a compacted
        # table swapped in mid-flight reassigned EVERY aid.
        inc = self.inc
        dev = self._serving_dev()
        reuses0 = inc.aid_reuses
        gen0 = self._table_gen
        groups = self._depth_groups(topics)
        if cyc is None:     # a direct call: books nobody closes
            cyc = _Cycle(0, len(topics), gen0, 0)
        t_call = _now_ns()
        handles, enc_ns, disp_ns = await asyncio.to_thread(
            self._encode_dispatch, inc, dev, topics, groups, cyc
        )
        await self._readback_gate()
        t_mid = _now_ns()
        self._rec_hops(cyc, t_call, t_mid)
        results, nbytes, rb_ns, trips = await asyncio.to_thread(
            self._readback_groups, handles, dev, cyc
        )
        t_ep = cyc.t_ep = _now_ns()
        self._rec_hops(cyc, t_mid, t_ep)
        cyc.spanned += enc_ns + disp_ns + rb_ns
        with _Annot("emqx.match.epilogue", seq=cyc.seq, n=cyc.n, t_ns=t_ep):
            self._note_split((enc_ns + disp_ns) / 1e9, rb_ns / 1e9)
            if self.metrics is not None:
                self.metrics.inc("tpu.match.readback_bytes", nbytes)
                self.metrics.inc("tpu.match.readback_roundtrips", trips)
            return self._collect_rows(topics, groups, results,
                                      inc, reuses0, gen0)

    def _rec_hops(self, cyc: _Cycle, t_call: int, t_back: int) -> None:
        """One worker hop, seen from the loop: ``t_call`` (``to_thread``
        called) → the worker's first line, and its last line →
        ``t_back`` (this loop runs the caller again)."""
        cyc.spanned += (cyc.t_in - t_call) + (t_back - cyc.t_out)
        if self._sp_hop_out is not None:    # the loop's handles: all or none
            self._sp_hop_out.rec(t_call, cyc.t_in, cyc.n, cyc.gen, cyc.seq)
            self._sp_hop_back.rec(cyc.t_out, t_back, cyc.n, cyc.gen, cyc.seq)

    def _collect_rows(self, topics: List[str], groups, results,
                      inc, reuses0: int, gen0: int) -> List[Any]:
        """Loop-side epilogue shared by the serial path and the
        pipelined readback child: stitch group results back into batch
        order, enforce the per-flight staleness guards, re-run spilled
        rows on the host tables, merge deep-filter hits."""
        rows: List[Any] = [None] * len(topics)
        spilled: List[int] = []
        for (idx, _d), (grows, gspill) in zip(groups, results):
            for j, i in enumerate(idx):
                rows[i] = grows[j]
            spilled.extend(idx[j] for j in gspill)
        if self.inc.aid_reuses != reuses0 or inc is not self.inc \
                or self._table_gen != gen0:
            raise _StaleRace("aid reused or table swapped mid-flight")
        if self.metrics is not None:
            # counted only once the whole batch is known good, so
            # batches/topics counters stay consistent
            self.metrics.inc("tpu.match.batches", len(groups))
        spset = set(spilled)
        for r in spilled:
            rows[r] = self._host_ids(topics[r])
            if self.metrics is not None:
                self.metrics.inc("tpu.match.fallback_host")
        mc = self.mc
        if mc is not None and mc.degraded_serving:
            # degraded mesh: replicated rows lost the dead shards'
            # answer segments — CPU-fill ONLY those aids (a live
            # EP-routed row never intersects: every literal-root match
            # lives on the root's owner shard, which is alive, and
            # wildcard-root filters ride the replicated micro-table)
            fill = mc.dead_aids()
            if fill:
                filled = 0
                for r, t in enumerate(topics):
                    if r in spset:
                        continue    # host-served: already complete
                    add = [a for a in self._host_ids(t) if a in fill]
                    if add:
                        rows[r].extend(add)
                        filled += 1
                if filled:
                    mc.cpu_filled_rows += filled
                    if self.metrics is not None:
                        self.metrics.inc("tpu.mesh.cpu_filled_rows",
                                         filled)
            self._mesh_watch()
        if self._deep:
            # too-deep filters live host-side; merge their hits
            for r, t in enumerate(topics):
                if r not in spset:
                    rows[r].extend(self._deep_ids(t))
        if self.metrics is not None:
            self.metrics.inc("tpu.match.topics", len(topics))
            if spilled:
                self.metrics.inc(
                    "tpu.match.active_overflow", len(spilled)
                )
        return rows

    def _mint_hints(self, pending: List[Any], rows: List[Any],
                    epoch: int, rule_gen: int,
                    cyc: Optional[_Cycle] = None) -> None:
        """Park the device's rows in the hint cache and wake the
        waiters.  Each future resolves to the batch's books (``cyc``;
        no consumer reads the result but ``prefetch``'s own
        ``match_resume`` span, which starts at ``t_mint``)."""
        t0 = _now_ns()
        with _Annot("emqx.match.epilogue", seq=cyc.seq if cyc else 0,
                    n=len(pending), t_ns=t0):
            for p, row in zip(pending, rows):
                topic, fut = p[0], p[1]
                # pop-then-insert: a refreshed hint is ACTIVE — plain
                # assignment would keep its stale dict position and
                # let the post-insert prune evict it ahead of colder
                # entries, wasting the device work just spent on it
                self._hints.pop(topic, None)
                self._hints[topic] = (epoch, rule_gen,
                                      *self._split_row(row))
                if not fut.done():
                    fut.set_result(cyc)
            self._evict()
            if self.deadline and self.metrics is not None:
                self._count_misses(pending)
        if cyc is not None:
            cyc.t_mint = _now_ns()

    def _evict(self) -> None:
        # evict AFTER insert, least-recently-SERVED first (dict
        # order is recency: hint_routes re-appends on a hit).
        # Post-insert pruning makes the cap a true invariant
        # even when a single batch exceeds it (the batch's own
        # oldest entries go too), counts refreshed-in-place
        # topics as the no-ops they are, and the metric is the
        # exact deletion count.  The old full-clear thrashed
        # working sets just over hint_cap between full-cache
        # and cold-cache — the hot head of a Zipf working set
        # must survive the arrival of its own cold tail.
        excess = len(self._hints) - self.hint_cap
        if excess > 0:
            it = iter(self._hints)
            for k in [next(it) for _ in range(excess)]:
                del self._hints[k]
            if self.metrics is not None:
                self.metrics.inc("tpu.match.hint_evicted", excess)

    def _count_misses(self, pending: List[Any]) -> None:
        try:
            now = asyncio.get_running_loop().time()
        except RuntimeError:
            # no running loop (direct sync call in tests): deadline
            # accounting is loop-time based, so there is nothing to count
            return
        late = sum(1 for p in pending if len(p) > 2 and now > p[2])
        if late:
            self.metrics.inc("broker.match.deadline_miss", late)

    def _note_split(self, disp_s: float, rb_s: float) -> None:
        """Feed the split dispatch-vs-readback estimate from the stage
        timers: ``disp_s`` is the worker-thread encode+dispatch span,
        ``rb_s`` the d2h readback span — neither includes queue-wait,
        which the combined ``_est_dispatch_s`` EWMA picks up in
        pipeline mode (slots sit in the inflight queue inside its
        t0→resolve window)."""
        self._est_disp_s = self._est_disp_s * 0.7 + disp_s * 0.3
        self._est_rb_s = self._est_rb_s * 0.7 + rb_s * 0.3
        if self._est_split_samples < 1 << 30:
            self._est_split_samples += 1

    #: split-estimate warm threshold: below this many component
    #: samples the combined EWMA serves (the histograms/timers are
    #: cold right after start or a long idle gap)
    SPLIT_WARM = 8

    def _dispatch_est(self) -> float:
        """The dispatch-time estimate the partial-flush trigger and the
        adaptive bound subtract from the budget: the split components'
        sum once warm (queue-wait-free), the combined EWMA as the cold
        fallback."""
        if self._est_split_samples >= self.SPLIT_WARM:
            return self._est_disp_s + self._est_rb_s
        return self._est_dispatch_s

    def _fail_over_waiters(self) -> None:
        """Serve-loop death (kill, crash, stop): resolve every in-flight
        waiter NOW so each blocked ``prefetch`` falls to the CPU path
        immediately instead of burning the full ``prefetch_timeout_s``."""
        pending, self._pending = self._pending, []
        if not pending:
            return
        for p in pending:
            if not p[1].done():
                p[1].set_result(None)
        if self.metrics is not None:
            self.metrics.inc("broker.match.cpu_fallback", len(pending))
        log.warning("match serve loop exited with %d waiter(s) in "
                    "flight; failed over to the CPU path", len(pending))

    # ------------------------------------------------------------------
    # deadline-aware continuous-batching serve loop (opt-in)
    # ------------------------------------------------------------------

    async def _deadline_loop(self) -> None:
        """Continuous batching under a latency budget: dispatch when the
        adaptive bound fills OR the oldest waiter's remaining budget no
        longer covers the (EWMA-estimated) dispatch time — whichever
        comes first.  See the module docstring for the full ladder."""
        loop = asyncio.get_running_loop()
        try:
            if self._pending:
                # restart mid-backlog: the dead run consumed the wake
                self._batch_wake.set()
            while True:
                await self._batch_wake.wait()
                self._batch_wake.clear()
                t_wake = _now_ns()      # a cycle starts: woken with work
                while self._pending:
                    if not self._device_ok():
                        # breaker open / brownout stage 3 / mirror gone
                        # stale with waiters queued: CPU answers them now
                        self._cpu_serve(self._pop_batch(len(self._pending)))
                        continue
                    bound = self._deadline_bound()
                    slack = (self._pending[0][2] - loop.time()
                             - self._dispatch_est())
                    if len(self._pending) < bound and slack > 0:
                        # gather window: admit more arrivals, but never
                        # wait past the oldest waiter's budget; geometric
                        # re-check keeps idle wakeups bounded while the
                        # wake event stays responsive to new arrivals
                        wait = min(slack,
                                   max(self.batch_window_s, slack / 4))
                        try:
                            await asyncio.wait_for(
                                self._batch_wake.wait(), wait)
                        except asyncio.TimeoutError:
                            pass
                        self._batch_wake.clear()
                        continue
                    if len(self._pending) < bound \
                            and self.metrics is not None:
                        # partial batch forced out by the budget — the
                        # deadline doing its job, not an anomaly
                        self.metrics.inc("broker.match.deadline_dispatch")
                    await self._serve_batch_deadline(
                        self._pop_batch(bound), t_wake)
                    t_wake = _now_ns()  # the next cycle, if work is left
        finally:
            self._fail_over_waiters()

    def _deadline_bound(self) -> int:
        """Arrival-rate-adaptive batch bound: a batch covers at most the
        budget's worth of arrivals after the estimated dispatch time is
        paid, so fill latency + dispatch fits the budget at any load —
        floored at the arrivals landing DURING one dispatch, or the loop
        would fall behind by construction (an infeasible budget degrades
        to throughput mode, never to a diverging queue).  Brownout stage
        1+ shrinks the cap (half, then quarter)."""
        rate = (self._rate_ewma if self._rate_ewma is not None
                else self._last_rate)
        est = self._dispatch_est()
        headroom = max(self.deadline_s - est,
                       self.deadline_s * 0.25)
        bound = max(1, min(self.max_batch,
                           max(int(rate * headroom),
                               int(rate * est * 1.2))))
        lvl = self._brownout()
        if lvl:
            bound = max(1, bound >> min(lvl, 2))
        return bound

    def _lane_caps(self, bound: int) -> Tuple[int, int]:
        """Per-lane (short-topic, long-topic) caps from the observed
        short-lane traffic fraction — a deep-topic flood cannot consume
        the whole bound and starve the cheap shallow kernel.  25% slack
        per lane so a lagging estimate never starves shifting traffic."""
        if not self.short_depth or self.short_depth >= self.depth:
            return bound, bound
        frac = self._short_frac if self._short_frac is not None else 0.5
        short = min(bound, max(1, int(bound * frac * 1.25) + 1))
        long_ = min(bound, max(1, int(bound * (1.0 - frac) * 1.25) + 1))
        return short, long_

    def _pop_batch(self, bound: int) -> List[Any]:
        """Pop up to ``bound`` waiters from the queue head, honoring the
        per-lane caps; waiters whose lane is full stay queued IN ORDER
        (their budget forces the next dispatch soon enough).  The scan is
        bounded so a deep backlog can't turn the pop quadratic."""
        short_cap, long_cap = self._lane_caps(bound)
        pend = self._pending
        take: List[Any] = []
        rest: List[Any] = []
        limit = min(len(pend), 4 * bound)
        pos = 0
        while pos < limit and len(take) < bound:
            entry = pend[pos]
            pos += 1
            if self._is_short(entry[0]):
                if short_cap > 0:
                    short_cap -= 1
                    take.append(entry)
                else:
                    rest.append(entry)
            elif long_cap > 0:
                long_cap -= 1
                take.append(entry)
            else:
                rest.append(entry)
        rest.extend(pend[pos:])
        self._pending = rest
        return take

    async def _serve_batch_deadline(self, pending: List[Any],
                                    t_wake: int = 0) -> None:
        """One deadline-mode dispatch: chaos seam + per-dispatch timeout
        around the kernel call; ANY failure answers the whole batch from
        the CPU tables immediately and feeds the circuit breaker."""
        if not pending:
            return
        cyc = self._open_cycle(pending, t_wake)
        if self.pipeline:
            await self._pipeline_dispatch(pending, True, cyc)
            return
        topics = [p[0] for p in pending]
        epoch = self._synced_epoch
        rule_gen = self._synced_rule_gen
        t0 = time.monotonic()
        try:
            rows = await asyncio.wait_for(
                self._dispatch_guarded(topics, cyc),
                self.dispatch_timeout_s)
        except asyncio.CancelledError:
            # loop death mid-dispatch: the finally-failover resolves
            self._pending = pending + self._pending
            raise
        except _StaleRace:
            self._cpu_serve(pending)    # benign race: no breaker strike
        except CompileMiss:
            # fresh padded shape not compiled yet: the CPU trie answers
            # NOW while the kernel cache warms it in the background —
            # the device is healthy, so no breaker strike
            self._cpu_serve(pending)
        except Exception:
            log.debug("deadline dispatch failed; CPU trie serves the "
                      "batch", exc_info=True)
            self._breaker_note_failure()
            self._cpu_serve(pending)
        else:
            self._breaker_note_ok()
            # EWMA dispatch-time estimate drives the partial-flush trigger
            dt = time.monotonic() - t0
            self._est_dispatch_s = self._est_dispatch_s * 0.7 + dt * 0.3
            self._mint_hints(pending, rows, epoch, rule_gen, cyc)
        finally:
            self._close_cycle(cyc)

    def _cpu_serve(self, pending: List[Any]) -> None:
        """Answer a batch from the CPU tables (host NFA walk + deep
        trie), minting hints at the MIRROR's epoch so the device outage
        stays invisible to publishes — this is the fallback the whole
        ladder bottoms out on (broker/trie.py answers every query the
        device table does)."""
        if not pending:
            return
        # the host table reflects every drained delta (_seen_epoch) and
        # the live rule gen — host answers are as fresh as serving gets
        epoch = self._seen_epoch
        rule_gen = self._rule_gen
        deep = (self._deep_trie.match_many([p[0] for p in pending])
                if self._deep else None)
        rows_of: Dict[str, List[int]] = {}
        for p in pending:
            topic, fut = p[0], p[1]
            row = rows_of.get(topic)
            if row is None:
                row = list(self.inc.match_host(topic))
                if deep is not None:
                    row.extend(self._deep[f] for f in deep[topic])
                rows_of[topic] = row
            self._hints.pop(topic, None)
            self._hints[topic] = (epoch, rule_gen, *self._split_row(row))
            if not fut.done():
                fut.set_result(None)
        self._evict()
        if self.metrics is not None:
            self.metrics.inc("broker.match.cpu_fallback", len(pending))
            self._count_misses(pending)
        # a shard failure lands here (the failed batch CPU-serves):
        # reconcile the mesh ladder — alarm, state metric, rebuild
        self._mesh_watch()

    # ------------------------------------------------------------------
    # overlapped serve pipeline (opt-in, match.pipeline.enable)
    # ------------------------------------------------------------------

    async def _pipeline_dispatch(self, pending: List[Any],
                                 deadline_mode: bool,
                                 cyc: Optional[_Cycle] = None) -> None:
        """Pipeline-mode front half of a serve batch: encode + dispatch
        in a worker thread, then hand the in-flight slot to the
        ``match.readback`` child and return — the serve loop goes
        straight back to batching (and encoding batch N+1) while this
        batch computes on device.  Every slot carries
        the aid-reuse/table-gen guards it dispatched against, so a swap
        or reuse landing mid-flight discards exactly the stale slot.
        ``cyc`` rides the slot for its ``seq`` alone: the overlapped
        stages of this path share the stage spans, not the closed
        books of the serial one."""
        if not pending:
            return
        topics = [p[0] for p in pending]
        epoch = self._synced_epoch
        rule_gen = self._synced_rule_gen
        inc = self.inc
        reuses0 = inc.aid_reuses
        gen0 = self._table_gen
        t0 = time.monotonic()
        try:
            if not self._usable():
                raise RuntimeError("mirror stale")
            await self._fault_gate()
            dev = self._serving_dev()
            groups = self._depth_groups(topics)
            dispatch = asyncio.to_thread(
                self._encode_dispatch, inc, dev, topics, groups, cyc)
            if deadline_mode:
                handles, enc_ns, disp_ns = await asyncio.wait_for(
                    dispatch, self.dispatch_timeout_s)
            else:
                handles, enc_ns, disp_ns = await dispatch
            slot = (pending, topics, groups, handles, inc, dev,
                    reuses0, gen0, epoch, rule_gen, t0, deadline_mode,
                    enc_ns + disp_ns, cyc)
            await self._inflight_q.put(slot)   # backpressure at depth
            self._inflight_n += 1
            self._set_inflight_metric()
        except asyncio.CancelledError:
            # loop death mid-dispatch (or mid-put): the finally-failover
            # resolves these waiters immediately
            self._pending = pending + self._pending
            raise
        except _StaleRace:
            self._cpu_serve(pending)        # benign race: no strike
        except CompileMiss:
            self._cpu_serve(pending)        # shape warms in background
        except Exception:
            log.debug("pipelined dispatch failed; CPU trie serves the "
                      "batch", exc_info=True)
            if deadline_mode:
                self._breaker_note_failure()
            self._cpu_serve(pending)

    async def _readback_loop(self) -> None:
        """Supervised ``match.readback`` child: drains the in-flight
        slot queue, fetches each slot's answer and mints hints — the
        back half of the double-buffered chain.  A kill resolves every
        queued slot's waiters NOW (CPU path serves) and the supervised
        restart resumes consuming."""
        try:
            while True:
                slot = await self._inflight_q.get()
                try:
                    await self._finish_slot(slot)
                finally:
                    self._inflight_n -= 1
                    self._set_inflight_metric()
        finally:
            self._fail_over_slots()

    async def _finish_slot(self, slot: Tuple[Any, ...]) -> None:
        """Readback + guard check + hint mint for one in-flight slot;
        ANY failure (chaos seam, timeout, stale guard) answers the
        slot's batch from the CPU tables.  The finally backstop keeps
        the kill path from stranding waiters on the prefetch timeout."""
        (pending, topics, groups, handles, inc, dev, reuses0, gen0,
         epoch, rule_gen, t0, deadline_mode, dispatch_ns, cyc) = slot
        try:
            try:
                await self._readback_gate()
                results, nbytes, rb_ns, trips = await asyncio.wait_for(
                    asyncio.to_thread(
                        self._readback_groups, handles, dev, cyc),
                    self.dispatch_timeout_s)
                self._note_split(dispatch_ns / 1e9, rb_ns / 1e9)
                if self.metrics is not None:
                    self.metrics.inc("tpu.match.readback_bytes", nbytes)
                    self.metrics.inc("tpu.match.readback_roundtrips",
                                     trips)
                rows = self._collect_rows(topics, groups, results,
                                          inc, reuses0, gen0)
            except asyncio.CancelledError:
                raise
            except _StaleRace:
                # the swap/reuse happened AFTER this slot dispatched:
                # only this slot's answer is untrusted — CPU serves it,
                # no breaker strike (the device is healthy)
                self._cpu_serve(pending)
                return
            except Exception:
                log.debug("pipelined readback failed; CPU trie serves "
                          "the batch", exc_info=True)
                if deadline_mode:
                    self._breaker_note_failure()
                self._cpu_serve(pending)
                return
            if deadline_mode:
                self._breaker_note_ok()
                # full dispatch→readback time feeds the partial-flush
                # estimate: with the stages overlapped this is the
                # latency a waiter actually experiences
                dt = time.monotonic() - t0
                self._est_dispatch_s = (
                    self._est_dispatch_s * 0.7 + dt * 0.3)
            self._mint_hints(pending, rows, epoch, rule_gen, cyc)
        finally:
            for p in pending:
                if not p[1].done():
                    p[1].set_result(None)

    def _fail_over_slots(self) -> None:
        """Readback-child death: resolve every queued slot's waiters so
        their publishes fall to the CPU path immediately instead of
        burning the full prefetch timeout (the in-flight twin of
        :meth:`_fail_over_waiters`)."""
        q = self._inflight_q
        n = 0
        while q is not None and not q.empty():
            slot = q.get_nowait()
            for p in slot[0]:
                if not p[1].done():
                    p[1].set_result(None)
                    n += 1
        self._inflight_n = 0
        self._set_inflight_metric()
        if n:
            if self.metrics is not None:
                self.metrics.inc("broker.match.cpu_fallback", n)
            log.warning("match readback loop exited with %d waiter(s) "
                        "in flight; failed over to the CPU path", n)

    def _set_inflight_metric(self) -> None:
        if self.metrics is not None:
            self.metrics.set("broker.match.pipeline_inflight",
                             self._inflight_n)

    # ------------------------------------------------------------------
    # circuit breaker + brownout
    # ------------------------------------------------------------------

    def _brownout(self) -> int:
        olp = self.olp
        lvl = 0 if olp is None else olp.brownout_level()
        if lvl != self._last_brownout:
            if lvl > self._last_brownout and self.flightrec is not None:
                # brownout ESCALATION: capture what the last few
                # hundred batches were doing when the ladder stepped
                # (de-escalation is recovery, nothing to forensic)
                self.flightrec.dump("brownout")
            self._last_brownout = lvl
            if self.metrics is not None:
                self.metrics.set("broker.match.brownout_level", lvl)
        return lvl

    def _device_ok(self) -> bool:
        """May the next dispatch go to the device?"""
        if self._breaker_open or not self._usable():
            return False
        return self._brownout() < 3

    def _breaker_note_ok(self) -> None:
        self._breaker_failures = 0

    def _breaker_note_failure(self) -> None:
        self._breaker_failures += 1
        if (not self._breaker_open
                and self._breaker_failures >= self.breaker_threshold):
            self._trip_breaker()

    def _trip_breaker(self) -> None:
        self._breaker_open = True
        self._set_breaker_metric(1)
        log.error("match-service breaker OPEN after %d consecutive "
                  "dispatch failures; CPU trie serves",
                  self._breaker_failures)
        if self.alarms is not None:
            self.alarms.activate(
                "match_degraded",
                {"failures": self._breaker_failures},
                "device match dispatch failing; serving from CPU trie",
            )
        if self.flightrec is not None:
            # the forensic payoff: what the serve path was doing for
            # the last few hundred batches before the trip
            self.flightrec.dump("breaker_trip")
        sup = getattr(self, "supervisor", None)
        if sup is not None:
            # supervised recovery child: a crashing probe restarts per
            # policy instead of leaving the breaker open forever
            self._probe_child = sup.start_child(
                "match.probe", self._probe_loop, restart="transient")
        else:
            self._probe_child = asyncio.ensure_future(self._probe_loop())

    def _close_breaker(self) -> None:
        self._breaker_open = False
        self._breaker_failures = 0
        self._set_breaker_metric(0)
        log.warning("match-service breaker closed: device dispatch "
                    "healthy again")
        if self.alarms is not None:
            self.alarms.deactivate("match_degraded")

    def _set_breaker_metric(self, state: int) -> None:
        if self.metrics is not None:
            self.metrics.set("broker.match.breaker_state", state)

    async def _probe_loop(self) -> None:
        """Breaker recovery: every ``probe_interval``, push one canary
        batch through the full dispatch seam (same chaos gate, same
        timeout).  First success closes the breaker and ends the child
        (transient — a clean return is 'recovered')."""
        while self._running and self._breaker_open:
            await asyncio.sleep(self.breaker_probe_interval_s)
            if not self._running:
                return
            self._set_breaker_metric(2)
            try:
                await asyncio.wait_for(
                    self._probe_guarded(), self.dispatch_timeout_s)
            except asyncio.CancelledError:
                raise
            except Exception:
                log.debug("match breaker probe failed; staying open",
                          exc_info=True)
                self._set_breaker_metric(1)
                continue
            self._close_breaker()
            return

    async def _probe_guarded(self) -> None:
        await self._fault_gate()
        await asyncio.to_thread(self._probe_dispatch)

    def _probe_dispatch(self) -> None:
        """One tiny dispatch through the warmed kernel shape — proves
        encode → device → readback end to end without touching the
        serving counters.  With the multichip backend active the probe
        rides the mesh, so a dead shard keeps the breaker open until
        the shard recovers."""
        from ..ops import encode_batch

        mc = self._mc_active()
        if mc is not None:
            enc = mc.encode(["probe/health"], batch=64)
            res = mc.dispatch(enc)
            mc.readback(res, 1)
            return
        enc = encode_batch(self.inc, ["probe/health"], batch=64)
        decode_packed(self.dev.serve(*enc), 1, self.dev.max_matches)

    def info(self) -> dict:
        return {
            "ready": self.ready,
            "filters": self.inc.n_filters,
            "states": self.inc.n_states,
            "rules": len(self._rule_refs),
            "device_epoch": self.dev.epoch,
            "router_epoch": self.router.epoch,
            "synced_epoch": self._synced_epoch,
            "uploads": self.dev.uploads,
            "delta_applies": self.dev.delta_applies,
            "deadline": self.deadline,
            "pipeline": self.pipeline,
            "pipeline_depth": self.pipeline_depth,
            "pipeline_inflight": self._inflight_n,
            "breaker": "open" if self._breaker_open else "closed",
            "breaker_failures": self._breaker_failures,
            "brownout": self._last_brownout,
            "est_dispatch_ms": round(self._est_dispatch_s * 1e3, 3),
            # the split components (satellite of ROADMAP dispatch-tax
            # (c)): what the partial-flush trigger actually subtracts
            # once warm, and whether it is warm
            "est_disp_ms": round(self._est_disp_s * 1e3, 3),
            "est_readback_ms": round(self._est_rb_s * 1e3, 3),
            "est_split_warm": (
                self._est_split_samples >= self.SPLIT_WARM),
            "pending": len(self._pending),
            # kernel backend routing (ISSUE 13)
            "backend": self.backend,
            "join_rebuilds": self.dev.join_rebuilds,
            "autotune": (self.tuner.info()
                         if self.tuner is not None else None),
            # multichip serve backend (ISSUE 15)
            "multichip": (self.mc.info() if self.mc is not None
                          else None),
            "segments": ({
                "dir": self.segments_dir,
                "loaded": self._segment_loaded,
                "table_gen": self._table_gen,
                "mutations": self._mut_count,
                "abandoned": self._compact_abandoned,
                "grow_applies": self.dev.grow_applies,
                "dirty_rows_uploaded": self.dev.dirty_rows_uploaded,
                "kernel_cache": (self.kcache.info()
                                 if self.kcache is not None else None),
            } if self.segments else None),
        }
