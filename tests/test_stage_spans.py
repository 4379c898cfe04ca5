"""The stage spans that close the books on a publish and on a batch
(ISSUE 26): one recording handle (observe/span.py), the batcher's cycle
tiled by its stages and tied together by ``seq``, the publish's life on
the connection's loop, the ``emqx.match.*`` profiler annotations, and
the bucket layout the benchmark's delta reader copies."""

import asyncio
import glob
import json
import os

import numpy as np
import pytest

from emqx_tpu import faultinject
from emqx_tpu.client import Client
from emqx_tpu.config import Config
from emqx_tpu.faultinject import FaultInjector
from emqx_tpu.node import BrokerNode
from emqx_tpu.observe.flightrec import STAGES, FlightRecorder, Ring
from emqx_tpu.observe.hist import (
    HIST_NAMES, N_BUCKETS, SUB_BITS, HistSet, LatencyHistogram,
    bucket_bounds,
)
from emqx_tpu.observe.metrics import Metrics
from emqx_tpu.observe.span import Span, stage_span

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYER_DIR = os.path.join(REPO, "cellbench", "layer_metrics")

# the stages of one serial-path cycle and how often each is recorded
CYCLE_STAGES = {
    "match_cycle": 1, "match_window": 1, "match_hop_out": 2,
    "match_hop_back": 2, "match_encode": 1, "match_dispatch": 1,
    "match_readback": 1, "match_epilogue": 1,
}
PUBLISH_STAGES = ("ingest_queue", "intercept", "handle_publish",
                  "match_resume")


# ---------------------------------------------------------------------------
# the handle
# ---------------------------------------------------------------------------

def test_span_feeds_histogram_and_ring_from_one_pair_of_stamps():
    hs, ring = HistSet("main"), Ring("match.serve", depth=64)
    sp = stage_span("match_cycle", hs, ring)
    sp.rec(1_000, 4_000, batch=8, gen=3, seq=17)
    assert hs.hist("obs.stage.match_cycle").count == 1
    assert ring.snapshot() == [
        (STAGES.index("match_cycle"), 1_000, 3_000, 8, 3, 17)]
    # either sink alone; neither: the site holds None
    stage_span("match_cycle", hs).rec(0, 10)
    assert hs.hist("obs.stage.match_cycle").count == 2 and ring.idx == 1
    stage_span("match_cycle", None, ring).rec(0, 10, seq=18)
    assert hs.hist("obs.stage.match_cycle").count == 2 and ring.idx == 2
    assert stage_span("match_cycle") is None
    with pytest.raises(ValueError):
        stage_span("not_a_stage", hs, ring)


def test_every_stage_has_its_histogram_and_the_old_ids_stand():
    assert {f"obs.stage.{s}" for s in STAGES} <= set(HIST_NAMES)
    # packed ids are positions: additions go to the end only
    assert STAGES[:8] == (
        "ingest_parse", "fanout_queue", "match_wait", "match_encode",
        "match_dispatch", "match_readback", "deliver", "flush")
    assert HIST_NAMES[:10] == [
        "obs.stage.ingest_parse", "obs.stage.fanout_queue",
        "obs.stage.match_wait", "obs.stage.match_encode",
        "obs.stage.match_dispatch", "obs.stage.match_readback",
        "obs.stage.deliver", "obs.stage.flush",
        "obs.e2e.publish_deliver", "obs.e2e.publish_deliver_leg"]


def test_flightrec_dump_holds_clock_and_seq(tmp_path):
    import time

    fr = FlightRecorder(str(tmp_path), depth=64)
    stage_span("match_window", None, fr.ring("match.serve")).rec(
        time.perf_counter_ns() - 500, time.perf_counter_ns(), 8, 1, seq=41)
    fr.ring("fanout").push(1, 500, 20, batch=4)     # no batch cycle: seq 0
    before = time.perf_counter_ns(), time.time_ns()
    with open(fr.dump("manual")) as f:
        payload = json.load(f)
    after = time.perf_counter_ns(), time.time_ns()
    clock = payload["clock"]
    assert before[0] <= clock["perf_counter_ns"] <= after[0]
    assert before[1] <= clock["time_ns"] <= after[1]
    by = {e["name"]: e for e in payload["traceEvents"] if e["ph"] == "X"}
    assert by["match_window"]["args"] == {"batch": 8, "gen": 1, "seq": 41}
    assert by["fanout_queue"]["args"]["seq"] == 0
    # the events' ts is the clock entry's perf_counter, in microseconds
    assert by["match_window"]["ts"] * 1e3 <= clock["perf_counter_ns"]


# ---------------------------------------------------------------------------
# a real node: one batch through the serial path, one PUBLISH through the
# default listener with the intercept on
# ---------------------------------------------------------------------------

class _Served:
    """A node with the device match on, one subscriber, one publisher."""

    def __init__(self, extra: str = "") -> None:
        self.cfg = Config(file_text=(
            'listeners.tcp.default.bind = "127.0.0.1:0"\n' + extra))
        self.cfg.put("tpu.enable", True)
        self.cfg.put("tpu.bypass_rate", 0.0)

    async def __aenter__(self):
        self.node = node = BrokerNode(self.cfg)
        await node.start()
        port = node.listeners.all()[0].port
        self.ms = ms = node.match_service
        self.sub = Client(clientid="s", port=port)
        self.pub = Client(clientid="p", port=port)
        await self.sub.connect()
        await self.sub.subscribe("room/+/temp", qos=1)
        await self.pub.connect()
        for _ in range(600):
            if ms.ready and ms._seen_epoch == node.broker.router.epoch \
                    and ms.dev.epoch == ms.inc.epoch:
                break
            await asyncio.sleep(0.05)
        assert ms.ready
        # compile the one bucket these tests use, outside what they count
        await self.publish("room/warm/temp")
        return self

    async def __aexit__(self, *_exc):
        await self.sub.close()
        await self.pub.close()
        await self.node.stop()

    async def publish(self, topic: str) -> None:
        await self.pub.publish(topic, b"x", qos=1)
        assert (await self.sub.recv(10.0)).topic == topic

    def counts(self):
        hs = self.node.hists
        return {} if hs is None else {
            n.split(".")[-1]: hs.hist(n).count for n in hs.names()}

    def ring_events(self):
        return [(plane, STAGES[e[0]], *e[1:])
                for plane, r in self.node.flightrec._rings.items()
                for e in r.snapshot()]


def _delta(after, before):
    return {k: v - before[k] for k, v in after.items() if v != before[k]}


def test_one_batch_closes_its_books_and_one_publish_its_life():
    async def main():
        async with _Served() as s:
            m = s.node.observed.metrics
            c0, k0 = s.counts(), m.all()
            seq0 = s.ms._seq
            await s.publish("room/1/temp")
            d = _delta(s.counts(), c0)
            k1 = m.all()
            # the batch: every stage of the cycle, as often as the table
            # in observe/hist.py says
            for stage, n in CYCLE_STAGES.items():
                assert d.get(stage) == n, (stage, d)
            assert d.get("match_wait") == 1
            # the publish: once each (PUBLISH only: the subscriber's
            # PUBACK goes through a queue too and records nothing)
            for stage in PUBLISH_STAGES:
                assert d.get(stage) == 1, (stage, d)
            # one popped batch, one seq, on every plane's ring events
            assert s.ms._seq == seq0 + 1
            mine = [e for e in s.ring_events() if e[-1] == s.ms._seq]
            got = {}
            for _plane, stage, *_rest in mine:
                got[stage] = got.get(stage, 0) + 1
            assert got == {**CYCLE_STAGES, "match_wait": 1}, got
            assert {e[0] for e in mine} == {
                "match.serve", "match.encode", "match.readback"}
            by = {stage: (start, dur) for _p, stage, start, dur, *_r
                  in mine if CYCLE_STAGES.get(stage) == 1}
            cyc0, cyc_ns = by["match_cycle"]
            # the stages lie inside the cycle, in order, window first
            assert by["match_window"][0] == cyc0
            order = ["match_window", "match_encode", "match_dispatch",
                     "match_readback", "match_epilogue"]
            starts = [by[st][0] for st in order]
            assert starts == sorted(starts)
            ep0, ep_ns = by["match_epilogue"]
            assert ep0 + ep_ns == cyc0 + cyc_ns
            # the books: what lay inside a span never exceeds the cycle
            cyc = k1["tpu.match.cycle_ns"] - k0["tpu.match.cycle_ns"]
            spanned = (k1["tpu.match.cycle_spanned_ns"]
                       - k0["tpu.match.cycle_spanned_ns"])
            assert cyc == cyc_ns
            assert 0 < spanned <= cyc
            assert spanned == sum(dur for _p, stage, _s, dur, *_r in mine
                                  if stage in CYCLE_STAGES
                                  and stage != "match_cycle")
            # intercept holds wait + the rest of the cycle + resume
            h = s.node.hists.hist
            assert h("obs.stage.intercept").percentile_ns(50) >= 0.9 * (
                cyc_ns - by["match_window"][1])

    asyncio.run(main())


def _raise(exc):
    def boom(*_a, **_k):
        raise exc
    return boom


@pytest.mark.parametrize("how", ["stale_race", "compile_miss", "injected"])
def test_a_failing_batch_still_closes_its_cycle(how, monkeypatch):
    from emqx_tpu.broker import match_service as MS

    async def main():
        async with _Served() as s:
            m = s.node.observed.metrics
            if how == "stale_race":
                # after the readback: hops and epilogue are on the books
                monkeypatch.setattr(
                    s.ms, "_collect_rows",
                    _raise(MS._StaleRace("aid reused mid-flight")))
            elif how == "compile_miss":
                monkeypatch.setattr(
                    s.ms, "_encode_dispatch",
                    _raise(MS.CompileMiss("bucket not compiled")))
            else:
                faultinject.install(FaultInjector([
                    {"point": "match.dispatch", "action": "raise",
                     "times": 0}], seed=1))
            c0, k0 = s.counts(), m.all()
            try:
                await s.publish("room/2/temp")      # the host trie serves
            finally:
                faultinject.uninstall()
            d, k1 = _delta(s.counts(), c0), m.all()
            assert d.get("match_cycle") == 1 and d.get("match_window") == 1
            assert "match_resume" not in d          # empty-handed waiter
            assert d.get("intercept") == d.get("handle_publish") == 1
            hops = 2 if how == "stale_race" else None
            assert d.get("match_hop_out") == d.get("match_hop_back") == hops
            assert d.get("match_epilogue") == (1 if hops else None)
            cyc = k1["tpu.match.cycle_ns"] - k0["tpu.match.cycle_ns"]
            spanned = (k1["tpu.match.cycle_spanned_ns"]
                       - k0["tpu.match.cycle_spanned_ns"])
            assert 0 < spanned <= cyc
            assert k1["broker.match.cpu_fallback"] \
                - k0["broker.match.cpu_fallback"] == 1

    asyncio.run(main())


def test_deadline_loop_closes_the_same_books():
    async def main():
        async with _Served("match.deadline.enable = true\n") as s:
            assert s.ms.deadline
            c0 = s.counts()
            await s.publish("room/3/temp")
            d = _delta(s.counts(), c0)
            for stage, n in CYCLE_STAGES.items():
                assert d.get(stage) == n, (stage, d)
            assert d.get("match_resume") == 1

    asyncio.run(main())


def test_new_sites_are_zero_call_when_histograms_are_off(monkeypatch):
    """``obs.hist.enable = false``: every per-publish site holds None
    (one identity test, no call); the per-batch sites feed the always-on
    ring alone and never a histogram."""
    hist_calls, span_calls = [], []
    monkeypatch.setattr(LatencyHistogram, "record",
                        lambda self, ns: hist_calls.append(ns))
    real_rec = Span.rec

    def spy(self, *a, **k):
        span_calls.append(self)
        return real_rec(self, *a, **k)

    monkeypatch.setattr(Span, "rec", spy)

    async def main():
        async with _Served("obs.hist.enable = false\n") as s:
            assert s.node.hists is None and s.node._conn_hists is None
            for conn in s.node._all_conns:
                assert conn._h_parse is conn._h_queue is None
                assert conn._h_intercept is conn._h_handle is None
            ms = s.ms
            assert ms._h_wait is ms._h_resume is None
            assert ms._sp_cycle.hist is None and ms._sp_cycle.ring is not None
            span_calls.clear()
            await s.publish("room/4/temp")
            assert hist_calls == []
            # ring-only handles of the one batch, and nothing per publish
            assert span_calls and all(
                sp.hist is None and sp.ring is not None for sp in span_calls)
            assert len(span_calls) == sum(CYCLE_STAGES.values())
            # (the loop clock's plane holds the node's long busy runs:
            # always on, like every ring, and no batch's)
            stages = {STAGES[e[0]]
                      for plane, r in s.node.flightrec._rings.items()
                      if plane != "loop" for e in r.snapshot()}
            assert stages == set(CYCLE_STAGES)
            assert {STAGES[e[0]] for e in s.node.flightrec.ring(
                "loop").snapshot()} <= {"loop_run"}

    asyncio.run(main())


def test_profiler_trace_holds_the_match_annotations(tmp_path):
    """The clocks are joined by data: inside a ``jax.profiler`` session
    the four synchronous stages appear as ``emqx.match.<stage>`` events
    carrying the batch's ``seq``, its size and the span's own
    ``perf_counter_ns`` start (outside a session the same sites ran in
    every other test of this file)."""
    import jax
    from jax.profiler import ProfileData

    async def main():
        async with _Served() as s:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
            try:
                await s.publish("room/5/temp")
            finally:
                jax.profiler.stop_trace()
            return s.ms._seq, [e for e in s.ring_events()
                               if e[-1] == s.ms._seq]

    seq, ring = asyncio.run(main())
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    events = [(ev.name, ev.start_ns, ev.duration_ns, dict(ev.stats))
              for plane in ProfileData.from_file(path).planes
              for line in plane.lines for ev in line.events
              if ev.name.startswith("emqx.match.")]
    mine = [e for e in events if e[3]["seq"] == seq]
    assert sorted(e[0] for e in mine) == [
        "emqx.match.dispatch", "emqx.match.encode", "emqx.match.epilogue",
        "emqx.match.epilogue", "emqx.match.readback"]
    assert all(e[3]["n"] == 1 for e in mine)
    # every event is an anchor: its t_ns is the ring event's own start,
    # and trace start - t_ns is one offset for the whole slice
    starts = {stage: start for _p, stage, start, *_r in ring}
    offsets = []
    for name, start, _dur, stats in mine:
        stage = "match_" + name.rsplit(".", 1)[1]
        if name != "emqx.match.epilogue":
            assert stats["t_ns"] == starts[stage]
        offsets.append(start - stats["t_ns"])
    assert max(offsets) - min(offsets) < 5e6, offsets    # same clock rate


# ---------------------------------------------------------------------------
# the bucket layout, exported, against the benchmark's copy of it
# ---------------------------------------------------------------------------

def test_cellbench_bucket_bounds_are_the_exported_layout():
    from cellbench import reduce as R

    assert (SUB_BITS, N_BUCKETS) == (4, 688)
    assert R._SUB_BITS == SUB_BITS
    assert len(LatencyHistogram().counts) == N_BUCKETS
    for idx in range(N_BUCKETS):
        assert tuple(R.bucket_bounds(idx)) == tuple(bucket_bounds(idx)), idx


def test_record_lands_where_bucket_of_says():
    """``record`` inlines ``_bucket_of`` (the hot path): the two, and the
    exported bounds, stay one layout."""
    from emqx_tpu.observe.hist import _bucket_of

    rng = np.random.default_rng(11)
    vals = [-5, 0, 1, 15, 16, 17, 31, 32, 2**45 - 1, 2**45, 2**46, 2**60]
    vals += [int(v) for v in rng.lognormal(13, 3, size=5000)]
    for v in vals:
        h = LatencyHistogram()
        h.record(v)
        idx = _bucket_of(v)
        assert h.counts[idx] == 1 and h.count == 1, v
        lower, width = bucket_bounds(idx)
        if 0 <= v < 2**45:
            assert lower <= v < lower + width, (v, idx)


@pytest.mark.parametrize("stat", ["p50", "p95", "p100"])
@pytest.mark.parametrize("seed", [5, 6, 7])
def test_cellbench_delta_percentile_is_the_histograms_own(stat, seed):
    from cellbench import reduce as R

    rng = np.random.default_rng(seed)
    h = LatencyHistogram()
    for v in rng.lognormal(np.log(2e6), 1.1, size=4000):
        h.record(int(v))
    zeros = [0] * N_BUCKETS
    want = h.percentile_ns(float(stat[1:])) / 1e6
    assert R.hist_delta_stat(zeros, h.snapshot(), stat) == pytest.approx(
        want, rel=1e-12)
    # a delta: what was there before the window does not count
    before = h.snapshot()
    for v in rng.lognormal(np.log(5e5), 0.4, size=1000):
        h.record(int(v))
    only = LatencyHistogram()
    only.counts = [a - b for a, b in zip(h.snapshot(), before)]
    assert R.hist_delta_stat(before, h.snapshot(), stat) == pytest.approx(
        only.percentile_ns(float(stat[1:])) / 1e6, rel=1e-12)


@pytest.mark.parametrize("fname", sorted(os.listdir(LAYER_DIR)))
def test_layer_metric_file_reads_a_registered_name(fname):
    """A misspelt histogram or counter in a layer-metric file costs a
    run on the chip (a listed metric that reads nothing ends a traced
    run with no result): hold every file to the registries here."""
    with open(os.path.join(LAYER_DIR, fname)) as f:
        spec = json.load(f)
    if spec["kind"] == "hist_delta":
        assert spec["hist"] in HIST_NAMES
        # p100: the largest sample, within its bucket
        assert spec["stat"] in ("p50", "p95", "p99", "p100")
    elif spec["kind"] == "counter_ratio":
        known = set(Metrics().all())
        names = list(spec["num"])
        if spec["den"] != "window_publishes":
            names += spec["den"]
        assert names and set(names) <= known, set(names) - known
