"""Metrics/stats/alarms/$SYS — emqx_metrics/emqx_stats/emqx_alarm/emqx_sys
parity surface (SURVEY.md §5.5)."""

import pytest

from emqx_tpu.broker.broker import Broker
from emqx_tpu.broker.message import make_message
from emqx_tpu.broker.session import SubOpts
from emqx_tpu.observe import Alarms, Metrics, Stats, SysBroker
from emqx_tpu.observe.metrics import METRIC_NAMES
from emqx_tpu.observe.wiring import observe


def test_metrics_fixed_names_and_inc():
    m = Metrics()
    assert "messages.received" in METRIC_NAMES
    m.inc("messages.received")
    m.inc("messages.received", 5)
    assert m.get("messages.received") == 6
    with pytest.raises(KeyError):
        m.inc("not.a.metric")


def test_metrics_packet_and_qos_families():
    m = Metrics()
    m.inc_recv_packet("connect", nbytes=12)
    m.inc_sent_packet("connack", nbytes=4)
    m.inc_msg_received(2)
    m.inc_msg_dropped("queue_full")
    assert m.get("packets.connect.received") == 1
    assert m.get("packets.connack.sent") == 1
    assert m.get("bytes.received") == 12 and m.get("bytes.sent") == 4
    assert m.get("messages.qos2.received") == 1
    assert m.get("messages.dropped") == 1
    assert m.get("messages.dropped.queue_full") == 1


def test_stats_watermarks():
    s = Stats()
    s.setstat("connections.count", 5)
    s.setstat("connections.count", 3)
    assert s.get("connections.count") == 3
    assert s.get("connections.max") == 5


def test_stats_pull_provider():
    s = Stats()
    n = {"v": 7}
    s.provide("topics.count", lambda: n["v"])
    assert s.get("topics.count") == 7
    n["v"] = 9
    assert s.all()["topics.count"] == 9


def test_alarms_lifecycle_and_events():
    events = []
    a = Alarms(history_size=2)
    a.on_change = lambda kind, alarm: events.append((kind, alarm.name))
    assert a.activate("high_cpu", {"usage": 0.93})
    assert not a.activate("high_cpu")  # idempotent
    assert a.is_active("high_cpu")
    assert a.deactivate("high_cpu")
    assert not a.deactivate("high_cpu")
    assert events == [("activate", "high_cpu"), ("deactivate", "high_cpu")]
    for i in range(4):
        a.activate(f"x{i}")
        a.deactivate(f"x{i}")
    assert len(a.history) == 2  # bounded


def test_sys_broker_tick_publishes_under_prefix():
    out = []
    sys = SysBroker("node1", lambda t, p: out.append((t, p)), interval=60)
    sys.attach(stats=lambda: {"connections.count": 2}, metrics=lambda: {"messages.received": 3})
    assert sys.tick(now=sys.start_time + 61)
    topics = [t for t, _ in out]
    assert "$SYS/brokers/node1/uptime" in topics
    assert "$SYS/brokers/node1/stats/connections.count" in topics
    assert "$SYS/brokers/node1/metrics/messages.received" in topics
    out.clear()
    assert not sys.tick(now=sys.start_time + 90)  # within interval


def test_observe_wires_broker_hooks():
    b = Broker()
    obs = observe(b)
    b.open_session("sub1")
    b.subscribe("sub1", "t/+")
    res = b.publish(make_message("pub", "t/1", b"x", qos=1))
    assert res.matched == 1
    m = obs.metrics
    assert m.get("messages.received") == 1
    assert m.get("messages.qos1.received") == 1
    assert m.get("messages.delivered") == 1
    assert m.get("session.created") == 1
    assert obs.stats.get("topics.count") == 1
    assert obs.stats.get("sessions.count") == 1
    assert obs.stats.get("subscriptions.count") == 1
    # no-subscriber drop accounted
    b.publish(make_message("pub", "none/here", b"x"))
    assert m.get("messages.dropped.no_subscribers") == 1


def test_sys_messages_do_not_count_as_received():
    b = Broker()
    obs = observe(b, sys_interval=0)
    b.open_session("s")
    b.subscribe("s", "$SYS/brokers/#", SubOpts())
    obs.sys.tick()
    assert obs.metrics.get("messages.received") == 0
    # but the subscriber saw the $SYS publishes
    sess = b.sessions["s"]
    assert sess is not None


def test_connections_count_tracks_live_channels():
    import asyncio

    """connections.count / live_connections.count come from the CM —
    regression: they were never wired and stayed 0 (found driving the
    dashboard against a live node)."""
    async def main():
        from emqx_tpu.client import Client
        from emqx_tpu.config import Config
        from emqx_tpu.node import BrokerNode

        node = BrokerNode(Config(
            file_text='listeners.tcp.default.bind = "127.0.0.1:0"\n'))
        await node.start()
        try:
            port = node.listeners.all()[0].port
            cs = []
            for i in range(3):
                c = Client(clientid=f"cc{i}", port=port)
                await c.connect()
                cs.append(c)
            stats = node.observed.stats.all()
            assert stats["connections.count"] == 3
            assert stats["live_connections.count"] == 3
            assert stats["connections.max"] >= 3
            await cs[0].disconnect()
            await asyncio.sleep(0.05)
            assert node.observed.stats.all()["connections.count"] == 2
            for c in cs[1:]:
                await c.disconnect()
        finally:
            await node.stop()

    asyncio.run(main())


def test_topic_metrics_counts_and_rest():
    """emqx_topic_metrics analog: exact-topic counters over the publish
    path + REST lifecycle."""
    import asyncio

    async def main():
        import json as _json

        from emqx_tpu.bridge import httpc
        from emqx_tpu.client import Client
        from emqx_tpu.config import Config
        from emqx_tpu.node import BrokerNode

        node = BrokerNode(Config(file_text=(
            'listeners.tcp.default.bind = "127.0.0.1:0"\n'
            'dashboard.enable = true\ndashboard.listen = "127.0.0.1:0"\n'
            'api_key.enable = true\napi_key.key = "k"\n'
            'api_key.secret = "s"\n')))
        await node.start()
        try:
            base = f"http://127.0.0.1:{node.mgmt_server.port}/api/v5"
            r = await httpc.request("POST", f"{base}/login", body=_json.dumps(
                {"username": "admin", "password": "public"}).encode())
            tok = _json.loads(r.body)["token"]
            hdr = {"authorization": f"Bearer {tok}"}

            r = await httpc.request("POST", f"{base}/mqtt/topic_metrics",
                                    headers=hdr,
                                    body=b'{"topic": "m/1"}')
            assert r.status == 201
            # wildcards rejected; duplicates 409
            r = await httpc.request("POST", f"{base}/mqtt/topic_metrics",
                                    headers=hdr,
                                    body=b'{"topic": "m/+"}')
            assert r.status == 400
            r = await httpc.request("POST", f"{base}/mqtt/topic_metrics",
                                    headers=hdr,
                                    body=b'{"topic": "m/1"}')
            assert r.status == 409

            port = node.listeners.all()[0].port
            sub = Client(clientid="tm-s", port=port)
            await sub.connect()
            await sub.subscribe("m/1")
            pub = Client(clientid="tm-p", port=port)
            await pub.connect()
            for i in range(3):
                await pub.publish("m/1", b"x", qos=1)
            await pub.publish("m/other", b"x")  # unregistered: no count
            await asyncio.wait_for(sub.messages.get(), 5)

            r = await httpc.request("GET", f"{base}/mqtt/topic_metrics",
                                    headers=hdr)
            data = _json.loads(r.body)["data"]
            assert len(data) == 1
            rec = data[0]
            assert rec["topic"] == "m/1"
            assert rec["messages.in"] == 3
            assert rec["messages.qos1.in"] == 3
            assert rec["messages.out"] >= 1

            # reset zeroes counters and rate
            r = await httpc.request(
                "PUT", f"{base}/mqtt/topic_metrics/m/1/reset",
                headers=hdr)
            assert r.status == 204
            r = await httpc.request("GET", f"{base}/mqtt/topic_metrics",
                                    headers=hdr)
            rec = _json.loads(r.body)["data"][0]
            assert rec["messages.in"] == 0 and rec["rate.in"] == 0.0
            # invalid names: embedded wildcard chars and non-strings
            r = await httpc.request("POST", f"{base}/mqtt/topic_metrics",
                                    headers=hdr,
                                    body=b'{"topic": "a/x+y"}')
            assert r.status == 400
            r = await httpc.request("POST", f"{base}/mqtt/topic_metrics",
                                    headers=hdr, body=b'{"topic": 123}')
            assert r.status == 400
            r = await httpc.request(
                "DELETE", f"{base}/mqtt/topic_metrics/m/1", headers=hdr)
            assert r.status == 204
            await sub.disconnect()
            await pub.disconnect()
        finally:
            await node.stop()

    asyncio.run(main())


# ---------------------------------------------------------------------------
# stage-level latency observatory (ISSUE 12): histograms + flight recorder
# ---------------------------------------------------------------------------

def test_hist_percentiles_track_np_percentile():
    import numpy as np

    from emqx_tpu.observe.hist import LatencyHistogram

    rng = np.random.default_rng(3)
    # lognormal ns around ~5 ms — the shape real stage latencies have
    vals = rng.lognormal(mean=np.log(5e6), sigma=0.9, size=30000)
    h = LatencyHistogram()
    for v in vals:
        h.record(int(v))
    for q in (50, 95, 99):
        hp = h.percentile_ns(q)
        npp = float(np.percentile(vals, q))
        # the bench parity gate's tolerance: 1/16-octave sub-buckets
        assert abs(hp - npp) <= 0.12 * npp, (q, hp, npp)
    assert h.count == len(vals)
    assert h.to_dict()["p50_ms"] > 0


def test_hist_record_many_matches_scalar_records():
    import numpy as np

    from emqx_tpu.observe.hist import LatencyHistogram

    rng = np.random.default_rng(4)
    secs = rng.lognormal(mean=np.log(3e-3), sigma=1.2, size=5000)
    a, b = LatencyHistogram(), LatencyHistogram()
    for s in secs:
        a.record(int(s * 1e9))
    b.record_many_s(secs)
    assert a.counts == b.counts


def test_hist_merge_sums_planes_and_registry_is_fixed():
    import pytest as _pytest

    from emqx_tpu.observe.hist import (
        HIST_NAMES, HistSet, LatencyHistogram,
    )

    main, shard = HistSet("main"), HistSet("shard0")
    main.hist("obs.stage.deliver").record(1_000_000)
    shard.hist("obs.stage.deliver").record(2_000_000)
    shard.hist("obs.stage.ingest_parse").record(5_000)
    merged = HistSet.merge_all([main, shard])
    assert merged["obs.stage.deliver"].count == 2
    assert merged["obs.stage.ingest_parse"].count == 1
    pct = HistSet.percentiles([main, shard])
    assert set(pct) == set(HIST_NAMES)
    assert pct["obs.stage.deliver"]["count"] == 2
    # the fixed-table discipline: a typo'd name raises at the lookup
    with _pytest.raises(KeyError):
        main.hist("obs.stage.not_a_stage")
    # single-writer merge is a read-time sum, sources keep counting
    main.hist("obs.stage.deliver").record(1_000_000)
    assert merged["obs.stage.deliver"].count == 2  # snapshot, not live
    assert LatencyHistogram.merged(
        [main.hist("obs.stage.deliver")]).count == 2


def test_hist_recording_sites_zero_call_when_disabled(monkeypatch):
    """The overhead-gate spy (the faultinject idiom): with hists=None
    every recording site is an attribute check, never a call."""
    import asyncio as aio

    from emqx_tpu.broker import Broker, FanoutPipeline, SubOpts, \
        make_message
    from emqx_tpu.observe.hist import LatencyHistogram

    calls = []
    monkeypatch.setattr(
        LatencyHistogram, "record",
        lambda self, ns: calls.append(ns))
    monkeypatch.setattr(
        LatencyHistogram, "record_s",
        lambda self, s: calls.append(s))

    async def main():
        b = Broker()
        got = []
        b.on_deliver = lambda cid, pubs: got.extend(pubs)
        b.open_session("s")
        b.subscribe("s", "t/#", SubOpts())
        p = FanoutPipeline(b, window_s=0.0)   # hists defaults to None
        await p.start()
        for i in range(20):
            assert p.offer(make_message("pub", f"t/{i}", b"x"))
        deadline = aio.get_event_loop().time() + 2.0
        while (p._q or p._busy) and \
                aio.get_event_loop().time() < deadline:
            await aio.sleep(0.002)
        await p.stop()
        assert len(got) == 20
        assert calls == []          # not one record() anywhere

    aio.run(main())


def test_hist_recording_sites_record_when_enabled():
    import asyncio as aio

    from emqx_tpu.broker import Broker, FanoutPipeline, SubOpts, \
        make_message
    from emqx_tpu.observe.hist import HistSet

    async def main():
        b = Broker()
        b.on_deliver = lambda cid, pubs: None
        b.open_session("s")
        b.subscribe("s", "t/#", SubOpts())
        hs = HistSet("main")
        p = FanoutPipeline(b, window_s=0.0, hists=hs)
        await p.start()
        for i in range(20):
            assert p.offer(make_message("pub", f"t/{i}", b"x"))
        deadline = aio.get_event_loop().time() + 2.0
        while (p._q or p._busy) and \
                aio.get_event_loop().time() < deadline:
            await aio.sleep(0.002)
        await p.stop()
        assert hs.hist("obs.stage.fanout_queue").count >= 1
        assert hs.hist("obs.stage.deliver").count >= 1
        assert hs.hist("obs.stage.flush").count >= 1
        assert hs.hist("obs.e2e.publish_deliver").count >= 1

    aio.run(main())


def test_sync_publish_path_records_spans_on_fanout_bypass():
    """ISSUE 13 observability follow-on (b): traffic the fanout gate
    BYPASSES to the per-message ``Broker.publish`` path must still land
    deliver/flush/e2e spans — bypass rates climbing no longer hollow
    out the histograms."""
    import asyncio as aio

    from emqx_tpu.broker import Broker, FanoutPipeline, SubOpts, \
        make_message
    from emqx_tpu.observe.hist import HistSet

    async def main():
        b = Broker()
        got = []
        b.on_deliver = lambda cid, pubs: got.extend(pubs)
        b.open_session("s")
        b.subscribe("s", "t/#", SubOpts())
        hs = HistSet("main")
        b.attach_hists(hs)
        # huge bypass threshold: the low-rate gate refuses every offer,
        # exactly the path a quiet publisher rides in production
        p = FanoutPipeline(b, window_s=0.0, hists=hs, bypass_rate=1e9)
        await p.start()
        b.fanout = p
        for i in range(10):
            m = make_message("pub", f"t/{i}", b"x")
            if not p.offer(m):        # the caller contract: bypass →
                b.publish(m)          # per-message sync path
        await p.stop()
        assert len(got) == 10
        assert b.metrics is None     # bypass metric needs observe();
        assert hs.hist("obs.stage.deliver").count >= 10
        assert hs.hist("obs.stage.flush").count >= 10
        assert hs.hist("obs.e2e.publish_deliver").count >= 10

    aio.run(main())


def test_sync_publish_spans_zero_call_when_unattached(monkeypatch):
    """Without attach_hists the sync path stays an attribute check —
    the zero-cost-when-off discipline every recording site follows."""
    from emqx_tpu.observe.hist import LatencyHistogram

    calls = []
    monkeypatch.setattr(LatencyHistogram, "record",
                        lambda self, ns: calls.append(ns))
    monkeypatch.setattr(LatencyHistogram, "record_s",
                        lambda self, s: calls.append(s))
    b = Broker()
    b.open_session("s")
    b.subscribe("s", "t/#", SubOpts())
    res = b.publish(make_message("pub", "t/1", b"x"))
    assert res.matched == 1
    assert calls == []


def test_flightrec_ring_wraps_and_snapshots_in_order():
    from emqx_tpu.observe.flightrec import Ring

    r = Ring("main", depth=64)
    for i in range(100):
        r.push(1, i, 10, batch=i)
    snap = r.snapshot()
    assert len(snap) == 64
    starts = [e[1] for e in snap]
    assert starts == list(range(36, 100))   # oldest→newest, wrapped
    # depth rounds up to a power of two
    assert len(Ring("x", depth=100).buf) == 128


def test_flightrec_dump_writes_valid_perfetto_trace(tmp_path):
    import json as _json

    from emqx_tpu.observe.flightrec import (
        DUMP_REASONS, FlightRecorder,
    )
    from emqx_tpu.observe.metrics import Metrics

    m = Metrics()
    fr = FlightRecorder(str(tmp_path), depth=128, metrics=m)
    ring = fr.ring("match.encode")
    for i in range(10):
        ring.push(3, 1000 + i * 100, 50, batch=8, gen=i)
    fr.ring("fanout").push(1, 500, 20, batch=4)
    path = fr.dump("manual", note="test")
    assert path is not None and path.endswith(".json")
    with open(path) as f:
        payload = _json.load(f)
    assert payload["reason"] == "manual"
    evs = payload["traceEvents"]
    slices = [e for e in evs if e["ph"] == "X"]
    metas = [e for e in evs if e["ph"] == "M"]
    assert len(slices) == 11
    assert len(metas) == 2           # one thread_name per plane
    # events ordered by ts (the chaos-test contract)
    ts = [e["ts"] for e in slices]
    assert ts == sorted(ts)
    assert slices[0]["name"] == "fanout_queue"
    assert {e["args"]["name"] for e in metas} == {
        "match.encode", "fanout"}
    assert m.get("obs.flightrec.dumps") == 1
    assert fr.dumps == 1 and fr.last_reason == "manual"
    # reasons are a fixed vocabulary
    assert "breaker_trip" in DUMP_REASONS
    with pytest.raises(ValueError):
        fr.dump("no_such_reason")


def test_flightrec_dump_failure_leaves_no_torn_file(tmp_path, monkeypatch):
    import json as _json
    import os as _os

    from emqx_tpu.observe.flightrec import FlightRecorder

    fr = FlightRecorder(str(tmp_path), depth=64)
    fr.ring("main").push(0, 1, 2)

    def boom(*a, **kw):
        raise OSError("disk died mid-write")

    monkeypatch.setattr(_json, "dump", boom)
    assert fr.dump("manual") is None          # contained, not raised
    assert fr.dumps == 0
    # no torn JSON, no leftover temp file
    assert [p for p in _os.listdir(tmp_path)] == []
    monkeypatch.undo()
    # and the recorder still works afterwards
    assert fr.dump("manual") is not None


def test_slow_subs_e2e_histogram_one_clock_read(monkeypatch):
    import time as _time

    from emqx_tpu.observe.slow_subs import SlowSubs

    ss = SlowSubs(threshold_ms=100.0, window_s=10.0)
    reads = [0]
    real = _time.time

    def counting_time():
        reads[0] += 1
        return real()

    class Msg:
        retain = False
        topic = "a/b"

        def __init__(self, age_s):
            self.timestamp = real() - age_s

    monkeypatch.setattr(
        "emqx_tpu.observe.slow_subs.time.time", counting_time)
    reads[0] = 0
    ss._on_delivered("c1", Msg(0.5))      # past threshold: ranked
    assert reads[0] == 1                  # ONE wall-clock read
    reads[0] = 0
    ss._on_delivered("c1", Msg(0.01))     # fast: histogram only
    assert reads[0] == 1
    monkeypatch.undo()
    assert len(ss.ranking()) == 1         # only the slow one ranked
    e2e = ss.e2e()
    assert e2e["count"] == 2              # but BOTH deliveries measured
    assert e2e["p50_ms"] > 0
    ss.clear()
    assert ss.e2e()["count"] == 0


def test_sys_broker_publishes_hist_payloads():
    import json as _json

    got = []
    sysb = SysBroker("n1", lambda t, p: got.append((t, p)), interval=0)
    sysb.attach_hists(lambda: {
        "obs.stage.deliver": {"count": 3, "p50_ms": 1.5, "p95_ms": 2.0,
                              "p99_ms": 2.5, "max_ms": 3.0},
        "obs.stage.flush": {"count": 0},     # empty: skipped
    })
    assert sysb.tick(now=1e9)
    hist_topics = {t: p for t, p in got if "/hist/" in t}
    assert list(hist_topics) == ["$SYS/brokers/n1/hist/obs.stage.deliver"]
    body = _json.loads(next(iter(hist_topics.values())))
    assert body["p99_ms"] == 2.5


def test_statsd_hist_timing_lines_and_line_boundary_chunking():
    from emqx_tpu.observe.statsd import StatsdPusher

    class FakeMetrics:
        def __init__(self, n):
            self._d = {f"fake.counter.{i:04d}": i for i in range(n)}

        def all(self):
            return dict(self._d)

    class FakeStats(FakeMetrics):
        pass

    class Observed:
        metrics = FakeMetrics(400)      # ~10 KB of counter lines
        stats = FakeStats(50)

    pusher = StatsdPusher(
        Observed(), server="127.0.0.1:1",
        hist_source=lambda: {
            "obs.stage.deliver": {"count": 7, "p50_ms": 1.25,
                                  "p95_ms": 2.5, "p99_ms": 4.75,
                                  "max_ms": 9.0},
            "obs.stage.flush": {"count": 0},
        })
    payload = pusher.render()
    text = payload.decode()
    assert "emqx.obs.stage.deliver.p99:4.75|ms" in text
    assert "emqx.obs.stage.deliver.p50:1.25|ms" in text
    assert "emqx.obs.stage.deliver.count:7|g" in text
    assert "obs.stage.flush" not in text     # empty hists are skipped
    assert len(payload) > 8000               # forces the chunk path

    sent = []

    class FakeSock:
        def sendto(self, data, addr):
            sent.append(bytes(data))

        def close(self):
            pass

    pusher._sock = FakeSock()
    pusher.push()
    assert len(sent) >= 2                    # multi-datagram flush
    for chunk in sent:
        assert len(chunk) <= 8000
        for line in chunk.decode().splitlines():
            # every line in every datagram is whole: name:value|type
            name, rest = line.split(":", 1)
            assert name and rest.rsplit("|", 1)[1] in ("c", "g", "ms")
    # recombining the datagrams yields exactly the rendered payload
    assert b"\n".join(sent) == payload
    assert pusher.pushes == 1


def test_obs_hist_disable_wires_none_everywhere():
    """obs.hist.enable = false: every plane's histogram handle is None,
    so (with the spy test above proving None ⇒ no call) the whole
    recording surface is zero-call."""
    import asyncio as aio

    from emqx_tpu.config import Config
    from emqx_tpu.node import BrokerNode

    async def main():
        cfg = Config(file_text=(
            'listeners.tcp.default.bind = "127.0.0.1:0"\n'
            "obs.hist.enable = false\n"))
        cfg.put("tpu.enable", True)
        cfg.put("broker.fanout.enable", True)
        node = BrokerNode(cfg)
        await node.start()
        try:
            assert node.hists is None
            assert node.hist_sets() == []
            assert node.hist_percentiles() == {}
            fp = node.fanout_pipeline
            assert fp._sp_queue is None and fp._h_e2e is None
            ms = node.match_service
            if ms is not None:   # device may be absent on CI
                assert ms._h_wait is None and ms._h_resume is None
            # the flight recorder stays ALWAYS on regardless
            assert node.flightrec is not None
            assert node.supervisor.flightrec is node.flightrec
        finally:
            await node.stop()

    aio.run(main())


def test_obs_hist_enabled_by_default_and_wired():
    import asyncio as aio

    from emqx_tpu.config import Config
    from emqx_tpu.node import BrokerNode

    async def main():
        cfg = Config(
            file_text='listeners.tcp.default.bind = "127.0.0.1:0"\n')
        cfg.put("broker.fanout.enable", True)
        node = BrokerNode(cfg)
        await node.start()
        try:
            assert node.hists is not None
            assert node.fanout_pipeline._sp_queue is not None
            pct = node.hist_percentiles()
            from emqx_tpu.observe.hist import HIST_NAMES
            assert set(pct) == set(HIST_NAMES)
        finally:
            await node.stop()

    aio.run(main())


def test_per_leg_e2e_hist_sampled_when_enabled():
    """``obs.hist.e2e_per_leg_sample = N`` records every Nth delivery
    leg into the per-leg e2e histogram — the per-subscriber skew
    signal the batch-level e2e span can't see."""
    import asyncio as aio

    from emqx_tpu.broker import Broker, FanoutPipeline, SubOpts, \
        make_message
    from emqx_tpu.observe.hist import HistSet

    async def main():
        b = Broker()
        b.on_deliver = lambda cid, pubs: None
        for i in range(4):
            b.open_session(f"s{i}")
            b.subscribe(f"s{i}", "t/#", SubOpts())
        hs = HistSet("main")
        p = FanoutPipeline(b, window_s=0.0, hists=hs,
                           e2e_per_leg_sample=2)
        await p.start()
        for i in range(10):
            assert p.offer(make_message("pub", f"t/{i}", b"x"))
        deadline = aio.get_event_loop().time() + 2.0
        while (p._q or p._busy) and \
                aio.get_event_loop().time() < deadline:
            await aio.sleep(0.002)
        await p.stop()
        # 10 msgs × 4 subscribers = 40 legs, sampled every 2nd
        leg = hs.hist("obs.e2e.publish_deliver_leg")
        assert leg.count == 20, leg.count
        # the batch-level span keeps recording alongside
        assert hs.hist("obs.e2e.publish_deliver").count >= 1

    aio.run(main())


def test_per_leg_e2e_hist_zero_call_when_off(monkeypatch):
    """Default off: the per-leg histogram is never looked up and
    record_s is never called for it — the recording site stays an
    attribute check (spy-asserted)."""
    import asyncio as aio

    from emqx_tpu.broker import Broker, FanoutPipeline, SubOpts, \
        make_message
    from emqx_tpu.observe.hist import HistSet, LatencyHistogram

    async def main():
        b = Broker()
        b.on_deliver = lambda cid, pubs: None
        b.open_session("s")
        b.subscribe("s", "t/#", SubOpts())
        hs = HistSet("main")
        leg_calls = []
        orig = LatencyHistogram.record_s
        leg_hist = hs.hist("obs.e2e.publish_deliver_leg")

        def spy(self, s):
            if self is leg_hist:
                leg_calls.append(s)
            return orig(self, s)

        monkeypatch.setattr(LatencyHistogram, "record_s", spy)
        p = FanoutPipeline(b, window_s=0.0, hists=hs)  # sample=0 (off)
        assert p._h_e2e_leg is None
        await p.start()
        for i in range(10):
            assert p.offer(make_message("pub", f"t/{i}", b"x"))
        deadline = aio.get_event_loop().time() + 2.0
        while (p._q or p._busy) and \
                aio.get_event_loop().time() < deadline:
            await aio.sleep(0.002)
        await p.stop()
        assert leg_calls == []       # not one record for the leg hist
        assert hs.hist("obs.e2e.publish_deliver").count >= 1

    aio.run(main())
