#!/usr/bin/env python
"""Broker publish→deliver e2e A/B — per-message path vs fanout pipeline.

CPU-only (no device needed): measures the broker-side processing path
the fanout pipeline amortizes, on the telemetry-broadcast shape — twice:

* QoS1 publishers → wildcard **QoS0** subscribers (fire-and-forget
  delivery, the PR-1 number), and
* QoS1 publishers → wildcard **QoS1 windowed** subscribers with acks
  flowing (the acknowledged-delivery stack: batched inflight admission
  + ack/write coalescing, the PR-2 number) under ``"qos1"``, and
* QoS2 publishers → wildcard **QoS2 windowed** subscribers running the
  full exactly-once exchange (ack-run ingest + batched QoS2 state
  machine, the PR-5 number) under ``"qos2"``.

Modes:

* ``--smoke``  — small N, ~15 s wall: the per-PR tracking numbers
  (wired as the ``slow``-marked ``tests/test_bench_e2e.py``).
* default      — the full A/B shapes ``bench.py`` reports under
  ``fanout_e2e`` / ``qos1_e2e``.

Prints one JSON object: per_message / pipeline sections plus the
delivered-msgs/s ``speedup`` (QoS0 fields at top level for
compatibility; the acknowledged A/B nests under ``"qos1"``).

``--chaos`` adds a ``"chaos"`` section: one kill-and-recover cycle per
delivery subsystem (fanout drain, cluster replication, bridge sink,
exhook channel) under the supervision tree, asserting QoS1 delivery
stays exactly-once through the wound — the CI-fast slice of
``tests/test_chaos_delivery.py``.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mesh_smoke(fn: str, n_filters: int) -> dict:
    """One bench.<fn> mesh row in ITS OWN subprocess with a virtual
    8-device CPU mesh (the conftest pattern).  Forcing 8 XLA host
    devices in THIS process would slow every single-chip row (8
    device threads on a 1-core box stall the table_lifecycle churn
    gates), so the mesh A/Bs are isolated instead."""
    import subprocess

    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        flags = (flags + " --xla_force_host_platform_device_count=8") \
            .strip()
    proc = subprocess.run(
        [sys.executable, "-c",
         "import json, bench; print(json.dumps("
         f"bench.{fn}(n_filters={n_filters})))"],
        capture_output=True, text=True, cwd=REPO, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": flags},
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"{fn} smoke failed: {proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def multichip_serve_smoke(n_filters: int) -> dict:
    return _mesh_smoke("bench_multichip_serve_smoke", n_filters)


def multichip_ep_smoke(n_filters: int) -> dict:
    return _mesh_smoke("bench_multichip_ep_smoke", n_filters)


def multichip_balance_smoke(n_filters: int) -> dict:
    return _mesh_smoke("bench_multichip_balance_smoke", n_filters)


def staticcheck_gate() -> dict:
    """Cold full-tree staticcheck as a CI gate row (ISSUE 19): runs
    ``scripts/staticcheck.py`` in a subprocess against a throwaway
    cache dir (so the row always measures the COLD cost, never a
    warm cache someone else left behind) and reports the exit code
    plus wall seconds.  ``gate_clean`` is the real invariant — the
    tree must scan clean with zero live waivers; ``gate_budget`` is
    the cold-scan ceiling (10 s here: the bench box is allowed to be
    slower than the ≤4 s dev-loop budget tests/test_staticcheck.py
    asserts, but a 10 s cold scan means the analysis went
    super-linear and the dev loop is next)."""
    import shutil
    import subprocess
    import tempfile
    import time

    cache_dir = tempfile.mkdtemp(prefix="staticcheck_bench_")
    try:
        t0 = time.perf_counter()
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts",
                                          "staticcheck.py"),
             "--cache-dir", cache_dir],
            capture_output=True, text=True, cwd=REPO, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        cold_s = time.perf_counter() - t0
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
    from emqx_tpu.devtools.staticcheck.rules import ALL_RULES

    tail = (proc.stdout or "").strip().splitlines()
    return {
        "exit_code": proc.returncode,
        "cold_s": round(cold_s, 3),
        "rules": len(ALL_RULES),
        "summary": tail[-1] if tail else "",
        "gate_clean": proc.returncode == 0,
        "gate_budget": cold_s <= 10.0,
    }


def chaos_smoke() -> dict:
    """One kill-and-recover cycle per subsystem; each section reports
    ok plus the evidence (restart counts, delivered totals)."""
    import asyncio as aio

    from emqx_tpu.broker import (
        Broker, FanoutPipeline, SubOpts, make_message,
    )
    from emqx_tpu.observe.metrics import Metrics
    from emqx_tpu.supervise import Supervisor

    def sup_of(m):
        return Supervisor(metrics=m, backoff_base=0.001,
                          backoff_max=0.01, jitter=0.0)

    async def settle(pred, timeout=8.0):
        deadline = aio.get_event_loop().time() + timeout
        while not pred() and aio.get_event_loop().time() < deadline:
            await aio.sleep(0.002)
        return pred()

    async def fanout_cycle():
        b = Broker()
        m = Metrics()
        sup = sup_of(m)
        sess, _ = b.open_session("sub", max_inflight=64)
        b.subscribe("sub", "t/#", SubOpts(qos=1))
        got, dups = [], [0]

        def on_deliver(cid, pubs):
            stack = list(pubs)
            while stack:
                p = stack.pop(0)
                got.append(p.msg.payload)
                if p.msg.dup:
                    dups[0] += 1
                if p.pid is not None:
                    _, more = sess.puback(p.pid)
                    stack.extend(more)

        b.on_deliver = on_deliver
        p = FanoutPipeline(b, window_s=0.0, supervisor=sup, metrics=m)
        await p.start()
        b.fanout = p
        n = 200
        killed = False
        for i in range(n):
            p.offer(make_message("pub", "t/x", b"%d" % i, qos=1))
            if i == n // 2:
                await aio.sleep(0.005)   # let the drain loop spin up
                killed = p._child.kill()
                await aio.sleep(0.003)   # ... and the restart land
        ok = await settle(lambda: len(got) >= n)
        delivered = len(got)
        exactly_once = sorted(int(x) for x in got) == list(range(n))
        restarts = m.get("broker.supervisor.restarts")
        await p.stop()
        await sup.stop()
        return {"ok": bool(ok and killed and exactly_once and not dups[0]
                           and restarts >= 1),
                "delivered": delivered, "duplicates": dups[0],
                "restarts": restarts}

    async def cluster_cycle():
        from emqx_tpu.client import Client
        from emqx_tpu.config import Config
        from emqx_tpu.node import BrokerNode

        async def start(name, seeds=""):
            cfg = Config(file_text=(
                f'node.name = "{name}"\n'
                'listeners.tcp.default.bind = "127.0.0.1:0"\n'
                'cluster.enable = true\n'
                'cluster.listen = "127.0.0.1:0"\n'
                f'cluster.seeds = "{seeds}"\n'
                'cluster.heartbeat_interval = 200ms\n'
            ))
            cfg.put("tpu.enable", False)
            node = BrokerNode(cfg)
            await node.start()
            node.cluster.SYNC_INTERVAL = 0.02
            return node

        n1 = await start("chaos1@smoke")
        n2 = await start(
            "chaos2@smoke", seeds=f"127.0.0.1:{n1.cluster.listen_port}")
        try:
            peered = await settle(
                lambda: n2.cluster.name in n1.cluster.peers
                and n1.cluster.peers[n2.cluster.name].up)
            child = n1.supervisor.lookup("cluster.sync")
            killed = child is not None and child.kill()
            sub = Client(clientid="cs", port=n1.listeners.all()[0].port)
            await sub.connect()
            await sub.subscribe("chaos/+/x", qos=1)
            replicated = await settle(
                lambda: bool(n2.broker.router.match_routes("chaos/a/x")))
            pub = Client(clientid="cp", port=n2.listeners.all()[0].port)
            await pub.connect()
            await pub.publish("chaos/a/x", b"hello", qos=1)
            got = await sub.recv(timeout=5)
            restarts = n1.observed.metrics.get("broker.supervisor.restarts")
            await sub.disconnect()
            await pub.disconnect()
            return {"ok": bool(peered and killed and replicated
                               and got.payload == b"hello"
                               and restarts >= 1),
                    "restarts": restarts}
        finally:
            await n2.stop()
            await n1.stop()

    async def bridge_cycle():
        from emqx_tpu.bridge.resource import BufferedWorker, Connector

        class Sink(Connector):
            def __init__(self):
                self.got = []

            async def send(self, items):
                self.got.extend(items)

        m = Metrics()
        sup = sup_of(m)
        sink = Sink()
        w = BufferedWorker(sink, name="chaos", batch_size=4,
                           retry_base=0.001, retry_max=0.01)
        w.supervisor = sup
        await w.start()
        items = [f"i{n}" for n in range(40)]
        for i, it in enumerate(items):
            w.enqueue(it)
            if i == 20:
                w._tasks[0].kill()
                await aio.sleep(0.002)
            await aio.sleep(0)
        ok = await settle(lambda: set(sink.got) >= set(items))
        restarts = m.get("broker.supervisor.restarts")
        await w.stop()
        await sup.stop()
        return {"ok": bool(ok and restarts >= 1),
                "delivered": len(set(sink.got)), "restarts": restarts}

    async def exhook_cycle():
        try:
            import types

            from emqx_tpu.exhook.manager import (
                ExHookManager, ServerSpec, _ServerState,
            )
        except ImportError:
            return {"skipped": "grpc unavailable"}

        class FakeStub:
            def __init__(self):
                self.calls = []

            def OnClientConnected(self, req):
                async def go():
                    self.calls.append(req)
                return go()

        b = Broker()
        m = Metrics()
        sup = sup_of(m)
        node = types.SimpleNamespace(broker=b, supervisor=sup,
                                     started_at=0.0)
        mgr = ExHookManager(node, [])
        st = _ServerState(spec=ServerSpec(name="s1", url="inproc"))
        st.stub = FakeStub()
        st.hooks = ["client.connected"]
        mgr.servers = [st]
        st.sender = sup.start_child("exhook.sender.s1",
                                    lambda: mgr._sender_loop(st))
        for i in range(3):
            st.queue.put_nowait(("OnClientConnected", i))
        await settle(lambda: len(st.stub.calls) == 3)
        st.sender.kill()
        for i in range(3, 6):
            st.queue.put_nowait(("OnClientConnected", i))
        ok = await settle(lambda: len(st.stub.calls) == 6)
        restarts = m.get("broker.supervisor.restarts")
        st.sender.cancel()
        await sup.stop()
        return {"ok": bool(ok and restarts >= 1),
                "notified": len(st.stub.calls), "restarts": restarts}

    async def match_cycle():
        """Serve-plane kill-and-recover (ISSUE 7): a clean prefetch+
        publish storm, the same storm with the match.batch loop killed
        mid-flight, a 10%-fault storm, then a breaker trip + recovery —
        delivery 1.0 throughout, waiters resolved without budget-length
        stalls, and the faulted storm's worst waiter within 2x the clean
        one (floored at 50 ms for tiny-denominator noise)."""
        import time as _time

        from emqx_tpu import faultinject as fi
        from emqx_tpu.broker.message import make_message
        from emqx_tpu.config import Config
        from emqx_tpu.faultinject import FaultInjector
        from emqx_tpu.node import BrokerNode

        cfg = Config(file_text='listeners.tcp.default.bind = "127.0.0.1:0"\n')
        cfg.put("tpu.enable", True)
        cfg.put("tpu.mirror_refresh_interval", 0.01)
        cfg.put("tpu.bypass_rate", 0.0)
        cfg.put("match.deadline.enable", True)
        cfg.put("match.deadline_ms", 50.0)
        cfg.put("match.breaker.threshold", 3)
        cfg.put("match.breaker.probe_interval", 0.05)
        cfg.put("supervisor.backoff_base", 0.005)
        cfg.put("supervisor.backoff_max", 0.05)
        node = BrokerNode(cfg)
        await node.start()
        try:
            b = node.broker
            ms = node.match_service
            if ms is None:
                return {"skipped": "match service unavailable"}
            got = []
            b.on_deliver = lambda cid, pubs: got.extend(
                bytes(p.msg.payload) for p in pubs)
            b.open_session("sub")
            b.subscribe("sub", "t/#", SubOpts())
            await settle(lambda: ms.ready and ms.dev.epoch == ms.inc.epoch,
                         timeout=60)

            async def storm(n, base, kill_at=None):
                child = node.supervisor.lookup("match.batch")
                waits = []
                for i in range(n):
                    topic = f"t/{base + i}/x"   # unique: every prefetch
                    t0 = _time.perf_counter()   # parks a real waiter
                    await ms.prefetch(topic)
                    waits.append(_time.perf_counter() - t0)
                    b.publish(make_message(
                        "pub", topic, b"%d" % (base + i)))
                    if kill_at is not None and i == kill_at:
                        child.kill()
                return waits

            n = 120
            clean = await storm(n, 0)
            killed = await storm(n, 1000, kill_at=40)
            fi.install(FaultInjector([
                {"point": "match.dispatch", "action": "raise",
                 "prob": 0.1, "times": 0}], seed=11))
            wounded = await storm(n, 2000)
            fi.uninstall()
            # breaker trip + recovery
            fi.install(FaultInjector([
                {"point": "match.dispatch", "action": "raise",
                 "times": 3}]))
            for i in range(3):
                await ms.prefetch(f"t/brk{i}/x")
            tripped = bool(ms._breaker_open) and \
                node.observed.alarms.is_active("match_degraded")
            for i in range(10):   # CPU path keeps serving while open
                topic = f"t/cpu{i}/x"
                await ms.prefetch(topic)
                b.publish(make_message("pub", topic, b"c%d" % i))
            recovered = await settle(lambda: not ms._breaker_open,
                                     timeout=15)
            alarm_cleared = not node.observed.alarms.is_active(
                "match_degraded")
            fi.uninstall()

            sent = 3 * n + 10
            delivered = len(got)
            restarts = node.observed.metrics.get(
                "broker.supervisor.restarts")
            waiter_bound = ms.prefetch_timeout_s * 0.9
            worst = max(clean + killed + wounded)
            p99_ratio = round(max(wounded) / max(max(clean), 1e-9), 2)
            p99_gate = max(wounded) <= max(2.0 * max(clean), 0.05)
            return {
                "ok": bool(delivered == sent and restarts >= 1
                           and tripped and recovered and alarm_cleared
                           and worst < waiter_bound and p99_gate),
                "delivered": delivered, "sent": sent,
                "delivery_ratio": round(delivered / max(1, sent), 4),
                "restarts": restarts,
                "breaker_tripped": tripped,
                "breaker_recovered": bool(recovered and alarm_cleared),
                "worst_waiter_ms": round(worst * 1e3, 1),
                "fault_vs_clean_worst_ratio": p99_ratio,
                "cpu_fallback": node.observed.metrics.get(
                    "broker.match.cpu_fallback"),
            }
        finally:
            fi.uninstall()
            await node.stop()

    async def segments_cycle():
        """Table-lifecycle chaos (ISSUE 9): kill the table.compact
        child mid-swap AND inject a table.swap fault (serving
        unaffected either way, the next cycle resumes), then corrupt
        the on-disk segment and cold-start a second node — checksum
        reject, full rebuild serves, delivery 1.0 throughout."""
        import tempfile

        from emqx_tpu import faultinject as fi
        from emqx_tpu.broker.message import make_message
        from emqx_tpu.config import Config
        from emqx_tpu.faultinject import FaultInjector
        from emqx_tpu.node import BrokerNode

        seg_dir = tempfile.mkdtemp(prefix="chaos_seg_")

        def make_cfg():
            cfg = Config(
                file_text='listeners.tcp.default.bind = "127.0.0.1:0"\n')
            cfg.put("tpu.enable", True)
            cfg.put("tpu.mirror_refresh_interval", 0.01)
            cfg.put("tpu.bypass_rate", 0.0)
            cfg.put("tpu.table", "python")
            cfg.put("match.deadline.enable", True)
            cfg.put("match.deadline_ms", 100.0)
            cfg.put("match.segments.enable", True)
            cfg.put("match.segments.dir", seg_dir)
            cfg.put("match.segments.compact_interval", 0.1)
            cfg.put("match.segments.compact_min_mutations", 1)
            cfg.put("supervisor.backoff_base", 0.005)
            cfg.put("supervisor.backoff_max", 0.05)
            return cfg

        node = BrokerNode(make_cfg())
        await node.start()
        got = []
        try:
            b = node.broker
            ms = node.match_service
            if ms is None:
                return {"skipped": "match service unavailable"}
            b.on_deliver = lambda cid, pubs: got.extend(
                bytes(p.msg.payload) for p in pubs)
            b.open_session("sub")
            b.subscribe("sub", "t/#", SubOpts())
            await settle(lambda: ms.ready, timeout=60)
            # injected swap fault: the cycle aborts atomically (no state
            # mutated) and the next interval compacts clean
            fi.install(FaultInjector([
                {"point": "table.swap", "action": "raise", "times": 1}]))
            sent = 0
            for i in range(60):
                topic = f"t/{i}/x"
                await ms.prefetch(topic)
                b.publish(make_message("pub", topic, b"%d" % i))
                sent += 1
            swapped = await settle(lambda: ms._table_gen >= 1, timeout=20)
            fi.uninstall()
            # kill the compact child mid-cycle: supervised restart
            child = node.supervisor.lookup("table.compact")
            killed = child is not None and child.kill()
            gen0 = ms._table_gen
            for i in range(60, 120):
                topic = f"t/{i}/x"
                # table mutations so the restarted compact child has
                # something to fold into the next segment
                b.subscribe("sub", f"chaos/{i}/+", SubOpts())
                await ms.prefetch(topic)
                b.publish(make_message("pub", topic, b"%d" % i))
                sent += 1
            resumed = await settle(
                lambda: ms._table_gen > gen0, timeout=20)
            restarts = node.observed.metrics.get(
                "broker.supervisor.restarts")
            compact_runs = node.observed.metrics.get(
                "tpu.table.compact_runs")
            seg_exists = os.path.exists(ms._segment_path)
            delivered = len(got)
        finally:
            fi.uninstall()
            await node.stop()
        # corrupt the segment: the next cold start must checksum-reject
        # it and serve from the full rebuild
        seg_path = os.path.join(seg_dir, "match_table.seg.npz")

        def flip_bytes():
            with open(seg_path, "r+b") as f:
                f.seek(256)
                f.write(b"\xff\xff\xff\xff")

        await aio.to_thread(flip_bytes)
        node2 = BrokerNode(make_cfg())
        await node2.start()
        got2 = []
        try:
            b2 = node2.broker
            ms2 = node2.match_service
            rejected = ms2 is not None and not ms2._segment_loaded
            b2.on_deliver = lambda cid, pubs: got2.extend(
                bytes(p.msg.payload) for p in pubs)
            b2.open_session("sub2")
            b2.subscribe("sub2", "t/#", SubOpts())
            await settle(lambda: ms2 is not None and ms2.ready,
                         timeout=60)
            for i in range(40):
                topic = f"t/r{i}/x"
                await ms2.prefetch(topic)
                b2.publish(make_message("pub", topic, b"r%d" % i))
            rebuilt_ok = await settle(lambda: len(got2) >= 40)
        finally:
            await node2.stop()
        return {
            "ok": bool(swapped and killed and resumed and seg_exists
                       and delivered == sent and rejected
                       and rebuilt_ok and restarts >= 1),
            "delivered": delivered, "sent": sent,
            "delivery_ratio": round(delivered / max(1, sent), 4),
            "restarts": restarts,
            "compact_runs": compact_runs,
            "swap_fault_recovered": swapped,
            "kill_resumed": resumed,
            "corrupt_segment_rejected": rejected,
            "rebuild_served": bool(rebuilt_ok),
        }

    async def pipeline_cycle():
        """Overlapped-serve-pipeline chaos (ISSUE 11): a clean storm,
        a storm with the match.readback child killed mid-flight, and a
        10%-injected match.readback fault storm — delivery 1.0
        throughout, waiters failing over to the CPU trie instead of
        stalling toward the prefetch timeout, supervised restart
        resumes the two-phase readback."""
        import time as _time

        from emqx_tpu import faultinject as fi
        from emqx_tpu.broker.message import make_message
        from emqx_tpu.config import Config
        from emqx_tpu.faultinject import FaultInjector
        from emqx_tpu.node import BrokerNode

        cfg = Config(file_text='listeners.tcp.default.bind = "127.0.0.1:0"\n')
        cfg.put("tpu.enable", True)
        cfg.put("tpu.mirror_refresh_interval", 0.01)
        cfg.put("tpu.bypass_rate", 0.0)
        cfg.put("match.pipeline.enable", True)
        cfg.put("supervisor.backoff_base", 0.005)
        cfg.put("supervisor.backoff_max", 0.05)
        node = BrokerNode(cfg)
        await node.start()
        try:
            b = node.broker
            ms = node.match_service
            if ms is None:
                return {"skipped": "match service unavailable"}
            got = []
            b.on_deliver = lambda cid, pubs: got.extend(
                bytes(p.msg.payload) for p in pubs)
            b.open_session("sub")
            b.subscribe("sub", "t/#", SubOpts())
            await settle(lambda: ms.ready and ms.dev.epoch == ms.inc.epoch,
                         timeout=60)

            async def storm(n, base, kill_at=None):
                child = node.supervisor.lookup("match.readback")
                waits = []
                for i in range(n):
                    topic = f"t/{base + i}/x"
                    t0 = _time.perf_counter()
                    await ms.prefetch(topic)
                    waits.append(_time.perf_counter() - t0)
                    b.publish(make_message(
                        "pub", topic, b"%d" % (base + i)))
                    if kill_at is not None and i == kill_at:
                        child.kill()
                return waits

            n = 100
            clean = await storm(n, 0)
            killed = await storm(n, 1000, kill_at=40)
            inj = fi.install(FaultInjector([
                {"point": "match.readback", "action": "raise",
                 "prob": 0.1, "times": 0}], seed=7))
            wounded = await storm(n, 2000)
            fi.uninstall()
            sent = 3 * n
            delivered = len(got)
            restarts = node.observed.metrics.get(
                "broker.supervisor.restarts")
            worst = max(clean + killed + wounded)
            rb_bytes = node.observed.metrics.get(
                "tpu.match.readback_bytes")
            return {
                "ok": bool(delivered == sent and restarts >= 1
                           and inj.fired.get("match.readback", 0) >= 1
                           and worst < ms.prefetch_timeout_s * 0.9
                           and rb_bytes > 0),
                "delivered": delivered, "sent": sent,
                "delivery_ratio": round(delivered / max(1, sent), 4),
                "restarts": restarts,
                "readback_faults": inj.fired.get("match.readback", 0),
                "worst_waiter_ms": round(worst * 1e3, 1),
                "readback_bytes": rb_bytes,
                "cpu_fallback": node.observed.metrics.get(
                    "broker.match.cpu_fallback"),
            }
        finally:
            fi.uninstall()
            await node.stop()

    async def admission_cycle():
        """Admission-plane chaos (ISSUE 14): an attacker is quarantined
        mid-storm, then the admission.score child is killed AND 10%
        admission.score faults are injected — every failure FAILS OPEN
        (standing decisions clear, admission_degraded raises, honest
        AND attacker traffic flows — never a new drop path), and the
        supervised restart resumes scoring, re-quarantines the
        attacker and clears the alarm."""
        from emqx_tpu import faultinject as fi
        from emqx_tpu.broker.message import make_message
        from emqx_tpu.config import Config
        from emqx_tpu.faultinject import FaultInjector
        from emqx_tpu.node import BrokerNode

        cfg = Config(file_text='listeners.tcp.default.bind = "127.0.0.1:0"\n')
        cfg.put("tpu.enable", False)
        cfg.put("admission.enable", True)
        cfg.put("admission.tick", 0.02)
        cfg.put("admission.hold_ticks", 2)
        cfg.put("admission.decay_ticks", 1000)   # no decay mid-test
        # the synthetic storm drives BOTH clients at the same msgs/s;
        # only the attacker's topic-scan shape (fresh topic per
        # message) must trip, so the verdict rides the fan dimension
        cfg.put("admission.max_publish_rate", 1_000_000.0)
        cfg.put("admission.fan_window", 0.1)
        cfg.put("admission.max_topic_fan", 50.0)
        cfg.put("supervisor.backoff_base", 0.005)
        cfg.put("supervisor.backoff_max", 0.05)
        node = BrokerNode(cfg)
        await node.start()
        try:
            b = node.broker
            adm = node.admission
            alarms = node.observed.alarms
            sess, _ = b.open_session("sub", max_inflight=64)
            b.subscribe("sub", "t/#", SubOpts(qos=1))
            got = []

            def on_deliver(cid, pubs):
                stack = list(pubs)
                while stack:
                    p = stack.pop(0)
                    got.append(p.msg.payload)
                    if p.pid is not None:
                        _, more = sess.puback(p.pid)
                        stack.extend(more)

            b.on_deliver = on_deliver
            seq = [0]
            sent = [0]

            def storm(n_honest=40, atk_per=40):
                # drive the REAL ingest seams: publish notes + the
                # QoS0-shed enforcement path in Broker.publish
                for _ in range(n_honest):
                    i = seq[0]
                    seq[0] += 1
                    sent[0] += 1
                    adm.note_publish("honest", "t/h", 64)
                    b.publish(make_message("honest", "t/h", b"%d" % i,
                                           qos=1))
                for k in range(atk_per):
                    topic = f"scan/{seq[0]}/{k}"
                    adm.note_publish("attacker", topic, 64)
                    b.publish(make_message("attacker", topic, b"a",
                                           qos=0))

            # phase 1: attacker climbs to quarantine; honest stays clean
            for _ in range(60):
                storm()
                await aio.sleep(0.01)
                if "attacker" in adm._shed:
                    break
            quarantined = "attacker" in adm._shed
            honest_row = adm.explain("honest")
            honest_clean = bool(
                honest_row is not None and honest_row["level"] == 0
                and not node.banned.check(clientid="honest"))
            shed_before = adm.shed_count
            storm()
            attacker_shed = adm.shed_count > shed_before

            # phase 2: a PERSISTENT injected fault crashes every tick
            # (the restarted child dies again) + an explicit kill —
            # fail-open must hold the whole time: shed set empty,
            # alarm active, attacker traffic flowing unscreened
            fi.install(FaultInjector([
                {"point": "admission.score", "action": "raise",
                 "times": 0}]))
            child = node.supervisor.lookup("admission.score")
            killed = child is not None and child.kill()
            failed_open = await settle(
                lambda: adm.degraded
                and alarms.is_active("admission_degraded")
                and "attacker" not in adm._shed)
            shed_frozen = adm.shed_count
            storm()
            no_new_drop_path = adm.shed_count == shed_frozen

            # phase 3: lift the fault → supervised restart resumes
            # scoring, re-quarantines the attacker, clears the alarm
            fi.uninstall()
            give_up = aio.get_event_loop().time() + 10.0
            while "attacker" not in adm._shed \
                    and aio.get_event_loop().time() < give_up:
                storm()
                await aio.sleep(0.01)
            recovered = "attacker" in adm._shed
            alarm_cleared = await settle(
                lambda: not alarms.is_active("admission_degraded"))

            # phase 4: 10% injected admission.score faults mid-storm —
            # wounded ticks fail open + restart, honest delivery holds
            inj = fi.install(FaultInjector([
                {"point": "admission.score", "action": "raise",
                 "prob": 0.1, "times": 0}], seed=5))
            for _ in range(30):
                storm()
                await aio.sleep(0.01)
            fi.uninstall()
            faults = inj.fired.get("admission.score", 0)
            ok_drain = await settle(lambda: len(got) >= sent[0])
            restarts = node.observed.metrics.get(
                "broker.supervisor.restarts")
            fail_opens = node.observed.metrics.get(
                "broker.admission.fail_open")
            delivered = len(got)
            return {
                "ok": bool(quarantined and honest_clean
                           and attacker_shed and killed
                           and failed_open and no_new_drop_path
                           and recovered and alarm_cleared and ok_drain
                           and delivered == sent[0]
                           and restarts >= 1 and faults >= 1),
                "delivered": delivered, "sent": sent[0],
                "delivery_ratio": round(
                    delivered / max(1, sent[0]), 4),
                "restarts": restarts,
                "fail_opens": fail_opens,
                "score_faults": faults,
                "quarantined_then_shed": bool(quarantined
                                              and attacker_shed),
                "honest_never_flagged": honest_clean,
                "failed_open": bool(failed_open),
                "no_new_drop_path": bool(no_new_drop_path),
                "alarm_raised_and_cleared": bool(failed_open
                                                 and alarm_cleared),
                "requarantined_after_restart": bool(recovered),
            }
        finally:
            fi.uninstall()
            await node.stop()

    async def all_cycles():
        return {
            "fanout": await fanout_cycle(),
            "cluster": await cluster_cycle(),
            "bridge": await bridge_cycle(),
            "exhook": await exhook_cycle(),
            "match": await match_cycle(),
            "pipeline": await pipeline_cycle(),
            "segments": await segments_cycle(),
            "admission": await admission_cycle(),
            # degraded-mesh cycle (ISSUE 18): shard kill → degraded
            # serving → supervised rebuild (one injected crash =
            # restart evidence) → canary re-admit, delivery 1.0
            # throughout.  Needs an 8-device mesh, so it rides the
            # same subprocess isolation as the multichip A/Bs.
            "mesh": await aio.to_thread(
                _mesh_smoke, "bench_mesh_chaos_smoke", 96),
        }

    return aio.run(all_cycles())


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(prog="bench_e2e")
    ap.add_argument("--smoke", action="store_true",
                    help="small-N CPU smoke (<60 s), for per-PR tracking")
    ap.add_argument("--chaos", action="store_true",
                    help="add one kill-and-recover cycle per subsystem")
    ap.add_argument("--duration", type=float, default=None,
                    help="override per-run duration (s)")
    args = ap.parse_args(argv)

    from bench import (
        _adversarial_size, _config1_size, _config1_sweep_size,
        _fanout_e2e_size, _qos1_e2e_size, _qos2_e2e_size,
        _table_lifecycle_size, bench_adversarial, bench_config1,
        bench_config1_sweep, bench_fanout_e2e, bench_kernel_join_smoke,
        bench_qos1_e2e, bench_qos2_e2e, bench_serve_deadline_smoke,
        bench_table_lifecycle,
    )

    size = _fanout_e2e_size(args.smoke)
    qsize = _qos1_e2e_size(args.smoke)
    q2size = _qos2_e2e_size(args.smoke)
    c1size = _config1_size(args.smoke)
    c1ssize = _config1_sweep_size(args.smoke)
    if args.duration is not None:
        size["duration"] = args.duration
        qsize["duration"] = args.duration
        q2size["duration"] = args.duration
        c1size["duration"] = args.duration
        c1ssize["duration"] = args.duration
    out = bench_fanout_e2e(**size)
    out["qos1"] = bench_qos1_e2e(**qsize)
    out["qos2"] = bench_qos2_e2e(**q2size)
    # connection-plane tracking numbers (PR 6): real-client config1
    # flag-off/flag-on A/B + the client-count sweep at constant load
    out["config1"] = bench_config1(**c1size)
    out["config1_sweep"] = bench_config1_sweep(**c1ssize)
    # deadline serve A/B (ISSUE 7): static vs deadline-mode continuous
    # batching at the same offered load, CPU-jax tiny scale — tracks
    # structure + delivery per PR; the real ratio comes from bench.py
    out["serve_deadline"] = bench_serve_deadline_smoke(
        seconds=(1.2 if args.smoke else 4.0))
    # streaming table lifecycle A/B (ISSUE 9): segment cold start vs
    # full rebuild + churn soak across live compaction swaps
    out["table_lifecycle"] = bench_table_lifecycle(
        **_table_lifecycle_size(args.smoke))
    # adversarial admission A/B (ISSUE 14): 5% attackers at 10x the
    # honest rate + a CONNECT storm — flag-on holds honest delivery 1.0
    # and p99 near clean while the ladder throttles/quarantines/bans
    # the attackers; flag-off records the brownout the gate prevents
    out["adversarial"] = bench_adversarial(**_adversarial_size(args.smoke))
    # kernel backend A/B (ISSUE 13): hash vs join vs auto at one serve
    # shape, short+deep mixes — the parity gate is CI-asserted, the
    # speedup ratios are tracking numbers for the r06 hardware round
    out["kernel_join"] = bench_kernel_join_smoke(
        n_filters=(2000 if args.smoke else 20000))
    # multichip serve A/B (ISSUE 15): the table sharded by topic-prefix
    # over the virtual 8-device CPU mesh vs the single-chip serve path
    # — parity / truncation-psum / shard-kill gates are CI-asserted;
    # the scaling ratio is a tracking number (8 host threads share one
    # CPU; bench.py's r06 hardware round owns the ≥6x claim).  Runs in
    # its own subprocess so the forced 8-device mesh cannot slow the
    # single-chip rows above.
    out["multichip_serve"] = multichip_serve_smoke(
        n_filters=(2000 if args.smoke else 20000))
    # prefix-EP routed vs replicated A/B (ISSUE 16): routed parity,
    # bucket-overflow fail-open, the per-shard width contract
    # (gate_shard_width_le_batch_over_tp) and routed-path shard-kill
    # failover are CI-asserted; the routed speedup is a tracking
    # number (host threads pay the all_to_all without the ICI win).
    out["multichip_ep"] = multichip_ep_smoke(
        n_filters=(2000 if args.smoke else 20000))
    # load-adaptive plane A/B (ISSUE 20): overflow-EWMA capacity grow
    # with zero dropped rows through the compile window, popularity
    # rebalance worst-shard width cut >= 1.5x on the skewed corpus,
    # post-remap routed parity, cold-start placement restore, and the
    # ep.rebalance fault no-op — all CI-asserted; the adaptive
    # speedup is a tracking number (host threads share one CPU).
    out["multichip_balance"] = multichip_balance_smoke(
        n_filters=(2000 if args.smoke else 20000))
    # stage-latency observatory parity (ISSUE 12): the serve sections'
    # p50/p99 now come from the product's histograms (observe/hist.py);
    # the legacy np.percentile extraction over the SAME post-warmup
    # samples must agree before the parallel lists stay deleted.  A
    # parity break here is a histogram-math bug, so the smoke fails
    # loudly instead of recording a gate nobody reads.
    for side in ("static", "deadline"):
        sec = out["serve_deadline"].get(side)
        if sec and "gate_hist_parity" in sec:
            assert sec["gate_hist_parity"], (
                "serve_deadline histogram/np.percentile parity broke",
                side, sec)
    # staticcheck gate row (ISSUE 19): the cold full-tree scan must
    # stay clean (exit 0, zero live waivers) and under the bench-box
    # cold budget — the per-PR smoke is where analysis regressions
    # (a rule gone quadratic, a new real finding) surface first
    out["staticcheck"] = staticcheck_gate()
    assert out["staticcheck"]["gate_clean"], (
        "staticcheck found new findings (or the CLI crashed)",
        out["staticcheck"])
    if args.chaos:
        out["chaos"] = chaos_smoke()
    print(json.dumps(out, indent=2))
    return out


if __name__ == "__main__":
    main()
