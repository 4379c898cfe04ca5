#!/usr/bin/env python
"""Headline benchmark at north-star scale: wildcard topic-match on TPU
vs the host-trie baseline, through the real serving engine
(BASELINE.json configs 1-3; north star: 10M wildcard subs).

Prints ONE JSON line:
  {"metric": "wildcard_match_throughput", "value": <topics/s/chip>,
   "unit": "topics/s/chip", "vs_baseline": <x over CPU>, ...}

What is measured (all numbers measured in-run, no estimates):
* CPU denominators — (a) the native C++ host trie (``NativeNfa.match_host``,
  conservative: faster than the reference's BEAM ``emqx_trie:match`` [U]),
  (b) the pure-Python FilterTrie at <=1M filters (the round-1/2 stand-in).
* Device build — ``NativeNfa.bulk_add`` (seconds at 10M; the old
  ``compile_filters`` O(table) python path is gone from the bench).
* Device throughput — depth-bucketed pipelined batches through the
  shipping kernel in raw-output mode (topics whose length <= 4 ride a
  5-step kernel; kernel depth bounds TOPIC length, not filter depth).
* Serving p50/p99 — an asyncio micro-batching loop (batch window +
  fixed-shape pad + device dispatch via the DeviceNfa serving engine +
  host fail-open re-run of spilled rows), measured per-topic
  enqueue→answer at 70% of measured max throughput, AND an iso-load
  comparison where the SAME harness drives the CPU engine at the load it
  can sustain.
* Delta apply — 1k subscribe/unsubscribe deltas drained and
  scatter-applied to the live device table, timed (the <50 ms bound).

Usage: python bench.py [--smoke] [--filters N] [--batch B] ...
"""

from __future__ import annotations

import argparse
import asyncio
import json
import math
import os
import sys
import time

import numpy as np

if os.environ.get("JAX_PLATFORMS", "").strip() == "cpu":
    # a CPU-pinned run (smoke/CI) asked to stay off the device: pin the
    # config too, which holds even where jax_platforms was set earlier
    import jax

    jax.config.update("jax_platforms", "cpu")


def build_workload(rng, n_filters: int, n_topics: int, depth: int = 8):
    """Wildcard-heavy filter set + concrete publish topics over a Zipfian
    topic tree (hot prefixes), BASELINE config 3 shape.  Vectorized: the
    per-level Zipf draws happen in bulk numpy; only the joins loop."""
    level_vocab = [
        [f"L{d}w{i}" for i in range(max(4, 2 ** (d + 2)))] for d in range(depth)
    ]
    zipf_w = []
    for d in range(depth):
        n = len(level_vocab[d])
        w = 1.0 / np.arange(1, n + 1)
        zipf_w.append(w / w.sum())

    def rand_paths(count):
        depths = rng.integers(2, depth + 1, size=count)
        cols = [
            rng.choice(len(level_vocab[d]), size=count, p=zipf_w[d])
            for d in range(depth)
        ]
        return [
            [level_vocab[i][cols[i][r]] for i in range(depths[r])]
            for r in range(count)
        ]

    filters = set()
    while len(filters) < n_filters:
        need = int((n_filters - len(filters)) * 1.3) + 16
        kinds = rng.random(need)
        plus_pos = rng.random(need)
        hash_cut = rng.random(need)
        for ws, kind, pp, hc in zip(rand_paths(need), kinds, plus_pos, hash_cut):
            if kind < 0.45:  # '+' somewhere
                ws[int(pp * len(ws))] = "+"
            elif kind < 0.75:  # '#' tail (replaces >=1 tail level)
                ws = ws[: max(1, int(hc * (len(ws) - 1)) + 1) - 1] or ws[:1]
                ws = ws + ["#"]
                if len(ws) > depth:
                    ws = ws[: depth - 1] + ["#"]
            filters.add("/".join(ws))
            if len(filters) >= n_filters:
                break
    topics = ["/".join(ws) for ws in rand_paths(n_topics)]
    return sorted(filters), topics


# ---------------------------------------------------------------------------
# host tables
# ---------------------------------------------------------------------------

def build_table(filters, depth):
    """Native C++ incremental NFA when available (seconds at 10M),
    Python IncrementalNfa otherwise."""
    from emqx_tpu.ops.incremental import IncrementalNfa

    t0 = time.perf_counter()
    try:
        from emqx_tpu.native.nfa import NativeNfa

        nt = NativeNfa(
            depth=depth,
            state_bucket=max(1024, 1 << int(np.ceil(np.log2(
                max(2, len(filters)) * 2.2)))),
            edge_bucket=max(64, 1 << int(np.ceil(np.log2(
                max(2, len(filters)) * 1.4)))),  # ~2 slots/bucket
        )
        added = nt.bulk_add(filters)
        assert added == len(filters), (added, len(filters))
        kind = "native"
    except Exception as e:  # toolchain missing: python path (small scales)
        print(f"# native nfa unavailable ({e}); python table", file=sys.stderr)
        nt = IncrementalNfa(depth=depth)
        for f in filters:
            nt.add(f)
        kind = "python"
    return nt, kind, time.perf_counter() - t0


def bench_cpu_native(table, topics, budget_s: float = 10.0):
    """Per-match latency of the C++ host trie (conservative denominator:
    it is faster than the reference's BEAM trie walk).

    Two passes: a TIMED cold pass (reported as `topics_per_s_cold`)
    that doubles as the warmup, then a warm pass over the same topics
    whose rate is the headline `topics_per_s` — steady-state match
    cost, not first-touch page faults on a cold multi-GB table.
    Round-3 review found the cold mean sat 4.6x below the same calls
    made warm (`serve_cpu_iso`), making every ratio built on it
    suspect — the warm rate is the honest denominator, and the cold
    number is kept alongside for continuity."""
    # cold pass (timed) doubles as the warmup for the warm pass
    cold = []
    deadline = time.perf_counter() + budget_s / 2
    i = 0
    while time.perf_counter() < deadline and i < len(topics):
        t0 = time.perf_counter()
        table.match_host(topics[i])
        cold.append(time.perf_counter() - t0)
        i += 1
    n_warmed = i
    lat = []
    deadline = time.perf_counter() + budget_s / 2
    j = 0
    while time.perf_counter() < deadline and j < n_warmed:
        t0 = time.perf_counter()
        table.match_host(topics[j])
        lat.append(time.perf_counter() - t0)
        j += 1
    if not cold:
        # empty topic list or first match overran the whole half-budget:
        # no honest number exists; fail loudly rather than emit NaNs
        raise RuntimeError(
            "bench_cpu_native: cold pass produced 0 samples "
            f"(topics={len(topics)}, budget_s={budget_s}); "
            "raise budget_s or check the table"
        )
    warm_fallback = not lat  # no warm sample landed; cold data reported
    lat = np.array(lat if lat else cold)
    cold = np.array(cold)
    out = {
        "topics_per_s": 1.0 / lat.mean(),
        "topics_per_s_cold": 1.0 / cold.mean(),
        "p50_us": float(np.percentile(lat, 50) * 1e6),
        "p99_us": float(np.percentile(lat, 99) * 1e6),
        "cold_p99_us": float(np.percentile(cold, 99) * 1e6),
        "measured": int(j or i),
    }
    if warm_fallback:
        out["warm_pass_missing"] = True  # headline keys hold COLD data
    return out


def bench_cpu_python(filters, topics, budget_s: float = 10.0,
                     max_filters: int = 1_000_000):
    """Round-1/2 Python FilterTrie baseline, capped (a 10M-node Python
    trie costs minutes + GBs; the native denominator covers full scale)."""
    from emqx_tpu.broker import FilterTrie

    sub = filters[:max_filters]
    tr = FilterTrie()
    t0 = time.perf_counter()
    for f in sub:
        tr.insert(f)
    build_s = time.perf_counter() - t0
    lat = []
    deadline = time.perf_counter() + budget_s
    i = 0
    while time.perf_counter() < deadline and i < len(topics):
        t0 = time.perf_counter()
        tr.match(topics[i])
        lat.append(time.perf_counter() - t0)
        i += 1
    lat = np.array(lat)
    return {
        "n_filters": len(sub),
        "build_s": build_s,
        "topics_per_s": 1.0 / lat.mean(),
        "p50_us": float(np.percentile(lat, 50) * 1e6),
        "p99_us": float(np.percentile(lat, 99) * 1e6),
        "measured": int(i),
    }


# ---------------------------------------------------------------------------
# device: throughput (depth-bucketed) + serving harness + deltas
# ---------------------------------------------------------------------------

SHORT_DEPTH = 4


_ENCODERS: dict = {}


def _encode(table, names, depth, batch):
    """Depth-overriding encode with a persistent per-table encoder (the
    native interner is push-incremental; rebuilding it per batch would
    re-ship the vocab every call)."""
    from emqx_tpu.ops.encode import TopicEncoder

    enc = _ENCODERS.get(id(table))
    if enc is None or enc.vocab is not table.vocab:
        enc = _ENCODERS[id(table)] = TopicEncoder(table.vocab)
    return enc.encode(names, depth, batch=batch)


def bench_device(table, topics, batch, iters, depth, active_slots):
    import jax

    from emqx_tpu.ops.device_table import DeviceNfa

    out = {}
    t0 = time.perf_counter()
    dev = DeviceNfa(table, active_slots=active_slots, compact_output=False,
                    max_matches=_serve_max_matches())
    out["upload_s"] = round(time.perf_counter() - t0, 3)
    out["device"] = str(jax.devices()[0])
    out["active_slots"] = active_slots

    short = [t for t in topics if t.count("/") < SHORT_DEPTH]
    long_ = [t for t in topics if t.count("/") >= SHORT_DEPTH]
    out["short_frac"] = round(len(short) / max(1, len(topics)), 3)

    def stream_batches(names, d):
        batches = []
        for i in range(0, len(names) - batch + 1, batch):
            w, l, s = _encode(table, names[i:i + batch], d, batch)
            batches.append(tuple(map(jax.numpy.asarray, (w, l, s))))
        if not batches:  # tile to one batch
            names = (names * (batch // max(1, len(names)) + 1))[:batch]
            w, l, s = _encode(table, names, d, batch)
            batches.append(tuple(map(jax.numpy.asarray, (w, l, s))))
        return batches

    t0 = time.perf_counter()
    w, l, s = _encode(table, short[:batch] or topics[:batch], SHORT_DEPTH,
                      batch)
    out["encode_ms_per_batch"] = round((time.perf_counter() - t0) * 1e3, 2)

    sb = stream_batches(short, SHORT_DEPTH)
    lb = stream_batches(long_, depth)

    def pipelined(batches, label):
        r = dev.match(*batches[0])
        np.asarray(r.matches)  # warm + sync
        nb = len(batches)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            rs = [dev.match(*batches[i % nb]) for i in range(iters)]
            np.asarray(rs[-1].matches)
            best = min(best, (time.perf_counter() - t0) / iters)
        return best

    t_short = pipelined(sb, "short")
    t_long = pipelined(lb, "long")
    out["short_ms_per_batch"] = round(t_short * 1e3, 2)
    out["long_ms_per_batch"] = round(t_long * 1e3, 2)
    fs = out["short_frac"]
    per_topic_s = (fs * t_short + (1 - fs) * t_long) / batch
    out["topics_per_s"] = round(1.0 / per_topic_s, 1)

    # spill audit across distinct batches (overflow rows re-run on host)
    spilled = total = 0
    for b in (sb + lb)[:8]:
        r = dev.match(*b)
        spilled += int(np.asarray(r.spilled_rows()).sum())
        total += batch
    out["spill_rate"] = round(spilled / max(1, total), 5)
    return dev, out


def _config1_shards_default() -> int:
    """Shard count for the flag-on config1 side: one worker loop per
    spare core, capped at 4; on a single-core box one shard still
    overlaps socket syscalls (GIL released) with the in-process
    loadgen."""
    return min(4, max(1, os.cpu_count() or 1))


def _config1_run(n_clients, rate_per_client, duration, qos, inflight,
                 fanout: bool, shards: int) -> dict:
    import asyncio as aio

    from emqx_tpu.bench_client import run_scenario
    from emqx_tpu.config import Config
    from emqx_tpu.node import BrokerNode

    async def run():
        cfg = Config(file_text=(
            'listeners.tcp.default.bind = "127.0.0.1:0"\n'
            + ('broker.fanout.enable = true\n' if fanout else '')
        ))
        cfg.put("tpu.enable", False)   # host-path e2e: no device drag
        if fanout and shards:
            cfg.put("broker.conn.shards", shards)
        node = BrokerNode(cfg)
        await node.start()
        try:
            out = await run_scenario(
                "pub", port=node.listeners.all()[0].port,
                count=n_clients, rate=rate_per_client,
                subscribers=n_clients, topic="bench/%i",
                qos=qos, payload_size=64, duration=duration,
                inflight=inflight, callback_subs=True)
        finally:
            await node.stop()
        return out

    s = aio.run(run())
    lat = s.get("latency_us") or {}
    sent = s.get("sent") or 0
    return {
        "sent": sent,
        "received": s.get("received"),
        # recv_rate shares BenchStats' wall clock (connect phase + run
        # + tail) with its numerator — slightly conservative, never
        # >100% of offered like a nominal-duration divisor was
        "msgs_per_s": s.get("recv_rate"),
        "delivery_ratio": round((s.get("received") or 0)
                                / max(1, sent), 4),
        "e2e_p50_us": lat.get("p50"),
        "e2e_p99_us": lat.get("p99"),
    }


def bench_config1(n_clients: int = 1000, rate_per_client: float = 10.0,
                  duration: float = 10.0, qos: int = 1,
                  inflight: int = 16, shards: int = None) -> dict:
    """BASELINE config 1 at its SPECIFIED shape (1k subs, 10k msg/s
    offered): emqtt_bench-style broker e2e — N exact-topic subscriber/
    publisher pairs through a LIVE in-process node over real TCP
    (protocol-mode datapath), measuring delivered msg/s and end-to-end
    p50/p99.  QoS1 with a pipelined-ack window (emqtt_bench async-pub
    mode); the load generator shares the host cores, so the number is
    combined loadgen+broker capacity — conservative for the broker
    alone.

    Reported as a flag-off/flag-on A/B: ``per_message`` is the default
    per-packet datapath, ``pipeline`` the batched stack
    (``broker.fanout.enable`` + connection-plane shards + hashed timer
    wheel + publish-run ingest).  Headline keys mirror the PIPELINE
    side — the configuration this PR ships for this shape."""
    if shards is None:
        shards = _config1_shards_default()
    per_msg = _config1_run(n_clients, rate_per_client, duration, qos,
                           inflight, fanout=False, shards=0)
    pipe = _config1_run(n_clients, rate_per_client, duration, qos,
                        inflight, fanout=True, shards=shards)
    return {
        "clients": n_clients,
        "offered_msgs_per_s": int(n_clients * rate_per_client),
        "shards": shards,
        **pipe,
        "per_message": per_msg,
        "pipeline": pipe,
        "speedup": round((pipe["msgs_per_s"] or 0.0)
                         / max(1e-9, per_msg["msgs_per_s"] or 0.0), 2),
    }


def bench_config1_sweep(counts=(1000, 5000, 10000),
                        total_rate: float = 10000.0,
                        duration: float = 10.0, qos: int = 1,
                        inflight: int = 16, shards: int = None) -> list:
    """Connection-count sweep at CONSTANT offered load (the
    "Benchmarking Message Brokers for IoT Edge" connection-scaling
    axis): each row runs the config1 shape flag-on with ``count`` total
    clients (count/2 publisher/subscriber pairs) all offering
    ``total_rate`` msgs/s combined, reporting per-count delivered rate
    and e2e p50/p99.  Counts that cannot fit the process fd limit
    (2 fds per in-process connection: client end + broker end) clamp
    to the feasible maximum and record what was requested — delivery
    correctness (ratio 1.0) is asserted at every count that runs."""
    import resource

    if shards is None:
        shards = _config1_shards_default()
    soft, _hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    # 4 fds per pair (2 conns × 2 ends) + slack for the node itself
    max_pairs = max(1, (soft - 512) // 4)
    rows = []
    for count in counts:
        pairs = count // 2
        clamped = min(pairs, max_pairs)
        rate = total_rate / max(1, clamped)
        row = _config1_run(clamped, rate, duration, qos, inflight,
                           fanout=True, shards=shards)
        row = {
            "clients": clamped * 2,
            "requested_clients": count,
            "fd_limited": clamped < pairs,
            "offered_msgs_per_s": int(total_rate),
            **row,
        }
        rows.append(row)
    return rows


def _adversarial_size(smoke: bool) -> dict:
    return ({"n_honest": 8, "honest_rate": 20.0, "duration": 2.5}
            if smoke
            else {"n_honest": 64, "honest_rate": 20.0, "duration": 8.0})


def bench_adversarial(n_honest: int = 64, honest_rate: float = 20.0,
                      duration: float = 8.0, attacker_frac: float = 0.05,
                      attacker_mult: float = 10.0,
                      storm_rate: float = 25.0,
                      inflight: int = 16) -> dict:
    """Hostile-traffic A/B (ISSUE 14, the P4-pipeline adversarial
    scenario): ``n_honest`` QoS1 publisher/subscriber pairs at
    ``honest_rate`` msgs/s each, plus **5% attackers at 10× the honest
    rate** (QoS0 topic-scan floods — every message a fresh topic, the
    shape the distinct-topic sketch exists for) and a CONNECT storm
    (reconnect churn over a small clientid pool).  Three runs:

    * ``clean``      — honest only, admission off: the p99 baseline;
    * ``attack_off`` — attackers + storm, ``admission.enable`` OFF: the
      brownout the admission plane exists to prevent (recorded, not
      gated — it IS the regression);
    * ``attack_on``  — same hostile mix, admission ON: the gates.

    Gate booleans ride the JSON: flag-on holds honest delivery_ratio
    1.0 and p99 within 1.5× of clean while the attackers are throttled
    / quarantined / banned, and no honest client is ever flagged."""
    import asyncio as aio

    from emqx_tpu.bench_client import run_scenario
    from emqx_tpu.config import Config
    from emqx_tpu.mqtt import frame as F
    from emqx_tpu.mqtt import packet as P
    from emqx_tpu.node import BrokerNode

    n_attackers = max(1, int(n_honest * attacker_frac))
    attacker_rate = honest_rate * attacker_mult

    async def attacker_loop(i: int, port: int, end_at: float,
                            out: dict) -> None:
        """QoS0 topic-scan flood from one attacker: distinct topic per
        message.  A kick/ban closes the socket; the loop retries and
        counts refused CONNECTs — the cheap-rejection win."""
        seq = 0
        interval = 1.0 / attacker_rate
        while time.perf_counter() < end_at:
            try:
                reader, writer = await aio.open_connection(
                    "127.0.0.1", port)
                writer.write(F.serialize(P.Connect(
                    proto_ver=4, clientid=f"atk_{i}", clean_start=True)))
                data = await aio.wait_for(reader.read(64), 5.0)
                # CONNACK rc != 0 (BANNED maps to v3 code 5): refused
                if len(data) >= 4 and data[3] != 0:
                    out["refused"] += 1
                    writer.close()
                    await aio.sleep(0.25)
                    continue
                next_at = time.perf_counter()
                while time.perf_counter() < end_at:
                    now = time.perf_counter()
                    if now < next_at:
                        await aio.sleep(next_at - now)
                    next_at += interval
                    seq += 1
                    writer.write(F.serialize(P.Publish(
                        qos=0, topic=f"scan/{i}/{seq}", payload=b"x" * 64)))
                    out["sent"] += 1
                    if seq % 64 == 0:
                        await writer.drain()
                writer.close()
            except (ConnectionError, OSError, aio.TimeoutError,
                    aio.IncompleteReadError):
                out["dropped_conns"] += 1
                await aio.sleep(0.1)

    async def storm_loop(port: int, end_at: float, out: dict) -> None:
        """CONNECT storm: reconnect churn over 4 clientids — each one's
        connect rate is storm_rate/4, far past any honest client's."""
        j = 0
        interval = 1.0 / storm_rate
        while time.perf_counter() < end_at:
            t0 = time.perf_counter()
            try:
                reader, writer = await aio.open_connection(
                    "127.0.0.1", port)
                writer.write(F.serialize(P.Connect(
                    proto_ver=4, clientid=f"storm_{j % 4}",
                    clean_start=True)))
                data = await aio.wait_for(reader.read(64), 5.0)
                if len(data) >= 4 and data[3] != 0:
                    out["refused"] += 1
                else:
                    out["connects"] += 1
                writer.close()
            except (ConnectionError, OSError, aio.TimeoutError):
                out["dropped_conns"] += 1
            j += 1
            delay = interval - (time.perf_counter() - t0)
            if delay > 0:
                await aio.sleep(delay)

    async def run_one(admission_on: bool, with_attackers: bool):
        cfg = Config(file_text=(
            'listeners.tcp.default.bind = "127.0.0.1:0"\n'
            'broker.fanout.enable = true\n'
        ))
        cfg.put("tpu.enable", False)
        if admission_on:
            cfg.put("admission.enable", True)
            cfg.put("admission.tick", 0.25)
            cfg.put("admission.hold_ticks", 2)
            cfg.put("admission.decay_ticks", 4)
            cfg.put("admission.ban_time", 30.0)
            # thresholds: 3x the honest per-client shape, an order of
            # magnitude under the attacker's — honest headroom AND a
            # fast verdict
            cfg.put("admission.max_publish_rate", honest_rate * 3)
            cfg.put("admission.max_topic_fan", 30.0)
            cfg.put("admission.max_connect_rate", 2.0)
        node = BrokerNode(cfg)
        await node.start()
        port = node.listeners.all()[0].port
        atk: list = []
        atk_out = {"sent": 0, "refused": 0, "dropped_conns": 0}
        storm_out = {"connects": 0, "refused": 0, "dropped_conns": 0}
        try:
            if with_attackers:
                end_at = time.perf_counter() + duration + 1.0
                atk = [aio.ensure_future(
                    attacker_loop(i, port, end_at, atk_out))
                    for i in range(n_attackers)]
                atk.append(aio.ensure_future(
                    storm_loop(port, end_at, storm_out)))
            honest = await run_scenario(
                "pub", port=port, count=n_honest, rate=honest_rate,
                subscribers=n_honest, topic="bench/%i", qos=1,
                payload_size=64, duration=duration, inflight=inflight,
                callback_subs=True)
            for t in atk:
                t.cancel()
            if atk:
                await aio.gather(*atk, return_exceptions=True)
            adm = node.admission
            decisions = (adm.list_decisions(all_rows=True)
                         if adm is not None else [])
            adm_info = adm.info() if adm is not None else None
            banned_by_admission = [
                e.who for e in node.banned.list() if e.by == "admission"]
            m = node.observed.metrics
            shed = m.get("broker.admission.shed_qos0")
            bans = m.get("broker.admission.banned")
        finally:
            await node.stop()
        lat = honest.get("latency_us") or {}
        sent = honest.get("sent") or 0
        flagged = [d for d in decisions if d["level"] > 0]
        honest_flagged = [
            d["clientid"] for d in flagged
            if d["clientid"].startswith("bench_")
        ] + [w for w in banned_by_admission if w.startswith("bench_")]
        return {
            "honest": {
                "sent": sent,
                "received": honest.get("received"),
                "delivery_ratio": round(
                    (honest.get("received") or 0) / max(1, sent), 4),
                "msgs_per_s": honest.get("recv_rate"),
                "e2e_p50_us": lat.get("p50"),
                "e2e_p99_us": lat.get("p99"),
            },
            "attackers": {
                "count": n_attackers,
                "rate_per_attacker": attacker_rate,
                "sent": atk_out["sent"],
                "connects_refused": atk_out["refused"],
                "dropped_conns": atk_out["dropped_conns"],
                "storm_connects": storm_out["connects"],
                "storm_refused": storm_out["refused"],
            } if with_attackers else None,
            "admission": adm_info,
            "decisions": flagged,
            "banned_by_admission": banned_by_admission,
            "honest_flagged": honest_flagged,
            "shed_qos0": shed,
            "bans": bans,
        }

    clean = aio.run(run_one(False, False))
    attack_off = aio.run(run_one(False, True))
    attack_on = aio.run(run_one(True, True))

    clean_p99 = clean["honest"]["e2e_p99_us"] or 0.0
    on_p99 = attack_on["honest"]["e2e_p99_us"] or 0.0
    off_p99 = attack_off["honest"]["e2e_p99_us"] or 0.0
    limited = (attack_on["bans"]
               + len(attack_on["decisions"])
               + attack_on["attackers"]["connects_refused"]
               + attack_on["attackers"]["storm_refused"])
    return {
        "workload": {
            "honest_pairs": n_honest, "honest_rate": honest_rate,
            "attackers": n_attackers, "attacker_rate": attacker_rate,
            "storm_rate": storm_rate, "duration_s": duration,
        },
        "clean": clean,
        "attack_off": attack_off,
        "attack_on": attack_on,
        # the flag-off brownout ratio is the regression the gates
        # protect against — recorded, never asserted (host-dependent)
        "p99_off_vs_clean": round(off_p99 / max(clean_p99, 1e-9), 2),
        "p99_on_vs_clean": round(on_p99 / max(clean_p99, 1e-9), 2),
        "gate_honest_delivery":
            attack_on["honest"]["delivery_ratio"] == 1.0,
        "gate_honest_p99": bool(
            on_p99 <= max(1.5 * clean_p99, 50_000.0)),
        "gate_attackers_limited": bool(limited >= 1),
        "gate_no_honest_flagged":
            not attack_on["honest_flagged"],
    }


def bench_fanout_e2e(n_pub: int = 16, n_sub: int = 32, duration: float = 6.0,
                     qos: int = 1, inflight: int = 32) -> dict:
    """Publish→deliver pipeline A/B (CPU mode, host-path routing): the
    SAME fan-out workload — ``n_pub`` unpaced QoS1 publishers with a
    pipelined-ack window, ``n_sub`` wildcard (``bench/#``) subscribers so
    every publish fans out ``n_sub`` ways (the telemetry-broadcast shape
    where broker-side processing dominates) — through the per-message
    path and through the batched fanout pipeline
    (``broker.fanout.enable``).  Both runs drive the broker with lean
    template publishers and counting subscribers so the A/B measures
    broker capacity, not loadgen overhead.  Reports both runs and the
    delivered-msgs/s ratio.  delivery_ratio is received /
    (sent × n_sub): 1.0 means no fan-out leg was dropped."""
    import asyncio as aio

    from emqx_tpu.bench_client import run_scenario
    from emqx_tpu.config import Config
    from emqx_tpu.node import BrokerNode

    async def run_one(fanout: bool):
        cfg = Config(file_text=(
            'listeners.tcp.default.bind = "127.0.0.1:0"\n'
            + ('broker.fanout.enable = true\n' if fanout else '')
        ))
        cfg.put("tpu.enable", False)   # host-path e2e: no device drag
        node = BrokerNode(cfg)
        await node.start()
        try:
            out = await run_scenario(
                "pub", port=node.listeners.all()[0].port,
                count=n_pub, rate=0.0, subscribers=n_sub,
                topic="bench/%i", sub_topic="bench/#", sub_qos=0,
                qos=qos, payload_size=64, duration=duration,
                inflight=inflight, lean_subs=True, lean_pubs=True)
        finally:
            await node.stop()
        return out

    def shape(s: dict) -> dict:
        lat = s.get("latency_us") or {}
        sent = s.get("sent") or 0
        return {
            "sent": sent,
            "received": s.get("received"),
            "msgs_per_s": s.get("recv_rate"),
            "delivery_ratio": round((s.get("received") or 0)
                                    / max(1, sent * n_sub), 4),
            "e2e_p50_us": lat.get("p50"),
            "e2e_p99_us": lat.get("p99"),
        }

    per_msg = shape(aio.run(run_one(False)))
    pipeline = shape(aio.run(run_one(True)))
    return {
        "workload": {"publishers": n_pub, "subscribers": n_sub,
                     "fanout": n_sub, "qos": qos, "sub_qos": 0,
                     "inflight": inflight, "duration_s": duration},
        "per_message": per_msg,
        "pipeline": pipeline,
        "speedup": round((pipeline["msgs_per_s"] or 0.0)
                         / max(1e-9, per_msg["msgs_per_s"] or 0.0), 2),
    }


def _bench_acked_e2e(qos: int, n_pub: int, n_sub: int, duration: float,
                     inflight: int) -> dict:
    """Acknowledged-delivery A/B at QoS1 or QoS2 (shared harness for
    ``qos1_e2e`` / ``qos2_e2e``): the fan-out shape of ``fanout_e2e``
    but the subscribers take **grants with a live acknowledged
    window** — every delivered PUBLISH carries a packet id, rides the
    subscriber session's inflight/mqueue machinery, and is acked by
    the lean subscriber (PUBACK at QoS1; the full PUBREC/PUBREL/
    PUBCOMP exchange at QoS2) — so the A/B measures the batched
    inflight admission + ack-run ingest + QoS2 batch + write
    coalescing stack end to end, per-message path vs pipeline.

    delivery_ratio is received / (sent × n_sub); 1.0 means every
    fan-out leg was (eventually) delivered — the run waits for the
    queued backlog to drain through the ack window before summarizing.
    ``duplicates`` counts DUP-flagged redeliveries and must be 0: the
    session retry interval (30 s) far exceeds the run, so any DUP here
    is a broker bug, not a genuine retry."""
    import asyncio as aio

    from emqx_tpu.bench_client import run_scenario
    from emqx_tpu.config import Config
    from emqx_tpu.node import BrokerNode

    async def run_one(fanout: bool):
        cfg = Config(file_text=(
            'listeners.tcp.default.bind = "127.0.0.1:0"\n'
            + ('broker.fanout.enable = true\n' if fanout else '')
        ))
        cfg.put("tpu.enable", False)   # host-path e2e: no device drag
        # unbounded session queues: the A/B asserts delivery_ratio 1.0,
        # so backlog between instant publisher acks and the subscriber
        # ack window must park, not drop
        cfg.put("mqtt.max_mqueue_len", 0)
        # a deep acknowledged window (windowed-consumer shape): acks
        # arrive in bursts the size of a TCP read's worth of deliveries
        cfg.put("mqtt.max_inflight", 128)
        # smaller pipeline queue = backpressure: overflow publishes take
        # the synchronous path, which keeps the post-run drain bounded
        cfg.put("broker.fanout.queue_cap", 4096)
        node = BrokerNode(cfg)
        await node.start()
        try:
            out = await run_scenario(
                "pub", port=node.listeners.all()[0].port,
                count=n_pub, rate=0.0, subscribers=n_sub,
                topic="bench/%i", sub_topic="bench/#", sub_qos=qos,
                qos=qos, payload_size=64, duration=duration,
                inflight=inflight, lean_subs=True, lean_pubs=True)
        finally:
            await node.stop()
        return out

    def shape(s: dict) -> dict:
        lat = s.get("latency_us") or {}
        sent = s.get("sent") or 0
        return {
            "sent": sent,
            "received": s.get("received"),
            "msgs_per_s": s.get("recv_rate"),
            "delivery_ratio": round((s.get("received") or 0)
                                    / max(1, sent * n_sub), 4),
            "duplicates": s.get("duplicates"),
            "e2e_p50_us": lat.get("p50"),
            "e2e_p99_us": lat.get("p99"),
        }

    per_msg = shape(aio.run(run_one(False)))
    pipeline = shape(aio.run(run_one(True)))
    return {
        "workload": {"publishers": n_pub, "subscribers": n_sub,
                     "fanout": n_sub, "qos": qos, "sub_qos": qos,
                     "inflight": inflight, "duration_s": duration},
        "per_message": per_msg,
        "pipeline": pipeline,
        "speedup": round((pipeline["msgs_per_s"] or 0.0)
                         / max(1e-9, per_msg["msgs_per_s"] or 0.0), 2),
    }


def bench_qos1_e2e(n_pub: int = 8, n_sub: int = 16, duration: float = 6.0,
                   inflight: int = 32) -> dict:
    """Acknowledged QoS1 A/B (the PR-2 tracking number); see
    :func:`_bench_acked_e2e`."""
    return _bench_acked_e2e(1, n_pub, n_sub, duration, inflight)


def bench_qos2_e2e(n_pub: int = 8, n_sub: int = 16, duration: float = 6.0,
                   inflight: int = 32) -> dict:
    """Exactly-once QoS2 A/B (the PR-5 tracking number): four control
    packets per delivered message — the shape where the ack-run ingest
    fast path and the batched QoS2 state machine carry the win; see
    :func:`_bench_acked_e2e`."""
    return _bench_acked_e2e(2, n_pub, n_sub, duration, inflight)


def _fanout_e2e_size(smoke: bool) -> dict:
    return ({"n_pub": 8, "n_sub": 8, "duration": 2.0} if smoke
            else {"n_pub": 16, "n_sub": 32, "duration": 6.0})


def _qos1_e2e_size(smoke: bool) -> dict:
    return ({"n_pub": 4, "n_sub": 4, "duration": 1.5} if smoke
            else {"n_pub": 8, "n_sub": 16, "duration": 6.0})


def _qos2_e2e_size(smoke: bool) -> dict:
    return ({"n_pub": 4, "n_sub": 4, "duration": 1.5} if smoke
            else {"n_pub": 8, "n_sub": 16, "duration": 6.0})


def _config1_size(smoke: bool) -> dict:
    """One definition for both call sites (full + device-unreachable):
    diverging sizes would silently measure different workloads under
    the same result key."""
    return ({"n_clients": 10, "duration": 2.0} if smoke
            else {"n_clients": 1000, "duration": 10.0})


def _config1_sweep_size(smoke: bool) -> dict:
    return ({"counts": (8, 16), "total_rate": 200.0, "duration": 1.5}
            if smoke
            else {"counts": (1000, 5000, 10000), "total_rate": 10000.0,
                  "duration": 10.0})


SERVE_INFLIGHT = 8   # batches in flight: d2h of i overlaps compute of i+1..
# the SHIPPED serving fan-out tuning, read from the product's own
# sources of truth (match_kernel.SERVE_FLAT_MULT, config.py
# "tpu.max_matches" — mult 8 / K=128, round-5 10M measurement): the
# bench must measure the configuration the product serves with.
# Resolved lazily: bench.py imports stay function-local so --help
# works without paying (or having) jax.


def _serve_flat_mult():
    from emqx_tpu.ops.match_kernel import SERVE_FLAT_MULT
    return SERVE_FLAT_MULT


def _serve_max_matches():
    from emqx_tpu.config import SCHEMA
    return SCHEMA["tpu.max_matches"].default


def _serve_flat_cap(batch):
    return _serve_flat_mult() * batch


def _readback(r, n, k):
    """Block on a served batch's packed answer; returns (ids-per-row,
    spilled rows) of its first ``n`` rows.  ``k`` is the dispatching
    DeviceNfa's max_matches.  This is the FULL consumer-side cost:
    transfer + decode."""
    from emqx_tpu.ops.match_kernel import decode_packed

    rows, spilled = decode_packed(r, n, k)
    return rows, np.asarray(spilled, dtype=np.int64)


def _dispatch(dev, table, names, depth, batch):
    """Encode + upload + enqueue one served batch; starts the async
    device→host copy so readback overlaps later batches (d2h sits
    on the serving path)."""
    import jax.numpy as jnp

    w, l, s = _encode(table, names, depth, batch)
    r = dev.serve(jnp.asarray(w), jnp.asarray(l), jnp.asarray(s))
    try:
        r.copy_to_host_async()
    except Exception:  # noqa: BLE001 — platform without async d2h
        pass
    return r


def warm_serve(dev, table, topics, batch, depth):
    """Trigger the serving-mode jit compile OUTSIDE any timed section."""
    names = (topics[:batch] * (batch // max(1, len(topics[:batch])) + 1)
             )[:batch]
    _readback(_dispatch(dev, table, names, depth, batch), batch,
              dev.max_matches)


def calibrate_serve(dev, table, topics, batch, depth=8,
                    engine="device", seconds=2.0):
    """Measured capacity of the FULL serve path (encode + dispatch +
    pipelined readback, or host batch match) — the honest pacing basis
    for the latency harness (pacing off the raw kernel rate just
    measures queue blowup).  Uses the same SERVE_INFLIGHT overlap as the
    harness so capacity and serving measure the same machine."""
    pos = 0

    def next_names():
        # rotate through the WHOLE workload: reusing one cache-hot slice
        # inflates the host trie's capacity ~5x at 10M filters
        nonlocal pos
        ns = topics[pos:pos + batch]
        pos += batch
        if len(ns) < batch:
            ns = (ns + topics * (batch // max(1, len(topics)) + 1))[:batch]
            pos = 0
        return ns

    done = 0
    if engine == "device":
        warm_serve(dev, table, topics, batch, depth)
        inflight = []
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            inflight.append(
                _dispatch(dev, table, next_names(), depth, batch))
            if len(inflight) >= SERVE_INFLIGHT:
                _readback(inflight.pop(0), batch, dev.max_matches)
                done += batch
        for r in inflight:
            _readback(r, batch, dev.max_matches)
            done += batch
    else:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            for t in next_names():
                table.match_host(t)
            done += batch
    return done / (time.perf_counter() - t0)


def _hist_parity_ok(hist_ms: float, np_ms: float) -> bool:
    """Histogram-vs-np.percentile parity: the log2 sub-buckets bound
    the relative error at ~1/16 per octave; 12% relative (plus a tiny
    absolute floor for sub-ms values where scheduler noise dominates)
    is the honest tolerance."""
    return abs(hist_ms - np_ms) <= max(0.12 * abs(np_ms), 0.05)


def _dl_buckets(batch: int) -> List[int]:
    """Padded-batch shapes the deadline harness may dispatch (pow2 from
    ``max(256, batch/32)`` up to ``batch``) — ALL warmed before the timed
    window, so a partial flush never stalls on a cold XLA compile."""
    lo = max(256, batch >> 5)
    out = []
    b = lo
    while b < batch:
        out.append(b)
        b *= 2
    out.append(batch)
    return out


async def serve_harness(dev, table, topics, batch, target_rate,
                        seconds, depth=8, window_s=0.0002,
                        engine="device", deadline_ms=None,
                        batch_hist=None):
    """Micro-batching serving loop against a VIRTUAL open-loop arrival
    process: topic i arrives at t0 + i/rate (computing arrivals
    analytically keeps the harness out of the measurement — a Python
    per-topic producer caps out near the engine's own rate).  Batcher
    flushes on window/size, dispatch via the serving engine, host re-run
    for spilled rows; per-topic latencies are done_t - arrival_t,
    vectorized.

    ``deadline_ms`` switches the batcher to DEADLINE mode (the
    MatchService continuous-batching loop's policy): the batch bound is
    the budget's worth of arrivals after the EWMA-estimated dispatch
    time is paid, a partial batch flushes the moment the oldest
    arrival's remaining budget no longer covers a dispatch, partial
    flushes pad to the smallest pre-warmed pow2 shape, and the device
    pipeline depth drops to 2 (latency- over throughput-oriented).
    ``batch_hist`` (a dict) receives the achieved batch-size histogram
    keyed by padded shape.

    Latency accounting rides the PRODUCT's histograms (observe/hist.py
    — same buckets, same percentile extraction the broker exports via
    $SYS/REST/statsd) instead of a private parallel list: ``p50_ms``/
    ``p99_ms`` are histogram-sourced, per-stage distributions ride the
    ``stages`` dict, and ``p50_np_ms``/``p99_np_ms`` keep the legacy
    ``np.percentile`` extraction over the SAME post-warmup samples so
    the smoke can assert parity (``gate_hist_parity``).  The deadline
    estimator mirrors the serve plane's SPLIT dispatch-vs-readback
    estimate (combined EWMA as the cold fallback)."""
    from emqx_tpu.observe.hist import LatencyHistogram

    h_e2e = LatencyHistogram()
    h_wait = LatencyHistogram()
    h_disp = LatencyHistogram()
    h_rb = LatencyHistogram()
    np_lats: List[np.ndarray] = []   # same post-warmup subset (parity)
    served = [0]
    n_topics = len(topics)
    spill_reruns = 0
    consumed = 0          # arrivals taken so far
    est = [0.005]         # EWMA dispatch→answer seconds (collector feeds)
    est_d = [0.004]       # split: dispatch component (batcher feeds)
    est_r = [0.001]       # split: readback component (collector feeds)
    est_samples = [0]
    deadline_flushes = [0]

    buckets = _dl_buckets(batch) if deadline_ms is not None else [batch]
    if deadline_ms is not None and engine == "device":
        for b in buckets:   # all shapes warm BEFORE the timed window
            warm_serve(dev, table, topics, b, depth)

    def _shape_of(take: int) -> int:
        for b in buckets:
            if take <= b:
                return b
        return batch

    inflight = 2 if deadline_ms is not None else SERVE_INFLIGHT
    inflight_q: asyncio.Queue = asyncio.Queue(maxsize=inflight)
    stop_at = time.perf_counter() + seconds
    t0 = time.perf_counter()
    # histograms (and the np parity subset) record only past the
    # cold-start ramp — the time-based twin of the old len//4 trim
    warm_at = t0 + seconds * 0.25

    def _e2e_record(done_t: float, lat_arr: np.ndarray) -> None:
        served[0] += len(lat_arr)
        if done_t >= warm_at:
            h_e2e.record_many_s(lat_arr)
            np_lats.append(lat_arr)

    async def batcher():
        """Encode + dispatch; readback happens in collector so up to
        ``inflight`` batches overlap on device (matching the raw
        pipelined path — the round-2 harness synced per batch and
        measured dispatch latency, not serving capacity)."""
        nonlocal consumed, spill_reruns
        while True:
            now = time.perf_counter()
            if now >= stop_at:
                break
            arrived = int((now - t0) * target_rate)
            avail = arrived - consumed
            if avail <= 0:
                await asyncio.sleep(min(window_s, 0.001))
                continue
            oldest_age = now - (t0 + consumed / target_rate)
            if deadline_ms is not None:
                budget = deadline_ms / 1e3
                # the serve plane's split estimate: dispatch + readback
                # components (fed where each stage runs) once warm, the
                # combined EWMA as the cold fallback — queue-wait never
                # pollutes the partial-flush trigger
                est_eff = (est_d[0] + est_r[0] if est_samples[0] >= 8
                           else est[0])
                # budget term: arrivals the remaining budget can absorb.
                # sustainability floor: a batch must at least cover the
                # arrivals landing DURING one dispatch, or the loop
                # falls behind by construction and the open-loop queue
                # diverges — when the budget is infeasible at this load
                # (est >= budget/2), throughput wins over the SLO.
                bound = max(1, min(batch, max(
                    int(target_rate * max(budget - est_eff,
                                          budget * 0.25)),
                    int(target_rate * est_eff * 1.2))))
                slack = budget - est_eff - oldest_age
                if avail < bound and slack > 0:
                    await asyncio.sleep(
                        min(max(slack / 4, 0.0005), 0.005))
                    continue
                take = min(avail, bound)
                if take < bound:
                    deadline_flushes[0] += 1
                pad = _shape_of(take)
            else:
                if avail < batch and oldest_age < window_s:
                    await asyncio.sleep(window_s / 4)
                    continue
                take = min(avail, batch)
                pad = batch
            if batch_hist is not None:
                key = str(pad)
                batch_hist[key] = batch_hist.get(key, 0) + 1
            first = consumed
            consumed += take
            names = [topics[(first + j) % n_topics] for j in range(take)]
            if engine == "device":
                disp_t = time.perf_counter()
                if disp_t >= warm_at:
                    # match_wait analog: oldest arrival → dispatch start
                    h_wait.record_s(
                        max(0.0, disp_t - (t0 + first / target_rate)))
                r = await asyncio.to_thread(
                    _dispatch, dev, table, names, depth, pad)
                d_end = time.perf_counter()
                est_d[0] = est_d[0] * 0.7 + (d_end - disp_t) * 0.3
                if d_end >= warm_at:
                    h_disp.record_s(d_end - disp_t)
                await inflight_q.put((first, take, names, r, disp_t))
            else:  # cpu engine: the host trie answers the whole batch
                await asyncio.to_thread(
                    lambda: [table.match_host(t) for t in names])
                done_t = time.perf_counter()
                arr_t = t0 + (first + np.arange(take)) / target_rate
                _e2e_record(done_t, done_t - arr_t)
        await inflight_q.put(None)

    async def collector():
        nonlocal spill_reruns
        while True:
            item = await inflight_q.get()
            if item is None:
                return
            first, take, names, r, disp_t = item
            rb0 = time.perf_counter()
            ids, rows = await asyncio.to_thread(
                _readback, r, take, dev.max_matches)
            rb1 = time.perf_counter()
            est_r[0] = est_r[0] * 0.7 + (rb1 - rb0) * 0.3
            est_samples[0] += 1
            if rb1 >= warm_at:
                h_rb.record_s(rb1 - rb0)
            rows = rows[rows < take]
            if len(rows):
                spill_reruns += len(rows)
                await asyncio.to_thread(
                    lambda: [table.match_host(names[i]) for i in rows])
            done_t = time.perf_counter()
            est[0] = est[0] * 0.7 + (done_t - disp_t) * 0.3
            arr_t = t0 + (first + np.arange(take)) / target_rate
            _e2e_record(done_t, done_t - arr_t)

    await asyncio.gather(batcher(), collector())
    if not served[0]:
        return None
    out = {
        "offered_rate": int(target_rate),
        "served": served[0],
        # histogram-sourced (the product's extraction); *_np_ms is the
        # legacy np.percentile over the SAME post-warmup samples — the
        # smoke asserts the two agree (gate_hist_parity)
        "p50_ms": round(h_e2e.percentile_ms(50), 2),
        "p99_ms": round(h_e2e.percentile_ms(99), 2),
        "spill_reruns": spill_reruns,
        "stages": {
            "match_wait": h_wait.to_dict(),
            "match_dispatch": h_disp.to_dict(),
            "match_readback": h_rb.to_dict(),
        },
        "hist": h_e2e.to_dict(),
    }
    if np_lats:
        arr = np.concatenate(np_lats)
        p50np = float(np.percentile(arr, 50)) * 1e3
        p99np = float(np.percentile(arr, 99)) * 1e3
        out["p50_np_ms"] = round(p50np, 2)
        out["p99_np_ms"] = round(p99np, 2)
        out["gate_hist_parity"] = _hist_parity_ok(
            out["p50_ms"], p50np) and _hist_parity_ok(
            out["p99_ms"], p99np)
    if deadline_ms is not None:
        out["deadline_ms"] = deadline_ms
        out["deadline_flushes"] = deadline_flushes[0]
        out["served_rate"] = int(served[0] / max(seconds, 1e-9))
        # the split dispatch/readback estimates the deadline loop ran
        # with (the ROADMAP dispatch-tax (c) closure, JSON-recorded)
        out["est_dispatch_ms"] = round(est_d[0] * 1e3, 3)
        out["est_readback_ms"] = round(est_r[0] * 1e3, 3)
        out["est_combined_ms"] = round(est[0] * 1e3, 3)
        out["est_split_warm"] = est_samples[0] >= 8
    return out


def bench_serve_deadline(dev, table, topics, batch, offered_rate,
                         seconds, deadline_ms, depth=8,
                         serve_static=None):
    """A/B the deadline-mode serve loop against the static full-batch
    loop at the SAME offered load: p50/p99 + the achieved batch-size
    histogram.  ``serve_static`` reuses an already-measured static run
    (the headline ``serve_device`` section) instead of re-running it."""
    if serve_static is None:
        serve_static = asyncio.run(serve_harness(
            dev, table, topics, batch, offered_rate, seconds,
            depth=depth))
    hist: dict = {}
    dl = asyncio.run(serve_harness(
        dev, table, topics, batch, offered_rate, seconds, depth=depth,
        deadline_ms=deadline_ms, batch_hist=hist))
    out = {
        "offered_rate": int(offered_rate),
        "deadline_ms": deadline_ms,
        "batch": batch,
        "static": serve_static,
        "deadline": ({**dl, "batch_hist": hist} if dl else None),
    }
    if dl and serve_static:
        out["p99_improvement"] = round(
            serve_static["p99_ms"] / max(dl["p99_ms"], 1e-6), 2)
    return out


def bench_serve_deadline_smoke(n_filters=2000, batch=256, seconds=1.5,
                               deadline_ms=25.0, depth=8):
    """CPU-jax tiny-scale serve_deadline A/B for bench_e2e --smoke: the
    per-PR tracking number (structure + delivery, NOT the ratio — CI
    boxes make kernel-latency ratios noise)."""
    from emqx_tpu.ops.device_table import DeviceNfa

    rng = np.random.default_rng(7)
    filters, topics = build_workload(rng, n_filters, batch * 8, depth)
    table, kind, _ = build_table(filters, depth)
    dev = DeviceNfa(table, active_slots=8, compact_output=False,
                    max_matches=_serve_max_matches())
    cap = calibrate_serve(dev, table, topics, batch, depth=depth,
                          seconds=0.8)
    rate = 0.6 * cap
    out = bench_serve_deadline(dev, table, topics, batch, rate, seconds,
                               deadline_ms, depth=depth)
    out["table"] = kind
    out["n_filters"] = len(filters)
    return out


def bench_kernel_join(table, topics, batches=(256, 2048), iters=20,
                      depth=8, short_depth=4, reps=3):
    """Hash vs join vs auto kernel A/B (ISSUE 13).

    For every (batch, topic-mix) shape: dispatch the SAME encoded batch
    through the cuckoo-probe kernel and the sorted-relation join kernel
    (flat/row_meta serving mode — the readback contract both share),
    assert bit-for-bit parity, time both, then let the autotuner pick
    and time the auto route.  Gates ride the JSON for the r06
    real-hardware round: parity on every shape (CI-asserted), join
    ≥1.3× on at least one shape class, and auto within 5% of the
    better single backend on every measured shape."""
    import jax
    import jax.numpy as jnp

    from emqx_tpu.ops.device_table import DeviceNfa
    from emqx_tpu.ops.join_match import BackendAutotuner

    dev = DeviceNfa(table, active_slots=8,
                    max_matches=_serve_max_matches())
    dev.enable_join()
    short = [t for t in topics if t.count("/") < short_depth] or topics
    deep = [t for t in topics if t.count("/") >= short_depth] or topics
    tuner = BackendAutotuner(reps=reps)
    rows = []
    parity_all = True
    fields = ("matches", "n_matches", "row_meta",
              "active_overflow", "match_overflow")
    for B in batches:
        cap = _serve_flat_cap(B)
        for mix, src, d in (("short", short, short_depth),
                            ("deep", deep, depth)):
            names = (src * (B // max(1, len(src)) + 1))[:B]
            w, l, s = _encode(table, names, d, B)
            args = tuple(map(jnp.asarray, (w, l, s)))

            def run(be):
                def go():
                    r = dev.match(*args, flat_cap=cap, backend=be)
                    jax.device_get(r.row_meta)  # block to completion
                    return r
                return go

            rh, rj = run("hash")(), run("join")()
            parity = all(
                np.array_equal(np.asarray(jax.device_get(getattr(rh, f))),
                               np.asarray(jax.device_get(getattr(rj, f))))
                for f in fields)
            parity_all &= parity

            def best(go):
                t = float("inf")
                for _ in range(reps):
                    t0 = time.perf_counter()
                    for _ in range(iters):
                        go()
                    t = min(t, (time.perf_counter() - t0) / iters)
                return t

            t_hash = best(run("hash"))
            t_join = best(run("join"))
            s_, hb_, _d = table.shape_key()
            pick = tuner.measure(tuner.sig(B, d, s_, hb_),
                                 {"hash": run("hash"),
                                  "join": run("join")})
            t_auto = best(run(pick))
            rows.append({
                "batch": B, "mix": mix, "depth": d,
                "parity": bool(parity),
                "hash_us": round(t_hash * 1e6, 1),
                "join_us": round(t_join * 1e6, 1),
                "auto_us": round(t_auto * 1e6, 1),
                "auto_backend": pick,
                "join_speedup": round(t_hash / max(t_join, 1e-9), 3),
                "auto_within_5pct": bool(
                    t_auto <= 1.05 * min(t_hash, t_join)),
            })
    return {
        "rows": rows,
        "gate_parity_all": bool(parity_all),
        "best_join_speedup": max(
            (r["join_speedup"] for r in rows), default=0.0),
        "gate_join_ge_1_3x_any": bool(any(
            r["join_speedup"] >= 1.3 for r in rows)),
        "gate_auto_within_5pct": bool(all(
            r["auto_within_5pct"] for r in rows)),
        "autotune_picks": dict(tuner.picks),
    }


def bench_multichip_serve(n_filters=200_000, batch=2048, iters=10,
                          depth=8, tp=0, reps=3):
    """Multichip serve A/B (ISSUE 15): the single-chip DeviceNfa serve
    dispatch vs the table-sharded mesh backend, same filters, same
    batch.

    The mesh side shards the table by topic-prefix over dp×tp
    (parallel/multichip_serve.py) and returns service accept ids via
    the dense compact contract; the single-chip side is the serving
    path's flat readback.  Gates:

    * ``gate_hint_parity_all`` — per-topic service-aid rows agree
      BIT-FOR-BIT with the single-chip path (spilled rows re-run on
      the host tables on both sides, the serve plane's fail-open);
    * ``gate_truncation_failopen`` — at an artificially small
      max_matches the psum'd overflow flags exactly the rows whose
      true match count exceeds the cap, on both sides;
    * ``gate_shard_kill_failover`` — a killed shard raises at dispatch
      and the host tables answer the batch (delivery_ratio 1.0);
    * ``gate_scaling_ge_6x_at_8`` — topics/s mesh ≥ 6× single-chip
      with 8 real chips (meaningful ONLY on the r06 hardware round;
      host-thread CPU meshes share cores and record False — the
      ``measured_on`` field says which regime measured)."""
    import jax

    from emqx_tpu.ops import encode_batch
    from emqx_tpu.ops.device_table import DeviceNfa
    from emqx_tpu.ops.incremental import IncrementalNfa
    from emqx_tpu.ops.match_kernel import decode_packed
    from emqx_tpu.parallel.multichip_serve import (
        MultichipMatcher, ShardDead,
    )

    max_matches = _serve_max_matches()
    rng = np.random.default_rng(29)
    filters, topics = build_workload(rng, n_filters, batch * 4, depth)
    inc = IncrementalNfa(depth=depth)
    pairs = []
    for f in filters:
        try:
            inc.add(f)
            pairs.append((f, inc.aid_of(f)))
        except ValueError:
            pass   # too-deep filters stay host-side in the service too
    dev = DeviceNfa(inc, active_slots=8, max_matches=max_matches)
    mc = MultichipMatcher(depth=depth, tp=tp, active_slots=8,
                          max_matches=max_matches)
    mc.rebuild(pairs)
    mc.apply_pending()

    names = (topics * (batch // max(1, len(topics)) + 1))[:batch]

    def single_rows():
        enc = encode_batch(inc, names, batch=batch, depth=depth)
        return decode_packed(dev.serve(*enc), len(names), max_matches)

    def mesh_rows():
        enc = mc.encode(names, batch=batch, depth=depth)
        rows, sp, nbytes = mc.readback(mc.dispatch(enc), len(names))
        return rows, sp, nbytes

    rows1, sp1 = single_rows()
    rows8, sp8, d2h_bytes = mesh_rows()
    sp1s, sp8s = set(sp1), set(sp8)
    parity = True
    for i, t in enumerate(names):
        a = sorted(inc.match_host(t)) if i in sp1s else sorted(rows1[i])
        b = sorted(inc.match_host(t)) if i in sp8s else sorted(rows8[i])
        parity &= (a == b)

    # truncation fail-open: at an artificially small per-shard match
    # cap, every row the mesh did NOT flag must still be COMPLETE
    # (truncation is per shard segment; the psum'd overflow flags any
    # row where a segment clipped) — an under-approximating flag would
    # silently drop matches, the one failure mode this gate forbids
    mc_t = MultichipMatcher(depth=depth, tp=tp, active_slots=8,
                            max_matches=2)
    mc_t.rebuild(pairs)
    mc_t.apply_pending()
    enc_t = mc_t.encode(names, batch=batch, depth=depth)
    rows_t, sp_t, _ = mc_t.readback(mc_t.dispatch(enc_t), len(names))
    sp_ts = set(sp_t)
    truncation_ok = all(
        sorted(rows_t[i]) == sorted(inc.match_host(t))
        for i, t in enumerate(names) if i not in sp_ts)
    truncation_flagged = len(sp_ts)

    # timing: dispatch + readback per batch, best of reps
    def best(run):
        t = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(iters):
                run()
            t = min(t, (time.perf_counter() - t0) / iters)
        return t

    t1 = best(single_rows)
    t8 = best(mesh_rows)
    scaling = t1 / max(t8, 1e-9)
    n_devices = mc.n_devices
    platform = jax.devices()[0].platform

    # shard-kill failover: dispatch refuses, the host tables answer —
    # the serve plane's CPU fallback must reproduce exactly what the
    # mesh was serving before the kill (delivery_ratio 1.0)
    mc.kill_shard(0)
    killed_raises = False
    try:
        mc.dispatch(mc.encode(names[:4], batch=64, depth=depth))
    except ShardDead:
        killed_raises = True
    mc.revive_shard(0)
    ref4 = [sorted(inc.match_host(names[i])) if i in sp8s
            else sorted(rows8[i]) for i in range(4)]
    host4 = [sorted(inc.match_host(t)) for t in names[:4]]
    delivery_ratio = (sum(1 for a, b in zip(host4, ref4) if a == b)
                      / max(1, len(host4)))

    return {
        "n_filters": int(inc.n_filters),
        "batch": batch,
        "devices": n_devices,
        "mesh": {"dp": mc.dp, "tp": mc.tp},
        "measured_on": platform,
        "shard_filters": [sub.n_filters for sub in mc._subs],
        "single_chip_us": round(t1 * 1e6, 1),
        "mesh_us": round(t8 * 1e6, 1),
        "single_topics_per_s": round(batch / max(t1, 1e-9)),
        "mesh_topics_per_s": round(batch / max(t8, 1e-9)),
        "scaling_x": round(scaling, 3),
        "d2h_bytes_per_batch": int(d2h_bytes),
        "truncation_rows_flagged": int(truncation_flagged),
        "gate_hint_parity_all": bool(parity),
        "gate_truncation_failopen": bool(truncation_ok),
        "gate_shard_kill_failover": bool(
            killed_raises and delivery_ratio == 1.0),
        # the r06 claim: near-linear topics/s to 8 chips.  On a
        # host-thread CPU mesh every "chip" shares the same cores, so
        # this is expected False off-hardware — measured_on records
        # which regime produced the number.
        "gate_scaling_ge_6x_at_8": bool(
            n_devices == 8 and platform == "tpu" and scaling >= 6.0),
    }


def bench_multichip_serve_smoke(n_filters=2000, batch=256, depth=8):
    """CPU-mesh tiny-scale multichip_serve A/B for bench_e2e --smoke:
    the parity / truncation / shard-kill gates are the CI assertions;
    the scaling ratio is a tracking number (8 host threads on a shared
    CPU cannot show the chip scaling — bench.py's r06 round owns the
    ≥6x claim)."""
    return bench_multichip_serve(n_filters=n_filters, batch=batch,
                                 iters=3, depth=depth, reps=2)


def _multichip_serve_size(smoke: bool) -> dict:
    # full size caps the PYTHON subtable build (the mesh shards are
    # IncrementalNfa instances; 10M rides the r06 round with the
    # native-table port, tracked in ROADMAP)
    return (dict(n_filters=2000, batch=256, iters=3)
            if smoke else dict(n_filters=1_000_000, batch=2048,
                               iters=10))


def bench_multichip_ep(n_filters=200_000, batch=2048, iters=10,
                       depth=8, tp=0, reps=3, ep_slack=2.0):
    """Prefix-EP routed vs replicated multichip A/B (ISSUE 16): the
    same mesh, the same filters, the same offered load — one side
    replicates every topic row to every tp shard, the other buckets
    rows by root-token owner and all_to_all-routes them so each shard
    walks only what it owns.  Gates:

    * ``gate_routed_parity_all`` — routed service-aid rows agree
      BIT-FOR-BIT with the replicated backend (spilled rows re-run on
      the host tables on both sides);
    * ``gate_overflow_failopen`` — a root-skewed corpus overflows the
      (tp, C) bucket grid at slack 1.0; every flagged row re-runs on
      the host tables and stays COMPLETE (the dead-shard discipline);
    * ``gate_shard_width_le_batch_over_tp`` — per-shard processed
      batch width tp*C <= ceil(slack * Bl / tp): the routed step cut
      per-shard work by ~tp/slack vs the replicated Bl;
    * ``gate_shard_kill_failover`` — a killed shard raises BEFORE any
      all_to_all on the routed path; the host tables answer at
      delivery_ratio 1.0."""
    import jax

    from emqx_tpu.observe.metrics import Metrics
    from emqx_tpu.ops.incremental import IncrementalNfa
    from emqx_tpu.parallel.multichip_serve import (
        MultichipMatcher, ShardDead,
    )

    max_matches = _serve_max_matches()
    rng = np.random.default_rng(31)
    filters, topics = build_workload(rng, n_filters, batch * 4, depth)
    inc = IncrementalNfa(depth=depth)   # host oracle
    pairs = []
    for f in filters:
        try:
            inc.add(f)
            pairs.append((f, inc.aid_of(f)))
        except ValueError:
            pass

    def build(ep, slack=ep_slack):
        met = Metrics()
        mc = MultichipMatcher(depth=depth, tp=tp, active_slots=8,
                              max_matches=max_matches, metrics=met,
                              ep=ep, ep_slack=slack)
        mc.rebuild(pairs)
        mc.apply_pending()
        return mc, met

    mc_rep, _ = build(False)
    mc_ep, met = build(True)
    names = (topics * (batch // max(1, len(topics)) + 1))[:batch]

    def rows_of(mc, nm, b):
        enc = mc.encode(nm, batch=b, depth=depth)
        rows, sp, nbytes = mc.readback(mc.dispatch(enc), len(nm))
        return rows, set(sp), nbytes

    rows_r, sp_r, _ = rows_of(mc_rep, names, batch)
    rows_e, sp_e, _ = rows_of(mc_ep, names, batch)
    ici_bytes = int(met.get("tpu.match.ep_ici_bytes"))
    routed_used = met.get("tpu.match.ep_dispatches") > 0
    parity = all(
        (sorted(inc.match_host(t)) if i in sp_r else sorted(rows_r[i]))
        == (sorted(inc.match_host(t)) if i in sp_e else sorted(rows_e[i]))
        for i, t in enumerate(names))

    # overflow fail-open: every row shares one root, so one owner's
    # bucket column takes the whole source slice — at slack 1.0 the
    # grid cannot hold it, the overflowing rows are psum-flagged, and
    # the host tables keep them complete
    mc_ov, _ = build(True, slack=1.0)
    skew = [f"hot/{i}/x" for i in range(batch)]
    rows_s, sp_s, _ = rows_of(mc_ov, skew, batch)
    failopen_ok = all(
        (sorted(inc.match_host(t)) if i in sp_s else sorted(rows_s[i]))
        == sorted(inc.match_host(t)) for i, t in enumerate(skew))
    overflow_flagged = len(sp_s)

    # the width contract (per-shard processed rows, routed vs
    # replicated) — analytic, the same numbers the ep_shard_width /
    # ep_ici_bytes metrics export
    Bl = batch // mc_ep.dp
    C = mc_ep.ep_capacity(batch)
    width = mc_ep.tp * C
    gate_width = bool(
        routed_used and width <= math.ceil(ep_slack * Bl / mc_ep.tp))

    def best(run):
        t = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(iters):
                run()
            t = min(t, (time.perf_counter() - t0) / iters)
        return t

    t_rep = best(lambda: rows_of(mc_rep, names, batch))
    t_ep = best(lambda: rows_of(mc_ep, names, batch))

    # shard-kill on the routed path: the gate raises before any
    # all_to_all (a dead shard cannot answer for the roots it owns)
    mc_ep.kill_shard(0)
    killed_raises = False
    try:
        mc_ep.dispatch(mc_ep.encode(names, batch=batch, depth=depth))
    except ShardDead:
        killed_raises = True
    mc_ep.revive_shard(0)
    host4 = [sorted(inc.match_host(t)) for t in names[:4]]
    ref4 = [sorted(inc.match_host(names[i])) if i in sp_e
            else sorted(rows_e[i]) for i in range(4)]
    delivery_ratio = (sum(1 for a, b in zip(host4, ref4) if a == b)
                      / max(1, len(host4)))

    return {
        "n_filters": int(inc.n_filters),
        "batch": batch,
        "devices": mc_ep.n_devices,
        "mesh": {"dp": mc_ep.dp, "tp": mc_ep.tp},
        "measured_on": jax.devices()[0].platform,
        "native_subtables": bool(mc_ep.native),
        "ep_capacity": int(C),
        "replicated_shard_width": int(Bl),
        "routed_shard_width": int(width),
        "ici_bytes_per_batch": ici_bytes,
        "replicated_us": round(t_rep * 1e6, 1),
        "routed_us": round(t_ep * 1e6, 1),
        "replicated_topics_per_s": round(batch / max(t_rep, 1e-9)),
        "routed_topics_per_s": round(batch / max(t_ep, 1e-9)),
        # host-thread CPU meshes pay the all_to_all without the ICI
        # win, so this is a tracking number off-hardware (same
        # regime caveat as gate_scaling_ge_6x_at_8)
        "routed_speedup_x": round(t_rep / max(t_ep, 1e-9), 3),
        "overflow_rows_flagged": int(overflow_flagged),
        "gate_routed_parity_all": bool(parity and routed_used),
        "gate_overflow_failopen": bool(
            overflow_flagged > 0 and failopen_ok),
        "gate_shard_width_le_batch_over_tp": gate_width,
        "gate_shard_kill_failover": bool(
            killed_raises and delivery_ratio == 1.0),
    }


def bench_multichip_ep_smoke(n_filters=2000, batch=256, depth=8):
    """CPU-mesh tiny-scale multichip_ep A/B for bench_e2e --smoke: the
    routed-parity / overflow-fail-open / width gates are the CI
    assertions; the speedup is a tracking number (host threads share
    cores and pay the all_to_all without the per-shard width win —
    bench.py's r06 round owns the throughput claim)."""
    return bench_multichip_ep(n_filters=n_filters, batch=batch,
                              iters=3, depth=depth, reps=2)


def _multichip_ep_size(smoke: bool) -> dict:
    return (dict(n_filters=2000, batch=256, iters=3)
            if smoke else dict(n_filters=1_000_000, batch=2048,
                               iters=10))


def bench_mesh_degraded(n_filters=200_000, batch=2048, iters=10,
                        depth=8, tp=0, reps=3):
    """Degraded-mesh serve A/B (ISSUE 18): the same mesh at the same
    offered load in three regimes — healthy, one shard dead (scoped
    failover), rebuild-in-flight — then the canary re-admit round
    trip.  Gates:

    * ``gate_degraded_rows_on_device_ge_7_8ths`` — with one of tp=8
      shards dead, >= 7/8 of a root-balanced batch still serves on
      device (only the dead shard's EP-owned rows divert to the CPU
      trie; recorded False off tp=8);
    * ``gate_degraded_delivery_all`` — every degraded-batch row
      (on-device + CPU fill) agrees BIT-FOR-BIT with the host oracle:
      delivery_ratio 1.0 while degraded;
    * ``gate_readmit_zero_stale`` — after online rebuild + re-admit
      the full batch agrees bit-for-bit with the host oracle AND a
      filter added while the shard was dead (the delta tail) serves
      on-device: no stale subtable rows survive re-admission."""
    from emqx_tpu.observe.metrics import Metrics
    from emqx_tpu.ops.incremental import IncrementalNfa
    from emqx_tpu.parallel.multichip_serve import (
        MultichipMatcher, shard_of_filter,
    )

    import jax

    max_matches = _serve_max_matches()
    met = Metrics()
    if tp == 0 and len(jax.devices()) % 8 == 0:
        tp = 8     # the gate regime: dp=1 x tp=8, all chips matching
    mc = MultichipMatcher(depth=depth, tp=tp, active_slots=8,
                          max_matches=max_matches, metrics=met,
                          ep=True, degraded=True)
    tpn = mc.tp
    if tpn < 2:
        return {"skipped": f"mesh has tp={tpn}; degraded A/B needs "
                "tp >= 2 (run under a multi-device mesh)"}

    # root-balanced corpus: every shard owns the same share of the
    # batch's roots, so the on-device fraction under one dead shard is
    # exactly (tp-1)/tp when the scoped failover works
    per_owner = max(1, n_filters // (2 * tpn))
    roots: dict = {t: [] for t in range(tpn)}
    i = 0
    while any(len(v) < per_owner for v in roots.values()):
        r = f"r{i}"
        o = shard_of_filter(r, tpn)
        if len(roots[o]) < per_owner:
            roots[o].append(r)
        i += 1
    inc = IncrementalNfa(depth=depth)   # host oracle
    pairs = []

    def add(flt):
        inc.add(flt)
        pairs.append((flt, inc.aid_of(flt)))

    for o in range(tpn):
        for r in roots[o]:
            add(f"{r}/a/+")
            add(f"{r}/b/#")
    add("+/m/#")                        # one micro (replicated) filter
    mc.rebuild(pairs)
    mc.apply_pending()

    names = [f"{roots[k % tpn][(k // tpn) % per_owner]}/a/x"
             for k in range(batch)]

    def rows_of(nm):
        enc = mc.encode(nm, batch=batch, depth=depth)
        rows, sp, _ = mc.readback(mc.dispatch(enc), len(nm))
        return rows, set(sp)

    def parity(rows, sp, fill=frozenset()):
        for k, t in enumerate(names):
            host = set(inc.match_host(t))
            got = host if k in sp else set(rows[k]) | (host & fill)
            if got != host:
                return False
        return True

    def best(run):
        t = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(iters):
                run()
            t = min(t, (time.perf_counter() - t0) / iters)
        return t

    rows_h, sp_h = rows_of(names)
    healthy_ok = parity(rows_h, sp_h)
    t_h = best(lambda: rows_of(names))

    # one shard dead: its EP-owned rows divert to the CPU trie, every
    # other row stays on device; micro merge migrates off shard 0
    mc.kill_shard(0)
    rows_d, sp_d = rows_of(names)
    on_device_frac = 1.0 - len(sp_d) / max(1, batch)
    delivery_all = parity(rows_d, sp_d, fill=mc.dead_aids())
    t_d = best(lambda: rows_of(names))

    # delta lands WHILE the shard is dead: the online rebuild must
    # replay it from the live pair state (the zero-stale contract)
    delta_flt = f"{roots[0][0]}/c/+"
    add(delta_flt)
    delta_aid = pairs[-1][1]

    # rebuild-in-flight: serving continues while a worker thread
    # reconstructs the lost subtable (same offered load as above)
    import threading as _threading
    th = _threading.Thread(target=mc.rebuild_shard, args=(0, pairs))
    th.start()
    t_r = best(lambda: rows_of(names))
    th.join()

    # canary re-admit: the rebuilt shard's own topics, bit-parity vs
    # the host oracle before the shard may serve again
    ctop = mc.canary_topics(0)
    cb = 64
    while cb < len(ctop):
        cb <<= 1
    crows, csp = mc.canary_rows(ctop, cb, 0)
    csps = set(csp)
    canary_ok = all(
        set(crows[k]) == set(inc.match_host(t))
        for k, t in enumerate(ctop) if k not in csps)
    if canary_ok:
        mc.revive_shard(0)

    rows_p, sp_p = rows_of(names)
    post_ok = parity(rows_p, sp_p)
    drows, dsp = rows_of([f"{roots[0][0]}/c/z"] + names[1:])
    delta_served = 0 not in dsp and delta_aid in drows[0]

    return {
        "n_filters": int(inc.n_filters),
        "batch": batch,
        "mesh": {"dp": mc.dp, "tp": tpn},
        "devices": mc.n_devices,
        "healthy_us": round(t_h * 1e6, 1),
        "one_dead_us": round(t_d * 1e6, 1),
        "rebuild_inflight_us": round(t_r * 1e6, 1),
        "degraded_on_device_frac": round(on_device_frac, 4),
        "degraded_cpu_rows": len(sp_d),
        "degraded_batches": int(mc.degraded_batches),
        "cpu_filled_rows": int(mc.cpu_filled_rows),
        "rebuild_s": round(float(met.get("tpu.mesh.rebuild_s")), 3),
        "readmit_canary_fails": int(mc.readmit_canary_fails),
        "gate_healthy_parity_all": bool(healthy_ok),
        "gate_degraded_rows_on_device_ge_7_8ths": bool(
            tpn == 8 and on_device_frac >= 7 / 8),
        "gate_degraded_delivery_all": bool(delivery_all),
        "gate_readmit_zero_stale": bool(
            canary_ok and post_ok and delta_served),
    }


def bench_mesh_degraded_smoke(n_filters=2000, batch=256, depth=8):
    """CPU-mesh tiny-scale mesh_degraded A/B: the row-accounting /
    delivery / zero-stale gates are the CI assertions; the regime
    timings are tracking numbers (8 host threads share one CPU)."""
    return bench_mesh_degraded(n_filters=n_filters, batch=batch,
                               iters=3, depth=depth, reps=2)


def _mesh_degraded_size(smoke: bool) -> dict:
    return (dict(n_filters=2000, batch=256, iters=3)
            if smoke else dict(n_filters=1_000_000, batch=2048,
                               iters=10))


def bench_multichip_balance(n_filters=200_000, batch=2048, iters=10,
                            depth=8, tp=0, reps=3):
    """Load-adaptive match plane A/B (ISSUE 20): a root-skewed corpus
    whose hot roots all crc32-collide on shard 0, served static
    (crc32 placement, fixed bucket grid) vs adaptive (overflow-EWMA
    capacity grow + popularity rebalance) on the same mesh.  Gates:

    * ``gate_grow_zero_drops`` — the overflow EWMA triggers at least
      one background capacity grow, and EVERY row of every batch
      served through the compile window stays complete (spilled rows
      re-run on the host tables — fail-open, zero breaker strikes);
    * ``gate_balance_width_ge_1_5x`` — after one balance pass + apply,
      the worst shard's share of the batch's rows (host placement
      bincount) drops by >= 1.5x vs the static crc32 placement;
    * ``gate_routed_parity_all`` — post-remap routed rows agree
      BIT-FOR-BIT with the replicated backend (spilled rows re-run on
      the host tables on both sides);
    * ``gate_coldstart_placement_restored`` — save/load round trip
      after both the resize and the remap restores the identical
      override map and serves the skewed batch complete;
    * ``gate_rebalance_fault_noop`` — an injected ``ep.rebalance``
      fault raises BEFORE anything is staged: placement unchanged,
      the next batch delivers 1.0."""
    import tempfile

    import jax

    from emqx_tpu import faultinject as fi
    from emqx_tpu.faultinject import FaultInjector, InjectedFault
    from emqx_tpu.observe.metrics import Metrics
    from emqx_tpu.ops.incremental import IncrementalNfa
    from emqx_tpu.parallel.multichip_serve import (
        MultichipMatcher, shard_of_filter,
    )

    max_matches = _serve_max_matches()
    if tp == 0 and len(jax.devices()) % 8 == 0:
        tp = 8
    met = Metrics()
    mkw = dict(depth=depth, tp=tp, active_slots=8,
               max_matches=max_matches, ep=True, ep_slack=1.0)
    mc_ad = MultichipMatcher(metrics=met, ep_autotune=True,
                             ep_grow_threshold=0.02,
                             balance_budget=64, **mkw)
    tpn = mc_ad.tp
    if tpn < 2:
        return {"skipped": f"mesh has tp={tpn}; balance A/B needs "
                "tp >= 2 (run under a multi-device mesh)"}

    # skewed corpus: every hot root crc32-owns shard 0, plus a thin
    # root-balanced cold tail so the other shards are not empty
    n_hot = max(4, tpn)
    per_shard = max(1, n_filters // (4 * tpn))
    hot: list = []
    cold: dict = {t: [] for t in range(tpn)}
    i = 0
    while (len(hot) < n_hot
           or any(len(v) < per_shard for v in cold.values())):
        r = f"b{i}"
        o = shard_of_filter(r, tpn)
        if o == 0 and len(hot) < n_hot:
            hot.append(r)
        elif len(cold[o]) < per_shard:
            cold[o].append(r)
        i += 1
    inc = IncrementalNfa(depth=depth)   # host oracle
    pairs = []

    def add(flt):
        inc.add(flt)
        pairs.append((flt, inc.aid_of(flt)))

    for r in hot:
        add(f"{r}/a/+")
        add(f"{r}/b/#")
    for o in range(tpn):
        for r in cold[o]:
            add(f"{r}/a/+")
    mc_ad.rebuild(pairs)
    mc_ad.apply_pending()
    mc_static = MultichipMatcher(**mkw)
    mc_static.rebuild(pairs)
    mc_static.apply_pending()
    mc_rep = MultichipMatcher(depth=depth, tp=tp, active_slots=8,
                              max_matches=max_matches, ep=False)
    mc_rep.rebuild(pairs)
    mc_rep.apply_pending()

    # 7/8 of the batch lands on the hot (shard-0) roots — the static
    # placement's worst shard takes nearly the whole batch
    names = []
    for k in range(batch):
        if k % 8 != 0:
            names.append(f"{hot[k % n_hot]}/a/x")
        else:
            o = (k // 8) % tpn
            names.append(f"{cold[o][(k // (8 * tpn)) % len(cold[o])]}/a/x")

    def rows_of(mc, nm, b):
        enc = mc.encode(nm, batch=b, depth=depth)
        rows, sp, nbytes = mc.readback(mc.dispatch(enc), len(nm))
        return rows, set(sp), nbytes

    def complete(rows, sp, nm):
        return all(
            (sorted(inc.match_host(t)) if k in sp else sorted(rows[k]))
            == sorted(inc.match_host(t)) for k, t in enumerate(nm))

    def worst_width(mc):
        cnt = np.zeros(mc.tp, np.int64)
        for t in names:
            cnt[mc.shard_of(t)] += 1
        return int(cnt.max())

    # phase 1 — capacity grow under overflow: at slack 1.0 the hot
    # rows overflow shard 0's bucket column every batch; the EWMA
    # crosses the grow threshold and the grid grows in the background
    # while every batch keeps serving (fail-open) through the window
    grow_ok = True
    overflow_static = 0
    deadline = time.perf_counter() + 90.0
    while mc_ad.ep_resizes < 1 and time.perf_counter() < deadline:
        rows_g, sp_g, _ = rows_of(mc_ad, names, batch)
        overflow_static = max(overflow_static, len(sp_g))
        grow_ok = grow_ok and complete(rows_g, sp_g, names)
    while mc_ad._resize_busy and time.perf_counter() < deadline:
        time.sleep(0.01)
    rows_g, sp_g, _ = rows_of(mc_ad, names, batch)
    grow_ok = grow_ok and complete(rows_g, sp_g, names)
    gate_grow = bool(mc_ad.ep_resizes >= 1 and grow_ok
                     and mc_ad.failovers == 0)

    # phase 2 — popularity rebalance: the load slab accumulated
    # through phase 1; one balance pass stages the override map, the
    # next rebuild applies it (the compaction-swap cadence)
    moved = mc_ad.plan_rebalance()
    mc_ad.rebuild(pairs)
    mc_ad.apply_pending()
    w_static = worst_width(mc_static)
    w_ad = worst_width(mc_ad)
    ratio = w_static / max(1, w_ad)
    gate_balance = bool(moved > 0 and ratio >= 1.5)

    rows_r, sp_r, _ = rows_of(mc_rep, names, batch)
    rows_e, sp_e, _ = rows_of(mc_ad, names, batch)
    overflow_adaptive = len(sp_e)
    routed_used = met.get("tpu.match.ep_dispatches") > 0
    parity = all(
        (sorted(inc.match_host(t)) if k in sp_r else sorted(rows_r[k]))
        == (sorted(inc.match_host(t)) if k in sp_e else sorted(rows_e[k]))
        for k, t in enumerate(names))

    def best(run):
        t = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(iters):
                run()
            t = min(t, (time.perf_counter() - t0) / iters)
        return t

    t_static = best(lambda: rows_of(mc_static, names, batch))
    t_ad = best(lambda: rows_of(mc_ad, names, batch))

    # phase 3 — cold start after both resize and remap: the override
    # map round-trips through the v3 segment set and the restored
    # partition serves the same skewed batch complete
    with tempfile.TemporaryDirectory() as td:
        mc_ad.save_segments(td, epoch=3)
        mc2 = MultichipMatcher(ep_autotune=True, **mkw)
        restored = mc2.load_segments(td, expect_epoch=3)
        cold_ok = False
        if restored:
            mc2.apply_pending()
            rows_c, sp_c, _ = rows_of(mc2, names, batch)
            cold_ok = (mc2._placement == mc_ad._placement
                       and worst_width(mc2) == w_ad
                       and complete(rows_c, sp_c, names))

    # phase 4 — injected ep.rebalance fault: raises before anything
    # is staged; placement unchanged, the next batch delivers 1.0
    place_before = dict(mc_ad._placement)
    fi.install(FaultInjector([
        {"point": "ep.rebalance", "action": "raise", "times": 1}]))
    fault_raised = False
    try:
        try:
            mc_ad.plan_rebalance()
        except InjectedFault:
            fault_raised = True
    finally:
        fi.uninstall()
    rows_f, sp_f, _ = rows_of(mc_ad, names, batch)
    gate_fault = bool(fault_raised
                      and mc_ad._placement == place_before
                      and mc_ad._placement_next is None
                      and complete(rows_f, sp_f, names))

    return {
        "n_filters": int(inc.n_filters),
        "batch": batch,
        "devices": mc_ad.n_devices,
        "mesh": {"dp": mc_ad.dp, "tp": tpn},
        "measured_on": jax.devices()[0].platform,
        "hot_roots": n_hot,
        "moved_roots": int(moved),
        "placement_overrides": len(mc_ad._placement),
        "ep_resizes": int(mc_ad.ep_resizes),
        "ep_cap_class": int(mc_ad._cap_class),
        "overflow_rows_static_worst": int(overflow_static),
        "overflow_rows_adaptive": int(overflow_adaptive),
        "static_worst_width": int(w_static),
        "adaptive_worst_width": int(w_ad),
        "worst_width_ratio_x": round(ratio, 3),
        "static_us": round(t_static * 1e6, 1),
        "adaptive_us": round(t_ad * 1e6, 1),
        # host-thread CPU meshes share cores, so the speedup is a
        # tracking number off-hardware (r06 owns the throughput claim)
        "adaptive_speedup_x": round(t_static / max(t_ad, 1e-9), 3),
        "gate_grow_zero_drops": gate_grow,
        "gate_balance_width_ge_1_5x": gate_balance,
        "gate_routed_parity_all": bool(parity and routed_used),
        "gate_coldstart_placement_restored": bool(restored and cold_ok),
        "gate_rebalance_fault_noop": gate_fault,
    }


def bench_multichip_balance_smoke(n_filters=2000, batch=256, depth=8):
    """CPU-mesh tiny-scale multichip_balance A/B for bench_e2e
    --smoke: the grow/balance/parity/cold-start/fault gates are the
    CI assertions; the speedup is a tracking number (host threads
    share cores — bench.py's r06 round owns the throughput claim)."""
    return bench_multichip_balance(n_filters=n_filters, batch=batch,
                                   iters=3, depth=depth, reps=2)


def _multichip_balance_size(smoke: bool) -> dict:
    return (dict(n_filters=2000, batch=256, iters=3)
            if smoke else dict(n_filters=1_000_000, batch=2048,
                               iters=10))


def bench_mesh_chaos_smoke(n_filters=96, depth=8):
    """Node-level degraded-mesh kill→degraded→rebuild→re-admit cycle
    (ISSUE 18) — the bench_e2e --chaos ``"mesh"`` section.  Needs a
    multi-device mesh (bench_e2e isolates it in a subprocess with
    ``--xla_force_host_platform_device_count=8``); tp < 2 reports
    skipped.  One injected ``mesh.rebuild`` fault crashes the
    supervised rebuild child (the section's restarts >= 1 evidence);
    the restarted child rebuilds, canaries, and re-admits — delivery
    1.0 end to end, mesh_degraded alarm raised and cleared."""
    import asyncio

    from emqx_tpu import faultinject as fi
    from emqx_tpu.broker import SubOpts
    from emqx_tpu.broker.message import make_message
    from emqx_tpu.config import Config
    from emqx_tpu.faultinject import FaultInjector
    from emqx_tpu.node import BrokerNode

    async def settle(pred, timeout=20.0):
        loop = asyncio.get_event_loop()
        deadline = loop.time() + timeout
        while not pred() and loop.time() < deadline:
            await asyncio.sleep(0.002)
        return pred()

    async def cycle():
        cfg = Config(
            file_text='listeners.tcp.default.bind = "127.0.0.1:0"\n')
        cfg.put("tpu.enable", True)
        cfg.put("tpu.mirror_refresh_interval", 0.01)
        cfg.put("tpu.bypass_rate", 0.0)
        cfg.put("match.deadline.enable", True)
        cfg.put("match.deadline_ms", 100.0)
        cfg.put("match.multichip.enable", True)
        cfg.put("match.multichip.ep.enable", True)
        cfg.put("match.multichip.degraded.enable", True)
        cfg.put("supervisor.backoff_base", 0.005)
        cfg.put("supervisor.backoff_max", 0.05)
        node = BrokerNode(cfg)
        await node.start()
        try:
            b = node.broker
            ms = node.match_service
            mc = ms.mc if ms is not None else None
            if mc is None or mc.tp < 2:
                return {"skipped": "multichip mesh unavailable "
                        f"(tp={getattr(mc, 'tp', 0)})"}
            got = []
            b.on_deliver = lambda cid, pubs: got.extend(
                bytes(p.msg.payload) for p in pubs)
            b.open_session("sub")
            for i in range(n_filters):
                b.subscribe("sub", f"r{i}/a/+", SubOpts())
            await settle(lambda: ms.ready and mc.ready, timeout=120)

            sent = 0

            async def storm(lo, hi):
                # DISJOINT topic ranges per phase: every prefetch
                # parks a fresh waiter and dispatches (a repeated
                # topic would serve from its hint without touching
                # the mesh)
                nonlocal sent
                for i in range(lo, hi):
                    topic = f"r{i}/a/x"
                    await ms.prefetch(topic)
                    b.publish(make_message("pub", topic, b"%d" % i))
                    sent += 1

            third = n_filters // 3
            await storm(0, third)
            # one injected rebuild fault: the supervised mesh.rebuild
            # child crashes once and the supervisor restart retries
            fi.install(FaultInjector([
                {"point": "mesh.rebuild", "action": "raise",
                 "times": 1}]))
            mc.kill_shard(0)
            await storm(third, third + 3)
            # sample the degraded evidence EARLY (the rebuild child
            # may re-admit mid-storm); the flight-recorder dump is
            # the durable latch
            alarm_raised = (
                node.observed.alarms.is_active("mesh_degraded")
                or node.flightrec.last_reason == "mesh_degraded")
            await storm(third + 3, 2 * third)
            degraded_seen = mc.degraded_batches > 0
            readmitted = await settle(lambda: not mc.dead_shards,
                                      timeout=60)
            fi.uninstall()
            alarm_cleared = await settle(
                lambda: not node.observed.alarms.is_active(
                    "mesh_degraded"), timeout=30)
            await storm(2 * third, n_filters)
            await settle(lambda: len(got) >= sent, timeout=30)
            restarts = node.observed.metrics.get(
                "broker.supervisor.restarts")
            return {
                "ok": bool(len(got) == sent and restarts >= 1
                           and degraded_seen and alarm_raised
                           and readmitted and alarm_cleared
                           and mc.rebuilds >= 1),
                "delivered": len(got), "sent": sent,
                "delivery_ratio": round(len(got) / max(1, sent), 4),
                "restarts": restarts,
                "degraded_batches": int(mc.degraded_batches),
                "cpu_filled_rows": int(mc.cpu_filled_rows),
                "rebuilds": int(mc.rebuilds),
                "readmit_canary_fails": int(mc.readmit_canary_fails),
                "alarm_raised_and_cleared": bool(alarm_raised
                                                 and alarm_cleared),
                "flightrec_dumped": bool(
                    node.flightrec.last_reason == "mesh_degraded"),
                "mesh_state": mc.mesh_state(),
            }
        finally:
            fi.uninstall()
            await node.stop()

    return asyncio.run(cycle())


def bench_kernel_join_smoke(n_filters=2000, batch=256, depth=8):
    """CPU-jax tiny-scale kernel_join A/B for bench_e2e --smoke: the
    parity row is the CI gate; the ratios are tracking numbers (kernel
    timings on a loaded CPU box are noise — bench.py owns the claim)."""
    rng = np.random.default_rng(17)
    filters, topics = build_workload(rng, n_filters, batch * 8, depth)
    table, kind, _ = build_table(filters, depth)
    out = bench_kernel_join(table, topics, batches=(batch,), iters=5,
                            depth=depth, reps=2)
    out["table"] = kind
    out["n_filters"] = len(filters)
    return out


def _table_lifecycle_size(smoke: bool) -> dict:
    return (dict(n_filters=6000, seconds=1.5) if smoke
            else dict(n_filters=20000, seconds=3.0))


def bench_table_lifecycle(n_filters=20000, seconds=3.0, churn_sessions=32,
                          deadline_ms=100.0, depth=6):
    """Streaming table lifecycle A/B (ISSUE 9).

    ``cold_start``: full rebuild (per-filter add + aid_of — the
    bootstrap shape that costs 64 s at 10M, BENCH_r05) vs segment
    load + delta-tail replay.  The trie hydration that backgrounds in
    the live service is measured and reported separately, never hidden.

    ``churn``: sustained subscribe/unsubscribe against a SERVING
    deadline-mode MatchService with segments enabled and an aggressive
    compaction cadence, so the soak crosses live segment swaps; per-
    prefetch waits land in a stall histogram and the gate demands zero
    waiters past the deadline budget."""
    import asyncio as aio
    import tempfile

    from emqx_tpu.ops.incremental import IncrementalNfa
    from emqx_tpu.storage.segments import (
        load_segment, restore_incremental, save_segment,
    )

    rng = np.random.default_rng(17)
    filters, _topics = build_workload(rng, n_filters, 64, depth)
    out = {"n_filters": len(filters), "table": "python",
           "deadline_ms": deadline_ms}

    # -- cold start: rebuild vs segment load + tail replay -------------
    t0 = time.perf_counter()
    inc = IncrementalNfa(depth=depth)
    for f in filters:
        inc.add(f)
        inc.aid_of(f)
    rebuild_ms = (time.perf_counter() - t0) * 1e3
    seg_dir = tempfile.mkdtemp(prefix="bench_seg_")
    seg_path = os.path.join(seg_dir, "match_table.seg.npz")
    routing = {aid for aid, f in enumerate(inc.accept_filters)
               if f is not None}
    t0 = time.perf_counter()
    save_segment(seg_path, inc, deep={}, routing_aids=routing)
    save_ms = (time.perf_counter() - t0) * 1e3
    tail = [f"bench/tail/{i}/+" for i in range(64)]
    t0 = time.perf_counter()
    seg = load_segment(seg_path)
    inc2 = restore_incremental(seg)
    load_ms = (time.perf_counter() - t0) * 1e3
    t0 = time.perf_counter()
    inc2._hydrate()           # backgrounds in the live service
    hydrate_ms = (time.perf_counter() - t0) * 1e3
    identical = bool(
        np.array_equal(inc.node_tab, inc2.node_tab)
        and np.array_equal(inc.edge_tab, inc2.edge_tab)
        and list(inc.accept_filters) == list(inc2.accept_filters))
    t0 = time.perf_counter()
    for f in tail:            # the delta-log tail since the segment
        inc2.add(f)
    tail_ms = (time.perf_counter() - t0) * 1e3
    cold_ms = load_ms + tail_ms
    out["cold_start"] = {
        "rebuild_ms": round(rebuild_ms, 1),
        "segment_save_ms": round(save_ms, 1),
        "segment_load_ms": round(load_ms, 1),
        "tail_replayed": len(tail),
        "tail_replay_ms": round(tail_ms, 1),
        "hydrate_ms": round(hydrate_ms, 1),
        "arrays_identical": identical,
        "speedup": round(rebuild_ms / max(cold_ms, 1e-6), 1),
        "gate_cold_start_10x": bool(rebuild_ms >= 10.0 * cold_ms),
    }

    # -- churn soak across live segment swaps --------------------------
    async def soak() -> dict:
        from emqx_tpu.broker import Broker, SubOpts
        from emqx_tpu.broker.match_service import MatchService
        from emqx_tpu.observe.metrics import Metrics

        b = Broker()
        m = Metrics()
        base = filters[: min(400, len(filters))]
        for i, flt in enumerate(base):
            cid = f"s{i % churn_sessions}"
            if cid not in b.sessions:
                b.open_session(cid)
            b.subscribe(cid, flt, SubOpts())
        ms = MatchService(
            b, metrics=m, depth=depth, table="python", bypass_rate=0.0,
            deadline=True, deadline_s=deadline_ms / 1e3,
            segments=True, segments_dir=seg_dir + "_churn",
            compact_interval_s=0.3, compact_min_mutations=1,
        )
        await ms.start()
        loop = aio.get_running_loop()
        for _ in range(2000):
            if ms.ready:
                break
            await aio.sleep(0.01)
        pool = filters[400: 400 + 2000] or filters
        # warm the serve shapes OUTSIDE the timed soak (a real deploy
        # pre-warms at bootstrap; the kernel cache then keeps resizes
        # compile-free, which is what the soak measures)
        for w in range(4):
            await ms.prefetch(f"warm/{w}/x")
        waits: List[float] = []
        churn = 0
        t_end = loop.time() + seconds
        i = 0
        while loop.time() < t_end:
            for j in range(4):   # 4 mutations per serve round trip
                k = i * 4 + j
                flt = pool[k % len(pool)]
                cid = f"c{k % churn_sessions}"
                if cid not in b.sessions:
                    b.open_session(cid)
                if k % 2 == 0:
                    b.subscribe(cid, flt, SubOpts())
                else:
                    b.unsubscribe(cid, pool[(k - 1) % len(pool)])
                churn += 1
            t0 = time.perf_counter()
            await ms.prefetch(f"soak/{i}/x")
            waits.append(time.perf_counter() - t0)
            i += 1
        swaps = ms._table_gen
        compact_runs = m.get("tpu.table.compact_runs")
        dirty_rows = m.get("tpu.table.dirty_rows_uploaded")
        cache_hits = m.get("tpu.table.compile_cache_hits")
        deadline_miss = m.get("broker.match.deadline_miss")
        await ms.stop()
        edges = [5, 10, 25, 50, 100, 250, 1000]
        hist = {f"<={e}ms": 0 for e in edges}
        hist[">1000ms"] = 0
        for w in waits:
            ms_w = w * 1e3
            for e in edges:
                if ms_w <= e:
                    hist[f"<={e}ms"] += 1
                    break
            else:
                hist[">1000ms"] += 1
        # the deadline loop GATHERS up to the budget under light load
        # (PR-7 design: fill latency is spent, not saved), so a healthy
        # wait hovers at ~budget + dispatch.  A STALL is a waiter held
        # past that — the signature of a blocking rebuild/upload/
        # compile on the serve path (the pre-lifecycle failure mode).
        # On a multi-core host the build thread gets its own core, so
        # the gate tightens to the 2x-budget bound (ROADMAP
        # table-lifecycle leftover (c)); the 1-core bench VM keeps the
        # looser prefetch-timeout bound because GIL contention from the
        # compaction thread legitimately produces ~2x-budget tails.
        # The full wait histogram rides along either way so
        # budget-scale tails stay visible.
        multi_core = (os.cpu_count() or 1) > 1
        budget_bound_ms = 2.0 * deadline_ms
        timeout_bound_ms = ms.prefetch_timeout_s * 0.9 * 1e3
        stall_bound_ms = (budget_bound_ms if multi_core
                          else timeout_bound_ms)
        stalls = sum(1 for w in waits if w * 1e3 > stall_bound_ms)
        return {
            "ops": churn,
            "ops_per_s": int(churn / max(seconds, 1e-9)),
            "prefetches": len(waits),
            "worst_wait_ms": round(max(waits) * 1e3, 1) if waits else 0,
            "stall_hist": hist,
            "stall_bound_ms": round(stall_bound_ms, 1),
            # which bound gated this run (host-dependent): "2x_budget"
            # needs a core for the build thread, "prefetch_timeout" is
            # the 1-core GIL-contention fallback
            "stall_bound": ("2x_budget" if multi_core
                            else "prefetch_timeout"),
            "stalls_past_budget": stalls,
            "deadline_miss": deadline_miss,
            "segment_swaps": swaps,
            "compact_runs": compact_runs,
            "dirty_rows_uploaded": dirty_rows,
            "compile_cache_hits": cache_hits,
            "gate_zero_stalls": bool(waits and stalls == 0
                                     and swaps >= 1),
        }

    out["churn"] = asyncio.run(soak())
    return out


def bench_deltas(dev, table, n=1000):
    """Live subscribe/unsubscribe churn against the serving table:
    mutate, drain, scatter-apply on device — the <50 ms bound."""
    out = {}
    t0 = time.perf_counter()
    for i in range(n):
        table.add(f"bench/delta/{i}/+")
    out["mutate_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
    t0 = time.perf_counter()
    applied = dev.sync()
    out["drain_apply_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
    out["applied"] = bool(applied)
    out["uploads"] = dev.uploads
    out["delta_applies"] = dev.delta_applies
    t0 = time.perf_counter()
    for i in range(n):
        table.remove(f"bench/delta/{i}/+")
    dev.sync()
    out["remove_roundtrip_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--filters", type=int, default=10_000_000)
    ap.add_argument("--batch", type=int, default=8192)
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--depth", type=int, default=8)
    ap.add_argument("--active-slots", type=int, default=8)
    ap.add_argument("--cpu-budget-s", type=float, default=8.0)
    ap.add_argument("--serve-seconds", type=float, default=10.0)
    ap.add_argument("--smoke", action="store_true", help="tiny sizes, CPU ok")
    args = ap.parse_args()
    if args.smoke:
        args.filters, args.batch, args.iters = 2000, 256, 5
        args.serve_seconds = 2.0

    def note(msg):
        print(f"# [{time.perf_counter()-T0:7.1f}s] {msg}", file=sys.stderr,
              flush=True)

    T0 = time.perf_counter()

    # a missing chip is an error, never a CPU result under a device
    # metric's name (--smoke is the CPU correctness-and-counts tier)
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.smoke:
        sys.exit(f"bench.py: JAX reports platform {platform!r}, not "
                 "'tpu': no accelerator to measure (--smoke runs the "
                 "tiny CPU tier)")

    rng = np.random.default_rng(42)
    n_topics = max(args.batch * 8, 8192)
    t0 = time.perf_counter()
    filters, topics = build_workload(rng, args.filters, n_topics, args.depth)
    gen_s = time.perf_counter() - t0
    note(f"workload: {len(filters)} filters")

    table, kind, build_s = build_table(filters, args.depth)
    note(f"table built ({kind}, {build_s:.1f}s)")
    cpu = bench_cpu_native(table, topics, args.cpu_budget_s)
    cpu_py = bench_cpu_python(
        filters, topics, args.cpu_budget_s,
        max_filters=200_000 if not args.smoke else 2000)
    note(f"cpu baselines done (native {cpu['topics_per_s']:.0f}/s)")
    c1 = bench_config1(**_config1_size(args.smoke))
    note(f"config1 broker e2e done: per-message "
         f"{c1['per_message']['msgs_per_s']}/s vs pipeline "
         f"{c1['pipeline']['msgs_per_s']}/s p99="
         f"{c1['pipeline']['e2e_p99_us']}us ({c1['speedup']}x)")
    c1s = bench_config1_sweep(**_config1_sweep_size(args.smoke))
    note("config1 sweep done: " + "; ".join(
        f"{r['clients']}c {r['msgs_per_s']}/s p99={r['e2e_p99_us']}us"
        for r in c1s))
    fe = bench_fanout_e2e(**_fanout_e2e_size(args.smoke))
    note(f"fanout e2e done: per-message {fe['per_message']['msgs_per_s']}/s"
         f" vs pipeline {fe['pipeline']['msgs_per_s']}/s"
         f" ({fe['speedup']}x)")
    q1 = bench_qos1_e2e(**_qos1_e2e_size(args.smoke))
    note(f"qos1 e2e done: per-message {q1['per_message']['msgs_per_s']}/s"
         f" vs pipeline {q1['pipeline']['msgs_per_s']}/s"
         f" ({q1['speedup']}x)")
    q2 = bench_qos2_e2e(**_qos2_e2e_size(args.smoke))
    note(f"qos2 e2e done: per-message {q2['per_message']['msgs_per_s']}/s"
         f" vs pipeline {q2['pipeline']['msgs_per_s']}/s"
         f" ({q2['speedup']}x)")
    tl = bench_table_lifecycle(**_table_lifecycle_size(args.smoke))
    note(f"table lifecycle done: cold start "
         f"{tl['cold_start']['speedup']}x, churn "
         f"{tl['churn']['ops_per_s']} ops/s across "
         f"{tl['churn']['segment_swaps']} swap(s), "
         f"{tl['churn']['stalls_past_budget']} stall(s)")
    adv = bench_adversarial(**_adversarial_size(args.smoke))
    note(f"adversarial A/B done: p99 off {adv['p99_off_vs_clean']}x / "
         f"on {adv['p99_on_vs_clean']}x of clean, honest delivery "
         f"{adv['attack_on']['honest']['delivery_ratio']}, "
         f"attackers_limited={adv['gate_attackers_limited']}")

    dev, tpu = bench_device(table, topics, args.batch, args.iters,
                            args.depth, args.active_slots)
    note(f"device throughput {tpu['topics_per_s']:.0f}/s "
         f"(spill {tpu['spill_rate']})")

    # kernel backend A/B (ISSUE 13): hash vs join vs auto at the serve
    # shapes, short- and deep-topic mixes, parity-gated
    kj = bench_kernel_join(
        table, topics,
        batches=(max(256, args.batch // 8), args.batch),
        iters=max(5, args.iters // 2), depth=args.depth)
    note(f"kernel join A/B done: parity={kj['gate_parity_all']} "
         f"best_join_speedup={kj['best_join_speedup']}x "
         f"auto_within_5pct={kj['gate_auto_within_5pct']}")

    # multichip serve A/B (ISSUE 15): single-chip serve dispatch vs
    # the table-sharded mesh backend — hint parity bit-for-bit,
    # truncation psum fail-open, shard-kill failover, and the
    # gate_scaling_ge_6x_at_8 boolean for the r06 hardware round
    mcs = bench_multichip_serve(
        **_multichip_serve_size(args.smoke), depth=args.depth)
    note(f"multichip serve A/B done: parity="
         f"{mcs['gate_hint_parity_all']} scaling={mcs['scaling_x']}x "
         f"on {mcs['devices']}x{mcs['measured_on']} "
         f"ge_6x_at_8={mcs['gate_scaling_ge_6x_at_8']}")

    # prefix-EP routed vs replicated A/B (ISSUE 16): routed parity,
    # bucket-overflow fail-open, the per-shard width contract, and
    # shard-kill failover on the routed path
    mce = bench_multichip_ep(
        **_multichip_ep_size(args.smoke), depth=args.depth)
    note(f"multichip EP A/B done: parity="
         f"{mce['gate_routed_parity_all']} width="
         f"{mce['routed_shard_width']}/{mce['replicated_shard_width']} "
         f"width_gate={mce['gate_shard_width_le_batch_over_tp']}")

    # degraded-mesh A/B (ISSUE 18): healthy vs one-dead vs
    # rebuild-in-flight at equal offered load — the scoped-failover
    # row accounting, delivery 1.0 while degraded, and the zero-stale
    # re-admit gate (needs a multi-device mesh; skipped on 1 device)
    msd = bench_mesh_degraded(
        **_mesh_degraded_size(args.smoke), depth=args.depth)
    note(f"mesh degraded A/B done: on_device="
         f"{msd.get('degraded_on_device_frac')} "
         f"delivery={msd.get('gate_degraded_delivery_all')} "
         f"readmit_zero_stale={msd.get('gate_readmit_zero_stale')}"
         if "skipped" not in msd else
         f"mesh degraded A/B skipped: {msd['skipped']}")

    # load-adaptive plane A/B (ISSUE 20): overflow-EWMA capacity grow
    # with zero dropped rows, popularity rebalance worst-shard width
    # cut, post-remap parity, cold-start placement restore, and the
    # ep.rebalance fault no-op (needs a multi-device mesh)
    mcb = bench_multichip_balance(
        **_multichip_balance_size(args.smoke), depth=args.depth)
    note(f"multichip balance A/B done: width_ratio="
         f"{mcb['worst_width_ratio_x']}x resizes={mcb['ep_resizes']} "
         f"grow={mcb['gate_grow_zero_drops']} "
         f"balance={mcb['gate_balance_width_ge_1_5x']}"
         if "skipped" not in mcb else
         f"multichip balance A/B skipped: {mcb['skipped']}")

    # serving: device at 70% of its measured max; CPU at 70% of ITS max
    # through the same harness (iso-harness, each engine at its own
    # sustainable load) — the honest p99 comparison
    dev_cap = calibrate_serve(dev, table, topics, args.batch,
                              depth=args.depth)
    serve_dev = asyncio.run(serve_harness(
        dev, table, topics, args.batch, 0.7 * dev_cap, args.serve_seconds,
        depth=args.depth))
    if serve_dev:
        serve_dev["serve_capacity"] = int(dev_cap)
    note(f"device serve done: {serve_dev}")
    # half-batch pass: per-dispatch cost is kernel-dominated, so B/2
    # halves fill+pipeline latency while usually staying above the CPU's
    # whole capacity — the equal-or-higher-load p99 point
    b2 = max(256, args.batch // 2)
    dev_cap2 = calibrate_serve(dev, table, topics, b2, depth=args.depth)
    serve_dev2 = asyncio.run(serve_harness(
        dev, table, topics, b2, 0.7 * dev_cap2,
        min(args.serve_seconds, 6.0), depth=args.depth))
    if serve_dev2:
        serve_dev2["serve_capacity"] = int(dev_cap2)
        serve_dev2["batch"] = b2
    note(f"device serve (b/2) done: {serve_dev2}")
    # quarter-batch pass: the low-latency operating point — fill +
    # pipeline-depth x batch-period shrink 4x while capacity usually
    # still clears the CPU's offered load, so it stays gate-eligible
    b4 = max(256, args.batch // 4)
    serve_dev4 = None
    if b4 < b2:
        dev_cap4 = calibrate_serve(dev, table, topics, b4,
                                   depth=args.depth)
        serve_dev4 = asyncio.run(serve_harness(
            dev, table, topics, b4, 0.7 * dev_cap4,
            min(args.serve_seconds, 6.0), depth=args.depth))
        if serve_dev4:
            serve_dev4["serve_capacity"] = int(dev_cap4)
            serve_dev4["batch"] = b4
        note(f"device serve (b/4) done: {serve_dev4}")
    cpu_cap = calibrate_serve(dev, table, topics, min(args.batch, 1024),
                              depth=args.depth, engine="cpu")
    serve_cpu = asyncio.run(serve_harness(
        dev, table, topics, min(args.batch, 1024), 0.7 * cpu_cap,
        min(args.serve_seconds, 6.0), depth=args.depth, engine="cpu"))
    if serve_cpu:
        serve_cpu["serve_capacity"] = int(cpu_cap)
    note(f"cpu serve done: {serve_cpu}")
    # equal-load pass: the CPU engine driven at the DEVICE's offered
    # rate through the same harness.  Above its capacity the CPU is an
    # open-loop queue: latency grows ~linearly for the whole window, so
    # the p99 here is window-bound, not an equilibrium — that IS the
    # finding (the device sustains a load under which the CPU diverges);
    # the window length is recorded with the number.
    serve_cpu_eq = None
    if serve_dev:
        eq_s = min(args.serve_seconds, 6.0)
        serve_cpu_eq = asyncio.run(serve_harness(
            dev, table, topics, min(args.batch, 1024),
            serve_dev["offered_rate"], eq_s, depth=args.depth,
            engine="cpu"))
        if serve_cpu_eq:
            serve_cpu_eq["window_s"] = eq_s
            serve_cpu_eq["backlog_at_end"] = int(
                serve_dev["offered_rate"] * eq_s - serve_cpu_eq["served"])
        note(f"cpu serve at device load done: {serve_cpu_eq}")

    # deadline-aware serve A/B (ISSUE 7): static full-batch (the
    # serve_device run above) vs the deadline-mode continuous-batching
    # loop at the SAME offered load.  Budget = the measured CPU-iso p99
    # (the match.deadline_ms default's derivation).  The acceptance
    # gates compare against the static half/quarter-batch runs.
    serve_deadline = None
    if serve_dev:
        dl_ms = serve_cpu["p99_ms"] if serve_cpu else 41.0
        serve_deadline = bench_serve_deadline(
            dev, table, topics, args.batch, serve_dev["offered_rate"],
            min(args.serve_seconds, 6.0), dl_ms, depth=args.depth,
            serve_static=serve_dev)
        dl = serve_deadline.get("deadline")
        if dl:
            if serve_dev4:
                serve_deadline["gate_p99_le_quarter_batch"] = bool(
                    dl["p99_ms"] <= serve_dev4["p99_ms"])
            if serve_dev2:
                serve_deadline["gate_throughput_ge_half_batch"] = bool(
                    dl["served_rate"] >= 0.95 * min(
                        serve_dev2["offered_rate"],
                        serve_dev2["served"]
                        / max(1e-9, min(args.serve_seconds, 6.0))))
        note(f"serve deadline A/B done: {serve_deadline}")

    deltas = bench_deltas(dev, table)
    note("deltas done")

    mem = (table.memory_bytes() if hasattr(table, "memory_bytes") else {})
    # equal-or-higher-load gate: the device only earns a p99 ratio from
    # runs whose offered load met or beat the CPU harness's offered load
    eligible = [s for s in (serve_dev, serve_dev2, serve_dev4)
                if s and serve_cpu
                and s["offered_rate"] >= serve_cpu["offered_rate"]]
    p99_speedup = (round(serve_cpu["p99_ms"]
                         / min(s["p99_ms"] for s in eligible), 2)
                   if eligible else None)
    # both engines at the SAME offered rate (the device's): the
    # capacity-gap p99 ratio.  Window-bound when the CPU is past
    # capacity (see serve_cpu_equal_load.window_s) — reported alongside
    # the iso-load ratio, never silently substituted for it.
    p99_speedup_eq = (round(serve_cpu_eq["p99_ms"] / serve_dev["p99_ms"], 2)
                      if serve_cpu_eq and serve_dev else None)
    result = {
        "metric": "wildcard_match_throughput",
        "value": tpu["topics_per_s"],
        "unit": "topics/s/chip",
        # BOTH denominators, side by side (round-3 review: the warm
        # per-match rate and the serve-capacity rate must corroborate;
        # the weakest-denominator 9.46x claim is dead).  vs_baseline is
        # raw kernel throughput over the WARM per-match CPU rate;
        # vs_baseline_serve is end-to-end serving capacity over the CPU
        # serving capacity through the same harness.
        "vs_baseline": round(tpu["topics_per_s"] / cpu["topics_per_s"], 2),
        "vs_baseline_serve": (
            round(max(s["serve_capacity"]
                      for s in (serve_dev, serve_dev2, serve_dev4) if s)
                  / max(1, serve_cpu["serve_capacity"]), 2)
            if serve_cpu and (serve_dev or serve_dev2 or serve_dev4)
            else None
        ),
        # measured serving p99 — NOT an amortized estimate.  The
        # device side is the best p99 among device harness
        # runs whose offered load is >= the CPU's offered load, so the
        # ratio never credits the device for serving less traffic.
        "p99_speedup": p99_speedup,
        # informational ONLY: window-bound when the CPU is past
        # capacity (its open-loop queue diverges, so this ratio grows
        # with serve_seconds) — it demonstrates the capacity gap and is
        # deliberately NOT an input to the north-star boolean below
        "p99_speedup_equal_load": p99_speedup_eq,
        # the round-2 north star, answered explicitly every run from
        # the load-invariant iso/equal-eligible ratio alone
        "north_star_p99_10x": (None if p99_speedup is None
                               else bool(p99_speedup >= 10.0)),
        "throughput_speedup": (
            round(serve_dev["serve_capacity"]
                  / max(1, serve_cpu["serve_capacity"]), 2)
            if serve_cpu and serve_dev else None
        ),
        "n_filters": len(filters),
        "workload_gen_s": round(gen_s, 1),
        "table": {"kind": kind, "build_s": round(build_s, 1), **{
            k: v for k, v in mem.items()}},
        "cpu_native": {k: round(v, 3) if isinstance(v, float) else v
                       for k, v in cpu.items()},
        "cpu_python_trie": {k: round(v, 3) if isinstance(v, float) else v
                            for k, v in cpu_py.items()},
        "tpu": tpu,
        "serve_device": serve_dev,
        "serve_device_half_batch": serve_dev2,
        "serve_device_quarter_batch": serve_dev4,
        "serve_deadline": serve_deadline,
        "kernel_join": kj,
        "multichip_serve": mcs,
        "multichip_ep": mce,
        "mesh_degraded": msd,
        "multichip_balance": mcb,
        "serve_cpu_iso": serve_cpu,
        "serve_cpu_equal_load": serve_cpu_eq,
        "config1_broker_e2e": c1,
        "config1_sweep": c1s,
        "fanout_e2e": fe,
        "qos1_e2e": q1,
        "qos2_e2e": q2,
        "table_lifecycle": tl,
        "adversarial": adv,
        "delta": deltas,
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
