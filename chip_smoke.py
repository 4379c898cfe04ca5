#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path runs on the chip.

One process, no child that touches JAX.  A default ``BrokerNode`` (plus
the listener bind and ``tpu.bypass_rate = 0``) takes >= 1,000,000
distinct wildcard subscriptions (``BASELINE.json`` config 2: ``+``/``#``
mix, depth-8 tree, made from ``--seed``), mirrors them onto the device,
and serves a burst of distinct QoS-1 publishes from real TCP clients:
listener -> channel -> MatchService -> device -> Session.deliver.

Correct means, in this order: (a) every TCP subscriber received exactly
one message per (publish, own filter) pair that ``emqx_tpu.topic.match``
says matches; (b) the counters show the DEVICE answered (hints served,
no CPU fallback, no bypass, breaker closed); (c) for every published
topic the device's answer equals the host trie's over the whole table.

    python chip_smoke.py                     # one chip (what the driver runs)
    python chip_smoke.py --chips 4           # ONLY the mesh path, four chips
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse            # tiny, CPU
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python chip_smoke.py --rehearse --chips 4

Every phase prints one JSON line; the LAST line is
``{"ok": true, "device": {"platform", "kind", "count"}}`` and is printed
only when every check passed.  Any failure, or a platform other than
``tpu`` without ``--rehearse``, exits non-zero with no result line.  The
seconds printed are set-up facts, not metrics.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time
from collections import Counter

# (filters asked of the generator, distinct wildcard filters required,
#  bulk sessions, warm-up topics, measured topics, TCP publishers)
FULL = dict(ask=1_600_000, need=1_000_000, sessions=1000, warm=512,
            burst=4096, publishers=64)
TINY = dict(ask=6_000, need=3_000, sessions=50, warm=128, burst=512,
            publishers=16)
DEPTH = 8                  # BASELINE.json config 2/3 tree depth
BIND_KEY, BIND = "listeners.tcp.default.bind", "127.0.0.1:0"
TCP_SUBSCRIBERS = 8
FILTERS_PER_SUBSCRIBER = 4
MAX_MATCHES_PER_FILTER = 64   # keeps a subscriber's backlog << mqueue


class SmokeFailure(Exception):
    pass


def emit(phase: str, **kv) -> None:
    print(json.dumps({"phase": phase, **kv}), flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


async def settle(pred, timeout: float, interval: float = 0.05) -> bool:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        await asyncio.sleep(interval)
    return pred()


def distinct_topics(rng, build_workload, filters_ask: int, n: int):
    """(sorted filters, n DISTINCT topics): a repeated topic would be
    answered by the hint cache without the device seeing it."""
    filters, topics = build_workload(rng, filters_ask, 4 * n, depth=DEPTH)
    seen = dict.fromkeys(topics)
    while len(seen) < n:
        _f, more = build_workload(rng, 16, 4 * n, depth=DEPTH)
        seen.update(dict.fromkeys(more))
    return filters, list(seen)[:n]


def pick_subscriber_filters(router, T, topics, n_filters: int):
    """``n_filters`` wildcard filters of the table, each matching between
    1 and MAX_MATCHES_PER_FILTER of the measured topics (selection only —
    the expected deliveries are recomputed with ``topic.match``)."""
    picked = []
    for topic in topics:
        wild = sorted({f for f, _d in router.match_routes(topic)
                       if T.wildcard(f)} - set(picked),
                      key=lambda f: (-f.count("/"), f))
        for flt in wild:
            hits = sum(1 for t in topics if T.match(t, flt))
            if 1 <= hits <= MAX_MATCHES_PER_FILTER:
                picked.append(flt)
                break
        if len(picked) == n_filters:
            return picked
    raise SmokeFailure(
        f"only {len(picked)} of {n_filters} subscriber filters found")


async def publish_all(pubs, topics, tag: bytes) -> None:
    """Each publisher sends its slice serially at QoS 1 (the connection's
    intercept stage is serial, so a device batch holds at most one
    publish per publisher)."""
    async def one(k, c):
        for i in range(k, len(topics), len(pubs)):
            rc = await c.publish(topics[i], tag + b"%d" % i, qos=1,
                                 timeout=120.0)
            check(not rc, f"PUBACK reason {rc} for {topics[i]!r}")

    await asyncio.gather(*(one(k, c) for k, c in enumerate(pubs)))


def mesh_facts(ms, jax, table_bytes: int) -> dict:
    """Four-chip extras: the mesh is up and every device holds a shard."""
    mc = ms.mc
    check(not (ms.ready or ms.info()["ready"])
          or (mc is not None and mc.ready),
          "the service says ready and the mesh it was configured with "
          "is not up: ready has to mean the configured plane")
    check(mc is not None and mc.ready,
          "multichip matcher not ready: the one-chip path would serve")
    node_stk, edge_stk = mc._arrs[0], mc._arrs[1]
    holders = sorted(d.id for d in node_stk.sharding.device_set)
    check(len(holders) == 4 and
          len(edge_stk.sharding.device_set) == 4,
          f"stacked shard arrays live on devices {holders}, not on four")
    # the batch's operand too: put once into the step's own input
    # sharding, never on one device for the call to reshard
    operand = mc._put_operands(mc.encode(["smoke/operand"], batch=64))
    op_holders = sorted(d.id for d in operand.sharding.device_set)
    check(operand.committed and op_holders == holders,
          f"a dispatch's operand lives on devices {op_holders}, the "
          f"stacked shards on {holders}: the step would reshard it")
    shard_bytes = int(node_stk.nbytes + edge_stk.nbytes) // 4
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in jax.devices()]
    # the one-chip mirror (the fallback while the mesh is not ready)
    # stays resident on its own device beside that device's shard
    mirror_dev = next(iter(ms.dev.arrays()[0].sharding.device_set))
    shares = None
    if None not in in_use:        # the CPU backend reports no stats
        shares = [b - (table_bytes if d == mirror_dev else 0)
                  for d, b in zip(jax.devices(), in_use)]
        check(min(shares) >= shard_bytes
              and max(shares) <= 2 * min(shares),
              f"per-device bytes_in_use {in_use} (less the one-chip "
              f"mirror: {shares}), a shard is {shard_bytes}: not all "
              "four chips hold a comparable share of the table")
    return {"mesh": ms.mesh_info(), "shard_devices": holders,
            "operand_devices": op_holders,
            "shard_bytes": shard_bytes, "device_bytes_in_use": in_use,
            "one_chip_mirror_on": mirror_dev.id,
            "bytes_less_mirror": shares}


async def run(args, jax) -> None:
    import numpy as np

    import bench
    from emqx_tpu import topic as T
    from emqx_tpu.client import Client
    from emqx_tpu.config import Config
    from emqx_tpu.node import BrokerNode, enable_xla_cache
    from emqx_tpu.ops.match_kernel import nfa_match_packed

    size = TINY if args.rehearse else FULL
    enable_xla_cache()
    cache_dir = jax.config.jax_compilation_cache_dir or ""
    cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    # -- table ---------------------------------------------------------
    t0 = time.perf_counter()
    rng = np.random.default_rng(args.seed)
    filters, topics = distinct_topics(
        rng, bench.build_workload, size["ask"], size["warm"] + size["burst"])
    warm_topics, burst = topics[:size["warm"]], topics[size["warm"]:]
    n_wild = sum(1 for f in filters if T.wildcard(f))
    gen_s = time.perf_counter() - t0
    check(n_wild >= size["need"],
          f"{n_wild} distinct wildcard filters < {size['need']}")
    check(len(set(burst)) == size["burst"], "measured topics not distinct")

    puts = {"tpu.enable": True, "tpu.bypass_rate": 0.0}
    if args.chips == 4:
        puts.update({"match.multichip.enable": True,
                     "match.multichip.tp": 4,
                     "match.multichip.ep.enable": True})
    cfg = Config(file_text=f'{BIND_KEY} = "{BIND}"\n')
    for k, v in puts.items():
        cfg.put(k, v)
    sets = {BIND_KEY: BIND, **puts}
    node = BrokerNode(cfg)
    b = node.broker
    t0 = time.perf_counter()
    for s in range(size["sessions"]):
        b.open_session(f"bulk{s}")
    for i, flt in enumerate(filters):
        b.subscribe(f"bulk{i % size['sessions']}", flt)
    sub_s = time.perf_counter() - t0
    emit("table", seed=args.seed, config_set=sets, filters=len(filters),
         wildcard_filters=n_wild, sessions=size["sessions"],
         generate_s=round(gen_s, 1), subscribe_s=round(sub_s, 1),
         xla_cache={"dir": cache_dir, "entries_at_start": cached})

    clients = []
    try:
        # -- start: mirror upload + the two depth-lane compiles ---------
        t0 = time.perf_counter()
        await node.start()
        start_s = time.perf_counter() - t0
        ms = node.match_service
        check(ms is not None,
              "node.match_service is None: MatchService failed to start "
              "and the host trie serves (see the log above)")
        check(await settle(lambda: ms.ready, 600.0),
              "device mirror not ready after 600 s")
        ready_s = time.perf_counter() - t0
        port = node.listeners.all()[0].port
        m = node.observed.metrics

        subs = []
        own = pick_subscriber_filters(
            b.router, T, burst, TCP_SUBSCRIBERS * FILTERS_PER_SUBSCRIBER)
        for s in range(TCP_SUBSCRIBERS):
            c = Client(clientid=f"sub{s}", port=port)
            await c.connect()
            clients.append(c)
            mine = own[s::TCP_SUBSCRIBERS]
            rcs = await c.subscribe([(f, 1) for f in mine])
            check(all(rc < 0x80 for rc in rcs), f"SUBACK {rcs}")
            subs.append((c, mine))
        pubs = []
        for p in range(size["publishers"]):
            c = Client(clientid=f"pub{p}", port=port)
            await c.connect()
            clients.append(c)
            pubs.append(c)
        check(await settle(
            lambda: ms.ready and ms._seen_epoch == b.router.epoch
            and ms.dev.epoch == ms.inc.epoch, 120.0),
            "device mirror did not catch up with the router")

        info = ms.info()
        node_tab, edge_tab = ms.dev.arrays()[:2]
        table_bytes = int(node_tab.nbytes + edge_tab.nbytes)
        stats = jax.devices()[0].memory_stats()
        in_use = None if stats is None else int(stats["bytes_in_use"])
        emit("mirror", ready=info["ready"], breaker=info["breaker"],
             filters=info["filters"], states=info["states"],
             uploads=info["uploads"], backend=info["backend"],
             table_kind=ms.table_kind, table_bytes=table_bytes,
             node_tab=list(node_tab.shape), edge_tab=list(edge_tab.shape),
             device_bytes_in_use=in_use, start_s=round(start_s, 1),
             ready_s=round(ready_s, 1))
        check(info["ready"] and info["breaker"] == "closed",
              f"mirror not serving: {info['ready']=} {info['breaker']=}")
        check(info["filters"] >= size["need"], "too few filters on device")
        check(info["uploads"] >= 1, "no table upload happened")
        check(ms.table_kind == "native",
              "python table twin in use: g++ is missing, the native NFA "
              "table did not build")
        if in_use is None:
            check(args.rehearse, "device reports no memory stats")
        else:
            check(in_use >= table_bytes,
                  f"device holds {in_use} B < the table's {table_bytes} B")
        mesh = mesh_facts(ms, jax, table_bytes) if args.chips == 4 else None
        if mesh is not None:
            emit("mesh", **mesh)

        # -- warm-up: every shape the burst can dispatch -----------------
        t0 = time.perf_counter()
        await publish_all(pubs, warm_topics, b"w:")
        check(await settle(lambda: m.get("tpu.match.batches") > 0, 60.0),
              "warm-up moved no device batch")
        emit("warmup", publishes=len(warm_topics),
             seconds=round(time.perf_counter() - t0, 1),
             compiled_match_shapes=nfa_match_packed._cache_size(),
             batches=m.get("tpu.match.batches"))
        check(nfa_match_packed._cache_size() >= 2,
              "the two depth lanes did not both compile")
        snap = dict(m.all())

        # -- traffic -----------------------------------------------------
        t0 = time.perf_counter()
        await publish_all(pubs, burst, b"m:")
        burst_s = time.perf_counter() - t0

        # (a) deliveries, against topic.match alone
        want = [Counter(i for i, t in enumerate(burst) for f in mine
                        if T.match(t, f)) for _c, mine in subs]
        n_want = sum(sum(w.values()) for w in want)
        got = [Counter() for _ in subs]

        def drain() -> int:
            for (c, _mine), g in zip(subs, got):
                while not c.messages.empty():
                    msg = c.messages.get_nowait()
                    if msg.payload.startswith(b"m:"):
                        g[int(msg.payload[2:])] += 1
            return sum(sum(g.values()) for g in got)

        await settle(lambda: drain() >= n_want, 60.0)
        await asyncio.sleep(0.5)          # anything extra still in flight
        n_got = drain()
        missing = sum(sum((w - g).values()) for w, g in zip(want, got))
        extra = sum(sum((g - w).values()) for w, g in zip(want, got))
        emit("deliveries", publishes=len(burst), expected=n_want,
             received=n_got, missing=missing, extra=extra,
             seconds=round(burst_s, 2))
        check(n_want > 0, "no delivery was expected: nothing was checked")
        check(missing == 0 and extra == 0,
              f"{missing} deliveries missing, {extra} extra")

        # (b) the device answered, by the counters
        now = m.all()
        d = {k: now.get(k, 0) - snap.get(k, 0) for k in (
            "tpu.match.hint_served", "tpu.match.hint_stale",
            "tpu.match.prefetch_timeout", "broker.match.cpu_fallback",
            "tpu.match.bypass", "tpu.match.batches", "tpu.match.topics",
            "tpu.match.fallback_host", "tpu.match.ep_dispatches",
            "tpu.match.ep_overflow_rows")}
        n = len(burst)
        unserved = n - d["tpu.match.hint_served"]
        accounted = (d["tpu.match.hint_stale"]
                     + d["tpu.match.prefetch_timeout"])
        info = ms.info()
        emit("counters", publishes=n, delta=d, unserved=unserved,
             mean_topics_per_batch=round(
                 d["tpu.match.topics"] / max(1, d["tpu.match.batches"]), 1),
             fallback_host_share=round(
                 d["tpu.match.fallback_host"]
                 / max(1, d["tpu.match.topics"]), 4),
             breaker=info["breaker"],
             mesh_state=(m.get("tpu.mesh.state")
                         if args.chips == 4 else None))
        check(d["tpu.match.hint_served"] >= 0.99 * n,
              f"device hints served {d['tpu.match.hint_served']} of {n}")
        check(unserved <= accounted,
              f"{unserved - accounted} publishes fell to the host trie "
              "with no counter saying why")
        check(d["broker.match.cpu_fallback"] == 0
              and d["tpu.match.bypass"] == 0,
              "cpu_fallback or bypass moved during the burst")
        check(d["tpu.match.batches"] > 0 and d["tpu.match.topics"] > 0,
              "no device batch during the burst")
        check(info["breaker"] == "closed", "breaker opened")
        if args.chips == 4:
            check(ms.mc is not None and ms.mc.ready,
                  "multichip matcher dropped out during the burst")
            check(d["tpu.match.ep_dispatches"] > 0,
                  "no EP-routed dispatch: the mesh did not serve")

        # (c) device answer == host trie answer, whole table, every topic
        misses = []
        for t in burst:
            dev_routes = b.device_match(t)
            if dev_routes is None or \
                    set(dev_routes) != set(b.router.match_routes(t)):
                misses.append(t)
        emit("parity", topics=len(burst), mismatches=len(misses),
             first=misses[:3])
        check(not misses, f"{len(misses)} topics: device != host trie")
    finally:
        for c in clients:
            try:
                await c.close()
            except Exception:  # noqa: BLE001 — teardown only
                pass
        await node.stop()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes; a CPU backend is allowed")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs ONLY the multichip mesh path")
    args = ap.parse_args(argv)

    import jax  # after the arguments: --help must not claim the chip

    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX reports platform {platform!r}, not 'tpu' "
              "(no accelerator; --rehearse allows a CPU run)",
              file=sys.stderr)
        return 2
    if args.chips == 4 and len(devs) != 4:
        print(f"chip_smoke: --chips 4 needs exactly four devices, JAX "
              f"reports {len(devs)}", file=sys.stderr)
        return 2
    try:
        asyncio.run(run(args, jax))
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
