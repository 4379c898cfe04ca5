#!/usr/bin/env python3
"""cellbench — run one cell of ``BENCHMARK.json`` once.

    python3 cellbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip: it builds the cell's deployment from
``--seed``, starts a real ``BrokerNode`` (the node's default configuration
plus what the configuration's file lists), waits for the device mirror,
and only then starts the load generator, child processes that never
import JAX (``loadgen.py``).  Inside the window only the generator's
sockets drive the node.  The last line of standard output is the result
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``; ``compared`` comes last).

Everything that belongs to one cell is data found by name: the
configuration's file (``configs``), the traffic mix
(``cellbench/traffic/<traffic>.json``), the table generator
(``cellbench/tables/<generator>.py``) and each per-layer metric
(``cellbench/layer_metrics/<metric>.json``).  No cell, configuration or
metric is named in this file.

Not cells: ``--rehearse`` (tiny sizes from the files' own ``rehearse``
blocks, CPU allowed, for tests), ``--sweep-rates`` (several windows on one
set-up, seed + i each, for the sweep tables in PERF.md), ``--control
<name>`` (the reference with one guarantee broken in the program's place:
has to come out as not correct).
"""

from __future__ import annotations

import os
import sys
import time

if __name__ == "__main__" and os.environ.get("PYTHONHASHSEED") != "0":
    # str hashes are salted per process; with 10^6 filters in dicts that
    # moves the run's speed from process to process.  One fixed salt for
    # the node and its generators: the same work from the same seed.
    os.execve(sys.executable, [sys.executable] + sys.argv,
              dict(os.environ, PYTHONHASHSEED="0"))

T_PROCESS = time.monotonic()    # process start, as near as Python gets

import argparse                 # noqa: E402
import asyncio                  # noqa: E402
import copy                     # noqa: E402
import gc                       # noqa: E402
import importlib                # noqa: E402
import json                     # noqa: E402
import resource                 # noqa: E402
import shutil                   # noqa: E402
import tempfile                 # noqa: E402

import numpy as np              # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from cellbench import plan as P             # noqa: E402
from cellbench import reduce as R           # noqa: E402
from cellbench import reduce_trace as RT    # noqa: E402
from cellbench import reference as REF      # noqa: E402
from cellbench import rooflines as ROOF     # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
LOWERED_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
now_ns = time.monotonic_ns


class BenchError(Exception):
    pass


def note(msg: str) -> None:
    print(f"cellbench: {msg}", file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def merged(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def rng_of(seed: int, *stream: int):
    return np.random.default_rng([seed, *stream])


class Compiles:
    """Backend compiles (or loads from the persistent cache) as JAX's own
    monitoring reports them: times on the monotonic clock."""

    def __init__(self) -> None:
        from jax import monitoring

        self.at = []
        self.lowered = 0        # a lowering ends where its compile begins
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _duration, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.at.append(now_ns())
        elif event == LOWERED_EVENT:
            self.lowered += 1

    def busy(self) -> bool:
        """A backend compile has begun and not ended."""
        return self.lowered > len(self.at)

    def between(self, t0: int, t1: int) -> int:
        return sum(1 for t in self.at if t0 <= t < t1)


class HostStalls:
    """What the host's runtime did to the one event loop, seen from
    outside the program: garbage-collector pauses (``gc.callbacks``) and
    how late a 10 ms timer of the harness's own came back."""

    def __init__(self) -> None:
        self.gc = []            # (start_ns, duration_ns, generation)
        self.lag = []           # (at_ns, overshoot_ns) of late timers
        self._t = 0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info) -> None:
        if phase == "start":
            self._t = now_ns()
        else:
            self.gc.append((self._t, now_ns() - self._t, info["generation"]))

    async def watch(self) -> None:
        tick = int(10e6)
        while True:
            t = now_ns()
            await asyncio.sleep(tick / 1e9)
            self.lag.append((t, now_ns() - t - tick))

    def window(self, t0: int, t1: int) -> dict:
        gcs = [(s, d, g) for s, d, g in self.gc if t0 <= s < t1]
        lags = [(s, d) for s, d in self.lag if t0 <= s < t1]
        return {
            "gc_pause_ms": sum(d for _s, d, _g in gcs) / 1e6,
            "gc_pause_max_ms": max([d for _s, d, _g in gcs] + [0]) / 1e6,
            "gc_full_collections": sum(1 for _s, _d, g in gcs if g == 2),
            "loop_stall_max_ms": max([d for _s, d in lags] + [0]) / 1e6,
            "loop_stalls_over_100ms": [
                [round((s - t0) / 1e9, 3), round(d / 1e6, 1)]
                for s, d in lags if d > 100e6][:20],
            "gc_over_50ms": [
                [round((s - t0) / 1e9, 3), round(d / 1e6, 1), g]
                for s, d, g in gcs if d > 50e6][:20],
        }


# ---------------------------------------------------------------------------
# the deployment: table + node, made from the seed
# ---------------------------------------------------------------------------

class Deployment:
    def __init__(self, cfg: dict, seed: int) -> None:
        self.cfg, self.seed = cfg, seed
        self.used = set()       # topics already published in this run
        self.phases = {}
        self.node = None

    async def start(self) -> None:
        from emqx_tpu.config import Config
        from emqx_tpu.node import BrokerNode, enable_xla_cache

        cfg = self.cfg
        enable_xla_cache()
        t = time.monotonic()
        gen = importlib.import_module(
            f"cellbench.tables.{cfg['table']['generator']}")
        self.n_pub = int(cfg["publishers"])
        self.table = gen.build(rng_of(self.seed, 1), cfg["table"]["params"],
                               self.n_pub)
        self.phases["table_s"] = time.monotonic() - t

        node_cfg = dict(cfg["node_config"])
        bind_key = next(k for k in node_cfg if k.endswith(".bind"))
        conf = Config(
            file_text=f'{bind_key} = "{node_cfg.pop(bind_key)}"\n')
        for k, v in node_cfg.items():
            conf.put(k, v)
        self.node = node = BrokerNode(conf)
        t = time.monotonic()
        sessions = int(cfg.get("bulk_sessions", 0))
        b = node.broker
        for s in range(sessions):
            b.open_session(f"bulk{s}")
        for i, flt in enumerate(self.table.filters):
            b.subscribe(f"bulk{i % sessions}", flt)
        self.phases["subscribe_s"] = time.monotonic() - t

        t = time.monotonic()
        await node.start()
        self.ms = ms = node.match_service
        if ms is None:
            raise BenchError("node.match_service is None: the device match "
                             "service did not start, the host trie serves")
        await self.settle(lambda: ms.ready, 600.0, "device mirror not ready")
        self.phases["node_ready_s"] = time.monotonic() - t
        self.port = node.listeners.all()[0].port
        self.metrics = node.observed.metrics
        # every filter the router will hold, for the plain reference
        self.filter_set = set(self.table.filters) | set(
            self.table.tcp_filters)

    async def settle(self, pred, timeout: float, what: str) -> None:
        end = time.monotonic() + timeout
        while not pred():
            if time.monotonic() > end:
                raise BenchError(f"{what} after {timeout:.0f} s")
            await asyncio.sleep(0.05)

    async def mirror_caught_up(self) -> None:
        def ok():
            i = self.ms.info()
            return i["ready"] and i["synced_epoch"] == i["router_epoch"]
        await self.settle(ok, 120.0,
                          "device mirror did not catch up with the router")

    def hist_counts(self) -> dict:
        out = {}
        for hs in self.node.hist_sets():
            for name in hs.names():
                c = hs.hist(name).snapshot()
                prev = out.get(name)
                out[name] = c if prev is None else [
                    a + b for a, b in zip(prev, c)]
        return out

    async def stop(self) -> None:
        if self.node is not None:
            await self.node.stop()


# ---------------------------------------------------------------------------
# one window: plan -> workers -> raw arrays
# ---------------------------------------------------------------------------

def merge_schedules(a: P.Schedule, b: P.Schedule) -> P.Schedule:
    due = np.concatenate([a.due, b.due])
    order = np.argsort(due, kind="stable")
    return P.Schedule(
        due[order], np.concatenate([a.pub, b.pub])[order],
        a.topics + b.topics,
        np.concatenate([a.topic, b.topic + len(a.topics)])[order])


def write_plans(tmp, dep, mix, warm, win, expected, fault):
    """One plan directory per worker; publishers and subscribers are
    dealt round-robin.  Returns the directories."""
    n_workers = int(mix.get("workers", 1))
    qos = int(mix["qos"])
    dirs = []
    for w in range(n_workers):
        d = os.path.join(tmp, f"w{w}")
        os.mkdir(d)
        pubs = [g for g in range(dep.n_pub) if g % n_workers == w]
        local = {g: i for i, g in enumerate(pubs)}
        subs = [[g, f, int(dep.cfg.get("subscriber_qos", qos))]
                for g, f in enumerate(dep.table.tcp_filters)
                if g % n_workers == w]
        mine = {g for g, _f, _q in subs}
        arrays = {}
        for name, sch in (("warm", warm), ("win", win)):
            keep = np.flatnonzero(sch.pub % n_workers == w)
            used = sorted(set(sch.topic[keep].tolist()))
            where = {t: i for i, t in enumerate(used)}
            arrays[f"{name}_due"] = sch.due[keep]
            arrays[f"{name}_pub"] = np.asarray(
                [local[int(g)] for g in sch.pub[keep]], np.int32)
            arrays[f"{name}_seq"] = keep.astype(np.int64)
            arrays[f"{name}_topic"] = np.asarray(
                [where[int(t)] for t in sch.topic[keep]], np.int32)
            with open(os.path.join(d, f"{name}_topics.txt"), "w") as f:
                f.write("\n".join(sch.topics[t] for t in used))
        np.savez(os.path.join(d, "plan.npz"), **arrays)
        meta = {
            "host": "127.0.0.1", "port": dep.port, "worker": w,
            "qos": qos, "payload_bytes": int(mix["payload_bytes"]),
            "publishers": pubs, "subscribers": subs,
            "expect_window": sum(1 for subs_i in expected
                                 for s in subs_i if s in mine),
            "drain_max_s": float(mix["drain_max_s"]),
            "linger_s": float(mix["linger_s"]), **fault}
        with open(os.path.join(d, "plan.json"), "w") as f:
            json.dump(meta, f)
        dirs.append(d)
    return dirs


class Workers:
    def __init__(self) -> None:
        self.procs = []
        self.readers = []
        self.said = []          # per worker: the words it has said
        self.progress = []      # per worker: (sent, acked) of the warm-up

    async def spawn(self, dirs) -> None:
        for d in dirs:
            p = await asyncio.create_subprocess_exec(
                sys.executable, os.path.join(HERE, "loadgen.py"), d,
                stdin=asyncio.subprocess.PIPE,
                stdout=asyncio.subprocess.PIPE)
            self.procs.append(p)
            self.said.append(set())
            self.progress.append((0, 0))
            self.readers.append(asyncio.ensure_future(
                self._read(len(self.procs) - 1, p)))

    async def _read(self, k: int, p) -> None:
        while True:
            words = (await p.stdout.readline()).split()
            if not words:
                self.said[k].add(b"eof")
                return
            if words[0] == b"p":
                self.progress[k] = (int(words[1]), int(words[2]))
            else:
                self.said[k].add(words[0])

    async def expect(self, word: str, timeout: float) -> None:
        end = time.monotonic() + timeout
        while not all(word.encode() in s for s in self.said):
            if any(b"eof" in s for s in self.said):
                raise BenchError(f"a load generator ended before {word!r}")
            if time.monotonic() > end:
                raise BenchError(f"load generators not {word!r} "
                                 f"after {timeout:.0f} s")
            await asyncio.sleep(0.02)

    def lag(self) -> int:
        """Warm-up publishes sent and not yet acknowledged."""
        return sum(s - a for s, a in self.progress)

    def tell(self, line: str) -> None:
        for p in self.procs:
            p.stdin.write(line.encode() + b"\n")

    async def close(self) -> None:
        """Stop every worker and wait until each has ended."""
        for p in self.procs:
            if p.returncode is None:
                try:
                    p.stdin.close()
                except (OSError, RuntimeError):
                    pass
        for p in self.procs:
            try:
                await asyncio.wait_for(p.wait(), 5.0)
            except asyncio.TimeoutError:
                p.kill()
                await p.wait()
        for r in self.readers:
            r.cancel()


async def at(t_ns: int) -> None:
    await asyncio.sleep(max(0.0, (t_ns - now_ns()) / 1e9))


async def measure(dep, mix, rate, seconds, seed, trace, control, compiles,
                  stalls, peak):
    """One warm-up + window + drain on a running deployment.  Returns the
    raw material for ``result_of``."""
    rng = rng_of(seed, 2)
    warm_cfg = mix["warmup"]
    warm = P.schedule(rng, mix, dep.table, rate, float(warm_cfg["max_s"]),
                      dep.n_pub, dep.used)
    for k, size in enumerate(warm_cfg.get("volleys", [])):
        # `size` publishes due at one instant, one a connection: the
        # backlog a stall would leave, so that every batch bucket the
        # window can meet is compiled before it
        v = P.volley(rng, mix, dep.table, int(size), dep.n_pub, dep.used)
        v.due += int((1.0 + k * float(warm_cfg["volley_every_s"])) * 1e9)
        warm = merge_schedules(warm, v)
    win = P.schedule(rng, mix, dep.table, rate, seconds, dep.n_pub,
                     dep.used)
    win_topics = [win.topic_of(i) for i in range(len(win))]
    expected = REF.expected_deliveries(win_topics, dep.table.tcp_filters)

    fault = {k: v for k, v in (control or {}).items()
             if k == "drop_delivery_every"}
    tmp = tempfile.mkdtemp(prefix="cellbench-")
    workers = Workers()
    out = {"rate": rate, "seconds": seconds, "seed": seed,
           "traced": bool(trace)}
    loop = asyncio.get_running_loop()
    try:
        dirs = write_plans(tmp, dep, mix, warm, win, expected, fault)
        await workers.spawn(dirs)
        await workers.expect("connected", 180.0)
        await dep.mirror_caught_up()

        w0 = now_ns() + int(0.2e9)
        workers.tell(f"warm {w0}")
        min_ns, quiet_ns = (int(float(warm_cfg[k]) * 1e9)
                            for k in ("min_s", "quiet_s"))
        max_ns = int((float(warm_cfg["max_s"]) - 1.0) * 1e9)
        # steady before the window: for quiet_s no compile has ended or
        # is under way and the broker has kept pace with the schedule
        # (fewer than pace_s seconds of publishes unacknowledged)
        behind = max(20, int(rate * float(warm_cfg["pace_s"])))

        async def steady(not_before: int) -> bool:
            unsteady = not_before
            while True:
                await asyncio.sleep(0.1)
                now = now_ns()
                if compiles.busy() or workers.lag() > behind:
                    unsteady = now
                last = max([unsteady] + compiles.at)
                if now - w0 >= max_ns:
                    return False
                if now - w0 >= min_ns and now - last >= quiet_ns:
                    return True

        ok = await steady(w0)
        if ok:
            # A full collection over the broker's heap stops the one event
            # loop for seconds at the 1M-filter size and comes round once
            # in a minute or two of traffic: a window of run_seconds would
            # catch it on some seeds and not on others.  So one is made
            # here, in set-up, where its length is read (full_gc_pause_ms)
            # and the collector's counters start every window alike; the
            # warm-up then runs on until the backlog it left is gone.
            t = now_ns()
            gc.collect()
            out["full_gc_pause_ms"] = (now_ns() - t) / 1e6
            ok = await steady(now_ns())
        out["warmup_steady"] = ok
        t0 = now_ns() + int(0.3e9)
        t1 = t0 + int(seconds * 1e9)
        workers.tell(f"go {t0}")
        out["warmup_s"] = (t0 - w0) / 1e9
        await at(t0)
        note(f"window open for {seconds:g} s")
        out["window_start_after_process_s"] = time.monotonic() - T_PROCESS
        c0, h0 = dep.metrics.all(), dep.hist_counts()

        tr = None
        if trace:
            import jax

            slice_s = min(float(mix["trace_slice_s"]), seconds / 2)
            ta = t1 - int(slice_s * 1e9)
            await at(ta - int(0.3e9))
            tdir = os.path.join(tmp, "trace")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            await loop.run_in_executor(
                None, lambda: jax.profiler.start_trace(
                    tdir, profiler_options=opts))
            # the slice also on the clock of the stage events' ``t_ns``
            ta, pa = now_ns(), time.perf_counter_ns()
            await at(t1)
            tb, pb = now_ns(), time.perf_counter_ns()
            c1, h1 = dep.metrics.all(), dep.hist_counts()
            await loop.run_in_executor(None, jax.profiler.stop_trace)
            tr = (tdir, ta, tb, pa, pb)
        else:
            await at(t1)
            c1, h1 = dep.metrics.all(), dep.hist_counts()
        out["compiles_in_window"] = compiles.between(t0, t1)
        out["host"] = stalls.window(t0, t1)
        stats = peak()
        await workers.expect(
            "done", float(mix["drain_max_s"]) + float(mix["linger_s"]) + 60)
        res = [np.load(os.path.join(d, "result.npz")) for d in dirs]
        # while the subscribers are still connected: routes resolve live
        out["device_answers"] = device_answers(
            dep, win_topics, seed, int(dep.cfg["device_answer_sample"]),
            control)
    finally:
        await workers.close()
    try:
        out.update(t0=t0, t1=t1, win=win, win_topics=win_topics,
                   expected=expected, c0=c0, c1=c1, h0=h0, h1=h1,
                   memory=stats)
        out["raw"] = {k: np.concatenate([r[k] for r in res])
                      for k in ("seq", "sent", "acked", "d_sub", "d_seq",
                                "d_recv")}
        counts = np.stack([r["counts"] for r in res])
        out["warm_sent"], out["warm_acked"], out["warm_received"], \
            out["dup_flagged"], out["conn_lost"] = (
                int(x) for x in counts[:, :5].sum(axis=0))
        out["drain_close"] = int(counts[:, 5].max())
        if tr is not None:
            tdir, ta, tb, pa, pb = tr
            paths = [os.path.join(dp, f) for dp, _d, fs in os.walk(tdir)
                     for f in fs if f.endswith(".xplane.pb")]
            if not paths:
                raise BenchError("the profiler wrote no .xplane.pb")
            out["trace_rows"] = RT.load(paths[0])
            out["trace_slice"] = (ta, tb)
            out["trace_slice_t_ns"] = (pa, pb)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


# ---------------------------------------------------------------------------
# raw arrays -> the result line
# ---------------------------------------------------------------------------

def device_answers(dep, win_topics, seed: int, n: int, control):
    """A sample, drawn from the seed, of the window's topics: what the
    device answered for each (the hint the timed path minted and
    ``Broker.publish`` consumed, read back through the broker's own
    ``device_match`` entry) against the plain reference over the whole
    table.  Returns (sampled, answered, mismatches, first mismatches)."""
    topics = list(dict.fromkeys(win_topics))
    rng = rng_of(seed, 3)
    picks = rng.choice(len(topics), size=min(n, len(topics)), replace=False)
    swap = (control or {}).get("device_answer")
    answered = wrong = 0
    first = []
    for i in picks:
        t = topics[i]
        want = REF.matching(t, dep.filter_set)
        if swap is not None:
            got = swap(t, dep.filter_set)
        else:
            routes = dep.node.broker.device_match(t)
            if routes is None:
                continue
            got = {r[0] for r in routes}
        answered += 1
        if got != want:
            wrong += 1
            if len(first) < 3:
                first.append([t, sorted(got ^ want)[:4]])
    return len(picks), answered, wrong, first


# a reader's answer where the running tree has no such histogram or counter
# (None: it has one, and it is silent)
ABSENT = object()


def read_layers(want_layers, value_of, must):
    """The per-layer metrics this cell lists, and the names left out
    because the running tree cannot have them.

    A reader that finds nothing to read returns None (a histogram or
    counter that took no sample, no program of the kernel's name in the
    trace): the metric is left out, and where ``must(name)`` says it has
    to be there the run fails, so that a span, counter or kernel that
    exists cannot fall silent behind a well-formed line.  A reader returns
    ``ABSENT`` where the node registers no histogram, or none of the
    counters, of that name (a tree from before the span was added, which
    is what the parent of the PR that adds it is): left out, by name, in
    the second list, and never fatal."""
    layers, silent, left_out = {}, [], []
    for name, unit in want_layers:
        v = value_of(name)
        if v is ABSENT:
            left_out.append(name)
            note(f"per-layer metric {name}: the node registers nothing of "
                 "that name, left out")
        elif v is not None:
            layers[name] = {"value": v, "unit": unit}
        elif must(name):
            silent.append(name)
        else:
            note(f"per-layer metric {name}: nothing to read, left out")
    if silent:
        raise BenchError("listed for this cell and nothing to read: "
                         + ", ".join(silent))
    return layers, left_out


def hist_value(spec, before: dict, after: dict):
    """A percentile of what one histogram recorded between two snapshots
    of the node's histograms: ``ABSENT`` where the node has none of that
    name, None where it has and nothing was recorded."""
    name = spec["hist"]
    if name not in after:
        return ABSENT
    return R.hist_delta_stat(before[name], after[name], spec["stat"])


def counter_value(spec, delta: dict, publishes: int):
    """A ratio of counter deltas (``delta`` has every counter the node's
    registry knows): ``ABSENT`` where it knows none of the metric's
    counters, None where the denominator did not move."""
    per_publish = spec["den"] == "window_publishes"
    names = spec["num"] + ([] if per_publish else spec["den"])
    if not any(k in delta for k in names):
        return ABSENT
    num = sum(delta.get(k, 0) for k in spec["num"])
    den = publishes if per_publish else sum(
        delta.get(k, 0) for k in spec["den"])
    if den <= 0:
        return None
    return float(spec.get("scale", 1.0)) * num / den


def result_of(dep, mix, m, layer_specs, want_layers, peaks, devs, rehearse):
    raw, win = m["raw"], m["win"]
    t0, t1 = m["t0"], m["t1"]
    order = np.argsort(raw["seq"])
    seq, sent, acked = (raw[k][order] for k in ("seq", "sent", "acked"))
    if len(seq) != len(win) or (seq != np.arange(len(win))).any():
        raise BenchError("the workers' publishes do not add up to the plan")
    due_abs = t0 + win.due
    lat, recv, n_expected, missing, extra = R.join_deliveries(
        seq, due_abs, m["expected"], raw["d_sub"], raw["d_seq"],
        raw["d_recv"])
    missing_ns = m["drain_close"] + int(
        float(mix["drain_max_s"]) * 1e9) - t0
    e2e = R.end_to_end(lat, recv, n_expected, t0, t1, missing_ns)

    def parts(k):
        out, edges = [], np.linspace(t0, t1, k + 1).astype(np.int64)
        for a, b in zip(edges, edges[1:]):
            pick = (recv - lat >= a) & (recv - lat < b)     # by due time
            h = R.end_to_end(lat[pick], recv[pick], int(pick.sum()), a, b,
                             missing_ns)
            out.append({k_: h[k_] for k_ in ("e2e_p50_ms", "e2e_p95_ms")})
        return out

    halves, thirds = parts(2), parts(3)
    n = len(win)
    unacked = int((acked == 0).sum())
    unsent = int((sent == 0).sum())
    d = {k: m["c1"].get(k, 0) - m["c0"].get(k, 0) for k in m["c1"]}

    series = {
        "sent_minus_due": (sent[sent > 0] - due_abs[sent > 0], n),
        "acked_minus_due": (acked[acked > 0] - due_abs[acked > 0], n),
        "e2e": (lat, n_expected),
    }
    tr = None
    if "trace_rows" in m:
        ta, tb = m["trace_slice"]
        # a served program is known by the word its roofline metric reads
        tr = RT.reduce(
            m["trace_rows"], (tb - ta) / 1e9, slice_ns=m["trace_slice_t_ns"],
            served=[spec["module"] for spec in layer_specs.values()
                    if spec["kind"] == "trace_roofline"] or None)
        if tr is not None and tr["idle_note"]:
            note("idle time not split by stage, the longest gaps are named "
                 f"as before: {tr['idle_note']}")
    values = {"compiles_in_window": float(m["compiles_in_window"]),
              "full_gc_pause_ms": m.get("full_gc_pause_ms"),
              **{k: v for k, v in m["host"].items()
                 if isinstance(v, (int, float))}}

    def layer_value(spec):
        kind = spec["kind"]
        if kind == "generator":
            vals, total = series[spec["series"]]
            return R.series_stat(vals, total, spec["stat"], missing_ns)
        if kind == "hist_delta":
            return hist_value(spec, m["h0"], m["h1"])
        if kind == "counter_ratio":
            return counter_value(spec, d, n)
        if kind == "harness":
            return values.get(spec["value"])
        if kind == "trace":
            return None if tr is None else tr.get(spec["value"])
        if kind == "trace_roofline":
            if tr is None or peaks is None:
                return None
            secs, calls = RT.module_seconds(tr, spec["module"])
            if calls == 0:
                return None
            ta, tb = m["trace_slice"]
            inside = [t for t, at_ in zip(m["win_topics"], due_abs)
                      if ta <= at_ < tb]
            need = getattr(ROOF, spec["bytes"])(
                inside, int(dep.node.config.get(spec["width_key"])))
            return ROOF.roofline_pct(need, secs, peaks)
        raise BenchError(f"unknown per-layer source kind {kind!r}")

    layers, left_out = read_layers(
        want_layers, lambda name: layer_value(layer_specs[name]),
        # the line of a traced run is its per-layer metrics: there a
        # silent one is a fault.  Only a run with no device plane (a
        # rehearsal on the CPU) may leave the trace's metrics out.
        must=lambda name: m["traced"] and not (
            tr is None and rehearse
            and layer_specs[name]["kind"] in ("trace", "trace_roofline")))

    sampled, answered, wrong, first = m["device_answers"]
    host_pct = 100.0 * (1.0 - d.get("tpu.match.hint_served", 0) / max(1, n))
    limits = dep.cfg["limits"]
    compared = {
        "missing": {"value": missing, "limit": limits["missing"]},
        "extra": {"value": extra, "limit": limits["extra"]},
        "unacked": {"value": unacked + m["conn_lost"],
                    "limit": limits["unacked"]},
        "device_mismatch": {"value": wrong,
                            "limit": limits["device_mismatch"]},
        "device_unanswered_pct": {
            "value": 100.0 * (1.0 - answered / max(1, sampled)),
            "limit": limits["device_unanswered_pct"]},
        "host_answered_pct": {"value": host_pct,
                              "limit": limits["host_answered_pct"]},
    }
    correct = n_expected > 0 and sampled > 0 and all(
        c["value"] <= c["limit"] for c in compared.values())

    stats = m["memory"] or {}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    line = {
        "correct": bool(correct), "attempted": n,
        "failed": unacked + missing,
        "metrics": None, "device": device,
    }
    if tr is not None:
        device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    line["window"] = {
        "rate_offered": m["rate"], "seconds": m["seconds"],
        "publishes": n, "unsent": unsent, "expected_deliveries": n_expected,
        "warmup_s": m["warmup_s"], "warmup_steady": m["warmup_steady"],
        "warm_sent": m["warm_sent"],
        "warm_acked": m["warm_acked"], "dup_flagged": m["dup_flagged"],
        "halves": halves, "thirds": thirds, "e2e_p99_ms": e2e["e2e_p99_ms"],
        "layers": {k: v["value"] for k, v in layers.items()},
        "layers_left_out": left_out,
        "trace": tr and {k: tr[k] for k in (
            "device_clock_shift_ms", "modules_in_place_pct", "idle_note")},
        "host": m["host"], "full_gc_pause_ms": m.get("full_gc_pause_ms"),
        "phases_s": dep.phases,
        "device_answers": {"sampled": sampled, "answered": answered,
                           "first_mismatches": first},
        "counters": {k: v for k, v in sorted(d.items())
                     if v and k.startswith(("tpu.", "broker.match",
                                            "messages.dropped",
                                            "delivery.dropped"))},
    }
    return line, e2e, layers, compared


def print_result(line, metrics, compared) -> None:
    line = dict(line)
    line["metrics"] = metrics
    line["compared"] = compared         # comes last
    for name, c in compared.items():
        note(f"compared {name} = {c['value']} (limit {c['limit']})")
    print(json.dumps(line), flush=True)


# ---------------------------------------------------------------------------

async def run(args, bench, cell, cfg, mix, devs, peaks) -> int:
    compiles = Compiles()
    stalls = HostStalls()
    control = {}
    for name in filter(None, (args.control or "").split(",")):
        control.update(REF.CONTROLS[name])
    layer_specs = {}
    want_layers = []
    for pm in bench["per_layer"]:
        if "workloads" in pm and cell["name"] not in pm["workloads"]:
            continue
        layer_specs[pm["name"]] = load_json(
            "cellbench", "layer_metrics", pm["name"] + ".json")
        want_layers.append((pm["name"], pm["unit"]))
    e2e_units = {em["name"]: em["unit"] for em in bench["end_to_end"]
                 if "workloads" not in em or cell["name"] in em["workloads"]}

    def peak():
        return devs[0].memory_stats()

    dep = Deployment(cfg, args.seed)
    try:
        await dep.start()
        watch = asyncio.ensure_future(stalls.watch())
        runs = [(float(mix["rate_msgs_per_s"]), args.seed)]
        if args.sweep_rates:
            runs = [(float(r), args.seed + i)
                    for i, r in enumerate(args.sweep_rates.split(","))]
        for k, (rate, seed) in enumerate(runs):
            m = await measure(dep, mix, rate, args.seconds, seed,
                              bool(args.trace), control, compiles, stalls,
                              peak)
            line, e2e, layers, compared = result_of(
                dep, mix, m, layer_specs, want_layers, peaks, devs,
                args.rehearse)
            if k == 0:
                setup_s = m["window_start_after_process_s"]
            e2e["setup_s"] = setup_s
            if args.trace:
                metrics = layers
            else:
                metrics = {k_: {"value": e2e[k_], "unit": u}
                           for k_, u in e2e_units.items()}
            if len(runs) > 1:
                # several windows on one set-up: everything on each line
                line["all"] = {"e2e": e2e, "layers": {
                    k_: v["value"] for k_, v in layers.items()}}
                os.makedirs(args.out, exist_ok=True)
                with open(os.path.join(
                        args.out, f"series_{cell['name']}.jsonl"), "a") as f:
                    f.write(json.dumps({**line, "compared": compared}) + "\n")
            print_result(line, metrics, compared)
            if args.sweep_rates and (
                    not m["warmup_steady"]
                    or e2e["delivered_msgs_per_s"] < 0.9 * rate):
                note(f"sweep: {rate:g}/s is past the knee, stopping")
                break
        watch.cancel()
    finally:
        await dep.stop()
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", help="one or more of "
                    + ",".join(sorted(REF.CONTROLS)))
    ap.add_argument("--sweep-rates")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"))
    args = ap.parse_args(argv)

    bench = load_json("BENCHMARK.json")
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        note(f"no workload {args.workload!r} in BENCHMARK.json")
        return 2
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = load_json(entry["file"])
    mix = load_json("cellbench", "traffic", cell["traffic"] + ".json")
    if args.rehearse:
        cfg = merged(cfg, cfg["rehearse"])
        mix = merged(mix, mix["rehearse"])
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])

    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    if soft != hard:
        resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))

    import jax      # after the arguments: --help must not claim the chip

    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu" and not args.rehearse:
        note(f"JAX reports platform {platform!r}, not 'tpu' "
             "(--rehearse allows a CPU run at tiny sizes)")
        return 2
    if len(devs) < int(cell["chips"]):
        note(f"the cell asks for {cell['chips']} chips, JAX reports "
             f"{len(devs)}")
        return 2
    peaks = load_json("cellbench", "peaks.json").get(devs[0].device_kind)
    if peaks is None and not args.rehearse:
        note(f"device kind {devs[0].device_kind!r} is not in peaks.json")
        return 2
    try:
        return asyncio.run(run(args, bench, cell, cfg, mix, devs, peaks))
    except BenchError as e:
        note(f"FAILED: {e}")
        return 1


if __name__ == "__main__":
    sys.exit(main())
