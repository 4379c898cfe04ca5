"""The four-chip cell's deployment on the CPU (ISSUE 36): a ``BrokerNode``
built from ``cellbench/configs/wild1m_tp4.json``'s own ``node_config``
(``wild1m``'s three keys plus ``match.multichip.enable``, ``tp`` 4 and
``ep.enable``; every other ``match.multichip.*`` key at its default), at
that file's ``rehearse`` table size, on the suite's 8 virtual devices
(so the mesh is tp 4 x dp 2).  Held here: the mesh's answers against the
benchmark's plain reference, the counters that prove which plane
answered, a readiness that means the mesh, and the two spans and the
module name the mesh path has.

One departure, as in ``chip_smoke.py``: ``tpu.bypass_rate`` 0, so that a
publish sent alone still asks the device."""

import asyncio
import json
import os
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from cellbench import plan as P                     # noqa: E402
from cellbench import reference as REF              # noqa: E402
from cellbench.tables import zipf_tree              # noqa: E402
from emqx_tpu.client import Client                  # noqa: E402
from emqx_tpu.config import Config                  # noqa: E402
from emqx_tpu.node import BrokerNode                # noqa: E402
from emqx_tpu.observe.flightrec import STAGES       # noqa: E402
from emqx_tpu.ops.match_kernel import (SERVE_FLAT_MULT,  # noqa: E402
                                       decode_packed, decode_row_meta)
from emqx_tpu.parallel import multichip_serve as MC  # noqa: E402

with open(os.path.join(REPO, "cellbench", "configs",
                       "wild1m_tp4.json")) as f:
    CELL = json.load(f)
SEED = 3600000007
COUNTERS = ("tpu.match.batches", "tpu.match.topics",
            "tpu.match.shard_dispatches", "tpu.match.ep_dispatches",
            "tpu.match.ep_overflow_rows", "tpu.match.fallback_host",
            "tpu.match.hint_served", "broker.match.cpu_fallback",
            "tpu.mesh.apply_failed")


def rng_of(*stream):
    return np.random.default_rng([SEED, *stream])


def cell_config(**over) -> Config:
    node_cfg = {**CELL["node_config"], "tpu.bypass_rate": 0.0, **over}
    conf = Config(file_text='listeners.tcp.default.bind = "{}"\n'.format(
        node_cfg.pop("listeners.tcp.default.bind")))
    for k, v in node_cfg.items():
        conf.put(k, v)
    return conf


class Deployment:
    """The deployment, tiny: the seeded table held by offline bulk
    sessions, one TCP subscriber per root word, ``publishers`` TCP
    publishers."""

    def __init__(self, publishers: int = 8, wait_ready: bool = True,
                 **over) -> None:
        self.conf = cell_config(**over)
        self.n_pub, self.wait_ready = publishers, wait_ready
        params = {**CELL["table"]["params"],
                  **CELL["rehearse"]["table"]["params"]}
        self.table = zipf_tree.build(rng_of(1), params, publishers)
        self.filters = set(self.table.filters) | set(self.table.tcp_filters)
        self.used = set()
        self.clients = []

    async def __aenter__(self):
        self.node = node = BrokerNode(self.conf)
        b = node.broker
        sessions = int(CELL["rehearse"]["bulk_sessions"])
        for s in range(sessions):
            b.open_session(f"bulk{s}")
        for i, flt in enumerate(self.table.filters):
            b.subscribe(f"bulk{i % sessions}", flt)
        await node.start()
        self.ms = node.match_service
        self.m = node.observed.metrics
        self.port = node.listeners.all()[0].port
        if self.wait_ready:
            await self.ready()
        return self

    async def __aexit__(self, *_exc):
        for c in self.clients:
            await c.close()
        await self.node.stop()

    async def settle(self, pred, timeout: float = 120.0) -> bool:
        end = asyncio.get_running_loop().time() + timeout
        while not pred():
            if asyncio.get_running_loop().time() > end:
                return False
            await asyncio.sleep(0.05)
        return True

    async def ready(self) -> None:
        def ok():
            i = self.ms.info()
            return i["ready"] and i["synced_epoch"] == i["router_epoch"]
        assert await self.settle(ok), self.ms.info()

    async def connect(self, wait_ready: bool = True):
        self.subs = []
        for k, flt in enumerate(self.table.tcp_filters):
            c = Client(clientid=f"sub{k}", port=self.port)
            await c.connect()
            await c.subscribe(flt, qos=1)
            self.subs.append(c)
        self.pubs = []
        for k in range(self.n_pub):
            c = Client(clientid=f"pub{k}", port=self.port)
            await c.connect()
            self.pubs.append(c)
        self.clients = self.subs + self.pubs
        if wait_ready:
            await self.ready()

    def fresh(self, n: int, stream: int = 2):
        return P.fresh_topics(rng_of(stream), self.table, n, self.used)

    async def publish_all(self, topics) -> None:
        async def one(k, c):
            for i in range(k, len(topics), len(self.pubs)):
                assert not await c.publish(topics[i], b"x", qos=1,
                                           timeout=60.0)
        await asyncio.gather(*(one(k, c) for k, c in enumerate(self.pubs)))

    async def received(self, want: int):
        """Topics delivered to each TCP subscriber, until ``want`` in
        all (one more second is given to anything extra)."""
        got = [[] for _ in self.subs]

        async def drain(k, c):
            while True:
                got[k].append((await c.recv(3600.0)).topic)

        tasks = [asyncio.ensure_future(drain(k, c))
                 for k, c in enumerate(self.subs)]
        await self.settle(lambda: sum(map(len, got)) >= want, 60.0)
        await asyncio.sleep(1.0)
        for t in tasks:
            t.cancel()
        return got

    def counters(self) -> dict:
        all_ = self.m.all()
        return {k: all_.get(k, 0) for k in COUNTERS}


def delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def wildcard_matches(dep, topic) -> set:
    """The reference's answer over the filters the DEVICE table holds:
    plain filters are the router's dict's, not the automaton's."""
    return {f for f in REF.matching(topic, dep.filters)
            if "+" in f or "#" in f}


def hint_filters(dep, topic):
    routes = dep.node.broker.device_match(topic)
    return None if routes is None else {r[0] for r in routes}


# ---------------------------------------------------------------------------
# (1) the served path: real connections, every answer against the reference
# ---------------------------------------------------------------------------

def test_the_mesh_serves_fresh_topics_and_agrees_with_the_reference():
    async def main():
        async with Deployment() as dep:
            await dep.connect()
            mc = dep.ms.mc
            assert mc is not None and mc.ready and mc.ep and mc.tp == 4
            assert not mc.ep_compact and not mc.degraded \
                and not mc.ep_autotune and mc.ep_slack == 2.0
            topics = dep.fresh(400)
            expected = REF.expected_deliveries(topics,
                                               dep.table.tcp_filters)
            c0 = dep.counters()
            await dep.publish_all(topics)
            got = await dep.received(sum(map(len, expected)))
            d = delta(dep.counters(), c0)
            # one delivery per matching connected subscriber, none extra
            want = [[] for _ in dep.subs]
            for t, subs in zip(topics, expected):
                for k in subs:
                    want[k].append(t)
            assert [sorted(g) for g in got] == [sorted(w) for w in want]
            # every minted hint is the reference's match set, whole table
            for t in topics:
                assert hint_filters(dep, t) == REF.matching(t, dep.filters)
            # which plane answered, by the counters
            assert d["tpu.match.batches"] > 0
            assert d["tpu.match.shard_dispatches"] == d["tpu.match.batches"]
            assert d["tpu.match.ep_dispatches"] == \
                d["tpu.match.shard_dispatches"]
            assert d["tpu.match.topics"] == len(topics)
            assert d["tpu.match.hint_served"] == len(topics)
            assert d["broker.match.cpu_fallback"] == 0
            assert d["tpu.mesh.apply_failed"] == 0

    asyncio.run(main())


# ---------------------------------------------------------------------------
# (2) the shares: the owner's segment + the micro-table, each counted once
# ---------------------------------------------------------------------------

def bucket_overflows(mc, owners, batch: int = 64):
    """Rows the EP front end cannot place: each ``tp`` instance buckets
    its source slice of the dp-local batch by owner, ``ep_capacity``
    slots a (source, owner) bucket, rows in order."""
    width, cap = batch // mc.dp // mc.tp, mc.ep_capacity(batch)
    seen, out = {}, []
    for r, owner in enumerate(owners):
        k = seen[r // width, owner] = seen.get((r // width, owner), 0) + 1
        if k > cap:
            out.append(r)
    return out


def test_each_topics_answer_is_its_owners_segment_and_the_micro_table_once():
    async def main():
        async with Deployment() as dep:
            ms, mc = dep.ms, dep.ms.mc
            topics = dep.fresh(24)       # three source slices of a 64 batch
            owners = [MC.shard_of_filter(t, mc.tp) for t in topics]
            res = mc.dispatch(mc.encode(topics, batch=64))
            # the routed answer: one packed array, a served-format block
            # (row_meta, then SERVE_FLAT_MULT·Bl flat ids) a dp group
            raw = np.asarray(jax.device_get(res))
            rows, spilled, nbytes = mc.readback(res, len(topics))
            bl = 64 // mc.dp
            assert raw.shape == (mc.dp * bl * (1 + SERVE_FLAT_MULT),)
            assert nbytes == raw.nbytes
            # the topics fill the first dp block; the others hold pads
            blocks = raw.reshape(mc.dp, -1)
            counts, sp = decode_row_meta(blocks[:, :bl].reshape(-1))
            assert not counts[len(topics):].any()
            assert (rows, spilled) == decode_packed(blocks[0], len(topics),
                                                    mc.max_matches
                                                    + mc.ep_micro_matches)
            # the fail-open set is the bucket rule's, nothing else
            assert spilled == bucket_overflows(mc, owners)
            assert np.flatnonzero(sp).tolist() == spilled
            assert len(spilled) < len(topics) // 2
            micro_hits = 0
            for r, t in enumerate(topics):
                if r in spilled:        # the host trie re-runs the row
                    assert counts[r] == 0
                    continue
                want, owner = wildcard_matches(dep, t), owners[r]
                aids = rows[r]
                assert len(aids) == counts[r] and min(aids, default=0) >= 0
                assert len(aids) == len(set(aids))      # counted once
                got = set(ms._split_row(aids)[0])
                assert got == want, (t, got ^ want)
                micro = {f for f in got if MC.is_micro_filter(f)}
                assert micro == {f for f in want if MC.is_micro_filter(f)}
                assert {MC.shard_of_filter(f, mc.tp)
                        for f in got - micro} <= {owner}
                micro_hits += len(micro)
            assert micro_hits > 0       # the replicated table did answer
            assert len(mc._micro_filters) == sum(
                1 for f in dep.filters
                if MC.is_micro_filter(f) and ("+" in f or "#" in f))

    asyncio.run(main())


# ---------------------------------------------------------------------------
# (3) a volley on one owner overflows its bucket; the host re-runs the rows
# ---------------------------------------------------------------------------

def test_a_volley_on_one_root_overflows_its_bucket_and_stays_exact():
    async def main():
        async with Deployment() as dep:
            ms, mc = dep.ms, dep.ms.mc
            root = dep.table.vocab[0][0]
            topics = []
            while len(topics) < 48:
                topics += [t for t in dep.fresh(256, stream=3)
                           if t.split("/", 1)[0] == root]
            topics = topics[:48]
            # every row to one owner: half of each source slice has no
            # slot (capacity_slack 2.0: 2/tp of a slice's width a bucket)
            over = bucket_overflows(mc, [0] * len(topics))
            assert len(over) == 24
            c0 = dep.counters()
            rows = await ms._device_serve(topics)
            d = delta(dep.counters(), c0)
            assert d["tpu.match.ep_overflow_rows"] == len(over)
            assert d["tpu.match.fallback_host"] == len(over)    # the trie
            assert d["tpu.match.shard_dispatches"] == 1
            for t, row in zip(topics, rows):
                assert set(ms._split_row(row)[0]) == \
                    wildcard_matches(dep, t)

    asyncio.run(main())


# ---------------------------------------------------------------------------
# (4) readiness means the configured plane
# ---------------------------------------------------------------------------

class Failing:
    """Raises for as long as ``on``; counts its calls."""

    def __init__(self, real, what: str) -> None:
        self.real, self.what, self.on, self.calls = real, what, True, 0

    def __call__(self, *a, **k):
        self.calls += 1
        if self.on:
            raise RuntimeError(f"injected: {self.what}")
        return self.real(*a, **k)


@pytest.mark.parametrize("what", ["apply", "constructor"])
def test_a_mesh_that_does_not_come_up_leaves_the_node_not_ready(
        what, monkeypatch, caplog):
    if what == "apply":
        real = MC.MultichipMatcher.apply_pending
        fail = Failing(real, what)
        monkeypatch.setattr(MC.MultichipMatcher, "apply_pending",
                            lambda self: fail(self))
    else:
        real = MC.MultichipMatcher.__init__
        fail = Failing(real, what)
        monkeypatch.setattr(MC.MultichipMatcher, "__init__",
                            lambda self, **k: fail(self, **k))

    async def main():
        async with Deployment(publishers=2, wait_ready=False) as dep:
            ms, m = dep.ms, dep.m
            # two failed passes at least: the sync loop keeps trying
            assert await dep.settle(
                lambda: m.all()["tpu.mesh.apply_failed"] >= 2, 60.0)
            assert ms._mirror_ready       # the one-chip mirror IS up...
            assert not ms.ready and not ms.info()["ready"]   # ...and hidden
            assert (ms.mc is None) == (what == "constructor")
            # the host trie serves, nothing reaches a device
            await dep.connect(wait_ready=False)
            c0 = dep.counters()
            t = dep.fresh(1)[0]
            await dep.publish_all([t])
            got = await dep.received(1)
            assert sum(map(len, got)) == 1
            d = delta(dep.counters(), c0)
            assert d["tpu.match.batches"] == 0
            assert d["tpu.match.shard_dispatches"] == 0
            assert d["tpu.match.hint_served"] == 0
            assert hint_filters(dep, t) is None
            # logged once per distinct error, not once per pass
            said = [r for r in caplog.records
                    if f"injected: {what}" in str(r.exc_info)]
            assert len(said) == 1 and fail.calls >= 2
            # the next clean pass: ready, and the mesh serves
            fail.on = False
            await dep.ready()
            assert ms.ready and ms.mc is not None and ms.mc.ready
            failed = m.all()["tpu.mesh.apply_failed"]
            c0 = dep.counters()
            t = dep.fresh(1)[0]
            await dep.publish_all([t])
            assert sum(map(len, await dep.received(1))) == 1
            d = delta(dep.counters(), c0)
            assert d["tpu.match.shard_dispatches"] == 1 == \
                d["tpu.match.batches"]
            assert hint_filters(dep, t) == REF.matching(t, dep.filters)
            assert m.all()["tpu.mesh.apply_failed"] == failed

    with caplog.at_level("WARNING"):
        asyncio.run(main())


def test_the_matcher_says_ready_only_after_its_serve_shapes_are_warm():
    """A whole repartition runs the step once per configured serve depth
    on the STAGED arrays and publishes them last: ``mc.ready`` (and so
    ``ms.ready``) never turns true over a cold shape."""
    async def main():
        async with Deployment() as dep:
            ms, mc = dep.ms, dep.ms.mc
            assert mc.warm_depths == (ms.short_depth, ms.depth)
            seen, real = [], mc._step_for

            def spy(shape, routed, **k):
                seen.append((shape, routed, mc.ready, ms.ready))
                return real(shape, routed, **k)

            mc._step_for = spy
            gen0 = mc.gen
            mc.rebuild(ms._mc_pairs())
            assert not mc.ready and not ms.ready
            ms._dirty.set()
            assert await dep.settle(lambda: mc.gen > gen0 and ms.ready)
            warm = [s for s in seen if not s[2]]
            assert [s[0] for s in warm] == [(64, d) for d in mc.warm_depths]
            assert all(routed and not up for _s, routed, _r, up in warm)

    asyncio.run(main())


def test_with_the_flag_off_ready_is_the_mirrors_as_before():
    async def main():
        async with Deployment(**{"match.multichip.enable": False}) as dep:
            ms = dep.ms
            assert ms.mc is None and not ms._mc_wanted
            assert ms.ready and ms._mirror_ready and ms.info()["ready"]
            await dep.connect()
            c0 = dep.counters()
            t = dep.fresh(1)[0]
            await dep.publish_all([t])
            assert sum(map(len, await dep.received(1))) == 1
            d = delta(dep.counters(), c0)
            assert d["tpu.match.batches"] == 1
            assert d["tpu.match.shard_dispatches"] == 0
            assert hint_filters(dep, t) == REF.matching(t, dep.filters)
            assert ms._serving_dev() is ms.dev

    asyncio.run(main())


def test_a_mesh_that_drops_out_is_not_covered_by_the_mirror():
    """A compaction swap queues a repartition (``mc.rebuild``): until the
    sync loop lands it the node is not ready, and a dispatch that was
    already past ``_usable`` is a stale race, never the one-chip
    mirror's."""
    from emqx_tpu.broker.match_service import _StaleRace

    async def main():
        async with Deployment() as dep:
            ms = dep.ms
            ms.mc.rebuild(ms._mc_pairs())
            assert ms._mirror_ready and not ms.ready and not ms._usable()
            with pytest.raises(_StaleRace):
                ms._serving_dev()
            ms._dirty.set()
            await dep.ready()
            assert ms._serving_dev() is ms.mc

    asyncio.run(main())


# ---------------------------------------------------------------------------
# (5) what the mesh adds to the books, and its name in a device trace
# ---------------------------------------------------------------------------

def hist_counts(dep) -> dict:
    hs = dep.node.hists
    return {n.split(".")[-1]: hs.hist(n).count for n in hs.names()}


def test_one_batch_is_one_mesh_fetch_and_one_mesh_decode_inside_readback():
    async def main():
        async with Deployment(publishers=1) as dep:
            await dep.connect()
            await dep.publish_all(dep.fresh(1))       # compile the bucket
            await dep.received(1)
            n0, seq0 = hist_counts(dep), dep.ms._seq
            await dep.publish_all(dep.fresh(1))
            await dep.received(1)
            d = {k: v - n0[k] for k, v in hist_counts(dep).items()
                 if v != n0[k]}
            assert d["mesh_fetch"] == d["mesh_decode"] == 1 == \
                d["match_readback"], d
            assert dep.ms._seq == seq0 + 1
            by = {STAGES[e[0]]: e for r in
                  dep.node.flightrec._rings.values()
                  for e in r.snapshot() if e[-1] == dep.ms._seq}
            rb, fetch, dec = (by[k] for k in (
                "match_readback", "mesh_fetch", "mesh_decode"))
            # (sid, start, dur, batch, gen, seq): the two tile the
            # matcher's readback, inside the stage that calls it
            assert rb[1] <= fetch[1]
            assert fetch[1] + fetch[2] == dec[1]
            assert dec[1] + dec[2] <= rb[1] + rb[2]
            assert fetch[2] + dec[2] >= 0.5 * rb[2]
            assert fetch[3:] == dec[3:] == rb[3:]

    asyncio.run(main())


def test_one_batch_is_one_mesh_put_and_one_mesh_launch_inside_dispatch():
    async def main():
        async with Deployment(publishers=1) as dep:
            await dep.connect()
            await dep.publish_all(dep.fresh(1))       # compile the bucket
            await dep.received(1)
            n0, seq0 = hist_counts(dep), dep.ms._seq
            puts0 = dep.m.all()["tpu.mesh.operand_puts"]
            c0 = dep.counters()
            await dep.publish_all(dep.fresh(1))
            await dep.received(1)
            d = {k: v - n0[k] for k, v in hist_counts(dep).items()
                 if v != n0[k]}
            assert d["mesh_put"] == d["mesh_launch"] == 1 == \
                d["match_dispatch"], d
            assert dep.ms._seq == seq0 + 1
            # one host array placed a dispatch (three before the packed
            # operand): the ratio the result line's counters give
            assert delta(dep.counters(), c0)[
                "tpu.match.shard_dispatches"] == 1
            assert dep.m.all()["tpu.mesh.operand_puts"] == puts0 + 1
            by = {STAGES[e[0]]: e for r in
                  dep.node.flightrec._rings.values()
                  for e in r.snapshot() if e[-1] == dep.ms._seq}
            disp, put, launch = (by[k] for k in (
                "match_dispatch", "mesh_put", "mesh_launch"))
            # (sid, start, dur, batch, gen, seq): the two tile the
            # matcher's dispatch, inside the stage that calls it
            assert disp[1] <= put[1]
            assert put[1] + put[2] == launch[1]
            assert launch[1] + launch[2] <= disp[1] + disp[2]
            assert put[2] + launch[2] >= 0.5 * disp[2]
            assert put[3:] == launch[3:] == disp[3:]

    asyncio.run(main())


def test_probes_warm_calls_and_canaries_place_an_operand_and_record_no_span():
    async def main():
        async with Deployment() as dep:
            ms, mc = dep.ms, dep.ms.mc
            n0 = hist_counts(dep)

            def puts():
                return dep.m.all()["tpu.mesh.operand_puts"]

            # the repartition's warm calls went through the helper
            assert puts() >= len(mc.warm_depths)
            p0 = puts()
            ms._probe_dispatch()
            assert puts() == p0 + 1
            mc._warm_capacity((64, ms.depth), 1)
            assert puts() == p0 + 2
            topics = mc.canary_topics(0, cap=8)
            rows, _spilled = mc.canary_rows(topics, 64, readmit=0)
            assert puts() == p0 + 3 and len(rows) == len(topics)
            n1 = hist_counts(dep)
            for stage in ("mesh_put", "mesh_launch", "mesh_fetch",
                          "mesh_decode"):
                assert n1[stage] == n0[stage] == 0, stage

    asyncio.run(main())


# what a rehearsal of the cell reads of the two dispatch spans: on this
# tree; on a tree from before them (the parent of the PR that brought
# them: ``read_layers`` leaves the two out by name and nothing else);
# on one from before every mesh span (after the readback's two)
MESH_SPAN_METRICS = ["mesh_fetch_p50_ms", "mesh_decode_p50_ms",
                     "mesh_put_p50_ms", "mesh_launch_p50_ms"]
_WITHOUT = """
import sys
sys.path.insert(0, {root!r})
from cellbench import run as RUN
good = RUN.Deployment.hist_counts
RUN.Deployment.hist_counts = lambda self: {{
    k: v for k, v in good(self).items()
    if not k.startswith({prefixes!r})}}
sys.exit(RUN.main(sys.argv[1:]))
"""


@pytest.mark.parametrize("without, gone", [
    ((), []),
    (("obs.stage.mesh_put", "obs.stage.mesh_launch"), MESH_SPAN_METRICS[2:]),
    (("obs.stage.mesh_",), MESH_SPAN_METRICS),
], ids=["this_tree", "parent", "no_mesh_span"])
def test_a_traced_rehearsal_reads_the_dispatch_spans_or_names_them(without,
                                                                    gone):
    import subprocess

    flag = "--xla_force_host_platform_device_count"
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=f"{flag}=4")
    head = ([os.path.join(REPO, "cellbench", "run.py")] if not without else
            ["-c", _WITHOUT.format(root=REPO, prefixes=without)])
    p = subprocess.run(
        [sys.executable, *head, "--workload", "wild1m_tp4.fanin_fresh",
         "--rehearse", "--seconds", "3", "--trace", "1", "--seed",
         "3700000021"], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["device"]["count"] == 4
    assert line["window"]["layers_left_out"] == gone
    got = {k: v["value"] for k, v in line["metrics"].items()}
    for name in MESH_SPAN_METRICS:
        assert (name in got) == (name not in gone), name
    if not without:
        assert got["mesh_put_p50_ms"] > 0 and got["mesh_launch_p50_ms"] > 0
    assert got["mesh_served_pct"] > 99.0 and got["ep_routed_pct"] == 100.0
    # the window's edges may cut one batch between two of its counters
    c = line["window"]["counters"]
    assert abs(c["tpu.mesh.operand_puts"]
               - c["tpu.match.shard_dispatches"]) <= 1
    # one answer buffer a dp group: the rehearsal's mesh is tp 4 on four
    # devices, so one a dispatch (eleven before the packed answer)
    assert abs(c["tpu.mesh.answer_buffers"]
               - c["tpu.match.shard_dispatches"]) <= 1
    assert abs(c["tpu.match.shard_dispatches"]
               - c["tpu.match.batches"]) <= 1


# sha1 of the one-chip served program's lowered text, as the tree before
# the mesh's routed answer shared its scatter (commit 69c279d), per
# (B, D, S, Hb, active_slots, max_matches)
SERVED_HLO = {
    (64, 8, 256, 64, 8, 16): "c8aefda263ac71ae935330e82f6772070cd68295",
    (16, 16, 1024, 256, 16, 32): "6fe2e962ebd5dc742d04a7c2540ef3e5c2e35c97",
}


@pytest.mark.parametrize("shape", sorted(SERVED_HLO))
def test_the_one_chip_served_program_lowers_as_before(shape):
    import hashlib

    import jax.numpy as jnp

    from emqx_tpu.ops import match_kernel as MK

    B, D, S, Hb, A, K = shape
    sd, i32 = jax.ShapeDtypeStruct, jnp.int32
    low = MK.nfa_match_packed.lower(
        sd((B, D), i32), sd((B,), i32), sd((B,), jnp.bool_),
        sd((S, 4), i32), sd((Hb, MK.BUCKET_SLOTS * 4), i32), sd((2,), i32),
        active_slots=A, max_matches=K, flat_cap=MK.SERVE_FLAT_MULT * B)
    assert hashlib.sha1(low.as_text().encode()).hexdigest() == \
        SERVED_HLO[shape]


def test_the_mesh_step_is_a_module_of_its_own_name_with_named_phases():
    async def main():
        async with Deployment() as dep:
            mc = dep.ms.mc
            enc = mc.encode(dep.fresh(4), batch=64)
            step = mc._step_for((64, int(enc[0].shape[1])), routed=True)
            assert "mesh_match" in step.__name__
            text = step.lower(mc._put_operands(enc), *mc._arrs).as_text(
                debug_info=True)
            assert "module @jit_mesh_match" in text
            for scope in ("mesh.route", "mesh.walk", "mesh.micro",
                          "mesh.compact"):
                assert scope in text, scope
            assert "all_to_all" in text and "all_reduce" in text

    asyncio.run(main())
