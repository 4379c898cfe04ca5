"""Tier-1 enforcement of the project-invariant static analysis suite
(emqx_tpu/devtools/staticcheck) — the dialyzer/xref analog.

Layers:

* **the tree is clean**: all thirteen rules over ``emqx_tpu/`` plus
  the bench drivers (``bench.py``, ``scripts/bench_e2e.py``) produce
  zero non-waived findings, and every waiver (if any ever lands) is an
  explicit, justified, expiring entry — no silent suppressions;
* **the rules work**: each rule has a tripping and a passing fixture
  under ``tests/staticcheck_fixtures/``, waiver keys are line-stable,
  and expiry/staleness behave;
* **the whole-program analysis crosses modules**: the ``xmod`` fixture
  package puts every offending call in a different module than its
  thread/loop entry and the findings land at the right file:line; the
  ``twoplane``/``twohop`` packages pin the context-sensitive lattice
  (k=2 caller chains keep two entries through one shared mid-function
  distinct, so per-entry exemptions scope correctly);
* **the cache is sound**: warm runs reuse summaries+findings, a dep
  edit invalidates exactly its dependents, ``--changed`` re-checks
  changed files plus reverse import-graph dependents;
* **the CLI works**: a violation seeded into a copy of
  ``broker/fanout.py`` is caught with a file:line finding and exit 1;
  a clean run exits 0.

Satellite coverage rides along: the event-loop lag probe
(broker/olp.py) and the QUIC-timer / kafka-poll supervised children.
"""

import asyncio
import datetime
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from emqx_tpu.devtools.staticcheck import (
    Registries, WaiverFile, check_paths, get_rules,
)
from emqx_tpu.devtools.staticcheck.rules import ALL_RULES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "emqx_tpu")
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "staticcheck_fixtures")
WAIVER_FILE = os.path.join(REPO, "staticcheck-waivers.json")
CLI = os.path.join(REPO, "scripts", "staticcheck.py")


def run(coro):
    return asyncio.run(coro)


def check_fixture(name, rules, tmp_path, relpath="emqx_tpu/broker"):
    """Run ``rules`` over one fixture file, staged under a repo-shaped
    temp tree so path-scoped rules (delivery-path prefixes, allowlists)
    see the intended relative path."""
    dest_dir = tmp_path / relpath
    dest_dir.mkdir(parents=True, exist_ok=True)
    dest = dest_dir / name
    shutil.copy(os.path.join(FIXTURES, name), dest)
    return check_paths([str(dest)], get_rules(rules), root=str(tmp_path))


# ---------------------------------------------------------------------------
# the tree is clean (the tier-1 gate)
# ---------------------------------------------------------------------------

#: the tier-1 scan set: the package plus the bench drivers whose
#: metric/config literals have silently drifted before
SCAN_PATHS = [PKG, os.path.join(REPO, "bench.py"),
              os.path.join(REPO, "scripts", "bench_e2e.py")]


def test_tree_has_zero_nonwaived_findings():
    findings = check_paths(SCAN_PATHS, get_rules(), root=REPO)
    wf = WaiverFile.load(WAIVER_FILE)
    new, waived, expired, stale = wf.apply(findings)
    assert not new, (
        "staticcheck found new violations (fix them or add an expiring "
        "waiver with a reason):\n"
        + "\n".join(
            f"  {f.location()}: [{f.rule}] {f.message}"
            + (f"\n      path: {' -> '.join(f.chain)}" if f.chain
               else "")
            for f in new)
    )
    assert not expired, (
        "expired waivers still have live findings: "
        + ", ".join(w.key for w in expired)
    )


def test_waiver_file_has_no_silent_suppressions():
    with open(WAIVER_FILE) as f:
        data = json.load(f)
    for w in data.get("waivers", []):
        assert w.get("reason"), f"waiver {w.get('key')} has no reason"
        # a malformed date must fail here, not silently never expire
        datetime.date.fromisoformat(w["expires"])


# ---------------------------------------------------------------------------
# per-rule fixtures: each rule trips and passes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rule,trip,ok,n_trip", [
    ("no-unsupervised-task", "trip_tasks.py", "ok_tasks.py", 3),
    ("loop-thread-taint", "trip_threads.py", "ok_threads.py", 6),
    ("shard-affinity", "trip_affinity.py", "ok_affinity.py", 3),
    # seeds GENERATED from _SHARD_LOCAL x handle_in dispatch facts: a
    # shard-legal handler can no longer silently miss its seed
    ("shard-affinity", "trip_affinity_gen.py", "ok_affinity_gen.py", 1),
    # serve-pipeline worker threads (ISSUE 11): an unseeded to_thread
    # pipeline stage writing Broker state trips; the pure-compute
    # worker + loop-side-write shape passes
    ("shard-affinity", "trip_affinity_pipeline.py",
     "ok_affinity_pipeline.py", 1),
    # multichip mesh worker threads (ISSUE 15): an unseeded to_thread
    # partition-apply writing MatchService state trips; the
    # matcher-owns-its-own-state + loop-side-readiness shape passes
    ("shard-affinity", "trip_affinity_mesh.py",
     "ok_affinity_mesh.py", 1),
    ("torn-read", "trip_tornread.py", "ok_tornread.py", 2),
    ("lock-order", "trip_lockorder.py", "ok_lockorder.py", 1),
    # object-sensitive lock identity (ISSUE 17): two unrelated _lock
    # attrs on different classes no longer alias — the cross-class
    # same-name deadlock trips, the cross-class chain passes
    ("lock-order", "trip_lockident.py", "ok_lockident.py", 1),
    ("no-blocking-in-async", "trip_blocking.py", "ok_blocking.py", 2),
    ("no-swallowed-exceptions", "trip_exceptions.py",
     "ok_exceptions.py", 3),
    ("await-under-lock", "trip_locks.py", "ok_locks.py", 3),
    ("registry-drift", "trip_drift.py", "ok_drift.py", 10),
    ("unawaited-coroutine", "trip_coroutines.py", "ok_coroutines.py", 3),
    # device-plane dataflow rules (ISSUE 19): reuse after a donated
    # dispatch trips (rebind/result-only/branch-dispatch pass), a
    # device sync on a main/shard path trips (thread worker, host
    # asarray and unreached helper pass), and an await between the
    # reads of one invariant group on an unlocked main path trips
    # (one critical section, await-before, unreached pass)
    ("use-after-donate", "trip_donate.py", "ok_donate.py", 2),
    ("host-sync-in-loop", "trip_hostsync.py", "ok_hostsync.py", 4),
    ("await-torn-read", "trip_awaittorn.py", "ok_awaittorn.py", 2),
])
def test_rule_fixture_pair(rule, trip, ok, n_trip, tmp_path):
    tripped = check_fixture(trip, [rule], tmp_path)
    assert len(tripped) == n_trip, (
        f"{rule} on {trip}: expected {n_trip} findings, got "
        f"{[(f.line, f.message) for f in tripped]}"
    )
    assert all(f.rule == rule for f in tripped)
    assert all(f.line > 0 for f in tripped)
    passed = check_fixture(ok, [rule], tmp_path)
    assert passed == [], (
        f"{rule} on {ok} should be clean, got "
        f"{[(f.line, f.message) for f in passed]}"
    )


def test_swallowed_exceptions_scoped_to_delivery_paths(tmp_path):
    # the same tripping file is FINE outside the delivery-path prefixes
    out = check_fixture("trip_exceptions.py", ["no-swallowed-exceptions"],
                        tmp_path, relpath="emqx_tpu/ops")
    assert out == []


def test_task_allowlist_honors_site_and_reason(tmp_path):
    # stage the tripping file at an allowlisted (path, qualname):
    # client.py / Client.connect is allowlisted as request-scoped
    dest_dir = tmp_path / "emqx_tpu"
    dest_dir.mkdir(parents=True, exist_ok=True)
    dest = dest_dir / "client.py"
    dest.write_text(
        "import asyncio\n\n\n"
        "class Client:\n"
        "    async def connect(self):\n"
        "        asyncio.ensure_future(self._read_loop())\n\n"
        "    async def other(self):\n"
        "        asyncio.ensure_future(self._read_loop())\n\n"
        "    async def _read_loop(self):\n"
        "        pass\n"
    )
    out = check_paths([str(dest)], get_rules(["no-unsupervised-task"]),
                      root=str(tmp_path))
    # connect() is allowlisted, other() is not
    assert len(out) == 1 and out[0].context == "Client.other"


# ---------------------------------------------------------------------------
# waivers: keys, expiry, staleness
# ---------------------------------------------------------------------------

def _fixture_findings(tmp_path):
    out = check_fixture("trip_blocking.py", ["no-blocking-in-async"],
                        tmp_path)
    assert out
    return out


def test_waiver_suppresses_until_expiry_then_resurfaces(tmp_path):
    findings = _fixture_findings(tmp_path)
    t0 = datetime.date(2026, 8, 1)
    wf = WaiverFile.baseline(findings, days=30, today=t0)
    # live: everything waived, run is clean
    new, waived, expired, stale = wf.apply(
        findings, today=t0 + datetime.timedelta(days=15))
    assert not new and len(waived) == len(findings) and not expired
    # past expiry: findings come back AND the expired entries surface
    new, waived, expired, stale = wf.apply(
        findings, today=t0 + datetime.timedelta(days=31))
    assert len(new) == len(findings) and not waived
    assert len(expired) == len(wf.waivers)


def test_stale_waivers_are_reported(tmp_path):
    findings = _fixture_findings(tmp_path)
    wf = WaiverFile.baseline(findings, today=datetime.date(2026, 8, 1))
    new, waived, expired, stale = wf.apply(
        [], today=datetime.date(2026, 8, 2))
    assert len(stale) == len(wf.waivers) and not new


def test_waiver_keys_survive_line_drift(tmp_path):
    a = check_fixture("trip_blocking.py", ["no-blocking-in-async"],
                      tmp_path)
    # same code shifted two lines down: same keys, different lines
    src = open(os.path.join(FIXTURES, "trip_blocking.py")).read()
    shifted = tmp_path / "emqx_tpu" / "broker" / "trip_blocking.py"
    shifted.write_text("# shim\n# shim\n" + src)
    b = check_paths([str(shifted)], get_rules(["no-blocking-in-async"]),
                    root=str(tmp_path))
    assert [f.key for f in a] == [f.key for f in b]
    assert [f.line for f in a] != [f.line for f in b]


def test_waiver_file_roundtrip(tmp_path):
    findings = _fixture_findings(tmp_path)
    wf = WaiverFile.baseline(findings, today=datetime.date(2026, 8, 1))
    p = tmp_path / "w.json"
    wf.save(str(p))
    loaded = WaiverFile.load(str(p))
    assert [w.key for w in loaded.waivers] == [w.key for w in wf.waivers]


# ---------------------------------------------------------------------------
# registries extract the real registration sites
# ---------------------------------------------------------------------------

def test_registries_extract_from_tree():
    reg = Registries.load()
    assert "messages.delivered" in reg.metric_names
    assert "broker.olp.loop_lag_us" in reg.metric_names
    assert "messages.dropped.olp_shed" in reg.metric_names
    assert "mqtt.max_inflight" in reg.config_keys
    assert "overload_protection.lag_probe_interval" in reg.config_keys
    assert "fanout.drain" in reg.fault_points
    assert "message.acked" in reg.hook_points
    assert "client.enhanced_authenticate" in reg.hook_points
    assert "obs.stage.match_readback" in reg.hist_names
    assert "obs.e2e.publish_deliver" in reg.hist_names
    assert "breaker_trip" in reg.dump_reasons
    assert "supervisor_degraded" in reg.dump_reasons
    assert "match_cycle" in reg.stage_names
    assert "obs.flightrec.dumps" in reg.metric_names
    assert "obs.hist.enable" in reg.config_keys


def test_registries_match_runtime_tables():
    # the AST extraction and the live modules must agree, or the drift
    # rule itself has drifted
    from emqx_tpu import faultinject
    from emqx_tpu.config import SCHEMA
    from emqx_tpu.observe.metrics import Metrics

    reg = Registries.load()
    assert reg.metric_names == set(Metrics().all().keys())
    assert reg.config_keys == set(SCHEMA.keys())
    assert reg.fault_points == set(faultinject.POINTS)
    from emqx_tpu.broker.hooks import HOOK_POINTS
    assert reg.hook_points == set(HOOK_POINTS)
    from emqx_tpu.observe.flightrec import DUMP_REASONS
    from emqx_tpu.observe.hist import HIST_NAMES
    assert reg.hist_names == set(HIST_NAMES)
    assert reg.dump_reasons == set(DUMP_REASONS)
    # observe/span.py resolves both sinks from a stage's one name
    from emqx_tpu.observe.flightrec import STAGES
    assert reg.stage_names == set(STAGES)
    assert {f"obs.stage.{s}" for s in STAGES} <= set(HIST_NAMES)


# ---------------------------------------------------------------------------
# whole-program analysis: cross-module resolution (the xmod package)
# ---------------------------------------------------------------------------

def _stage_xmod(tmp_path):
    dest = tmp_path / "xmod"
    shutil.copytree(os.path.join(FIXTURES, "xmod"), dest)
    return dest


def test_cross_module_taint_lands_in_the_helper_module(tmp_path):
    dest = _stage_xmod(tmp_path)
    out = check_paths([str(dest)], get_rules(["loop-thread-taint"]),
                      root=str(tmp_path))
    # the thread entry is entry.py; the affine call (and the finding)
    # is two modules away in helper.py, at the ensure_future line
    assert len(out) == 1, [(f.path, f.line, f.message) for f in out]
    f = out[0]
    assert f.path == "xmod/helper.py"
    src = open(os.path.join(FIXTURES, "xmod", "helper.py")).read()
    want = src[:src.index("asyncio.ensure_future")].count("\n") + 1
    assert f.line == want
    assert "notify" in f.message
    # the thread-entry chain rides the structured chain field now
    assert "relay" in f.chain and f.chain[-1] == "notify"


def test_cross_module_unawaited_coroutine(tmp_path):
    dest = _stage_xmod(tmp_path)
    out = check_paths([str(dest)], get_rules(["unawaited-coroutine"]),
                      root=str(tmp_path))
    assert len(out) == 1, [(f.path, f.line, f.message) for f in out]
    assert out[0].path == "xmod/entry.py"
    assert "flush" in out[0].message


def test_cross_module_shard_affinity_write(tmp_path):
    dest = _stage_xmod(tmp_path)
    out = check_paths([str(dest)], get_rules(["shard-affinity"]),
                      root=str(tmp_path))
    assert len(out) == 1, [(f.path, f.line, f.message) for f in out]
    f = out[0]
    assert f.path == "xmod/entry.py" and f.context == "shard_worker"
    assert "main-loop-only" in f.message


def test_generated_seeds_cover_real_shard_local_handlers():
    """The real tree's Channel._handle_puback/... seeds come from the
    _SHARD_LOCAL x handle_in join, not from a hand-kept list: every
    packet type shards handle locally has its dispatch handler seeded
    (shard, locked), and the main-only handlers (SUBSCRIBE, ...) do
    not."""
    import ast as _ast

    from emqx_tpu.devtools.staticcheck.graph import Project
    from emqx_tpu.devtools.staticcheck.symbols import extract_module

    summaries = []
    for rel in ("emqx_tpu/transport/shards.py",
                "emqx_tpu/broker/channel.py"):
        with open(os.path.join(REPO, rel)) as f:
            src = f.read()
        summaries.append(extract_module(rel, _ast.parse(src), src))
    shards, channel = summaries
    assert "PUBACK" in shards.shard_local
    assert channel.classes["Channel"].dispatch["PUBACK"] == \
        "_handle_puback"
    aff = Project(summaries).affinity()
    for m in ("_handle_puback", "_handle_pubrec", "_handle_pubrel",
              "_handle_pubcomp"):
        fqid = f"emqx_tpu.broker.channel:Channel.{m}"
        assert fqid in aff.generated_seeds, (m, aff.generated_seeds)
        assert ("shard", True) in aff.contexts(fqid)
    # a main-only dispatch target must NOT be seeded by generation
    assert "emqx_tpu.broker.channel:Channel._handle_subscribe" \
        not in aff.generated_seeds


# ---------------------------------------------------------------------------
# context sensitivity: the twoplane package (k=1 paths)
# ---------------------------------------------------------------------------

def _stage_twoplane(tmp_path, drop=None):
    dest = tmp_path / "twoplane"
    shutil.copytree(os.path.join(FIXTURES, "twoplane"), dest)
    if drop:
        (dest / drop).unlink()
    return dest


def test_twoplane_flags_only_the_shard_path(tmp_path):
    """The SAME helper is called locked-from-main and unlocked-from-
    shard: exactly one finding, on the shard path, chain naming the
    shard entry — the context-insensitive lattice had to over-flag or
    over-absorb here."""
    dest = _stage_twoplane(tmp_path)
    out = check_paths([str(dest)], get_rules(["shard-affinity"]),
                      root=str(tmp_path))
    assert len(out) == 1, [(f.path, f.line, f.message) for f in out]
    f = out[0]
    assert f.path == "twoplane/helper.py" and f.context == "bump"
    assert f.chain[0] == "ShardChannel.handle_ack_run"
    assert "ShardPool._main_handle" not in f.chain


def test_twoplane_locked_main_path_alone_is_clean(tmp_path):
    # with the shard caller gone, the only path is locked-from-main:
    # zero findings
    dest = _stage_twoplane(tmp_path, drop="shardline.py")
    out = check_paths([str(dest)], get_rules(["shard-affinity"]),
                      root=str(tmp_path))
    assert out == [], [(f.path, f.line, f.message) for f in out]


def test_per_context_allow_fact_scopes_to_the_path(tmp_path, monkeypatch):
    """An AFFINITY_ALLOWED_SITES entry scoped (plane, entry) exempts
    only that path: scoping it to the main entry keeps the shard
    finding; scoping it to the shard entry clears the tree."""
    from emqx_tpu.devtools.staticcheck import project as facts

    dest = _stage_twoplane(tmp_path)
    site = ("twoplane/helper.py", "bump")
    # scoped to the benign main path: the shard finding survives
    monkeypatch.setattr(facts, "AFFINITY_ALLOWED_SITES", {
        site: ("main path holds the mutex by construction", "main",
               "ShardPool._main_handle"),
    })
    out = check_paths([str(dest)], get_rules(["shard-affinity"]),
                      root=str(tmp_path))
    assert len(out) == 1 and out[0].chain[0] == \
        "ShardChannel.handle_ack_run"
    # scoped to the offending shard path: tree goes clean
    monkeypatch.setattr(facts, "AFFINITY_ALLOWED_SITES", {
        site: ("hypothetical: shard entry serializes via its own loop",
               "shard", "ShardChannel.handle_ack_run"),
    })
    out = check_paths([str(dest)], get_rules(["shard-affinity"]),
                      root=str(tmp_path))
    assert out == []
    # the old over-broad string form still exempts every path
    monkeypatch.setattr(facts, "AFFINITY_ALLOWED_SITES", {
        site: "over-broad: every path exempt",
    })
    out = check_paths([str(dest)], get_rules(["shard-affinity"]),
                      root=str(tmp_path))
    assert out == []


# ---------------------------------------------------------------------------
# context sensitivity: the twohop package (k=2 caller chains)
# ---------------------------------------------------------------------------

def _stage_twohop(tmp_path):
    dest = tmp_path / "twohop"
    shutil.copytree(os.path.join(FIXTURES, "twohop"), dest)
    return dest


def test_twohop_keeps_grandparent_entries_distinct(tmp_path):
    """TWO shard entries reach the same offending helper through ONE
    shared mid-function: k=1 collapses both at the mid hop; the k=2
    chain keeps the grandparent entry, so the lattice records two
    distinct contexts and each traces to its own entry."""
    from emqx_tpu.devtools.staticcheck import analyze

    dest = _stage_twohop(tmp_path)
    res = analyze([str(dest)], get_rules(["shard-affinity"]),
                  root=str(tmp_path))
    aff = res.project.affinity()
    fqid = "twohop.helper:bump"
    paths = aff.paths(fqid)
    assert ("shard", False,
            ("twohop.mid:relay",
             "twohop.entries:ShardChannel.handle_ack_run")) in paths
    assert ("shard", False,
            ("twohop.mid:relay",
             "twohop.entries:ShardChannel.check_keepalive")) in paths
    traces = sorted(tuple(aff.trace_ctx(fqid, c)) for c in paths)
    assert traces == [
        ("ShardChannel.check_keepalive", "relay", "bump"),
        ("ShardChannel.handle_ack_run", "relay", "bump"),
    ]


def test_twohop_scoped_exemption_needs_k2(tmp_path, monkeypatch):
    """A (plane, entry) exemption scoped to ONE of the two entries
    must leave the OTHER entry's finding standing — impossible under
    k=1, where both paths share the mid-hop context."""
    from emqx_tpu.devtools.staticcheck import project as facts

    dest = _stage_twohop(tmp_path)
    site = ("twohop/helper.py", "bump")
    monkeypatch.setattr(facts, "AFFINITY_ALLOWED_SITES", {
        site: ("hypothetical: the ack-run entry serializes its own "
               "loop", "shard", "ShardChannel.handle_ack_run"),
    })
    out = check_paths([str(dest)], get_rules(["shard-affinity"]),
                      root=str(tmp_path))
    assert len(out) == 1, [(f.path, f.line, f.chain) for f in out]
    assert out[0].chain[0] == "ShardChannel.check_keepalive"
    # exempting the other entry flips which finding survives
    monkeypatch.setattr(facts, "AFFINITY_ALLOWED_SITES", {
        site: ("hypothetical", "shard", "ShardChannel.check_keepalive"),
    })
    out = check_paths([str(dest)], get_rules(["shard-affinity"]),
                      root=str(tmp_path))
    assert len(out) == 1
    assert out[0].chain[0] == "ShardChannel.handle_ack_run"
    # the bare (every-path) form still clears the tree
    monkeypatch.setattr(facts, "AFFINITY_ALLOWED_SITES", {
        site: "over-broad: every path exempt",
    })
    out = check_paths([str(dest)], get_rules(["shard-affinity"]),
                      root=str(tmp_path))
    assert out == []


def test_torn_read_locked_entry_path_is_clean(tmp_path, monkeypatch):
    """A (shard, locked) entry covers every read in the function: only
    the unlocked path makes the group reads a finding."""
    from emqx_tpu.devtools.staticcheck import project as facts

    dest_dir = tmp_path / "emqx_tpu" / "broker"
    dest_dir.mkdir(parents=True)
    dest = dest_dir / "lockedreader.py"
    # _handle_publish is seeded (shard, locked=True): reads need no
    # site-level lock
    dest.write_text(
        "class Session:\n"
        "    def __init__(self):\n"
        "        self.inflight = {}\n"
        "        self.mqueue = []\n\n\n"
        "class ShardChannel:\n"
        "    def _handle_publish(self, sess):\n"
        "        return len(sess.inflight) + len(sess.mqueue)\n"
    )
    out = check_paths([str(dest)], get_rules(["torn-read"]),
                      root=str(tmp_path))
    assert out == [], [(f.line, f.message) for f in out]


def test_finding_chain_rides_json_and_text_reports(tmp_path):
    from emqx_tpu.devtools.staticcheck.report import (
        format_json, format_text)

    dest = _stage_twoplane(tmp_path)
    out = check_paths([str(dest)], get_rules(["shard-affinity"]),
                      root=str(tmp_path))
    assert len(out) == 1
    blob = json.loads(format_json(out))
    assert blob["findings"][0]["chain"] == [
        "ShardChannel.handle_ack_run", "bump"]
    text = format_text(out)
    assert "path: ShardChannel.handle_ack_run -> bump" in text


def test_lock_order_allowed_fact_suppresses_cycle(tmp_path, monkeypatch):
    from emqx_tpu.devtools.staticcheck import project as facts

    monkeypatch.setattr(facts, "LOCK_ORDER_ALLOWED", {
        ("Pair.a_lock", "Pair.b_lock"):
            "fixture locks never contend (test)",
    })
    out = check_fixture("trip_lockorder.py", ["lock-order"], tmp_path)
    assert out == []


def test_lock_order_witnesses_name_both_edges(tmp_path):
    out = check_fixture("trip_lockorder.py", ["lock-order"], tmp_path)
    assert len(out) == 1
    chain = " | ".join(out[0].chain)
    assert ("Pair.a_lock->Pair.b_lock" in chain
            and "Pair.b_lock->Pair.a_lock" in chain)
    assert "Pair._grab_a" in chain  # the cross-call edge is named


def test_real_tree_lock_graph_has_no_cycle_and_known_edge():
    """The real tree's lock graph: the shard fast path takes the
    handoff lock under the channel mutex (ShardChannel.mutex →
    Handoff._lock, object-qualified) and nothing acquires them in the
    opposite order."""
    from emqx_tpu.devtools.staticcheck import analyze

    res = analyze([PKG], get_rules([]), root=REPO)
    lo = res.project.lock_order()
    assert ("ShardChannel.mutex", "Handoff._lock") in lo.edges
    assert lo.cycles() == []


def test_affinity_paths_expose_k2_callers():
    """The real tree's lattice keeps per-caller-chain paths: Channel
    ack handlers generated-seeded (shard, locked) AND reachable from
    main-plane consumers stay separable, and non-seed contexts carry
    up to two call-site hops (nearest first)."""
    from emqx_tpu.devtools.staticcheck import analyze

    res = analyze([PKG], get_rules([]), root=REPO)
    aff = res.project.affinity()
    fqid = "emqx_tpu.broker.channel:Channel._handle_puback"
    paths = aff.paths(fqid)
    assert ("shard", True, ()) in paths  # the generated seed
    # every recorded path resolves to an exact, non-guessed chain,
    # and every context chain is a ≤2-hop tuple of fqids (or the
    # merged-hub star)
    for ctx in paths:
        chain = aff.trace_ctx(fqid, ctx)
        assert chain[-1] == "Channel._handle_puback"
        assert isinstance(ctx[2], tuple) and len(ctx[2]) <= 2
        for hop in ctx[2]:
            assert hop == "*" or ":" in hop, ctx


def test_affinity_keys_survive_line_drift(tmp_path):
    a = check_fixture("trip_affinity.py", ["shard-affinity"], tmp_path)
    src = open(os.path.join(FIXTURES, "trip_affinity.py")).read()
    shifted = tmp_path / "emqx_tpu" / "broker" / "trip_affinity.py"
    shifted.write_text("# shim\n# shim\n" + src)
    b = check_paths([str(shifted)], get_rules(["shard-affinity"]),
                    root=str(tmp_path))
    assert [f.key for f in a] == [f.key for f in b]
    assert [f.line for f in a] != [f.line for f in b]


def test_delivery_path_scope_covers_post_pr4_modules():
    from emqx_tpu.devtools.staticcheck import project

    for mod in project.DELIVERY_PATH_REQUIRED_MODULES:
        assert mod.startswith(project.DELIVERY_PATH_PREFIXES), mod
        assert os.path.exists(os.path.join(REPO, mod)), mod


def test_drift_checks_metric_reads_like_the_bench_drivers(tmp_path):
    # bench.py / scripts/bench_e2e.py read metrics by literal name
    # (metrics.get); a drifted name must trip like a write would
    dest_dir = tmp_path / "emqx_tpu" / "broker"
    dest_dir.mkdir(parents=True)
    dest = dest_dir / "snap.py"
    dest.write_text(
        "def snap(metrics):\n"
        "    ok = metrics.get(\"broker.supervisor.restarts\")\n"
        "    bad = metrics.get(\"broker.not_a_real_metric\")\n"
        "    return ok, bad\n"
    )
    out = check_paths([str(dest)], get_rules(["registry-drift"]),
                      root=str(tmp_path))
    assert len(out) == 1 and out[0].line == 3


def _stage_deadseam(tmp_path, pkg):
    dest = tmp_path / pkg
    shutil.copytree(os.path.join(FIXTURES, pkg), dest)
    return dest


def test_dead_seam_declared_but_ungated_point_trips(tmp_path):
    """A point the package's faultinject module declares with NO
    literal act/check gate anywhere in the scanned tree is a
    registered-but-never-fired chaos point: one drift finding at the
    declaration."""
    dest = _stage_deadseam(tmp_path, "deadseam_trip")
    out = check_paths([str(dest)], get_rules(["registry-drift"]),
                      root=str(tmp_path))
    assert len(out) == 1, [(f.path, f.line, f.message) for f in out]
    f = out[0]
    assert f.path == "deadseam_trip/faultinject.py"
    assert "mesh.rebuild" in f.message and "ever gates" in f.message


def test_dead_seam_fully_gated_package_is_clean(tmp_path):
    # both declared points gated (one .act, one .check): no findings —
    # and trees that declare no points at all stay silent (every other
    # fixture run in this file would trip otherwise)
    dest = _stage_deadseam(tmp_path, "deadseam_ok")
    out = check_paths([str(dest)], get_rules(["registry-drift"]),
                      root=str(tmp_path))
    assert out == [], [(f.path, f.line, f.message) for f in out]


def test_real_tree_has_no_dead_fault_seams():
    """Every point emqx_tpu/faultinject.py declares has ≥1 literal
    gate in the scan set (pass-1 facts, not a grep): the chaos
    surface cannot silently grow points nothing fires."""
    from emqx_tpu import faultinject
    from emqx_tpu.devtools.staticcheck import analyze

    res = analyze(SCAN_PATHS, get_rules([]), root=REPO)
    declared, used = set(), set()
    for s in res.project.modules.values():
        declared.update(p for p, _ in s.fault_points)
        used.update(s.fault_uses)
    assert declared == set(faultinject.POINTS)
    assert declared <= used, declared - used


def test_cli_default_scan_set_includes_bench_drivers():
    import importlib.util

    spec = importlib.util.spec_from_file_location("sc_cli", CLI)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert "bench.py" in mod.DEFAULT_SCAN_PATHS
    assert "scripts/bench_e2e.py" in mod.DEFAULT_SCAN_PATHS


# ---------------------------------------------------------------------------
# the analysis cache: warm reuse, dep-edit invalidation, --changed
# ---------------------------------------------------------------------------

def _mini_pkg(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "a.py").write_text("async def go():\n    pass\n")
    (pkg / "b.py").write_text(
        "from .a import go\n\n\ndef run():\n    go()\n")
    return pkg


def _mini_analyze(tmp_path, pkg):
    from emqx_tpu.devtools.staticcheck import analyze
    from emqx_tpu.devtools.staticcheck.cache import (
        AnalysisCache, environment_digest)

    env = environment_digest(["unawaited-coroutine"])
    cache = AnalysisCache(str(tmp_path / "cc"), env)
    return analyze([str(pkg)], get_rules(["unawaited-coroutine"]),
                   root=str(tmp_path), cache=cache)


def test_cache_warm_run_reuses_everything(tmp_path):
    pkg = _mini_pkg(tmp_path)
    r1 = _mini_analyze(tmp_path, pkg)
    assert len(r1.findings) == 1 and r1.files_walked == 3
    r2 = _mini_analyze(tmp_path, pkg)
    assert [f.key for f in r2.findings] == [f.key for f in r1.findings]
    assert r2.files_walked == 0 and r2.files_cached == 3


def test_cache_invalidates_on_dependency_edit(tmp_path):
    pkg = _mini_pkg(tmp_path)
    assert len(_mini_analyze(tmp_path, pkg).findings) == 1
    # a.go becomes sync: b.py is byte-identical but its finding must
    # disappear — the transitive deps digest invalidates it
    (pkg / "a.py").write_text("def go():\n    pass\n")
    r = _mini_analyze(tmp_path, pkg)
    assert r.findings == []
    assert r.files_walked >= 2  # a.py (changed) AND b.py (dependent)


def test_cache_invalidates_on_content_edit(tmp_path):
    pkg = _mini_pkg(tmp_path)
    assert len(_mini_analyze(tmp_path, pkg).findings) == 1
    (pkg / "b.py").write_text(
        "from .a import go\n\n\nasync def run():\n    await go()\n")
    assert _mini_analyze(tmp_path, pkg).findings == []


def test_cli_no_cache_flag_skips_the_cache(tmp_path):
    pkg = _mini_pkg(tmp_path)
    cache_dir = tmp_path / "cachedir"
    r = _cli("--root", str(tmp_path), "--cache-dir", str(cache_dir),
             "--no-cache", str(pkg))
    assert r.returncode == 1, r.stdout + r.stderr
    assert not cache_dir.exists()
    r = _cli("--root", str(tmp_path), "--cache-dir", str(cache_dir),
             str(pkg))
    assert r.returncode == 1, r.stdout + r.stderr
    assert (cache_dir / "cache.json").exists()


def _git(tmp_path, *args):
    return subprocess.run(
        ["git", "-C", str(tmp_path), "-c", "user.email=t@t",
         "-c", "user.name=t", *args],
        capture_output=True, text=True, timeout=30)


def test_cli_changed_mode_rechecks_reverse_dependents(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "a.py").write_text("def go():\n    pass\n")
    (pkg / "b.py").write_text(
        "from .a import go\n\n\ndef run():\n    go()\n")
    assert _git(tmp_path, "init", "-q").returncode == 0
    assert _git(tmp_path, "add", "-A").returncode == 0
    assert _git(tmp_path, "commit", "-qm", "seed").returncode == 0
    # clean at HEAD: --changed with nothing changed is a no-op pass
    r = _cli("--root", str(tmp_path), "--no-cache", "--changed",
             str(pkg))
    assert r.returncode == 0, r.stdout + r.stderr
    # flip a.go to async: b.py (UNCHANGED per git) now discards a
    # coroutine — --changed must re-check it as a reverse dependent
    (pkg / "a.py").write_text("async def go():\n    pass\n")
    r = _cli("--root", str(tmp_path), "--no-cache", "--changed",
             str(pkg))
    assert r.returncode == 1, r.stdout + r.stderr
    assert "b.py" in r.stdout and "unawaited-coroutine" in r.stdout


def test_cli_changed_mode_facts_edit_rechecks_everything(tmp_path):
    """Editing the ownership-facts module (project.py INVARIANT_GROUPS
    et al.) re-surfaces per-context findings in files git considers
    UNCHANGED: --changed widens to the full tree because nothing
    imports the checker."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    shutil.copy(os.path.join(FIXTURES, "trip_tornread.py"),
                pkg / "reader.py")
    facts_dir = tmp_path / "emqx_tpu" / "devtools" / "staticcheck"
    facts_dir.mkdir(parents=True)
    facts_file = facts_dir / "project.py"
    facts_file.write_text("# stand-in for the facts module\n")
    assert _git(tmp_path, "init", "-q").returncode == 0
    assert _git(tmp_path, "add", "-A").returncode == 0
    assert _git(tmp_path, "commit", "-qm", "seed").returncode == 0
    # nothing changed: --changed is a no-op pass (findings and all)
    r = _cli("--root", str(tmp_path), "--no-cache", "--changed",
             "--rule", "torn-read", str(pkg))
    assert r.returncode == 0, r.stdout + r.stderr
    # a facts edit: reader.py is unchanged per git, its per-context
    # findings must re-surface anyway
    facts_file.write_text(
        "# stand-in for the facts module\n# INVARIANT_GROUPS edited\n")
    r = _cli("--root", str(tmp_path), "--no-cache", "--changed",
             "--rule", "torn-read", str(pkg))
    assert r.returncode == 1, r.stdout + r.stderr
    assert "reader.py" in r.stdout and "torn-read" in r.stdout


def test_changed_targets_helper_widens_on_facts_edit():
    import importlib.util

    spec = importlib.util.spec_from_file_location("sc_cli2", CLI)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    from emqx_tpu.devtools.staticcheck import analyze

    res = analyze([PKG], get_rules([]), root=REPO)
    # a facts/rules edit → None (full re-check)
    assert mod.changed_targets(
        res.project,
        {"emqx_tpu/devtools/staticcheck/project.py"}) is None
    # an ordinary edit → the file + reverse dependents only
    targets = mod.changed_targets(
        res.project, {"emqx_tpu/broker/inflight.py"})
    assert "emqx_tpu/broker/inflight.py" in targets
    assert "emqx_tpu/broker/session.py" in targets  # imports inflight
    assert "emqx_tpu/topic.py" not in targets


def test_cache_version_bump_invalidates_prior_payloads(tmp_path):
    """v4 payloads (no device-plane sites, k=1 contexts) must never
    be read back into the v5 analysis: a version-stamp mismatch
    forces a full re-walk instead of deserializing stale summaries."""
    from emqx_tpu.devtools.staticcheck.cache import CACHE_VERSION

    # the ISSUE-19 bump: ModuleSummary grew await/donate/device-sync
    # sites and fault-point decl/use facts; contexts went k=2
    assert CACHE_VERSION == 5
    pkg = _mini_pkg(tmp_path)
    r1 = _mini_analyze(tmp_path, pkg)
    assert r1.files_walked == 3
    cache_file = tmp_path / "cc" / "cache.json"
    data = json.loads(cache_file.read_text())
    data["version"] = CACHE_VERSION - 1
    cache_file.write_text(json.dumps(data))
    r2 = _mini_analyze(tmp_path, pkg)
    assert r2.files_walked == 3 and r2.files_cached == 0
    assert [f.key for f in r2.findings] == [f.key for f in r1.findings]


def _jobs_pkg(tmp_path, n=6):
    """≥ _POOL_MIN_FILES modules, each with one unawaited-coroutine
    finding, so the pooled pass-1 has real work and a deterministic
    finding set to compare against serial."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    for i in range(n):
        (pkg / f"m{i}.py").write_text(
            f"async def go{i}():\n    pass\n\n\n"
            f"def run{i}():\n    go{i}()\n")
    return pkg


def test_analyze_jobs_pool_matches_serial_and_caches(tmp_path):
    """jobs>1 routes the cold pass-1 parse through a process pool:
    identical findings to serial, and the pooled run still stores
    every summary (the next run is fully warm)."""
    from emqx_tpu.devtools.staticcheck import analyze
    from emqx_tpu.devtools.staticcheck.cache import (
        AnalysisCache, environment_digest)

    pkg = _jobs_pkg(tmp_path)
    env = environment_digest(["unawaited-coroutine"])
    rules = get_rules(["unawaited-coroutine"])
    cold = analyze([str(pkg)], rules, root=str(tmp_path),
                   cache=AnalysisCache(str(tmp_path / "cc"), env),
                   jobs=4)
    assert len(cold.findings) == 6 and cold.files_walked == 7
    serial = analyze([str(pkg)], rules, root=str(tmp_path), jobs=1)
    assert [f.key for f in cold.findings] == \
        [f.key for f in serial.findings]
    warm = analyze([str(pkg)], rules, root=str(tmp_path),
                   cache=AnalysisCache(str(tmp_path / "cc"), env),
                   jobs=4)
    assert warm.files_walked == 0 and warm.files_cached == 7
    assert [f.key for f in warm.findings] == \
        [f.key for f in cold.findings]


def test_cli_jobs_flag_output_matches_serial(tmp_path):
    pkg = _jobs_pkg(tmp_path)
    r_serial = _cli("--root", str(tmp_path), "--no-cache",
                    "--jobs", "1", str(pkg))
    r_par = _cli("--root", str(tmp_path), "--no-cache",
                 "--jobs", "4", str(pkg))
    assert r_serial.returncode == 1, r_serial.stdout + r_serial.stderr
    assert r_par.returncode == 1, r_par.stdout + r_par.stderr
    assert r_par.stdout == r_serial.stdout


def test_cache_findings_roundtrip_context_chain(tmp_path):
    """Cached per-file findings keep the chain field across the
    save/load cycle (v3 cache payload)."""
    from emqx_tpu.devtools.staticcheck.cache import (
        _finding_from_dict, _finding_to_dict)
    from emqx_tpu.devtools.staticcheck.core import Finding

    f = Finding(rule="torn-read", path="p.py", line=3, col=1,
                message="m", context="C.f",
                chain=("ShardChannel.handle_ack_run", "C.f"))
    assert _finding_from_dict(_finding_to_dict(f)) == f


def test_new_rules_are_in_the_tier1_battery():
    names = {r.name for r in ALL_RULES}
    assert {"shard-affinity", "torn-read", "lock-order",
            "use-after-donate", "host-sync-in-loop",
            "await-torn-read"} <= names
    assert len(ALL_RULES) == 13


@pytest.mark.slow
def test_full_tree_scan_cold_and_warm_budgets(tmp_path):
    # all 13 rules active (the battery assert keeps this honest): the
    # cold bound moved 3.0 → 4.0 s for the three device-plane rules +
    # the k=2 lattice; warm stays ≤1 s — the dev-loop contract
    assert len(ALL_RULES) == 13
    cache_dir = tmp_path / "cc"
    t0 = time.monotonic()
    r = _cli("--cache-dir", str(cache_dir))
    cold = time.monotonic() - t0
    assert r.returncode == 0, r.stdout + r.stderr
    t0 = time.monotonic()
    r = _cli("--cache-dir", str(cache_dir))
    warm = time.monotonic() - t0
    assert r.returncode == 0, r.stdout + r.stderr
    assert cold <= 4.0, f"cold full-tree scan took {cold:.2f}s"
    assert warm <= 1.0, f"warm full-tree scan took {warm:.2f}s"


# ---------------------------------------------------------------------------
# CLI: exit codes + seeded-violation catch
# ---------------------------------------------------------------------------

def _cli(*args):
    return subprocess.run(
        [sys.executable, CLI, *args],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )


def test_cli_catches_seeded_fanout_violation(tmp_path):
    src = open(os.path.join(PKG, "broker", "fanout.py")).read()
    seeded = (
        src
        + "\n\nasync def _seeded_violation():\n"
          "    time.sleep(0.001)\n"
    )
    dest_dir = tmp_path / "emqx_tpu" / "broker"
    dest_dir.mkdir(parents=True)
    dest = dest_dir / "fanout.py"
    dest.write_text(seeded)
    seed_line = seeded[:seeded.index("    time.sleep")].count("\n") + 1
    r = _cli(str(dest))
    assert r.returncode == 1, r.stdout + r.stderr
    assert f"fanout.py:{seed_line}:" in r.stdout
    assert "no-blocking-in-async" in r.stdout


def test_cli_clean_file_exits_zero(tmp_path):
    r = _cli(os.path.join(FIXTURES, "ok_blocking.py"))
    assert r.returncode == 0, r.stdout + r.stderr


def test_cli_unknown_rule_exits_two():
    r = _cli("--rule", "no-such-rule")
    assert r.returncode == 2


def test_cli_baseline_write_then_clean(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import time\n\n\nasync def f():\n    time.sleep(1)\n")
    wpath = tmp_path / "waivers.json"
    r = _cli(str(bad), "--waivers", str(wpath), "--baseline", "write")
    assert r.returncode == 0, r.stdout + r.stderr
    assert json.load(open(wpath))["waivers"]
    r = _cli(str(bad), "--waivers", str(wpath))
    assert r.returncode == 0, r.stdout + r.stderr  # all waived now


@pytest.mark.slow
def test_cli_full_tree_under_ten_seconds():
    t0 = time.monotonic()
    r = _cli()
    dt = time.monotonic() - t0
    assert r.returncode == 0, r.stdout + r.stderr
    assert dt < 10.0, f"staticcheck took {dt:.1f}s over the tree"


# ---------------------------------------------------------------------------
# satellite: event-loop lag probe → Olp.report
# ---------------------------------------------------------------------------

def test_lag_probe_trips_overload_without_queue_growth():
    from emqx_tpu.broker.olp import LoopLagProbe, Olp
    from emqx_tpu.observe.alarm import Alarms
    from emqx_tpu.observe.metrics import Metrics

    alarms = Alarms()
    olp = Olp(alarms=alarms, max_loop_lag=0.05, cooloff=10.0)
    m = Metrics()
    probe = LoopLagProbe(olp, metrics=m, interval=0.01, alpha=1.0)
    assert not olp.overloaded()
    probe.observe(0.2)  # 200 ms drift >> 50 ms budget, queue depth 0
    assert olp.overloaded()
    assert alarms.is_active("overload")
    assert m.get("broker.olp.loop_lag_us") == 200_000


def test_lag_probe_ewma_smooths_one_off_spikes():
    from emqx_tpu.broker.olp import LoopLagProbe, Olp

    olp = Olp(max_loop_lag=0.5, cooloff=10.0)
    probe = LoopLagProbe(olp, interval=0.01, alpha=0.3)
    probe.observe(0.0)
    probe.observe(1.0)  # single spike: EWMA stays under the 0.5 budget
    assert probe.lag == pytest.approx(0.3)
    assert not olp.overloaded()
    for _ in range(10):  # sustained saturation does trip it
        probe.observe(1.0)
    assert olp.overloaded()


def test_lag_probe_run_measures_sleep_drift():
    from emqx_tpu.broker.olp import LoopLagProbe, Olp

    ticks = iter([0.0, 0.05, 0.05, 0.10])  # two samples of 40ms drift

    async def fake_sleep(_):
        try:
            return None
        finally:
            fake_sleep.calls += 1
            if fake_sleep.calls >= 2:
                raise asyncio.CancelledError

    fake_sleep.calls = 0
    probe = LoopLagProbe(
        Olp(max_loop_lag=10.0), interval=0.01,
        clock=lambda: next(ticks), sleep=fake_sleep, alpha=1.0,
    )

    async def go():
        with pytest.raises(asyncio.CancelledError):
            await probe.run()

    run(go())
    assert probe.samples == 1  # second sleep cancelled before sampling
    assert probe.last_raw == pytest.approx(0.04)


# ---------------------------------------------------------------------------
# satellite: QUIC endpoint timer + kafka poll as supervised children
# ---------------------------------------------------------------------------

def test_quic_timer_registers_as_transient_child_and_reaps():
    pytest.importorskip(
        "cryptography", reason="quic stack needs cryptography")
    from emqx_tpu.supervise import Supervisor
    from emqx_tpu.transport.quic import QuicEndpoint

    async def go():
        sup = Supervisor()
        ep = QuicEndpoint(None, b"", b"", None, supervisor=sup)
        ep._ensure_timer()
        child = sup.lookup("quic.timer")
        assert child is not None and child.restart == "transient"
        # by_cid is empty: the loop returns normally, supervision ends
        for _ in range(50):
            if child.done():
                break
            await asyncio.sleep(0.01)
        assert child.done() and child.state == "done"
        # next activity cycle: a fresh child replaces (not accretes)
        ep._timer_task = None
        ep._ensure_timer()
        assert sum(1 for c in sup.children if c.name == "quic.timer") == 1
        await sup.stop()

    run(go())


def test_kafka_poll_registers_as_transient_child():
    from emqx_tpu.bridge.kafka import KafkaConnector, KafkaError
    from emqx_tpu.supervise import Supervisor

    async def go():
        sup = Supervisor()
        conn = KafkaConnector(
            {"server": "127.0.0.1:1", "ingress": {"topic": "t"}},
            name="k", local_publish=lambda *a, **kw: None)
        conn.supervisor = sup

        async def no_meta(topic):
            raise KafkaError("no metadata")

        conn.client.partitions = no_meta  # ingress-only start path
        await conn.start()
        child = sup.lookup("bridge.kafka.k.poll")
        assert child is not None and child.restart == "transient"
        assert conn._poll_task is child
        await conn.stop()
        assert child.done()

    run(go())
