"""Per-PR e2e tracking: the ``scripts/bench_e2e.py --smoke`` A/B must
run clean on CPU and deliver every fan-out leg on BOTH paths.

Marked ``slow`` (tier-1 runs ``-m 'not slow'``): the smoke A/B is two
~2 s broker runs plus node start/stop.  The speedup itself is NOT
asserted here — a loaded CI box makes ratios noisy; the bench reports
it, the test pins correctness (delivery_ratio) and that the harness
keeps working.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_bench_e2e_smoke_delivers_everything():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "bench_e2e.py"),
         "--smoke", "--chaos"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout)
    for path in ("per_message", "pipeline"):
        sec = out[path]
        assert sec["sent"] > 0, (path, sec)
        assert sec["delivery_ratio"] == 1.0, (path, sec)
    assert out["speedup"] > 0
    # acknowledged-delivery A/Bs: QoS1 windowed subscribers (acks
    # flowing) and QoS2 exactly-once (PUBREC/PUBREL/PUBCOMP flowing) —
    # every fan-out leg delivered, and no DUP redelivery
    # (retry_interval far exceeds the run, so a DUP is a broker bug)
    for section in ("qos1", "qos2"):
        for path in ("per_message", "pipeline"):
            sec = out[section][path]
            assert sec["sent"] > 0, (section, path, sec)
            assert sec["delivery_ratio"] == 1.0, (section, path, sec)
            assert sec["duplicates"] == 0, (section, path, sec)
        assert out[section]["speedup"] > 0
    # connection-plane sections (PR 6): config1 real-client A/B (full
    # protocol clients over the sharded + timer-wheel flag-on node)
    # delivers everything on both sides, and every client-count sweep
    # row completes with ratio 1.0
    for path in ("per_message", "pipeline"):
        sec = out["config1"][path]
        assert sec["sent"] > 0, (path, sec)
        assert sec["delivery_ratio"] == 1.0, (path, sec)
    assert out["config1"]["shards"] >= 1
    for row in out["config1_sweep"]:
        assert row["sent"] > 0, row
        assert row["delivery_ratio"] == 1.0, row
        assert row["e2e_p99_us"] is not None, row
    # deadline serve A/B (ISSUE 7): both sides of the static-vs-deadline
    # A/B served the offered storm and the achieved batch-size histogram
    # is recorded; the p99 ratio itself is bench.py's number, not a CI
    # assertion (kernel-latency ratios are noise on a loaded box)
    sd = out["serve_deadline"]
    assert sd["deadline_ms"] > 0
    assert sd["static"]["served"] > 0, sd
    assert sd["deadline"]["served"] > 0, sd
    assert sd["deadline"]["batch_hist"], sd
    # stage-latency observatory (ISSUE 12): the serve sections report
    # per-stage p50/p99 from the PRODUCT's histograms, parity-checked
    # against the legacy np.percentile extraction over the same
    # post-warmup samples, and the deadline JSON records the split
    # dispatch/readback estimates
    for side in ("static", "deadline"):
        sec = sd[side]
        assert sec["gate_hist_parity"], (side, sec)
        assert sec["stages"]["match_dispatch"]["count"] > 0, sec
        assert sec["hist"]["count"] > 0, sec
    assert sd["deadline"]["est_dispatch_ms"] > 0, sd
    assert sd["deadline"]["est_readback_ms"] > 0, sd
    # kernel backend A/B (ISSUE 13): the join kernel answers every
    # shape bit-for-bit like the hash kernel (matches, counts,
    # row_meta, overflow vectors), the autotuner picked a real backend
    # per shape, and the ratio gates rode the JSON (asserted only for
    # structure — kernel timing ratios on a loaded CI box are noise;
    # the ≥1.3x and auto-within-5% claims belong to bench.py's r06
    # real-hardware round)
    kj = out["kernel_join"]
    assert kj["gate_parity_all"], kj
    assert kj["rows"], kj
    for row in kj["rows"]:
        assert row["parity"], row
        assert row["hash_us"] > 0 and row["join_us"] > 0, row
        assert row["auto_us"] > 0, row
        assert row["auto_backend"] in ("hash", "join"), row
    assert "gate_join_ge_1_3x_any" in kj, kj
    # multichip serve A/B (ISSUE 15): on the virtual 8-device CPU mesh
    # the sharded table reproduces the single-chip rows bit-for-bit,
    # unflagged rows survive an artificially small per-shard match cap
    # complete (truncation psum fail-open), and a killed shard holds
    # delivery 1.0 via the host tables.  The scaling ratio is a
    # tracking number — 8 host threads share one CPU, so the ≥6x
    # claim belongs to bench.py's r06 hardware round
    mcs = out["multichip_serve"]
    assert mcs["gate_hint_parity_all"], mcs
    assert mcs["gate_truncation_failopen"], mcs
    assert mcs["gate_shard_kill_failover"], mcs
    assert mcs["devices"] == 8 and mcs["mesh"]["tp"] > 1, mcs
    assert mcs["single_topics_per_s"] > 0, mcs
    assert mcs["mesh_topics_per_s"] > 0, mcs
    assert "gate_scaling_ge_6x_at_8" in mcs, mcs
    assert mcs["measured_on"] == "cpu", mcs
    # prefix-EP routed vs replicated A/B (ISSUE 16): routed answers
    # are bit-parity with the replicated backend, a root-skewed
    # corpus overflows the bucket grid and fails open complete, the
    # per-shard processed width honors tp*C <= ceil(slack*Bl/tp),
    # and a killed shard raises before routing (delivery 1.0 via the
    # host tables).  Routed speedup is a tracking number off-hardware.
    mce = out["multichip_ep"]
    assert mce["gate_routed_parity_all"], mce
    assert mce["gate_overflow_failopen"], mce
    assert mce["gate_shard_width_le_batch_over_tp"], mce
    assert mce["gate_shard_kill_failover"], mce
    assert mce["devices"] == 8 and mce["mesh"]["tp"] > 1, mce
    assert mce["routed_shard_width"] <= mce["replicated_shard_width"], mce
    assert mce["ici_bytes_per_batch"] > 0, mce
    assert mce["overflow_rows_flagged"] > 0, mce
    assert mce["replicated_topics_per_s"] > 0, mce
    assert mce["routed_topics_per_s"] > 0, mce
    assert "gate_auto_within_5pct" in kj, kj
    assert kj["autotune_picks"], kj
    # load-adaptive plane A/B (ISSUE 20): the overflow EWMA grew the
    # bucket grid at least once with every row complete through the
    # compile window (fail-open, zero breaker strikes), one balance
    # pass cut the worst shard's row share >= 1.5x on the skewed
    # corpus, the post-remap routed rows are bit-parity with the
    # replicated backend, the override map survives a cold start, and
    # an injected ep.rebalance fault stages nothing.  The adaptive
    # speedup is a tracking number (host threads share one CPU).
    mcb = out["multichip_balance"]
    assert mcb["gate_grow_zero_drops"], mcb
    assert mcb["gate_balance_width_ge_1_5x"], mcb
    assert mcb["gate_routed_parity_all"], mcb
    assert mcb["gate_coldstart_placement_restored"], mcb
    assert mcb["gate_rebalance_fault_noop"], mcb
    assert mcb["devices"] == 8 and mcb["mesh"]["tp"] > 1, mcb
    assert mcb["ep_resizes"] >= 1, mcb
    assert mcb["moved_roots"] >= 1, mcb
    assert mcb["worst_width_ratio_x"] >= 1.5, mcb
    assert mcb["adaptive_worst_width"] < mcb["static_worst_width"], mcb
    # streaming table lifecycle A/B (ISSUE 9): segment cold start >=10x
    # the full rebuild at bench scale, arrays byte-identical after the
    # round trip, and the churn soak sustains mutations across >=1 live
    # segment swap with zero waiters stalled toward the prefetch
    # timeout (the acceptance gate booleans ride in the JSON)
    tl = out["table_lifecycle"]
    cold = tl["cold_start"]
    assert cold["arrays_identical"], cold
    assert cold["gate_cold_start_10x"], cold
    churn = tl["churn"]
    assert churn["ops"] > 0 and churn["prefetches"] > 0, churn
    assert churn["segment_swaps"] >= 1, churn
    assert churn["gate_zero_stalls"], churn
    # the host-dependent stall bound is recorded: the tight 2x-budget
    # bound on multi-core hosts (the build thread gets its own core),
    # the prefetch-timeout fallback on the 1-core bench VM
    import os as _os
    want = "2x_budget" if (_os.cpu_count() or 1) > 1 \
        else "prefetch_timeout"
    assert churn["stall_bound"] == want, churn
    # adversarial admission A/B (ISSUE 14): flag-on holds honest
    # delivery 1.0 with no honest client ever flagged while the ladder
    # limits the attackers (throttle/quarantine/ban/refused CONNECTs);
    # the p99-vs-clean ratios are recorded for the bench (latency
    # ratios on a loaded CI box are noise — the 1.5x gate boolean rides
    # the JSON with a 50 ms noise floor and is asserted as present)
    adv = out["adversarial"]
    assert adv["attack_on"]["honest"]["sent"] > 0, adv
    assert adv["gate_honest_delivery"], adv
    assert adv["gate_attackers_limited"], adv
    assert adv["gate_no_honest_flagged"], adv
    assert "gate_honest_p99" in adv and "p99_off_vs_clean" in adv, adv
    assert adv["attack_on"]["bans"] >= 1 \
        or adv["attack_on"]["decisions"], adv
    # staticcheck gate row (ISSUE 19): the cold full-tree scan ran in
    # a subprocess against a throwaway cache, came back clean (exit 0,
    # zero live waivers — staticcheck-waivers.json is empty by policy)
    # and under the bench-box cold budget, with all 13 rules active
    sc = out["staticcheck"]
    assert sc["gate_clean"], sc
    assert sc["exit_code"] == 0, sc
    assert sc["gate_budget"], sc
    assert sc["rules"] == 13, sc
    assert sc["cold_s"] > 0, sc
    assert "0 finding(s)" in sc["summary"], sc
    # chaos smoke: one kill-and-recover cycle per subsystem (including
    # the ISSUE-7 serve plane under "match"), each healing via
    # supervisor restart with delivery intact
    for name, section in out["chaos"].items():
        if section.get("skipped"):
            continue
        assert section["ok"], (name, section)
        assert section["restarts"] >= 1, (name, section)
    match = out["chaos"]["match"]
    assert match["delivery_ratio"] == 1.0, match
    assert match["breaker_tripped"] and match["breaker_recovered"], match
    # serve-pipeline chaos (ISSUE 11): readback child killed mid-storm
    # + 10% injected match.readback faults both hold delivery 1.0 with
    # waiters failing over to the CPU trie, and the two-phase readback
    # shipped real (non-slab) byte counts
    pc = out["chaos"]["pipeline"]
    assert pc["delivery_ratio"] == 1.0, pc
    assert pc["readback_faults"] >= 1, pc
    assert pc["readback_bytes"] > 0, pc
    # table-lifecycle chaos (ISSUE 9): swap fault + compact kill both
    # heal with delivery intact; a corrupt segment checksum-rejects and
    # the full rebuild serves
    seg = out["chaos"]["segments"]
    assert seg["delivery_ratio"] == 1.0, seg
    assert seg["corrupt_segment_rejected"] and seg["rebuild_served"], seg
    assert seg["swap_fault_recovered"] and seg["kill_resumed"], seg
    # admission chaos (ISSUE 14): scorer killed + held down by a
    # persistent injected fault mid-storm → FAIL-OPEN (standing
    # decisions clear, admission_degraded raised, attacker traffic
    # flows — never a new drop path), zero honest drops attributable
    # to admission, supervised restart resumes scoring and clears the
    # alarm; a 10%-fault storm holds delivery 1.0 too
    ac = out["chaos"]["admission"]
    assert ac["delivery_ratio"] == 1.0, ac
    assert ac["quarantined_then_shed"], ac
    assert ac["honest_never_flagged"], ac
    assert ac["failed_open"] and ac["no_new_drop_path"], ac
    assert ac["alarm_raised_and_cleared"], ac
    assert ac["requarantined_after_restart"], ac
    assert ac["score_faults"] >= 1 and ac["fail_opens"] >= 1, ac
    # degraded-mesh chaos (ISSUE 18): shard killed mid-storm with the
    # degraded flag on → scoped failover serves (degraded batches
    # counted), the mesh_degraded alarm + flightrec dump fire, the
    # supervised rebuild survives one injected mesh.rebuild crash
    # (the restart evidence), and the canary re-admits the shard —
    # delivery 1.0 across the whole cycle, ladder back to healthy
    mdc = out["chaos"]["mesh"]
    assert mdc["delivery_ratio"] == 1.0, mdc
    assert mdc["degraded_batches"] >= 1, mdc
    assert mdc["rebuilds"] >= 1, mdc
    assert mdc["alarm_raised_and_cleared"], mdc
    assert mdc["flightrec_dumped"], mdc
    assert mdc["mesh_state"] == 0, mdc
