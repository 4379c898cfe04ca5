"""The four-chip cell's rehearsal on four CPU devices (``conftest.py``):
its five mesh metrics read in a traced run, the line is well formed, a
tree without the two mesh spans (the parent of the PR that added them)
leaves exactly those two out by name, and the ``approx_match`` control
comes out as not correct.  Run by hand, with the rest of this directory:

    JAX_PLATFORMS=cpu python -m pytest cellbench/tests -q
"""

import json
import os
import sys

from test_run_rehearse import BENCH, ROOT, listed, run_cell

CELL = "wild1m_tp4.fanin_fresh"
MESH = sorted(m["name"] for m in BENCH["per_layer"]
              if m.get("workloads") == [CELL])
SPANS = ["mesh_fetch_p50_ms", "mesh_decode_p50_ms"]


def test_the_cell_is_declared_as_the_issue_names_it():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "wild1m_tp4", "fanin_fresh_tp4", 4)
    assert MESH == sorted(["mesh_served_pct", "ep_routed_pct",
                           "ep_overflow_pct", *SPANS])
    with open(os.path.join(ROOT, "cellbench", "configs",
                           "wild1m.json")) as f:
        one = json.load(f)
    with open(os.path.join(ROOT, "cellbench", "configs",
                           "wild1m_tp4.json")) as f:
        four = json.load(f)
    # the one-chip cell's deployment on the mesh: same table, same
    # guarantees' words for delivery, same limits, not one loosened
    for key in ("table", "bulk_sessions", "publishers", "subscriber_qos",
                "device_answer_sample", "limits", "rehearse", "reduced"):
        assert four[key] == one[key], key
    assert four["guarantees"]["qos"] == one["guarantees"]["qos"] == 1
    assert {k: v for k, v in four["node_config"].items()
            if k not in one["node_config"]} == {
        "match.multichip.enable": True, "match.multichip.tp": 4,
        "match.multichip.ep.enable": True}
    assert {k: four["node_config"][k] for k in one["node_config"]} == \
        one["node_config"]
    # the traffic: fanin_fresh's mix but for the rate and the warm-up cap
    with open(os.path.join(ROOT, "cellbench", "traffic",
                           "fanin_fresh.json")) as f:
        mix1 = json.load(f)
    with open(os.path.join(ROOT, "cellbench", "traffic",
                           "fanin_fresh_tp4.json")) as f:
        mix4 = json.load(f)
    for mix in (mix1, mix4):
        mix.pop("name")
        mix.pop("rate_msgs_per_s")
        mix["warmup"].pop("max_s")
    assert mix4 == mix1


def test_a_traced_rehearsal_reads_the_five_mesh_metrics():
    line, _err = run_cell("--workload", CELL, "--seed", "3600000011",
                          "--trace", "1")
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 4
    got = {k: v["value"] for k, v in line["metrics"].items()}
    # no batch off the mesh; the window's edges may cut one batch
    # between its dispatch and its collection (the two counters)
    assert abs(got["mesh_served_pct"] - 100.0) < 1.0
    assert got["ep_routed_pct"] == 100.0
    assert 0.0 <= got["ep_overflow_pct"] < 25.0
    assert got["mesh_fetch_p50_ms"] > 0 and got["mesh_decode_p50_ms"] > 0
    # inside the readback stage that calls them (its own file is listed
    # for the one-chip cell alone, so its number is not on this line)
    assert line["window"]["layers_left_out"] == []
    # every listed metric but the device plane's one, which no CPU has
    assert "device_idle_pct" not in line["metrics"]
    assert len(line["metrics"]) == listed(CELL) - 1
    counters = line["window"]["counters"]
    assert abs(counters["tpu.match.shard_dispatches"]
               - counters["tpu.match.batches"]) <= 1


def test_a_tree_without_the_mesh_spans_leaves_those_two_out(monkeypatch,
                                                            capsys):
    """What the parent's traced line looks like: the three ratios read
    (every counter existed), the two histograms are left out by name."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, ROOT)
    from cellbench import run as RUN

    good = RUN.Deployment.hist_counts
    monkeypatch.setattr(
        RUN.Deployment, "hist_counts",
        lambda self: {k: v for k, v in good(self).items()
                      if not k.startswith("obs.stage.mesh_")})
    rc = RUN.main(["--workload", CELL, "--seed", "3600000012", "--seconds",
                   "3", "--trace", "1", "--rehearse"])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["window"]["layers_left_out"] == SPANS
    assert set(MESH) - set(line["metrics"]) == set(SPANS)
    assert len(line["metrics"]) == listed(CELL) - 3


def test_an_approximate_match_set_is_not_correct_on_the_mesh():
    line, _err = run_cell("--workload", CELL, "--seed", "3600000013",
                          "--trace", "0", "--control", "approx_match")
    assert line["correct"] is False
    assert line["compared"]["device_mismatch"]["value"] > 0
    assert line["compared"]["missing"]["value"] == 0
