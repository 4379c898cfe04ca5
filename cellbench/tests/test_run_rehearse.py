"""One whole ``--rehearse`` run per cell on the CPU, ending in a
well-formed last line; the controls and a timed path broken underneath
come out as not correct.  Run by hand before the chip:

    JAX_PLATFORMS=cpu python -m pytest cellbench/tests -q
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]


def listed(cell):
    return sum(1 for m in BENCH["per_layer"]
               if cell in m.get("workloads", [cell]))


def run_cell(*extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--rehearse", "--seconds",
         "3", *extra], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", ["0", "1"])
def test_whole_run(cell, trace):
    line, err = run_cell("--workload", cell, "--seed", "3000000007",
                         "--trace", trace)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    kind = "per_layer" if trace == "1" else "end_to_end"
    known = {m["name"]: m["unit"] for m in BENCH[kind]}
    for name, m in line["metrics"].items():
        assert known[name] == m["unit"] and isinstance(m["value"], float)
    if trace == "0":
        assert set(line["metrics"]) == set(known)
    else:
        # a CPU run has no device plane: device metrics are left out,
        # never written from a CPU number
        assert "device_idle_pct" not in line["metrics"]
        assert "busy_s" not in line["device"]
        # and every other listed metric is read: the node has them all
        assert len(line["metrics"]) == listed(cell) - 2
        assert line["window"]["layers_left_out"] == []
    for name, c in line["compared"].items():
        assert f"compared {name} = " in err


@pytest.mark.parametrize("cell", CELLS)
def test_a_dropped_delivery_is_not_correct(cell):
    line, _err = run_cell("--workload", cell, "--seed", "11", "--trace",
                          "0", "--control", "qos0_loss")
    assert line["correct"] is False
    assert line["compared"]["missing"]["value"] > 0


def test_an_approximate_match_set_is_not_correct():
    cell = next(w["name"] for w in BENCH["workloads"]
                if w["config"] == "wild1m")
    line, _err = run_cell("--workload", cell, "--seed", "12", "--trace",
                          "0", "--control", "approx_match")
    assert line["correct"] is False
    assert line["compared"]["device_mismatch"]["value"] > 0


def test_a_listed_metric_with_nothing_to_read_fails_the_traced_run():
    """A span, counter or kernel that took no sample reads None: the
    traced run ends without a result; only where ``must`` lets it go is it
    left out (cellbench/tests/test_layers.py has the readers' cases)."""
    sys.path.insert(0, ROOT)
    from cellbench import run as RUN

    want = [("match_wait_p95_ms", "ms"), ("nfa_match_roofline", "%")]
    read = {"match_wait_p95_ms": 8.5, "nfa_match_roofline": None}
    with pytest.raises(RUN.BenchError, match="nfa_match_roofline"):
        RUN.read_layers(want, read.get, must=lambda name: True)
    layers, left_out = RUN.read_layers(want, read.get,
                                       must=lambda name: False)
    assert layers == {"match_wait_p95_ms": {"value": 8.5, "unit": "ms"}}
    assert left_out == []


def gone(counts):
    """The histogram under another name, as on a tree from before the
    span was added (or after a rename): the node registers none of it."""
    return {("x." + k if k.endswith("match_wait") else k): v
            for k, v in counts.items()}


def silent(counts):
    """The histogram is registered and nothing records into it."""
    return {k: ([0] * len(v) if k.endswith("match_wait") else v)
            for k, v in counts.items()}


@pytest.mark.parametrize("underneath, ends", [(gone, "left_out"),
                                              (silent, "no_result")])
def test_a_histogram_gone_is_left_out_and_a_silent_one_ends_the_run(
        underneath, ends, monkeypatch, capsys):
    """The whole traced run with one histogram changed underneath."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, ROOT)
    from cellbench import run as RUN

    good = RUN.Deployment.hist_counts
    monkeypatch.setattr(RUN.Deployment, "hist_counts",
                        lambda self: underneath(good(self)))
    rc = RUN.main(["--workload", CELLS[0], "--seed", "14", "--seconds", "3",
                   "--trace", "1", "--rehearse"])
    out, err = capsys.readouterr()
    assert "match_wait_p95_ms" in err
    if ends == "no_result":
        assert rc != 0 and not out.strip()
        return
    assert rc == 0
    line = json.loads(out.strip().splitlines()[-1])
    assert line["window"]["layers_left_out"] == ["match_wait_p95_ms"]
    assert "match_wait_p95_ms" not in line["metrics"]
    assert line["correct"] is True
    assert len(line["metrics"]) == listed(CELLS[0]) - 3


def test_no_accelerator_and_no_rehearse_exits_non_zero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and not p.stdout.strip()


@pytest.mark.parametrize("cell", CELLS)
def test_an_answer_altered_where_it_is_produced(cell, monkeypatch, capsys):
    """The rest of a run with the timed path broken underneath: every
    seventh route assembly from a device hint loses its last route."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, ROOT)
    from cellbench import run as RUN
    from emqx_tpu.broker.router import Router

    good = Router.routes_with_wild
    calls = [0]

    def broken(self, name, wild_filters):
        routes = good(self, name, wild_filters)
        calls[0] += 1
        return routes[:-1] if calls[0] % 7 == 0 else routes

    monkeypatch.setattr(Router, "routes_with_wild", broken)
    rc = RUN.main(["--workload", cell, "--seed", "13", "--seconds", "3",
                   "--trace", "0", "--rehearse"])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False
    c = line["compared"]
    assert c["missing"]["value"] + c["device_mismatch"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_a_host_wide_stall_loses_no_delivery(cell):
    """Node and generators stopped together for 5 s inside the window
    (``stall_probe.py``): every delivery still arrives, late.  This holds
    the probe and a run's way through a stall; the fault itself (the
    node's default session queue of 1,000 shedding QoS 1: ``missing``
    717 on the chip, 0 with the configuration's queue, PERF.md 6) does
    not show at rehearsal size, where the default reads 0 as well."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "cellbench", "tests",
                                      "stall_probe.py"), "1", "5", "--",
         "--rehearse", "--seconds", "4", "--workload", cell, "--seed", "15",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["window"]["host"]["loop_stall_max_ms"] > 4000
    assert line["compared"]["missing"]["value"] == 0
    assert line["correct"] is True
