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
    """A renamed span, counter or kernel reads None: the traced run ends
    without a result; only where ``must`` lets it go is it left out."""
    sys.path.insert(0, ROOT)
    from cellbench import run as RUN

    want = [("match_wait_p95_ms", "ms"), ("nfa_match_roofline", "%")]
    read = {"match_wait_p95_ms": 8.5, "nfa_match_roofline": None}
    with pytest.raises(RUN.BenchError, match="nfa_match_roofline"):
        RUN.read_layers(want, read.get, must=lambda name: True)
    layers = RUN.read_layers(want, read.get, must=lambda name: False)
    assert layers == {"match_wait_p95_ms": {"value": 8.5, "unit": "ms"}}


def test_a_silent_span_ends_the_traced_rehearsal_without_a_result(
        monkeypatch, capsys):
    """The whole run with one histogram gone underneath (as after a
    rename in the program): exit non-zero, no result line."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, ROOT)
    from cellbench import run as RUN

    good = RUN.Deployment.hist_counts

    def renamed(self):
        return {("x." + k if k.endswith("match_wait") else k): v
                for k, v in good(self).items()}

    monkeypatch.setattr(RUN.Deployment, "hist_counts", renamed)
    rc = RUN.main(["--workload", CELLS[0], "--seed", "14", "--seconds", "3",
                   "--trace", "1", "--rehearse"])
    out, err = capsys.readouterr()
    assert rc != 0 and not out.strip()
    assert "match_wait_p95_ms" in err


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
