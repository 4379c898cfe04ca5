"""The per-layer readers: what a listed metric does when the running tree
cannot have it (left out, by name), when it has it and it stayed silent
(the traced run fails), and ``BENCHMARK.json`` against the metric files
and the names the program registers."""

import json
import os

import pytest

from cellbench import run as RUN
from emqx_tpu.observe.hist import HIST_NAMES, N_BUCKETS
from emqx_tpu.observe.metrics import Metrics

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
FILES = sorted(f[:-5] for f in os.listdir(
    os.path.join(ROOT, "cellbench", "layer_metrics")))

HIST = {"kind": "hist_delta", "hist": "obs.stage.match_window",
        "stat": "p50"}
RATIO = {"kind": "counter_ratio", "num": ["tpu.match.cycle_spanned_ns"],
         "den": ["tpu.match.cycle_ns"], "scale": 100.0}
PER_PUBLISH = {"kind": "counter_ratio", "num": ["tpu.match.hint_served"],
               "den": "window_publishes", "scale": 100.0}


def counts(**at):
    c = [0] * N_BUCKETS
    for idx, n in at.items():
        c[int(idx[1:])] = n
    return c


def read(spec, h0=None, h1=None, delta=None):
    if spec["kind"] == "hist_delta":
        return RUN.hist_value(spec, h0 or {}, h1 or {})
    return RUN.counter_value(spec, delta or {}, 1000)


WINDOW = "obs.stage.match_window"
CASES = {
    # name: (what the reader returns, what a TRACED run makes of it)
    "histogram_the_node_does_not_register": (
        lambda: read(HIST, {"obs.stage.other": counts()},
                     {"obs.stage.other": counts(b300=4)}), "left_out"),
    "histogram_registered_and_silent": (
        lambda: read(HIST, {WINDOW: counts(b300=4)},
                     {WINDOW: counts(b300=4)}), "fails"),
    "histogram_with_samples": (
        lambda: read(HIST, {WINDOW: counts(b300=4)},
                     {WINDOW: counts(b300=4, b310=9)}), "read"),
    "counters_the_registry_does_not_know": (
        lambda: read(RATIO, delta={"tpu.match.batches": 5}), "left_out"),
    "known_counter_with_a_zero_denominator": (
        lambda: read(RATIO, delta={"tpu.match.cycle_ns": 0,
                                   "tpu.match.cycle_spanned_ns": 0}),
        "fails"),
    "one_of_the_counters_known": (
        lambda: read(RATIO, delta={"tpu.match.cycle_ns": 0}), "fails"),
    "counters_that_moved": (
        lambda: read(RATIO, delta={"tpu.match.cycle_ns": 200,
                                   "tpu.match.cycle_spanned_ns": 198}),
        "read"),
    "per_publish_counter_unknown": (
        lambda: read(PER_PUBLISH, delta={"tpu.match.batches": 5}),
        "left_out"),
    "per_publish_counter_known": (
        lambda: read(PER_PUBLISH, delta={"tpu.match.hint_served": 990}),
        "read"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_what_a_traced_run_makes_of_a_reader(case):
    value_of, then = CASES[case]
    want = [("the_metric", "ms"), ("another", "ms")]

    def value(name):
        return value_of() if name == "the_metric" else 1.5

    if then == "fails":
        assert value("the_metric") is None
        with pytest.raises(RUN.BenchError, match="the_metric"):
            RUN.read_layers(want, value, must=lambda name: True)
        # an untraced run prints no per-layer metric: it may be silent
        layers, left_out = RUN.read_layers(want, value,
                                           must=lambda name: False)
        assert list(layers) == ["another"] and left_out == []
        return
    layers, left_out = RUN.read_layers(want, value, must=lambda name: True)
    assert layers["another"] == {"value": 1.5, "unit": "ms"}
    if then == "left_out":
        assert value("the_metric") is RUN.ABSENT
        assert left_out == ["the_metric"] and "the_metric" not in layers
    else:
        assert left_out == [] and layers["the_metric"]["value"] > 0


def test_a_counter_ratio_reads_its_ratio():
    assert RUN.counter_value(RATIO, {"tpu.match.cycle_ns": 200,
                                     "tpu.match.cycle_spanned_ns": 198},
                             1000) == 99.0
    assert RUN.counter_value(PER_PUBLISH, {"tpu.match.hint_served": 990},
                             1000) == 99.0


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["per_layer"]])
def test_a_listed_metric_has_a_file_the_program_can_feed(name):
    """Every listed name has a reader's file, and what the file reads is a
    histogram or a counter the program registers today (a misspelt one
    would be left out of every run, by name, and nobody would fail)."""
    assert name in FILES
    with open(os.path.join(ROOT, "cellbench", "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    if spec["kind"] == "hist_delta":
        assert spec["hist"] in HIST_NAMES
        assert spec["stat"] in ("p50", "p95", "p99")
    elif spec["kind"] == "counter_ratio":
        known = Metrics().all()
        den = [] if spec["den"] == "window_publishes" else spec["den"]
        assert all(k in known for k in spec["num"] + den)
    else:
        assert spec["kind"] in ("generator", "harness", "trace",
                                "trace_roofline")


def test_every_metric_file_is_listed():
    assert FILES == sorted(m["name"] for m in BENCH["per_layer"])
