"""The per-layer metrics that read a phase histogram as seconds per
observation (PERF.md section 3): the eight ``run_<phase>_s`` of the two
live cells, from ``nmz_run_phase_seconds`` as the search home observes
it of every run it first meets, and ``encode`` / ``save`` / ``load``
``_s_per_request`` of every cell, from the request spans that were there
and that nothing read. Data files only: declared where stated, sum over
count of the window's delta, left out where the family holds nothing."""

import json
import os
import sys

import pytest

import tiny_root

sys.path.insert(0, tiny_root.BENCH)

import layer_metrics  # noqa: E402
import manifest  # noqa: E402

from namazu_tpu.obs import metrics, spans  # noqa: E402

LIVE = ["zk2212-fle3.live", "zk2212-fle3.live-d64"]
#: name -> (family, phase, layer, cells; None = every cell)
METRICS = dict(
    {f"run_{phase}_s": (spans.RUN_PHASE, phase, "campaign supervisor", LIVE)
     for phase in spans.RUN_PHASES},
    encode_s_per_request=(spans.SEARCH_PHASE, "encode", "search driver",
                          None),
    save_s_per_request=(spans.SEARCH_PHASE, "save", "search home", None),
    load_s_per_request=(spans.SEARCH_PHASE, "load", "search home", None))
NAMES = sorted(METRICS)


def observed(reg, family, phase, seconds):
    """``seconds`` observed into ``family{phase}`` the way the program
    does it; the registry document as the ``metrics`` op serves it."""
    old = metrics.set_registry(reg)
    was_on = metrics.enabled()
    metrics.configure(True)
    try:
        for s in seconds:
            spans._observe_phase(family, phase, s)
        return json.loads(json.dumps(reg.to_jsonable()))
    finally:
        metrics.configure(was_on)
        metrics.set_registry(old)


@pytest.fixture(scope="module")
def man():
    man = manifest.Manifest(tiny_root.REPO)
    man.validate()
    return man


def test_the_eight_run_metrics_name_the_runs_own_phases():
    assert len(NAMES) == 11
    assert sorted(n for n in NAMES if n.startswith("run_")) == sorted(
        f"run_{p}_s" for p in ("boot", "prepare", "testee", "drain",
                               "search", "endpoints", "validate", "record"))


@pytest.mark.parametrize("name", NAMES)
def test_the_metric_is_declared_for_its_cells_and_no_other(man, name):
    family, phase, layer, cells = METRICS[name]
    entry = man.per_layer[name]
    assert man.doc["per_layer"].count(entry) == 1
    assert entry.get("workloads") == cells
    assert (entry["unit"], entry["better"], entry["source"],
            entry["layer"], entry["moves"]) == (
        "s", "lower", "program_counter", layer, "searched_runs_per_hour")
    every = [w["name"] for w in man.doc["workloads"]]
    reported = [c for c in every if name in {
        m["name"] for m in man.metrics_of(c, "per_layer")}]
    assert reported == (cells or every)
    decl = man.layer_metric(name)
    assert decl["reduce"] == "per" and "scale" not in decl
    for side, field in (("value", "sum"), ("other", "count")):
        assert decl[side] == {"kind": "counter", "name": family,
                              "labels": {"phase": phase}, "field": field}
    assert os.path.exists(os.path.join(
        tiny_root.BENCH, "layer_metrics", name + ".json"))


@pytest.mark.parametrize("name", NAMES)
def test_the_metric_is_sum_over_count_of_the_windows_delta(man, name):
    family, phase, _, _ = METRICS[name]
    reg = metrics.MetricsRegistry()
    before = observed(reg, family, phase, [9.0, 7.0])  # set-up
    observed(reg, family, "another_phase", [100.0])
    after = observed(reg, family, phase, [0.25, 0.5, 0.75, 1.5])
    obs = {"metrics_before": before, "metrics_after": after}
    assert layer_metrics.evaluate(man.layer_metric(name), obs) \
        == pytest.approx(0.75, abs=1e-12)
    # a sidecar whose registry was empty when the window opened
    obs = {"metrics_before": {"metrics": []}, "metrics_after": after}
    assert layer_metrics.evaluate(man.layer_metric(name), obs) \
        == pytest.approx(19.0 / 6, abs=1e-12)


@pytest.mark.parametrize("name", NAMES)
def test_the_metric_is_left_out_where_nothing_was_observed(man, name):
    """A commit without the family, a fleet cell (synthesised histories
    carry no phases) and a window that met no new run: nothing to read,
    nothing reported, nothing raised."""
    family, phase, _, _ = METRICS[name]
    decl = man.layer_metric(name)
    reg = metrics.MetricsRegistry()
    other = observed(reg, spans.SEARCH_PHASE, "evolve", [0.01])
    assert layer_metrics.evaluate(decl, {
        "metrics_before": other, "metrics_after": other}) is None
    met = observed(reg, family, phase, [0.5])
    assert layer_metrics.evaluate(decl, {
        "metrics_before": met, "metrics_after": met}) is None
    assert layer_metrics.evaluate(decl, {}) is None
