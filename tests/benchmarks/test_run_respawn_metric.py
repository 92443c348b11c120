"""``run_respawn_s`` (PERF.md section 3): the supervisor's stretch from
one run's reap to the next run's go, as the run that waited for it
stored it — the ``respawn`` rows of ``nmz_run_phase_seconds``, sum over
count, in the registry of the search home that first met those runs,
read by the general reader. Declared, data only, for the cells whose
traffic is a ``campaign`` — taken from the manifest, so the next live
cell does not break this file — and left out where no run stored the
row, as on a commit before it."""

import json
import os
import sys

import pytest

import tiny_root

sys.path.insert(0, tiny_root.BENCH)

import layer_metrics  # noqa: E402
import manifest  # noqa: E402

from namazu_tpu.obs import metrics, spans  # noqa: E402

NAME = "run_respawn_s"


def met(reg, respawns):
    """Stored runs observed the way the search home's ingest does it,
    one a ``respawns`` entry (the seconds its supervisor told it; None:
    a campaign's first run, or a run of a commit that stores no such
    row); the registry document as the ``metrics`` op serves it."""
    old = metrics.set_registry(reg)
    was_on = metrics.enabled()
    metrics.configure(True)
    try:
        for seconds in respawns:
            rows = [["standby", None, -2.5, 2.5], ["boot", None, 0.0, 0.01],
                    ["prepare", None, 0.01, 0.25]]
            if seconds is not None:
                rows.insert(0, ["respawn", None, -seconds, seconds])
            spans.run_phases_observed(rows)
        return json.loads(json.dumps(reg.to_jsonable()))
    finally:
        metrics.configure(was_on)
        metrics.set_registry(old)


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(tiny_root.REPO)


def test_run_respawn_s_is_declared_once_for_the_campaign_cells(man):
    man.validate()
    decl = man.layer_metric(NAME)
    assert decl["reduce"] == "per" and "scale" not in decl
    for side, field in (("value", "sum"), ("other", "count")):
        assert decl[side] == {"kind": "counter", "name": spans.RUN_PHASE,
                              "labels": {"phase": spans.RESPAWN_PHASE},
                              "field": field}
    entry = man.per_layer[NAME]
    assert man.doc["per_layer"].count(entry) == 1
    assert [m["name"] for m in man.doc["per_layer"]].count(NAME) == 1
    assert (entry["unit"], entry["better"], entry["source"]) == (
        "s", "lower", "program_counter")
    assert (entry["layer"], entry["moves"]) == (
        "campaign supervisor", "searched_runs_per_hour")
    live = [w["name"] for w in man.doc["workloads"]
            if man.traffic(w)["kind"] == "campaign"]
    assert live and sorted(entry["workloads"]) == sorted(live)
    for cell in man.doc["workloads"]:
        listed = NAME in {m["name"] for m in
                          man.metrics_of(cell["name"], "per_layer")}
        assert listed == (cell["name"] in live)
    # the declaration is data: the one file it adds
    assert os.path.exists(os.path.join(
        tiny_root.BENCH, "layer_metrics", NAME + ".json"))


@pytest.mark.parametrize("respawns, mean", [
    ([0.0625] * 11, 0.0625),
    ([0.03125, 0.0625, 0.09375], 0.0625),
    ([3.0, None, 3.125, None], 3.0625)],
    ids=["every_run_the_same", "a_mean_over_the_runs_met",
         "a_run_without_the_row_counts_on_neither_side"])
def test_run_respawn_s_is_the_sum_over_the_count(man, respawns, mean):
    reg = metrics.MetricsRegistry()
    # set-up: the campaign's first run has none, its second the first
    before = met(reg, [None, 7.0])
    after = met(reg, respawns)
    assert layer_metrics.evaluate(man.layer_metric(NAME), {
        "metrics_before": before, "metrics_after": after}) \
        == pytest.approx(mean, abs=1e-12)


def test_run_respawn_s_is_left_out_where_no_run_stored_the_row(man):
    """A program whose runs are not told their respawn (the parent
    commit) and a fleet cell (synthesised histories carry no phases):
    nothing to read, nothing reported, nothing raised."""
    reg = metrics.MetricsRegistry()
    before = met(reg, [None])
    after = met(reg, [None] * 7)
    assert '"respawn"' not in json.dumps(after)
    decl = man.layer_metric(NAME)
    assert layer_metrics.evaluate(decl, {
        "metrics_before": before, "metrics_after": after}) is None
    assert layer_metrics.evaluate(decl, {
        "metrics_before": {"metrics": []},
        "metrics_after": {"metrics": []}}) is None


def test_run_respawn_s_is_left_out_where_the_window_met_no_run(man):
    reg = metrics.MetricsRegistry()
    before = met(reg, [None, 0.0625, 0.0625])
    assert layer_metrics.evaluate(man.layer_metric(NAME), {
        "metrics_before": before, "metrics_after": before}) is None
