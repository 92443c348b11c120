"""``run_standby_share`` (PERF.md section 3): runs that were started
from a standby child over the runs a supervisor spawned, among the runs
the search home first met in the window — the count of the ``standby``
rows over the count of the ``boot`` rows of ``nmz_run_phase_seconds``,
read by the general reader. Declared for the two live cells, data only,
and left out where no standby was observed, as on a commit before it."""

import json
import os
import sys

import pytest

import tiny_root

sys.path.insert(0, tiny_root.BENCH)

import layer_metrics  # noqa: E402
import manifest  # noqa: E402

from namazu_tpu.obs import metrics, spans  # noqa: E402

NAME = "run_standby_share"
LIVE = ["zk2212-fle3.live", "zk2212-fle3.live-d64"]


def met(reg, runs):
    """The rows of ``runs`` stored runs observed the way the search
    home's ingest does it (True: the run's child stood by; False: it
    started cold; None: nobody spawned it, so it has no ``boot``
    either); the registry document as the ``metrics`` op serves it."""
    old = metrics.set_registry(reg)
    was_on = metrics.enabled()
    metrics.configure(True)
    try:
        for warm in runs:
            rows = [["prepare", None, 0.5, 0.25]]
            if warm is not None:
                rows.insert(0, ["boot", None, 0.0, 0.01 if warm else 0.5])
            if warm:
                rows.insert(0, ["standby", None, -2.5, 2.5])
            spans.run_phases_observed(rows)
        return json.loads(json.dumps(reg.to_jsonable()))
    finally:
        metrics.configure(was_on)
        metrics.set_registry(old)


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(tiny_root.REPO)


def test_run_standby_share_is_declared_for_the_two_live_cells(man):
    man.validate()
    decl = man.layer_metric(NAME)
    assert decl["reduce"] == "share_of" and "scale" not in decl
    for side, phase in (("value", spans.STANDBY_PHASE), ("other", "boot")):
        assert decl[side] == {"kind": "counter", "name": spans.RUN_PHASE,
                              "labels": {"phase": phase}, "field": "count"}
    entry = man.per_layer[NAME]
    assert man.doc["per_layer"].count(entry) == 1
    assert (entry["unit"], entry["better"], entry["source"]) == (
        "%", "higher", "program_counter")
    assert (entry["layer"], entry["moves"]) == (
        "campaign supervisor", "searched_runs_per_hour")
    assert entry["workloads"] == LIVE
    for cell in man.doc["workloads"]:
        listed = NAME in {m["name"] for m in
                          man.metrics_of(cell["name"], "per_layer")}
        assert listed == (cell["name"] in LIVE)
    # the declaration is data: the one file it adds (no pin on its
    # place in the list: the next appended metric would break it)
    assert os.path.exists(os.path.join(
        tiny_root.BENCH, "layer_metrics", NAME + ".json"))


@pytest.mark.parametrize("runs, share", [
    ([True] * 15, 100.0),
    ([True, True, False, True], 75.0),
    ([True, None, None, False], 50.0)],
    ids=["every_run_warm", "one_retry_started_cold",
         "runs_nobody_spawned_count_on_neither_side"])
def test_run_standby_share_is_read_from_the_two_counts(man, runs, share):
    reg = metrics.MetricsRegistry()
    # set-up: the campaign's first run is cold, its second already warm
    before = met(reg, [False, True])
    after = met(reg, runs)
    assert layer_metrics.evaluate(man.layer_metric(NAME), {
        "metrics_before": before, "metrics_after": after}) \
        == pytest.approx(share, abs=1e-9)


def test_run_standby_share_is_left_out_without_a_standby_row(man):
    """A program whose supervisor keeps no standby (the parent commit)
    and a fleet cell (synthesised histories carry no phases): nothing
    to read, nothing reported, nothing raised."""
    reg = metrics.MetricsRegistry()
    before = met(reg, [False])
    after = met(reg, [False] * 14)
    assert '"standby"' not in json.dumps(after)
    decl = man.layer_metric(NAME)
    assert layer_metrics.evaluate(decl, {
        "metrics_before": before, "metrics_after": after}) is None
    assert layer_metrics.evaluate(decl, {
        "metrics_before": {"metrics": []},
        "metrics_after": {"metrics": []}}) is None


def test_run_standby_share_is_left_out_where_the_window_met_no_run(man):
    reg = metrics.MetricsRegistry()
    before = met(reg, [False, True, True])
    assert layer_metrics.evaluate(man.layer_metric(NAME), {
        "metrics_before": before, "metrics_after": before}) is None
