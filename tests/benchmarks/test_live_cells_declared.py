"""What every live cell reports, stated without a pin on a list's length
or a cell's place in it: a per-layer metric of the campaign supervisor
is listed for exactly the cells whose mix is a ``campaign``, whichever
they are, and ``zk2212-fle3-hunt5k.live-d1024`` (PR 43) is one of them.

(The older pins of these lists — ``test_run_phase_metrics.py``,
``test_run_standby_share_metric.py``,
``test_delay_table_share_metric.py`` — name the two live cells of their
day and went red when the third was appended; a ``benchmark`` PR
repairs them, ROADMAP R5 (iv).)"""

import json
import os
import sys

import pytest

import tiny_root

sys.path.insert(0, tiny_root.BENCH)

import layer_metrics  # noqa: E402
import manifest  # noqa: E402

from namazu_tpu.obs import metrics, spans  # noqa: E402

DEEP = "zk2212-fle3-hunt5k.live-d1024"
#: the metrics only a campaign of the real testee has something for
LIVE_ONLY = sorted(
    ["run_wall_p50_s", "live_install_p50_s", "run_standby_share"]
    + [f"run_{phase}_s" for phase in spans.RUN_PHASES])


@pytest.fixture(scope="module")
def man():
    man = manifest.Manifest(tiny_root.REPO)
    man.validate()
    return man


def live_cells(man):
    return [w["name"] for w in man.doc["workloads"]
            if man.traffic(w)["kind"] == "campaign"]


def test_the_deep_cell_is_a_live_cell_of_the_fle3_hunt(man):
    assert DEEP in live_cells(man)
    cell, shallow = man.cell(DEEP), man.cell("zk2212-fle3.live-d64")
    assert cell["chips"] == 1
    config, of_d64 = man.config(cell), man.config(shallow)
    # the same hunt at the same widths under the same guarantees: what
    # differs is the depth of the history, and only that is `reduced`
    for key in ("testee", "search", "shipped_width", "reduced"):
        assert config[key] == of_d64[key], key
    mine, theirs = dict(config["guarantees"]), dict(of_d64["guarantees"])
    mine.pop("numerics"), theirs.pop("numerics")
    assert mine == theirs
    with open(os.path.join(tiny_root.REPO, config["history"])) as f, \
            open(os.path.join(tiny_root.REPO, of_d64["history"])) as g:
        assert json.load(f) == json.load(g)
    mix, of_mix = man.traffic(cell), man.traffic(shallow)
    assert set(mix) == set(of_mix)
    assert (mix["prefill_runs"], mix["prefill_failures"]) == (1024, 68)
    assert mix["history_depth_at_start"] == (
        mix["prefill_runs"] + mix["warmup_searched_runs"])
    # twice the archive's rows and more: every request of the window
    # overwrites the whole ring
    assert mix["prefill_runs"] >= 2 * config["shipped_width"]["archive_rows"]


@pytest.mark.parametrize("name", LIVE_ONLY)
def test_a_supervisor_metric_lists_the_live_cells_and_no_other(man, name):
    entry = man.per_layer[name]
    assert entry["layer"] == "campaign supervisor"
    assert sorted(entry["workloads"]) == sorted(live_cells(man))
    for cell in man.doc["workloads"]:
        listed = name in {m["name"] for m in
                          man.metrics_of(cell["name"], "per_layer")}
        assert listed == (cell["name"] in entry["workloads"])


def test_the_deep_cell_reports_every_metric_its_shallow_twin_does(man):
    def names(cell):
        return {m["name"] for m in man.metrics_of(cell, "per_layer")}

    assert names(DEEP) - names("zk2212-fle3.live-d64") == {
        "archive_overwrite_share"}
    assert names("zk2212-fle3.live-d64") <= names(DEEP)
    e2e = {m["name"] for m in man.metrics_of(DEEP, "end_to_end")}
    assert e2e == {"searched_runs_per_hour", "setup_s"}


def registry_doc(written, overwritten):
    reg = metrics.MetricsRegistry()
    old, was_on = metrics.set_registry(reg), metrics.enabled()
    metrics.configure(True)
    try:
        for ring, (w, o) in {"archive": (written, overwritten),
                             "failure": (7, 0)}.items():
            spans.ring_rows(ring, w, o)
        return json.loads(json.dumps(reg.to_jsonable()))
    finally:
        metrics.configure(was_on)
        metrics.set_registry(old)


@pytest.mark.parametrize("before, after, share", [
    ((512, 0), (1538, 1026), 100.0),   # the window's writes all landed
    ((0, 0), (1026, 514), 100.0 * 514 / 1026),  # a sidecar's first
    ((64, 0), (130, 0), 0.0),          # a history the ring still holds
], ids=["past_capacity", "the_request_that_wraps", "under_capacity"])
def test_archive_overwrite_share_is_overwritten_over_written(
        man, before, after, share):
    entry = man.per_layer["archive_overwrite_share"]
    assert entry["workloads"] == [DEEP]
    assert (entry["unit"], entry["better"], entry["source"],
            entry["layer"], entry["moves"]) == (
        "%", "higher", "program_counter", "search driver",
        "searched_runs_per_hour")
    decl = man.layer_metric("archive_overwrite_share")
    obs = {"metrics_before": registry_doc(*before),
           "metrics_after": registry_doc(*after)}
    assert layer_metrics.evaluate(decl, obs) == pytest.approx(share)


def test_archive_overwrite_share_is_left_out_without_the_counter(man):
    # a commit without the counter (the parent of PR 43): nothing to
    # read is nothing reported, and nothing raises
    decl = man.layer_metric("archive_overwrite_share")
    empty = {"metrics": []}
    assert layer_metrics.evaluate(
        decl, {"metrics_before": empty, "metrics_after": empty}) is None
