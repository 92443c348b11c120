"""``storage_settled_run_share`` (PERF.md section 3): of the runs
allocated in the stored histories a search home opened or refreshed, the
share the open did not visit — seen settled by an earlier one — read by
the general reader from the two counters ``obs.storage_open`` writes,
and left out where they do not exist, as on a commit before the
watermark."""

import json
import os
import sys

import pytest

import tiny_root

sys.path.insert(0, tiny_root.BENCH)

import layer_metrics  # noqa: E402
import manifest  # noqa: E402

from namazu_tpu.obs import metrics, spans  # noqa: E402

NAME = "storage_settled_run_share"


def record(reg, opens):
    """One ``init()`` / ``refresh()`` per ``(runs, visited)`` of
    ``opens``; the registry document as the ``metrics`` op serves it."""
    old = metrics.set_registry(reg)
    try:
        for runs, visited in opens:
            spans.storage_open(runs, visited)
        return json.loads(json.dumps(reg.to_jsonable()))
    finally:
        metrics.set_registry(old)


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(tiny_root.REPO)


def test_settled_run_share_is_declared_for_every_cell(man):
    man.validate()
    decl = man.layer_metric(NAME)
    assert decl["value"]["name"] == spans.STORAGE_OPEN_SETTLED_RUNS
    assert decl["other"]["name"] == spans.STORAGE_OPEN_RUNS
    assert decl["reduce"] == "share_of"
    entry = man.per_layer[NAME]
    assert "workloads" not in entry  # every cell opens a storage
    assert (entry["unit"], entry["better"], entry["source"]) == (
        "%", "higher", "program_counter")
    assert (entry["layer"], entry["moves"]) == (
        "search home", "searched_runs_per_hour")
    for cell in man.doc["workloads"]:
        assert NAME in {m["name"] for m in
                        man.metrics_of(cell["name"], "per_layer")}
    # the declaration is data: the one file this metric adds
    assert os.path.exists(os.path.join(
        tiny_root.BENCH, "layer_metrics", NAME + ".json"))


@pytest.mark.parametrize("opens, share", [
    ([(d, 1) for d in range(1030, 1037)],
     100.0 * (sum(range(1030, 1037)) - 7) / sum(range(1030, 1037))),
    ([(64, 0)] * 300, 100.0),
    ([(d, 1) for d in range(20, 33)],
     100.0 * (sum(range(20, 33)) - 13) / sum(range(20, 33)))],
    ids=["live-d1024", "fleet8-d64", "live"])
def test_settled_run_share_is_read_from_the_two_counters(man, opens, share):
    reg = metrics.MetricsRegistry()
    before = record(reg, [(opens[0][0], opens[0][0])])  # set-up: walked whole
    after = record(reg, opens)
    assert layer_metrics.evaluate(man.layer_metric(NAME), {
        "metrics_before": before, "metrics_after": after}) \
        == pytest.approx(share, abs=1e-9)
    assert share > (99.8 if opens[0][0] > 1000 else 95.0)


def test_a_window_of_whole_walks_reads_zero_not_nothing(man):
    reg = metrics.MetricsRegistry()
    before = record(reg, [(64, 64)])
    after = record(reg, [(64, 64), (65, 65)])
    assert layer_metrics.evaluate(man.layer_metric(NAME), {
        "metrics_before": before, "metrics_after": after}) == 0.0


def test_settled_run_share_is_left_out_without_its_counters(man):
    """A program without the counters (the parent commit): nothing to
    read, nothing reported, nothing raised."""
    reg = metrics.MetricsRegistry()
    old = metrics.set_registry(reg)
    try:
        spans.ingest_runs(66)
        before = json.loads(json.dumps(reg.to_jsonable()))
        spans.ingest_runs(67)
        after = json.loads(json.dumps(reg.to_jsonable()))
    finally:
        metrics.set_registry(old)
    assert "nmz_storage_open" not in json.dumps(after)
    assert layer_metrics.evaluate(man.layer_metric(NAME), {
        "metrics_before": before, "metrics_after": after}) is None
