"""``ingest_cached_run_share`` (PERF.md section 3): stored runs a request
took from the process's encoded-run records over stored runs walked, read
by the general reader from two counters of the program's own recording
sites — and left out where the first counter does not exist, as on a
commit before the records."""

import json
import os
import sys

import pytest

import tiny_root

sys.path.insert(0, tiny_root.BENCH)

import layer_metrics  # noqa: E402
import manifest  # noqa: E402

from namazu_tpu.obs import metrics, spans  # noqa: E402

NAME = "ingest_cached_run_share"


def record(reg, depths, cached=True):
    """One ingest per entry of ``depths``, each finding all but its
    newest run among the records (``cached=None``: a program without
    the counter); the registry document as the ``metrics`` op serves
    it."""
    old = metrics.set_registry(reg)
    try:
        for depth in depths:
            spans.ingest_runs(depth)
            if cached is not None:
                spans.ingest_cached_runs(depth - 1 if cached else 0)
        return json.loads(json.dumps(reg.to_jsonable()))
    finally:
        metrics.set_registry(old)


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(tiny_root.REPO)


def test_cached_run_share_is_declared_for_every_cell(man):
    man.validate()
    decl = man.layer_metric(NAME)
    assert decl["value"]["name"] == spans.INGEST_CACHED_RUNS
    assert decl["other"]["name"] == spans.INGEST_RUNS
    assert decl["reduce"] == "share_of"
    entry = man.per_layer[NAME]
    assert "workloads" not in entry  # every cell ingests
    assert (entry["unit"], entry["better"], entry["source"]) == (
        "%", "higher", "program_counter")
    assert (entry["layer"], entry["moves"]) == (
        "ingest and encode", "searched_runs_per_hour")
    for cell in man.doc["workloads"]:
        assert NAME in {m["name"] for m in
                        man.metrics_of(cell["name"], "per_layer")}
    # the declaration is data: the one file this metric adds
    assert os.path.exists(os.path.join(
        tiny_root.BENCH, "layer_metrics", NAME + ".json"))


@pytest.mark.parametrize("depths, share", [
    (range(66, 78), 100.0 * (sum(range(66, 78)) - 12) / sum(range(66, 78))),
    ([64] * 25, 100.0 * 63 / 64)], ids=["live-d64", "one_new_run_each"])
def test_cached_run_share_is_read_from_the_two_counters(man, depths, share):
    reg = metrics.MetricsRegistry()
    before = record(reg, [64, 65], cached=False)  # set-up: cold requests
    after = record(reg, depths)
    assert layer_metrics.evaluate(man.layer_metric(NAME), {
        "metrics_before": before, "metrics_after": after}) \
        == pytest.approx(share, abs=1e-9)
    assert 98.0 < share < 99.0


def test_a_window_of_misses_reads_zero_not_nothing(man):
    reg = metrics.MetricsRegistry()
    before = record(reg, [64], cached=False)
    after = record(reg, [64, 64], cached=False)
    assert layer_metrics.evaluate(man.layer_metric(NAME), {
        "metrics_before": before, "metrics_after": after}) == 0.0


def test_cached_run_share_is_left_out_without_its_counter(man):
    """A program without the counter (the parent commit): nothing to
    read, nothing reported, nothing raised."""
    reg = metrics.MetricsRegistry()
    before = record(reg, [66], cached=None)
    after = record(reg, [66, 67, 68], cached=None)
    assert spans.INGEST_CACHED_RUNS not in json.dumps(after)
    assert layer_metrics.evaluate(man.layer_metric(NAME), {
        "metrics_before": before, "metrics_after": after}) is None
