"""``delay_table_request_share`` (PERF.md section 3): evolves whose
compiled step took its first occurrences from per-trace tables over the
``evolve`` phases of the window, read by the general reader from the
program's own counter and its phase histogram — 100 % in the six
delay-mode cells, not listed for the order-mode one, and left out where
the counter does not exist, as on a commit before it."""

import json
import os
import sys
import time

import pytest

import tiny_root

sys.path.insert(0, tiny_root.BENCH)

import layer_metrics  # noqa: E402
import manifest  # noqa: E402

from namazu_tpu.obs import metrics, spans  # noqa: E402

NAME = "delay_table_request_share"
ORDER_CELL = "zk2080-reconfig5.fleet8-d64"


def record(reg, evolves):
    """One ``evolve`` phase per entry of ``evolves``, each counted under
    its scorer branch as ``models/search.py`` counts it (None: a program
    without the table counter); the registry document as the ``metrics``
    op serves it."""
    old = metrics.set_registry(reg)
    was_on = metrics.enabled()
    metrics.configure(True)
    try:
        for branch in evolves:
            spans.search_phase_observed("evolve", 0.01, time.monotonic())
            if branch is not None:
                spans.evolve_request(branch)
                if branch != "order":
                    spans.evolve_table_request()
        return json.loads(json.dumps(reg.to_jsonable()))
    finally:
        metrics.configure(was_on)
        metrics.set_registry(old)


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(tiny_root.REPO)


def test_delay_table_request_share_is_declared_for_the_delay_mode_cells(man):
    man.validate()
    decl = man.layer_metric(NAME)
    assert decl["value"]["name"] == spans.EVOLVE_TABLE_REQUESTS
    assert decl["value"]["labels"] == {}
    assert (decl["other"]["name"], decl["other"]["labels"],
            decl["other"]["field"]) == (
        spans.SEARCH_PHASE, {"phase": "evolve"}, "count")
    assert decl["reduce"] == "share_of"
    entry = man.per_layer[NAME]
    assert (entry["unit"], entry["better"], entry["source"]) == (
        "%", "higher", "program_counter")
    assert (entry["layer"], entry["moves"]) == (
        "island step", "searched_runs_per_hour")
    # every cell but the one whose tables are permutations
    cells = [c["name"] for c in man.doc["workloads"]]
    assert entry["workloads"] == [c for c in cells if c != ORDER_CELL]
    assert len(entry["workloads"]) == 6
    for cell in cells:
        listed = NAME in {m["name"] for m in
                          man.metrics_of(cell, "per_layer")}
        assert listed == (cell != ORDER_CELL)
    # the declaration is data: the one file it adds (no pin on its
    # place in the list: the next appended metric would break it)
    assert os.path.exists(os.path.join(
        tiny_root.BENCH, "layer_metrics", NAME + ".json"))


@pytest.mark.parametrize("evolves, share", [
    (["blockwise"] * 300, 100.0),
    (["dense"] * 7, 100.0),
    (["dense", "blockwise", "order", "order"], 50.0)],
    ids=["zab5", "dense", "half_in_order_mode"])
def test_delay_table_request_share_is_read_from_the_two_counters(
        man, evolves, share):
    reg = metrics.MetricsRegistry()
    before = record(reg, ["dense", "order"])  # set-up
    after = record(reg, evolves)
    assert layer_metrics.evaluate(man.layer_metric(NAME), {
        "metrics_before": before, "metrics_after": after}) \
        == pytest.approx(share, abs=1e-9)


def test_delay_table_request_share_is_left_out_in_order_mode(man):
    """A process whose every evolve took the order branch never writes
    the counter: nothing to read, nothing reported."""
    reg = metrics.MetricsRegistry()
    before = record(reg, ["order"] * 2)
    after = record(reg, ["order"] * 5)
    assert layer_metrics.evaluate(man.layer_metric(NAME), {
        "metrics_before": before, "metrics_after": after}) is None


def test_delay_table_request_share_is_left_out_without_its_counter(man):
    """A program without the counter (the parent commit): nothing to
    read, nothing reported, nothing raised."""
    reg = metrics.MetricsRegistry()
    before = record(reg, [None])
    after = record(reg, [None, None, None])
    assert spans.EVOLVE_TABLE_REQUESTS not in json.dumps(after)
    assert layer_metrics.evaluate(man.layer_metric(NAME), {
        "metrics_before": before, "metrics_after": after}) is None
