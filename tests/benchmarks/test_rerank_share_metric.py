"""``rerank_compiled_share`` (PERF.md section 3): replies whose re-rank
finished on the device over the ``surrogate`` phases of the window, read
by the general reader from the program's own counter and its phase
histogram — and left out where the counter does not exist, as on a
commit before it."""

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

NAME = "rerank_compiled_share"


def record(reg, paths):
    """One ``surrogate`` phase per entry of ``paths``, each counting its
    re-rank under that path (None: nothing to re-rank with, or a program
    without the counter); the registry document as the ``metrics`` op
    serves it."""
    old = metrics.set_registry(reg)
    was_on = metrics.enabled()
    metrics.configure(True)
    try:
        for path in paths:
            spans.search_phase_observed("surrogate", 0.01, time.monotonic())
            if path is not None:
                spans.rerank_request(path)
        return json.loads(json.dumps(reg.to_jsonable()))
    finally:
        metrics.configure(was_on)
        metrics.set_registry(old)


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(tiny_root.REPO)


def test_rerank_compiled_share_is_declared_for_every_cell(man):
    man.validate()
    decl = man.layer_metric(NAME)
    assert decl["value"]["name"] == spans.RERANK_REQUESTS
    assert decl["value"]["labels"] == {"path": "compiled"}
    assert (decl["other"]["name"], decl["other"]["labels"],
            decl["other"]["field"]) == (
        spans.SEARCH_PHASE, {"phase": "surrogate"}, "count")
    assert decl["reduce"] == "share_of"
    entry = man.per_layer[NAME]
    assert "workloads" not in entry  # every cell's replies are re-ranked
    assert (entry["unit"], entry["better"], entry["source"]) == (
        "%", "higher", "program_counter")
    assert (entry["layer"], entry["moves"]) == (
        "search driver", "searched_runs_per_hour")
    for cell in man.doc["workloads"]:
        assert NAME in {m["name"] for m in
                        man.metrics_of(cell["name"], "per_layer")}
    # appended, and the declaration is data: the one file it adds
    assert man.doc["per_layer"][-1]["name"] == NAME
    assert os.path.exists(os.path.join(
        tiny_root.BENCH, "layer_metrics", NAME + ".json"))


@pytest.mark.parametrize("paths, share", [
    (["compiled"] * 300, 100.0),
    (["compiled"] * 3 + ["host"], 75.0),
    (["host", None], 0.0)], ids=["fleet", "one_on_the_host", "none"])
def test_rerank_compiled_share_is_read_from_the_two_counters(man, paths,
                                                            share):
    reg = metrics.MetricsRegistry()
    before = record(reg, ["compiled", "host"])  # set-up
    after = record(reg, paths)
    assert layer_metrics.evaluate(man.layer_metric(NAME), {
        "metrics_before": before, "metrics_after": after}) \
        == pytest.approx(share, abs=1e-9)


def test_rerank_compiled_share_is_left_out_where_no_pick_was_compiled(man):
    """The counter writes only the path taken: a process whose every
    pick finished on the host (a remote surrogate, a guidance map: no
    cell) has no ``compiled`` sample, and the reader reports nothing."""
    reg = metrics.MetricsRegistry()
    before = record(reg, ["host"] * 2)
    after = record(reg, ["host"] * 5)
    assert layer_metrics.evaluate(man.layer_metric(NAME), {
        "metrics_before": before, "metrics_after": after}) is None


def test_rerank_compiled_share_is_left_out_without_its_counter(man):
    """A program without the counter (the parent commit): nothing to
    read, nothing reported, nothing raised."""
    reg = metrics.MetricsRegistry()
    before = record(reg, [None])
    after = record(reg, [None, None, None])
    assert spans.RERANK_REQUESTS not in json.dumps(after)
    assert layer_metrics.evaluate(man.layer_metric(NAME), {
        "metrics_before": before, "metrics_after": after}) is None
