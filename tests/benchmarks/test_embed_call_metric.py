"""``ingest_runs_per_embed_call`` (PERF.md section 3): stored runs
walked over device calls of the batched embed program, read by the
general reader from the two counters the program's own recording sites
fill — and left out where the second counter does not exist, as on a
commit before the batched embed."""

import json
import os
import sys

import pytest

import tiny_root

sys.path.insert(0, tiny_root.BENCH)

import layer_metrics  # noqa: E402
import manifest  # noqa: E402

from namazu_tpu.obs import metrics, spans  # noqa: E402

NAME = "ingest_runs_per_embed_call"


def record(reg, requests, runs, calls):
    """``requests`` ingests of ``runs`` stored runs in ``calls`` device
    calls each, through the program's recording sites; the registry
    document as the ``metrics`` op serves it."""
    old = metrics.set_registry(reg)
    try:
        for _ in range(requests):
            spans.ingest_runs(runs)
            for _ in range(calls):
                spans.ingest_embed_call()
        return json.loads(json.dumps(reg.to_jsonable()))
    finally:
        metrics.set_registry(old)


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(tiny_root.REPO)


def test_runs_per_embed_call_is_declared_for_every_cell(man):
    man.validate()
    decl = man.layer_metric(NAME)
    assert decl["value"]["name"] == spans.INGEST_RUNS
    assert decl["other"]["name"] == spans.INGEST_EMBED_CALLS
    assert decl["reduce"] == "per"
    entry = man.per_layer[NAME]
    assert man.doc["per_layer"].count(entry) == 1  # declared once
    assert "workloads" not in entry  # every cell ingests
    assert (entry["layer"], entry["better"], entry["moves"]) == (
        "ingest and encode", "higher", "searched_runs_per_hour")
    for cell in man.doc["workloads"]:
        assert NAME in {m["name"] for m in
                        man.metrics_of(cell["name"], "per_layer")}
    # the declaration is data: the one file this metric adds
    assert os.path.exists(os.path.join(
        tiny_root.BENCH, "layer_metrics", NAME + ".json"))


def test_runs_per_embed_call_is_read_from_the_two_counters(man):
    reg = metrics.MetricsRegistry()
    before = record(reg, 2, 66, 2)   # set-up: two warm requests
    after = record(reg, 9, 70, 2)    # the window: depth 66-75, 2 chunks
    assert layer_metrics.evaluate(man.layer_metric(NAME), {
        "metrics_before": before, "metrics_after": after}) == 35.0


@pytest.mark.parametrize("calls", [None, 0], ids=["parent", "no_ingest"])
def test_runs_per_embed_call_is_left_out_without_the_second_counter(
        man, calls):
    """A program without the counter (the parent commit), or a window
    without a device call: nothing to divide by, nothing reported,
    nothing raised."""
    reg = metrics.MetricsRegistry()
    before = record(reg, 1, 66, 0) if calls is None else record(
        reg, 1, 66, 2)
    after = record(reg, 3, 66, 0)
    assert (spans.INGEST_EMBED_CALLS in json.dumps(after)) \
        == (calls is not None)
    assert layer_metrics.evaluate(man.layer_metric(NAME), {
        "metrics_before": before, "metrics_after": after}) is None
