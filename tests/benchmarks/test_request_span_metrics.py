"""The per-layer metrics that read the program's own request spans
(PERF.md section 3), and the ``zk2212-fle3.live-d64`` cell: each new
``layer_metrics/*.json`` evaluated by the general reader against a
registry document the program's own registry wrote and against the
recorded chip trace's quantities; the grown manifest; the new mix
through the tiny checkout the live cells' rehearsal uses (the rehearsal
itself is ``test_cells_live.py``'s, which takes every ``live*`` cell of
BENCHMARK.json — one file, because the rehearsal testee's port is
fixed)."""

import json
import os
import sys

import pytest

import tiny_root

sys.path.insert(0, tiny_root.BENCH)

import layer_metrics  # noqa: E402
import manifest  # noqa: E402
import trace_reduce  # noqa: E402

from namazu_tpu.obs import metrics, spans  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "zk2212-fle3.live-d64"
FLEET = ["zk2212-fle3.fleet8-d64", "zk2212-fle3.fleet8-d16-x4"]

#: per-request phase seconds of a window of 5 requests over 66 stored
#: runs each, after a set-up of 2 (what ``record`` below writes)
PHASES = {"queue": 1.5, "handle": 2.0, "ingest": 1.65,
          "ingest_read": 0.66, "ingest_encode": 0.33, "ingest_embed": 0.594,
          "evolve": 0.04, "dispatch": 0.002, "surrogate": 0.06}
WANT = {
    "wire_queue_s_per_request": 1.5,
    "ingest_read_ms_per_stored_run": 10.0,
    "ingest_encode_ms_per_stored_run": 5.0,
    "ingest_embed_ms_per_stored_run": 9.0,
    "surrogate_s_per_request": 0.06,
    "dispatch_s_per_request": 0.002,
    "program_compiles": 0.0,
}


def record(requests: int, reg: metrics.MetricsRegistry) -> dict:
    """``requests`` more requests into ``reg`` through the program's
    own recording sites; the registry document as the ``metrics`` op
    serves it."""
    old = metrics.set_registry(reg)
    try:
        for _ in range(requests):
            for phase, seconds in PHASES.items():
                spans.search_phase_observed(phase, seconds, 0.0)
            spans.ingest_runs(66)
        return json.loads(json.dumps(reg.to_jsonable()))
    finally:
        metrics.set_registry(old)
        spans.reset_span_ring()


@pytest.fixture(scope="module")
def obs():
    reg = metrics.MetricsRegistry()
    # set-up: two warm requests, and every lowering of the run
    reg.counter(spans.COMPILES, "").inc(57)
    before = record(2, reg)
    after = record(5, reg)
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        trace = trace_reduce.reduce(json.load(f)["events"])
    return {"metrics_before": before, "metrics_after": after,
            "trace": trace}


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(tiny_root.REPO)


@pytest.mark.parametrize("name", sorted(WANT))
def test_counter_metrics_read_the_programs_registry(man, obs, name):
    decl = man.layer_metric(name)
    assert layer_metrics.evaluate(decl, obs) == pytest.approx(WANT[name])
    # a program without these spans (the parent commit): nothing to
    # read, nothing reported, nothing raised
    assert layer_metrics.evaluate(decl, {
        "metrics_before": {"metrics": []},
        "metrics_after": {"metrics": []}, "trace": {}}) is None


@pytest.mark.parametrize("name, scope", [
    ("mutate_share", "scope_s.nmz_mutate"),
    ("migrate_share", "scope_s.nmz_migrate")])
def test_scope_shares_read_the_recorded_chip_trace(man, obs, name, scope):
    decl = man.layer_metric(name)
    got = layer_metrics.evaluate(decl, obs)
    assert got == pytest.approx(
        100.0 * obs["trace"][scope] / obs["trace"]["device_busy_s"])
    assert 0.0 <= got <= 100.0
    # off a chip the reduction finds no device plane
    assert layer_metrics.evaluate(decl, {"trace": {"n_devices": 0}}) is None


def test_the_stages_and_the_outside_span_divide_by_the_same_runs(obs):
    """``ingest_ms_per_stored_run`` (the launcher's span over
    ``storage.nr_stored_histories()``) and the three stage metrics
    (the program's phases over ``nmz_ingest_runs_total``) must be
    comparable: same divisor, so the stages sum to the part of
    ``ingest`` they cover."""
    d = layer_metrics.counter_delta
    runs = d(obs["metrics_before"], obs["metrics_after"],
             spans.INGEST_RUNS, {}, "value")
    assert runs == 5 * 66
    stages = sum(WANT[f"ingest_{s}_ms_per_stored_run"]
                 for s in ("read", "encode", "embed"))
    ingest = 1000 * d(obs["metrics_before"], obs["metrics_after"],
                      spans.SEARCH_PHASE, {"phase": "ingest"}, "sum") / runs
    assert 0.9 * ingest <= stages <= ingest


def test_the_grown_manifest(man):
    man.validate()
    new = set(WANT) | {"mutate_share", "migrate_share"}
    assert new <= set(man.per_layer)
    # the fleet cells of the time are listed, and no live cell is
    queued = man.per_layer["wire_queue_s_per_request"]["workloads"]
    assert set(FLEET) <= set(queued)
    assert all("fleet8" in cell for cell in queued)
    assert man.per_layer["wire_queue_s_per_request"]["moves"] \
        == "install_p50_s"
    for name in new - {"wire_queue_s_per_request"}:
        assert "workloads" not in man.per_layer[name]
        assert man.per_layer[name]["moves"] == "searched_runs_per_hour"
    cell = man.cell(CELL)
    assert cell["chips"] == 1 and cell["config"] == "zk2212-fle3"
    reported = {m["name"] for m in man.metrics_of(CELL, "per_layer")}
    assert new - {"wire_queue_s_per_request"} <= reported
    assert "wire_queue_s_per_request" not in reported
    assert {m["name"] for m in man.metrics_of(CELL, "end_to_end")} == {
        "searched_runs_per_hour", "setup_s"}
    for fleet in FLEET:
        assert "wire_queue_s_per_request" in {
            m["name"] for m in man.metrics_of(fleet, "per_layer")}
    # the cell is there, once, after the cells it was appended to
    names = [w["name"] for w in man.doc["workloads"]]
    assert names.count(CELL) == 1
    assert names.index("zk2212-fle3.live") < names.index(CELL)


def test_the_new_mix_and_its_tiny_twin(man, tmp_path):
    mix = man.traffic(man.cell(CELL))
    live = man.traffic(man.cell("zk2212-fle3.live"))
    d64 = man.traffic(man.cell("zk2212-fle3.fleet8-d64"))
    assert mix["kind"] == "campaign" and mix["campaigns"] == 1
    assert mix["chips"] == 1
    # hunt depth as the fleet backlog has it, everything else as `live`
    assert mix["prefill_runs"] == d64["history_depth"] == 64
    assert mix["prefill_failures"] == d64["history_failures"] == 4
    assert mix["history_depth_at_start"] == mix["prefill_runs"] \
        + mix["warmup_searched_runs"]
    assert {k: mix[k] for k in ("warmup_searched_runs", "trace_slice_s")} \
        == {k: live[k] for k in ("warmup_searched_runs", "trace_slice_s")}
    # the tiny checkout takes the cell as it takes every live cell
    root = tiny_root.build(tmp_path)
    tiny = manifest.Manifest(root)
    tiny.validate()
    tiny_mix = tiny.traffic(tiny.cell(CELL))
    assert tiny_mix["kind"] == "campaign"
    assert tiny_mix["prefill_runs"] == tiny_root.TINY_MIX["live"][
        "prefill_runs"]
    assert os.path.exists(os.path.join(
        root, "benchmarks", "layer_metrics", "program_compiles.json"))
