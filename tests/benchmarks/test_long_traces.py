"""``zk2212-zab5`` (PERF.md section 4): stored runs past 1,024 events,
``recent`` mode with 4 reference traces, the blockwise scorer.

* the configuration, its two cells and the three per-layer metrics in
  the manifest, and the metrics read by the general reader from the
  counters the program's own recording sites fill — left out on a
  registry without them, as on the parent commit;
* a rehearsal of ``zk2212-zab5.fleet8-d32`` at the toy width ON THE
  CONFIGURATION'S OWN TEMPLATES (``tiny_root`` gives every
  configuration the 8-event rehearsal testee's; here the recorded
  ~1,500-event runs are put back): storages synthesised from them go
  through the sidecar's ``SearchService.handle`` and every reply's, the
  fused step's and the re-rank's fitness is held against
  ``benchmarks/reference.py`` within ``run.py``'s limits;
* the same with every stored trace cut at 1,024 events underneath:
  ``correct`` has to come out false.
"""

import json
import os
import shutil
import sys
import textwrap

import pytest

import tiny_root

sys.path.insert(0, tiny_root.BENCH)

import layer_metrics  # noqa: E402
import manifest  # noqa: E402

from namazu_tpu.obs import metrics, spans  # noqa: E402

CONFIG = "zk2212-zab5"
CELL = "zk2212-zab5.fleet8-d32"
ETCD = "etcd3517-kv3.fleet8-d64"
EVENTS = 1492  # a stored run of the configuration


def record(reg, requests, blockwise=True, events=True):
    """``requests`` requests over 32 stored runs of ``EVENTS`` events
    through the program's recording sites; the registry document as
    the ``metrics`` op serves it."""
    old = metrics.set_registry(reg)
    try:
        for _ in range(requests):
            spans.ingest_runs(32)
            if events:
                spans.ingest_events(32 * EVENTS)
            spans.search_phase_observed("ingest_read", 0.5, 0.0)
            spans.search_phase_observed("ingest_encode", 0.75, 0.0,
                                        pieces=32, events=32 * EVENTS)
            spans.search_phase_observed("evolve", 0.2, 0.0)
            if blockwise is not None:
                spans.evolve_request("blockwise" if blockwise else "dense")
        return json.loads(json.dumps(reg.to_jsonable()))
    finally:
        metrics.set_registry(old)
        spans.reset_span_ring()


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(tiny_root.REPO)


def test_the_configuration_and_its_cells_are_declared(man):
    man.validate()
    assert [c["name"] for c in man.doc["configs"]][-1] == CONFIG
    assert [w["name"] for w in man.doc["workloads"]][-2:] == [ETCD, CELL]
    cfg = man.config(man.cell(CELL))
    assert cfg["testee"]["example"] == "examples/zk-zab"
    assert cfg["guarantees"]["reference_traces"] == 4
    assert cfg["guarantees"]["scorer"] == "blockwise"
    assert "reference_traces" not in cfg["shipped_width"]  # run.py's
    assert min(cfg["shipped_width"]["trace_lengths"]) > 1024
    assert set(man.configs[CONFIG]["reduced"]) == set(cfg["reduced"]) == {
        "runs_per_campaign", "history_depth", "client_writes"}
    with open(os.path.join(tiny_root.REPO, cfg["history"])) as f:
        templates = json.load(f)
    assert (len(templates["successes"]), len(templates["failures"])) \
        == (6, 3)
    lengths = sorted({-(-len(t["actions"]) // 128) * 128
                      for t in templates["successes"]
                      + templates["failures"]})
    assert lengths == cfg["shipped_width"]["trace_lengths"]
    kinds = {a["event_hint"].split(":")[1]
             for a in templates["successes"][0]["actions"]}
    assert {"fle", "zab", "cm", "sm"} <= kinds
    mix = man.traffic(man.cell(CELL))
    assert {k: mix[k] for k in (
        "kind", "campaigns", "history_depth", "history_failures",
        "think_s", "warmup_requests_per_client", "chips",
        "trace_slice_s")} == {
        "kind": "fleet", "campaigns": 8, "history_depth": 32,
        "history_failures": 4, "think_s": 0.0,
        "warmup_requests_per_client": 2, "chips": 1, "trace_slice_s": 8.0}
    assert man.cell(ETCD) == dict(man.cell(ETCD), config="etcd3517-kv3",
                                  traffic="fleet8-d64", chips=1)
    for cell in (ETCD, CELL):
        assert {m["name"] for m in man.metrics_of(cell, "end_to_end")} == {
            "searched_runs_per_hour", "install_p50_s", "setup_s"}
        assert "wire_queue_s_per_request" in {
            m["name"] for m in man.metrics_of(cell, "per_layer")}


@pytest.mark.parametrize("name, layer, cells", [
    ("ingest_encode_us_per_event", "ingest and encode", None),
    ("ingest_read_us_per_event", "ingest and encode", None),
    ("blockwise_request_share", "island step", [CELL])])
def test_the_new_metrics_are_declared(man, name, layer, cells):
    entry = man.per_layer[name]
    assert entry.get("workloads") == cells
    assert (entry["layer"], entry["source"], entry["moves"]) == (
        layer, "program_counter", "searched_runs_per_hour")
    reported = [w["name"] for w in man.doc["workloads"]
                if name in {m["name"] for m in
                            man.metrics_of(w["name"], "per_layer")}]
    assert reported == (cells or [w["name"] for w in man.doc["workloads"]])


def test_the_new_metrics_read_the_programs_counters(man):
    reg = metrics.MetricsRegistry()
    obs = {"metrics_before": record(reg, 2),
           "metrics_after": record(reg, 5)}
    per_event = 1e6 / (32 * EVENTS)
    assert layer_metrics.evaluate(
        man.layer_metric("ingest_encode_us_per_event"), obs) \
        == pytest.approx(0.75 * per_event)
    assert layer_metrics.evaluate(
        man.layer_metric("ingest_read_us_per_event"), obs) \
        == pytest.approx(0.5 * per_event)
    assert layer_metrics.evaluate(
        man.layer_metric("blockwise_request_share"), obs) == 100.0
    # the span ring's rows carry what the stage walked
    old = metrics.set_registry(metrics.MetricsRegistry())
    try:
        ring = spans.reset_span_ring()
        spans.search_phase_observed("ingest_encode", 0.1, 0.0, pieces=2,
                                    events=2 * EVENTS)
        assert ring.since(0)["rows"][0][7] == {"pieces": 2,
                                               "events": 2 * EVENTS}
    finally:
        metrics.set_registry(old)
        spans.reset_span_ring()


@pytest.mark.parametrize("how", ["parent", "dense_only"])
def test_the_new_metrics_are_left_out_without_their_counters(man, how):
    """A program without the counters (the parent commit), or a window
    whose every evolve took the dense branch: nothing to read, nothing
    reported, nothing raised."""
    reg = metrics.MetricsRegistry()
    kw = ({"blockwise": None, "events": False} if how == "parent"
          else {"blockwise": False})
    obs = {"metrics_before": record(reg, 1, **kw),
           "metrics_after": record(reg, 3, **kw)}
    assert layer_metrics.evaluate(
        man.layer_metric("blockwise_request_share"), obs) is None
    for name in ("ingest_encode_us_per_event", "ingest_read_us_per_event"):
        got = layer_metrics.evaluate(man.layer_metric(name), obs)
        assert (got is None) == (how == "parent")


# -- the cell on its own templates, at the toy width --------------------------


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tiny checkout with ``zk2212-zab5`` given back its recorded
    templates and its reference mode; 512 hint buckets, so that some
    buckets are first met past the 1,024th event of a run (at 64 every
    bucket is met early, and a cut trace scores like a whole one)."""
    root = tiny_root.build(tmp_path_factory.mktemp("bench_long"))
    man = manifest.Manifest(root)
    path = man.path(man.configs[CONFIG]["file"])
    with open(path) as f:
        cfg = json.load(f)
    cfg["history"] = "benchmarks/configs/" + CONFIG + ".history.json"
    shutil.copy(os.path.join(tiny_root.REPO, cfg["history"]),
                os.path.join(root, cfg["history"]))
    cfg["search"]["set"].update(reference_mode="recent", hint_buckets=512)
    with open(path, "w") as f:
        json.dump(cfg, f)
    # 8 runs deep with 3 failures: 5 successes, so 4 reference traces
    path = os.path.join(root, "benchmarks", "traffic", "fleet8-d32.json")
    with open(path) as f:
        mix = json.load(f)
    mix["history_depth"] = 8
    with open(path, "w") as f:
        json.dump(mix, f)
    return root


def run(root, **kw):
    rc, result, out, err = tiny_root.run_cell(root, CELL, 1, **kw)
    assert rc == 0, err[-3000:]
    return result, tiny_root.tagged(out, "facts: "), result["checks"]


def test_long_traces_agree_with_the_reference(root):
    result, facts, checks = run(root, trace=1)
    assert result["correct"] is True, checks
    agree = facts["agreement"]
    assert agree["reference_traces"] == 4
    assert agree["reply_answers"] == result["attempted"] >= 2
    assert agree["rerank_answers"] == 2 * 64  # both populations, whole
    for name in ("reply", "fused", "rerank"):
        got, limit = (checks[f"{name}_fitness_gap"][k]
                      for k in ("value", "limit"))
        assert got <= limit == 0.05
    assert checks["archive_rows_gap"]["value"] <= 1e-5
    assert all(e > 1024 for e in facts["history"]["events_per_run"])
    # the per-layer metrics of the cell, from the sidecar's own registry
    m = result["metrics"]
    assert m["blockwise_request_share"]["value"] == 100.0
    assert 0 < m["ingest_encode_us_per_event"]["value"] < 1e4
    assert 0 < m["ingest_read_us_per_event"]["value"] < 1e4
    # one L group: a request's 8 runs go to the device in one call
    assert m["ingest_runs_per_embed_call"]["value"] == pytest.approx(
        8.0, rel=0.25)


def test_traces_cut_at_1024_events_are_not_correct(root, tmp_path):
    site = tmp_path / "site"
    site.mkdir()
    (site / "sitecustomize.py").write_text(
        "import sys\n"
        "if any(a.endswith('sidecar_main.py') for a in sys.argv):\n"
        f"    sys.path.insert(0, {tiny_root.REPO!r})\n"
        + textwrap.indent(textwrap.dedent("""
        from namazu_tpu.ops import trace_encoding as _te
        _orig = _te.encode_trace_views
        def _cut(trace, L=None, **kw):
            return _orig(trace, L=1024 if L is None else L, **kw)
        _te.encode_trace_views = _cut
        """), "    "))
    result, _facts, checks = run(root,
                                 extra_env={"PYTHONPATH": str(site)})
    assert result["correct"] is False, checks
    # the events past the cut are missing from the resident reference
    # traces and from the rows the rings hold
    assert checks["reference_buckets_differ"]["value"] > 0
    assert checks["archive_rows_gap"]["value"] \
        > checks["archive_rows_gap"]["limit"]
