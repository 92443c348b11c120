"""CPU rehearsals of the benchmark's fleet cells, of a throw-away cell
added from files alone, and of a run whose timed path is broken
underneath (``correct`` has to come out false)."""

import json
import os
import textwrap

import pytest

import tiny_root

with open(os.path.join(tiny_root.REPO, "BENCHMARK.json")) as _f:
    _CELLS = json.load(_f)["workloads"]
FLEET = [c for c in _CELLS if not c["traffic"].startswith("live")]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root.build(tmp_path_factory.mktemp("bench_fleet"))


@pytest.fixture(scope="module")
def reorder_root(tmp_path_factory):
    return tiny_root.build(tmp_path_factory.mktemp("bench_fleet_reorder"),
                           search=tiny_root.REORDER_SEARCH)


#: every number a run compares, with its limit: what a delay-mode run
#: printed before the order mode was opened, and the one exact check
#: that came with it
CHECKS = {
    "reply_fitness_gap": 0.05, "fused_fitness_gap": 0.05,
    "rerank_fitness_gap": 0.05, "archive_rows_gap": 1e-5,
    "failure_rows_gap": 1e-5, "reference_times_gap": 0.0,
    "failed_cycles": 0, "window_compiles": 0, "no_cycle_completed": 0,
    "shard_rows_wrong": 0, "sidecar_unclean_stop": 0, "pairs_differ": 0,
    "labels_differ": 0, "ring_counts_differ": 0,
    "reference_buckets_differ": 0, "tables_out_of_range": 0,
    "answers_missing": 0, "release_mode_differs": 0}


@pytest.mark.parametrize("cell", FLEET, ids=[c["name"] for c in FLEET])
def test_fleet_cell_rehearsal(root, cell):
    rc, result, out, err = tiny_root.run_cell(
        root, cell["name"], cell["chips"], trace=0)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True, out[-3000:]
    checks = result["checks"]
    assert {k: c["limit"] for k, c in checks.items()} == CHECKS
    exact = [k for k, limit in CHECKS.items() if limit == 0]
    assert all(checks[k]["value"] == 0 for k in exact)
    assert result["device"]["count"] == cell["chips"]
    assert set(result["metrics"]) == {
        "searched_runs_per_hour", "install_p50_s", "setup_s"}
    facts = json.loads(next(line for line in out.splitlines()
                            if line.startswith("facts: "))[7:])
    # one population shard per device
    for search in facts["searches"]:
        assert search["shard_rows"] == [64 // cell["chips"]] \
            * cell["chips"]
    # every reply of the window was held against the reference, and the
    # state worked out from the storages matched the resident rows
    agree = facts["agreement"]
    assert agree["reply_answers"] == result["attempted"] \
        == sum(facts["requests_per_client"])
    assert agree["fused_answers"] >= len(facts["searches"])
    assert agree["archive_rows_gap"] < 1e-5
    assert facts["depth_at_open"] == facts["depth_at_close"] == 6
    assert min(facts["requests_per_client"]) >= 1


def test_fleet_cell_rehearsal_in_reorder_mode(reorder_root):
    """The same cell with the order half of the genome searched: the
    request states the mode, the search holds it, and every answer is
    held to the order-mode reference under the same limits."""
    cell = FLEET[0]
    rc, result, out, err = tiny_root.run_cell(
        reorder_root, cell["name"], cell["chips"], trace=0)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True, out[-3000:]
    checks = result["checks"]
    assert {k: c["limit"] for k, c in checks.items()} == CHECKS
    facts = tiny_root.tagged(out, "facts: ")
    for held in facts["searches"]:
        assert (held["release_mode"], held["order_gap"],
                held["order_window"]) == ("reorder", 0.01, 0.05)
    agree = facts["agreement"]
    assert agree["reply_answers"] == result["attempted"] >= 2
    assert agree["rerank_answers"] == 2 * 64  # both populations, whole
    assert agree["events_on_a_window_edge"] == 0
    assert 0 < agree["comparison_s"] < 30
    # priorities are clipped to the delay range; nothing else is read
    # differently: the rings hold REALIZED releases in both modes
    assert checks["archive_rows_gap"]["value"] < 1e-5


def test_a_cell_a_mix_a_config_and_a_metric_from_new_files_only(
        root, tmp_path):
    """What a later PR does: new files and one entry each in
    BENCHMARK.json — no file that is there is edited."""
    before = {}
    for dirpath, _dirs, files in os.walk(os.path.join(root, "benchmarks")):
        for name in files:
            path = os.path.join(dirpath, name)
            before[path] = os.path.getmtime(path)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    with open(os.path.join(root, doc["configs"][0]["file"])) as f:
        cfg = json.load(f)
    cfg["name"] = "throwaway"
    with open(os.path.join(root, "benchmarks/configs/throwaway.json"),
              "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, "benchmarks/traffic/fleet8-d64.json")) as f:
        mix = json.load(f)
    mix.update(name="fleet3", campaigns=3)
    with open(os.path.join(root, "benchmarks/traffic/fleet3.json"),
              "w") as f:
        json.dump(mix, f)
    decl = {"name": "save_share", "layer": "search home", "unit": "%",
            "better": "lower", "moves": "searched_runs_per_hour",
            "value": {"kind": "span", "name": "save"},
            "reduce": "share_of",
            "other": {"kind": "span", "name": "handle"}}
    with open(os.path.join(root, "benchmarks/layer_metrics/save_share.json"),
              "w") as f:
        json.dump(decl, f)
    doc["configs"].append({
        "name": "throwaway", "source": "https://example.org/throwaway",
        "file": "benchmarks/configs/throwaway.json", "reduced": [],
        "why": "test"})
    doc["workloads"].append({
        "name": "throwaway.fleet3", "config": "throwaway",
        "traffic": "fleet3", "chips": 1, "why": "test"})
    for m in doc["end_to_end"]:
        if m["name"] == "install_p50_s":
            m["workloads"].append("throwaway.fleet3")
    doc["per_layer"].append({
        "name": "save_share", "unit": "%", "better": "lower",
        "source": "program_span", "layer": "search home",
        "moves": "searched_runs_per_hour",
        "workloads": ["throwaway.fleet3"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)

    rc, result, out, err = tiny_root.run_cell(
        root, "throwaway.fleet3", 1, trace=1)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True, out[-3000:]
    assert 0 < result["metrics"]["save_share"]["value"] < 100
    facts = json.loads(next(line for line in out.splitlines()
                            if line.startswith("facts: "))[7:])
    assert len(facts["requests_per_client"]) == 3
    for path, mtime in before.items():
        assert os.path.getmtime(path) == mtime, f"{path} was edited"


_RUN_ADDS = """
        from namazu_tpu.models import search as _s
        _orig = _s.SearchBase.add_executed_trace
        _calls = [0]
        def _add(self, *a, **kw):
            _calls[0] += 1
            for _ in range({times} if _calls[0] % 5 == 0 else 1):
                _orig(self, *a, **kw)
        _s.SearchBase.add_executed_trace = _add
        """
_SCORE_OFF = """
        def _off(*a, **kw):
            fit, feats = _orig(*a, **kw)
            return fit + 0.2, feats
        """

BREAKS = {
    # a step that returns its state unchanged: the sidecar answers with
    # the best it had, no generation is run
    "evolve_returns_state_unchanged": """
        from namazu_tpu.models import search as _s
        _s.ScheduleSearch.run = lambda self, encoded, generations=50: \\
            self.best()
        """,
    # an answer altered where it is produced, in the fused island step
    # ONLY: the scorer inside ``islands._local_step`` is off by a
    # constant; the scorer the replies are re-scored with is sound
    "fused_step_scorer_altered": """
        from namazu_tpu.parallel import islands as _isl
        _orig = _isl.score_population_multi
        """ + _SCORE_OFF + """
        _isl.score_population_multi = _off
        """,
    # ... and the other way round: the fused step is sound, the scorer
    # of the reply's re-rank is off
    "reply_scorer_altered": """
        from namazu_tpu.parallel import islands as _isl
        from namazu_tpu.ops import schedule as _sch
        _orig = _sch.score_population_multi
        """ + _SCORE_OFF + """
        _sch.score_population_multi = _off
        """,
    # ingest leaves out, or feeds twice, every fifth stored run
    "ingest_drops_a_run": _RUN_ADDS.format(times=0),
    "ingest_duplicates_a_run": _RUN_ADDS.format(times=2),
}

#: the number of the ``checks:`` line that has to catch each break
CAUGHT_BY = {
    "evolve_returns_state_unchanged": "failed_cycles",
    "fused_step_scorer_altered": "fused_fitness_gap",
    "reply_scorer_altered": "reply_fitness_gap",
    "ingest_drops_a_run": "ring_counts_differ",
    "ingest_duplicates_a_run": "ring_counts_differ",
}


@pytest.mark.parametrize("how", sorted(BREAKS))
def test_broken_timed_path_is_not_correct(root, tmp_path, how):
    """The harness's own look for a chip is skipped (``--cpu``) and the
    rest of a run is driven, with the program broken underneath through
    a ``sitecustomize`` the children import."""
    site = tmp_path / "site"
    site.mkdir()
    (site / "sitecustomize.py").write_text(
        "import sys\n"
        "if any(a.endswith('sidecar_main.py') for a in sys.argv):\n"
        f"    sys.path.insert(0, {tiny_root.REPO!r})\n"
        + textwrap.indent(textwrap.dedent(BREAKS[how]), "    "))
    cell = FLEET[0]
    rc, result, out, err = tiny_root.run_cell(
        root, cell["name"], cell["chips"],
        extra_env={"PYTHONPATH": str(site)})
    assert rc == 0, err[-3000:]
    assert result["correct"] is False, out[-2000:]
    checks = result["checks"]
    caught = CAUGHT_BY[how]
    assert checks[caught]["value"] > checks[caught]["limit"], checks
    if how.endswith("scorer_altered"):
        # only the altered path's number moves
        other = ({"fused_fitness_gap", "reply_fitness_gap"}
                 - {caught}).pop()
        assert checks[other]["value"] <= checks[other]["limit"], checks
