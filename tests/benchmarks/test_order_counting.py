"""The counting order scorer (PR 32) and the configuration that rides on
it, held by the benchmark's own means: the INSIDE of
``ops.schedule.order_release_times`` broken two ways has to end
``correct: false`` on the fitness numbers, and the new configuration's
file has to state its source's three facts, its assumptions, and cuts
of scale only."""

import json
import os
import textwrap

import pytest

import tiny_root

CELL = "zk2080-reconfig5.fleet8-d64"

#: each break wraps the program's ``order_release_times`` where the
#: scorers look it up; the search still HOLDS the stated mode, gap and
#: window, so only the answers tell
BREAKS = {
    # every event but its window's first is given the slot after its own
    "ranks_off_by_one_slot": """
        import jax.numpy as _jnp
        from namazu_tpu.ops import schedule as _sch
        _orig = _sch.order_release_times
        def _late(prio, trace, gap, window=0.0):
            t = _orig(prio, trace, gap, window)
            close = (_jnp.floor(trace.arrival / window) + 1.0) * window
            return _jnp.where(trace.mask & (t > close + gap / 2),
                              t + gap, t)
        _sch.order_release_times = _late
        """,
    # buckets of EQUAL priority released by bucket id, where the
    # semantics state the arrival
    "ties_broken_by_bucket_id": """
        import jax.numpy as _jnp
        from namazu_tpu.ops import schedule as _sch
        _orig = _sch.order_release_times
        def _by_id(prio, trace, gap, window=0.0):
            ids = _jnp.arange(prio.shape[0], dtype=prio.dtype)
            return _orig(prio - ids * 1e-7, trace, gap, window)
        _sch.order_release_times = _by_id
        """,
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root.build(tmp_path_factory.mktemp("bench_counting"),
                           search=tiny_root.REORDER_SEARCH)


@pytest.mark.parametrize("how", sorted(BREAKS))
def test_a_broken_inside_of_the_order_scorer_is_not_correct(
        root, tmp_path, how):
    site = tmp_path / "site"
    site.mkdir()
    (site / "sitecustomize.py").write_text(
        "import sys\n"
        "if any(a.endswith('sidecar_main.py') for a in sys.argv):\n"
        f"    sys.path.insert(0, {tiny_root.REPO!r})\n"
        + textwrap.indent(textwrap.dedent(BREAKS[how]), "    "))
    rc, result, out, err = tiny_root.run_cell(
        root, CELL, 1, extra_env={"PYTHONPATH": str(site)})
    assert rc == 0, err[-3000:]
    assert result["correct"] is False, out[-2000:]
    checks = result["checks"]
    over = [k for k in ("rerank_fitness_gap", "fused_fitness_gap")
            if checks[k]["value"] > checks[k]["limit"]]
    assert over, checks
    assert checks["release_mode_differs"]["value"] == 0
    assert list(result)[-1] == "checks"


def test_the_sound_cell_rehearses_correct_in_its_own_mode(root):
    """The same root without a break: ``correct: true``, the request
    states reorder mode and the sidecar counted every evolve under the
    order branch."""
    rc, result, out, err = tiny_root.run_cell(root, CELL, 1, trace=1)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True, out[-3000:]
    assert result["checks"]["release_mode_differs"]["value"] == 0
    assert result["metrics"]["order_request_share"]["value"] == 100.0
    assert "blockwise_request_share" not in result["metrics"]


def test_the_configuration_states_its_source_and_cuts_scale_only():
    with open(os.path.join(tiny_root.REPO, "BENCHMARK.json")) as f:
        doc = json.load(f)
    entry = next(c for c in doc["configs"] if c["name"] == "zk2080-reconfig5")
    with open(os.path.join(tiny_root.REPO, entry["file"])) as f:
        cfg = json.load(f)
    # the source's three facts
    for fact in ("zk-repro-2080.nfqhook", "FLE", "nothing else",
                 "dumb", "80 ms", "ReconfigRecoveryTest"):
        assert fact in cfg["source"], fact
    for fact in ("zk-repro-2080.nfqhook", "FLE", "dumb 80 ms",
                 "ReconfigRecoveryTest"):
        assert fact in entry["source"], fact
    assert cfg["testee"]["example"] == "examples/zk-reconfig"
    # what this repo chose, each with its reason
    assert set(cfg["assumed"]) >= {
        "servers", "reconfiguration", "the_bug", "scenario",
        "failure_rate", "reorder_mapping", "reference_mode",
        "events_per_run"}
    assert all(len(v) > 40 for v in cfg["assumed"].values())
    # cuts of scale only, the same in both places; no shape among them
    assert cfg["reduced"] == entry["reduced"] == [
        "runs_per_campaign", "history_depth", "scenario_steps"]
    s = cfg["search"]["set"]
    assert (s["release_mode"], s["reorder_gap"], s["reorder_window"]) \
        == ("reorder", 80, 500)
    g = cfg["guarantees"]
    assert (g["release_mode"], g["reorder_gap_ms"], g["reorder_window_ms"],
            g["scorer"], g["reference_traces"]) \
        == ("reorder", 80, 500, "order", 4)
    assert "within 0.05" in g["numerics"]
    assert g["no_compile_in_window"] is True
    # the recorded templates: 6 successes + 3 failures, every run a
    # whole scenario (256 election messages or more) under the cap
    with open(os.path.join(tiny_root.REPO, cfg["history"])) as f:
        hist = json.load(f)
    assert (len(hist["successes"]), len(hist["failures"])) == (6, 3)
    for run in hist["successes"]:
        assert 256 <= len(run["actions"]) <= 4096
        assert all(":fle:" in a["event_hint"] for a in run["actions"])
    # the one cell, on one chip, over the mix that is there
    cell = next(w for w in doc["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("zk2080-reconfig5", "fleet8-d64", 1)
    own = [m["name"] for m in doc["per_layer"]
           if m.get("workloads") == [CELL]]
    assert own == ["order_request_share"]
