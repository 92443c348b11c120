"""CPU rehearsals of the benchmark's ``live`` cells: each cell of
BENCHMARK.json end to end at a toy width, through the real harness —
sidecar launcher, history synthesiser, ``nmz-tpu campaign`` on the
rehearsal testee, the relay, the window and the comparison with the
numpy reference. (One file: the rehearsal testee's REST port is fixed.)"""

import json
import os

import pytest

import tiny_root

with open(os.path.join(tiny_root.REPO, "BENCHMARK.json")) as _f:
    _CELLS = json.load(_f)["workloads"]
LIVE = [c for c in _CELLS if c["traffic"].startswith("live")]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root.build(tmp_path_factory.mktemp("bench_live"))


@pytest.fixture(scope="module")
def reorder_root(tmp_path_factory):
    """The same checkout with every configuration searching the order
    half of the genome (``release_mode = "reorder"``)."""
    return tiny_root.build(tmp_path_factory.mktemp("bench_live_reorder"),
                           search=tiny_root.REORDER_SEARCH)


def _held_to_counts(result, out):
    """A live run held to counts and orderings, not to what a 4 s
    window completes: on an xdist worker under six loaded workers a
    cycle of the rehearsal testee can outlast the window (it read
    ``no_cycle_completed`` for ``zk2212-fle3.live-d64`` in the driver's
    run and passed alone). An empty window may fail the numbers that
    say so and no other; a window with a cycle in it is held in full."""
    facts = tiny_root.tagged(out, "facts: ")
    checks = result["checks"]
    over = {k for k, c in checks.items() if c["value"] > c["limit"]}
    cycles = facts["cycles_completed"]
    if cycles:
        assert result["correct"] is True and not over, out[-3000:]
    else:
        assert over <= {"no_cycle_completed", "answers_missing"}, checks
    assert result["failed"] == 0 and result["attempted"] == cycles
    assert result["device"]["platform"] == "cpu"
    assert checks["window_compiles"]["value"] == 0
    assert facts["depth_at_open"] == 7  # 6 stored + 1 warm-up run
    assert facts["depth_at_close"] == 7 + cycles
    # a run installs its table before its result is written: the last
    # install of a window may belong to a run that ends after it
    assert cycles <= facts["installs_in_window"] <= cycles + 1
    # and every reply of the window was held against the reference
    assert facts["agreement"]["reply_answers"] == facts["installs_in_window"]
    # the cycles counted ended inside the window
    assert facts["cycle_ends_s"] == sorted(facts["cycle_ends_s"])
    assert all(0 < t <= facts["seconds"] for t in facts["cycle_ends_s"])
    return facts, checks


@pytest.mark.parametrize("cell", LIVE, ids=[c["name"] for c in LIVE])
def test_live_cell_rehearsal(root, cell):
    rc, result, out, err = tiny_root.run_cell(
        root, cell["name"], cell["chips"], trace=1, seconds=4.0)
    assert rc == 0, err[-3000:]
    facts, checks = _held_to_counts(result, out)
    assert checks["release_mode_differs"] == {"value": 0, "limit": 0}
    assert facts["searches"][0]["release_mode"] == "delay"
    m = result["metrics"]
    # the install reading of a live cell is a per-layer metric only
    assert "install_p50_s" not in m
    assert m["window_compiles"]["value"] == 0
    if facts["cycles_completed"]:
        assert m["run_wall_p50_s"]["value"] > 0
        assert m["live_install_p50_s"]["value"] > 0
    # device-trace metrics have nothing to read off a chip: left out,
    # never written from a CPU reading
    assert "device_idle_share" not in m and "pairdist_roofline" not in m


def test_live_cell_rehearsal_in_reorder_mode(reorder_root):
    """The campaign of the real (rehearsal) testee under the policy's
    reorder buffer: every run installs a priority table from the
    sidecar, and every answer is held to the order-mode reference."""
    cell = LIVE[0]
    rc, result, out, err = tiny_root.run_cell(
        reorder_root, cell["name"], cell["chips"], trace=1, seconds=4.0)
    assert rc == 0, err[-3000:]
    facts, checks = _held_to_counts(result, out)
    assert checks["release_mode_differs"] == {"value": 0, "limit": 0}
    held = facts["searches"][0]
    assert (held["release_mode"], held["order_gap"],
            held["order_window"]) == ("reorder", 0.01, 0.05)
    assert held["fault_coin"] is False
    agree = facts["agreement"]
    assert agree["rerank_answers"] == 64  # the whole population
    assert agree["rerank_fitness_gap"] <= 1e-4  # float32 against float32


def test_live_cell_end_to_end_metrics(root):
    cell = LIVE[0]
    rc, result, out, err = tiny_root.run_cell(
        root, cell["name"], cell["chips"], trace=0, seconds=4.0)
    assert rc == 0, err[-3000:]
    assert set(result["metrics"]) == {"searched_runs_per_hour", "setup_s"}
    assert result["metrics"]["searched_runs_per_hour"]["value"] > 0


def test_no_tpu_means_nonzero_exit_and_no_result(root):
    cell = LIVE[0]
    rc, result, out, err = tiny_root.run_cell(
        root, cell["name"], cell["chips"], cpu=False)
    assert rc != 0 and result is None
    assert not [line for line in out.splitlines()
                if line.startswith("{")], out
    assert "no TPU" in err
