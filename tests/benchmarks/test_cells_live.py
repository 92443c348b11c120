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


@pytest.mark.parametrize("cell", LIVE, ids=[c["name"] for c in LIVE])
def test_live_cell_rehearsal(root, cell):
    rc, result, out, err = tiny_root.run_cell(
        root, cell["name"], cell["chips"], trace=1, seconds=4.0)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True, out[-3000:]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["device"]["platform"] == "cpu"
    m = result["metrics"]
    # the install reading of a live cell is a per-layer metric only
    assert "live_install_p50_s" in m and "install_p50_s" not in m
    assert m["run_wall_p50_s"]["value"] > 0
    assert m["window_compiles"]["value"] == 0
    # device-trace metrics have nothing to read off a chip: left out,
    # never written from a CPU reading
    assert "device_idle_share" not in m and "pairdist_roofline" not in m
    facts = json.loads(next(line for line in out.splitlines()
                            if line.startswith("facts: "))[7:])
    assert facts["depth_at_open"] == 7  # 6 stored + 1 warm-up run
    assert facts["depth_at_close"] == 7 + facts["cycles_completed"]
    assert facts["installs_in_window"] == facts["cycles_completed"]


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
