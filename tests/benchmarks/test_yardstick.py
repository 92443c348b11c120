"""The benchmark's yardstick on the CPU: manifest rules, rate and
percentile arithmetic, the numpy scorer against a hand-worked case, the
tolerance against a lower-precision control, the history synthesiser,
the declarative per-layer reductions and the trace reduction."""

import copy
import json
import os
import sys

import numpy as np
import pytest

import tiny_root

sys.path.insert(0, tiny_root.BENCH)

import history  # noqa: E402
import layer_metrics  # noqa: E402
import manifest  # noqa: E402
import peaks  # noqa: E402
import rates  # noqa: E402
import reference  # noqa: E402
import trace_reduce  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


# -- manifest ---------------------------------------------------------------


def test_the_committed_manifest_is_valid():
    man = manifest.Manifest(tiny_root.REPO)
    man.validate()
    names = {m["name"] for m in man.doc["end_to_end"]}
    assert names == {"searched_runs_per_hour", "install_p50_s", "setup_s"}
    install = man.end_to_end["install_p50_s"]
    assert all("fleet8" in c for c in install["workloads"])
    for cell in man.cells:
        reported = {m["name"] for m in man.metrics_of(cell, "end_to_end")}
        if ".live" in cell:
            assert "install_p50_s" not in reported
            assert "live_install_p50_s" in {
                m["name"] for m in man.metrics_of(cell, "per_layer")}
    assert sum(c["chips"] == 4 for c in man.cells.values()) <= 1


def _break_unit(doc):
    doc["end_to_end"][0]["unit"] = "runs per hour and more"


def _break_moves(doc):
    # a live cell does not report install_p50_s end to end
    doc["per_layer"][2]["moves"] = "install_p50_s"


def _break_chips(doc):
    for w in doc["workloads"][:3]:
        w["chips"] = 4


def _break_name(doc):
    doc["workloads"][0]["name"] = "zk live"


def _break_bound(doc):
    doc["end_to_end"][0]["bound"] = 0.5


@pytest.mark.parametrize("breaker", [_break_unit, _break_moves,
                                     _break_chips, _break_name,
                                     _break_bound],
                         ids=lambda f: f.__name__)
def test_manifest_validation_refuses(breaker):
    man = manifest.Manifest(tiny_root.REPO)
    man.doc = copy.deepcopy(man.doc)
    breaker(man.doc)
    man.cells = {w["name"]: w for w in man.doc["workloads"]}
    man.end_to_end = {m["name"]: m for m in man.doc["end_to_end"]}
    with pytest.raises(manifest.ManifestError):
        man.validate()


# -- rates -------------------------------------------------------------------


def test_rate_is_cycle_based_not_count_over_seconds():
    # 13 runs of 3.7 s after the opening completion; the window (50 s)
    # closes 1.9 s after the 13th: count/seconds would read 936
    t_open = 1000.0
    done = [t_open + 3.7 * i for i in range(15)]
    rate = rates.closed_loop_rate_per_hour([done], t_open, 50.0)
    assert rate == pytest.approx(3600 / 3.7)
    assert 3600 * 13 / 50 == pytest.approx(936)


def test_rate_window_edges_and_several_clients():
    t_open = 10.0
    # a completion AT the opening instant is the boundary, not a cycle;
    # one at t_open + seconds counts; one after it does not
    done = [10.0, 12.0, 14.0, 15.0, 15.0001]
    assert rates.closed_loop_rate_per_hour([done], t_open, 5.0) == \
        pytest.approx(3600 * 3 / 5.0)
    assert rates.closed_loop_rate_per_hour([[10.0, 16.0]], t_open,
                                           5.0) is None
    # two clients out of phase: each over its own whole cycles (A: 2
    # cycles of 2 s from its boundary at 10; B: 2 cycles of 2 s from its
    # boundary at 9) — the burst phase of the other does not matter
    a, b = [8.0, 10.0, 12.0, 14.0], [7.0, 9.0, 11.0, 13.0, 15.5]
    assert rates.closed_loop_rate_per_hour([a, b], t_open, 5.0) == \
        pytest.approx(3600 * (2 / 4.0 + 2 / 4.0))
    # a client with no cycle inside the window adds nothing
    assert rates.closed_loop_rate_per_hour([a, [9.0, 16.0]], t_open,
                                           5.0) == pytest.approx(1800.0)
    assert rates.p50([3, 1, 2, 10]) == 2.5 and rates.p50([]) is None
    assert rates.iqr_spread([100, 101, 102, 103, 104, 105]) == \
        pytest.approx((104.25 - 100.75) / 102.5)


# -- the numpy scorer ---------------------------------------------------------


def _hand_case():
    # H = 4 buckets, 3 events: bucket 0 at t=0.0, bucket 1 at t=0.1,
    # bucket 0 again at t=0.2 (not first); bucket 2, 3 never occur
    hint_ids = np.array([0, 1, 0, 0], np.int32)
    arrival = np.array([0.0, 0.1, 0.2, 0.0], np.float32)
    mask = np.array([True, True, True, False])
    pairs = np.array([[0, 1], [1, 2]], np.int32)
    return hint_ids, arrival, mask, pairs


def test_numpy_scorer_hand_worked_case():
    hint_ids, arrival, mask, pairs = _hand_case()
    delays = np.array([[0.0, 0.0, 0.0, 0.0],
                       [0.3, 0.0, 0.0, 0.0]], np.float32)
    tau = 0.1
    f = reference.features(delays, hint_ids, arrival, mask, pairs, tau)
    sig = lambda z: 1 / (1 + np.exp(-z))  # noqa: E731
    # genome 0: first = [0.0, 0.1, BIG]; pair (0,1): (0.1-0)/0.1 = 1;
    # pair (1,2): BIG-vs-finite saturates at +30
    assert f[0] == pytest.approx([sig(1.0), sig(30.0)])
    # genome 1 delays bucket 0 by 0.3: first[0] = 0.3 -> (0.1-0.3)/0.1
    assert f[1] == pytest.approx([sig(-2.0), sig(30.0)], rel=1e-6)
    archive = np.array([[0.5, 0.5], [1.0, 1.0]], np.float32)
    failures = np.array([[0.0, 1.0]], np.float32)
    w = {"novelty": 0.3, "bug": 1.0, "delay_cost": 0.5, "tau": tau}
    fit = reference.score(delays, [(hint_ids, arrival, mask)], pairs,
                          archive, failures, w, novelty_scale=0.5)
    for g in range(2):
        d2a = min(((f[g] - a) ** 2).sum() for a in archive)
        d2f = ((f[g] - failures[0]) ** 2).sum()
        want = 0.3 * 0.5 * d2a - 1.0 * d2f - 0.5 * delays[g].mean()
        assert fit[g] == pytest.approx(want, abs=1e-6)
    # two traces: the mean of the per-trace distance terms
    fit2 = reference.score(delays, [(hint_ids, arrival, mask)] * 2, pairs,
                           archive, failures, w, novelty_scale=0.5)
    assert fit2 == pytest.approx(fit)


def _state(tmp_path, seed, depth=16, failures=3, **over):
    """A synthesised storage at the configuration's own trace shape and
    width, and the reference's state after one request on it."""
    templates = history.load_templates(os.path.join(
        tiny_root.BENCH, "configs", "zk2212-fle3.history.json"))
    d = tmp_path / f"storage{seed}"
    d.mkdir()
    (d / "storage.json").write_text('{"type": "naive", "next_run": 0}')
    history.fill_storage(str(d), templates, depth, failures, seed)
    sp = {"K": 256, "H": 256, "seed": seed, "w_novelty": 0.3,
          "w_bug": 1.0, "w_delay_cost": 0.0005, "tau": 0.1,
          "max_interval": 0.4, "min_failure_signatures": 3}
    state = reference.SearchState(
        sp, {"reference_mode": "envelope"}, over.get("archive_rows", 512),
        over.get("failure_rows", 64))
    runs = reference.read_runs(str(d), reference.stored_depth(str(d)), 256)
    state.ingest(runs)
    return state, runs


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_comparison_refuses_the_precision_below(tmp_path, seed):
    """The control, at a size a test can hold: the reference with its
    f.c operands one step below the stated type, put in the program's
    place, lands far above the limit; a scorer AT the stated type (or
    more accurate than it: at float32) lands far below it."""
    import run as bench_run

    limit = bench_run.FITNESS_GAP_LIMIT
    state, runs = _state(tmp_path, seed)
    rng = np.random.RandomState(seed)
    # tables as a search answers them: near a stored failure's own
    tables = np.clip(
        np.stack([r.released - r.arrival for r in runs if not r.ok])
        .mean() + rng.uniform(-0.05, 0.05, (48, 256)), 0, 0.4)
    ref32 = state.score(tables)
    stated = state.score(tables, "bfloat16")
    lower = state.score(tables, reference.LOWER["bfloat16"])
    assert reference.fitness_gap(lower, ref32, stated) > 3 * limit
    noise = rng.uniform(-1e-4, 1e-4, 48)
    for sound in (stated + noise, ref32 + noise):
        assert reference.fitness_gap(sound, ref32, stated) < limit / 3
    assert reference.fitness_gap([float("nan")], [0.0], [0.0]) > limit


def test_state_from_storage_rings_and_references(tmp_path):
    """The novelty ring takes every stored run again on every request
    and overwrites its oldest rows; the failure ring takes one row per
    distinct hint/entity sequence; the reference is the successes'
    earliest arrival per bucket."""
    state, runs = _state(tmp_path, 7, depth=16, failures=3,
                         archive_rows=40)
    assert state.archive_n == 16 and (state.archive[16:] == 0.5).all()
    assert state.labels[:16].tolist() == [0.0 if r.ok else 1.0
                                          for r in runs]
    first = state.archive.copy()
    state.ingest(runs)
    state.ingest(runs)  # 48 rows into 40 slots: the ring wrapped
    assert state.archive_n == 48
    assert (state.archive[:8] == first[8:16]).all()
    assert (state.archive[16:32] == first[:16]).all()
    sigs = {r.signature() for r in runs if not r.ok}
    assert state.failure_n == len(sigs) <= 3
    assert (state.failures[state.failure_n:] == 0.5).all()
    assert state.novelty_scale() == (
        1.0 if len(sigs) < 3 else max(0.25, 3 / len(sigs)))
    hint_ids, arrival, mask = state.traces[0]
    ok = [r for r in runs if r.ok]
    for b, t in zip(hint_ids, arrival):
        assert t == min(r.arrival[r.hint_ids == b].min()
                        for r in ok if (r.hint_ids == b).any())
    assert sorted(hint_ids) == sorted({int(b) for r in ok
                                       for b in r.hint_ids})
    # a run with a bucket no run had before refits the pairs and clears
    # both rings
    odd = reference.Run([{"class": "x", "entity": "e", "event_hint": "new",
                          "event_arrived": 1.0, "triggered_time": 1.1}],
                        True, 256, index=16)
    state.ingest(runs + [odd])
    assert state.archive_n == 17


@pytest.mark.parametrize("occupied", [range(0, 60, 5), range(0, 200, 7)],
                         ids=["fewer_pairs_than_K", "more_pairs_than_K"])
def test_reference_buckets_and_pairs_are_the_programs(occupied):
    """The copies the reference keeps of the deployment's hash and pair
    sample agree with the program's (which the reference never
    imports)."""
    if tiny_root.REPO not in sys.path:
        sys.path.insert(0, tiny_root.REPO)
    from namazu_tpu.ops import trace_encoding as te

    for hint in ("FLE:1->2:notify", "", "kv:put:/a"):
        assert reference.fnv64a(hint.encode()) % 256 == te.hint_bucket(
            hint, 256)
    assert (reference.informative_pairs(occupied, 256, 256, 11)
            == te.informative_pairs(list(occupied), 256, 256, 11)).all()
    assert (reference.sample_pairs(256, 256, 11)
            == te.sample_pairs(256, 256, 11)).all()


# -- the history synthesiser ---------------------------------------------------


def test_history_is_seeded_and_holds_the_stated_failures(tmp_path):
    templates = history.load_templates(os.path.join(
        tiny_root.BENCH, "configs", "zk2212-fle3.history.json"))
    digests = []
    for seed in (3_000_000_001, 3_000_000_001, 5):
        d = tmp_path / f"s{len(digests)}"
        d.mkdir()
        (d / "storage.json").write_text('{"type": "naive", "next_run": 0}')
        facts = history.fill_storage(str(d), templates, 16, 3, seed)
        assert facts["depth"] == 16
        oks = []
        body = []
        for i in range(16):
            with open(d / f"{i:08x}" / "result.json") as f:
                oks.append(json.load(f)["successful"])
            with open(d / f"{i:08x}" / "trace.json") as f:
                body.append(f.read())
        assert oks.count(False) == 3 and oks[-1] is True
        assert json.loads((d / "storage.json").read_text())["next_run"] == 16
        digests.append(hash(tuple(body)))
        acts = json.loads(body[0])
        assert 16 <= len(acts) <= 18
        assert all(a["triggered_time"] >= a["event_arrived"] for a in acts)
    assert digests[0] == digests[1] != digests[2]


# -- declarative per-layer metrics --------------------------------------------


def test_layer_metric_menu():
    obs = {
        "spans": {"handle": [[0, 1.0, None], [1, 3.0, None]],
                  "ingest": [[0, 0.5, 10], [1, 1.5, 30]]},
        "metrics_before": {"metrics": [{
            "name": "c", "samples": [{"labels": {"p": "x"},
                                      "value": {"sum": 1.0, "count": 2}}]}]},
        "metrics_after": {"metrics": [{
            "name": "c", "samples": [{"labels": {"p": "x"},
                                      "value": {"sum": 4.0, "count": 8}}]}]},
        "trace": {"device_busy_s": 0.5, "window_s": 10.0},
        "compiles": [],
        "client": {"install_s": [0.2, 0.4, 0.3]},
    }
    ev = layer_metrics.evaluate
    span = {"kind": "span", "name": "ingest"}
    assert ev({"value": {"kind": "span", "name": "handle"},
               "reduce": "p50"}, obs) == 2.0
    assert ev({"value": span, "reduce": "share_of",
               "other": {"kind": "span", "name": "handle"}}, obs) == 50.0
    assert ev({"value": span, "reduce": "per", "scale": 1000,
               "other": dict(span, field="arg")}, obs) == 50.0
    ctr = {"kind": "counter", "name": "c", "labels": {"p": "x"}}
    assert ev({"value": dict(ctr, field="sum"), "reduce": "per",
               "other": dict(ctr, field="count")}, obs) == 0.5
    assert ev({"value": {"kind": "trace", "name": "device_busy_s"},
               "reduce": "inverse_share_of",
               "other": {"kind": "trace", "name": "window_s"}}, obs) == 95.0
    assert ev({"value": {"kind": "compile"}, "reduce": "count"}, obs) == 0.0
    assert ev({"value": {"kind": "client", "name": "install_s"},
               "reduce": "p50"}, obs) == 0.3
    # nothing to read -> nothing reported
    assert ev({"value": {"kind": "trace", "name": "absent"},
               "reduce": "sum"}, obs) is None
    assert ev({"value": {"kind": "run_log", "name": "run_wall_s"},
               "reduce": "p50"}, obs) is None


def test_peaks_table_and_pairdist_counts():
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
    c = peaks.pairdist_counts(rows=4096, archive_rows=512,
                              failure_rows=64, k=256)
    assert c["flops"] == 2 * 4096 * 576 * 256
    assert c["bytes"] == 2 * 256 * (4096 + 576) + 4 * (4096 + 576) \
        + 8 * 4096
    shape = {"population_per_chip": 1024, "reference_traces": 4,
             "archive_rows": 512, "failure_rows": 64, "feature_pairs": 256}
    assert peaks.pairdist_counts_of(shape) == c
    decl = {"value": {"kind": "trace", "name": "kernel_s.k"},
            "reduce": "roofline", "kernel": "k",
            "counts": "peaks:pairdist_counts_of"}
    obs = {"trace": {"kernel_s.k": 10 * 20e-6, "kernel_calls.k": 10},
           "shape": shape, "device_kind": "TPU v5 lite"}
    assert layer_metrics.evaluate(decl, obs) == pytest.approx(
        100 * (c["flops"] / 197e12) / 20e-6)
    assert layer_metrics.evaluate(decl, dict(obs, shape=None)) is None
    r = peaks.roofline_share(c, calls=10, kernel_s=10 * 20e-6,
                             device_kind="TPU v5 lite")
    assert r["bound"] == "flops"
    assert r["share_pct"] == pytest.approx(
        100 * (c["flops"] / 197e12) / 20e-6)


# -- the trace reduction -------------------------------------------------------


def _ev(plane, name, start_us, dur_us, scope=None, line="XLA Ops"):
    e = {"plane": plane, "line": line, "name": name,
         "start_ns": start_us * 1e3, "dur_ns": dur_us * 1e3}
    if scope is not None:
        e["scope"] = scope
        e["module"] = "jit_fused"
    return e


def test_trace_reduction_hand_made():
    d0, d1, host = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
    sc = "jit(fused)/jit(main)/nmz_score/x"
    mu = "jit(fused)/jit(main)/nmz_mutate/y"
    events = [
        _ev(host, "bench:handle", 0, 1000, line="t1"),
        _ev(host, "bench:ingest", 0, 400, line="t1"),
        _ev(host, "bench:evolve", 400, 500, line="t1"),
        _ev(host, "nmz:evolve", 450, 400, line="t1"),
        _ev(host, "bench:save", 900, 100, line="t1"),
        # device 0: busy 500..700 and 750..800
        _ev(d0, "fusion.1", 500, 100, mu),
        _ev(d0, "min_sq_distance_pair_pallas.3", 600, 100, sc),
        _ev(d0, "collective-permute.2", 750, 50,
            "jit(fused)/jit(main)/nmz_migrate/z"),
        # device 1: busy 500..800, the collective hidden under an op
        _ev(d1, "while.10", 500, 300, "jit(fused)/jit(main)/while"),
        _ev(d1, "fusion.1", 500, 290, mu),
        _ev(d1, "collective-permute.2", 750, 50,
            "jit(fused)/jit(main)/nmz_migrate/z"),
        _ev(host, "nmz:encode", 2000, 0, line="t1"),  # stretches window
    ]
    r = trace_reduce.reduce(events)
    assert r["n_devices"] == 2
    assert r["window_s"] == pytest.approx(2000e-6)
    assert r["device_busy_s"] == pytest.approx((250 + 300) / 2 * 1e-6)
    assert r["scope_s.nmz_score"] == pytest.approx(50e-6)
    assert r["scope_s.nmz_mutate"] == pytest.approx(195e-6)
    assert r["kernel_calls.min_sq_distance_pair_pallas"] == 0.5
    assert r["kernel_s.min_sq_distance_pair_pallas"] == pytest.approx(50e-6)
    assert r["collective_s"] == pytest.approx(50e-6)
    # device 0's collective runs alone (50 us exposed); device 1's is
    # hidden under fusion.1 except its last 10 us — the enclosing while
    # is a container and hides nothing
    assert r["collective_exposed_s"] == pytest.approx((50 + 10) / 2 * 1e-6)
    assert r["evolve_union_s"] == pytest.approx(400e-6)
    assert r["requests"] == 1
    # per request: what falls inside the whole evolve spans (450..850)
    assert r["evolve_spans"] == 1
    assert r["device_busy_in_evolve_s"] == r["device_busy_s"]
    assert r["collective_exposed_in_evolve_s"] == r["collective_exposed_s"]
    # a second tenant's device work with no whole evolve span around it
    # (its request is cut by the slice) counts for the device, not for
    # the requests the slice holds
    cut = trace_reduce.reduce(
        events + [_ev(d0, "fusion.1", 1500, 100, mu),
                  _ev(d1, "fusion.1", 1500, 100, mu)])
    assert cut["device_busy_s"] == pytest.approx(
        r["device_busy_s"] + 100e-6)
    assert cut["device_busy_in_evolve_s"] == r["device_busy_in_evolve_s"]
    # another kernel is a name in a metric's file, not an edit here
    other = trace_reduce.reduce(events, kernels=("fusion",))
    assert other["kernel_calls.fusion"] == 1.0
    gaps = dict(r["idle_gaps"])
    # device 0 idles 0..400 under ingest, device 1 too: 400 us each
    assert gaps["ingest"] == pytest.approx(400e-6)
    assert gaps["no_request_in_the_sidecar"] == pytest.approx(1000e-6)
    assert sum(gaps.values()) + r["device_busy_s"] == \
        pytest.approx(r["window_s"])
    ops = dict(r["device_ops"])
    assert ops["jit_fused:mutate/fusion.1"] == pytest.approx(195e-6)
    assert ops["jit_fused:/while.10"] == pytest.approx(0.0)


def test_scopes_from_compiled_text():
    text = """
HloModule jit_fused, entry_computation_layout={()->f32[]}
  %fusion.226 = f32[4096,256]{1,0:T(8,128)} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(fused)/jit(main)/while/body/nmz_mutate/select_n" source_file="ga.py" source_line=3}
  ROOT %min_sq_distance_pair_pallas.12 = (f32[4096,1]) custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(fused)/jit(main)/while/body/nmz_score/pallas_call"}
  %copy.1 = f32[2]{0} copy(%x)
"""
    got = trace_reduce.hlo_scopes(text)
    assert got == {
        "fusion.226": "jit(fused)/jit(main)/while/body/nmz_mutate/select_n",
        "min_sq_distance_pair_pallas.12":
            "jit(fused)/jit(main)/while/body/nmz_score/pallas_call"}
    e = {"name": "fusion.226", "module": "jit_fused",
         "scope": got["fusion.226"]}
    assert trace_reduce._label(e) == "jit_fused:mutate/fusion.226"


def test_interval_arithmetic():
    u = trace_reduce.union([[5, 7], [1, 3], [2, 4], [7, 8], [9, 9]])
    assert u == [[1, 4], [5, 8]]
    assert trace_reduce.intersect(u, [[0, 2], [3, 6]]) == \
        [[1, 2], [3, 4], [5, 6]]
    assert trace_reduce.subtract(u, [[0, 2], [3, 6]]) == [[2, 3], [6, 8]]
    assert trace_reduce.total(u) == 6.0


RECORDED = os.path.join(HERE, "recorded_trace.json")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded chip trace committed")
def test_trace_reduction_on_a_recorded_chip_trace():
    with open(RECORDED) as f:
        doc = json.load(f)
    r = trace_reduce.reduce(doc["events"])
    for key, want in doc["expect"].items():
        assert r[key] == pytest.approx(want, rel=1e-9), key
    assert 0 < r["device_busy_s"] < r["window_s"]
    gaps = sum(v for _k, v in r["idle_gaps"])
    assert gaps + r["device_busy_s"] == pytest.approx(r["window_s"],
                                                      rel=1e-6)
