"""The order half of the genome in the yardstick (``release_mode =
"reorder"``): the plain reference's windowed-permutation semantics by
hand, against the program's scorer on seeded tables, what it still
refuses, and tiny-root runs whose timed path is broken underneath —
each has to come out ``correct: false`` on the number that names it.
(The fleet and live rehearsals of a sound reorder run sit with the
delay-mode ones, ``test_cells_fleet.py`` / ``test_cells_live.py``.)"""

import json
import os
import sys
import textwrap

import numpy as np
import pytest

import tiny_root

sys.path.insert(0, tiny_root.BENCH)

import history  # noqa: E402
import reference  # noqa: E402
import temp_root  # noqa: E402

SP = {"K": 256, "H": 256, "seed": 5, "w_novelty": 0.3, "w_bug": 1.0,
      "w_delay_cost": 0.0005, "tau": 0.1, "max_interval": 0.4,
      "min_failure_signatures": 3}
REORDER = {"release_mode": "reorder", "reorder_gap": 0.08,
           "reorder_window": 0.5}
IP = {"reference_mode": "envelope", "order_mode_max_l": 4096}


# -- the semantics, by hand ---------------------------------------------------


def _hand_trace():
    # six events, one masked; window 0.5 s: [0, 0.5) holds events 0, 1,
    # 2 (and the masked 3), [0.5, 1.0) holds 4 and 5
    hint_ids = np.array([0, 1, 2, 3, 1, 0], np.int32)
    arrival = np.array([0.0, 0.1, 0.2, 0.3, 0.6, 0.7], np.float32)
    mask = np.array([True, True, True, False, True, True])
    return hint_ids, arrival, mask


def test_windowed_permutation_by_hand():
    hint_ids, arrival, mask = _hand_trace()
    # bucket 2 first, buckets 0 and 1 tie on priority: arrival decides
    prio = np.array([[0.05, 0.05, 0.01, 0.0]], np.float32)
    gap, window = 0.08, 0.5
    t = reference.ordered_release(prio, hint_ids, arrival, mask, gap,
                                  window)[0]
    # window 0 releases at its end, 0.5: bucket 2, then the tie in
    # arrival order (event 0 before event 1); the masked event is
    # absent: it takes no slot though its priority is the lowest
    assert t[[2, 0, 1]] == pytest.approx([0.5, 0.58, 0.66])
    assert t[3] == reference.BIG
    # window 1 releases at 1.0: the tie again, event 4 arrived first
    assert t[[4, 5]] == pytest.approx([1.0, 1.08])
    # one global window: slots from 0, gap apart, over all live events
    t0 = reference.ordered_release(prio, hint_ids, arrival, mask, gap,
                                   0.0)[0]
    assert t0[[2, 0, 1, 4, 5]] == pytest.approx(
        [0.0, 0.08, 0.16, 0.24, 0.32])
    assert t0[3] == reference.BIG
    # features: first release per bucket, tau = gap / 2
    pairs = np.array([[0, 1], [2, 0], [1, 3]], np.int32)
    f = reference.features(prio, hint_ids, arrival, mask, pairs, gap / 2,
                           order=(gap, window))[0]
    sig = lambda z: 1 / (1 + np.exp(-z))  # noqa: E731
    # first = [0.58, 0.66, 0.5, BIG]: one slot apart is 2 tau
    assert f == pytest.approx([sig(2.0), sig(2.0), sig(30.0)], rel=1e-5)
    # a table is priorities, not delays: shifting all of them moves
    # nothing, and the fitness carries no delay cost
    shifted = reference.features(prio + 0.1, hint_ids, arrival, mask,
                                 pairs, gap / 2, order=(gap, window))[0]
    assert (shifted == f).all()


def test_window_index_is_taken_in_float32():
    # float32(0.7) / float32(0.1) rounds to 7.0, where the device
    # divides its float32 arrivals; the same arrival over 0.1 in float64
    # is 6.99999988 and floors to 6
    a = np.array([0.7], np.float32)
    assert reference.window_index(a, 0.1).tolist() == [7]
    assert int(np.floor(np.float64(a[0]) / 0.1)) == 6
    assert reference.window_index(a, 0.0).tolist() == [0]
    mask = np.ones(3, bool)
    edge = np.array([0.0, 0.7, 0.75], np.float32)
    # the first event of every trace arrives at 0: 0 / w is 0 exactly
    assert reference.events_on_a_window_edge(edge, mask, 0.1) == 1
    assert reference.events_on_a_window_edge(edge, mask, 0.0) == 0
    assert reference.events_on_a_window_edge(edge, ~mask, 0.1) == 0


def test_the_state_reads_the_mode_in_one_place():
    delay = reference.SearchState(SP, IP, 8, 4)
    assert delay.order is None and delay.held_mode() == ["delay"]
    assert delay.weights["tau"] == 0.1 and delay.trace_cap == 0
    assert delay.weights["delay_cost"] == 0.0005
    state = reference.SearchState({**SP, **REORDER}, IP, 8, 4)
    assert state.order == (0.08, 0.5)
    assert state.held_mode() == ["reorder", 0.08, 0.5]
    # tau = gap / 2, no delay cost, the request's cap on a trace
    assert state.weights["tau"] == 0.04
    assert state.weights["delay_cost"] == 0.0
    assert state.trace_cap == 4096
    # the gap has a floor of 0.1 ms, a window below 0 is one window
    low = reference.SearchState({**SP, **REORDER, "reorder_gap": 0.0,
                                 "reorder_window": -1.0}, IP, 8, 4)
    assert low.order == (1e-4, 0.0) and low.weights["tau"] == 5e-5


@pytest.mark.parametrize("sp, ip, names", [
    ({"max_fault": 0.1}, {}, "max_fault"),
    ({"guidance": True}, {}, "guidance"),
    ({}, {"failure_pool": "/pool"}, "failure_pool"),
    ({}, {"knowledge": "127.0.0.1:1"}, "knowledge"),
    ({"release_mode": "swap"}, {}, "'swap'"),
], ids=["fault", "guidance", "failure_pool", "knowledge", "unknown_mode"])
@pytest.mark.parametrize("mode", [{}, REORDER], ids=["delay", "reorder"])
def test_the_reference_refuses_by_name(mode, sp, ip, names):
    with pytest.raises(reference.Refused, match=names):
        reference.SearchState({**SP, **mode, **sp}, {**IP, **ip}, 8, 4)


def _storage(tmp_path, seed, depth=12, failures=3):
    templates = history.load_templates(os.path.join(
        tiny_root.BENCH, "configs", "zk2212-fle3.history.json"))
    d = tmp_path / f"storage{seed}"
    d.mkdir()
    (d / "storage.json").write_text('{"type": "naive", "next_run": 0}')
    history.fill_storage(str(d), templates, depth, failures, seed)
    return reference.read_runs(str(d), reference.stored_depth(str(d)), 256)


def test_a_history_over_the_stated_cap_is_refused(tmp_path):
    """A cut hunt is not the hunt: the harness gives no result."""
    runs = _storage(tmp_path, 3)
    longest = max(len(r.hint_ids) for r in runs)
    state = reference.SearchState(
        {**SP, **REORDER}, {**IP, "order_mode_max_l": longest}, 16, 4)
    state.ingest(runs)  # at the cap: whole
    cut = reference.SearchState(
        {**SP, **REORDER}, {**IP, "order_mode_max_l": longest - 1}, 16, 4)
    with pytest.raises(reference.Refused, match="cut hunt is not the hunt"):
        cut.ingest(runs)
    # an explicit trace length is the cap of the mode
    with pytest.raises(reference.Refused, match=f"at {longest - 1}"):
        reference.SearchState(
            {**SP, **REORDER}, {**IP, "L": longest - 1}, 16, 4).ingest(runs)
    # a delay-mode history is never refused: a run the program cut
    # shows in the numbers compared (test_long_traces.py)
    reference.SearchState(SP, {**IP, "L": longest - 1}, 16, 4).ingest(runs)


def test_stored_runs_are_embedded_from_realized_releases(tmp_path):
    """In reorder mode too a stored run's row is its REALIZED releases,
    no permutation, at the mode's tau."""
    runs = _storage(tmp_path, 4)
    state = reference.SearchState({**SP, **REORDER}, IP, 16, 4)
    state.ingest(runs)
    run = runs[0]
    want = reference.features(
        np.zeros((1, 256), np.float32), run.hint_ids, run.released,
        np.ones(len(run.hint_ids), bool), state.pairs, 0.04)[0]
    assert (state.archive[0] == want).all()
    delay = reference.SearchState(SP, IP, 16, 4)
    delay.ingest(runs)
    assert (delay.pairs == state.pairs).all()
    assert np.abs(delay.archive[0] - want).max() > 0.01  # another tau


# -- against the program's scorer ---------------------------------------------


@pytest.mark.parametrize("window", [0.0, 0.3, 0.5])
@pytest.mark.parametrize("T", [1, 4])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_reference_scores_what_the_program_scores(seed, T, window):
    """Seeded priority tables, ties on both clip boundaries, masked
    events: the numpy reference and ``score_population_multi`` in
    order mode agree to float32 rounding."""
    if tiny_root.REPO not in sys.path:
        sys.path.insert(0, tiny_root.REPO)
    import jax.numpy as jnp

    from namazu_tpu.ops import schedule as sch

    S, H, K, L, n, gap = 48, 64, 32, 128, 90, 0.08
    rng = np.random.RandomState(seed)
    traces = []
    for _ in range(T):
        h, a = np.zeros(L, np.int32), np.zeros(L, np.float32)
        m = np.zeros(L, bool)
        h[:n] = rng.randint(0, H, n)
        a[:n] = np.sort(rng.uniform(0, 3, n))
        m[:n] = True
        m[rng.randint(0, n, 5)] = False
        traces.append((h, a, m))
    pairs = reference.sample_pairs(K, H, seed)
    tables = rng.uniform(0, 0.1, (S, H)).astype(np.float32)
    tables[:, :8], tables[:, 8:12] = 0.0, 0.1
    archive = rng.uniform(0, 1, (16, K)).astype(np.float32)
    failures = rng.uniform(0, 1, (4, K)).astype(np.float32)
    want = reference.score(
        tables, traces, pairs, archive, failures,
        {"novelty": 0.3, "bug": 1.0, "delay_cost": 0.0, "tau": gap / 2,
         "order": (gap, window)}, novelty_scale=0.7)
    weights = sch.ScoreWeights(
        novelty=0.3, bug=1.0, delay_cost=0.0, tau=gap / 2, order_mode=True,
        order_gap=gap, order_window=window)
    stacked = sch.TraceArrays(*(jnp.stack([t[i] for t in traces])
                                for i in range(3)))
    got, _ = sch.score_population_multi(
        jnp.asarray(tables), stacked, jnp.asarray(pairs),
        jnp.asarray(archive), jnp.asarray(failures), weights,
        novelty_scale=jnp.float32(0.7))
    assert np.abs(np.asarray(got, np.float64) - want).max() <= 1e-5
    assert np.abs(want).max() > 1.0  # fitness of some size was compared


PINNED = {"float32": -143.64342944179734, "bfloat16": -143.65701818392043,
          "archive": 65507.65839507284}


def test_the_delay_mode_reference_reads_what_it_read(tmp_path):
    """Delay-mode numbers of a fixed seed, pinned from the reference as
    it stood before it learnt the order mode (PR 28's file)."""
    runs = _storage(tmp_path, 1, depth=16)
    state = reference.SearchState({**SP, "seed": 1}, IP, 512, 64)
    state.ingest(runs)
    rng = np.random.RandomState(1)
    tables = rng.uniform(0, 0.4, (8, 256)).astype(np.float32)
    fit = state.score(tables, ("float32", "bfloat16"))
    assert float(fit["float32"].sum()) == pytest.approx(
        PINNED["float32"], rel=1e-9)
    assert float(fit["bfloat16"].sum()) == pytest.approx(
        PINNED["bfloat16"], rel=1e-9)
    assert float(state.archive.sum()) == pytest.approx(
        PINNED["archive"], rel=1e-12)
    assert (state.archive_n, state.failure_n) == (16, 3)



# -- a reorder run with the timed path broken underneath ----------------------


_SCORE_OFF = """
        from namazu_tpu.ops import schedule as _sch
        _orig = _sch.score_population_multi
        def _off(*a, **kw):
            fit, feats = _orig(*a, **kw)
            return fit + 0.2, feats
        _sch.score_population_multi = _off
        """

BREAKS = {
    # the program's order scorer off by a constant, where the reply's
    # re-rank looks it up
    "order_scorer_off_by_a_constant": _SCORE_OFF,
    # the program scores one global window where the request states
    # 50 ms: the search still HOLDS 50 ms, so only the answers tell
    "one_global_window_scored": """
        from namazu_tpu.ops import schedule as _sch
        _orig = _sch.order_release_times
        _sch.order_release_times = \\
            lambda prio, trace, gap, window=0.0: _orig(prio, trace, gap, 0.0)
        """,
    # a reorder request answered by a search built for delay mode
    "reorder_request_held_to_delay_semantics": """
        from namazu_tpu.models import search as _s
        _orig = _s.make_score_weights
        _s.make_score_weights = \\
            lambda **kw: _orig(**{**kw, "release_mode": "delay"})
        """,
    # every stored trace cut at 4 events (of 12: six hints, each met
    # twice), under the cap the request states (4,096)
    "traces_cut_under_the_stated_cap": """
        from namazu_tpu.ops import trace_encoding as _te
        _orig = _te.encode_trace_views
        _te.encode_trace_views = \\
            lambda trace, L=None, **kw: _orig(trace, L=4, **kw)
        """,
}

#: the number of the ``checks:`` line that has to catch each break
CAUGHT_BY = {
    "order_scorer_off_by_a_constant": "rerank_fitness_gap",
    "one_global_window_scored": "rerank_fitness_gap",
    "reorder_request_held_to_delay_semantics": "release_mode_differs",
    "traces_cut_under_the_stated_cap": "reference_buckets_differ",
}
CELL = "zk2212-fle3.fleet8-d64"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root.build(tmp_path_factory.mktemp("bench_order"),
                           search=tiny_root.REORDER_SEARCH)


@pytest.mark.parametrize("how", sorted(BREAKS))
def test_broken_reorder_path_is_not_correct(root, tmp_path, how):
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
    caught = CAUGHT_BY[how]
    assert checks[caught]["value"] > checks[caught]["limit"], checks
    if how != "reorder_request_held_to_delay_semantics":
        # the search holds what the request states: only answers differ
        assert checks["release_mode_differs"]["value"] == 0
    # the numbers are where the contract wants them: last in the
    # result's line, and as the last line of stderr
    assert list(result)[-1] == "checks"
    assert json.loads(err.strip().splitlines()[-1][8:]) == checks


def test_a_history_over_the_cap_gives_no_result(root):
    """The harness refuses, with a sentence, a reorder configuration
    whose recorded runs exceed the cap the request states."""
    site = os.path.join(root, "cap_site")
    os.makedirs(site, exist_ok=True)
    with open(os.path.join(site, "sitecustomize.py"), "w") as f:
        f.write(textwrap.dedent(f"""
            import sys
            sys.path.insert(0, {tiny_root.REPO!r})
            from namazu_tpu.policy import tpu as _tpu
            _tpu.TPUSearchPolicy.ORDER_MODE_MAX_L = 8
            """))
    rc, result, out, err = tiny_root.run_cell(
        root, CELL, 1, extra_env={"PYTHONPATH": site})
    assert rc != 0 and result is None
    assert "REFUSED" in err and "a cut hunt is not the hunt" in err
    assert not [line for line in out.splitlines() if line.startswith("{")]


def test_a_fault_half_is_refused_before_anything_warms(tmp_path):
    """What the reference does not cover is refused from the request,
    which is known before the sidecar warms: no search is built, no
    window is driven, no result is printed."""
    root = tiny_root.build(tmp_path, search={"max_fault": 0.05})
    rc, result, out, err = tiny_root.run_cell(root, CELL, 1)
    assert rc == 1 and result is None
    assert "REFUSED" in err and "max_fault" in err
    assert "facts: " not in out
    work = os.path.join(root, "chiprun_out", "benchmarks", CELL)
    kept = [name for _d, _s, names in os.walk(work) for name in names]
    assert "sidecar.log" in kept
    # a served request leaves the search's checkpoint in its storage
    assert not [n for n in kept if n.endswith(".npz")], kept


def test_a_temporary_root_states_keys_at_the_shipped_width(tmp_path):
    """How a mode no cell runs yet is read on the chip
    (``benchmarks/temp_root.py``): the checkout's manifest and data
    files, every configuration's ``search.set`` with the keys on top
    and nothing cut; the run behind ``--`` is ``run.py``'s own."""
    root = str(tmp_path / "root")
    rc = temp_root.main([
        root, 'release_mode="reorder"', "reorder_gap=80",
        "reorder_window=500", "max_fault=0.05", "--", "--workload", CELL,
        "--seed", "3", "--seconds", "2", "--trace", "0", "--cpu", "1"])
    assert rc == 1  # refused by name, from the request (the fault half)
    with open(os.path.join(tiny_root.REPO, "BENCHMARK.json")) as f:
        configs = json.load(f)["configs"]
    for cfg in configs:
        with open(os.path.join(tiny_root.REPO, cfg["file"])) as f:
            shipped = json.load(f)
        with open(os.path.join(root, cfg["file"])) as f:
            mine = json.load(f)
        assert mine["search"]["set"] == {
            **shipped["search"]["set"], "release_mode": "reorder",
            "reorder_gap": 80, "reorder_window": 500, "max_fault": 0.05}
        mine["search"]["set"] = shipped["search"]["set"]
        assert mine == shipped
    assert os.path.samefile(os.path.join(root, "namazu_tpu"),
                            os.path.join(tiny_root.REPO, "namazu_tpu"))
    assert temp_root.main(["--"]) == 2 and temp_root.main([root]) == 2

