"""Fused search loop (doc/performance.md "Fused search loop").

The contracts this file pins:

* chunk independence — G generations in ONE fused (lax.scan'd, donated)
  dispatch produce the SAME populations/fitness/best tables/history as
  G dispatches of one generation from the same state, because the PRNG
  key of a generation is ``fold_in(base_key, gen)`` whatever the chunk
  length (the same-draw-order rule ``ScheduledQueue.put_many``
  documents for the control plane); end to end, two searches that
  differ only in ``fused_chunk`` agree to the bit;
* no mid-run recompiles — fixed-capacity archive buffers with traced
  occupancy scalars hit ONE compiled scorer for every occupancy, and
  the surrogate's padded minibatches hit one compiled train step;
* device-resident ingest — re-running against an overlapping reference
  window appends only the new trace rows (dynamic_update_slice) instead
  of re-staging the stack;
* checkpoint compatibility — a checkpoint written at one chunk length
  continues at another to the same result; a population-shape mismatch
  retrains instead of crashing (the PR 11 width rule extended);
* observability — the fused run publishes the host_io phase span, the
  fused-labeled scorer gauge, and a generation record whose host_io_s
  feeds the analytics host-gap share.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from namazu_tpu import obs
from namazu_tpu.models.ga import GAConfig
from namazu_tpu.models.search import ScheduleSearch, SearchConfig
from namazu_tpu.ops import schedule
from namazu_tpu.ops import trace_encoding as te
from namazu_tpu.ops.schedule import (
    ScoreWeights,
    TraceArrays,
    min_sq_distance,
)
from namazu_tpu.parallel.islands import (
    init_island_state,
    make_fused_island_step,
)
from namazu_tpu.parallel.mesh import make_mesh
from tests.scoring import score_one

H, L, K = 32, 64, 32


def toy_trace(n=48, seed=0):
    rng = np.random.RandomState(seed)
    enc = te.encode_event_stream(
        [f"hint{rng.randint(12)}" for _ in range(n)],
        arrivals=sorted(rng.rand(n).tolist()),
        L=L, H=H,
    )
    return TraceArrays(
        jnp.asarray(enc.hint_ids), jnp.asarray(enc.arrival),
        jnp.asarray(enc.mask),
    ), enc


def inputs():
    trace, _ = toy_trace()
    pairs = jnp.asarray(te.sample_pairs(K, H, 0))
    archive = jnp.full((16, K), 0.5, jnp.float32)
    failures = jnp.full((4, K), 0.5, jnp.float32)
    return trace, pairs, archive, failures


def search_cfg(**kw):
    base = SearchConfig(H=H, K=K, archive_size=16, failure_size=8,
                        population=64, migrate_k=2, seed=3,
                        ga=GAConfig(max_delay=0.05))
    return base._replace(**kw)


def enc_of(n, seed):
    rng = np.random.RandomState(seed)
    return te.encode_event_stream(
        [f"h{rng.randint(12)}" for _ in range(n)],
        arrivals=sorted(rng.rand(n).tolist()), H=H,
    )


# -- bit-exactness ----------------------------------------------------------


@pytest.mark.parametrize("gens", [2, 5])
def test_fused_scan_bit_exact_vs_one_generation_per_call(gens):
    mesh = make_mesh(8)
    cfg = GAConfig(max_delay=0.05)
    trace, pairs, archive, failures = inputs()
    key = jax.random.PRNGKey(1)

    step = make_fused_island_step(mesh, cfg, ScoreWeights(), migrate_k=2,
                                  generations=1)
    s_one = init_island_state(jax.random.PRNGKey(0), 64, H, cfg)
    hist_one = []
    for _ in range(gens):
        s_one, h = step(s_one, key, trace, pairs, archive, failures)
        hist_one.append(np.asarray(h))

    fused = make_fused_island_step(mesh, cfg, ScoreWeights(), migrate_k=2,
                                   generations=gens)
    s_fu, hist = fused(init_island_state(jax.random.PRNGKey(0), 64, H, cfg),
                       key, trace, pairs, archive, failures)

    assert int(s_fu.gen) == gens
    assert hist.shape == (gens,)
    assert np.array_equal(np.concatenate(hist_one), np.asarray(hist))
    assert np.array_equal(np.asarray(s_one.pop.delays),
                          np.asarray(s_fu.pop.delays))
    assert np.array_equal(np.asarray(s_one.pop.faults),
                          np.asarray(s_fu.pop.faults))
    assert np.array_equal(np.asarray(s_one.best_fitness),
                          np.asarray(s_fu.best_fitness))
    assert np.array_equal(np.asarray(s_one.best_delays),
                          np.asarray(s_fu.best_delays))
    # the history's last entry is that generation's global best, and the
    # carried best is the running max of the history (monotone contract)
    h = np.asarray(hist)
    assert float(s_fu.best_fitness) == pytest.approx(h.max())


def test_fused_state_is_donated():
    mesh = make_mesh(8)
    cfg = GAConfig(max_delay=0.05)
    trace, pairs, archive, failures = inputs()
    fused = make_fused_island_step(mesh, cfg, ScoreWeights(),
                                   migrate_k=2, generations=2)
    state = init_island_state(jax.random.PRNGKey(0), 64, H, cfg)
    state, _ = fused(state, jax.random.PRNGKey(1), trace, pairs,
                     archive, failures)
    # the very first call re-shards the freshly-initialized population
    # onto the mesh (no aliasing possible across a layout change); from
    # the second chunk on — the campaign's steady state — the sharded
    # population buffer is donated and reused in place, so the caller
    # must keep only the returned state (models/search.py does)
    steady = state
    old_delays = steady.pop.delays
    new_state, _ = fused(steady, jax.random.PRNGKey(1), trace, pairs,
                         archive, failures)
    assert old_delays.is_deleted()
    assert not new_state.pop.delays.is_deleted()


# -- the migration ring -----------------------------------------------------


def test_migration_k_clamped_to_island_population():
    """migrate_k larger than the per-island population must clamp, not
    crash (regression: top_k(k=10) on an 8-row island)."""
    mesh = make_mesh(8)
    cfg = GAConfig(max_delay=0.05)
    step = make_fused_island_step(mesh, cfg, ScoreWeights(), migrate_k=10,
                                  generations=1)
    trace, pairs, archive, failures = inputs()
    state = init_island_state(jax.random.PRNGKey(0), 64, H, cfg)  # 8/island
    state, _ = step(state, jax.random.PRNGKey(1), trace, pairs, archive,
                    failures)
    assert np.isfinite(float(state.best_fitness))


# -- no mid-run recompiles --------------------------------------------------


def test_scorer_occupancy_mask_equals_slicing_without_retrace():
    rng = np.random.RandomState(0)
    trace, _ = toy_trace()
    pairs = jnp.asarray(te.sample_pairs(K, H, 0))
    archive = jnp.asarray(rng.rand(16, K).astype(np.float32))
    failures = jnp.asarray(rng.rand(8, K).astype(np.float32))
    delays = jnp.asarray(rng.rand(12, H).astype(np.float32) * 0.05)

    # on concrete arrays the scorer runs its compiled twin: that is
    # the cache an occupancy must not grow
    twin = schedule._score_population_multi_jit
    before = twin._cache_size()
    cached = None
    for occ_a, occ_f in ((1, 1), (5, 3), (16, 8)):
        fit_m, _ = score_one(
            delays, trace, pairs, archive, failures, ScoreWeights(),
            archive_n=jnp.asarray(occ_a, jnp.int32),
            failure_n=jnp.asarray(occ_f, jnp.int32))
        # the sliced reference under a jit of its own (inlined there,
        # it leaves the twin's cache alone)
        fit_s, _ = jax.jit(lambda a, f: score_one(
            delays, trace, pairs, a, f, ScoreWeights()))(
                archive[:occ_a], failures[:occ_f])
        # masking rows past the occupancy == slicing the buffer: each
        # candidate distance is the same math, but the sliced call's
        # differently-shaped matmul may accumulate in a different order,
        # so the comparison is tight-tolerance rather than bitwise
        # (the fused-vs-stepwise BIT-exactness pin compares equal-shape
        # programs and stays exact)
        assert np.allclose(np.asarray(fit_m), np.asarray(fit_s),
                           rtol=1e-5, atol=1e-6)
        size = twin._cache_size()
        if cached is None:
            cached = size
            assert size == before + 1  # exactly one new specialization
        # growing occupancy never traces a new program
        assert size == cached


def test_min_sq_distance_empty_occupancy_is_masked():
    rng = np.random.RandomState(1)
    feats = jnp.asarray(rng.rand(4, K).astype(np.float32))
    archive = jnp.asarray(rng.rand(8, K).astype(np.float32))
    full = min_sq_distance(feats, archive)
    masked = min_sq_distance(feats, archive,
                             valid_n=jnp.asarray(8, jnp.int32))
    assert np.array_equal(np.asarray(full), np.asarray(masked))
    empty = min_sq_distance(feats, archive,
                            valid_n=jnp.asarray(0, jnp.int32))
    assert float(np.min(np.asarray(empty))) > 1e30  # mask identity


def test_pair_kernel_refuses_empty_buffers():
    """The tile-index routing needs both segments non-empty; empty-ring
    callers hold fixed-capacity buffers and mask with occupancy."""
    from namazu_tpu.ops.pallas_score import (
        min_sq_distance_pair_pallas,
        min_sq_distance_pallas,
    )

    feats = jnp.zeros((4, K), jnp.float32)
    full = jnp.zeros((8, K), jnp.float32)
    empty = jnp.zeros((0, K), jnp.float32)
    with pytest.raises(ValueError, match="occupancy"):
        min_sq_distance_pair_pallas(feats, empty, full, interpret=True)
    with pytest.raises(ValueError, match="occupancy"):
        min_sq_distance_pair_pallas(feats, full, empty, interpret=True)
    with pytest.raises(ValueError, match="occupancy"):
        min_sq_distance_pallas(feats, empty, interpret=True)


def test_surrogate_train_compiles_once_across_occupancy():
    from namazu_tpu.models.surrogate import RewardSurrogate

    # the whole fit is one compiled call, shared by every surrogate of
    # the process; its shapes are quantised (minibatches per epoch to a
    # power of two, rows to as many batches), so an occupancy that
    # grows inside a quantum meets no new shape. K=9: a width no other
    # test trains at, so the first call here is the first lowering
    sur = RewardSurrogate(K=9, seed=0)
    rng = np.random.RandomState(0)
    lowered = sur._train._cache_size()
    for quantum in ((5, 9, 13, 16), (17, 25, 32), (33, 50, 64)):
        for n in quantum:
            feats = rng.rand(n, 9).astype(np.float32)
            labels = (rng.rand(n) > 0.5).astype(np.float32)
            sur.train(feats, labels, epochs=1, batch=16, seed=n)
        lowered += 1
        assert sur._train._cache_size() == lowered
    assert RewardSurrogate(K=9, seed=1)._train is sur._train
    # padded rows are weight-0: training on a padded batch equals
    # training on the same rows alone (the update is identical)
    a = RewardSurrogate(K=8, seed=0)
    b = RewardSurrogate(K=8, seed=0)
    feats = rng.rand(6, 8).astype(np.float32)
    labels = (rng.rand(6) > 0.5).astype(np.float32)
    a.train(feats, labels, epochs=1, batch=16, seed=1)
    b.train(feats, labels, epochs=1, batch=6, seed=1)
    assert np.allclose(a.predict(feats), b.predict(feats), atol=1e-6)


# -- device-resident end-to-end --------------------------------------------


def test_schedule_search_bit_exact_across_chunk_lengths_and_runs():
    a = ScheduleSearch(search_cfg(fused_chunk=1))
    b = ScheduleSearch(search_cfg(fused_chunk=16))
    refs = [enc_of(40, 1), enc_of(48, 2)]
    for s in (a, b):
        s.add_executed_trace(enc_of(40, 5))
        s.add_failure_trace(enc_of(44, 6))
    ra = a.run(refs, generations=17)
    rb = b.run(refs, generations=17)
    assert np.array_equal(ra.delays, rb.delays)
    assert np.array_equal(ra.faults, rb.faults)
    assert ra.fitness == rb.fitness
    # second round: the reference window slides, one archive row lands
    # incrementally, the resident store appends instead of re-staging
    for s in (a, b):
        s.add_executed_trace(enc_of(52, 7), reproduced=True)
    refs2 = refs + [enc_of(52, 8)]
    ra2 = a.run(refs2, generations=9)
    rb2 = b.run(refs2, generations=9)
    assert np.array_equal(ra2.delays, rb2.delays)
    assert ra2.fitness == rb2.fitness
    assert b._traces.rebuilds == 1  # one initial staging...
    assert b._traces.appends == 1  # ...then appends, never re-uploads


def test_resident_store_evicts_stale_rows_and_rebuilds_on_growth():
    from namazu_tpu.models.search import _ResidentTraces

    store = _ResidentTraces(capacity=4)
    e1, e2, e3 = enc_of(40, 1), enc_of(40, 2), enc_of(40, 3)
    L = e1.hint_ids.shape[0]
    store.view([e1, e2], L)
    assert (store.rebuilds, store.appends) == (1, 0)
    store.view([e1, e2, e3], L)
    assert (store.rebuilds, store.appends) == (1, 1)
    # same refs again: nothing new staged
    store.view([e1, e2, e3], L)
    assert (store.rebuilds, store.appends) == (1, 1)
    # ring full: stale rows are evicted for new ones, no rebuild
    e4, e5 = enc_of(40, 4), enc_of(40, 5)
    store.view([e3, e4, e5], L)
    assert store.rebuilds == 1
    assert len(store.slots) <= store.capacity
    # a longer trace forces the one legitimate re-staging
    long = enc_of(200, 6)  # auto-length pads past the resident L
    h, arr, m, fb = store.view([e5, long], long.hint_ids.shape[0])
    assert store.rebuilds == 2
    # the view matches a fresh host stack of the same references
    sh, _se, sa, sm, sf = te.stack_traces([e5, long])
    assert np.array_equal(np.asarray(h), sh)
    assert np.array_equal(np.asarray(arr), sa)
    assert np.array_equal(np.asarray(m), sm)
    assert np.array_equal(np.asarray(fb), sf)
    # ... and at the caller's length when the long trace has left the
    # window: the short rows' tails masked, nothing staged anew
    h, arr, m, fb = store.view([e5, e4], long.hint_ids.shape[0])
    assert store.rebuilds == 2 and store.L == long.hint_ids.shape[0]
    sh, _se, sa, sm, sf = te.stack_traces([e5, e4])
    for got, want in ((h, sh), (arr, sa), (m, sm), (fb, sf)):
        got = np.asarray(got)
        assert got.shape == (2, store.L)
        assert np.array_equal(got[:, :L], want) and not got[:, L:].any()


# -- checkpoint compatibility ----------------------------------------------


def test_checkpoint_round_trips_between_chunk_lengths(tmp_path):
    ck = str(tmp_path / "search.npz")
    pre = ScheduleSearch(search_cfg(fused_chunk=1))
    pre.add_executed_trace(enc_of(40, 5))
    pre.add_failure_trace(enc_of(44, 6))
    pre.run([enc_of(40, 1)], generations=5)
    pre.save(ck)

    # a checkpoint written a generation a dispatch -> chunks of 16
    fused = ScheduleSearch(search_cfg(fused_chunk=16))
    fused.load(ck)
    assert fused.generations_run == pre.generations_run
    r_f = fused.run([enc_of(40, 1)], generations=6)

    # the same continuation a generation a dispatch is bit-identical
    cont = ScheduleSearch(search_cfg(fused_chunk=1))
    cont.load(ck)
    r_s = cont.run([enc_of(40, 1)], generations=6)
    assert np.array_equal(r_f.delays, r_s.delays)
    assert r_f.fitness == r_s.fitness

    # ... and a checkpoint written in chunks loads back the other way
    ck2 = str(tmp_path / "search2.npz")
    fused.save(ck2)
    back = ScheduleSearch(search_cfg(fused_chunk=1))
    back.load(ck2)
    assert back.generations_run == fused.generations_run
    assert np.array_equal(np.asarray(back._state.pop.delays),
                          np.asarray(fused._state.pop.delays))


def test_checkpoint_population_mismatch_keeps_fresh_population(tmp_path):
    ck = str(tmp_path / "search.npz")
    big = ScheduleSearch(search_cfg(population=64))
    big.add_failure_trace(enc_of(44, 6))
    big.run([enc_of(40, 1)], generations=3)
    big.save(ck)

    small = ScheduleSearch(search_cfg(population=32))
    small.load(ck)  # must not raise
    # archives and best tables restored; population stays this config's
    assert small._failure_n == big._failure_n
    assert small._state.pop.delays.shape == (32, H)
    assert np.array_equal(np.asarray(small._state.best_delays),
                          np.asarray(big._state.best_delays))
    # and the loop still evolves (re-training the population)
    r = small.run([enc_of(40, 1)], generations=3)
    assert np.isfinite(r.fitness)


def test_failed_fused_dispatch_does_not_brick_the_search(monkeypatch):
    """Donation invalidates the input state at call time; a dispatch
    that then FAILS must leave the search usable (the long-lived
    sidecar contract): population restarts, best-so-far restores from
    the last completed round's host snapshot, and the next run()
    succeeds."""
    s = ScheduleSearch(search_cfg(fused_chunk=4))
    s.add_failure_trace(enc_of(44, 6))
    r1 = s.run([enc_of(40, 1)], generations=4)
    assert np.isfinite(r1.fitness)

    real = s._fused_step_for(4)

    def dying(state, *a, **kw):
        # consume (donate) the state like the real dispatch, then die
        real(state, *a, **kw)
        raise RuntimeError("injected device failure")

    monkeypatch.setattr(s, "_fused_step_for", lambda g: dying)
    with pytest.raises(RuntimeError):
        s.run([enc_of(40, 1)], generations=4)
    monkeypatch.undo()
    # the object recovered: best-so-far survived, and evolution resumes
    assert float(s._state.best_fitness) == pytest.approx(r1.fitness)
    r2 = s.run([enc_of(40, 1)], generations=4)
    assert np.isfinite(r2.fitness)
    assert r2.fitness >= r1.fitness  # monotone best, across the failure


def test_host_lane_gauge_never_regresses_best(tmp_path):
    from namazu_tpu.obs import metrics

    metrics.configure(True)
    metrics.reset()
    try:
        s = ScheduleSearch(search_cfg(fused_chunk=2))
        s.add_failure_trace(enc_of(44, 6))
        best_seen = -np.inf
        for seed in (1, 2, 3):
            r = s.run([enc_of(40, seed)], generations=4)
            best_seen = max(best_seen, r.fitness)
            v = metrics.registry().value("nmz_search_best_fitness",
                                         backend="ga")
            # "best fitness seen so far": later rounds' weaker chunks
            # (e.g. after archive growth lowers novelty) must not pull
            # the gauge below an earlier best
            assert v == pytest.approx(float(s._state.best_fitness),
                                      abs=1e-6)
            assert v >= best_seen - 1e-6
    finally:
        metrics.reset()
        metrics.configure(False)


# -- observability ----------------------------------------------------------


def test_fused_run_publishes_host_io_span_and_fused_source(tmp_path):
    from namazu_tpu.obs import analytics as an
    from namazu_tpu.obs import metrics
    from namazu_tpu.obs.recorder import recorder

    metrics.configure(True)
    metrics.reset()
    rec = recorder()
    rec.begin_run("fused-test")
    try:
        s = ScheduleSearch(search_cfg(fused_chunk=4))
        s.add_failure_trace(enc_of(44, 6))
        s.run([enc_of(40, 1)], generations=9)
        reg = metrics.registry()
        assert (reg.value("nmz_scorer_schedules_per_sec", source="fused")
                or 0) > 0
        assert (reg.value("nmz_search_host_gap_share", backend="ga")
                is not None)
        prom = reg.render_prometheus()
        assert 'nmz_search_phase_seconds_count{phase="host_io"}' in prom
        run = rec.current()
        gens = [g for g in run.snapshot()["generations"]
                if g.get("kind") == "generation"]
        assert gens and gens[-1].get("host_io_s") is not None
        # the host lane's drained per-generation best history lands on
        # the record: one point per generation (each generation's own
        # global best — the round's best is their running max)
        curve = gens[-1].get("fit_curve")
        assert curve is not None and len(curve) == 9
        assert all(np.isfinite(v) for v in curve)
        assert max(curve) == pytest.approx(gens[-1]["best_fitness"],
                                           abs=1e-5)
        conv = an.convergence_stats([run])
        assert "host_gap_share" in conv["backends"]["ga"]
        # the report surfaces the share as its own convergence line
        from namazu_tpu.obs.report import render_markdown

        payload = an.compute_payload(recorder_runs=[run], publish=False)
        md = render_markdown(payload)
        assert "host-gap share per generation" in md
    finally:
        rec.end_run()
        metrics.reset()
        metrics.configure(False)
