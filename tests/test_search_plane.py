"""TPU search-plane tests on the virtual 8-device CPU mesh.

Covers: trace encoding, schedule scoring semantics, GA improvement,
island-model sharding (shard_map + ppermute migration), search driver
checkpointing, and the surrogate model.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from namazu_tpu.models.ga import GAConfig, Population, ga_generation, init_population
from namazu_tpu.models.search import ScheduleSearch, SearchConfig
from namazu_tpu.models.surrogate import RewardSurrogate
from namazu_tpu.ops import trace_encoding as te
from namazu_tpu.ops.schedule import (
    ScoreWeights,
    TraceArrays,
    first_occurrence,
    min_sq_distance,
    release_times,
    schedule_features,
    trace_features,
)
from tests.scoring import score_one
from namazu_tpu.parallel.islands import (
    init_island_state,
    make_fused_island_step,
)
from namazu_tpu.parallel.mesh import make_mesh

H, L, K = 32, 64, 64


def toy_trace(n=48, n_hints=16):
    enc = te.encode_event_stream(
        [f"hint{i % n_hints}" for i in range(n)],
        arrivals=[i * 0.001 for i in range(n)],
        L=L, H=H,
    )
    return TraceArrays(
        jnp.asarray(enc.hint_ids), jnp.asarray(enc.arrival),
        jnp.asarray(enc.mask),
    ), enc


def test_encode_trace_shapes_and_determinism():
    enc1 = te.encode_event_stream(["a", "b", "a"], L=L, H=H)
    enc2 = te.encode_event_stream(["a", "b", "a"], L=L, H=H)
    assert enc1.length == 3
    assert (enc1.hint_ids == enc2.hint_ids).all()
    assert enc1.hint_ids[0] == enc1.hint_ids[2]  # same hint, same bucket
    assert enc1.mask[:3].all() and not enc1.mask[3:].any()


def test_sample_pairs_no_self_pairs():
    pairs = te.sample_pairs(K, H, seed=1)
    assert pairs.shape == (K, 2)
    assert (pairs[:, 0] != pairs[:, 1]).all()
    assert pairs.min() >= 0 and pairs.max() < H


def test_release_times_and_first_occurrence():
    trace, _ = toy_trace()
    delays = jnp.zeros(H)
    t = release_times(delays, trace)
    assert float(t[0]) == pytest.approx(0.0)
    masked = t[~np.asarray(trace.mask)]
    assert (np.asarray(masked) > 1e8).all()
    first = first_occurrence(t, trace, H)
    # buckets present in the trace have finite first-occurrence
    present = np.unique(np.asarray(trace.hint_ids)[np.asarray(trace.mask)])
    assert (np.asarray(first)[present] < 1e8).all()


def test_features_respond_to_delays():
    trace, _ = toy_trace()
    pairs = jnp.asarray(te.sample_pairs(K, H, 0))
    f0 = schedule_features(jnp.zeros(H), trace, pairs, tau=0.005)
    assert ((np.asarray(f0) >= 0) & (np.asarray(f0) <= 1)).all()
    # delaying one present bucket flips some precedence features
    present = int(np.asarray(trace.hint_ids)[0])
    f1 = schedule_features(
        jnp.zeros(H).at[present].set(0.05), trace, pairs, tau=0.005
    )
    assert not np.allclose(np.asarray(f0), np.asarray(f1))


def test_trace_features_match_zero_delay_schedule():
    trace, _ = toy_trace()
    pairs = jnp.asarray(te.sample_pairs(K, H, 0))
    tf = trace_features(trace, pairs, 0.005, H)
    sf = schedule_features(jnp.zeros(H), trace, pairs, 0.005)
    assert np.allclose(np.asarray(tf), np.asarray(sf))


def test_min_sq_distance_matches_naive():
    rng = np.random.RandomState(0)
    feats = rng.rand(8, K).astype(np.float32)
    archive = rng.rand(5, K).astype(np.float32)
    got = np.asarray(min_sq_distance(jnp.asarray(feats), jnp.asarray(archive)))
    want = np.min(
        ((feats[:, None, :] - archive[None, :, :]) ** 2).sum(-1), axis=1
    )
    assert np.allclose(got, want, rtol=1e-3, atol=1e-4)


def test_novelty_zero_for_archived_schedule():
    trace, _ = toy_trace()
    pairs = jnp.asarray(te.sample_pairs(K, H, 0))
    f = schedule_features(jnp.zeros(H), trace, pairs, 0.005)
    archive = jnp.stack([f])
    d = min_sq_distance(f[None], archive)
    assert float(d[0]) == pytest.approx(0.0, abs=1e-4)


def test_score_population_shapes():
    trace, _ = toy_trace()
    pairs = jnp.asarray(te.sample_pairs(K, H, 0))
    pop = init_population(jax.random.PRNGKey(0), 64, H, GAConfig())
    archive = jnp.full((16, K), 0.5)
    fails = jnp.full((4, K), 0.5)
    fit, feats = score_one(pop.delays, trace, pairs, archive, fails)
    assert fit.shape == (64,)
    assert feats.shape == (64, K)
    assert np.isfinite(np.asarray(fit)).all()


def test_ga_improves_fitness_toward_target():
    """GA should learn delays whose interleaving matches a target feature
    vector (pure bug-affinity objective)."""
    trace, _ = toy_trace()
    pairs = jnp.asarray(te.sample_pairs(K, H, 0))
    # target: the interleaving produced by a specific hidden schedule
    hidden = jax.random.uniform(jax.random.PRNGKey(7), (H,), minval=0.0,
                                maxval=0.05)
    target = schedule_features(hidden, trace, pairs, 0.005)[None]
    archive = jnp.full((1, K), 0.5)  # neutral novelty
    weights = ScoreWeights(novelty=0.0, bug=1.0, delay_cost=0.0)
    cfg = GAConfig(max_delay=0.05, mutation_sigma=0.005)

    pop = init_population(jax.random.PRNGKey(1), 256, H, cfg)
    key = jax.random.PRNGKey(2)
    first_best = None
    for g in range(30):
        fit, _ = score_one(pop.delays, trace, pairs, archive, target,
                           weights)
        if first_best is None:
            first_best = float(fit.max())
        key, k = jax.random.split(key)
        pop = ga_generation(k, pop, fit, cfg)
    fit, _ = score_one(pop.delays, trace, pairs, archive, target,
                       weights)
    final_best = float(fit.max())
    assert final_best > first_best + 1e-3
    assert final_best > -0.05  # close to the target interleaving


def test_island_step_on_8_device_mesh():
    assert len(jax.devices()) == 8
    trace, _ = toy_trace()
    pairs = jnp.asarray(te.sample_pairs(K, H, 0))
    archive = jnp.full((16, K), 0.5)
    fails = jnp.full((4, K), 0.5)
    mesh = make_mesh(8)
    cfg = GAConfig(max_delay=0.05)
    step = make_fused_island_step(mesh, cfg, ScoreWeights(), migrate_k=4,
                                  generations=1)
    state = init_island_state(jax.random.PRNGKey(0), 512, H, cfg)
    key = jax.random.PRNGKey(3)
    f0 = None
    for _ in range(8):
        state, _ = step(state, key, trace, pairs, archive, fails)
        if f0 is None:
            f0 = float(state.best_fitness)
    assert int(state.gen) == 8
    assert float(state.best_fitness) >= f0
    assert state.pop.delays.shape == (512, H)
    # population stays within genome bounds after migration + mutation
    d = np.asarray(state.pop.delays)
    assert (d >= 0).all() and (d <= cfg.max_delay + 1e-6).all()


def test_island_determinism_same_seed():
    trace, _ = toy_trace()
    pairs = jnp.asarray(te.sample_pairs(K, H, 0))
    archive = jnp.full((8, K), 0.5)
    fails = jnp.full((2, K), 0.5)
    mesh = make_mesh(8)
    cfg = GAConfig(max_delay=0.05)

    def run():
        step = make_fused_island_step(mesh, cfg, ScoreWeights(),
                                      migrate_k=2, generations=1)
        state = init_island_state(jax.random.PRNGKey(5), 256, H, cfg)
        for _ in range(4):
            state, _ = step(state, jax.random.PRNGKey(6), trace, pairs,
                            archive, fails)
        return np.asarray(state.best_delays)

    assert np.allclose(run(), run())


def test_search_driver_archives_and_checkpoint(tmp_path):
    cfg = SearchConfig(H=H, L=L, K=K, population=256,
                       ga=GAConfig(max_delay=0.05))
    search = ScheduleSearch(cfg)
    _, enc = toy_trace()
    search.add_executed_trace(enc)
    search.add_failure_trace(enc)
    best1 = search.run(enc, generations=5)
    assert np.isfinite(best1.fitness)
    assert search.generations_run == 5

    path = str(tmp_path / "ckpt.npz")
    search.save(path)
    search2 = ScheduleSearch(cfg)
    search2.load(path)
    assert search2.generations_run == 5
    assert np.allclose(search2.best().delays, best1.delays)
    # resumed search keeps improving monotonically
    best2 = search2.run(enc, generations=5)
    assert best2.fitness >= best1.fitness


def test_surrogate_learns_separable_labels():
    rng = np.random.RandomState(0)
    n = 512
    feats = rng.rand(n, K).astype(np.float32)
    labels = (feats[:, 0] > 0.5).astype(np.float32)
    sur = RewardSurrogate(K=K, hidden=32, lr=3e-3)
    sur.train(feats, labels, epochs=30, batch=128)
    preds = sur.predict(feats)
    acc = ((preds > 0.5) == (labels > 0.5)).mean()
    assert acc > 0.9
    order = np.argsort(-preds)[:10]  # the ten likeliest to reproduce
    assert (labels[order] == 1).mean() >= 0.9
