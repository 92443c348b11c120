"""The reply's re-rank as three compiled calls on the resident population
(``ScheduleSearch._surrogate_pick``: score, pick, train) against a plain
re-statement of the host path it replaced, kept here: the scorer op by
op, numpy's top-k, one jitted step and one ``float(loss)`` per minibatch.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from namazu_tpu import obs
from namazu_tpu.models.ga import GAConfig, Population
from namazu_tpu.models.search import ScheduleSearch, SearchConfig
from namazu_tpu.models.surrogate import RewardSurrogate, _programs, top_rows
from namazu_tpu.obs import spans
from namazu_tpu.ops import schedule
from namazu_tpu.ops import trace_encoding as te

from tests.test_request_spans import isolated_obs

P, H, K, L = 64, 16, 16, 64


def enc_of(seed, n=40, spacing=1e-3):
    rng = np.random.RandomState(seed)
    return te.encode_event_stream(
        [f"hint{rng.randint(10)}" for _ in range(n)],
        arrivals=list(np.cumsum(rng.rand(n) * spacing)), L=L, H=H)


def make_search(seed=3, n_devices=1, runs=10, K=K, H=H, archive=128, topk=8):
    """A search whose archive holds ``runs`` labelled runs, every third
    a failure (both classes past ``MIN_CLASS_EXAMPLES`` from 9 on)."""
    s = ScheduleSearch(SearchConfig(
        H=H, L=L, K=K, archive_size=archive, failure_size=8, population=P,
        migrate_k=2, seed=seed, ga=GAConfig(max_delay=0.05),
        surrogate_topk=topk), n_devices=n_devices)
    with s.embed_batch():
        for i in range(runs):
            add_run(s, i)
    return s


def add_run(s, i):
    e = te.encode_event_stream(
        [f"hint{(i * 7 + j * (1 + i % 3)) % 10}" for j in range(40)],
        arrivals=[j * 1e-3 * (1 + i % 3) for j in range(40)], L=L,
        H=s.cfg.H)
    s.add_executed_trace(e, reproduced=(i % 3 == 0))
    if i % 3 == 0:
        s.add_failure_trace(e)


# -- the host path, re-stated ----------------------------------------------


def host_train(K_, feats, labels, epochs, seed, init_seed, batch=256):
    """The minibatch loop as it was: the same permutation per epoch,
    batches of ``batch`` padded with zero-weight rows, one jitted step
    and one blocking ``float(loss)`` each. Returns (params, last loss)."""
    model, tx = _programs(128, 1e-3)[:2]
    params = model.init(jax.random.PRNGKey(init_seed),
                        jnp.zeros((1, K_), jnp.float32))
    opt_state = tx.init(params)

    def loss_fn(params, f, lb, w):
        per = optax.sigmoid_binary_cross_entropy(model.apply(params, f), lb)
        return (per * w).sum() / jnp.maximum(w.sum(), 1.0)

    @jax.jit
    def step(params, opt_state, f, lb, w):
        loss, grads = jax.value_and_grad(loss_fn)(params, f, lb, w)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    n, rng, loss = len(feats), np.random.RandomState(seed), 0.0
    for _ in range(epochs):
        order = rng.permutation(n)
        for i in range(0, n, batch):
            idx = order[i:i + batch]
            f = np.zeros((batch, K_), np.float32)
            f[:len(idx)] = feats[idx]
            lb = np.zeros((batch,), np.float32)
            lb[:len(idx)] = labels[idx]
            w = np.zeros((batch,), np.float32)
            w[:len(idx)] = 1.0
            params, opt_state, l = step(params, opt_state, jnp.asarray(f),
                                        jnp.asarray(lb), jnp.asarray(w))
            loss = float(l)
    return params, loss


def host_pick(s, encs, params=None, remote=None):
    """``_surrogate_pick`` as it was, on the population fetched to the
    host: (table, faults, fitness) or None."""
    _encs, trace, pairs, archive, failures = s._device_inputs_fused(encs)
    delays_np, faults_np = s._fetch_population()
    with jax.disable_jit():  # op by op, on the default device
        fitness, feats = schedule.score_population_multi(
            jnp.asarray(delays_np), jax.device_get(trace),
            jax.device_get(pairs), jax.device_get(archive),
            jax.device_get(failures), s.cfg.weights,
            novelty_scale=jnp.asarray(s.novelty_scale(), jnp.float32))
    fitness, feats = np.asarray(fitness), np.asarray(feats)
    k = min(s.cfg.surrogate_topk, s.population)
    top = np.argsort(-fitness, kind="stable")[:k]
    cand = feats[top].mean(axis=1)
    gains = frags = None
    if s.guidance is not None:
        gains, frags = s._candidate_guidance(delays_np[top], encs)
    full = cand if frags is None else np.hstack([cand, frags])
    base = None
    if params is not None:
        model = _programs(128, 1e-3)[0]
        base = np.asarray(jax.nn.sigmoid(model.apply(params, full)))
    elif remote is not None:
        base = remote(full)
    if base is None:
        if gains is None:
            return None
        f = fitness[top]
        span = float(f.max() - f.min())
        base = (f - f.min()) / span if span > 0 else np.zeros_like(f)
    score = base if gains is None else base + s.cfg.guidance_bonus * gains
    winner = int(top[int(np.argmax(score))])
    return delays_np[winner], faults_np[winner], float(fitness[winner])


def assert_same_pick(best, want):
    np.testing.assert_array_equal(best.delays, want[0])
    np.testing.assert_array_equal(best.faults, want[1])
    assert best.fitness == pytest.approx(want[2], abs=1e-6)
    assert isinstance(best.fitness, float)


def counted(path):
    return obs.metrics.registry().value(spans.RERANK_REQUESTS, path=path)


# -- (i) the compiled phase against the host path ---------------------------


@pytest.mark.parametrize("seed", [3, 11])
@pytest.mark.parametrize("T", [1, 2])
def test_compiled_rerank_picks_what_the_host_path_picked(T, seed):
    s = make_search(seed=seed)
    encs = [enc_of(100 + seed + t) for t in range(T)]
    with isolated_obs():
        best = s.run(encs, generations=3)
        assert counted("compiled") == 1 and counted("host") is None
    feats, labels = s.labeled_archive()
    params, loss = host_train(K, feats, labels, 4,
                              seed=s.cfg.seed + s.generations_run,
                              init_seed=s.cfg.seed)
    got = s._surrogate.state.params
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6,
                                   rtol=0)
    assert int(s._surrogate.state.opt_state[0].count) == 4
    assert_same_pick(best, host_pick(s, encs, params=params))


def test_train_returns_the_last_real_steps_loss_on_the_device():
    rng = np.random.RandomState(0)
    feats = rng.rand(40, 8).astype(np.float32)
    labels = (rng.rand(40) > 0.5).astype(np.float32)
    sur = RewardSurrogate(K=8, seed=5)
    loss = sur.train(feats, labels, epochs=2, batch=16, seed=9)
    assert isinstance(loss, jax.Array) and loss.shape == ()
    # 3 minibatches an epoch padded to 4: the last step is padding
    _, want = host_train(8, feats, labels, 2, seed=9, init_seed=5, batch=16)
    assert float(loss) == pytest.approx(want, abs=1e-6)
    assert int(sur.state.opt_state[0].count) == 6


def test_padding_to_a_capacity_fits_what_the_bare_rows_fit():
    """``capacity`` only quantises the shapes (8 steps an epoch for 100
    rows of 16, 5 of them padding): the state ends bit for bit where the
    3 real steps an epoch leave it."""
    rng = np.random.RandomState(2)
    feats = rng.rand(40, 8).astype(np.float32)
    labels = (rng.rand(40) > 0.5).astype(np.float32)
    bare, padded = RewardSurrogate(K=8, seed=5), RewardSurrogate(K=8, seed=5)
    bare.train(feats, labels, epochs=3, batch=16, seed=4)
    padded.train(feats, labels, epochs=3, batch=16, seed=4, capacity=100)
    for x, y in zip(jax.tree.leaves(bare.state),
                    jax.tree.leaves(padded.state)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert int(padded.state.opt_state[0].count) == 9


# -- (ii) a padded step ------------------------------------------------------


def test_a_padded_step_is_an_exact_no_op_and_a_zero_gradient_is_not():
    rng = np.random.RandomState(1)
    n, batch = 33, 16  # 3 minibatches an epoch, padded to 4
    feats = rng.rand(n, 8).astype(np.float32)
    labels = (rng.rand(n) > 0.5).astype(np.float32)
    padded = RewardSurrogate(K=8, seed=0)
    padded.train(feats, labels, epochs=3, batch=batch, seed=2)
    # the same steps without the padding ones, through the same program
    exact = RewardSurrogate(K=8, seed=0)
    order = np.random.RandomState(2)
    idx = np.full((3, 64), -1, np.int32)
    for e in range(3):
        idx[e, :n] = order.permutation(n)
    idx = idx.reshape(12, batch)
    real = idx[(idx >= 0).any(axis=1)]
    assert len(real) == 9
    f = np.zeros((64, 8), np.float32)
    f[:n] = feats
    lb = np.zeros((64,), np.float32)
    lb[:n] = labels
    exact.state, _ = exact._train(exact.state, f, lb, real)
    for a, b in zip(jax.tree.leaves(padded.state),
                    jax.tree.leaves(exact.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(padded.state.opt_state[0].count) == 9
    # the shortcut that is wrong: a zero-weight step WITHOUT the mask.
    # Its gradient is zero, and Adam still moves every parameter (its
    # first moment has not decayed) and counts the step
    st = padded.state
    zero = jax.tree.map(jnp.zeros_like, st.params)
    updates, opt_state = padded.tx.update(zero, st.opt_state, st.params)
    moved = optax.apply_updates(st.params, updates)
    assert any(not np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(moved),
                               jax.tree.leaves(st.params)))
    assert int(opt_state[0].count) == int(st.opt_state[0].count) + 1


# -- (iii) one compile per shape, none as the history grows ------------------


def rerank_programs():
    _model, _tx, train, _predict, pick = _programs(128, 1e-3)
    return (schedule._score_population_multi_jit, train, pick)


def test_two_searches_of_one_shape_share_the_three_programs():
    before = [p._cache_size() for p in rerank_programs()]
    # a feature width no other test file uses: these are first lowerings
    a = make_search(seed=1, K=24)
    a.run([enc_of(1)], generations=2)
    first = [p._cache_size() for p in rerank_programs()]
    assert [n - m for n, m in zip(first, before)] == [1, 1, 1]
    b = make_search(seed=2, K=24)
    b.run([enc_of(2)], generations=2)
    assert [p._cache_size() for p in rerank_programs()] == first
    assert a._surrogate._train is b._surrogate._train


@pytest.mark.parametrize("depths", [(10, 26), (66, 78), (250, 262)],
                         ids=["live", "live-d64", "across_a_batch"])
def test_a_growing_history_lowers_nothing_after_the_first_request(depths):
    """The live cells: the history grows inside the window, and no
    input of the three programs has a shape that follows it — not
    across a minibatch's 256 rows either, which every cell's archive
    crosses as requests re-ingest their history. (The first request
    that finds a NEW run lowers the rings' scatter, as before: the
    cells' two warm-up requests.)"""
    lo, hi = depths
    s = make_search(seed=4, runs=lo - 1, archive=512)
    refs = [enc_of(7)]
    with isolated_obs():
        obs.ensure_compile_listener()
        s.run(refs, generations=2)
        add_run(s, lo - 1)
        s.run(refs, generations=2)
        lowered = obs.metrics.registry().value(spans.COMPILES)
        assert lowered  # the listener is on and saw the warm-up
        for i in range(lo, hi):
            add_run(s, i)
            s.run(refs, generations=2)
        assert obs.metrics.registry().value(spans.COMPILES) == lowered
        assert counted("compiled") == hi - lo + 2


# -- (iv) the name a launcher wraps ------------------------------------------


def test_the_scorers_name_is_called_once_per_rerank_outside_jit(monkeypatch):
    s = make_search(seed=5)
    seen = []
    orig = schedule.score_population_multi

    def wrapper(*a, **kw):
        out = orig(*a, **kw)
        seen.append(out[0])
        return out

    monkeypatch.setattr(schedule, "score_population_multi", wrapper)
    encs = [enc_of(8)]
    s.run(encs, generations=2)
    assert len(seen) == 1
    fitness = seen[0]
    assert isinstance(fitness, jax.Array)
    assert not isinstance(fitness, jax.core.Tracer)
    assert fitness.shape == (P,)
    # in the population's row order, as the launcher dumps it
    _encs, trace, pairs, archive, failures = s._device_inputs_fused(encs)
    with jax.disable_jit():
        want, _ = orig(
            jnp.asarray(s._fetch_population()[0]), trace, pairs, archive,
            failures, s.cfg.weights,
            novelty_scale=jnp.asarray(s.novelty_scale(), jnp.float32))
    # (compiled against op by op: the distance's expansion cancels in
    # another order, a few 1e-6 on a fitness of -0.18)
    np.testing.assert_allclose(np.asarray(fitness), np.asarray(want),
                               atol=1e-5, rtol=0)
    assert not np.allclose(np.asarray(want), np.asarray(want)[::-1],
                           atol=1e-5)  # another row order would show


def test_under_a_trace_the_scorer_runs_inline():
    """The fused island step calls the same name with tracers: no
    nested compiled call, the body as it was."""
    s = make_search(seed=5)
    _encs, trace, pairs, archive, failures = s._device_inputs_fused(
        [enc_of(8)])
    text = jax.make_jaxpr(lambda d: schedule.score_population_multi(
        d, trace, pairs, archive, failures, s.cfg.weights))(
            s._state.pop.delays)
    assert "name=score_population_multi" not in str(text)


# -- (v) a mesh of several devices -------------------------------------------


def test_on_a_four_device_mesh_the_pick_is_the_one_device_pick():
    encs = [enc_of(9), enc_of(10)]
    four = make_search(seed=6, n_devices=4)
    best = four.run(encs, generations=3)
    pop = four._state.pop.delays
    # the state keeps its shards: never replaced by a replicated copy
    assert sorted(int(x.data.shape[0]) for x in pop.addressable_shards) \
        == [P // 4] * 4
    assert len(pop.sharding.device_set) == 4
    one = make_search(seed=6, n_devices=1)
    delays, faults = four._fetch_population()
    one._state = one._state._replace(pop=Population(
        delays=jnp.asarray(delays), faults=jnp.asarray(faults)))
    one.generations_run = four.generations_run
    _encs, trace, pairs, archive, failures = one._device_inputs_fused(encs)
    want = one._surrogate_pick(
        trace, pairs, archive, failures,
        jnp.asarray(one.novelty_scale(), jnp.float32), encs)
    assert_same_pick(best, want)
    assert_same_pick(best, host_pick(four, encs,
                                     params=one._surrogate.state.params))


def test_a_sharded_population_is_reranked_where_it_lives(monkeypatch):
    """On a mesh the re-rank is the same three compiled calls, on one
    chip's copy of a device-to-device gather (the Mosaic pair kernel
    cannot be partitioned): nothing of the population is fetched, the
    launcher's wrapper sees a [P] vector on ONE device in row order."""
    seen = []
    real = schedule.score_population_multi

    def wrapper(delays, *a, **kw):
        out = real(delays, *a, **kw)
        if not isinstance(delays, jax.core.Tracer):  # not the fused step
            seen.append((delays, out[0]))
        return out

    monkeypatch.setattr(schedule, "score_population_multi", wrapper)
    four = make_search(seed=6, n_devices=4)
    monkeypatch.setattr(four, "_fetch_population", None)  # never called
    with isolated_obs():
        four.run([enc_of(9)], generations=2)
        assert (counted("compiled"), counted("host")) == (1, None)
    (delays, fitness), = seen
    assert fitness.shape == (P,) and len(fitness.sharding.device_set) == 1
    np.testing.assert_array_equal(np.asarray(delays),
                                  np.asarray(four._state.pop.delays))
    assert len(four._state.pop.delays.sharding.device_set) == 4


# -- (vi) the paths that finish on the host ----------------------------------


def test_a_remote_surrogate_finishes_on_the_host_and_picks_the_same():
    s = make_search(seed=7, runs=4)  # one failure: too thin to train
    calls = []

    def remote(feats):
        calls.append(feats.shape)
        return feats @ np.linspace(-1.0, 1.0, feats.shape[1])

    s.remote_surrogate = remote
    encs = [enc_of(12)]
    with isolated_obs():
        best = s.run(encs, generations=3)
        assert counted("host") == 1 and counted("compiled") is None
    assert s._surrogate is None and calls == [(8, K)]
    assert_same_pick(best, host_pick(s, encs, remote=remote))
    # an outage: the rows went to the host, nothing to rank them with
    s.remote_surrogate = lambda feats: None
    with isolated_obs():
        assert s._surrogate_pick(*s._device_inputs_fused(encs)[1:], None,
                                 encs) is None
        assert counted("host") == 1


@pytest.mark.parametrize("runs", [3, 9], ids=["fitness_base", "surrogate"])
def test_a_guidance_map_finishes_on_the_host_and_picks_the_same(runs):
    from namazu_tpu.guidance import GUIDANCE_DIMS
    from namazu_tpu.models.ingest import IngestParams, ingest_history
    from tests.test_guidance import FakeStorage, make_trace

    s = ScheduleSearch(SearchConfig(
        H=32, K=K, population=16, archive_size=16, failure_size=8,
        surrogate_topk=4), n_devices=1)
    s.enable_guidance()
    st = FakeStorage([(make_trace(i, 0.05 * (i % 3 == 0)), i % 3 != 0)
                      for i in range(runs)])
    refs = ingest_history(s, st, IngestParams(H=32, guidance=True))
    with isolated_obs():
        best = s.run(refs, generations=2)
        assert counted("host") == 1 and counted("compiled") is None
    params = None
    if runs >= 9:
        feats, labels = s.labeled_archive()
        assert feats.shape[1] == K + GUIDANCE_DIMS
        params = s._surrogate.state.params
    else:
        assert s._surrogate is None
    assert_same_pick(best, host_pick(s, refs, params=params))


def test_nothing_to_rerank_with_counts_nothing():
    s = make_search(seed=8, runs=4)
    with isolated_obs():
        s.run([enc_of(13)], generations=2)
        assert counted("compiled") is None and counted("host") is None


def test_top_rows_are_the_stable_top_k_best_first():
    fitness = jnp.asarray([0.5, 2.0, 2.0, -1.0, 3.0])
    feats = jnp.arange(5 * 2 * 3, dtype=jnp.float32).reshape(5, 2, 3)
    delays = jnp.arange(10, dtype=jnp.float32).reshape(5, 2)
    cand, d, f, fit = top_rows(fitness, feats, delays, -delays, 3)
    np.testing.assert_array_equal(np.asarray(fit), [3.0, 2.0, 2.0])
    np.testing.assert_array_equal(np.asarray(d), np.asarray(delays)[[4, 1, 2]])
    np.testing.assert_array_equal(np.asarray(f), -np.asarray(delays)[[4, 1, 2]])
    np.testing.assert_allclose(np.asarray(cand),
                               np.asarray(feats)[[4, 1, 2]].mean(axis=1))
