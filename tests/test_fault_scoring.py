"""Fault-aware counterfactual scoring (BASELINE config 4).

The fault half of a genome must carry fitness signal: a dropped event
vanishes from the counterfactual interleaving before first-occurrence, so
a bug that *requires* a drop (reference semantics: PacketFaultAction,
action_fault_packet.go:29-46; probabilistic injection randompolicy.go:
300-317) is findable by the search, and the found table replays to the
same drops through policy/tpu.py's deterministic per-bucket coin.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from namazu_tpu.models.ga import GAConfig, ga_generation, init_population
from namazu_tpu.ops import trace_encoding as te
from namazu_tpu.ops.schedule import (
    ScoreWeights,
    TraceArrays,
    apply_faults,
    drop_mask,
    schedule_features,
    score_population_multi,
    trace_features,
)
from tests.scoring import stack

# the scorer takes a [T, L] stack: each property below is held at T 1
# and at T 3, where the fault penalty is a mean over the traces
STACKS = pytest.mark.parametrize("T", [1, 3])

H, L, K = 32, 64, 64


def stream(n=48, n_hints=16, skip_hint=None):
    """Periodic hint stream; optionally omit every event of one hint (the
    interleaving a real drop of that packet class would produce)."""
    hints, arrivals = [], []
    t = 0.0
    for i in range(n):
        h = f"hint{i % n_hints}"
        t += 0.001
        if skip_hint is not None and h == skip_hint:
            continue
        hints.append(h)
        arrivals.append(t)
    return te.encode_event_stream(hints, arrivals=arrivals, L=L, H=H)


def arrays(enc):
    return TraceArrays(
        jnp.asarray(enc.hint_ids), jnp.asarray(enc.arrival),
        jnp.asarray(enc.mask),
    )


def test_fault_coin_deterministic_and_matches_policy():
    coin = te.fault_coin(seed=3, H=H)
    assert coin.shape == (H,)
    assert ((coin >= 0) & (coin < 1)).all()
    assert np.allclose(coin, te.fault_coin(seed=3, H=H))

    # the policy's replay decision is the scorer's drop decision
    from namazu_tpu.policy.tpu import TPUSearchPolicy

    pol = TPUSearchPolicy()
    pol.seed, pol.H, pol.max_fault = 3, H, 1.0
    faults = np.zeros(H, np.float32)
    bucket = te.hint_bucket("hint3", H)
    faults[bucket] = min(1.0, coin[bucket] + 0.05)  # just above the coin
    pol.install_table(np.zeros(H), faults=faults)
    assert pol._fault_for("hint3") == (coin[bucket] < faults[bucket])
    assert pol._fault_for("hint3")  # and it does fire


def test_drop_mask_removes_bucket_events():
    enc = stream()
    trace = arrays(enc)
    coin = jnp.asarray(te.fault_coin(0, H))
    bucket = te.hint_bucket("hint3", H)
    faults = jnp.zeros(H).at[bucket].set(float(coin[bucket]) + 1e-3)
    dropped = np.asarray(drop_mask(faults, coin, trace))
    hid = np.asarray(trace.hint_ids)
    msk = np.asarray(trace.mask)
    assert dropped[msk & (hid == bucket)].all()
    assert not dropped[msk & (hid != bucket)].any()
    # masked-out padding never counts as dropped
    assert not dropped[~msk].any()

    eff = apply_faults(trace, faults, coin)
    assert not (np.asarray(eff.mask) & (hid == bucket)).any()


def test_dropping_bucket_matches_skip_trace_features():
    """Counterfactually dropping every 'hint3' event must land on exactly
    the features of a trace recorded *without* those events — the scorer's
    drop model agrees with what a real packet drop does to the record."""
    full, skipped = stream(), stream(skip_hint="hint3")
    pairs = jnp.asarray(te.sample_pairs(K, H, 0))
    coin = jnp.asarray(te.fault_coin(0, H))
    bucket = te.hint_bucket("hint3", H)
    faults = jnp.zeros(H).at[bucket].set(float(coin[bucket]) + 1e-3)

    f_drop = schedule_features(jnp.zeros(H), arrays(full), pairs, 0.005,
                               faults=faults, coin=coin)
    f_skip = trace_features(arrays(skipped), pairs, 0.005, H)
    # arrival times differ slightly (skip compresses later arrivals is NOT
    # true here: arrivals are preserved), so features match exactly
    assert np.allclose(np.asarray(f_drop), np.asarray(f_skip), atol=1e-5)
    # and differ from the no-fault features
    f_plain = schedule_features(jnp.zeros(H), arrays(full), pairs, 0.005)
    assert not np.allclose(np.asarray(f_drop), np.asarray(f_plain))


@STACKS
def test_fault_cost_penalizes_drop_everything(T):
    traces = stack(*[arrays(stream(n=48 - 8 * t)) for t in range(T)])
    pairs = jnp.asarray(te.sample_pairs(K, H, 0))
    coin = jnp.asarray(te.fault_coin(0, H))
    archive = jnp.full((4, K), 0.5)
    fails = jnp.full((2, K), 0.5)
    weights = ScoreWeights(novelty=0.0, bug=0.0, delay_cost=0.0,
                           fault_cost=1.0)
    delays = jnp.zeros((2, H))
    faults = jnp.stack([jnp.zeros(H), jnp.ones(H)])  # none vs all dropped
    fit, _ = score_population_multi(delays, traces, pairs, archive, fails,
                                    weights, faults=faults, coin=coin)
    assert float(fit[0]) == pytest.approx(0.0, abs=1e-6)
    assert float(fit[1]) == pytest.approx(-1.0, abs=1e-5)  # all live dropped


def test_fault_cost_is_the_mean_of_the_traces_dropped_shares():
    """One bucket dropped, three traces that hold 3 of 48, 3 of 40 and
    none of its events: the penalty is the mean of the three shares,
    not the share of the events pooled."""
    encs = [stream(), stream(n=40), stream(skip_hint="hint3")]
    pairs = jnp.asarray(te.sample_pairs(K, H, 0))
    coin = jnp.asarray(te.fault_coin(0, H))
    bucket = te.hint_bucket("hint3", H)
    faults = jnp.zeros((1, H)).at[0, bucket].set(float(coin[bucket]) + 1e-3)
    shares = [float(((e.hint_ids == bucket) & e.mask).sum() / e.mask.sum())
              for e in encs]
    assert shares[0] > 0 and shares[2] == 0 and shares[0] != shares[1]
    weights = ScoreWeights(novelty=0.0, bug=0.0, delay_cost=0.0,
                           fault_cost=1.0)
    fit, _ = score_population_multi(
        jnp.zeros((1, H)), stack(*map(arrays, encs)), pairs,
        jnp.full((4, K), 0.5), jnp.full((2, K), 0.5), weights,
        faults=faults, coin=coin)
    assert float(fit[0]) == pytest.approx(-np.mean(shares), abs=1e-6)


@STACKS
def test_no_fault_args_is_backward_compatible(T):
    traces = stack(*[arrays(stream(n=48 - 8 * t)) for t in range(T)])
    pairs = jnp.asarray(te.sample_pairs(K, H, 0))
    archive = jnp.full((4, K), 0.5)
    fails = jnp.full((2, K), 0.5)
    pop = init_population(jax.random.PRNGKey(0), 16, H, GAConfig())
    f1, _ = score_population_multi(pop.delays, traces, pairs, archive,
                                   fails)
    coin = jnp.ones((H,))  # coin >= 1: fault half is a no-op
    f2, _ = score_population_multi(pop.delays, traces, pairs, archive,
                                   fails, faults=pop.faults, coin=coin)
    assert np.allclose(np.asarray(f1), np.asarray(f2), atol=1e-6)


@STACKS
def test_ga_learns_drop_requiring_bug(T):
    """Planted structure: the failure signature is the interleaving with
    every 'hint3' event missing. Only a genome that actually drops that
    bucket can match it; the GA must select the fault dimension."""
    full, skipped = stream(), stream(skip_hint="hint3")
    trace = stack(*[arrays(full)] * T)
    pairs = jnp.asarray(te.sample_pairs(K, H, 0))
    coin = jnp.asarray(te.fault_coin(0, H))
    bucket = te.hint_bucket("hint3", H)
    target = trace_features(arrays(skipped), pairs, 0.005, H)[None]
    archive = jnp.full((1, K), 0.5)
    # pure bug-affinity objective with a small drop cost so indiscriminate
    # dropping is not free
    weights = ScoreWeights(novelty=0.0, bug=1.0, delay_cost=0.0,
                           fault_cost=0.05)
    cfg = GAConfig(max_delay=0.02, max_fault=1.0, mutation_sigma=0.01)

    pop = init_population(jax.random.PRNGKey(1), 256, H, cfg)
    key = jax.random.PRNGKey(2)
    for _ in range(25):
        fit, _ = score_population_multi(pop.delays, trace, pairs, archive,
                                        target, weights, faults=pop.faults,
                                        coin=coin)
        key, k = jax.random.split(key)
        pop = ga_generation(k, pop, fit, cfg)
    fit, _ = score_population_multi(pop.delays, trace, pairs, archive,
                                    target, weights, faults=pop.faults,
                                    coin=coin)
    best = int(jnp.argmax(fit))
    best_faults = np.asarray(pop.faults[best])
    coin_np = np.asarray(coin)
    # the winning genome actually drops the decisive bucket...
    assert best_faults[bucket] > coin_np[bucket]
    # ...and its counterfactual matches the failure signature closely
    assert float(fit[best]) > -0.02

    # ablation: with the fault half disabled the same objective is
    # unreachable (the bug REQUIRES the drop)
    nofault, _ = score_population_multi(pop.delays, trace, pairs, archive,
                                        target, weights)
    assert float(fit[best]) > float(nofault.max()) + 0.005


def test_score_population_multi_with_faults():
    full, skipped = stream(), stream(skip_hint="hint3")
    h, _, a, m, _fb = te.stack_traces([full, full])
    traces = TraceArrays(jnp.asarray(h), jnp.asarray(a), jnp.asarray(m))
    pairs = jnp.asarray(te.sample_pairs(K, H, 0))
    coin = jnp.asarray(te.fault_coin(0, H))
    bucket = te.hint_bucket("hint3", H)
    target = trace_features(arrays(skipped), pairs, 0.005, H)[None]
    archive = jnp.full((1, K), 0.5)
    weights = ScoreWeights(novelty=0.0, bug=1.0, delay_cost=0.0,
                           fault_cost=0.0)
    delays = jnp.zeros((2, H))
    faults = jnp.stack([
        jnp.zeros(H),
        jnp.zeros(H).at[bucket].set(float(coin[bucket]) + 1e-3),
    ])
    fit, feats = score_population_multi(delays, traces, pairs, archive,
                                        target, weights, faults=faults,
                                        coin=coin)
    assert feats.shape == (2, 2, K)
    # the dropping genome matches the failure signature on every trace
    assert float(fit[1]) > float(fit[0]) + 0.005
    assert float(fit[1]) == pytest.approx(0.0, abs=1e-4)


def test_policy_replays_fault_table():
    """The installed fault table turns into default_fault_action at
    release time — the control-plane half of config 4."""
    from namazu_tpu.policy.tpu import TPUSearchPolicy
    from namazu_tpu.signal.event import PacketEvent
    from namazu_tpu.signal.action import PacketFaultAction

    pol = TPUSearchPolicy()
    pol.seed, pol.H, pol.max_fault = 0, H, 1.0
    ev = PacketEvent.create(entity_id="zk1", src_entity="zk1",
                            dst_entity="zk2", payload=b"hi")
    bucket = te.hint_bucket(ev.replay_hint(), H)
    coin = te.fault_coin(0, H)
    faults = np.zeros(H, np.float32)
    faults[bucket] = min(1.0, float(coin[bucket]) + 0.05)
    pol.install_table(np.zeros(H), faults=faults)
    action = pol._action_for(ev)
    assert isinstance(action, PacketFaultAction)
    # below the coin: the event is released normally
    faults[bucket] = max(0.0, float(coin[bucket]) - 0.05)
    pol.install_table(np.zeros(H), faults=faults)
    action = pol._action_for(ev)
    assert not isinstance(action, PacketFaultAction)


def test_drop_mask_respects_faultable_flag():
    """A hint-bucket collision between a faultable and a non-faultable
    event must not produce scored drops the control plane never
    realizes: only events whose class supports a fault action drop
    (advisor finding, round 2)."""
    hint_ids = jnp.zeros((4,), jnp.int32)  # all collide in bucket 0
    trace = TraceArrays(
        hint_ids,
        jnp.arange(4, dtype=jnp.float32) * 1e-3,
        jnp.ones((4,), bool),
        faultable=jnp.asarray([True, False, True, False]),
    )
    faults = jnp.ones((H,), jnp.float32)  # drop everything possible
    coin = jnp.zeros((H,), jnp.float32)  # coin < faults everywhere
    d = np.asarray(drop_mask(faults, coin, trace))
    assert d.tolist() == [True, False, True, False]
    eff = apply_faults(trace, faults, coin)
    assert np.asarray(eff.mask).tolist() == [False, True, False, True]


def test_encode_trace_marks_faultable_classes():
    from namazu_tpu.signal.action import EventAcceptanceAction, NopAction
    from namazu_tpu.signal.event import (
        LogEvent,
        PacketEvent,
        FilesystemEvent,
        FilesystemOp,
    )
    from namazu_tpu.utils.trace import SingleTrace

    pkt = PacketEvent.create(entity_id="a", src_entity="a",
                             dst_entity="b", payload=b"x")
    fs = FilesystemEvent.create(entity_id="a", op=FilesystemOp.PRE_WRITE,
                                path="/tmp/f")
    log = LogEvent.create(entity_id="a", line="observed")
    trace = SingleTrace([
        EventAcceptanceAction.for_event(pkt),
        EventAcceptanceAction.for_event(fs),
        NopAction.for_event(log),
    ])
    for i, a in enumerate(trace):
        a.mark_triggered(100.0 + i)
    enc = te.encode_trace(trace, H=H)
    assert enc.faultable[:3].tolist() == [True, True, False]
    assert te.class_supports_fault("PacketEvent")
    assert te.class_supports_fault("FilesystemEvent")
    assert not te.class_supports_fault("LogEvent")
    assert not te.class_supports_fault("ProcSetEvent")
    assert te.class_supports_fault("")  # unrecorded: conservative
    assert te.class_supports_fault("NoSuchClass")


def test_blockwise_fault_drop_respects_faultable():
    """The long-trace scan path applies the same faultable gate as the
    dense path."""
    from namazu_tpu.ops.schedule import first_occurrence_blockwise

    n = 2048  # > LONG_TRACE_THRESHOLD
    hint_ids = np.zeros((n,), np.int32)
    arrival = np.arange(n, dtype=np.float32) * 1e-3
    mask = np.ones((n,), bool)
    faultable = np.zeros((n,), bool)
    faultable[0] = True  # only the first event may drop
    delays = jnp.zeros((H,), jnp.float32)
    faults = jnp.ones((H,), jnp.float32)
    coin = jnp.zeros((H,), jnp.float32)
    first, ndrop = first_occurrence_blockwise(
        delays, jnp.asarray(hint_ids), jnp.asarray(arrival),
        jnp.asarray(mask), faults=faults, coin=coin,
        faultable=jnp.asarray(faultable),
    )
    assert int(ndrop) == 1
    # bucket 0's first occurrence is now the SECOND event's arrival
    assert np.isclose(float(first[0]), arrival[1])
