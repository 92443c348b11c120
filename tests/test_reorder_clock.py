"""Realized-vs-scored order with a scripted clock — ZERO real sleeps.

The wall-clock variants in test_order_mode.py drive a live orchestrator
through real reorder windows and need generous margins to survive CI
scheduling stalls. Here the policy's injectable clock (``_now``) scripts
the arrivals exactly, drains are invoked at explicit window boundaries,
and the realized release order is compared against the scorer's
``order_release_times`` permutation for the same arrivals — the
realized==scored invariant, deterministic and instant.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from namazu_tpu.ops.schedule import TraceArrays, order_release_times
from namazu_tpu.policy import create_policy
from namazu_tpu.policy.replayable import fnv64a
from namazu_tpu.signal import PacketEvent
from namazu_tpu.utils.config import Config

H = 64
WINDOW = 0.25


def make_policy(table):
    pol = create_policy("tpu_search")
    pol.load_config(Config({
        "explore_policy": "tpu_search",
        "explore_policy_param": {
            "seed": 5, "release_mode": "reorder",
            "reorder_window": int(WINDOW * 1000), "reorder_gap": 1,
            "search_on_start": False, "hint_buckets": H,
        },
    }))
    pol.install_table(table)
    pol.start = lambda: None  # no threads: drains are driven explicitly
    released = []
    pol._emit = released.append
    return pol, released


def scripted(pol, arrivals_hints):
    """Queue events at scripted fake-clock arrival times."""
    for t, hint in arrivals_hints:
        pol._now = lambda t=t: t
        pol.queue_event(PacketEvent.create("n0", "a", "b", hint=hint))


def test_realized_order_equals_scored_order_no_sleeps():
    hints = ["pA", "pB", "pC", "pD", "pE"]
    # pD arrives in window 1; the rest co-pend in window 0 and must be
    # permuted by priority, while pD stays behind the boundary
    arrivals = [0.01, 0.05, 0.11, 0.30, 0.18]
    prios = {f"a->b:{h}": p
             for h, p in zip(hints, [4.0, 1.0, 3.0, 0.0, 2.0])}
    table = np.full((H,), 9.0, np.float32)
    for h, p in prios.items():
        table[fnv64a(h.encode()) % H] = p

    pol, released = make_policy(table)
    scripted(pol, zip(arrivals, hints))
    assert pol._anchor == arrivals[0]

    # drain window 0 at its boundary, then everything at the next
    pol._drain_pending(gap=0.0, boundary=pol._anchor + WINDOW)
    n_first = len(released)
    pol._drain_pending(gap=0.0, boundary=pol._anchor + 2 * WINDOW)
    realized = [a.event_hint.split(":", 1)[1] for a in released]

    # the scorer's permutation for the same arrivals/buckets
    enc_hints = [f"a->b:{h}" for h in hints]
    hint_ids = jnp.asarray([fnv64a(h.encode()) % H for h in enc_hints])
    trace = TraceArrays(
        hint_ids,
        jnp.asarray(np.asarray(arrivals, np.float32) - arrivals[0]),
        jnp.ones((len(hints),), bool),
    )
    t = np.asarray(order_release_times(
        jnp.asarray(table), trace, gap=0.001, window=WINDOW))
    scored = [hints[i] for i in np.argsort(t, kind="stable")]

    assert realized == scored
    # window 0 closed with exactly its own four events
    assert n_first == 4 and realized[-1] == "pD"


def test_window_boundary_respects_scripted_arrivals():
    """An event arriving after a drain boundary stays pending."""
    table = np.zeros((H,), np.float32)
    pol, released = make_policy(table)
    scripted(pol, [(0.0, "x"), (0.6, "y")])
    pol._drain_pending(gap=0.0, boundary=0.25)
    assert [a.event_hint for a in released] == ["a->b:x"]
    pol._drain_pending(gap=0.0, boundary=None)  # shutdown flush
    assert [a.event_hint for a in released] == ["a->b:x", "a->b:y"]


def test_a_window_filled_past_window_over_gap_counts_as_an_overrun(
        monkeypatch):
    """What ``nmz_reorder_window_overruns_total`` counts: a window whose
    paced drain (``reorder_gap`` between releases) ended after the NEXT
    window's boundary. At a gap of 80 ms a 0.25 s window holds 4 events
    inside its own span ((n - 1) x 80 ms <= 250 ms); the fifth runs
    over. Inside a window the realized order is still the scored one —
    it is the slots of the window after it that come later than the
    scorer's ``close + gap * rank``."""
    from namazu_tpu import obs
    from namazu_tpu.obs import spans
    from namazu_tpu.policy import tpu as policy_tpu
    from tests.test_request_spans import isolated_obs

    gap = 0.08
    hints = [f"p{i}" for i in range(8)]
    # six events in window 0 (over window / gap), two in window 1
    arrivals = [0.00, 0.03, 0.06, 0.10, 0.15, 0.20, 0.30, 0.40]
    table = np.full((H,), 9.0, np.float32)
    for i, h in enumerate(hints):
        table[fnv64a(f"a->b:{h}".encode()) % H] = float(len(hints) - i)
    with isolated_obs():
        pol, released = make_policy(table)
        scripted(pol, zip(arrivals, hints))
        clock = [pol._anchor + WINDOW]  # the loop's wake-up: the boundary
        pol._now = lambda: clock[0]
        monkeypatch.setattr(
            policy_tpu.time, "sleep",
            lambda s: clock.__setitem__(0, clock[0] + s))
        pol._drain_pending(gap=gap, boundary=pol._anchor + WINDOW)
        # six events took 5 gaps = 0.40 s: past the next boundary
        assert clock[0] == pytest.approx(pol._anchor + WINDOW + 5 * gap)
        assert clock[0] > pol._anchor + 2 * WINDOW
        pol._drain_pending(gap=gap, boundary=pol._anchor + 2 * WINDOW)
        reg = obs.metrics.registry()
        assert reg.value(spans.REORDER_WINDOWS, policy=pol.name) == 2
        assert reg.value(spans.REORDER_WINDOW_OVERRUNS,
                         policy=pol.name) == 1
        events = reg.sample(spans.REORDER_WINDOW_EVENTS, policy=pol.name)
        assert (events.count, events.sum) == (2, 8.0)
        # the shutdown flush drains no window and counts none
        scripted(pol, [(0.6, "p0")])
        pol._drain_pending(gap=0.0)
        assert reg.value(spans.REORDER_WINDOWS, policy=pol.name) == 2
    realized = [a.event_hint.split(":", 1)[1] for a in released[:8]]
    hint_ids = jnp.asarray([fnv64a(f"a->b:{h}".encode()) % H
                            for h in hints])
    trace = TraceArrays(hint_ids, jnp.asarray(arrivals, jnp.float32),
                        jnp.ones((len(hints),), bool))
    t = np.asarray(order_release_times(jnp.asarray(table), trace,
                                       gap=gap, window=WINDOW))
    # inside each window the realized order is the scored one ...
    win = (np.asarray(arrivals) // WINDOW).astype(int)
    by_window = sorted(range(8), key=lambda i: (win[i], t[i]))
    assert realized == [hints[i] for i in by_window]
    # ... but the scorer's slots of window 0 run to close + 5 gaps =
    # 0.65 s, past window 1's close at 0.5 s where ITS slots are scored
    # from: by scored TIME the two windows interleave, and the policy,
    # which drains window-major, realizes window 1 later than scored.
    # That divergence is what the overrun counter counts.
    assert t[:6].max() == pytest.approx(WINDOW + 5 * gap)
    assert t[6:].min() == pytest.approx(2 * WINDOW)
    assert realized != [hints[i] for i in np.argsort(t, kind="stable")]
