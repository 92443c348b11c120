"""tpu_search policy integration: history -> search -> installed schedule."""

import contextlib
import time
import types

import numpy as np
import pytest

from namazu_tpu.policy import create_policy
from namazu_tpu.signal import EventAcceptanceAction, PacketEvent
from namazu_tpu.storage import new_storage
from namazu_tpu.utils.config import Config
from namazu_tpu.utils.policy_tester import pump_concurrent
from namazu_tpu.utils.trace import SingleTrace


def record_run(storage, entities, successful):
    storage.create_new_working_dir()
    t = SingleTrace()
    now = time.time()
    for i, e in enumerate(entities):
        ev = PacketEvent.create(e, e, "peer", hint=f"{e}:{i % 4}")
        a = ev.default_action()
        a.mark_triggered(now + i * 0.002)
        t.append(a)
    storage.record_new_trace(t)
    from namazu_tpu.signal.base import HINT_SPACE

    # stamp like cli/run_cmd.py does: unstamped runs are treated as
    # pre-flow-prefix recordings and excluded from search ingest
    storage.record_result(successful, 0.5,
                          metadata={"hint_space": HINT_SPACE})


@pytest.fixture
def history(tmp_path):
    st = new_storage("naive", str(tmp_path / "st"))
    st.create()
    record_run(st, ["a", "b", "a", "c", "b", "a"], successful=True)
    record_run(st, ["b", "a", "c", "a", "b", "c"], successful=False)
    return st


def small_cfg(tmp_path, extra=None):
    param = {
        "max_interval": 30,
        "generations": 6,
        "population": 128,
        "hint_buckets": 32,
        "trace_length": 64,
        "feature_pairs": 32,
        "seed": 11,
        "checkpoint": str(tmp_path / "search.npz"),
    }
    param.update(extra or {})
    return Config({"explore_policy_param": param})


def test_search_installs_schedule_from_history(tmp_path, history):
    policy = create_policy("tpu_search")
    policy.load_config(small_cfg(tmp_path))
    policy.set_history_storage(history)
    try:
        policy.start()
        assert policy.wait_for_search(timeout=180)
        assert policy._delays is not None
        assert policy._delays.shape == (32,)
        assert (policy._delays >= 0).all()
        assert (policy._delays <= 0.03 + 1e-6).all()
        # checkpoint written for the next run
        assert (tmp_path / "search.npz").exists()
        # events answered using the searched table
        acts = pump_concurrent(policy, 20, entities=3)
        assert len(acts) == 20
        assert all(isinstance(a, EventAcceptanceAction) for a in acts)
    finally:
        policy.shutdown()


def test_fallback_to_hash_delays_without_history(tmp_path):
    policy = create_policy("tpu_search")
    policy.load_config(small_cfg(tmp_path, {"search_on_start": False}))
    try:
        acts = pump_concurrent(policy, 10, entities=2)
        assert len(acts) == 10
        assert policy._delays is None  # still on the hash fallback
    finally:
        policy.shutdown()


def test_checkpoint_resume_across_policy_instances(tmp_path, history):
    p1 = create_policy("tpu_search")
    p1.load_config(small_cfg(tmp_path))
    p1.set_history_storage(history)
    p1.start()
    assert p1.wait_for_search(timeout=180)
    gen1 = p1._search.generations_run
    p1.shutdown()

    p2 = create_policy("tpu_search")
    p2.load_config(small_cfg(tmp_path))
    p2.set_history_storage(history)
    p2.start()
    assert p2.wait_for_search(timeout=180)
    assert p2._search.generations_run == gen1 + 6  # resumed, not restarted
    p2.shutdown()


def test_delay_lookup_deterministic(tmp_path):
    policy = create_policy("tpu_search")
    policy.load_config(small_cfg(tmp_path, {"search_on_start": False}))
    d1 = policy._delay_for("packet:a->b")
    d2 = policy._delay_for("packet:a->b")
    d3 = policy._delay_for("packet:b->a")
    assert d1 == d2
    assert 0 <= d1 < 0.03
    assert d1 != d3
    policy.shutdown()


def test_reorder_window_zero_rejected(tmp_path):
    """window=0 means 'one global window' to the scorer but a busy-spin
    continuous drain to the control plane — must fail fast."""
    policy = create_policy("tpu_search")
    with pytest.raises(ValueError, match="reorder_window"):
        policy.load_config(small_cfg(tmp_path, {
            "release_mode": "reorder", "reorder_window": 0,
        }))
    # delay mode doesn't care about the window knob
    policy2 = create_policy("tpu_search")
    policy2.load_config(small_cfg(tmp_path, {
        "release_mode": "delay", "reorder_window": 0,
        "search_on_start": False,
    }))


class _RecordingSearch:
    """Stub search backend: records what _ingest_history feeds it."""

    def __init__(self):
        self.executed = []
        self.failures = []
        self.occupied = None

    def set_occupied_buckets(self, occupied):
        self.occupied = list(occupied)

    def add_executed_trace(self, enc, reproduced=False, arrival=None):
        self.executed.append((enc, reproduced))

    def add_failure_trace(self, enc):
        self.failures.append(enc)

    def embed_batch(self):
        # ingest wraps its adds in the search's batch; a stub has
        # nothing to defer
        return contextlib.nullcontext(types.SimpleNamespace(calls=0, groups=0))


def _policy_with_storage(storage):
    pol = create_policy("tpu_search")
    pol.load_config(Config({"explore_policy_param": {
        "search_on_start": False, "hint_buckets": 32,
        "reference_mode": "recent",
    }}))
    pol.set_history_storage(storage)
    return pol


def test_ingest_history_refs_are_successes_only(tmp_path):
    """References for the counterfactual are SUCCESS traces whenever any
    exist — a failure trace's arrivals already contain the bug-inducing
    delays, so scoring against it lets a no-op genome match the failure
    signature (advisor finding, round 2)."""
    st = new_storage("naive", str(tmp_path / "st"))
    st.create()
    record_run(st, ["a", "b", "a"], successful=True)
    record_run(st, ["b", "a", "c"], successful=False)
    record_run(st, ["c", "b", "a"], successful=True)
    record_run(st, ["a", "c", "b"], successful=False)
    record_run(st, ["b", "c", "a"], successful=False)
    pol = _policy_with_storage(st)
    search = _RecordingSearch()
    refs = pol._ingest_history(search)
    # 2 successes exist -> refs are exactly those (latest first), never
    # padded with failures
    assert len(refs) == 2
    # all five runs still feed the archives
    assert len(search.executed) == 5
    assert len(search.failures) == 3


def test_ingest_history_refs_fall_back_to_failures(tmp_path):
    st = new_storage("naive", str(tmp_path / "st"))
    st.create()
    record_run(st, ["a", "b", "a"], successful=False)
    record_run(st, ["b", "a", "c"], successful=False)
    pol = _policy_with_storage(st)
    search = _RecordingSearch()
    refs = pol._ingest_history(search)
    assert len(refs) == 2  # no success yet: failures anchor the search
