"""Persistent search sidecar (SURVEY.md §5.8's orchestrator ⇄ JAX
boundary): framed-JSON wire, shared ingest with the in-process policy,
warm-search amortization, checkpoint interchangeability, and the
policy's sidecar delegation — which never searches in-process, because
the sidecar is the one process that owns the chip.
"""


import numpy as np
import pytest

from namazu_tpu.sidecar import SidecarServer, request
from namazu_tpu.storage import new_storage
from namazu_tpu.utils.config import Config

from tests.test_tpu_policy import record_run  # reuse the history fixture


@pytest.fixture
def history(tmp_path):
    st = new_storage("naive", str(tmp_path / "st"))
    st.create()
    record_run(st, ["a", "b", "a", "c", "b", "a"], successful=True)
    record_run(st, ["b", "a", "c", "a", "b", "c"], successful=False)
    return st


@pytest.fixture
def server():
    s = SidecarServer(port=0)
    s.start()
    yield s
    s.shutdown()


SEARCH_PARAMS = {
    "H": 32, "K": 32, "population": 64, "migrate_k": 2, "seed": 5,
    "max_interval": 0.05, "surrogate_topk": 0,
}
INGEST_PARAMS = {"H": 32, "max_interval": 0.05}


def search_req(history, ckpt=""):
    return {
        "op": "search",
        "key": history.dir,
        "storage": history.dir,
        "search_params": SEARCH_PARAMS,
        "ingest_params": INGEST_PARAMS,
        "generations": 4,
        "checkpoint": ckpt,
    }


def test_ping(server):
    # no search built yet: the ping itself must not be what initialises
    # the device backend, so it carries no device
    resp = request(f"127.0.0.1:{server.port}", {"op": "ping"})
    assert resp == {"ok": True, "searches": 0}


def test_replies_state_the_device(server, history):
    """The search states which device it ran on where a caller can read
    it: every search reply, and the ping once a search exists."""
    import jax

    addr = f"127.0.0.1:{server.port}"
    want = {"platform": jax.devices()[0].platform,
            "kind": jax.devices()[0].device_kind,
            "count": len(jax.devices())}
    assert request(addr, search_req(history))["device"] == want
    assert request(addr, {"op": "ping"})["device"] == want


def test_search_and_warm_amortization(server, history, tmp_path):
    addr = f"127.0.0.1:{server.port}"
    ckpt = str(tmp_path / "side.npz")
    r1 = request(addr, search_req(history, ckpt))
    assert r1["ok"] and np.isfinite(r1["fitness"])
    assert len(r1["delays"]) == 32
    assert (tmp_path / "side.npz").exists()
    _fp, held = server.service._searches[history.dir]
    steps = dict(held._fused_steps)
    assert steps and all(f._cache_size() == 1 for f in steps.values())

    r2 = request(addr, search_req(history, ckpt))
    assert r2["ok"]
    assert r2["generations_run"] > r1["generations_run"]
    # the whole point of the sidecar: the compiled search is held, so a
    # follow-up request skips construction + jit warm-up. Held to what
    # is held, not to the two requests' wall times: on an xdist worker
    # whose earlier files had warmed these shapes, under six loaded
    # workers, "cold" read 0.38 s and "warm" 0.35 s (alone: 4.2 / 0.1)
    assert server.service._searches[history.dir][1] is held
    assert held._fused_steps == steps  # the same jitted steps,
    assert all(f._cache_size() == 1 for f in steps.values())  # not retraced


def test_checkpoint_interchangeable_with_in_process(server, history,
                                                    tmp_path):
    """A checkpoint written by the sidecar loads in an in-process
    ScheduleSearch built with the same params — the two homes are
    interchangeable mid-experiment."""
    from namazu_tpu.models.search import ScheduleSearch
    from namazu_tpu.models.search import build_search_from_params

    addr = f"127.0.0.1:{server.port}"
    ckpt = str(tmp_path / "x.npz")
    assert request(addr, search_req(history, ckpt))["ok"]
    local = build_search_from_params(SEARCH_PARAMS)
    assert isinstance(local, ScheduleSearch)
    local.load(ckpt)
    assert local.generations_run >= 4


def test_cached_search_reloads_newer_checkpoint(server, history, tmp_path):
    """Runs under the in-process config may evolve and save between two
    sidecar requests; the sidecar's next request for that key must
    reload the newer on-disk checkpoint instead of overwriting it with
    its stale cached state (lost update, ADVICE r4)."""
    from namazu_tpu.models.ingest import IngestParams, ingest_history
    from namazu_tpu.models.search import build_search_from_params

    ckpt = str(tmp_path / "c.npz")
    addr = f"127.0.0.1:{server.port}"
    r1 = request(addr, search_req(history, ckpt))
    assert r1["ok"]

    # simulate an in-process search evolving past the cached state
    s = build_search_from_params(SEARCH_PARAMS)
    s.load(ckpt)
    refs = ingest_history(s, history, IngestParams(**INGEST_PARAMS))
    s.run(refs, generations=6)
    s.save(ckpt)
    disk_gen = s.generations_run
    assert disk_gen > r1["generations_run"]

    r2 = request(addr, search_req(history, ckpt))
    assert r2["ok"]
    # reloaded from disk, then ran this request's 4 generations on top
    assert r2["generations_run"] == disk_gen + 4


def test_keep_alive_search_requests_share_one_connection(server, history,
                                                         tmp_path):
    """The wire is keep-alive since the knowledge plane (one connection,
    many framed request/response pairs); the search op — seconds of
    work per request — must ride it just like the cheap ops, and the
    old one-shot `request` client keeps working against the same
    server (covered by every other test here)."""
    import socket

    from namazu_tpu.endpoint.agent import read_frame, write_frame

    ckpt = str(tmp_path / "ka.npz")
    with socket.create_connection(("127.0.0.1", server.port)) as s:
        write_frame(s, {"op": "ping"})
        assert read_frame(s)["ok"]
        write_frame(s, search_req(history, ckpt))
        r1 = read_frame(s)
        assert r1["ok"] and np.isfinite(r1["fitness"])
        write_frame(s, search_req(history, ckpt))
        r2 = read_frame(s)
        assert r2["ok"]
        assert r2["generations_run"] > r1["generations_run"]


def test_unknown_op_and_bad_storage(server):
    addr = f"127.0.0.1:{server.port}"
    assert not request(addr, {"op": "nope"})["ok"]
    bad = {"op": "search", "key": "k", "storage": "/nonexistent-st",
           "search_params": SEARCH_PARAMS, "ingest_params": INGEST_PARAMS,
           "generations": 1, "checkpoint": ""}
    resp = request(addr, bad)
    assert not resp["ok"] and "storage" in resp["error"]


def test_policy_delegates_to_sidecar(server, history):
    """tpu_search with sidecar=addr installs the sidecar's table and
    never builds a local search."""
    from namazu_tpu.policy import create_policy

    pol = create_policy("tpu_search")
    pol.load_config(Config({
        "explore_policy": "tpu_search",
        "explore_policy_param": {
            "seed": 5, "max_interval": 50, "hint_buckets": 32,
            "feature_pairs": 32, "population": 64, "generations": 4,
            "migrate_k": 2, "surrogate_topk": 0,
            "sidecar": f"127.0.0.1:{server.port}",
            "checkpoint": "side_pol.npz",
        },
    }))
    pol.set_history_storage(history)
    pol.start()
    assert pol.wait_for_search(timeout=120)
    assert pol._delays is not None and pol._delays.shape == (32,)
    assert pol._search is None  # the heavy path never ran locally
    pol.shutdown()


def test_sidecar_without_checkpoint_fails_fast():
    """The sidecar evolve's product ships via the checkpoint; a config
    with sidecar but no checkpoint is wasted work every run and must be
    rejected at load, like the other enum knobs."""
    from namazu_tpu.policy import create_policy

    pol = create_policy("tpu_search")
    with pytest.raises(ValueError, match="checkpoint"):
        pol.load_config(Config({
            "explore_policy": "tpu_search",
            "explore_policy_param": {"sidecar": "127.0.0.1:10990"},
        }))


def _sidecar_policy(history, addr, monkeypatch):
    from namazu_tpu.policy import create_policy
    from namazu_tpu.policy.tpu import TPUSearchPolicy

    def no_search(self):
        raise AssertionError(
            "a policy in sidecar mode built an in-process search — a "
            "second process on a chip the sidecar owns")

    monkeypatch.setattr(TPUSearchPolicy, "_build_search", no_search)
    pol = create_policy("tpu_search")
    pol.load_config(Config({
        "explore_policy": "tpu_search",
        "explore_policy_param": {
            "seed": 5, "max_interval": 50, "hint_buckets": 32,
            "feature_pairs": 32, "population": 64, "generations": 2,
            "migrate_k": 2, "surrogate_topk": 0,
            "sidecar": addr, "checkpoint": "fb.npz",
        },
    }))
    pol.set_history_storage(history)
    return pol


def test_policy_keeps_hash_delays_when_sidecar_down(history, monkeypatch):
    """One process per chip: with a sidecar configured the policy never
    constructs a search, whatever happens to the request — here nothing
    listens, and the hash fallback simply remains."""
    pol = _sidecar_policy(history, "127.0.0.1:1", monkeypatch)
    pol.start()
    assert not pol.wait_for_search(timeout=60)  # nothing installed
    assert pol._search is None and pol._table_source() == "hash"
    pol.shutdown()


def test_policy_keeps_its_table_when_sidecar_refuses(server, history,
                                                     monkeypatch):
    """Same rule when the sidecar answers but fails the request: the
    checkpointed table installed at start stays, no local search."""
    import os

    ckpt = os.path.join(history.dir, "fb.npz")
    assert request(f"127.0.0.1:{server.port}",
                   search_req(history, ckpt))["ok"]
    monkeypatch.setattr(server.service, "_search",
                        lambda req: {"ok": False, "error": "boom"})
    pol = _sidecar_policy(history, f"127.0.0.1:{server.port}",
                          monkeypatch)
    pol.start()
    assert pol.wait_for_search(timeout=60)
    assert pol._search is None and pol._table_source() == "table"
    installed = np.array(pol._delays)
    with np.load(ckpt) as z:
        np.testing.assert_array_equal(installed, z["best_delays"])
    pol.shutdown()
