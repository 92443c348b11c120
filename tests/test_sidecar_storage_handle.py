"""The search home keeps one storage handle per key beside the search it
keeps there (sidecar.py ``SearchService._get_storage``): a request
refreshes the handle — from its watermark on — where it used to open the
storage anew and walk every stored run, and what the request answers is
what it answered before."""

import os
import shutil

import pytest

from namazu_tpu import sidecar
from namazu_tpu.sidecar import SearchService
from namazu_tpu.storage import (
    HistoryStorage,
    NaiveStorage,
    load_storage,
    new_storage,
)
from namazu_tpu.storage.base import register_storage

from tests.test_sidecar import INGEST_PARAMS, SEARCH_PARAMS
from tests.test_tpu_policy import record_run

RUNS = [(["a", "b", "a", "c", "b", "a"], True),
        (["b", "a", "c", "a", "b", "c"], False),
        (["a", "c", "b", "a", "b", "c"], True),
        (["c", "b", "a", "b", "a", "c"], False),
        (["b", "c", "a", "a", "c", "b"], True),
        (["c", "a", "b", "c", "a", "b"], True)]


def make_history(path, depth=2, backend="naive"):
    st = new_storage(backend, str(path))
    st.create()
    for entities, ok in RUNS[:depth]:
        record_run(load_storage(str(path)), entities, ok)
    return st.dir


def search_req(storage_dir, key="campaign"):
    return {"op": "search", "key": key, "storage": storage_dir,
            "search_params": SEARCH_PARAMS, "ingest_params": INGEST_PARAMS,
            "generations": 4}


class Opens:
    """The handles a service opened (``load_storage`` as sidecar.py
    calls it) and the refreshes it made of each."""

    def __init__(self, monkeypatch):
        self.opened, self.refreshed = [], []
        monkeypatch.setattr(sidecar, "load_storage", self.load)
        real = NaiveStorage.refresh

        def refresh(handle):
            self.refreshed.append(handle)
            return real(handle)

        monkeypatch.setattr(NaiveStorage, "refresh", refresh)

    def load(self, storage_dir):
        self.opened.append(load_storage(storage_dir))
        return self.opened[-1]


@pytest.fixture
def opens(monkeypatch):
    return Opens(monkeypatch)


def ok(service, req):
    resp = service.handle(req)
    assert resp["ok"] and "fitness" in resp, resp
    return resp


def test_k_requests_of_a_key_open_its_storage_once(tmp_path, opens):
    d = make_history(tmp_path / "st")
    service = SearchService()
    for k in range(4):
        ok(service, search_req(d))
        record_run(load_storage(d), *RUNS[2 + k])
        assert len(opens.opened) == 1
        assert opens.refreshed == opens.opened * k
    # the handle saw each run stored since: one visit a request
    assert opens.opened[0].last_open == (5, 1)
    # another key is another handle, of the same dir or not
    ok(service, search_req(d, key="other"))
    assert len(opens.opened) == 2 and len(opens.refreshed) == 3


def another_dir(tmp_path, d):
    return make_history(tmp_path / "elsewhere", depth=3)


def removed_and_recreated(tmp_path, d):
    shutil.rmtree(d)
    return make_history(d, depth=2)


def next_run_went_backwards(tmp_path, d):
    st = load_storage(d)
    shutil.rmtree(st.run_dir(3))
    with open(os.path.join(d, "storage.json"), "w") as f:
        f.write('{"type": "naive", "next_run": 3}')
    return d


@pytest.mark.parametrize("change", [another_dir, removed_and_recreated,
                                    next_run_went_backwards])
def test_a_handle_that_no_longer_describes_the_storage_is_dropped(
        tmp_path, opens, change):
    d = make_history(tmp_path / "st", depth=4)
    service = SearchService()
    ok(service, search_req(d))
    ok(service, search_req(d))
    assert len(opens.opened) == 1
    d2 = change(tmp_path, d)
    ok(service, search_req(d2))
    assert len(opens.opened) == 2
    fresh = opens.opened[1]
    assert fresh.dir == d2 and fresh is not opens.opened[0]
    assert service._storages["campaign"] == (d2, fresh)
    ok(service, search_req(d2))
    assert len(opens.opened) == 2 and opens.refreshed[-1] is fresh


def test_a_storage_that_went_away_is_refused_and_found_again(tmp_path, opens):
    d = make_history(tmp_path / "st")
    service = SearchService()
    ok(service, search_req(d))
    shutil.move(d, d + ".away")
    resp = service.handle(search_req(d))
    assert not resp["ok"] and resp["error"].startswith("storage:")
    assert "campaign" not in service._storages
    shutil.move(d + ".away", d)
    ok(service, search_req(d))
    assert len(opens.opened) == 2


@register_storage
class SnapshotStorage(NaiveStorage):
    """A backend that has only the default ``refresh()``: what its
    handle knows of the storage it learnt at ``init()``."""

    NAME = "snapshot-for-test"
    refresh = HistoryStorage.refresh


def test_a_backend_with_only_the_default_refresh_is_served(tmp_path, opens):
    d = make_history(tmp_path / "st", backend=SnapshotStorage.NAME)
    service = SearchService()
    gens = []
    for k in range(3):
        gens.append(ok(service, search_req(d))["generations_run"])
        record_run(load_storage(d), *RUNS[2 + k])
    # a handle of it is opened at every request, as before, and none kept
    assert len(opens.opened) == 3 and opens.refreshed == []
    assert all(type(h) is SnapshotStorage for h in opens.opened)
    assert [h.last_open[0] for h in opens.opened] == [2, 3, 4]
    assert service._storages == {}
    assert gens == [4, 8, 12]


def test_the_kept_handle_answers_what_a_fresh_open_answers(tmp_path,
                                                           monkeypatch):
    """Over a storage that grows one run a request: fitness, table and
    generation count of every reply, against a service that opens the
    storage anew at every request (the parent's path)."""
    d = make_history(tmp_path / "st")
    kept, anew = SearchService(), SearchService()
    monkeypatch.setattr(
        anew, "_get_storage", lambda key, storage_dir: load_storage(
            storage_dir))
    for k in range(4):
        a = ok(kept, search_req(d))
        b = ok(anew, search_req(d))
        assert a["fitness"] == b["fitness"]
        assert a["delays"] == b["delays"] and a["faults"] == b["faults"]
        assert a["generations_run"] == b["generations_run"] == 4 * (k + 1)
        record_run(load_storage(d), *RUNS[2 + k])
    assert anew._storages == {}
    assert kept._storages["campaign"][1].last_open == (5, 1)
