"""Request-scoped spans of the search plane (obs/spans.py "request-scoped
spans", doc/observability.md "Request spans"): the framed server's
request scope and ``queue`` span, the sidecar's span tree for one
``search`` under one id, the compile listener, the ring and its framed
``spans`` op, the device trace on demand, and the off switch."""

import contextlib
import json
import logging
import threading
import time

import pytest

from namazu_tpu import obs
from namazu_tpu.endpoint.framed import FramedServer
from namazu_tpu.obs import export, federation, spans
from namazu_tpu.obs.context import wire_stamp
from namazu_tpu.sidecar import DeviceTraceCapture, SidecarServer, request
from namazu_tpu.storage import load_storage, new_storage
from namazu_tpu.utils.config import Config

from tests.test_tpu_policy import record_run

SEARCH_PARAMS = {
    "H": 32, "K": 32, "population": 64, "migrate_k": 2, "seed": 5,
    "max_interval": 0.05, "surrogate_topk": 4,
}
INGEST_PARAMS = {"H": 32, "max_interval": 0.05}

#: every span of one warm ``search`` request at this width (one fused
#: chunk, so one ``host_io``; no pool configured, so no ``ingest_pool``)
SPANS_OF_A_SEARCH = (
    "queue", "handle", "lock_wait", "load", "ingest", "ingest_read",
    "ingest_encode", "ingest_embed", "encode", "evolve", "place",
    "dispatch", "host_io", "wait", "surrogate", "save", "reply")


class FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: records what the
    program would have written into a device profile."""

    seen: list = []

    def __init__(self, name, **kw):
        self.row = (name, kw.get("rid"))

    def __enter__(self):
        FakeAnnotation.seen.append(self.row)

    def __exit__(self, *exc):
        return False


@contextlib.contextmanager
def isolated_obs():
    """An empty registry and ring, observability on; the process-global
    state the other test files see is put back afterwards."""
    was_on = obs.metrics.enabled()
    old = obs.metrics.set_registry(obs.metrics.MetricsRegistry())
    obs.metrics.configure(True)
    try:
        yield spans.reset_span_ring()
    finally:
        obs.metrics.configure(was_on)
        obs.metrics.set_registry(old)
        spans.reset_span_ring()


@pytest.fixture
def fresh_obs():
    with isolated_obs() as ring:
        yield ring


def make_history(path):
    st = new_storage("naive", str(path))
    st.create()
    record_run(st, ["a", "b", "a", "c", "b", "a"], successful=True)
    record_run(st, ["b", "a", "c", "a", "b", "c"], successful=False)
    record_run(st, ["a", "c", "b", "a", "b", "c"], successful=True)
    return st


def search_req(st, ckpt="", **extra):
    return dict({
        "op": "search", "key": st.dir, "storage": st.dir,
        "search_params": SEARCH_PARAMS, "ingest_params": INGEST_PARAMS,
        "generations": 2, "checkpoint": ckpt}, **extra)


def wait_for_rows(read, until=lambda rows: any(r[1] == "reply"
                                               for r in rows)):
    """A ``reply`` span ends after the client has its answer: give the
    worker a moment to record it."""
    deadline = time.monotonic() + 10
    while True:
        rows = read()
        if until(rows) or time.monotonic() > deadline:
            return rows
        time.sleep(0.01)


def phase_sample(phase, field, family=spans.SEARCH_PHASE):
    s = obs.metrics.registry().sample(family, phase=phase)
    return 0 if s is None else getattr(s, field)


# -- the framed server's request scope ---------------------------------------


@pytest.fixture
def slow_server(fresh_obs):
    seen = []

    def handler(req):
        seen.append(obs.current_request())
        time.sleep(0.2)
        return {"ok": True}

    srv = FramedServer(handler, name="spans-test")
    srv.bind_tcp("127.0.0.1", 0)
    srv.start()
    yield srv, seen
    srv.shutdown()
    srv.join()


def test_eight_requests_four_workers_four_wait(slow_server):
    """The wait the ledger could not explain: a ``search`` holds one of
    the four framed workers for its whole handler, so of eight
    concurrent requests four wait a handler's length in the queue."""
    srv, seen = slow_server
    addr = f"127.0.0.1:{srv.port}"
    threads = [threading.Thread(target=request,
                                args=(addr, {"op": "search"}))
               for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)
    rows = wait_for_rows(
        lambda: spans.span_ring().since(0)["rows"],
        lambda rows: sum(r[1] == "reply" for r in rows) == 8)
    queue = [r for r in rows if r[1] == "queue"]
    assert len(queue) == 8
    assert sum(r[5] > 0.15 for r in queue) == 4
    assert sum(r[5] < 0.05 for r in queue) == 4
    assert len({r[0] for r in queue}) == 8
    assert len([r for r in rows if r[1] == "reply"]) == 8
    # the handler could read the id and the arrival stamp it ran under
    assert {rid for rid, _arrived in seen} == {r[0] for r in queue}
    assert phase_sample("queue", "count") == 8


def test_ctx_stamped_request_keeps_the_clients_id(slow_server):
    srv, _seen = slow_server
    stamp = wire_stamp()
    resp = request(f"127.0.0.1:{srv.port}",
                   {"op": "search", "ctx": stamp})
    assert resp["ok"]
    rows = wait_for_rows(lambda: spans.span_ring().since(0)["rows"])
    assert {r[0] for r in rows} == {f"{stamp['o']}:{stamp['lc']}"}
    assert [r[1] for r in rows] == ["queue", "reply"]


def test_other_ops_run_under_no_request_scope(slow_server):
    srv, seen = slow_server
    assert request(f"127.0.0.1:{srv.port}", {"op": "ping"})["ok"]
    assert seen == [None]
    assert spans.span_ring().since(0)["rows"] == []


# -- one sidecar search, one tree ---------------------------------------------


@pytest.fixture(scope="module")
def searched(tmp_path_factory):
    """One warm ``search`` against a sidecar at test width, under a
    client's stamp: its span rows, the annotations a profiler would
    have seen, and the registry's phase counts for that request."""
    tmp = tmp_path_factory.mktemp("spans")
    st = make_history(tmp / "st")
    real_cls = spans._trace_annotation_cls
    with isolated_obs():
        srv = SidecarServer(port=0)
        srv.start()
        try:
            yield _one_warm_search(srv, st, str(tmp / "c.npz"))
        finally:
            spans._trace_annotation_cls = real_cls
            srv.shutdown()


def _one_warm_search(srv, st, ckpt) -> dict:
    addr = f"127.0.0.1:{srv.port}"
    # the first request compiles the search, the second the device
    # mirrors' row updates, the third the surrogate's training and
    # re-rank; from then on a request lowers nothing
    for _ in range(4):
        assert request(addr, search_req(st, ckpt))["ok"]
    wait_for_rows(lambda: spans.span_ring().since(0)["rows"],
                  lambda rows: sum(r[1] == "reply" for r in rows) == 4)
    spans._trace_annotation_cls = FakeAnnotation
    FakeAnnotation.seen = []
    before = {p: phase_sample(p, "count") for p in SPANS_OF_A_SEARCH}
    stamp = wire_stamp()
    resp = request(addr, search_req(st, ckpt, ctx=stamp))
    assert resp["ok"]
    rid = f"{stamp['o']}:{stamp['lc']}"
    rows = wait_for_rows(
        lambda: [r for r in request(addr, {"op": "spans"})["rows"]
                 if r[0] == rid])
    counts = {p: phase_sample(p, "count") - before[p]
              for p in SPANS_OF_A_SEARCH}
    return {"rid": rid, "rows": rows, "counts": counts,
            "annotations": list(FakeAnnotation.seen)}


@pytest.mark.parametrize("name", SPANS_OF_A_SEARCH)
def test_each_span_once_per_request(searched, name):
    assert [r[1] for r in searched["rows"]].count(name) == 1
    assert searched["counts"][name] == 1


def test_the_request_stays_inside_its_budget(searched):
    assert "compile" not in [r[1] for r in searched["rows"]]
    assert len(searched["rows"]) <= 20
    assert sum(searched["counts"].values()) <= 20


def test_the_tree_nests_and_self_time_is_sound(searched):
    (tree,) = export.span_trees(searched["rows"])
    assert tree["rid"] == searched["rid"]
    assert [n["name"] for n in tree["spans"]] == ["queue", "handle",
                                                  "reply"]
    handle = tree["spans"][1]
    # (``extract`` runs only when the surrogate re-rank picks nothing)
    assert [n["name"] for n in handle["children"]
            if n["name"] != "extract"] == [
        "lock_wait", "load", "ingest", "encode", "evolve", "surrogate",
        "save"]
    by_name = {n["name"]: n for n in handle["children"]}
    assert {n["name"] for n in by_name["ingest"]["children"]} == {
        "ingest_read", "ingest_encode", "ingest_embed"}
    assert [n["name"] for n in by_name["evolve"]["children"]] == [
        "place", "dispatch", "host_io", "wait"]
    assert by_name["ingest"]["attrs"] == {"runs": 3}

    def check(node):
        assert node["self_s"] >= 0.0
        end = node["t_mono"] + node["seconds"]
        for child in node["children"]:
            assert node["t_mono"] <= child["t_mono"]
            assert child["t_mono"] + child["seconds"] <= end + 1e-6
            check(child)

    for node in tree["spans"]:
        check(node)
    stages = sum(n["seconds"] for n in by_name["ingest"]["children"])
    assert stages <= by_name["ingest"]["seconds"]
    text = export.render_span_trees(searched["rows"])
    assert text.startswith(f"request {searched['rid']}")
    assert "      ingest_embed" in text and "runs=3" in text


@pytest.mark.parametrize("name", ["evolve", "encode", "surrogate"])
def test_one_annotation_per_request_for_the_trace_reduction(searched,
                                                            name):
    """``benchmarks/trace_reduce.py`` counts ``nmz:evolve`` spans and
    attributes idle gaps by these names: one each, under the id."""
    assert searched["annotations"].count(
        (f"nmz:{name}", searched["rid"])) == 1
    assert [a for a, _rid in searched["annotations"]].count(
        f"nmz:{name}") == 1


def test_observed_spans_are_not_annotated(searched):
    names = {a for a, _rid in searched["annotations"]}
    assert not names & {"nmz:queue", "nmz:dispatch", "nmz:ingest_read"}
    assert {rid for _a, rid in searched["annotations"]} == {
        searched["rid"]}


def test_chrome_trace_takes_span_rows(searched):
    doc = export.chrome_trace(None, spans=searched["rows"])
    begins = [e for e in doc["traceEvents"] if e.get("ph") == "b"]
    assert len(begins) == len(searched["rows"])
    assert {e["id"] for e in begins} == {searched["rid"]}
    assert min(e["ts"] for e in begins) == 0
    names = {e["args"]["name"] for e in doc["traceEvents"]
             if e["name"] == "process_name"}
    assert "search requests" in names


# -- compiles by phase --------------------------------------------------------


def test_a_recompile_inside_evolve_is_counted_there(fresh_obs, tmp_path):
    from namazu_tpu.models.ingest import IngestParams, ingest_history
    from namazu_tpu.models.search import build_search_from_params

    st = make_history(tmp_path / "st")
    search = build_search_from_params(SEARCH_PARAMS)
    refs = ingest_history(search, st, IngestParams(**INGEST_PARAMS))
    search.run(refs, generations=2)
    total = obs.metrics.registry().value(spans.COMPILES)
    in_evolve = phase_sample("evolve", "count", spans.COMPILE_SECONDS)
    assert total > 0 and in_evolve > 0
    # a chunk length this search has not dispatched yet: the fused step
    # is lowered again, inside the evolve phase and nowhere else
    search.run(refs, generations=3)
    assert obs.metrics.registry().value(spans.COMPILES) == total + 1
    assert phase_sample("evolve", "count",
                        spans.COMPILE_SECONDS) == in_evolve + 1
    rows = fresh_obs.since(0)["rows"]
    assert [r[2] for r in rows if r[1] == "compile"][-1] == "evolve"
    # ... and a third run at a known length lowers nothing
    search.run(refs, generations=3)
    assert obs.metrics.registry().value(spans.COMPILES) == total + 1


def test_a_compile_row_names_the_lowered_program(fresh_obs, tmp_path):
    from namazu_tpu.models.ingest import IngestParams, ingest_history
    from namazu_tpu.models.search import build_search_from_params

    st = make_history(tmp_path / "st")
    search = build_search_from_params(SEARCH_PARAMS)
    refs = ingest_history(search, st, IngestParams(**INGEST_PARAMS))
    # a chunk length no search of this process has dispatched: the
    # fused step is lowered whatever ran before in the process
    search.run(refs, generations=5)
    rows = [r for r in fresh_obs.since(0)["rows"] if r[1] == "compile"]
    assert rows and all(r[7].get("fun_name") for r in rows)
    # jax names the module after the jitted function
    fused = [r for r in rows if r[7]["fun_name"] == "jit(fused)"]
    assert fused and {r[2] for r in fused} == {"evolve"}
    # ... and ``tools spans`` prints it on the row's line
    text = export.render_span_trees(fresh_obs.since(0)["rows"])
    assert any(line.lstrip().startswith("compile")
               and "fun_name=jit(fused)" in line
               for line in text.splitlines())


# -- the off switch -----------------------------------------------------------


def test_disabled_leaves_ring_and_registry_empty_and_the_reply_equal(
        fresh_obs, tmp_path):
    st = make_history(tmp_path / "st")
    replies = []
    for on in (False, True):
        obs.metrics.configure(on)
        srv = SidecarServer(port=0)
        srv.start()
        try:
            replies.append(request(f"127.0.0.1:{srv.port}",
                                   search_req(st)))
        finally:
            srv.shutdown()
        if not on:
            assert fresh_obs.since(0)["rows"] == []
            assert obs.metrics.registry().to_jsonable()["metrics"] == []
    assert replies[0]["ok"]
    assert json.dumps(replies[0], sort_keys=True) == json.dumps(
        replies[1], sort_keys=True)
    assert fresh_obs.since(0)["rows"]


# -- the ring and its op ------------------------------------------------------


def test_the_ring_is_bounded_and_counts_drops(fresh_obs):
    ring = spans.reset_span_ring(rows=4)
    for i in range(6):
        with obs.search_phase("encode", i=i):
            pass
    doc = ring.since(0)
    assert [r[7]["i"] for r in doc["rows"]] == [2, 3, 4, 5]
    assert doc["dropped"] == 2 and doc["next"] == 6
    assert obs.metrics.registry().value(spans.SPAN_ROWS_DROPPED) == 2
    # a reader that fell behind resumes at the oldest row still held
    assert [r[7]["i"] for r in ring.since(1)["rows"]] == [2, 3, 4, 5]


def test_the_spans_op_pages_by_cursor(fresh_obs):
    for i in range(5):
        obs.search_phase_observed("dispatch", 0.001 * i,
                                  time.monotonic(), pieces=i)
    got, cursor = [], 0
    while True:
        resp = federation.handle_obs_op(
            {"op": "spans", "since": cursor, "limit": 2})
        assert resp["ok"] and len(resp["rows"]) <= 2
        if not resp["rows"]:
            break
        got += resp["rows"]
        cursor = resp["next"]
    assert [r[7]["pieces"] for r in got] == [0, 1, 2, 3, 4]
    assert cursor == 5 and resp["dropped"] == 0
    anchor = resp["anchor"]
    assert abs(anchor["wall"] - time.time()) < 5
    assert abs(anchor["mono"] - time.monotonic()) < 5
    assert not federation.handle_obs_op(
        {"op": "spans", "since": "x"})["ok"]


# -- the device trace on demand -----------------------------------------------


def test_device_trace_fails_open_while_a_capture_is_live(fresh_obs,
                                                         tmp_path):
    cap = DeviceTraceCapture()
    assert cap.handle({}) == {"ok": True, "live": False, "dir": ""}
    first = cap.handle({"dir": str(tmp_path / "a"), "seconds": 60})
    try:
        assert first["ok"] and first["live"]
        second = cap.handle({"dir": str(tmp_path / "b"), "seconds": 1})
        assert second["ok"] is False and "already live" in second["error"]
        assert cap.handle({})["live"] is True
    finally:
        cap.stop()
    assert cap.handle({})["live"] is False
    assert obs.metrics.registry().value(spans.SEARCH_DEVICE_TRACES) == 1
    assert not (tmp_path / "b").exists()
    assert cap.handle({"dir": str(tmp_path / "c"),
                      "seconds": "soon"})["ok"] is False
    cap.stop()  # nothing live: a no-op


# -- the storage open ---------------------------------------------------------


def open_counters():
    reg = obs.metrics.registry()
    return (reg.value(spans.STORAGE_OPEN_RUNS),
            reg.value(spans.STORAGE_OPEN_SETTLED_RUNS))


def test_an_open_counts_its_runs_and_those_it_did_not_visit(fresh_obs,
                                                            tmp_path):
    """``nmz_storage_open_runs_total`` / ``..._settled_runs_total`` move
    by (N, N - visited) an ``init()`` or ``refresh()``, the ``load`` row
    of a request says ``runs=`` / ``visited=``, and nothing moves with
    observability off."""
    st = make_history(tmp_path / "st")  # one handle: no watermark yet
    kept = load_storage(st.dir)
    assert open_counters() == (3, 0)  # walked whole, and written at 0
    record_run(kept, ["c", "b", "a", "b", "a", "c"], successful=True)
    load_storage(st.dir)  # the writer persisted what it had seen
    assert open_counters() == (3 + 4, 0 + 3)
    kept.refresh()
    assert kept.last_open == (4, 1)
    assert open_counters() == (7 + 4, 3 + 3)
    srv = SidecarServer(port=0)
    srv.start()
    try:
        addr = f"127.0.0.1:{srv.port}"
        for _ in range(2):
            assert request(addr, search_req(st))["ok"]
        rows = wait_for_rows(
            lambda: request(addr, {"op": "spans"})["rows"],
            lambda rows: sum(r[1] == "reply" for r in rows) == 2)
        obs.metrics.configure(False)
        assert request(addr, search_req(st))["ok"]
    finally:
        srv.shutdown()
    # the key's first request opened the storage, the second refreshed
    assert [r[7] for r in rows if r[1] == "load"] == [
        {"runs": 4, "visited": 1}, {"runs": 4, "visited": 0}]
    text = export.render_span_trees(rows)
    assert "runs=4  visited=1" in text and "runs=4  visited=0" in text
    obs.metrics.configure(True)
    assert open_counters() == (11 + 8, 6 + 7)
    obs.metrics.configure(False)
    load_storage(st.dir)
    kept.refresh()
    obs.metrics.configure(True)
    assert open_counters() == (19, 13)


# -- the two homes ------------------------------------------------------------


def test_the_in_process_home_reports_ingest_once(fresh_obs, tmp_path):
    from namazu_tpu.policy import create_policy

    st = make_history(tmp_path / "st")
    policy = create_policy("tpu_search")
    policy.load_config(Config({"explore_policy_param": {
        "max_interval": 30, "generations": 2, "population": 64,
        "hint_buckets": 32, "feature_pairs": 32, "seed": 11,
        "checkpoint": str(tmp_path / "search.npz")}}))
    policy.set_history_storage(st)
    try:
        policy.start()
        assert policy.wait_for_search(timeout=180)
    finally:
        policy.shutdown()
    rows = fresh_obs.since(0)["rows"]
    ingest = [r for r in rows if r[1] == "ingest"]
    assert len(ingest) == 1 and ingest[0][0] is None
    assert ingest[0][7] == {"runs": 3}
    assert phase_sample("ingest", "count") == 1
    assert obs.metrics.registry().value(spans.INGEST_RUNS) == 3
    assert [r[1] for r in rows].count("save") == 1


def test_the_install_line_names_the_sidecars_request(fresh_obs, tmp_path,
                                                     caplog):
    """The run's log and the sidecar's span tree name the same request;
    the line's prefix is what the benchmark's ``_INSTALL_RE`` matches,
    once per run."""
    import re

    from namazu_tpu.policy import create_policy

    st = make_history(tmp_path / "st")
    srv = SidecarServer(port=0)
    srv.start()
    try:
        pol = create_policy("tpu_search")
        pol.load_config(Config({
            "explore_policy": "tpu_search",
            "explore_policy_param": {
                "seed": 5, "max_interval": 50, "hint_buckets": 32,
                "feature_pairs": 32, "population": 64, "generations": 2,
                "migrate_k": 2, "surrogate_topk": 0,
                "sidecar": f"127.0.0.1:{srv.port}",
                "checkpoint": "side_pol.npz"}}))
        pol.set_history_storage(st)
        with caplog.at_level(logging.INFO):
            pol.start()
            assert pol.wait_for_search(timeout=120)
            pol.shutdown()
    finally:
        srv.shutdown()
    lines = [r.getMessage() for r in caplog.records
             if "installed sidecar schedule" in r.getMessage()]
    assert len(lines) == 1
    m = re.match(r"installed sidecar schedule \(fitness (\S+), gen (\d+)\)"
                 r" on .*, request (\S+)$", lines[0])
    assert m, lines[0]
    handles = [r for r in fresh_obs.since(0)["rows"] if r[1] == "handle"]
    assert [r[0] for r in handles] == [m.group(3)]
