"""Persistent search sidecar: the orchestrator ⇄ JAX boundary.

SURVEY.md §5.8 calls for a fourth endpoint-like boundary beside
local/REST/agent: the control plane ships recorded history to a
long-lived JAX process and gets the best schedule back. Without it,
every `run` process pays search construction + jit warm-up (seconds)
for a two-second experiment; the sidecar holds the compiled search and
device state for the WHOLE experiment, so a per-run search request costs
one ingest + a few warm generations (~100 ms class) plus a loopback
round trip.

Wire: framed JSON over TCP (the same 4-byte little-endian length prefix
as the guest-agent endpoint — endpoint/agent.py read_frame/write_frame),
keep-alive: a connection may carry any number of request/response pairs
(the PR 5 persistent-connection pattern; requests on one connection are
served in order). Old one-shot clients — send one frame, read the
reply, close — keep working: the server loop simply sees EOF.

* ``{"op": "ping"}`` -> ``{"ok": true, "searches": N, "device": {...}}``
  (``device`` = platform/kind/count of the chips this process owns,
  present once the first search has initialised the backend)
* ``{"op": "search", "key": str, "storage": dir,
     "search_params": {...}, "ingest_params": {...},
     "generations": N, "checkpoint": path}``
  -> ``{"ok": true, "fitness": f, "delays": [...], "faults": [...],
        "generations_run": N, "device": {...}}``
* ``{"op": "device_trace", "dir": D, "seconds": S}`` -> a
  ``jax.profiler`` capture of the next S seconds into D
  (:class:`DeviceTraceCapture`); ``{"op": "spans", "since": N}`` -> the
  request-scoped span rows (obs/federation.py ``handle_obs_op``)
* knowledge-plane ops (``pool_push`` / ``pool_pull`` /
  ``surrogate_predict`` / ``stats``; doc/knowledge.md) when the sidecar
  was started with ``--pool-dir`` — without it they answer
  ``{"ok": false, ...}`` and clients degrade to local-only search.

The sidecar reads the storage directory itself (same host by design —
this boundary rides loopback/DCN, never the per-event hot path), runs
the SAME ingest the in-process policy uses (models/ingest.py), and
persists the checkpoint so in-process and sidecar searches are
interchangeable mid-experiment. A changed ``search_params`` fingerprint
for a key rebuilds that search.

Start one with ``nmz-tpu sidecar --listen 127.0.0.1:10990``; point the
policy at it with ``sidecar = "127.0.0.1:10990"`` in
``explore_policy_param``.
"""

from __future__ import annotations

import json
import os
import socket
import threading
from typing import Dict, Optional, Tuple

import numpy as np

from namazu_tpu import obs
from namazu_tpu.endpoint.agent import read_frame, write_frame
from namazu_tpu.endpoint.framed import FramedServer
from namazu_tpu.storage import HistoryStorage, load_storage
from namazu_tpu.utils.log import get_logger

log = get_logger("sidecar")


class DeviceTraceCapture:
    """The device trace on demand: one ``jax.profiler`` capture at a
    time, started by the ``device_trace`` op and stopped by a timer, so
    the op holds a framed worker for the start only. The program's
    ``nmz:<phase>`` annotations carry the request id as ``rid``, so the
    capture and the ``spans`` op describe the same requests. Fail-open:
    a capture already live, or a profiler the runtime cannot start,
    answers ``{"ok": false}`` and never raises into the wire."""

    MAX_SECONDS = 600.0

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._timer: Optional[threading.Timer] = None
        self._dir = ""

    def handle(self, req: dict) -> dict:
        """The ``device_trace`` op: start a capture into ``dir`` for
        ``seconds``, or (no ``dir``) read whether one is live."""
        out = str(req.get("dir") or "")
        if not out:
            # a status read: the CLI polls this until the timer fired
            return {"ok": True, "live": self._timer is not None,
                    "dir": self._dir}
        try:
            seconds = min(max(float(req.get("seconds", 5.0)), 0.0),
                          self.MAX_SECONDS)
        except (TypeError, ValueError):
            return {"ok": False, "error": "seconds must be a number"}
        with self._lock:
            if self._timer is not None:
                return {"ok": False, "live": True, "dir": self._dir,
                        "error": "a device trace is already live"}
            try:
                import jax

                os.makedirs(out, exist_ok=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(out, profiler_options=opts)
            except Exception as e:
                log.warning("device-trace capture unavailable (%s)", e)
                return {"ok": False, "error": f"profiler: {e}"}
            self._dir = out
            self._timer = threading.Timer(seconds, self.stop)
            self._timer.daemon = True
            self._timer.start()
        log.info("capturing a %.1f s device trace into %s", seconds, out)
        return {"ok": True, "live": True, "dir": out, "seconds": seconds}

    def stop(self) -> None:
        """End the live capture, if any (the timer, and shutdown: a
        profiler session must not outlive the server)."""
        with self._lock:
            timer, self._timer = self._timer, None
            if timer is None:
                return
            timer.cancel()
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception:
                log.debug("device-trace stop failed", exc_info=True)
                return
        obs.search_device_trace(self._dir)


class SearchService:
    """Holds one live search per experiment key."""

    def __init__(self) -> None:
        # key -> (params-fingerprint, search)
        self._searches: Dict[str, Tuple[str, object]] = {}
        # key -> (storage dir, its open handle): see _get_storage
        self._storages: Dict[str, Tuple[str, HistoryStorage]] = {}
        self._lock = threading.Lock()
        # one lock per key, held across the whole ingest+evolve+save:
        # a timed-out client's next request for the same storage must
        # queue behind the in-flight one — concurrent ingest would clear
        # the archives mid-evolve (set_occupied_buckets) and corrupt the
        # shared checkpoint
        self._key_locks: Dict[str, threading.Lock] = {}
        # parallel.mesh.device_summary() of the backend the searches run
        # on; None until the first search is built (a ping must not be
        # what initialises the device backend)
        self._device: Optional[dict] = None
        self.device_trace = DeviceTraceCapture()

    def handle(self, req: dict) -> dict:
        op = req.get("op")
        if op == "ping":
            resp = {"ok": True, "searches": len(self._searches)}
            if self._device is not None:
                resp["device"] = self._device
        elif op == "search":
            # the root of the request's span tree (obs/spans.py)
            with obs.search_phase("handle"):
                resp = self._search(req)
        elif op == "device_trace":
            resp = self.device_trace.handle(req)
        else:
            resp = {"ok": False, "error": f"unknown op {op!r}"}
        obs.sidecar_request(str(op), bool(resp.get("ok")))
        return resp

    def _get_search(self, key: str, params: dict, checkpoint: str):
        fp = json.dumps(params, sort_keys=True)
        with self._lock:
            cached = self._searches.get(key)
        if cached is not None and cached[0] == fp:
            search = cached[1]
            self._maybe_reload(search, checkpoint)
            return search, False
        # build OUTSIDE the global lock: jit construction can take
        # seconds and must not block ping or other keys' requests — the
        # caller already holds this key's lock, which serializes
        # same-key requests (ADVICE r4)
        from namazu_tpu.models.search import build_search_from_params

        search = build_search_from_params(params)
        if self._device is None:
            from namazu_tpu.parallel.mesh import device_summary

            self._device = device_summary()
            log.info("searching on %s/%s x%d", self._device["platform"],
                     self._device["kind"], self._device["count"])
        if checkpoint and os.path.exists(checkpoint):
            try:
                search.load(checkpoint)
                log.info("loaded checkpoint %s (gen %d)",
                         checkpoint, search.generations_run)
            except Exception:
                log.exception("checkpoint %s not loadable; fresh "
                              "search", checkpoint)
        with self._lock:
            self._searches[key] = (fp, search)
        return search, True

    def _get_storage(self, key: str, storage_dir: str) -> HistoryStorage:
        """The key's storage handle, caught up with the runs stored
        since its last request (``refresh()``: from the handle's
        watermark on, where a fresh ``load_storage`` of a history
        nobody writes a watermark for — a tenant's synthesised one —
        would walk every stored run at every request). Opened anew
        where the key has none, where the request names another dir,
        and where the refresh raises (the dir went away or came back
        with fewer runs). A backend that has only the default
        ``refresh()`` says nothing about what its handle remembers
        from ``init()``, so none of its handles is kept. Called under
        the key's lock."""
        kept = self._storages.get(key)
        if kept is not None and kept[0] == storage_dir:
            try:
                kept[1].refresh()
                return kept[1]
            except Exception as e:
                log.info("storage handle of %s dropped (%s); opening it "
                         "anew", storage_dir, e)
        if kept is not None:
            with self._lock:
                del self._storages[key]
            kept[1].close()
        storage = load_storage(storage_dir)
        if type(storage).refresh is not HistoryStorage.refresh:
            with self._lock:
                self._storages[key] = (storage_dir, storage)
        return storage

    def _maybe_reload(self, search, checkpoint: str) -> None:
        """Reload a cached search whose on-disk checkpoint is AHEAD of
        it: the two homes of the search are interchangeable
        mid-experiment, so runs under the in-process config may have
        evolved and saved since this key's last request, and serving
        the next one from the stale in-memory state would overwrite
        those generations at the next save (lost update, ADVICE r4).
        generations_run is monotonic, so disk-ahead detection is one
        npz field read."""
        if not checkpoint or not os.path.exists(checkpoint):
            return
        try:
            with np.load(checkpoint) as z:
                disk_gen = (int(z["generations_run"])
                            if "generations_run" in z else -1)
        except Exception:
            return  # unreadable/corrupt: keep the live state
        if disk_gen > search.generations_run:
            try:
                search.load(checkpoint)
                log.info(
                    "reloaded checkpoint %s: disk at gen %d, cached "
                    "search at %d (an in-process search ran between "
                    "requests)", checkpoint, disk_gen,
                    search.generations_run)
            except Exception:
                log.exception("newer checkpoint %s not loadable; "
                              "keeping cached state", checkpoint)

    def _key_lock(self, key: str) -> threading.Lock:
        with self._lock:
            return self._key_locks.setdefault(key, threading.Lock())

    def _search(self, req: dict) -> dict:
        key = str(req.get("key") or req.get("storage") or "default")
        lock = self._key_lock(key)
        with obs.search_phase("lock_wait"):
            lock.acquire()
        try:
            return self._search_locked(key, req)
        finally:
            lock.release()

    def _search_locked(self, key: str, req: dict) -> dict:
        from namazu_tpu.models.ingest import IngestParams, ingest_history

        params = req.get("search_params") or {}
        checkpoint = str(req.get("checkpoint") or "")
        storage_dir = req.get("storage")
        with obs.search_phase("load") as opened:
            search, fresh = self._get_search(key, params, checkpoint)
            try:
                storage = (self._get_storage(key, str(storage_dir))
                           if storage_dir else None)
            except Exception as e:
                return {"ok": False, "error": f"storage: {e}"}
            if storage is not None and storage.last_open is not None:
                opened["runs"], opened["visited"] = storage.last_open
        ip = req.get("ingest_params") or {}
        if ip.get("knowledge"):
            # a sidecar-hosted search serves knowledge-wired tenants
            # too: its ingest pushes/pulls the global pool (below, via
            # IngestParams) and its candidate re-rank may consult the
            # shared surrogate — possibly our own loopback, which is
            # fine (each connection gets its own handler thread)
            from namazu_tpu.knowledge import shared_client
            from namazu_tpu.knowledge.client import pairs_fingerprint

            kc = shared_client(
                str(ip["knowledge"]),
                tenant=str(ip.get("knowledge_tenant") or ""),
                scenario=str(ip.get("knowledge_scenario") or ""))
            search.remote_surrogate = (
                lambda feats, _c=kc, _s=search:
                    _c.predict(feats, pairs_fp=pairs_fingerprint(_s.pairs)))
        references = ingest_history(
            search, storage,
            IngestParams(**{k: v for k, v in ip.items()
                            if k in IngestParams._fields}))
        if not references:
            return {"ok": True, "no_history": True,
                    "generations_run": search.generations_run}
        best = search.run(references,
                          generations=int(req.get("generations", 64)))
        if checkpoint:
            try:
                search.save(checkpoint)
            except Exception:
                log.exception("could not save checkpoint %s", checkpoint)
        return {
            "ok": True,
            "fitness": float(best.fitness),
            "delays": [float(x) for x in best.delays],
            "faults": [float(x) for x in best.faults],
            "generations_run": search.generations_run,
            "device": self._device,
        }


class SidecarServer:
    def __init__(self, host: str = "127.0.0.1", port: int = 10990,
                 knowledge=None):
        self.service = SearchService()
        # optional multi-tenant knowledge service (knowledge/service.py):
        # the sidecar is its host process, sharing the framed wire
        self.knowledge = knowledge
        self._host, self._port = host, port
        # the shared keep-alive serve loop (endpoint/framed.py): one
        # frame-hygiene/error-answering/span-context implementation
        # across the framed wires. Keep-alive matters here: knowledge
        # clients push and pull on every run of a campaign, and
        # re-paying TCP setup per request would tax exactly the
        # cold-run path the warm-start exists to speed up; one-shot
        # clients still work — their close is just the first EOF.
        self._srv: Optional[FramedServer] = None

    @property
    def port(self) -> int:
        assert self._srv is not None
        return self._srv.port

    def start(self) -> None:
        srv = FramedServer(self._dispatch, name="sidecar")
        srv.bind_tcp(self._host, self._port)
        srv.start()
        self._srv = srv
        log.info("search sidecar on %s:%d", self._host, self.port)

    def shutdown(self) -> None:
        # shutdown severs live keep-alive connections too, or "kill
        # the service" would leave already-connected clients talking
        # to a half-dead server instead of degrading cleanly
        srv, self._srv = self._srv, None
        if srv is not None:
            srv.shutdown()
            # the handler threads reach the searches' device state
            # through this object: see them out before the caller (a
            # process on its way to exit) lets go of it
            srv.join()
        self.service.device_trace.stop()
        if self.knowledge is not None:
            self.knowledge.close()

    def _dispatch(self, req: dict) -> dict:
        """Route one request: knowledge ops to the hosted knowledge
        service (an explicit refusal when none is configured, so clients
        can tell "no knowledge here" from a dead host and degrade),
        everything else to the search service."""
        op = req.get("op")
        from namazu_tpu.knowledge import KNOWLEDGE_OPS
        from namazu_tpu.obs import federation

        # observability ops (obs/federation.py): the sidecar's framed
        # wire doubles as a telemetry push target / fleet surface, so
        # knowledge-plane processes can aggregate without an HTTP stack
        obs_resp = federation.handle_obs_op(req)
        if obs_resp is not None:
            return obs_resp
        if op in KNOWLEDGE_OPS:
            if self.knowledge is None:
                resp = {"ok": False,
                        "error": "knowledge service not configured "
                                 "(start the sidecar with --pool-dir)"}
            else:
                resp = self.knowledge.handle(req)
            obs.sidecar_request(str(op), bool(resp.get("ok")))
            return resp
        resp = self.service.handle(req)
        if op == "ping" and self.knowledge is not None:
            # advertise the knowledge plane (and its version) so a
            # client can discover it from the same probe old clients
            # already send; a knowledge-less sidecar answers the
            # pre-knowledge shape unchanged
            resp["knowledge"] = True
            resp["knowledge_v"] = self.knowledge.VERSION
        return resp


def request(addr: str, req: dict, timeout: float = 300.0) -> dict:
    """One framed request/response against a sidecar at ``host:port``."""
    host, _, port = addr.rpartition(":")
    with socket.create_connection((host or "127.0.0.1", int(port)),
                                  timeout=timeout) as s:
        write_frame(s, req)
        resp = read_frame(s)
    if resp is None:
        raise ConnectionError(f"sidecar {addr}: connection closed")
    return resp


def serve_sidecar(host: str, port: int, pool_dir: str = "",
                  state_dir: str = "", telemetry_url: str = "") -> int:
    """CLI entry: serve until interrupted. ``pool_dir`` enables the
    multi-tenant knowledge service (doc/knowledge.md) on the same
    wire; ``telemetry_url`` pushes this process's metrics to a fleet
    aggregator so the sidecar shows up in the campaign's ``/fleet``
    view (doc/observability.md "Fleet telemetry")."""
    knowledge = None
    if pool_dir:
        from namazu_tpu.knowledge import KnowledgeService

        knowledge = KnowledgeService(pool_dir, state_dir=state_dir)
        log.info("knowledge service enabled: pool %s",
                 knowledge.pool_dir)
    server = SidecarServer(host, port, knowledge=knowledge)
    server.start()
    from namazu_tpu.obs import federation

    federation.ensure_self_relay(
        "sidecar",
        push_url=(telemetry_url
                  or os.environ.get("NMZ_TELEMETRY_URL", "")))
    # continuous profiling: where does sidecar time go (framed wire vs
    # surrogate scoring) — served over the framed `profile` op
    from namazu_tpu.obs import profiling

    profiling.ensure_profiler("sidecar")
    try:
        threading.Event().wait()
    except KeyboardInterrupt:
        pass
    finally:
        server.shutdown()
    return 0
