#!/usr/bin/env python3
"""The benchmark's launcher for the search sidecar — the ONE process of
a run that holds the chip(s).

It serves the program's ``SidecarServer`` and ``SearchService``
unchanged on the same framed wire, and adds what only the process that
holds the chip can do:

* a device check at start (exit 3 off a TPU, or with fewer chips than
  the cell asks for; ``--cpu N`` is the tests' dry run);
* per-request bookkeeping: wall start, duration, the depth of the
  storage as the request arrives, the reply's table and fitness, and
  the fused island step's own answer (its best table and fitness and
  its device-side generation counter, read where the program has
  already fetched them), and a handle on the fitness vector the
  reply's re-rank scored the whole population to (kept on the device,
  read after the window); and a count of compilations
  (``{"op": "bench_info"}``);
* with ``--trace 1``: host spans around ``SearchService.handle``,
  ingest, evolve and save (``jax.profiler.TraceAnnotation`` plus a host
  clock reading), ``bench_trace_start`` / ``bench_trace_stop`` around
  a slice of the window, and after the window ``bench_trace_reduce``
  (``benchmarks/trace_reduce.py``);
* ``bench_state``: outside the timed window, every answer recorded
  above and, per search, the device-resident inputs its last request
  evolved against (pair sample, novelty and failure rings, reference
  traces) go to an ``.npz``, with the population as it stands, the
  fitness the last reply's re-rank gave each of its tables, and the
  best fitness that one more dispatch of the window's own compiled
  fused step finds in it; ``bench_probe`` then reads the fused step's
  fitness of single tables of that population the parent names; the
  dump also says what each search HOLDS (release mode, order gap and
  window, whether it has a fault coin), for the parent to hold against
  what the request states; the parent holds all of it against the
  plain reference, which it works out from the storage files alone.

The program's own ``device_trace_dir`` is not used: it is a one-shot
capture of the first evolve and changes the ``search_params``
fingerprint.
"""

from __future__ import annotations

import argparse
import contextlib
import glob
import json
import os
import shutil
import signal
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class Book:
    """What the launcher records beside the program. Appends are
    GIL-atomic; readers copy."""

    def __init__(self, tracing: bool) -> None:
        self.tracing = tracing
        self.requests: list = []   # search requests, one dict each
        self.compiles: list = []   # [wall time, seconds] per lowering
        self.spans: dict = {}      # name -> [[wall start, seconds, arg]]
        self.last_refs: dict = {}  # search id -> last reference traces
        self.thread = threading.local()  # the serving thread's last answers
        self.rerank: dict = {}     # search id -> last re-rank's fitness
        self.population: dict = {}  # key -> the population as dumped

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span: a ``TraceAnnotation`` for the profiler and a
        host-clock row ``[wall start, seconds, arg]``; the body may set
        ``row[2]``."""
        import jax

        row = [time.time(), 0.0, None]
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(f"bench:{name}"):
                yield row
        finally:
            row[1] = time.perf_counter() - t0
            self.spans.setdefault(name, []).append(row)


def install_hooks(book: Book) -> None:
    """Bookkeeping around the program's calls — wrappers only, the
    program's code runs unchanged inside them."""
    import jax.monitoring

    import reference
    from namazu_tpu import sidecar as sc
    from namazu_tpu.models import ingest as ingest_mod
    from namazu_tpu.models.search import ScheduleSearch

    def on_duration(event, duration, **_kw):
        if event == LOWERING_EVENT:
            book.compiles.append([time.time(), float(duration)])

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    orig_ingest = ingest_mod.ingest_history

    def ingest_history(search, storage, p):
        if not book.tracing:
            refs = orig_ingest(search, storage, p)
        else:
            with book.span("ingest") as s:
                refs = orig_ingest(search, storage, p)
                try:
                    s[2] = int(storage.nr_stored_histories())
                except Exception:
                    pass
        book.last_refs[id(search)] = refs
        return refs

    ingest_mod.ingest_history = ingest_history

    orig_handle = sc.SearchService.handle

    def handle(self, req):
        if req.get("op") != "search":
            return orig_handle(self, req)
        try:
            depth = reference.stored_depth(str(req.get("storage")))
        except (OSError, ValueError, KeyError):
            depth = None
        book.thread.answer = None
        wall, t0 = time.time(), time.perf_counter()
        if book.tracing:
            with book.span("handle"):
                resp = orig_handle(self, req)
        else:
            resp = orig_handle(self, req)
        book.requests.append({
            "wall": wall, "seconds": time.perf_counter() - t0,
            "key": str(req.get("key") or req.get("storage") or "default"),
            "ok": bool(resp.get("ok")),
            "no_history": bool(resp.get("no_history")),
            "error": resp.get("error"),
            "generations_run": resp.get("generations_run"),
            "depth": depth, "fitness": resp.get("fitness"),
            "delays": resp.get("delays"), "fused": book.thread.answer,
        })
        return resp

    sc.SearchService.handle = handle

    # the fused step keeps the scorer it imported; the reply's re-rank
    # (``_surrogate_pick``) looks it up in ``ops.schedule`` at each call
    import namazu_tpu.parallel.islands  # noqa: F401
    from namazu_tpu.ops import schedule as sch

    orig_score = sch.score_population_multi

    def score_population_multi(*a, **kw):
        out = orig_score(*a, **kw)
        book.thread.rerank = out[0]
        return out

    sch.score_population_multi = score_population_multi

    orig_run = ScheduleSearch.run

    def run(self, *a, **kw):
        book.thread.rerank = None
        if book.tracing:
            with book.span("evolve"):
                best = orig_run(self, *a, **kw)
        else:
            best = orig_run(self, *a, **kw)
        book.rerank[id(self)] = book.thread.rerank
        # the fused step's own answer: the host copy the program made of
        # its best table and fitness, and its generation counter
        snap = getattr(self, "_best_snapshot", None)
        if snap is not None:
            book.thread.answer = {"delays": snap[0], "fitness": snap[2],
                                 "gen": int(self._state.gen)}
        return best

    ScheduleSearch.run = run

    if book.tracing:
        orig_save = ScheduleSearch.save

        def save(self, *a, **kw):
            with book.span("save"):
                return orig_save(self, *a, **kw)

        ScheduleSearch.save = save


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def dump_state(service, book: Book, req: dict) -> dict:
    """Every answer of the run and, for each search, the inputs its
    last request evolved against as they sit on the device, to an
    ``.npz`` (module docstring)."""
    import numpy as np

    requests = list(book.requests)
    H = max([len(r["delays"]) for r in requests if r["delays"]] or [0])
    out = {"reply_delays": np.zeros((len(requests), H), np.float32),
           "fused_delays": np.zeros((len(requests), H), np.float32)}
    rows = []
    for i, r in enumerate(requests):
        fused = r["fused"] or {}
        if r["delays"]:
            out["reply_delays"][i] = r["delays"]
        if fused:
            out["fused_delays"][i] = fused["delays"]
        rows.append({k: r[k] for k in (
            "wall", "seconds", "key", "ok", "no_history", "error",
            "generations_run", "depth", "fitness")}
            | {"fused_fitness": fused.get("fitness"),
               "fused_gen": fused.get("gen")})
    searches = {}
    for n, (key, (_fp, search)) in enumerate(
            sorted(service._searches.items())):
        with service._key_lock(key):
            _encs, trace, pairs, archive, failures = \
                search._device_inputs_fused(book.last_refs[id(search)])
            resident = {
                "pairs": pairs, "archive": archive, "failures": failures,
                "labels": search.archive_labels,
                "hint_ids": trace.hint_ids, "arrival": trace.arrival,
                "mask": trace.mask}
            for name, value in resident.items():
                out[f"s{n}_{name}"] = np.asarray(value)
            population = np.asarray(search._fetch_population()[0],
                                    np.float32)
            book.population[key] = out[f"s{n}_population"] = population
            rerank = book.rerank.get(id(search))
            if rerank is not None:
                out[f"s{n}_rerank_fitness"] = np.asarray(rerank)
            probe = fused_best_of(search, book)
            searches[key] = {
                "n": n, "archive_n": int(search._archive_n),
                "probe_fitness": probe,
                "failure_n": int(search._failure_n),
                "population": int(search._state.pop.delays.shape[0]),
                "shard_rows": sorted(
                    int(s.data.shape[0]) for s in
                    search._state.pop.delays.addressable_shards),
                "novelty_scale": float(search.novelty_scale()),
                # what the search holds, for the parent to hold against
                # what the request states
                "fault_coin": search._coin is not None,
                "release_mode": ("reorder" if search.cfg.weights.order_mode
                                 else "delay"),
                "order_gap": float(search.cfg.weights.order_gap),
                "order_window": float(search.cfg.weights.order_window)}
    np.savez(req["out"], **out)
    import jax

    return {"ok": True, "out": req["out"], "requests": rows,
            "searches": searches,
            "operand": ("bfloat16" if jax.default_backend() == "tpu"
                        else "float32")}


def fused_best_of(search, book: Book) -> float:
    """One more dispatch of the fused step the window drove — the same
    compiled program, the search's own state and resident inputs: the
    best fitness it finds in the population as it stands (its first
    generation's)."""
    import jax.numpy as jnp
    import numpy as np

    _encs, trace, pairs, archive, failures = \
        search._device_inputs_fused(book.last_refs[id(search)])
    search._place_state()
    fused = search._fused_step_for(search.cfg.fused_chunk)
    bias = (None if search.guidance is None
            else jnp.asarray(search.guidance.mutation_bias()))
    search._state, fit_hist = fused(
        search._state, search._key, trace, pairs, archive, failures, None,
        jnp.asarray(search.novelty_scale(), jnp.float32), bias)
    return float(np.asarray(fit_hist)[0])


def probe_tables(service, book: Book, req: dict) -> dict:
    """The fitness the fused step gives single tables of the dumped
    population: every row of the population is set to the one table, so
    the best of the first generation IS that table's fitness."""
    import numpy as np

    key = str(req["key"])
    _fp, search = service._searches[key]
    population = book.population[key]
    fitness = []
    with service._key_lock(key):
        for row in req["rows"]:
            pop = search._state.pop
            search._state = search._state._replace(pop=pop._replace(
                delays=np.tile(population[int(row)],
                               (population.shape[0], 1))))
            fitness.append(fused_best_of(search, book))
    return {"ok": True, "fitness": fitness}


class Tracer:
    def __init__(self, out_dir: str) -> None:
        self.out_dir = out_dir
        self.t_start = None

    def start(self) -> dict:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench:slice_start"):
            self.t_start = time.time()
        return {"ok": True, "wall": self.t_start}

    def stop(self) -> dict:
        """Inside the window: end the slice, nothing else."""
        import jax

        with jax.profiler.TraceAnnotation("bench:slice_stop"):
            self.t_stop = time.time()
        jax.profiler.stop_trace()
        return {"ok": True, "slice_wall_s": self.t_stop - self.t_start}

    def reduce(self, service, book: Book, req: dict) -> dict:
        """After the window: the trace to quantities. The scope of each
        device op comes from the compiled text of the fused step the
        window ran (lowered again here; the compile is a cache hit)."""
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import trace_reduce

        found = sorted(glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            return {"ok": False, "error": "the profiler wrote no trace"}
        scopes = {}
        try:
            scopes["jit_fused"] = trace_reduce.hlo_scopes(
                fused_step_text(service, book, str(req["key"])))
        except Exception as e:  # the quantities by scope are then absent
            print(f"sidecar_main: no HLO text for scopes: {e!r}",
                  file=sys.stderr, flush=True)
        if req.get("probe"):
            trace_reduce.write_probe(found[-1], req["probe"])
        events = trace_reduce.xplane_to_events(found[-1], scopes)
        reduced = trace_reduce.reduce(
            events, tuple(req.get("kernels") or trace_reduce.KERNELS))
        if req.get("probe"):
            with open(req["probe"].replace(".json", ".events.json"),
                      "w") as f:
                json.dump(sorted(events,
                                 key=lambda e: e["start_ns"])[:1500], f)
        xplane_bytes = os.path.getsize(found[-1])
        shutil.rmtree(os.path.join(self.out_dir, "plugins"),
                      ignore_errors=True)  # hundreds of MB at full width
        return {"ok": True, "reduced": reduced,
                "xplane_bytes": xplane_bytes}


def fused_step_text(service, book: Book, key: str) -> str:
    """The compiled text of the fused island step as the search of
    ``key`` dispatches it (``ScheduleSearch._run_fused``)."""
    import jax.numpy as jnp

    _fp, search = service._searches[key]
    with service._key_lock(key):
        _encs, trace, pairs, archive, failures = \
            search._device_inputs_fused(book.last_refs[id(search)])
        search._place_state()
        fused = search._fused_step_for(search.cfg.fused_chunk)
        nov = jnp.asarray(search.novelty_scale(), jnp.float32)
        bias = (None if search.guidance is None
                else jnp.asarray(search.guidance.mutation_bias()))
        return fused.lower(search._state, search._key, trace, pairs,
                           archive, failures, None, nov,
                           bias).compile().as_text()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--listen", required=True, metavar="HOST:PORT")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--trace-dir", default="")
    ap.add_argument("--cpu", type=int, default=0, metavar="N",
                    help="dry run on N virtual CPU devices (tests)")
    args = ap.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.cpu}").strip()
    sys.path.insert(0, ROOT)

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if not args.cpu and device["platform"] != "tpu":
        print("sidecar_main: JAX found no TPU (no CPU fallback)",
              file=sys.stderr)
        return 3
    if device["count"] != args.chips:
        print(f"sidecar_main: the cell asks for {args.chips} chip(s), "
              f"JAX reports {device['count']}", file=sys.stderr)
        return 3

    from namazu_tpu.sidecar import SidecarServer

    book = Book(tracing=bool(args.trace))
    install_hooks(book)
    tracer = Tracer(args.trace_dir)
    host, _, port = args.listen.rpartition(":")
    server = SidecarServer(host or "127.0.0.1", int(port))
    program_dispatch = server._dispatch

    def dispatch(req: dict) -> dict:
        op = req.get("op")
        if op == "bench_info":
            return {"ok": True, "device": device,
                    "memory_peak_bytes": memory_peak_bytes(),
                    "compiles": list(book.compiles),
                    "spans": {k: list(v) for k, v in book.spans.items()}}
        if op == "bench_state":
            return dump_state(server.service, book, req)
        if op == "bench_probe":
            return probe_tables(server.service, book, req)
        if op == "bench_trace_start":
            return tracer.start()
        if op == "bench_trace_stop":
            return tracer.stop()
        if op == "bench_trace_reduce":
            return tracer.reduce(server.service, book, req)
        return program_dispatch(req)

    server._dispatch = dispatch
    server.start()
    print(f"sidecar_main: ready on {args.listen}, device {device}",
          flush=True)
    stop = threading.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        signal.signal(sig, lambda *_: stop.set())
    try:
        stop.wait()
    finally:
        server.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
