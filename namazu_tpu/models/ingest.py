"""History ingest: stored experiment runs -> search-plane state.

Shared by the in-process policy (policy/tpu.py) and the persistent
search sidecar (namazu_tpu/sidecar.py): both must featurize the same
history the same way — arrival-anchored references, realized-release
embeddings, failure-derived demonstration seeds, hint-space guard — or
a schedule trained in one home would not replay in the other.
"""

from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict
from typing import Hashable, List, NamedTuple, Optional

import numpy as np

from namazu_tpu import obs
from namazu_tpu.ops import trace_encoding as te
from namazu_tpu.signal.base import HINT_SPACE
from namazu_tpu.utils.log import get_logger

log = get_logger("models.ingest")

#: most recent runs whose labeled features feed the shared surrogate
#: per ingest — bounds the extra featurize cost (and the wire payload)
#: on long histories; older runs were already pushed by earlier ingests
MAX_EXAMPLE_PUSH = 64

#: byte bound of the encoded-run records this process keeps (a module
#: constant, as models/search.py's EMBED_CHUNK is): a record's arrays
#: are 27.6 KB at L 1536 and 2.3 KB at L 128 (plus 1 KB for a failure's
#: seed at H 256), so this holds a 5,000-run hunt of 1,500-event traces
#: (140 MB) or some 75,000 18-event runs
RUN_CACHE_BYTES = 256 << 20


def _push_surrogate_examples(client, search, encoded) -> None:
    """Stream (digest, features, reproduced?) for the most recent runs
    to the knowledge service's shared surrogate. Runs AFTER
    ``set_occupied_buckets``: features only pool between searches with
    the same precedence-pair sample, and the pairs are final once the
    occupied buckets are set — the fingerprint scopes the server-side
    store (knowledge/service.py). Best-effort: surrogate sharing is an
    accelerator, never a dependency."""
    from namazu_tpu.knowledge.client import pairs_fingerprint
    from namazu_tpu.models.failure_pool import trace_digest

    try:
        examples = []
        recent = encoded[-MAX_EXAMPLE_PUSH:]
        rows = search._embed([enc_rt for _, enc_rt, _, _ in recent])
        for (enc, enc_rt, ok, _seed), feats in zip(recent, rows):
            if search.guidance_feats is not None:
                # guided campaigns train on [precedence | DAG-shape];
                # the widened K keys a separate service-side store, so
                # the walling holds without any new wire field
                feats = np.concatenate(
                    [feats, search._guidance_feats_of(enc_rt, enc)])
            examples.append({
                "digest": trace_digest(enc_rt),
                "feats": [float(x) for x in feats],
                "label": 0.0 if ok else 1.0,
            })
        client.push(examples=examples,
                    pairs_fp=pairs_fingerprint(search.pairs))
    except Exception:
        log.exception("could not push surrogate examples")


class IngestParams(NamedTuple):
    H: int = te.DEFAULT_H
    L: int = 0  # explicit trace-length cap; 0 = policy defaults
    release_mode: str = "delay"  # "delay" | "reorder"
    reference_mode: str = "recent"  # "recent" | "envelope"
    max_interval: float = 0.1  # seed-table clip (seconds)
    max_reference_traces: int = 4
    max_seed_genomes: int = 16
    # order mode materializes [population, L] release times per
    # reference trace and generation: a stored run LONGER than this is
    # cut (and the benchmark's reference refuses such a history); a
    # shorter one is encoded at its own length quantum, as in delay mode
    order_mode_max_l: int = 4096
    # shared failure-signature pool directory ("" = off): every ingested
    # failure is persisted there, and pooled signatures from OTHER runs/
    # batches/experiments are folded into the failure archive + seeds —
    # the cross-batch memory that keeps a search from training on the
    # 1-2 failures its own phase A happened to record
    # (models/failure_pool.py)
    failure_pool: str = ""
    # knowledge-service address "host:port" ("" = off): the remote
    # backend behind the same pool interface (doc/knowledge.md) —
    # failures stream to the fleet-global pool and pooled signatures
    # from OTHER campaigns/hosts fold back in, with graceful degradation
    # to the local pool (or none) on outage. tenant/scenario identify
    # the pushing campaign and the experiment fingerprint for
    # warm-start keying and the shared surrogate's feature-space scoping
    knowledge: str = ""
    knowledge_tenant: str = ""
    knowledge_scenario: str = ""
    # causality guidance (doc/search.md): rebuild the per-campaign
    # relation CoverageMap from the stored history on every ingest (a
    # pure function of the recorded runs — no extra persistence to
    # corrupt), warm-start its frontier from the knowledge service's
    # pooled coverage, and push the campaign's own bits back. 0 width/
    # window = the guidance defaults.
    guidance: bool = False
    guidance_width: int = 0
    guidance_window: int = 0


def failure_seed(trace, H: int, max_interval: float):
    """Per-bucket delay table replaying this failure's injected delays:
    for the first released event of each bucket, ``release - arrival``
    IS the delay the recording policy injected on it (absolute times —
    no anchor needed). Replayed against similar arrivals, the table
    re-enacts the failure's interleaving up to the system's reactions;
    it seeds the search as a demonstration (models/search.py
    seed_population)."""
    seed = np.zeros((H,), np.float32)
    seen = set()
    got = False
    for a in trace:
        arr = getattr(a, "event_arrived", None)
        rel = a.triggered_time
        if not arr or not rel:
            continue
        hint = getattr(a, "event_hint", "") or \
            f"{a.event_class or a.class_name()}:{a.entity_id}"
        b = te.hint_bucket(hint, H)
        if b in seen:
            continue
        seen.add(b)
        seed[b] = min(max(rel - arr, 0.0), max_interval)
        got = True
    return seed if got else None


class _RunRecord(NamedTuple):
    """What ``_ingest_history`` keeps of one stored run: both encoded
    views, the verdict, the failure's demonstration seed, the events it
    recorded and the hint space it was stamped with. A run of another
    hint space is never encoded: its views are None."""

    enc: Optional[te.EncodedTrace]
    enc_rt: Optional[te.EncodedTrace]
    ok: bool
    seed: Optional[np.ndarray]
    n_events: int
    stamp: str

    def arrays(self) -> List[np.ndarray]:
        if self.enc is None:
            return []
        # the two views share every array but the times
        held = [self.enc.hint_ids, self.enc.entity_ids, self.enc.mask,
                self.enc.faultable, self.enc.arrival, self.enc_rt.arrival]
        return held if self.seed is None else held + [self.seed]


class RunRecordCache:
    """Encoded-run records, least recently used first, at most
    ``max_bytes`` of arrays. One per process (``_RUN_RECORDS``), not per
    storage object: the sidecar loads its storage anew for every request
    and serves several keys from several workers at once, so the lock
    covers the dict and never a read or an encode (two workers that miss
    the same run both encode it; the later ``put`` wins, with an equal
    record).

    A record is looked up by what it was computed under and handed out
    only while the run's signature still equals the one it was stored
    with; the signature is taken BEFORE the files are read, so a record
    is never older than its signature, and a rewritten run replaces its
    record at the next request. The arrays of a stored record are
    read-only: every request shares them.

    A history larger than the bound, walked in stored order as ingest
    walks it, evicts each record before the walk comes round to it
    again: every run misses, and a request costs what it cost without
    the cache plus the bookkeeping."""

    #: what a record weighs beyond its arrays (objects, key, dict slot)
    RECORD_OVERHEAD = 1024

    def __init__(self, max_bytes: int) -> None:
        self.max_bytes = max_bytes
        self._lock = threading.Lock()
        self._records: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._bytes = 0

    def __len__(self) -> int:
        return len(self._records)

    def get(self, key: tuple, signature: Hashable) -> Optional[_RunRecord]:
        with self._lock:
            held = self._records.get(key)
            if held is None or held[0] != signature:
                return None
            self._records.move_to_end(key)
            return held[1]

    def put(self, key: tuple, signature: Hashable,
            rec: _RunRecord) -> None:
        arrays = rec.arrays()
        for a in arrays:
            a.setflags(write=False)
        size = self.RECORD_OVERHEAD + sum(a.nbytes for a in arrays)
        with self._lock:
            old = self._records.pop(key, None)
            if old is not None:
                self._bytes -= old[2]
            if size > self.max_bytes:
                return
            self._records[key] = (signature, rec, size)
            self._bytes += size
            while self._bytes > self.max_bytes:
                _, (_, _, dropped) = self._records.popitem(last=False)
                self._bytes -= dropped


_RUN_RECORDS = RunRecordCache(RUN_CACHE_BYTES)


def _read_run(storage, i: int):
    """``(trace, successful, hint-space stamp, run phases)`` of stored
    run ``i``; raises what the storage raises for a run it will not
    serve. Absent stamps default to "content-v1", the same convention
    the checkpoint loader uses (te.checkpoint_hint_space): every
    recording made by a stamping build carries the tag
    (cli/run_cmd.py). The phases are the rows an observed run stored of
    itself (``metadata["phases"]``), None where it stored none."""
    trace = storage.get_stored_history(i)
    ok = storage.is_successful(i)
    try:
        meta = storage.get_metadata(i) or {}
    except Exception:
        meta = {}
    return trace, ok, meta.get("hint_space", "content-v1"), \
        meta.get("phases")


def _encode_run(trace, ok: bool, stamp: str, cap: Optional[int],
                pads: bool, p: IngestParams) -> _RunRecord:
    if stamp != HINT_SPACE:
        return _RunRecord(None, None, ok, None, len(trace), stamp)
    # a cap that ``pads`` (an explicit ``trace_length``) is every run's
    # length; one that does not (the order mode's) only cuts: a run
    # under it keeps its own length quantum
    L = cap if cap is not None and (pads or len(trace) > cap) else None
    # two views of every run, one encode pass: arrival-anchored =
    # counterfactual reference; realized = archive embedding
    enc, enc_rt = te.encode_trace_views(trace, L=L, H=p.H)
    seed = None if ok else failure_seed(trace, p.H, p.max_interval)
    return _RunRecord(enc, enc_rt, ok, seed, len(trace), stamp)


class _Stages:
    """Wall time accumulated per ingest stage over the per-run loops:
    one ``obs.search_phase_observed`` each per ingest (a span per
    stored run would cost more than it shows)."""

    def __init__(self) -> None:
        # stage -> [first start, seconds, {"pieces": n, <counts>}]
        self.stages: dict = {}

    def add(self, stage: str, start: float, pieces: int = 1,
            **counts: int) -> float:
        """Charge ``start``..now to ``stage``, and add ``counts`` (what
        the stage walked: ``events=``, ``groups=``) to its row's
        arguments; returns now."""
        now = time.monotonic()
        row = self.stages.setdefault(stage, [start, 0.0, {}])
        row[1] += now - start
        for name, n in dict(counts, pieces=pieces).items():
            row[2][name] = row[2].get(name, 0) + n
        return now

    def report(self) -> None:
        for stage, (first, seconds, counts) in self.stages.items():
            obs.search_phase_observed(stage, seconds, first, **counts)


def ingest_history(search, storage, p: IngestParams) -> List:
    """Feed stored traces into the search's archives; return the
    reference traces to evolve against. One ``ingest`` phase per call
    in whichever home runs it, with its stages beside it
    (``ingest_read`` / ``ingest_encode`` / ``ingest_embed`` /
    ``ingest_pool``, doc/observability.md "Request spans"). The embed
    stage hands the whole history to the device at once
    (``ScheduleSearch.embed_batch``); its row's ``pieces`` = device calls and
    ``groups`` = padded trace lengths among the runs (all embedded at
    the search's length class, by one program); the encode row's ``events`` = events of the runs
    ingested and ``cached`` = how many of those runs came from the
    encoded-run records (``RunRecordCache``) instead of the storage:
    every stored run charges ``ingest_read`` its signature (and its
    read on a miss) and ``ingest_encode`` the rest (the encode on a
    miss), so both stages stay per stored run of the history INGESTED,
    parsed or not.

    References are the most recent SUCCESSFUL runs (padded with failures
    only when no success exists yet): the counterfactual asks "what
    would delaying bucket X do to the interleaving the next run will
    naturally produce", so it must be anchored on arrivals close to what
    an ordinary run records. The failure traces instead supply the
    *target* features through the failure archive (bug-affinity term) —
    embedded at their REALIZED release times, where a delay-induced
    failure's signature actually lives (te.encode_trace docstring).
    """
    if storage is None:
        return []
    stages = _Stages()
    with obs.search_phase("ingest") as attrs:
        try:
            return _ingest_history(search, storage, p, stages, attrs)
        finally:
            stages.report()


def _ingest_history(search, storage, p: IngestParams, stages: _Stages,
                    attrs: dict) -> List:
    t = time.monotonic()
    try:
        n = storage.nr_stored_histories()
    except Exception:
        return []
    attrs["runs"] = n
    obs.ingest_runs(n)
    stages.add("ingest_read", t)
    # causality guidance: wire the map BEFORE any archive write so the
    # DAG-shape feature fragments land slot-aligned with the archive.
    # ``fresh``: every ingest re-feeds the WHOLE stored history, so the
    # map rebuilds from scratch each time — a persistent (sidecar)
    # search serving repeated requests must not double-observe
    gmap = None
    if p.guidance:
        gmap = search.enable_guidance(p.guidance_width or None,
                                      p.guidance_window or None,
                                      fresh=True)
    pads = p.L > 0
    if pads:
        cap: Optional[int] = p.L
    elif p.release_mode == "reorder":
        cap = p.order_mode_max_l
    else:
        cap = None  # delay mode scores long traces blockwise
    encoded = []
    skipped_unstamped = events = cached = 0
    for i in range(n):
        t = time.monotonic()
        # what the loop needs of a stored run is a pure function of its
        # two files and (cap, pads, H, max_interval, HINT_SPACE): a run
        # whose signature has not changed since a request encoded it is
        # taken from the record kept then, and nothing of it is opened
        signature = storage.run_signature(i)
        key = rec = None
        if signature is not None:
            key = (os.path.abspath(storage.run_dir(i)), cap, pads, p.H,
                   p.max_interval, HINT_SPACE)
            rec = _RUN_RECORDS.get(key, signature)
        hit = rec is not None
        run = None
        if not hit:
            try:
                run = _read_run(storage, i)
            except Exception:
                stages.add("ingest_read", t)
                continue
        t = stages.add("ingest_read", t)
        if run is not None:
            *run, phases = run
            rec = _encode_run(*run, cap, pads, p)
            if key is not None:
                _RUN_RECORDS.put(key, signature, rec)
                # the run's own cycle by phase, observed where its
                # record is first kept: once per run and process, until
                # the record is evicted or the run rewritten. A storage
                # without signatures keeps no record and would observe
                # its whole history again at every request
                obs.run_phases_observed(phases)
        # runs recorded under a different replay-hint format hash into a
        # different bucket space — training on them would deliver
        # arbitrary delays under a "searched schedule" log
        if rec.stamp != HINT_SPACE:
            skipped_unstamped += 1
            continue
        if rec.enc.truncated:
            log.warning(
                "trace %d truncated: %d events beyond the L=%d cap were "
                "dropped from scoring (%s)", i, rec.enc.truncated, cap,
                "configured trace_length" if p.L > 0
                else "order-mode memory bound")
        encoded.append((rec.enc, rec.enc_rt, rec.ok, rec.seed))
        events += rec.n_events
        cached += hit
        stages.add("ingest_encode", t, events=rec.n_events, cached=int(hit))
    obs.ingest_cached_runs(cached)
    obs.ingest_events(events)
    if skipped_unstamped:
        log.warning(
            "%d stored run(s) recorded in another hint space were "
            "excluded from search ingest (this build: %s); re-record "
            "under the current build to train on them",
            skipped_unstamped, HINT_SPACE)
    # cross-batch failure pool: persist this storage's failures, then
    # pull in signatures recorded by OTHER runs/batches (dedup by
    # content digest — re-ingesting our own failures is a no-op). With a
    # knowledge service configured the same flow additionally rides the
    # fleet-global pool: push own failures up, pull the fleet's down —
    # and an outage silently degrades to the local-only path (the
    # client logs one warning; a campaign never fails on knowledge)
    pooled = []
    client = None
    t = time.monotonic()
    if p.knowledge:
        from namazu_tpu.knowledge import shared_client

        client = shared_client(p.knowledge, tenant=p.knowledge_tenant,
                               scenario=p.knowledge_scenario)
    if p.failure_pool or client is not None:
        from namazu_tpu.models.failure_pool import (
            entry_to_jsonable,
            pool_add,
            pool_load,
            trace_digest,
        )

        own = set()
        push_entries = []
        for enc, enc_rt, ok, seed in encoded:
            if ok:
                continue
            try:
                own.add(trace_digest(enc_rt))
                if p.failure_pool:
                    pool_add(p.failure_pool, enc_rt, enc, seed, p.H)
                if client is not None:
                    push_entries.append(
                        entry_to_jsonable(enc_rt, enc, seed, p.H))
            except Exception:
                log.exception("could not pool failure signature")
        if p.failure_pool:
            pooled = pool_load(p.failure_pool, p.H, exclude=own)
        if client is not None:
            client.push(entries=push_entries)  # None on outage: fine
            have = own | {e.digest for e in pooled}
            # the coverage-frontier warm-start piggybacks on the entry
            # pull (one round trip): relations the FLEET already
            # exercised are not this campaign's frontier. An outage
            # returns None — local-only coverage, never a failed
            # ingest (the cardinal knowledge rule).
            space = (None if gmap is None
                     else {"H": gmap.H, "w": gmap.width,
                           "win": gmap.window})
            remote = client.pull(p.H, exclude=have,
                                 coverage_space=space)
            if remote is not None:
                r_entries, _table = remote[0], remote[1]
                # the cold-run warm-start: fleet signatures this search
                # has never seen are about to enter its archives
                fresh = sum(
                    1 for e in r_entries
                    if not search.has_failure_signature(e.digest))
                obs.knowledge_warmstart("archive", fresh)
                pooled = pooled + r_entries
                if gmap is not None:
                    obs.knowledge_warmstart(
                        "coverage", gmap.merge_bits(remote[2]))
        if pooled:
            log.info("folding %d pooled failure signature(s) into the "
                     "search (pool %s%s)", len(pooled),
                     p.failure_pool or "-",
                     f", knowledge {p.knowledge}" if p.knowledge else "")
        t = stages.add("ingest_pool", t)
    # concentrate the feature pairs on the buckets the experiment
    # actually produces BEFORE embedding anything (a pair change clears
    # the archives; the loop below repopulates them in full)
    occupied = sorted(
        {int(b) for enc, _, _, _ in encoded
         for b in enc.hint_ids[enc.mask]}
        | {int(b) for e in pooled
           for b in e.realized.hint_ids[e.realized.mask]})
    search.set_occupied_buckets(occupied)
    seeds = [s for _, _, ok, s in encoded if not ok and s is not None]
    # most recent failures first: when seeds outnumber slots the
    # freshest demonstrations win; pooled demonstrations (already
    # newest-first) fill the remaining slots
    seeds = seeds[::-1] + [e.seed for e in pooled if e.seed is not None]
    if seeds:
        search.seed_population(seeds[: p.max_seed_genomes])
    if gmap is not None:
        # fold every known run's realized ordering into the coverage
        # frontier — pooled entries too, and BEFORE the archive-dedupe
        # skip below: a checkpoint-restored search may already hold a
        # signature whose relations this (fresh) map has never seen
        from namazu_tpu.guidance import bucket_sequence_from_encoded

        for e in pooled:
            gmap.observe(bucket_sequence_from_encoded(e.realized))
        for _enc, enc_rt, _ok, _seed in encoded:
            gmap.observe(bucket_sequence_from_encoded(enc_rt))
    failures, successes = [], []
    # every add below queues its trace; the batch's exit embeds them
    # all (models/search.py embed_batch) — same rows, same slots, same
    # order as one device round trip per run
    with search.embed_batch() as batch:
        for e in pooled:
            # same treatment as an in-storage failure: archive
            # embedding (novelty + surrogate positive) and
            # failure-signature target — once per distinct signature
            # (re-requests must not duplicate surrogate positives or
            # evict diverse runs from the archive). Pooled entries go
            # in FIRST: the failure archive is a ring, and adding them
            # after the storage's own failures could wrap around and
            # evict exactly the signatures most relevant to THIS
            # experiment — the storage's own must always survive a
            # full pool
            if search.has_failure_signature(e.digest):
                continue
            search.add_executed_trace(e.realized, reproduced=True,
                                      arrival=e.arrival)
            search.add_failure_trace(e.realized)
        for enc, enc_rt, ok, _ in encoded:
            # "failure" = the run reproduced the bug (validate failed);
            # the label feeds the surrogate's training set
            search.add_executed_trace(enc_rt, reproduced=not ok,
                                      arrival=enc)
            if not ok:
                search.add_failure_trace(enc_rt)
                failures.append(enc)
            else:
                successes.append(enc)
    t = stages.add("ingest_embed", t, pieces=batch.calls,
                   groups=batch.groups)
    if gmap is not None:
        scenario = p.knowledge_scenario or "local"
        obs.relation_coverage(scenario, gmap.covered(), gmap.width,
                              gmap.one_sided_count())
        if client is not None:
            # publish the campaign's frontier so the NEXT cold campaign
            # of this scenario warm-starts past it; best-effort like
            # every knowledge op
            client.push(coverage={
                "H": gmap.H, "w": gmap.width, "win": gmap.window,
                "bits": gmap.bits_list(),
            })
    if client is not None and encoded:
        _push_surrogate_examples(client, search, encoded)
        stages.add("ingest_pool", t)
    if p.reference_mode == "envelope" and successes:
        return [te.envelope_trace(successes)]
    pool = successes if successes else failures
    if not pool and pooled:
        # a fresh storage with no runs of its own can still evolve
        # against pooled signatures' natural arrivals
        pool = [e.arrival for e in reversed(pooled)]
    return pool[::-1][: p.max_reference_traces]
