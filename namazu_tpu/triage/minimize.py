"""Delta-debug a failing run's delay table toward a MINIMAL reproducer.

The failing run installed a whole delay table (up to H buckets of
injected delay), but the bug almost never needs all of it — usually one
or two ordering flips carry the failure. This module finds them:

1. **Candidates** come from the causality plane: ``relation_flips``
   between the failing run's realized dispatch order and a passing
   baseline names the ordering relations that actually differ, already
   transitively reduced and suspicion-ranked (obs/causality.py). Each
   flip maps — through the occurrence-key identity — back to the hint
   buckets of its two participants, and the failure's own
   ``failure_seed`` table says what delay the recording policy injected
   on each bucket. A candidate reproducer is a SUBSET of flips, i.e. the
   seed table restricted to those flips' buckets.
2. **Probing is mostly free.** A candidate table's realized order is
   simulated, not executed: candidate release times are
   ``arrival + table[bucket]`` and a stable argsort yields the order the
   delay-mode policy would realize (guidance/signature.py
   ``bucket_sequence_from_encoded`` — the exact release rule the search
   plane scores with). A candidate is *feasible* when it re-realizes
   every required flip, and it is *scored* by how far its predicted
   relation coverage diverges from the passing baseline
   (``CoverageMap.predicted_gain``). The whole subset lattice is probed
   this way without running the system once.
3. **Only survivors replay.** The best few feasible candidates
   (smallest first) are validated by a REAL run: a throwaway storage is
   initialized from the experiment's own materials, pre-seeded with the
   failing trace, given the candidate table as an installed search
   checkpoint, and executed through the ordinary campaign runner. A
   replay that fails validation reproduces the bug — that candidate is
   the minimal reproducer, and the dossier says ``validated: true``.
   Each candidate escalates through up to two tables before the next
   candidate gets a slot: the flip subset alone, then the subset plus
   its *causal prefix* (every seeded bucket whose traffic starts no
   later than the flip's target event). The failing run's recorded
   arrivals already embed upstream delay shifts — zeroing the upstream
   buckets replays a run the flip never happens in — so the prefix
   restores the context while the SUBSET remains the explanation. The
   last replay slot is reserved for the full failure seed, the
   always-reproduces fallback that keeps the dossier actionable even
   when no small subset survives.

The result is a self-contained **dossier** (``SCHEMA_DOSSIER``):
minimal table + flip set + probe journal + the ``tools why`` divergence
explanation + a causality-DAG slice around the critical path, keyed by
the run's failure signature (``models/failure_pool.trace_digest`` over
the realized encoding — the same key the knowledge pool dedupes on, so
dossiers attach to pool entries with no new identity scheme).
"""

from __future__ import annotations

import itertools
import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from namazu_tpu import obs
from namazu_tpu.guidance.coverage import CoverageMap
from namazu_tpu.guidance.signature import (
    bucket_sequence_from_docs,
    bucket_sequence_from_encoded,
)
from namazu_tpu.models.failure_pool import trace_digest
from namazu_tpu.models.ingest import failure_seed
from namazu_tpu.obs import causality
from namazu_tpu.ops import trace_encoding as te
from namazu_tpu.signal.base import HINT_SPACE
from namazu_tpu.storage import load_storage
from namazu_tpu.utils.config import Config, parse_duration
from namazu_tpu.utils.log import get_logger

log = get_logger("triage")

SCHEMA_DOSSIER = "nmz-triage-v1"

#: journal entries kept in the dossier; past this the tail is counted,
#: never silently dropped (the no-silent-caps stance)
JOURNAL_CAP = 200


class MinimizeError(Exception):
    """Minimization cannot even start (no failing run, no injected
    delays to shrink, ...) — distinct from a run that minimizes to an
    unvalidated candidate, which is a *result* (``validated: false``),
    not an error."""


class MinimizeBudget:
    """How much the minimizer may spend. Simulation is cheap (numpy on
    the encoded trace), replay is a full campaign run — the defaults
    keep the simulated:replayed ratio far past the 80% the triage
    plane promises (``nmz_triage_probes_total`` proves it per run)."""

    def __init__(self, max_probes: int = 4096, max_replays: int = 4,
                 replay_deadline_s: float = 120.0,
                 pair_pool: int = 8) -> None:
        self.max_probes = max(1, int(max_probes))
        self.max_replays = max(0, int(max_replays))
        self.replay_deadline_s = float(replay_deadline_s)
        #: top-scored singles that combine into pairs/triples — the
        #: lattice is probed smallest-first, so the pool only bounds
        #: the combinatorial middle, never the singles or the full set
        self.pair_pool = max(2, int(pair_pool))


# -- trace -> record docs (the causality plane's input shape) --------------

def _docs_from_trace(trace, zero_delay: bool = False) -> List[dict]:
    """A stored trace's actions as flight-recorder-shaped record docs,
    so the causality plane's functions (relation_flips, critical_path)
    apply to storages directly. ``zero_delay=True`` stamps each event's
    dispatch at its ARRIVAL — the synthetic "what the run would have
    looked like with no injected delay" baseline used when the storage
    holds no passing run to diff against."""
    docs = []
    for a in trace:
        arr = getattr(a, "event_arrived", None) or 0.0
        rel = a.triggered_time or 0.0
        dispatched = (arr or rel) if zero_delay else rel
        if not dispatched:
            continue  # never-dispatched: invisible to ordering
        docs.append({
            "entity": a.entity_id,
            "event_class": a.event_class or a.class_name(),
            "hint": getattr(a, "event_hint", "") or "",
            "t": {"intercepted": arr or dispatched,
                  "dispatched": dispatched},
        })
    return docs


def _key_map(docs: Sequence[dict]) -> Tuple[List[str], Dict[str, dict]]:
    """``(dispatch-ordered occurrence keys, key -> doc)`` for one run,
    replicating the causality plane's identity derivation EXACTLY
    (export.order_lines_from_docs + _occurrence_keys: timestamp-only
    stable sort, entity + class:hint line, occurrence counter) — a
    divergence here would map a flip back to the wrong event."""
    rows = []
    for i, doc in enumerate(docs):
        t = doc.get("t") or {}
        if doc.get("kind") or "dispatched" not in t:
            continue
        name = doc.get("event_class") or "event"
        if doc.get("hint"):
            name = f"{name}:{doc['hint']}"
        rows.append((t["dispatched"], f"{doc.get('entity', '')} {name}", i))
    rows.sort(key=lambda r: r[0])
    seen: Dict[str, int] = {}
    order: List[str] = []
    by_key: Dict[str, dict] = {}
    for _, line, i in rows:
        n = seen.get(line, 0)
        seen[line] = n + 1
        key = f"{line}#{n}"
        order.append(key)
        by_key[key] = docs[i]
    return order, by_key


def _bucket_of(doc: dict, H: int) -> int:
    """A doc's delay-table bucket — the failure_seed convention:
    recorded hint, else ``class:entity``."""
    hint = doc.get("hint") or \
        f"{doc.get('event_class') or 'event'}:{doc.get('entity', '')}"
    return te.hint_bucket(hint, H)


def _dag_slice(order: Sequence[str], participants: Sequence[str],
               radius: int = 3) -> List[str]:
    """The dispatch-order window around the flip participants — the
    DAG neighborhood a human reads first."""
    idx = {k: i for i, k in enumerate(order)}
    keep = set()
    for key in participants:
        i = idx.get(key)
        if i is None:
            continue
        keep.update(range(max(0, i - radius),
                          min(len(order), i + radius + 1)))
    return [order[i] for i in sorted(keep)]


# -- the replay harness ----------------------------------------------------

def _replay_once(storage_dir: str, base_cfg: Config, H: int,
                 max_interval_s: float, trace_f, table: np.ndarray,
                 deadline_s: float) -> Dict[str, Any]:
    """Execute ONE candidate table for real: throwaway storage from the
    experiment's own materials, the failing trace pre-seeded as stored
    history, the candidate table installed as a ready search checkpoint,
    then one ordinary ``run``. Returns ``{"reproduced": bool, ...}``.

    The pre-seeded trace matters twice: the tpu_search policy only
    treats a round as install-only when history exists (n=0 would start
    an evolution), and the huge ``search_every`` plus the seeded n=1
    guarantees the round installs ``triage_repro.npz`` verbatim and
    skips evolution — the run executes EXACTLY the candidate delays.
    """
    replay_dir = tempfile.mkdtemp(prefix="nmz-triage-")
    try:
        cfg = dict(base_cfg.to_jsonable())
        # the replay is hermetic: no knowledge wire, no telemetry push,
        # no endpoint ports to collide with a live orchestrator's
        for key in ("knowledge", "telemetry_url", "event_journal",
                    "event_journal_dir", "run_id"):
            cfg.pop(key, None)
        # the testee's inspectors still need a REST endpoint — on a
        # FRESH port (exported as NMZ_REST_PORT for the run scripts, the
        # examples' convention), so a live orchestrator on the
        # experiment's configured port never collides with the replay
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            rest_port = s.getsockname()[1]
        cfg["rest_port"] = rest_port
        cfg["agent_port"] = -1
        cfg["explore_policy"] = "tpu_search"
        param = dict(cfg.get("explore_policy_param")
                     or cfg.get("explorePolicyParam") or {})
        cfg.pop("explorePolicyParam", None)
        param.pop("knowledge", None)
        param.update({
            "checkpoint": "triage_repro.npz",
            "hint_buckets": int(H),
            # numbers mean milliseconds in duration params; write the
            # unit out so the seconds value survives verbatim
            "max_interval": f"{max_interval_s}s",
            # install-only: with one stored run and this cadence the
            # policy installs triage_repro.npz (an np.load) and returns
            # before any search is built, so the replay child never
            # imports jax and needs no device pin
            "search_every": 1_000_000,
            "generations": 1,
            "population": 8,
        })
        cfg["explore_policy_param"] = param
        # NOT config.toml/json: init copies the config by basename, and
        # run must find only the init-written config.json snapshot
        cfg_path = os.path.join(replay_dir, "replay_config.json")
        with open(cfg_path, "w") as f:
            json.dump(cfg, f, indent=2, sort_keys=True)
        replay_storage = os.path.join(replay_dir, "storage")
        from namazu_tpu.cli import cli_main  # lazy: cli imports us back

        rc = cli_main(["init", cfg_path,
                       os.path.join(storage_dir, "materials"),
                       replay_storage])
        if rc != 0:
            return {"reproduced": False, "error": f"init rc {rc}"}
        st = load_storage(replay_storage)
        try:
            st.create_new_working_dir()
            st.record_new_trace(trace_f)
            st.record_result(False, 0.0,
                             metadata={"hint_space": HINT_SPACE})
        finally:
            st.close()
        np.savez(os.path.join(replay_storage, "triage_repro.npz"),
                 best_delays=np.asarray(table, np.float32),
                 generations_run=np.asarray(1),
                 best_fitness=np.asarray(0.0),
                 hint_space=np.asarray(HINT_SPACE))
        from namazu_tpu.utils.cmd import CmdFactory, kill_process_group

        env = CmdFactory().env()
        env["NMZ_REST_PORT"] = str(rest_port)
        with open(os.path.join(replay_dir, "replay.log"), "ab") as lf:
            child = subprocess.Popen(
                [sys.executable, "-m", "namazu_tpu.cli", "run",
                 replay_storage],
                stdout=lf, stderr=subprocess.STDOUT,
                env=env, start_new_session=True)
            try:
                child.wait(timeout=deadline_s)
            except subprocess.TimeoutExpired:
                kill_process_group(child)
                return {"reproduced": False, "timeout": True}
        try:
            st = load_storage(replay_storage)
            try:
                n = st.nr_stored_histories()
                # index 0 is the pre-seeded history; the replay's own
                # run is the last one — reproduced iff it FAILED
                reproduced = n >= 2 and st.is_successful(n - 1) is False
            finally:
                st.close()
        except Exception:
            log.exception("replay storage unreadable after run")
            return {"reproduced": False,
                    "error": "replay storage unreadable",
                    "rc": child.returncode}
        return {"reproduced": bool(reproduced), "rc": child.returncode}
    finally:
        shutil.rmtree(replay_dir, ignore_errors=True)


def _default_replay(storage_dir: str, cfg: Config, H: int,
                    max_interval_s: float, trace_f,
                    deadline_s: float) -> Callable[[np.ndarray], bool]:
    def replay(table: np.ndarray) -> bool:
        res = _replay_once(storage_dir, cfg, H, max_interval_s,
                           trace_f, table, deadline_s)
        if res.get("error") or res.get("timeout"):
            log.warning("replay probe degraded: %s",
                        res.get("error") or "deadline expired")
        return bool(res.get("reproduced"))
    return replay


# -- the minimizer ---------------------------------------------------------

def failure_signature(storage_dir: str,
                      run_index: Optional[int] = None) -> str:
    """The failure signature a minimization of this run would carry —
    computed WITHOUT minimizing, so callers can ask the knowledge pool
    for an existing dossier (``triage_pull``) before paying for
    anything. Same key the failure pool dedupes on: ``trace_digest``
    over the realized encoding."""
    storage = load_storage(os.path.abspath(storage_dir))
    try:
        i_fail, _ = _pick_runs(storage, run_index, None)
        trace_f = storage.get_stored_history(i_fail)
    finally:
        storage.close()
    cfg = _storage_config(os.path.abspath(storage_dir))
    H = int(cfg.policy_param("hint_buckets", te.DEFAULT_H))
    return trace_digest(te.encode_trace(trace_f, H=H, realized=True))

def _pick_runs(storage, run_index: Optional[int],
               baseline_index: Optional[int]
               ) -> Tuple[int, Optional[int]]:
    n = storage.nr_stored_histories()
    if n == 0:
        raise MinimizeError("storage holds no runs")
    fail = run_index
    if fail is None:
        for i in range(n - 1, -1, -1):
            if storage.is_quarantined(i):
                continue
            if storage.is_successful(i) is False:
                fail = i
                break
        if fail is None:
            raise MinimizeError("storage holds no failing run to "
                                "minimize")
    else:
        if not (0 <= fail < n):
            raise MinimizeError(f"run {fail} out of range (storage "
                                f"holds {n})")
        if storage.is_successful(fail):
            raise MinimizeError(f"run {fail} succeeded — nothing to "
                                "minimize")
    base = baseline_index
    if base is None:
        for i in range(n - 1, -1, -1):
            if i == fail or storage.is_quarantined(i):
                continue
            if storage.is_successful(i):
                base = i
                break
    elif not (0 <= base < n):
        raise MinimizeError(f"baseline {base} out of range")
    return fail, base


def _storage_config(storage_dir: str) -> Config:
    for name in ("config.toml", "config.json"):
        path = os.path.join(storage_dir, name)
        if os.path.exists(path):
            return Config.from_file(path)
    return Config({})


def _enumerate_subsets(actionable: List[dict],
                       budget: MinimizeBudget):
    """Candidate flip subsets, smallest-first: every single, then pairs
    and triples over the top-scored pool, then the full set — ddmin's
    subset lattice walked bottom-up, because the whole point is that
    probes are (nearly) free and small reproducers are the prize."""
    idx = list(range(len(actionable)))
    yield from ([i] for i in idx)
    pool = idx[:budget.pair_pool]
    for size in (2, 3):
        if len(pool) >= size:
            yield from (list(c)
                        for c in itertools.combinations(pool, size))
    if len(idx) > 3:
        yield idx


def minimize_run(storage_dir: str,
                 run_index: Optional[int] = None,
                 baseline_index: Optional[int] = None,
                 top: int = 12,
                 budget: Optional[MinimizeBudget] = None,
                 replay: Optional[Callable[[np.ndarray], bool]] = None
                 ) -> Dict[str, Any]:
    """Minimize one failing stored run to a dossier (module header).

    ``replay`` overrides the real-execution harness — ``None`` uses the
    fork-a-campaign-run default; tests (and the ``--no-replay`` CLI
    path, via ``lambda table: False``-style stubs) inject their own.
    Raises :class:`MinimizeError` when minimization cannot start;
    returns an UNVALIDATED dossier (``validated: false``) when it can
    start but no candidate replays to a failure within budget.
    """
    budget = budget or MinimizeBudget()
    storage_dir = os.path.abspath(storage_dir)
    storage = load_storage(storage_dir)
    try:
        i_fail, i_base = _pick_runs(storage, run_index, baseline_index)
        trace_f = storage.get_stored_history(i_fail)
        trace_p = (storage.get_stored_history(i_base)
                   if i_base is not None else None)
    finally:
        storage.close()

    cfg = _storage_config(storage_dir)
    H = int(cfg.policy_param("hint_buckets", te.DEFAULT_H))
    max_interval_s = parse_duration(cfg.policy_param("max_interval", 100))
    seed = failure_seed(trace_f, H, max_interval_s)
    if seed is None:
        raise MinimizeError(
            f"run {i_fail} carries no injected delays (no "
            "arrival/release stamps) — there is no table to minimize")

    fail_docs = _docs_from_trace(trace_f)
    pass_docs = (_docs_from_trace(trace_p) if trace_p is not None
                 else _docs_from_trace(trace_f, zero_delay=True))
    run_a = f"run-{i_fail:08d}"
    run_b = (f"run-{i_base:08d}" if i_base is not None
             else "baseline-zero-delay")
    why = causality.why_payload(fail_docs, pass_docs, run_a, run_b,
                                top=top)
    diff = why["diff"]
    order_f, by_key = _key_map(fail_docs)

    # flips -> delay-table buckets: a flip is ACTIONABLE when both
    # participants map back to failing-run events in DIFFERENT buckets
    # (a delay table indexes buckets — it cannot reorder within one)
    actionable: List[dict] = []
    for f in diff.get("flips") or []:
        da, db = by_key.get(f["first"]), by_key.get(f["then"])
        if da is None or db is None:
            continue
        bf, bt = _bucket_of(da, H), _bucket_of(db, H)
        if bf == bt:
            continue
        actionable.append({
            "first": f["first"], "then": f["then"],
            "score": f["score"],
            "bucket_first": bf, "bucket_then": bt,
            "buckets": sorted({bf, bt}),
        })
    if not actionable:
        raise MinimizeError(
            "no actionable ordering flips between the failing run and "
            f"{run_b} — the divergence is not bucket-separable "
            f"({diff.get('inverted_pairs', 0)} inverted pair(s))")

    # the free-probe apparatus: the failing run's arrival-anchored
    # encoding (candidate release = arrival + delay), and a coverage
    # frontier trained on the PASSING order so predicted_gain measures
    # "how far from passing does this candidate steer"
    enc = te.encode_trace(trace_f, H=H)
    cov = CoverageMap(H)
    cov.observe(bucket_sequence_from_docs(pass_docs, H))

    def _probe(subset: List[int]) -> Tuple[np.ndarray, bool, float]:
        C = np.zeros((H,), np.float32)
        for i in subset:
            for b in actionable[i]["buckets"]:
                C[b] = seed[b]
        seq = bucket_sequence_from_encoded(
            enc, enc.arrival + C[enc.hint_ids])
        first: Dict[int, int] = {}
        for pos, b in enumerate(seq):
            first.setdefault(int(b), pos)
        feasible = all(
            first.get(actionable[i]["bucket_first"], -1) >= 0
            and first.get(actionable[i]["bucket_then"], -1) >= 0
            and first[actionable[i]["bucket_first"]]
            < first[actionable[i]["bucket_then"]]
            for i in subset)
        return C, feasible, cov.predicted_gain(seq)

    journal: List[dict] = []
    probes_simulated = 0
    scored: List[Tuple[int, float, int, List[int], np.ndarray]] = []
    for subset in _enumerate_subsets(actionable, budget):
        if probes_simulated >= budget.max_probes:
            log.warning("probe budget (%d) exhausted with subsets "
                        "left unprobed", budget.max_probes)
            break
        C, feasible, gain = _probe(subset)
        probes_simulated += 1
        journal.append({
            "mode": "simulated",
            "flips": [[actionable[i]["first"], actionable[i]["then"]]
                      for i in subset],
            "feasible": feasible, "gain": round(gain, 4),
        })
        if feasible:
            scored.append((len(subset), -gain, len(scored), subset, C))
    obs.triage_probe("simulated", probes_simulated)

    # survivors replay smallest-first, best-gain within a size; when
    # simulation screened everything out, the ranking is still the
    # replay order — simulation is a heuristic, replay is the judge
    if not scored:
        log.warning("no candidate passed the feasibility screen; "
                    "replaying the top-scored subsets anyway")
        for k, subset in enumerate(
                _enumerate_subsets(actionable, budget)):
            C, _, gain = _probe(subset)
            scored.append((len(subset), -gain, k, subset, C))
            if len(scored) >= max(1, budget.max_replays):
                break
    scored.sort()

    if replay is None:
        replay = _default_replay(storage_dir, cfg, H, max_interval_s,
                                 trace_f, budget.replay_deadline_s)

    # causal-prefix closure: first arrival per bucket, failure_seed's
    # hint convention (models/ingest.py) so the indices line up
    seed_arr = np.asarray(seed, np.float32)
    first_seen: Dict[int, float] = {}
    for a in trace_f:
        arr = getattr(a, "event_arrived", None)
        if not arr:
            continue
        hint = getattr(a, "event_hint", "") or \
            f"{a.event_class or a.class_name()}:{a.entity_id}"
        b = te.hint_bucket(hint, H)
        if b not in first_seen or arr < first_seen[b]:
            first_seen[b] = float(arr)

    def _with_prefix(C: np.ndarray, subset: List[int]) -> np.ndarray:
        horizon = max((by_key[actionable[i]["then"]]["t"]["intercepted"]
                       for i in subset), default=0.0)
        C2 = C.copy()
        for b, t0 in first_seen.items():
            if seed_arr[b] > 0 and t0 <= horizon:
                C2[b] = seed_arr[b]
        return C2

    # replay plan: per candidate, bare subset then subset+prefix; the
    # last slot is reserved for the full seed (module header, step 3)
    plans: List[Tuple[List[int], np.ndarray, float, str]] = []
    for _, neg_gain, _, subset, C in scored:
        plans.append((subset, C, -neg_gain, "subset"))
        C2 = _with_prefix(C, subset)
        if not np.array_equal(C2, C):
            plans.append((subset, C2, -neg_gain, "subset+prefix"))
    if budget.max_replays > 1:
        plans = plans[:budget.max_replays - 1]
    if budget.max_replays > 0 and np.any(seed_arr > 0):
        plans.append((list(range(len(actionable))),
                      seed_arr.copy(), 0.0, "full_seed"))

    probes_replayed = 0
    minimal: Optional[List[int]] = None
    minimal_table: Optional[np.ndarray] = None
    validated = False
    variant = "subset"
    for subset, C, gain, kind in plans:
        if probes_replayed >= budget.max_replays:
            break
        reproduced = bool(replay(C))
        probes_replayed += 1
        journal.append({
            "mode": "replayed", "table": kind,
            "flips": [[actionable[i]["first"], actionable[i]["then"]]
                      for i in subset],
            "gain": round(gain, 4), "reproduced": reproduced,
        })
        if reproduced:
            minimal, minimal_table, validated = subset, C, True
            variant = kind
            break
    obs.triage_probe("replayed", probes_replayed)
    if minimal is None:
        # best unvalidated candidate: the smallest feasible subset
        # (or the full actionable set when nothing was even feasible)
        minimal = scored[0][3] if scored else list(range(len(actionable)))
        minimal_table, _, _ = _probe(minimal)

    minimal_flips = [dict(actionable[i]) for i in minimal]
    ratio = 1.0 - len(minimal) / float(max(1, len(actionable)))
    obs.triage_minimized(ratio)

    participants = [k for f in minimal_flips
                    for k in (f["first"], f["then"])]
    sig = trace_digest(te.encode_trace(trace_f, H=H, realized=True))
    # the dossier ships the table that actually VALIDATED (it may carry
    # causal-prefix buckets beyond the minimal flips — the flips are
    # the explanation, the table is the reproducer)
    delays = {str(int(b)): float(minimal_table[b])
              for b in np.flatnonzero(minimal_table > 0)}
    dropped = max(0, len(journal) - JOURNAL_CAP)
    dossier = {
        "schema": SCHEMA_DOSSIER,
        "signature": sig,
        "storage": storage_dir,
        "run_index": i_fail,
        "baseline_index": i_base,
        "table": {"H": H, "max_interval_s": max_interval_s,
                  "delays": delays, "variant": variant},
        "flips": minimal_flips,
        "minimal_flips": len(minimal_flips),
        "candidate_flips": len(actionable),
        "probes_simulated": probes_simulated,
        "probes_replayed": probes_replayed,
        "minimization_ratio": round(ratio, 4),
        "validated": validated,
        "why": why,
        "dag_slice": {
            "around_flips": _dag_slice(order_f, participants),
            "critical_path": why["runs"]["a"]["critical_path"],
        },
        "journal": journal[:JOURNAL_CAP],
        "journal_dropped": dropped,
    }
    from namazu_tpu.triage import store as _store

    _store.record_dossier(dossier)
    log.info("minimized run %d: %d/%d flip(s), %d simulated / %d "
             "replayed probe(s), validated=%s", i_fail,
             len(minimal_flips), len(actionable), probes_simulated,
             probes_replayed, validated)
    return dossier


# -- rendering -------------------------------------------------------------

def render_dossier_md(dossier: Dict[str, Any]) -> str:
    """Markdown face of a dossier (``tools minimize --format md``)."""
    table = dossier.get("table") or {}
    lines = [
        f"# Triage dossier `{dossier.get('signature', '?')}`",
        "",
        f"- storage: `{dossier.get('storage', '?')}` "
        f"run {dossier.get('run_index')} "
        f"(baseline: {dossier.get('baseline_index', 'zero-delay')})",
        f"- minimal reproducer: {dossier.get('minimal_flips', 0)} "
        f"flip(s) of {dossier.get('candidate_flips', 0)} candidate(s) "
        f"(minimization ratio "
        f"{dossier.get('minimization_ratio', 0.0)})",
        f"- probe budget: {dossier.get('probes_simulated', 0)} "
        f"simulated / {dossier.get('probes_replayed', 0)} replayed",
        f"- validation: "
        f"{'replay-validated' if dossier.get('validated') else 'NOT validated (no replay reproduced the failure within budget)'}",
    ]
    flips = dossier.get("flips") or []
    if flips:
        lines += ["", "## Minimal ordering flips", "",
                  "| score | first | then | buckets |",
                  "|---|---|---|---|"]
        for f in flips:
            lines.append(f"| {f.get('score')} | `{f.get('first')}` "
                         f"| `{f.get('then')}` | {f.get('buckets')} |")
    delays = table.get("delays") or {}
    if delays:
        lines += ["", "## Minimal delay table "
                  f"(H={table.get('H')}, clip "
                  f"{table.get('max_interval_s')}s)", "",
                  "| bucket | delay (s) |", "|---|---|"]
        for b in sorted(delays, key=int):
            lines.append(f"| {b} | {delays[b]:.6f} |")
    dag = (dossier.get("dag_slice") or {}).get("around_flips") or []
    if dag:
        lines += ["", "## Dispatch order around the flips", ""]
        lines += [f"- `{k}`" for k in dag]
    why = dossier.get("why")
    if why:
        lines += ["", "---", "",
                  causality.render_why_md(why, perfetto=False)]
    lines.append("")
    return "\n".join(lines)
