"""Experiment analytics: cross-run statistics over a HistoryStorage.

PR 1 (metrics) answers "how many / how fast right now"; PR 2 (flight
recorder) answers "what order did run X execute". This module is the
third tier — the *experiment* plane — answering the cross-run questions
neither instantaneous gauges nor per-run timelines can: is the search
exploring new interleavings or replaying old ones, is time-to-first-
reproduction shrinking, has the search plane gone dead, and which
branches diverge between successful and failed runs.

Four statistic families, one payload (``compute_payload``):

* **coverage** — distinct-interleaving coverage via the search plane's
  own ``trace_digest`` (models/failure_pool.py: hint/entity sequence,
  timing-invariant), the unique-digest growth curve, and the novelty
  rate per window of runs (the saturation signal: a window that adds
  no new digest means the schedule source is replaying itself);
* **reproduction** — failure rate with a Wilson 95% interval (run
  counts are small; a normal approximation would lie), mean runs to
  reproduce, time-to-first-failure, repros/hour;
* **convergence** — best-fitness and archive-occupancy trends from the
  flight recorder's generation records, plus stall detection: the
  search is stalled when fitness AND novelty both flatline over the
  last ``STALL_WINDOW`` rounds (either alone is normal — fitness
  plateaus while the archive diversifies, novelty pauses while fitness
  climbs);
* **fault localization** — the analyzer's success/failure divergence
  ranking (namazu_tpu/analyzer.py), the reference's "Suspicious:" list.

The same payload is served by ``GET /analytics`` on the REST endpoint
(the orchestrator process registers its storage dir via
``set_storage_dir``), rendered by ``nmz-tpu tools report``
(obs/report.py), and published as ``nmz_experiment_*`` gauges so a
scraper can chart cross-run trends live. The live stall detector
(``note_search_round``, fed by ``obs.search_round``) trips the
``nmz_search_stall`` gauge and a run-tagged warning as soon as a search
goes dead — before the report stage. Schema and metric names:
doc/observability.md ("Experiment analytics").
"""

from __future__ import annotations

import json
import os
import threading
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Tuple

from namazu_tpu.obs import spans, stats
from namazu_tpu.obs.stats import wilson_interval  # noqa: F401 (canonical
# home moved to obs/stats.py; re-exported here for compatibility)
from namazu_tpu.utils.log import get_logger

log = get_logger("obs.analytics")

__all__ = [
    "DEFAULT_TOP", "DEFAULT_WINDOW", "STALL_WINDOW", "STALL_REL_EPS",
    "RELATION_H", "RELATION_WIDTH", "RELATION_WINDOW",
    "wilson_interval", "detect_stall", "trace_digest_of",
    "relation_bits_of",
    "coverage_stats", "reproduction_stats", "entity_stats",
    "convergence_stats", "suspicious_branches", "compute_payload",
    "progress_stats", "progress_rows", "progress_document",
    "ProgressFold", "progress_payload",
    "payload", "set_storage_dir", "storage_dir",
    "set_knowledge_address", "knowledge_address",
    "StallDetector", "note_search_round", "reset_stall_detector",
]

#: suspicious-branch rows kept in the payload
DEFAULT_TOP = 20
#: runs per novelty window (the saturation curve's resolution)
DEFAULT_WINDOW = 8
#: search rounds both fitness and novelty must flatline over to stall
STALL_WINDOW = 8
#: relative fitness improvement below which a window counts as flat
STALL_REL_EPS = 1e-3
#: per-entity table rows kept before folding into "_other"
MAX_ENTITY_ROWS = 16

#: the analytics plane's relation-signature space (guidance plane,
#: doc/search.md): a FIXED measurement space — hint buckets, bitmap
#: width, pair window — independent of any one policy's configuration,
#: so relation-coverage curves compare across campaigns. The search
#: plane's live CoverageMap uses the policy's own H instead (actionable
#: bias needs the genome's bucket space); both run the same derivation.
RELATION_H = 256
RELATION_WIDTH = 4096
RELATION_WINDOW = 16


# -- building blocks -------------------------------------------------------

def detect_stall(fitness: List[float],
                 novelty: Optional[List[float]] = None,
                 window: int = STALL_WINDOW,
                 rel_eps: float = STALL_REL_EPS) -> bool:
    """True when the last ``window`` search rounds improved neither best
    fitness (relative improvement <= ``rel_eps``) nor novelty (the
    distinct-failure count is unchanged). ``novelty=None`` (no novelty
    series recorded) degrades to fitness-only detection."""
    if len(fitness) < window:
        return False
    recent = fitness[-window:]
    scale = max(1.0, abs(recent[0]))
    fit_flat = (max(recent) - recent[0]) <= rel_eps * scale
    if not fit_flat:
        return False
    if novelty is None or len(novelty) < window:
        return True
    return novelty[-1] <= novelty[-window]


def trace_digest_of(trace) -> str:
    """Content digest of one stored trace — the SAME digest the search
    plane dedupes failure signatures by (models/failure_pool.py), so
    "unique interleavings" here and ``failure_distinct`` in the archive
    gauges count in one currency. Imported lazily: the digest needs the
    numpy featurizer, and the analytics module itself must stay
    importable from stdlib-only control-plane processes."""
    from namazu_tpu.models.failure_pool import trace_digest
    from namazu_tpu.ops import trace_encoding as te

    return trace_digest(te.encode_trace(trace))


def relation_bits_of(trace) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """One stored run's relation-coverage signature in the analytics
    measurement space (guidance plane): ``(covered bits, reverse
    bits)`` — the reverse bits are where each exercised relation's
    FLIP would land, so the campaign-level difference reverse - covered
    measures the open ordering frontier. Lazy import for the same
    stdlib-importability reason as the digest."""
    from namazu_tpu.guidance import (
        bucket_sequence_from_trace,
        reverse_signature_bits,
        signature_bits,
    )

    seq = bucket_sequence_from_trace(trace, RELATION_H)
    fwd = signature_bits(seq, width=RELATION_WIDTH,
                         window=RELATION_WINDOW)
    rev = reverse_signature_bits(seq, width=RELATION_WIDTH,
                                 window=RELATION_WINDOW)
    return (tuple(int(b) for b in fwd), tuple(int(b) for b in rev))


# -- per-storage statistics ------------------------------------------------

#: digest memo keyed by (storage dir, run index): a completed run's
#: trace is immutable, so its digest never changes — without this every
#: /analytics scrape re-runs the numpy featurizer + sha256 over EVERY
#: stored run, a per-scrape cost that grows linearly with the experiment
_digest_cache: Dict[Tuple[str, int], str] = {}
_digest_cache_lock = threading.Lock()
_DIGEST_CACHE_MAX = 65536


def _run_digest(storage, i: int, trace) -> str:
    key_dir = getattr(storage, "dir", None)
    if key_dir is None:  # storage without a stable identity: no memo
        return trace_digest_of(trace)
    key = (key_dir, i)
    with _digest_cache_lock:
        hit = _digest_cache.get(key)
    if hit is not None:
        return hit
    digest = trace_digest_of(trace)
    with _digest_cache_lock:
        if len(_digest_cache) >= _DIGEST_CACHE_MAX:
            _digest_cache.clear()
        _digest_cache[key] = digest
    return digest


#: relation-signature memo, same rationale as the digest memo (a
#: completed run's trace is immutable); value = (covered, reverse).
#: Its OWN, much smaller cap: one entry is two bit tuples (up to a few
#: thousand ints — ~100x a digest string), so the digest cache's 65536
#: ceiling would let a long-lived /analytics server grow unbounded in
#: practice before ever clearing
_relation_cache: Dict[Tuple[str, int],
                      Tuple[Tuple[int, ...], Tuple[int, ...]]] = {}
_RELATION_CACHE_MAX = 4096


def _run_relation_bits(storage, i: int, trace
                       ) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    key_dir = getattr(storage, "dir", None)
    if key_dir is None:
        return relation_bits_of(trace)
    key = (key_dir, i)
    with _digest_cache_lock:
        hit = _relation_cache.get(key)
    if hit is not None:
        return hit
    bits = relation_bits_of(trace)
    with _digest_cache_lock:
        if len(_relation_cache) >= _RELATION_CACHE_MAX:
            _relation_cache.clear()
        _relation_cache[key] = bits
    return bits


def _quarantined_count(storage) -> int:
    """How many of the storage's allocated run dirs are crash-
    quarantined (0 for backends without quarantine support)."""
    try:
        return len(getattr(storage, "quarantined_runs")())
    except Exception:
        return 0


def coverage_stats(storage, window: int = DEFAULT_WINDOW) -> Dict[str, Any]:
    """Distinct-interleaving coverage of a storage's recorded runs —
    two curves in one section: the classic unique-``trace_digest``
    growth curve (whole interleavings) and the relation-coverage curve
    (guidance plane: which ORDERING RELATIONS the runs exercised,
    counted in the fixed analytics measurement space). The regime the
    guidance plane exists for is digests saturating while relations
    still grow: the schedule source keeps producing "new" runs whose
    orderings are all old news — flagged explicitly."""
    n = storage.nr_stored_histories()
    digests: List[str] = []
    run_bits: List[Tuple[int, ...]] = []
    missing = 0
    # counted over ALL allocated run dirs (a quarantined run past the
    # last completed one is outside nr_stored_histories' range)
    quarantined = _quarantined_count(storage)
    digest_errors = 0
    is_quarantined = getattr(storage, "is_quarantined", None)
    for i in range(n):
        if is_quarantined is not None and is_quarantined(i):
            # crash-quarantined run (storage INCOMPLETE marker): its
            # trace exists but is untrustworthy — excluded from
            # coverage (doc/robustness.md)
            continue
        try:
            trace = storage.get_stored_history(i)
        except Exception:
            missing += 1  # crashed run: no trace.json on disk
            continue
        try:
            # both derivations BEFORE either append: a failure in the
            # second must exclude the run from every count, not leave
            # it half-counted with the two curves desynced
            digest = _run_digest(storage, i, trace)
            bits = _run_relation_bits(storage, i, trace)
        except Exception:
            # an environment problem (featurizer import, numpy), NOT
            # empty data — report it as its own bucket so a broken
            # install cannot masquerade as "N runs without a trace"
            if not digest_errors:
                log.exception("trace digest failed for run %d; coverage "
                              "will undercount", i)
            digest_errors += 1
            continue
        digests.append(digest)
        run_bits.append(bits)
    seen: set = set()
    curve: List[int] = []
    for d in digests:
        seen.add(d)
        curve.append(len(seen))
    novelty: List[float] = []
    prior: set = set()
    for start in range(0, len(digests), window):
        chunk = digests[start:start + window]
        fresh = len({d for d in chunk} - prior)
        novelty.append(round(fresh / len(chunk), 3))
        prior.update(chunk)
    unique = len(seen)
    # relation-coverage curve: cumulative covered bits, and per window
    # the fraction of runs that FIRST-COVERED at least one relation —
    # the guidance plane's novelty rule (coverage.py), mirrored here.
    # Reverse bits accumulate in parallel: their uncovered remainder is
    # the campaign's open ordering frontier (relations exercised in one
    # direction whose flip was never seen).
    rel_seen: set = set()
    rev_seen: set = set()
    rel_curve: List[int] = []
    rel_added: List[bool] = []
    for fwd, rev in run_bits:
        rel_added.append(any(b not in rel_seen for b in fwd))
        rel_seen.update(fwd)
        rev_seen.update(rev)
        rel_curve.append(len(rel_seen))
    rel_novelty: List[float] = []
    for start in range(0, len(rel_added), window):
        chunk = rel_added[start:start + window]
        rel_novelty.append(round(sum(chunk) / len(chunk), 3))
    rel_saturated = len(rel_novelty) >= 2 and rel_novelty[-1] == 0.0
    frontier = len(rev_seen - rel_seen)
    saturated = len(novelty) >= 2 and novelty[-1] == 0.0
    return {
        "runs": len(digests),
        "runs_without_trace": missing,
        "runs_quarantined": quarantined,
        "digest_errors": digest_errors,
        "unique_interleavings": unique,
        "coverage": round(unique / len(digests), 4) if digests else 0.0,
        "curve": curve,
        "window": window,
        "novelty_per_window": novelty,
        "saturated": saturated,
        "relation_width": RELATION_WIDTH,
        "relation_bits": len(rel_seen),
        "relation_coverage": round(len(rel_seen) / RELATION_WIDTH, 4),
        "relation_curve": rel_curve,
        "relation_novelty_per_window": rel_novelty,
        "relation_saturated": rel_saturated,
        # relations exercised in one direction whose flip was never
        # observed — where relation coverage can still grow even after
        # every digest window reads stale
        "relation_frontier_bits": frontier,
        # the motivating regime (doc/search.md): digest novelty reads
        # saturated — the schedule source is replaying known
        # interleavings — while the ordering frontier is still open
        # (either relations grew in the last window, or one-sided
        # relations remain to flip). Exactly when digest-guided search
        # has nothing left to chase and relation-guided search does.
        "digests_saturated_relations_growing": (
            saturated and (not rel_saturated or frontier > 0)),
    }


#: one stored run as the reproduction and progress surfaces read it:
#: ``(index, failure, required_time, virtual_time_s)`` — ``failure`` is
#: the SPRT's outcome (True = repro), ``required_time`` None where the
#: result states an outcome and no time (the run then counts for the
#: band verdict and not for the rates), ``virtual_time_s`` the stored
#: metadata value as it is, None on a wall run
ProgressRow = Tuple[int, bool, Optional[float], Any]


def progress_rows(storage, start: int = 0, stop: Optional[int] = None
                  ) -> Tuple[List[ProgressRow], int]:
    """The walk of the progress surface, over runs ``[start, stop)``
    (``stop`` None: up to the last completed run): one
    :data:`ProgressRow` per run that states an outcome, in storage
    order, and how many of the range are crash-quarantined. A
    quarantined run and one whose result cannot be read give no row.
    Each run is visited once, its ``result.json`` parsed once; nothing
    outside the range is touched, which is what lets a campaign
    supervisor read only the runs its last attempt made
    (:class:`ProgressFold`)."""
    if stop is None:
        stop = storage.nr_stored_histories()
    is_quarantined = getattr(storage, "is_quarantined", None)
    rows: List[ProgressRow] = []
    quarantined = 0
    for i in range(start, stop):
        if is_quarantined is not None and is_quarantined(i):
            quarantined += 1
            continue
        try:
            failure = not storage.is_successful(i)
        except Exception:
            continue
        try:
            t = storage.get_required_time(i)
        except Exception:
            rows.append((i, failure, None, None))
            continue
        try:
            virtual = storage.get_metadata(i).get("virtual_time_s")
        except Exception:
            virtual = None
        rows.append((i, failure, t, virtual))
    return rows, quarantined


def _reproduction_from_rows(rows: List[ProgressRow], quarantined: int
                            ) -> Dict[str, Any]:
    """:func:`reproduction_stats`'s arithmetic over kept rows. Every sum
    is taken anew over the row list, in its order: a total carried from
    one call to the next would round differently from the one a fresh
    walk computes."""
    outcomes: List[Tuple[bool, float]] = []
    # virtual-clock runs (doc/performance.md "Virtual clock") record
    # their VIRTUAL elapsed as metadata beside the wall required_time;
    # a wall run's virtual time IS its wall time, so the virtual total
    # stays well-defined over mixed storages
    total_virtual = 0.0
    vclock_runs = 0
    for _i, failure, t, virtual in rows:
        if t is None:
            continue
        outcomes.append((not failure, t))
        if virtual is not None:
            total_virtual += float(virtual)
            vclock_runs += 1
        else:
            total_virtual += t
    runs = len(outcomes)
    failures = sum(1 for ok, _ in outcomes if not ok)
    total_time = sum(t for _, t in outcomes)
    lo, hi = wilson_interval(failures, runs)
    ttff = None
    first_failure = None
    acc = 0.0
    for i, (ok, t) in enumerate(outcomes):
        acc += t
        if not ok:
            ttff, first_failure = round(acc, 3), i
            break
    rate = failures / runs if runs else 0.0
    out: Dict[str, Any] = {
        "runs": runs,
        "runs_quarantined": quarantined,
        "failures": failures,
        "failure_rate": round(rate, 4),
        "failure_rate_ci95": [round(lo, 4), round(hi, 4)],
        "mean_runs_to_reproduce": (round(runs / failures, 2)
                                   if failures else None),
        # inverse of the rate interval: the pessimistic end of "how many
        # more runs until the next repro" is what an experiment budget
        # is planned against
        "runs_to_reproduce_ci95": ([round(1.0 / hi, 2), round(1.0 / lo, 2)]
                                   if failures and lo > 0 else None),
        "time_to_first_failure_s": ttff,
        "first_failure_run": first_failure,
        "total_time_s": round(total_time, 3),
        "repros_per_hour": stats.repros_per_hour(failures,
                                                 total_time) or 0.0,
        # virtual-denominated twins, present only when at least one
        # run actually fast-forwarded: the wall fields above keep their
        # meaning (SPRT budgets and calibration artifacts are
        # wall-denominated), the virtual ones say how much scenario
        # time the campaign covered
        "vclock_runs": vclock_runs,
        "total_virtual_time_s": (round(total_virtual, 3)
                                 if vclock_runs else None),
        "repros_per_hour_virtual": (
            stats.repros_per_hour(failures, total_virtual)
            if vclock_runs else None),
    }
    return out


def reproduction_stats(storage) -> Dict[str, Any]:
    """Failure (= bug reproduction) statistics across a storage's runs."""
    rows, _ = progress_rows(storage)
    # counted over ALL allocated run dirs, as coverage_stats counts it
    return _reproduction_from_rows(rows, _quarantined_count(storage))


def _run_outcomes(storage) -> List[bool]:
    """The storage's completed-run outcome sequence in campaign order
    (True = failure = repro), quarantined runs excluded — what the
    progress surface replays through the band SPRT."""
    return [failure for _i, failure, _t, _v in progress_rows(storage)[0]]


def progress_stats(storage, coverage: Optional[Dict[str, Any]] = None,
                   calibration: Optional[Dict[str, Any]] = None,
                   checkpoint: Optional[Dict[str, Any]] = None
                   ) -> Dict[str, Any]:
    """The live campaign-progress document (obs/stats.py machinery over
    one storage): measured rate + CI, repros/hour, ETA forecasts, the
    sequential band verdict, and the search-pays/random-suffices regime
    call. The walk (:func:`progress_rows` over the whole storage) and
    then the arithmetic (:func:`progress_document`), so the REST
    ``/progress`` body, the ``/analytics`` fold, ``tools report`` and a
    campaign supervisor's fold (:class:`ProgressFold`) all agree
    byte-for-byte."""
    rows, _ = progress_rows(storage)
    return progress_document(rows, _quarantined_count(storage),
                             coverage=coverage, calibration=calibration,
                             checkpoint=checkpoint)


def progress_document(rows: List[ProgressRow], quarantined: int,
                      coverage: Optional[Dict[str, Any]] = None,
                      calibration: Optional[Dict[str, Any]] = None,
                      checkpoint: Optional[Dict[str, Any]] = None
                      ) -> Dict[str, Any]:
    """:func:`progress_stats`'s arithmetic. Pure function of its inputs
    — no storage, no wall-clock reads. Every field is ``None`` rather
    than NaN on a young campaign (0 or 1 completed runs, no failures
    yet): the document must always survive ``json.dumps(...,
    allow_nan=False)``."""
    repro = _reproduction_from_rows(rows, quarantined)
    outcomes = [failure for _i, failure, _t, _v in rows]
    runs = len(outcomes)
    failures = sum(outcomes)
    band = tuple(stats.DEFAULT_BAND)
    band_source = "default"
    if calibration and isinstance(calibration.get("band"), (list, tuple)) \
            and len(calibration["band"]) == 2:
        band = (float(calibration["band"][0]),
                float(calibration["band"][1]))
        band_source = "calibration"
    # cap out of reach: live progress reads "undecided" until the SPRT
    # genuinely concludes (the budget-capped point-estimate fallback is
    # the calibration harness's semantics, not a scrape's)
    sprt = stats.BandSPRT.replay(outcomes, lo=band[0], hi=band[1],
                                 max_runs=runs + 1)
    rate = failures / runs if runs else None
    rph = repro.get("repros_per_hour") or None
    runs_to_ci = stats.runs_for_ci_width(rate if failures else None)
    doc: Dict[str, Any] = {
        "runs": runs,
        "failures": failures,
        "runs_quarantined": repro.get("runs_quarantined", 0),
        "repro_rate": round(rate, 4) if rate is not None else None,
        "rate_ci95": repro.get("failure_rate_ci95") if runs else None,
        "repros_per_hour": rph,
        "total_time_s": repro.get("total_time_s", 0.0),
        # virtual-clock twins (None on pure wall campaigns): reported
        # as SEPARATE fields so every wall-denominated consumer (SPRT
        # budgets, calibration A/Bs) keeps reading the fields above
        "repros_per_hour_virtual": repro.get("repros_per_hour_virtual"),
        "total_virtual_time_s": repro.get("total_virtual_time_s"),
        "eta_next_repro_virtual_s": stats.eta_next_repro_s(
            repro.get("repros_per_hour_virtual")),
        # forecasters (obs/stats.py): None = nothing to extrapolate yet
        "eta_next_repro_s": stats.eta_next_repro_s(rph),
        "eta_10_repros_s": stats.eta_to_n_repros_s(rph, failures, 10),
        "runs_to_ci_width": ({
            "width": stats.DEFAULT_CI_WIDTH,
            "runs": runs_to_ci,
            "more_runs": max(0, runs_to_ci - runs),
        } if runs_to_ci is not None else None),
        # the sequential band verdict, replayed deterministically over
        # the outcome sequence (max_runs = what actually ran, so a live
        # campaign reads "undecided" until the SPRT truly concludes)
        "band": [band[0], band[1]],
        "band_source": band_source,
        "band_verdict": sprt.verdict or "undecided",
        "band_decided_by": sprt.decided_by,
        "regime": stats.regime_verdict(
            rate, runs, band=band,
            digests_saturated_relations_growing=bool(
                (coverage or {}).get(
                    "digests_saturated_relations_growing"))),
    }
    if calibration is not None:
        doc["calibration"] = {
            "schema": calibration.get("schema"),
            "status": calibration.get("status"),
            "knobs": calibration.get("knobs"),
            "rate": calibration.get("rate"),
            "rate_ci95": calibration.get("rate_ci95"),
            "runs_saved_pct": calibration.get("runs_saved_pct"),
        }
    if checkpoint is not None:
        requested = int(checkpoint.get("requested_runs", 0) or 0)
        slots = [s for s in checkpoint.get("slots", [])
                 if not s.get("in_progress")]
        remaining = max(0, requested - len(slots))
        mean_run_s = (repro["total_time_s"] / runs) if runs else None
        doc["campaign"] = {
            "requested_runs": requested,
            "completed_slots": len(slots),
            "stopped_reason": checkpoint.get("stopped_reason"),
            "eta_completion_s": (round(remaining * mean_run_s, 1)
                                 if mean_run_s is not None else None),
        }
    return doc


class ProgressFold:
    """One storage's progress rows kept from slot to slot, for a reader
    that stays (the campaign supervisor): :meth:`fold` reads the runs at
    or past the watermark — each once — and advances it, and
    :meth:`document` is :func:`progress_document` over everything kept,
    so after every slot the document equals what :func:`progress_stats`
    walks the whole storage for.

    What lets the rows below the watermark stand: a run dir whose child
    has been reaped is written by nothing again (storage/naive.py:
    every write goes to the handle's current run dir; ``init()`` and
    fsck mark only result-less runs, and the storage's ``refresh()``
    has applied ``init()``'s marking to the dirs being folded). A stored
    run edited by hand under a running campaign is seen by whoever
    recomputes (``/progress``, ``tools report``) and by this reader at
    its next whole walk."""

    def __init__(self) -> None:
        self._rows: List[ProgressRow] = []
        self._quarantined = 0
        #: how many allocated runs have been folded; None = none yet
        self.watermark: Optional[int] = None
        self._walked = False

    def contradicted_by(self, allocated: int) -> bool:
        """Whether a storage holding ``allocated`` runs has fewer than
        were folded: the rows kept describe runs that are gone."""
        return self.watermark is not None and allocated < self.watermark

    def fold(self, storage, allocated: int) -> None:
        """Read runs ``[watermark, allocated)`` of ``storage`` into the
        rows kept; the whole range anew (a walk) where nothing was
        folded yet or the watermark is contradicted."""
        walk = self.watermark is None or self.contradicted_by(allocated)
        rows, quarantined = progress_rows(
            storage, 0 if walk else self.watermark, allocated)
        if walk:
            self._rows, self._quarantined = rows, quarantined
            self._walked = True
        else:
            self._rows += rows
            self._quarantined += quarantined
        self.watermark = allocated

    def take_path(self) -> str:
        """How the rows got here since this was last asked: ``"walk"``
        where the whole history was read, else ``"fold"`` — asked once a
        published document, for its counter."""
        path, self._walked = "walk" if self._walked else "fold", False
        return path

    def document(self, coverage: Optional[Dict[str, Any]] = None,
                 calibration: Optional[Dict[str, Any]] = None,
                 checkpoint: Optional[Dict[str, Any]] = None
                 ) -> Dict[str, Any]:
        return progress_document(self._rows, self._quarantined,
                                 coverage=coverage, calibration=calibration,
                                 checkpoint=checkpoint)


def _load_doc(dir_path: str, name: str) -> Optional[Dict[str, Any]]:
    """A storage dir's JSON object ``name``; None when absent, torn or
    no object."""
    try:
        with open(os.path.join(dir_path, name)) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    return doc if isinstance(doc, dict) else None


def _progress_inputs(dir_path: Optional[str]
                     ) -> Tuple[Optional[Dict[str, Any]],
                                Optional[Dict[str, Any]]]:
    """Best-effort read of a storage dir's calibration artifact
    (calibration.json, namazu_tpu/calibrate) and campaign checkpoint
    (campaign.json) — (None, None) when absent or unreadable, so a torn
    file degrades the fold instead of failing the payload."""
    if not dir_path:
        return None, None
    return (_load_doc(dir_path, "calibration.json"),
            _load_doc(dir_path, "campaign.json"))


def entity_stats(storage,
                 max_rows: int = MAX_ENTITY_ROWS) -> List[Dict[str, Any]]:
    """Per-entity event totals across all recorded traces, busiest
    first; entities past ``max_rows`` fold into one ``_other`` row (same
    cardinality stance as the metric plane's entity-label cap)."""
    counts: "OrderedDict[str, Dict[str, Any]]" = OrderedDict()
    for i in range(storage.nr_stored_histories()):
        try:
            trace = storage.get_stored_history(i)
        except Exception:
            continue
        seen_here: set = set()
        for a in trace:
            row = counts.get(a.entity_id)
            if row is None:
                row = counts[a.entity_id] = {
                    "entity": a.entity_id, "events": 0,
                    "classes": set(), "runs": 0,
                }
            row["events"] += 1
            row["classes"].add(a.event_class or a.class_name())
            if a.entity_id not in seen_here:
                seen_here.add(a.entity_id)
                row["runs"] += 1
    rows = sorted(counts.values(),
                  key=lambda r: (-r["events"], r["entity"]))
    out = [{"entity": r["entity"], "events": r["events"],
            "classes": len(r["classes"]), "runs": r["runs"]}
           for r in rows[:max_rows]]
    if len(rows) > max_rows:
        rest = rows[max_rows:]
        out.append({
            "entity": "_other",
            "events": sum(r["events"] for r in rest),
            "classes": len(set().union(*(r["classes"] for r in rest))),
            "runs": max(r["runs"] for r in rest),
        })
    return out


# -- recorder-derived statistics -------------------------------------------

def convergence_stats(recorder_runs,
                      window: int = STALL_WINDOW) -> Dict[str, Any]:
    """Search-plane convergence from the flight recorder's generation
    records (obs/recorder.py ``record_generation``/``record_install``),
    concatenated across the recorded runs in ring order."""
    fitness: Dict[str, List[float]] = {}
    archive: Dict[str, List[float]] = {}
    novelty: Dict[str, List[float]] = {}
    generations: Dict[str, int] = {}
    installs: Dict[str, int] = {}
    host_io: Dict[str, float] = {}
    host_elapsed: Dict[str, float] = {}
    rounds = 0
    for run in recorder_runs or []:
        snap = run.snapshot()
        for g in snap["generations"]:
            if g.get("kind") == "generation":
                rounds += 1
                b = g.get("backend", "?")
                fitness.setdefault(b, []).append(
                    float(g.get("best_fitness", 0.0)))
                generations[b] = max(generations.get(b, 0),
                                     int(g.get("gen_end", 0)))
                if g.get("archive_entries") is not None:
                    archive.setdefault(b, []).append(
                        float(g["archive_entries"]))
                if g.get("distinct_failures") is not None:
                    novelty.setdefault(b, []).append(
                        float(g["distinct_failures"]))
                if g.get("host_io_s") is not None:
                    # fused-loop rounds: host-I/O lane wall time vs the
                    # round's whole evolve span -> per-generation
                    # host-gap share (doc/performance.md)
                    host_io[b] = host_io.get(b, 0.0) + float(g["host_io_s"])
                    host_elapsed[b] = host_elapsed.get(b, 0.0) + max(
                        0.0, float(g.get("t_end", 0.0))
                        - float(g.get("t_start", 0.0)))
            elif g.get("kind") == "install":
                src = g.get("source", "?")
                installs[src] = installs.get(src, 0) + 1
    backends: Dict[str, Any] = {}
    for b in sorted(fitness):
        fit = fitness[b]
        backends[b] = {
            "rounds": len(fit),
            "generations": generations.get(b, 0),
            "best_fitness": round(max(fit), 6),
            "fitness_curve": [round(v, 6) for v in fit[-64:]],
            "archive_curve": [int(v) for v in archive.get(b, [])[-64:]],
            "novelty_curve": [int(v) for v in novelty.get(b, [])[-64:]],
            "stalled": detect_stall(fit, novelty.get(b), window=window),
        }
        if b in host_io and host_elapsed.get(b, 0.0) > 0:
            backends[b]["host_gap_share"] = round(
                min(1.0, host_io[b] / host_elapsed[b]), 4)
    return {
        "search_rounds": rounds,
        "installs": dict(sorted(installs.items())),
        "backends": backends,
        "stalled": any(v["stalled"] for v in backends.values()),
    }


def suspicious_branches(storage, top: int = DEFAULT_TOP
                        ) -> List[Dict[str, Any]]:
    """The analyzer's divergence ranking as payload rows."""
    from namazu_tpu.analyzer import analyze_storage

    return [
        {"branch": b, "divergence": round(div, 4),
         "fail_hit_rate": round(fr, 4), "success_hit_rate": round(sr, 4)}
        for b, div, fr, sr in analyze_storage(storage, top=top)
    ]


# -- the payload -----------------------------------------------------------

def compute_payload(storage=None, recorder_runs=None,
                    top: int = DEFAULT_TOP, window: int = DEFAULT_WINDOW,
                    publish: bool = True) -> Dict[str, Any]:
    """The full analytics document: deterministic for a given storage +
    recorder state (no wall-clock stamps — two computations over the
    same inputs compare equal, which the golden-file test and the
    REST-vs-CLI parity check both lean on)."""
    if storage is not None:
        coverage = coverage_stats(storage, window=window)
        repro = reproduction_stats(storage)
        entities = entity_stats(storage)
        suspicious = suspicious_branches(storage, top=top)
    else:
        coverage = {"runs": 0, "runs_without_trace": 0,
                    "digest_errors": 0,
                    "unique_interleavings": 0, "coverage": 0.0,
                    "curve": [], "window": window,
                    "novelty_per_window": [], "saturated": False,
                    "relation_width": RELATION_WIDTH,
                    "relation_bits": 0, "relation_coverage": 0.0,
                    "relation_curve": [],
                    "relation_novelty_per_window": [],
                    "relation_saturated": False,
                    "relation_frontier_bits": 0,
                    "digests_saturated_relations_growing": False}
        repro = reproduction_stats(_EmptyStorage())
        entities = []
        suspicious = []
    convergence = convergence_stats(recorder_runs, window=STALL_WINDOW)
    doc = {
        "schema": "nmz-analytics-v1",
        "experiment": {
            "runs": repro["runs"],
            "failures": repro["failures"],
            "entities": len(entities),
            "search_rounds": convergence["search_rounds"],
        },
        "coverage": coverage,
        "reproduction": repro,
        "entities": entities,
        "convergence": convergence,
        "suspicious": suspicious,
    }
    # progress fold (obs/stats.py): the sequential-statistics surface,
    # folded in only when the storage dir carries a calibration artifact
    # or a campaign checkpoint — file-driven so the CLI report and the
    # REST route agree byte-for-byte (parity test), and golden storages
    # (neither file) render unchanged
    progress = None
    st_dir = getattr(storage, "dir", None)
    if st_dir:
        calib, ckpt = _progress_inputs(st_dir)
        if calib is not None or ckpt is not None:
            progress = progress_stats(storage, coverage=coverage,
                                      calibration=calib, checkpoint=ckpt)
            doc["progress"] = progress
    if publish:
        # the relation-coverage gauge's storage-derived face; the live
        # per-campaign face is published by the ingest path with the
        # knowledge scenario label (models/ingest.py)
        spans.relation_coverage(
            "storage", coverage.get("relation_bits", 0),
            coverage.get("relation_width", RELATION_WIDTH))
        spans.experiment_stats(
            runs=repro["runs"],
            failures=repro["failures"],
            failure_rate=repro["failure_rate"],
            unique_interleavings=coverage["unique_interleavings"],
            coverage=coverage["coverage"],
            novelty_last_window=(coverage["novelty_per_window"][-1]
                                 if coverage["novelty_per_window"]
                                 else None),
            time_to_first_failure_s=repro["time_to_first_failure_s"],
            mean_runs_to_reproduce=repro["mean_runs_to_reproduce"],
        )
        if progress is not None:
            spans.campaign_progress(
                rate=progress["repro_rate"],
                ci=progress["rate_ci95"],
                repros_per_hour=progress["repros_per_hour"],
                eta_next_repro_s=progress["eta_next_repro_s"],
                runs_to_ci=(progress["runs_to_ci_width"] or {}).get(
                    "more_runs"),
                in_band=(1 if progress["band_verdict"] == "in_band"
                         else 0 if progress["band_verdict"] in
                         ("below", "above") else None),
            )
    return doc


class _EmptyStorage:
    """Zero-run stand-in so the no-storage payload shares one code path."""

    def nr_stored_histories(self) -> int:
        return 0


# -- process-global wiring (the REST /analytics source) --------------------

_storage_dir: Optional[str] = None


def set_storage_dir(dir_path: Optional[str]) -> None:
    """Register the experiment storage the live ``/analytics`` route
    aggregates over (``nmz-tpu run`` registers its storage dir; embedded
    orchestrators and tests may register any initialized storage)."""
    global _storage_dir
    _storage_dir = dir_path or None


def storage_dir() -> Optional[str]:
    return _storage_dir


_knowledge_addr: Optional[str] = None


def set_knowledge_address(addr: Optional[str]) -> None:
    """Register the knowledge-service address whose pool/tenant stats
    the live payload folds in (``nmz-tpu run --knowledge`` registers
    it; None unregisters). Purely additive: no address, no section."""
    global _knowledge_addr
    _knowledge_addr = addr or None


def knowledge_address() -> Optional[str]:
    return _knowledge_addr


def _knowledge_section() -> Optional[Dict[str, Any]]:
    """Pool/tenant stats from the registered knowledge service — the
    fleet-level counterpart of the per-storage sections. Best-effort
    like the storage join: an outage yields ``available: false``, never
    a failed payload (a scrape must not 500 on a dead sidecar)."""
    addr = _knowledge_addr
    if not addr:
        return None
    from namazu_tpu.knowledge import shared_client

    stats = shared_client(addr, tenant="analytics").stats()
    if stats is None:
        return {"address": addr, "available": False}
    return {
        "address": addr,
        "available": True,
        "pool_size": stats.get("pool_size", 0),
        "tenant_count": stats.get("tenant_count", 0),
        "scenario_count": stats.get("scenario_count", 0),
        "pushes": stats.get("pushes", 0),
        "pulls": stats.get("pulls", 0),
        "dedupe_hits": stats.get("dedupe_hits", 0),
        "surrogate": stats.get("surrogate", {}),
    }


def payload(top: int = DEFAULT_TOP,
            window: int = DEFAULT_WINDOW) -> Dict[str, Any]:
    """The live analytics document: the registered storage (when one is
    registered and loadable) joined with this process's flight-recorder
    runs. Storage trouble degrades to a recorder-only payload rather
    than failing the route — a mid-experiment scrape must not 500
    because a run dir is being written."""
    st = None
    d = _storage_dir
    if d:
        try:
            from namazu_tpu.storage import load_storage

            st = load_storage(d)
        except Exception:
            log.warning("analytics storage %s unreadable; serving "
                        "recorder-only payload", d, exc_info=True)
    from namazu_tpu.obs import recorder as _recorder

    try:
        doc = compute_payload(storage=st,
                              recorder_runs=_recorder.recorder().runs(),
                              top=top, window=window)
    finally:
        if st is not None:
            st.close()
    know = _knowledge_section()
    if know is not None:
        doc["knowledge"] = know
    # SLO compliance (obs/slo.py): folded in only when objectives were
    # DECLARED in config — like the knowledge section, purely additive,
    # so the compute_payload parity (REST vs CLI on an slo-less fleet)
    # is untouched
    try:
        from namazu_tpu.obs import federation

        slo_doc = federation.slo_summary()
        if slo_doc is not None:
            doc["slo"] = slo_doc
    except Exception:
        log.warning("slo summary failed; payload served without it",
                    exc_info=True)
    # causality fold (obs/causality.py): per-run critical-path latency
    # attribution over this process's recorded runs — additive like the
    # knowledge/slo sections (no recorded runs, no section), so the
    # compute_payload parity stays untouched
    try:
        runs = _recorder.recorder().runs()
        if runs:
            from namazu_tpu.obs import causality

            rows = []
            for run in runs[-4:]:  # newest runs; a bounded fold
                records, gens, run_id = causality.docs_of_run(run)
                if not records:
                    continue
                graph = causality.build_graph(records, gens, run_id)
                row = causality.critical_path(records, run_id)
                row["acyclic"] = graph.is_acyclic()
                row["stamp_inversions"] = len(graph.stamp_inversions())
                rows.append(row)
            if rows:
                doc["causality"] = {"runs": rows}
    except Exception:
        log.warning("causality fold failed; payload served without it",
                    exc_info=True)
    # triage fold (namazu_tpu/triage): per-signature dossier summaries —
    # additive like the knowledge/slo/causality sections (no dossiers,
    # no section), preserving the compute_payload parity
    try:
        from namazu_tpu.triage import store as _triage_store

        rows = _triage_store.summaries()
        if rows:
            doc["triage"] = {"dossiers": rows}
    except Exception:
        log.warning("triage fold failed; payload served without it",
                    exc_info=True)
    return doc


def progress_payload() -> Dict[str, Any]:
    """The live ``GET /progress`` body: progress_stats over the
    registered storage, always served (default band, all-None
    forecasts) even before the first run lands — a young campaign
    scrape returns zeros, never a 404 or NaN."""
    st = None
    d = _storage_dir
    if d:
        try:
            from namazu_tpu.storage import load_storage

            st = load_storage(d)
        except Exception:
            log.warning("progress storage %s unreadable; serving "
                        "zero-run payload", d, exc_info=True)
    calib, ckpt = _progress_inputs(d)
    try:
        doc = progress_stats(st if st is not None else _EmptyStorage(),
                             calibration=calib, checkpoint=ckpt)
    finally:
        if st is not None:
            st.close()
    doc["schema"] = "nmz-progress-v1"
    doc["storage"] = d
    return doc


# -- live stall detection --------------------------------------------------

class StallDetector:
    """Per-backend sliding window over (best_fitness, distinct_failures)
    search rounds; trips when both flatline (``detect_stall``). Fed by
    ``obs.search_round`` on every round, so a dead search surfaces as
    the ``nmz_search_stall`` gauge and one run-tagged warning while the
    experiment is still running — not in the post-hoc report."""

    def __init__(self, window: int = STALL_WINDOW,
                 rel_eps: float = STALL_REL_EPS) -> None:
        self.window = window
        self.rel_eps = rel_eps
        self._lock = threading.Lock()
        self._fitness: Dict[str, deque] = {}
        self._novelty: Dict[str, deque] = {}
        self._stalled: Dict[str, bool] = {}

    def update(self, backend: str, best_fitness: float,
               distinct_failures: float) -> Tuple[bool, bool]:
        """Feed one round; returns (stalled, changed-since-last-round)."""
        with self._lock:
            fit = self._fitness.setdefault(
                backend, deque(maxlen=self.window))
            nov = self._novelty.setdefault(
                backend, deque(maxlen=self.window))
            fit.append(float(best_fitness))
            nov.append(float(distinct_failures))
            stalled = detect_stall(list(fit), list(nov),
                                   window=self.window,
                                   rel_eps=self.rel_eps)
            changed = stalled != self._stalled.get(backend, False)
            self._stalled[backend] = stalled
            return stalled, changed


_stall_detector = StallDetector()


def reset_stall_detector(window: int = STALL_WINDOW,
                         rel_eps: float = STALL_REL_EPS) -> StallDetector:
    """Fresh detector (tests); returns it."""
    global _stall_detector
    _stall_detector = StallDetector(window, rel_eps)
    return _stall_detector


def note_search_round(backend: str, best_fitness: float,
                      distinct_failures: float) -> bool:
    """Live stall hook (called by ``obs.search_round``): updates the
    detector, mirrors the verdict into ``nmz_search_stall{backend}``,
    and logs the stall/recovery transitions (run-tagged via the log
    plane's ``[run_id]`` filter)."""
    stalled, changed = _stall_detector.update(
        backend, best_fitness, distinct_failures)
    spans.search_stall(backend, stalled)
    if changed and stalled:
        log.warning(
            "search plane stalled: backend=%s best_fitness and "
            "distinct-failure novelty both flat over the last %d rounds "
            "(best=%.6g, distinct_failures=%d) — the schedule source is "
            "replaying itself", backend, _stall_detector.window,
            best_fitness, int(distinct_failures))
    elif changed:
        log.info("search plane resumed progress (backend=%s)", backend)
    return stalled
