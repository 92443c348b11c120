"""ScheduleSearch: the host-side driver around the sharded island GA.

Owns the novelty/failure archives (host ring buffers mirrored to device),
runs generations on the mesh, and extracts the best delay/fault tables for
the control plane to replay. Checkpointing is plain ``.npz`` (population,
archives, RNG state) — search state survives across experiment runs, which
the reference has no equivalent for (SURVEY.md section 5.4).
"""

from __future__ import annotations

import contextlib
import functools
import os
import time
from typing import NamedTuple, Optional

import numpy as np

from namazu_tpu import obs
from namazu_tpu.models import SEARCH_DEFAULTS, refuse_search_backend
from namazu_tpu.models.ga import GAConfig
from namazu_tpu.ops import trace_encoding as te
from namazu_tpu.ops.schedule import ScoreWeights, scorer_branch
from namazu_tpu.utils.log import get_logger

log = get_logger("models.search")


class SearchConfig(NamedTuple):
    """What a search is built from. The fields that are knobs of the
    ``tpu_search`` policy take their defaults from ``SEARCH_DEFAULTS``
    (namazu_tpu/models/__init__.py), where each is described."""

    H: int = SEARCH_DEFAULTS["H"]  # hint buckets (genome length)
    L: int = SEARCH_DEFAULTS["L"]  # encode-length cap hint; 0 = uncapped
    # (the driver encodes before calling run(): informational)
    K: int = SEARCH_DEFAULTS["K"]  # feature pairs
    archive_size: int = 512  # novelty archive capacity
    failure_size: int = 64  # failure archive capacity
    population: int = SEARCH_DEFAULTS["population"]
    migrate_k: int = SEARCH_DEFAULTS["migrate_k"]
    seed: int = SEARCH_DEFAULTS["seed"]
    ga: GAConfig = GAConfig()
    weights: ScoreWeights = ScoreWeights()
    # learned surrogate (BASELINE config 5): when > 0, an online MLP
    # P(reproduce | features) trained on executed runs re-ranks the top-k
    # genomes of the evolved population, and run() returns the candidate
    # with the highest predicted repro instead of the raw fitness argmax.
    # 0 disables (fitness argmax, the pre-surrogate behavior).
    surrogate_topk: int = SEARCH_DEFAULTS["surrogate_topk"]
    # novelty anneal: with fewer than this many DISTINCT
    # failure signatures in the archive the search keeps its full
    # configured novelty weight (keep exploring — exploiting 1-2
    # signatures overfits their noise, the round-4 A/B floor's root
    # cause); once the archive holds >= this many, the novelty weight is
    # scaled by min_failure_signatures / n_signatures (never below
    # novelty_floor) so a rich archive shifts the search toward
    # exploitation. 0 disables (static weights).
    min_failure_signatures: int = SEARCH_DEFAULTS["min_failure_signatures"]
    novelty_floor: float = SEARCH_DEFAULTS["novelty_floor"]
    # causality guidance (doc/search.md): weight of the predicted
    # relation-coverage gain in the final candidate pick, added on top
    # of the surrogate probability (or the normalized fitness when no
    # surrogate has trained). Only consulted once a CoverageMap is
    # wired via enable_guidance(); with none wired the search is
    # bit-identical to pre-guidance behavior.
    guidance_bonus: float = SEARCH_DEFAULTS["guidance_bonus"]
    # generations per dispatch of the fused island step
    # (doc/performance.md "Fused search loop"): lax.scan over
    # fused_chunk generations with the island state DONATED, traces
    # and archives device-resident across run() calls, host I/O
    # double-buffered against the next chunk's compute. A dispatch-shape
    # choice only: results do not depend on it (same key fold order;
    # pinned by tests/test_fused_loop.py), and 1 is one dispatch per
    # generation.
    fused_chunk: int = SEARCH_DEFAULTS["fused_chunk"]


class BestSchedule(NamedTuple):
    delays: np.ndarray  # f32[H] seconds per hint bucket
    faults: np.ndarray  # f32[H] fault probability per hint bucket
    fitness: float


# -- device-resident buffers (fused search loop) ---------------------------

#: stored runs per call of the batched embed program
#: (``ops.schedule.batched_trace_features``): ONE chunk shape whatever
#: the depth, so a history that grows by a run per request never meets
#: a new shape — a warm request compiles nothing
EMBED_CHUNK = 64

_row_update_jit = None
_rows_scatter_jit = None


def _device_rows_scatter(archive, failures, rows, archive_slots,
                         failure_slots):
    """Write a chunk of embedded rows into the device-resident rings in
    place: one scatter per ring with the ring DONATED, from rows
    already on the device (the embed program's output), both in ONE
    compiled call — so the program exists from the first warm request
    on, whether or not a failure has been written yet. ``*_slots``
    i32[C] name each row's slot in that ring; a row that is not
    written there (chunk padding, a success in the failure ring, a
    slot a later row of the request takes) carries the ring's size,
    which ``mode="drop"`` discards. Slots in range are distinct (the
    caller keeps the last write of each), so the result does not
    depend on the scatter's order."""
    global _rows_scatter_jit
    import jax

    if _rows_scatter_jit is None:
        def rows_scatter(a, f, r, sa, sf):
            return (a.at[sa].set(r, mode="drop"),
                    f.at[sf].set(r, mode="drop"))

        _rows_scatter_jit = jax.jit(rows_scatter, donate_argnums=(0, 1))
    return _rows_scatter_jit(archive, failures, rows, archive_slots,
                             failure_slots)


def _device_row_update(buf, row, slot: int):
    """Write one row of a device-resident 2-D buffer in place:
    ``dynamic_update_slice`` with the buffer DONATED, so a resident
    trace row costs one [L]-row upload instead of re-staging the whole
    buffer next run. ``slot`` is traced — every occupancy hits the
    same compiled update. One jit serves all buffers (cache keys on
    shape/dtype)."""
    global _row_update_jit
    import jax
    import jax.numpy as jnp

    if _row_update_jit is None:
        def row_update(b, r, s):
            return jax.lax.dynamic_update_slice(b, r[None], (s, 0))

        _row_update_jit = jax.jit(row_update, donate_argnums=(0,))
    return _row_update_jit(buf, jnp.asarray(row),
                           jnp.asarray(slot, jnp.int32))


@functools.lru_cache(maxsize=None)
def _gathered(mesh):
    """Compiled device-to-device all-gather over ``mesh`` (one chip: no-op)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.jit(lambda x: x, out_shardings=NamedSharding(mesh, P()))


class _ResidentTraces:
    """Device-resident encoded-trace rows for the campaign's lifetime.

    The policy's ingest re-encodes a sliding window of recent reference
    traces every search request; pre-fusion, every request re-uploaded
    the whole stack. Here each distinct trace (content-keyed) is
    uploaded ONCE into a row of a fixed device buffer (appends via the
    donated ``dynamic_update_slice`` helper); a request's ordered view
    is assembled device-side by a row gather. The buffers are
    ``[capacity, L]`` with ``L`` the length the caller holds (a
    search's length class, :meth:`ScheduleSearch._hold_length`), and a view
    is ``[T, L]`` whatever the references' own lengths: a shorter
    trace's tail carries ``te.pad_trace_row``'s fills (masked, so it
    adds no event), and the live part of every row is value-identical
    to ``te.stack_traces`` of the same references (the
    fused-vs-unfused contract). Rows whose trace has left the
    reference window are evicted oldest-first when the buffer is full;
    a view at another length than the resident one re-stages the
    buffers at that length (the store keeps no length of its own: the
    caller's class only grows, so this happens once per step of it).
    """

    def __init__(self, capacity: int = 16):
        self.capacity = capacity
        self.slots: dict = {}  # digest -> row index
        self.order: list = []  # digests, oldest first (eviction order)
        self.bufs = None  # dict name -> device array [N, L]
        self.L = 0
        self.appends = 0  # rows uploaded incrementally (tests)
        self.rebuilds = 0  # full re-stagings (tests)

    @staticmethod
    def key_of(enc: "te.EncodedTrace") -> str:
        import hashlib

        h = hashlib.blake2b(digest_size=16)
        h.update(enc.hint_ids.tobytes())
        h.update(enc.arrival.tobytes())
        h.update(enc.mask.tobytes())
        h.update(enc.faultable.tobytes())
        return h.hexdigest()

    def _put(self, key: str, enc: "te.EncodedTrace", slot: int) -> None:
        """Write one trace's rows at ``slot`` — the ONE way a row gets
        into the buffers, for the first staging and for every later
        append, so the row update (one program per dtype) is lowered
        where the buffers are born and no later request can find it
        cold, whenever its run first moves the reference envelope.
        The row is padded to the buffers' length with
        ``te.pad_trace_row``, the host stacker's exact pad fills."""
        rows = te.pad_trace_row(enc, self.L)
        for name in self.bufs:
            self.bufs[name] = _device_row_update(
                self.bufs[name], rows[name], slot)
        self.slots[key] = slot
        self.order.append(key)

    def _rebuild(self, encs, keys, length: int) -> None:
        import jax.numpy as jnp

        self.capacity = max(self.capacity, len(encs))
        self.L = length
        shape = (self.capacity, self.L)
        self.bufs = {
            "hint": jnp.asarray(np.zeros(shape, np.int32)),
            "arr": jnp.asarray(np.zeros(shape, np.float32)),
            "mask": jnp.asarray(np.zeros(shape, bool)),
            "flt": jnp.asarray(np.zeros(shape, bool)),
        }
        self.slots = {}
        self.order = []
        for k, e in zip(keys, encs):
            if k not in self.slots:
                self._put(k, e, len(self.slots))
        self.rebuilds += 1
        obs.resident_trace_rows("restage", len(self.slots))

    def _append(self, key: str, enc: "te.EncodedTrace", live) -> None:
        if len(self.slots) < self.capacity:
            slot = len(self.slots)
        else:
            # evict the oldest row not in the current reference window
            victim = next(k for k in self.order if k not in live)
            slot = self.slots.pop(victim)
            self.order.remove(victim)
            obs.resident_trace_rows("evict")
        self._put(key, enc, slot)
        self.appends += 1
        obs.resident_trace_rows("append")

    def view(self, encs, length: int):
        """Device arrays (hint, arrival, mask, faultable), each
        ``[T, length]`` (no trace of ``encs`` is longer), for the
        ordered references — uploading only rows not already
        resident."""
        import jax.numpy as jnp

        keys = [self.key_of(e) for e in encs]
        live = set(keys)
        if (self.bufs is None or length != self.L
                or len(live) > self.capacity):
            self._rebuild(encs, keys, length)
        else:
            for k, e in zip(keys, encs):
                if k not in self.slots:
                    self._append(k, e, live)
        idx = jnp.asarray([self.slots[k] for k in keys], jnp.int32)
        return tuple(self.bufs[name][idx]
                     for name in ("hint", "arr", "mask", "flt"))


def make_score_weights(
    *,
    release_mode: str,
    w_novelty: float,
    w_bug: float,
    w_delay_cost: float,
    w_fault_cost: float,
    tau: float,
    reorder_gap: float,
    reorder_window: float,
) -> ScoreWeights:
    """ScoreWeights for a release mode — one home for the subtle part:
    scoring must model the same realization the control plane uses.
    Order mode permutes within reorder_window batches by the table's
    priorities; delay mode adds the table to arrivals. delay_cost=0 in
    order mode: uniform priority shifts don't change the permutation,
    so penalizing the table's mean would only drive priorities onto the
    0 clip boundary (collapsing to arrival order via the tie-break);
    tau of the order of the gap keeps adjacent ranks' precedence
    features saturated."""
    if release_mode == "reorder":
        gap = max(reorder_gap, 1e-4)
        return ScoreWeights(
            novelty=w_novelty, bug=w_bug, fault_cost=w_fault_cost,
            order_mode=True, order_gap=gap,
            order_window=max(reorder_window, 0.0),
            tau=gap * 0.5, delay_cost=0.0,
        )
    return ScoreWeights(
        novelty=w_novelty, bug=w_bug, delay_cost=w_delay_cost,
        fault_cost=w_fault_cost, tau=tau,
    )


def build_search_from_params(params: dict, mesh=None):
    """The search from the flat JSON-able knobs the policy states
    (``TPUSearchPolicy._search_params``, in-process or over the
    sidecar's wire): the ONE place that turns knobs into a
    ``SearchConfig``, weights, the search and its guidance wiring. A
    knob ``params`` leaves out takes its ``SEARCH_DEFAULTS`` value; the
    keys an older policy still states for the backend that went
    (``REMOVED_KEYS``, policy/tpu.py) build the same search as params
    without them, and a backend other than the GA is refused."""
    refuse_search_backend(params.get("search_backend"))
    p = {**SEARCH_DEFAULTS, **params}
    cfg = SearchConfig(
        H=p["H"], L=p["L"], K=p["K"],
        population=p["population"],
        migrate_k=p["migrate_k"],
        seed=p["seed"],
        ga=GAConfig(max_delay=p["max_interval"],
                    max_fault=p["max_fault"]),
        weights=make_score_weights(
            release_mode=p["release_mode"],
            w_novelty=p["w_novelty"], w_bug=p["w_bug"],
            w_delay_cost=p["w_delay_cost"],
            w_fault_cost=p["w_fault_cost"], tau=p["tau"],
            reorder_gap=p["reorder_gap"],
            reorder_window=p["reorder_window"]),
        surrogate_topk=p["surrogate_topk"],
        min_failure_signatures=p["min_failure_signatures"],
        novelty_floor=p["novelty_floor"],
        guidance_bonus=p["guidance_bonus"],
        fused_chunk=p["fused_chunk"],
    )
    search = ScheduleSearch(cfg, mesh=mesh, n_devices=p["devices"])
    if p["guidance"]:
        # wired BEFORE any checkpoint load/ingest so the archive's
        # DAG-shape feature fragments stay slot-aligned
        search.enable_guidance(p["guidance_width"] or None,
                               p["guidance_window"] or None)
    return search


#: where the persistent compile cache lives when the caller placed none:
#: a fixed path inside the checkout, so every process of one experiment
#: (sidecar, in-process ``run`` children, chip_smoke.py phases) shares
#: it — the directory is part of the cache key, so it must never move
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache() -> None:
    """Place XLA's persistent compilation cache. One rule: a caller who
    set ``JAX_COMPILATION_CACHE_DIR`` owns the placement (JAX reads the
    variable itself; nothing is set in code); otherwise the cache goes
    to :data:`COMPILE_CACHE_DIR`. Policy searches run inside
    short-lived ``run`` processes (SURVEY.md 3.1 — the repro loop is
    many processes); without the cache every one re-pays the fused
    step's compile."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


#: a ring of ``_EmbedBatch.writes`` -> its ``ring`` label on the
#: ``nmz_ring_rows_*`` counters
_RING_LABEL = {"archive": "archive", "failures": "failure"}


class _EmbedBatch:
    """What an open :meth:`ScheduleSearch.embed_batch` has queued: the
    traces to embed, in order, and per ring the ``(slot, row)`` writes
    the adds worked out. ``calls`` = device calls its flush made,
    ``groups`` = padded trace lengths among the queued traces (all of
    them embedded at the search's length class, by one program),
    ``overwrites`` = per ring, the writes whose slot held a live row."""

    def __init__(self) -> None:
        self.encs: list = []
        self.writes: dict = {"archive": [], "failures": []}
        self.overwrites: dict = {"archive": 0, "failures": 0}
        # id(trace) -> its archive row, until a failure-ring write
        # claims it: a new failure's row IS its archive row, and no row
        # is written to two slots of one ring
        self.unclaimed: dict = {}
        self.calls = self.groups = 0

    def queue(self, encoded: te.EncodedTrace) -> int:
        self.encs.append(encoded)
        return len(self.encs) - 1


class ScheduleSearch:
    """The search: the precedence-pair sample, the novelty/failure
    feature archives (host ring buffers mirrored to the device), the
    island GA's state on the mesh, the re-rank, and the ``.npz``
    checkpoint."""

    #: the checkpoint's ``backend`` tag and the ``backend`` label of the
    #: search gauges (doc/observability.md)
    BACKEND = "ga"

    def __init__(self, cfg: SearchConfig = SearchConfig(),
                 mesh=None, n_devices: Optional[int] = None):
        import jax

        from namazu_tpu.parallel.islands import init_island_state
        from namazu_tpu.parallel.mesh import make_mesh

        configure_compile_cache()
        # the first search of a process is what starts counting its
        # lowerings
        obs.ensure_compile_listener()
        self.cfg = cfg
        self.pairs = te.sample_pairs(cfg.K, cfg.H, cfg.seed)
        # neutral (0.5) features = "no information"; rings overwrite oldest
        self.archive = np.full((cfg.archive_size, cfg.K), 0.5, np.float32)
        # label per archive slot: did that run reproduce the bug? (the
        # surrogate's training target; slots beyond _archive_n are unused)
        self.archive_labels = np.zeros((cfg.archive_size,), np.float32)
        self._archive_n = 0
        self.failures = np.full((cfg.failure_size, cfg.K), 0.5, np.float32)
        self._failure_n = 0
        # failure-signature dedupe: ingest re-feeds the WHOLE stored
        # history every search request, so without it the failure ring
        # fills with copies of the same 1-2 signatures and crowds out
        # older distinct ones — exactly the thin-signature regime the
        # novelty anneal and the cross-batch pool exist to escape.
        # Slot-aligned digests (evicted slot -> digest leaves the set).
        self._failure_digests = [""] * cfg.failure_size
        self._failure_digest_set: set = set()
        self._batch: Optional[_EmbedBatch] = None  # the open embed_batch
        # the ONE trace length this search's programs take: the longest
        # encoded length among every trace it has met, stored runs and
        # references alike (_hold_length). Not in the checkpoint: every
        # ingest hands over the whole stored history (models/ingest.py),
        # so a restored search's first request finds it again
        self.length_class = 0
        self.generations_run = 0
        # optional shared-surrogate hook (doc/knowledge.md): a callable
        # ``feats [N, K] -> probs [N] | None`` serving predictions from
        # the knowledge service's cross-tenant model. Consulted only
        # when the LOCAL surrogate is still too thin to train (the
        # exact cold-start window cross-campaign knowledge exists for);
        # None / a None return degrades to the fitness argmax
        self.remote_surrogate = None
        # causality guidance (doc/search.md): the per-campaign relation
        # CoverageMap, wired by enable_guidance() (policy/sidecar, only
        # when the guidance knob AND the obs plane are on). None = the
        # exact pre-guidance blind search — no extra features, no bias,
        # no bonus.
        self.guidance = None
        # per-archive-slot DAG-shape feature fragment (f32[size, G]),
        # allocated with the map: the surrogate's feature space becomes
        # [precedence K | guidance G] and the (scenario, pairs_fp, K')
        # walling keeps it from ever pooling with unguided campaigns
        self.guidance_feats = None
        # fault half of the genome is scored only when faults can be
        # non-zero; coin=None keeps the pre-config-4 jit cache entry
        self._coin = (te.fault_coin(cfg.seed, cfg.H)
                      if cfg.ga.max_fault > 0 else None)
        self.mesh = mesh if mesh is not None else make_mesh(n_devices)
        n_islands = self.mesh.size
        # population must divide evenly across islands
        per_island = max(1, cfg.population // n_islands)
        self.population = per_island * n_islands

        self._key = jax.random.PRNGKey(cfg.seed)
        self._state = init_island_state(
            jax.random.PRNGKey(cfg.seed + 1), self.population, cfg.H, cfg.ga
        )
        self._surrogate = None  # built lazily on first labeled training
        # fused-loop machinery (doc/performance.md "Fused search loop"):
        # per-chunk-length fused step cache, device mirrors of the host
        # archive rings (kept in sync by _mirror_rows' scatters),
        # and the device-resident reference-trace store
        self._fused_steps: dict = {}
        self._dev_mirrors = {"archive": None, "failures": None}
        self._dev_pairs = None
        self._dev_pairs_src = None
        self._dev_coin = None
        self._traces = _ResidentTraces()
        # host-side snapshot of (best_delays, best_faults, best_fitness)
        # from the last COMPLETED round: donation means a failed fused
        # dispatch leaves self._state pointing at deleted buffers, and
        # this (a few KB) is what _recover_state rebuilds the best from
        self._best_snapshot = None

    # -- causality guidance (doc/search.md) -------------------------------

    def enable_guidance(self, width: Optional[int] = None,
                        window: Optional[int] = None,
                        fresh: bool = False):
        """Wire the relation-coverage map (idempotent; a changed bitmap
        space rebuilds it — bit indices are only comparable within one
        (H, width, window) space). ``fresh`` rebuilds unconditionally:
        ingest passes it so the map stays a pure function of (stored
        history + fleet coverage) per ingest — a sidecar-cached search
        serving repeated requests must not double-observe the same
        history into one accumulating map. Returns the map."""
        from namazu_tpu.guidance import (
            DEFAULT_WIDTH,
            DEFAULT_WINDOW,
            GUIDANCE_DIMS,
            CoverageMap,
        )

        width = int(width or DEFAULT_WIDTH)
        window = int(window or DEFAULT_WINDOW)
        g = self.guidance
        if (g is None or fresh or g.width != width
                or g.window != window or g.H != self.cfg.H):
            self.guidance = CoverageMap(H=self.cfg.H, width=width,
                                        window=window)
        if self.guidance_feats is None:
            self.guidance_feats = np.zeros(
                (self.cfg.archive_size, GUIDANCE_DIMS), np.float32)
            # guidance wired onto a LIVE search (obs toggled on between
            # rounds): the feature space just widened, so a surrogate
            # trained at the old width and archive rows without aligned
            # fragments are both stale — same contract as the
            # checkpoint-restore width guard. The next ingest re-feeds
            # the full history with fragments attached.
            self._surrogate = None
            if self._archive_n > 0:
                self.archive[:] = 0.5
                self.archive_labels[:] = 0.0
                self._archive_n = 0
                self._mirror_invalidate()
        return self.guidance

    def _guidance_dims(self) -> int:
        return (0 if self.guidance_feats is None
                else self.guidance_feats.shape[1])

    def _guidance_feats_of(self, realized: te.EncodedTrace,
                           arrival: Optional[te.EncodedTrace]
                           ) -> np.ndarray:
        """DAG-shape fragment of one executed run: program order from
        the arrival view, dispatch order from the realized release
        times. Without an arrival view (legacy call sites) the realized
        view anchors both — the ordering fragment is still exact, only
        the crossing scalars degenerate to zero reordering."""
        from namazu_tpu.guidance import dag_shape_features

        src = arrival if arrival is not None else realized
        m = realized.mask
        return dag_shape_features(
            realized.hint_ids[m], src.arrival[m], realized.arrival[m],
            width=self.guidance.width, dims=self._guidance_dims())

    def set_occupied_buckets(self, occupied) -> None:
        """Refit the precedence-pair sample to the hint buckets actually
        observed in the recorded traces (``te.informative_pairs``) so the
        feature space resolves realizable precedences instead of mostly
        absent-vs-absent neutral pairs.

        When the pairs actually change, every stored feature is in the
        OLD space: the archives are cleared (the caller re-ingests the
        full history right after, ``policy/tpu.py _ingest_history``) and
        the best-so-far fitness is reset. Checkpoints persist the pairs,
        so a stable hint population across runs keeps archives and best
        intact."""
        new = te.informative_pairs(occupied, self.cfg.K, self.cfg.H,
                                   self.cfg.seed)
        if np.array_equal(new, self.pairs):
            return
        self.pairs = new
        self._mirror_invalidate()
        self.archive[:] = 0.5
        self.archive_labels[:] = 0.0
        if self.guidance_feats is not None:
            self.guidance_feats[:] = 0.0  # slot-aligned with archive
        self._archive_n = 0
        self.failures[:] = 0.5
        self._failure_n = 0
        # the caller re-ingests the full history right after, so the
        # digests must clear with the features they key
        self._failure_digests = [""] * self.cfg.failure_size
        self._failure_digest_set.clear()
        self._reset_best()

    def _reset_best(self) -> None:
        """Invalidate the best-so-far record (feature space changed)."""
        import jax.numpy as jnp

        self._state = self._state._replace(
            best_fitness=jnp.full((), -jnp.inf, jnp.float32))

    # -- embedding executed runs into the rings ----------------------------

    def _hold_length(self, encs) -> int:
        """The search's length class once it has met ``encs``: the
        longest encoded length (``te._auto_length``: a multiple of
        ``te.L_QUANTUM`` unless the caller stated one) of any trace it
        was ever handed, which never shrinks. The embed program, the
        resident reference rows, the fused step and the re-rank all
        take this one length, so which runs a request happens to hold
        changes no program's shape. An ingest hands over the whole
        stored history at once (``_flush``), so the first request fixes
        the class; a later run past it STEPS it — one re-staging of the
        resident rows and one lowering of each program at the new
        length, counted (``nmz_length_class_steps_total``)."""
        longest = max((e.hint_ids.shape[0] for e in encs), default=0)
        if longest > self.length_class:
            if self.length_class:
                log.info("a trace of padded length %d steps the search's "
                         "length class from %d: its programs lower once "
                         "more", longest, self.length_class)
                obs.length_class_step()
            self.length_class = longest
        return self.length_class

    def _embed_chunks(self, encs):
        """Yield ``(indices, rows)`` per device call of the batched
        embed program: the traces :data:`EMBED_CHUNK` at a time, every
        one at the search's length class (a shorter run's tail and a
        short chunk's spare rows masked), so a request is
        ``ceil(N / EMBED_CHUNK)`` calls of ONE program whatever lengths
        its runs have. ``rows`` f32[EMBED_CHUNK, K] stays on the
        device; ``rows[j]`` embeds ``encs[indices[j]]``."""
        from namazu_tpu.ops.schedule import batched_trace_features

        embed = batched_trace_features(self.cfg.weights.tau, self.cfg.H)
        L = self._hold_length(encs)
        # the runs a per-length embed would have grouped apart
        obs.embed_traces(
            len(encs), sum(e.hint_ids.shape[0] < L for e in encs))
        for k in range(0, len(encs), EMBED_CHUNK):
            indices = list(range(k, min(k + EMBED_CHUNK, len(encs))))
            hint = np.zeros((EMBED_CHUNK, L), np.int32)
            arrival = np.zeros((EMBED_CHUNK, L), np.float32)
            mask = np.zeros((EMBED_CHUNK, L), bool)
            for j, i in enumerate(indices):
                n = encs[i].hint_ids.shape[0]
                hint[j, :n] = encs[i].hint_ids
                arrival[j, :n] = encs[i].arrival
                mask[j, :n] = encs[i].mask
            obs.ingest_embed_call()
            yield indices, embed(hint, arrival, mask, self.pairs)

    def _embed(self, encs) -> np.ndarray:
        """Feature rows f32[N, K] of executed traces, in order."""
        out = np.empty((len(encs), self.cfg.K), np.float32)
        for indices, rows in self._embed_chunks(encs):
            out[indices] = np.asarray(rows)[:len(indices)]
        return out

    def _feats_of(self, encoded: te.EncodedTrace) -> np.ndarray:
        return self._embed([encoded])[0]

    @contextlib.contextmanager
    def embed_batch(self):
        """Defer the embedding of every ``add_executed_trace`` /
        ``add_failure_trace`` inside the context to its exit: the adds
        do their bookkeeping at once (slot, label, digest, guidance
        fragment, fill count — so dedupe and eviction see each other
        exactly as in a per-run loop) and queue the trace; the exit
        embeds all of them in ``ceil(N / EMBED_CHUNK)`` device calls
        per trace length and writes the rows. Ingest wraps a whole
        request's history in one; an add outside any is the batch of
        one. Re-entrant: the outermost context flushes. Between an add
        and the flush the rings' ROWS lag their counts — nothing reads
        them inside a batch. Yields the batch (``calls``)."""
        if self._batch is not None:
            yield self._batch
            return
        batch = self._batch = _EmbedBatch()
        try:
            yield batch
        finally:
            # also on an error in the body: the adds that did happen
            # advanced the fill counts, and their rows must follow
            self._batch = None
            self._flush(batch)

    def _flush(self, batch: _EmbedBatch) -> None:
        """Embed a batch's traces and write its rows: per device call
        one fetch (the host rings, labels and checkpoints need the
        rows) and, where the device mirrors exist, one call that
        scatters into both from the rows already on the device. Of
        several writes to one slot (a request with more rows than the
        ring) the last is kept — what the per-run order leaves
        behind."""
        rings = {"archive": self.archive, "failures": self.failures}
        # ring -> {row: slot}, through {slot: row of its LAST write}
        slot_of = {
            which: {row: slot for slot, row in dict(writes).items()}
            for which, writes in batch.writes.items()}
        batch.groups = len({e.hint_ids.shape[0] for e in batch.encs})
        for which, writes in batch.writes.items():
            obs.ring_rows(_RING_LABEL[which], len(writes),
                          batch.overwrites[which])
        for indices, rows in self._embed_chunks(batch.encs):
            batch.calls += 1
            host = np.asarray(rows)
            slots = {}
            for which, ring in rings.items():
                size = ring.shape[0]
                slots[which] = np.full((EMBED_CHUNK,), size, np.int32)
                for j, i in enumerate(indices):
                    slots[which][j] = slot_of[which].get(i, size)
                written = slots[which] < size
                ring[slots[which][written]] = host[written]
            self._mirror_rows(rows, slots)

    def add_executed_trace(self, encoded: te.EncodedTrace,
                           reproduced: bool = False,
                           arrival: Optional[te.EncodedTrace] = None
                           ) -> None:
        """Record an executed run's interleaving into the novelty archive,
        labeled with whether it reproduced the bug (surrogate target).
        ``arrival`` (the same run's arrival-anchored view) feeds the
        guidance plane's DAG-shape features when guidance is wired."""
        with self.embed_batch() as batch:
            slot = self._archive_n % self.cfg.archive_size
            row = batch.queue(encoded)
            batch.writes["archive"].append((slot, row))
            batch.overwrites["archive"] += \
                self._archive_n >= self.cfg.archive_size
            batch.unclaimed[id(encoded)] = row
            self.archive_labels[slot] = 1.0 if reproduced else 0.0
            if self.guidance_feats is not None:
                self.guidance_feats[slot] = self._guidance_feats_of(
                    encoded, arrival)
            self._archive_n += 1

    def add_failure_trace(self, encoded: te.EncodedTrace) -> None:
        """Record a bug-reproducing run — the bug-affinity target.
        Idempotent per distinct signature (content digest): re-ingesting
        the same stored failure never spends a ring slot. Inside a
        batch that already queued this trace for the archive, the
        failure ring takes that row instead of a second embedding."""
        from namazu_tpu.models.failure_pool import trace_digest

        digest = trace_digest(encoded)
        if digest in self._failure_digest_set:
            obs.failure_signatures_deduped()
            return
        with self.embed_batch() as batch:
            slot = self._failure_n % self.cfg.failure_size
            evicted = self._failure_digests[slot]
            if evicted:
                self._failure_digest_set.discard(evicted)
            batch.overwrites["failures"] += \
                self._failure_n >= self.cfg.failure_size
            row = batch.unclaimed.pop(id(encoded), None)
            if row is None:
                row = batch.queue(encoded)
            batch.writes["failures"].append((slot, row))
            self._failure_digests[slot] = digest
            self._failure_digest_set.add(digest)
            self._failure_n += 1

    def distinct_failure_signatures(self) -> int:
        """How many distinct failure signatures the archive currently
        holds — the novelty anneal's progress variable."""
        return len(self._failure_digest_set)

    def has_failure_signature(self, digest: str) -> bool:
        """Whether a signature digest is already archived — lets ingest
        skip the whole embed/add path for known pooled entries (not just
        the ring write): without this, every search request re-embeds
        every pooled signature and stuffs duplicate reproduced=True rows
        into the novelty archive / surrogate training set."""
        return digest in self._failure_digest_set

    # -- device-resident mirrors (fused loop) -----------------------------

    def _mirror_rows(self, rows, slots: dict) -> None:
        """A chunk of rows went into the host rings: apply the same
        writes to the device mirrors (one call, both donated), so the
        next fused run stages nothing. No mirrors (none built yet, or
        invalidated — they are built and dropped together) = nothing
        to do: the next fused run stages the host rings."""
        m = self._dev_mirrors
        if m["archive"] is not None and m["failures"] is not None:
            m["archive"], m["failures"] = _device_rows_scatter(
                m["archive"], m["failures"], rows, slots["archive"],
                slots["failures"])

    def _mirror_invalidate(self) -> None:
        """Bulk host-side mutation (checkpoint load, pair refit,
        guidance rewiring): device mirrors rebuild from the host arrays
        on the next fused run. The resident TRACE rows stay — they are
        content-keyed and none of these mutations rewrites a recorded
        trace."""
        self._dev_mirrors = {"archive": None, "failures": None}
        self._dev_pairs = None
        self._dev_pairs_src = None

    def _record_progress(self, generations: int, elapsed: float,
                         schedules_scored: int, best_fitness: float,
                         host_io_s: Optional[float] = None,
                         fit_curve: Optional[list] = None) -> None:
        """Publish one run()'s worth of search telemetry (obs plane):
        generations/sec, jitted-scorer schedules/s, best fitness, and the
        archive occupancies — live counterparts of bench.py's metric.
        ``host_io_s`` (fused loop) is the round's overlapped host-I/O
        lane wall time (doc/performance.md "Fused search loop")."""
        obs.search_round(
            self.BACKEND, generations, elapsed,
            schedules=schedules_scored, best_fitness=best_fitness,
            archive_entries=min(self._archive_n, self.cfg.archive_size),
            failure_entries=min(self._failure_n, self.cfg.failure_size),
            distinct_failures=self.distinct_failure_signatures(),
            host_io_s=host_io_s,
        )
        # flight recorder: the round lands on the run's search track and
        # advances the generation id that tags each policy decision;
        # archive occupancies ride along so the experiment plane can
        # reconstruct convergence/novelty trends per round
        # (obs/analytics.py convergence_stats)
        obs.record_generation(
            self.BACKEND, generations, elapsed, best_fitness,
            archive_entries=min(self._archive_n, self.cfg.archive_size),
            failure_entries=min(self._failure_n, self.cfg.failure_size),
            distinct_failures=self.distinct_failure_signatures(),
            host_io_s=host_io_s,
            fit_curve=fit_curve,
        )

    def labeled_archive(self):
        """(feats [N,K'], labels [N]) of the populated archive slots
        whose outcome is known (NaN labels — pre-surrogate checkpoints —
        are excluded). With guidance wired, K' = K + GUIDANCE_DIMS: the
        DAG-shape fragment rides along, so the surrogate learns from
        ordering SHAPE as well as precedence features."""
        n = min(self._archive_n, self.cfg.archive_size)
        feats, labels = self.archive[:n], self.archive_labels[:n]
        if self.guidance_feats is not None:
            feats = np.hstack([feats, self.guidance_feats[:n]])
        known = np.isfinite(labels)
        return feats[known], labels[known]

    def _device_inputs_fused(self, encoded):
        """``(encs, traces, pairs, archive, failures)`` for the island
        step, device-resident: the ordered trace view comes from the
        resident store (only missing rows upload) at the search's
        length class, ``[T, class]`` whichever runs the references are,
        pairs/archive/failure buffers from the device mirrors (synced
        by ``_mirror_rows``; staged whole only after a bulk
        invalidation). Array VALUES are those of ``te.stack_traces`` of
        the same references, with a masked tail where the class is
        past their longest."""
        import jax.numpy as jnp

        from namazu_tpu.ops.schedule import TraceArrays

        encs = encoded if isinstance(encoded, (list, tuple)) else [encoded]
        h, a, m, fb = self._traces.view(encs, self._hold_length(encs))
        trace = TraceArrays(h, a, m,
                            fb if self._coin is not None else None)
        if self._dev_pairs is None or self._dev_pairs_src is not self.pairs:
            self._dev_pairs = jnp.asarray(self.pairs)
            self._dev_pairs_src = self.pairs
        m = self._dev_mirrors
        if m["archive"] is None or m["failures"] is None:
            # staged whole, through the scatter that keeps them in step
            # from the next ingest on (``_mirror_rows``), with every
            # slot out of range: nothing is written, and the program is
            # lowered in the request that builds the mirrors instead of
            # in the first one that finds them built
            m["archive"], m["failures"] = _device_rows_scatter(
                jnp.asarray(self.archive), jnp.asarray(self.failures),
                np.zeros((EMBED_CHUNK, self.cfg.K), np.float32),
                *(np.full((EMBED_CHUNK,), ring.shape[0], np.int32)
                  for ring in (self.archive, self.failures)))
        return encs, trace, self._dev_pairs, m["archive"], m["failures"]

    def _place_state(self) -> None:
        """Commit the island state to its mesh sharding (population
        sharded over the island axes, scalars/best replicated) BEFORE
        the first fused dispatch. A freshly-initialized (or
        checkpoint-restored / seeded) state is uncommitted, and jit
        keys its cache on concrete shardings: without this, the first
        fused call compiles for the uncommitted layout and the second
        — fed the donated-out, properly-sharded state — compiles AGAIN,
        which is exactly the warm-request jit cost the sidecar exists
        to amortize away. ``device_put`` on an already-placed array is
        a no-op, so steady-state calls cost nothing."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from namazu_tpu.models.ga import Population
        from namazu_tpu.parallel.islands import IslandState

        axes = tuple(self.mesh.axis_names)
        pop_sh = NamedSharding(self.mesh, P(axes))
        rep = NamedSharding(self.mesh, P())
        st = self._state
        self._state = IslandState(
            pop=Population(
                delays=jax.device_put(st.pop.delays, pop_sh),
                faults=jax.device_put(st.pop.faults, pop_sh),
            ),
            gen=jax.device_put(st.gen, rep),
            best_fitness=jax.device_put(st.best_fitness, rep),
            best_delays=jax.device_put(st.best_delays, rep),
            best_faults=jax.device_put(st.best_faults, rep),
        )

    def _fused_step_for(self, generations: int):
        """The jitted fused step for a chunk length (cached: a campaign
        with a fixed generations-per-run sees at most two lengths —
        the chunk and the remainder)."""
        fn = self._fused_steps.get(generations)
        if fn is None:
            from namazu_tpu.parallel.islands import make_fused_island_step

            fn = make_fused_island_step(
                self.mesh, self.cfg.ga, self.cfg.weights,
                migrate_k=self.cfg.migrate_k, generations=generations)
            self._fused_steps[generations] = fn
        return fn

    def seed_population(self, delay_tables) -> None:
        """Inject imitation genomes into the population before evolving.

        The GA's objective (match the failure archive in feature space)
        has local optima the mutation kernel rarely escapes — e.g. the
        asymmetric early/late delivery split that decides a leader
        election. But the control plane already *has* near-reproducing
        genomes: each recorded failure's per-bucket injected delays
        (release - arrival) form a delay table that, replayed against
        similar arrivals, re-enacts that failure's interleaving up to the
        system's reactions. Those tables are spread across the islands
        (one per stride) so every island refines from a demonstration
        instead of from noise; crossover/migration then mix them with the
        evolved material."""
        import jax
        import jax.numpy as jnp

        if len(delay_tables) == 0:
            return
        seeds = np.clip(
            np.stack([np.asarray(t, np.float32) for t in delay_tables]),
            0.0, self.cfg.ga.max_delay)
        n = min(seeds.shape[0], self.population)
        delays = np.array(jax.device_get(self._state.pop.delays))
        stride = max(1, self.population // n)
        idx = [min(i * stride, self.population - 1) for i in range(n)]
        delays[idx] = seeds[:n]
        # uncommitted on purpose: the island step's shard_map shards its
        # inputs itself; a device_put-committed array would pin the
        # population to one device and fail on a multi-device mesh
        self._state = self._state._replace(
            pop=self._state.pop._replace(delays=jnp.asarray(delays)))

    # -- search ----------------------------------------------------------

    def run(self, encoded, generations: int = 50) -> BestSchedule:
        """Evolve against one or more reference traces for N generations.

        Returns the best schedule seen so far (monotonic across calls) —
        unless ``cfg.surrogate_topk > 0`` and the surrogate has trained on
        both outcomes, in which case the evolved population's top-k by
        fitness are re-ranked by predicted P(reproduce) and the winner is
        returned (the candidate worth the next wall-clock replay).

        The device-resident loop (doc/performance.md "Fused search
        loop"): generations run in fused_chunk-sized scans — one jitted
        dispatch each, island state donated — while the host lane drains
        the PREVIOUS chunk's per-generation best-fitness history
        (``jax.device_get`` on arrays the device finished or is
        finishing while the current chunk computes). The host gap shows
        up as ``nmz_search_phase_seconds{phase="host_io"}`` and the
        generation record's ``host_io_s``. Inside ``evolve``: ``place``
        (re-sharding the state), ``dispatch`` (time inside the fused
        calls, accumulated: the host's share of an async dispatch) and
        ``wait`` (the final block on the device). The in-step phases
        are ``jax.named_scope``-annotated in parallel/islands.py,
        visible in a device profile."""
        with obs.search_phase("encode") as attrs:
            encs, trace, pairs, archive, failures = \
                self._device_inputs_fused(encoded)
            attrs["length_class"] = self.length_class
        import jax.numpy as jnp

        if self._coin is not None and self._dev_coin is None:
            self._dev_coin = jnp.asarray(self._coin)
        coin = self._dev_coin if self._coin is not None else None
        nov_scale = jnp.asarray(self.novelty_scale(), jnp.float32)
        bias = (None if self.guidance is None
                else jnp.asarray(self.guidance.mutation_bias()))
        host_io_s = 0.0
        fit_curve: list = []
        pending = None
        dispatch_s, dispatch_t0, dispatches = 0.0, None, 0
        t0 = time.perf_counter()
        with obs.search_phase("evolve"):
            # the whole evolve section recovers as one unit: dispatch
            # is ASYNC, so a device-side failure can surface not at the
            # fused() call but later — at the host lane's device_get of
            # a poisoned history, or at the final block_until_ready.
            # Wherever it surfaces, the donated-in buffers are gone and
            # self._state must be rebuilt, or every later run() of a
            # long-lived sidecar search fails against deleted arrays.
            try:
                with obs.search_phase("place"):
                    self._place_state()  # one jit cache entry, not two
                done = 0
                while done < generations:
                    g = min(self.cfg.fused_chunk, generations - done)
                    fused = self._fused_step_for(g)
                    # the input state is DONATED: keep only the
                    # returned one
                    td = time.monotonic()
                    state, fit_hist = fused(
                        self._state, self._key, trace, pairs, archive,
                        failures, coin, nov_scale, bias)
                    dispatch_s += time.monotonic() - td
                    dispatch_t0 = td if dispatch_t0 is None else dispatch_t0
                    dispatches += 1
                    self._state = state
                    done += g
                    if pending is not None:
                        # double-buffered host lane: drain chunk N-1's
                        # snapshot while chunk N computes on device
                        th = time.perf_counter()
                        with obs.search_phase("host_io"):
                            self._drain_host_lane(pending, fit_curve)
                        host_io_s += time.perf_counter() - th
                    pending = fit_hist
                if pending is not None:
                    th = time.perf_counter()
                    with obs.search_phase("host_io"):
                        self._drain_host_lane(pending, fit_curve)
                    host_io_s += time.perf_counter() - th
                if dispatch_t0 is not None:
                    obs.search_phase_observed("dispatch", dispatch_s,
                                              dispatch_t0,
                                              pieces=dispatches)
                with obs.search_phase("wait"):
                    self._state.best_fitness.block_until_ready()
            except Exception:
                self._recover_state()
                raise
        # one completed evolve, counted where its ``evolve`` span ends
        # (the two agree over any window), under the scorer branch the
        # island step takes for these reference traces' padded length
        order_mode = self.cfg.weights.order_mode
        obs.evolve_request(scorer_branch(trace.hint_ids.shape[-1],
                                         order_mode))
        if not order_mode:
            # delay mode, at every length: the step's first occurrences
            # came from per-trace tables
            obs.evolve_table_request()
        elapsed = time.perf_counter() - t0
        self.generations_run += generations
        # recovery snapshot (tiny: two [H] rows + a scalar): the newest
        # completed round's best, host-side, surviving any later
        # donated-dispatch failure
        self._best_snapshot = (
            np.asarray(self._state.best_delays),
            np.asarray(self._state.best_faults),
            float(self._state.best_fitness),
        )
        # scorer-throughput source label "fused": the serving figure of
        # the fused loop, beside the backend-labeled gauge search_round
        # publishes (doc/observability.md)
        obs.scorer_throughput(
            "fused", generations * self.population / max(elapsed, 1e-9))
        self._record_progress(generations, elapsed,
                              generations * self.population,
                              float(self._state.best_fitness),
                              host_io_s=host_io_s, fit_curve=fit_curve)
        with obs.search_phase("surrogate"):
            picked = self._surrogate_pick(trace, pairs, archive, failures,
                                          nov_scale, encs=encs)
        if picked is not None:
            return picked
        with obs.search_phase("extract"):
            return self.best()

    def _drain_host_lane(self, fit_hist, fit_curve: list) -> None:
        """The overlapped host-I/O work for one completed chunk: fetch
        its per-generation global-best history (blocks only until THAT
        chunk's results exist — the current chunk keeps computing),
        publish live progress, and grow the per-generation curve that
        lands on the round's flight-recorder generation record
        (``fit_curve``). Everything here runs while the device is busy,
        which is what closes the pre-fusion host gaps."""
        vals = np.asarray(fit_hist)
        fit_curve.extend(float(v) for v in vals)
        if vals.size:
            # the gauge is "best fitness seen so far": publish the
            # running max (this run's curve so far, floored at the last
            # completed round's best) — a chunk's own last generation
            # can sit BELOW an earlier best and must not regress it
            prev = (self._best_snapshot[2] if self._best_snapshot
                    else float("-inf"))
            obs.search_progress(self.BACKEND, max(prev, max(fit_curve)))

    def _recover_state(self) -> None:
        """Rebuild a usable island state after a fused dispatch died
        mid-flight: the donated input buffers are deleted, so the
        population restarts fresh (keyed off the generation counter —
        no replayed draws) while the best-so-far tables restore from
        the host snapshot of the last completed round. Progress inside
        the failed round is lost; the object — and a long-lived
        sidecar serving it — keeps working."""
        import jax
        import jax.numpy as jnp

        from namazu_tpu.parallel.islands import init_island_state

        log.warning(
            "fused dispatch failed mid-round; rebuilding island state "
            "(population restarts, best-so-far restored from the last "
            "completed round)")
        self._state = init_island_state(
            jax.random.PRNGKey(self.cfg.seed + 1 + self.generations_run),
            self.population, self.cfg.H, self.cfg.ga)
        self._state = self._state._replace(
            gen=jnp.asarray(self.generations_run, jnp.int32))
        snap = self._best_snapshot
        if snap is not None:
            bd, bf, fit = snap
            self._state = self._state._replace(
                best_fitness=jnp.asarray(fit, jnp.float32),
                best_delays=jnp.asarray(bd),
                best_faults=jnp.asarray(bf),
            )

    def novelty_scale(self) -> float:
        """Annealed multiplier on ``weights.novelty`` (see
        ``SearchConfig.min_failure_signatures``): 1.0 while the failure
        archive holds fewer than the threshold's worth of distinct
        signatures, then decays as threshold/n, floored."""
        ms = self.cfg.min_failure_signatures
        if ms <= 0:
            return 1.0
        n = self.distinct_failure_signatures()
        if n < ms:
            return 1.0
        return max(self.cfg.novelty_floor, ms / n)

    def _fetch_population(self):
        """Population as host numpy arrays (delays, faults)."""
        pop = self._state.pop
        return np.asarray(pop.delays), np.asarray(pop.faults)

    # -- surrogate (BASELINE config 5) ------------------------------------

    #: minimum labeled examples PER CLASS before surrogate re-ranking
    #: may override the fitness argmax: an MLP fit on one positive
    #: re-ranks near-randomly, and handing it veto power over the
    #: evolved best dilutes a good schedule into mush (observed: with a
    #: single recorded failure the re-ranked pick lost the failure's
    #: decisive starve pattern that the argmax carried)
    MIN_CLASS_EXAMPLES = 3

    def _surrogate_input_dims(self) -> int:
        """Surrogate feature width: precedence K, plus the guidance
        plane's DAG-shape fragment when a map is wired. The knowledge
        service keys example stores by this width, so guided and
        unguided campaigns can never pool training data."""
        return self.cfg.K + self._guidance_dims()

    def _train_surrogate(self):
        """Fit the online MLP on the labeled archive; returns it, or None
        when surrogate use is off or either outcome class is still too
        thin to learn from."""
        if self.cfg.surrogate_topk <= 0:
            return None
        feats, labels = self.labeled_archive()
        pos = int((labels > 0.5).sum())
        if min(pos, len(labels) - pos) < self.MIN_CLASS_EXAMPLES:
            return None  # nothing reliably learnable yet
        if self._surrogate is None:
            from namazu_tpu.models.surrogate import RewardSurrogate

            self._surrogate = RewardSurrogate(
                K=self._surrogate_input_dims(), seed=self.cfg.seed)
        self._surrogate.train(feats, labels, epochs=4,
                              seed=self.cfg.seed + self.generations_run,
                              capacity=self.cfg.archive_size)
        return self._surrogate

    def _candidate_guidance(self, delays: np.ndarray, encs):
        """Predicted relation-coverage gain + DAG-shape fragment per
        candidate delay table, simulated against the most recent
        reference trace under the delay-mode release rule
        (``release = arrival + delays[bucket]`` — the same
        counterfactual the scorer anchors on). Returns
        ``(gains f32[k], frags f32[k, G])``."""
        from namazu_tpu.guidance import dag_shape_features

        enc = encs[0]
        m = enc.mask
        buckets = enc.hint_ids[m]
        arrivals = enc.arrival[m]
        k = delays.shape[0]
        gains = np.zeros((k,), np.float32)
        frags = np.zeros((k, self._guidance_dims()), np.float32)
        for i in range(k):
            times = arrivals + delays[i][buckets]
            order = np.argsort(times, kind="stable")
            gains[i] = self.guidance.predicted_gain(buckets[order])
            frags[i] = dag_shape_features(
                buckets, arrivals, times,
                width=self.guidance.width, dims=self._guidance_dims())
        return gains, frags

    def _surrogate_pick(self, trace, pairs, archive, failures,
                        nov_scale=None, encs=()) -> Optional[BestSchedule]:
        """Re-rank the evolved population's fitness top-k; return the
        winner (None = nothing to re-rank with — fitness argmax).
        The base score is predicted P(reproduce): the local online MLP
        once it has both outcome classes (train, score, pick: three
        compiled calls on the resident population, ONE fetch); in the
        cold-start window ``remote_surrogate``'s; with neither, the
        top-k's normalized fitness. A guidance map adds
        ``cfg.guidance_bonus`` x its gain (for both the k rows cross)."""
        surrogate = self._train_surrogate()
        remote = self.remote_surrogate if surrogate is None else None
        guided = self.guidance is not None and len(encs) > 0
        if self.cfg.surrogate_topk <= 0 or (
                surrogate is None and remote is None and not guided):
            return None  # the knob is off, or nothing to re-rank with
        import jax

        from namazu_tpu.models.surrogate import top_rows
        from namazu_tpu.ops import schedule

        k = min(self.cfg.surrogate_topk, self.population)
        # ONE chip's copy of the islands' shards (the Mosaic pair kernel
        # cannot be partitioned); self._state keeps its sharding
        delays, faults = (x.addressable_shards[0].data
                          for x in _gathered(self.mesh)(self._state.pop))
        # by its module attribute and OUTSIDE jit: a launcher may wrap
        # the name to keep the [P] fitness; trace arrives stacked [T, L]
        fitness, feats = schedule.score_population_multi(
            delays, trace, pairs, archive, failures, self.cfg.weights,
            faults=None if self._coin is None else faults,
            coin=self._dev_coin, novelty_scale=nov_scale)
        if remote is None and not guided:
            table, drops, fit = jax.device_get(
                surrogate.pick(fitness, feats, delays, faults, k))
            obs.rerank_request("compiled")
            return BestSchedule(table, drops, float(fit))
        cand, top_delays, top_faults, f = jax.device_get(
            top_rows(fitness, feats, delays, faults, k))
        obs.rerank_request("host")
        gains, frags = (self._candidate_guidance(top_delays, encs)
                        if guided else (None, None))
        base = None
        if surrogate is not None or remote is not None:
            full = cand if frags is None else np.hstack([cand, frags])
            base = (surrogate.predict(full) if surrogate is not None
                    else remote(full))
        if base is None:
            if gains is None:
                return None  # outage/untrained, no guidance: argmax
            span = float(f.max() - f.min())
            base = (f - f.min()) / span if span > 0 else np.zeros_like(f)
        score = (np.asarray(base) if gains is None
                 else np.asarray(base) + self.cfg.guidance_bonus * gains)
        winner = int(np.argmax(score))
        return BestSchedule(top_delays[winner], top_faults[winner],
                            float(f[winner]))

    def best(self) -> BestSchedule:
        return BestSchedule(
            delays=np.asarray(self._state.best_delays),
            faults=np.asarray(self._state.best_faults),
            fitness=float(self._state.best_fitness),
        )

    # -- persistence -----------------------------------------------------

    def save(self, path: str) -> None:
        import jax

        with obs.search_phase("save"):
            flat = {
                "backend": np.asarray(self.BACKEND),
                "hint_space": np.asarray(te.HINT_SPACE),
                "pairs": self.pairs,
                "archive": self.archive,
                "archive_labels": self.archive_labels,
                "archive_n": np.asarray(self._archive_n),
                "failures": self.failures,
                "failure_n": np.asarray(self._failure_n),
                "failure_digests": np.asarray(self._failure_digests),
                "key": np.asarray(jax.random.key_data(self._key)),
                "generations_run": np.asarray(self.generations_run),
            }
            if self.guidance_feats is not None:
                flat["guidance_feats"] = self.guidance_feats
            pop_delays, pop_faults = self._fetch_population()
            flat.update(
                pop_delays=pop_delays,
                pop_faults=pop_faults,
                gen=np.asarray(self._state.gen),
                best_fitness=np.asarray(self._state.best_fitness),
                best_delays=np.asarray(self._state.best_delays),
                best_faults=np.asarray(self._state.best_faults),
            )
            if self._surrogate is not None:
                from jax.flatten_util import ravel_pytree

                vec, _ = ravel_pytree(self._surrogate.state.params)
                flat["surrogate_params"] = np.asarray(vec)
            tmp = path + ".tmp.npz"
            np.savez(tmp, **flat)
            os.replace(tmp, path)

    def load(self, path: str) -> None:
        import jax
        import jax.numpy as jnp

        from namazu_tpu.models.ga import Population
        from namazu_tpu.parallel.islands import IslandState

        with np.load(path) as z:
            # pre-backend-tag checkpoints have no "backend" key
            saved = str(z["backend"]) if "backend" in z else self.BACKEND
            if saved != self.BACKEND:
                raise ValueError(
                    f"checkpoint {path} was written by the {saved!r} "
                    f"search backend, which this build does not have "
                    f"(its one search is {self.BACKEND!r}); delete it "
                    "and search again"
                )
            if ("best_delays" in z
                    and z["best_delays"].shape != (self.cfg.H,)):
                # a mismatched genome length would load silently and
                # IndexError later on the policy's event hot path
                raise ValueError(
                    f"checkpoint {path} has H={z['best_delays'].shape[0]} "
                    f"delay buckets, config has H={self.cfg.H}"
                )
            space = te.checkpoint_hint_space(z)
            if space != te.HINT_SPACE:
                # every archived feature and evolved delay table keys
                # buckets in the old hint space; resuming from it would
                # deliver arbitrary delays under a "searched schedule" log
                raise ValueError(
                    f"checkpoint {path} was built in hint space {space!r}; "
                    f"this build hashes {te.HINT_SPACE!r} — delete it and "
                    "re-record"
                )
            if "pairs" in z:  # pre-informative-pairs checkpoints lack it
                self.pairs = z["pairs"]
            self.archive = z["archive"]
            if "archive_labels" in z:
                self.archive_labels = z["archive_labels"]
            else:
                # pre-surrogate checkpoint: outcomes of the archived runs
                # are unknown — NaN marks the slots unusable as training
                # data (a 0.0 default would teach the surrogate that the
                # runs that DID reproduce predict no-repro)
                self.archive_labels = np.full(
                    (self.cfg.archive_size,), np.nan, np.float32)
            self._archive_n = int(z["archive_n"])
            if self.guidance_feats is not None:
                if "guidance_feats" in z \
                        and z["guidance_feats"].shape \
                        == self.guidance_feats.shape:
                    self.guidance_feats = np.array(z["guidance_feats"])
                else:
                    # a pre-guidance (or differently-sized) checkpoint:
                    # its archive rows have no aligned DAG-shape
                    # fragment, and training a widened surrogate on
                    # zero-filled fragments would teach it that shape
                    # features mean nothing. Drop the archive — the
                    # very next ingest re-feeds the full stored history
                    # with fragments attached (models/ingest.py).
                    self.archive[:] = 0.5
                    self.archive_labels[:] = 0.0
                    self._archive_n = 0
            self.failures = z["failures"]
            self._failure_n = int(z["failure_n"])
            if "failure_digests" in z:
                self._failure_digests = [str(d) for d in
                                         z["failure_digests"]]
                self._failure_digest_set = {d for d in
                                            self._failure_digests if d}
            else:
                # pre-dedupe checkpoint: ring contents are unkeyed (and
                # possibly duplicated); the next ingest re-keys afresh
                self._failure_digests = [""] * self.cfg.failure_size
                self._failure_digest_set = set()
            self._key = jax.random.wrap_key_data(jnp.asarray(z["key"]))
            self.generations_run = int(z["generations_run"])
            pd = np.asarray(z["pop_delays"])
            pf = np.asarray(z["pop_faults"])
            expected = (self.population, self.cfg.H)
            if pd.shape != expected or pf.shape != expected:
                # a population/genome-width mismatch (config changed
                # between runs, or a checkpoint from a differently-sized
                # mesh) must not crash the load OR shard-mismatch later
                # inside the step: keep the fresh population and
                # re-evolve — archives, best tables, and the RNG stream
                # restore as usual (the PR 11 width-mismatch-retrains
                # rule extended to the island state; pinned by
                # tests/test_fused_loop.py)
                log.warning(
                    "checkpoint population %s does not fit this config %s; "
                    "keeping a fresh population (archives and best tables "
                    "restored)", pd.shape, expected)
                pop = self._state.pop
            else:
                pop = Population(delays=jnp.asarray(pd),
                                 faults=jnp.asarray(pf))
            self._state = IslandState(
                pop=pop,
                gen=jnp.asarray(z["gen"]),
                best_fitness=jnp.asarray(z["best_fitness"]),
                best_delays=jnp.asarray(z["best_delays"]),
                best_faults=jnp.asarray(z["best_faults"]),
            )
            # the recovery snapshot tracks the restored best too — a
            # fused dispatch failing right after a checkpoint load must
            # not lose the loaded tables (_recover_state)
            self._best_snapshot = (
                np.asarray(z["best_delays"]),
                np.asarray(z["best_faults"]),
                float(z["best_fitness"]),
            )
            if "surrogate_params" in z:
                from jax.flatten_util import ravel_pytree

                from namazu_tpu.models.surrogate import RewardSurrogate

                # deterministic re-init yields the unravel structure;
                # the optimizer restarts (momentum is not worth
                # persisting)
                self._surrogate = RewardSurrogate(
                    K=self._surrogate_input_dims(), seed=self.cfg.seed)
                ref, unravel = ravel_pytree(self._surrogate.state.params)
                vec = jnp.asarray(z["surrogate_params"])
                if vec.shape == ref.shape:
                    self._surrogate.state = self._surrogate.state._replace(
                        params=unravel(vec)
                    )
                else:
                    # guidance was toggled since this checkpoint was
                    # written: the feature widths differ, so the
                    # persisted weights don't apply — retrain from the
                    # labeled archive instead of failing the whole load
                    self._surrogate = None
        # every buffer just changed wholesale; device-resident mirrors
        # (fused loop) must rebuild from the restored host arrays
        self._mirror_invalidate()


#: held by tests/benchmarks/, which patch ``SearchBase._flush`` and
#: ``.add_executed_trace``: the same object, so they land on what runs
SearchBase = ScheduleSearch
