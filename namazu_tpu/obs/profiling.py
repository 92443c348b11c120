"""Continuous sampling profiler (doc/observability.md "Profiling").

Every process class (orchestrator, campaign ``run`` children, edge
inspectors, uds/shm endpoints, the knowledge sidecar, the campaign
supervisor) runs one of these: a timer-driven stack sampler over
``sys._current_frames()`` that folds samples into a bounded
collapsed-stack table keyed by the plane taxonomy the rest of the obs
plane already speaks — ``edge`` / ``policy`` / ``wire`` / ``search`` /
``host_io`` (everything else: ``other``).

Cost contract (same as the recorder): with ``obs_enabled = false`` (or
``profile_enabled = false``) nothing starts and every module-level
helper is a single global ``None`` check. Enabled, the sampler costs
one ``sys._current_frames()`` walk per interval (default 100 Hz) —
measured ≤2% on the edge pipeline bench (``bench.py --pipeline`` A/B
vs ``--no-profile``).

Locking contract (the recorder-interplay rule): the sample path NEVER
takes the metrics-registry lock — or any lock shared with application
code. Samples append to a plain list (atomic under the GIL, the
"lock-free buffer"); a separate fold thread swaps the buffer out and
folds it into the collapsed table under the profiler's own private
lock. Only the fold thread — never the sampler — publishes fold stats
to the metrics registry. ``tests/test_profiling.py`` pins zero
deadlocks under concurrent registry hammering.

Exports: collapsed stacks (Brendan-Gregg folded text), speedscope JSON
(``GET /profile``), and a differential-selection delta payload that
rides the TelemetryRelay wire (absolute cumulative counts, fingerprints
acked only after a successful push — the PR 9 exactly-once contract
extended to profiles).
"""

from __future__ import annotations

import atexit
import os
import sys
import threading
from typing import Dict, List, Optional, Tuple

#: wire schema for the delta payload riding TelemetryRelay docs
SCHEMA = "nmz-profile-v1"

#: plane taxonomy — the same axis the recorder/causality planes use
PLANES = ("edge", "policy", "wire", "search", "host_io", "other")

#: default sampling period: 100 Hz keeps per-sample cost (~tens of µs
#: for a dozen threads) well under the 2% overhead contract
DEFAULT_INTERVAL_S = 0.01
#: fold cadence: how often the drain thread folds the sample buffer
DEFAULT_FOLD_INTERVAL_S = 0.5
#: bounded collapsed table: distinct stacks beyond this fold into a
#: per-plane ``(overflow)`` bucket and are counted, never dropped silently
DEFAULT_MAX_STACKS = 512
DEFAULT_MAX_DEPTH = 48

#: path fragments → plane, first match wins scanning leaf → root.
#: Fragments are matched against '/'-normalized co_filename.
_PLANE_PATHS = (
    ("namazu_tpu/inspector/edge", "edge"),
    ("namazu_tpu/policy/", "policy"),
    ("namazu_tpu/endpoint/", "wire"),
    ("namazu_tpu/signal/", "wire"),
    ("namazu_tpu/inspector/", "wire"),   # transceivers / signal wires
    ("namazu_tpu/obs/federation", "wire"),
    ("namazu_tpu/storage/", "host_io"),
    ("namazu_tpu/chaos/journal", "host_io"),
    ("namazu_tpu/models/", "search"),
    ("namazu_tpu/ops/", "search"),
    ("namazu_tpu/parallel/", "search"),
    ("namazu_tpu/guidance/", "search"),
    ("namazu_tpu/knowledge", "search"),
)

#: function names that pin a plane regardless of module (the fused
#: search loop's host lane lives in models/search.py but is host_io)
_PLANE_FUNCS = {
    "_drain_host_lane": "host_io",
    "_host_refill": "host_io",
}

_OVERFLOW_FRAME = "(overflow)"


def _norm_path(p: str) -> str:
    return p.replace("\\", "/")


def _relname(path: str) -> str:
    """Stable short name for a source file: repo-relative under
    ``namazu_tpu/`` (or the repo root), basename otherwise — so two
    rigs' profiles align frame-for-frame in profdiff."""
    p = _norm_path(path)
    i = p.rfind("namazu_tpu/")
    if i >= 0:
        return p[i:]
    parts = p.rsplit("/", 2)
    if len(parts) >= 2:
        return "/".join(parts[-2:])
    return p


class Profiler:
    """One per process. Two daemon threads: ``-sample`` walks
    ``sys._current_frames()`` on a timer and appends raw ``(tid,
    [code, ...])`` samples to a plain list; ``-fold`` periodically swaps
    that list out and folds it into the bounded collapsed table."""

    def __init__(self, job: str = "", *,
                 interval_s: float = DEFAULT_INTERVAL_S,
                 fold_interval_s: float = DEFAULT_FOLD_INTERVAL_S,
                 max_stacks: int = DEFAULT_MAX_STACKS,
                 max_depth: int = DEFAULT_MAX_DEPTH) -> None:
        self.job = job or "proc"
        self.interval_s = max(0.001, float(interval_s))
        self.fold_interval_s = max(0.01, float(fold_interval_s))
        self.max_stacks = int(max_stacks)
        self.max_depth = int(max_depth)
        # sample path state: appended by the sampler thread only; the
        # fold thread swaps the whole list (both ops atomic under the
        # GIL — no lock on the sample path, ever)
        self._buf: List[Tuple[int, list]] = []
        # profiler-private lock guarding ONLY the folded table; taken
        # by the fold thread and by readers, never by the sampler
        self._lock = threading.Lock()
        self._stacks: Dict[Tuple[str, Tuple[str, ...]], int] = {}
        self._samples = 0
        self._dropped = 0          # samples folded into (overflow)
        self._own: set = set()     # sampler+fold thread idents (skipped)
        self._tags: Dict[int, str] = {}   # tid → plane override
        self._names: Dict[object, Tuple[str, Optional[str]]] = {}
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._started = False

    # -- lifecycle ----------------------------------------------------

    def start(self) -> None:
        if self._started:
            return
        self._started = True
        self._stop.clear()
        for name, fn in (("sample", self._sample_loop),
                         ("fold", self._fold_loop)):
            t = threading.Thread(target=fn, name=f"nmz-prof-{name}",
                                 daemon=True)
            self._threads.append(t)
        for t in self._threads:
            t.start()

    def stop(self, timeout: float = 2.0) -> None:
        if not self._started:
            return
        self._stop.set()
        for t in self._threads:
            t.join(timeout=timeout)
        self._threads = []
        self._started = False
        self._fold_once()   # drain whatever the sampler left behind

    def running(self) -> bool:
        return self._started

    def drain(self) -> None:
        """Synchronously fold whatever the sampler has buffered — for
        readers (bench epilogue, tests) that must not wait out a fold
        interval before a snapshot reflects recent samples."""
        self._fold_once()

    # -- sample path (NO foreign locks) -------------------------------

    def _sample_loop(self) -> None:
        self._own.add(threading.get_ident())
        stop, max_depth = self._stop, self.max_depth
        while not stop.wait(self.interval_s):
            try:
                frames = sys._current_frames()
            except Exception:
                continue
            own = self._own
            buf = self._buf   # re-read: the fold thread swaps it
            for tid, frame in frames.items():
                if tid in own:
                    continue
                stack = []
                f = frame
                while f is not None and len(stack) < max_depth:
                    stack.append(f.f_code)
                    f = f.f_back
                buf.append((tid, stack))
            # hold no foreign frame across the wait: a frame kept alive
            # only by this thread is deallocated HERE, together with
            # whatever its locals own (see ensure_profiler's exit hook)
            frames = frame = f = None

    # -- fold path (may take the registry lock, off the sample path) ---

    def _fold_loop(self) -> None:
        self._own.add(threading.get_ident())
        while not self._stop.wait(self.fold_interval_s):
            self._fold_once()

    def _fold_once(self) -> None:
        # swap is atomic under the GIL; a sampler iteration holding the
        # old list may append a few more entries after the swap — those
        # are statistical dust (≤ one sample period per fold), accepted
        buf, self._buf = self._buf, []
        if not buf:
            return
        tags = dict(self._tags)
        folded: Dict[Tuple[str, Tuple[str, ...]], int] = {}
        for tid, codes in buf:
            key = self._fold_stack(tid, codes, tags)
            folded[key] = folded.get(key, 0) + 1
        dropped = 0
        with self._lock:
            st = self._stacks
            for key, n in folded.items():
                if key in st:
                    st[key] += n
                elif len(st) < self.max_stacks:
                    st[key] = n
                else:
                    # bounded table: fold into a per-plane overflow
                    # bucket (visible in exports) instead of dropping
                    dropped += n
                    ok = (key[0], (_OVERFLOW_FRAME,))
                    st[ok] = st.get(ok, 0) + n
            self._samples += sum(folded.values())
            self._dropped += dropped
        self._publish_fold_stats()

    def _fold_stack(self, tid: int, codes: list, tags: Dict[int, str]
                    ) -> Tuple[str, Tuple[str, ...]]:
        names_leaf_first: List[str] = []
        plane = None
        cache = self._names
        for code in codes:   # leaf → root
            ent = cache.get(code)
            if ent is None:
                path = _norm_path(code.co_filename)
                name = f"{_relname(path)}:{code.co_name}"
                p = _PLANE_FUNCS.get(code.co_name)
                if p is None:
                    for frag, pl in _PLANE_PATHS:
                        if frag in path:
                            p = pl
                            break
                if len(cache) > 8192:   # generated-code safety valve
                    cache.clear()
                ent = (name, p)
                cache[code] = ent
            names_leaf_first.append(ent[0])
            if plane is None and ent[1] is not None:
                plane = ent[1]
        if plane is None:
            plane = tags.get(tid, "other")
        return plane, tuple(reversed(names_leaf_first))

    def _publish_fold_stats(self) -> None:
        # fold-thread only — allowed to take the registry lock
        try:
            from namazu_tpu.obs import metrics
            if not metrics.enabled():
                return
            reg = metrics.get()
            g = reg.gauge("nmz_profile_samples_total",
                          "cumulative profiler samples folded")
            g.set(float(self._samples))
            reg.gauge("nmz_profile_stacks",
                      "distinct collapsed stacks held").set(
                float(len(self._stacks)))
            if self._dropped:
                reg.gauge("nmz_profile_overflow_samples_total",
                          "samples folded into the bounded-table "
                          "overflow bucket").set(float(self._dropped))
        except Exception:
            pass

    # -- tagging -------------------------------------------------------

    def tag_thread(self, tid: int, plane: str) -> None:
        """Pin a plane for a thread whose stacks don't resolve by module
        (e.g. a FramedServer worker parked in the selector)."""
        if plane in PLANES:
            self._tags[tid] = plane

    # -- exports -------------------------------------------------------

    def snapshot(self) -> dict:
        """Absolute cumulative payload — the profdiff/file interchange
        form and the base of the wire delta."""
        with self._lock:
            stacks = [{"plane": k[0], "stack": list(k[1]), "count": c}
                      for k, c in self._stacks.items()]
            samples, dropped = self._samples, self._dropped
        stacks.sort(key=lambda s: -s["count"])
        return {"schema": SCHEMA, "job": self.job,
                "interval_s": self.interval_s,
                "samples_total": samples, "dropped": dropped,
                "stacks": stacks}

    def collapsed(self) -> str:
        """Brendan-Gregg folded text: ``plane;root;...;leaf count``."""
        snap = self.snapshot()
        lines = [";".join([s["plane"]] + s["stack"]) + f" {s['count']}"
                 for s in snap["stacks"]]
        return "\n".join(lines) + ("\n" if lines else "")

    def speedscope(self) -> dict:
        return speedscope_from_payload(self.snapshot())

    def top_self_frame(self) -> Optional[dict]:
        """Dominant self-time frame: the leaf with the most samples.
        Feeds the /fleet PROF column."""
        selfs = self_times(self.snapshot())
        if not selfs:
            return None
        frame, count = max(selfs.items(), key=lambda kv: kv[1])
        total = sum(selfs.values())
        return {"frame": frame, "count": count,
                "share": (count / total) if total else 0.0}

    def reset_counts(self) -> None:
        with self._lock:
            self._stacks.clear()
            self._samples = 0
            self._dropped = 0
        self._buf = []


# -- payload helpers (pure functions, shared with profdiff) ------------

def self_times(payload: dict) -> Dict[str, int]:
    """Leaf self-sample counts per frame from a ``nmz-profile-v1``
    payload (the quantity profdiff ranks deltas on)."""
    out: Dict[str, int] = {}
    for s in payload.get("stacks") or []:
        stack = s.get("stack") or []
        if not stack:
            continue
        leaf = stack[-1]
        out[leaf] = out.get(leaf, 0) + int(s.get("count", 0))
    return out


def frame_planes(payload: dict) -> Dict[str, str]:
    """frame → plane (first plane seen claiming the frame as leaf)."""
    out: Dict[str, str] = {}
    for s in payload.get("stacks") or []:
        stack = s.get("stack") or []
        if stack:
            out.setdefault(stack[-1], s.get("plane", "other"))
    return out


def speedscope_from_payload(payload: dict) -> dict:
    """Render a payload as a speedscope "sampled" profile. Weights are
    seconds (count × sampling interval); each stack gets a synthetic
    ``plane:<name>`` root so the flamegraph groups by plane."""
    interval = float(payload.get("interval_s") or DEFAULT_INTERVAL_S)
    frames: List[dict] = []
    index: Dict[str, int] = {}

    def fidx(name: str) -> int:
        i = index.get(name)
        if i is None:
            i = len(frames)
            index[name] = i
            frames.append({"name": name})
        return i

    samples, weights = [], []
    total = 0.0
    for s in payload.get("stacks") or []:
        names = [f"plane:{s.get('plane', 'other')}"] + list(
            s.get("stack") or [])
        w = int(s.get("count", 0)) * interval
        samples.append([fidx(n) for n in names])
        weights.append(w)
        total += w
    prof = {"type": "sampled",
            "name": f"{payload.get('job') or 'proc'} cpu",
            "unit": "seconds", "startValue": 0, "endValue": total,
            "samples": samples, "weights": weights}
    return {"$schema": "https://www.speedscope.app/file-format-schema.json",
            "shared": {"frames": frames},
            "profiles": [prof], "activeProfileIndex": 0,
            "exporter": "namazu-tpu", "name": payload.get("job") or "proc"}


def payload_from_collapsed(text: str, job: str = "") -> dict:
    """Parse folded text back into a payload (profdiff file input)."""
    stacks = []
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        path, _, count = line.rpartition(" ")
        try:
            n = int(count)
        except ValueError:
            continue
        segs = path.split(";")
        if segs and segs[0] in PLANES:
            plane, segs = segs[0], segs[1:]
        else:
            plane = "other"
        if segs:
            stacks.append({"plane": plane, "stack": segs, "count": n})
    return {"schema": SCHEMA, "job": job, "interval_s": DEFAULT_INTERVAL_S,
            "samples_total": sum(s["count"] for s in stacks),
            "dropped": 0, "stacks": stacks}


def payload_from_speedscope(doc: dict) -> dict:
    """Invert :func:`speedscope_from_payload` (profdiff file input)."""
    frames = [f.get("name", "?") for f in
              (doc.get("shared") or {}).get("frames") or []]
    profs = doc.get("profiles") or []
    stacks: Dict[Tuple[str, Tuple[str, ...]], int] = {}
    interval = DEFAULT_INTERVAL_S
    for prof in profs:
        if prof.get("type") != "sampled":
            continue
        for idxs, w in zip(prof.get("samples") or [],
                           prof.get("weights") or []):
            names = [frames[i] for i in idxs if 0 <= i < len(frames)]
            plane = "other"
            if names and names[0].startswith("plane:"):
                plane = names[0][len("plane:"):]
                names = names[1:]
            if not names:
                continue
            key = (plane, tuple(names))
            # weights are seconds; undo the count×interval scaling
            stacks[key] = stacks.get(key, 0) + max(
                1, int(round(float(w) / interval)))
    out = [{"plane": k[0], "stack": list(k[1]), "count": c}
           for k, c in stacks.items()]
    return {"schema": SCHEMA, "job": doc.get("name") or "",
            "interval_s": interval,
            "samples_total": sum(s["count"] for s in out),
            "dropped": 0, "stacks": out}


# -- wire delta (PR 9 differential-selection contract) -----------------

class ProfileDelta:
    """Differential selection for the profile payload riding the
    TelemetryRelay doc: absolute cumulative counts, only stacks whose
    count changed since the last ACKED push are sent, and fingerprints
    advance only via :meth:`mark_acked` — a dropped push resends the
    same absolutes, a duplicate replay is deduped by the doc's ``seq``
    watermark, so the aggregator converges exactly-once."""

    #: bound per push; unsent changed stacks simply ride a later cycle
    MAX_STACKS_PER_PUSH = 512

    def __init__(self, prof: Profiler) -> None:
        self._prof = prof
        self._acked: Dict[Tuple[str, Tuple[str, ...]], int] = {}

    def encode(self) -> Tuple[Optional[dict], dict]:
        snap = self._prof.snapshot()
        changed = []
        fps: Dict[Tuple[str, Tuple[str, ...]], int] = {}
        for s in snap["stacks"]:
            key = (s["plane"], tuple(s["stack"]))
            if self._acked.get(key) == s["count"]:
                continue
            changed.append(s)
            fps[key] = s["count"]
            if len(changed) >= self.MAX_STACKS_PER_PUSH:
                break
        if not changed:
            return None, {}
        payload = {"schema": SCHEMA, "job": snap["job"],
                   "interval_s": snap["interval_s"],
                   "samples_total": snap["samples_total"],
                   "dropped": snap["dropped"], "stacks": changed}
        return payload, fps

    def mark_acked(self, fps: dict) -> None:
        self._acked.update(fps)

    def reset(self) -> None:
        self._acked.clear()


# -- process-global wiring (single-check no-op contract) ---------------

_PROFILER: Optional[Profiler] = None
_LOCK = threading.Lock()


def enabled() -> bool:
    return _PROFILER is not None


def profiler() -> Optional[Profiler]:
    return _PROFILER


def _profile_switched_off(cfg=None) -> bool:
    env = os.environ.get("NMZ_PROFILE", "").strip().lower()
    if env in ("0", "false", "off", "no"):
        return True
    if cfg is not None:
        try:
            v = cfg.get("profile_enabled")
        except Exception:
            v = None
        if v is not None and not bool(v):
            return True
    return False


def ensure_profiler(job: str, *, interval_s: Optional[float] = None,
                    cfg=None) -> Optional[Profiler]:
    """Idempotently start this process's profiler (mirrors
    ``federation.ensure_self_relay``). No-op — one enabled() check —
    when obs is off, and honored off-switches: ``profile_enabled =
    false`` / ``NMZ_PROFILE=0``. First caller names the job; later
    calls return the running instance unchanged."""
    global _PROFILER
    if _PROFILER is not None:
        return _PROFILER
    from namazu_tpu.obs import metrics
    if not metrics.enabled() or _profile_switched_off(cfg):
        return None
    if interval_s is None:
        try:
            interval_s = float(
                os.environ.get("NMZ_PROFILE_INTERVAL_S", "") or
                (cfg.get("profile_interval_s") if cfg is not None else 0)
                or DEFAULT_INTERVAL_S)
        except (TypeError, ValueError):
            interval_s = DEFAULT_INTERVAL_S
    with _LOCK:
        if _PROFILER is None:
            p = Profiler(job, interval_s=interval_s)
            p.start()
            # join the sampler before the interpreter finalises: it
            # holds other threads' frames, so it can be the last owner
            # of a returned function's locals — and when those are jax
            # objects their C++ destructors drop and retake the GIL. A
            # daemon thread retaking it after finalisation began is
            # killed mid-destructor and aborts the process (rc 134
            # after a successful in-process searched `run`). Registered
            # after the telemetry relay's exit flush, so it runs first
            # and the flush still carries the final samples.
            atexit.register(p.stop)
            _PROFILER = p
    return _PROFILER


def tag_current_thread(plane: str) -> None:
    """Plane hint for the calling thread; single global check when the
    profiler is off."""
    p = _PROFILER
    if p is not None:
        p.tag_thread(threading.get_ident(), plane)


def payload() -> Optional[dict]:
    p = _PROFILER
    return p.snapshot() if p is not None else None


def render_collapsed() -> str:
    p = _PROFILER
    return p.collapsed() if p is not None else ""


def speedscope_doc() -> Optional[dict]:
    p = _PROFILER
    return p.speedscope() if p is not None else None


def reset() -> None:
    """Test hygiene (mirrors ``federation.reset``): stop and forget the
    process profiler."""
    global _PROFILER
    with _LOCK:
        p, _PROFILER = _PROFILER, None
    if p is not None:
        p.stop()
