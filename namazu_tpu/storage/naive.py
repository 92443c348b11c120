"""Naive (filesystem JSON) history storage.

Parity: /root/reference/nmz/historystorage/naive — layout per storage dir:

::

    storage.json          {"type": "naive", "next_run": N, "settled": M}
                          N run dirs are allocated; runs [0, M) were each
                          seen settled (below). Written by the calls that
                          allocate (create(), create_new_working_dir())
                          and by no reader; a file without "settled"
                          (every storage from before the field, a
                          hand-written one) reads as M = 0.
    config.json           copy of the experiment config
    materials/            copy of the user's experiment scripts
    00000000/             one dir per run (%08x, parity naive.go:143-158)
        trace.json        the action sequence (JSON, not gob)
        result.json       {"successful": bool, "required_time": s, "metadata": {}}
        INCOMPLETE        quarantine marker (crash-safety, doc/robustness.md):
                          written by init()/fsck when a run crashed after
                          recording its trace but before its result. A
                          quarantined run is invisible to every query —
                          analytics, repro-rate stats, the search plane's
                          history ingest — so a partial run cannot pollute
                          cross-run statistics. ``nmz-tpu tools fsck``
                          lists and repairs quarantined runs.

The reference also writes per-action ``actions/<i>.{action,event}.json``
files; here the whole trace is one JSON array — same information, one file.

**Settled** (the watermark ``settled``): a run is settled once it holds a
``result.json`` or the quarantine marker. Whether a run crashed is
decided when it settles, so ``init()`` / ``refresh()`` look for crashed
runs from the watermark on and not from run 0, and move the watermark
over the settled runs they find in a row; the first run with neither
(in flight, or killed before its trace: ``tools fsck --repair`` marks
those) holds it back until it settles. Settled is not unchanged: a
rewritten run still has a new ``run_signature``, and ``fsck`` still
visits every run. The one narrowing: a run whose ``result.json`` is
REMOVED after it was seen settled is no longer quarantined by the next
``init()``; ``fsck`` lists it (``incomplete_unmarked``).

All JSON writes are atomic (utils/atomic.py: tmp + fsync + rename), so a
SIGKILL mid-write leaves the previous complete content, never a torn file.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Hashable, Iterable, List, Optional

from namazu_tpu import obs
from namazu_tpu.storage.base import HistoryStorage, StorageError, register_storage
from namazu_tpu.utils.atomic import atomic_write_json, atomic_write_text, is_tmp_artifact
from namazu_tpu.utils.log import get_logger
from namazu_tpu.utils.trace import SingleTrace

log = get_logger("storage.naive")

#: quarantine marker file inside a run dir (see module docstring)
INCOMPLETE_MARKER = "INCOMPLETE"


def _file_signature(path: str) -> tuple:
    """What tells one content of ``path`` from the next without opening
    it: every write here is tmp + rename, so a rewritten file is a new
    inode (size and mtime catch an edit made in place from outside)."""
    st = os.stat(path)
    return st.st_ino, st.st_size, st.st_mtime_ns


@register_storage
class NaiveStorage(HistoryStorage):
    NAME = "naive"

    def __init__(self, dir_path: str):
        self.dir = os.path.abspath(dir_path)
        self._next_run = 0
        self._settled = 0  # the watermark (module docstring)
        self._current_run_dir: Optional[str] = None
        self._last_result: tuple = (None, None)  # see _result

    # -- layout helpers --------------------------------------------------

    def _meta_path(self) -> str:
        return os.path.join(self.dir, "storage.json")

    def run_dir(self, i: int) -> str:
        """Run ``i``'s working dir (%08x layout, parity naive.go:143-158)
        — the public accessor for per-run artifacts (coverage.json,
        nmz.log) beyond the trace/result pair."""
        return os.path.join(self.dir, f"{i:08x}")

    def _load_meta(self) -> Dict[str, Any]:
        with open(self._meta_path()) as f:
            return json.load(f)

    def _save_meta(self) -> None:
        atomic_write_json(self._meta_path(),
                          {"type": self.NAME, "next_run": self._next_run,
                           "settled": self._settled})

    def _marker_path(self, i: int) -> str:
        return os.path.join(self.run_dir(i), INCOMPLETE_MARKER)

    # -- lifecycle -------------------------------------------------------

    def create(self) -> None:
        os.makedirs(self.dir, exist_ok=True)
        if os.path.exists(self._meta_path()):
            raise StorageError(f"storage already exists: {self.dir}")
        self._next_run = self._settled = 0
        self._save_meta()

    def init(self) -> None:
        if not os.path.exists(self._meta_path()):
            raise StorageError(f"not a storage dir: {self.dir}")
        meta = self._load_meta()
        self._next_run = int(meta["next_run"])
        settled = int(meta.get("settled", 0))
        # a watermark past the allocated runs: the storage shrank under
        # the file (an operator's edit), so nothing it says is trusted
        self._settled = settled if 0 <= settled <= self._next_run else 0
        self._quarantine_crashed_runs()

    def refresh(self) -> int:
        next_run = int(self._load_meta()["next_run"])
        if next_run < self._next_run:
            raise StorageError(
                f"{self.dir}: next_run went from {self._next_run} back to "
                f"{next_run} under an open handle (the storage shrank or "
                "was made anew); open it again")
        self._next_run = next_run
        self._quarantine_crashed_runs()
        return self._next_run

    def _quarantine_crashed_runs(self) -> None:
        """Mark run dirs, from the watermark on, that hold a trace but
        no result: the signature of a run killed between
        ``record_new_trace`` and ``record_result``; and move the
        watermark over the settled runs met in a row (module
        docstring). Dirs with NEITHER file are left unmarked here — an
        in-flight run looks exactly like that, and init() runs
        concurrently with live runs (the /analytics route loads the
        storage mid-experiment); ``tools fsck --repair``, which only an
        operator invokes on a quiescent storage, marks those too. Such
        a run holds the watermark, so the open after it crashed still
        visits it."""
        first = self._settled
        in_a_row = True
        for i in range(first, self._next_run):
            run_dir = self.run_dir(i)
            # the result first: a completed run costs one ``stat``
            settled = (os.path.exists(os.path.join(run_dir, "result.json"))
                       or os.path.exists(self._marker_path(i)))
            if not settled and os.path.exists(
                    os.path.join(run_dir, "trace.json")):
                atomic_write_text(
                    self._marker_path(i),
                    "crashed between trace and result; quarantined by "
                    "init()\n")
                log.warning("run %08x has a trace but no result (crash "
                            "mid-run); quarantined", i)
                settled = True
            in_a_row = in_a_row and settled
            if in_a_row:
                self._settled = i + 1
        self.last_open = (self._next_run, self._next_run - first)
        obs.storage_open(*self.last_open)

    # -- per-run ---------------------------------------------------------

    def create_new_working_dir(self) -> str:
        run_dir = self.run_dir(self._next_run)
        os.makedirs(run_dir, exist_ok=False)
        self._next_run += 1
        self._save_meta()
        self._current_run_dir = run_dir
        return run_dir

    def record_new_trace(self, trace: SingleTrace) -> None:
        if self._current_run_dir is None:
            raise StorageError("no working dir; call create_new_working_dir first")
        atomic_write_text(
            os.path.join(self._current_run_dir, "trace.json"),
            trace.to_json())

    def record_result(
        self,
        successful: bool,
        required_time: float,
        metadata: Optional[Dict[str, Any]] = None,
    ) -> None:
        if self._current_run_dir is None:
            raise StorageError("no working dir; call create_new_working_dir first")
        atomic_write_json(
            os.path.join(self._current_run_dir, "result.json"),
            {
                "successful": successful,
                "required_time": required_time,
                "metadata": metadata or {},
            },
        )
        # a concurrent init() (live /analytics scrape) may have seen the
        # trace-no-result window just above and quarantined this run;
        # the result landing proves it completed
        marker = os.path.join(self._current_run_dir, INCOMPLETE_MARKER)
        if os.path.exists(marker):
            os.unlink(marker)

    # -- quarantine ------------------------------------------------------

    def quarantine_current_run(self, reason: str = "") -> None:
        if self._current_run_dir is None:
            return
        atomic_write_text(
            os.path.join(self._current_run_dir, INCOMPLETE_MARKER),
            (reason or "run aborted; nothing recorded") + "\n")

    def is_quarantined(self, i: int) -> bool:
        return os.path.exists(self._marker_path(i))

    def quarantined_runs(self) -> List[int]:
        return [i for i in range(self._next_run) if self.is_quarantined(i)]

    def run_signature(self, i: int) -> Optional[Hashable]:
        """``_file_signature`` of ``trace.json`` and of ``result.json``
        and no quarantine marker: three ``stat``s, nothing opened. None
        for a quarantined run, a missing file or a ``stat`` that raises:
        such a run has no signature and takes the ordinary queries,
        which raise what they always raised."""
        if self.is_quarantined(i):
            return None
        run_dir = self.run_dir(i)
        try:
            return (_file_signature(os.path.join(run_dir, "trace.json")),
                    _file_signature(os.path.join(run_dir, "result.json")))
        except OSError:
            return None

    def fsck(self, repair: bool = False) -> Dict[str, Any]:
        """Integrity report over every allocated run dir; with
        ``repair``, quarantine incomplete runs (including trace-less
        ones — fsck is operator-invoked on a quiescent storage, so the
        in-flight ambiguity init() must respect does not apply) and
        sweep orphan ``*.tmp`` files a hard kill left mid-atomic-write.
        """
        report: Dict[str, Any] = {
            "dir": self.dir,
            "next_run": self._next_run,
            "complete": 0,
            "quarantined": [],
            "incomplete_unmarked": [],
            "missing_dirs": [],
            "tmp_artifacts": [],
            "repaired": repair,
        }
        for i in range(self._next_run):
            run_dir = self.run_dir(i)
            if not os.path.isdir(run_dir):
                report["missing_dirs"].append(i)
                continue
            for name in sorted(os.listdir(run_dir)):
                if is_tmp_artifact(name):
                    path = os.path.join(run_dir, name)
                    report["tmp_artifacts"].append(path)
                    if repair:
                        os.unlink(path)
            if self.is_quarantined(i):
                report["quarantined"].append(i)
            elif os.path.exists(os.path.join(run_dir, "result.json")):
                report["complete"] += 1
            else:
                report["incomplete_unmarked"].append(i)
                if repair:
                    atomic_write_text(
                        self._marker_path(i),
                        "no result recorded; quarantined by fsck\n")
        for name in sorted(os.listdir(self.dir)):
            if is_tmp_artifact(name):
                path = os.path.join(self.dir, name)
                report["tmp_artifacts"].append(path)
                if repair:
                    os.unlink(path)
        if repair:
            # keep what was actually repaired visible: callers decide
            # exit codes on it even though the dirs are now quarantined
            report["repaired_runs"] = report["incomplete_unmarked"]
            report["quarantined"] = sorted(
                report["quarantined"] + report["incomplete_unmarked"])
            report["incomplete_unmarked"] = []
        else:
            report["repaired_runs"] = []
        return report

    # -- queries ---------------------------------------------------------

    def nr_stored_histories(self) -> int:
        # up to the last run that completed (has a result), found from
        # the newest run down: one ``stat`` where every run completed
        for i in reversed(range(self._next_run)):
            if os.path.exists(os.path.join(self.run_dir(i), "result.json")):
                return i + 1
        return 0

    def _result(self, i: int) -> Dict[str, Any]:
        """Run ``i``'s ``result.json``, parsed once for the queries that
        follow one another on the same run (``is_successful`` then
        ``get_metadata``): the last document is kept and revalidated by
        ``stat`` — a rewritten file is a new inode."""
        if self.is_quarantined(i):
            raise StorageError(f"run {i:08x} is quarantined (INCOMPLETE)")
        path = os.path.join(self.run_dir(i), "result.json")
        try:
            key = (path, _file_signature(path))
        except OSError:
            raise StorageError(f"run {i:08x} has no result") from None
        last = self._last_result
        if last[0] != key:
            with open(path) as f:
                last = self._last_result = (key, json.load(f))
        return last[1]

    def get_stored_history(self, i: int) -> SingleTrace:
        # quarantined runs ARE likely to have a trace — refusing to
        # serve it is the point: a crash-truncated run must not feed
        # coverage stats or the search plane's archives
        if self.is_quarantined(i):
            raise StorageError(f"run {i:08x} is quarantined (INCOMPLETE)")
        path = os.path.join(self.run_dir(i), "trace.json")
        if not os.path.exists(path):
            raise StorageError(f"run {i:08x} has no trace")
        with open(path) as f:
            return SingleTrace.from_json(f.read())

    def is_successful(self, i: int) -> bool:
        return bool(self._result(i)["successful"])

    def get_required_time(self, i: int) -> float:
        return float(self._result(i)["required_time"])

    def get_metadata(self, i: int) -> Dict[str, Any]:
        return dict(self._result(i).get("metadata") or {})

    def search(self, prefix: List[str]) -> Iterable[int]:
        out = []
        for i in range(self.nr_stored_histories()):
            try:
                trace = self.get_stored_history(i)
            except StorageError:
                continue
            classes = [a.class_name() for a in trace]
            if classes[: len(prefix)] == list(prefix):
                out.append(i)
        return out
