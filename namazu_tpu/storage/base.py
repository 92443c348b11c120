"""HistoryStorage interface and factory.

Parity: /root/reference/nmz/historystorage/historystorage.go:22-61
(interface + New/LoadStorage).
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Hashable, Iterable, List, Optional, Tuple

from namazu_tpu.utils.trace import SingleTrace


class StorageError(Exception):
    pass


class HistoryStorage:
    """One experiment's history: N runs, each with a trace and a result."""

    NAME = "abstract"

    #: what the last ``init()`` / ``refresh()`` did: (runs allocated,
    #: runs it visited); None where the backend visits no run to open
    last_open: Optional[Tuple[int, int]] = None

    # -- lifecycle -------------------------------------------------------

    def create(self) -> None:
        """Create the on-disk layout (once, at `init` time)."""
        raise NotImplementedError

    def init(self) -> None:
        """Open an existing storage (every `run`, every tool, the first
        request of a key at a search home). A backend with crash
        detection quarantines here the runs that hold a trace and no
        result, and visits for that only the runs not yet seen
        *settled*: a run is settled once it has a result or a
        quarantine marker, and the storage records how far the settled
        runs reach without a gap (storage/naive.py: ``"settled"`` in
        ``storage.json``, absent = 0, past ``next_run`` = not trusted
        and the storage walked whole). Only the calls that allocate a
        run write that file (``create()``, ``create_new_working_dir()``);
        ``init()`` and ``refresh()`` never do, so a reader cannot put a
        stale ``next_run`` back under a writer. A run that is in flight
        holds the watermark, so it is visited again by every open until
        it settles. One narrowing follows: a run that LOSES its result
        after it was seen settled is not quarantined by a later
        ``init()``; ``fsck``, which visits every run, reports it."""
        raise NotImplementedError

    def close(self) -> None:
        pass

    def refresh(self) -> int:
        """Catch a handle that is kept open up with the runs other
        processes have allocated since ``init()`` or the last refresh,
        doing what ``init()`` would for the runs this handle has not
        yet seen settled (the new ones, and any that was in flight last
        time), and for those alone; returns how many runs are allocated
        now. A long-lived reader (the campaign supervisor between two
        runs, a search home between two requests of a key) calls it in
        place of a fresh ``load_storage``. It raises where the handle
        no longer describes the storage — the dir is gone, or fewer
        runs are allocated than the handle has seen (shrunk, or made
        anew) — and the caller opens a fresh handle. This default is
        for a backend whose queries keep no state from ``init()``."""
        return self.nr_stored_histories()

    # -- per-run ---------------------------------------------------------

    def create_new_working_dir(self) -> str:
        """Allocate the next run directory; returns its path."""
        raise NotImplementedError

    def record_new_trace(self, trace: SingleTrace) -> None:
        raise NotImplementedError

    def record_result(
        self,
        successful: bool,
        required_time: float,
        metadata: Optional[Dict[str, Any]] = None,
    ) -> None:
        raise NotImplementedError

    def quarantine_current_run(self, reason: str = "") -> None:
        """Mark the in-flight run dir as deliberately abandoned (infra
        failure / deadline abort: nothing will be recorded). Keeps an
        aborted run distinguishable from a crashed one — fsck treats
        marked dirs as accounted for, unmarked ones as findings."""

    # -- queries ---------------------------------------------------------

    def run_dir(self, i: int) -> str:
        """Path of run ``i``'s working directory — the public accessor
        for per-run artifacts beyond the trace/result pair (e.g. the
        analyzer's ``coverage.json``, the run's ``nmz.log``)."""
        raise NotImplementedError

    def nr_stored_histories(self) -> int:
        raise NotImplementedError

    def is_quarantined(self, i: int) -> bool:
        """Whether run ``i`` was quarantined as incomplete (crash-safety;
        see storage/naive.py). Quarantined runs raise StorageError from
        every per-run query so partial data cannot pollute cross-run
        statistics; backends without crash detection report none."""
        return False

    def quarantined_runs(self) -> List[int]:
        return [i for i in range(self.nr_stored_histories())
                if self.is_quarantined(i)]

    def run_signature(self, i: int) -> Optional[Hashable]:
        """A hashable token that changes whenever run ``i``'s stored
        content or visibility does, or None where the backend cannot say
        (this default). A reader that kept something derived from the
        run under ``(run_dir(i), signature)`` may reuse it while the
        signature compares equal (models/ingest.py's encoded-run
        records); None means "never cached": every query re-read."""
        return None

    def get_stored_history(self, i: int) -> SingleTrace:
        raise NotImplementedError

    def is_successful(self, i: int) -> bool:
        raise NotImplementedError

    def get_required_time(self, i: int) -> float:
        raise NotImplementedError

    def get_metadata(self, i: int) -> Dict[str, Any]:
        raise NotImplementedError

    def search(self, prefix: List[str]) -> Iterable[int]:
        """Indices of runs whose trace's action-class sequence starts with
        ``prefix`` (parity: naive.go:232-257 linear scan)."""
        raise NotImplementedError


_BACKENDS: Dict[str, type] = {}


def register_storage(cls: type) -> type:
    _BACKENDS[cls.NAME] = cls
    return cls


def new_storage(name: str, dir_path: str) -> HistoryStorage:
    """Parity: historystorage.New (historystorage.go:42-53)."""
    try:
        cls = _BACKENDS[name]
    except KeyError:
        raise StorageError(
            f"unknown storage type {name!r}; known: {sorted(_BACKENDS)}"
        ) from None
    return cls(dir_path)


def load_storage(dir_path: str) -> HistoryStorage:
    """Open an existing storage dir, reading its recorded backend type
    (parity: LoadStorage, historystorage.go:55-61)."""
    meta_path = os.path.join(dir_path, "storage.json")
    if not os.path.exists(meta_path):
        raise StorageError(f"not a storage dir (no storage.json): {dir_path}")
    with open(meta_path) as f:
        meta = json.load(f)
    st = new_storage(meta["type"], dir_path)
    st.init()
    return st
