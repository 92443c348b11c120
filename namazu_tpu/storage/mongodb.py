"""MongoDB-backed history storage (gated).

Parity: /root/reference/nmz/historystorage/mongodb/mongodb.go:25-105 — a
decorator over the naive backend that additionally inserts every trace and
result into MongoDB collections for cross-experiment querying. This image
ships no ``pymongo``; the class registers itself only when the import
succeeds, otherwise ``new_storage("mongodb", ...)`` reports the gap.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from namazu_tpu.storage.base import register_storage
from namazu_tpu.storage.naive import NaiveStorage
from namazu_tpu.utils.trace import SingleTrace

try:
    import pymongo  # noqa: F401

    _HAVE_PYMONGO = True
except ImportError:
    _HAVE_PYMONGO = False


class MongoDBStorage(NaiveStorage):
    NAME = "mongodb"

    DEFAULT_URL = "mongodb://localhost:27017"
    DB_NAME = "namazu_tpu"

    def __init__(self, dir_path: str, url: Optional[str] = None):
        super().__init__(dir_path)
        import pymongo

        self._client = pymongo.MongoClient(url or self.DEFAULT_URL)
        self._db = self._client[self.DB_NAME]

    def record_new_trace(self, trace: SingleTrace) -> None:
        super().record_new_trace(trace)
        self._db.traces.insert_one({
            "run_dir": self._current_run_dir,
            "actions": trace.to_jsonable(),
        })

    def record_result(
        self,
        successful: bool,
        required_time: float,
        metadata: Optional[Dict[str, Any]] = None,
    ) -> None:
        super().record_result(successful, required_time, metadata)
        self._db.results.insert_one({
            "run_dir": self._current_run_dir,
            "successful": successful,
            "required_time": required_time,
            "metadata": metadata or {},
        })

    def run_signature(self, i: int) -> None:
        """None ("never cached"): the collections are written beside
        the files, and nothing here can say whether they changed."""
        return None

    def close(self) -> None:
        self._client.close()


if _HAVE_PYMONGO:
    register_storage(MongoDBStorage)
