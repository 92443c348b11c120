"""New configurations are appended: what PR 31 accepted stays a prefix
of ``BENCHMARK.json``'s lists, and on that prefix the accepted test of
``zk2212-zab5`` still holds.

``test_long_traces.py::test_the_configuration_and_its_cells_are_declared``
holds ``zk2212-zab5`` to the LAST place of both lists, so it is red from
the first configuration appended after it (PR 32, ``zk2080-reconfig5``)
until a ``benchmark`` PR makes its two position lines a membership test
(PERF.md section 7). It stops at those lines; the rest of its body is
run here against the manifest cut to the accepted prefix, not restated.
"""

import sys

import pytest

import test_long_traces
import tiny_root

sys.path.insert(0, tiny_root.BENCH)

import manifest  # noqa: E402

ACCEPTED_CONFIGS = ["zk2212-fle3", "etcd3517-kv3", "zk2212-zab5"]
ACCEPTED_CELLS = [
    "zk2212-fle3.live", "zk2212-fle3.fleet8-d64",
    "zk2212-fle3.fleet8-d16-x4", "zk2212-fle3.live-d64",
    "etcd3517-kv3.fleet8-d64", "zk2212-zab5.fleet8-d32"]


class AcceptedPrefix:
    """The manifest with the two lists of ``doc`` cut to what PR 31
    accepted; everything else (``validate`` included) is the whole
    manifest's own."""

    def __init__(self, man):
        self._man = man
        self.doc = dict(
            man.doc,
            configs=man.doc["configs"][:len(ACCEPTED_CONFIGS)],
            workloads=man.doc["workloads"][:len(ACCEPTED_CELLS)])

    def __getattr__(self, name):
        return getattr(self._man, name)


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(tiny_root.REPO)


def test_the_accepted_entries_keep_their_order(man):
    man.validate()
    prefix = AcceptedPrefix(man)
    assert [c["name"] for c in prefix.doc["configs"]] == ACCEPTED_CONFIGS
    assert [w["name"] for w in prefix.doc["workloads"]] == ACCEPTED_CELLS
    assert len(man.doc["configs"]) > len(ACCEPTED_CONFIGS)  # appended


def test_zk2212_zab5_is_declared_as_accepted(man):
    test_long_traces.test_the_configuration_and_its_cells_are_declared(
        AcceptedPrefix(man))
