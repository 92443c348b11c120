"""One place builds a search, from one table of defaults.

``models.search.build_search_from_params`` is the only code that turns
the ``tpu_search`` knobs into a ``SearchConfig``, weights, the search
and its guidance wiring — in the policy's own process and in the
sidecar — and ``models.SEARCH_DEFAULTS`` is the only place a knob's
default is written. A key that went with the code it selected is named,
once, when a config still sets it; the backend that went (MCTS, PR 48)
is refused wherever it is still asked for: in a config, in an older
policy's params, in a checkpoint's tag. A checkpoint the search wrote
before it became one class loads, and is written back key for key.
"""

import glob
import logging
import os

import numpy as np
import pytest

from namazu_tpu.models import SEARCH_DEFAULTS
from namazu_tpu.models.ga import GAConfig
from namazu_tpu.models.search import (
    ScheduleSearch,
    SearchBase,
    SearchConfig,
    build_search_from_params,
)
from namazu_tpu.policy import create_policy
from namazu_tpu.policy.tpu import REMOVED_KEYS
from namazu_tpu.utils.config import Config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLE_CONFIGS = sorted(
    os.path.relpath(p, ROOT) for p in
    glob.glob(os.path.join(ROOT, "examples", "*", "config_tpu*.toml")))
#: a checkpoint written by ``ScheduleSearch.save`` at PR 47 (commit
#: 0d299a4: ``SearchBase`` + ``ScheduleSearch``), surrogate trained
GOLDEN_CHECKPOINT = os.path.join(ROOT, "tests", "golden",
                                 "search_checkpoint_pr47.npz")
GOLDEN_CFG = SearchConfig(H=16, K=16, L=32, population=32, archive_size=16,
                          failure_size=4, seed=3, surrogate_topk=4,
                          ga=GAConfig(max_delay=0.05))


def _policy(param: dict):
    pol = create_policy("tpu_search")
    pol.load_config(Config({"explore_policy": "tpu_search",
                            "explore_policy_param": param}))
    return pol


def test_the_examples_are_where_they_were():
    assert len(EXAMPLE_CONFIGS) == 7, EXAMPLE_CONFIGS


@pytest.mark.parametrize("path", EXAMPLE_CONFIGS)
def test_policy_and_builder_build_the_same_search(path):
    """What a shipped config makes in the policy's process is what the
    sidecar makes from the params the policy sends it."""
    pol = create_policy("tpu_search")
    pol.load_config(Config.from_file(os.path.join(ROOT, path)))
    params = pol._search_params()
    assert set(params) == set(SEARCH_DEFAULTS)
    ours, theirs = pol._build_search(), build_search_from_params(params)
    assert type(ours) is type(theirs) is ScheduleSearch
    assert ours.cfg == theirs.cfg
    assert (ours.guidance is None) == (theirs.guidance is None) \
        == (not pol._guidance_active())
    # ... and the file's own knobs are the ones that arrived
    assert (ours.cfg.population, ours.cfg.H, ours.cfg.K,
            ours.cfg.ga.max_delay, ours.cfg.surrogate_topk) == \
        (pol.population, pol.H, pol.K, pol.max_interval,
         pol.surrogate_topk)


def test_one_table_of_defaults():
    """A policy that loaded nothing states the table; a builder given
    nothing builds from it; and ``SearchConfig``'s own defaults (with
    ``GAConfig``'s and ``ScoreWeights``' under them) are that search's."""
    assert create_policy("tpu_search")._search_params() == SEARCH_DEFAULTS
    assert _policy({})._search_params() == SEARCH_DEFAULTS
    assert build_search_from_params({}).cfg == SearchConfig()


@pytest.mark.parametrize("key", sorted(REMOVED_KEYS))
def test_a_removed_key_is_named_once_and_ignored(key, caplog):
    value = {"fused": False, "search_backend": "ga"}.get(key, 4)
    with caplog.at_level(logging.WARNING, logger="namazu_tpu"):
        pol = _policy({key: value, "population": 64})
    said = [r.getMessage() for r in caplog.records
            if repr(key) in r.getMessage()]
    assert len(said) == 1 and "removed" in said[0]
    assert REMOVED_KEYS[key] in said[0]  # what happens instead
    assert not hasattr(pol, key)
    assert key not in pol._search_params()
    assert pol._search_params() == {**SEARCH_DEFAULTS, "population": 64}
    # nothing else about unknown keys changed: they are not mentioned
    with caplog.at_level(logging.WARNING, logger="namazu_tpu"):
        caplog.clear()
        _policy({"no_such_knob": 1})
    assert not caplog.records


# -- the backend that went ---------------------------------------------------


def test_a_config_that_asks_for_mcts_is_refused():
    """Not a warning: a hunt that asked for MCTS must not run the GA."""
    with pytest.raises(ValueError, match="MCTS backend was removed"):
        _policy({"search_backend": "mcts"})


def test_params_that_ask_for_mcts_are_refused():
    """Params reach the sidecar from a policy that may be older."""
    with pytest.raises(ValueError, match="MCTS backend was removed"):
        build_search_from_params({"search_backend": "mcts",
                                  "population": 64, "devices": 1})


def test_an_older_policys_params_build_the_same_search():
    """PR 47's ``_search_params()`` still stated the backend and the
    four tree knobs: with ``"ga"`` they build what params without them
    build."""
    older = {**SEARCH_DEFAULTS, "population": 64, "devices": 1,
             "search_backend": "ga", "mcts_tree_depth": 24,
             "mcts_levels": 8, "mcts_simulations": 256,
             "mcts_rollouts": 64}
    ours = build_search_from_params(
        {"population": 64, "devices": 1})
    theirs = build_search_from_params(older)
    assert type(theirs) is ScheduleSearch and theirs.cfg == ours.cfg
    assert theirs.population == ours.population == 64


def test_one_search_class_under_both_names():
    """``tests/benchmarks/`` patch ``SearchBase._flush`` and
    ``.add_executed_trace``: the name is the one class, so a patch lands
    on what ``ScheduleSearch`` runs."""
    assert SearchBase is ScheduleSearch
    assert ScheduleSearch.__mro__ == (ScheduleSearch, object)


# -- the checkpoint ----------------------------------------------------------


def _npz(path):
    with np.load(path) as z:
        return {k: z[k] for k in z.files}


def test_a_checkpoint_tagged_mcts_is_refused(tmp_path):
    """Re-homed from tests/test_mcts.py: the one search refuses a file
    another backend wrote, with a sentence; its own tag is ``"ga"``."""
    flat = _npz(GOLDEN_CHECKPOINT)
    assert str(flat["backend"]) == "ga"
    flat["backend"] = np.asarray("mcts")
    path = str(tmp_path / "mcts.npz")
    np.savez(path, **flat)
    with pytest.raises(ValueError, match="written by the 'mcts' search "
                                         "backend.*delete it"):
        ScheduleSearch(GOLDEN_CFG, n_devices=1).load(path)


def test_a_checkpoint_from_before_the_fold_loads_and_is_written_back(
        tmp_path):
    """A search home restarts from checkpoints written before PR 48:
    the parent's file loads, and ``save`` writes the same keys in the
    same order with the same dtypes, shapes and values."""
    want = _npz(GOLDEN_CHECKPOINT)
    s = ScheduleSearch(GOLDEN_CFG, n_devices=1)
    s.load(GOLDEN_CHECKPOINT)
    assert s._surrogate is not None
    assert s.generations_run == 3 and s._archive_n == 8
    assert s.distinct_failure_signatures() == s._failure_n == 4
    np.testing.assert_array_equal(s.best().delays, want["best_delays"])
    assert s._best_snapshot[2] == float(want["best_fitness"])
    path = str(tmp_path / "back.npz")
    s.save(path)
    got = _npz(path)
    assert list(got) == list(want)
    for key, value in want.items():
        assert got[key].dtype == value.dtype, key
        np.testing.assert_array_equal(got[key], value, err_msg=key)
