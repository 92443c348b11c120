"""One place builds a search, from one table of defaults.

``models.search.build_search_from_params`` is the only code that turns
the ``tpu_search`` knobs into a ``SearchConfig``, weights, a backend and
its guidance wiring — in the policy's own process and in the sidecar —
and ``models.SEARCH_DEFAULTS`` is the only place a knob's default is
written. A key that went with the code it selected is named, once, when
a config still sets it.
"""

import glob
import logging
import os

import pytest

from namazu_tpu.models import SEARCH_DEFAULTS
from namazu_tpu.models.search import (
    MCTSSearch,
    ScheduleSearch,
    SearchConfig,
    build_search_from_params,
)
from namazu_tpu.policy import create_policy
from namazu_tpu.policy.tpu import REMOVED_KEYS
from namazu_tpu.utils.config import Config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLE_CONFIGS = sorted(
    os.path.relpath(p, ROOT) for p in
    glob.glob(os.path.join(ROOT, "examples", "*", "config_tpu*.toml"))
    + glob.glob(os.path.join(ROOT, "examples", "*", "config_mcts.toml")))


def _policy(param: dict):
    pol = create_policy("tpu_search")
    pol.load_config(Config({"explore_policy": "tpu_search",
                            "explore_policy_param": param}))
    return pol


def test_the_examples_are_where_they_were():
    assert len(EXAMPLE_CONFIGS) == 8, EXAMPLE_CONFIGS


@pytest.mark.parametrize("path", EXAMPLE_CONFIGS)
def test_policy_and_builder_build_the_same_search(path):
    """What a shipped config makes in the policy's process is what the
    sidecar makes from the params the policy sends it."""
    pol = create_policy("tpu_search")
    pol.load_config(Config.from_file(os.path.join(ROOT, path)))
    params = pol._search_params()
    assert set(params) == set(SEARCH_DEFAULTS)
    ours, theirs = pol._build_search(), build_search_from_params(params)
    assert type(ours) is type(theirs) is (
        MCTSSearch if pol.search_backend == "mcts" else ScheduleSearch)
    assert ours.cfg == theirs.cfg
    assert (ours.guidance is None) == (theirs.guidance is None) \
        == (not pol._guidance_active())
    if isinstance(ours, MCTSSearch):
        assert ours.mcts_cfg == theirs.mcts_cfg
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
    value = {"fused": False}.get(key, 4)
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
