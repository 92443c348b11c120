"""Bring-up contracts (PR 21): the compile cache is placed from outside,
the bench's device modes refuse to run off a TPU, and a process that
searched exits 0 — the host-side rules ``chip_smoke.py`` leans on.
"""

import json
import os
import subprocess
import sys

import pytest

from tests.test_tpu_policy import record_run

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return env


# -- compile cache placement ------------------------------------------------


@pytest.fixture
def cache_config():
    import jax

    saved = jax.config.jax_compilation_cache_dir
    yield jax.config
    jax.config.update("jax_compilation_cache_dir", saved)


def test_compile_cache_env_placement_is_left_alone(monkeypatch,
                                                   cache_config):
    """A caller who set JAX_COMPILATION_CACHE_DIR owns the placement:
    the program sets no cache directory in code."""
    from namazu_tpu.models.search import configure_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    cache_config.update("jax_compilation_cache_dir", "/what/jax/read")
    configure_compile_cache()
    assert cache_config.jax_compilation_cache_dir == "/what/jax/read"


def test_compile_cache_defaults_into_the_checkout(monkeypatch,
                                                  cache_config):
    """Unset, the cache goes to one fixed path inside the checkout —
    never under ~, a temp name, a pid or a time."""
    from namazu_tpu.models.search import (
        COMPILE_CACHE_DIR,
        configure_compile_cache,
    )

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    cache_config.update("jax_compilation_cache_dir", None)
    configure_compile_cache()
    assert COMPILE_CACHE_DIR == os.path.join(ROOT, ".jax_cache")
    assert cache_config.jax_compilation_cache_dir == COMPILE_CACHE_DIR


# -- bench.py: no CPU number under a device metric's name -------------------


def test_bench_device_mode_refuses_a_non_tpu_backend():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py")],
        env=_child_env(), capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""  # no JSON line, no number
    assert "need a TPU" in proc.stderr


# -- a process that searched exits 0 ----------------------------------------

_SEARCH_AND_EXIT = """
import sys
from namazu_tpu.obs import metrics, profiling
metrics.configure(True)
profiling.ensure_profiler("test", interval_s=0.001)

def work():
    from namazu_tpu.ops import trace_encoding as te
    from namazu_tpu.models.search import build_search_from_params
    search = build_search_from_params({
        "H": 32, "K": 32, "population": 64, "migrate_k": 2,
        "surrogate_topk": 0, "fused_chunk": 2})
    enc = te.encode_event_stream([f"h{i % 7}" for i in range(30)],
                                 L=32, H=32)
    search.run([enc], generations=2)
    return 0

sys.exit(work())
"""


def test_sampling_profiler_does_not_abort_a_process_that_searched():
    """The sampler holds other threads' frames, so when ``work`` returns
    it can be the last owner of the search's jax objects and free them
    from a daemon thread while the interpreter finalises — SIGABRT
    (rc 134) after the work succeeded. At the parent commit this child
    aborted 10 times out of 10."""
    for _ in range(2):
        proc = subprocess.run([sys.executable, "-c", _SEARCH_AND_EXIT],
                              env=_child_env(), capture_output=True,
                              text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]


def test_in_process_searched_runs_exit_zero(tmp_path):
    """`nmz-tpu run` exits 0 when the run succeeded: a loop of
    in-process searched run children at test width, every one exiting 0
    with its searched table installed (a campaign books rc 134 as an
    infra failure and retries the slot)."""
    from namazu_tpu.cli import cli_main
    from namazu_tpu.storage import load_storage

    materials = tmp_path / "materials"
    materials.mkdir()
    config = tmp_path / "config.json"
    config.write_text(json.dumps({
        "explore_policy": "tpu_search",
        "rest_port": 0,
        "run": "sleep 0.2",
        "validate": "true",
        "explore_policy_param": {
            "seed": 3, "max_interval": 30, "generations": 4,
            "population": 64, "hint_buckets": 32, "feature_pairs": 32,
            "migrate_k": 2, "fused_chunk": 2, "checkpoint": "search.npz",
        },
    }))
    storage = str(tmp_path / "st")
    assert cli_main(["init", str(config), str(materials), storage]) == 0
    st = load_storage(storage)
    record_run(st, ["a", "b", "a", "c", "b", "a"], successful=True)
    record_run(st, ["b", "a", "c", "a", "b", "c"], successful=False)
    st.close()
    for i in range(3):
        proc = subprocess.run(
            [sys.executable, "-m", "namazu_tpu.cli", "run", storage],
            env=_child_env(), capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        with open(os.path.join(storage, f"{2 + i:08x}", "nmz.log")) as f:
            log = f.read()
        assert "installed searched schedule" in log, log[-2000:]
        assert f"gen {4 * (i + 1)}) on cpu/" in log, log[-2000:]
