"""A throw-away checkout for the benchmark's CPU rehearsals: the real
``BENCHMARK.json``, harness, traffic mixes and per-layer metric files,
with every configuration cut to a toy width and pointed at the
rehearsal testee (``mini_example``), so that the tier-1 suite can drive
each cell end to end in seconds and off the examples' fixed ports."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(REPO, "benchmarks")

TINY_SEARCH = {"population": 64, "hint_buckets": 64, "feature_pairs": 32,
               "generations": 4, "fused_chunk": 2}
TINY_MIX = {
    "live": {"prefill_runs": 6, "prefill_failures": 3,
             "warmup_searched_runs": 1,
             "trace_slice_s": 2.0},
    "fleet": {"campaigns": 2, "history_depth": 6, "history_failures": 3,
              "warmup_requests_per_client": 2,
              "trace_slice_s": 2.0},
}


#: the order half of the genome at the rehearsal testee's scale (12
#: events over ~0.2 s, priorities in [0, 20 ms]): four arrival windows
REORDER_SEARCH = {"release_mode": "reorder", "reorder_gap": 10,
                  "reorder_window": 50}


def build(tmp: str, search: dict = None) -> str:
    """Returns the root of a tiny checkout under ``tmp``; ``search``
    is set on top of every configuration's ``search.set``."""
    root = os.path.join(str(tmp), "root")
    os.makedirs(os.path.join(root, "benchmarks", "configs"))
    os.symlink(os.path.join(REPO, "namazu_tpu"),
               os.path.join(root, "namazu_tpu"))
    for name in os.listdir(BENCH):
        src = os.path.join(BENCH, name)
        if name.endswith(".py"):
            shutil.copy(src, os.path.join(root, "benchmarks", name))
    shutil.copytree(os.path.join(BENCH, "layer_metrics"),
                    os.path.join(root, "benchmarks", "layer_metrics"))
    shutil.copytree(os.path.join(HERE, "mini_example"),
                    os.path.join(root, "examples", "mini"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        doc = json.load(f)
    for cfg in doc["configs"]:
        with open(os.path.join(REPO, cfg["file"])) as f:
            c = json.load(f)
        c["testee"] = {"example": "examples/mini", "materials": "materials",
                       "record_config": "config.toml",
                       "search_config": "config_search.toml",
                       "ports": [10967]}
        c["history"] = "examples/mini/history.json"
        c["search"]["drop"] = []
        c["search"]["set"].update(TINY_SEARCH, **(search or {}))
        c["guarantees"]["generations_per_request"] = 4
        with open(os.path.join(root, cfg["file"]), "w") as f:
            json.dump(c, f)
    os.makedirs(os.path.join(root, "benchmarks", "traffic"))
    for name in os.listdir(os.path.join(BENCH, "traffic")):
        with open(os.path.join(BENCH, "traffic", name)) as f:
            mix = json.load(f)
        mix.update(TINY_MIX["live" if mix["kind"] == "campaign"
                            else "fleet"])
        with open(os.path.join(root, "benchmarks", "traffic", name),
                  "w") as f:
            json.dump(mix, f)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return root


def tagged(out: str, tag: str) -> dict:
    """The JSON of the ``facts: `` line of a run's stdout."""
    return json.loads(next(line for line in out.splitlines()
                           if line.startswith(tag))[len(tag):])


def run_cell(root: str, cell: str, chips: int, trace: int = 0,
             seconds: float = 3.0, seed: int = 2147483659,
             extra_env: dict = None, cpu: bool = True):
    """One run of ``benchmarks/run.py`` in a process of its own (the
    parent must stay off jax). Returns (rc, result-or-None, stdout,
    stderr)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # the launcher sets its own device count
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")
    env.update(extra_env or {})
    argv = [sys.executable, os.path.join(root, "benchmarks", "run.py"),
            "--workload", cell, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]
    if cpu:
        argv += ["--cpu", str(chips)]
    proc = subprocess.run(argv, cwd=root, env=env, capture_output=True,
                          text=True, timeout=600)
    lines = proc.stdout.strip().splitlines()
    result = None
    if proc.returncode == 0 and lines:
        result = json.loads(lines[-1])
    return proc.returncode, result, proc.stdout, proc.stderr
