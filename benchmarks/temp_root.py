#!/usr/bin/env python3
"""One run of a cell from a TEMPORARY root: the checkout's
``BENCHMARK.json``, traffic mixes, per-layer metric files and
configurations at the SHIPPED width and with the real templates, with
keys set on top of every configuration's ``search.set`` — how a mode or
a width no cell runs yet is read on the chip before a configuration
states it (PERF.md section 2's reorder-mode readings were made so).

    python3 benchmarks/temp_root.py <dir> <key>=<json> ... -- \\
        --workload <cell> --seed <n> --seconds <s> --trace <0|1>

e.g. ``.bench_tmp/w500 release_mode='"reorder"' reorder_gap=80
reorder_window=500``. ``<dir>`` is made under the checkout if it is not
there (name one ``.gitignore`` lists); what follows ``--`` goes to
``run.py`` with ``--root <dir>``, and the run's exit code is this one's.
The program and the examples are the checkout's own (symlinks).
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def build(root: str, search_set: dict) -> str:
    """The temporary root at ``root`` (kept if it is there already)."""
    if os.path.isdir(root):
        return root
    os.makedirs(os.path.join(root, "benchmarks"))
    for name in ("namazu_tpu", "examples"):
        os.symlink(os.path.join(REPO, name), os.path.join(root, name))
    for name in ("traffic", "layer_metrics", "configs"):
        shutil.copytree(os.path.join(HERE, name),
                        os.path.join(root, "benchmarks", name))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    configs = os.path.join(root, "benchmarks", "configs")
    for name in os.listdir(configs):
        if name.endswith(".history.json"):
            continue
        with open(os.path.join(configs, name)) as f:
            cfg = json.load(f)
        cfg["search"]["set"].update(search_set)
        with open(os.path.join(configs, name), "w") as f:
            json.dump(cfg, f, indent=1)
    return root


def main(argv: list) -> int:
    if "--" not in argv or not argv or argv[0] == "--":
        print(__doc__, file=sys.stderr)
        return 2
    cut = argv.index("--")
    search_set = {}
    for item in argv[1:cut]:
        key, _, value = item.partition("=")
        search_set[key] = json.loads(value)
    root = build(os.path.join(REPO, argv[0]), search_set)
    return subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--root", root]
        + argv[cut + 1:], cwd=root).returncode


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
