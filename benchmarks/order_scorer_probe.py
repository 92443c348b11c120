#!/usr/bin/env python3
"""How long ONE call of the program's population scorer takes on
JAX's default backend at the shipped width, order mode beside delay
mode, by reference-trace shape: the reading PERF.md section 7 sizes a
reorder cell from (a request costs 64 generations of it, the reply's
re-rank one more, eager). A diagnosis of the program, not a cell.

    python3 benchmarks/order_scorer_probe.py [T:L:events ...]

One JSON line per mode and shape: the first call (compiles) and the
mean of three more, jitted. Default shapes: the ``zk2212-fle3`` envelope
(1:128:18) and ``zk2212-zab5``'s four recent traces at L 1536 and at the
order mode's cap (4:1536:1500, 4:4096:1500).
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(REPO, ".jax_cache"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from namazu_tpu.ops import schedule as sch  # noqa: E402

P, H, K = 4096, 256, 256  # population, hint buckets, feature pairs
GAP_S, WINDOW_S = 0.08, 0.5


def main(argv: list) -> int:
    shapes = [tuple(int(x) for x in a.split(":")) for a in argv] or [
        (1, 128, 18), (4, 1536, 1500), (4, 4096, 1500)]
    rng = np.random.RandomState(0)
    u = rng.randint(0, H, K)
    pairs = jnp.asarray(np.stack(
        [u, (u + rng.randint(1, H, K)) % H], 1).astype(np.int32))
    archive = jnp.asarray(rng.uniform(0, 1, (512, K)).astype(np.float32))
    failures = jnp.asarray(rng.uniform(0, 1, (64, K)).astype(np.float32))
    tables = jnp.asarray(rng.uniform(0, 0.1, (P, H)).astype(np.float32))
    print(json.dumps({"device": str(jax.devices()[0])}), flush=True)
    for T, L, n in shapes:
        hints = np.zeros((T, L), np.int32)
        arrival = np.zeros((T, L), np.float32)
        mask = np.zeros((T, L), bool)
        for t in range(T):
            hints[t, :n] = rng.randint(0, H, n)
            arrival[t, :n] = np.sort(rng.uniform(0, 5, n))
            mask[t, :n] = True
        trace = sch.TraceArrays(jnp.asarray(hints), jnp.asarray(arrival),
                                jnp.asarray(mask))
        for order in (True, False):
            w = sch.ScoreWeights(
                novelty=0.3, bug=1.0, delay_cost=0.0, tau=GAP_S / 2,
                order_mode=order, order_gap=GAP_S, order_window=WINDOW_S)
            score = jax.jit(lambda d, tr, w=w: sch.score_population_multi(
                d, tr, pairs, archive, failures, w)[0])
            t0 = time.time()
            jax.block_until_ready(score(tables, trace))
            first = time.time() - t0
            t0 = time.time()
            for _ in range(3):
                out = score(tables, trace)
            jax.block_until_ready(out)
            print(json.dumps({
                "mode": "order" if order else "delay", "T": T, "L": L,
                "events": n, "first_s": round(first, 3),
                "each_s": round((time.time() - t0) / 3, 5)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
