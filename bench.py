"""Benchmark: interleavings scored per second per chip — and, with
``--pipeline``, events dispatched per second through the event plane.

The reference explores ONE interleaving per wall-clock experiment run
(minutes); its published metric is bug-repro rate per N runs (BASELINE.md).
This framework's throughput lever is how many candidate interleavings the
search plane can *score* per second on one chip — the denominator of
schedules-tried-per-hour. The benchmark times the jitted population scorer
(counterfactual release times -> precedence features -> archive-distance
matmul) at production sizes on the TPU and compares against a
single-thread numpy implementation of the same math (the CPU-python
baseline a reference-style policy could at best use). The device mode
exits non-zero when JAX's device is not a TPU — there is no CPU
fallback; ``--smoke`` (tiny CI sizes, no history) is the one shape that
runs on any backend.

``--pipeline`` measures the OTHER half of the serving path: a loopback
inspector -> REST endpoint -> orchestrator -> policy -> action poll ->
ack loop (doc/performance.md), reported as ``events_dispatched_per_sec``
for both the batched fast path and the per-event compatibility wire on
the same workload. No jax — the event plane is pure control plane.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Every completed round appends to BENCH_HISTORY.jsonl with a ``metric``
field; ``--gate`` compares only against same-metric, same-platform
history entries.
"""

from __future__ import annotations

import argparse
import datetime
import gc
import json
import math
import os
import re
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

# append-only bench trajectory: one JSON line per completed bench round
# (revision, timestamp, schedules/s, platform) — the ONE stable input
# for cross-round analytics and the --gate regression check, replacing
# archaeology over loose BENCH_r0*.json files
HISTORY_PATH = os.environ.get(
    "NMZ_BENCH_HISTORY",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "BENCH_HISTORY.jsonl"))
# --gate: fail when the fresh measurement falls more than this far below
# the best recent same-platform history entry
GATE_DEFAULT_PCT = float(os.environ.get("NMZ_BENCH_GATE_PCT", "30"))
# history entries (newest, same-platform) the gate baselines against —
# bounded so a years-long history cannot freeze the baseline on one
# ancient lucky measurement
GATE_BASELINE_WINDOW = 20


def _code_revision() -> str:
    """Short git revision of the working tree ("" when unavailable) —
    recorded into every history line so a figure can be traced to the
    code that produced it."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            timeout=10, capture_output=True, text=True,
        )
        return out.stdout.strip() if out.returncode == 0 else ""
    except Exception:
        return ""


def _require_tpu(smoke: bool) -> dict:
    """The two device modes measure the chip. On any other backend they
    exit non-zero before a number is printed — a CPU figure is never
    written under a device metric's name. ``--smoke`` (the CI shape)
    only validates the machinery and the artifact shape, so it runs on
    whatever backend JAX has and labels its line with it. Returns the
    device as JAX reports it."""
    from namazu_tpu.parallel.mesh import device_summary

    d = device_summary()
    device = {"platform": d["platform"], "device_kind": d["kind"],
              "device_count": d["count"]}
    if device["platform"] != "tpu" and not smoke:
        raise SystemExit(
            f"error: bench.py's device modes need a TPU; JAX reports "
            f"{device['platform']} ({device['device_kind']}). Use "
            f"--smoke to validate the machinery off the chip.")
    return device


def load_history(path: str = HISTORY_PATH) -> list:
    """All parseable history records, oldest first (bad lines skipped —
    an interrupted append must not brick every later gate)."""
    records = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if isinstance(rec, dict):
                    records.append(rec)
    except OSError:
        pass
    return records


def append_history(record: dict, path: str = HISTORY_PATH) -> None:
    with open(path, "a") as f:
        f.write(json.dumps(record, sort_keys=True) + "\n")


def _profiles_path(history_path: str) -> str:
    """Sidecar file to the bench history: the last healthy run's
    sampling profile per (metric, platform, transport mode) — what a
    failing ``--gate`` diffs against so the failure NAMES the
    regressing frames instead of just quoting a number."""
    return history_path + ".profiles.json"


def _profile_key(record: dict) -> str:
    return "|".join((_record_metric(record),
                     str(record.get("platform")),
                     str(record.get("transport_mode")
                         or record.get("mode") or "")))


def load_baseline_profile(record: dict,
                          history_path: str = HISTORY_PATH):
    try:
        with open(_profiles_path(history_path)) as f:
            doc = json.load(f)
        return doc.get(_profile_key(record)) \
            if isinstance(doc, dict) else None
    except (OSError, ValueError):
        return None


def store_baseline_profile(record: dict, prof: dict,
                           history_path: str = HISTORY_PATH) -> None:
    """Record ``prof`` (an ``nmz-profile-v1`` payload) as the baseline
    profile for ``record``'s gate key — called after a healthy
    (gate-passing or ungated) non-smoke pipeline round."""
    path = _profiles_path(history_path)
    try:
        with open(path) as f:
            doc = json.load(f)
        if not isinstance(doc, dict):
            doc = {}
    except (OSError, ValueError):
        doc = {}
    doc[_profile_key(record)] = prof
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
        f.write("\n")
    os.replace(tmp, path)


def emit_gate_profdiff(record: dict, prof,
                       history_path: str = HISTORY_PATH):
    """A failed ``--gate`` should say WHERE the time went: diff this
    run's profile against the stored baseline profile and write the
    ranked self-time frame deltas beside the history (JSON + text),
    echoing the top entries to stderr. Returns the artifact path, or
    None when either profile is missing (profiler off, first gated
    round). Never raises — the gate's exit code is the contract."""
    try:
        base = load_baseline_profile(record, history_path)
        if not base or not prof:
            print("# gate profdiff: no stored baseline profile or "
                  "profiler off; cannot name regressing frames",
                  file=sys.stderr)
            return None
        from namazu_tpu.obs import profdiff as _profdiff

        d = _profdiff.diff(base, prof)
        out_path = history_path + ".gate_profdiff.json"
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(d, f)
            f.write("\n")
        os.replace(tmp, out_path)
        with open(history_path + ".gate_profdiff.txt", "w") as f:
            f.write(_profdiff.render_text(d) + "\n")
        print(f"# gate profdiff written: {out_path}", file=sys.stderr)
        for line in _profdiff.render_text(d, limit=5).splitlines():
            print(f"# {line}", file=sys.stderr)
        return out_path
    except Exception as e:
        print(f"# gate profdiff failed: {e}", file=sys.stderr)
        return None


#: the scorer bench's metric name — also the implied metric of history
#: records that predate the ``metric`` field
SCORER_METRIC = "interleavings_scored_per_sec_per_chip"
PIPELINE_METRIC = "events_dispatched_per_sec"


def _record_metric(rec: dict) -> str:
    return rec.get("metric") or SCORER_METRIC


def _record_value(rec: dict):
    """The gated figure of a history record: generic ``value``, falling
    back to the scorer records' historical ``schedules_per_sec`` key."""
    v = rec.get("value")
    if v is None:
        v = rec.get("schedules_per_sec")
    try:
        return float(v) if v is not None else None
    except (TypeError, ValueError):
        return None


def gate_record(current: dict, history: list,
                threshold_pct: float = GATE_DEFAULT_PCT,
                window: int = GATE_BASELINE_WINDOW):
    """Regression gate: compare a fresh bench record against the best of
    the last ``window`` same-platform, same-METRIC history entries
    (scorer and pipeline rounds share one history file; a 5M schedules/s
    figure must never baseline a 40k events/s one).

    Returns ``(ok, reasons, baseline)``. A regression is a primary
    figure (or, when both records carry one, ``coverage``) more than
    ``threshold_pct`` percent below the baseline. Cross-platform
    comparisons are refused by construction — a CPU fallback reading
    40k/s must never read as a 99.6% TPU regression (the round-4 lesson
    all over again).
    """
    metric = _record_metric(current)
    # pipeline records carry the transport mode and workload/tuning
    # knobs: a per-event run must never be gated against a batched
    # baseline (a documented ~14x gap), an edge (zero-RTT) run never
    # against either (a further ~40x), nor a window-0 run against a
    # 50ms-window one — only like-configured records compare. Scorer
    # records carry none of these keys, so their comparisons are
    # unchanged. ``transport_mode`` is the canonical mode key; records
    # that predate it fall back to ``mode``.
    # codec and edge_shards joined in round 9: a JSON-wire figure must
    # never baseline a binary-wire one, nor a 1-shard run an N-shard
    # one — they are different machines
    # "runs" joined in round 10 (tenancy plane): an 8-tenant aggregate
    # figure must never baseline against a single-run one
    # "profile" joined with the profiling plane: the sampling profiler
    # rides the pipeline bench by default (budgeted <=2%), and the
    # --no-profile A/B figure must never cross-gate a profiled one
    # "virtual_clock" joined with the virtual-clock plane: a
    # fast-forwarded campaign figure must never baseline a wall-rate
    # one (nor the reverse) — the whole point of the A/B is that they
    # differ by an order of magnitude; "delay_scale" rides along so a
    # scale-50 record never baselines a scale-10 one
    CONFIG_KEYS = ("n_events", "n_entities", "batch_max",
                   "flush_window", "poll_linger", "gc_disabled",
                   "telemetry", "codec", "edge_shards", "edge_events",
                   "runs", "profile", "virtual_clock",
                   "delay_scale")

    def _mode(rec):
        return rec.get("transport_mode") or rec.get("mode")

    same = [h for h in history
            if h.get("platform") == current.get("platform")
            and _record_metric(h) == metric
            and _mode(h) == _mode(current)
            and all(h.get(k) == current.get(k) for k in CONFIG_KEYS)
            and _record_value(h)][-window:]
    reasons = []
    baseline = {}
    if not same:
        return True, [f"no {current.get('platform')!r} history to gate "
                      "against; pass"], baseline
    frac = threshold_pct / 100.0
    # scorer records keep their historical key/label so pre-metric
    # tooling (and humans) reading gate output see familiar names
    label = "schedules/s" if metric == SCORER_METRIC else metric
    key = "schedules_per_sec" if metric == SCORER_METRIC else "value"
    base_rate = max(_record_value(h) for h in same)
    baseline[key] = base_rate
    cur_rate = _record_value(current) or 0.0
    if cur_rate < base_rate * (1.0 - frac):
        reasons.append(
            f"{label} regression: {cur_rate:.1f} is "
            f"{100.0 * (1.0 - cur_rate / base_rate):.1f}% below the "
            f"recent best {base_rate:.1f} (threshold {threshold_pct:g}%)")
    covs = [float(h["coverage"]) for h in same
            if h.get("coverage") is not None]
    if covs and current.get("coverage") is not None:
        base_cov = max(covs)
        baseline["coverage"] = base_cov
        cur_cov = float(current["coverage"])
        if cur_cov < base_cov * (1.0 - frac):
            reasons.append(
                f"coverage regression: {cur_cov:.4f} is "
                f"{100.0 * (1.0 - cur_cov / base_cov):.1f}% below the "
                f"recent best {base_cov:.4f} "
                f"(threshold {threshold_pct:g}%)")
    return (not reasons), reasons, baseline


def numpy_score(delays, hint_ids, arrival, mask, pairs, archive, failures,
                tau=0.005):
    """Reference single-thread numpy implementation (one genome batch)."""
    P, H = delays.shape
    L = hint_ids.shape[0]
    BIG = 1e9
    t = arrival[None, :] + delays[:, hint_ids]  # [P, L]
    t = np.where(mask[None, :], t, BIG)
    first = np.full((P, H), BIG, np.float32)
    for p in range(P):  # scatter-min, the honest scalar way
        np.minimum.at(first[p], hint_ids, t[p])
    du = first[:, pairs[:, 0]]
    dv = first[:, pairs[:, 1]]
    z = np.clip((dv - du) / tau, -30, 30)
    feats = 1.0 / (1.0 + np.exp(-z))
    d2a = ((feats[:, None, :] - archive[None]) ** 2).sum(-1).min(1)
    d2f = ((feats[:, None, :] - failures[None]) ** 2).sum(-1).min(1)
    return d2a - d2f - 0.01 * delays.mean(-1)


def _stage_p99(name: str = "nmz_event_stage_seconds",
               stage: str = "wire"):
    """Current cumulative snapshot of one stage's latency histogram
    (None when never observed) — deltas around a run isolate that
    run's contribution."""
    from namazu_tpu.obs import metrics as _metrics

    child = _metrics.registry().sample(name, stage=stage)
    return None if child is None else child.snapshot()


def _p99_from_delta(before, after) -> "tuple[float | None, int]":
    """(p99 upper bound, sample count) of the histogram delta between
    two cumulative snapshots."""
    if after is None:
        return None, 0
    b_buckets = dict(before["buckets"]) if before else {}
    deltas = [(upper, acc - b_buckets.get(upper, 0))
              for upper, acc in after["buckets"]]
    count = after["count"] - (before["count"] if before else 0)
    if count <= 0:
        return None, 0
    want = 0.99 * count
    for upper, acc in deltas:
        if acc >= want:
            return upper, count
    return float("inf"), count


def run_pipeline(n_events: int, n_entities: int, use_batch: bool,
                 flush_window: float, batch_max: int,
                 run_id: str, poll_linger: float = 0.02,
                 edge: bool = False, codec: str = "auto",
                 edge_shards: int = 0, extras: dict = None) -> float:
    """One loopback event-plane run: real REST endpoint on an ephemeral
    port, real orchestrator threads, the TPU policy with zero delays
    (``max_interval=0`` — the measured quantity is plumbing, not
    injected fuzz), one RestTransceiver per entity. Returns events/s
    from first send to last acknowledged action received.

    ``edge=True`` measures the zero-RTT dispatch path
    (doc/performance.md): a zero-delay table is installed + published,
    the transceivers sync it up front, and every event is decided and
    released at the edge — the orchestrator only sees asynchronous
    backhaul. Decision semantics are pinned bit-for-bit against the
    central path by the trace-differ equivalence test
    (tests/test_edge_dispatch.py).

    ``edge_shards >= 1`` measures the sharded serving plane ("Binary
    wire + sharded edge"): entities hashed across an EdgeShardPool and
    bursts sent through ``send_events_burst`` (grouped verdicts, the
    production burst-inspector API). ``codec`` is the wire codec
    preference for every transceiver; ``extras`` (when given) receives
    per-shard rates and the run's wire-stage p99."""
    from namazu_tpu.inspector.rest_transceiver import RestTransceiver
    from namazu_tpu.orchestrator import Orchestrator
    from namazu_tpu.policy import create_policy
    from namazu_tpu.signal import PacketEvent
    from namazu_tpu.utils.config import Config

    cfg = Config({
        "rest_port": 0,
        "run_id": run_id,
        "explore_policy": "tpu_search",
        "explore_policy_param": {
            "search_on_start": False,
            "max_interval": 0,
            "seed": 7,
        },
    })
    policy = create_policy("tpu_search")
    policy.load_config(cfg)
    if edge:
        policy.install_table([0.0] * policy.H, source="bench")
    orc = Orchestrator(cfg, policy, collect_trace=False)
    orc.start()
    port = orc.hub.endpoint("rest").port
    entities = [f"bench-{i}" for i in range(max(1, n_entities))]
    pool = None
    if edge and edge_shards >= 1:
        from namazu_tpu.inspector.edge import EdgeShardPool

        pool = EdgeShardPool(edge_shards, backhaul_window=30.0)
    txs = {
        e: RestTransceiver(
            e, f"http://127.0.0.1:{port}", use_batch=use_batch,
            flush_window=flush_window, batch_max=batch_max,
            # the poll side drains bursts: a wider receive batch plus a
            # linger that matches the flush window keeps GET/DELETE
            # round trips amortized over whole bursts
            poll_batch=2 * batch_max, poll_linger=poll_linger,
            edge=edge, codec=codec, shard_pool=pool,
            # backhaul coalescing window wider than the whole dispatch
            # phase: trace backhaul is asynchronous BY DESIGN (the
            # orchestrator reconciles it behind the serving plane —
            # in production it runs in a separate process on its own
            # core), so the measured quantity is the dispatch rate with
            # backhaul deferred, and the shutdown flush below still
            # delivers every record synchronously before the run ends
            backhaul_window=(30.0 if edge
                             else max(flush_window, 0.02)))
        for e in entities
    }
    # GC is paused for the timed window only (timeit's own
    # convention): at 6-figure event rates a generational collection
    # that rescans the bench's pre-minted corpus adds double-digit
    # jitter to the figure, and cycle collection is not part of the
    # per-event plumbing being measured. Records carry
    # ``gc_disabled`` so the gate never baselines across the change.
    gc_was_enabled = gc.isenabled()
    try:
        for tx in txs.values():
            tx.start()
            if edge:
                version = tx.sync_table()
                assert version is not None and tx.edge_active, \
                    "edge bench: table sync failed"
        chans = []
        handles = []
        if edge:
            # burst sends: the inspectors that need 6-figure event
            # rates intercept in bursts (rawpacket, hookswitch), and
            # the edge's vectorized decide amortizes per-event overhead
            # across each burst. Events are minted up front — the
            # measured quantity is the serving plane's dispatch rate,
            # not interception cost. Sharded mode drives the burst API
            # (grouped verdicts); unsharded keeps the per-event waiter
            # wire of rounds 7/8 so their figures stay comparable.
            BURST = 256
            bursts = []
            for e_idx, e in enumerate(entities):
                evs = [PacketEvent.create(e, e, "peer",
                                          hint=f"h{i % 64}")
                       for i in range(e_idx, n_events, len(entities))]
                bursts.extend((txs[e], evs[i:i + BURST])
                              for i in range(0, len(evs), BURST))

            if pool is not None:
                def send():
                    for tx, burst in bursts:
                        handles.append(tx.send_events_burst(burst))
            else:
                def send():
                    for tx, burst in bursts:
                        chans.extend(tx.send_events(burst))
        else:
            def send():
                for i in range(n_events):
                    e = entities[i % len(entities)]
                    ev = PacketEvent.create(e, e, "peer",
                                            hint=f"h{i % 64}")
                    chans.append(txs[e].send_event(ev))
        # one shared timing epilogue: the modes differ ONLY in the send
        # loop, so the drain/timing convention can never diverge
        # between the figures the gate compares
        wire_before = _stage_p99()
        if gc_was_enabled:
            gc.disable()
        t0 = time.perf_counter()
        send()
        for h in handles:
            h.get_all(timeout=120)
        for ch in chans:
            ch.get(timeout=120)
        elapsed = time.perf_counter() - t0
        if extras is not None:
            p99, samples = _p99_from_delta(wire_before, _stage_p99())
            extras["wire_stage_p99_s"] = p99
            extras["wire_stage_samples"] = samples
            if pool is not None and elapsed > 0:
                extras["per_shard_events_per_sec"] = [
                    round(s.decisions / elapsed, 1)
                    for s in pool.shards]
    finally:
        if gc_was_enabled:
            gc.enable()
        for tx in txs.values():
            tx.shutdown()
        orc.shutdown()
    return n_events / elapsed if elapsed > 0 else float("inf")


#: the round-8 single-run batched central-wire figure (BENCH_r08.json)
#: — the reference the multi-run aggregate criterion is stated against
#: (ROADMAP item 1: >= 10x aggregate across 8+ runs on one orchestrator)
R08_BATCHED_BASELINE = 7772.8


def run_multi_pipeline(runs: int, n_events: int, n_entities: int,
                       flush_window: float, batch_max: int,
                       run_id: str, poll_linger: float = 0.02,
                       codec: str = "auto", wire: str = "uds",
                       shm: bool = True, edge: bool = False,
                       edge_shards: int = 0, extras: dict = None):
    """N concurrent namespaced pipelines against ONE TenantOrchestrator
    (doc/tenancy.md): each run leases its own namespace, drives
    ``n_events`` through the batched REST wire under its X-Nmz-Run
    header (entity names deliberately IDENTICAL across runs — namespace
    isolation is the machinery under test), and the aggregate
    events/s across all runs is the figure. Returns
    ``(aggregate_rate, per_run_rates)``."""
    import threading

    from namazu_tpu.policy import create_policy
    from namazu_tpu.signal import PacketEvent
    from namazu_tpu.tenancy.host import TenantOrchestrator
    from namazu_tpu.utils.config import Config

    runs = max(1, int(runs))
    ns_param = {"search_on_start": False, "max_interval": 0, "seed": 7}
    uds_path = f"/tmp/nmz-bench-multi-{os.getpid()}.sock"
    cfg = Config({
        "rest_port": 0,
        "run_id": run_id,
        "explore_policy": "tpu_search",
        "explore_policy_param": dict(ns_param),
        # every tenant holds ~2 keep-alive connections per entity; the
        # bounded pool must not queue the bench's own steady state
        "rest_max_threads": max(64, 4 * runs * max(1, n_entities)),
    })
    if wire == "uds":
        cfg.set("uds_path", uds_path)
    policy = create_policy("tpu_search")
    policy.load_config(cfg)
    if edge:
        # the zero-RTT serving plane under tenancy: one published
        # zero-delay table, per-namespace backhaul reconciliation —
        # each tenant's records land in its own pinned flight-recorder
        # run while decisions never touch the central GIL-bound path
        policy.install_table([0.0] * policy.H, source="bench")
    host = TenantOrchestrator(cfg, policy, collect_trace=False)
    host.start()
    port = host.hub.endpoint("rest").port
    url = f"http://127.0.0.1:{port}"
    leases = [host.registry.lease(
        f"bench-r{j}", ttl_s=600.0, policy="tpu_search",
        policy_param=dict(ns_param), collect_trace=False)
        for j in range(runs)]
    entities = [f"bench-{i}" for i in range(max(1, n_entities))]
    per_run_elapsed = [0.0] * runs
    per_run_done = [0.0] * runs
    errors = []
    barrier = threading.Barrier(runs + 1)

    pools = {}
    if edge and edge_shards >= 1:
        from namazu_tpu.inspector.edge import EdgeShardPool

        # one shard pool per tenant run (a tenant's edge shards are its
        # own, like its policy); entities hash across each pool's cores
        pools = {j: EdgeShardPool(edge_shards, backhaul_window=30.0)
                 for j in range(runs)}

    def make_tx(entity: str, j: int):
        if wire == "uds":
            from namazu_tpu.inspector.uds_transceiver import UdsTransceiver

            # the consolidated framed serving plane (endpoint/framed.py
            # selector core): no HTTP parse on the hot path, the shm
            # ring for the post side — the wire the tenancy plane is
            # built to saturate
            return UdsTransceiver(
                entity, uds_path, batch_max=batch_max,
                poll_batch=2 * batch_max, poll_linger=poll_linger,
                codec=codec, shm=shm and not edge, edge=edge,
                shard_pool=pools.get(j),
                backhaul_window=30.0 if edge else 0.05,
                run_ns=f"bench-r{j}")
        from namazu_tpu.inspector.rest_transceiver import RestTransceiver

        return RestTransceiver(
            entity, url, use_batch=True, flush_window=flush_window,
            batch_max=batch_max, poll_batch=2 * batch_max,
            poll_linger=poll_linger, codec=codec, edge=edge,
            shard_pool=pools.get(j),
            backhaul_window=30.0 if edge else max(flush_window, 0.02),
            run_ns=f"bench-r{j}")

    def drive(j: int) -> None:
        txs = {e: make_tx(e, j) for e in entities}
        try:
            for tx in txs.values():
                tx.start()
                if edge:
                    version = tx.sync_table()
                    assert version is not None and tx.edge_active, \
                        "multi-run edge bench: table sync failed"
            # pre-minted bursts of batch_max (the batched-wire
            # workload shape: a burst costs one post_batch op / one
            # flush, exactly like the single-run batched path under
            # load)
            bursts = []
            for e_idx, e in enumerate(entities):
                evs = [PacketEvent.create(e, e, "peer",
                                          hint=f"h{i % 64}")
                       for i in range(e_idx, n_events, len(entities))]
                bursts.extend((txs[e], evs[i:i + batch_max])
                              for i in range(0, len(evs), batch_max))
            barrier.wait()
            t0 = time.perf_counter()
            chans = []
            handles = []
            if edge and pools:
                for tx, burst in bursts:
                    handles.append(tx.send_events_burst(burst))
            else:
                for tx, burst in bursts:
                    chans.extend(tx.send_events(burst))
            for h in handles:
                h.get_all(timeout=240)
            for ch in chans:
                ch.get(timeout=240)
            done = time.perf_counter()
            per_run_elapsed[j] = done - t0
            per_run_done[j] = done
        except Exception as e:  # surface, don't hang the barrier
            errors.append((j, e))
            try:
                barrier.abort()
            except Exception:
                pass
        finally:
            for tx in txs.values():
                tx.shutdown()

    threads = [threading.Thread(target=drive, args=(j,),
                                name=f"bench-run-{j}", daemon=True)
               for j in range(runs)]
    gc_was_enabled = gc.isenabled()
    try:
        for t in threads:
            t.start()
        if gc_was_enabled:
            gc.disable()
        barrier.wait()  # all transceivers connected: the timed window
        t0 = time.perf_counter()
        for t in threads:
            t.join(timeout=300)
        # the aggregate window is first send -> LAST run's final ack;
        # transceiver shutdown (deferred backhaul flush, by design
        # asynchronous) stays outside it, same convention as the
        # single-run epilogue
        elapsed = (max(per_run_done) - t0) if any(per_run_done) else 0.0
    finally:
        if gc_was_enabled:
            gc.enable()
        for lease in leases:
            try:
                host.registry.release(lease["lease_id"],
                                      want_trace=False)
            except Exception:
                pass
        host.shutdown()
    hung = [j for j, t in enumerate(threads) if t.is_alive()]
    if hung:
        # a run that never finished must fail the bench loudly — its
        # events would otherwise inflate the aggregate (and poison the
        # gate baseline) while contributing no completed dispatches
        raise RuntimeError(f"multi-run bench: run(s) {hung} did not "
                           "finish within the join window")
    if errors:
        raise RuntimeError(f"multi-run bench failed: {errors[0][1]!r} "
                           f"(run {errors[0][0]})")
    per_run = [n_events / e if e > 0 else float("inf")
               for e in per_run_elapsed]
    aggregate = runs * n_events / elapsed if elapsed > 0 else float("inf")
    if extras is not None:
        extras["per_run_events_per_sec"] = [round(r, 1) for r in per_run]
    return aggregate, per_run


def pipeline_main(args: argparse.Namespace) -> None:
    """The ``--pipeline`` entry point: measure the batched fast path and
    the per-event compatibility wire on the SAME loopback workload, emit
    one JSON line with both figures, append to the bench history under
    the ``events_dispatched_per_sec`` metric (skipped for --smoke — the
    smoke workload is sized for CI liveness, not for measurement)."""
    n_events = 64 if args.smoke else args.pipeline_events
    n_entities = 2 if args.smoke else args.pipeline_entities
    # the edge path runs 2-3 orders of magnitude faster than the
    # central wires: it gets its own (larger) workload so the figure
    # integrates over a meaningful window instead of a few ms. A gate
    # config key like the rest.
    edge_events = n_events if args.smoke or not args.edge_events \
        else args.edge_events
    # fleet telemetry rides the bench like production (the orchestrator
    # starts the process relay; the edge dispatchers register their
    # gauge collectors): the enabled relay's overhead budget is <2% on
    # the edge figure (doc/observability.md "Fleet telemetry").
    # --no-telemetry measures the disabled plane — one global read on
    # the relay seams, the obs_enabled cost contract.
    telemetry_on = not getattr(args, "no_telemetry", False)
    from namazu_tpu.obs import federation, profiling

    federation.configure(telemetry_on)
    # the sampling profiler rides the bench like production: always-on
    # is the plane's design contract (doc/observability.md
    # "Profiling"), and --no-profile is the A/B arm of its <=2%
    # overhead budget. A gate config key like telemetry — profiled and
    # unprofiled figures never cross-compare.
    profile_on = not getattr(args, "no_profile", False)
    if profile_on:
        profiling.ensure_profiler("bench")
    # seeded fault plans reach the bench like any other process class
    # (doc/robustness.md): a no-op unless NMZ_CHAOS is set. CI's
    # seeded-slowdown smoke leans on this — inject a stage slowdown
    # into one arm and profdiff it against a clean arm.
    from namazu_tpu import chaos as _chaos

    _chaos.install_from_env()
    edge_shards = max(0, int(getattr(args, "edge_shards", 0)))
    runs = max(1, int(getattr(args, "runs", 1)))
    if runs > 1:
        return multi_run_main(args, runs, n_events, n_entities,
                              telemetry_on)
    out = {
        "metric": PIPELINE_METRIC,
        "unit": "events/s",
        # the figure is host-loopback-bound, not accelerator-bound;
        # its own platform tag keeps the gate from ever comparing it
        # against chip scorer numbers
        "platform": "loopback",
        "n_events": n_events,
        "n_entities": n_entities,
        "batch_max": args.batch_max,
        "flush_window": args.flush_window,
        "poll_linger": args.poll_linger,
        "telemetry": telemetry_on,
        "profile": profile_on,
        "codec": args.codec,
        "edge_shards": edge_shards,
        "edge_events": edge_events,
    }
    if args.smoke:
        out["smoke"] = True
    per_event = batched = edge = None
    if args.pipeline_mode in ("both", "per-event"):
        per_event = run_pipeline(
            n_events, n_entities, use_batch=False,
            flush_window=args.flush_window, batch_max=args.batch_max,
            run_id=f"bench-pipeline-perevent-{os.getpid()}",
            poll_linger=args.poll_linger, codec=args.codec)
        out["per_event_events_per_sec"] = round(per_event, 1)
    if args.pipeline_mode in ("both", "batched"):
        extras = {}
        batched = run_pipeline(
            n_events, n_entities, use_batch=True,
            flush_window=args.flush_window, batch_max=args.batch_max,
            run_id=f"bench-pipeline-batched-{os.getpid()}",
            poll_linger=args.poll_linger, codec=args.codec,
            extras=extras)
        out["batched_events_per_sec"] = round(batched, 1)
        out["batched_wire_stage_p99_s"] = extras.get("wire_stage_p99_s")
    if args.edge or args.pipeline_mode == "edge":
        extras = {}
        edge = run_pipeline(
            edge_events, n_entities, use_batch=True,
            flush_window=args.flush_window, batch_max=args.batch_max,
            run_id=f"bench-pipeline-edge-{os.getpid()}",
            poll_linger=args.poll_linger, edge=True, codec=args.codec,
            edge_shards=edge_shards, extras=extras)
        out["edge_events_per_sec"] = round(edge, 1)
        # the serving plane's wire segment: the edge path decides
        # locally, so its per-event wire stage all but disappears —
        # recorded beside the batched figure so the shrink is in the
        # artifact, not just the narrative
        out["edge_wire_stage_p99_s"] = extras.get("wire_stage_p99_s")
        out["edge_wire_stage_samples"] = extras.get(
            "wire_stage_samples", 0)
        if "per_shard_events_per_sec" in extras:
            out["per_shard_events_per_sec"] = \
                extras["per_shard_events_per_sec"]
        if edge_shards >= 1 and not args.smoke:
            # the round-9 serving-plane criterion (ROADMAP item 2):
            # >= 1M events/s aggregate loopback through the sharded
            # burst path
            out["criterion"] = {
                "aggregate_events_per_sec_min": 1_000_000,
                "met": edge >= 1_000_000,
            }
    # the codec byte ledger across every run above (labels are
    # per-process cumulative; the ratio is what matters)
    try:
        from namazu_tpu.obs import metrics as _metrics

        fam = {}
        for m in _metrics.registry().to_jsonable()["metrics"]:
            if m.get("name") == "nmz_wire_bytes_total":
                for s in m.get("samples", []):
                    codec_label = (s.get("labels") or {}).get("codec")
                    if codec_label:
                        fam[codec_label] = fam.get(codec_label, 0) \
                            + int(s.get("value", 0))
        if fam:
            out["wire_bytes_by_codec"] = fam
    except Exception:
        pass
    # primary figure: the fastest configured transport (edge when
    # measured — it IS the serving-plane headline)
    primary = edge if edge is not None else (
        batched if batched is not None else per_event)
    transport_mode = ("edge" if edge is not None
                      else "batched" if batched is not None
                      else "per-event")
    out["value"] = round(primary, 1)
    out["transport_mode"] = transport_mode
    if batched is not None and per_event:
        out["speedup"] = round(batched / per_event, 2)
    if edge is not None and batched:
        out["edge_speedup_vs_batched"] = round(edge / batched, 2)

    prior = load_history(args.history)
    record = {
        "timestamp": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
        "revision": _code_revision(),
        "metric": PIPELINE_METRIC,
        "value": out["value"],
        # the primary figure's transport mode — the gate only compares
        # same-mode records ("mode" kept alongside for pre-edge tooling
        # and history continuity)
        "transport_mode": transport_mode,
        "mode": transport_mode,
        "n_events": n_events,
        "n_entities": n_entities,
        # measurement condition, not a tuning knob: the timed window
        # runs with GC paused (see run_pipeline) — the gate must never
        # baseline across that change
        "gc_disabled": True,
        # likewise a measurement condition: whether the fleet-telemetry
        # relay ran during the timed window (the gate must not compare
        # relay-on vs relay-off records, however small the budgeted gap)
        "telemetry": telemetry_on,
        # same again for the sampling profiler (the --no-profile A/B)
        "profile": profile_on,
        "batch_max": args.batch_max,
        "flush_window": args.flush_window,
        "poll_linger": args.poll_linger,
        "codec": args.codec,
        "edge_shards": edge_shards,
        "edge_events": edge_events,
        "unit": out["unit"],
        "platform": out["platform"],
    }
    if "speedup" in out:
        record["speedup"] = out["speedup"]
        record["per_event_events_per_sec"] = \
            out["per_event_events_per_sec"]
    if "edge_speedup_vs_batched" in out:
        record["edge_speedup_vs_batched"] = \
            out["edge_speedup_vs_batched"]
        record["batched_events_per_sec"] = out["batched_events_per_sec"]
    prof_payload = _capture_bench_profile(args, profile_on)
    if not args.smoke:
        try:
            append_history(record, args.history)
        except OSError as e:  # the JSON line must still come out
            print(f"# could not append bench history: {e}",
                  file=sys.stderr)
    if args.gate:
        ok, reasons, baseline = gate_record(
            record, prior, threshold_pct=args.gate_threshold)
        out["gate"] = {"ok": ok, "threshold_pct": args.gate_threshold,
                       "baseline": baseline, "reasons": reasons}
        print(json.dumps(out))
        if not ok:
            # name the regressing frames, not just the number
            emit_gate_profdiff(record, prof_payload, args.history)
            for reason in reasons:
                print(f"# GATE FAILED: {reason}", file=sys.stderr)
            raise SystemExit(1)
        if prof_payload and not args.smoke:
            store_baseline_profile(record, prof_payload, args.history)
        return
    print(json.dumps(out))
    if prof_payload and not args.smoke:
        store_baseline_profile(record, prof_payload, args.history)


def _capture_bench_profile(args, profile_on: bool):
    """Drain + snapshot the bench's own sampling profile after the
    measured runs: returns the ``nmz-profile-v1`` payload (None when
    off) and honors ``--profile-out`` (speedscope JSON artifact — the
    flamegraph CI uploads from the pipeline smoke)."""
    if not profile_on:
        return None
    from namazu_tpu.obs import profiling

    prof = profiling.profiler()
    if prof is not None:
        prof.drain()  # fold the tail so short smokes aren't empty
    payload = profiling.payload()
    out_path = getattr(args, "profile_out", None)
    if out_path:
        doc = profiling.speedscope_doc()
        if doc is not None:
            try:
                with open(out_path, "w") as f:
                    json.dump(doc, f)
                    f.write("\n")
                print(f"# profile written: {out_path}",
                      file=sys.stderr)
            except OSError as e:
                print(f"# could not write profile: {e}",
                      file=sys.stderr)
    return payload


def multi_run_main(args: argparse.Namespace, runs: int,
                   n_events: int, n_entities: int,
                   telemetry_on: bool) -> None:
    """``--pipeline --runs N``: the tenancy-plane aggregate — N
    concurrent namespaced batched pipelines on ONE orchestrator,
    reported per-run + aggregate and gated under its own ``runs``
    config key (multi-run figures never baseline single-run ones)."""
    profile_on = not getattr(args, "no_profile", False)
    edge = bool(args.edge or args.pipeline_mode == "edge")
    edge_shards = max(0, int(getattr(args, "edge_shards", 0)))
    edge_events = n_events if args.smoke or not args.edge_events \
        else args.edge_events
    extras = {}
    central = central_per_run = None
    if not edge or args.pipeline_mode in ("both", "batched"):
        central_extras = {}
        central, central_per_run = run_multi_pipeline(
            runs, n_events, n_entities,
            flush_window=args.flush_window, batch_max=args.batch_max,
            run_id=f"bench-pipeline-multi-{os.getpid()}",
            poll_linger=args.poll_linger, codec=args.codec,
            extras=central_extras)
        extras = central_extras
    edge_agg = None
    if edge:
        edge_extras = {}
        edge_agg, _ = run_multi_pipeline(
            runs, edge_events, n_entities,
            flush_window=args.flush_window, batch_max=args.batch_max,
            run_id=f"bench-pipeline-multi-edge-{os.getpid()}",
            poll_linger=args.poll_linger, codec=args.codec,
            edge=True, edge_shards=edge_shards, extras=edge_extras)
        extras = edge_extras
    aggregate = edge_agg if edge_agg is not None else central
    out = {
        "metric": PIPELINE_METRIC,
        "unit": "events/s",
        "platform": "loopback",
        "runs": runs,
        "n_events": n_events,
        "n_entities": n_entities,
        "batch_max": args.batch_max,
        "flush_window": args.flush_window,
        "poll_linger": args.poll_linger,
        "telemetry": telemetry_on,
        "profile": profile_on,
        "codec": args.codec,
        "value": round(aggregate, 1),
        "transport_mode": "edge" if edge_agg is not None else "batched",
        "aggregate_events_per_sec": round(aggregate, 1),
        "per_run_events_per_sec": extras.get("per_run_events_per_sec"),
        "edge_shards": edge_shards,
        "edge_events": edge_events if edge_agg is not None else None,
        # the ROADMAP item-1 acceptance bar: >= 10x the round-8
        # single-run batched central figure, on one orchestrator
        "criterion": {
            "baseline_single_run_batched": R08_BATCHED_BASELINE,
            "aggregate_events_per_sec_min": round(
                10 * R08_BATCHED_BASELINE, 1),
            "met": aggregate >= 10 * R08_BATCHED_BASELINE,
        },
    }
    if central is not None and edge_agg is not None:
        # the central-path aggregate rides along for transparency: it
        # is GIL-bound in-process (the tenants and the host share one
        # interpreter here; production tenants are separate processes)
        out["central_aggregate_events_per_sec"] = round(central, 1)
        out["central_per_run_events_per_sec"] = central_per_run and [
            round(r, 1) for r in central_per_run]
    if args.smoke:
        out["smoke"] = True
    prior = load_history(args.history)
    record = {
        "timestamp": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
        "revision": _code_revision(),
        "metric": PIPELINE_METRIC,
        "value": out["value"],
        "transport_mode": out["transport_mode"],
        "mode": out["transport_mode"],
        "edge_shards": edge_shards,
        "edge_events": out.get("edge_events"),
        "runs": runs,
        "n_events": n_events,
        "n_entities": n_entities,
        "gc_disabled": True,
        "telemetry": telemetry_on,
        "profile": profile_on,
        "batch_max": args.batch_max,
        "flush_window": args.flush_window,
        "poll_linger": args.poll_linger,
        "codec": args.codec,
        "unit": out["unit"],
        "platform": out["platform"],
    }
    prof_payload = _capture_bench_profile(args, profile_on)
    if not args.smoke:
        try:
            append_history(record, args.history)
        except OSError as e:
            print(f"# could not append bench history: {e}",
                  file=sys.stderr)
    if args.gate:
        ok, reasons, baseline = gate_record(
            record, prior, threshold_pct=args.gate_threshold)
        out["gate"] = {"ok": ok, "threshold_pct": args.gate_threshold,
                       "baseline": baseline, "reasons": reasons}
        print(json.dumps(out))
        if not ok:
            emit_gate_profdiff(record, prof_payload, args.history)
            for reason in reasons:
                print(f"# GATE FAILED: {reason}", file=sys.stderr)
            raise SystemExit(1)
        if prof_payload and not args.smoke:
            store_baseline_profile(record, prof_payload, args.history)
        return
    print(json.dumps(out))
    if prof_payload and not args.smoke:
        store_baseline_profile(record, prof_payload, args.history)


# -- virtual-clock campaign A/B (doc/performance.md "Virtual clock") ------

#: the campaign A/B's metric and artifact (acceptance: ISSUE 20)
VCLOCK_METRIC = "campaign_repros_per_hour"
VCLOCK_TARGET_RATIO = 10.0
VCLOCK_SMOKE_MIN_SPEEDUP = 3.0
VCLOCK_OUT_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "VCLOCK_r01.json")
#: the zk-election scenario's stock knobs the delay scale multiplies —
#: the calibrated decision window (examples/zk-election/calibration.json)
#: and the random policy's fuzz-interval ceiling (config.toml)
VCLOCK_BASE_WINDOW_MS = 424
VCLOCK_BASE_MAX_INTERVAL_MS = 400


def _wilson_ci95(k: int, n: int) -> list:
    """Wilson score interval for a binomial proportion at z=1.96."""
    if n <= 0:
        return [0.0, 1.0]
    z = 1.96
    p = k / n
    denom = 1.0 + z * z / n
    center = (p + z * z / (2 * n)) / denom
    half = z * math.sqrt(p * (1 - p) / n + z * z / (4 * n * n)) / denom
    return [round(max(0.0, center - half), 4),
            round(min(1.0, center + half), 4)]


def _campaign_arm(virtual: bool, runs: int, workdir: str,
                  config_path: str, materials: str,
                  window_ms: int, wall_deadline_s: float) -> dict:
    """One campaign arm: fresh storage, N supervised runs, per-run
    repro classification from result.json. Both arms get the SAME
    config and environment; only --virtual-clock differs."""
    label = "virtual" if virtual else "wall"
    storage = os.path.join(workdir, f"st_{label}")
    env = dict(os.environ)
    env["NMZ_CALIB_DECISION_WINDOW_MS"] = str(window_ms)
    subprocess.run(
        [sys.executable, "-m", "namazu_tpu.cli", "init",
         config_path, materials, storage],
        env=env, check=True, capture_output=True, text=True)
    argv = [sys.executable, "-m", "namazu_tpu.cli", "campaign", storage,
            "-n", str(runs), "--wall-deadline", str(wall_deadline_s)]
    if virtual:
        argv.append("--virtual-clock")
    t0 = time.monotonic()
    proc = subprocess.run(argv, env=env, capture_output=True, text=True)
    wall_s = time.monotonic() - t0
    if proc.returncode != 0:
        tail = (proc.stderr or proc.stdout or "").strip()[-800:]
        raise RuntimeError(
            f"{label} campaign arm exited {proc.returncode}: {tail}")
    per_run = []
    for name in sorted(os.listdir(storage)):
        result_path = os.path.join(storage, name, "result.json")
        if not os.path.isfile(result_path):
            continue
        with open(result_path) as f:
            result = json.load(f)
        meta = result.get("metadata") or {}
        entry = {"run": name,
                 "repro": not bool(result.get("successful", True)),
                 "required_time_s": round(
                     float(result.get("required_time") or 0.0), 2)}
        for key in ("virtual_time_s", "wall_time_s", "vclock_speedup"):
            if key in meta:
                entry[key] = meta[key]
        per_run.append(entry)
    with open(os.path.join(storage, "campaign.json")) as f:
        checkpoint = json.load(f)
    classes = [s.get("class") for s in checkpoint.get("slots", [])
               if not s.get("in_progress")]
    n = len(per_run)
    k = sum(1 for r in per_run if r["repro"])
    wall_h = wall_s / 3600.0
    speedups = [r["vclock_speedup"] for r in per_run
                if r.get("vclock_speedup")]
    virtual_total = sum(r.get("virtual_time_s", 0.0) for r in per_run)
    arm = {
        "virtual_clock": virtual,
        "runs": n,
        "repros": k,
        "repro_rate": round(k / n, 4) if n else None,
        "repro_rate_wilson_ci95": _wilson_ci95(k, n),
        "campaign_wall_s": round(wall_s, 2),
        "runs_per_hour": round(n / wall_h, 1) if wall_h > 0 else None,
        "repros_per_hour_raw": (round(k / wall_h, 2)
                                if wall_h > 0 else None),
        "slot_classes": classes,
        "per_run": per_run,
    }
    if speedups:
        # the virtual arm's internal accounting: virtual seconds each
        # run covered vs the wall seconds it took (run_cmd metadata)
        arm["virtual_time_s_total"] = round(virtual_total, 2)
        arm["per_run_speedup_mean"] = round(
            sum(speedups) / len(speedups), 2)
    return arm


def campaign_main(args) -> None:
    """The --campaign mode: the same zk-election campaign twice —
    wall-rate control, then --virtual-clock — at an identical delay
    scale, recording repros/hour for both arms.

    The comparison is the tentpole's claim made measurable: scheduled
    fuzz delays and decision windows cost the wall arm real seconds
    but the virtual arm only jump targets, so at an equal per-run
    repro rate (overlapping Wilson CIs — same config, same policy,
    only the clock differs) repros/hour scales with runs/hour. The
    regression gate never compares a virtual record against a wall
    one: both carry ``virtual_clock`` as a gate config key."""
    smoke = bool(args.smoke)
    runs = 3 if smoke else max(1, int(args.campaign_runs))
    scale = 10.0 if smoke else max(1.0, float(args.campaign_scale))
    window_ms = int(VCLOCK_BASE_WINDOW_MS * scale)
    max_interval_ms = int(VCLOCK_BASE_MAX_INTERVAL_MS * scale)
    # generous per-run wall deadline: the scaled election plus slack —
    # a hung child must not wedge the bench, but a healthy wall-rate
    # run must never be killed mid-window
    wall_deadline_s = window_ms / 1000.0 * 4.0 + 120.0
    here = os.path.dirname(os.path.abspath(__file__))
    example = os.path.join(here, "examples", "zk-election")
    materials = os.path.join(example, "materials")
    with open(os.path.join(example, "config.toml")) as f:
        config_text = f.read()
    config_text = re.sub(r"(?m)^max_interval = \d+",
                         f"max_interval = {max_interval_ms}",
                         config_text)
    workdir = args.campaign_workdir or tempfile.mkdtemp(
        prefix="nmz-vclock-bench-")
    cleanup = not args.campaign_workdir
    os.makedirs(workdir, exist_ok=True)
    out_path = args.campaign_out or VCLOCK_OUT_PATH
    try:
        config_path = os.path.join(workdir, "config.toml")
        with open(config_path, "w") as f:
            f.write(config_text)
        arms = {}
        for virtual in (False, True):
            label = "virtual" if virtual else "wall"
            print(f"# campaign arm: {label} ({runs} run(s), delay "
                  f"scale {scale:g}, window {window_ms}ms)",
                  file=sys.stderr)
            arms[label] = _campaign_arm(
                virtual, runs, workdir, config_path, materials,
                window_ms, wall_deadline_s)
    finally:
        if cleanup:
            shutil.rmtree(workdir, ignore_errors=True)
    wall, virt = arms["wall"], arms["virtual"]
    # equal per-run repro rate is the precondition (overlapping Wilson
    # CIs); GIVEN it, the repros/hour ratio is the runs/hour ratio at
    # the pooled rate — robust when one small arm happens to draw 0
    # repros, where the raw ratio would be 0/0
    lo_w, hi_w = wall["repro_rate_wilson_ci95"]
    lo_v, hi_v = virt["repro_rate_wilson_ci95"]
    ci_overlap = lo_w <= hi_v and lo_v <= hi_w
    pooled_n = wall["runs"] + virt["runs"]
    pooled_rate = ((wall["repros"] + virt["repros"]) / pooled_n
                   if pooled_n else 0.0)
    at_pooled = {
        label: (round(pooled_rate * arm["runs_per_hour"], 2)
                if arm["runs_per_hour"] else None)
        for label, arm in arms.items()}
    ratio = None
    if at_pooled["wall"] and at_pooled["virtual"]:
        ratio = round(at_pooled["virtual"] / at_pooled["wall"], 2)
    elif wall["runs_per_hour"] and virt["runs_per_hour"]:
        ratio = round(virt["runs_per_hour"] / wall["runs_per_hour"], 2)
    out = {
        "metric": VCLOCK_METRIC,
        "unit": "repros/hour",
        # host-loopback control plane, like the pipeline figures
        "platform": "loopback",
        "example": "zk-election",
        "delay_scale": scale,
        "decision_window_ms": window_ms,
        "max_interval_ms": max_interval_ms,
        "runs_per_arm": runs,
        "wall": wall,
        "virtual": virt,
        "pooled_repro_rate": round(pooled_rate, 4),
        "repro_rate_ci_overlap": ci_overlap,
        "repros_per_hour_at_pooled_rate": at_pooled,
        "throughput_ratio": ratio,
        "rule": (f">={VCLOCK_TARGET_RATIO:g}x repros/hour vs the "
                 "wall-rate arm at overlapping per-run Wilson 95% CIs "
                 "(identical config both arms; records tagged "
                 "virtual_clock so the gate never compares them)"),
    }
    if smoke:
        # the CI job's contract (tier1.yml "Virtual-clock smoke"): the
        # virtual arm must cover >=3x its wall time in virtual seconds
        # and its slots must classify exactly like the wall control —
        # fast-forward must never turn an experiment into a timeout
        speedup = virt.get("per_run_speedup_mean") or 0.0
        classes_match = (virt["slot_classes"] == wall["slot_classes"])
        out["smoke_gate"] = {
            "per_run_speedup_mean": speedup,
            "min_speedup": VCLOCK_SMOKE_MIN_SPEEDUP,
            "slot_classes_match": classes_match,
            "ok": (speedup >= VCLOCK_SMOKE_MIN_SPEEDUP
                   and classes_match),
        }
        print(json.dumps(out))
        if not out["smoke_gate"]["ok"]:
            print(f"# VCLOCK SMOKE FAILED: speedup {speedup} "
                  f"(need >={VCLOCK_SMOKE_MIN_SPEEDUP}), classes "
                  f"match={classes_match}", file=sys.stderr)
            raise SystemExit(1)
        return
    out["ratio_ok"] = bool(ratio and ratio >= VCLOCK_TARGET_RATIO)
    tmp = out_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, out_path)
    prior = load_history(args.history)
    stamp = datetime.datetime.now(
        datetime.timezone.utc).isoformat(timespec="seconds")
    for label, arm in arms.items():
        record = {
            "timestamp": stamp,
            "revision": _code_revision(),
            "metric": VCLOCK_METRIC,
            "value": at_pooled[label],
            "unit": "repros/hour",
            "platform": "loopback",
            "virtual_clock": arm["virtual_clock"],
            "delay_scale": scale,
            "runs": runs,
            "repros": arm["repros"],
            "campaign_wall_s": arm["campaign_wall_s"],
            "throughput_ratio": ratio,
        }
        try:
            append_history(record, args.history)
        except OSError as e:
            print(f"# could not append bench history: {e}",
                  file=sys.stderr)
    if args.gate:
        # same-arm history regression gating plus the absolute
        # acceptance rule; virtual and wall records never compare
        # (virtual_clock and delay_scale are gate config keys)
        virt_record = {"metric": VCLOCK_METRIC, "platform": "loopback",
                       "virtual_clock": True, "delay_scale": scale,
                       "runs": runs, "value": at_pooled["virtual"]}
        ok, reasons, baseline = gate_record(
            virt_record, prior, threshold_pct=args.gate_threshold)
        accept = bool(out["ratio_ok"] and ci_overlap)
        out["gate"] = {"ok": ok and accept,
                       "threshold_pct": args.gate_threshold,
                       "baseline": baseline, "reasons": reasons}
        print(json.dumps(out))
        if not accept:
            print(f"# GATE FAILED: throughput ratio {ratio} (need "
                  f">={VCLOCK_TARGET_RATIO:g}) with CI overlap="
                  f"{ci_overlap}", file=sys.stderr)
            raise SystemExit(1)
        if not ok:
            for reason in reasons:
                print(f"# GATE FAILED: {reason}", file=sys.stderr)
            raise SystemExit(1)
        return
    print(json.dumps(out))


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(
        description="namazu_tpu scorer benchmark (one JSON line)")
    ap.add_argument("--gate", action="store_true",
                    help="after measuring, compare against the bench "
                         "history and exit 1 on a regression beyond "
                         "--gate-threshold (CI regression gating)")
    ap.add_argument("--gate-threshold", type=float,
                    default=GATE_DEFAULT_PCT, metavar="PCT",
                    help="allowed percent drop below the recent best "
                         f"same-platform figure (default {GATE_DEFAULT_PCT:g})")
    ap.add_argument("--history", default=HISTORY_PATH,
                    help="bench-history JSONL path (default "
                         "BENCH_HISTORY.jsonl next to bench.py; env "
                         "NMZ_BENCH_HISTORY)")
    ap.add_argument("--coverage", type=float, default=None,
                    help="optional exploration-coverage figure (the "
                         "unique-interleaving fraction from `nmz-tpu "
                         "tools report`) folded into the history record "
                         "and gated alongside schedules/s")
    ap.add_argument("--pipeline", action="store_true",
                    help="measure the event plane instead of the "
                         "scorer: a loopback inspector -> orchestrator "
                         "-> policy -> ack loop, reported as "
                         "events_dispatched_per_sec (no jax needed)")
    ap.add_argument("--smoke", action="store_true",
                    help="fixed tiny workload for CI liveness — "
                         "completes fast, emits the JSON line, appends "
                         "no history; the only shape in which the "
                         "device modes run on a non-TPU backend")
    ap.add_argument("--pipeline-events", type=int, default=2000,
                    metavar="N", help="events per pipeline run "
                    "(default 2000)")
    ap.add_argument("--pipeline-entities", type=int, default=2,
                    metavar="K", help="concurrent loopback entities "
                    "(default 2 — on small hosts more entities just "
                    "multiply polling threads and GIL contention)")
    ap.add_argument("--runs", type=int, default=1, metavar="N",
                    help="with --pipeline: drive N concurrent "
                         "NAMESPACED pipelines against one "
                         "TenantOrchestrator (tenancy plane, "
                         "doc/tenancy.md) and report per-run + "
                         "aggregate events/s; a gate config key — "
                         "multi-run figures never baseline single-run "
                         "ones (default 1 = the classic single-run "
                         "modes)")
    ap.add_argument("--pipeline-mode", default="both",
                    choices=("both", "batched", "per-event", "edge"),
                    help="which transport(s) to measure (default both; "
                         "the printed line carries each mode's figure; "
                         "'edge' measures only the zero-RTT path)")
    ap.add_argument("--edge", action="store_true",
                    help="with --pipeline: also measure the zero-RTT "
                         "edge-dispatch path (published delay table, "
                         "local decisions, async backhaul — "
                         "doc/performance.md); the edge figure becomes "
                         "the primary gated value")
    ap.add_argument("--edge-events", type=int, default=0, metavar="N",
                    help="with --edge: events for the edge run "
                         "(default = --pipeline-events; the zero-RTT "
                         "path is ~3 orders faster than the central "
                         "wires, so a stable figure needs a larger "
                         "workload)")
    ap.add_argument("--codec", default="auto",
                    choices=("auto", "json", "binary"),
                    help="wire codec preference for every pipeline "
                         "transceiver (doc/performance.md \"Binary "
                         "wire + sharded edge\"): auto negotiates the "
                         "binary codec per connection, json pins the "
                         "legacy wire; a gate config key — figures "
                         "never baseline across codecs")
    ap.add_argument("--edge-shards", type=int, default=0, metavar="K",
                    help="with --edge: shard the edge across K "
                         "EdgeShardPool engines and drive the "
                         "send_events_burst serving-plane API "
                         "(grouped verdicts; reports per-shard and "
                         "aggregate events/s, 1M-criterion gated); "
                         "0 = the round-7/8 per-entity dispatchers")
    ap.add_argument("--no-profile", action="store_true",
                    help="with --pipeline: run WITHOUT the sampling "
                         "profiler (the A/B arm of its <=2% overhead "
                         "budget, doc/observability.md \"Profiling\"); "
                         "records carry `profile` so the gate never "
                         "compares across the switch")
    ap.add_argument("--profile-out", default="", metavar="PATH",
                    help="with --pipeline: write the bench process's "
                         "sampling profile as speedscope JSON to PATH "
                         "after the run (the flamegraph artifact CI "
                         "uploads from the pipeline smoke)")
    ap.add_argument("--no-telemetry", action="store_true",
                    help="with --pipeline: disable the fleet-telemetry "
                         "relay for the timed window (the no-op-plane "
                         "cost check, doc/observability.md); records "
                         "carry `telemetry` so the gate never compares "
                         "across this switch")
    ap.add_argument("--batch-max", type=int, default=128, metavar="N",
                    help="transceiver coalescing size cap (default 128)")
    ap.add_argument("--flush-window", type=float, default=0.05,
                    metavar="S", help="transceiver coalescing window in "
                    "seconds; 0 = synchronous per-send flush "
                    "(default 0.05)")
    ap.add_argument("--poll-linger", type=float, default=0.05,
                    metavar="S", help="server-side action-poll linger "
                    "in seconds: after the first action, keep filling "
                    "the batch this long (default 0.05)")
    ap.add_argument("--campaign", action="store_true",
                    help="virtual-clock campaign A/B (doc/performance"
                         ".md \"Virtual clock\"): run the zk-election "
                         "campaign twice with IDENTICAL config — once "
                         "wall-rate, once --virtual-clock — and record "
                         "repros/hour for both arms in VCLOCK_r01.json."
                         " With --smoke: 3 runs/arm at a small delay "
                         "scale, gated on the virtual arm covering "
                         ">=3x its wall time and slot classes matching "
                         "the wall control (the CI job)")
    ap.add_argument("--campaign-runs", type=int, default=10, metavar="N",
                    help="supervised runs per campaign arm "
                         "(default 10)")
    ap.add_argument("--campaign-scale", type=float, default=100.0,
                    metavar="X",
                    help="delay scale applied identically to BOTH "
                         "arms: the scenario's fuzz intervals and "
                         "decision window are multiplied by X "
                         "(default 100). The virtual arm fast-forwards "
                         "the added idle time; the wall arm sleeps "
                         "through it — the decoupling the bench "
                         "measures")
    ap.add_argument("--campaign-out", default="", metavar="PATH",
                    help="where to write the campaign A/B record "
                         "(default VCLOCK_r01.json next to bench.py)")
    ap.add_argument("--campaign-workdir", default="", metavar="DIR",
                    help="scratch dir for the two arms' storages "
                         "(default: a fresh temp dir, removed after)")
    return ap.parse_args(argv)


def main(argv=None) -> None:
    args = parse_args(argv)
    if args.campaign:
        # pure control plane, like --pipeline: no jax import — the
        # campaign A/B runs the same everywhere
        return campaign_main(args)
    if args.pipeline:
        # pure control plane: no jax import — the event plane runs the
        # same everywhere
        return pipeline_main(args)

    device = _require_tpu(args.smoke)
    import jax
    import jax.numpy as jnp

    from namazu_tpu.models.ga import GAConfig, init_population
    from namazu_tpu.ops import trace_encoding as te
    from namazu_tpu.ops.schedule import (
        ScoreWeights,
        TraceArrays,
        score_population_multi,
    )

    if args.smoke:
        P, H, L, K, A, F, iters = 256, 64, 128, 64, 64, 16, 8
    else:
        # production sizes: 8192 genomes x 256-event trace, 1024-entry
        # archive, 50 scoring passes per timed dispatch
        P, H, L, K, A, F, iters = 8192, 256, 256, 256, 1024, 64, 50

    n_ev = min(240, L - 16)
    enc = te.encode_event_stream(
        [f"hint:{i % 96}" for i in range(n_ev)],
        arrivals=[i * 1e-3 for i in range(n_ev)],
        L=L, H=H,
    )
    trace = TraceArrays(  # one trace, as the [1, L] stack the scorer takes
        jnp.asarray(enc.hint_ids)[None], jnp.asarray(enc.arrival)[None],
        jnp.asarray(enc.mask)[None],
    )
    pairs = jnp.asarray(te.sample_pairs(K, H, 0))
    archive = jnp.asarray(
        np.random.RandomState(0).rand(A, K).astype(np.float32))
    failures = jnp.asarray(
        np.random.RandomState(1).rand(F, K).astype(np.float32))
    pop = init_population(jax.random.PRNGKey(0), P, H,
                          GAConfig(max_delay=0.1))
    weights = ScoreWeights()

    @jax.jit
    def score_chain(delays):
        # The production pattern: the search loop chains generations
        # on-device and only synchronises when a run's schedule is
        # extracted (models/search.py run()). One fori_loop = ONE
        # dispatch for all `iters` scoring passes, so the host->device
        # round trip is paid once, not per call. Each pass perturbs the
        # population by its own fitness (what GA mutation does), which
        # also keeps XLA from collapsing the loop.
        def step(_, d):
            fit, _f = score_population_multi(d, trace, pairs, archive,
                                             failures, weights)
            return d + 1e-9 * fit[:, None]
        return jax.lax.fori_loop(0, iters, step, delays)

    # warmup/compile
    score_chain(pop.delays).block_until_ready()

    best_dt = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        score_chain(pop.delays).block_until_ready()
        best_dt = min(best_dt, time.perf_counter() - t0)

    # publish through the observability registry and read the reported
    # figure back from it: the bench's JSON line and live telemetry
    # (GET /metrics, nmz_scorer_schedules_per_sec) share one source of
    # truth and can never disagree
    from namazu_tpu import obs

    obs.configure(True)  # the bench is a telemetry producer by definition
    obs.scorer_throughput("bench", P * iters / best_dt)
    device_rate = obs.scorer_throughput_value("bench")

    # numpy baseline on a small slice, per-schedule rate extrapolated
    nb = 64
    np_args = (
        np.asarray(pop.delays)[:nb], enc.hint_ids, enc.arrival, enc.mask,
        np.asarray(pairs), np.asarray(archive), np.asarray(failures),
    )
    # Pin the BLAS pool at runtime (numpy's BLAS read its env when this
    # module imported numpy, long before this line): an unpinned pool
    # made vs_baseline swing >2x between identical runs. best-of-5 for
    # BOTH sides, so the ratio is built from symmetric estimators.
    try:
        from threadpoolctl import threadpool_limits
    except ImportError:  # the JSON line must still come out; the
        # baseline is just noisier without the pin
        import contextlib

        def threadpool_limits(limits):
            return contextlib.nullcontext()

    with threadpool_limits(limits=1):
        numpy_score(*np_args)  # warm cache
        np_dts = []
        for _ in range(5):
            t0 = time.perf_counter()
            numpy_score(*np_args)
            np_dts.append(time.perf_counter() - t0)
    baseline_rate = nb / min(np_dts)

    platform = device["platform"]
    out = {
        "metric": SCORER_METRIC,
        "value": round(device_rate, 1),
        "unit": "schedules/s",
        "vs_baseline": round(device_rate / baseline_rate, 2),
        **device,
    }
    if args.smoke:
        # tiny CI workload: validate the machinery + artifact shape,
        # never a history point
        out["smoke"] = True
        print(json.dumps(out))
        return

    # bench trajectory: every completed round appends one history line;
    # the gate baselines against the entries that PRECEDED this round
    if args.coverage is not None:
        out["coverage"] = args.coverage
    prior = load_history(args.history)
    record = {
        "timestamp": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
        "revision": _code_revision(),
        "metric": SCORER_METRIC,
        "schedules_per_sec": out["value"],
        "unit": out["unit"],
        "vs_baseline": out["vs_baseline"],
        "platform": platform,
    }
    if args.coverage is not None:
        record["coverage"] = args.coverage
    try:
        append_history(record, args.history)
    except OSError as e:  # the JSON line must still come out
        print(f"# could not append bench history: {e}", file=sys.stderr)

    if args.gate:
        ok, reasons, baseline = gate_record(
            record, prior, threshold_pct=args.gate_threshold)
        out["gate"] = {"ok": ok, "threshold_pct": args.gate_threshold,
                       "baseline": baseline, "reasons": reasons}
        print(json.dumps(out))
        if not ok:
            for reason in reasons:
                print(f"# GATE FAILED: {reason}", file=sys.stderr)
            raise SystemExit(1)
        return
    print(json.dumps(out))


if __name__ == "__main__":
    main()
