#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the searched campaign still
starts on the chip.

Drives the product's main path once, through the entry points a user
calls, at the width the ``tpu_search`` policy ships (population 4096,
hint_buckets 256, feature_pairs 256, 64 generations in fused chunks of
16, archive 512 / failures 64, up to 4 reference traces, surrogate
re-rank on) against the deployment the reference hunted ZOOKEEPER-2212
on — ``examples/zk-election``, a 3-node FLE ensemble:

    nmz-tpu init -> campaign -n N (random; records the history)
      -> nmz-tpu sidecar (owns the chip) -> campaign -n M (searched)
      -> sidecar stopped -> one nmz-tpu run with the in-process search

and, in a process of its own before that, checks what the entry points
cannot show: the Mosaic custom call in the compiled fused step, chip
fitness against the independent numpy scorer (``bench.numpy_score``),
chunks of 16 == one generation a dispatch, cold vs warm compile, and
— on a multi-chip host — one population shard per device and the
migration ring.

Ownership rule, by construction: this parent never imports jax; every
phase is a child process started after the previous one has exited, so
exactly one process holds the chip at any time.

Last line of stdout: ``{"ok": true, "device": {"platform": "tpu",
"kind": ..., "count": N}}`` — exactly those keys. What the run
established besides (compile seconds, numpy agreement, chunk
independence, mesh, searched runs, phase seconds) is the ``chip_smoke
facts:`` line before it and ``chiprun_out/chip_smoke/facts.json``. Any
other outcome exits non-zero and prints no result line — including "JAX
found no TPU": there is no CPU fallback. ``--cpu [N]`` is the explicit
dry run (N virtual CPU devices; prints ``"platform": "cpu"``) used to
debug the script off the chip.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
EXAMPLE = os.path.join(ROOT, "examples", "zk-election")
#: logs, storages and per-phase results; chiprun copies this tree back
OUT = os.path.join(ROOT, "chiprun_out", "chip_smoke")

#: the contract's wall limit is 1200 s, compilation included
DEADLINE_S = 1150.0
RANDOM_RUNS = 4
SEARCHED_RUNS = 4
GENERATIONS = 64  # the policy's shipped per-run generation count

#: keys of examples/zk-election/config_tpu_sidecar.toml that shrink the
#: search to a toy; dropped so the policy's own defaults (the shipped
#: width) apply
TOY_WIDTH_KEYS = ("generations", "population", "hint_buckets",
                  "feature_pairs", "migrate_k")


class SmokeFailure(Exception):
    pass


T0 = time.monotonic()
PHASE_S: dict = {}  # phase name -> wall seconds, for the result line
_LIVE: list = []  # Popen objects whose process groups we must not leak


def note(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


# -- parent: process plumbing (no jax here) --------------------------------


def _kill_group(proc: subprocess.Popen) -> None:
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        pass


def _cleanup() -> None:
    while _LIVE:
        _kill_group(_LIVE.pop())


def _tail(path: str, lines: int = 40) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-lines:])
    except OSError as e:
        return f"<no log: {e}>"


def _remaining() -> float:
    return DEADLINE_S - (time.monotonic() - T0)


def _default_sigint() -> None:
    # a caller that runs this script in the background hands down an
    # ignored SIGINT; the sidecar's clean stop needs the default one
    signal.signal(signal.SIGINT, signal.SIG_DFL)


def _spawn(argv, log_name: str, env: dict) -> "tuple[subprocess.Popen, str]":
    log_path = os.path.join(OUT, log_name)
    with open(log_path, "ab") as lf:
        proc = subprocess.Popen(argv, cwd=ROOT, env=env, stdout=lf,
                                stderr=lf, start_new_session=True,
                                preexec_fn=_default_sigint)
    _LIVE.append(proc)
    return proc, log_path


def run_phase(name: str, argv, env: dict, cap_s: float) -> str:
    """Run one child to completion; returns its log path. A non-zero
    exit or a timeout fails the smoke with the child's log tail."""
    note(f"phase {name}: {' '.join(argv[1:])}")
    t0 = time.monotonic()
    proc, log_path = _spawn(argv, f"{name}.log", env)
    try:
        rc = proc.wait(timeout=max(1.0, min(cap_s, _remaining())))
    except subprocess.TimeoutExpired:
        _kill_group(proc)
        raise SmokeFailure(
            f"phase {name} timed out after "
            f"{time.monotonic() - t0:.0f}s\n{_tail(log_path)}")
    finally:
        # the child is gone (or killed): sweep whatever it left in its
        # process group before the next phase may take the chip
        _kill_group(proc)
        _LIVE.remove(proc)
    PHASE_S[name] = round(time.monotonic() - t0, 1)
    if rc != 0:
        raise SmokeFailure(
            f"phase {name} exited {rc}\n{_tail(log_path)}")
    note(f"phase {name}: ok in {PHASE_S[name]}s")
    return log_path


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _toml(doc: dict) -> str:
    """Flat config dict (scalars + one level of tables) as TOML."""
    def val(v):
        if isinstance(v, bool):
            return "true" if v else "false"
        if isinstance(v, (int, float)):
            return repr(v)
        return json.dumps(str(v))

    top = [f"{k} = {val(v)}" for k, v in doc.items()
           if not isinstance(v, dict)]
    for name, table in doc.items():
        if isinstance(table, dict):
            top.append(f"\n[{name}]")
            top += [f"{k} = {val(v)}" for k, v in table.items()]
    return "\n".join(top) + "\n"


def search_config(sidecar_addr: str) -> str:
    """The example's sidecar search config at the SHIPPED width: the toy
    overrides dropped, the sidecar address ours; ``sidecar_addr == ""``
    gives the in-process variant of the same config."""
    import tomllib

    with open(os.path.join(EXAMPLE, "config_tpu_sidecar.toml"), "rb") as f:
        doc = tomllib.load(f)
    param = doc["explore_policy_param"]
    for key in TOY_WIDTH_KEYS:
        param.pop(key, None)
    if sidecar_addr:
        param["sidecar"] = sidecar_addr
    else:
        param.pop("sidecar")
    # a cold compile inside the first request must not be mistaken for
    # a dead search (milliseconds; the default is 120 s)
    param["search_join_timeout"] = 600_000
    return _toml(doc)


# -- parent: what a searched run must have logged --------------------------

_INSTALL_RE = re.compile(
    r"installed (sidecar|searched) schedule \(fitness (\S+), gen (\d+)\) "
    r"on (\S+)/(.+) x(\d+)")
_FAILURE_MARKS = ("schedule search failed", "unreachable/failed",
                  "Traceback (most recent call last)")


def check_searched_run(storage: str, index: int, source: str,
                       want_gen: int, device: dict) -> dict:
    """One searched run's nmz.log: the search installed a table whose
    source is ``source`` ("sidecar", or "searched" for the in-process
    home), at generation ``want_gen``, on ``device`` — and nothing fell
    back to hash delays."""
    log_path = os.path.join(storage, f"{index:08x}", "nmz.log")
    try:
        with open(log_path, errors="replace") as f:
            text = f.read()
    except OSError as e:
        raise SmokeFailure(f"run {index}: no log ({e})")
    for mark in _FAILURE_MARKS:
        if mark in text:
            raise SmokeFailure(
                f"run {index}: log carries {mark!r} — the search fell "
                f"back to hash delays\n{_tail(log_path)}")
    found = _INSTALL_RE.findall(text)
    if len(found) != 1:
        raise SmokeFailure(
            f"run {index}: expected exactly one searched install, "
            f"found {len(found)}\n{_tail(log_path)}")
    src, fitness, gen, platform, kind, count = found[0]
    if src != source:
        raise SmokeFailure(
            f"run {index}: install source {src!r}, wanted {source!r}")
    if int(gen) != want_gen:
        raise SmokeFailure(
            f"run {index}: generation count {gen}, wanted {want_gen} "
            f"(+{GENERATIONS} per searched run)")
    if not math.isfinite(float(fitness)):
        raise SmokeFailure(f"run {index}: non-finite fitness {fitness}")
    got = {"platform": platform, "kind": kind, "count": int(count)}
    if got != device:
        raise SmokeFailure(
            f"run {index}: searched on {got}, the smoke's device is "
            f"{device}")
    return {"run": index, "source": source, "gen": int(gen),
            "fitness": float(fitness)}


def check_checkpoint_width(storage: str, want_gen: int) -> None:
    """The checkpoint the searches share carries the shipped width."""
    import numpy as np

    with np.load(os.path.join(storage, "search.npz")) as z:
        shapes = {k: tuple(z[k].shape) for k in
                  ("pop_delays", "pairs", "archive", "failures")}
        gen = int(z["generations_run"])
    want = {"pop_delays": (4096, 256), "pairs": (256, 2),
            "archive": (512, 256), "failures": (64, 256)}
    if shapes != want:
        raise SmokeFailure(
            f"checkpoint is not at the shipped width: {shapes} != {want}")
    if gen != want_gen:
        raise SmokeFailure(
            f"checkpoint at generation {gen}, wanted {want_gen}")


def campaign(storage: str, runs: int, name: str, env: dict) -> None:
    # the supervisor prints its summary as the last stdout line; it
    # shares the phase log with its children's output
    log_path = run_phase(
        name,
        [sys.executable, "-m", "namazu_tpu.cli", "campaign", storage,
         "-n", str(runs), "--no-resume", "--json", "--retries", "0",
         "--wall-deadline", "700"],
        env, cap_s=900)
    summary = None
    with open(log_path, errors="replace") as f:
        for line in f:
            line = line.strip()
            if line.startswith("{") and "stopped_reason" in line:
                summary = json.loads(line)
    if summary is None:
        raise SmokeFailure(f"{name}: no campaign summary\n"
                           f"{_tail(log_path)}")
    if summary.get("experiment") != runs or summary.get("infra") \
            or summary.get("timeout"):
        raise SmokeFailure(
            f"{name}: wanted {runs} recorded experiment runs and no "
            f"infra/timeout slot, got {summary}\n{_tail(log_path)}")


def sidecar_ping(addr: str) -> dict:
    # the package's own framed client; importing it pulls no jax in
    # (parent_main asserts as much before it prints the result)
    from namazu_tpu.sidecar import request

    return request(addr, {"op": "ping"}, timeout=10)


def parent_main(args) -> int:
    if not os.path.isdir(os.path.join(ROOT, "namazu_tpu")) \
            or not os.path.isdir(EXAMPLE):
        print("chip_smoke: this script drives the namazu_tpu checkout it "
              "lives in; none found beside it", file=sys.stderr)
        return 2
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    # the example's scripts start their processes with `python`
    env["PATH"] = (os.path.dirname(sys.executable) + os.pathsep
                   + env.get("PATH", ""))
    if args.cpu:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.cpu}").strip()
    cache_dir = env.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(ROOT, ".jax_cache")

    def cache_entries() -> int:
        try:
            return len(os.listdir(cache_dir))
        except OSError:
            return 0

    entries0 = cache_entries()

    # 1. the device, the kernels and the numerics, in one process that
    # holds the chip alone (cold compile)
    child = [sys.executable, os.path.abspath(__file__)] \
        + (["--cpu", str(args.cpu)] if args.cpu else []) + ["--child"]
    cold_json = os.path.join(OUT, "device_cold.json")
    run_phase("device_cold",
              child + ["device", "--out", cold_json], env, cap_s=600)
    with open(cold_json) as f:
        cold = json.load(f)
    device = cold["device"]
    entries1 = cache_entries()
    # a cache that arrived pre-warmed (the machine kept
    # JAX_COMPILATION_CACHE_DIR from an earlier call) turns every
    # compile into a hit: nothing to add, nothing for the second
    # process to beat. An empty one must have been written to.
    compiled_cold = entries1 > entries0
    if entries0 == 0 and not compiled_cold:
        raise SmokeFailure(
            f"the first process left no compile-cache entry in "
            f"{cache_dir}")

    # 2. a second process, same cache directory: warm compile
    warm_json = os.path.join(OUT, "device_warm.json")
    run_phase("device_warm",
              child + ["device", "--compile-only", "--out", warm_json],
              env, cap_s=300)
    with open(warm_json) as f:
        warm = json.load(f)
    cold_s, warm_s = cold["compile_s"], warm["compile_s"]
    if compiled_cold and not warm_s < cold_s:
        raise SmokeFailure(
            f"second process compiled no faster than the first: "
            f"cold {cold_s}s, warm {warm_s}s, cache {cache_dir}")

    # 3. the README flow: init -> random campaign (records the history)
    storage = os.path.join(OUT, "zk")
    run_phase("init",
              [sys.executable, "-m", "namazu_tpu.cli", "init",
               os.path.join(EXAMPLE, "config.toml"),
               os.path.join(EXAMPLE, "materials"), storage],
              env, cap_s=60)
    campaign(storage, RANDOM_RUNS, "campaign_random", env)

    # 4. the sidecar takes the chip; the searched campaign delegates
    addr = f"127.0.0.1:{_free_port()}"
    with open(os.path.join(storage, "config.toml"), "w") as f:
        f.write(search_config(addr))
    note(f"starting the sidecar on {addr}")
    sidecar, sidecar_log = _spawn(
        [sys.executable, "-m", "namazu_tpu.cli", "sidecar",
         "--listen", addr], "sidecar.log", env)
    try:
        t0 = time.monotonic()
        while True:
            if sidecar.poll() is not None:
                raise SmokeFailure(
                    f"sidecar exited {sidecar.returncode} at start")
            try:
                if sidecar_ping(addr).get("ok"):
                    break
            except OSError:
                pass
            if time.monotonic() - t0 > 60:
                raise SmokeFailure(
                    "sidecar did not answer a ping in 60s")
            time.sleep(0.2)
        campaign(storage, SEARCHED_RUNS, "campaign_searched", env)
        ping = sidecar_ping(addr)
        if ping.get("device") != device:
            raise SmokeFailure(
                f"sidecar reports device {ping.get('device')}, the "
                f"smoke's device is {device}")
    except SmokeFailure as e:
        raise SmokeFailure(f"{e}\n--- sidecar log ---\n"
                           f"{_tail(sidecar_log)}")
    finally:
        # the chip must be free before the in-process run: SIGINT is
        # the sidecar's own clean stop, the group kill the backstop
        if sidecar.poll() is None:
            sidecar.send_signal(signal.SIGINT)
            try:
                sidecar.wait(timeout=60)  # 4 chips took ~13 s to let go
            except subprocess.TimeoutExpired:
                note("sidecar ignored SIGINT for 60s; killing it")
        sidecar_rc = sidecar.poll()
        _kill_group(sidecar)
        _LIVE.remove(sidecar)
    if sidecar_rc != 0:
        raise SmokeFailure(
            f"sidecar did not stop cleanly on SIGINT (exit "
            f"{sidecar_rc}; None = it had to be killed)\n"
            f"{_tail(sidecar_log)}")
    searched = [
        check_searched_run(storage, RANDOM_RUNS + i, "sidecar",
                           GENERATIONS * (i + 1), device)
        for i in range(SEARCHED_RUNS)]
    check_checkpoint_width(storage, GENERATIONS * SEARCHED_RUNS)

    # 5. the search's other home: one in-process searched run, chip free
    with open(os.path.join(storage, "config.toml"), "w") as f:
        f.write(search_config(""))
    run_phase("run_inprocess",
              [sys.executable, "-m", "namazu_tpu.cli", "run", storage],
              env, cap_s=700)
    searched.append(check_searched_run(
        storage, RANDOM_RUNS + SEARCHED_RUNS, "searched",
        GENERATIONS * (SEARCHED_RUNS + 1), device))
    check_checkpoint_width(storage, GENERATIONS * (SEARCHED_RUNS + 1))

    # the contract's result: exactly these keys, the device as JAX
    # reported it to the child that held the chip
    result = {"ok": True,
              "device": {"platform": str(device["platform"]),
                         "kind": str(device["kind"]),
                         "count": int(device["count"])}}
    facts = {
        **result,
        "compile_s": {"cold": cold_s, "warm": warm_s,
                      "cache_dir": cache_dir,
                      "cache_entries": [entries0, entries1,
                                        cache_entries()]},
        "mosaic_custom_call": cold["mosaic_custom_call"],
        "numpy_agreement": cold["numpy_agreement"],
        "chunk16_equals_chunk1": cold["chunk16_equals_chunk1"],
        "kernels": cold["kernels"],
        "mesh": cold["mesh"],
        "searched_runs": searched,
        "phase_s": PHASE_S,
        "total_s": round(time.monotonic() - T0, 1),
    }
    with open(os.path.join(OUT, "facts.json"), "w") as f:
        json.dump(facts, f, indent=1)
    assert "jax" not in sys.modules, "the smoke's parent imported jax"
    # what the run established, for the record (CHANGES.md quotes it);
    # the result line below stays the last line of stdout
    print("chip_smoke facts: " + json.dumps(facts), flush=True)
    print(json.dumps(result), flush=True)
    return 0


# -- child: the one process that holds the chip ----------------------------

#: relative rounding error of one round-to-nearest bf16 operand (8
#: significand bits) and of one f32 operation
U_BF16 = 2.0 ** -9
U_F32 = 2.0 ** -24


def _synthetic_history(search, n_refs: int = 4, seed: int = 0):
    """A seeded stand-in for an ingested campaign: ``n_refs`` reference
    traces (240 events over 96 hints, the bench's trace shape) plus a
    labeled archive deep enough for the surrogate to train."""
    import numpy as np

    from namazu_tpu.ops import trace_encoding as te

    rng = np.random.RandomState(seed)

    def one():
        order = rng.permutation(240)
        arrivals = np.sort(rng.uniform(0.0, 0.24, 240)).tolist()
        return te.encode_event_stream(
            [f"hint:{i % 96}" for i in order], arrivals=arrivals,
            L=256, H=search.cfg.H)

    refs = [one() for _ in range(n_refs)]
    occupied = sorted({int(h) for r in refs for h in r.hint_ids[r.mask]})
    search.set_occupied_buckets(occupied)
    for i in range(8):
        enc = one()
        search.add_executed_trace(enc, reproduced=bool(i % 2))
        if i % 2:
            search.add_failure_trace(enc)
    return refs


def _lower_fused(search, refs):
    """The fused island step exactly as ``ScheduleSearch.run``
    dispatches its first chunk, lowered (not run): the smoke reads the
    compiled program and times its compile."""
    import jax.numpy as jnp

    _encs, trace, pairs, archive, failures = \
        search._device_inputs_fused(refs)
    search._place_state()
    fused = search._fused_step_for(search.cfg.fused_chunk)
    nov = jnp.asarray(search.novelty_scale(), jnp.float32)
    return fused.lower(search._state, search._key, trace, pairs, archive,
                       failures, None, nov, None)


def _shards_by_device(search, arr):
    """``arr``'s addressable shards as host arrays, in mesh order."""
    import numpy as np

    by_dev = {s.device: np.asarray(s.data) for s in arr.addressable_shards}
    return [by_dev.get(d) for d in search.mesh.devices.flat]


def _check_mesh(search, before, refs, workdir: str) -> dict:
    """One population shard per device, the ppermute ring, checkpoint
    save/load and the surrogate's population fetch — on the sharded
    state a multi-chip host really holds."""
    import jax
    import numpy as np

    from namazu_tpu.models.search import build_search_from_params

    n = len(jax.devices())
    mesh_n = int(np.prod(list(search.mesh.shape.values())))
    assert mesh_n == n, f"mesh has {mesh_n} devices, host has {n}"
    delays = search._state.pop.delays
    shards = delays.addressable_shards
    devs = {s.device for s in shards}
    assert len(shards) == n and len(devs) == n, (
        f"{len(shards)} shards on {len(devs)} devices, wanted {n}")
    rows = search.population // n
    assert all(s.data.shape == (rows, search.cfg.H) for s in shards), (
        [s.data.shape for s in shards])
    after = _shards_by_device(search, delays)
    assert all(a is not None for a in after)
    changed = [bool((a != b).any()) for a, b in zip(after, before)]
    assert all(changed), f"population unchanged on islands: {changed}"
    # the ring: after a generation, island j's leading migrate_k rows
    # (its elites) sit verbatim in island j+1's tail rows
    k = search.cfg.migrate_k
    ring = [bool(np.array_equal(after[(j + 1) % n][rows - k:],
                                after[j][:k])) for j in range(n)]
    if n > 1:
        assert all(ring), f"migrants did not land on the ring: {ring}"
    # surrogate population fetch + checkpoint round trip on this state
    pd, pf = search._fetch_population()
    assert pd.shape == (search.population, search.cfg.H) == pf.shape
    ckpt = os.path.join(workdir, "mesh_ckpt.npz")
    search.save(ckpt)
    again = build_search_from_params({})
    again.load(ckpt)
    assert again.generations_run == search.generations_run
    assert np.array_equal(np.asarray(again._state.pop.delays), pd)
    best = again.run(refs, generations=again.cfg.fused_chunk)
    assert np.isfinite(best.fitness)
    assert again.generations_run == \
        search.generations_run + again.cfg.fused_chunk
    assert len({s.device for s in
                again._state.pop.delays.addressable_shards}) == n

    def where(x):
        return sorted(str(d) for d in x.devices())

    return {
        "devices": n,
        "rows_per_device": rows,
        "population_changed": changed,
        "ring_migrants_landed": ring if n > 1 else None,
        "checkpoint_roundtrip": True,
        # inputs the fused step takes replicated: resident on fewer
        # devices than the mesh = re-broadcast on every dispatch
        "resident_traces_on": where(search._traces.bufs["hint"]),
        "archive_mirror_on": where(search._dev_mirrors["archive"]),
    }


def _numpy_agreement() -> dict:
    """Chip fitness vs the independent numpy scorer at the bench's
    width (P 8192, A 1024), on a 64-genome sample.

    fitness = min_a d2(f,a) - min_f d2(f,fl) - 0.01*mean(delays) with
    d2 = |f|^2 + |c|^2 - 2 f.c. On the TPU the f.c operands are rounded
    to bf16 (8 significand bits: relative error within u = 2^-9;
    products exact in f32, f32 accumulation). Modelling each operand's
    rounding error as uniform in [-u, u], a product is off by a
    relative error of variance 2u^2/3, so

        sigma(d2(f_p, c)) = 2 u sqrt(2/3) sqrt(sum_k (f_pk c_k)^2)

    and a min over perturbed values moves by at most the largest
    perturbation. The stated tolerance is six sigma on each of the two
    distance terms (largest sigma over the archive's rows), plus 2e-3
    for everything that stays f32 (feature sigmoids, norms, the
    accumulation order). The worst-case bound — every rounding error at
    +-u with the same sign, 2(2u + u^2) sum|f_pk c_k| per term — is
    reported beside it. Off the TPU both operands stay f32: u = 2^-24."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import bench
    from namazu_tpu.models.ga import GAConfig, init_population
    from namazu_tpu.ops import trace_encoding as te
    from namazu_tpu.ops.schedule import (
        ScoreWeights,
        TraceArrays,
        score_population_multi,
    )

    P, H, L, K, A, F, sample = 8192, 256, 256, 256, 1024, 64, 64
    enc = te.encode_event_stream(
        [f"hint:{i % 96}" for i in range(240)],
        arrivals=[i * 1e-3 for i in range(240)], L=L, H=H)
    # one trace, as the [1, L] stack the scorer takes
    trace = TraceArrays(jnp.asarray(enc.hint_ids)[None],
                        jnp.asarray(enc.arrival)[None],
                        jnp.asarray(enc.mask)[None])
    pairs = te.sample_pairs(K, H, 0)
    archive = np.random.RandomState(0).rand(A, K).astype(np.float32)
    failures = np.random.RandomState(1).rand(F, K).astype(np.float32)
    pop = init_population(jax.random.PRNGKey(0), P, H,
                          GAConfig(max_delay=0.1))
    w = ScoreWeights()
    fitness, feats = score_population_multi(
        pop.delays, trace, jnp.asarray(pairs), jnp.asarray(archive),
        jnp.asarray(failures), w)
    fitness, feats = np.asarray(fitness), feats[:, 0]
    assert fitness.shape == (P,) and np.isfinite(fitness).all()

    delays = np.asarray(pop.delays)[:sample]
    ref = bench.numpy_score(delays, enc.hint_ids, enc.arrival, enc.mask,
                            pairs, archive, failures, tau=w.tau)
    f = np.asarray(feats, np.float64)[:sample]
    u = U_BF16 if jax.default_backend() == "tpu" else U_F32

    def sigma(c):  # largest sigma(d2(f_p, c_row)) over c's rows
        c = c.astype(np.float64)
        return 2 * u * np.sqrt(2 / 3) * np.sqrt(
            (f * f) @ (c * c).T).max(axis=1)

    def worst(c):  # the same, every rounding error at +-u, one sign
        return 2 * (2 * u + u * u) * (
            np.abs(f) @ np.abs(c.astype(np.float64)).T).max(axis=1)

    tol = 6 * (sigma(archive) + sigma(failures)) + 2e-3
    err = np.abs(fitness[:sample] - ref)
    ok = bool((err <= tol).all())

    return {
        "ok": ok,
        "sample": sample,
        "shape": {"P": P, "H": H, "L": L, "K": K, "A": A, "F": F},
        "operand_u": u,
        "max_abs_err": float(err.max()),
        "tolerance": float(tol.min()),
        "worst_case_bound": float(
            (worst(archive) + worst(failures)).max()),
        "fitness_abs_mean": float(np.abs(ref).mean()),
    }


def _pallas_vs_xla() -> dict:
    """The single-archive Pallas kernel at the bench's and the default
    width against XLA's path on the same chip (same operand rounding):
    two f32 evaluations of three K-term sums of terms in [0,1], each
    within K*2^-24 of |terms| <= 4K."""
    import jax.numpy as jnp
    import numpy as np

    from namazu_tpu.ops.pallas_score import min_sq_distance_pallas
    from namazu_tpu.ops.schedule import min_sq_distance

    K, out = 256, {}
    for (p, a) in ((8192, 1024), (4096, 512)):
        x = jnp.asarray(np.random.RandomState(2).rand(p, K)
                        .astype(np.float32))
        y = jnp.asarray(np.random.RandomState(3).rand(a, K)
                        .astype(np.float32))
        err = float(np.abs(np.asarray(min_sq_distance_pallas(x, y))
                           - np.asarray(min_sq_distance(x, y))).max())
        assert err <= 2 * K * U_F32 * 4 * K, (p, a, err)
        out[f"min_sq_distance_pallas[{p},{a},{K}]"] = {
            "max_abs_err_vs_xla": err}
    return out


def _chunk16_vs_chunk1(refs_seed: int = 0) -> dict:
    """tests/test_fused_loop.py's contract, at the shipped width, on
    this device: two searches from one seed, one in fused chunks of 16
    generations and one a generation a dispatch, must hold the same
    population and best."""
    import numpy as np

    from namazu_tpu.models.search import build_search_from_params

    out = {}
    states = []
    for chunk in (16, 1):
        s = build_search_from_params({"fused_chunk": chunk,
                                      "surrogate_topk": 0})
        refs = _synthetic_history(s, seed=refs_seed)
        s.run(refs, generations=32)
        states.append((np.asarray(s._state.pop.delays),
                       np.asarray(s._state.best_delays),
                       float(s._state.best_fitness)))
    (pf, bf, ff), (ps, bs, fs) = states
    out["bit_exact"] = bool(np.array_equal(pf, ps)
                            and np.array_equal(bf, bs) and ff == fs)
    out["population_max_abs_diff"] = float(np.abs(pf - ps).max())
    out["population_rows_differing"] = int((pf != ps).any(axis=1).sum())
    out["best_fitness"] = [ff, fs]
    return out


def child_device(args) -> int:
    """Everything that needs jax, in the one process holding the chip."""
    from namazu_tpu.parallel.mesh import device_summary

    device = device_summary()
    print(f"device: {device}", flush=True)
    if device["platform"] != "tpu" and not args.cpu:
        print("chip_smoke: JAX found no TPU (there is no CPU fallback; "
              "--cpu is the explicit dry run)", file=sys.stderr)
        return 3
    on_tpu = device["platform"] == "tpu"

    from namazu_tpu.models.search import build_search_from_params

    # the shipped width: an empty params dict is the policy's defaults
    search = build_search_from_params({})
    assert (search.population, search.cfg.H, search.cfg.K,
            search.cfg.archive_size, search.cfg.failure_size,
            search.cfg.fused_chunk, search.cfg.surrogate_topk) == \
        (4096, 256, 256, 512, 64, 16, 16), search.cfg
    refs = _synthetic_history(search)
    lowered = _lower_fused(search, refs)
    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = round(time.perf_counter() - t0, 2)
    print(f"fused step compiled in {compile_s}s", flush=True)
    out = {"device": device, "compile_s": compile_s}
    if args.compile_only:
        with open(args.out, "w") as f:
            json.dump(out, f)
        return 0

    text = compiled.as_text()
    mosaic = "tpu_custom_call" in text
    if on_tpu and not mosaic:
        raise AssertionError(
            "the compiled fused step holds no Mosaic custom call: the "
            "XLA path (or interpret mode) stood in for the kernel")
    out["mosaic_custom_call"] = mosaic if on_tpu else None

    before = _shards_by_device(search, search._state.pop.delays)
    t0 = time.perf_counter()
    best = search.run(refs, generations=GENERATIONS)
    run_s = round(time.perf_counter() - t0, 2)
    import numpy as np

    assert search.generations_run == GENERATIONS
    assert np.isfinite(best.fitness) and best.delays.shape == (256,)
    assert search._surrogate is not None, "surrogate re-rank did not train"
    print(f"{GENERATIONS} generations at the shipped width: {run_s}s, "
          f"best fitness {best.fitness:.4f}", flush=True)
    out["mesh"] = _check_mesh(search, before, refs,
                              os.path.dirname(args.out))
    out["mesh"]["first_run_s"] = run_s
    print(f"mesh: {out['mesh']}", flush=True)

    out["numpy_agreement"] = _numpy_agreement()
    out["kernels"] = _pallas_vs_xla() if on_tpu else {}
    print(f"numpy agreement: {out['numpy_agreement']}", flush=True)
    if not out["numpy_agreement"]["ok"]:
        raise AssertionError(
            f"chip fitness disagrees with bench.numpy_score beyond the "
            f"stated tolerance: {out['numpy_agreement']}")

    out["chunk16_equals_chunk1"] = _chunk16_vs_chunk1()
    print(f"chunks of 16 vs 1: {out['chunk16_equals_chunk1']}",
          flush=True)
    if not on_tpu and not out["chunk16_equals_chunk1"]["bit_exact"]:
        raise AssertionError(
            "chunks of 16 != chunks of 1 off the TPU, where the tier-1 "
            f"test pins bit-exactness: {out['chunk16_equals_chunk1']}")

    with open(args.out, "w") as f:
        json.dump(out, f)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu", type=int, nargs="?", const=1, default=0,
                    metavar="N",
                    help="explicit dry run on N virtual CPU devices "
                         "(default 1); the result line says "
                         "platform: cpu")
    ap.add_argument("--child", choices=("device",), help=argparse.SUPPRESS)
    ap.add_argument("--out", help=argparse.SUPPRESS)
    ap.add_argument("--compile-only", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        return child_device(args)
    try:
        return parent_main(args)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        _cleanup()


if __name__ == "__main__":
    sys.exit(main())
