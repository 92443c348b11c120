#!/usr/bin/env python3
"""One command, one cell, one run:

    python3 benchmarks/run.py --workload <config>.<mix> --seed <n> \
        --seconds <s> --trace <0|1>

This parent never imports jax. It starts the search sidecar through
``benchmarks/sidecar_main.py`` (the one process that holds the chips),
makes the cell's histories from ``--seed``, warms the cell's own shapes
with the mix's fixed number of warm-up cycles, opens the window ON a
cycle boundary, measures for ``--seconds``, stops everything it
started, checks what the window produced against the plain reference
and prints, as the last line of stdout, the result object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``). Facts that are not metrics go on the ``facts:`` line
before it and into ``chiprun_out/benchmarks/<cell>/facts.json``; every
number compared, beside its limit, last in the result object (``checks``)
and as the ``checks:`` line that ends stderr.

Off a TPU (or with fewer chips than the cell asks for) it exits non-zero
and prints no result. ``--cpu N`` is the tests' explicit dry run.
"""

from __future__ import annotations

import time

T0 = time.time()  # process start, as near as Python lets us read it

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import socket  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import history  # noqa: E402
import layer_metrics  # noqa: E402
import manifest as manifest_mod  # noqa: E402
import rates  # noqa: E402
import reference  # noqa: E402
import wire  # noqa: E402

#: the limits of the comparison with the plain reference
#: (benchmarks/reference.py; the readings each was set from: PERF.md
#: section 2). Fitness units; feature units; the times are exact.
FITNESS_GAP_LIMIT = 0.05
ROWS_GAP_LIMIT = 1e-5
#: tables per search whose fused-step fitness is read one by one
PROBE_TABLES = 4
SIDECAR_START_S = 240.0
REQUEST_TIMEOUT_S = 900.0

_INSTALL_RE = re.compile(
    r"installed sidecar schedule \(fitness (\S+), gen (\d+)\)")
_FAILURE_MARKS = ("schedule search failed", "unreachable/failed",
                  "Traceback (most recent call last)")


class BenchFailure(Exception):
    """The run cannot produce a result (exit code rides along)."""

    def __init__(self, msg: str, code: int = 1) -> None:
        super().__init__(msg)
        self.code = code


def note(msg: str) -> None:
    print(f"[bench +{time.time() - T0:6.1f}s] {msg}", file=sys.stderr,
          flush=True)


# -- processes ----------------------------------------------------------------


class Procs:
    """Every child this run starts, each in a session of its own, all
    stopped and waited for at exit."""

    def __init__(self, log_dir: str, env: dict, cwd: str) -> None:
        self.log_dir, self.env, self.cwd = log_dir, env, cwd
        self.live: list = []

    def spawn(self, argv, log_name: str) -> subprocess.Popen:
        with open(os.path.join(self.log_dir, log_name), "ab") as lf:
            proc = subprocess.Popen(
                argv, cwd=self.cwd, env=self.env, stdout=lf, stderr=lf,
                start_new_session=True,
                preexec_fn=lambda: signal.signal(signal.SIGINT,
                                                 signal.SIG_DFL))
        self.live.append(proc)
        return proc

    def run(self, argv, log_name: str, timeout: float) -> None:
        proc = self.spawn(argv, log_name)
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.stop(proc, grace=0)
            raise BenchFailure(f"{argv[1:4]} timed out after {timeout}s")
        self.live.remove(proc)
        if rc != 0:
            raise BenchFailure(
                f"{' '.join(argv[1:5])} exited {rc}\n"
                + tail(os.path.join(self.log_dir, log_name)))

    def stop(self, proc: subprocess.Popen, grace: float = 60.0) -> int:
        """SIGINT (the program's clean stop), then the group kill."""
        if proc.poll() is None and grace > 0:
            proc.send_signal(signal.SIGINT)
            try:
                proc.wait(timeout=grace)
            except subprocess.TimeoutExpired:
                note(f"pid {proc.pid} ignored SIGINT for {grace}s")
        rc = proc.poll()
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            pass
        if proc in self.live:
            self.live.remove(proc)
        return rc if rc is not None else -9

    def stop_all(self) -> None:
        """The failure path: two SIGINTs (a campaign supervisor kills
        its in-flight run's group on the second), then the group kill."""
        for proc in list(self.live):
            for _ in range(2):
                if proc.poll() is None:
                    proc.send_signal(signal.SIGINT)
                    time.sleep(0.3)
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                pass
            self.stop(proc, grace=0)


def tail(path: str, lines: int = 30) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-lines:])
    except OSError as e:
        return f"<no log: {e}>"


# -- the cell's search config -------------------------------------------------


def _toml(doc: dict) -> str:
    def val(v):
        if isinstance(v, bool):
            return "true" if v else "false"
        if isinstance(v, (int, float)):
            return repr(v)
        return json.dumps(str(v))

    out = [f"{k} = {val(v)}" for k, v in doc.items()
           if not isinstance(v, dict)]
    for name, table in doc.items():
        if isinstance(table, dict):
            out.append(f"\n[{name}]")
            out += [f"{k} = {val(v)}" for k, v in table.items()]
    return "\n".join(out) + "\n"


def search_config(root: str, config: dict, sidecar_addr: str,
                  seed: int) -> dict:
    """The example's search config at the SHIPPED width: the toy-width
    keys dropped, ``sidecar``/``checkpoint``/``search_every`` set, the
    policy's seed from ``--seed`` (``chip_smoke.py::search_config``)."""
    import tomllib

    testee = config["testee"]
    with open(os.path.join(root, testee["example"],
                           testee["search_config"]), "rb") as f:
        doc = tomllib.load(f)
    param = doc["explore_policy_param"]
    for key in config["search"]["drop"]:
        param.pop(key, None)
    param.update(config["search"]["set"])
    param["sidecar"] = sidecar_addr
    param["seed"] = seed % (2 ** 31 - 2)
    return doc


def policy_request(root: str, doc: dict, storage_dir: str) -> dict:
    """The end-of-run ``search`` request as the policy builds it
    (``TPUSearchPolicy._sidecar_search``), for one storage: the policy
    object made from the cell's config states the parameters."""
    if root not in sys.path:
        sys.path.insert(0, root)
    from namazu_tpu.policy import create_policy
    from namazu_tpu.utils.config import Config

    cfg = Config.from_string(_toml(doc), "toml")
    policy = create_policy(cfg.get("explore_policy"))
    policy.load_config(cfg)

    class _Dir:
        dir = os.path.abspath(storage_dir)

    policy._storage = _Dir()
    return {
        "op": "search",
        "key": os.path.abspath(storage_dir),
        "storage": os.path.abspath(storage_dir),
        "search_params": policy._search_params(),
        "ingest_params": policy._ingest_params()._asdict(),
        "generations": policy.generations,
        "checkpoint": os.path.abspath(policy._checkpoint()),
    }


# -- the sidecar --------------------------------------------------------------


class Sidecar:
    def __init__(self, procs: Procs, chips: int, trace: int, cpu: int,
                 work: str) -> None:
        self.procs, self.work = procs, work
        self.addr = f"127.0.0.1:{wire.free_port()}"
        argv = [sys.executable, os.path.join(HERE, "sidecar_main.py"),
                "--listen", self.addr, "--trace", str(trace),
                "--chips", str(chips),
                "--trace-dir", os.path.join(work, "trace")]
        if cpu:
            argv += ["--cpu", str(cpu)]
        self.log = os.path.join(work, "sidecar.log")
        self.proc = procs.spawn(argv, "sidecar.log")

    def call(self, doc: dict, timeout: float = REQUEST_TIMEOUT_S) -> dict:
        resp = wire.request(self.addr, doc, timeout=timeout)
        if not resp.get("ok"):
            raise BenchFailure(f"sidecar {doc.get('op')}: {resp}")
        return resp

    def wait_ready(self) -> dict:
        t0 = time.time()
        while True:
            rc = self.proc.poll()
            if rc is not None:
                raise BenchFailure(
                    f"the sidecar exited {rc} at start\n{tail(self.log)}",
                    code=3 if rc == 3 else 1)
            try:
                return self.call({"op": "bench_info"}, timeout=10)
            except OSError:
                pass
            if time.time() - t0 > SIDECAR_START_S:
                raise BenchFailure("the sidecar did not answer in "
                                   f"{SIDECAR_START_S}s\n{tail(self.log)}")
            time.sleep(0.1)

    def stop(self) -> int:
        return self.procs.stop(self.proc, grace=90.0)


# -- a relay on the campaign's wire (live cells) ------------------------------


class Relay:
    """A frame-by-frame forwarder between the ``run`` children and the
    sidecar, for the client-side clock around each end-of-run request
    (``live_install_p50_s``) — no byte is changed."""

    def __init__(self, upstream: str) -> None:
        self.upstream = upstream
        self.records: list = []
        self._srv = socket.socket()
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind(("127.0.0.1", 0))
        self._srv.listen(16)
        self.addr = f"127.0.0.1:{self._srv.getsockname()[1]}"
        self._stop = False
        self._thread = threading.Thread(target=self._accept, daemon=True)
        self._thread.start()

    def _accept(self) -> None:
        while not self._stop:
            try:
                conn, _ = self._srv.accept()
            except OSError:
                return
            threading.Thread(target=self._serve, args=(conn, time.time()),
                             daemon=True).start()

    def _serve(self, conn: socket.socket, t_accept: float) -> None:
        try:
            with conn, wire.connect(self.upstream,
                                    REQUEST_TIMEOUT_S) as up:
                t_in = t_accept
                while True:
                    req = wire.read_frame(conn)
                    if req is None:
                        return
                    wire.write_frame(up, req)
                    resp = wire.read_frame(up)
                    if resp is None:
                        return
                    wire.write_frame(conn, resp)
                    t_out = time.time()
                    if req.get("op") == "search":
                        self.records.append({"t_start": t_in,
                                             "t_end": t_out})
                    t_in = time.time()
        except (OSError, ValueError):
            return

    def close(self) -> None:
        self._stop = True
        self._srv.close()
        self._thread.join(timeout=5)


# -- the testee's fixed ports -------------------------------------------------


class PortGuard:
    """Holds the testee's fixed ports bound while the sidecar starts, so
    that no long-lived connection of the chip's runtime is handed one of
    them as its ephemeral source port (seen once on the chip host: node
    1's listen port "already in use" for a whole run). Released before
    the first run of the campaign."""

    def __init__(self, ports) -> None:
        self.socks = []
        for port in ports:
            s = socket.socket()
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            try:
                s.bind(("127.0.0.1", int(port)))
            except OSError as e:
                s.close()
                self.release()
                raise BenchFailure(f"the testee's port {port} is taken "
                                   f"before the run starts: {e}")
            self.socks.append(s)

    def release(self) -> None:
        for s in self.socks:
            s.close()
        self.socks = []


# -- traffic: the one general generator ---------------------------------------


def init_storage(procs: Procs, root: str, config: dict,
                 storage: str) -> None:
    testee = config["testee"]
    example = os.path.join(root, testee["example"])
    procs.run([sys.executable, "-m", "namazu_tpu.cli", "init",
               os.path.join(example, testee["record_config"]),
               os.path.join(example, testee["materials"]), storage],
              "init.log", timeout=120)


def write_config(storage: str, doc: dict) -> None:
    with open(os.path.join(storage, "config.toml"), "w") as f:
        f.write(_toml(doc))


class Window:
    """What one measured window yields, whatever the mix."""

    def __init__(self) -> None:
        self.t_open = 0.0
        self.completions: list = []   # completion instants in the window
        self.clients: list = []       # per client: all its completions
        self.install_s: list = []     # client-clock request seconds
        self.run_wall_s: list = []    # live: run child wall seconds
        self.attempted = 0
        self.failed = 0
        self.failures: list = []      # why a cycle failed
        self.facts: dict = {}
        self.requests: dict = {}      # key -> the search request sent
        self.states: dict = {}        # key -> its reference.SearchState
        # key -> (first, count): which of the sidecar's replies for that
        # key, in the order it gave them, the clients got in the window
        self.replies: dict = {}

    def state_requests(self, requests: list, width: dict) -> None:
        """The requests this run will send, and the plain reference's
        state for each, built BEFORE anything warms: a search it does
        not cover is refused here (``reference.Refused``), not after a
        whole window."""
        self.requests = {r["key"]: r for r in requests}
        self.states = {
            key: reference.SearchState(
                r["search_params"], r["ingest_params"],
                width["archive_rows"], width["failure_rows"])
            for key, r in self.requests.items()}


def drive_live(ctx, win: Window) -> None:
    """One campaign, closed loop: ``nmz-tpu campaign`` runs the real
    testee; the next run starts when the previous run's install has
    returned and its result is recorded."""
    mix, procs, sidecar = ctx["mix"], ctx["procs"], ctx["sidecar"]
    guard = ctx["guard"]
    storage = os.path.join(ctx["work"], "campaign0")
    init_storage(procs, ctx["root"], ctx["config"], storage)
    depth0 = mix["prefill_runs"]
    win.facts["history"] = history.fill_storage(
        storage, ctx["templates"], depth0, mix["prefill_failures"],
        ctx["seed"], hold_back=True)
    relay = Relay(sidecar.addr)
    ctx["closers"].append(relay.close)
    doc = search_config(ctx["root"], ctx["config"], relay.addr, ctx["seed"])
    write_config(storage, doc)
    request = policy_request(ctx["root"], doc, storage)
    win.state_requests([request], ctx["config"]["shipped_width"])
    sidecar.wait_ready()
    # the first request builds the search and compiles (or loads) its
    # programs; sent from here so that no run child waits on a compile
    # a second one, after the two held-back runs appear, meets a new
    # failure signature and a moved reference envelope: the row updates
    # of the device mirrors, which a window would otherwise compile at
    # its first reproduction
    sidecar.call(request)
    history.reveal_held_back(storage)
    first = sidecar.call(request)
    direct = 2  # requests sent from here, not through the relay
    note(f"warm requests: gen {first.get('generations_run')}")
    guard.release()

    warm = mix["warmup_searched_runs"]
    camp = procs.spawn(
        [sys.executable, "-m", "namazu_tpu.cli", "campaign", storage,
         "-n", "100000", "--no-resume", "--json", "--retries", "0",
         "--wall-deadline", "120"], "campaign.log")

    def result_mtime(i: int):
        try:
            return os.stat(os.path.join(
                storage, f"{i:08x}", "result.json")).st_mtime
        except OSError:
            return None

    def wait_for(i: int, deadline: float) -> float:
        while True:
            t = result_mtime(i)
            if t is not None:
                return t
            if camp.poll() is not None:
                raise BenchFailure(
                    f"the campaign exited {camp.returncode} before run "
                    f"{i}\n{tail(os.path.join(ctx['work'], 'campaign.log'))}")
            if time.time() > deadline:
                raise BenchFailure(f"run {i} did not finish in time")
            time.sleep(0.01)

    first_in_window = depth0 + warm
    win.t_open = wait_for(first_in_window - 1, time.time() + 600)
    ctx["on_open"](win.t_open)
    t_close = win.t_open + ctx["seconds"]
    while time.time() < t_close and camp.poll() is None:
        ctx["tick"]()
        time.sleep(0.02)
    ctx["on_close"]()
    # graceful: the supervisor lets the run in flight end, then exits
    procs.stop(camp, grace=120.0)

    i = first_in_window
    while True:
        t = result_mtime(i)
        if t is None or t > t_close:
            break
        win.completions.append(t)
        win.attempted += 1
        why = check_live_run(storage, i)
        if why:
            win.failed += 1
            win.failures.append(f"run {i}: {why}")
        i += 1
    n_done = i - first_in_window
    win.clients = [[win.t_open] + win.completions]
    try:
        with open(os.path.join(storage, "campaign.json")) as f:
            slots = json.load(f)["slots"]
    except (OSError, ValueError, KeyError):
        slots = []
    for slot in slots[warm:warm + n_done]:
        if slot.get("class") != "experiment":
            win.failed += 1
            win.failures.append(f"slot {slot.get('slot')}: "
                                f"{slot.get('class')}")
        win.run_wall_s.append(float(slot["attempts"][-1]["wall_s"]))
    in_window = [r for r in relay.records
                 if win.t_open < r["t_end"] <= t_close]
    win.install_s = [r["t_end"] - r["t_start"] for r in in_window]
    win.replies[request["key"]] = (
        direct + sum(r["t_end"] <= win.t_open for r in relay.records),
        len(in_window))
    repro = 0
    for k in range(first_in_window, i):
        with open(os.path.join(storage, f"{k:08x}", "result.json")) as f:
            repro += 0 if json.load(f)["successful"] else 1
    win.facts.update(depth_at_open=first_in_window, depth_at_close=i,
                     reproductions=repro)


def check_live_run(storage: str, index: int) -> str:
    """One searched run's own log: exactly one table installed from the
    sidecar, nothing fell back to hash delays ("" = sound)."""
    path = os.path.join(storage, f"{index:08x}", "nmz.log")
    try:
        with open(path, errors="replace") as f:
            text = f.read()
    except OSError as e:
        return f"no log ({e})"
    for mark in _FAILURE_MARKS:
        if mark in text:
            return f"log carries {mark!r}"
    found = _INSTALL_RE.findall(text)
    if len(found) != 1:
        return f"{len(found)} sidecar installs in its log"
    return ""


def drive_fleet(ctx, win: Window) -> None:
    """N campaign storages at a held depth, N closed-loop clients with
    ``think_s`` between reply and next request, each sending the
    policy's own search request for its storage."""
    mix, procs, sidecar = ctx["mix"], ctx["procs"], ctx["sidecar"]
    n = mix["campaigns"]
    base = os.path.join(ctx["work"], "campaign0")
    init_storage(procs, ctx["root"], ctx["config"], base)
    doc = search_config(ctx["root"], ctx["config"], sidecar.addr,
                        ctx["seed"])
    storages = [base] + [os.path.join(ctx["work"], f"campaign{i}")
                         for i in range(1, n)]
    for s in storages[1:]:
        shutil.copytree(base, s)
    for i, s in enumerate(storages):
        win.facts["history"] = history.fill_storage(
            s, ctx["templates"], mix["history_depth"],
            mix["history_failures"], ctx["seed"] + 7919 * i)
        write_config(s, doc)
    requests = [policy_request(ctx["root"], doc, s) for s in storages]
    win.state_requests(requests, ctx["config"]["shipped_width"])
    sidecar.wait_ready()

    records: list = []
    stop = threading.Event()

    def client(i: int) -> None:
        while not stop.is_set():
            t0 = time.time()
            try:
                resp = wire.request(sidecar.addr, requests[i],
                                    timeout=REQUEST_TIMEOUT_S)
            except (OSError, ValueError) as e:
                resp = {"ok": False, "error": str(e)}
            records.append({"client": i, "t_start": t0,
                            "t_end": time.time(),
                            "ok": bool(resp.get("ok")),
                            "error": resp.get("error")})
            if not resp.get("ok"):
                return
            if mix["think_s"] > 0:
                stop.wait(mix["think_s"])

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(n)]
    for t in threads:
        t.start()
    warm = mix["warmup_requests_per_client"]
    deadline = time.time() + 900
    while True:
        done = [0] * n
        for r in list(records):
            done[r["client"]] += 1
        if min(done) >= warm:
            break
        if not any(t.is_alive() for t in threads) \
                or time.time() > deadline:
            bad = [r for r in records if not r["ok"]]
            raise BenchFailure(f"warm-up did not complete: {bad[:2]}")
        time.sleep(0.01)
    # the window opens on the completion that ended the warm-up
    win.t_open = max(r["t_end"] for r in list(records))
    ctx["on_open"](win.t_open)
    t_close = win.t_open + ctx["seconds"]
    while time.time() < t_close:
        ctx["tick"]()
        time.sleep(0.02)
    ctx["on_close"]()
    stop.set()
    for t in threads:
        t.join(timeout=REQUEST_TIMEOUT_S)
    win.clients = [[r["t_end"] for r in records if r["client"] == i]
                   for i in range(n)]
    for r in records:
        if win.t_open < r["t_end"] <= t_close:
            win.completions.append(r["t_end"])
            win.install_s.append(r["t_end"] - r["t_start"])
            win.attempted += 1
    # a client's k-th reply is the sidecar's k-th for its storage
    for i in range(n):
        ends = [r["t_end"] for r in records if r["client"] == i]
        win.replies[requests[i]["key"]] = (
            sum(t <= win.t_open for t in ends),
            sum(win.t_open < t <= t_close for t in ends))
    win.facts.update(depth_at_open=mix["history_depth"],
                     depth_at_close=mix["history_depth"],
                     requests_per_client=[
                         win.replies[r["key"]][1] for r in requests])


DRIVERS = {"campaign": drive_live, "fleet": drive_fleet}


def replies_by_key(rows: list) -> dict:
    """The sidecar's rows (indices into ``rows``) per key, in the order
    it answered them: ``Window.replies`` counts along these."""
    by_key: dict = {}
    for i in sorted(range(len(rows)), key=lambda i: rows[i]["wall"]):
        by_key.setdefault(rows[i]["key"], []).append(i)
    return by_key


def check_replies(rows: list, win: Window, generations: int) -> None:
    """Every request the sidecar served up to the window's last reply:
    answered, from history, and the search's generation counter — the
    host's and the fused step's own on the device — advanced by exactly
    the stated number per request (a step that returns its state
    unchanged, or a skipped request, breaks the chain). A failure
    counts where the reply is one of the window's."""
    for key, mine in replies_by_key(rows).items():
        first, count = win.replies.get(key, (0, 0))
        prev = None
        for k, r in enumerate(rows[i] for i in mine[:first + count]):
            why = ""
            if not r["ok"]:
                why = f"refused: {r.get('error')}"
            elif r["no_history"]:
                why = "no_history"
            else:
                now = (r["generations_run"], r["fused_gen"])
                if None in now:
                    why = f"no generation counter: {now}"
                elif prev is not None and now != (prev[0] + generations,
                                                  prev[1] + generations):
                    why = (f"generation counters {prev} -> {now}, wanted "
                           f"+{generations}")
                prev = now if None not in now else prev
            if why and k >= first:
                win.failed += 1
                win.failures.append(f"request for {os.path.basename(key)}"
                                    f": {why}")


# -- one run ------------------------------------------------------------------


def run_cell(args) -> int:
    root = os.path.abspath(args.root)
    if not os.path.isdir(os.path.join(root, "namazu_tpu")):
        print("benchmarks/run.py: no namazu_tpu checkout beside "
              "BENCHMARK.json; nothing to measure", file=sys.stderr)
        return 2
    man = manifest_mod.Manifest(root)
    cell = man.cell(args.workload)
    config, mix = man.config(cell), man.traffic(cell)
    work = os.path.join(root, "chiprun_out", "benchmarks", cell["name"])
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)

    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    env["PATH"] = (os.path.dirname(sys.executable) + os.pathsep
                   + env.get("PATH", ""))
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(root, ".jax_cache"))
    if args.cpu:
        env["JAX_PLATFORMS"] = "cpu"
    procs = Procs(work, env, root)
    closers: list = []
    win = Window()
    obs: dict = {}
    try:
        guard = PortGuard(config["testee"].get("ports", [])
                          if mix["kind"] == "campaign" else [])
        closers.append(guard.release)
        sidecar = Sidecar(procs, cell["chips"], args.trace, args.cpu, work)
        tracing = {"on": False, "stop_at": None}

        def on_open(t_open: float) -> None:
            obs["metrics_before"] = sidecar.call(
                {"op": "metrics"})["metrics"]
            if args.trace:
                sidecar.call({"op": "bench_trace_start"})
                tracing["on"] = True
                tracing["stop_at"] = t_open + min(
                    mix["trace_slice_s"], args.seconds)

        def stop_trace() -> None:
            tracing["on"] = False
            sidecar.call({"op": "bench_trace_stop"})

        def tick() -> None:
            if tracing["on"] and time.time() >= tracing["stop_at"]:
                stop_trace()

        def on_close() -> None:
            if tracing["on"]:
                stop_trace()
            obs["metrics_after"] = sidecar.call(
                {"op": "metrics"})["metrics"]

        ctx = {"root": root, "work": work, "config": config, "mix": mix,
               "procs": procs, "sidecar": sidecar, "seed": args.seed,
               "guard": guard,
               "seconds": float(args.seconds), "closers": closers,
               "templates": history.load_templates(
                   os.path.join(root, config["history"])),
               "on_open": on_open, "tick": tick, "on_close": on_close}
        DRIVERS[mix["kind"]](ctx, win)
        setup_s = win.t_open - T0
        note(f"window closed: {len(win.completions)} cycle(s)")

        # outside the timed window: what it produced, against the
        # plain reference
        t_close = win.t_open + args.seconds
        if args.trace:
            probe_dir = os.path.join(os.path.dirname(work), "probes")
            os.makedirs(probe_dir, exist_ok=True)
            obs["trace"] = sidecar.call({
                "op": "bench_trace_reduce", "key": min(win.requests),
                "kernels": sorted({
                    decl["kernel"]
                    for m in man.metrics_of(cell["name"], "per_layer")
                    for decl in [man.layer_metric(m["name"])]
                    if "kernel" in decl}),
                "probe": os.path.join(probe_dir, cell["name"] + ".json"),
            })["reduced"]
        info = sidecar.call({"op": "bench_info"})
        dump = sidecar.call({"op": "bench_state",
                             "out": os.path.join(work, "state.npz")})
        check_replies(dump["requests"], win,
                      config["guarantees"]["generations_per_request"])
        # the plain reference, from the storages (numpy, on the host);
        # the sidecar stays up to answer for the tables it names
        t_compare = time.time()
        agree = compare_answers(
            dump, win, lambda key, rows: sidecar.call(
                {"op": "bench_probe", "key": key, "rows": rows})["fitness"])
        # what every run pays after its window (a run has 360 s in all)
        agree["comparison_s"] = time.time() - t_compare
        obs["compiles"] = [c for c in info["compiles"]
                           if win.t_open < c[0] <= t_close]
        obs["spans"] = {
            name: [r for r in rows if win.t_open < r[0] <= t_close]
            for name, rows in info["spans"].items()}
        device, memory_peak = info["device"], info["memory_peak_bytes"]
        rc = sidecar.stop()
        if rc != 0:
            win.failures.append(f"the sidecar exited {rc} on SIGINT")
    finally:
        for close in closers:
            close()
        procs.stop_all()

    search0 = dump["searches"][min(dump["searches"])]
    shards_wrong = sum(
        s["shard_rows"] != [s["population"] // device["count"]]
        * device["count"] for s in dump["searches"].values())
    checks = {
        "reply_fitness_gap": [agree["reply_fitness_gap"],
                              FITNESS_GAP_LIMIT],
        "fused_fitness_gap": [agree["fused_fitness_gap"],
                              FITNESS_GAP_LIMIT],
        "rerank_fitness_gap": [agree["rerank_fitness_gap"],
                               FITNESS_GAP_LIMIT],
        "archive_rows_gap": [agree["archive_rows_gap"], ROWS_GAP_LIMIT],
        "failure_rows_gap": [agree["failure_rows_gap"], ROWS_GAP_LIMIT],
        "reference_times_gap": [agree["reference_times_gap"], 0.0],
        "failed_cycles": [win.failed, 0],
        "window_compiles": [len(obs["compiles"]), 0],
        "no_cycle_completed": [int(not win.completions), 0],
        "shard_rows_wrong": [shards_wrong, 0],
        "sidecar_unclean_stop": [int(rc != 0), 0],
    }
    for name in ("pairs_differ", "labels_differ", "ring_counts_differ",
                 "reference_buckets_differ", "tables_out_of_range",
                 "answers_missing", "release_mode_differs"):
        checks[name] = [agree[name], 0]
    correct = all(got <= limit for got, limit in checks.values())

    # the metrics of this run
    obs.update(run_log={"run_wall_s": win.run_wall_s},
               client={"install_s": win.install_s},
               device_kind=device["kind"])
    rate = rates.closed_loop_rate_per_hour(win.clients, win.t_open,
                                           args.seconds)
    values = {"searched_runs_per_hour": rate,
              "install_p50_s": rates.p50(win.install_s),
              "setup_s": setup_s}
    metrics = {}
    if args.trace:
        if device["platform"] == "tpu":
            obs["shape"] = {
                "population_per_chip":
                    search0["population"] // device["count"],
                "reference_traces": agree["reference_traces"],
                **config["shipped_width"]}
        for m in man.metrics_of(cell["name"], "per_layer"):
            v = layer_metrics.evaluate(man.layer_metric(m["name"]), obs)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in man.metrics_of(cell["name"], "end_to_end"):
            v = values.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"], "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": win.attempted,
              "failed": win.failed, "metrics": metrics, "device": dev}
    trace = obs.get("trace") or {}
    if args.trace and trace.get("n_devices"):
        dev["busy_s"] = trace["device_busy_s"]
        dev["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}

    n_inst = len(win.install_s)
    facts = {
        "workload": cell["name"], "seed": args.seed,
        "seconds": args.seconds, "trace": args.trace,
        "cycles_completed": len(win.completions),
        "installs_in_window": n_inst,
        "install_p50_s": rates.p50(win.install_s),
        "searched_runs_per_hour": rate,
        "setup_s": setup_s, "failures": win.failures[:10],
        "cycle_ends_s": [round(t - win.t_open, 3)
                         for t in sorted(win.completions)],
        "run_wall_s": win.run_wall_s,
        "agreement": agree, "operand": dump["operand"],
        "searches": list(dump["searches"].values()),
        "compiles_in_window": obs["compiles"],
        "trace_quantities": {k: v for k, v in trace.items()
                             if k not in ("device_ops", "idle_gaps")},
        **win.facts,
    }
    n = len(win.completions)
    if n:
        facts["reproductions_wilson95"] = wilson(
            win.facts.get("reproductions"), n)
    if args.trace:
        facts["rooflines"] = {
            m["name"]: layer_metrics.roofline(decl, obs)
            for m in man.metrics_of(cell["name"], "per_layer")
            for decl in [man.layer_metric(m["name"])]
            if decl["reduce"] == "roofline"}
    with open(os.path.join(work, "facts.json"), "w") as f:
        json.dump(facts, f, indent=1)
    if correct:
        # a sound run keeps its logs and facts; the storages (an 8 MB
        # checkpoint each) would crowd what a chip call may bring back
        for name in os.listdir(work):
            if name.startswith(("campaign", "state.npz", "trace")):
                path = os.path.join(work, name)
                shutil.rmtree(path) if os.path.isdir(path) \
                    else os.remove(path)
    assert "jax" not in sys.modules, "the benchmark's parent imported jax"
    print("facts: " + json.dumps(facts), flush=True)
    # every number compared beside its limit: last in the result's
    # line, and as the last line of stderr
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    print("checks: " + json.dumps(result["checks"]), file=sys.stderr,
          flush=True)
    print(json.dumps(result), flush=True)
    return 0


def compare_answers(dump: dict, win: Window, probe) -> dict:
    """Every answer of the window against the plain reference
    (``benchmarks/reference.py``). Per search, its requests are
    replayed in order against the state worked out from the storage
    files (``win.states``: built from the request before the window,
    the one place the mode is decided). A reply is the window's where
    the CLIENT got it inside (``win.replies``): the clock ``attempted``
    is counted on, so every cycle counted has its reply compared and
    no other reply is. Answers: ``reply`` — each reply's (table, fitness);
    ``fused`` — the fused step's own best (table, fitness) where that
    request improved it and, after the last request, the best fitness
    it finds in the population as it stands and its fitness of the
    ``PROBE_TABLES`` tables of that population on which the operand
    precision tells most (``probe(key, rows)`` asks the sidecar);
    ``rerank`` — the fitness the last reply's re-rank gave every table
    of that population. The device-resident inputs after the last
    request are held against the same state row for row. Beside each
    number, the same with the reference one precision below put in the
    program's place (``control_*``: has to come out above the limit)."""
    import numpy as np

    with np.load(dump["out"]) as z:
        arrays = {k: z[k] for k in z.files}
    stated = dump["operand"]
    lower = reference.LOWER[stated]
    operands = tuple(dict.fromkeys(("float32", stated, lower)))
    kinds = ("reply", "fused", "rerank")
    got = {k: [] for k in kinds}
    ref = {k: {op: [] for op in operands} for k in kinds}
    out = {"tables_out_of_range": 0, "answers_missing": 0,
           "release_mode_differs": 0, "events_on_a_window_edge": 0,
           "reference_traces": 0, "fused_bests_of_the_window": 0}
    gaps: dict = {}
    rows = dump["requests"]
    by_key = replies_by_key(rows)

    def answer(kind, table, fitness, state):
        out["tables_out_of_range"] += int(
            table.min() < 0.0
            or table.max() > state.max_interval * (1 + 1e-6))
        got[kind].append(fitness)
        for op, f in state.score(table, operands).items():
            ref[kind][op].append(float(f[0]))

    for key, search in sorted(dump["searches"].items()):
        if search["fault_coin"]:
            raise BenchFailure("the reference covers fault-free searches "
                               "only: this search holds a fault coin")
        # the mode is the one the REQUEST states; the search is held to it
        req, state = win.requests[key], win.states[key]
        held = [search["release_mode"]] + (
            [search["order_gap"], search["order_window"]]
            if search["release_mode"] == "reorder" else [])
        out["release_mode_differs"] += int(held != state.held_mode())
        first, count = win.replies.get(key, (0, 0))
        of_window = set(by_key.get(key, [])[first:first + count])
        # a reply a client counted that the sidecar has no row for
        out["answers_missing"] += count - len(of_window)
        mine = [i for i in by_key.get(key, [])
                if rows[i]["ok"] and not rows[i]["no_history"]]
        runs = reference.read_runs(
            req["storage"], max([rows[i]["depth"] or 0 for i in mine]
                                or [0]), state.H)
        best = None
        for i in mine:
            r = rows[i]
            state.ingest([run for run in runs if run.index < r["depth"]])
            improved = r["fused_fitness"] != best
            best = r["fused_fitness"]
            if i not in of_window:
                continue
            if r["fitness"] is None or r["fused_fitness"] is None:
                out["answers_missing"] += 1
                continue
            answer("reply", arrays["reply_delays"][i], r["fitness"], state)
            if improved:
                out["fused_bests_of_the_window"] += 1
                answer("fused", arrays["fused_delays"][i],
                       r["fused_fitness"], state)
        # after the last request: the population as it stands
        n = search["n"]
        by_op = state.score(arrays[f"s{n}_population"], operands)
        got["fused"].append(search["probe_fitness"])
        for op in operands:
            ref["fused"][op].append(float(by_op[op].max()))
        if f"s{n}_rerank_fitness" in arrays:
            got["rerank"] += arrays[f"s{n}_rerank_fitness"].tolist()
            for op in operands:
                ref["rerank"][op] += by_op[op].tolist()
        else:
            out["answers_missing"] += 1
        told = np.argsort(-np.abs(by_op[lower] - by_op[stated]))
        told = [int(t) for t in told[:PROBE_TABLES]]
        got["fused"] += probe(key, told)
        for op in operands:
            ref["fused"][op] += by_op[op][told].tolist()
        resident = {k: arrays[f"s{n}_{k}"] for k in (
            "pairs", "archive", "labels", "failures", "hint_ids",
            "arrival", "mask")}
        resident.update(archive_n=search["archive_n"],
                        failure_n=search["failure_n"])
        for name, v in reference.resident_gap(state, resident).items():
            gaps[name] = max(gaps.get(name, 0), v)
        out["reference_traces"] = max(out["reference_traces"],
                                      len(state.traces))
        out["events_on_a_window_edge"] += state.events_on_a_window_edge()
    out.update(gaps)
    for kind in kinds:
        out[f"{kind}_answers"] = len(got[kind])
        out[f"{kind}_fitness_gap"] = reference.fitness_gap(
            got[kind], ref[kind]["float32"], ref[kind][stated])
        out[f"{kind}_gap_vs_float32"] = float(np.abs(
            np.asarray(got[kind]) - ref[kind]["float32"]).max()) \
            if got[kind] else 0.0
        out[f"control_{kind}_fitness_gap"] = reference.fitness_gap(
            ref[kind][lower], ref[kind]["float32"], ref[kind][stated])
    out["answers_missing"] += int(not got["reply"])
    out["control_operand"] = lower
    return out


def wilson(k, n: int, z: float = 1.96):
    if k is None or n <= 0:
        return None
    p = k / n
    d = 1 + z * z / n
    c = p + z * z / (2 * n)
    h = z * ((p * (1 - p) + z * z / (4 * n)) / n) ** 0.5
    return [max(0.0, (c - h) / d), min(1.0, (c + h) / d)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--root", default=os.path.dirname(HERE),
                    help=argparse.SUPPRESS)
    ap.add_argument("--cpu", type=int, default=0, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    try:
        return run_cell(args)
    except BenchFailure as e:
        print(f"benchmarks/run.py: FAILED: {e}", file=sys.stderr)
        return e.code
    except reference.Refused as e:
        print(f"benchmarks/run.py: REFUSED: {e}", file=sys.stderr)
        return 1
    except manifest_mod.ManifestError as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
