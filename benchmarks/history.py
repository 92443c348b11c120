"""History synthesiser: fills a campaign storage with stored runs of a
configuration's own trace shape, from ``--seed``.

A configuration commits a handful of runs recorded once from its real
testee (``<config>.history.json``: hints, entities, arrival offsets and
injected delays of every action, and whether the run reproduced the
bug). A stored run is one of those templates re-stamped: arrival
offsets and delays jittered from the seed, uuids fresh, wall time
today's. The number of failures is FIXED per mix (not drawn), so every
seed gives the sidecar the same amount of work — only which runs fail,
which template each run follows and the jitter change.

The storage is written in the layout the program's ``NaiveStorage``
reads (``storage.json``, ``%08x/trace.json``, ``%08x/result.json``); the
directory itself is created by the program's own ``init``.

``python benchmarks/history.py extract <storage> <out.json>`` is how the
committed templates were made from a recorded storage.
"""

from __future__ import annotations

import json
import os
import sys
import uuid

import numpy as np

#: multiplicative jitter on every arrival offset and injected delay, and
#: an additive one (seconds) on arrivals: recorded runs of one testee
#: differ by about this much between themselves
JITTER_REL = 0.10
JITTER_ABS_S = 0.004


def extract_templates(storage_dir: str, successes: int = 6,
                      failures: int = 3) -> dict:
    """Templates from a recorded storage: the first ``successes``
    successful and ``failures`` failed runs."""
    with open(os.path.join(storage_dir, "storage.json")) as f:
        n = int(json.load(f)["next_run"])
    out = {"successes": [], "failures": []}
    hint_space = None
    for i in range(n):
        run = os.path.join(storage_dir, f"{i:08x}")
        try:
            with open(os.path.join(run, "result.json")) as f:
                result = json.load(f)
            with open(os.path.join(run, "trace.json")) as f:
                trace = json.load(f)
        except OSError:
            continue
        actions = trace["actions"] if isinstance(trace, dict) else trace
        if not actions:
            continue
        hint_space = result.get("metadata", {}).get("hint_space",
                                                    hint_space)
        t0 = min(a["event_arrived"] for a in actions)
        tmpl = {
            "required_time": result["required_time"],
            "actions": [{
                "class": a["class"], "entity": a["entity"],
                "option": a.get("option", {}),
                "event_class": a["event_class"],
                "event_hint": a["event_hint"],
                "arrival_offset": a["event_arrived"] - t0,
                "delay": a["triggered_time"] - a["event_arrived"],
            } for a in actions],
        }
        key = "successes" if result["successful"] else "failures"
        want = successes if result["successful"] else failures
        if len(out[key]) < want:
            out[key].append(tmpl)
    out["hint_space"] = hint_space
    return out


def load_templates(path: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    if not doc.get("successes") or not doc.get("failures"):
        raise ValueError(f"{path}: needs at least one successful and one "
                         "failed template run")
    return doc


#: the last stored run arrives this much earlier than its template, so
#: that it moves the reference envelope (the per-bucket minimum arrival)
EARLY = 0.85


def plan_outcomes(rng: np.random.RandomState, depth: int,
                  n_failures: int) -> list:
    """Which of ``depth`` stored runs failed: exactly ``n_failures`` of
    them. The last two runs are always a failure, then a success; the
    other failures' positions come from the seed."""
    if not 1 <= n_failures <= depth - 2:
        raise ValueError(f"failures {n_failures} must be in "
                         f"[1, {depth - 2}]")
    failed = set(rng.choice(depth - 2, size=n_failures - 1,
                            replace=False).tolist())
    failed.add(depth - 2)
    return [i not in failed for i in range(depth)]


def _uuid(rng: np.random.RandomState) -> str:
    return str(uuid.UUID(int=int(rng.randint(0, 2**31 - 1)) << 64
                         | int(rng.randint(0, 2**31 - 1))))


def restamp(rng: np.random.RandomState, tmpl: dict, t_base: float,
            early: float = 1.0) -> list:
    """One stored trace from a template: the recorded structure with
    jittered times, as the program's trace.json holds it."""
    actions = []
    for a in tmpl["actions"]:
        rel = early * (1.0 + rng.uniform(-JITTER_REL, JITTER_REL))
        arrived = (t_base + a["arrival_offset"] * rel
                   + early * rng.uniform(0.0, JITTER_ABS_S))
        delay = max(0.0, a["delay"]
                    * (1.0 + rng.uniform(-JITTER_REL, JITTER_REL)))
        actions.append({
            "type": "action", "class": a["class"], "entity": a["entity"],
            "uuid": _uuid(rng),
            "option": a["option"],
            "event_uuid": _uuid(rng),
            "event_class": a["event_class"],
            "event_hint": a["event_hint"],
            "event_arrived": arrived,
            "triggered_time": arrived + delay,
        })
    return actions


def fill_storage(storage_dir: str, templates: dict, depth: int,
                 n_failures: int, seed: int, hold_back: bool = False) -> dict:
    """Append ``depth`` synthesised runs to an initialised, empty
    storage. With ``hold_back`` the last two (a failure, then an early
    success) are written but not yet counted: ``reveal_held_back`` makes
    them appear, as two more runs of the campaign would. Returns what
    was written (for the facts line)."""
    rng = np.random.RandomState(seed % (2**32))
    meta_path = os.path.join(storage_dir, "storage.json")
    with open(meta_path) as f:
        meta = json.load(f)
    if meta.get("type") != "naive" or int(meta.get("next_run", 0)) != 0:
        raise ValueError(f"{storage_dir}: not an empty naive storage")
    outcomes = plan_outcomes(rng, depth, n_failures)
    t_base = 1.7e9 + float(rng.uniform(0, 1e6))
    # every seed gets the same multiset of templates (each used equally
    # often), in another order: the seed must not change the work
    order = {}
    for ok in (True, False):
        pool = templates["successes" if ok else "failures"]
        picks = [pool[j % len(pool)] for j in range(outcomes.count(ok))]
        order[ok] = [picks[j] for j in rng.permutation(len(picks))]
    for i, ok in enumerate(outcomes):
        tmpl = order[ok].pop()
        run = os.path.join(storage_dir, f"{i:08x}")
        os.makedirs(run)
        with open(os.path.join(run, "trace.json"), "w") as f:
            json.dump(restamp(rng, tmpl, t_base + 10.0 * i,
                              early=EARLY if i == depth - 1 else 1.0), f)
        with open(os.path.join(run, "result.json"), "w") as f:
            json.dump({"successful": ok,
                       "required_time": tmpl["required_time"],
                       "metadata": {"hint_space":
                                    templates["hint_space"]}}, f)
    meta["next_run"] = depth - 2 if hold_back else depth
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    return {"depth": depth, "failures": n_failures,
            "events_per_run": [len(t["actions"])
                               for t in templates["successes"]]}


def reveal_held_back(storage_dir: str) -> None:
    meta_path = os.path.join(storage_dir, "storage.json")
    with open(meta_path) as f:
        meta = json.load(f)
    meta["next_run"] = int(meta["next_run"]) + 2
    with open(meta_path, "w") as f:
        json.dump(meta, f)


if __name__ == "__main__":
    if len(sys.argv) != 4 or sys.argv[1] != "extract":
        sys.exit("usage: history.py extract <storage-dir> <out.json>")
    with open(sys.argv[3], "w") as out_f:
        json.dump(extract_templates(sys.argv[2]), out_f, indent=0)
