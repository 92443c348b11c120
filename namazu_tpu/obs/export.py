"""Exporters for recorded runs: Chrome-trace/Perfetto JSON and NDJSON.

A :class:`~namazu_tpu.obs.recorder.RunTrace` renders three ways:

* :func:`chrome_trace` — the Chrome Trace Event format (the JSON both
  ``chrome://tracing`` and https://ui.perfetto.dev load directly): one
  track (pid/tid pair) per entity, one per policy, and one for the
  search plane's generation rounds + schedule installs. Every event's
  ``args`` carries the full structured record, so the decision that
  caused a delay is one click away in the UI.
* :func:`to_ndjson` — newline-delimited JSON, one record per line with
  run-relative timestamps (µs precision), stable across identical
  scripted runs, so two runs diff with plain ``diff``.
* :func:`span_trees` / :func:`render_span_trees` — the search plane's
  request-scoped span rows (obs/spans.py, read over the framed ``spans``
  op) as per-request trees with self time; :func:`chrome_trace` takes
  the same rows as a second input and draws them on a fourth block.
* :func:`order_lines` / :func:`diff_runs` — the realized dispatch
  ORDER only (entity + event class + hint), the thing Namazu exists to
  control; :func:`diff_runs` renders two runs' orders as a unified
  diff.

All exporters work off ``RunTrace.snapshot()`` — one lock acquisition,
then pure rendering — so they are safe against writers mid-run.
"""

from __future__ import annotations

import difflib
import json
from typing import Any, Dict, List, Optional

# Chrome-trace process ids: one synthetic "process" per plane so the
# viewer groups entity tracks, policy tracks, and the search plane's
# generation track into three collapsible blocks.
PID_ENTITIES = 1
PID_POLICIES = 2
PID_SEARCH = 3
PID_REQUESTS = 4

_PROCESS_NAMES = {
    PID_ENTITIES: "entities",
    PID_POLICIES: "policies",
    PID_SEARCH: "search plane",
    PID_REQUESTS: "search requests",
}


def _us(snapshot: Dict[str, Any], mono: float) -> int:
    """Monotonic stamp -> integer µs offset from the run's start."""
    return max(0, int(round((mono - snapshot["started_mono"]) * 1e6)))


class _Tracks:
    """Stable (pid, name) -> integer tid assignment + metadata events."""

    def __init__(self) -> None:
        self._tids: Dict[tuple, int] = {}
        self._per_pid: Dict[int, int] = {}
        self.meta: List[Dict[str, Any]] = []

    def tid(self, pid: int, name: str) -> int:
        key = (pid, name)
        tid = self._tids.get(key)
        if tid is None:
            tid = self._per_pid.get(pid, 0) + 1
            self._per_pid[pid] = tid
            self._tids[key] = tid
            self.meta.append({
                "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
                "args": {"name": name},
            })
        return tid


def chrome_trace(run, spans: Optional[List] = None) -> Dict[str, Any]:
    """Render a recorded run as a Chrome Trace Event JSON document.
    ``spans`` — request-scoped span rows (obs/spans.py) — draw on a
    block of their own, one track per serving thread; ``run`` may be
    None when the rows are all there is (``nmz-tpu tools spans
    --chrome``), the clock then starts at the earliest row."""
    spans = spans or []
    if run is not None:
        snap = run.snapshot()
    else:
        snap = {"run_id": "", "records": [], "generations": [],
                "dropped_records": 0,
                "started_mono": min([r[4] for r in spans] or [0.0]),
                "started_wall": min([r[3] for r in spans] or [0.0])}
    tracks = _Tracks()
    events: List[Dict[str, Any]] = []

    for rid, name, parent, _wall, mono, seconds, thread, attrs in spans:
        # async pairs keyed by the request: a ``queue`` span was waited
        # out while its worker still served the request before, so
        # complete 'X' slices on the thread's track would not nest
        pair = {"name": name, "cat": "span", "id": str(rid or "none"),
                "pid": PID_REQUESTS,
                "tid": tracks.tid(PID_REQUESTS, str(thread))}
        events.append(dict(pair, ph="b", ts=_us(snap, mono), args=dict(
            attrs or {}, rid=rid, parent=parent, seconds=seconds)))
        events.append(dict(pair, ph="e", ts=_us(snap, mono + seconds)))

    for entry in snap["records"]:
        rec, doc = entry["rec"], entry["json"]
        t = rec.t
        first = rec.first_stamp()
        if first is None:
            continue
        last = max(t.values())
        # entity track: the event's whole life, interception -> last
        # stamp. Async begin/end pairs ('b'/'e', keyed by the event
        # uuid), NOT complete 'X' slices: several events are in flight
        # per entity at once — the very concurrency this recorder exists
        # to show — and 'X' slices on one tid must be strictly nested,
        # so partially-overlapping spans would mis-render in the viewer.
        entity = rec.entity or "_unknown"
        name = rec.event_class or "event"
        if rec.hint:
            name = f"{name}:{rec.hint}"
        name = name[:120]
        tid = tracks.tid(PID_ENTITIES, entity)
        events.append({
            "name": name, "cat": "event", "ph": "b", "id": rec.event_id,
            "pid": PID_ENTITIES, "tid": tid,
            "ts": _us(snap, first), "args": doc,
        })
        events.append({
            "name": name, "cat": "event", "ph": "e", "id": rec.event_id,
            "pid": PID_ENTITIES, "tid": tid,
            "ts": max(_us(snap, last), _us(snap, first)),
        })
        # policy track: decision -> release/dispatch, i.e. the injected
        # schedule itself (the span Namazu is in the business of
        # shaping). Also async pairs: a policy holds many delayed events
        # concurrently, so these spans overlap by construction. The
        # 'decision' cat keeps the pair distinct from the entity pair
        # sharing the same id (async matching is by cat + id + name).
        if rec.policy and "decided" in t:
            end = t.get("released", t.get("dispatched", t["decided"]))
            pname = (rec.hint or name)[:120]
            ptid = tracks.tid(PID_POLICIES, rec.policy)
            events.append({
                "name": pname, "cat": "decision", "ph": "b",
                "id": rec.event_id,
                "pid": PID_POLICIES, "tid": ptid,
                "ts": _us(snap, t["decided"]),
                "args": {"event": rec.event_id, "entity": rec.entity,
                         "decision": dict(rec.decision)},
            })
            events.append({
                "name": pname, "cat": "decision", "ph": "e",
                "id": rec.event_id,
                "pid": PID_POLICIES, "tid": ptid,
                "ts": max(_us(snap, end), _us(snap, t["decided"])),
            })

    for g in snap["generations"]:
        if g.get("kind") == "generation":
            tid = tracks.tid(PID_SEARCH, f"generations:{g['backend']}")
            events.append({
                "name": f"gen {g['gen_start']}..{g['gen_end']}",
                "cat": "search",
                "ph": "X",
                "pid": PID_SEARCH,
                "tid": tid,
                "ts": _us(snap, g["t_start"]),
                "dur": max(0, _us(snap, g["t_end"]) - _us(snap, g["t_start"])),
                "args": {"backend": g["backend"],
                         "best_fitness": g.get("best_fitness")},
            })
        elif g.get("kind") == "install":
            tid = tracks.tid(PID_SEARCH, "installs")
            events.append({
                "name": f"install:{g['source']}",
                "cat": "search",
                "ph": "i",
                "s": "p",
                "pid": PID_SEARCH,
                "tid": tid,
                "ts": _us(snap, g["t"]),
                "args": {"source": g["source"],
                         "generation": g.get("generation")},
            })

    events.sort(key=lambda e: (e["ts"], e["pid"], e["tid"]))
    meta = [{
        "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
        "args": {"name": pname},
    } for pid, pname in sorted(_PROCESS_NAMES.items())
        if spans or pid != PID_REQUESTS]
    return {
        "traceEvents": meta + tracks.meta + events,
        "displayTimeUnit": "ms",
        "metadata": {
            "run_id": snap["run_id"],
            "started_unix": round(snap["started_wall"], 6),
            "records": len(snap["records"]),
            "dropped_records": snap["dropped_records"],
        },
    }


def span_trees(rows: List) -> List[Dict[str, Any]]:
    """Span rows ``(rid, name, parent, t_wall, t_mono, seconds, thread,
    attrs)`` as one tree per request, oldest request first. A node:
    ``{"name", "t_mono", "seconds", "self_s", "attrs", "children"}``;
    ``self_s`` is the span minus the part of it its children cover
    (a child accumulated over a loop, ``pieces=<n>``, by its length). A
    row's parent is the span of that name, under the same request,
    that was open when the row started."""
    by_rid: Dict[Any, List[Dict[str, Any]]] = {}
    for rid, name, parent, _wall, mono, seconds, _thread, attrs in rows:
        by_rid.setdefault(rid, []).append({
            "name": name, "parent": parent, "t_mono": mono,
            "seconds": seconds, "attrs": attrs or {}, "children": []})
    trees = []
    for rid, nodes in by_rid.items():
        nodes.sort(key=lambda n: (n["t_mono"], -n["seconds"]))
        roots = []
        for node in nodes:
            home = [p for p in nodes if p["name"] == node["parent"]
                    and p is not node]
            inside = [p for p in home if p["t_mono"] <= node["t_mono"]
                      <= p["t_mono"] + p["seconds"]]
            owner = (inside or home or [None])[-1]
            (roots if owner is None else owner["children"]).append(node)
        for node in nodes:
            covered, upto = 0.0, node["t_mono"]
            end = node["t_mono"] + node["seconds"]
            for child in node["children"]:
                if "pieces" in child["attrs"]:
                    # accumulated over a loop: no interval of its own
                    covered += child["seconds"]
                    continue
                a = max(child["t_mono"], upto)
                b = min(child["t_mono"] + child["seconds"], end)
                if b > a:
                    covered += b - a
                    upto = b
            node["self_s"] = max(0.0, node["seconds"] - covered)
            del node["parent"]
        trees.append({"rid": rid, "t_mono": nodes[0]["t_mono"],
                      "seconds": max(n["t_mono"] + n["seconds"]
                                     for n in nodes) - nodes[0]["t_mono"],
                      "spans": roots})
    trees.sort(key=lambda t: t["t_mono"])
    return trees


def render_span_trees(rows: List) -> str:
    """:func:`span_trees` as indented text, one block per request."""
    out: List[str] = []

    def walk(node, depth):
        attrs = "".join(f"  {k}={v}" for k, v in sorted(
            node["attrs"].items()))
        out.append(f"{'  ' * depth}{node['name']:<{24 - 2 * depth}}"
                   f"{node['seconds']:10.6f} s  self "
                   f"{node['self_s']:10.6f} s{attrs}")
        for child in node["children"]:
            walk(child, depth + 1)

    for tree in span_trees(rows):
        out.append(f"request {tree['rid'] or '(none)'}  "
                   f"{tree['seconds']:.6f} s")
        for node in tree["spans"]:
            walk(node, 1)
    return "\n".join(out) + ("\n" if out else "")


def to_ndjson(run) -> str:
    """One JSON line per event record (interception order), then one per
    search-plane entry — run-relative µs-precision times throughout, so
    identical scripted runs serialize identically."""
    snap = run.snapshot()
    anchor = snap["started_mono"]
    lines = []
    for entry in snap["records"]:
        doc = dict(entry["json"])
        doc["run_id"] = snap["run_id"]
        lines.append(json.dumps(doc, sort_keys=True))
    for g in snap["generations"]:
        doc = dict(g)
        for key in ("t", "t_start", "t_end"):
            if key in doc:
                doc[key] = round(doc[key] - anchor, 6)
        doc["run_id"] = snap["run_id"]
        lines.append(json.dumps(doc, sort_keys=True))
    return "\n".join(lines) + ("\n" if lines else "")


def order_lines_from_docs(docs) -> List[str]:
    """Realized dispatch order from record dicts in the NDJSON shape —
    the ONE implementation of the order identity (entity + class:hint,
    sorted by dispatch stamp; uuids and timings deliberately absent):
    both the in-process path below and the CLI's over-the-wire
    ``trace diff`` route through it, so local and remote diffs can
    never disagree on what "same interleaving" means."""
    rows = []
    for doc in docs:
        t = doc.get("t") or {}
        if doc.get("kind") or "dispatched" not in t:
            continue  # search-plane entries / never-dispatched events
        name = doc.get("event_class") or "event"
        if doc.get("hint"):
            name = f"{name}:{doc['hint']}"
        rows.append((t["dispatched"], f"{doc.get('entity', '')} {name}"))
    rows.sort(key=lambda r: r[0])
    return [line for _, line in rows]


def order_lines(run) -> List[str]:
    """The realized dispatch order of a recorded run — the schedule's
    IDENTITY, the thing a reproduced interleaving must match."""
    snap = run.snapshot()
    return order_lines_from_docs([entry["json"]
                                  for entry in snap["records"]])


def diff_order(a: List[str], b: List[str],
               label_a: str, label_b: str) -> str:
    """Unified diff of two dispatch orders ("" = same interleaving)."""
    return "\n".join(difflib.unified_diff(
        a, b, fromfile=f"run/{label_a}", tofile=f"run/{label_b}",
        lineterm=""))


def diff_runs(run_a, run_b) -> str:
    """Unified diff of two recorded runs' realized dispatch orders."""
    return diff_order(order_lines(run_a), order_lines(run_b),
                      run_a.run_id, run_b.run_id)
