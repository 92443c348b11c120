"""From a profiler trace to the quantities the per-layer metrics read.

``xplane_to_events`` (needs jax, so it runs in the process that traced)
turns the profiler's ``.xplane.pb`` into plain dicts; ``reduce`` is pure
Python over those dicts, so the tests run it on a small recorded trace.

One event: ``{"plane", "line", "name", "start_ns", "dur_ns"}`` and, for
device ops, ``module`` (the program that was running) and ``scope`` (the
op's ``jax.named_scope`` path, from the compiled module's text). Times
on all planes share the profiler's clock.

Quantities (seconds are per device: summed over the device planes and
divided by their number):

* ``window_s`` — between the launcher's ``bench:slice_start`` and
  ``bench:slice_stop`` markers (without them: first to last instant)
* ``device_busy_s`` — union of the intervals in which an op ran
* ``scope_s.<scope>`` — op time under ``nmz_score`` / ``nmz_mutate`` /
  ``nmz_migrate`` / ``nmz_select`` (``parallel/islands.py``)
* ``kernel_s.<kernel>`` / ``kernel_calls.<kernel>`` — op time and count
  of the named kernels (``KERNELS``)
* ``collective_s`` / ``collective_exposed_s`` — time in collective ops,
  and the part during which no other op ran on that device
* ``evolve_union_s`` — union of the program's ``nmz:evolve`` host spans
* ``requests`` — ``bench:handle`` spans that ended inside the trace
* ``device_ops`` / ``idle_gaps`` — the ten largest, for ``breakdown``
"""

from __future__ import annotations

import bisect
import json
import re

KERNELS = ("min_sq_distance_pair_pallas",)
SCOPES = ("nmz_score", "nmz_mutate", "nmz_migrate", "nmz_select")
#: ops that only contain other ops (the generation loop is one ``while``
#: spanning every op of its body): counted by their self time
CONTAINER_RE = re.compile(r"^(while|conditional|call)[.\d]*$")
COLLECTIVE_RE = re.compile(
    r"collective-permute|all-gather|all-reduce|all-to-all|reduce-scatter")
#: gaps shorter than this are the device's own turn-around between ops
SHORT_GAP_NS = 100_000

#: host spans in the order an idle gap is attributed: innermost first
GAP_ORDER = (
    ("nmz:encode", "evolve:_encode"),
    ("nmz:host_io", "evolve:_host_io"),
    ("nmz:surrogate", "surrogate"),
    ("nmz:extract", "extract"),
    ("nmz:evolve", "evolve:_dispatch/wait"),
    ("bench:evolve", "evolve:_other"),
    ("bench:ingest", "ingest"),
    ("bench:save", "save"),
    ("bench:handle", "handle:_other__storage_load__wire_"),
)
NO_REQUEST = "no_request_in_the_sidecar"
SHORT_GAPS = "device:_gaps_under_100_us_between_ops"


# -- reading the profiler's file (jax needed) -------------------------------


HLO_LINE_RE = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*metadata=\{[^}]*op_name="([^"]+)"')


def hlo_scopes(hlo_text: str) -> dict:
    """``{instruction name: op_name}`` from a compiled module's text —
    where the ``jax.named_scope`` path of each op is stated (the TPU's
    trace events carry the instruction, not its scope)."""
    out = {}
    for line in hlo_text.splitlines():
        m = HLO_LINE_RE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def xplane_to_events(path: str, scopes: dict = None) -> list:
    """``scopes`` maps a module's name (``jit_fused``) to its
    ``hlo_scopes``; each device op is given the module that was running
    (the ``XLA Modules`` line) and, where known, its scope."""
    from jax.profiler import ProfileData

    scopes = scopes or {}
    data = ProfileData.from_file(path)
    events = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        modules = []
        if device:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules = sorted(
                        (float(ev.start_ns),
                         float(ev.start_ns) + float(ev.duration_ns),
                         ev.name.split("(", 1)[0]) for ev in line.events)
        starts = [m[0] for m in modules]
        for line in plane.lines:
            if device and line.name != "XLA Ops":
                continue
            for ev in line.events:
                name = ev.name
                if not device and not name.startswith(("bench:", "nmz:")):
                    continue
                rec = {"plane": plane.name, "line": line.name,
                       "start_ns": float(ev.start_ns),
                       "dur_ns": float(ev.duration_ns)}
                if device:
                    if " = " in name:
                        # the TPU's op events carry the whole HLO line
                        name = name.split(" = ", 1)[0].lstrip("%")
                    i = bisect.bisect_right(starts, rec["start_ns"]) - 1
                    module = (modules[i][2] if i >= 0
                              and rec["start_ns"] < modules[i][1] else "")
                    rec["module"] = module
                    rec["scope"] = scopes.get(module, {}).get(name, "")
                rec["name"] = name
                events.append(rec)
    return events


def write_probe(xplane_path: str, out_path: str, per_line: int = 25) -> None:
    """A few raw events of every plane and line, with all their stats,
    for reading a trace by hand."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    doc = []
    for plane in data.planes:
        for line in plane.lines:
            rows, count = [], 0
            for ev in line.events:
                count += 1
                if len(rows) < per_line:
                    rows.append({"name": ev.name,
                                 "start_ns": float(ev.start_ns),
                                 "dur_ns": float(ev.duration_ns),
                                 "stats": {str(k): str(v)[:200]
                                           for k, v in ev.stats}})
            doc.append({"plane": plane.name, "line": line.name,
                        "events": count, "first": rows})
    with open(out_path, "w") as f:
        json.dump(doc, f)


# -- interval arithmetic ------------------------------------------------------


def union(intervals) -> list:
    out = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def total(intervals) -> float:
    return float(sum(b - a for a, b in intervals))


def intersect(xs, ys) -> list:
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append([a, b])
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(xs, ys) -> list:
    """``xs`` minus ``ys`` (sorted, disjoint lists)."""
    out, j = [], 0
    for a, b in xs:
        cur = a
        while j < len(ys) and ys[j][1] <= cur:
            j += 1
        k = j
        while k < len(ys) and ys[k][0] < b:
            if ys[k][0] > cur:
                out.append([cur, ys[k][0]])
            cur = max(cur, ys[k][1])
            k += 1
        if cur < b:
            out.append([cur, b])
    return out


# -- the reduction -----------------------------------------------------------


def _label(e: dict) -> str:
    """``jit_fused:mutate/fusion.226``: module, innermost nmz scope,
    op name as the trace prints it."""
    inner = ""
    for p in (e.get("scope") or "").split("/"):
        if p.startswith("nmz_"):
            inner = p[len("nmz_"):]
    return f"{e.get('module', '')}:{inner}/{e['name']}"


def reduce(events: list, kernels=KERNELS) -> dict:
    dev_planes = sorted({e["plane"] for e in events
                         if e["plane"].startswith("/device:")})
    if not dev_planes:
        return {"n_devices": 0}
    n = len(dev_planes)
    marks = {e["name"]: e["start_ns"] for e in events
             if e["name"] in ("bench:slice_start", "bench:slice_stop")}
    events = [e for e in events if e["name"] not in marks]
    starts = [e["start_ns"] for e in events]
    ends = [e["start_ns"] + e["dur_ns"] for e in events]
    # the slice runs between the launcher's two markers; a trace
    # without them (a hand-made one) from its first to its last instant
    t_lo = marks.get("bench:slice_start", min(starts))
    t_hi = marks.get("bench:slice_stop", max(ends))
    out = {"n_devices": n, "window_s": (t_hi - t_lo) / 1e9}

    host = {}
    for e in events:
        if not e["plane"].startswith("/device:"):
            host.setdefault(e["name"], []).append(
                [e["start_ns"], e["start_ns"] + e["dur_ns"]])
    host_union = {k: union(v) for k, v in host.items()}
    evolve_u = host_union.get("nmz:evolve", [])
    out["evolve_union_s"] = total(evolve_u) / 1e9
    out["requests"] = len(host.get("bench:handle", []))
    # a request's device step runs inside its evolve span, and a slice
    # cuts requests at both ends (eight tenants overlap): per request,
    # count what falls inside the evolve spans the slice holds whole
    out["evolve_spans"] = len(host.get("nmz:evolve", []))

    busy = coll = exposed = busy_ev = exposed_ev = 0.0
    scope_s = {s: 0.0 for s in SCOPES}
    kernel_s = {k: 0.0 for k in kernels}
    kernel_calls = {k: 0 for k in kernels}
    op_s: dict = {}
    gap_s: dict = {}
    for plane in dev_planes:
        ops = [e for e in events if e["plane"] == plane]
        spans = [[e["start_ns"], e["start_ns"] + e["dur_ns"]] for e in ops]
        busy_u = union(spans)
        busy += total(busy_u)
        coll_iv, other_iv, containers = [], [], []
        for e, iv in zip(ops, spans):
            if CONTAINER_RE.match(e["name"]):
                containers.append((e, iv))
                continue
            label = _label(e)
            op_s[label] = op_s.get(label, 0.0) + e["dur_ns"]
            scope = e.get("scope") or ""
            for s in SCOPES:
                if s in scope:
                    scope_s[s] += e["dur_ns"]
                    break
            for k in kernels:
                if k in e["name"]:
                    kernel_s[k] += e["dur_ns"]
                    kernel_calls[k] += 1
            (coll_iv if COLLECTIVE_RE.search(e["name"])
             else other_iv).append(iv)
        leaves = union(coll_iv + other_iv)
        for e, iv in containers:
            label = _label(e)
            op_s[label] = op_s.get(label, 0.0) + total(
                subtract([iv], leaves))
        coll_u = union(coll_iv)
        coll += total(coll_u)
        exposed_iv = subtract(coll_u, union(other_iv))
        exposed += total(exposed_iv)
        busy_ev += total(intersect(busy_u, evolve_u))
        exposed_ev += total(intersect(exposed_iv, evolve_u))
        # idle gaps of this device, by what the host was doing
        gaps = subtract([[t_lo, t_hi]], busy_u)
        short = [g for g in gaps if g[1] - g[0] < SHORT_GAP_NS]
        gap_s[SHORT_GAPS] = gap_s.get(SHORT_GAPS, 0.0) + total(short)
        rest = [g for g in gaps if g[1] - g[0] >= SHORT_GAP_NS]
        for span_name, gap_name in GAP_ORDER:
            cover = host_union.get(span_name)
            if not cover or not rest:
                continue
            hit = intersect(rest, cover)
            if hit:
                gap_s[gap_name] = gap_s.get(gap_name, 0.0) + total(hit)
                rest = subtract(rest, cover)
        gap_s[NO_REQUEST] = gap_s.get(NO_REQUEST, 0.0) + total(rest)

    out["device_busy_s"] = busy / n / 1e9
    out["collective_s"] = coll / n / 1e9
    out["collective_exposed_s"] = exposed / n / 1e9
    out["device_busy_in_evolve_s"] = busy_ev / n / 1e9
    out["collective_exposed_in_evolve_s"] = exposed_ev / n / 1e9
    for s in SCOPES:
        out[f"scope_s.{s}"] = scope_s[s] / n / 1e9
    for k in kernels:
        out[f"kernel_s.{k}"] = kernel_s[k] / n / 1e9
        out[f"kernel_calls.{k}"] = kernel_calls[k] / n
    out["device_ops"] = [[k, v / n / 1e9] for k, v in sorted(
        op_s.items(), key=lambda kv: -kv[1])[:10]]
    out["idle_gaps"] = [[k, v / n / 1e9] for k, v in sorted(
        gap_s.items(), key=lambda kv: -kv[1])[:10] if v > 0]
    return out
