"""The one general reader of declarative per-layer metrics.

A metric is a file ``benchmarks/layer_metrics/<name>.json``:

    {"name", "layer", "unit", "better", "moves",
     "value": {"kind": ..., ...},      what is read
     "reduce": one of the menu below,
     "other": {"kind": ..., ...},      the second operand, where needed
     "scale": 1000}                    optional factor (s -> ms)

``value.kind`` and what it reads from a run's observations:

* ``span``    — the launcher's host spans of the whole window:
                ``name`` (handle/ingest/evolve/save), ``field``
                ``seconds`` (default) or ``arg``
* ``counter`` — before/after delta of the sidecar's own registry
                (``{"op": "metrics"}``): ``name``, ``labels``, ``field``
                ``value`` / ``sum`` / ``count``
* ``trace``   — a quantity of ``trace_reduce.reduce``: ``name``
* ``compile`` — lowering events inside the window
* ``run_log`` — per-run readings of the campaign's log: ``name``
* ``client``  — client-clock readings around requests: ``name``

``reduce``: ``p50`` (median of the readings), ``sum``, ``count``,
``per`` (sum / other's sum), ``share_of`` (100 * sum / other's sum),
``inverse_share_of`` (100 * (1 - sum / other's sum)), ``roofline``
(least time of ``kernel`` over its traced time, in percent; its
operations and bytes by the function ``counts`` names,
``<module>:<function>`` under ``benchmarks/``, called with the run's
``shape``; the peaks by ``benchmarks/peaks.py``).

A metric whose source holds nothing to read returns None and is left
out of the result line.
"""

from __future__ import annotations

import importlib
import statistics
from typing import Optional

import peaks


def counter_delta(before: dict, after: dict, name: str, labels: dict,
                  field: str) -> Optional[float]:
    """One sample's increase between two ``to_jsonable`` registry
    documents (histograms: ``sum`` or ``count``)."""

    def pick(doc):
        for fam in (doc or {}).get("metrics", []):
            if fam["name"] != name:
                continue
            for s in fam["samples"]:
                if all(s["labels"].get(k) == v for k, v in labels.items()):
                    v = s["value"]
                    return float(v[field] if isinstance(v, dict) else v)
        return None

    b, a = pick(before), pick(after)
    if a is None:
        return None
    return a - (b or 0.0)


def read(spec: dict, obs: dict):
    """The readings a ``value``/``other`` spec names: a list, a number
    or None."""
    kind = spec["kind"]
    if kind == "span":
        rows = obs.get("spans", {}).get(spec["name"])
        if not rows:
            return None
        col = 2 if spec.get("field") == "arg" else 1
        vals = [r[col] for r in rows if r[col] is not None]
        return vals or None
    if kind == "counter":
        return counter_delta(obs.get("metrics_before"),
                             obs.get("metrics_after"), spec["name"],
                             spec.get("labels", {}),
                             spec.get("field", "value"))
    if kind == "trace":
        return (obs.get("trace") or {}).get(spec["name"])
    if kind == "compile":
        return obs.get("compiles")
    if kind in ("run_log", "client"):
        return obs.get(kind, {}).get(spec["name"]) or None
    raise ValueError(f"unknown value kind {kind!r}")


def roofline(decl: dict, obs: dict) -> Optional[dict]:
    """Share, bounding peak and least time of a ``roofline`` metric."""
    calls = (obs.get("trace") or {}).get(f"kernel_calls.{decl['kernel']}")
    kernel_s = read(decl["value"], obs)
    if not calls or not kernel_s or _sum(kernel_s) <= 0 \
            or not obs.get("shape"):
        return None
    module, _, fn = decl["counts"].partition(":")
    counts = getattr(importlib.import_module(module), fn)(obs["shape"])
    return peaks.roofline_share(counts, calls, _sum(kernel_s),
                                obs["device_kind"])


def _sum(x) -> float:
    return float(sum(x)) if isinstance(x, (list, tuple)) else float(x)


def evaluate(decl: dict, obs: dict) -> Optional[float]:
    value = read(decl["value"], obs)
    how = decl["reduce"]
    if how == "count":
        return None if value is None else float(len(value))
    if value is None:
        return None
    scale = float(decl.get("scale", 1.0))
    if how == "p50":
        return scale * statistics.median(value)
    if how == "sum":
        return scale * _sum(value)
    if how == "roofline":
        detail = roofline(decl, obs)
        return None if detail is None else detail["share_pct"]
    other = read(decl["other"], obs)
    if other is None or _sum(other) == 0:
        return None
    ratio = _sum(value) / _sum(other)
    if how == "per":
        return scale * ratio
    if how == "share_of":
        return 100.0 * ratio
    if how == "inverse_share_of":
        return 100.0 * (1.0 - ratio)
    raise ValueError(f"unknown reduction {how!r}")
