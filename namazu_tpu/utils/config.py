"""Experiment configuration.

Parity with /root/reference/nmz/util/config/config.go:23-117 (viper-based
TOML/YAML/JSON with centralized defaults). Python 3.11+ ships ``tomllib``,
so TOML needs no third-party dependency; YAML is accepted when PyYAML is
importable, JSON always.

All keys are snake_case. Dotted access (``cfg.get("explore_policy_param.
min_interval_ms")``) walks nested tables. For compatibility with configs
written against the reference's camelCase keys, lookups fall back to the
camelCase spelling of each path segment.
"""

from __future__ import annotations

import json
import re
import tomllib
from typing import Any, Dict, Optional

DEFAULTS: Dict[str, Any] = {
    # which policy drives the exploration
    "explore_policy": "random",
    # policy-specific parameters, passed verbatim to policy.load_config
    "explore_policy_param": {},
    # history storage backend
    "storage_type": "naive",
    # experiment scripts, run with CWD = materials dir
    "init": "",
    "run": "",
    "validate": "",
    "clean": "",
    # out-of-tree policy plugins: modules or .py files (relative paths
    # resolve against the materials dir) imported before the policy is
    # created; each registers itself via register_policy
    # (namazu_tpu/policy/plugins.py; reference counterpart:
    # example/template/mypolicy.go's compile-your-own-main flow)
    "policy_plugins": [],
    # endpoints: -1 = disabled, 0 = auto-assign, >0 = fixed port
    "rest_port": -1,
    "agent_port": -1,  # framed-TCP guest-agent endpoint (reference: pbPort)
    # do not start the exploration policy until REST /control enables it
    "skip_init_orchestration": False,
    # liveness watchdog (doc/robustness.md): entities with no inbound
    # event for this many seconds are declared dead and their parked
    # events force-released (nmz_entity_stalled_total); 0 disables
    "entity_liveness_timeout_s": 0,
    # per-phase deadlines for the experiment scripts (seconds; 0 = none).
    # enforced with process-group kill so a hung script's forked testee
    # children die with it (utils/cmd.py, cli/run_cmd.py)
    "run_deadline_s": 0,
    "validate_deadline_s": 0,
    "clean_deadline_s": 0,
    # observability plane (namazu_tpu/obs): event-lifecycle spans,
    # metrics registry, GET /metrics on the REST endpoint. Disabling
    # reduces the per-event hot path to one flag check (obs/metrics.py)
    "obs_enabled": True,
    # container mode
    "container": {},
}


def _camel(segment: str) -> str:
    parts = segment.split("_")
    return parts[0] + "".join(p.title() for p in parts[1:])


class Config:
    def __init__(self, data: Optional[Dict[str, Any]] = None):
        self._data: Dict[str, Any] = dict(data or {})

    # -- constructors ----------------------------------------------------

    @classmethod
    def from_file(cls, path: str) -> "Config":
        text = open(path, "rb").read()
        if path.endswith((".toml", ".tml")):
            return cls(tomllib.loads(text.decode()))
        if path.endswith(".json"):
            return cls(json.loads(text))
        if path.endswith((".yaml", ".yml")):
            import yaml  # optional dependency, present in this image

            return cls(yaml.safe_load(text))
        # sniff: try TOML then JSON
        return cls.from_string(text.decode())

    @classmethod
    def from_string(cls, text: str, fmt: str = "") -> "Config":
        if fmt == "toml" or not fmt:
            try:
                return cls(tomllib.loads(text))
            except tomllib.TOMLDecodeError:
                if fmt:
                    raise
        if fmt in ("", "json"):
            return cls(json.loads(text))
        if fmt in ("yaml", "yml"):
            import yaml

            return cls(yaml.safe_load(text))
        raise ValueError(f"unknown config format {fmt!r}")

    # -- access ----------------------------------------------------------

    def _lookup(self, data: Any, path: str) -> Any:
        cur = data
        for seg in path.split("."):
            if not isinstance(cur, dict):
                raise KeyError(path)
            if seg in cur:
                cur = cur[seg]
            elif _camel(seg) in cur:
                cur = cur[_camel(seg)]
            else:
                raise KeyError(path)
        return cur

    def get(self, path: str, default: Any = None) -> Any:
        try:
            return self._lookup(self._data, path)
        except KeyError:
            pass
        try:
            return self._lookup(DEFAULTS, path)
        except KeyError:
            return default

    def is_set(self, path: str) -> bool:
        """Whether ``path`` was given explicitly (not just a DEFAULT)."""
        try:
            self._lookup(self._data, path)
            return True
        except KeyError:
            return False

    def set(self, path: str, value: Any) -> None:
        # mirror get()'s camelCase fallback: a reference-style config
        # holds e.g. "explorePolicyParam", and creating a snake_case
        # sibling table would SHADOW it on every later lookup — one
        # `run --knowledge` would silently reset every other policy
        # param to defaults
        segs = path.split(".")
        cur = self._data
        for seg in segs[:-1]:
            if seg not in cur and isinstance(cur.get(_camel(seg)), dict):
                seg = _camel(seg)
            nxt = cur.setdefault(seg, {})
            if not isinstance(nxt, dict):
                nxt = cur[seg] = {}
            cur = nxt
        leaf = segs[-1]
        if leaf not in cur and _camel(leaf) in cur:
            leaf = _camel(leaf)
        cur[leaf] = value

    def policy_param(self, key: str, default: Any = None) -> Any:
        return self.get(f"explore_policy_param.{key}", default)

    def to_jsonable(self) -> Dict[str, Any]:
        return dict(self._data)

    def dump_json(self, path: str) -> None:
        # atomic: the config snapshot is part of the storage's persistent
        # state — a kill mid-init must not leave a torn config.json that
        # poisons every later `run` (utils/atomic.py)
        from namazu_tpu.utils.atomic import atomic_write_json

        atomic_write_json(path, self._data, indent=2, sort_keys=True)

    def __repr__(self) -> str:  # pragma: no cover
        return f"Config({self._data!r})"


_DURATION_RE = re.compile(r"^\s*([0-9.]+)\s*(ms|s|m|h|us)?\s*$")
_UNIT_SECONDS = {"us": 1e-6, "ms": 1e-3, "s": 1.0, None: 1e-3, "m": 60.0, "h": 3600.0}


def parse_duration(value: Any, default_unit_ms: bool = True) -> float:
    """Parse a duration into seconds.

    Accepts numbers (interpreted as milliseconds, matching the reference's
    convention for interval params, e.g. minInterval/maxInterval in ms —
    randompolicy.go:156-228) or strings with a unit suffix ("80ms", "1.5s").
    """
    if isinstance(value, (int, float)):
        return float(value) * (1e-3 if default_unit_ms else 1.0)
    m = _DURATION_RE.match(str(value))
    if not m:
        raise ValueError(f"bad duration {value!r}")
    num, unit = float(m.group(1)), m.group(2)
    if unit is None and not default_unit_ms:
        return num
    return num * _UNIT_SECONDS[unit]
