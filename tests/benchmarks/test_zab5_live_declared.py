"""``zk2212-zab5-live`` and its cell, as declared: the full-stream
ZOOKEEPER-2212 hunt on the live path (PR 45).

The configuration is ``zk2212-zab5``'s — source, testee, hint form,
``search.drop`` / ``search.set``, shipped width, guarantees — with the
client session cut to 21 creates, so that a run of the real testee fits
a window ten times; its templates pad to both sides of a length
quantum; its two per-layer metrics are counter arithmetic over the
window's delta of the sidecar's registry. Membership only: no place in
a list, no length of a list."""

import contextlib
import json
import os
import sys

import pytest

import tiny_root

sys.path.insert(0, tiny_root.BENCH)

import history  # noqa: E402
import layer_metrics  # noqa: E402
import manifest  # noqa: E402

from namazu_tpu.obs import metrics, spans  # noqa: E402
from namazu_tpu.ops import trace_encoding as te  # noqa: E402

CELL, CONFIG, TWIN = ("zk2212-zab5-live.live-d64", "zk2212-zab5-live",
                      "zk2212-zab5")
#: what may differ from ``zk2212-zab5``'s file, and nothing else
DIFFERS = {"name", "source", "deployment", "history", "assumed", "reduced"}
#: keys this file has and its twin's has not
ADDED = {"architecture", "reduced_why"}
LIVE_METRICS = (
    ["run_wall_p50_s", "live_install_p50_s", "run_standby_share",
     "run_respawn_s", "delay_table_request_share"]
    + [f"run_{phase}_s" for phase in spans.RUN_PHASES])
APPENDS, BELOW = ("reference_rows_appended_per_request",
                  "embed_below_class_share")


@pytest.fixture(scope="module")
def man():
    man = manifest.Manifest(tiny_root.REPO)
    man.validate()
    return man


def test_the_configuration_is_zab5s_cut_to_the_live_path(man):
    cell = man.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "live-d64", 1)
    assert man.traffic(cell)["kind"] == "campaign"
    config = man.config(cell)
    twin = man.config(man.cell("zk2212-zab5.fleet8-d32"))
    assert twin["name"] == TWIN and config["name"] == CONFIG
    assert set(config) - set(twin) == ADDED and set(twin) <= set(config)
    assert config["architecture"] is None
    for key in set(twin) - DIFFERS - {"testee", "shipped_width",
                                      "guarantees"}:
        assert config[key] == twin[key], key
    # the testee: the same example, materials and port; the pair of
    # config files that set the write count
    mine, theirs = dict(config["testee"]), dict(twin["testee"])
    assert (mine.pop("record_config"), mine.pop("search_config")) == (
        "config_w21.toml", "config_tpu_sidecar_w21.toml")
    theirs.pop("record_config"), theirs.pop("search_config")
    assert mine == theirs
    # every width; the two padded lengths are stated under `assumed`
    theirs = dict(twin["shipped_width"])
    assert theirs.pop("trace_lengths") == [1536]
    assert config["shipped_width"] == theirs
    assert "L 384" in config["assumed"]["events_per_run"]
    assert "L 512" in config["assumed"]["events_per_run"]
    # every guarantee; no trace passes L 1024, and the limit is 0.05
    mine, theirs = dict(config["guarantees"]), dict(twin["guarantees"])
    assert (mine.pop("scorer"), theirs.pop("scorer")) == (
        "dense", "blockwise")
    assert "0.05" in mine.pop("numerics") and theirs.pop("numerics")
    assert mine == theirs and mine["reference_traces"] == 4
    assert config["reduced"] == twin["reduced"] and (
        "client_writes" in config["reduced"])
    assert config["assumed"]["client_writes"].startswith("21 ")
    entry = man.configs[CONFIG]
    assert entry["reduced"] == config["reduced"]
    assert "zk-found-2212.nfqhook" in entry["source"]
    assert entry["source"] == config["source"]


def test_the_two_config_files_only_set_the_write_count(man):
    # ... and the knob calibrated at that count, which the example's
    # one calibration.json (100 writes) cannot carry: explicit
    # environment wins over the artifact (cli/run_cmd.py)
    testee = man.config(man.cell(CELL))["testee"]
    example = os.path.join(tiny_root.REPO, testee["example"])
    for new, old in ((testee["record_config"], "config.toml"),
                     (testee["search_config"], "config_tpu_sidecar.toml")):
        with open(os.path.join(example, new)) as f, \
                open(os.path.join(example, old)) as g:
            mine, theirs = f.read().splitlines(), g.read().splitlines()
        differ = [(a, b) for a, b in zip(mine, theirs) if a != b]
        assert len(mine) == len(theirs) and differ == [(
            'run = "NMZ_ZAB_WRITES=21 NMZ_CALIB_REJOIN_DELAY_MS=773 '
            'sh $NMZ_MATERIALS_DIR/run.sh"',
            'run = "sh $NMZ_MATERIALS_DIR/run.sh"')]
    with open(os.path.join(example, "materials", "run.sh")) as f:
        assert 'WRITES="${NMZ_ZAB_WRITES:-100}"' in f.read()


def test_the_templates_pad_to_both_sides_of_a_quantum(man):
    config = man.config(man.cell(CELL))
    templates = history.load_templates(
        os.path.join(tiny_root.REPO, config["history"]))
    padded = sorted(te._auto_length(len(t["actions"]))
                    for t in templates["successes"])
    assert padded == [384] * 4 + [512] * 2
    assert len(templates["failures"]) == 3
    assert max(te._auto_length(len(t["actions"]))
               for t in templates["failures"]) <= 512
    # live-d64 stores 60 successes: each template ten times, so every
    # seed holds 40 short and 20 long ones, in another order
    mix = man.traffic(man.cell(CELL))
    stored = mix["prefill_runs"] - mix["prefill_failures"]
    assert stored % len(templates["successes"]) == 0
    # the source's events: FLE, ZAB and client messages, no ping
    kinds = {h.split(":")[1] for t in templates["successes"]
             for h in (a["event_hint"] for a in t["actions"])}
    assert kinds == {"fle", "zab", "cm", "sm"}
    for t in templates["successes"]:
        creates = [a for a in t["actions"]
                   if ":cm:create:" in a["event_hint"]]
        assert len(creates) == 21


def test_the_cell_is_listed_where_a_live_delay_mode_cell_is(man):
    for name in LIVE_METRICS:
        assert CELL in man.per_layer[name]["workloads"], name
    for name, layer in ((APPENDS, "search driver"),
                        (BELOW, "ingest and encode")):
        entry = man.per_layer[name]
        assert entry["workloads"] == [CELL]
        assert (entry["source"], entry["layer"], entry["moves"]) == (
            "program_counter", layer, "searched_runs_per_hour")
    assert (man.per_layer[APPENDS]["unit"],
            man.per_layer[BELOW]["unit"]) == ("rows", "%")
    e2e = {m["name"] for m in man.metrics_of(CELL, "end_to_end")}
    assert e2e == {"searched_runs_per_hour", "setup_s"}
    reported = {m["name"] for m in man.metrics_of(CELL, "per_layer")}
    twin = {m["name"] for m in man.metrics_of("zk2212-fle3.live-d64",
                                              "per_layer")}
    assert reported - twin == {APPENDS, BELOW} and twin <= reported
    assert "pairdist_roofline" in reported


@contextlib.contextmanager
def registry_of_its_own():
    """A fresh registry, switched on, in the process's place."""
    reg = metrics.MetricsRegistry()
    old, was_on = metrics.set_registry(reg), metrics.enabled()
    metrics.configure(True)
    try:
        yield reg
    finally:
        metrics.configure(was_on)
        metrics.set_registry(old)


def registry_doc(evolves, appends=0, evictions=0, below=0, counted=True,
                 depth=60):
    """A sidecar's registry after ``evolves`` requests over a history
    ``depth`` runs deep of which ``below`` pad under the class
    (``counted``: by a program that has the counters)."""
    with registry_of_its_own() as reg:
        for k in range(evolves):
            with spans.search_phase("evolve"):
                pass
            if counted:
                spans.embed_traces(depth, below)
        if evolves and counted:
            spans.resident_trace_rows("restage", 4)
        for op, n in (("append", appends), ("evict", evictions)):
            if n:
                spans.resident_trace_rows(op, n)
        return json.loads(json.dumps(reg.to_jsonable()))


@pytest.mark.parametrize("before, after, rows, share", [
    # a window of 12 requests, 11 passing runs, 40 of 60 stored runs
    # under the class
    ((4, 2, 0, 40), (16, 13, 0, 40), 11 / 12, 100.0 * 40 / 60),
    # past 16 resident rows every append evicts; one length stored
    ((20, 18, 2, 0), (30, 28, 12, 0), 1.0, 0.0),
    # a sidecar's first requests
    ((0, 0, 0, 0), (2, 1, 0, 15), 0.5, 25.0),
], ids=["a_window", "evicting", "from_the_start"])
def test_the_two_metrics_are_counter_arithmetic(man, before, after, rows,
                                                share):
    obs = {"metrics_before": registry_doc(*before),
           "metrics_after": registry_doc(*after)}
    assert layer_metrics.evaluate(
        man.layer_metric(APPENDS), obs) == pytest.approx(rows)
    assert layer_metrics.evaluate(
        man.layer_metric(BELOW), obs) == pytest.approx(share)


def test_the_two_metrics_are_left_out_without_their_counters(man):
    # the parent of PR 45 has the evolve phase and neither counter:
    # nothing to read is nothing reported, and nothing raises
    obs = {"metrics_before": {"metrics": []},
           "metrics_after": registry_doc(1, counted=False)}
    for name in (APPENDS, BELOW):
        assert layer_metrics.evaluate(man.layer_metric(name), obs) is None
