"""Cells, configurations, traffic kinds and metrics are found by name, and
``BENCHMARK.json`` keeps to the shape the driver reads."""
import json
import os
import re

import pytest

import _bench_tiny  # noqa: F401 (puts the checkout root on sys.path)
from bench import cells, harness, readers, trace

SPEC = cells.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench", "tests/bench"]
    assert SPEC["command"][1] == "bench/run.py"
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) < 64 * 1024


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_each_cell_is_found_by_name(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(w["name"]) and len(w["why"]) <= 200
    cell = cells.load_cell(w["name"])
    assert (cell.config_name, cell.traffic, cell.chips) == (
        w["config"], w["traffic"], w["chips"])
    assert cell.config["name"] == w["config"]
    drv = cells.traffic(cell.traffic)
    for fn in ("prepare", "warmup", "window", "check", "work"):
        assert callable(getattr(drv, fn))
    assert set(cell.params["limits"]) == {"miss_depth", "extra_depth",
                                          "t_gap"}
    # Every cell reports setup_s, another end-to-end metric and a
    # per-layer metric.
    e2e = {m["name"] for m in cells.metrics_for(w["name"], "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cells.metrics_for(w["name"], "per_layer")


@pytest.mark.parametrize("c", SPEC["configs"], ids=lambda c: c["name"])
def test_each_configuration_is_its_own_file(c):
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert c["file"] == f"bench/configs/{c['name']}.json"
    with open(os.path.join(cells.ROOT, c["file"])) as f:
        cfg = json.load(f)
    assert cfg["reduced"] == c["reduced"]
    assert set(cfg["reduced"]) <= set(cfg["source_values"])
    assert any(w["config"] == c["name"] for w in SPEC["workloads"])


METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_each_metric_has_a_reader(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert callable(cells.reader(m["name"]))
    if "bound" in m:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    else:
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
        assert "\n" not in m["layer"]
    names = {w["name"] for w in SPEC["workloads"]}
    assert set(m.get("workloads", names)) <= names


def _run(kind):
    rec = {"kind": kind, "elapsed_s": 2.0, "attempted": 2, "failed": 0,
           "execs": [{"comp": 0, "wall_s": 1.0, "plan_s": 0.25,
                      "dispatch_s": 0.25, "sync_s": 0.25, "qsegs": 10,
                      "hits": 5}] * 2}
    red = trace.Reduced(window_s=2.0, busy_s=0.5,
                        op_seconds={"distthresh_kernel": 0.25, "copy": 0.1},
                        idle_by_span={}, longest_gaps=[], num_devices=1)
    return harness.Run(cell=None, setup_s=3.0, record=rec,
                       compiles={"compile_s": 1.5}, device_kind="TPU v5 lite",
                       trace=red, work=(1e9, 1e8))


def test_readers_read_their_kind_and_nothing_else():
    batch, other = _run("batch"), _run("other")
    assert cells.reader("qseg_per_s")(batch) == pytest.approx(10.0)
    assert cells.reader("marshal_ms.batch")(batch) == pytest.approx(250.0)
    assert cells.reader("plan_ms.batch")(batch) == pytest.approx(250.0)
    assert cells.reader("compile_s.batch")(batch) == 1.5
    assert cells.reader("device_idle.batch")(batch) == pytest.approx(0.75)
    share = cells.reader("distthresh_roofline.batch")(batch)
    assert share == pytest.approx(100 * (1e8 / 819e9) / 0.25)
    assert readers.kernel_seconds(batch) == 0.25
    for name in ("qseg_per_s", "marshal_ms.batch", "plan_ms.batch",
                 "compile_s.batch", "device_idle.batch",
                 "distthresh_roofline.batch"):
        assert cells.reader(name)(other) is None, name
    assert cells.reader("setup_s")(other) == 3.0
    batch.trace = None
    assert cells.reader("distthresh_roofline.batch")(batch) is None
    assert cells.reader("device_idle.batch")(batch) is None


def test_unknown_names_are_refused():
    with pytest.raises(KeyError):
        cells.load_cell("no.such.cell")
    with pytest.raises(KeyError):
        cells.reader("no_such_metric")
    with pytest.raises(KeyError):
        cells.traffic("no_such_kind")
