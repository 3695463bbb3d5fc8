"""Cells, configurations, traffic mixes, metrics and kernel work counts are
files of their own, found by name from ``BENCHMARK.json``."""

import json
import re

import pytest

import cell
import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bm():
    return spec.load_benchmark()


def test_every_name_has_its_file(bm):
    for c in bm["configs"]:
        cfg = spec.config(bm, c["name"])
        assert cfg["name"] == c["name"]
        assert c["file"].startswith("bench/configs/")
        spec.load_plugin("drivers", cfg["entry"])
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
    for w in bm["workloads"]:
        spec.traffic(w["traffic"])
        assert "x_rel_err_max" in cell.limits(w["name"])
    for m in bm["end_to_end"] + bm["per_layer"]:
        mod = spec.load_plugin("metrics", m["name"])
        assert callable(mod.read)
    for c in bm["configs"]:
        w = spec.load_plugin("work", spec.config(bm, c["name"])["solver"]
                             ["sketch"])
        assert w.KERNELS and callable(w.flops) and callable(w.min_bytes)


def test_unknown_names_are_errors(bm):
    with pytest.raises(spec.SpecError):
        spec.workload(bm, "no-such-cell")
    with pytest.raises(spec.SpecError):
        spec.traffic("no-such-mix")
    with pytest.raises(spec.SpecError):
        spec.load_plugin("metrics", "no_such_metric")
    with pytest.raises(spec.SpecError):
        spec.peaks("TPU v99")
    assert spec.peaks("TPU v5 lite")["flops_per_s"] == 197e12


def test_metrics_are_chosen_by_cell(bm):
    names = {m["name"] for m in spec.metrics_for(bm, "lib-expdecay-16k",
                                                 trace=False)}
    assert names == {"solves_per_s", "latency_p95_ms", "setup_s"}
    per = {m["name"] for m in spec.metrics_for(bm, "lib-expdecay-16k",
                                               trace=True)}
    assert "sketch_roofline_pct" in per and "solves_per_s" not in per
    synthetic = {"end_to_end": [{"name": "a"}, {"name": "b",
                                                "workloads": ["x"]}],
                 "per_layer": []}
    assert [m["name"] for m in spec.metrics_for(synthetic, "y",
                                                trace=False)] == ["a"]


def test_benchmark_file_keeps_the_contract(bm):
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert bm["paths"] == ["bench"]
    assert 1 <= bm["run_seconds"] <= 51
    cells = {w["name"] for w in bm["workloads"]}
    for c in bm["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and 1 <= len(c["why"]) <= 200
        assert len(c["source"]) <= 200 and len(c["reduced"]) <= 16
    assert {w["config"] for w in bm["workloads"]} == {
        c["name"] for c in bm["configs"]}
    for w in bm["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bm["workloads"]) <= max(
        1, len(bm["workloads"]) // 2)
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bm["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for m in bm["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in moved.get("workloads", [w])
    for w in cells:
        reported = {m["name"] for m in spec.metrics_for(bm, w, trace=False)}
        assert "setup_s" in reported and len(reported) >= 2
        assert spec.metrics_for(bm, w, trace=True)
    assert len(json.dumps(bm)) < 64 * 1024
