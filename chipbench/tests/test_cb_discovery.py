"""Configurations, traffic mixes and metric readers are found by name from
files, and BENCHMARK.json keeps to the benchmark's contract."""
import json
import re

import pytest

import _paths
import harness

with open(_paths.ROOT / "BENCHMARK.json") as _f:
    BENCH = json.load(_f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert BENCH["paths"] == ["chipbench"]
    r = BENCH["run_seconds"]
    assert 1 <= r <= 51
    # a full check of 24 cells fits: runs, compiles and the spare
    assert (2 + 14 * 24) * (r + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_keys():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += CELLS + [c["name"] for c in BENCH["configs"]]
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in BENCH["workloads"]]:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    assert {m["name"]: m["bound"] for m in BENCH["end_to_end"]}["setup_s"] <= 0.25


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    c = harness.load_cell(_paths.ROOT, cell)
    assert c.chips in (1, 4)
    assert (harness.HERE / "families" / f"{c.config['family']}.py").exists()
    e2e = {m["name"] for m in c.metrics(trace=False)}
    assert "setup_s" in e2e and len(e2e) >= 2
    per_layer = c.metrics(trace=True)
    assert per_layer
    for m in c.metrics(False) + per_layer:
        mod = harness.load_module(harness.HERE / "metrics" / f"{m['name']}.py")
        assert callable(mod.read)
    for m in per_layer:  # each moves a metric the cell reports
        assert m["moves"] in e2e


def test_configs_are_files_of_their_own():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for c in BENCH["configs"]:
        with open(_paths.ROOT / c["file"]) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])


def test_at_most_one_cell_on_four_chips():
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 2)


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_program_serves_the_configuration_as_stated(name):
    import jax

    from repro.core.functions import model_config
    from repro.models import init_params
    import weights

    (entry,) = [c for c in BENCH["configs"] if c["name"] == name]
    with open(_paths.ROOT / entry["file"]) as f:
        cfg = json.load(f)
    fam = harness.load_module(harness.HERE / "families" / f"{cfg['family']}.py")
    mc = model_config(cfg["program_arch"], cfg["full_width"])
    fam.check_program(cfg, mc)
    weights.check_layout(fam.param_shapes(cfg), jax.eval_shape(
        lambda: init_params(mc, jax.random.PRNGKey(0))))


def test_a_departing_program_is_refused():
    import dataclasses

    from repro.core.functions import model_config

    with open(_paths.BENCH / "configs" / "qwen2.5-3b.json") as f:
        cfg = json.load(f)
    fam = harness.load_module(harness.HERE / "families" / "qwen2.py")
    mc = dataclasses.replace(model_config("qwen2.5-3b", True), d_ff=8192)
    with pytest.raises(ValueError, match="d_ff"):
        fam.check_program(cfg, mc)
