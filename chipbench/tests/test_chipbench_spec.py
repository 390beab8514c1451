"""BENCHMARK.json against the benchmark's contract, and every cell resolved
to its files: configuration, workload, traffic, driver, reference, readers."""

import json
import os
import re

import pytest

import small  # noqa: F401  (puts chipbench/ and src/ on the path)
import bench

SPEC = bench.benchmark_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")


def line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert 1 <= len(SPEC["paths"]) <= 16
    assert all(PATH.match(p) and ".." not in p.split("/") for p in SPEC["paths"])
    assert 1 <= len(SPEC["command"]) <= 32 and all(line(w) for w in SPEC["command"])
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) <= 64 * 1024


def test_names_units_and_keys():
    seen = set()
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
        assert c["file"].startswith(tuple(p + "/" for p in SPEC["paths"]))
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and line(w["why"])
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["name"] not in seen
        seen.add(m["name"])
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert line(m["layer"])
    assert "setup_s" in {m["name"] for m in SPEC["end_to_end"]}
    assert len({c["name"] for c in SPEC["configs"]}) == len(SPEC["configs"])
    assert len({w["name"] for w in SPEC["workloads"]}) == len(SPEC["workloads"])
    assert len({(w["config"], w["traffic"]) for w in SPEC["workloads"]}) == len(SPEC["workloads"])


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_resolves(cell):
    c = bench.resolve(cell)
    assert c.driver.run and c.reference.init
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in e2e
        assert callable(bench.metric_reader(m["name"]).read)
    conf = next(x for x in SPEC["configs"] if x["name"] == c.entry["config"])
    assert os.path.samefile(os.path.join(bench.ROOT, conf["file"]),
                            os.path.join(bench.HERE, "configs", c.entry["config"] + ".json"))


def test_every_config_is_used():
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_weights_have_the_programs_layout(cell):
    """The benchmark makes the weights; at the cell's full size their tree
    and shapes are the program's (shapes only, nothing computed)."""
    import jax
    from program import check_layout, program_config
    c = bench.resolve(cell)
    shapes = jax.eval_shape(lambda k: c.reference.init(c.config, k), jax.random.PRNGKey(0))
    check_layout(program_config(c, {}), shapes)
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert n == bench.work("model").param_count(c.config)
