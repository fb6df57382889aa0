"""BENCHMARK.json and the files each of its entries is found by."""
import json
import re

import pytest

import tpu_bench_helpers as h
from benchmarks.tpu import spec

BENCH = spec.load_bench()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_workload_resolves_to_its_files(name):
    cell = spec.cell(name, BENCH)
    assert cell.traffic["driver"]
    assert callable(cell.driver().run_window)
    assert isinstance(cell.limits["max_logit_gap"], float)
    assert set(cell.config["reduced"]) == set(cell.config.get("published",
                                                             {}))
    assert cell.traffic["source"]
    assert all(isinstance(v, str) for v in cell.traffic["reduced"].values())
    fam = spec.family(cell.config["family"])
    assert callable(fam.leaves) and callable(fam.layer)
    assert callable(fam.step_work) and fam.SIZES
    for m in cell.per_layer:
        assert callable(cell.reader(m.name).read), m.name
    assert {m.name for m in cell.end_to_end} >= {
        "output_tok_s", "ttft_p95_ms", "tpot_p95_ms", "setup_s"}


def test_entries_keep_to_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert len(json.dumps(BENCH)) < 64 * 1024
    for k in ("configs", "workloads"):
        for x in BENCH[k]:
            assert 1 <= len(x["why"]) <= 200 and "\n" not in x["why"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    assert all(0.01 <= m["bound"] <= 0.25 for m in BENCH["end_to_end"])
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for c in BENCH["configs"]:
        with open(h.REPO + "/" + c["file"]) as f:
            conf = json.load(f)
        assert conf["name"] == c["name"] and conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]


def test_config_files_state_the_program_sizes():
    from benchmarks.tpu import harness
    for w in BENCH["workloads"]:
        cell = spec.cell(w["name"], BENCH)
        harness.program_config(cell.config)


def test_unknown_workload_is_refused():
    with pytest.raises(spec.SpecError):
        spec.cell("no-such-cell", BENCH)


def test_unknown_family_is_refused():
    with pytest.raises(spec.SpecError, match="families"):
        spec.family("no-such-family")
