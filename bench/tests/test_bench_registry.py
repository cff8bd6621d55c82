"""Configurations, mixes, generators and per-layer metrics are found by
name, and BENCHMARK.json keeps to the shape its contract fixes."""
import json
import re
import shutil

import pytest

from bench.registry import ROOT, Benchmark
from bench.tests import tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_cell_finds_its_files():
    b = Benchmark()
    for cell in b.spec["workloads"]:
        cfg = b.config(cell["config"])
        mix = b.traffic(cell["traffic"])
        assert mix["loop"] in ("closed", "open", "batch")
        b.generator(cfg["graph"]["generator"])
        assert any(m["name"] == "setup_s" for m in b.end_to_end(cell["name"]))
        assert len(b.end_to_end(cell["name"])) >= 2
        assert b.per_layer(cell["name"])
        for m in b.per_layer(cell["name"]):
            b.reader(m["name"])


def test_benchmark_json_keeps_to_its_contract():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in spec[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in spec["end_to_end"]}
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(pairs) == len(set(pairs))
    for c in spec["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith(spec["paths"][0] + "/")


def test_every_configuration_has_limits_for_what_its_cells_compare():
    b = Benchmark()
    for cell in b.spec["workloads"]:
        lim = b.limits(cell["config"])
        loop = b.traffic(cell["traffic"])["loop"]
        need = (("frogs_lost", "frog_count_z2") if loop == "batch"
                else ("unanswered", "malformed", "ppr_mass_deficit"))
        for name in need:
            assert lim[name]["limit"] >= 0, (cell["name"], name)


def test_a_cell_defined_only_by_new_files_is_found(tmp_path):
    root = tiny.make(tmp_path)
    (root / "bench/traffic/burst.json").write_text(json.dumps({
        "loop": "open", "warmup_s": 1,
        "arrivals": {"process": "poisson", "rate_per_s": 8},
        "request": {"k": 10, "epsilon": 0.15, "delta": 0.1},
        "sources": {"dist": "zipf", "constant": 0.99}}))
    (root / "bench/configs/other.json").write_text(
        json.dumps(dict(tiny.TINY_CONFIG, p_T=0.2)))
    (root / "bench/limits/other.json").write_text(json.dumps(
        {"unanswered": {"limit": 0}, "malformed": {"limit": 0},
         "ppr_mass_deficit": {"limit": 0.05}}))
    (root / "bench/metrics/gateway.sheds.py").write_text(
        "def read(run):\n    return run['counters']['sheds']\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "other", "source": "test",
                            "file": "bench/configs/other.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "other-burst", "config": "other",
                              "traffic": "burst", "chips": 1, "why": "test"})
    next(m for m in spec["end_to_end"]
         if m["name"] == "answer_p90_s")["workloads"].append("other-burst")
    spec["per_layer"].append({"name": "gateway.sheds", "unit": "requests",
                              "better": "lower", "source": "program_counter",
                              "layer": "gateway", "moves": "answer_p90_s",
                              "workloads": ["other-burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    b = Benchmark(root)
    assert b.config("other")["p_T"] == 0.2
    assert b.traffic("burst")["request"]["epsilon"] == 0.15
    assert b.limits("other")["ppr_mass_deficit"]["limit"] == 0.05
    assert {m["name"] for m in b.end_to_end("other-burst")} == {
        "answer_p90_s", "setup_s"}
    assert [m["name"] for m in b.per_layer("other-burst")] == [
        "gateway.sheds"]
    assert b.reader("gateway.sheds")({"counters": {"sheds": 3}}) == 3


def test_a_missing_file_is_an_error(tmp_path):
    root = tiny.make(tmp_path)
    b = Benchmark(root)
    with pytest.raises(FileNotFoundError):
        b.traffic("nonesuch")
    with pytest.raises(FileNotFoundError):
        b.reader("nonesuch.metric")
    with pytest.raises(FileNotFoundError):
        b.limits("nonesuch")
    with pytest.raises(KeyError):
        b.cell("nonesuch")
    shutil.rmtree(root / "bench/graphs")
    with pytest.raises(FileNotFoundError):
        b.generator("chung_lu")
