"""BENCHMARK.json against the harness: every name resolves to its files,
and every cell reports what the contract asks of it.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q"""
import json
import os
import re

from harness import bench

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def spec():
    return bench.load_spec(bench.ROOT)


def test_names_and_files():
    s = spec()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in s[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for c in s["configs"]:
        assert os.path.exists(os.path.join(bench.ROOT, c["file"]))
        cfg = json.load(open(os.path.join(bench.ROOT, c["file"])))
        assert os.path.exists(os.path.join(
            bench.BENCH_DIR, "references", cfg["reference"] + ".py"))
    for w in s["workloads"]:
        assert os.path.exists(os.path.join(bench.BENCH_DIR, "traffic",
                                           w["traffic"] + ".json"))
        t = json.load(open(os.path.join(bench.BENCH_DIR, "traffic",
                                        w["traffic"] + ".json")))
        assert os.path.exists(os.path.join(bench.BENCH_DIR, "jobs",
                                           t["kind"] + ".py"))
    for m in s["end_to_end"] + s["per_layer"]:
        assert os.path.exists(os.path.join(bench.BENCH_DIR, "metrics",
                                           m["name"] + ".py")), m["name"]


def test_every_cell_reports_enough():
    s = spec()
    for w in s["workloads"]:
        cell = bench.find_cell(s, w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        assert cell.limits, "a cell's correctness limits are set"
        for m in cell.per_layer:
            assert m["moves"] in e2e


def test_bounds():
    s = spec()
    for m in s["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert 1 <= s["run_seconds"] <= 51
