"""BENCHMARK.json resolves to its files and keeps to the benchmark's rules."""
from __future__ import annotations

import json
import pathlib
import re

import pytest

from chipbench_tiny import ROOT, RUN

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
# keys a reduction may never name: the widths of the model
WIDTHS = ("hidden_size", "intermediate_size", "num_attention_heads",
          "num_key_value_heads", "head_dim", "vocab_size")


@pytest.fixture(scope="module")
def bench():
    return RUN.load_bench()


def _one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    assert any(bench["command"][1].startswith(p + "/")
               for p in bench["paths"])
    rs = bench["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check of 24 cells fits the check's time
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_configs_resolve(bench):
    files = set()
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _one_line(c["why"]) and _one_line(c["source"])
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["source"] == c["source"]
        assert sorted(c["reduced"]) == sorted(conf["reduced"])
        for key in c["reduced"]:
            assert NAME.match(key) and key not in WIDTHS
            assert not key.endswith(("_dim", "_rank"))
            assert conf[key] == conf["reduced"][key][1]
        assert set(conf["limits"]) == {"logit_err", "selection_gap",
                                       "not_greedy"}
        assert conf["limits"]["not_greedy"] == 0
        # the architecture module, by the key or by default, in chipbench/
        rel = conf.get("reference", "chipbench/reference.py")
        arch = RUN.architecture(bench, conf)
        assert pathlib.Path(arch.__file__) == ROOT / rel
        assert pathlib.Path(rel).parts[0] == "chipbench"
        assert all(callable(getattr(arch, n, None)) for n in RUN.ARCH_NAMES)


def test_workloads_resolve(bench):
    names = [w["name"] for w in bench["workloads"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    pairs = {(w["config"], w["traffic"]) for w in bench["workloads"]}
    assert len(pairs) == len(names)
    configs = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _one_line(w["why"])
        mix = json.loads((ROOT / "chipbench/traffic" / f"{w['traffic']}.json")
                         .read_text())
        assert mix["arrival"] in ("poisson", "backlog")
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(names) // 2)


def test_metrics_resolve(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = [w["name"] for w in bench["workloads"]]
    every = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in every}) == len(every)
    for m in every:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert (ROOT / "chipbench/metrics" / f"{m['name']}.py").is_file()
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert e2e["setup_s"]["bound"] <= 0.25 and "workloads" not in e2e["setup_s"]
    layers = {}
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _one_line(m["layer"]) and m["moves"] in e2e
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m.get("workloads", moved)) <= set(moved)
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
        layers.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_every_cell_reports_enough(bench):
    for w in bench["workloads"]:
        def reports(section):
            return [m["name"] for m in bench[section]
                    if w["name"] in m.get("workloads", [w["name"]])]
        e2e = reports("end_to_end")
        assert "setup_s" in e2e and len(e2e) >= 2
        assert reports("per_layer")


def test_peaks_have_a_source():
    peaks = json.loads((ROOT / "chipbench/peaks.json").read_text())
    for kind, p in peaks.items():
        assert p["bf16_flops_per_s"] > 0 and p["hbm_bytes_per_s"] > 0
        assert p["source"]
