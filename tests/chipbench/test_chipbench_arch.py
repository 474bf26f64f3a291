"""A configuration's architecture module: the harness takes the weights, the
reference and the FLOP counts from the module that the configuration file
names, or from the dense ``chipbench/reference.py`` without the key (tiny
size, CPU)."""
from __future__ import annotations

import json
import pathlib
import types

import jax
import numpy as np
import pytest

from chipbench_tiny import MIXES, ROOT, RUN, steer, tiny_conf
from chipbench import cell as C
from chipbench import reference as R

SEED = 2**31 + 41
WRONG_LOGITS = "tests/chipbench/arch_wrong_logits.py"
DOUBLE_FLOPS = "tests/chipbench/arch_double_flops.py"


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    monkeypatch.setattr(RUN, "use_compile_cache", lambda serve: None)


def _main(capsys, cell, trace=0):
    rc = RUN.main(["--workload", cell, "--seed", str(SEED), "--seconds", "2",
                   "--trace", str(trace)])
    out, err = capsys.readouterr()
    return rc, out, err


def _named(rel: str) -> dict:
    return dict(tiny_conf(), reference=rel)


def test_without_the_key_the_cell_runs_the_dense_module():
    conf = tiny_conf()
    assert RUN.ARCH_KEY not in conf
    arch = RUN.architecture(RUN.load_bench(), conf)
    assert pathlib.Path(arch.__file__) == ROOT / "chipbench/reference.py"
    cell = C.Cell(conf, dict(MIXES["reprefill"]), SEED, arch)
    cell.build()
    try:
        assert cell.arch is arch
        assert cell.cfg == R.program_config(conf)
        got, want = cell.params, R.make_params(conf, SEED)
        assert (jax.tree_util.tree_structure(got)
                == jax.tree_util.tree_structure(want))
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            a, b = np.asarray(a), np.asarray(b)
            assert a.dtype == b.dtype
            assert np.array_equal(a.view(np.uint16), b.view(np.uint16))
    finally:
        cell.release()


@pytest.mark.parametrize("rel,why", [
    ("chipbench/no_such_module.py", "does not exist"),
    ("src/repro/__init__.py", "lies outside the benchmark's paths"),
    ("../reference.py", "lies outside the benchmark's paths"),
    ("chipbench/traffic.py", "does not define dims_of")])
def test_a_module_the_harness_cannot_use_stops_the_run(monkeypatch, capsys,
                                                        rel, why):
    built = []
    monkeypatch.setattr(C.Cell, "build", lambda self: built.append(self))
    rc, out, err = _main(capsys, steer(monkeypatch, conf=_named(rel)))
    assert rc == 2 and out == ""
    assert f"'{RUN.ARCH_KEY}'" in err and rel in err and why in err
    assert not built


def test_the_comparison_runs_through_the_named_module(monkeypatch, capsys):
    rc, out, _ = _main(capsys, steer(monkeypatch, conf=_named(WRONG_LOGITS)))
    assert rc == 0
    res = json.loads(out.strip().splitlines()[-1])
    assert res["correct"] is False and res["failed"] == 0
    err = res["check"]["logit_err"]
    assert err["value"] > err["limit"]


@pytest.mark.parametrize("kind,name", [("reprefill", "step_mfu.reprefill"),
                                       ("decode", "step_mfu.decode")])
def test_the_readers_count_with_the_named_module(monkeypatch, capsys, kind,
                                                 name):
    """The run's record carries the named module, and the reading is twice
    the dense module's on the same record."""
    real, records = RUN.reader, []

    def reader(metric):
        mod = real(metric)
        if metric != name:
            return mod
        return types.SimpleNamespace(
            read=lambda run: records.append(run) or mod.read(run))

    monkeypatch.setattr(RUN, "reader", reader)
    cell = steer(monkeypatch, kind, conf=_named(DOUBLE_FLOPS))
    rc, out, _ = _main(capsys, cell, trace=1)
    assert rc == 0
    (run,) = records
    assert pathlib.Path(run.arch.__file__) == ROOT / DOUBLE_FLOPS
    got = json.loads(out.strip().splitlines()[-1])["metrics"][name]["value"]
    dense = real(name).read(types.SimpleNamespace(**dict(vars(run), arch=R)))
    assert dense > 0 and got == 2 * dense


def test_the_roofline_counts_with_the_named_module():
    """The decode-attention roofline of a recorded v5e decode trace, with
    the kernel's calls counted by the dense module and by one that doubles
    every count."""
    trace = json.loads((ROOT / "tests/chipbench/trace_v5e_decode.json")
                       .read_text())
    bench = RUN.load_bench()
    conf = json.loads((ROOT / "chipbench/configs/qwen2.5-7b-8l.json")
                      .read_text())

    def read(conf):
        arch = RUN.architecture(bench, conf)
        run = types.SimpleNamespace(
            raw_trace=trace, t0=0.0, t_close=1.0, dims=arch.dims_of(conf),
            arch=arch, kernel_calls=[(0.5, [2118, 2049], 128, 148, 8, 16)],
            peak_flops=197e12, peak_bw=819e9)
        return RUN.reader("decode_attention_roofline").read(run)

    dense = read(conf)
    assert dense > 0
    assert read(dict(conf, reference=DOUBLE_FLOPS)) == 2 * dense
