"""A tiny configuration and mixes that run the chip benchmark's harness on
the CPU, with the device check steered to a fake peaks entry."""
from __future__ import annotations

import copy
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import run as RUN  # noqa: E402

CELLS = {"reprefill": "qwen2.5-7b-8l.reprefill",
         "decode": "qwen2.5-7b-8l.decode"}

MIXES = {
    "reprefill": {"prefix_tokens": 256, "suffix_tokens": [8, 16],
                  "decode_tokens": [0], "arrival": "poisson",
                  "rate_per_s": 4.0, "max_concurrency": 2},
    "decode": {"prefix_tokens": 256, "suffix_tokens": [8],
               "decode_tokens": [6], "arrival": "backlog",
               "backlog_per_s": 2.0, "max_concurrency": 3},
}

# limits for the tiny configuration on the CPU: sound runs read a logit
# error of 0.006-0.018 and a selection gap under 2e-3; the fp8 control reads
# a logit error of 0.09-0.2 (tests/chipbench/test_chipbench_faults.py)
TINY_LIMITS = {"logit_err": 0.05, "selection_gap": 0.02, "not_greedy": 0}

FAKE_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1,
               "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}


def tiny_conf() -> dict:
    conf = json.loads((ROOT / "chipbench/configs/qwen2.5-7b-8l.json")
                      .read_text())
    conf.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                num_key_value_heads=2, head_dim=16, num_hidden_layers=4,
                vocab_size=256, limits=dict(TINY_LIMITS))
    conf["system"].update(period=2, subperiod=1, device_cap=8, host_cap=16)
    return conf


def steer(monkeypatch, kind: str = "reprefill", conf: dict = None,
          **mix_keys):
    """Point the harness at the tiny configuration (or `conf`) and a fake
    device; returns the cell name to run."""
    conf = tiny_conf() if conf is None else conf
    mix = dict(copy.deepcopy(MIXES[kind]), **mix_keys)
    real = RUN.resolve

    def resolve(bench, workload, trace):
        cell, _, _, metrics, _ = real(bench, workload, trace)
        return cell, conf, mix, metrics, RUN.architecture(bench, conf)

    monkeypatch.setattr(RUN, "resolve", resolve)
    monkeypatch.setattr(RUN, "device_info", lambda chips: dict(FAKE_DEVICE))
    return CELLS[kind]
