"""The control of each comparison, at a size a test run can hold: the
plain reference in the program's place reads under the configuration's
limit at the configuration's own storage, and over it (by three times
the sound reading or more) one precision lower.  The chip's readings at
the cells' own sizes are in PERF.md (PR 24); ``benchmark/control.py``
takes them."""

import json
import os

import pytest

from benchmark import harness
from benchmark.apps import gemm, potrf


def config(name):
    with open(os.path.join(harness.ROOT, "benchmark", "configs",
                           name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 7, 3000000019])
def test_potrf_fp8_control_fails_the_limit(seed):
    cfg = config("dplasma_potrf_bf16")
    limit = cfg["limits"]["offdiag_resid"]
    traffic = {"n": 2048, "mb": 256}
    sound = potrf.control(cfg, traffic, seed, "config")["offdiag_resid"]
    lower = potrf.control(cfg, traffic, seed, "fp8")["offdiag_resid"]
    assert sound < limit < lower and lower >= 3 * sound


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 7, 3000000019])
def test_gemm_fp8_control_fails_the_limit(seed):
    cfg = config("dplasma_gemm_bf16")
    limit = cfg["limits"]["c_rel_err"]
    traffic = {"m": 256, "n": 256, "k": 512, "mb": 128}
    assert gemm.control(cfg, traffic, seed, "config")["c_rel_err"] == 0.0
    assert gemm.control(cfg, traffic, seed, "fp8")["c_rel_err"] > 3 * limit
