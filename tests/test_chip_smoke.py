"""The phases of `chip_smoke.py`, run on the CPU at tiny sizes.

The script itself refuses to run without a TPU; these tests call its
phase functions directly so its control flow and checks are exercised on
every run of the suite with no chip. Only the device check is left out
of the passing path: on the CPU it must refuse.
"""
import os
import sys

import pytest

from conftest import run_forced_devices

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, REPO_ROOT)

import chip_smoke  # noqa: E402

LOG2_N = 10


@pytest.fixture(scope="module")
def graph():
    return chip_smoke.build_graph(LOG2_N)


def test_device_check_refuses_cpu():
    with pytest.raises(SystemExit) as e:
        chip_smoke.device_check()
    assert "no TPU" in str(e.value)


def test_batch_phase(graph):
    out = chip_smoke.batch_phase(graph, walks_per_node=32)
    assert out["n"] == graph.n and out["m"] == graph.m
    assert out["overflow"] == 0 and out["residual"] == 0
    assert out["l1"] < chip_smoke.L1_TOL
    assert out["topk"] >= chip_smoke.TOPK_MIN
    assert out["rounds"] > 0 and out["power_iters"] > 0


def test_serve_phase(graph):
    out = chip_smoke.serve_phase(graph, slots=2, walks_per_query=1 << 12,
                                 num_queries=4)
    assert out["queries"] == 4
    assert out["dropped"] == out["admit_dropped"] == out["rejected"] == 0
    assert out["worst_l1"] < chip_smoke.L1_TOL
    assert out["worst_topk"] >= chip_smoke.TOPK_MIN


def test_ppr_reference_matches_exact_ppr(graph):
    """The script's device-side power iteration agrees with the dense
    `exact_ppr` oracle where the latter is affordable."""
    import numpy as np
    from repro.core.personalized import exact_ppr
    solve = chip_smoke.ppr_reference(graph)
    for sources in ([5], [1, 700, 33]):
        want = np.asarray(exact_ppr(graph, chip_smoke.EPS, sources),
                          np.float64)
        np.testing.assert_allclose(solve(np.asarray(sources)), want,
                                   atol=1e-5)


def test_kernel_phase():
    out = chip_smoke.kernel_phase(log2_n=LOG2_N, walks_per_node=16)
    # interpret mode on the CPU: the kernel path draws the same counts
    assert out["bit_identical"] and out["zeta_l1"] == 0.0
    assert out["tpu_custom_calls"] == 0


def test_four_chip_phase():
    out = run_forced_devices(f"""
import json, sys
sys.path.insert(0, {REPO_ROOT!r})
import chip_smoke
out = chip_smoke.four_chip_phase(chip_smoke.build_graph({LOG2_N}),
                                 walks_per_node=16)
print(json.dumps(out))
""", devices=4)
    assert out["zeta_equal"] and out["zeta_l1"] == 0.0
    assert len(out["peak_bytes_after_4"]) == 4
