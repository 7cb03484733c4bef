"""The peak table and the Eqn (10) byte count."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, peaks, reference  # noqa: E402


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        peaks.peak("cpu")
    with pytest.raises(KeyError):
        peaks.roofline_seconds(1.0, 1.0, "TPU v9 imaginary")


def test_v5e_peaks():
    p = peaks.peak("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12


@pytest.mark.parametrize("config", [c["name"] for c in
                                    harness.load_benchmark(ROOT)["configs"]])
def test_eqn10_bytes_of_each_configuration(config):
    bench = harness.load_benchmark(ROOT)
    cfg = harness.config(ROOT, bench, config)
    n = cfg["expected"]["fluid_nodes"]
    q = len(reference.lattice(cfg["lattice"])[0])
    assert q == 19
    assert peaks.eqn10_bytes(q, n, 4) == 2 * 19 * n * 4


def test_eqn10_bytes_of_the_sphere_pack():
    # 1.785 GB per step for the 11,742,643 fluid nodes of the sphere pack
    assert peaks.eqn10_bytes(19, 11_742_643, 4) == 1_784_881_736


def test_lbgk_flops_per_node_d3q19():
    e, _, _ = reference.lattice("D3Q19")
    # 30 non-zero velocity components: rho 18, j 2*30-3, equilibrium
    # 2*30-19+6*19, u.u 3, relaxation 3*19
    assert peaks.lbgk_flops_per_node(e) == 18 + 57 + 155 + 3 + 57
