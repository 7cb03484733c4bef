"""Peak rates of the chips the benchmark runs on, and the work it counts.

Peaks are keyed by ``jax.Device.device_kind``; a device that is not in the
table is an error, never a default.  The byte count is the paper's Eqn (10)
minimum (arXiv:1611.02445): every fluid node reads and writes each of its
Q populations once per step, whatever implements the step.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
    # 819 GB/s per chip
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
}


def peak(device_kind: str) -> dict:
    """The peak rates of ``device_kind``; raises ``KeyError`` if unknown."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def eqn10_bytes(q: int, n_fluid: int, itemsize: int) -> int:
    """Eqn (10): least bytes one step moves, 2 * Q * n_fluid * itemsize."""
    return 2 * q * n_fluid * itemsize


def lbgk_flops_per_node(e) -> int:
    """Operations of one LBGK (incompressible) node update, counted from
    the formulas: rho, j, the equilibrium and the relaxation."""
    q = len(e)
    nonzero = sum(1 for v in e for c in v if c != 0)
    flops = q - 1                       # rho = sum f
    flops += 2 * nonzero - 3            # j = sum e f over nonzero e
    flops += 2 * nonzero - q + 6 * q    # e.u, then w (rho + 3eu + 4.5eu^2 - 1.5u^2)
    flops += 3                          # u.u
    flops += 3 * q                      # f + (feq - f) / tau
    return flops


def roofline_seconds(nbytes: float, flops: float, device_kind: str) -> float:
    """Least time the chip needs for ``nbytes`` and ``flops``: the larger of
    the memory and the compute bound."""
    p = peak(device_kind)
    return max(nbytes / p["hbm_bytes_per_s"], flops / p["bf16_flops_per_s"])
