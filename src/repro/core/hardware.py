"""Hardware calibrations for the perf model (paper Table 4 parameters).

The paper profiles these on Ascend 910c; we provide:
  - TPU_V5E: analytic calibration from the assignment's roofline constants
    (197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI) with standard
    achievable-fraction deratings (MXU GEMM ~85 %, attention ~60/40 %).
    Used by the cluster simulator and the roofline analysis.
  - ASCEND_910C: the paper's platform, reconstructed from public numbers
    (A100-class: ~400 TFLOP/s fp16 per chip in the 910c dual-die package ->
    ~each die ≈ A100) — used to sanity-check Figure 3 shapes.
  - cpu_measured(): fitted from timed engine runs on a CPU host
    (benchmarks/bench_perfmodel_accuracy.py writes the fit).

``for_device`` picks the calibration for the device a runtime serves on:
accelerators by their ``device_kind`` (a kind missing from
``DEVICE_KINDS`` is an error, never a silent default), ``cpu_measured()``
only on the CPU platform.
"""
from __future__ import annotations

from repro.core.perf_model import HardwareParams

TPU_V5E = HardwareParams(
    name="tpu_v5e",
    F_g=197e12 * 0.85,
    F_ap=197e12 * 0.60,
    F_ad=197e12 * 0.40,
    M_g=819e9 * 0.80,
    M_a=819e9 * 0.70,
    O_p=8e-3,
    O_d=4e-3,
    B_c=50e9 * 0.80,          # one ICI link direction, 80 % efficiency
    hbm_capacity=16e9,
    peak_flops=197e12,
    peak_hbm_bw=819e9,
)

ASCEND_910C = HardwareParams(
    name="ascend_910c",
    F_g=320e12 * 0.75,        # per chip (dual-die), bf16, A100-SXM class
    F_ap=320e12 * 0.55,
    F_ad=320e12 * 0.35,
    M_g=1.6e12 * 0.75,
    M_a=1.6e12 * 0.65,
    O_p=10e-3,                # paper: xLLM prefill runtime overhead
    O_d=4e-3,
    B_c=100e9,                # RDMA KV-transfer effective bandwidth
    hbm_capacity=64e9,
    peak_flops=320e12,
    peak_hbm_bw=1.6e12,
)


def cpu_measured(F: float = 50e9, M: float = 10e9, O_p: float = 30e-3,
                 O_d: float = 8e-3) -> HardwareParams:
    """Container-CPU calibration; defaults are rough, the accuracy benchmark
    fits them from measured prefill/decode timings."""
    return HardwareParams(
        name="cpu", F_g=F, F_ap=F * 0.7, F_ad=F * 0.5, M_g=M, M_a=M,
        O_p=O_p, O_d=O_d, B_c=1e9, hbm_capacity=8e9, peak_flops=F,
        peak_hbm_bw=M)


# jax ``Device.device_kind`` -> calibration
DEVICE_KINDS = {
    "TPU v5 lite": TPU_V5E,
}


def for_device(device) -> HardwareParams:
    """Roofline calibration for a jax device (see module docstring)."""
    if device.platform == "cpu":
        return cpu_measured()
    try:
        return DEVICE_KINDS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no hardware calibration for {device.platform} device kind "
            f"{device.device_kind!r}; known kinds: {sorted(DEVICE_KINDS)}"
        ) from None
