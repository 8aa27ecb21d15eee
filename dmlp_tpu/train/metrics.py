"""Throughput and MFU accounting (BASELINE.json metric: samples/sec/chip).

The reference's only metric is wall-clock ms (common.cpp:130); the training
extension reports the driver-requested rates on top: samples/sec/chip and
model FLOPs utilization, using the standard 6 * batch * matmul-params
estimate for fwd+bwd FLOPs (2 fwd + 4 bwd per weight element per example).
"""

from __future__ import annotations

from typing import Optional

import jax

from dmlp_tpu.train.model import num_matmul_params

# Peak dense (bf16) FLOP/s per chip by PJRT device kind prefix. Source:
# Google Cloud TPU documentation, per-generation system architecture
# pages ("TPU v5e": 197 TFLOP/s bf16 per chip; jax reports that chip as
# device_kind "TPU v5 lite"). A kind that is not listed is an error,
# not a default: a utilisation divided by a guessed peak is not a
# measurement.
PEAK_FLOPS_BY_KIND = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6": 918e12,
}


class UnknownDeviceKind(LookupError):
    """The device's kind has no entry in PEAK_FLOPS_BY_KIND."""


def peak_flops_for_kind(kind: str) -> Optional[float]:
    """The table's peak for a ``device_kind`` string, None if unlisted."""
    return next((peak for prefix, peak in PEAK_FLOPS_BY_KIND.items()
                 if kind.startswith(prefix)), None)


def peak_flops_per_chip(device: Optional[jax.Device] = None) -> float:
    kind = (device or jax.devices()[0]).device_kind
    peak = peak_flops_for_kind(kind)
    if peak is None:
        raise UnknownDeviceKind(
            f"no peak FLOP/s known for device kind {kind!r}; add it to "
            "train.metrics.PEAK_FLOPS_BY_KIND with its source")
    return peak


def train_step_flops(params, batch_size: int) -> float:
    """~FLOPs of one fwd+bwd step (6 per weight element per example)."""
    return 6.0 * batch_size * num_matmul_params(params)


def throughput_metrics(params, batch_size: int, step_time_s: float,
                       n_chips: int,
                       peak_per_chip: Optional[float] = None) -> dict:
    """Rates of one step; ``mfu`` only where the device's peak is known
    (the train loop also logs on CPU, where there is a rate and no
    utilisation)."""
    samples_per_sec = batch_size / step_time_s
    flops = train_step_flops(params, batch_size)
    out = {
        "samples_per_sec": samples_per_sec,
        "samples_per_sec_per_chip": samples_per_sec / n_chips,
        "step_time_ms": step_time_s * 1e3,
        "model_flops_per_step": flops,
    }
    try:
        peak = (peak_per_chip if peak_per_chip is not None
                else peak_flops_per_chip())
    except UnknownDeviceKind:
        return out
    out["mfu"] = flops / (step_time_s * n_chips * peak)
    return out
