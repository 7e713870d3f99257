"""The device a run is on: required, named, and its published peaks."""

from __future__ import annotations

from typing import Any, Dict, List

# Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.
# Source: Google Cloud documentation, "TPU v5e" system architecture: 197
# TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s of
# chip-to-chip interconnect. A peak measured by the program under test is
# not a yardstick, so none is.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 200e9,
    },
}


class NoAccelerator(SystemExit):
    """Raised (as a non-zero exit with no result line) when the machine
    does not hold the chips the cell asks for."""


def peaks(device_kind: str) -> Dict[str, float]:
    """The peaks table's row; a device that is not in it is an error,
    never a default."""
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; the "
            f"table holds {sorted(PEAKS)}. Add the chip with its source "
            "before reporting a roofline share on it.")
    return PEAKS[device_kind]


def require_tpu(chips: int) -> List[Any]:
    """The first ``chips`` TPU devices, or a non-zero exit: there is no
    CPU configuration of the yardstick."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(
            f"yardstick measures a TPU; JAX found platform "
            f"{devs[0].platform!r} ({devs[0].device_kind}). Not run.")
    if len(devs) < chips:
        raise NoAccelerator(
            f"this cell needs {chips} chip(s); JAX found {len(devs)}. "
            "Not run.")
    return devs[:chips]


def peak_bytes(dev) -> int:
    """The most one chip held. The TPU runtime keeps two accounts:
    ``peak_bytes_in_use`` for live buffers (parameters, optimizer state,
    staged batches, results) and ``peak_bytes_reserved`` for the
    temporaries of the programs it runs, which it reserves apart and
    which for a train step are most of the memory (4.4 GB of 5.7 GB for
    ResNet-50 at batch 1536; ``bytes_limit`` less both is what it reports
    free). The chip holds both, so both are counted."""
    stats = dev.memory_stats()
    return int(stats["peak_bytes_in_use"]) + int(
        stats.get("peak_bytes_reserved", 0))


def describe(devices: List[Any]) -> Dict[str, Any]:
    """``device`` of the result line: what JAX reports, and the peak
    bytes held on the fullest of the chips the cell used."""
    import jax
    peak = max(peak_bytes(d) for d in devices)
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": jax.device_count(),
            "memory_peak_bytes": peak}
