"""The chip: its peaks, the look for it, and its memory reading.

Peaks are the published ones of one TPU v5e chip (Google Cloud
documentation, "TPU v5e": 197 TFLOP/s in bf16, 16 GB of HBM at 819 GB/s),
keyed by the ``device_kind`` that JAX reports. A kind that is not in the
table is an error, never a default: a share of a guessed peak is not a
measurement.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
    "TPU v5e": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                "source": "cloud.google.com/tpu/docs/v5e"},
}


class NoChip(RuntimeError):
    """JAX found no accelerator the benchmark may report numbers for."""


def peaks_for(kind: str) -> dict:
    if kind not in PEAKS:
        raise NoChip(f"device_kind {kind!r} is not in the benchmark's peak "
                     f"table ({sorted(PEAKS)}); add it with its source")
    return PEAKS[kind]


def find_devices(chips: int, require_chip: bool = True):
    """The ``chips`` devices a cell runs on, and how to name them in the
    result. Raises :class:`NoChip` where JAX has no TPU, too few chips, or
    a kind without peaks — unless a test asked for the rest of the run
    (``require_chip=False``), which then names the CPU it ran on."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    kind = str(devices[0].device_kind)
    if require_chip:
        if platform != "tpu":
            raise NoChip(f"the benchmark measures on a TPU; JAX found "
                         f"platform {platform!r} ({kind})")
        peaks_for(kind)
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chip(s); JAX found "
                     f"{len(devices)}")
    return devices[:chips], {"platform": platform, "kind": kind,
                             "count": chips}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest device (0 where the backend keeps
    no memory statistics, as the CPU)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
