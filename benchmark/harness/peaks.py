"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports.  A device that is not in the table is an error,
never a default: a utilisation against a guessed peak is worse than none."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float        # FLOP/s, dense bf16
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


_V5E = Peaks(
    flops_bf16=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
    source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
           '16 GB HBM2e at 819 GB/s per chip')

PEAKS = {
    "TPU v5 lite": _V5E,    # what a v5e reports (chip run, PR 21)
    "TPU v5e": _V5E,
}


class UnknownDevice(LookupError):
    pass


def peaks_of(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no peaks known for device_kind {device_kind!r}; add it to "
            f"benchmark/harness/peaks.py with its source") from None
