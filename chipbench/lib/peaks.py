"""The benchmark's own table of published hardware peaks, keyed by
``jax.devices()[0].device_kind``.  It is a copy on purpose: the yardstick
may not move with ``paddle_tpu.analysis.cost_model.DEVICE_PEAKS``.  A kind
that is not here is an error, never a default."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    kind: str
    bf16_flops: float       # FLOP/s per chip
    hbm_bytes_s: float      # bytes/s per chip
    ici_bytes_s: float      # bytes/s chip to chip
    hbm_bytes: float        # bytes per chip
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        kind="TPU v5 lite", bf16_flops=197e12, hbm_bytes_s=819e9,
        ici_bytes_s=200e9, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip "
               "interconnect"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise LookupError(
            f"no published peaks for device_kind {device_kind!r}: add a row "
            f"with its source to chipbench/lib/peaks.py "
            f"(known: {sorted(PEAKS)})") from None
