"""Published peaks of the chips the benchmark runs on, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16 and 394 TOP/s int8 per chip, 16 GiB of HBM2 at 819 GB/s,
1,600 Gbit/s of inter-chip interconnect per chip.  JAX reports a v5e chip as
``device_kind == "TPU v5 lite"``.  A kind missing from this table is an error:
a share of a peak is never computed against a guessed peak.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "source": "Google Cloud, TPU v5e",
        "bf16_flops": 197e12,
        "int8_ops": 394e12,
        "hbm_bytes": 16 * 2**30,
        "hbm_bw": 819e9,
        "ici_bw": 1600e9 / 8,  # bytes/s per chip, all links together
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; "
            f"bench/peaks.py knows {sorted(PEAKS)}"
        ) from None
