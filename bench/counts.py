"""The table of chip peaks that operations and bytes are divided by.

The operations and bytes themselves come from shapes alone, by each
architecture's ``dims`` (``bench/models/<name>.py``), which follows that
architecture's plain reference and not the program's kernels.
"""
from __future__ import annotations

#: Published peaks per chip, keyed by ``device_kind`` as JAX reports it.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2**30,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                  "16 GB HBM at 819 GB/s per chip",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peaks of one chip; a kind not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None

