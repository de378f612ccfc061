"""Published peaks per chip, keyed by ``device_kind`` as JAX reports it.

A device missing from this table is an error: no roofline or utilization
is computed against a guessed peak.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,        # bf16 matrix unit
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' system architecture",
    },
}


def lookup(device_kind: str) -> dict:
    """The peaks of ``device_kind``; ``KeyError`` where it is not listed."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it to "
                       f"bench/peaks.py with its source") from None
