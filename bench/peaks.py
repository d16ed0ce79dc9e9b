"""Published peaks of one chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s (bf16) and 819 GB/s of HBM bandwidth per chip.  The chip
has no float64 unit, so the float64 work of the interior point is far
from the compute peak by construction; the table keeps the published
numbers, not a derated guess.  A device that is not here is an error,
not a default.
"""

from __future__ import annotations

PEAKS = {
    # device_kind as JAX reports it on a v5e
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    """``{"flops_per_s", "bytes_per_s"}`` of one ``device_kind`` chip."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to {__name__}") from None


def roofline_share(flops: float, nbytes: float, busy_s: float,
                   device_kind: str) -> tuple[float, str]:
    """Least time for the work over ``busy_s``, in %, and what bounds it."""
    pk = peaks(device_kind)
    t_flops, t_bytes = flops / pk["flops_per_s"], nbytes / pk["bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "bandwidth"
    return 100.0 * max(t_flops, t_bytes) / busy_s, bound
