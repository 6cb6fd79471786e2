"""Published peaks of each accelerator the benchmark runs on, keyed by
``jax.Device.device_kind``.  A roofline share is measured against these,
never against a rate the program measured itself.  A device that is not
here is an error, not a default."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    bf16_flops: float      # FLOP/s
    int8_ops: float        # OP/s
    hbm_bytes_s: float     # bytes/s
    hbm_bytes: float       # bytes of device memory
    source: str


PEAKS = {
    "TPU v5 lite": Peak(bf16_flops=197e12, int8_ops=393e12,
                        hbm_bytes_s=819e9, hbm_bytes=16e9,
                        source='Google Cloud documentation, "TPU v5e"'),
}


class UnknownDeviceError(KeyError):
    """No published peaks for this device kind."""


def for_kind(kind: str) -> Peak:
    try:
        return PEAKS[kind]
    except KeyError:
        raise UnknownDeviceError(
            f"no published peaks for device kind {kind!r}; add them to "
            f"bench/peaks.py with their source") from None
