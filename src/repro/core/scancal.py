"""Latency calibration for the scan-strategy choice (compressed residency).

Sequential scans are bandwidth-bound ("Micro-architectural Analysis of
OLAP"): packing a column at ``width`` bits streams ``width/32`` of the raw
bytes but pays lane-parallel ALU work to test predicates in code space.
This module is :mod:`repro.core.wirecal`'s sibling for the MEMORY
hierarchy — three machine rates and a roofline over them decide, per
scanned column, whether to evaluate the predicate on packed words or to
decode the column and filter raw:

  ``packed_ms = packed_bytes / mem_GBps + rows / scan_gvps``
  ``decode_ms = packed_bytes / mem_GBps + rows / unpack_gvps
              + raw_bytes / mem_GBps``       (write + re-read decoded)

Packed wins when the saved bandwidth (raw bytes never streamed) exceeds
the extra ALU cost of the in-place code test — the same
codec-must-outrun-the-medium discipline the wire chooser applies to the
network.  The crossover is a property of the MACHINE, so the rates live
in one table keyed by ``device_kind`` (``jax.Device.device_kind``), each
entry recording where its numbers came from.  ``python -m
repro.core.scancal`` measures the rates of the device it runs on and
prints the entry to add; a device kind missing from the table is an
error, not a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ScanCalibration:
    """Machine rates of the scan roofline (GB/s and Gvalues/s).

    ``mem_gbps``: resident-column streaming bandwidth.  ``scan_gvps``:
    predicate-on-packed throughput (values tested per second, SWAR
    kernel).  ``unpack_gvps``: full-column unpack throughput."""

    mem_gbps: float
    scan_gvps: float
    unpack_gvps: float
    source: str


TABLE = {
    # CPU rehearsals (XLA's host backend): assumed rates of the paper's
    # bandwidth-bound nodes, memory far slower than the vector units
    "cpu": ScanCalibration(
        mem_gbps=6.0, scan_gvps=4.0, unpack_gvps=4.0,
        source="assumed: paper-era bandwidth-bound node, CPU rehearsals"),
    "TPU v5 lite": ScanCalibration(
        mem_gbps=283.5246371914444, scan_gvps=7.3719894591148,
        unpack_gvps=5.3590444049601285,
        source=("measured: python -m repro.core.scancal on one TPU v5 lite "
                "(rows=67108864, width=12, best of 20), 2026-10-16")),
}


class ScanCalError(RuntimeError):
    """No calibration entry exists for the device kind in use."""


def for_device(device_kind: str) -> ScanCalibration:
    """The table entry for ``device_kind``; raises :class:`ScanCalError`
    for a device that was never calibrated."""
    try:
        return TABLE[device_kind]
    except KeyError:
        raise ScanCalError(
            f"no scan calibration for device kind {device_kind!r} "
            f"(known: {sorted(TABLE)}); measure it with `python -m "
            f"repro.core.scancal` on that device and add the printed entry "
            f"to repro.core.scancal.TABLE") from None


# ---------------------------------------------------------------------------
# roofline predictors (ms; bytes / GBps / 1e6 == ms, rows / Gvps / 1e6 == ms)
# ---------------------------------------------------------------------------


def packed_scan_bytes(rows: int, width: int) -> int:
    """Bytes streamed by predicate-on-packed: the packed words plus the
    emitted validity bitset."""
    from repro.core import compression

    return (compression.packed_words(rows, width)
            + compression.bitset_words(rows)) * 4


def decode_scan_bytes(rows: int, width: int, itemsize: int = 4) -> int:
    """Bytes touched by decode-then-filter: packed words in, decoded
    column out + re-read, bitset out."""
    from repro.core import compression

    return (compression.packed_words(rows, width) * 4
            + 2 * rows * itemsize + compression.bitset_words(rows) * 4)


def predict_packed_ms(rows: int, width: int, *,
                      cal: ScanCalibration) -> float:
    return (packed_scan_bytes(rows, width) / (cal.mem_gbps * 1e6)
            + rows / (cal.scan_gvps * 1e6))


def predict_decode_ms(rows: int, width: int, itemsize: int = 4, *,
                      cal: ScanCalibration) -> float:
    return (decode_scan_bytes(rows, width, itemsize) / (cal.mem_gbps * 1e6)
            + rows / (cal.unpack_gvps * 1e6))


def choose_scan_mode(rows: int, width: int, itemsize: int = 4, *,
                     cal: ScanCalibration) -> str:
    """'packed' iff the roofline predicts the in-place code-space test is
    at least as fast as decoding the column and filtering raw."""
    packed = predict_packed_ms(rows, width, cal=cal)
    decode = predict_decode_ms(rows, width, itemsize, cal=cal)
    return "packed" if packed <= decode else "decode"


# ---------------------------------------------------------------------------
# calibration (run once per device kind)
# ---------------------------------------------------------------------------


def calibrate(*, rows: int = 1 << 20, width: int = 12,
              repeat: int = 20) -> ScanCalibration:
    """Measure streaming bandwidth, the jit'd predicate-on-packed kernel,
    and the full unpack on a representative shape, on the default
    device."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import compression
    from repro.kernels import ops

    padded = -(-rows // 32) * 32
    rng = np.random.default_rng(0)
    codes = jnp.asarray(
        rng.integers(0, 1 << width, size=padded).astype(np.uint32))
    words = compression.pack_bits(codes, width)
    raw = codes.astype(jnp.int32)

    stream = jax.jit(jnp.sum)
    unpack = jax.jit(lambda w: compression.unpack_bits(w, padded, width))
    jax.block_until_ready(stream(raw))
    jax.block_until_ready(unpack(words))
    jax.block_until_ready(ops.scan_filter(
        words, 1, 100, rows=rows, padded_rows=padded, width=width))

    def best(fn):
        times = []
        for _ in range(repeat):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            times.append(time.perf_counter() - t0)
        return min(times)

    t_mem = best(lambda: stream(raw))
    t_scan = best(lambda: ops.scan_filter(
        words, 1, 100, rows=rows, padded_rows=padded, width=width))
    t_unpack = best(lambda: unpack(words))
    dev = jax.devices()[0]
    return ScanCalibration(
        mem_gbps=rows * 4 / t_mem / 1e9,
        scan_gvps=rows / t_scan / 1e9,
        unpack_gvps=rows / t_unpack / 1e9,
        source=(f"measured: python -m repro.core.scancal on one "
                f"{dev.device_kind} (rows={rows}, width={width}, "
                f"best of {repeat})"),
    )


def main(argv=None) -> int:
    import argparse

    import jax

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=1 << 20)
    ap.add_argument("--width", type=int, default=12)
    ap.add_argument("--repeat", type=int, default=20)
    args = ap.parse_args(argv)
    cal = calibrate(rows=args.rows, width=args.width, repeat=args.repeat)
    print(f"{jax.devices()[0].device_kind!r}: {cal!r},")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
