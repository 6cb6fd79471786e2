"""Share of the HBM roofline that the packed scan kernel
(``kernels/scan_filter.py``) reaches, in %: the bytes its calls must move
over their summed device time, against the chip's published bandwidth.

The kernel is memory-bound: per call it reads one packed column
(``padded_rows * width / 32`` uint32 words) and writes one bitset word per
32 rows.  The bytes are computed here from the TPC-H column domains and
the row counts of the deployment, not taken from the program: each
request of the traced window adds the scans its query needs
(``SCANS``).  When the number of scan-kernel events in the trace is not
the number those requests need, the bytes and the events describe
different work, and the reader reports nothing.
"""
from __future__ import annotations

import math

from bench import peaks
from bench.reference.tpch_data import day, table_sizes

# Bits of each scanned column's code: the span of its TPC-H domain
# (frame of reference) or its dictionary size.
WIDTHS = {
    # ship date = order date (< 1998-08-02) + 1..121 days
    ("lineitem", "l_shipdate"): (day(1998, 8, 2) - 1 + 121 - 1).bit_length(),
    ("lineitem", "l_discount"): (11 - 1).bit_length(),   # 0.00..0.10
    ("lineitem", "l_quantity"): (50 - 1).bit_length(),   # 1..50
    ("orders", "o_orderdate"): (day(1998, 8, 2) - 1).bit_length(),
}

# Columns each query of the cells filters with the packed scan kernel.
SCANS = {
    "q1": [("lineitem", "l_shipdate")],
    "q1_offedge": [("lineitem", "l_shipdate")],
    "q6": [("lineitem", "l_shipdate"), ("lineitem", "l_discount"),
           ("lineitem", "l_quantity")],
    "q14_promo": [("lineitem", "l_shipdate")],
    "q4": [("orders", "o_orderdate")],
    "q18": [],
}

# The kernel's device events are its custom calls, named after the jitted
# wrapper: "%_scan_filter.<n> = u32[1,<groups>] custom-call(...)".
KERNEL = "%_scan_filter"


def is_kernel(event_name: str) -> bool:
    return event_name.split(" = ", 1)[0].startswith(KERNEL)


def scan_bytes(rows: int, width: int) -> int:
    """Bytes one scan-kernel call moves over ``rows`` rows at ``width``
    bits: the packed words read plus the bitset words written."""
    groups = math.ceil(rows / 32)
    return groups * width * 4 + groups * 4


def request_bytes(name: str, sf: float, nodes: int) -> tuple:
    """(bytes, kernel calls) of one request of ``name`` on one node."""
    sizes = table_sizes(sf, nodes)
    scans = SCANS[name]
    return (sum(scan_bytes(sizes[t] // nodes, WIDTHS[(t, c)])
                for t, c in scans), len(scans))


def read(run):
    if not run.trace:
        return None
    events = [v for k, v in run.trace["ops"].items() if is_kernel(k)]
    calls = sum(n for n, _ in events)
    seconds = sum(t for _, t in events)
    want_bytes = want_calls = 0
    for r in run.records:
        b, c = request_bytes(r.name, run.config["scale_factor"],
                             run.config["nodes"])
        want_bytes += b
        want_calls += c
    if not calls or calls != want_calls or seconds <= 0:
        return None
    bw = peaks.for_kind(run.device_kind).hbm_bytes_s
    return 100.0 * want_bytes / seconds / bw
