"""Share of the traced window in which the chip ran no operation, in %,
in the power cell: 1 minus the union of device-op intervals over the
window."""
from bench.trace_reduce import idle_pct


def read(run):
    return idle_pct(run.trace)
