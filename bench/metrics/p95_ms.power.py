"""95th-percentile service time of the power cell's requests, in ms
(closed loop: a request is due when the one before it is answered): the
exact order statistic over every request of the window, never a median
of chunks; a request never answered counts as infinitely late."""
from bench.traffic.common import latency_ms


def read(run):
    return latency_ms(run.records, 0.95)
