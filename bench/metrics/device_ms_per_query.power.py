"""Device busy time per request of the traced window, in ms: the union
of the chip's operation intervals over the requests that ran in it (all
the kernels of one request, and nothing of the host)."""


def read(run):
    if not run.trace or not run.records or run.trace["busy_s"] <= 0:
        return None
    return run.trace["busy_s"] / len(run.records) * 1e3
