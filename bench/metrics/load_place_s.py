"""Host seconds the program spent placing the resident columns on the
devices, until they are ready (``TPCHDriver.load_seconds["place"]``)."""


def read(run):
    return run.load_seconds.get("place")
