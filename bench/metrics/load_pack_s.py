"""Host seconds the program spent packing the columns at load
(``TPCHDriver.load_seconds["pack"]``)."""


def read(run):
    return run.load_seconds.get("pack")
