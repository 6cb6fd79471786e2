"""Host seconds the program spent generating the data at load
(``TPCHDriver.load_seconds["generate"]``)."""


def read(run):
    return run.load_seconds.get("generate")
