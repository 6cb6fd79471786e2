"""Host seconds of set-up the program spent compiling
(``TPCHDriver.load_seconds["compile"]``): lowering each prepared shape,
and the first dispatch of each of its specializations, which traces the
plan and compiles it or loads it from the persistent cache."""


def read(run):
    return run.load_seconds.get("compile")
