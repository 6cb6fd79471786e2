"""Seconds of set-up that none of the program's step timers covers:
``setup_s`` less the sum of ``TPCHDriver.load_seconds`` (its steps are
disjoint).  That is the imports, JAX's start-up, the warm-up executions
themselves, and any step that is not timed.  Nothing where the program
does not time each step of ``STEPS``: the rest would then hold them."""

STEPS = ("generate", "pack", "place", "catalog", "compile")


def read(run):
    if any(step not in run.load_seconds for step in STEPS):
        return None
    return run.setup_s - sum(run.load_seconds.values())
