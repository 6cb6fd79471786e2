"""Process start to the first timed request: load (generate, pack,
place), cube build and warm-up, in seconds."""


def read(run):
    return run.setup_s
