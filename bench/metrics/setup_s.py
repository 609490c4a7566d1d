"""Process start to the window: imports, generate(), operands, compile
or cache load, and the warm-up calls."""


def read(run):
    return run.setup_s
