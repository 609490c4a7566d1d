"""Host seconds in ``designs.generate`` (the front door's plan-time
work and gates)."""


def read(run):
    return run.generate_s
