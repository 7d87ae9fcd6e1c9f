"""Set-up: from the start of the run to the start of the window --
imports, device start, compile or cache load, warm-up of the cell's own
shapes."""


def read(run):
    return run.setup_s
