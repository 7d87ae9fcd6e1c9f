"""Instances tested-then-trained in the window, over the window's wall
time: from the window's start to the block on its last chunk."""


def read(run):
    return run.instances / run.window_s
