"""Seconds from the start of the process to the start of the window:
imports, device start, inputs and the warm call, compilation included."""


def read(run):
    return run.setup_s
