"""Seconds from process start to the opening of the window: loading,
weights and inputs, compiling or loading the program, the check rounds."""


def read(run):
    return run.setup_s
