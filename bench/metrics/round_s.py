"""Window seconds over the rounds completed in the window."""


def read(run):
    return run.window.window_s / run.window.rounds
