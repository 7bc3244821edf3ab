"""95th percentile over every round of the window of the time between a
round's completion and the previous one's (a call of several rounds
shares its time out evenly)."""
import numpy as np


def read(run):
    return float(np.percentile(run.window.round_times, 95))
