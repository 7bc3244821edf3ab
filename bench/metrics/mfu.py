"""Model FLOP utilization of the round, in percent: the configuration's
model FLOPs per round (forward and backward, recomputation not counted)
times the rounds of the window, over the window and the chip's bf16 peak
from ``bench/peaks.json``."""


def read(run):
    flops = getattr(run.cell, "flops_per_round", None)
    if not flops or run.peak is None:
        return None
    rounds = run.trace["rounds"] if run.trace else run.window.rounds
    window = run.trace["window_s"] if run.trace else run.window.window_s
    return 100.0 * flops * rounds / window / run.peak["bf16_flops"]
