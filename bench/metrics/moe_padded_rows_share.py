"""Share of the rows the held experts' grouped matmuls compute that hold no
routed pair, in percent: 1 - (token-expert pairs kept by the held experts)
/ (rows computed, the device budget's), summed over the MoE layers and local steps of the cell's check
rounds, as the program counts them in its round metrics. Nothing to read
from a program or a system that does not count them."""


def read(run):
    rows = getattr(run.cell, "moe_rows", None)
    if not rows or not rows["buffer"]:
        return None
    return 100.0 * (1.0 - rows["kept"] / rows["buffer"])
