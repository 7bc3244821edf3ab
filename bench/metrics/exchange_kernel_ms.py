"""Device milliseconds per round in the Pallas kernels. On the measured
paths the only Pallas kernels are the exchange's (flash attention is off),
so this is the exchange kernels' time. Nothing to read if the trace holds
no Pallas kernel."""


def read(run):
    if run.trace is None or not run.trace["kernel_calls"]:
        return None
    return 1e3 * run.trace["kernel_s"] / run.trace["rounds"]
