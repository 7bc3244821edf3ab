"""Host milliseconds per round spent inside the call into the program's
entry point (``round`` or ``run_chunk``) until it returns, before any
wait: the harness's ``dispatch`` span."""


def read(run):
    return 1e3 * sum(run.window.dispatch_s) / run.window.rounds
