"""Names of the federated round's layers in a profiler trace.

Device scopes (``jax.named_scope``) name the ops of the jitted round: the
name is written into each op's metadata at compile time and costs nothing
when the program runs. Host spans (``jax.profiler.TraceAnnotation``) mark
the host's entry points and record nothing unless a profiler session is
open. ``bench/scopes.py`` reads both from a trace.

A scope named ``<parent>.<child>`` is entered inside ``<parent>``, and a
parent's time holds its children's.
"""

# device scopes
LOCAL_STEPS = "fl.local_steps"     # step counts, batches, SGD steps
EXCHANGE = "fl.exchange"           # encode, decode, rotations, averaging
NOISE = "fl.exchange.noise"        # the exchange's sign and dither draws
POPULATION = "fl.population"       # the population store's row traffic
SCOPES = (LOCAL_STEPS, EXCHANGE, NOISE, POPULATION)
# the model's blocks inside the local steps (forward and backward); not in
# SCOPES, whose readers pin its four layers
MLA = "fl.local_steps.mla"         # latent attention
MOE = "fl.local_steps.moe"         # router, dispatch, experts, combine

# host spans
ROUND = "fl.round"                 # one eager round's dispatch
CHUNK = "fl.chunk"                 # one scanned chunk's dispatch
SYNC = "fl.sync"                   # simulate() reading metrics on the host
EVAL = "fl.eval"                   # simulate()'s eval_fn
HOST_SPANS = (ROUND, CHUNK, SYNC, EVAL)
