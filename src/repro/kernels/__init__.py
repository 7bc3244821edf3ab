# Pallas kernels for the paper's compute hot-spots, all swept against the
# pure-jnp oracles in ref.py (tests/test_kernels.py):
#   exchange.py      — fused rotated-space exchange (rotate+round+wrap /
#                      snap+inverse-rotate), batched over messages; the
#                      production path via repro.compression.pipeline
#   flash_attention.py — attention tile for the model substrate
#   geometry.py      — Hadamard block geometry + fp32 matmul, shared with
#                      repro.compression.rotation (imports nothing of repro)
