# Pallas TPU kernels for the compute hot-spots of the Sieve runtime:
#   fused_swiglu     — single-pass SwiGLU, gate/up/down in one kernel: the
#                      grouped MXU head for popular experts (paper §6.3) and
#                      the streaming GEMV tail for 1-token experts (§6.2)
#   decode_attention — the memory-bound decode attention (paper §2.2)
# ops.py holds the jit'd wrappers; ref.py the pure-jnp oracles.

from . import ops, ref  # noqa: F401
