"""TPU kernel layer (Pallas).

Hand-written kernels for the ops where XLA's default lowering leaves MXU/HBM
performance on the table.  The dispatcher runs a kernel on a TPU and its
pure-jax reference on the CPU backend; tests run the kernels themselves in
Pallas interpret mode by asking for it.
"""

from .attention import attention, flash_attention, merge_attention  # noqa: F401
