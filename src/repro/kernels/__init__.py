"""Pallas TPU kernels for the performance-critical compute layers.

Each kernel directory contains:
  kernel.py — pl.pallas_call with explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper (padding, dtype dispatch, cost hints)
  ref.py    — pure-jnp oracle used by tests/test_kernels_*.py

Kernels:
  binary_ip       RaBitQ level-1: query x packed 1-bit codes as a sign GEMM
                  on the MXU (the TPU-native replacement for popcount Hamming)
  int4_dist       RaBitQ level-2: packed 4-bit dequant + squared-L2 refine
  flash_attention LM prefill attention (causal / sliding window / bidir, GQA)
  paged_attention LM decode through a record-level KV block table — the
                  paper's record_map indirection applied to the KV cache

Every kernel entry point takes ``interpret: bool | None = None`` and resolves
it through ``interpret_mode``: the Pallas interpreter runs only where JAX's
default backend is the CPU, which has no Pallas TPU lowering.  An explicit
value overrides (compile-for-TPU tests pass False; timing harnesses pass True
to compare against the compiled kernel).
"""

import jax


def interpret_mode(interpret: bool | None = None) -> bool:
    """Whether a Pallas call runs in interpret mode: ``interpret`` when
    given, else True exactly when ``jax.default_backend() == "cpu"``."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)
