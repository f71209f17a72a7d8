"""Pallas TPU kernels for CD-BFL's compute hot-spots.

* pack — the paper's Q (block top-k sparsification) packed straight into
  the wire format by VMEM-tile-local threshold bisection (no sort), and
  its unpack; the bisection lives in block_topk.
* fused_compress — the pack run on θ − v formed in VMEM, and the QSGD
  quantization (paper ref [26]) of the packed carrier.
* fused_update — paper Eq. 9 (consensus correction + Langevin noise) in one
  memory-bound pass.
* flash_attention — causal GQA attention for the LM training path, forward
  and backward, with each block's scores kept in VMEM.

ops.py: jit'd wrappers (padding/tiling); ref.py: pure-jnp oracles.
The wrappers take their mode from :func:`interpret_mode`: compiled by
Mosaic on a TPU, the Pallas interpreter on every other backend. Only the
low-level ``*_pallas`` functions take ``interpret`` explicitly, so tests
can compile them for a described TPU from a CPU host.
"""
import jax


def interpret_mode() -> bool:
    """True exactly when the default backend is not a TPU."""
    return jax.default_backend() != "tpu"


# after interpret_mode: ops imports it from this package
from repro.kernels import ops, ref  # noqa: E402,F401
