"""Pallas TPU kernels for the compute hot-spots the paper optimizes.

Four kernels, each with a pure-jnp oracle (ref.py) and a jit'd public
wrapper (ops.py); validated against the oracle across shape/dtype sweeps in
interpret mode on CPU; TPU is the compile target (kernels/backend.py):

  distance/    tiled pairwise L2 on the MXU + the bulk-gather (chunked load)
               candidate scorer
  rabitq_dot/  fused bit-unpack + estimator inner product for RaBitQ codes,
               incl. the search-step variant with fused invalid-id masking
  topk/        small-k frontier top-k via iterative min-extraction
  flash_attention/  blockwise attention for the LM serving cells

Submodule ops are exposed lazily (PEP 562): model code imports individual
kernels from inside jit-traced functions, and an eager package-wide import
there would execute unrelated modules (and create their module-level
constants) under the active trace.
"""

_LAZY = {
    "distance_ops": "repro.kernels.distance",
    "rabitq_ops": "repro.kernels.rabitq_dot",
    "topk_ops": "repro.kernels.topk",
}

__all__ = list(_LAZY)


def __getattr__(name):
    if name in _LAZY:
        import importlib

        return importlib.import_module(_LAZY[name] + ".ops")
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
