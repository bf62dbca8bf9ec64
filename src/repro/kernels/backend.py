"""The one rule for where a Pallas kernel runs.

Every kernel wrapper takes `interpret: bool | None`; None resolves here.
The CPU runs the Pallas interpreter (the test platform), the TPU compiles
through Mosaic, and any other platform is an error: a kernel never drops
silently to the interpreter on a device it was not written for.
"""

from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """None -> True on CPU, False on TPU; raises on any other platform."""
    if interpret is not None:
        return interpret
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run on TPU (compiled) or CPU (interpreted); "
        f"the default backend is {platform!r}")
