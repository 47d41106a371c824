"""How the Pallas kernels meet Mosaic, the TPU kernel compiler.

Two decisions live here and nowhere else:

* **Where a kernel runs.** Every ``pallas_call`` in ``kernels/*/kernel.py``
  takes ``interpret=None`` by default and resolves it with
  ``interpret_mode``: compiled on a TPU, interpreted on any other platform
  (the CPU test suite). An explicit bool still wins — the compile tests
  pass ``interpret=False`` to lower a kernel for a described TPU from a CPU
  host. No wrapper hard-codes the choice.
* **Which tiles Mosaic accepts.** The last dim of every block is a multiple
  of 128 lanes or the whole axis; the second-to-last a multiple of 8
  sublanes (32-bit) or the whole axis.
"""
from __future__ import annotations

import jax


def interpret_mode(interpret: bool | None = None) -> bool:
    """``interpret`` if given, else True unless JAX's backend is a TPU."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"


def pick_row_tile(v: int) -> int:
    """Largest row (sublane) tile of 256..8 that divides ``v``, else ``v``."""
    for t in (256, 128, 64, 32, 16, 8):
        if v % t == 0:
            return t
    return v


def pick_word_tile(w: int) -> int:
    """Word (lane) tile: 128, or the whole row when ``w`` is not a multiple
    of 128."""
    return 128 if w % 128 == 0 else w
