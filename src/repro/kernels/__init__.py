"""Pallas TPU kernels, validated on CPU in interpret mode.

On the RL training path (what ``repro.rl`` runs):

* ``dense_block/stack.py`` — the fused L-layer MLP/DenseNet/D2RL stack,
  forward + custom-VJP backward; SAC/TD3/OFENet train through it under
  ``network.block_backend="fused"``.
* ``replay_tree/`` — the device sum-tree (fused proportional-descent
  sample + one-hot set) behind prioritized device replay under
  ``replay.kernel="pallas"``.

Not on the RL path: the single-layer ``dense_block/dense_block.py`` /
``ops.py`` kernels, ``flash_attention/`` and ``ssd_scan/`` (used only by
their tests and ``benchmarks/kernels_micro.py``).

``default_interpret()`` is the one interpret-mode policy: kernels lower
through Mosaic on TPU and run in the Pallas interpreter everywhere else.
A caller that pins ``interpret=False`` off-TPU gets an error from
``require_mosaic``, never a silent substitute.
"""
from __future__ import annotations

from typing import Optional

import jax


def mosaic_available() -> bool:
    """True when Pallas kernels can real-lower (Mosaic is TPU-only)."""
    return jax.default_backend() == "tpu"


def default_interpret(interpret: Optional[bool] = None) -> bool:
    """Resolve an ``interpret`` argument: None -> interpret off-TPU only."""
    if interpret is None:
        return not mosaic_available()
    return bool(interpret)


def require_mosaic(what: str) -> None:
    """Raise unless ``what`` can lower through Mosaic here."""
    if not mosaic_available():
        raise RuntimeError(
            f"{what}: interpret=False needs a TPU backend (Mosaic), but "
            f"jax.default_backend() is {jax.default_backend()!r}; pass "
            f"interpret=True or leave it to default_interpret()")
