"""Pallas compiler parameters + interpret-mode policy shared by the kernels.

Kernels declare their grid semantics as lowercase strings
("parallel"/"arbitrary") and their scoped-VMEM limit in bytes, and build
their ``pltpu.CompilerParams`` through ``tpu_compiler_params``.

``auto_interpret`` is the one implementation of the kernels' interpret-mode
default (previously copy-pasted into every ops wrapper): interpret off-TPU,
compiled on TPU, overridable for a whole process via ``REPRO_INTERPRET=0|1``
without threading ``interpret=`` through every call site.
"""

from __future__ import annotations

import os

import jax
from jax.experimental.pallas import tpu as pltpu

_TRUTHY = ("1", "true", "yes", "on")
_FALSY = ("0", "false", "no", "off")


def auto_interpret() -> bool:
    """Default for the kernel wrappers' ``interpret=None``.

    Priority: the ``REPRO_INTERPRET`` environment variable (``1`` forces
    Pallas interpret mode even on TPU, ``0`` forces compiled mode even off
    TPU -- e.g. to exercise the Mosaic lowering under a CPU emulator), then
    the backend rule: interpret everywhere except real TPU.
    """
    env = os.environ.get("REPRO_INTERPRET", "").strip().lower()
    if env in _TRUTHY:
        return True
    if env in _FALSY:
        return False
    if env and env != "auto":
        raise ValueError(
            f"REPRO_INTERPRET={env!r}: expected 0/1 (or auto/empty)"
        )
    return jax.default_backend() != "tpu"


def tpu_compiler_params(
    dimension_semantics: tuple[str, ...], vmem_limit_bytes: int | None = None
):
    """CompilerParams with the given per-grid-dim semantics and scoped-VMEM
    limit (``None`` leaves the compiler's own default, which is far below
    the chip's VMEM)."""
    return pltpu.CompilerParams(
        dimension_semantics=tuple(dimension_semantics),
        vmem_limit_bytes=vmem_limit_bytes,
    )
