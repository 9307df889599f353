"""Version-tolerant shims over the moving Pallas TPU API surface.

The compiler-params class has been renamed across jax releases
(``TPUCompilerParams`` -> ``CompilerParams``) and its constructor signature
drifts; kernels only use it as an optional scheduling hint, so resolution
failures degrade to "no hint" instead of an import/attribute error.
"""
from __future__ import annotations

from typing import Optional, Sequence

from jax.experimental.pallas import tpu as pltpu

__all__ = ["tpu_compiler_params"]


def tpu_compiler_params(
    dimension_semantics: Optional[Sequence[str]] = None,
    *,
    vmem_limit_bytes: Optional[int] = None,
) -> Optional[object]:
    """Best-effort ``compiler_params`` for ``pl.pallas_call``: the grid's
    dimension semantics and the kernel's scoped VMEM limit, each where given
    (None if the installed jax exposes neither spelling or rejects the
    arguments)."""
    cls = getattr(pltpu, "CompilerParams", None) or getattr(
        pltpu, "TPUCompilerParams", None
    )
    if cls is None:
        return None
    kw = {}
    if dimension_semantics is not None:
        kw["dimension_semantics"] = tuple(dimension_semantics)
    if vmem_limit_bytes is not None:
        kw["vmem_limit_bytes"] = int(vmem_limit_bytes)
    try:
        return cls(**kw)
    except TypeError:
        return None
