"""The single place this repo touches ``jax.experimental``.

Written for the installed jax (0.9.x): every experimental symbol the
engine needs is imported here once and re-exported, so a later jax
that moves or renames one is fixed in this file only:

  * ``pallas`` / ``pallas.tpu`` / ``pallas.triton`` module homes
    (re-exported as ``pl`` / ``pltpu`` / ``pltriton``);
  * the compiler-params constructors :func:`tpu_compiler_params`,
    :func:`gpu_compiler_params` and :func:`compiler_params_for`, which
    reject keywords the params class does not know instead of dropping
    them (a misspelt ``vmem_limit_bytes`` must fail loudly, not vanish);
  * ``shard_map`` and ``axis_size`` (the public ``jax`` names);
  * :func:`platform` / :func:`available_backends`, the
    backend-portability surface the engine builds on
    (docs/portability.md).

Keep this module dependency-light: importing it must never require a
TPU, and must stay side-effect free.
"""
from __future__ import annotations

from typing import Any

import jax

from jax.experimental import pallas as pl                   # noqa: F401
from jax.experimental.pallas import tpu as pltpu            # noqa: F401

# The GPU (Triton) lowering may be absent from a CPU-only build;
# ``None`` means "no GPU pallas in this install" and every GPU-backend
# entry point degrades to a loud, catchable error rather than an import
# crash (docs/portability.md).
try:
    from jax.experimental.pallas import triton as pltriton  # noqa: F401
except ImportError:                                # pragma: no cover
    pltriton = None

__all__ = ["pl", "pltpu", "pltriton", "tpu_compiler_params",
           "gpu_compiler_params", "compiler_params_for", "has_gpu_pallas",
           "platform", "available_backends", "shard_map", "axis_size"]

shard_map = jax.shard_map
axis_size = jax.lax.axis_size


def tpu_compiler_params(**kwargs: Any):
    """``pltpu.CompilerParams(**kwargs)``; an unknown keyword raises
    ``TypeError``."""
    return pltpu.CompilerParams(**kwargs)


def gpu_compiler_params(**kwargs: Any):
    """``pltriton.CompilerParams(**kwargs)``; an unknown keyword raises
    ``TypeError``. Raises ``ImportError`` when this jax has no GPU
    pallas at all."""
    if pltriton is None:
        raise ImportError(
            "this jax install has no Pallas GPU (Triton) lowering; "
            "the 'gpu' engine backend is unavailable "
            "(see docs/portability.md)")
    return pltriton.CompilerParams(**kwargs)


def compiler_params_for(backend: str, n_grid: int = 1,
                        vmem_limit_bytes: int | None = None):
    """Platform-appropriate ``pallas_call`` compiler params.

    ``backend`` is a *resolved* engine backend (``kernels.ops``
    dispatch): ``pallas``/``interpret`` get the TPU params (interpret
    mode ignores them, but keeping one object per family means the
    interpreted kernel traces exactly what the compiled one would);
    ``gpu`` gets the Triton params. ``n_grid`` is the pallas grid rank
    — TPU marks every dimension "arbitrary" (sequential semantics the
    revolving/streaming kernels rely on), which has no Triton analog:
    GPU grid dimensions are parallel, which is exactly why the engine
    restricts the GPU backend to scratch-free variants.
    ``vmem_limit_bytes`` is the kernel's scoped-VMEM limit on TPU (the
    engine passes its modeled footprint, ``core.blocking.vmem_limit``;
    without it Mosaic applies its small default scoped limit).
    """
    if backend == "gpu":
        return gpu_compiler_params()
    return tpu_compiler_params(
        dimension_semantics=("arbitrary",) * n_grid,
        vmem_limit_bytes=vmem_limit_bytes)


def has_gpu_pallas() -> bool:
    """Whether this jax install ships a Pallas GPU (Triton) lowering."""
    return pltriton is not None


def platform() -> str:
    """The host's default jax platform: "cpu" | "gpu" | "tpu"."""
    return jax.default_backend()


def available_backends() -> tuple[str, ...]:
    """Engine backends runnable on THIS host, ground truth first.

    ``interpret`` (the Pallas interpreter on CPU — the oracle every
    other backend is differential-tested against) and ``reference``
    (the jit-compiled jnp oracle) are always available; ``pallas``
    joins on a TPU host, ``gpu`` on a GPU host whose jax ships the
    Triton lowering. ``tests/test_backends.py`` runs its matrix over
    exactly this list.
    """
    out = ["interpret", "reference"]
    plat = platform()
    if plat == "tpu":
        out.append("pallas")
    elif plat == "gpu" and has_gpu_pallas():
        out.append("gpu")
    return tuple(out)
