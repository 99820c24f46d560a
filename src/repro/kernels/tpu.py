"""The device the kernels compile for: backend check and memory table.

One place answers "are we on a TPU?" and "how much VMEM may a kernel ask
for, and how much HBM does the chip hold?".  The planner
(``fpp/planner.MemoryModel.for_device``) budgets against the same row the
kernels request through :func:`compiler_params`, so a block size the
planner accepts is one the kernels are allowed to compile.

A TPU whose ``device_kind`` is not in :data:`TPU_MEMORY` is an error, not
a default: a guessed VMEM budget is how a plan that compiles on one chip
is refused (or thrashes) on another.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax

MiB = 1024 * 1024
GiB = 1024 * MiB


@dataclasses.dataclass(frozen=True)
class TpuMemory:
    """Per-TensorCore memory of one TPU generation.

    ``vmem_limit_bytes`` is what every kernel requests
    (``vmem_limit_bytes`` of its compiler parameters) and what the planner
    budgets one visit's working set against; ``hbm_bytes`` is the chip's
    HBM capacity.
    """
    vmem_limit_bytes: int
    hbm_bytes: int


#: keyed by ``jax.Device.device_kind``.  Source: Google Cloud TPU v5e
#: documentation (16 GiB HBM2 per chip) and the per-TensorCore table of
#: ``jax.experimental.pallas.tpu.get_tpu_info`` (v5e: 128 MiB VMEM).  The
#: kernels request 100 MiB of the 128 MiB, leaving the rest to the
#: compiler's own scratch and the pipeline's second buffers.
TPU_MEMORY = {
    "TPU v5 lite": TpuMemory(vmem_limit_bytes=100 * MiB, hbm_bytes=16 * GiB),
}

#: the generation the kernels are compiled for when no TPU is attached
#: (ahead-of-time compiles against a described v5e topology)
REFERENCE_KIND = "TPU v5 lite"


def on_tpu() -> bool:
    """True when the default backend is a TPU (kernels compile natively);
    elsewhere the Pallas kernels run in interpret mode."""
    return jax.default_backend() == "tpu"


def tpu_memory(device_kind: str) -> TpuMemory:
    """The memory row for a TPU ``device_kind``; unknown kinds raise."""
    try:
        return TPU_MEMORY[device_kind]
    except KeyError:
        raise ValueError(
            f"no memory model for TPU device kind {device_kind!r} (known: "
            f"{sorted(TPU_MEMORY)}); add its VMEM limit and HBM size to "
            f"repro.kernels.tpu.TPU_MEMORY") from None


def vmem_limit_bytes() -> int:
    """The VMEM limit every kernel requests: the attached TPU's row, or the
    reference generation's when compiling without a chip."""
    kind = jax.devices()[0].device_kind if on_tpu() else REFERENCE_KIND
    return tpu_memory(kind).vmem_limit_bytes


def compiler_params(dimension_semantics: Sequence[str]):
    """Mosaic compiler parameters shared by every kernel of the repo."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=tuple(dimension_semantics),
        vmem_limit_bytes=vmem_limit_bytes())
