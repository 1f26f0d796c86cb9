"""Pallas TPU kernels for the paper's compute hot-spots (§4.2):

- gemv:         cache-resident INT8 weight-stationary GEMV / thin matmul
                (LLC-streamed weights → HBM→VMEM BlockSpec streaming;
                 L1-pinned activation → VMEM-pinned activation block)
- flash_decode: Flash-style decode attention over the contiguous KV cache
                (each row's live KV range copied in tiles, online softmax,
                GQA, INT8 KV)
- fused_ffn:    gated-FFN fusion — both GEMVs + elementwise in one kernel so
                weight tiles are streamed exactly once (paper Fig 6b)

Each package: <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd wrapper
with platform dispatch), ref.py (pure-jnp oracle used by tests and by the CPU
dry-run path). On a TPU the serving path's slotted decode attends through
``flash_decode`` (``models/transformer.py::live_range_block``); its other
attention and its matmuls are the jnp forms in ``models/`` and
``core/wa.py``.
"""
from __future__ import annotations

from typing import Optional

import jax


def use_pallas_kernel(use_pallas: Optional[bool], interpret: bool) -> bool:
    """Whether an ops wrapper runs its Pallas kernel.

    ``interpret=True`` runs the kernel in interpret mode on any backend;
    ``use_pallas=None`` picks the kernel on a TPU and the jnp oracle
    elsewhere. ``use_pallas=True`` off a TPU raises: the kernel never
    drops into interpret mode unless the caller asked for it."""
    if interpret:
        return True
    on_tpu = jax.default_backend() == "tpu"
    if use_pallas is None:
        return on_tpu
    if use_pallas and not on_tpu:
        raise ValueError(
            f"use_pallas=True needs a TPU (backend is "
            f"{jax.default_backend()!r}); pass interpret=True to run the "
            "kernel in interpret mode")
    return use_pallas
