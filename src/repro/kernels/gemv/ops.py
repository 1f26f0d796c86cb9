"""jit'd public wrapper: dynamic per-row activation quantization (W8A8) +
platform dispatch (Pallas on TPU, oracle elsewhere / when interpreting)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import use_pallas_kernel
from repro.kernels.gemv.gemv import gemv_int8_pallas
from repro.kernels.gemv.ref import gemv_int8_ref
from repro.quant.int8 import QuantizedTensor, quantize_int8


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret",
                                             "out_dtype"))
def gemv_int8(x: jax.Array, w: QuantizedTensor, *, use_pallas: bool = None,
              interpret: bool = False, out_dtype=jnp.bfloat16) -> jax.Array:
    """x: (..., K) float; w: QuantizedTensor (K,N) int8 + (1,N) scale."""
    lead = x.shape[:-1]
    K = x.shape[-1]
    xf = x.reshape(-1, K)
    xq = quantize_int8(xf, axis=-1)
    ws = w.scale.reshape(1, -1)
    if use_pallas_kernel(use_pallas, interpret):
        out = gemv_int8_pallas(xq.values, xq.scale, w.values, ws,
                               interpret=interpret)
    else:
        out = gemv_int8_ref(xq.values, xq.scale, w.values, ws)
    return out.reshape(*lead, -1).astype(out_dtype)
