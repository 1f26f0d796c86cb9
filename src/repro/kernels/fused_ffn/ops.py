"""jit'd wrapper with platform dispatch for the fused gated FFN."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import use_pallas_kernel
from repro.kernels.fused_ffn.fused_ffn import fused_ffn_pallas
from repro.kernels.fused_ffn.ref import fused_ffn_ref


@functools.partial(jax.jit, static_argnames=("act", "use_pallas", "interpret",
                                             "block_f", "out_dtype"))
def fused_ffn(x, w_gate, w_up, w_down, *, act: str = "silu",
              use_pallas: bool = None, interpret: bool = False,
              block_f: int = 512, out_dtype=jnp.bfloat16) -> jax.Array:
    """x: (..., D) → (..., D)."""
    lead = x.shape[:-1]
    xf = x.reshape(-1, x.shape[-1])
    if use_pallas_kernel(use_pallas, interpret):
        out = fused_ffn_pallas(xf, w_gate, w_up, w_down, act=act,
                               block_f=block_f, interpret=interpret)
    else:
        out = fused_ffn_ref(xf, w_gate, w_up, w_down, act=act)
    return out.reshape(*lead, -1).astype(out_dtype)
