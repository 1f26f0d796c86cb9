#!/usr/bin/env python3
"""Record the small chip trace the trace-reduction test reads.

    python3 bench/testdata/record_trace.py OUT.json

Runs a few executions of two small jitted programs on the chip, with the
benchmark's window markers and dispatch marks, flattens the profile with
``bench.trace_reduce.flatten`` and writes the neutral form to OUT.json.
"""
from __future__ import annotations

import json
import sys
import tempfile
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2])]


def main(out: str):
    import jax
    import jax.numpy as jnp

    from bench import trace_reduce

    def matmul_step(x):
        return jnp.tanh(x @ x) * 0.5

    def reduce_step(x):
        return jnp.sum(jnp.exp(x), axis=0)

    f, g = jax.jit(matmul_step), jax.jit(reduce_step)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    f(x).block_until_ready()
    g(x).block_until_ready()
    d = tempfile.mkdtemp(prefix="bench_trace_")
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation(trace_reduce.BEGIN):
        pass
    for _ in range(3):
        with jax.profiler.TraceAnnotation("dispatch:matmul_step"):
            pass
        f(x).block_until_ready()
        time.sleep(0.002)
        with jax.profiler.TraceAnnotation("dispatch:reduce_step"):
            pass
        g(x).block_until_ready()
    with jax.profiler.TraceAnnotation(trace_reduce.END):
        pass
    jax.profiler.stop_trace()
    path = sorted(Path(d).rglob("*.xplane.pb"))[-1]
    tr = trace_reduce.flatten(str(path))
    Path(out).write_text(json.dumps(tr))
    print(f"wrote {out}: {len(tr['device'])} device events, "
          f"{len(tr['host'])} host events")


if __name__ == "__main__":
    main(sys.argv[1])
