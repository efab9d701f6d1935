"""Record `small.xplane.pb`, the TPU trace the trace-reduction test reads.

    python3 perf/testdata/record_trace.py    # on a machine with a TPU

Inside one `perf_traced_call` span the device runs a matmul program three
times; between the second and the third the host sleeps 50 ms inside a
`host_wait` span, so the trace holds one idle gap of about 50 ms that the
reduction must give to `host_wait`.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def main() -> int:
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        print("record_trace: JAX found no TPU", file=sys.stderr)
        return 2
    step = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    jax.block_until_ready(step(x))          # compile outside the trace
    tmp = os.path.join(HERE, "_trace_tmp")
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("perf_traced_call"):
        y = jax.block_until_ready(step(step(x)))
        with jax.profiler.TraceAnnotation("host_wait"):
            time.sleep(0.05)
        jax.block_until_ready(step(y))
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(src, os.path.join(HERE, "small.xplane.pb"))
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"wrote {os.path.join(HERE, 'small.xplane.pb')} "
          f"({os.path.getsize(os.path.join(HERE, 'small.xplane.pb'))} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
