"""Record the small trace that ``test_chip_bench_trace.py`` reduces.

    python3 benchmarks/chip/tests/record_small_trace.py <out_dir>

Run on one TPU: three rounds of 20 chained bf16 1024 x 1024 matmul programs
(host span ``device_work``), each followed by a 20 ms host sleep (span
``host_work``), inside the benchmark's ``bench/traced_window`` annotation.
Prints the planes and lines of the trace and the path of its ``.xplane.pb``.
"""
import glob
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.profiler import ProfileData  # noqa: E402

from chiplib.spans import ANNOTATION_PREFIX, Spans  # noqa: E402


def main(out: str) -> None:
    shutil.rmtree(out, ignore_errors=True)
    f = jax.jit(lambda x: jnp.tanh(x @ x) * 0.5)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    f(x).block_until_ready()
    spans = Spans()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + "traced_window"):
        for _ in range(3):
            with spans.span("device_work"):
                y = x
                for _ in range(20):
                    y = f(y)
                y.block_until_ready()
            with spans.span("host_work"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)[0]
    for plane in ProfileData.from_file(path).planes:
        print(plane.name, [(ln.name, sum(1 for _ in ln.events))
                           for ln in plane.lines][:12])
    print(path, os.path.getsize(path))


if __name__ == "__main__":
    main(sys.argv[1])
