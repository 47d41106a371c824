"""Records ``tiny.xplane.pb``, the profiler trace the trace-reduction test
reads: on a TPU, three jitted matmuls, each inside a ``bench.pump`` span,
with ``bench.wait`` sleeps between them, all inside ``bench.window``.

    python bench/tests/data/make_trace.py <output directory>
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData, TraceAnnotation


def main(out: str) -> None:
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((1024, 1024), jnp.float32)
    f(x).block_until_ready()
    tmp = tempfile.mkdtemp(dir=out)
    jax.profiler.start_trace(tmp)
    with TraceAnnotation("bench.window"):
        for _ in range(3):
            with TraceAnnotation("bench.pump"):
                f(x).block_until_ready()
            with TraceAnnotation("bench.wait"):
                time.sleep(0.005)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    dst = os.path.join(out, "tiny.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tmp)
    for plane in ProfileData.from_file(dst).planes:
        for line in plane.lines:
            evs = list(line.events)
            print(plane.name, "|", line.name, "|", len(evs), "|",
                  [(e.name, e.start_ns, e.duration_ns) for e in evs[:3]])


if __name__ == "__main__":
    main(sys.argv[1])
