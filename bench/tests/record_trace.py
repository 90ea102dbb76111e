"""Record the small device trace that ``test_harness_trace.py`` reduces.

    python3 bench/tests/record_trace.py [out.xplane.pb]

On a chip: a tiny jitted program runs three times inside a ``window``
annotation, each call inside ``sched_step`` and followed by a 20 ms sleep
inside ``wait``, so the trace holds device operations, idle gaps and the
host spans over them. Prints every plane and line of the trace with its
first events, and the reduction.
"""
from __future__ import annotations

import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from yardstick import trace  # noqa: E402


def main(out: str) -> int:
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((1024, 1024), jnp.float32)
    jax.block_until_ready(f(x))
    tdir = tempfile.mkdtemp(prefix="record_trace_")
    jax.profiler.start_trace(tdir, profiler_options=trace.profile_options())
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("sched_step"):
                jax.block_until_ready(f(x))
            with jax.profiler.TraceAnnotation("wait"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    path = trace.find_xplane(tdir)
    shutil.copy(path, out)
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(out)
    for plane in pd.planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("   line", repr(line.name), len(evs),
                  [(e.name, e.start_ns, e.duration_ns) for e in evs[:3]])
    print(trace.reduce_profile(pd, annotations=("sched_step", "wait")))
    shutil.rmtree(tdir, ignore_errors=True)
    print("size", os.path.getsize(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1
                  else os.path.join(HERE, "data", "trace_small.xplane.pb")))
