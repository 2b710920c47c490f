#!/usr/bin/env python3
"""Record the small trace that ``benchmarks/tests/test_trace_reduce.py`` reads:
three runs of one tiny jitted program with a pause between them, on whatever
device JAX has, and print the trace's planes and lines. Run on the chip:

    python benchmarks/tools/record_trace_fixture.py <out_dir>
"""

import glob
import json
import os
import shutil
import sys
import time


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    out_dir = sys.argv[1]
    trace_dir = os.path.join(out_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)

    @jax.jit
    def fixture_program(x):
        for _ in range(4):
            x = jnp.tanh(x @ x) * 0.5
        return x.sum()

    x = jnp.ones((512, 512), jnp.float32)
    fixture_program(x).block_until_ready()
    jax.profiler.start_trace(trace_dir)
    with jax.profiler.TraceAnnotation("bench:sync"):
        pass
    for _ in range(3):
        fixture_program(x).block_until_ready()
        time.sleep(0.05)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    shutil.copy(path, os.path.join(out_dir, "fixture.xplane.pb"))
    data = ProfileData.from_file(path)
    shape = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            events = list(line.events)
            lines[line.name] = {
                "events": len(events),
                "first": [
                    [e.name, e.start_ns, e.duration_ns] for e in events[:6]
                ],
            }
        shape[plane.name] = lines
    print(json.dumps({"bytes": os.path.getsize(path), "planes": shape}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
