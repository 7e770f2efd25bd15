#!/usr/bin/env python3
"""Record the small chip trace kept under `benchmarks/fixtures/`: two bursts
of bfloat16 matrix multiplications with a deliberate host sleep between them,
inside a `bench.window` span. Prints `trace.describe` (the hand look) and
`trace.reduce_trace` (the numbers the tests then hold the reduction to), and
leaves the trace and both texts in `chiprun_out/fixture/`.

    chiprun -- python3 benchmarks/tools/record_fixture.py
"""
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import trace
    assert jax.devices()[0].platform == 'tpu', jax.devices()
    out = os.path.join(ROOT, 'chiprun_out', 'fixture')
    tdir = os.path.join(ROOT, 'output', 'benchmarks', 'trace', 'fixture')
    shutil.rmtree(tdir, ignore_errors=True)
    os.makedirs(out, exist_ok=True)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    chain = jax.jit(lambda a: (a @ a) @ a)
    chain(x).block_until_ready()
    trace.start(tdir)
    with jax.profiler.TraceAnnotation('bench.window'):
        # the device plane's clock runs about a millisecond off the host plane's: sleeps at both ends keep
        # every op inside the window span whichever way it is off
        time.sleep(0.005)
        for burst in range(2):
            with jax.profiler.TraceAnnotation('bench.burst'):
                for _ in range(20):
                    y = chain(x)
                y.block_until_ready()
            with jax.profiler.TraceAnnotation('bench.deliberate_sleep'):
                time.sleep(0.03 if burst == 0 else 0.005)
    trace.stop()
    path = trace.newest_xplane(tdir)
    shutil.copy(path, os.path.join(out, 'toy_matmuls.xplane.pb'))
    text = trace.describe(path, events=4)
    reduced = trace.reduce_trace(path)
    open(os.path.join(out, 'describe.txt'), 'w').write(text)
    json.dump(reduced, open(os.path.join(out, 'reduced.json'), 'w'), indent=1)
    print(text[-6000:])
    print(json.dumps(reduced, indent=1), os.path.getsize(path), 'bytes')


if __name__ == '__main__':
    main()
