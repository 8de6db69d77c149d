"""Records the small device trace the reduction's tests read
(``fixture.xplane.pb``): two compiled programs with known names, three
host phases, a deliberate host-side gap.  Run on the chip, once:

    python3 benchmarks/tests/record_fixture.py <out.xplane.pb>
"""
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp

    from benchmarks import trace

    @jax.jit
    def chunk_step(x):
        def body(c, _):
            return jnp.tanh(c @ c) * 0.5, None
        return jax.lax.scan(body, x, None, length=8)[0]

    @jax.jit
    def learn_step(x):
        return (x @ x.T).sum()

    x = jnp.ones((256, 256)) * 0.01
    float(learn_step(chunk_step(x)))          # compile outside the trace
    tmp = tempfile.mkdtemp(prefix="gsc-fixture-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=opts)
    t0 = time.time()
    for _ in range(2):
        with jax.profiler.TraceAnnotation("scenario_regen"):
            time.sleep(0.02)              # the device idles under this span
        with jax.profiler.TraceAnnotation("dispatch"):
            y = x
            for _ in range(3):
                y = chunk_step(y)
            z = learn_step(y)
        with jax.profiler.TraceAnnotation("drain"):
            float(z)
    window = time.time() - t0
    jax.profiler.stop_trace()
    trace.keep(tmp, out)
    red = trace.reduce_file(out, window)
    loaded = trace.load(out)
    print("window_s", window, "busy_s", red["busy_s"], "events",
          red["n_events"])
    print("loops", red["top_level_loops"])
    print("breakdown", red["breakdown"])
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(out).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", line.name, len(evs),
                  [(e.name[:40], round(e.start_ns), round(e.duration_ns),
                    dict(list(e.stats)[:4])) for e in evs[:3]])


if __name__ == "__main__":
    main(sys.argv[1])
