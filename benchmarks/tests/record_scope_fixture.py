"""Records the small device trace that the scope join's tests read
(``tests/assets/scope_fixture.*``): a ``chunk_step`` whose control-step
scan runs under ``rollout_step`` with ``sim_substep`` and
``policy_forward`` nested in it, a second program with no scope map
(``all_finite``), device-idle gaps under a nested host span, under the
root span alone and under none, and executions that the trace cuts at
its start and at its end.  Run on the chip, once:

    python3 benchmarks/tests/record_scope_fixture.py <out_dir>

Writes ``scope_fixture.xplane.pb``, the compiled text of ``chunk_step``
(``scope_fixture.hlo.txt``, without its source-location tables, which
name the recording machine's paths) and ``scope_fixture.json`` (the
host-clock window and the module names the trace holds).
"""
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

STEPS, SUBSTEPS, WIDTH = 12, 3, 2048
QUEUED = 10          # executions in flight when the trace starts


def without_locations(text: str) -> str:
    """Compiled text less its ``FileNames`` ... ``StackFrames`` tables: the
    lines from ``FileNames`` to the first computation."""
    lines = text.split("\n")
    if "FileNames" not in lines:
        return text
    start = lines.index("FileNames")
    end = next(i for i in range(start, len(lines))
               if lines[i].startswith(("%", "ENTRY")))
    return "\n".join(lines[:start] + lines[end:])


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    from gsc_tpu.obs.trace import find_profile, load_profile

    hi = jax.lax.Precision.HIGHEST

    @jax.jit
    def chunk_step(x):
        def substep(c, _):
            with jax.named_scope("sim_substep"):
                return jnp.tanh(jnp.dot(c, c, precision=hi)) * 0.5, None

        def control(c, _):
            with jax.named_scope("rollout_step"):
                c = c * 0.9 + 0.01
                c = jax.lax.scan(substep, c, None, length=SUBSTEPS)[0]
                with jax.named_scope("policy_forward"):
                    c = jnp.sin(jnp.dot(c, c.T, precision=hi)) * 0.5
            return c, None

        return jax.lax.scan(control, x, None, length=STEPS)[0]

    @jax.jit
    def all_finite(x):
        return jnp.isfinite(x).all()

    x = jnp.full((WIDTH, WIDTH), 0.01, jnp.float32)
    compiled = chunk_step.lower(x).compile()
    float(all_finite(chunk_step(x)))          # compile outside the trace
    tmp = tempfile.mkdtemp(prefix="gsc-scope-fixture-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    y = x
    for _ in range(QUEUED):                   # the device is busy as the
        y = chunk_step(y)                     # trace starts: a cut execution
    t0 = time.time()
    jax.profiler.start_trace(tmp, profiler_options=opts)
    span = jax.profiler.TraceAnnotation
    with span("episode"):
        with span("dispatch"):
            y = chunk_step(chunk_step(y))
        with span("drain"):
            y.block_until_ready()
        with span("harness_observe"):
            time.sleep(0.02)                  # idle under the inner span
        with span("ckpt"):
            ok = bool(all_finite(y))          # a program with no map
        time.sleep(0.01)                      # idle under the root alone
        with span("publish"):
            y = compiled(y)                   # the captured executable
            y.block_until_ready()
    time.sleep(0.01)                          # idle under no span
    for _ in range(3):                        # in flight as the trace stops
        y = chunk_step(y)
    time.sleep(0.005)
    jax.profiler.stop_trace()
    window = time.time() - t0
    y.block_until_ready()
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "scope_fixture.xplane.pb")
    shutil.copyfile(find_profile(tmp), path)
    with open(os.path.join(out_dir, "scope_fixture.hlo.txt"), "w") as f:
        f.write(without_locations(compiled.as_text()))
    loaded = load_profile(path)
    modules = sorted({m[0] for dev in loaded["devices"].values()
                      for m in dev["modules"]})
    meta = {"window_s": window, "finite": ok, "modules": modules}
    with open(os.path.join(out_dir, "scope_fixture.json"), "w") as f:
        json.dump(meta, f, indent=1)
    print(json.dumps(meta))
    for name, dev in loaded["devices"].items():
        ops, mods = dev["ops"], dev["modules"]
        print(name, "ops", len(ops), "modules", len(mods))
        if ops and mods:
            print("first op", ops[0][1], "first module", mods[0][1:],
                  "last op end", max(o[1] + o[2] for o in ops),
                  "last module end", mods[-1][1] + mods[-1][2])
    print("spans", [(n, round(d * 1e-6, 3)) for n, _, d in loaded["spans"]])


if __name__ == "__main__":
    main(sys.argv[1])
