#!/usr/bin/env python3
"""Record the small TPU profiler trace that the trace-reduction tests read.

    python3 chipbench/tests/record_trace.py OUT_DIR

Runs on one TPU chip: a jitted four-layer scan of bf16 matmuls (a stand-in
for a decode step) and a jitted argmax, five rounds each with a short host
sleep between them, inside a ``chipbench.window`` annotation.  The xplane
file lands under ``OUT_DIR/plugins/profile/<time>/``, and a summary of its
planes and lines is printed, so the layout the reducer relies on can be read
off without the chip.  Exits non-zero without a TPU.
"""
import glob
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "tpu")


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"record_trace: found {dev.platform!r}, not a TPU", file=sys.stderr)
        return 2

    def layers(w, x):
        def body(h, wi):
            return jnp.tanh(h @ wi), None
        h, _ = jax.lax.scan(body, x, w)
        return h

    step = jax.jit(layers)
    pick = jax.jit(lambda h: jnp.argmax(h, axis=-1))
    w = jax.random.normal(jax.random.key(0), (4, 1024, 1024), jnp.bfloat16) * 0.03
    x = jax.random.normal(jax.random.key(1), (64, 1024), jnp.bfloat16)
    pick(step(w, x)).block_until_ready()

    jax.profiler.start_trace(out_dir)
    with jax.profiler.TraceAnnotation("chipbench.sync"):
        t_sync = time.time()
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for _ in range(5):
            with jax.profiler.TraceAnnotation("chipbench.step"):
                h = step(w, x)
                pick(h).block_until_ready()
            time.sleep(0.002)
    jax.profiler.stop_trace()
    print(f"t_sync {t_sync!r}")

    path = sorted(glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    print(f"xplane {path} {os.path.getsize(path)} bytes")
    for plane in ProfileData.from_file(path).planes:
        print(f"plane {plane.name!r} stats {dict(plane.stats)}")
        for line in plane.lines:
            evs = list(line.events)
            print(f"  line {line.name!r} events {len(evs)}")
            for e in evs[:4]:
                print(f"    {e.name!r} start {e.start_ns} dur {e.duration_ns} "
                      f"stats {dict(e.stats)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/trace"))
