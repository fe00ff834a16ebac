"""Record the small TPU trace that ``test_bench_trace.py`` reads.

    python3 bench/tests/record_trace.py <out.xplane.pb>

On one TPU chip: inside a ``bench.window`` span, the host sleeps 20 ms
under a ``bench.schedule`` span, the Pallas flash-attention kernel runs
under a ``bench.execute`` span, the host sleeps 50 ms under a
``bench.switch_wait`` span, a matmul runs under another ``bench.execute``
span, and the host sleeps 20 ms more. The trace is copied to ``out`` and
its planes, lines and events are printed, so that the expected numbers of
the test can be read off them.
"""
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench.lib import trace as tr  # noqa: E402
from repro.kernels.flash_attention import flash_attention  # noqa: E402


def main(out: str) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace: needs a TPU")
    q = jnp.ones((1, 4, 512, 128), jnp.bfloat16)
    kv = jnp.ones((1, 2, 512, 128), jnp.bfloat16)
    a = jnp.ones((2048, 2048), jnp.bfloat16)
    mm = jax.jit(lambda x: x @ x)
    # compile outside the trace
    jax.block_until_ready(flash_attention(q, kv, kv, interpret=False))
    jax.block_until_ready(mm(a))
    log_dir = tempfile.mkdtemp(prefix="bench_trace_")
    jax.profiler.start_trace(log_dir, profiler_options=tr.profile_options())
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.schedule"):
            time.sleep(0.02)    # device and host clocks differ by ~1 ms
        with jax.profiler.TraceAnnotation("bench.execute"):
            jax.block_until_ready(flash_attention(q, kv, kv,
                                                  interpret=False))
        with jax.profiler.TraceAnnotation("bench.switch_wait"):
            time.sleep(0.05)
        with jax.profiler.TraceAnnotation("bench.execute"):
            jax.block_until_ready(mm(a))
        with jax.profiler.TraceAnnotation("bench.schedule"):
            time.sleep(0.02)
    jax.profiler.stop_trace()
    shutil.copy(tr.find_xplane(log_dir), out)
    shutil.rmtree(log_dir)
    data = jax.profiler.ProfileData.from_file(out)
    for plane in data.planes:
        print("plane", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  line", line.name, len(events))
            for e in events[:40]:
                print("    ", e.name, e.start_ns, e.duration_ns)
    print(tr.summarize(tr.load(out)))


if __name__ == "__main__":
    main(sys.argv[1])
