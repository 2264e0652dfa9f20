"""How ``tiny_tpu.xplane.pb`` was recorded (on the chip, PR 23): three
executions of one small program with host sleeps between them, python and
host tracers off so that the file stays small.

    python3 benchmark/tests/record_tiny_trace.py <out_dir>
"""
import glob
import shutil
import sys
import time

import jax
import jax.numpy as jnp

out = sys.argv[1]


@jax.jit
def tiny_program(x):
    def body(c, _):
        return jnp.tanh(c @ c), None
    return jax.lax.scan(body, x, None, length=3)[0]


x = jnp.ones((512, 512), jnp.bfloat16)
tiny_program(x).block_until_ready()
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
opts.host_tracer_level = 0
jax.profiler.start_trace(out + "/tiny_trace", profiler_options=opts)
t0 = time.perf_counter()
for _ in range(3):
    tiny_program(x).block_until_ready()
    time.sleep(0.002)
span = time.perf_counter() - t0
jax.profiler.stop_trace()
path = glob.glob(out + "/tiny_trace/plugins/profile/*/*.xplane.pb")[0]
shutil.copy(path, out + "/tiny_tpu.xplane.pb")
print("recorded", path, "span_s", span, jax.devices()[0].device_kind)
