"""Per-IR-op device-time profile of the ResNet-50 training step (r4),
with the fixed (async-excluded) attribution.  Prints the op table plus
the device busy time per step."""

import os
import tempfile

os.environ["PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION"] = "python"

import numpy as np
import jax

import paddle_tpu as fluid
from paddle_tpu.models import resnet as R
from paddle_tpu import profiler

BATCH, STEPS = 256, 2

main_prog, startup = fluid.Program(), fluid.Program()
with fluid.program_guard(main_prog, startup):
    avg_cost, acc, feeds = R.resnet_train_program(BATCH)
    fluid.optimizer.Momentum(learning_rate=0.01, momentum=0.9) \
        .minimize(avg_cost)
main_prog.amp = True
scope = fluid.Scope()
with fluid.scope_guard(scope):
    exe = fluid.Executor()
    exe.run(startup)
    rng = np.random.RandomState(0)
    batches = [{
        "image": rng.rand(BATCH, 3, 224, 224).astype("float32"),
        "label": rng.randint(0, 1000, (BATCH, 1)).astype("int64"),
    } for _ in range(STEPS)]
    stacked = {k: jax.device_put(np.stack([b[k] for b in batches]))
               for k in batches[0]}
    exe.run_steps(main_prog, feed=stacked, fetch_list=[avg_cost.name],
                  steps=STEPS)  # compile + settle
    td = tempfile.mkdtemp()
    jax.profiler.start_trace(td)
    exe.run_steps(main_prog, feed=stacked, fetch_list=[avg_cost.name],
                  steps=STEPS)
    jax.profiler.stop_trace()
    _, rows = profiler.compiled_op_table(td)
    import shutil
    shutil.rmtree(td, ignore_errors=True)
    # NOTE: whole-plane busy time counts every program the process ran
    # in the window; the scope-attributed table below is this step's
    total = sum(r[2] for r in rows)
    print(f"attributed: {total * 1e3 / STEPS:.1f} ms/step")
    for op, calls, sec in rows[:18]:
        print(f"  {op:32s} {calls:6d} {sec * 1e3 / STEPS:9.3f} ms/step")
