"""Test configuration: force an 8-device virtual CPU platform BEFORE jax
initializes, so multi-chip sharding tests run without TPU hardware
(mirrors the reference's strategy of simulating clusters on one host,
SURVEY.md §4.5).  The suite never runs on the chip: what has to be shown
there lives in ``chip_smoke.py``, and what the chip's compiler has to
accept is compiled against a described topology in
``tests/test_tpu_compile.py``."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def corrupt_largest_file(ckpt_dir, truncate_to_half=True):
    """Tear a committed checkpoint for fault-tolerance tests: truncate
    (or bit-flip) its largest payload file, sparing the manifest."""
    files = [(os.path.getsize(os.path.join(dp, f)), os.path.join(dp, f))
             for dp, _, fs in os.walk(str(ckpt_dir))
             for f in fs if f != "MANIFEST.json"]
    size, victim = max(files)
    with open(victim, "r+b") as f:
        if truncate_to_half:
            f.truncate(size // 2)
        else:
            f.seek(size - 1)
            byte = f.read(1)
            f.seek(-1, 2)
            f.write(bytes([byte[0] ^ 0xFF]))
    return victim


@pytest.fixture(autouse=True)
def fresh_programs():
    """Each test gets fresh default programs and a fresh scope."""
    import paddle_tpu as fluid
    from paddle_tpu.framework import Program
    from paddle_tpu.scope import Scope, scope_guard

    main, startup = Program(), Program()
    prev_main = fluid.switch_main_program(main)
    prev_startup = fluid.switch_startup_program(startup)
    scope = Scope()
    with scope_guard(scope):
        yield
    fluid.switch_main_program(prev_main)
    fluid.switch_startup_program(prev_startup)
