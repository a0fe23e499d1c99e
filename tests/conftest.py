"""Test configuration: run everything on CPU with 8 virtual devices.

Multi-device sharding logic is testable without TPU hardware via XLA's host
platform device-count override — set before jax is first imported.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# Persistent compilation cache: OFF for the suite and for every child process
# it starts (main.py, serve.py and the in-process benches all call
# utils.logging.enable_compile_cache), through JAX's own switch.  On jax 0.9.0
# a cache hit of a donate_argnums step gave the same numbers as a fresh
# compile (two-process check, PR 22), so the old jaxlib-0.4.37 heap corruption
# is gone; what remains is that the XLA:CPU loader logs a "machine type
# doesn't match ... could lead to SIGILL" error on every hit, that a cache
# under the checkout would make one test's compile time depend on another's,
# and that a compile for the described TPU (tests/test_tpu_compile.py) can be
# written to the cache but not read back without a chip.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import jax  # noqa: E402

# The trainer's static HBM plan (obs/memory.plan_for) pays a duplicate AOT
# compile of the train step — harmless in real runs, but it would double the
# compile cost of every Trainer-constructing test.  Default it off; the perf
# attribution integration test monkeypatches it back on.
os.environ.setdefault("RELORA_TPU_MEM_PLAN", "0")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs
