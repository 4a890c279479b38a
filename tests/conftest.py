"""Test configuration: run the suite on a simulated 8-device CPU mesh.

SURVEY §4.2 build lesson: the reference tests distributed logic single-host
(Gloo fake, subprocess ranks); the TPU-native equivalent is
xla_force_host_platform_device_count so sharding/collective tests execute a
real 8-way SPMD program without hardware. Must run before jax import.
"""

import os

# tests force the CPU: the 8-device simulated mesh only exists on the cpu
# platform (the chip is exercised by chip_smoke.py, not by this suite)
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
# 8 virtual devices share one physical core: a lagging device thread can
# miss XLA-CPU's default 40s collective rendezvous kill on a busy host
if "xla_cpu_collective_call_terminate_timeout_seconds" not in flags:
    flags += (" --xla_cpu_collective_call_terminate_timeout_seconds=900"
              " --xla_cpu_collective_call_warn_stuck_timeout_seconds=300")
os.environ["XLA_FLAGS"] = flags
# keep CI deterministic and quiet
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

# numerics tests compare against f32 references; the TPU-idiomatic low default
# (bf16 MXU passes) is exercised explicitly by the kernel/perf tests instead
jax.config.update("jax_default_matmul_precision", "highest")

# persistent compilation cache: the suite is compile-bound; cached XLA
# executables cut full-suite time several-fold on reruns
from paddle_tpu._bootstrap import configure_compile_cache  # noqa: E402

configure_compile_cache()


# ---------------------------------------------------------------------------
# global-state hygiene: tests that fleet.init() a hybrid mesh must not leak
# it into later tests (the ambient mesh changes eager-collective routing)
# ---------------------------------------------------------------------------
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _restore_global_mesh():
    from paddle_tpu.distributed.mesh import get_mesh, set_mesh
    from paddle_tpu.distributed import fleet
    prev = get_mesh()
    prev_fleet = dict(fleet._fleet_state)
    yield
    set_mesh(prev)
    fleet._fleet_state.clear()
    fleet._fleet_state.update(prev_fleet)
