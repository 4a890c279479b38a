"""Test configuration: run the suite on a simulated 8-device CPU mesh.

SURVEY §4.2 build lesson: the reference tests distributed logic single-host
(Gloo fake, subprocess ranks); the TPU-native equivalent is
xla_force_host_platform_device_count so sharding/collective tests execute a
real 8-way SPMD program without hardware. Must run before jax import.
"""

import faulthandler
import os
import signal
import sys

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

import pytest  # noqa: E402

# persistent compilation cache: the suite is compile-bound; cached XLA
# executables cut full-suite time several-fold on reruns
from paddle_tpu._bootstrap import configure_compile_cache  # noqa: E402

configure_compile_cache()


def pytest_configure(config):
    # pytest-xdist 3.8.0 under `--dist loadfile` hands the files out by
    # NUMBER OF TESTS, descending, so the one-case files (the quality gates,
    # minutes each) start last and the run ends on one worker with five
    # idle. Off, files go out in collection order. The scheduler reads the
    # option in the controller (scheduler/loadscope.py:374); set here and
    # not as `addopts = --no-loadscope-reorder`, which a run with
    # `-p no:xdist` rejects as an unknown argument.
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


#: the files that hold over ~200 s of case time, started right behind the
#: quality gates: in its alphabetical place one of them ends the run on one
#: worker with five idle (PR 67's tree: 7,627 s of case time, a sixth of
#: which is 1,271 s, read 1,462.8 s of the 1,470 s limit). The seconds are
#: the driver's junit of that tree (ISSUE 68): test_tpu_aot_compile 336
#: (split in two at PR 68: 158 + 179), test_launch_rows 250,
#: test_launch_ahead 249, test_ragged_kernel 248, test_serving_engine 215,
#: test_ragged_kernel_blocks 212; and test_vision_zoo, 134 s in six cases
#: that the alphabet starts last (PR 68's first whole run ended on it)
LONG_FILES = ("test_tpu_aot_compile", "test_tpu_aot_compile_step",
              "test_launch_rows", "test_launch_ahead", "test_ragged_kernel",
              "test_serving_engine", "test_ragged_kernel_blocks",
              "test_vision_zoo")


def _start_order(item) -> int:
    stem = os.path.basename(item.nodeid.split("::", 1)[0])[:-len(".py")]
    if stem.startswith("test_quality_gate_"):
        return 0
    return 1 if stem in LONG_FILES else 2


def pytest_collection_modifyitems(items):
    # the quality gates are trainings, minutes each under the eager tape
    # (test_quality_gate_ocr.py: ~630 s beside five busy workers): started
    # in their alphabetical place, two thirds in, they end the run alone;
    # the long files follow them (a stable sort: a file's cases stay
    # together and in their order)
    items.sort(key=_start_order)


# ---------------------------------------------------------------------------
# every case has a limit of its own: a case that hangs (a child that never
# answers, a rendezvous nobody joins) fails with its stack, and does not
# cost the whole run its clock. ONE limit: twice the longest case of the
# driver's command under six workers, fixtures' setup included, and not over
# a third of the suite's clock, which is what binds (the rec gate with its
# net's training reads 246-313 s, PR 52). A hook around the whole protocol and
# not a fixture: a function-scoped fixture is set up after the module-scoped
# ones, whose setup is where the long cases spend their time.
# ---------------------------------------------------------------------------
CASE_LIMIT_S = 480


def _case_timed_out(signum, frame):
    pytest.fail(f"case exceeded CASE_LIMIT_S = {CASE_LIMIT_S} s "
                f"(every thread's stack: captured stderr)")


@pytest.hookimpl(wrapper=True)
def pytest_runtest_protocol(item):
    old = signal.signal(signal.SIGALRM, _case_timed_out)
    signal.setitimer(signal.ITIMER_REAL, CASE_LIMIT_S)
    # a second earlier, and from a thread of its own: it writes even when
    # the main thread is stuck in a call that lets no handler run
    faulthandler.dump_traceback_later(CASE_LIMIT_S - 1, file=sys.__stderr__)
    try:
        return (yield)
    finally:
        faulthandler.cancel_dump_traceback_later()
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


# ---------------------------------------------------------------------------
# jax keeps every jitted function's fast-path entries of a PROCESS in two
# shared LRU lists of 8,192 (`jax._src.pjit._cpp_pjit_cache_*`). A worker
# that has run a few hundred cases — the eager OCR trainings alone dispatch
# thousands of op signatures — fills them, and a function jitted after that
# keeps NO entry: `f._cache_size()` reads 0 after a call, and the cases that
# count a program's compiled variants (`program_cache_sizes()`,
# `test_ragged_kernel.py::TestRaggedJit`) fail on whichever worker got
# there first (PR 54: five `TestDisaggregated` cases in one run, a
# `TestRaggedJit` case in the next). Before a FILE's first case, lists more
# than half full are emptied: the entries only, not the traced or compiled
# programs behind them, so the next call of a live function re-enters at
# the cost of a dictionary lookup.
# ---------------------------------------------------------------------------
@pytest.fixture(autouse=True, scope="module")
def _room_in_the_jit_fast_path_lists():
    try:
        from jax._src import pjit as _pjit
        lists = (_pjit._cpp_pjit_cache_fun_only,
                 _pjit._cpp_pjit_cache_explicit_attributes)
        if any(2 * c.size() > c.capacity() for c in lists):
            for c in lists:
                c.clear_all()
    except (ImportError, AttributeError):   # another jax: its own lists
        pass
    yield


# ---------------------------------------------------------------------------
# global-state hygiene: tests that fleet.init() a hybrid mesh must not leak
# it into later tests (the ambient mesh changes eager-collective routing)
# ---------------------------------------------------------------------------
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
