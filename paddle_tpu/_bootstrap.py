"""Early jax.distributed bootstrap (ref: the reference initialises its
collective context from the PADDLE_* env at import/bring-up time —
SURVEY §3.1). MUST be the first import in paddle_tpu/__init__.py:
``jax.distributed.initialize`` refuses to run once anything has
initialised the XLA backend. Package import itself never does (a
launcher parent imports the package and must leave the chip to its
workers — tests/test_launch.py pins that). The launcher
(distributed/launch) exports COORDINATOR_ADDRESS (the jax coordination
port published through the TCPStore rendezvous) + PADDLE_TRAINERS_NUM /
PADDLE_TRAINER_ID; any worker that imports paddle_tpu joins the pod
automatically. ``init_parallel_env()`` stays the explicit-API parity
surface and is a no-op when this already ran."""

from __future__ import annotations

import os


def maybe_initialize() -> bool:
    """Join the jax distributed pod if the launcher env says we are one of
    N>1 processes. Idempotent. Returns True if this process is (now)
    initialized as part of a multi-process pod."""
    n = os.environ.get("PADDLE_TRAINERS_NUM", "1")
    # ONLY the launcher-published coordinator endpoint triggers the join:
    # PADDLE_MASTER is the TCPStore's port, and the jax coordination
    # service can never share it (rank 0 would fail to bind / everyone
    # else would hang talking the wrong protocol) — so it must not be
    # used as a fallback here
    coord = os.environ.get("COORDINATOR_ADDRESS")
    if n == "1" or not coord:
        return False
    # a worker's own subprocesses (dataloader workers, helpers) inherit the
    # launcher env; they must NOT join the pod as a duplicate of the
    # parent's rank — the marker records which pid actually joined
    joined_pid = os.environ.get("PADDLE_DIST_JOINED_PID")
    if joined_pid is not None and joined_pid != str(os.getpid()):
        return False
    import jax
    if jax.distributed.is_initialized():
        return True
    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        # cross-process CPU collectives need gloo (the simulated
        # multi-host path; TPU pods ride ICI/DCN natively)
        try:
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        except Exception:
            pass
    jax.distributed.initialize(
        coordinator_address=coord,
        num_processes=int(n),
        process_id=int(os.environ.get("PADDLE_TRAINER_ID", 0)))
    os.environ["PADDLE_DIST_JOINED_PID"] = str(os.getpid())
    return True


def configure_compile_cache() -> str:
    """Place jax's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and
    no directory is set in code.  Otherwise the cache lives at ONE fixed,
    git-ignored path inside the checkout — the path is part of the
    cache key, so a directory built from a temp name, pid or time never
    hits.  Called by every entry point that compiles at scale
    (chip_smoke.py, bench.py, the run_pretrain CLI, the bench tools'
    row processes, tests/conftest.py)."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


maybe_initialize()
