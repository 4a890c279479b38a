"""Runtime flag registry with environment override.

TPU-native equivalent of the reference's in-house gflags clone
(ref: paddle/common/flags.cc, macros PHI_DEFINE_EXPORTED_*; python surface
paddle.set_flags / paddle.get_flags). Three properties preserved:

1. every flag is overridable by env ``FLAGS_<name>`` at import time,
2. flags are get/set-able at runtime via :func:`set_flags` / :func:`get_flags`,
3. unknown flags raise instead of silently no-op.

Flags here are plain Python (typed, validated); performance-critical consumers
read them once per trace, not per op.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict, Iterable, Mapping, Optional

__all__ = ["define_flag", "get_flags", "set_flags", "flag", "flags_guard"]

_lock = threading.RLock()


class _Flag:
    __slots__ = ("name", "default", "value", "type", "help", "validator")

    def __init__(self, name: str, default: Any, help: str = "",
                 validator: Optional[Callable[[Any], bool]] = None):
        self.name = name
        self.default = default
        self.type = type(default)
        self.help = help
        self.validator = validator
        self.value = self._from_env(default)

    def _from_env(self, default: Any) -> Any:
        raw = os.environ.get(self.name)
        if raw is None:
            return default
        return _parse(raw, self.type)

    def set(self, value: Any) -> None:
        if self.type is bool and isinstance(value, str):
            value = _parse(value, bool)
        elif not isinstance(value, self.type):
            try:
                value = self.type(value)
            except (TypeError, ValueError):
                raise TypeError(
                    f"flag {self.name} expects {self.type.__name__}, got "
                    f"{type(value).__name__}: {value!r}")
        if self.validator is not None and not self.validator(value):
            raise ValueError(f"invalid value for flag {self.name}: {value!r}")
        self.value = value


def _parse(raw: str, ty: type) -> Any:
    if ty is bool:
        return raw.strip().lower() in ("1", "true", "yes", "on")
    if ty is int:
        return int(raw)
    if ty is float:
        return float(raw)
    return raw


_registry: Dict[str, _Flag] = {}


def define_flag(name: str, default: Any, help: str = "",
                validator: Optional[Callable[[Any], bool]] = None) -> None:
    """Register a flag. ``name`` must start with ``FLAGS_``."""
    if not name.startswith("FLAGS_"):
        raise ValueError(f"flag name must start with FLAGS_: {name}")
    with _lock:
        if name in _registry:
            raise ValueError(f"flag already defined: {name}")
        _registry[name] = _Flag(name, default, help, validator)


def flag(name: str) -> Any:
    """Fast read of a single flag value."""
    try:
        return _registry[name].value
    except KeyError:
        raise KeyError(f"unknown flag: {name}") from None


def get_flags(names: Optional[Iterable[str] | str] = None) -> Dict[str, Any]:
    if isinstance(names, str):
        names = [names]
    with _lock:
        if names is None:
            names = list(_registry)
        out = {}
        for n in names:
            if n not in _registry:
                raise KeyError(f"unknown flag: {n}")
            out[n] = _registry[n].value
        return out


def set_flags(flags: Mapping[str, Any]) -> None:
    with _lock:
        for n, v in flags.items():
            if n not in _registry:
                raise KeyError(f"unknown flag: {n}")
            _registry[n].set(v)


class flags_guard:
    """Context manager that temporarily overrides flags."""

    def __init__(self, **overrides: Any):
        self._overrides = {k if k.startswith("FLAGS_") else "FLAGS_" + k: v
                           for k, v in overrides.items()}
        self._saved: Dict[str, Any] = {}

    def __enter__(self):
        self._saved = get_flags(list(self._overrides))
        set_flags(self._overrides)
        return self

    def __exit__(self, *exc):
        set_flags(self._saved)
        return False


# ---------------------------------------------------------------------------
# Core flags (parity with the reference's canonical set where meaningful on TPU;
# CUDA-specific flags documented as unsupported in docs/UNSUPPORTED.md).
# ---------------------------------------------------------------------------
define_flag("FLAGS_check_nan_inf", False,
            "post-op NaN/Inf scan with op-level blame (debug mode)")
define_flag("FLAGS_deterministic", False,
            "force deterministic lowering choices (parity: FLAGS_cudnn_deterministic)")
define_flag("FLAGS_use_fusion_compiler", False,
            "enable the CINN-parity fusion pass pipeline (parity: FLAGS_use_cinn)")
define_flag("FLAGS_flash_impl", "intree",
            "which flash-attention kernel sdpa routes to when eligible: "
            "'intree' (ops/pallas_flash.py, authored+tunable), 'bundled' "
            "(jax.experimental.pallas.ops.tpu.flash_attention), or "
            "'composite' (never take a fused kernel)",
            validator=lambda v: v in ("intree", "bundled", "composite"))
define_flag("FLAGS_mla_decode_impl", "auto",
            "MLA absorbed-latent decode attention: 'auto' (fused "
            "single-cache-read kernel ops/pallas_mla.py when the latent "
            "rank is lane-aligned, einsum otherwise), 'fused' (pin the "
            "kernel), or 'xla' (pin the two-einsum composite)",
            validator=lambda v: v in ("auto", "fused", "xla"))
define_flag("FLAGS_gmm_impl", "auto",
            "grouped-GEMM (MoE expert compute): 'auto' (fastest-first: "
            "ragged_dot -> in-tree ops/pallas_gmm.py -> bundled "
            "megablox -> einsum), or pin 'xla'/'intree'/'bundled'/"
            "'einsum'",
            validator=lambda v: v in ("auto", "xla", "intree", "bundled",
                                      "einsum"))
define_flag("FLAGS_metrics", True,
            "record observability metrics (paddle_tpu.observability): "
            "counters/gauges/histograms from ops dispatch, jit caches, "
            "trainer, serving and collectives. Off = every instrumented "
            "site degrades to one attribute test (near-zero overhead)")
define_flag("FLAGS_request_tracing", True,
            "record per-request / per-train-step span timelines "
            "(paddle_tpu.observability.tracing): enqueue/admit/prefill/"
            "token events in the serving engine and data/fwd/bwd/opt "
            "phases in the trainer, with chrome-trace export and "
            "TTFT/TPOT/e2e SLO histograms. Off = every stamp degrades "
            "to one attribute test (near-zero overhead)")
define_flag("FLAGS_trace_ring_size", 2048,
            "finished request traces kept in the in-memory ring buffer "
            "for export (oldest evicted first); the step records' ring "
            "holds tracing.STEPS_PER_SLOT times as many",
            validator=lambda v: v >= 1)
define_flag("FLAGS_eager_op_cache_size", 4096,
            "max entries in the per-op jitted computation cache")
define_flag("FLAGS_fault_spec", "",
            "deterministic fault-injection plan (paddle_tpu.resilience): "
            "semicolon-separated clauses 'kind@site[:opt=val...]' plus an "
            "optional 'seed=N'. Kinds: nan_loss/inf_loss/spike_loss, "
            "nan_grad/inf_grad, ckpt_write_fail/ckpt_read_corrupt, "
            "loader_raise, collective_delay/collective_hang/"
            "collective_error, preempt. "
            "Empty = no faults (zero overhead). See docs/RESILIENCE.md")
define_flag("FLAGS_collective_timeout", 0.0,
            "seconds before an in-flight collective is declared hung by "
            "the watchdog (distributed.watchdog): the flight-recorder ring "
            "is dumped to the worker log dir and a diagnostic "
            "CollectiveTimeout is raised (trainer routes it to an "
            "emergency checkpoint). 0 = watchdog off; instrumented call "
            "sites degrade to one attribute test",
            validator=lambda v: v >= 0)
define_flag("FLAGS_flight_record_size", 256,
            "capacity of the collective flight-recorder ring buffer "
            "(last-N collective calls kept for post-mortem dumps)",
            validator=lambda v: v >= 1)
define_flag("FLAGS_watchdog_interval", 0.0,
            "watchdog monitor poll interval in seconds; 0 = auto "
            "(FLAGS_collective_timeout/4, clamped to [0.01, 0.25])",
            validator=lambda v: v >= 0)
define_flag("FLAGS_ckpt_retries", 3,
            "bounded retry budget for checkpoint write failures "
            "(framework.io.save / distributed.checkpoint.save_state_dict)",
            validator=lambda v: v >= 0)
define_flag("FLAGS_ckpt_retry_backoff", 0.05,
            "base seconds for exponential backoff between checkpoint "
            "write retries", validator=lambda v: v >= 0)
define_flag("FLAGS_log_level", 0, "VLOG-style verbosity (higher = chattier)")
define_flag("FLAGS_allocator_strategy", "pjrt",
            "memory allocator strategy; TPU memory is owned by PJRT")
