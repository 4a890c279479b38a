"""Launcher-driven auto-tuner trials with OOM survival (VERDICT r4 item
6; ref: python/paddle/distributed/auto_tuner/ — each candidate runs as a
real short launcher subprocess; OOM/crash is recorded, pruned, and tuning
completes with the best feasible config)."""

import math
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestLauncherDrivenTuning:
    def test_oom_candidate_survived_best_feasible_picked(self, tmp_path):
        from paddle_tpu.distributed.auto_tuner import AutoTuner

        # two 8-device candidates: dp8 replicates master+opt on every
        # device (~16.5 MB/dev measured); dp2 x mp2 x zero2 shards them
        # (~7.5 MB/dev). A 12 MB predictive-HBM budget OOMs the first.
        space = {"dp_degree": [2, 8], "mp_degree": [1, 2],
                 "pp_degree": [1], "sharding_degree": [1, 2],
                 "sharding_stage": [1], "micro_batch_size": [1],
                 "use_recompute": [False]}
        tuner = AutoTuner(total_devices=8, search_space=space,
                          global_batch=8, num_layers=2, num_heads=4)
        cands = {(c["dp_degree"], c["mp_degree"], c["sharding_degree"])
                 for c in tuner.candidates}
        assert (8, 1, 1) in cands and (2, 2, 2) in cands

        base = {"model": {"preset": "tiny", "num_hidden_layers": 2},
                "data": {"corpus": None},
                "seq_len": 64, "global_batch": 8, "remat": "none",
                "log_interval": 10,
                "hbm_budget_bytes": 12 * 1024 * 1024}
        best, history = tuner.tune_launched(
            base, workdir=str(tmp_path), steps=4, timeout=300,
            env={"JAX_PLATFORMS": "cpu",
                 "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
                 "PYTHONPATH": REPO + os.pathsep
                 + os.environ.get("PYTHONPATH", "")})

        by_key = {(h["dp_degree"], h["mp_degree"], h["sharding_degree"]):
                  h for h in history}
        # the replicated candidate hit the predictive OOM gate and was
        # recorded — not fatal
        assert by_key[(8, 1, 1)]["status"] == "oom", history
        assert by_key[(8, 1, 1)]["metric"] == -math.inf
        # the sharded candidate ran and won
        assert by_key[(2, 2, 2)]["status"] == "ok", history
        assert by_key[(2, 2, 2)]["metric"] > 0
        assert (best["dp_degree"], best["mp_degree"],
                best["sharding_degree"]) == (2, 2, 2)
