"""Launcher CLI: spawn, rank env, workerlogs, restart policy (SURVEY P14)."""

import os
import textwrap

import pytest

from paddle_tpu.distributed.launch import launch


def _write_script(tmp_path, body):
    p = tmp_path / "trainer.py"
    p.write_text(textwrap.dedent(body))
    return str(p)


def test_spawn_two_ranks_env_and_logs(tmp_path):
    out = tmp_path / "env"
    out.mkdir()
    script = _write_script(tmp_path, f"""
        import os, json
        rank = os.environ["PADDLE_TRAINER_ID"]
        keep = {{k: v for k, v in os.environ.items()
                if k.startswith(("PADDLE_", "JAX_", "COORDINATOR"))}}
        with open(os.path.join({str(out)!r}, rank + ".json"), "w") as f:
            json.dump(keep, f)
        print("rank", rank, "done")
    """)
    rc = launch(["--nproc_per_node", "2", "--log_dir",
                 str(tmp_path / "log"), script])
    assert rc == 0
    import json
    e0 = json.load(open(out / "0.json"))
    e1 = json.load(open(out / "1.json"))
    assert e0["PADDLE_TRAINERS_NUM"] == "2"
    assert e1["PADDLE_TRAINER_ID"] == "1"
    assert e0["JAX_NUM_PROCESSES"] == "2"
    assert e0["COORDINATOR_ADDRESS"] == e1["COORDINATOR_ADDRESS"]
    assert len(e0["PADDLE_TRAINER_ENDPOINTS"].split(",")) == 2
    # per-rank logs written (ref: workerlog.N)
    log0 = (tmp_path / "log" / "workerlog.0").read_text()
    assert "rank 0 done" in log0
    assert "rank 1 done" in (tmp_path / "log" / "workerlog.1").read_text()


def test_nonzero_exit_propagates(tmp_path):
    script = _write_script(tmp_path, """
        import sys
        sys.exit(3)
    """)
    rc = launch(["--nproc_per_node", "1", "--log_dir",
                 str(tmp_path / "log"), script])
    assert rc == 3


def test_restart_policy_recovers(tmp_path):
    sentinel = tmp_path / "came_before"
    script = _write_script(tmp_path, f"""
        import os, sys
        s = {str(sentinel)!r}
        if not os.path.exists(s):
            open(s, "w").write("x")
            sys.exit(1)   # first attempt fails
        print("second attempt ok")
    """)
    rc = launch(["--nproc_per_node", "1", "--max_restarts", "1",
                 "--log_dir", str(tmp_path / "log"), script])
    assert rc == 0
    assert "second attempt ok" in (tmp_path / "log" / "workerlog.0").read_text()


def test_elastic_manager_membership():
    from paddle_tpu.native import TCPStore
    from paddle_tpu.distributed.launch import ElasticManager
    s = TCPStore(is_master=True, world_size=2)
    try:
        m0 = ElasticManager(s, node_rank=0, ttl=5.0)
        m1 = ElasticManager(s, node_rank=1, ttl=5.0)
        m0.heartbeat()
        assert m0.alive_nodes(2) == [0]
        assert m0.membership_changed(expected=2)
        m1.heartbeat()
        assert m0.alive_nodes(2) == [0, 1]
        assert not m0.membership_changed(expected=2)
    finally:
        s.close()


def test_heartbeat_payload_channel_tolerated():
    """The '|'-suffix payload channel (used by the collective watchdog to
    publish flight progress) must not break liveness parsing."""
    from paddle_tpu.native import TCPStore
    from paddle_tpu.distributed.launch import ElasticManager
    s = TCPStore(is_master=True, world_size=1)
    try:
        m = ElasticManager(s, node_rank=0, ttl=5.0)
        m.heartbeat(payload="rank=0,seq=7,op=all_reduce")
        assert m.alive_nodes(1) == [0]
        raw = s.get("heartbeat/0").decode()
        assert raw.split("|", 1)[1] == "rank=0,seq=7,op=all_reduce"
    finally:
        s.close()


def test_claim_slot_rechecks_racing_joiner():
    """Two joiners race for the same stale slot: the loser's post-add
    re-check sees the winner's fresh heartbeat and must move on to the
    next slot instead of double-claiming."""
    import time as _time
    from paddle_tpu.native import TCPStore
    from paddle_tpu.distributed.launch import ElasticManager

    class RacingStore:
        """Store wrapper that simulates a rival joiner winning slot 0
        between our claim-counter add and the heartbeat re-check."""

        def __init__(self, inner):
            self._inner = inner
            self._raced = False

        def __getattr__(self, name):
            return getattr(self._inner, name)

        def add(self, key, amount):
            token = self._inner.add(key, amount)
            if key == "claim/0" and not self._raced:
                self._raced = True
                self._inner.set("heartbeat/0", str(_time.time()))
            return token

    s = TCPStore(is_master=True, world_size=1)
    try:
        m = ElasticManager(RacingStore(s), node_rank=99, ttl=5.0,
                           min_nodes=1, max_nodes=3)
        slot = m.claim_slot()
        assert slot == 1                    # slot 0 lost to the rival
        assert m.node_rank == 1
        assert m.alive_nodes(2) == [0, 1]   # both heartbeating now
        m.heartbeat()                       # our token is current: no raise
    finally:
        s.close()


def test_heartbeat_slot_theft_fence():
    """A node that paused past the TTL and lost its slot to a newer
    claimant must see the moved claim counter and exit, not keep
    heartbeating a slot it no longer owns (split-brain fence)."""
    import pytest
    from paddle_tpu.native import TCPStore
    from paddle_tpu.distributed.launch import ElasticManager
    s = TCPStore(is_master=True, world_size=1)
    try:
        m = ElasticManager(s, node_rank=0, ttl=5.0, min_nodes=1,
                           max_nodes=2)
        m.register_slot()
        m.heartbeat()                       # own token: fine
        s.add("claim/0", 1)                 # a newer owner claims the slot
        with pytest.raises(RuntimeError, match="reclaimed"):
            m.heartbeat()
    finally:
        s.close()


def test_restart_banner_marks_each_attempt(tmp_path):
    """Satellite bugfix: workerlog.N is opened append-mode across
    restarts, so every (re)spawn writes a '=== restart N / gen G ==='
    marker separating the attempts."""
    sentinel = tmp_path / "came_before"
    script = _write_script(tmp_path, f"""
        import os, sys
        s = {str(sentinel)!r}
        if not os.path.exists(s):
            open(s, "w").write("x")
            sys.exit(1)
        print("attempt two ok")
    """)
    rc = launch(["--nproc_per_node", "1", "--max_restarts", "1",
                 "--log_dir", str(tmp_path / "log"), script])
    assert rc == 0
    log = (tmp_path / "log" / "workerlog.0").read_text()
    assert "=== restart 0 / gen 0 ===" in log
    assert "=== restart 1 / gen 0 ===" in log
    # the failing first attempt's lines sit under the first banner
    assert log.index("=== restart 0") < log.index("=== restart 1") \
        < log.index("attempt two ok")


def test_flight_report_merged_on_terminal_failure(tmp_path):
    """On terminal child failure the controller collects per-rank
    flightdump.*.json from the log dir into one flight_report.json naming
    the lagging rank (ISSUE 3 post-mortem merge)."""
    import json
    script = _write_script(tmp_path, """
        import json, os, sys
        # stand in for the watchdog: write this rank's flight dump, then
        # die the way a hung collective does after CollectiveTimeout
        rank = int(os.environ["PADDLE_TRAINER_ID"])
        seqs = {0: 2, 1: 1}[rank]
        recs = [{"seq": i + 1, "op": "all_reduce", "shapes": [[4]],
                 "dtypes": ["float32"], "bytes": 16, "axis": "dp",
                 "start": 0.0, "end": 0.1, "duration_s": 0.1,
                 "status": "ok"} for i in range(seqs)]
        dump = {"version": 1, "rank": rank, "last_completed_seq": seqs,
                "records": recs}
        path = os.path.join(os.environ["PADDLE_LOG_DIR"],
                            f"flightdump.{rank}.json")
        with open(path, "w") as f:
            json.dump(dump, f)
        # wait for the peer's dump so the controller can't reap one rank
        # before the other has written (both must appear in the report)
        import time
        peer = os.path.join(os.environ["PADDLE_LOG_DIR"],
                            f"flightdump.{1 - rank}.json")
        for _ in range(200):
            if os.path.exists(peer):
                break
            time.sleep(0.05)
        sys.exit(7)
    """)
    rc = launch(["--nproc_per_node", "2", "--max_restarts", "0",
                 "--log_dir", str(tmp_path / "log"), script])
    assert rc == 7
    report = json.load(open(tmp_path / "log" / "flight_report.json"))
    assert report["world"] == 2
    assert report["exit_code"] == 7
    assert report["lagging_rank"] == 1
    assert report["last_completed_seq"] == {"0": 2, "1": 1} or \
        report["last_completed_seq"] == {0: 2, 1: 1}
    fd = report["first_divergence"]
    assert fd["seq"] == 2 and fd["reason"] == "missing_rank"


def test_fault_injection_sigkill_worker_recovers(tmp_path):
    """Kill-a-worker fault injection (SURVEY §5.3): rank 1 SIGKILLs itself
    mid-run on the first attempt; the watch loop must tear the pod down and
    relaunch it, and the retry completes on all ranks."""
    sentinel = tmp_path / "already_died"
    done = tmp_path / "done"
    done.mkdir()
    script = _write_script(tmp_path, f"""
        import os, signal, time
        rank = os.environ["PADDLE_TRAINER_ID"]
        s = {str(sentinel)!r}
        if rank == "1" and not os.path.exists(s):
            open(s, "w").write("x")
            os.kill(os.getpid(), signal.SIGKILL)  # simulated host failure
        if rank == "0" and not os.path.exists(s):
            time.sleep(30)  # would hang forever if the pod were not torn down
        open(os.path.join({str(done)!r}, rank), "w").write("ok")
        print("rank", rank, "finished")
    """)
    import time
    t0 = time.time()
    rc = launch(["--nproc_per_node", "2", "--max_restarts", "1",
                 "--log_dir", str(tmp_path / "log"), script])
    assert rc == 0
    # rank 0's first attempt was killed by the controller (not after 30s)
    assert time.time() - t0 < 25
    assert "rank 0 finished" in (tmp_path / "log" / "workerlog.0").read_text()
    assert "rank 1 finished" in (tmp_path / "log" / "workerlog.1").read_text()
    # both ranks completed the retry attempt
    assert (done / "0").exists() and (done / "1").exists()


# -- the chip belongs to the workers (ISSUE 21) ------------------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("module", ["paddle_tpu.distributed.launch.main",
                                    "paddle_tpu.distributed.auto_tuner"])
def test_parent_import_leaves_backend_uninitialised(module):
    """A launcher / auto-tuner parent imports the package and then spawns
    workers that need the chip: a parent that initialised the XLA backend
    would hold it.  Checked in a fresh interpreter."""
    import subprocess
    import sys
    code = (f"import {module}\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, list(xla_bridge._backends)\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-2000:]


def test_devices_are_split_across_local_ranks():
    from paddle_tpu.distributed.launch.controllers import _visible_chips_env
    # one rank: every listed chip, no process grid
    assert _visible_chips_env("0,1,2,3", 0, 1) == {
        "TPU_VISIBLE_CHIPS": "0,1,2,3"}
    # four ranks on a four-chip host: one chip each, never all chip 0
    envs = [_visible_chips_env("0,1,2,3", r, 4) for r in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert {e["TPU_PROCESS_BOUNDS"] for e in envs} == {"2,2,1"}
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4
    assert [e["CLOUD_TPU_TASK_ID"] for e in envs] == ["0", "1", "2", "3"]
    with pytest.raises(ValueError):
        _visible_chips_env("0,1,2", 0, 2)


def test_compile_cache_is_placed_from_outside(monkeypatch):
    import jax
    from paddle_tpu._bootstrap import configure_compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        # set: jax reads the variable itself, code sets no directory
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        jax.config.update("jax_compilation_cache_dir", None)
        assert configure_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir is None
        # unset: one fixed path inside the checkout, the same every call
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert configure_compile_cache() == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir \
            == os.path.join(REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_peak_table_has_no_default():
    import types
    from paddle_tpu.device import peaks
    v5e = types.SimpleNamespace(device_kind="TPU v5 lite", platform="tpu")
    assert peaks.require_peak(v5e).bf16_flops == 197e12
    assert peaks.peak(v5e).hbm_bytes_per_s == 819e9
    cpu = types.SimpleNamespace(device_kind="cpu", platform="cpu")
    assert peaks.peak(cpu) is None
    with pytest.raises(RuntimeError, match="cpu"):
        peaks.require_peak(cpu)
