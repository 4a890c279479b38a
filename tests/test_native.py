"""Native C++ components: flags, TCPStore, profiler (SURVEY §2.1 native
contract). The store is exercised cross-process via subprocess clients."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from paddle_tpu import native


def test_native_lib_builds():
    assert native.available(), "native.so failed to build (g++ required)"


def test_flags_roundtrip_and_env_override(monkeypatch):
    native.native_flag_define("FLAGS_test_native_x", "7")
    assert native.native_flag_get("FLAGS_test_native_x") == "7"
    native.native_flag_set("FLAGS_test_native_x", "9")
    assert native.native_flag_get("FLAGS_test_native_x") == "9"
    monkeypatch.setenv("FLAGS_test_native_env", "42")
    native.native_flag_define("FLAGS_test_native_env", "0")
    assert native.native_flag_get("FLAGS_test_native_env") == "42"


class TestTCPStore:
    def test_kv_set_get_add(self):
        s = native.TCPStore(is_master=True, world_size=1)
        try:
            s.set("k", "v1")
            assert s.get("k") == b"v1"
            assert s.get("missing") is None
            assert s.add("ctr", 5) == 5
            assert s.add("ctr", 2) == 7
            s.delete("k")
            assert s.get("k") is None
        finally:
            s.close()

    def test_wait_blocks_until_set(self):
        import threading
        s = native.TCPStore(is_master=True, world_size=1)
        c = native.TCPStore(port=s.port, world_size=1)
        try:
            def setter():
                import time
                time.sleep(0.2)
                c.set("late", "here")
            t = threading.Thread(target=setter)
            t.start()
            assert s.wait("late", timeout=5.0) == b"here"
            t.join()
        finally:
            c.close()
            s.close()

    def test_wait_timeout(self):
        s = native.TCPStore(is_master=True, world_size=1)
        try:
            with pytest.raises(TimeoutError):
                s.wait("never", timeout=0.3)
        finally:
            s.close()

    def test_cross_process_barrier(self, tmp_path):
        """3 real OS processes rendezvous through the C++ store."""
        s = native.TCPStore(is_master=True, world_size=4)
        # load the native module standalone: the subprocesses need the
        # store only, not the full framework
        native_init = os.path.join(os.getcwd(), "paddle_tpu", "native",
                                   "__init__.py")
        script = textwrap.dedent(f"""
            import importlib.util
            spec = importlib.util.spec_from_file_location(
                "pt_native", {repr(native_init)})
            native = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(native)
            c = native.TCPStore(port={s.port}, world_size=4)
            c.add("joined", 1)
            c.barrier("b0", timeout=30)
            print("OK")
        """)
        procs = [subprocess.Popen([sys.executable, "-c", script],
                                  stdout=subprocess.PIPE,
                                  stderr=subprocess.PIPE)
                 for _ in range(3)]
        s.barrier("b0", timeout=30)
        for p in procs:
            out, err = p.communicate(timeout=60)
            assert p.returncode == 0, err.decode()
            assert b"OK" in out
        assert int(s.get("joined")) == 3
        s.close()


class TestProfiler:
    def test_record_and_export(self, tmp_path):
        native.prof_clear()
        native.prof_enable(True)
        with native.RecordEvent("outer"):
            with native.RecordEvent("inner"):
                sum(range(1000))
        native.prof_enable(False)
        assert native.prof_event_count() == 2
        out = str(tmp_path / "trace.json")
        n = native.prof_export(out)
        assert n == 2
        data = json.load(open(out))
        names = {e["name"] for e in data["traceEvents"]}
        assert names == {"outer", "inner"}
        assert all(e["ph"] == "X" and e["dur"] >= 0
                   for e in data["traceEvents"])
        native.prof_clear()

    def test_disabled_records_nothing(self):
        native.prof_clear()
        native.prof_enable(False)
        with native.RecordEvent("nope"):
            pass
        assert native.prof_event_count() == 0
