"""Multiprocess DataLoader workers (ref: python/paddle/io/dataloader/
worker.py — VERDICT r1 item 9): order/content parity with the serial
path, per-worker seeding + worker_init_fn, error propagation, and
genuine cross-process concurrency (interval overlap, not wall-clock).

Everything the loader ships to a worker lives at module level: with a
jax-initialized parent the DataLoader resolves mp_context=None to
"spawn" (fork-after-init is the flake this guards against), and spawn
pickles the dataset, collate_fn and worker_init_fn by qualname.
"""

import os
import pathlib
import time

import numpy as np
import pytest

from paddle_tpu.io import DataLoader, Dataset, IterableDataset, \
    get_worker_info


class SquareDataset(Dataset):
    def __init__(self, n=32):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        return np.asarray([i, i * i], np.int64)


class OverlapDataset(Dataset):
    """Each item sleeps, then reports (pid, start_ns, end_ns) from the
    system-wide monotonic clock — overlapping intervals from distinct
    pids prove the workers really ran concurrently."""

    def __len__(self):
        return 8

    def __getitem__(self, i):
        t0 = time.monotonic_ns()
        time.sleep(0.25)
        return np.asarray([os.getpid(), t0, time.monotonic_ns()], np.int64)


class FailingDataset(SquareDataset):
    def __getitem__(self, i):
        if i == 7:
            raise RuntimeError("boom at 7")
        return super().__getitem__(i)


class WorkerInfoDataset(SquareDataset):
    """Items report the worker that served them. The workers pull from
    ONE task queue, so the first one up can serve every batch before a
    slower one has started (spawn, which the loader takes once this
    process has a jax backend, starts a worker in seconds): an item
    therefore waits until every worker's `InitMarker` file is in
    `directory`, with a limit sized for a loaded host. The worker that
    waits holds one task, so each of the others takes one too."""

    def __init__(self, n, directory):
        super().__init__(n)
        self.directory = str(directory)

    def __getitem__(self, i):
        info = get_worker_info()
        assert info is not None and info.num_workers == 2
        limit = time.monotonic() + 120
        while not all((pathlib.Path(self.directory) / f"init{w}").exists()
                      for w in range(info.num_workers)):
            assert time.monotonic() < limit, "a worker never started"
            time.sleep(0.01)
        return np.asarray([i, info.id], np.int64)


class DictDS(Dataset):
    def __len__(self):
        return 8

    def __getitem__(self, i):
        return {"x": np.full((3,), i, np.float32), "tag": str(i)}


class ObjDS(Dataset):
    def __len__(self):
        return 8

    def __getitem__(self, i):
        return {"x": np.full((2,), i, np.float32),
                "meta": np.array([{"id": i}], object)}


class InitMarker:
    """Picklable worker_init_fn carrying its marker directory."""

    def __init__(self, directory):
        self.directory = str(directory)

    def __call__(self, worker_id):
        (pathlib.Path(self.directory) / f"init{worker_id}").write_text(
            str(worker_id))


def sum_collate(batch):
    return np.stack(batch).sum(0)


def obj_collate(batch):
    return {"x": np.stack([b["x"] for b in batch]),
            "meta": np.concatenate([b["meta"] for b in batch])}


def _collect(loader):
    return [np.asarray(b._data) if hasattr(b, "_data") else np.asarray(b)
            for b in loader]


class TestProcessWorkers:
    def test_matches_serial_order_and_content(self):
        ds = SquareDataset(33)
        serial = _collect(DataLoader(ds, batch_size=4, num_workers=0))
        proc = _collect(DataLoader(ds, batch_size=4, num_workers=3,
                                   worker_mode="process"))
        assert len(serial) == len(proc)
        for a, b in zip(serial, proc):
            np.testing.assert_array_equal(a, b)

    def test_worker_info_and_init_fn(self, tmp_path):
        out = _collect(DataLoader(WorkerInfoDataset(8, tmp_path),
                                  batch_size=2, num_workers=2,
                                  worker_mode="process",
                                  worker_init_fn=InitMarker(tmp_path)))
        ids = np.concatenate([o[:, 1] for o in out])
        assert set(ids.tolist()) == {0, 1}
        assert (tmp_path / "init0").exists()
        assert (tmp_path / "init1").exists()

    def test_error_propagates(self):
        dl = DataLoader(FailingDataset(16), batch_size=4, num_workers=2,
                        worker_mode="process")
        with pytest.raises(RuntimeError, match="boom at 7"):
            _collect(dl)

    def test_workers_run_concurrently(self):
        # interval-overlap, not wall-clock: worker startup under spawn is
        # load-sensitive (seconds on a busy 1-core CI host) and is not
        # the mechanism under test. Two workers round-robin the batches;
        # sleeping items from DIFFERENT pids must overlap in time.
        rows = np.concatenate(_collect(DataLoader(
            OverlapDataset(), batch_size=1, num_workers=2,
            worker_mode="process")))
        by_pid = {}
        for pid, t0, t1 in rows.tolist():
            by_pid.setdefault(pid, []).append((t0, t1))
        assert len(by_pid) == 2, by_pid.keys()
        (a_iv, b_iv) = by_pid.values()
        overlap = any(a0 < b1 and b0 < a1
                      for a0, a1 in a_iv for b0, b1 in b_iv)
        assert overlap, (a_iv, b_iv)

    def test_auto_spawn_when_jax_initialized(self):
        # importing paddle_tpu initializes the cpu backend in this
        # process, so the default (mp_context=None) must resolve to
        # spawn; an explicit context always wins
        assert DataLoader(SquareDataset(4))._resolve_mp_context() \
            == "spawn"
        assert DataLoader(SquareDataset(4),
                          mp_context="fork")._resolve_mp_context() \
            == "fork"

    def test_iterable_rejected(self):
        class It(IterableDataset):
            def __iter__(self):
                yield from range(4)
        with pytest.raises(NotImplementedError):
            DataLoader(It(), num_workers=2, worker_mode="process")

    def test_custom_collate_runs_in_worker(self):
        out = list(DataLoader(SquareDataset(8), batch_size=4,
                              num_workers=2, worker_mode="process",
                              collate_fn=sum_collate))
        ref = list(DataLoader(SquareDataset(8), batch_size=4,
                              num_workers=0, collate_fn=sum_collate))
        for a, b in zip(out, ref):
            np.testing.assert_array_equal(a, b)


class TestSharedMemoryTransport:
    def test_shm_matches_pickle(self):
        ds = SquareDataset(24)
        shm = _collect(DataLoader(ds, batch_size=4, num_workers=2,
                                  worker_mode="process",
                                  use_shared_memory=True))
        pkl = _collect(DataLoader(ds, batch_size=4, num_workers=2,
                                  worker_mode="process",
                                  use_shared_memory=False))
        assert len(shm) == len(pkl) == 6
        for a, b in zip(shm, pkl):
            np.testing.assert_array_equal(a, b)

    def test_shm_dict_batches(self):
        out = list(DataLoader(DictDS(), batch_size=4, num_workers=2,
                              worker_mode="process",
                              use_shared_memory=True))
        assert len(out) == 2
        np.testing.assert_allclose(np.asarray(out[0]["x"]._data)[:, 0],
                                   [0, 1, 2, 3])
        assert out[0]["tag"] == ["0", "1", "2", "3"]

    def test_no_leaked_segments(self):
        # scope to this loader's attributable names: global /dev/shm
        # diffs flake against unrelated concurrent processes
        import glob
        _collect(DataLoader(SquareDataset(16), batch_size=4,
                            num_workers=2, worker_mode="process",
                            use_shared_memory=True))
        assert glob.glob("/dev/shm/ppio*") == []

    def test_early_break_cleans_up(self):
        import glob
        dl = DataLoader(SquareDataset(32), batch_size=4, num_workers=2,
                        worker_mode="process", use_shared_memory=True)
        it = iter(dl)
        next(it)
        it.close()  # early break — pending batches must be unlinked
        time.sleep(0.3)
        leaked = glob.glob("/dev/shm/ppio*")
        assert leaked == [], leaked

    def test_object_dtype_stays_on_pickle_path(self):
        out = list(DataLoader(ObjDS(), batch_size=4, num_workers=2,
                              worker_mode="process",
                              use_shared_memory=True,
                              collate_fn=obj_collate))
        assert out[0]["meta"][0]["id"] == 0
        np.testing.assert_allclose(out[1]["x"][:, 0], [4, 5, 6, 7])

    def test_early_break_pickle_mode_does_not_hang(self):
        ds = SquareDataset(32)
        dl = DataLoader(ds, batch_size=4, num_workers=2,
                        worker_mode="process", use_shared_memory=False)
        it = iter(dl)
        next(it)
        t0 = time.perf_counter()
        it.close()
        assert time.perf_counter() - t0 < 10
