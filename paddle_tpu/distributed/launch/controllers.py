"""Launch controllers: pod construction, watch loop, restart policy, elastic.

Reference mechanism (SURVEY §2.3 P14, §5.3):
- python/paddle/distributed/launch/controllers/collective.py — master
  rendezvous (TCPStore/etcd), builds the pod rank table, spawns per-rank
  subprocesses with PADDLE_* env, writes per-rank `workerlog.N`, watches
  children and restarts per policy.
- python/paddle/distributed/fleet/elastic/manager.py — ElasticManager
  watches membership (etcd TTL keys); on join/leave kills local trainers
  and relaunches with regenerated rank env.

TPU-native rework: the rendezvous/heartbeat store is our C++ TCPStore
(paddle_tpu.native); per-host processes get both the PADDLE_* env vars and
the jax.distributed coordination vars (COORDINATOR_ADDRESS / process id) so
`init_parallel_env()` can call jax.distributed.initialize on pods. Failure
detection = child exit codes + store heartbeats; recovery = checkpoint-based
relaunch (SURVEY §5.3: the TPU-idiomatic elastic story is preemption-aware
checkpoint + restart, not in-flight reconstruction).
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import time
from typing import List, Optional

from ...native import TCPStore

__all__ = ["CollectiveController", "ElasticManager"]


class _Proc:
    def __init__(self, popen, rank, log_path, log_file):
        self.popen = popen
        self.rank = rank
        self.log_path = log_path
        self.log_file = log_file


def _visible_chips_env(devices: str, local_rank: int, nproc: int) -> dict:
    """Per-rank chip visibility from ``--devices``.  The installed libtpu
    takes its chips from ``TPU_VISIBLE_CHIPS`` (the variable jax's own
    multi-process TPU tests set); each local rank gets its own equal
    share of the list, so ranks on one host never all claim chip 0.
    Ranks that share a host also tell libtpu the process grid — the
    one-chip-per-process recipe of the same tests (a 2x2 host for four
    ranks, a row otherwise)."""
    ids = [d for d in devices.split(",") if d]
    if len(ids) % nproc:
        raise ValueError(f"--devices names {len(ids)} chips, which "
                         f"{nproc} local ranks cannot share equally")
    k = len(ids) // nproc
    env = {"TPU_VISIBLE_CHIPS":
           ",".join(ids[local_rank * k:(local_rank + 1) * k])}
    if nproc > 1:
        if k != 1:
            raise ValueError("several ranks on one host take one chip "
                             "each: give --devices one id per rank")
        ports = [8476 + r for r in range(nproc)]
        env.update({
            "CLOUD_TPU_TASK_ID": str(local_rank),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "2,2,1" if nproc == 4
            else f"{nproc},1,1",
            "TPU_PROCESS_ADDRESSES":
                ",".join(f"localhost:{p}" for p in ports),
            "TPU_PROCESS_PORT": str(ports[local_rank]),
        })
    return env


class CollectiveController:
    """Spawn + watch the local ranks of a collective job."""

    def __init__(self, args):
        self.args = args
        self.node_rank = int(args.node_rank)
        # --nnodes MIN[:MAX] (ref elastic semantics): the pod launches at
        # MIN; MAX bounds how far a scale-up may grow the membership
        parts = str(args.nnodes).split(":")
        self.nnodes = int(parts[0])
        self.max_nnodes = int(parts[-1])
        if self.max_nnodes < self.nnodes:
            raise ValueError(
                f"--nnodes {args.nnodes}: max < min")
        self.nproc = int(args.nproc_per_node)
        self.world_size = self.nnodes * self.nproc
        self.procs: List[_Proc] = []
        self.store: Optional[TCPStore] = None
        self._restarts = 0
        # elastic state: SLOT is this node's stable membership identity
        # (the heartbeat key); node_rank is the per-generation compacted
        # rank derived from the world map
        self.elastic_on = (self.max_nnodes > self.nnodes
                           or getattr(args, "elastic_join", False))
        self.slot = self.node_rank
        self.gen = 0
        self.current_world: List[int] = list(range(self.nnodes))
        self.elastic: Optional[ElasticManager] = None

    # -- rendezvous ----------------------------------------------------------
    def _master_hostport(self):
        if self.args.master:
            host, _, port = self.args.master.rpartition(":")
            return host or "127.0.0.1", int(port)
        return "127.0.0.1", 0

    def rendezvous(self):
        host, port = self._master_hostport()
        is_master = self.node_rank == 0
        self.store = TCPStore(host=host, port=port, is_master=is_master,
                              world_size=self.nnodes,
                              timeout=self.args.rdzv_timeout)
        if is_master:
            port = self.store.port
        self.master_endpoint = f"{host}:{port}"
        # publish this node, wait for everyone (ref: pod/rank table build)
        self.store.set(f"node/{self.node_rank}", os.uname().nodename)
        self.store.barrier("rendezvous", timeout=self.args.rdzv_timeout)

    # -- env -----------------------------------------------------------------
    def _rank_env(self, local_rank: int) -> dict:
        rank = self.node_rank * self.nproc + local_rank
        endpoints = ",".join(
            f"{self.master_endpoint.split(':')[0]}:{9000 + r}"
            for r in range(self.world_size))
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(self.world_size),
            "PADDLE_TRAINER_ENDPOINTS": endpoints,
            "PADDLE_CURRENT_ENDPOINT":
                f"{self.master_endpoint.split(':')[0]}:{9000 + rank}",
            "PADDLE_MASTER": self.master_endpoint,
            "PADDLE_LOCAL_RANK": str(local_rank),
            "PADDLE_NNODES": str(self.nnodes),
            "PADDLE_NNODES_MAX": str(self.max_nnodes),
            "PADDLE_ELASTIC_GEN": str(self.gen),
            # jax.distributed bridge (multi-host TPU bring-up): a separate
            # port from the rendezvous store (see _publish_jax_coordinator;
            # AttributeError here means spawn() ordering broke — fail fast)
            "COORDINATOR_ADDRESS": self.jax_coordinator,
            "JAX_PROCESS_ID": str(rank),
            "JAX_NUM_PROCESSES": str(self.world_size),
            # where the watchdog drops flightdump.<rank>.json on a
            # collective timeout (collected by _write_flight_report)
            "PADDLE_LOG_DIR": os.path.abspath(self.args.log_dir),
        })
        if self.args.devices:
            env.update(_visible_chips_env(self.args.devices, local_rank,
                                          self.nproc))
        return env

    # -- spawn / watch -------------------------------------------------------
    def _publish_jax_coordinator(self, key: str = "jax/coordinator"):
        """Pick + publish the jax coordination-service endpoint (its OWN
        port — the store already owns master_endpoint's). Called at spawn
        time, not rendezvous, to shrink the free-port TOCTOU window to the
        child's startup; the port is drawn BELOW the Linux ephemeral range
        (32768+) so workers' own outbound connections can't land on it.
        Elastic generations each get their own key (a relaunch needs a
        fresh coordination service)."""
        import random
        import socket
        host = self.master_endpoint.split(":")[0]
        if self.node_rank == 0:
            rnd = random.Random()
            jport = None
            for _ in range(64):
                cand = rnd.randrange(20000, 30000)
                s = socket.socket()
                try:
                    s.bind((host if host != "127.0.0.1" else "", cand))
                    jport = cand
                    break
                except OSError:
                    continue
                finally:
                    s.close()
            if jport is None:
                raise RuntimeError("no free port for the jax coordinator")
            self.store.set(key, f"{host}:{jport}")
        self.jax_coordinator = self.store.wait(
            key, timeout=self.args.rdzv_timeout).decode()

    def spawn(self):
        if not hasattr(self, "jax_coordinator"):
            self._publish_jax_coordinator()
        os.makedirs(self.args.log_dir, exist_ok=True)
        self.procs = []
        for lr in range(self.nproc):
            rank = self.node_rank * self.nproc + lr
            log_path = os.path.join(self.args.log_dir, f"workerlog.{rank}")
            logf = open(log_path, "ab", buffering=0)
            # attempt marker: workerlog.N is opened append-mode across
            # restarts/generations, so post-mortems need to know which
            # attempt produced which lines
            logf.write(f"=== restart {self._restarts} / gen {self.gen} "
                       f"===\n".encode())
            cmd = [sys.executable, "-u", self.args.training_script,
                   *self.args.training_script_args]
            p = subprocess.Popen(cmd, env=self._rank_env(lr), stdout=logf,
                                 stderr=subprocess.STDOUT)
            self.procs.append(_Proc(p, rank, log_path, logf))

    # -- elastic generations -------------------------------------------------
    def _sync(self, key: str, n: int, timeout: float):
        """Store-counter barrier that works at ANY world size (the
        TCPStore barrier is pinned to its construction-time world_size,
        which elastic generations outgrow)."""
        self.store.add(key, 1)
        deadline = time.time() + timeout
        while time.time() < deadline:
            v = self.store.get(key)
            if v is not None and int(v) >= n:
                return
            time.sleep(0.05)
        raise TimeoutError(f"elastic sync {key}: {n} nodes not reached")

    def _world_map(self, gen: int) -> dict:
        import json as _json
        raw = self.store.wait(f"world/g{gen}",
                              timeout=self.args.rdzv_timeout)
        return {int(k): int(v) for k, v in _json.loads(raw).items()}

    def _enter_generation(self, gen: int):
        """Adopt the world map of `gen`: compacted node_rank, world size,
        fresh per-generation jax coordinator, cross-node spawn sync."""
        wmap = self._world_map(gen)
        if self.slot not in wmap:
            return False                      # scaled out of the job
        self.gen = gen
        self.node_rank = wmap[self.slot]
        self.nnodes = len(wmap)
        self.world_size = self.nnodes * self.nproc
        self.current_world = sorted(wmap)
        self._publish_jax_coordinator(f"jax/coordinator/g{gen}")
        self._sync(f"sync/g{gen}", self.nnodes, self.args.rdzv_timeout)
        return True

    def _elastic_poll(self) -> Optional[str]:
        """One elastic tick inside watch(): heartbeat our slot, let the
        LEADER (lowest alive slot) publish a new generation on membership
        change, and follow any generation bump. Returns 'respawned' after
        re-entering a new generation, 'exit' when this node was scaled
        out or lost its slot, None otherwise."""
        try:
            self.elastic.heartbeat()
        except RuntimeError:
            # slot reclaimed by a newer owner — we paused past the TTL
            self._kill_all()
            return "exit"
        ev = self.elastic.watch_once(self.current_world)
        if ev and ev["ranks"] is not None \
                and ev["alive"][0] == self.slot:
            # leader publishes the next generation (followers see the
            # gen bump below; HOLD publishes nothing and we keep polling)
            import json as _json
            nxt = self.gen + 1
            self.store.set(f"world/g{nxt}", _json.dumps(ev["ranks"]))
            self.store.set("gen", str(nxt))
        g = self.store.get("gen")
        if g is not None and int(g) > self.gen:
            self._kill_all()
            if not self._enter_generation(int(g)):
                return "exit"
            self.spawn()
            return "respawned"
        return None

    def _kill_all(self, sig=signal.SIGTERM, grace: float = 5.0):
        for pr in self.procs:
            if pr.popen.poll() is None:
                pr.popen.send_signal(sig)
        deadline = time.time() + grace
        for pr in self.procs:
            left = max(0.1, deadline - time.time())
            try:
                pr.popen.wait(timeout=left)
            except subprocess.TimeoutExpired:
                pr.popen.kill()
        for pr in self.procs:
            pr.log_file.close()

    def watch(self) -> int:
        """Poll children; on failure either restart the pod (up to
        --max_restarts) or tear down and propagate the exit code. With
        elastic enabled, each poll also heartbeats the membership slot and
        follows generation bumps (join -> scale-up relaunch, quorum loss
        -> hold, slot theft -> exit)."""
        while True:
            alive = 0
            restarted = False
            for pr in self.procs:
                rc = pr.popen.poll()
                if rc is None:
                    alive += 1
                elif rc != 0:
                    if self._restarts < self.args.max_restarts:
                        self._restarts += 1
                        self._kill_all()
                        self.spawn()
                        restarted = True
                        break
                    self._kill_all()
                    self._write_flight_report(rc)
                    return rc
            if restarted:
                continue
            if alive == 0:
                for pr in self.procs:
                    pr.log_file.close()
                return 0
            # elastic tick AFTER the children check: when the job just
            # completed everywhere, peers stop heartbeating as they exit —
            # a controller that still holds exited-0 children must report
            # success, not chase the departing membership into a
            # pointless extra generation
            if self.elastic is not None:
                act = self._elastic_poll()
                if act == "exit":
                    return 3                  # scaled out of the job
                if act == "respawned":
                    continue
            time.sleep(self.args.poll_interval)

    def _write_flight_report(self, rc: int) -> Optional[str]:
        """Post-mortem merge (ISSUE 3): on terminal child failure, collect
        any per-rank flightdump.<rank>.json the watchdog wrote into the log
        dir and merge them into one flight_report.json naming the lagging
        rank and the first divergent op. Best-effort: a job that died for
        non-collective reasons has no dumps and writes no report."""
        import glob as _glob
        import json as _json
        dumps = []
        for p in sorted(_glob.glob(
                os.path.join(self.args.log_dir, "flightdump.*.json"))):
            try:
                with open(p) as f:
                    dumps.append(_json.load(f))
            except (OSError, ValueError):
                continue
        if not dumps:
            return None
        from .. import watchdog as _wd
        report = _wd.merge_dumps(dumps)
        report["exit_code"] = rc
        report["restarts"] = self._restarts
        report["gen"] = self.gen
        out = os.path.join(self.args.log_dir, "flight_report.json")
        try:
            with open(out, "w") as f:
                _json.dump(report, f, indent=2)
        except OSError:
            return None
        return out

    def _elastic_setup(self):
        """Create the membership manager; founders register their own
        slot and the master seeds generation 0's world map; a JOINER
        (--elastic_join) claims a free slot instead and adopts the next
        generation the leader publishes for it."""
        import json as _json
        ttl = getattr(self.args, "elastic_ttl", 10.0)
        self.elastic = ElasticManager(self.store, self.slot, ttl=ttl,
                                      min_nodes=self.nnodes,
                                      max_nodes=self.max_nnodes)
        if getattr(self.args, "elastic_join", False):
            self.slot = self.elastic.claim_slot()
            g = self.store.get("gen")
            self.gen = int(g) if g is not None else 0
            # wait for the leader to notice our heartbeat and publish the
            # scale-up generation that includes us
            deadline = time.time() + self.args.rdzv_timeout
            while time.time() < deadline:
                self.elastic.heartbeat()
                g = self.store.get("gen")
                if g is not None and int(g) > self.gen:
                    if not self._enter_generation(int(g)):
                        raise RuntimeError(
                            "joined but the new generation excludes us")
                    return
                time.sleep(self.args.poll_interval)
            raise TimeoutError(
                "elastic join: no scale-up generation published "
                f"within {self.args.rdzv_timeout}s")
        self.elastic.register_slot()
        self.elastic.heartbeat()
        if self.node_rank == 0:
            self.store.set(
                "world/g0",
                _json.dumps({i: i for i in range(self.nnodes)}))
            self.store.set("gen", "0")
        self._enter_generation(0)

    def run(self) -> int:
        if self.elastic_on and getattr(self.args, "elastic_join", False):
            # joiner: client-connect to the running job's store, no
            # founding rendezvous barrier
            host, port = self._master_hostport()
            self.store = TCPStore(host=host, port=port, is_master=False,
                                  world_size=1,
                                  timeout=self.args.rdzv_timeout)
            self.master_endpoint = f"{host}:{port}"
            self._elastic_setup()
        else:
            self.rendezvous()
            if self.elastic_on:
                self._elastic_setup()
        self.spawn()
        try:
            return self.watch()
        finally:
            if self.store is not None:
                self.store.close()


class ElasticManager:
    """Membership watcher (ref: fleet/elastic/manager.py ElasticManager
    over etcd): nodes heartbeat TTL keys in the store (the etcd-lease
    equivalent); scale events trigger relaunch with regenerated ranks.

    min:max nnodes semantics (the reference's ``--nnodes 2:4``): the job
    runs with any alive membership in [min_nodes, max_nodes]. A LEAVE
    below min_nodes is a HOLD (wait for rejoin, do not relaunch smaller);
    a JOIN claims the first free/stale heartbeat slot (``claim_slot``) and
    — while below max_nodes — triggers a scale-up relaunch that includes
    the newcomer. ``watch_once`` is the etcd-watch equivalent the
    controller polls; it returns the event + the new compacted rank map.
    """

    def __init__(self, store: TCPStore, node_rank: int, ttl: float = 10.0,
                 min_nodes: int = 1, max_nodes: Optional[int] = None):
        self.store = store
        self.node_rank = node_rank
        self.ttl = ttl
        self.min_nodes = min_nodes
        self.max_nodes = max_nodes
        self._token: Optional[int] = None
        self._stop = False

    def register_slot(self) -> None:
        """Take an ownership token for this node's own slot (founders call
        this once at bring-up; joiners get theirs via claim_slot). The
        token makes slot ownership verifiable: heartbeat() refuses to keep
        a slot whose claim counter moved past our token."""
        self._token = self.store.add(f"claim/{self.node_rank}", 1)

    def heartbeat(self, payload: Optional[str] = None) -> None:
        if self._token is not None:
            cur = self.store.get(f"claim/{self.node_rank}")
            if cur is not None and int(cur) != self._token:
                raise RuntimeError(
                    f"elastic slot {self.node_rank} was reclaimed by a "
                    f"newer owner (claim {int(cur)} > ours {self._token}): "
                    "this node paused past the TTL and must exit")
        # liveness ts first; anything after '|' is an opaque payload
        # channel (alive_nodes splits it off) — the collective watchdog
        # publishes per-rank flight progress through it
        val = str(time.time())
        if payload:
            val = f"{val}|{payload}"
        self.store.set(f"heartbeat/{self.node_rank}", val)

    def alive_nodes(self, nnodes: int) -> List[int]:
        now = time.time()
        out = []
        for i in range(nnodes):
            v = self.store.get(f"heartbeat/{i}")
            if v is not None and \
                    now - float(v.split(b"|")[0]) < self.ttl:
                out.append(i)
        return out

    def membership_changed(self, expected: int) -> bool:
        return len(self.alive_nodes(expected)) != expected

    def claim_slot(self, max_nodes: Optional[int] = None) -> int:
        """A JOINING node takes the first free or TTL-stale heartbeat slot
        below max_nodes and starts heartbeating it (ref: elastic join =
        taking an etcd lease). The claim is ATOMIC: `add(claim/<i>)` is the
        store's fetch-and-add, so two racing joiners get distinct tokens
        and only the one whose token survives the re-check keeps the slot;
        a stale previous owner that resumes later sees the moved counter
        at its next heartbeat() and must exit (split-brain fence). Raises
        when the job is already at max_nnodes."""
        mx = max_nodes if max_nodes is not None else self.max_nodes
        if mx is None:
            raise ValueError("claim_slot needs max_nodes")
        now = time.time()
        for i in range(mx):
            v = self.store.get(f"heartbeat/{i}")
            if v is None or now - float(v.split(b"|")[0]) >= self.ttl:
                token = self.store.add(f"claim/{i}", 1)
                # re-check: if someone claimed between our read and our
                # add, the slot has a FRESH heartbeat now — only proceed
                # when it is still free/stale (our token is then the
                # newest and fences the loser)
                v2 = self.store.get(f"heartbeat/{i}")
                if v2 is not None and \
                        time.time() - float(v2.split(b"|")[0]) < self.ttl:
                    continue
                self.node_rank = i
                self._token = token
                self.heartbeat()
                return i
        raise RuntimeError(
            f"no free elastic slot: job already at max_nnodes={mx}")

    @staticmethod
    def _compact(alive) -> dict:
        """Old-slot -> new-node-rank map (survivors keep order)."""
        return {old: new for new, old in enumerate(sorted(alive))}

    def watch_once(self, current, max_nodes: Optional[int] = None):
        """One poll of the membership watch loop. ``current`` is the slot
        set of the running world. Returns None while membership is
        unchanged, else a dict:
          {"event": "scale_up"|"scale_in"|"rescale"|"hold",
           "alive": sorted slots,
           "ranks": {old_slot: new_node_rank} or None when holding}
        scale_up = pure join, scale_in = pure leave, rescale = both in
        one poll window. HOLD means alive dropped below min_nodes: keep
        the checkpointed state, keep polling, relaunch only when a rejoin
        restores quorum (the reference pauses the job the same way)."""
        mx = max_nodes if max_nodes is not None else self.max_nodes
        if mx is None:
            raise ValueError("watch_once needs max_nodes")
        alive = set(self.alive_nodes(mx))
        cur = set(current)
        if alive == cur:
            return None
        if len(alive) < self.min_nodes:
            return {"event": "hold", "alive": sorted(alive), "ranks": None}
        joined, left = alive - cur, cur - alive
        event = ("rescale" if joined and left
                 else "scale_up" if joined else "scale_in")
        return {"event": event, "alive": sorted(alive),
                "ranks": self._compact(alive)}

    def regenerate_ranks(self, nnodes: int) -> dict:
        """Compacted old-rank -> new-rank map over the surviving members
        (ref: ElasticManager's rank regeneration on a scale-in event). The
        relaunch then re-runs the launcher with nnodes=len(map) and each
        survivor's new node_rank."""
        return self._compact(self.alive_nodes(nnodes))
