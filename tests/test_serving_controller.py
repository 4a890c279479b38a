"""Fleet SLO autopilot (ISSUE 18): the two-scope feedback controller.

Covers the `EngineController` actuators (chunk up/down with hysteresis
and cooldown, spec-k cut-to-off, prefix-admission gating, graduated
shedding), `ServingEngine.reconfigure` greedy-exactness + single-entry
program caches, the `shed` terminal trace outcome (distinct from
`refused`/`overloaded`, carried into chrome export and fleet
stitching), the readmit/poll_elastic cold-stats warmup weights
(dogpile regression), the `FleetController` (weight rebalance, role
flips through the PR-15 drain path, capacity-loss guard), seeded
convergence properties (settles, bounded flips, cooldown honored), and
the scenario-level acceptance: controller-on meets the declared
step-indexed SLO targets that the static config provably misses, plus
a combined replica-kill + thrash chaos soak with zero request loss."""

import json
import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu import resilience as res
from paddle_tpu.observability import fleet as fleet_mod
from paddle_tpu.observability import tracing as tracing_mod
from paddle_tpu.serving import (EngineController, FleetController,
                                FleetRouter, ServingEngine, SLOTargets)
from paddle_tpu.serving import workloads
from paddle_tpu.serving.scheduler import Request, Scheduler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


@pytest.fixture(autouse=True)
def _obs_on():
    pm, pt = obs.enabled(), tracing_mod.enabled()
    obs.set_enabled(True)
    tracing_mod.set_enabled(True)
    yield
    obs.set_enabled(pm)
    tracing_mod.set_enabled(pt)


@pytest.fixture(scope="module")
def model():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
    cfg = llama_tiny_config(num_hidden_layers=1)
    paddle.seed(0)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _engine(model, **kw):
    base = dict(max_slots=2, page_size=4, prefill_chunk=4)
    base.update(kw)
    return ServingEngine(model, **base)


def _queue(eng, n, start=0):
    """Park `n` real requests in the admission queue (controller
    sensors read len(waiting); no device work is run)."""
    for i in range(start, start + n):
        eng.scheduler.submit(Request(np.arange(1, 5, dtype=np.int32), 2,
                                     request_id=f"q{i}"))


def _run(eng, prompt, max_new=4, rid="r0"):
    eng.add_request(prompt, max_new, request_id=rid)
    while eng.has_work():
        eng.step()
    return eng.collect()[rid]


# ---------------------------------------------------------------------------
# SLOTargets
# ---------------------------------------------------------------------------

class TestSLOTargets:
    def test_as_row_drops_none_and_sorts(self):
        t = SLOTargets(ttft_p90_steps=8, e2e_p90_ms=None)
        row = t.as_row()
        assert "e2e_p90_ms" not in row and "ttft_p90_ms" not in row
        assert row["ttft_p90_steps"] == 8
        assert row["queue_depth"] == 4 and row["shed_priority"] == 0
        assert list(row) == sorted(row)

    def test_shed_disabled_by_none(self, model):
        eng = _engine(model)
        ctl = EngineController(eng, SLOTargets(queue_depth=1,
                                               shed_priority=None),
                               patience=1, cooldown=1)
        _queue(eng, 6)
        for _ in range(10):
            ctl.on_step()
        assert ctl.shed_level == 0 and ctl.flips["shed"] == 0


# ---------------------------------------------------------------------------
# EngineController actuators (no device stepping: sensors are counts)
# ---------------------------------------------------------------------------

class TestEngineController:
    def test_chunk_escalates_then_releases(self, model):
        eng = _engine(model)
        ctl = EngineController(eng, SLOTargets(queue_depth=2),
                               patience=1, cooldown=1)
        _queue(eng, 5)
        for _ in range(6):
            ctl.on_step()
        assert eng.prefill_chunk == ctl.max_chunk == 16
        assert eng.rebuilds >= 2
        ups = [d for d in ctl.decisions if d["actuator"] == "prefill_chunk"
               and d["direction"] == "up"]
        assert ups and all("queue_depth" in d for d in ups)
        eng.scheduler.waiting.clear()
        for _ in range(12):
            ctl.on_step()
        assert eng.prefill_chunk == ctl.base_chunk == 4
        assert any(d["direction"] == "down" for d in ctl.decisions
                   if d["actuator"] == "prefill_chunk")

    def test_steady_pressure_bounds_flips(self, model):
        """Convergence: a constant overload moves the chunk actuator a
        bounded number of times (4 -> 8 -> 16, then it holds)."""
        eng = _engine(model)
        ctl = EngineController(eng, SLOTargets(queue_depth=2))
        _queue(eng, 8)
        for _ in range(60):
            ctl.on_step()
        assert eng.prefill_chunk == 16
        assert ctl.flips["prefill_chunk"] == 2
        assert ctl.flips["shed"] <= 2      # escalated and then held

    def test_cooldown_spacing_honored(self, model):
        eng = _engine(model)
        ctl = EngineController(eng, SLOTargets(queue_depth=1),
                               patience=1, cooldown=5)
        _queue(eng, 6)
        for _ in range(20):
            ctl.on_step()
        moves = [d["step"] for d in ctl.decisions
                 if d["actuator"] == "prefill_chunk"]
        assert moves
        assert all(b - a >= 5 for a, b in zip(moves, moves[1:]))

    def test_frozen_actuator_never_moves(self, model):
        """Runbook override: freezing an actuator pins it."""
        eng = _engine(model)
        ctl = EngineController(eng, SLOTargets(queue_depth=1),
                               patience=1, cooldown=1)
        ctl.frozen.add("prefill_chunk")
        _queue(eng, 6)
        for _ in range(10):
            ctl.on_step()
        assert eng.prefill_chunk == 4
        assert ctl.flips["prefill_chunk"] == 0

    def test_guard_pressures_without_queue(self, model):
        """FleetController capacity-loss guard: pressure with an EMPTY
        queue (the pre-emptive tightening after a drain)."""
        eng = _engine(model)
        ctl = EngineController(eng, SLOTargets(queue_depth=4),
                               patience=1, cooldown=1)
        ctl.guard(4)
        for _ in range(3):
            ctl.on_step()
        assert eng.prefill_chunk > 4
        assert ctl.flips["prefill_chunk"] >= 1

    def test_shed_escalates_to_refusal_and_releases(self, model):
        eng = _engine(model)
        ctl = EngineController(eng, SLOTargets(queue_depth=1,
                                               shed_priority=0),
                               patience=1, cooldown=1)
        _queue(eng, 6)
        for _ in range(8):
            ctl.on_step()
        assert ctl.shed_level == 2
        assert eng.scheduler.shed_below_priority == 0
        with pytest.raises(res.Shed):
            eng.add_request(np.arange(1, 5, dtype=np.int32), 2,
                            request_id="victim", priority=-1)
        # priority >= floor still admits while shedding
        eng.add_request(np.arange(1, 5, dtype=np.int32), 2,
                        request_id="vip", priority=1)
        eng.scheduler.waiting.clear()
        for _ in range(12):
            ctl.on_step()
        assert ctl.shed_level == 0
        assert eng.scheduler.shed_below_priority is None
        assert eng.scheduler.queue_timeout_s == ctl._base_timeout

    def test_spec_k_cuts_to_off_and_never_rearms(self, model):
        eng = _engine(model, spec_decode=2)
        ctl = EngineController(eng, SLOTargets(spec_accept=0.9),
                               patience=1, cooldown=1, min_spec_sample=4)
        eng.spec_drafted, eng.spec_accepted = 10, 1   # 10% acceptance
        ctl.on_step()
        assert eng.spec_k == 1
        eng.spec_drafted += 10
        ctl.on_step()
        assert eng.spec_k == 0
        for _ in range(10):                            # never auto re-raises
            ctl.on_step()
        assert eng.spec_k == 0 and ctl.flips["spec_k"] == 2
        cut = [d for d in ctl.decisions if d["actuator"] == "spec_k"]
        assert all(d["direction"] == "down" for d in cut)
        assert cut[0]["accept_rate"] == 0.1
        # the runbook re-arm path: an operator reconfigure
        assert eng.reconfigure(spec_decode=2) is True
        assert eng.spec_k == 2

    def test_prefix_admission_hysteresis(self, model):
        eng = _engine(model)
        ctl = EngineController(eng, SLOTargets(pool_high=0.5,
                                               pool_low=0.2),
                               patience=1, cooldown=1)
        stats = {"utilization": 0.0}
        eng.allocator.stats = lambda: stats         # sensor stub
        stats["utilization"] = 0.9
        ctl.on_step()
        assert eng.prefix_cache_admit is False
        stats["utilization"] = 0.4                  # inside the band
        ctl.on_step()
        assert eng.prefix_cache_admit is False      # hysteresis holds
        stats["utilization"] = 0.1
        ctl.on_step()
        assert eng.prefix_cache_admit is True
        assert ctl.flips["prefix_admit"] == 2

    def test_decisions_traced_with_measurement(self, model):
        tracing_mod.recorder().clear()
        eng = _engine(model, replica="r0")
        ctl = EngineController(eng, SLOTargets(queue_depth=1),
                               patience=1, cooldown=1)
        _queue(eng, 4)
        ctl.on_step()
        ctls = [t for t in tracing_mod.recorder().finished()
                if t.kind == "controller"]
        assert ctls
        tr = ctls[0]
        assert tr.outcome == "decision"
        last = tr.timeline()[-1].meta
        assert last["actuator"] == "prefill_chunk"
        assert last["queue_depth"] == 4
        assert "utilization" in last

    def test_convergence_property_seeded(self, model):
        """Seeded property: any ramp-then-drain load settles — bounded
        flips, chunk back at base, and every move outside cooldown."""
        eng = _engine(model)
        for seed in (0, 1, 2):
            rng = np.random.default_rng(seed)
            ctl = EngineController(eng, SLOTargets(queue_depth=3),
                                   patience=2, cooldown=4)
            eng.reconfigure(prefill_chunk=4)
            for step in range(80):
                depth = int(rng.integers(4, 9)) if step < 40 else 0
                eng.scheduler.waiting = [None] * depth
                ctl.on_step()
            eng.scheduler.waiting = []
            assert eng.prefill_chunk == 4, f"seed {seed} did not settle"
            assert sum(ctl.flips.values()) <= 10, f"seed {seed} oscillated"
            for a in ctl.ACTUATORS:
                moves = [d["step"] for d in ctl.decisions
                         if d["actuator"] == a]
                assert all(b - x >= 4 for x, b in zip(moves, moves[1:]))


# ---------------------------------------------------------------------------
# reconfigure: greedy-exact, single-entry program caches
# ---------------------------------------------------------------------------

class TestReconfigure:
    def test_outputs_exact_across_chunk_change(self, model):
        rng = np.random.RandomState(3)
        prompt = rng.randint(1, model.config.vocab_size, 10).astype(np.int32)
        ref = _run(_engine(model), prompt)
        eng = _engine(model)
        assert eng.reconfigure(prefill_chunk=8) is True
        assert eng.rebuilds == 1
        np.testing.assert_array_equal(_run(eng, prompt), ref)
        assert all(v <= 1 for v in eng.program_cache_sizes().values())

    def test_noop_reconfigure_skips_rebuild(self, model):
        eng = _engine(model)
        assert eng.reconfigure(prefill_chunk=4) is False
        assert eng.reconfigure() is False
        assert eng.rebuilds == 0

    def test_rebuild_midstream_keeps_decode_exact(self, model):
        rng = np.random.RandomState(4)
        prompt = rng.randint(1, model.config.vocab_size, 8).astype(np.int32)
        ref = _run(_engine(model), prompt, max_new=6)
        eng = _engine(model)
        eng.add_request(prompt, 6, request_id="r0")
        for _ in range(3):
            eng.step()
        eng.reconfigure(prefill_chunk=8)     # mid-request, pages intact
        while eng.has_work():
            eng.step()
        np.testing.assert_array_equal(eng.collect()["r0"], ref)


# ---------------------------------------------------------------------------
# the `shed` terminal outcome (satellite 2)
# ---------------------------------------------------------------------------

class TestShedOutcome:
    def test_shed_distinct_from_refused_with_measurement(self):
        tracing_mod.recorder().clear()
        sched = Scheduler(1, max_inflight=1)
        sched.submit(Request(np.arange(1, 4, dtype=np.int32), 2,
                             request_id="ok"))
        with pytest.raises(res.Overloaded) as over:
            sched.submit(Request(np.arange(1, 4, dtype=np.int32), 2,
                                 request_id="full"))
        assert not isinstance(over.value, res.Shed)
        sched.shed_below_priority = 0
        sched.shed_measurement = {"queue_depth": 7, "utilization": 0.9}
        with pytest.raises(res.Shed) as shed:
            sched.submit(Request(np.arange(1, 4, dtype=np.int32), 2,
                                 request_id="victim", priority=-1))
        assert shed.value.measurement["queue_depth"] == 7
        fins = {t.request_id: t
                for t in tracing_mod.recorder().finished()}
        assert fins["full"].outcome == "refused"
        assert fins["victim"].outcome == "shed"
        meta = fins["victim"].timeline()[-1].meta
        assert meta["priority"] == -1 and meta["floor"] == 0
        assert meta["queue_depth"] == 7     # the triggering measurement

    def test_shed_rides_chrome_export_and_fleet_stitch(
            self, model, tmp_path):
        tracing_mod.recorder().clear()
        eng = _engine(model, replica="r0")
        eng.scheduler.shed_below_priority = 0
        before = obs.snapshot()["serving.engine.requests"]
        with pytest.raises(res.Shed):
            eng.add_request(np.arange(1, 5, dtype=np.int32), 2,
                            request_id="shed-1", priority=-1)
        # the engine counter grows a distinct outcome label value
        series = {tuple(sorted(s["labels"].items())): s["value"]
                  for s in obs.snapshot()["serving.engine.requests"]
                  ["series"]}
        old = {tuple(sorted(s["labels"].items())): s["value"]
               for s in before["series"]}
        key = (("outcome", "shed"),)
        assert series[key] == old.get(key, 0) + 1
        p1 = str(tmp_path / "solo.json")
        tracing_mod.recorder().export_chrome_trace(p1)
        assert any(e.get("args", {}).get("outcome") == "shed"
                   for e in json.load(open(p1))["traceEvents"])
        p2 = str(tmp_path / "fleet.json")
        fleet_mod.stitch_chrome_trace(p2)
        assert any(e.get("args", {}).get("outcome") == "shed"
                   for e in json.load(open(p2))["traceEvents"])


# ---------------------------------------------------------------------------
# readmit / poll_elastic cold-stats warmup weights (satellite 1)
# ---------------------------------------------------------------------------

class TestReadmitWeights:
    def _router(self, model, n=2):
        engines = {f"r{i}": _engine(model, replica=f"r{i}")
                   for i in range(n)}
        return FleetRouter(engines), engines

    def test_readmit_seeds_weight_from_last_scrape(self, model):
        router, engines = self._router(model)
        for i in range(3):
            engines["r0"].add_request(np.arange(1, 6, dtype=np.int32), 2,
                                      request_id=f"w{i}")
        router.scrape()                       # federated view cached
        router.drain("r0")
        router.readmit("r0")
        # the busier it went down, the deeper the discount
        assert router.placement_weight["r0"] == \
            pytest.approx(router.readmit_warmup / (1.0 + 3))
        assert router.placement_weight["r1"] == 1.0

    def test_readmit_without_scrape_uses_default_warmup(self, model):
        router, _ = self._router(model)
        router.drain("r1")
        router.readmit("r1")
        assert router.placement_weight["r1"] == router.readmit_warmup

    def test_cold_weight_charges_phantom_load(self, model):
        """The dogpile regression: an empty just-readmitted replica must
        NOT outscore a warm one — the warmup weight charges phantom
        queue load until the ramp restores it."""
        router, engines = self._router(model)
        prompt = np.arange(1, 6, dtype=np.int32)
        router.placement_weight["r0"] = 0.5
        cold, _ = router._score(engines["r0"], prompt)
        warm, _ = router._score(engines["r1"], prompt)
        assert cold < warm
        phantom = router.queue_cost_tokens * 0.5 * router.warmup_load
        assert warm - cold == pytest.approx(phantom)

    def test_weight_ramps_back_per_step(self, model):
        router, _ = self._router(model)
        router.drain("r0")
        router.readmit("r0")
        w0 = router.placement_weight["r0"]
        assert w0 < 1.0
        router.step()
        assert router.placement_weight["r0"] == \
            pytest.approx(min(1.0, w0 + router.weight_recovery))
        for _ in range(6):
            router.step()
        assert router.placement_weight["r0"] == 1.0

    def test_poll_elastic_readmit_is_warmup_seeded(self, model):
        class FlappingElastic:
            def __init__(self):
                self.alive = [0, 1]

            def alive_nodes(self, n):
                return self.alive

        engines = {f"r{i}": _engine(model, replica=f"r{i}")
                   for i in range(2)}
        el = FlappingElastic()
        router = FleetRouter(engines, elastic=el)
        el.alive = [1]
        router.poll_elastic()
        assert router.live_replicas() == ["r1"]
        el.alive = [0, 1]
        router.poll_elastic()
        assert router.live_replicas() == ["r0", "r1"]
        assert router.placement_weight["r0"] == router.readmit_warmup


# ---------------------------------------------------------------------------
# FleetController: rebalance, role shifts, capacity guard
# ---------------------------------------------------------------------------

class TestFleetController:
    def test_rebalance_discounts_hot_replica(self, model):
        engines = {f"r{i}": _engine(model, max_slots=1, replica=f"r{i}")
                   for i in range(3)}
        router = FleetRouter(engines)
        fc = FleetController(router, SLOTargets(), interval=1)
        for i in range(8):
            engines["r0"].scheduler.submit(
                Request(np.arange(1, 5, dtype=np.int32), 2,
                        request_id=f"h{i}"))
        fc.on_step()
        assert router.placement_weight["r0"] == 0.5
        assert router.placement_weight["r1"] == 1.0
        assert fc.flips["weight"] == 1
        d = [d for d in fc.decisions if d["action"] == "rebalance"][0]
        assert d["replica"] == "r0" and d["load"] == 8

    def test_role_flip_on_handoff_backlog_never_last(self, model):
        engines = {"pf0": _engine(model, role="prefill", replica="pf0"),
                   "pf1": _engine(model, role="prefill", replica="pf1"),
                   "dec0": _engine(model, role="decode", replica="dec0")}
        router = FleetRouter(engines)
        fc = FleetController(router, SLOTargets(), interval=1,
                             handoff_backlog=2, role_patience=2)
        router._pending.extend([object(), object()])   # standing backlog
        fc.on_step()
        assert fc.flips["role"] == 0                    # patience not met
        fc.on_step()
        assert fc.flips["role"] == 1
        roles = sorted(e.role for e in engines.values())
        assert roles == ["decode", "decode", "prefill"]
        router._pending.clear()
        # with one prefill replica left, a backlog can never flip it
        router._pending.extend([object(), object()])
        for _ in range(6):
            fc.on_step()
        assert sum(e.role == "prefill" for e in engines.values()) == 1

    def test_capacity_loss_guards_survivors(self, model):
        slo = SLOTargets(queue_depth=4)
        engines = {f"r{i}": _engine(model, replica=f"r{i}",
                                    slo_targets=slo)
                   for i in range(2)}
        router = FleetRouter(engines)
        fc = FleetController(router, slo, guard_steps=6)
        assert router.controller is fc
        router.drain("r0")
        assert fc.flips["guard"] == 1
        assert engines["r1"].controller._guard == 6
        d = [d for d in fc.decisions if d["action"] == "capacity_guard"][0]
        assert d["lost"] == "r0" and d["survivors"] == 1
        # role repurposing is NOT a capacity loss: no second guard
        router.readmit("r0")
        router.set_role("r0", "prefill")
        assert fc.flips["guard"] == 1


# ---------------------------------------------------------------------------
# scenario-level acceptance: autopilot meets what static misses
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def burst_pair(model):
    obs.set_enabled(True)
    tracing_mod.set_enabled(True)
    return (workloads.run_scenario("burst", model),
            workloads.run_scenario("burst", model, autopilot=True))


@pytest.fixture(scope="module")
def thrash_pair(model):
    obs.set_enabled(True)
    tracing_mod.set_enabled(True)
    return (workloads.run_scenario("thrash", model),
            workloads.run_scenario("thrash", model, autopilot=True))


def _meets(row, field):
    return row[field] <= row["slo"][field]


class TestAutopilotAcceptance:
    def test_burst_on_meets_targets_static_misses(self, burst_pair):
        off, on = burst_pair
        for f in ("ttft_p90_steps", "e2e_p90_steps"):
            assert _meets(on, f), (f, on[f], on["slo"][f])
        assert not all(_meets(off, f)
                       for f in ("ttft_p90_steps", "e2e_p90_steps"))
        # the control loop never costs correctness or availability
        assert on["output_checksum"] == off["output_checksum"]
        assert on["zero_loss"] == off["zero_loss"] == 1
        assert on["shed"] == 0

    def test_thrash_on_meets_targets_static_misses(self, thrash_pair):
        off, on = thrash_pair
        for f in ("ttft_p90_steps", "e2e_p90_steps"):
            assert _meets(on, f), (f, on[f], on["slo"][f])
        assert not all(_meets(off, f)
                       for f in ("ttft_p90_steps", "e2e_p90_steps"))
        assert on["output_checksum"] == off["output_checksum"]
        assert on["zero_loss"] == off["zero_loss"] == 1

    def test_autopilot_row_replays_bit_exactly(self, model, burst_pair):
        """The determinism contract behind the committed _autopilot
        rows: controller sensors are counts, never clocks."""
        _, on = burst_pair
        again = workloads.run_scenario("burst", model, autopilot=True)
        for f in workloads.ROW_DETERMINISTIC:
            assert again[f] == on[f], f
        assert again["autopilot"] == 1
        assert again["scenario"] == "burst_autopilot"

    def test_replica_kill_autopilot_zero_loss_and_recovery(self, model):
        row = workloads.run_scenario("replica_kill", model,
                                     autopilot=True)
        assert row["zero_loss"] == 1
        assert row["completed"] == row["requests"]
        assert row["handoffs"] > row["requests"]    # the drain re-export
        for f in ("ttft_p90_steps", "e2e_p90_steps"):
            assert _meets(row, f), (f, row[f], row["slo"][f])

    def test_chaos_soak_thrash_plus_replica_kill(self, model):
        """Soak: the thrash adversary AND a mid-run replica kill with
        both controller scopes live — zero accepted-request loss, the
        fleet converges back to idle, and the capacity guard fired."""
        slo = SLOTargets(queue_depth=3, pool_high=0.7, pool_low=0.4)
        engines = {
            "pf0": _engine(model, role="prefill", replica="pf0",
                           slo_targets=slo),
            "dec0": _engine(model, role="decode", replica="dec0",
                            slo_targets=slo),
            "dec1": _engine(model, role="decode", replica="dec1",
                            slo_targets=slo),
        }
        router = FleetRouter(engines)
        fc = FleetController(router, slo)
        rng = np.random.default_rng(12)
        V = model.config.vocab_size
        shared = rng.integers(1, V, 8).astype(np.int32)
        submitted = []
        for step in range(10):
            if step < 4:   # good tenant: shared prefix
                rid = f"good{step}"
                router.submit(np.concatenate(
                    [shared, rng.integers(1, V, 2).astype(np.int32)]),
                    3, request_id=rid, tenant="good")
                submitted.append(rid)
            if step < 6:   # adversary: never-repeating prompts
                rid = f"evil{step}"
                router.submit(rng.integers(1, V, 12).astype(np.int32),
                              2, request_id=rid, tenant="adversary")
                submitted.append(rid)
            if step == 5:
                router.drain("dec0")
            if step == 8:
                router.readmit("dec0")
            router.step()
        results = router.run_to_completion()
        assert sorted(results) == sorted(submitted)   # zero request loss
        assert all(len(v) > 0 for v in results.values())
        assert fc.flips["guard"] >= 1                  # drain was guarded
        assert not router.has_work()                   # converged to idle
        summary = router.step_slo_summary()
        assert summary["e2e_p90_steps"] is not None


# ---------------------------------------------------------------------------
# bench-row plumbing for the autopilot artifacts
# ---------------------------------------------------------------------------

class TestArtifactPlumbing:
    def test_rows_declare_their_slo_targets(self, burst_pair):
        off, on = burst_pair
        for row in (off, on):
            assert row["slo"]["ttft_p90_steps"] == 13
            assert row["slo"]["e2e_p90_steps"] == 20
        assert off["autopilot"] == 0 and on["autopilot"] == 1

    def test_committed_artifact_has_paired_autopilot_rows(self):
        with open(os.path.join(REPO, "docs", "FLEET_BENCH.json")) as f:
            art = json.load(f)
        for name in workloads.SCENARIOS:
            assert name in art["scenarios"]
            ap = art["scenarios"].get(f"{name}_autopilot")
            assert ap is not None, f"{name}_autopilot row missing"
            assert ap["autopilot"] == 1
            assert ap["shed"] == 0
            assert ap["zero_loss"] == 1
            # paired rows ran the same traffic: greedy-exact outputs
            assert ap["output_checksum"] == \
                art["scenarios"][name]["output_checksum"]

    def test_perf_gate_bands_cover_autopilot_rows(self):
        import perf_gate
        rows = {r["key"]: r for r in perf_gate.fleet_rows(REPO)}
        for name in workloads.SCENARIOS:
            for f in ("ttft_p90_steps", "e2e_p90_steps", "shed"):
                key = f"fleet.{name}_autopilot.{f}"
                assert key in rows, key
                assert rows[key]["direction"] == "both"
                assert rows[key]["band"][0] == rows[key]["band"][1]
        assert rows["fleet.burst_autopilot.ttft_p99_ms"]["direction"] \
            == "lower"
        assert rows["fleet.burst_autopilot.e2e_p99_ms"]["direction"] \
            == "lower"
