"""tools/perf_gate.py: band derivation from the committed BENCH /
SERVING_BENCH artifacts, pass on current values, fail on a synthetically
regressed candidate row, and the non-fatal no-artifact path the verify
wiring relies on."""

import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import perf_gate  # noqa: E402


@pytest.fixture()
def mini_repo(tmp_path):
    """A scratch repo with one pretrain round + repeats + one serving
    row, so band math is assertable exactly."""
    (tmp_path / "docs").mkdir()
    with open(tmp_path / "BENCH_r01.json", "w") as f:
        json.dump({"parsed": {"metric": "pretrain_tps", "value": 1000.0}},
                  f)
    with open(tmp_path / "BENCH_r02.json", "w") as f:
        json.dump({"parsed": {"metric": "pretrain_tps", "value": 1010.0}},
                  f)
    with open(tmp_path / "docs" / "BENCH_REPEATS_r2.json", "w") as f:
        json.dump({"metric": "pretrain_tps",
                   "runs": [995.0, 1005.0, 1015.0],
                   "r1_band": [990.0, 1020.0]}, f)
    with open(tmp_path / "docs" / "SERVING_BENCH.json", "w") as f:
        json.dump({"decode": {"decode_tokens_per_s_per_chip": 200.0},
                   "note": "not a row"}, f)
    return str(tmp_path)


OBSERVATORY = {
    "kernels": [
        {"kernel": "ragged_paged_attention", "launches": 70,
         "bytes": 1.8e6},
        {"kernel": "fused_rms_norm", "launches": 140, "bytes": 3.2e5},
    ],
    "serving": {"bytes_per_token_model": 4e5,
                "bytes_per_token_measured": 4.1e5,
                "measured_over_model": 1.025},
}


@pytest.fixture()
def obs_repo(mini_repo):
    with open(os.path.join(mini_repo, "docs", "OBSERVATORY.json"),
              "w") as f:
        json.dump(OBSERVATORY, f)
    return mini_repo


class TestBands:
    def test_pretrain_band_is_union_of_runs_and_bands(self, mini_repo):
        rows = perf_gate.pretrain_rows(mini_repo, margin=0.0)
        assert len(rows) == 1
        r = rows[0]
        assert r["key"] == "pretrain.pretrain_tps"
        assert r["value"] == 1010.0          # latest round wins
        assert r["band"] == [990.0, 1020.0]  # union(runs, r1_band)
        assert r["ok"]

    def test_margin_widens_band(self, mini_repo):
        r = perf_gate.pretrain_rows(mini_repo, margin=0.01)[0]
        assert r["band"][0] == pytest.approx(990.0 * 0.99)
        assert r["band"][1] == pytest.approx(1020.0 * 1.01)

    def test_serving_rows_banded_by_noise(self, mini_repo):
        rows = perf_gate.serving_rows(mini_repo, noise=0.10)
        assert len(rows) == 1
        r = rows[0]
        assert r["key"] == "serving.decode.decode_tokens_per_s_per_chip"
        assert r["band"] == [pytest.approx(180.0), pytest.approx(220.0)]
        assert r["ok"]

    def test_no_repeats_falls_back_to_round_spread(self, mini_repo):
        os.unlink(os.path.join(mini_repo, "docs",
                               "BENCH_REPEATS_r2.json"))
        r = perf_gate.pretrain_rows(mini_repo, margin=0.0)[0]
        assert r["band"] == [1000.0, 1010.0]


class TestCheck:
    def test_regressed_candidate_fails(self, mini_repo, tmp_path):
        cand = tmp_path / "cand.json"
        with open(cand, "w") as f:
            json.dump({"pretrain.pretrain_tps": 900.0}, f)
        rc = perf_gate.main(["--repo", mini_repo, "--check", str(cand)])
        assert rc == 1

    def test_inband_candidate_passes(self, mini_repo, tmp_path):
        cand = tmp_path / "cand.json"
        with open(cand, "w") as f:
            json.dump({"pretrain.pretrain_tps": 1012.0,
                       "serving.decode.decode_tokens_per_s_per_chip":
                           190.0}, f)
        rc = perf_gate.main(["--repo", mini_repo, "--check", str(cand)])
        assert rc == 0

    def test_above_band_is_rerate_not_failure(self, mini_repo):
        rows = perf_gate.gate_rows(mini_repo, margin=0.0)
        out = perf_gate.check_candidate(
            {"pretrain.pretrain_tps": 5000.0}, rows)
        assert out[0]["ok"]   # higher-is-better: exceeding band passes

    def test_unknown_key_fails_loudly(self, mini_repo):
        rows = perf_gate.gate_rows(mini_repo)
        out = perf_gate.check_candidate({"pretrain.typo_tps": 1.0}, rows)
        assert not out[0]["ok"]
        assert out[0]["why"] == "unknown metric key"


class TestObservatoryRows:
    """ISSUE 11: per-kernel bytes-and-launches bands over
    docs/OBSERVATORY.json, two-sided (more traffic AND broken
    accounting both fail)."""

    def test_rows_derived_two_sided(self, obs_repo):
        rows = perf_gate.observatory_rows(obs_repo, noise=0.10)
        by_key = {r["key"]: r for r in rows}
        r = by_key["observatory.kernel.ragged_paged_attention.bytes"]
        assert r["direction"] == "both"
        assert r["band"] == [pytest.approx(1.62e6), pytest.approx(1.98e6)]
        assert set(by_key) >= {
            "observatory.kernel.fused_rms_norm.launches",
            "observatory.serving.bytes_per_token_model",
            "observatory.serving.bytes_per_token_measured",
            "observatory.serving.measured_over_model"}
        # the ratio row carries the absolute 25% acceptance band
        assert by_key["observatory.serving.measured_over_model"]["band"] \
            == list(perf_gate.OBSERVATORY_RATIO_BAND)
        assert all(r["ok"] for r in rows)

    def test_self_check_fails_when_ratio_out_of_band(self, obs_repo):
        art = dict(OBSERVATORY,
                   serving=dict(OBSERVATORY["serving"],
                                measured_over_model=1.4))
        with open(os.path.join(obs_repo, "docs", "OBSERVATORY.json"),
                  "w") as f:
            json.dump(art, f)
        assert perf_gate.main(["--repo", obs_repo]) == 1

    def test_bytes_growth_fails_both_directions(self, obs_repo):
        rows = perf_gate.gate_rows(obs_repo, noise=0.10)
        key = "observatory.kernel.ragged_paged_attention.bytes"
        grown = perf_gate.check_candidate({key: 1.8e6 * 1.5}, rows)
        shrunk = perf_gate.check_candidate({key: 1.8e6 * 0.5}, rows)
        inband = perf_gate.check_candidate({key: 1.8e6 * 1.05}, rows)
        assert not grown[0]["ok"] and not shrunk[0]["ok"]
        assert inband[0]["ok"]

    def test_unknown_kernel_exits_one(self, obs_repo, tmp_path):
        cand = tmp_path / "cand.json"
        art = {"kernels": [{"kernel": "mystery", "launches": 1,
                            "bytes": 10.0}], "serving": {}}
        with open(cand, "w") as f:
            json.dump(art, f)
        assert perf_gate.main(["--repo", obs_repo,
                               "--check", str(cand)]) == 1

    def test_missing_field_exits_one(self, obs_repo, tmp_path):
        cand = tmp_path / "cand.json"
        art = {"kernels": [{"kernel": "ragged_paged_attention",
                            "launches": 70}],   # bytes omitted
               "serving": dict(OBSERVATORY["serving"])}
        with open(cand, "w") as f:
            json.dump(art, f)
        assert perf_gate.main(["--repo", obs_repo,
                               "--check", str(cand)]) == 1

    def test_observatory_candidate_in_band_passes(self, obs_repo,
                                                  tmp_path):
        cand = tmp_path / "cand.json"
        with open(cand, "w") as f:
            json.dump(OBSERVATORY, f)
        assert perf_gate.main(["--repo", obs_repo,
                               "--check", str(cand)]) == 0

    def test_committed_artifact_roundtrips(self):
        # the real docs/OBSERVATORY.json must gate green against its
        # own bands (the acceptance criterion)
        path = os.path.join(REPO, "docs", "OBSERVATORY.json")
        assert os.path.exists(path)
        assert perf_gate.main(["--repo", REPO, "--check", path]) == 0

    def test_no_observatory_artifact_is_fine(self, mini_repo):
        assert perf_gate.observatory_rows(mini_repo) == []
        assert perf_gate.main(["--repo", mini_repo]) == 0


class TestCli:
    def test_no_artifacts_exit_zero(self, tmp_path):
        rc = perf_gate.main(["--repo", str(tmp_path)])
        assert rc == 0

    @staticmethod
    def _repo_with_round(tmp_path, scale=1.0):
        """The repo's committed docs/ artifacts plus one pretrain round
        record written here (the root BENCH_r*.json rounds of the retired
        chip environment are gone; the gate's reading of such a record
        is what these cases hold)."""
        shutil.copytree(os.path.join(REPO, "docs"),
                        str(tmp_path / "docs"),
                        ignore=shutil.ignore_patterns("*.md"))
        with open(os.path.join(REPO, "docs", "BENCH_REPEATS_r5.json")) as f:
            rep = json.load(f)
        with open(tmp_path / "BENCH_r05.json", "w") as f:
            json.dump({"parsed": {"metric": rep["metric"],
                                  "value": rep["runs"][0] * scale}}, f)
        return str(tmp_path)

    def test_self_check_on_committed_artifacts(self, tmp_path, capsys):
        # the repo's own artifacts must gate green (the acceptance
        # criterion + the verify-skill wiring)
        rc = perf_gate.main(["--repo", self._repo_with_round(tmp_path)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "pretrain." in out and "serving." in out

    def test_synthetic_regression_on_committed_artifacts(self, tmp_path):
        # the same artifacts with the pretrain row regressed 20%: expect 1
        rc = perf_gate.main(
            ["--repo", self._repo_with_round(tmp_path, scale=0.8)])
        assert rc == 1

    def test_json_mode(self, mini_repo, capsys):
        rc = perf_gate.main(["--repo", mini_repo, "--json"])
        assert rc == 0
        rep = json.loads(capsys.readouterr().out)
        assert rep["failed"] == 0
        assert {r["key"] for r in rep["rows"]} == {
            "pretrain.pretrain_tps",
            "serving.decode.decode_tokens_per_s_per_chip"}


class TestVmemDriftCheck:
    """ISSUE PR13 CI satellite: observatory candidates are cross-checked
    against a costmodel recompute at their own recorded scenario, judged
    at the SAME tolerance as paddlelint's PF406 (one shared constant)."""

    def _committed(self):
        with open(os.path.join(REPO, "docs", "OBSERVATORY.json")) as f:
            return json.load(f)

    def test_tolerance_is_shared_with_the_analyzer(self):
        from paddle_tpu.analysis import vmemmodel
        assert perf_gate.COST_DRIFT_RTOL is vmemmodel.COST_DRIFT_RTOL

    def test_committed_artifact_recomputes_exactly(self):
        rows = perf_gate.vmem_drift_rows(self._committed())
        assert len(rows) >= 5            # the full decode-layer chain
        assert all(r["ok"] for r in rows)
        assert all(r["value"] == r["band"][0] for r in rows)

    def test_candidate_without_scenario_fields_is_skipped(self):
        # artifacts predating the scenario extension stay green
        assert perf_gate.vmem_drift_rows(OBSERVATORY) == []
        art = self._committed()
        del art["scenario"]["hidden"]
        assert perf_gate.vmem_drift_rows(art) == []

    def test_drift_inside_noise_band_is_still_rejected(self, tmp_path):
        # +8% bytes: inside the 15% observatory noise band (the
        # per-kernel row passes) but beyond the 5% static tolerance —
        # exactly the stale-cost-table case the noise band cannot see
        art = self._committed()
        row = next(k for k in art["kernels"]
                   if k["kernel"] == "fused_ffn")
        row["bytes"] = int(row["bytes"] * 1.08)
        rows = perf_gate.vmem_drift_rows(art)
        bad = [r for r in rows if not r["ok"]]
        assert [r["key"] for r in bad] \
            == ["observatory.vmem.fused_ffn.bytes"]
        assert "static memory model" in bad[0]["why"]
        cand = tmp_path / "cand.json"
        with open(cand, "w") as f:
            json.dump(art, f)
        assert perf_gate.main(["--repo", REPO,
                               "--check", str(cand)]) == 1

    def test_unmodeled_kernel_rows_are_ignored(self):
        art = self._committed()
        art["kernels"].append({"kernel": "not_in_registry",
                               "bytes": 123, "launches": 1})
        keys = {r["key"] for r in perf_gate.vmem_drift_rows(art)}
        assert "observatory.vmem.not_in_registry.bytes" not in keys
