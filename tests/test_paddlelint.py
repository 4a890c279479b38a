"""paddlelint (paddle_tpu.analysis): per-rule true-positive/negative
fixtures, suppression comments, baseline round-trip, the whole-repo CI
gate, and seeded-defect detection in scratch copies of real modules.

The fixtures are the rule contract: each PTxxx has at least one snippet
the rule MUST flag and one structurally-similar snippet it must NOT flag
(the negative encodes the false-positive class the analyzer was tuned
against — shape branches, split-then-use keys, lock-guarded writes)."""

import json
import os
import shutil
import textwrap

import pytest

from paddle_tpu.analysis import (Config, analyze_paths, analyze_source,
                                 load_baseline, save_baseline,
                                 split_baseline)
from paddle_tpu.analysis.cli import main as lint_main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lint(src, **cfg_kw):
    return analyze_source(textwrap.dedent(src), Config(**cfg_kw))


def _rules(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------- PT001

class TestPT001TracerLeak:
    def test_branch_on_traced_value(self):
        fs = _lint("""
            import jax

            @jax.jit
            def f(x):
                if x > 0:
                    return x
                return x * 2
        """)
        assert _rules(fs) == ["PT001"]
        assert fs[0].severity == "error"
        assert "branch" in fs[0].detail

    def test_host_conversion_of_traced_value(self):
        fs = _lint("""
            import jax

            @jax.jit
            def f(x):
                return float(x) * 2
        """)
        assert _rules(fs) == ["PT001"]
        assert "float" in fs[0].detail

    def test_item_on_traced_value(self):
        fs = _lint("""
            import jax

            @jax.jit
            def f(x):
                y = x + 1
                return y.item()
        """)
        assert _rules(fs) == ["PT001"]

    def test_taint_propagates_through_local_call(self):
        # interprocedural: leak is in a helper only reachable with a
        # traced argument
        fs = _lint("""
            import jax

            def helper(v):
                if v > 0:
                    return v
                return -v

            @jax.jit
            def f(x):
                return helper(x * 2)
        """)
        assert "PT001" in _rules(fs)
        assert any(f.qualname == "helper" for f in fs)

    def test_shape_branch_is_not_a_leak(self):
        # .shape / .ndim / len() are static under trace
        fs = _lint("""
            import jax

            @jax.jit
            def f(x):
                if x.shape[0] > 1 and x.ndim == 2:
                    return x * 2
                return x
        """)
        assert "PT001" not in _rules(fs)

    def test_static_argnums_param_exempt(self):
        fs = _lint("""
            import functools
            import jax

            @functools.partial(jax.jit, static_argnums=(1,))
            def f(x, mode):
                if mode == "fast":
                    return x * 2
                return x
        """)
        assert "PT001" not in _rules(fs)

    def test_isinstance_guard_exempts_name(self):
        fs = _lint("""
            import jax

            @jax.jit
            def f(x, s=None):
                if isinstance(s, int) and s == 0:
                    return x
                return x * 2
        """)
        assert "PT001" not in _rules(fs)


# ---------------------------------------------------------------- PT002

class TestPT002RetraceHazard:
    def test_jit_inside_loop(self):
        fs = _lint("""
            import jax

            def build(fns):
                outs = []
                for fn in fns:
                    outs.append(jax.jit(fn))
                return outs
        """)
        assert _rules(fs) == ["PT002"]
        assert "jit-in-loop" in fs[0].detail

    def test_unhashable_static_argnums(self):
        fs = _lint("""
            import jax

            def build(fn):
                return jax.jit(fn, static_argnums={1, 2})
        """)
        assert _rules(fs) == ["PT002"]
        assert "static-args" in fs[0].detail

    def test_module_level_jit_ok(self):
        fs = _lint("""
            import jax

            def step(x):
                return x * 2

            jitted = jax.jit(step)
        """)
        assert "PT002" not in _rules(fs)

    def test_shape_branch_reported_only_under_strict(self):
        src = """
            import jax

            @jax.jit
            def f(x):
                if x.shape[0] > 1:
                    return x * 2
                return x
        """
        assert "PT002" not in _rules(_lint(src))
        strict = [f for f in _lint(src, strict=True) if f.rule == "PT002"]
        assert strict and strict[0].severity == "info"


# ---------------------------------------------------------------- PT003

class TestPT003HostSync:
    def test_sync_in_hot_entry(self):
        fs = _lint("""
            class Trainer:
                def training_step(self, batch):
                    loss = self.step(batch)
                    return loss.item()
        """)
        assert _rules(fs) == ["PT003"]
        assert "sync" in fs[0].detail

    def test_sync_reachable_from_hot_entry(self):
        fs = _lint("""
            def _log(loss):
                return float(loss.numpy())

            def training_step(batch):
                loss = batch * 2
                return _log(loss)
        """)
        assert "PT003" in _rules(fs)
        assert any(f.qualname == "_log" for f in fs)

    def test_sync_outside_hot_region_ok(self):
        fs = _lint("""
            def summarize(loss):
                return loss.item()

            def unrelated(batch):
                return summarize(batch)
        """)
        assert "PT003" not in _rules(fs)


# ---------------------------------------------------------------- PT004

class TestPT004RngHygiene:
    def test_key_reuse(self):
        fs = _lint("""
            import jax

            def sample(key):
                a = jax.random.normal(key, (2,))
                b = jax.random.uniform(key, (2,))
                return a + b
        """)
        assert _rules(fs) == ["PT004"]
        assert "key-reuse" in fs[0].detail

    def test_split_then_use_ok(self):
        fs = _lint("""
            import jax

            def sample(key):
                key, sub = jax.random.split(key)
                a = jax.random.normal(sub, (2,))
                key, sub = jax.random.split(key)
                b = jax.random.uniform(sub, (2,))
                return a + b
        """)
        assert "PT004" not in _rules(fs)

    def test_host_rng_in_traced_code(self):
        fs = _lint("""
            import jax
            import numpy as np

            @jax.jit
            def f(x):
                noise = np.random.randn(4)
                return x + noise
        """)
        assert "PT004" in _rules(fs)
        assert any("host-rng" in f.detail for f in fs)

    def test_host_rng_outside_trace_ok(self):
        fs = _lint("""
            import numpy as np

            def make_batch(n):
                return np.random.randn(n, 4)
        """)
        assert "PT004" not in _rules(fs)


# ---------------------------------------------------------------- PT005

class TestPT005FlagsAtTraceTime:
    def test_flags_guard_in_traced_function(self):
        fs = _lint("""
            import jax
            from paddle_tpu.flags import flags_guard

            @jax.jit
            def f(x):
                with flags_guard(flash_impl="composite"):
                    return x * 2
        """)
        assert _rules(fs) == ["PT005"]
        assert "flags" in fs[0].detail

    def test_set_flags_in_traced_function(self):
        fs = _lint("""
            import jax
            import paddle_tpu

            @jax.jit
            def f(x):
                paddle_tpu.set_flags({"FLAGS_flash_impl": "intree"})
                return x * 2
        """)
        assert _rules(fs) == ["PT005"]

    def test_flags_outside_trace_ok(self):
        fs = _lint("""
            import paddle_tpu

            def configure():
                paddle_tpu.set_flags({"FLAGS_flash_impl": "intree"})
        """)
        assert "PT005" not in _rules(fs)


# ---------------------------------------------------------------- PT006

class TestPT006SharedState:
    def test_unguarded_global_write_from_thread(self):
        fs = _lint("""
            import threading

            _events = []
            _count = 0

            def _worker():
                global _count
                _count += 1
                _events.append("tick")

            def start():
                threading.Thread(target=_worker, daemon=True).start()
        """)
        assert _rules(fs) == ["PT006"]
        assert {f.detail for f in fs} == {"write:_count", "write:_events"}

    def test_lock_guarded_write_ok(self):
        fs = _lint("""
            import threading

            _lock = threading.Lock()
            _count = 0

            def _worker():
                global _count
                with _lock:
                    _count += 1

            def start():
                threading.Thread(target=_worker, daemon=True).start()
        """)
        assert "PT006" not in _rules(fs)

    def test_local_rebind_ok(self):
        # a local that shadows a module global is not shared state
        fs = _lint("""
            import threading

            _count = 0

            def _worker():
                _count = 1
                return _count

            def start():
                threading.Thread(target=_worker, daemon=True).start()
        """)
        assert "PT006" not in _rules(fs)

    def test_same_write_outside_thread_region_ok(self):
        fs = _lint("""
            _events = []

            def record(e):
                _events.append(e)
        """)
        assert "PT006" not in _rules(fs)

    def test_trace_ring_exporter_unguarded_flagged(self):
        # the observability.tracing background-exporter shape with the
        # lock REMOVED: flush thread drains a module-level ring — PT006
        fs = _lint("""
            import threading

            _ring = []

            def _flush_loop():
                while _ring:
                    _ring.pop()

            def start_exporter():
                threading.Thread(target=_flush_loop,
                                 daemon=True).start()
        """)
        assert "PT006" in _rules(fs)
        assert any(f.detail == "write:_ring" for f in fs)

    def test_trace_ring_exporter_lock_guarded_ok(self):
        # the shipped recorder discipline: every ring access from the
        # flush thread sits under the one module lock
        fs = _lint("""
            import threading

            _lock = threading.Lock()
            _ring = []

            def _flush_loop():
                with _lock:
                    while _ring:
                        _ring.pop()

            def start_exporter():
                threading.Thread(target=_flush_loop,
                                 daemon=True).start()
        """)
        assert "PT006" not in _rules(fs)


# ----------------------------------------------------------- suppression

class TestSuppression:
    LEAKY = """
        import jax

        @jax.jit
        def f(x):
            if x > 0:{comment}
                return x
            return x * 2
    """

    def test_line_suppression(self):
        src = self.LEAKY.format(comment="  # paddlelint: disable=PT001")
        assert _lint(src) == []

    def test_wrong_rule_does_not_suppress(self):
        src = self.LEAKY.format(comment="  # paddlelint: disable=PT003")
        assert _rules(_lint(src)) == ["PT001"]

    def test_file_wide_suppression(self):
        src = ("# paddlelint: disable-file=PT001\n"
               + textwrap.dedent(self.LEAKY.format(comment="")))
        assert analyze_source(src, Config()) == []

    def test_disable_all(self):
        src = self.LEAKY.format(comment="  # paddlelint: disable=all")
        assert _lint(src) == []


# -------------------------------------------------------------- baseline

class TestBaseline:
    def _findings(self):
        return _lint("""
            import jax

            @jax.jit
            def f(x):
                return float(x)
        """)

    def test_round_trip(self, tmp_path):
        fs = self._findings()
        path = str(tmp_path / "baseline.json")
        save_baseline(path, fs, {fs[0].baseline_key: "accepted: legacy"})
        loaded = load_baseline(path)
        assert loaded == {fs[0].baseline_key: "accepted: legacy"}
        fresh, stale = split_baseline(fs, loaded)
        assert fresh == [] and stale == []

    def test_key_is_line_number_free(self):
        a = self._findings()[0]
        b = _lint("""
            import jax

            # shifted down by a comment block: the baseline key must
            # not move with the line number
            @jax.jit
            def f(x):
                return float(x)
        """)[0]
        assert a.line != b.line
        assert a.baseline_key == b.baseline_key

    def test_split_reports_fresh_and_stale(self, tmp_path):
        fs = self._findings()
        fresh, stale = split_baseline(fs, {"PT999|gone.py|f|x": "old"})
        assert [f.rule for f in fresh] == ["PT001"]
        assert stale == ["PT999|gone.py|f|x"]

    def test_missing_justification_stamped(self, tmp_path):
        fs = self._findings()
        path = str(tmp_path / "baseline.json")
        save_baseline(path, fs, {})
        with open(path) as f:
            data = json.load(f)
        assert data["entries"][0]["justification"] == "TODO: justify"


# ------------------------------------------------------------------ CLI

class TestCli:
    def _write(self, tmp_path, src):
        p = tmp_path / "mod.py"
        p.write_text(textwrap.dedent(src))
        return str(p)

    LEAKY = """
        import jax

        @jax.jit
        def f(x):
            return float(x)
    """

    def test_exit_one_on_findings(self, tmp_path, capsys):
        assert lint_main([self._write(tmp_path, self.LEAKY)]) == 1
        out = capsys.readouterr().out
        assert "PT001" in out and "1 finding(s)" in out

    def test_exit_zero_when_clean(self, tmp_path, capsys):
        assert lint_main([self._write(tmp_path, "x = 1\n")]) == 0

    def test_json_output(self, tmp_path, capsys):
        assert lint_main(["--json", self._write(tmp_path, self.LEAKY)]) == 1
        data = json.loads(capsys.readouterr().out)
        assert data["findings"][0]["rule"] == "PT001"
        assert "PT001" in data["rules"]

    def test_baseline_gates_to_zero(self, tmp_path, capsys):
        mod = self._write(tmp_path, self.LEAKY)
        base = str(tmp_path / "base.json")
        assert lint_main([mod, "--baseline", base,
                          "--write-baseline"]) == 0
        capsys.readouterr()
        assert lint_main([mod, "--baseline", base]) == 0
        assert "1 baselined" in capsys.readouterr().out

    def test_stale_baseline_reported(self, tmp_path, capsys):
        mod = self._write(tmp_path, self.LEAKY)
        base = str(tmp_path / "base.json")
        assert lint_main([mod, "--baseline", base,
                          "--write-baseline"]) == 0
        clean = self._write(tmp_path, "x = 1\n")
        capsys.readouterr()
        assert lint_main([clean, "--baseline", base]) == 0
        assert "stale baseline" in capsys.readouterr().out
        assert lint_main([clean, "--baseline", base,
                          "--fail-stale"]) == 1

    def test_rules_subset(self, tmp_path):
        mod = self._write(tmp_path, self.LEAKY)
        assert lint_main(["--rules", "PT006", mod]) == 0
        assert lint_main(["--rules", "PT001", mod]) == 1

    def test_unknown_rule_is_usage_error(self, tmp_path):
        assert lint_main(["--rules", "PT999",
                          self._write(tmp_path, "x = 1\n")]) == 2


# ------------------------------------------------- whole-repo CI gate

class TestRepoGate:
    def test_package_clean_against_baseline(self, capsys):
        """The tier-1 gate: paddlelint over paddle_tpu/ must produce zero
        non-baselined findings (same invocation as tools/paddlelint.py)."""
        rc = lint_main([os.path.join(REPO, "paddle_tpu"), "--baseline",
                        os.path.join(REPO, "tools",
                                     "paddlelint_baseline.json")])
        out = capsys.readouterr().out
        assert rc == 0, f"paddlelint gate failed:\n{out}"
        assert "0 finding(s)" in out

    def test_baseline_entries_are_justified(self):
        base = load_baseline(os.path.join(
            REPO, "tools", "paddlelint_baseline.json"))
        for key, justification in base.items():
            assert justification and "TODO" not in justification, key


# ------------------------------------------- seeded-defect detection

class TestSeededDefects:
    """Acceptance check: the analyzer must catch a tracer leak and an
    unguarded shared-state write seeded into scratch copies of the real
    modules it is meant to police."""

    def _scratch(self, tmp_path, rel, appended):
        dst = tmp_path / os.path.basename(rel)
        shutil.copy(os.path.join(REPO, rel), dst)
        with open(dst, "a") as f:
            f.write(textwrap.dedent(appended))
        return str(dst)

    def test_seeded_tracer_leak_in_trainer(self, tmp_path):
        clean = analyze_paths(
            [self._scratch(tmp_path, "paddle_tpu/trainer/trainer.py", "")])
        seeded = analyze_paths([self._scratch(
            tmp_path, "paddle_tpu/trainer/trainer.py", """

            import jax as _seeded_jax

            @_seeded_jax.jit
            def _seeded_step(loss):
                if loss > 0:
                    return loss
                return float(loss)
            """)])
        new = {f.baseline_key for f in seeded} - {f.baseline_key
                                                  for f in clean}
        hits = [f for f in seeded if f.baseline_key in new
                and f.rule == "PT001" and f.qualname == "_seeded_step"]
        assert len(hits) == 2  # the branch AND the float()

    def test_seeded_unguarded_write_in_watchdog(self, tmp_path):
        clean = analyze_paths([self._scratch(
            tmp_path, "paddle_tpu/distributed/watchdog.py", "")])
        assert not [f for f in clean if f.rule == "PT006"]
        seeded = analyze_paths([self._scratch(
            tmp_path, "paddle_tpu/distributed/watchdog.py", """

            _seeded_flight_log = []

            def _seeded_recorder_loop():
                _seeded_flight_log.append("tick")

            def _seeded_start_recorder():
                threading.Thread(target=_seeded_recorder_loop,
                                 daemon=True).start()
            """)])
        hits = [f for f in seeded if f.rule == "PT006"
                and f.qualname == "_seeded_recorder_loop"]
        assert len(hits) == 1
        assert hits[0].detail == "write:_seeded_flight_log"


# ---------------------------------------------------- kernel rules (PK)

_PALLAS_HEADER = """\
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.oracles import register_oracle


def _ref(*args, **kwargs):
    return args[0]

"""


_CERTIFY = """
register_oracle("run", kernel=run, reference=_ref,
                parity_test="tests/test_oracles.py::TestOracleParity")
"""


def _klint(src, certify=True, **cfg_kw):
    """Pallas fixture: shared header (imports + a dummy reference) plus,
    by default, a register_oracle on `run` so PK105 never pollutes the
    other rules' assertions."""
    body = _PALLAS_HEADER + textwrap.dedent(src)
    if certify:
        body += _CERTIFY
    return analyze_source(body, Config(**cfg_kw))


class TestPK101IndexMapOob:
    def test_unclamped_prefetch_table_read(self):
        fs = _klint("""
            def _kern(tab_ref, x_ref, o_ref):
                o_ref[:] = x_ref[:]

            def run(x, table):
                return pl.pallas_call(
                    _kern,
                    grid_spec=pltpu.PrefetchScalarGridSpec(
                        num_scalar_prefetch=1,
                        grid=(4,),
                        in_specs=[pl.BlockSpec(
                            (1, 128), lambda i, tab: (tab[i], 0))],
                        out_specs=pl.BlockSpec(
                            (1, 128), lambda i, tab: (i, 0)),
                    ),
                    out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                )(table, x)
        """)
        assert _rules(fs) == ["PK101"]
        assert fs[0].severity == "error"
        assert fs[0].detail.startswith("oob:in1:")

    def test_clamped_table_read_ok(self):
        fs = _klint("""
            def _kern(tab_ref, x_ref, o_ref):
                o_ref[:] = x_ref[:]

            def run(x, table):
                return pl.pallas_call(
                    _kern,
                    grid_spec=pltpu.PrefetchScalarGridSpec(
                        num_scalar_prefetch=1,
                        grid=(4,),
                        in_specs=[pl.BlockSpec(
                            (1, 128),
                            lambda i, tab: (jnp.clip(tab[i], 0, 7), 0))],
                        out_specs=pl.BlockSpec(
                            (1, 128), lambda i, tab: (i, 0)),
                    ),
                    out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                )(table, x)
        """)
        assert fs == []

    def test_literal_negative_block_index(self):
        fs = _klint("""
            def _kern(x_ref, o_ref):
                o_ref[:] = x_ref[:]

            def run(x):
                return pl.pallas_call(
                    _kern,
                    grid=(4,),
                    in_specs=[pl.BlockSpec((1, 128), lambda i: (-1, 0))],
                    out_specs=pl.BlockSpec((1, 128), lambda i: (i, 0)),
                    out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                )(x)
        """)
        assert _rules(fs) == ["PK101"]
        assert fs[0].detail.startswith("neg:in0:")


class TestPK102BlockSpecMismatch:
    def test_index_map_return_arity_vs_block_rank(self):
        fs = _klint("""
            def _kern(x_ref, o_ref):
                o_ref[:] = x_ref[:]

            def run(x):
                return pl.pallas_call(
                    _kern,
                    grid=(4,),
                    in_specs=[pl.BlockSpec((1, 128), lambda i: i)],
                    out_specs=pl.BlockSpec((1, 128), lambda i: (i, 0)),
                    out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                )(x)
        """)
        assert _rules(fs) == ["PK102"]
        assert "rank:in0:1!=2" == fs[0].detail

    def test_index_map_param_count_vs_grid(self):
        fs = _klint("""
            def _kern(x_ref, o_ref):
                o_ref[:] = x_ref[:]

            def run(x):
                return pl.pallas_call(
                    _kern,
                    grid=(4,),
                    in_specs=[pl.BlockSpec((1, 128),
                                           lambda i, j: (i, 0))],
                    out_specs=pl.BlockSpec((1, 128), lambda i: (i, 0)),
                    out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                )(x)
        """)
        assert _rules(fs) == ["PK102"]
        assert "arity:in0:2!=1" == fs[0].detail

    def test_unaligned_lane_dim_is_warning(self):
        fs = _klint("""
            def _kern(x_ref, o_ref):
                o_ref[:] = x_ref[:]

            def run(x):
                return pl.pallas_call(
                    _kern,
                    grid=(4,),
                    in_specs=[pl.BlockSpec((8, 100), lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((8, 100), lambda i: (i, 0)),
                    out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                )(x)
        """)
        assert _rules(fs) == ["PK102"]
        assert all(f.severity == "warning" for f in fs)
        assert {f.detail for f in fs} == {"lane:in0:100", "lane:out0:100"}

    def test_kernel_ref_count_vs_operand_list(self):
        fs = _klint("""
            def _kern(x_ref, y_ref, o_ref):
                o_ref[:] = x_ref[:]

            def run(x):
                return pl.pallas_call(
                    _kern,
                    grid=(4,),
                    in_specs=[pl.BlockSpec((1, 128), lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((1, 128), lambda i: (i, 0)),
                    out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                )(x)
        """)
        assert _rules(fs) == ["PK102"]
        assert fs[0].detail == "refs:3!=2"


class TestPK103AliasHazards:
    def test_alias_index_out_of_range(self):
        fs = _klint("""
            def _kern(x_ref, o_ref):
                o_ref[:] = x_ref[:]

            def run(x):
                return pl.pallas_call(
                    _kern,
                    grid=(4,),
                    in_specs=[pl.BlockSpec((1, 128), lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((1, 128), lambda i: (i, 0)),
                    out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                    input_output_aliases={5: 0},
                )(x)
        """)
        assert _rules(fs) == ["PK103"]
        assert fs[0].detail == "alias-range:5:0"

    def test_widened_alias_dtype(self):
        fs = _klint("""
            def _kern(x_ref, o_ref):
                o_ref[:] = x_ref[:]

            def run(x):
                return pl.pallas_call(
                    _kern,
                    grid=(4,),
                    in_specs=[pl.BlockSpec((1, 128), lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((1, 128), lambda i: (i, 0)),
                    out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
                    input_output_aliases={0: 0},
                )(x)
        """)
        assert _rules(fs) == ["PK103"]
        assert fs[0].detail.startswith("alias-dtype:0:0:")

    def test_matching_alias_pair_ok(self):
        fs = _klint("""
            def _kern(x_ref, o_ref):
                o_ref[:] = x_ref[:]

            def run(x):
                return pl.pallas_call(
                    _kern,
                    grid=(4,),
                    in_specs=[pl.BlockSpec((1, 128), lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((1, 128), lambda i: (i, 0)),
                    out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                    input_output_aliases={0: 0},
                )(x)
        """)
        assert fs == []

    def test_aliased_pair_with_different_specs(self):
        fs = _klint("""
            def _kern(x_ref, o_ref):
                o_ref[:] = x_ref[:]

            def run(x):
                return pl.pallas_call(
                    _kern,
                    grid=(4,),
                    in_specs=[pl.BlockSpec((2, 128), lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((1, 128), lambda i: (i, 0)),
                    out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                    input_output_aliases={0: 0},
                )(x)
        """)
        assert _rules(fs) == ["PK103"]
        assert fs[0].detail == "alias-spec:0:0"

    RAW = """
        def _kern(pg_ref, xin_ref, o_ref):
{body}

        def run(x, pg):
            def page_map(i, pg):
                return (jnp.clip(pg[i], 0, 7), 0)
            spec = pl.BlockSpec((1, 128), page_map)
            return pl.pallas_call(
                _kern,
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=1,
                    grid=(4,),
                    in_specs=[spec],
                    out_specs=spec,
                ),
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                input_output_aliases={{1: 0}},
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("arbitrary",)),
            )(pg, x)
    """

    def test_unguarded_aliased_read_with_revisiting_map(self):
        fs = _klint(self.RAW.format(
            body="            o_ref[:] = xin_ref[:] * 2"))
        assert _rules(fs) == ["PK103"]
        assert fs[0].detail.startswith("alias-raw:xin_ref:")

    def test_seed_on_first_visit_pattern_ok(self):
        fs = _klint(self.RAW.format(body=(
            "            @pl.when(pl.program_id(0) == 0)\n"
            "            def _seed():\n"
            "                o_ref[:] = xin_ref[:]")))
        assert fs == []


class TestPK104SubF32Accumulator:
    MATMUL = """
        def _kern(x_ref, o_ref, acc_ref):
            acc_ref[:] = jax.lax.dot(x_ref[:], x_ref[:]{pet})
            o_ref[:] = acc_ref[:].astype(o_ref.dtype)

        def run(x):
            return pl.pallas_call(
                _kern,
                grid=(4,),
                in_specs=[pl.BlockSpec((128, 128), lambda i: (0, 0))],
                out_specs=pl.BlockSpec((128, 128), lambda i: (0, 0)),
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                scratch_shapes=[pltpu.VMEM((128, 128), {acc})],
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("arbitrary",)),
            )(x)
    """

    def test_bf16_scratch_accumulator(self):
        fs = _klint(self.MATMUL.format(
            pet="", acc="jnp.bfloat16"))
        assert _rules(fs) == ["PK104"]
        assert fs[0].detail.startswith("acc:")

    def test_f32_scratch_ok(self):
        fs = _klint(self.MATMUL.format(
            pet="", acc="jnp.float32"))
        assert fs == []

    def test_sub_f32_preferred_element_type(self):
        fs = _klint(self.MATMUL.format(
            pet=",\n                preferred_element_type=jnp.bfloat16",
            acc="jnp.float32"))
        assert _rules(fs) == ["PK104"]
        assert fs[0].detail.startswith("pet:")

    def test_gate_requires_matmul_or_softmax(self):
        # bf16 scratch in a pure data-movement kernel: not an accumulator
        fs = _klint("""
            def _kern(x_ref, o_ref, tmp_ref):
                tmp_ref[:] = x_ref[:]
                o_ref[:] = tmp_ref[:]

            def run(x):
                return pl.pallas_call(
                    _kern,
                    grid=(4,),
                    in_specs=[pl.BlockSpec((128, 128),
                                           lambda i: (0, 0))],
                    out_specs=pl.BlockSpec((128, 128),
                                           lambda i: (0, 0)),
                    out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                    scratch_shapes=[pltpu.VMEM((128, 128),
                                               jnp.bfloat16)],
                    compiler_params=pltpu.CompilerParams(
                        dimension_semantics=("arbitrary",)),
                )(x)
        """)
        assert fs == []


class TestPK105OracleCertification:
    UNIT = """
        def _kern(x_ref, o_ref):
            o_ref[:] = x_ref[:]

        def run(x):
            return pl.pallas_call(
                _kern,
                grid=(4,),
                in_specs=[pl.BlockSpec((1, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((1, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            )(x)
    """

    def test_uncertified_kernel_flagged(self):
        fs = _klint(self.UNIT, certify=False)
        assert _rules(fs) == ["PK105"]
        assert fs[0].detail == "oracle:run"
        assert fs[0].severity == "warning"

    def test_registration_certifies(self):
        assert _klint(self.UNIT) == []

    def test_certification_reaches_through_wrappers(self):
        # register the public entry; the pallas_call lives two call
        # edges down — the closure must cover it
        fs = _klint("""
            def _kern(x_ref, o_ref):
                o_ref[:] = x_ref[:]

            def _impl(x):
                return pl.pallas_call(
                    _kern,
                    grid=(4,),
                    in_specs=[pl.BlockSpec((1, 128), lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((1, 128), lambda i: (i, 0)),
                    out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                )(x)

            def _dispatch(x):
                return _impl(x)

            def run(x):
                return _dispatch(x)
        """)
        assert fs == []

    def test_certification_follows_defvjp(self):
        # custom_vjp: the kernel call sits in the fwd rule, only the
        # public primal is registered — defvjp linkage must cover it
        fs = _klint("""
            def _kern(x_ref, o_ref):
                o_ref[:] = x_ref[:]

            def _fwd(x):
                y = pl.pallas_call(
                    _kern,
                    grid=(4,),
                    in_specs=[pl.BlockSpec((1, 128), lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((1, 128), lambda i: (i, 0)),
                    out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                )(x)
                return y, x

            def _bwd(res, g):
                return (g,)

            @jax.custom_vjp
            def run(x):
                return _fwd(x)[0]

            run.defvjp(_fwd, _bwd)
        """)
        assert fs == []


class TestKernelResolutionThroughIndirection:
    """The callgraph fix this PR rides on: kernels reached through
    functools.partial locals and factory-returned closures must resolve
    to their FunctionInfo so the PK checks see real params."""

    # indented to match the 12-space method-level fragments it is
    # concatenated onto (dedent runs on the joined string)
    CALL = """
            def run(x):
                {bind}
                return pl.pallas_call(
                    kern,
                    grid=(4,),
                    in_specs=[pl.BlockSpec((1, 128), lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((1, 128), lambda i: (i, 0)),
                    out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                )(x)
    """

    def test_partial_bound_kwargs_subtracted(self):
        fs = _klint("""
            def _kern(x_ref, o_ref, *, eps):
                o_ref[:] = x_ref[:] + eps
        """ + self.CALL.format(
            bind="kern = functools.partial(_kern, eps=1e-6)"))
        assert fs == []

    def test_bad_refs_detected_through_partial(self):
        fs = _klint("""
            def _kern(x_ref, y_ref, o_ref, *, eps):
                o_ref[:] = x_ref[:] + eps
        """ + self.CALL.format(
            bind="kern = functools.partial(_kern, eps=1e-6)"))
        assert _rules(fs) == ["PK102"]
        assert fs[0].detail == "refs:3!=2"

    def test_bad_refs_detected_through_factory_closure(self):
        fs = _klint("""
            def make_kernel(eps):
                def _kern(x_ref, y_ref, o_ref):
                    o_ref[:] = x_ref[:] + eps
                return _kern
        """ + self.CALL.format(
            bind="kern = make_kernel(0.5)"))
        assert _rules(fs) == ["PK102"]
        assert fs[0].detail == "refs:3!=2"


# ------------------------------------------------ collective rule (PC)

class TestPC201BranchDivergentCollective:
    def test_psum_under_python_branch_in_shard_map_body(self):
        fs = _lint("""
            import jax
            from jax.experimental.shard_map import shard_map

            def _body(x):
                if x.shape[0] > 128:
                    x = jax.lax.psum(x, "dp")
                return x

            def run(mesh, x):
                f = shard_map(_body, mesh=mesh, in_specs=None,
                              out_specs=None)
                return f(x)
        """)
        assert _rules(fs) == ["PC201"]
        assert fs[0].severity == "error"
        assert fs[0].qualname == "_body"
        assert fs[0].detail.startswith("branch-collective:psum:")

    def test_collective_in_cond_branch_fn(self):
        fs = _lint("""
            import jax
            from jax.experimental.shard_map import shard_map

            def _yes(x):
                return jax.lax.psum(x, "dp")

            def _no(x):
                return x

            def _body(flag, x):
                return jax.lax.cond(flag, _yes, _no, x)

            def run(mesh, flag, x):
                return shard_map(_body, mesh=mesh, in_specs=None,
                                 out_specs=None)(flag, x)
        """)
        assert _rules(fs) == ["PC201"]
        assert fs[0].qualname == "_yes"
        assert "branch function" in fs[0].message

    def test_straight_line_collective_ok(self):
        fs = _lint("""
            import jax
            from jax.experimental.shard_map import shard_map

            def _body(x):
                return jax.lax.psum(x * 2, "dp")

            def run(mesh, x):
                return shard_map(_body, mesh=mesh, in_specs=None,
                                 out_specs=None)(x)
        """)
        assert "PC201" not in _rules(fs)

    def test_branchy_collective_outside_shard_map_ok(self):
        fs = _lint("""
            import jax

            def helper(x):
                if x.shape[0] > 2:
                    return jax.lax.psum(x, "dp")
                return x
        """)
        assert "PC201" not in _rules(fs)


# ------------------------------------------ CLI: rule listing / filters

class TestCliRuleListing:
    def test_bare_rules_prints_table(self, capsys):
        assert lint_main(["--rules"]) == 0
        out = capsys.readouterr().out
        for rid in ("PT001", "PK101", "PK105", "PC201"):
            assert rid in out
        # one line per rule: id, severity, one-liner
        line = next(ln for ln in out.splitlines()
                    if ln.startswith("PK101"))
        assert "error" in line and "index_map" in line

    def test_list_rules_includes_severity(self, capsys):
        assert lint_main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        assert "PK104" in out and "warning" in out

    def test_only_filters(self, tmp_path):
        p = tmp_path / "mod.py"
        p.write_text(textwrap.dedent("""
            import jax

            @jax.jit
            def f(x):
                return float(x)
        """))
        assert lint_main(["--only", "PT006", str(p)]) == 0
        assert lint_main(["--only", "PT001", str(p)]) == 1

    def test_only_unknown_rule_is_usage_error(self, tmp_path):
        p = tmp_path / "mod.py"
        p.write_text("x = 1\n")
        assert lint_main(["--only", "PK999", str(p)]) == 2

    def test_json_rules_carry_severity(self, tmp_path, capsys):
        p = tmp_path / "mod.py"
        p.write_text("x = 1\n")
        assert lint_main(["--json", str(p)]) == 0
        data = json.loads(capsys.readouterr().out)
        assert data["rules"]["PT001"]["severity"] == "error"
        assert data["rules"]["PK105"]["severity"] == "warning"
        assert "description" in data["rules"]["PC201"]


# ----------------------------------------- whole-repo JSON family gate

class TestRepoJsonGate:
    def test_per_family_summary_and_justified_baseline(self, capsys):
        """ISSUE PR8 acceptance: every rule family reports zero fresh
        findings over the real package and the baseline carries no
        unjustified (empty / TODO-stamped) entries."""
        rc = lint_main([os.path.join(REPO, "paddle_tpu"), "--baseline",
                        os.path.join(REPO, "tools",
                                     "paddlelint_baseline.json"),
                        "--json"])
        data = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert data["schema_version"] == 1
        assert set(data["families"]) == {"PT", "PK", "PC", "PS", "PF",
                                         "PE"}
        for fam, info in sorted(data["families"].items()):
            assert info["fresh"] == 0, (fam, data["findings"])
            assert info["rules"], fam
            assert info["unjustified"] == [], fam
        assert data["baseline"]["unjustified"] == []
        assert data["baseline"]["stale"] == []
        # the single accepted PK entry (fusion JIT's definitional oracle)
        assert data["families"]["PK"]["baselined"] == 1
        assert data["families"]["PK"]["per_rule"]["PK105"]["baselined"] == 1
        # the sharding family gates the whole repo at zero: no fresh
        # findings, no baseline debt
        ps = data["families"]["PS"]
        assert ps["rules"] == ["PS301", "PS302", "PS303", "PS304",
                               "PS305", "PS306"]
        assert ps["baselined"] == 0
        assert all(c == {"fresh": 0, "baselined": 0}
                   for c in ps["per_rule"].values())
        # the memory lane gates at zero debt too: all six rules active,
        # nothing fresh, nothing baselined, nothing unjustified
        pf = data["families"]["PF"]
        assert pf["rules"] == ["PF401", "PF402", "PF403", "PF404",
                               "PF405", "PF406"]
        assert pf["baselined"] == 0
        assert all(c == {"fresh": 0, "baselined": 0}
                   for c in pf["per_rule"].values())
        # the effects lane ships with zero debt from day one: all six
        # rules active, nothing fresh, nothing baselined
        pe = data["families"]["PE"]
        assert pe["rules"] == ["PE501", "PE502", "PE503", "PE504",
                               "PE505", "PE506"]
        assert pe["baselined"] == 0
        assert all(c == {"fresh": 0, "baselined": 0}
                   for c in pe["per_rule"].values())
        # and the machine-readable PE505 verdicts certify every PF404
        # candidate plus the registered <=4-launch layer-body
        # composition (ISSUE 20 shipped the old front-half entry as
        # fused_qkv_rope_append)
        verdicts = {v["candidate"]: v for v in data["pe505_verdicts"]}
        comp = next(v for v in data["pe505_verdicts"]
                    if v["composition"] == "decode_layer_le4")
        assert comp["verdict"] == "legal"
        assert verdicts["fused_oproj_norm->fused_ffn"]["verdict"] \
            == "legal"


# -------------------------------------- seeded kernel/collective defects

class TestSeededKernelDefects:
    """ISSUE PR8 acceptance: each PK/PC rule catches exactly its seeded
    defect in a scratch copy of the real kernel modules, and stays quiet
    on the pristine copies. Copies are analyzed statically — never
    imported — so mutations are plain text edits."""

    RAGGED = "paddle_tpu/ops/pallas_ragged.py"
    FUSED = "paddle_tpu/ops/fused.py"

    def _analyze(self, tmp_path, rel, tag, old="", new="", append=""):
        src = open(os.path.join(REPO, rel)).read()
        if old:
            assert old in src, f"seed anchor vanished from {rel}: {old!r}"
            src = src.replace(old, new, 1)
        d = tmp_path / tag
        d.mkdir(exist_ok=True)
        p = d / os.path.basename(rel)   # same rel/modname as the clean
        p.write_text(src + textwrap.dedent(append))
        return analyze_paths([str(p)])

    def _seed(self, tmp_path, rel, **kw):
        clean = self._analyze(tmp_path, rel, "clean")
        seeded = self._analyze(tmp_path, rel, "seeded", **kw)
        new_keys = ({f.baseline_key for f in seeded}
                    - {f.baseline_key for f in clean})
        return [f for f in seeded if f.baseline_key in new_keys]

    def test_pristine_copies_are_quiet(self, tmp_path):
        for rel in (self.RAGGED, self.FUSED):
            fs = self._analyze(tmp_path, rel, "clean")
            assert [f for f in fs if f.rule.startswith(("PK", "PC"))] \
                == [], rel

    def test_pk101_catches_unclamped_page_table_read(self, tmp_path):
        # the row append's page map (the attention kernels read their
        # tables inside the kernel, for their own page DMAs)
        fresh = self._seed(
            tmp_path, self.FUSED,
            old="            return (0, jnp.clip(runs[2 * G + g], 0, "
                "total - 1),",
            new="            return (0, runs[2 * G + g],")
        assert fresh and {f.rule for f in fresh} == {"PK101"}
        assert all("runs" in f.detail for f in fresh)
        assert {f.qualname for f in fresh} == {"fused_append_rows"}

    def test_pk103_catches_widened_alias_dtype(self, tmp_path):
        fresh = self._seed(
            tmp_path, self.FUSED,
            old="jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype)",
            new="jax.ShapeDtypeStruct(k_pages.shape, jnp.float32)")
        assert fresh and {f.rule for f in fresh} == {"PK103"}
        assert any(f.detail.startswith("alias-dtype:3:0:")
                   for f in fresh)

    def test_pk104_catches_bf16_accumulator(self, tmp_path):
        fresh = self._seed(
            tmp_path, self.RAGGED,
            old="pltpu.VMEM((hb * tb, rows, D), jnp.float32),",
            new="pltpu.VMEM((hb * tb, rows, D), jnp.bfloat16),")
        assert fresh and {f.rule for f in fresh} == {"PK104"}
        assert fresh[0].detail.startswith("acc:")

    def test_pc201_catches_branch_divergent_psum(self, tmp_path):
        fresh = self._seed(tmp_path, self.FUSED, append="""

            from jax.experimental.shard_map import shard_map

            def _seeded_allreduce(x):
                if x.shape[0] > 128:
                    x = jax.lax.psum(x, "dp")
                return x

            def _seeded_launch(mesh, x):
                return shard_map(_seeded_allreduce, mesh=mesh,
                                 in_specs=None, out_specs=None)(x)
            """)
        assert fresh and {f.rule for f in fresh} == {"PC201"}
        assert fresh[0].qualname == "_seeded_allreduce"
        assert fresh[0].detail.startswith("branch-collective:psum:")


# ---------------------------------------------------------------- PS301

class TestPS301UnboundCollectiveAxis:
    def test_psum_over_axis_not_in_mesh(self):
        fs = _lint("""
            import jax
            from jax.sharding import Mesh, PartitionSpec as P
            from jax.experimental.shard_map import shard_map

            def f(devs, x):
                mesh = Mesh(devs, ("x", "y"))

                def body(v):
                    return jax.lax.psum(v, "dp")

                return shard_map(body, mesh=mesh, in_specs=(P("x"),),
                                 out_specs=P("x"))(x)
        """)
        assert _rules(fs) == ["PS301"]
        assert fs[0].detail == "unbound-axis:psum:dp"
        assert fs[0].severity == "error"

    def test_axis_present_in_mesh_is_quiet(self):
        fs = _lint("""
            import jax
            from jax.sharding import Mesh, PartitionSpec as P
            from jax.experimental.shard_map import shard_map

            def f(devs, x):
                mesh = Mesh(devs, ("x", "y"))

                def body(v):
                    return jax.lax.psum(v, "y")

                return shard_map(body, mesh=mesh, in_specs=(P("x"),),
                                 out_specs=P("x"))(x)
        """)
        assert _rules(fs) == []

    def test_vmap_bound_axis_inside_region_is_quiet(self):
        # body vmaps a helper with its own axis_name: that name is bound
        # even though the mesh doesn't carry it
        fs = _lint("""
            import jax
            from jax.sharding import Mesh, PartitionSpec as P
            from jax.experimental.shard_map import shard_map

            def f(devs, x):
                mesh = Mesh(devs, ("x",))

                def inner(u):
                    return jax.lax.psum(u, "v")

                def body(v):
                    return jax.vmap(inner, axis_name="v")(v)

                return shard_map(body, mesh=mesh, in_specs=(P("x"),),
                                 out_specs=P("x"))(x)
        """)
        assert _rules(fs) == []

    def test_symbolic_mesh_axes_are_quiet(self):
        # axis tuple not statically known: must degrade to no finding
        fs = _lint("""
            import jax
            from jax.sharding import Mesh, PartitionSpec as P
            from jax.experimental.shard_map import shard_map

            def f(devs, names, x):
                mesh = Mesh(devs, names)

                def body(v):
                    return jax.lax.psum(v, "dp")

                return shard_map(body, mesh=mesh, in_specs=(P("x"),),
                                 out_specs=P("x"))(x)
        """)
        assert _rules(fs) == []


# ---------------------------------------------------------------- PS302

class TestPS302SpecArity:
    def test_more_in_specs_than_body_params(self):
        fs = _lint("""
            import jax
            from jax.sharding import PartitionSpec as P
            from jax.experimental.shard_map import shard_map

            def f(mesh, x, y):
                def body(v):
                    return v

                return shard_map(body, mesh=mesh, in_specs=(P(), P()),
                                 out_specs=P())(x, y)
        """)
        assert _rules(fs) == ["PS302"]
        assert fs[0].detail == "in-specs-arity:2:1"
        assert fs[0].severity == "error"

    def test_out_specs_tuple_vs_returned_tuple(self):
        fs = _lint("""
            import jax
            from jax.sharding import PartitionSpec as P
            from jax.experimental.shard_map import shard_map

            def f(mesh, x):
                def body(v):
                    return v, v, v

                return shard_map(body, mesh=mesh, in_specs=(P(),),
                                 out_specs=(P(), P()))(x)
        """)
        assert _rules(fs) == ["PS302"]
        assert fs[0].detail == "out-specs-arity:2:3"

    def test_matching_arity_is_quiet(self):
        fs = _lint("""
            import jax
            from jax.sharding import PartitionSpec as P
            from jax.experimental.shard_map import shard_map

            def f(mesh, x, y):
                def body(v, w):
                    return v + w

                return shard_map(body, mesh=mesh, in_specs=(P(), P()),
                                 out_specs=P())(x, y)
        """)
        assert _rules(fs) == []

    def test_single_spec_for_any_arity_is_quiet(self):
        # a bare (non-sequence) in_specs broadcasts over all args in the
        # shard_map — no arity claim to check
        fs = _lint("""
            import jax
            from jax.sharding import PartitionSpec as P
            from jax.experimental.shard_map import shard_map

            def f(mesh, x, y):
                def body(v, w):
                    return v + w

                return shard_map(body, mesh=mesh, in_specs=P(),
                                 out_specs=P())(x, y)
        """)
        assert _rules(fs) == []


# ---------------------------------------------------------------- PS303

class TestPS303SpecShape:
    def test_duplicate_axis_across_entries(self):
        fs = _lint("""
            from jax.sharding import PartitionSpec as P

            SPEC = P("dp", ("dp", "mp"))
        """)
        assert _rules(fs) == ["PS303"]
        assert fs[0].detail == "dup-axis:dp"
        assert fs[0].severity == "error"

    def test_spec_rank_exceeds_array_rank(self):
        fs = _lint("""
            import jax
            import jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P

            def f(mesh):
                arr = jnp.zeros((4, 8))
                return jax.device_put(
                    arr, NamedSharding(mesh, P(None, None, "mp")))
        """)
        assert _rules(fs) == ["PS303"]
        assert fs[0].detail == "rank-excess:3:2"

    def test_trailing_nones_do_not_count_toward_rank(self):
        # P("dp", None) on a rank-1 array: min_rank is 1 after stripping
        fs = _lint("""
            import jax
            import jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P

            def f(mesh):
                arr = jnp.zeros((4,))
                return jax.device_put(arr, NamedSharding(mesh, P("dp", None)))
        """)
        assert _rules(fs) == []

    def test_shorter_spec_is_quiet(self):
        fs = _lint("""
            import jax
            import jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P

            def f(mesh):
                arr = jnp.zeros((4, 8))
                return jax.device_put(arr, NamedSharding(mesh, P("dp")))
        """)
        assert _rules(fs) == []


# ---------------------------------------------------------------- PS304

class TestPS304Divisibility:
    def test_statically_indivisible_dim(self):
        fs = _lint("""
            import jax
            import jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P
            from paddle_tpu.distributed.mesh import build_hybrid_mesh

            def f():
                mesh = build_hybrid_mesh(dp_degree=4)
                x = jnp.zeros((6, 128))
                return jax.device_put(x, NamedSharding(mesh, P("dp", None)))
        """)
        assert _rules(fs) == ["PS304"]
        assert fs[0].detail == "indivisible:0:6:4"
        assert fs[0].severity == "warning"

    def test_divisible_dim_is_quiet(self):
        fs = _lint("""
            import jax
            import jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P
            from paddle_tpu.distributed.mesh import build_hybrid_mesh

            def f():
                mesh = build_hybrid_mesh(dp_degree=4)
                x = jnp.zeros((8, 128))
                return jax.device_put(x, NamedSharding(mesh, P("dp", None)))
        """)
        assert _rules(fs) == []

    def test_symbolic_dim_is_advisory_under_strict_only(self):
        src = """
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            from paddle_tpu.distributed.mesh import build_hybrid_mesh

            def f(x):
                mesh = build_hybrid_mesh(dp_degree=4)
                return jax.device_put(x, NamedSharding(mesh, P("dp", None)))
        """
        assert _rules(_lint(src)) == []
        strict = _lint(src, strict=True)
        assert _rules(strict) == ["PS304"]
        assert strict[0].severity == "info"
        assert strict[0].detail == "indivisible-unverified:0:4"

    def test_unknown_axis_size_is_quiet(self):
        # degree comes in as a parameter: product is symbolic
        fs = _lint("""
            import jax
            import jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P
            from paddle_tpu.distributed.mesh import build_hybrid_mesh

            def f(n):
                mesh = build_hybrid_mesh(dp_degree=n)
                x = jnp.zeros((6, 128))
                return jax.device_put(x, NamedSharding(mesh, P("dp", None)))
        """)
        assert _rules(fs) == []


# ---------------------------------------------------------------- PS305

class TestPS305AxisShadowing:
    def test_vmap_axis_name_shadows_mesh_axis(self):
        fs = _lint("""
            import jax
            from jax.sharding import Mesh, PartitionSpec as P
            from jax.experimental.shard_map import shard_map

            def f(devs, x):
                mesh = Mesh(devs, ("dp", "mp"))

                def inner(u):
                    return u * 2

                def body(v):
                    return jax.vmap(inner, axis_name="dp")(v)

                return shard_map(body, mesh=mesh, in_specs=(P("dp"),),
                                 out_specs=P("dp"))(x)
        """)
        assert _rules(fs) == ["PS305"]
        assert fs[0].detail == "axis-shadow:vmap:dp"
        assert fs[0].severity == "warning"

    def test_distinct_vmap_axis_name_is_quiet(self):
        fs = _lint("""
            import jax
            from jax.sharding import Mesh, PartitionSpec as P
            from jax.experimental.shard_map import shard_map

            def f(devs, x):
                mesh = Mesh(devs, ("dp", "mp"))

                def inner(u):
                    return u * 2

                def body(v):
                    return jax.vmap(inner, axis_name="batch")(v)

                return shard_map(body, mesh=mesh, in_specs=(P("dp"),),
                                 out_specs=P("dp"))(x)
        """)
        assert _rules(fs) == []


# ---------------------------------------------------------------- PS306

class TestPS306UnsanitizedSpec:
    def test_layer_declared_spec_under_ambient_mesh(self):
        fs = _lint("""
            import jax
            from jax.sharding import NamedSharding
            from paddle_tpu.distributed.mesh import get_mesh

            def place(p):
                mesh = get_mesh()
                spec = getattr(p, "_sharding_spec", None)
                return jax.device_put(p, NamedSharding(mesh, spec))
        """)
        assert _rules(fs) == ["PS306"]
        assert fs[0].detail == "unsanitized-layer-spec"
        assert fs[0].severity == "warning"

    def test_sanitized_layer_spec_is_quiet(self):
        fs = _lint("""
            import jax
            from jax.sharding import NamedSharding
            from paddle_tpu.distributed.mesh import get_mesh, sanitize_spec

            def place(p):
                mesh = get_mesh()
                spec = sanitize_spec(mesh, getattr(p, "_sharding_spec", None))
                return jax.device_put(p, NamedSharding(mesh, spec))
        """)
        assert _rules(fs) == []

    def test_literal_axes_under_ambient_mesh(self):
        fs = _lint("""
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            from paddle_tpu.distributed.mesh import get_mesh

            def place(x):
                mesh = get_mesh()
                return jax.device_put(x, NamedSharding(mesh, P("mp")))
        """)
        assert _rules(fs) == ["PS306"]
        assert fs[0].detail == "unsanitized-spec:mp"

    def test_parameter_mesh_with_literal_spec_is_quiet(self):
        # a mesh handed in by the caller is a contract, not a
        # configuration point — pretrain.py's pattern
        fs = _lint("""
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            def place(mesh, x):
                return jax.device_put(x, NamedSharding(mesh, P("mp")))
        """)
        assert _rules(fs) == []

    def test_known_mesh_covering_spec_axes_is_quiet(self):
        # env is complete and contains every axis the spec names
        fs = _lint("""
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            from paddle_tpu.distributed.mesh import build_hybrid_mesh

            def place(x):
                mesh = build_hybrid_mesh(mp_degree=4)
                return jax.device_put(x, NamedSharding(mesh, P("mp")))
        """)
        assert _rules(fs) == []


# ----------------------------------------- seeded sharding/mesh defects

class TestSeededShardingDefects:
    """ISSUE PR9 acceptance: each PS rule catches exactly its seeded
    defect in a scratch copy of the real distributed modules, and stays
    quiet on the pristine copies. Copies are analyzed statically — never
    imported — so mutations are plain text edits."""

    MESH = "paddle_tpu/distributed/mesh.py"
    PP_EXEC = "paddle_tpu/distributed/pp_exec.py"
    SHARDING = "paddle_tpu/distributed/sharding.py"

    def _analyze(self, tmp_path, rel, tag, old="", new="", append=""):
        src = open(os.path.join(REPO, rel)).read()
        if old:
            assert old in src, f"seed anchor vanished from {rel}: {old!r}"
            src = src.replace(old, new, 1)
        d = tmp_path / tag
        d.mkdir(exist_ok=True)
        p = d / os.path.basename(rel)   # same rel/modname as the clean
        p.write_text(src + textwrap.dedent(append))
        return analyze_paths([str(p)])

    def _seed(self, tmp_path, rel, **kw):
        clean = self._analyze(tmp_path, rel, "clean")
        seeded = self._analyze(tmp_path, rel, "seeded", **kw)
        new_keys = ({f.baseline_key for f in seeded}
                    - {f.baseline_key for f in clean})
        return [f for f in seeded if f.baseline_key in new_keys]

    def test_pristine_copies_are_quiet(self, tmp_path):
        for rel in (self.MESH, self.PP_EXEC, self.SHARDING):
            fs = self._analyze(tmp_path, rel, "clean")
            assert [f for f in fs if f.rule.startswith("PS")] == [], rel

    def test_ps301_catches_psum_over_missing_axis(self, tmp_path):
        fresh = self._seed(tmp_path, self.MESH, append="""

            from jax.experimental.shard_map import shard_map

            def _seed_allreduce(x):
                mesh = build_hybrid_mesh(dp_degree=4, mp_degree=2)

                def body(v):
                    return jax.lax.psum(v, "tp")

                return shard_map(body, mesh=mesh,
                                 in_specs=(PartitionSpec("dp"),),
                                 out_specs=PartitionSpec("dp"))(x)
            """)
        assert fresh and {f.rule for f in fresh} == {"PS301"}
        assert fresh[0].detail == "unbound-axis:psum:tp"

    def test_ps302_catches_spec_arity_mismatch(self, tmp_path):
        fresh = self._seed(tmp_path, self.MESH, append="""

            from jax.experimental.shard_map import shard_map

            def _seed_badarity(x, y):
                mesh = build_hybrid_mesh(dp_degree=4)

                def body(v):
                    return v

                return shard_map(body, mesh=mesh,
                                 in_specs=(PartitionSpec("dp"),
                                           PartitionSpec()),
                                 out_specs=PartitionSpec("dp"))(x, y)
            """)
        assert fresh and {f.rule for f in fresh} == {"PS302"}
        assert fresh[0].detail == "in-specs-arity:2:1"

    def test_ps303_catches_dup_axis_and_rank_excess(self, tmp_path):
        fresh = self._seed(tmp_path, self.MESH, append="""

            import jax.numpy as jnp

            def _seed_badspec(mesh):
                arr = jnp.zeros((4, 8))
                spec = PartitionSpec("dp", ("dp", "mp"))
                return jax.device_put(
                    arr, NamedSharding(mesh, PartitionSpec(None, None, "mp")))
            """)
        assert fresh and {f.rule for f in fresh} == {"PS303"}
        assert {f.detail for f in fresh} == {"dup-axis:dp", "rank-excess:3:2"}

    def test_ps304_catches_indivisible_dim(self, tmp_path):
        fresh = self._seed(tmp_path, self.MESH, append="""

            import jax.numpy as jnp

            def _seed_indivisible():
                mesh = build_hybrid_mesh(dp_degree=4)
                x = jnp.zeros((6, 128))
                return jax.device_put(
                    x, NamedSharding(mesh, PartitionSpec("dp", None)))
            """)
        assert fresh and {f.rule for f in fresh} == {"PS304"}
        assert fresh[0].detail == "indivisible:0:6:4"

    def test_ps305_catches_vmap_axis_shadow(self, tmp_path):
        fresh = self._seed(tmp_path, self.MESH, append="""

            from jax.experimental.shard_map import shard_map

            def _seed_shadow(x):
                mesh = build_hybrid_mesh(dp_degree=4)

                def inner(u):
                    return u * 2

                def body(v):
                    return jax.vmap(inner, axis_name="dp")(v)

                return shard_map(body, mesh=mesh,
                                 in_specs=(PartitionSpec("dp"),),
                                 out_specs=PartitionSpec("dp"))(x)
            """)
        assert fresh and {f.rule for f in fresh} == {"PS305"}
        assert fresh[0].detail == "axis-shadow:vmap:dp"

    def test_ps306_catches_dropped_sanitize_in_sharding(self, tmp_path):
        fresh = self._seed(
            tmp_path, self.SHARDING,
            old='        base = sanitize_spec(mesh, getattr(p, '
                '"_sharding_spec", None))\n'
                '        spec = compose_sharding_spec(base, arr.shape, '
                'axis, size)',
            new='        spec = getattr(p, "_sharding_spec", None)')
        assert fresh and {f.rule for f in fresh} == {"PS306"}
        assert fresh[0].detail == "unsanitized-layer-spec"


# ------------------------------------------------- --changed-only mode

class TestChangedOnly:
    def _repo(self, tmp_path):
        """A tiny git repo: committed clean module + uncommitted leaky
        one. --changed-only must analyze only the latter."""
        import subprocess
        def git(*a):
            subprocess.run(["git", *a], cwd=tmp_path, check=True,
                           capture_output=True,
                           env={**os.environ,
                                "GIT_AUTHOR_NAME": "t",
                                "GIT_AUTHOR_EMAIL": "t@t",
                                "GIT_COMMITTER_NAME": "t",
                                "GIT_COMMITTER_EMAIL": "t@t"})
        git("init", "-q")
        (tmp_path / "clean.py").write_text(textwrap.dedent("""
            import jax

            @jax.jit
            def g(x):
                return float(x)
        """))
        git("add", "clean.py")
        git("commit", "-qm", "seed")
        (tmp_path / "leaky.py").write_text(textwrap.dedent("""
            import jax

            @jax.jit
            def f(x):
                return float(x)
        """))
        git("add", "leaky.py")  # staged => in `git diff HEAD`
        return tmp_path

    def test_only_changed_files_analyzed(self, tmp_path, capsys,
                                         monkeypatch):
        repo = self._repo(tmp_path)
        monkeypatch.chdir(repo)
        rc = lint_main(["--changed-only", "HEAD", "--json",
                        str(repo / "clean.py"), str(repo / "leaky.py")])
        data = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert data["changed_only"]["ref"] == "HEAD"
        assert data["changed_only"]["files"] == ["leaky.py"]
        # the committed-clean module's finding is NOT reported
        assert {f["path"] for f in data["findings"]} == {"leaky.py"}
        assert data["stale_baseline_keys"] == []

    def test_no_changes_exits_zero(self, tmp_path, capsys, monkeypatch):
        repo = self._repo(tmp_path)
        import subprocess
        subprocess.run(["git", "add", "-A"], cwd=repo, check=True)
        subprocess.run(["git", "commit", "-qm", "all"], cwd=repo,
                       check=True, capture_output=True,
                       env={**os.environ,
                            "GIT_AUTHOR_NAME": "t",
                            "GIT_AUTHOR_EMAIL": "t@t",
                            "GIT_COMMITTER_NAME": "t",
                            "GIT_COMMITTER_EMAIL": "t@t"})
        monkeypatch.chdir(repo)
        rc = lint_main(["--changed-only", "--json",
                        str(repo / "clean.py"), str(repo / "leaky.py")])
        data = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert data["changed_only"]["files"] == []
        assert data["findings"] == []

    def test_git_unavailable_falls_back_to_full_run(self, tmp_path,
                                                    capsys, monkeypatch):
        # no .git anywhere up from tmp_path/sub: `git diff` fails and the
        # CLI analyzes everything, warning on stderr
        sub = tmp_path / "sub"
        sub.mkdir()
        (sub / "leaky.py").write_text(textwrap.dedent("""
            import jax

            @jax.jit
            def f(x):
                return float(x)
        """))
        monkeypatch.chdir(sub)
        monkeypatch.setenv("GIT_DIR", str(sub / "nonexistent"))
        # path first: a greedy `--changed-only PATH` would read the
        # path as its optional REF value
        rc = lint_main([str(sub / "leaky.py"), "--changed-only"])
        cap = capsys.readouterr()
        assert rc == 1
        assert "git unavailable" in cap.err
        assert "PT001" in cap.out


# ------------------------------- changed-only factory-module expansion

class TestChangedOnlyFactoryExpansion:
    """ISSUE PR13 small fix: a kernel built in one module (the factory)
    and launched from another anchors its findings at the pallas_call
    site — so when only the factory file changes, `--changed-only` must
    pull the call-site file back into the analyzed set or the defect the
    edit introduced is silently skipped."""

    FACTORY = """
        def make_kernel(eps):
            def _kern(x_ref, y_ref, o_ref):
                o_ref[:] = x_ref[:] + eps
            return _kern
    """
    CALLSITE = """
        import jax
        from jax.experimental import pallas as pl

        from pkg.factory import make_kernel

        def run(x):
            kern = make_kernel(0.5)
            return pl.pallas_call(
                kern,
                grid=(4,),
                in_specs=[pl.BlockSpec((1, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((1, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            )(x)
    """

    def _pkg(self, tmp_path):
        from paddle_tpu.analysis.runner import discover
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "__init__.py").write_text("")
        (pkg / "factory.py").write_text(textwrap.dedent(self.FACTORY))
        (pkg / "callsite.py").write_text(textwrap.dedent(self.CALLSITE))
        return pkg, discover(str(pkg))

    def test_factory_change_pulls_in_call_site(self, tmp_path):
        from paddle_tpu.analysis.runner import (
            analyze_files, expand_changed_with_factories)
        pkg, files = self._pkg(tmp_path)
        changed = {os.path.abspath(str(pkg / "factory.py"))}
        sel = expand_changed_with_factories(files, changed)
        assert sorted(t[2] for t in sel) == ["pkg/callsite.py",
                                             "pkg/factory.py"]
        fs = analyze_files(sel, Config(rules={"PK102"}))
        assert [(f.rule, f.path, f.detail) for f in fs] \
            == [("PK102", "pkg/callsite.py", "refs:3!=2")]

    def test_naive_selection_misses_the_defect(self, tmp_path):
        # the regression this guards: filtering by changed paths alone
        # analyzes only the factory file, where no pallas_call site
        # exists, and the ref-count mismatch goes unreported
        from paddle_tpu.analysis.runner import analyze_files
        pkg, files = self._pkg(tmp_path)
        changed = {os.path.abspath(str(pkg / "factory.py"))}
        naive = [t for t in files
                 if os.path.abspath(t[1]) in changed]
        assert analyze_files(naive, Config(rules={"PK102"})) == []

    def test_call_site_change_is_not_duplicated(self, tmp_path):
        from paddle_tpu.analysis.runner import (
            expand_changed_with_factories)
        pkg, files = self._pkg(tmp_path)
        changed = {os.path.abspath(str(pkg / "factory.py")),
                   os.path.abspath(str(pkg / "callsite.py"))}
        sel = expand_changed_with_factories(files, changed)
        assert sorted(t[2] for t in sel) == ["pkg/callsite.py",
                                             "pkg/factory.py"]

    def test_no_changes_selects_nothing(self, tmp_path):
        from paddle_tpu.analysis.runner import (
            expand_changed_with_factories)
        _, files = self._pkg(tmp_path)
        assert expand_changed_with_factories(files, set()) == []


# ------------------------------------ JSON schema version + ordering

class TestJsonSchemaAndOrdering:
    def test_schema_version_present(self, tmp_path, capsys):
        p = tmp_path / "mod.py"
        p.write_text("x = 1\n")
        assert lint_main(["--json", str(p)]) == 0
        data = json.loads(capsys.readouterr().out)
        assert data["schema_version"] == 1

    def test_findings_sorted_rule_path_qualname(self, tmp_path, capsys):
        # two files, two rules each — emitted order must be
        # (rule, path, qualname), not discovery or pass order
        for name in ("b_mod.py", "a_mod.py"):
            (tmp_path / name).write_text(textwrap.dedent("""
                import jax

                @jax.jit
                def f(x):
                    if x > 0:          # PT001 branch on traced value
                        x = float(x)   # PT001 host conversion
                    return x

                def loop():
                    for _ in range(3):
                        g = jax.jit(lambda y: y)   # PT002
                    return g
            """))
        assert lint_main(["--json", str(tmp_path / "b_mod.py"),
                          str(tmp_path / "a_mod.py")]) == 1
        data = json.loads(capsys.readouterr().out)
        keys = [(f["rule"], f["path"], f["qualname"])
                for f in data["findings"]]
        assert keys == sorted(keys)
        assert len({f["rule"] for f in data["findings"]}) > 1
        assert len({f["path"] for f in data["findings"]}) > 1

    def test_rules_carry_module(self, tmp_path, capsys):
        p = tmp_path / "mod.py"
        p.write_text("x = 1\n")
        assert lint_main(["--json", str(p)]) == 0
        data = json.loads(capsys.readouterr().out)
        assert data["rules"]["PC201"]["module"].endswith(
            "rules_collective")
        assert data["rules"]["PF401"]["module"].endswith("rules_memory")


# ---------------------------------------------- rule-family registry

class TestRuleFamilyRegistry:
    def test_every_rule_has_a_module_and_family(self):
        from paddle_tpu.analysis.model import (FAMILIES, RULE_MODULES,
                                               RULES, rule_family)
        for rid in RULES:
            assert RULE_MODULES.get(rid), rid
            assert rule_family(rid) in FAMILIES, rid

    def test_pc201_mapping_documented_in_registry(self):
        # PC201 lives in rules_collective.py by design; the registry —
        # not the filename convention — records that
        from paddle_tpu.analysis.model import RULE_MODULES
        assert RULE_MODULES["PC201"].endswith(".rules_collective")

    def test_list_rules_grouped_by_family(self, capsys):
        assert lint_main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        headers = [ln for ln in out.splitlines() if ln.startswith("-- ")]
        assert [h.split()[1].rstrip(":") for h in headers] \
            == ["PC", "PE", "PF", "PK", "PS", "PT"]
        # rules listed under their family header
        lines = out.splitlines()
        pf_at = lines.index(next(h for h in headers if "PF" in h))
        pk_at = lines.index(next(h for h in headers if "PK" in h))
        pf401_at = next(i for i, ln in enumerate(lines)
                        if ln.startswith("PF401"))
        assert pf_at < pf401_at < pk_at
        # cross-filed rules carry their module marker
        pt003 = next(ln for ln in lines if ln.startswith("PT003"))
        assert "rules_hostsync" in pt003


# ------------------------------------------ seeded memory-lane defects

class TestSeededMemoryDefects:
    """ISSUE PR13 acceptance: each PF rule catches exactly its seeded
    defect in a scratch copy of the real kernel modules, and the
    pristine copies stay PF-quiet. Copies are analyzed statically —
    never imported — so mutations are plain text edits."""

    RAGGED = "paddle_tpu/ops/pallas_ragged.py"
    FUSED = "paddle_tpu/ops/fused.py"
    QUANT = "paddle_tpu/ops/quant.py"
    MEGADECODE = "paddle_tpu/ops/pallas_megadecode.py"
    MEGAFRONT = "paddle_tpu/ops/pallas_megafront.py"

    def _analyze(self, tmp_path, rel, tag, old="", new="", append="",
                 strict=False):
        src = open(os.path.join(REPO, rel)).read()
        if old:
            assert old in src, f"seed anchor vanished from {rel}: {old!r}"
            src = src.replace(old, new, 1)
        d = tmp_path / tag
        d.mkdir(exist_ok=True)
        p = d / os.path.basename(rel)
        p.write_text(src + textwrap.dedent(append))
        return analyze_paths([str(p)], Config(strict=strict))

    def _seed(self, tmp_path, rel, strict=False, **kw):
        clean = self._analyze(tmp_path, rel, "clean", strict=strict)
        seeded = self._analyze(tmp_path, rel, "seeded", strict=strict,
                               **kw)
        new_keys = ({f.baseline_key for f in seeded}
                    - {f.baseline_key for f in clean})
        return [f for f in seeded if f.baseline_key in new_keys]

    def test_pristine_copies_are_pf_quiet(self, tmp_path):
        for rel in (self.RAGGED, self.FUSED, self.QUANT,
                    self.MEGADECODE):
            fs = self._analyze(tmp_path, rel, "clean")
            assert [f for f in fs if f.rule.startswith("PF")] == [], rel

    def test_pf401_catches_vmem_overflow(self, tmp_path):
        # 4096x the f32 accumulator scratch: ~64 MiB against the 16 MiB
        # per-core budget
        fresh = self._seed(
            tmp_path, self.RAGGED,
            old="pltpu.VMEM((hb * tb, rows, D), jnp.float32),",
            new="pltpu.VMEM((hb * tb, rows * 4096, D), jnp.float32),")
        assert fresh and {f.rule for f in fresh} == {"PF401"}
        assert fresh[0].detail == "vmem:ragged_paged_attention"
        assert "MiB" in fresh[0].message

    def test_pf402_catches_read_after_donate(self, tmp_path):
        # `pages` is donated to output 0 of fused_append_rows; reading
        # it after the launch observes the in-place overwrite
        fresh = self._seed(
            tmp_path, self.FUSED,
            old="        )(runs.astype(jnp.int32), rows, pages)",
            new="        )(runs.astype(jnp.int32), rows, pages)\n"
                "        _ = pages.mean()")
        assert fresh and {f.rule for f in fresh} == {"PF402"}
        assert fresh[0].detail == "alias:pages->out0"
        assert fresh[0].qualname == "fused_append_rows"

    def test_pf403_catches_reduced_precision_accumulator_store(
            self, tmp_path):
        # scratch stays DECLARED f32 (PK104 quiet) but the store
        # truncates — the break PK104's declaration-side check misses
        fresh = self._seed(
            tmp_path, self.RAGGED,
            old="m_ref[at] = m_new",
            new="m_ref[at] = m_new.astype(jnp.bfloat16)")
        assert fresh and {f.rule for f in fresh} == {"PF403"}
        assert fresh[0].detail == "accum:m_ref"

    def test_pf403_catches_unaligned_int4_lane(self, tmp_path):
        # a Name-bound lane block (not a literal, so PK102's constant
        # lane check stays quiet) that breaks the nibble-packed 128
        # alignment
        fresh = self._seed(
            tmp_path, self.QUANT,
            old="bn = next((c for c in (2048, 1024, 512, 256, 128) "
                "if Np % c == 0), Np)",
            new="bn = 64")
        assert fresh and {f.rule for f in fresh} == {"PF403"}
        assert fresh[0].detail == "int4lane:bn"
        assert fresh[0].qualname == "int4_dequantize"

    def test_pf404_emits_decode_chain_fusion_worklist(self, tmp_path):
        # advisory, info severity: pristine copies of the three chain
        # modules are the fixture.  ISSUE 14 RESOLVED the old
        # rms->swiglu advisory and ISSUE 20 the rms->rope seam (those
        # pairs now live inside the mega-kernels); what remains is the
        # norm->front retile (the registered <=4-launch follow-on) and
        # the deliberate oproj->ffn seam the mega-kernels keep (VMEM
        # weight budget — see DECODE_CHAIN's comment)
        d = tmp_path / "chain"
        d.mkdir()
        paths = []
        for rel in (self.FUSED, self.MEGADECODE, self.MEGAFRONT):
            p = d / os.path.basename(rel)
            p.write_text(open(os.path.join(REPO, rel)).read())
            paths.append(str(p))
        fs = analyze_paths(paths, Config(strict=True))
        details = {f.detail for f in fs if f.rule == "PF404"}
        assert details == {
            "fuse:fused_rms_norm->fused_qkv_rope_append",
            "fuse:fused_oproj_norm->fused_ffn"}
        # ...and stays out of default (non-strict) runs
        fs = analyze_paths(paths, Config(strict=False))
        assert [f for f in fs if f.rule == "PF404"] == []

    def test_pf405_catches_indivisible_grid(self, tmp_path):
        # 8 tokens // 192 == 0 under the canonical shapes: the launch
        # silently skips every row
        fresh = self._seed(
            tmp_path, self.FUSED,
            old="grid=(T // bt,),",
            new="grid=(T // 192,),")
        assert fresh and {f.rule for f in fresh} == {"PF405"}
        assert fresh[0].detail == "grid:T // 192"
        assert fresh[0].qualname == "_rms_forward"

    def test_pf406_catches_cost_model_drift(self, tmp_path):
        # grow the dequant output block ~25%: BlockSpec-derived bytes
        # drift past COST_DRIFT_RTOL while VMEM stays in budget, so
        # exactly the drift rule fires
        fresh = self._seed(
            tmp_path, self.QUANT,
            old="out_specs=pl.BlockSpec((K2 * 2, bn), "
                "lambda j: (0, j)),",
            new="out_specs=pl.BlockSpec((K2 * 2 + 256, bn), "
                "lambda j: (0, j)),")
        # PE506 (ISSUE 19) attributes the same drift to the write side
        assert fresh and {f.rule for f in fresh} == {"PF406", "PE506"}
        assert any(f.detail == "drift:int4_dequantize" for f in fresh)


# ------------------------------------------------------ DCN tier (PS3xx)

class TestDCNTierAxes:
    """ISSUE 15: build_hybrid_mesh grew an explicit multi-slice DCN tier
    (dcn_dp/dcn_pp, outermost). The static mesh model must know the new
    axes — both the keyword degrees and the extended positional order —
    so the PS rules check DCN-tier layouts like any other axis."""

    def test_dcn_dp_statically_indivisible_dim(self):
        fs = _lint("""
            import jax
            import jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P
            from paddle_tpu.distributed.mesh import build_hybrid_mesh

            def f():
                mesh = build_hybrid_mesh(dcn_dp_degree=4)
                x = jnp.zeros((6, 128))
                return jax.device_put(
                    x, NamedSharding(mesh, P("dcn_dp", None)))
        """)
        assert _rules(fs) == ["PS304"]
        assert fs[0].detail == "indivisible:0:6:4"

    def test_dcn_dp_divisible_dim_is_quiet(self):
        fs = _lint("""
            import jax
            import jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P
            from paddle_tpu.distributed.mesh import build_hybrid_mesh

            def f():
                mesh = build_hybrid_mesh(dcn_dp_degree=4)
                x = jnp.zeros((8, 128))
                return jax.device_put(
                    x, NamedSharding(mesh, P("dcn_dp", None)))
        """)
        assert _rules(fs) == []

    def test_dcn_pp_positional_degree(self):
        # positional signature tail: ..., ep, dcn_dp, dcn_pp
        fs = _lint("""
            import jax
            import jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P
            from paddle_tpu.distributed.mesh import build_hybrid_mesh

            def f():
                mesh = build_hybrid_mesh(1, 1, 1, 1, 1, 1, 1, 4)
                x = jnp.zeros((6, 128))
                return jax.device_put(
                    x, NamedSharding(mesh, P("dcn_pp", None)))
        """)
        assert _rules(fs) == ["PS304"]
        assert fs[0].detail == "indivisible:0:6:4"

    def test_psum_over_dcn_axis_is_bound(self):
        # the hybrid mesh carries the dcn axes even at degree 1: a
        # collective over them is bound, not a PS301 unbound-axis error
        fs = _lint("""
            import jax
            from jax.sharding import PartitionSpec as P
            from jax.experimental.shard_map import shard_map
            from paddle_tpu.distributed.mesh import build_hybrid_mesh

            def f(x):
                mesh = build_hybrid_mesh()

                def body(v):
                    return jax.lax.psum(v, "dcn_dp")

                return shard_map(body, mesh=mesh, in_specs=(P("dp"),),
                                 out_specs=P("dp"))(x)
        """)
        assert _rules(fs) == []


# ---------------------------------------- seeded effects-lane defects

class TestSeededEffectsDefects:
    """ISSUE 19 acceptance: each PE rule catches exactly its seeded
    hazard in a scratch copy of the real kernel modules (alias swap,
    dropped accumulator guard, widened scatter, overlapping output
    index_map, fused-pair read/write inversion, write-side cost edit),
    and the pristine copies report zero fresh PE findings.  Copies are
    analyzed statically — never imported."""

    RAGGED = "paddle_tpu/ops/pallas_ragged.py"
    FUSED = "paddle_tpu/ops/fused.py"
    MEGADECODE = "paddle_tpu/ops/pallas_megadecode.py"
    MEGAFRONT = "paddle_tpu/ops/pallas_megafront.py"
    PAGED = "paddle_tpu/ops/pallas_paged.py"
    FLASHMASK = "paddle_tpu/ops/pallas_flashmask.py"

    def _analyze(self, tmp_path, rel, tag, old="", new="",
                 strict=False, extra=()):
        src = open(os.path.join(REPO, rel)).read()
        if old:
            assert old in src, f"seed anchor vanished from {rel}: {old!r}"
            src = src.replace(old, new, 1)
        d = tmp_path / tag
        d.mkdir(exist_ok=True)
        p = d / os.path.basename(rel)
        p.write_text(src)
        paths = [str(p)]
        for x in extra:        # pristine companions (cross-module
            q = d / os.path.basename(x)   # compositions need all sites)
            q.write_text(open(os.path.join(REPO, x)).read())
            paths.append(str(q))
        return analyze_paths(paths, Config(strict=strict))

    def _seed(self, tmp_path, rel, strict=False, extra=(), **kw):
        clean = self._analyze(tmp_path, rel, "clean", strict=strict,
                              extra=extra)
        seeded = self._analyze(tmp_path, rel, "seeded", strict=strict,
                               extra=extra, **kw)
        new_keys = ({f.baseline_key for f in seeded}
                    - {f.baseline_key for f in clean})
        return [f for f in seeded if f.baseline_key in new_keys]

    def test_pristine_copies_are_pe_quiet(self, tmp_path):
        for rel in (self.RAGGED, self.FUSED, self.MEGADECODE,
                    self.MEGAFRONT, self.PAGED, self.FLASHMASK):
            fs = self._analyze(tmp_path, rel, "clean")
            assert [f for f in fs if f.rule.startswith("PE")] == [], rel

    def test_pe501_catches_overlapping_output_index_map(self, tmp_path):
        # pin _rms_forward's output block to (0, 0): every grid step now
        # writes the same block, with no dimension_semantics declaring
        # the axis sequential
        # megafront rides along pristine so the layer-body composition
        # (whose members span both modules) resolves on both sides
        fresh = self._seed(
            tmp_path, self.FUSED, extra=(self.MEGAFRONT,),
            old="out_specs=pl.BlockSpec((bt, H), lambda i: (i, 0)),\n"
                "        out_shape=jax.ShapeDtypeStruct((T, H), "
                "x2.dtype),",
            new="out_specs=pl.BlockSpec((bt, H), lambda i: (0, 0)),\n"
                "        out_shape=jax.ShapeDtypeStruct((T, H), "
                "x2.dtype),")
        assert fresh and "PE501" in {f.rule for f in fresh}
        pe = next(f for f in fresh if f.rule == "PE501")
        assert pe.qualname == "_rms_forward"
        assert pe.detail == "ww:o_ref:ax0"
        # the poisoned member also flips the fusion verdict to hazard
        assert any(f.rule == "PE505" and
                   f.detail.startswith("fusehazard:") for f in fresh)

    def test_pe502_catches_swapped_alias_indices(self, tmp_path):
        # cross the donated page pools: vin_ref now aliases kp_ref,
        # which the kernel seeds BEFORE vin_ref's read
        fresh = self._seed(
            tmp_path, self.FUSED,
            old="input_output_aliases={3: 0, 4: 1}",
            new="input_output_aliases={3: 1, 4: 0}")
        assert fresh and "PE502" in {f.rule for f in fresh}
        pe = next(f for f in fresh if f.rule == "PE502")
        assert pe.detail == "radw:vin_ref->kp_ref"
        assert pe.qualname == "_append_kv_runs"

    def test_pe503_catches_dropped_accumulator_guard(self, tmp_path):
        # delete the seed of the online-softmax state at the top of a
        # grid cell: the page walk and the emit then read scratch that
        # no store of this launch has written
        fresh = self._seed(
            tmp_path, self.RAGGED,
            old="    acc_ref[:] = jnp.zeros_like(acc_ref)\n"
                "    m_ref[:] = jnp.full_like(m_ref, _NEG)\n"
                "    l_ref[:] = jnp.zeros_like(l_ref)\n",
            new="")
        assert fresh and {f.rule for f in fresh} == {"PE503"}
        assert {f.detail for f in fresh} \
            == {"acc:acc_ref", "acc:m_ref", "acc:l_ref"}

    #: the paged-append row write.  The kernels land it through a
    #: full-block select (Mosaic cannot lower a one-row store at a
    #: dynamic sublane offset), so the dynamic-scatter form PE504
    #: judges is seeded here in its place
    ROW_WRITE = "kp_ref[h, 0] = _put_row(kp_ref[h, 0], off, kr[h:h + 1])"

    def test_pe504_catches_widened_scatter(self, tmp_path):
        # a two-row dynamic scatter: adjacent table offsets may differ
        # by one, so step t and t+1 overlap
        fresh = self._seed(
            tmp_path, self.FUSED, old=self.ROW_WRITE,
            new="kp_ref[:, 0, pl.dslice(off, 2), :] = kr[:, None, :]")
        assert fresh and "PE504" in {f.rule for f in fresh}
        pe = next(f for f in fresh if f.rule == "PE504")
        assert pe.detail == "scatter:kp_ref:w2"
        assert pe.severity == "error"

    def test_pe504_contract_note_under_strict(self, tmp_path):
        # a width-1 table scatter surfaces as an info note (proven
        # under the append contract) only with --strict
        fs = self._analyze(
            tmp_path, self.FUSED, "seeded", strict=True,
            old=self.ROW_WRITE,
            new="kp_ref[:, 0, pl.dslice(off, 1), :] = kr[:, None, :]")
        details = {f.detail for f in fs if f.rule == "PE504"}
        assert details == {"scatter-contract:kp_ref"}
        assert all(f.severity == "info" for f in fs
                   if f.rule == "PE504")

    def test_pe505_catches_read_write_inversion(self, tmp_path):
        # shift fused_ffn's consumed-block index by one: the fused
        # launch would read a block its producer has not written yet
        fresh = self._seed(
            tmp_path, self.MEGADECODE,
            old="in_specs=[pl.BlockSpec((bt, H), lambda i: (i, 0)),",
            new="in_specs=[pl.BlockSpec((bt, H), "
                "lambda i: (i + 1, 0)),")
        assert fresh and {f.rule for f in fresh} == {"PE505"}
        details = {f.detail for f in fresh}
        # the pair candidate flips AND the layer-body composition that
        # contains it inherits the hazard
        assert "fusehazard:fused_oproj_norm->fused_ffn" in details
        assert ("fusehazard:fused_rms_norm->fused_qkv_rope_append->"
                "fused_oproj_norm->fused_ffn") in details
        pe = next(f for f in fresh if f.detail
                  == "fusehazard:fused_oproj_norm->fused_ffn")
        assert pe.severity == "error"
        # the hazard names the refs on both sides of the seam
        assert "xo_ref" in pe.message and "h_ref" in pe.message
        assert "read/write inversion" in pe.message

    def test_pe505_flips_illegal_on_retiled_megafront_out_spec(
            self, tmp_path):
        # ISSUE 20 acceptance: pin the fused front's q out-spec to
        # block (0, 0, 0).  The kernel's own launch stays PE501-quiet
        # (the token axis is declared arbitrary for the page scatter),
        # but the q stream no longer tiles the way downstream members
        # consume it, so the shipped layer-body composition's verdict
        # must flip from legal to hazard
        fresh = self._seed(
            tmp_path, self.MEGAFRONT,
            extra=(self.FUSED, self.MEGADECODE),
            old="out_specs=[pl.BlockSpec((1, heads, D), "
                "lambda t, pg, off: (t, 0, 0)),",
            new="out_specs=[pl.BlockSpec((1, heads, D), "
                "lambda t, pg, off: (0, 0, 0)),")
        hazards = [f for f in fresh if f.rule == "PE505"
                   and f.detail.startswith("fusehazard:")]
        assert hazards
        comp = next(f for f in hazards if f.detail ==
                    "fusehazard:fused_rms_norm->fused_qkv_rope_append"
                    "->fused_oproj_norm->fused_ffn")
        assert comp.severity == "error"
        assert "read/write inversion" in comp.message
        assert "qo_ref" in comp.message

    def test_pe506_catches_write_side_drift(self, tmp_path):
        # halve the rope output block's lane extent: written bytes
        # drop 50% below costmodel.bytes_written (PF406 fires on the
        # total too — PE506 is the write-side attribution)
        fresh = self._seed(
            tmp_path, self.FUSED,
            old="out_specs=pl.BlockSpec((1, bs, H, D), "
                "lambda b, i: (b, i, 0, 0)),",
            new="out_specs=pl.BlockSpec((1, bs, H, D // 2), "
                "lambda b, i: (b, i, 0, 0)),")
        assert fresh and "PE506" in {f.rule for f in fresh}
        pe = next(f for f in fresh if f.rule == "PE506")
        assert pe.detail == "wdrift:fused_rope"
        assert pe.qualname == "_rope_forward"

    def test_pe503_accepts_dma_filled_scratch(self, tmp_path):
        # paged v2's kbuf/vbuf double buffers are filled through
        # buf.at[...] DMA handles the scanner cannot order — they must
        # degrade to unknown, not fire PE503
        fs = self._analyze(tmp_path, self.PAGED, "clean")
        assert [f for f in fs if f.rule == "PE503"] == []

    def test_pe501_flashmask_declares_revisited_axis(self, tmp_path):
        # regression for the fix this PR ships: the flashmask launches
        # now declare the innermost (revisited) axis "arbitrary"; strip
        # the declaration and PE501 fires on the helper-built out specs
        fresh = self._seed(
            tmp_path, self.FLASHMASK,
            old="        compiler_params=_CPARAMS,\n"
                "        interpret=_interpret(),\n"
                "    )(kinds, s1, e1, s2, e2, q, k, v)",
            new="        interpret=_interpret(),\n"
                "    )(kinds, s1, e1, s2, e2, q, k, v)")
        assert fresh and "PE501" in {f.rule for f in fresh}
        pe = [f for f in fresh if f.rule == "PE501"]
        assert {f.detail for f in pe} == {"ww:o_ref:ax3",
                                          "ww:lse_ref:ax3"}

    def test_pe501_flash_table_axis_is_a_revisited_axis(self, tmp_path):
        # flash's grid walks a scalar-prefetched visit table: its output
        # maps read the pair axis only as `qi[t]` / `kj[t]`, so the axis
        # is table-driven, revisited, and has to be declared "arbitrary"
        # (`_CPARAMS`); strip the forward's declaration and PE501 fires
        fresh = self._seed(
            tmp_path, "paddle_tpu/ops/pallas_flash.py",
            old="        compiler_params=_CPARAMS,\n"
                "        interpret=_interpret(),\n"
                "    )(*table, seg_q, seg_kv, q, k, v)\n",
            new="        interpret=_interpret(),\n"
                "    )(*table, seg_q, seg_kv, q, k, v)\n")
        pe = [f for f in fresh if f.rule == "PE501"]
        assert {f.detail for f in pe} == {"ww:o_ref:ax2", "ww:lse_ref:ax2"}


# --------------------------------- serving modules: no-clock regression

class TestServingModulesLintClean:
    """ISSUE 19 satellite: the PR 17-18 serving modules claim a no-clock
    discipline (feedback control without host-time branches on the hot
    path) — lock in zero fresh PT/PC findings so a future edit cannot
    silently reintroduce host syncs or branch-divergent collectives."""

    MODULES = ("paddle_tpu/serving/controller.py",
               "paddle_tpu/serving/router.py")

    def test_controller_and_router_have_no_pt_pc_findings(self):
        for rel in self.MODULES:
            fs = analyze_paths([os.path.join(REPO, rel)])
            bad = [f for f in fs if f.rule.startswith(("PT", "PC"))]
            assert bad == [], (rel, [(f.rule, f.detail) for f in bad])

    def test_modules_are_clean_even_under_strict(self):
        for rel in self.MODULES:
            fs = analyze_paths([os.path.join(REPO, rel)],
                               Config(strict=True))
            assert fs == [], (rel, [(f.rule, f.detail) for f in fs])


# --------------------------------------------------- SARIF CI output

class TestSarifOutput:
    def test_sarif_file_carries_findings_and_rules(self, tmp_path,
                                                   capsys):
        p = tmp_path / "mod.py"
        p.write_text(textwrap.dedent("""
            import jax

            @jax.jit
            def f(x):
                return float(x)
        """))
        sarif = tmp_path / "out.sarif"
        assert lint_main([str(p), "--sarif", str(sarif)]) == 1
        doc = json.loads(sarif.read_text())
        assert doc["version"] == "2.1.0"
        run = doc["runs"][0]
        assert run["tool"]["driver"]["name"] == "paddlelint"
        rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
        assert {"PT001", "PK101", "PE501", "PE505"} <= rule_ids
        res = run["results"]
        assert res and res[0]["ruleId"] == "PT001"
        assert res[0]["level"] == "error"
        loc = res[0]["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"] == "mod.py"
        assert loc["region"]["startLine"] >= 1
        assert loc["region"]["startColumn"] >= 1
        # baselining key rides along for CI dedup across pushes
        assert "paddlelintKey" in res[0]["partialFingerprints"]

    def test_clean_run_writes_empty_results(self, tmp_path, capsys):
        p = tmp_path / "mod.py"
        p.write_text("x = 1\n")
        sarif = tmp_path / "out.sarif"
        assert lint_main([str(p), "--sarif", str(sarif)]) == 0
        doc = json.loads(sarif.read_text())
        assert doc["runs"][0]["results"] == []


# ------------------------------ changed-only fusion-candidate expansion

class TestChangedOnlyFusionExpansion:
    """ISSUE 19 satellite: PE505's legality verdict is a property of a
    fusion PAIR — editing the producer's file must pull the consumer's
    file into a --changed-only selection, or the restricted run would
    re-certify a fusion it can only see half of."""

    PROD = """
        import jax
        from jax.experimental import pallas as pl

        def _oproj_kernel(x_ref, xo_ref, h_ref):
            xo_ref[:] = x_ref[:]
            h_ref[:] = x_ref[:]

        def _oproj_norm_forward(x):
            T, H = x.shape
            bt = 8
            return pl.pallas_call(
                _oproj_kernel,
                grid=(T // bt,),
                in_specs=[pl.BlockSpec((bt, H), lambda i: (i, 0))],
                out_specs=[pl.BlockSpec((bt, H), lambda i: (i, 0)),
                           pl.BlockSpec((bt, H), lambda i: (i, 0))],
                out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                           jax.ShapeDtypeStruct(x.shape, x.dtype)],
            )(x)
    """
    CONS = """
        import jax
        from jax.experimental import pallas as pl

        def _ffn_kernel(h_ref, o_ref):
            o_ref[:] = h_ref[:]

        def _ffn_forward(h2):
            T, H = h2.shape
            bt = 8
            return pl.pallas_call(
                _ffn_kernel,
                grid=(T // bt,),
                in_specs=[pl.BlockSpec((bt, H), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((bt, H), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct(h2.shape, h2.dtype),
            )(h2)
    """

    def _pkg(self, tmp_path):
        from paddle_tpu.analysis.runner import discover
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "__init__.py").write_text("")
        (pkg / "prod.py").write_text(textwrap.dedent(self.PROD))
        (pkg / "cons.py").write_text(textwrap.dedent(self.CONS))
        (pkg / "other.py").write_text("x = 1\n")
        return pkg, discover(str(pkg))

    def test_producer_change_pulls_in_consumer_file(self, tmp_path):
        from paddle_tpu.analysis.runner import (
            expand_changed_with_fusion)
        pkg, files = self._pkg(tmp_path)
        changed = {os.path.abspath(str(pkg / "prod.py"))}
        sel = expand_changed_with_fusion(files, changed)
        assert sorted(t[2] for t in sel) == ["pkg/cons.py",
                                             "pkg/prod.py"]

    def test_consumer_change_pulls_in_producer_file(self, tmp_path):
        from paddle_tpu.analysis.runner import (
            expand_changed_with_fusion)
        pkg, files = self._pkg(tmp_path)
        changed = {os.path.abspath(str(pkg / "cons.py"))}
        sel = expand_changed_with_fusion(files, changed)
        assert sorted(t[2] for t in sel) == ["pkg/cons.py",
                                             "pkg/prod.py"]

    def test_unrelated_change_stays_narrow(self, tmp_path):
        from paddle_tpu.analysis.runner import (
            expand_changed_with_fusion)
        pkg, files = self._pkg(tmp_path)
        changed = {os.path.abspath(str(pkg / "other.py"))}
        sel = expand_changed_with_fusion(files, changed)
        assert sorted(t[2] for t in sel) == ["pkg/other.py"]
