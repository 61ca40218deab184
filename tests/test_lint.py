"""``repro.lint``: rule fixtures, suppressions, CLI/JSON contract, the
three acceptance-criterion plants on the real sources, the repo's own
error-clean baseline, and the runtime sync-sentinel pinned against
``ExecStats.num_syncs`` on a pipelined S2 run."""
import json
import textwrap

import pytest

import repro.lint  # noqa: F401  (DEAD001 reachability root for the package)
from repro.lint import LintConfig, lint_paths, lint_sources, summarize
from repro.lint.__main__ import main as lint_main
from repro.lint.sentinel import SyncSentinel

# Synthetic paths that land in the configured rule scopes.
SYNC_PATH = "src/repro/core/executor.py"
KERN_PATH = "src/repro/kernels/distthresh.py"
TRACE_PATH = "src/repro/core/anything.py"


def run(path, source, *rules):
    vs = lint_sources([(path, textwrap.dedent(source))], select=rules)
    return [(v.rule, v.line) for v in vs]


def rules_of(path, source, *rules):
    return {r for r, _ in run(path, source, *rules)}


# ----------------------------------------------------------------------
# SYNC001/002: implicit host syncs on the pipelined dispatch path.
# ----------------------------------------------------------------------
class TestSyncRules:
    def test_materializers_flagged(self):
        src = """\
        import numpy as np
        import jax.numpy as jnp

        def phase_a(batches):
            out = jnp.zeros(4)
            a = np.asarray(out)
            b = float(out)
            c = out.item()
            d = out.tolist()
            return a, b, c, d
        """
        hits = run(SYNC_PATH, src, "SYNC001")
        assert [r for r, _ in hits] == ["SYNC001"] * 4
        assert [line for _, line in hits] == [6, 7, 8, 9]

    def test_iteration_and_comprehension_flagged(self):
        src = """\
        import jax.numpy as jnp

        def phase_a():
            out = jnp.arange(4)
            for x in out:
                pass
            ys = [float(v) for v in out]
            zs = list(out)
        """
        hits = run(SYNC_PATH, src, "SYNC002")
        assert [r for r, _ in hits] == ["SYNC002"] * 3

    def test_post_sync_reads_allowed(self):
        src = """\
        import jax
        import numpy as np
        import jax.numpy as jnp

        def group(dispatches):
            out = jnp.zeros(4)
            out = jax.block_until_ready(out)
            return np.asarray(out)          # phase B: after the sync
        """
        assert run(SYNC_PATH, src, "SYNC001", "SYNC002") == []

    def test_sync_inside_loop_body_respected(self):
        src = """\
        import jax
        import numpy as np
        import jax.numpy as jnp

        def group(dispatches):
            for d in dispatches:
                out = jnp.zeros(4)
                out = jax.block_until_ready(out)
                n = np.asarray(out)
        """
        assert run(SYNC_PATH, src, "SYNC001") == []

    def test_sanctioned_post_sync_methods_skipped(self):
        src = """\
        import numpy as np
        import jax.numpy as jnp

        class Disp:
            def count(self):
                return int(jnp.zeros(()))        # post-sync by contract

            def marshal(self):
                return np.asarray(jnp.zeros(4))  # post-sync by contract

            def helper(self):
                return int(jnp.zeros(()))        # NOT in the protocol
        """
        hits = run(SYNC_PATH, src, "SYNC001")
        assert [r for r, _ in hits] == ["SYNC001"]

    def test_scope_limited_to_sync_modules(self):
        src = """\
        import numpy as np
        import jax.numpy as jnp

        def anywhere():
            return np.asarray(jnp.zeros(4))
        """
        assert run("src/repro/core/index.py", src, "SYNC001") == []

    def test_scheduler_worker_loop_in_scope(self):
        """The PR 7 ratchet: scheduler.py is a sync module, worker-call
        futures are device-tainted (``submit``/``wait``), and blocking on
        one (``.result()``) is a SYNC001 unless annotated sync-point."""
        src = """\
        from concurrent.futures import wait

        def drain(pool, work):
            futures = [pool.submit(w) for w in work]
            done, _ = wait(futures, timeout=0.01)
            for fut in done:
                rs = fut.result()
        """
        hits = run("src/repro/core/scheduler.py", src, "SYNC001", "SYNC002")
        assert ("SYNC002", 6) in hits          # iterating the done-set
        assert ("SYNC001", 7) in hits          # blocking on the future
        annotated = """\
        from concurrent.futures import wait

        def drain(pool, work):
            futures = [pool.submit(w) for w in work]
            done, _ = wait(futures, timeout=0.01)
            for fut in done:                   # lint: sync-point
                rs = fut.result()              # lint: sync-point
        """
        assert run("src/repro/core/scheduler.py", annotated,
                   "SYNC001", "SYNC002") == []

    def test_repo_scheduler_is_sync_module_by_default(self):
        from repro.lint.config import LintConfig as Cfg
        assert "repro/core/scheduler.py" in Cfg().sync_modules

    def test_host_metadata_calls_not_tainted(self):
        src = """\
        import jax
        import numpy as np

        def topo():
            devs = jax.devices()
            return np.asarray(devs)
        """
        assert run(SYNC_PATH, src, "SYNC001") == []

    def test_shape_access_untaints(self):
        src = """\
        import jax.numpy as jnp

        def meta():
            out = jnp.zeros((4, 2))
            n = int(out.shape[0])
            return n
        """
        assert run(SYNC_PATH, src, "SYNC001") == []


# ----------------------------------------------------------------------
# Suppression syntax.
# ----------------------------------------------------------------------
class TestSuppressions:
    SRC = """\
    import numpy as np
    import jax.numpy as jnp

    def f():
        out = jnp.zeros(4)
        return np.asarray(out)  # lint: ignore[SYNC001]
    """

    def test_line_ignore(self):
        assert run(SYNC_PATH, self.SRC, "SYNC001") == []

    def test_def_line_ignore_covers_body(self):
        src = """\
        import numpy as np
        import jax.numpy as jnp

        def f():  # lint: ignore[SYNC001]
            out = jnp.zeros(4)
            a = np.asarray(out)
            b = out.item()
            return a, b
        """
        assert run(SYNC_PATH, src, "SYNC001") == []

    def test_multiline_signature_ignore_covers_body(self):
        src = """\
        import numpy as np
        import jax.numpy as jnp

        def f(x,
              y):  # lint: ignore[SYNC001]
            out = jnp.zeros(4)
            return np.asarray(out)
        """
        assert run(SYNC_PATH, src, "SYNC001") == []

    def test_star_ignores_everything(self):
        src = self.SRC.replace("ignore[SYNC001]", "ignore[*]")
        assert run(SYNC_PATH, src, "SYNC001", "SYNC002") == []

    def test_wrong_rule_id_does_not_suppress(self):
        src = self.SRC.replace("ignore[SYNC001]", "ignore[KERN001]")
        assert rules_of(SYNC_PATH, src, "SYNC001") == {"SYNC001"}

    def test_sync_point_annotation(self):
        src = """\
        import numpy as np
        import jax.numpy as jnp

        def f():
            out = jnp.zeros(4)
            n = int(out)  # lint: sync-point — deliberate early count read
            return np.asarray(out)   # post-sync from here on
        """
        assert run(SYNC_PATH, src, "SYNC001") == []


# ----------------------------------------------------------------------
# KERN: Pallas kernel/BlockSpec contract checks.
# ----------------------------------------------------------------------
class TestKernRules:
    def test_index_map_arity_mismatch(self):
        src = """\
        import jax.experimental.pallas as pl

        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def launch(x):
            return pl.pallas_call(
                kernel,
                grid=(4, 4),
                in_specs=[pl.BlockSpec((8, 8), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, 8), lambda i, j: (i, j)),
                out_shape=None,
            )(x)
        """
        hits = run(KERN_PATH, src, "KERN001")
        assert [r for r, _ in hits] == ["KERN001"]

    def test_param_count_mismatch(self):
        src = """\
        import jax.experimental.pallas as pl

        def kernel(x_ref, y_ref, o_ref):
            o_ref[...] = x_ref[...]

        def launch(x):
            return pl.pallas_call(
                kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((8,), lambda i: (i,))],
                out_specs=pl.BlockSpec((8,), lambda i: (i,)),
                out_shape=None,
            )(x)
        """
        hits = run(KERN_PATH, src, "KERN002")
        assert [r for r, _ in hits] == ["KERN002"]

    def test_consistent_call_clean(self):
        src = """\
        import jax.experimental.pallas as pl

        def kernel(x_ref, y_ref, o_ref):
            o_ref[...] = x_ref[...] + y_ref[...]

        def launch(x, y):
            return pl.pallas_call(
                kernel,
                grid=(4, 2),
                in_specs=[pl.BlockSpec((8, 8), lambda i, j: (i, j)),
                          pl.BlockSpec((8, 8), lambda i, j: (i, j))],
                out_specs=pl.BlockSpec((8, 8), lambda i, j: (i, j)),
                out_shape=None,
            )(x, y)
        """
        assert run(KERN_PATH, src, "KERN001", "KERN002", "KERN004") == []

    def test_revisited_output_without_guard(self):
        src = """\
        import jax.experimental.pallas as pl

        def kernel(x_ref, o_ref):
            o_ref[...] = o_ref[...] + x_ref[...]

        def launch(x):
            return pl.pallas_call(
                kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((8,), lambda i: (i,))],
                out_specs=pl.BlockSpec((8,), lambda i: (0,)),
                out_shape=None,
            )(x)
        """
        hits = run(KERN_PATH, src, "KERN004")
        assert [r for r, _ in hits] == ["KERN004"]

    def test_revisited_output_with_when_guard_clean(self):
        src = """\
        import jax.experimental.pallas as pl

        def kernel(x_ref, o_ref):
            @pl.when(pl.program_id(0) == 0)
            def _():
                o_ref[...] = x_ref[...]

        def launch(x):
            return pl.pallas_call(
                kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((8,), lambda i: (i,))],
                out_specs=pl.BlockSpec((8,), lambda i: (0,)),
                out_shape=None,
            )(x)
        """
        assert run(KERN_PATH, src, "KERN004") == []

    def test_prefetch_ref_scanned_with_python_loop(self):
        src = """\
        import jax.experimental.pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def kernel(ti_ref, nl_ref, x_ref, o_ref):
            for i in range(8):
                o_ref[ti_ref[i]] = x_ref[i]

        def launch(ti, nl, x):
            gs = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(8,),
                in_specs=[pl.BlockSpec((8, 8), lambda s, ti, nl: (ti[s], 0))],
                out_specs=pl.BlockSpec((8,), lambda s, ti, nl: (0,)),
            )
            return pl.pallas_call(kernel, grid_spec=gs,
                                  out_shape=None)(ti, nl, x)
        """
        hits = run(KERN_PATH, src, "KERN006")
        assert [r for r, _ in hits] == ["KERN006"]

    def test_prefetch_ref_scanned_with_fori_loop(self):
        src = """\
        import jax
        import jax.experimental.pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def kernel(ti_ref, nl_ref, x_ref, o_ref):
            o_ref[0] = jax.lax.fori_loop(
                0, nl_ref[0], lambda i, acc: acc + ti_ref[i], 0)

        def launch(ti, nl, x):
            gs = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(8,),
                in_specs=[pl.BlockSpec((8, 8), lambda s, ti, nl: (ti[s], 0))],
                out_specs=pl.BlockSpec((8,), lambda s, ti, nl: (0,)),
            )
            return pl.pallas_call(kernel, grid_spec=gs,
                                  out_shape=None)(ti, nl, x)
        """
        hits = run(KERN_PATH, src, "KERN006")
        assert [r for r, _ in hits] == ["KERN006"]

    def test_prefetch_ref_grid_id_indexing_clean(self):
        # The sanctioned pattern: slot id from pl.program_id plus a
        # constant-index live-count read — exactly how the repo's
        # live-tile kernel consumes its prefetched list.
        src = """\
        import jax.experimental.pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def kernel(ti_ref, nl_ref, x_ref, o_ref):
            s = pl.program_id(0)
            @pl.when(s < nl_ref[0])
            def _run():
                o_ref[...] = x_ref[...] * ti_ref[s]

        def launch(ti, nl, x):
            gs = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(8,),
                in_specs=[pl.BlockSpec((8, 8), lambda s, ti, nl: (ti[s], 0))],
                out_specs=pl.BlockSpec((8,), lambda s, ti, nl: (0,)),
            )
            return pl.pallas_call(kernel, grid_spec=gs,
                                  out_shape=None)(ti, nl, x)
        """
        assert run(KERN_PATH, src, "KERN006") == []

    def test_non_prefetch_ref_loops_clean(self):
        # Loop-scanning an ordinary operand ref is outside KERN006's
        # contract; only the scalar-prefetch leading params are protected.
        src = """\
        import jax.experimental.pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def kernel(ti_ref, nl_ref, x_ref, o_ref):
            s = pl.program_id(0)
            for i in range(8):
                o_ref[i] = x_ref[i] + ti_ref[s]

        def launch(ti, nl, x):
            gs = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(8,),
                in_specs=[pl.BlockSpec((8, 8), lambda s, ti, nl: (ti[s], 0))],
                out_specs=pl.BlockSpec((8,), lambda s, ti, nl: (0,)),
            )
            return pl.pallas_call(kernel, grid_spec=gs,
                                  out_shape=None)(ti, nl, x)
        """
        assert run(KERN_PATH, src, "KERN006") == []

    def test_scope_limited_to_kern_modules(self):
        src = """\
        import jax.experimental.pallas as pl

        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def launch(x):
            return pl.pallas_call(
                kernel, grid=(4, 4),
                in_specs=[pl.BlockSpec((8,), lambda i: (i,))],
                out_specs=pl.BlockSpec((8,), lambda i: (i,)),
                out_shape=None)(x)
        """
        assert run("src/repro/serve/broker.py", src, "KERN001") == []


# ----------------------------------------------------------------------
# TRACE: tracer safety inside jit/shard_map scopes.
# ----------------------------------------------------------------------
class TestTraceRules:
    def test_branch_on_traced_value(self):
        src = """\
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            y = jnp.sum(x)
            if y > 0:
                y = y + 1
            return y
        """
        hits = run(TRACE_PATH, src, "TRACE001")
        assert [r for r, _ in hits] == ["TRACE001"]

    def test_static_arg_branch_clean(self):
        src = """\
        import functools
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnames=("flag",))
        def f(x, flag):
            if flag:
                return x + 1
            return x
        """
        assert run(TRACE_PATH, src, "TRACE001") == []

    def test_impure_call_under_trace(self):
        src = """\
        import time
        import jax

        @jax.jit
        def f(x):
            t = time.perf_counter()
            return x
        """
        hits = run(TRACE_PATH, src, "TRACE002")
        assert [r for r, _ in hits] == ["TRACE002"]

    def test_captured_state_mutation_under_trace(self):
        src = """\
        import jax

        acc = []

        @jax.jit
        def f(x):
            acc.append(x)
            return x
        """
        hits = run(TRACE_PATH, src, "TRACE003")
        assert [r for r, _ in hits] == ["TRACE003"]

    def test_local_mutation_clean(self):
        src = """\
        import jax

        @jax.jit
        def f(x):
            parts = []
            parts.append(x)
            return parts[0]
        """
        assert run(TRACE_PATH, src, "TRACE003") == []

    def test_untraced_function_unconstrained(self):
        src = """\
        import time
        import jax.numpy as jnp

        def host_helper(x):
            t = time.perf_counter()
            if jnp.sum(x) > 0:
                return t
            return 0.0
        """
        assert run(TRACE_PATH, src, "TRACE001", "TRACE002") == []


# ----------------------------------------------------------------------
# DEAD001: import-graph reachability.
# ----------------------------------------------------------------------
class TestDeadRule:
    def test_unreachable_module_flagged(self, tmp_path):
        items = [
            ("src/repro/api.py", "import repro.core.used\n"),
            ("src/repro/core/__init__.py", ""),
            ("src/repro/core/used.py", "X = 1\n"),
            ("src/repro/core/orphan.py", "Y = 2\n"),
        ]
        vs = lint_sources(items, select=("DEAD001",), root=str(tmp_path))
        assert [(v.rule, v.path) for v in vs] == [
            ("DEAD001", "src/repro/core/orphan.py")]
        assert all(v.severity == "warn" for v in vs)

    def test_test_imports_are_roots(self, tmp_path):
        (tmp_path / "tests").mkdir()
        (tmp_path / "tests" / "test_x.py").write_text(
            "from repro.core import orphan\n")
        items = [
            ("src/repro/api.py", ""),
            ("src/repro/core/__init__.py", ""),
            ("src/repro/core/orphan.py", "Y = 2\n"),
        ]
        vs = lint_sources(items, select=("DEAD001",), root=str(tmp_path))
        assert vs == []


# ----------------------------------------------------------------------
# The acceptance-criterion plants: mutate the *real* sources and assert
# the specific violation appears (and disappears on the clean tree).
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def real_sources():
    paths = ("src/repro/core/executor.py", "src/repro/kernels/distthresh.py",
             "src/repro/core/distributed.py")
    return {p: open(p, encoding="utf-8").read() for p in paths}


class TestPlants:
    def test_plant_item_in_phase_a(self, real_sources):
        path = "src/repro/core/executor.py"
        anchor = "slots[i] = disp.dispatch(batch, plan.capacities[i])"
        assert anchor in real_sources[path]
        mutated = real_sources[path].replace(
            anchor,
            anchor + '\n                _dbg = slots[i].out["count"].item()')
        vs = lint_sources([(path, mutated)], select=("SYNC001",))
        assert [v.rule for v in vs] == ["SYNC001"]
        vs = lint_sources([(path, real_sources[path])], select=("SYNC001",))
        assert vs == []

    def test_plant_index_map_arity(self, real_sources):
        path = "src/repro/kernels/distthresh.py"
        anchor = "pl.BlockSpec((cand_blk, 8), lambda i, j: (i, 0)),   # entries\n"
        assert real_sources[path].count(anchor) == 1
        mutated = real_sources[path].replace(
            anchor, "pl.BlockSpec((cand_blk, 8), lambda i: (i, 0)),\n")
        vs = lint_sources([(path, mutated)], select=("KERN001",))
        assert [v.rule for v in vs] == ["KERN001"]
        vs = lint_sources([(path, real_sources[path])],
                          select=("KERN001", "KERN002", "KERN004"))
        assert vs == []

    def test_plant_branch_on_traced(self, real_sources):
        path = "src/repro/core/distributed.py"
        anchor = "            return _finish(out)"
        assert anchor in real_sources[path]
        mutated = real_sources[path].replace(
            anchor,
            '            if out["count"] > 0:\n'
            "                out = dict(out)\n" + anchor)
        vs = lint_sources([(path, mutated)], select=("TRACE001",))
        assert [v.rule for v in vs] == ["TRACE001"]
        vs = lint_sources([(path, real_sources[path])],
                          select=("TRACE001", "TRACE002", "TRACE003"))
        assert vs == []


# ----------------------------------------------------------------------
# Repo baseline + CLI/JSON contract.
# ----------------------------------------------------------------------
class TestCliAndBaseline:
    def test_repo_is_error_clean(self):
        vs = lint_paths(["src"])
        errors = [v for v in vs if v.severity == "error"]
        assert errors == [], "\n".join(v.format() for v in errors)

    def test_cli_json_schema(self, capsys):
        code = lint_main(["src", "--format=json"])
        payload = json.loads(capsys.readouterr().out)
        assert code == 0
        assert payload["tool"] == "repro-lint"
        assert payload["schema_version"] == 1
        assert set(payload["counts"]) >= {"error", "warn"}
        assert payload["counts"]["error"] == 0
        for v in payload["violations"]:
            assert set(v) == {"rule", "severity", "path", "line", "col",
                              "message"}
            assert v["severity"] in ("error", "warn")
            assert v["line"] >= 1

    def test_cli_exit_code_on_error(self, tmp_path, capsys):
        bad = tmp_path / "executor.py"
        bad_path = tmp_path / "src" / "repro" / "core"
        bad_path.mkdir(parents=True)
        (bad_path / "executor.py").write_text(textwrap.dedent("""\
            import numpy as np
            import jax.numpy as jnp

            def f():
                return np.asarray(jnp.zeros(4))
            """))
        code = lint_main([str(bad_path / "executor.py"), "--root",
                          str(tmp_path)])
        out = capsys.readouterr().out
        assert code == 1
        assert "SYNC001" in out

    def test_select_and_ignore_filters(self):
        src = textwrap.dedent("""\
            import numpy as np
            import jax.numpy as jnp

            def f():
                out = jnp.zeros(4)
                a = np.asarray(out)
                b = list(out)
                return a, b
            """)
        both = lint_sources([(SYNC_PATH, src)])
        assert {v.rule for v in both} >= {"SYNC001", "SYNC002"}
        only1 = lint_sources([(SYNC_PATH, src)], select=("SYNC001",))
        assert {v.rule for v in only1} == {"SYNC001"}
        no1 = lint_sources([(SYNC_PATH, src)], ignore=("SYNC001",))
        assert "SYNC001" not in {v.rule for v in no1}

    def test_parse_error_is_violation_not_crash(self):
        vs = lint_sources([("src/repro/core/broken.py", "def f(:\n")])
        assert [v.rule for v in vs] == ["PARSE"]
        assert vs[0].severity == "error"

    def test_summarize(self):
        vs = lint_paths(["src"])
        counts = summarize(vs)
        assert counts["error"] == 0
        assert counts["warn"] >= 0

    def test_config_overrides(self):
        cfg = LintConfig(sync_modules=("repro/core/index.py",))
        src = textwrap.dedent("""\
            import numpy as np
            import jax.numpy as jnp

            def f():
                return np.asarray(jnp.zeros(4))
            """)
        vs = lint_sources([("src/repro/core/index.py", src)], config=cfg,
                          select=("SYNC001",))
        assert [v.rule for v in vs] == ["SYNC001"]


# ----------------------------------------------------------------------
# Runtime sentinel: the measured transfer count closes the loop on the
# static SYNC rules — pipelined S2 must do its ≤ 2 syncs per dispatch
# group and zero hidden blocking reads inside the run itself.
# ----------------------------------------------------------------------
class TestSentinel:
    @pytest.fixture(scope="class")
    def s2(self):
        from repro.api import ExecutionPolicy, TrajectoryDB
        policy = ExecutionPolicy(batching="periodic", batch_params={"s": 32},
                                 num_bins=200)
        db = TrajectoryDB.from_scenario("S2", scale=0.01, policy=policy)
        return db, db.scenario_queries, db.scenario_d

    def test_pipelined_run_sync_budget(self, s2):
        db, queries, d = s2
        be = db.backend("jnp")
        qs, _ = db._sorted(queries)
        plan = db._make_plan(qs, db.policy, "jnp", d=float(d))
        # warm-up outside the sentinel: tracing/compilation does its own
        # device↔host traffic that is not part of the steady-state claim
        be.run(qs, float(d), plan)
        with SyncSentinel() as s:
            rs, stats = be.run(qs, float(d), plan)
        rep = s.report()
        assert stats.pipelined
        assert len(rs.entry_idx) > 0
        # the static-rule claim, now measured: no hidden blocking reads,
        # and the explicit syncs are exactly what ExecStats reports,
        # within the paper's O(1)-per-group budget
        assert rep.blocking_reads == 0
        assert rep.explicit_syncs == stats.num_syncs
        assert stats.num_syncs <= 2 * stats.num_groups

    def test_sentinel_counts_reads(self):
        import jax
        import jax.numpy as jnp
        import numpy as np
        with SyncSentinel() as s:
            x = jnp.arange(4.0)
            jax.block_until_ready(x)
            np.asarray(x)
            x[0].item()
        rep = s.report()
        assert rep.explicit_syncs == 1
        assert rep.ready_reads + rep.blocking_reads == 2
        assert rep.by_kind.get("block_until_ready") == 1

    def test_sentinel_attributes_blocking_reads_to_groups(self):
        """A blocking read inside an executor dispatch-group scope is
        attributed to that group's label; reads outside any scope land
        under ``None``."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from repro.core.executor import _group_scope, current_group_label

        assert current_group_label() is None
        with SyncSentinel() as s:
            with _group_scope("pipelined:dispatch:7"):
                assert current_group_label() == "pipelined:dispatch:7"
                x = jnp.arange(65536.0)
                for _ in range(6):        # enough work to still be pending
                    x = jnp.sin(x) * 1.0001
                np.asarray(x)             # may or may not block — recorded
            assert current_group_label() is None
        rep = s.report()
        # every blocking read (if any) carries the group label; none are
        # unattributed because the only read happened inside the scope
        assert set(rep.blocking_by_group) <= {"pipelined:dispatch:7"}
        assert sum(rep.blocking_by_group.values()) == rep.blocking_reads

    def test_pipelined_run_attributes_no_blocking_reads(self):
        """End to end: a pipelined engine run has an empty per-group
        blame table — the executors' scopes are active, but nothing
        blocks inside them."""
        from repro.api import ExecutionPolicy, TrajectoryDB
        policy = ExecutionPolicy(batching="periodic", batch_params={"s": 32},
                                 num_bins=100)
        db = TrajectoryDB.from_scenario("S2", scale=0.005, policy=policy)
        be = db.backend("jnp")
        qs, _ = db._sorted(db.scenario_queries)
        plan = db._make_plan(qs, db.policy, "jnp", d=float(db.scenario_d))
        be.run(qs, float(db.scenario_d), plan)       # warm-up
        with SyncSentinel() as s:
            be.run(qs, float(db.scenario_d), plan)
        assert s.report().blocking_by_group == {}

    def test_sentinel_restores_patches(self):
        import jax
        import jax.numpy as jnp
        cls = type(jnp.zeros(()))
        before = (jax.block_until_ready, cls.__array__, cls.item)
        with SyncSentinel():
            pass
        after = (jax.block_until_ready, cls.__array__, cls.item)
        assert before == after


# ----------------------------------------------------------------------
# FAULT001: fault-injection hooks must sit behind `if faults.armed():`.
# ----------------------------------------------------------------------
class TestFault001:
    PATH = "src/repro/core/engine.py"

    def test_unguarded_qualified_call_flagged(self):
        src = """\
            from repro import faults

            def dispatch(batch):
                faults.inject("engine.dispatch", batch=batch.index)
                return run(batch)
            """
        assert rules_of(self.PATH, src, "FAULT001") == {"FAULT001"}

    def test_guarded_call_clean(self):
        src = """\
            from repro import faults

            def dispatch(batch):
                if faults.armed():
                    faults.inject("engine.dispatch", batch=batch.index)
                return run(batch)
            """
        assert run(self.PATH, src, "FAULT001") == []

    def test_ifexp_guard_accepted(self):
        src = """\
            from repro import faults

            def count(n):
                return faults.corrupt("engine.count", n) if faults.armed() else n
            """
        assert run(self.PATH, src, "FAULT001") == []

    def test_bare_imported_hook_flagged(self):
        src = """\
            from repro.faults import inject as _fi

            def pump():
                _fi("broker.plan", uid=0)
            """
        assert rules_of(self.PATH, src, "FAULT001") == {"FAULT001"}

    def test_unrelated_inject_name_ignored(self):
        src = """\
            def pump(container):
                container.inject("dependency")
                corrupt = lambda x: x
                corrupt(3)
            """
        assert run(self.PATH, src, "FAULT001") == []

    def test_suppression_honored(self):
        src = """\
            from repro import faults

            def dispatch(batch):
                faults.inject("engine.dispatch")  # lint: ignore[FAULT001]
            """
        assert run(self.PATH, src, "FAULT001") == []

    def test_faults_package_exempt(self):
        src = """\
            def inject(site, ctx):
                _PLAN.inject(site, ctx)
            """
        assert run("src/repro/faults/__init__.py", src, "FAULT001") == []

    def test_wrong_guard_still_flagged(self):
        src = """\
            from repro import faults

            def dispatch(batch, chaos):
                if chaos:
                    faults.inject("engine.dispatch")
                return run(batch)
            """
        assert rules_of(self.PATH, src, "FAULT001") == {"FAULT001"}

    def test_repo_sources_fault_clean(self):
        vs = lint_paths(["src"], select=("FAULT001",))
        assert vs == []
