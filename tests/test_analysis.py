"""tpulint (paddle_tpu.analysis) — the round-8 static-analysis gate.

Three layers of coverage:

1. **Per-rule fixtures** — every rule has a seeded-positive (known-bad
   snippet/jaxpr -> the rule FIRES) and a negative (idiomatic code ->
   silent), so a refactor cannot quietly lobotomize a rule.
2. **Regression locks** — the real hazards round 8 fixed stay fixed: the
   autotune harnesses draw q/k/v from SPLIT keys (AL001 clean), every MXU
   op carries a flops_fn (RA003 clean), the new flops fns compute the
   analytic MACs.
3. **The repo gate** — all passes over the real tree + flagship callables
   against analysis/baseline.json: any non-baselined finding fails tier-1,
   which is the CI contract ``python -m paddle_tpu.analysis`` enforces.
"""
import json
import os
import textwrap

import numpy as np
import pytest

from paddle_tpu.analysis import (PASSES, diff_against_baseline, load_baseline,
                                 run_all)
from paddle_tpu.analysis import astlint, bench_schema
from paddle_tpu.analysis.findings import Finding, write_baseline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lint(src, registry_names=("matmul", "softmax")):
    return astlint.lint_source(textwrap.dedent(src), "fixture.py",
                               registry_names=set(registry_names))


def _rules(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# findings / baseline core
# ---------------------------------------------------------------------------


class TestFindingsCore:
    def test_fingerprint_excludes_line_and_prose(self):
        a = Finding(rule="AL001", target="x.py", detail="f:key",
                    message="msg one", line=10)
        b = Finding(rule="AL001", target="x.py", detail="f:key",
                    message="different prose", line=99)
        assert a.fingerprint == b.fingerprint

    def test_baseline_roundtrip_and_diff(self, tmp_path):
        p = str(tmp_path / "baseline.json")
        f1 = Finding(rule="R1", target="t", detail="a", message="m")
        f2 = Finding(rule="R1", target="t", detail="b", message="m")
        write_baseline([f1], path=p)
        base = set(json.load(open(p))["findings"])
        assert base == {f1.fingerprint}
        new, accepted, fixed = diff_against_baseline([f2], base)
        assert [f.fingerprint for f in new] == [f2.fingerprint]
        assert not accepted and fixed == [f1.fingerprint]

    def test_partial_write_preserves_other_passes(self, tmp_path):
        """--passes source --write-baseline must not drop accepted
        fingerprints owned by the passes that did not run."""
        from paddle_tpu.analysis import pass_of_fingerprint

        p = str(tmp_path / "baseline.json")
        trace_fp = "JX005::serving-unified-step::arg11"
        src = Finding(rule="AL001", target="x.py", detail="f:key",
                      message="m")
        assert pass_of_fingerprint(trace_fp) == "trace"
        # the CLI's merge: source pass ran, trace entry preserved via keep=
        keep = {fp for fp in {trace_fp}
                if pass_of_fingerprint(fp) not in ("source",)}
        write_baseline([src], path=p, keep=keep)
        base = set(json.load(open(p))["findings"])
        assert base == {src.fingerprint, trace_fp}

    def test_partial_run_does_not_report_other_passes_stale(
            self, tmp_path, monkeypatch, capsys):
        """A --passes bench run must not report a baselined trace finding
        (whose pass did not run) as a stale entry to be dropped."""
        from paddle_tpu.analysis import __main__ as cli
        from paddle_tpu.analysis import findings as fmod

        p = tmp_path / "baseline.json"
        p.write_text(json.dumps(
            {"findings": ["JX005::serving-unified-step::arg11"]}))
        monkeypatch.setattr(fmod, "BASELINE_PATH", str(p))
        rc = cli.main(["--passes", "bench", "--json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert out["fixed_baseline_entries"] == []


# ---------------------------------------------------------------------------
# AL rules — seeded positive + negative per rule
# ---------------------------------------------------------------------------


class TestASTRules:
    def test_al001_fires_on_key_reuse(self):
        fs = _lint("""
            import jax

            def bench():
                key = jax.random.PRNGKey(0)
                q = jax.random.normal(key, (8, 8))
                k = jax.random.normal(key, (8, 8))
                return q, k
        """)
        assert "AL001" in _rules(fs)

    def test_al001_fires_in_second_same_named_def(self):
        # two classes both defining `forward` (the dominant method name in
        # this codebase): the SECOND one must not be invisible to the rule
        fs = _lint("""
            import jax

            class A:
                def forward(self, key):
                    return jax.random.normal(key, (4,))

            class B:
                def forward(self, key):
                    q = jax.random.normal(key, (4,))
                    v = jax.random.uniform(key, (4,))
                    return q, v
        """)
        assert "AL001" in _rules(fs)

    def test_al001_silent_on_split_keys(self):
        fs = _lint("""
            import jax

            def bench():
                kq, kk = jax.random.split(jax.random.PRNGKey(0), 2)
                q = jax.random.normal(kq, (8, 8))
                k = jax.random.normal(kk, (8, 8))
                return q, k
        """)
        assert "AL001" not in _rules(fs)

    def test_al001_silent_on_rebind_between_uses(self):
        fs = _lint("""
            import jax

            def bench(key):
                q = jax.random.normal(key, (8, 8))
                key = jax.random.fold_in(key, 1)
                k = jax.random.normal(key, (8, 8))
                return q, k
        """)
        assert "AL001" not in _rules(fs)

    def test_al001_scoped_to_innermost_function(self):
        # two nested closures each binding their own `key` param: no reuse
        fs = _lint("""
            import jax

            def outer():
                def a(key):
                    return jax.random.normal(key, (4,))
                b = lambda key: jax.random.uniform(key, (4,))
                return a, b
        """)
        assert "AL001" not in _rules(fs)

    def test_al002_fires_on_item_in_jitted_fn(self):
        fs = _lint("""
            import jax

            def step(x):
                return x * x.sum().item()

            step_jit = jax.jit(step)
        """)
        assert "AL002" in _rules(fs)

    def test_al002_fires_on_jit_decorator_forms(self):
        # the repo's own idiom (@jax.jit / @partial(jax.jit, ...)) must be
        # recognized, not just the jax.jit(fn) call form
        fs = _lint("""
            import jax
            from functools import partial

            @jax.jit
            def step(x):
                return x * x.sum().item()

            @partial(jax.jit, static_argnums=0)
            def step2(n, x):
                return x * x.max().item()
        """)
        al002 = [f for f in fs if f.rule == "AL002"]
        assert {f.detail for f in al002} == {"step:item", "step2:item"}

    def test_al002_silent_outside_jit_and_on_shapes(self):
        fs = _lint("""
            import jax

            def host_fn(x):
                return x.sum().item()  # eager: allowed

            def step(x):
                n = int(x.shape[0])   # static shape math: allowed
                return x * n

            step_jit = jax.jit(step)
        """)
        assert "AL002" not in _rules(fs)

    def test_al003_fires_on_loop_over_shape_in_jit(self):
        fs = _lint("""
            import jax

            def step(x):
                out = 0
                for i in range(x.shape[0]):
                    out = out + x[i]
                return out

            step_jit = jax.jit(step)
        """)
        assert "AL003" in _rules(fs)

    def test_al003_silent_on_scan_and_eager_loops(self):
        fs = _lint("""
            import jax
            from jax import lax

            def step(x):
                return lax.scan(lambda c, r: (c + r, None), 0.0, x)[0]

            step_jit = jax.jit(step)

            def eager(x):
                for i in range(x.shape[0]):  # not jitted: fine
                    pass
        """)
        assert "AL003" not in _rules(fs)

    def test_al004_fires_on_misaligned_tile(self):
        fs = _lint("""
            from jax.experimental import pallas as pl

            spec = pl.BlockSpec((8, 100), lambda i: (i, 0))
            spec2 = pl.BlockSpec((12, 128), lambda i: (i, 0))
        """)
        al004 = [f for f in _lint("""
            from jax.experimental import pallas as pl

            spec = pl.BlockSpec((8, 100), lambda i: (i, 0))
            spec2 = pl.BlockSpec((12, 128), lambda i: (i, 0))
        """) if f.rule == "AL004"]
        assert len(al004) == 2  # 100 % 128, 12 % 8
        assert "AL004" in _rules(fs)

    def test_al004_silent_on_aligned_and_squeezed_dims(self):
        fs = _lint("""
            from jax.experimental import pallas as pl

            a = pl.BlockSpec((8, 128), lambda i: (i, 0))
            b = pl.BlockSpec((None, 256, None, 128), lambda i: (i, 0, 0, 0))
            c = pl.BlockSpec((1, 1), lambda i: (0, 0))     # squeezed dims
            d = pl.BlockSpec((None, None, 8, 1), lambda i: (i, 0, 0, 0))
            e = pl.BlockSpec((rows, h), lambda i: (i, 0))  # non-constant
        """)
        assert "AL004" not in _rules(fs)

    def test_al005_fires_on_unregistered_op(self):
        fs = _lint("""
            from paddle_tpu.autograd.engine import apply_op

            def f(x):
                return apply_op("definitely_not_an_op_xyz", lambda v: v, x)
        """)
        assert "AL005" in _rules(fs)

    def test_al005_silent_on_registered_and_dynamic_names(self):
        fs = _lint("""
            from paddle_tpu.autograd.engine import apply_op

            def f(x, name):
                a = apply_op("matmul", lambda v: v, x)
                b = apply_op(f"rnn_{name}", lambda v: v, x)  # dynamic: strict
                return a, b                                  # mode covers it
        """)
        assert "AL005" not in _rules(fs)

    def test_pragma_suppresses(self):
        fs = _lint("""
            import jax

            def bench():
                key = jax.random.PRNGKey(0)
                q = jax.random.normal(key, (8, 8))
                k = jax.random.normal(key, (8, 8))  # tpulint: disable=AL001
                return q, k
        """)
        assert "AL001" not in _rules(fs)

    # -- AL006: raw perf_counter timing in the fenced hot-path dirs ---------

    _TIMING_SRC = """
        import time
        from time import perf_counter

        def f():
            t0 = time.perf_counter()
            t1 = perf_counter()
            t2 = time.perf_counter_ns()
            return t0, t1, t2
    """

    def test_al006_fires_in_inference_and_distributed(self):
        for where in ("paddle_tpu/inference/serving.py",
                      "paddle_tpu/distributed/fleet/fleet.py"):
            fs = astlint.lint_source(textwrap.dedent(self._TIMING_SRC),
                                     where)
            al006 = [f for f in fs if f.rule == "AL006"]
            assert len(al006) == 3, (where, fs)   # all three spellings

    def test_al006_silent_outside_fenced_dirs_and_in_observability(self):
        for where in ("paddle_tpu/models/gpt.py",     # timing allowed
                      "paddle_tpu/observability/tracing.py",  # owns clock
                      "fixture.py"):
            fs = astlint.lint_source(textwrap.dedent(self._TIMING_SRC),
                                     where)
            assert "AL006" not in _rules(fs), where

    def test_al006_pragma_suppresses(self):
        fs = astlint.lint_source(textwrap.dedent("""
            import time

            def f():
                return time.perf_counter()  # tpulint: disable=AL006
        """), "paddle_tpu/inference/serving.py")
        assert "AL006" not in _rules(fs)

    # -- AL007: swallowed exceptions in the fenced hot-path dirs ------------

    _SWALLOW_SRC = """
        def f():
            try:
                work()
            except:
                pass
            try:
                work()
            except Exception:
                pass
            try:
                work()
            except (ValueError, Exception):
                ...
    """

    def test_al007_fires_in_inference_and_distributed(self):
        for where in ("paddle_tpu/inference/serving.py",
                      "paddle_tpu/distributed/collective.py"):
            fs = astlint.lint_source(textwrap.dedent(self._SWALLOW_SRC),
                                     where)
            al007 = [f for f in fs if f.rule == "AL007"]
            # bare, broad, and broad-inside-a-tuple all fire
            assert len(al007) == 3, (where, fs)

    def test_al007_silent_on_narrow_or_handled_or_outside(self):
        handled = textwrap.dedent("""
            def f():
                try:
                    work()
                except KeyError:
                    pass                      # narrow: deliberate drop
                try:
                    work()
                except Exception as e:
                    log(e)                    # handled, not swallowed
                try:
                    work()
                except Exception:
                    raise RuntimeError("x")   # re-raised
        """)
        fs = astlint.lint_source(handled, "paddle_tpu/inference/serving.py")
        assert "AL007" not in _rules(fs)
        # the fence covers inference/ + distributed/ only
        fs = astlint.lint_source(textwrap.dedent(self._SWALLOW_SRC),
                                 "paddle_tpu/models/gpt.py")
        assert "AL007" not in _rules(fs)

    def test_al007_pragma_suppresses(self):
        fs = astlint.lint_source(textwrap.dedent("""
            def f():
                try:
                    work()
                except Exception:  # tpulint: disable=AL007
                    pass
        """), "paddle_tpu/inference/serving.py")
        assert "AL007" not in _rules(fs)

    def test_fleet_serving_sits_inside_both_hot_path_fences(self):
        """Round-18 satellite: the fleet layer
        (paddle_tpu/inference/fleet_serving.py) is hot-path serving code
        — the AL006 raw-timing fence AND the AL007 swallowed-exception
        fence must both cover it (directory fences; this pins the path
        so a future move out of inference/ fails loudly). The module
        itself ships clean: the repo gate below holds the baseline
        EMPTY over the real tree including it."""
        where = "paddle_tpu/inference/fleet_serving.py"
        fs = astlint.lint_source(textwrap.dedent(self._TIMING_SRC), where)
        assert len([f for f in fs if f.rule == "AL006"]) == 3, fs
        fs = astlint.lint_source(textwrap.dedent(self._SWALLOW_SRC), where)
        assert len([f for f in fs if f.rule == "AL007"]) == 3, fs

    def test_kv_transfer_sits_inside_both_hot_path_fences(self):
        """Round-20 satellite: the KV-page transfer wire
        (paddle_tpu/inference/kv_transfer.py) is hot-path serving code
        with exactly the failure modes AL006/AL007 exist for (ad-hoc
        timing around the wire, swallowed decode errors) — both
        directory fences must cover it, and the module ships clean (the
        repo gate below holds the baseline EMPTY over the real tree
        including it)."""
        where = "paddle_tpu/inference/kv_transfer.py"
        fs = astlint.lint_source(textwrap.dedent(self._TIMING_SRC), where)
        assert len([f for f in fs if f.rule == "AL006"]) == 3, fs
        fs = astlint.lint_source(textwrap.dedent(self._SWALLOW_SRC), where)
        assert len([f for f in fs if f.rule == "AL007"]) == 3, fs

    def test_tiered_kv_cache_sits_inside_both_hot_path_fences(self):
        """Round-21 satellite: the host-tier spill/restore code lives in
        paddle_tpu/inference/kv_cache.py — hot-path serving code with
        exactly the failure modes AL006/AL007 exist for (ad-hoc timing
        around the spill DMA, a swallowed checksum error silently
        scattering a corrupt payload into the pool) — both directory
        fences must cover it, and the module ships clean (the repo gate
        below holds the baseline EMPTY over the real tree including
        it)."""
        where = "paddle_tpu/inference/kv_cache.py"
        fs = astlint.lint_source(textwrap.dedent(self._TIMING_SRC), where)
        assert len([f for f in fs if f.rule == "AL006"]) == 3, fs
        fs = astlint.lint_source(textwrap.dedent(self._SWALLOW_SRC), where)
        assert len([f for f in fs if f.rule == "AL007"]) == 3, fs


# ---------------------------------------------------------------------------
# JX rules — seeded positive + negative per rule
# ---------------------------------------------------------------------------


class TestJaxprRules:
    @pytest.fixture(autouse=True)
    def _mods(self):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.analysis.jaxpr_checks import (analyze_jaxpr,
                                                      check_donation,
                                                      trace_callable)

        self.jax, self.jnp = jax, jnp
        self.analyze, self.donation, self.trace = (
            analyze_jaxpr, check_donation, trace_callable)

    def test_jx001_fires_on_f64_from_f32_inputs(self):
        jnp = self.jnp
        j = self.trace(lambda x: x.astype(jnp.float64).sum(),
                       jnp.ones((4,), jnp.float32))
        assert "JX001" in _rules(self.analyze(j, "t"))

    def test_jx001_silent_when_inputs_are_f64(self):
        jnp = self.jnp
        j = self.trace(lambda x: x.sum(), jnp.ones((4,), jnp.float64))
        assert "JX001" not in _rules(self.analyze(j, "t"))

    def test_jx002_fires_on_interior_contraction(self):
        jnp = self.jnp
        a = jnp.ones((256, 64, 256), jnp.float32)  # 16 MiB operand
        v = jnp.ones((64,), jnp.float32)
        j = self.trace(lambda a, v: jnp.einsum("ikj,k->ij", a, v), a, v)
        assert "JX002" in _rules(self.analyze(j, "t"))

    def test_jx002_silent_on_edge_contractions_and_small_operands(self):
        jnp = self.jnp
        a = jnp.ones((512, 512), jnp.float32)
        b = jnp.ones((512, 512), jnp.float32)
        j = self.trace(lambda a, b: a @ b, a, b)
        assert "JX002" not in _rules(self.analyze(j, "t"))
        small = jnp.ones((8, 4, 8), jnp.float32)  # interior but tiny
        v = jnp.ones((4,), jnp.float32)
        j = self.trace(lambda a, v: jnp.einsum("ikj,k->ij", a, v), small, v)
        assert "JX002" not in _rules(self.analyze(j, "t"))

    def test_jx003_fires_on_materialized_broadcast(self):
        jnp = self.jnp
        j = self.trace(
            lambda x: jnp.broadcast_to(x[None, :], (8192, 1024)) * 2.0,
            jnp.ones((1024,), jnp.float32))
        assert "JX003" in _rules(self.analyze(j, "t"))

    def test_jx003_silent_under_threshold(self):
        jnp = self.jnp
        j = self.trace(
            lambda x: jnp.broadcast_to(x[None, :], (64, 1024)) * 2.0,
            jnp.ones((1024,), jnp.float32))
        assert "JX003" not in _rules(self.analyze(j, "t"))

    def test_jx004_fires_on_debug_callback(self):
        jax, jnp = self.jax, self.jnp

        def f(x):
            jax.debug.print("x {}", x)
            return x * 2

        j = self.trace(f, jnp.ones((4,), jnp.float32))
        assert "JX004" in _rules(self.analyze(j, "t"))

    def test_jx004_silent_on_clean_program(self):
        jnp = self.jnp
        j = self.trace(lambda x: x * 2, jnp.ones((4,), jnp.float32))
        assert "JX004" not in _rules(self.analyze(j, "t"))

    def test_jx005_fires_on_unconsumed_donation(self):
        jnp = self.jnp
        fs = self.donation(lambda a, b: (b * 2.0,),
                           (jnp.ones((8, 8)), jnp.ones((4,))), (0,), "t")
        assert _rules(fs) == ["JX005"]

    def test_jx005_silent_when_donation_aliases(self):
        jnp = self.jnp
        fs = self.donation(lambda a, b: (a + 1.0, b.sum()),
                           (jnp.ones((8, 8)), jnp.ones((4,))), (0,), "t")
        assert fs == []

    def test_jx006_fires_on_const_bloat(self):
        jnp = self.jnp
        c = jnp.ones((512, 1024), jnp.float32)  # 2 MiB closed-over
        j = self.trace(lambda x: x + c, jnp.ones((1024,), jnp.float32))
        assert "JX006" in _rules(self.analyze(j, "t"))

    def test_jx006_silent_on_small_consts(self):
        jnp = self.jnp
        c = jnp.ones((16,), jnp.float32)
        j = self.trace(lambda x: x + c, jnp.ones((16,), jnp.float32))
        assert "JX006" not in _rules(self.analyze(j, "t"))


class TestOpDtypeTrace:
    def test_tr001_fires_on_promotion_and_respects_black(self):
        import jax.numpy as jnp

        from paddle_tpu.analysis.jaxpr_checks import OpDtypeTrace

        tr = OpDtypeTrace()
        f32, f64, bf16 = jnp.float32, jnp.float64, jnp.bfloat16
        # f64 out of f32 in: always a leak
        tr.records.append(("add", (f32, f32), (f64,)))
        # black op holding fp32 from bf16: by design
        tr.records.append(("layer_norm", (bf16,), (f32,)))
        # passthrough op promoting bf16 -> f32: a leak
        tr.records.append(("multiply", (bf16, bf16), (f32,)))
        # grad mirror: reported at the forward op only
        tr.records.append(("add_grad", (f32,), (f64,)))
        fs = tr.findings("fixture")
        assert sorted(f.detail for f in fs) == ["add", "multiply"]
        assert all(f.rule == "TR001" for f in fs)

    def test_tr001_silent_on_clean_model(self):
        from paddle_tpu.analysis.targets import analyze_gpt_eager

        assert analyze_gpt_eager() == []

    def test_hook_records_real_dispatch(self):
        import paddle_tpu as paddle
        from paddle_tpu.analysis.jaxpr_checks import OpDtypeTrace

        with OpDtypeTrace() as tr:
            a = paddle.to_tensor(np.ones((2, 2), np.float32))
            (a @ a).sum()
        names = [r[0] for r in tr.records]
        assert "matmul" in names and "sum" in names

    def test_hook_sees_inputs_under_saved_tensors_hooks(self):
        """Regression: the saved-tensors-hooks path nulls the diff leaves
        (unpin) before dispatch returns; input dtypes must be captured
        BEFORE that or TR001 loses exactly the float inputs."""
        import jax.numpy as jnp

        import paddle_tpu as paddle
        from paddle_tpu.analysis.jaxpr_checks import OpDtypeTrace
        from paddle_tpu.autograd import saved_tensors_hooks

        a = paddle.to_tensor(np.ones((2, 2), np.float32))
        a.stop_gradient = False
        with OpDtypeTrace() as tr:
            with saved_tensors_hooks(lambda t: t, lambda t: t):
                (a @ a).sum()
        mm = [r for r in tr.records if r[0] == "matmul"]
        assert mm and list(mm[0][1]) == [jnp.float32, jnp.float32], mm


# ---------------------------------------------------------------------------
# registry audit — seeded positives + the real-table negatives
# ---------------------------------------------------------------------------


class TestRegistryAudit:
    def test_ra001_fires_on_uncovered_row(self):
        from paddle_tpu.analysis.registry_audit import audit_golden_coverage
        from paddle_tpu.framework.op_registry import OP_TABLE, OpSpec

        name = "_tpulint_fixture_uncovered_op"
        OP_TABLE[name] = OpSpec(name=name)
        try:
            fs = audit_golden_coverage()
            assert name in {f.detail for f in fs}
        finally:
            del OP_TABLE[name]

    def test_ra001_clean_on_real_table(self):
        from paddle_tpu.analysis.registry_audit import audit_golden_coverage

        assert audit_golden_coverage() == []

    def test_ra002_fires_on_f64_spec(self, monkeypatch):
        from paddle_tpu.analysis.registry_audit import (audit_amp_dtype,
                                                        load_golden_module)

        import jax.numpy as jnp

        from paddle_tpu.tensor.tensor import Tensor

        mod = load_golden_module()
        bad = mod.Spec(
            fn=lambda x: Tensor(jnp.asarray(x).astype(jnp.float64)),
            builder=lambda rng: [rng.randn(4, 4).astype(np.float32)])
        monkeypatch.setitem(mod.SPECS, "abs", bad)
        fs = audit_amp_dtype(ops=["abs"])
        assert [f.detail for f in fs] == ["abs"] and fs[0].rule == "RA002"

    def test_ra002_clean_on_real_specs(self):
        from paddle_tpu.analysis.registry_audit import audit_amp_dtype

        assert audit_amp_dtype() == []

    def test_ra003_fires_on_flopless_white_op(self):
        from paddle_tpu.analysis.registry_audit import audit_flops
        from paddle_tpu.framework.op_registry import OP_TABLE, OpSpec

        name = "_tpulint_fixture_mxu_op"
        OP_TABLE[name] = OpSpec(name=name, amp="white")
        try:
            fs = audit_flops()
            assert name in {f.detail for f in fs}
        finally:
            del OP_TABLE[name]

    def test_ra003_every_mxu_op_has_flops(self):
        """Regression lock (round-8 satellite): the 14 amp-white rows that
        were invisible to MFU accounting now all carry a flops_fn."""
        from paddle_tpu.analysis.registry_audit import audit_flops

        assert audit_flops() == []


class TestNewFlopsFns:
    """The flops fns the RA003 burn-down added compute the analytic MACs."""

    def test_gemm_family(self):
        from paddle_tpu.utils.flops import flops

        assert flops("mm", {"X": [[4, 8]], "Y": [[8, 16]]}, {}) == 2 * 4 * 8 * 16
        assert flops("bmm", {"X": [[3, 4, 8]], "Y": [[3, 8, 16]]}, {}) \
            == 2 * 3 * 4 * 8 * 16
        assert flops("mv", {"X": [[4, 8]]}, {}) == 2 * 4 * 8
        assert flops("addmm", {"X": [[4, 8]], "Y": [[8, 16]]}, {}) \
            == 2 * 4 * 8 * 16 + 4 * 16
        assert flops("linear", {"x": [[2, 4, 8]], "weight": [[8, 16]]}, {}) \
            == 2 * 2 * 4 * 8 * 16 + 2 * 4 * 16
        assert flops("weight_only_linear",
                     {"x": [[2, 4, 8]], "weight": [[8, 16]]}, {}) > 0

    def test_conv_family(self):
        from paddle_tpu.utils.flops import flops

        # 1x1 conv over 8x8: 2 * n * co * ho * wo * ci * kh * kw
        n = flops("conv2d", {"Input": [[1, 3, 8, 8]],
                             "Filter": [[4, 3, 1, 1]]}, {})
        assert n == 2 * 1 * 4 * 8 * 8 * 3
        n1 = flops("conv1d", {"Input": [[1, 3, 8]], "Filter": [[4, 3, 3]]},
                   {"paddings": [1]})
        assert n1 == 2 * 1 * 4 * 8 * 3 * 3
        n3 = flops("conv3d", {"Input": [[1, 2, 4, 4, 4]],
                              "Filter": [[4, 2, 1, 1, 1]]}, {})
        assert n3 == 2 * 1 * 4 * 64 * 2
        nt = flops("conv2d_transpose", {"Input": [[1, 3, 8, 8]],
                                        "Filter": [[3, 4, 2, 2]]}, {})
        assert nt == 2 * (3 * 64) * 4 * 4

    def test_einsum_and_attention(self):
        from paddle_tpu.utils.flops import flops

        n = flops("einsum", {"Operands": [[4, 8], [8, 16]]},
                  {"equation": "ik,kj->ij"})
        assert n == 2 * 4 * 8 * 16
        # ellipsis/rank mismatch: exact 0, never a partial product
        assert flops("einsum", {"Operands": [[2, 3, 4, 8], [8, 16]]},
                     {"equation": "...ik,kj->...ij"}) == 0
        q = [[2, 16, 4, 32]]  # b, s, h, d
        n = flops("scaled_dot_product_attention", {"q": q, "k": q},
                  {"is_causal": False})
        assert n == 4 * 2 * 4 * 16 * 16 * 32
        assert flops("flash_attn_unpadded", {"q": q, "k": q},
                     {"causal": True}) == n // 2

    def test_flash_unpadded_packed_3d_shapes(self):
        """The op's REAL input layout ([total_tokens, H, D] packed varlen)
        must produce non-zero FLOPs — a 0 here is invisible-to-MFU, the
        exact hazard RA003 gates."""
        from paddle_tpu.utils.flops import flops

        q3 = {"q": [[64, 4, 32]], "k": [[64, 4, 32]]}  # T, h, d
        n = flops("flash_attn_unpadded", q3, {"max_seqlen_k": 16})
        assert n == 4 * 1 * 4 * 64 * 16 * 32
        # no max_seqlen attr: packed batch treated as one sequence
        assert flops("flash_attn_unpadded", q3, {}) == 4 * 1 * 4 * 64 * 64 * 32


# ---------------------------------------------------------------------------
# bench schema (BL001)
# ---------------------------------------------------------------------------


class TestBenchSchema:
    def test_validate_good_lines(self):
        good = [
            {"metric": "m", "value": 1.5, "unit": "tokens/s"},
            {"metric": "m", "value": 0, "unit": "tokens/s",
             "vs_baseline": 0.0, "error": "backend_unavailable"},
            {"metric": "m", "value": 3, "unit": "x",
             "anchor_tflops": 123.4},
        ]
        for obj in good:
            assert bench_schema.validate_line(obj) == [], obj

    def test_validate_bad_lines(self):
        bad = [
            {"value": 1, "unit": "x"},                      # no metric
            {"metric": "m", "unit": "x"},                   # no value
            {"metric": "m", "value": float("nan"), "unit": "x"},
            {"metric": "m", "value": True, "unit": "x"},    # bool value
            {"metric": "m", "value": 1, "unit": ""},        # empty unit
            {"metric": "m", "value": 1, "unit": "x",
             "vs_baseline": "0.57"},                        # stringly number
            ["not", "an", "object"],
        ]
        for obj in bad:
            assert bench_schema.validate_line(obj), obj

    def test_checked_line_raises_loudly(self):
        with pytest.raises(ValueError, match="malformed bench line"):
            bench_schema.checked_line({"metric": "m", "unit": "x"})
        out = bench_schema.checked_line(
            {"metric": "m", "value": 1.0, "unit": "x"})
        assert json.loads(out)["value"] == 1.0

    def test_telemetry_subobject_round15(self):
        """The telemetry snapshot riding bench lines is schema-gated:
        flat {str: finite number} only."""
        base = {"metric": "m", "value": 1.0, "unit": "x"}
        good = dict(base, telemetry={"serving_steps": 12,
                                     "kv_pages_free": 3.0,
                                     "serving_ttft_ms_p50": 1.25})
        assert bench_schema.validate_line(good) == []
        bad = [
            dict(base, telemetry={}),                       # empty
            dict(base, telemetry=[1, 2]),                   # not an object
            dict(base, telemetry={"a": float("nan")}),      # non-finite
            dict(base, telemetry={"a": "12"}),              # stringly
            dict(base, telemetry={"a": True}),              # bool
            dict(base, telemetry={"": 1.0}),                # empty key
            dict(base, telemetry={"a": {"nested": 1}}),     # not flat
        ]
        for obj in bad:
            assert bench_schema.validate_line(obj), obj
        # a live registry snapshot passes the gate end to end
        from paddle_tpu.observability import MetricsRegistry

        reg = MetricsRegistry()
        reg.counter("steps").inc(4)
        reg.histogram("lat", buckets=(1, 10)).observe(2.0)
        line = dict(base, telemetry=reg.snapshot_flat())
        assert bench_schema.validate_line(line) == []
        json.loads(bench_schema.checked_line(line))

    def test_lint_artifacts_flags_malformed_tail_line(self, tmp_path):
        art = {"n": 1, "cmd": "python bench.py", "rc": 0,
               "tail": 'noise\n{"metric": "m", "value": "oops", '
                       '"unit": "tokens/s"}\n'}
        (tmp_path / "BENCH_r99.json").write_text(json.dumps(art))
        fs = bench_schema.lint_artifacts(root=str(tmp_path))
        assert [f.rule for f in fs] == ["BL001"]

    def test_lint_artifacts_clean_on_good_tail(self, tmp_path):
        art = {"n": 1, "cmd": "python bench.py", "rc": 0,
               "tail": 'WARNING: noise\n{"metric": "m", "value": 1.0, '
                       '"unit": "tokens/s", "vs_baseline": 0.5}\n'}
        (tmp_path / "BENCH_r99.json").write_text(json.dumps(art))
        assert bench_schema.lint_artifacts(root=str(tmp_path)) == []

    def test_checked_in_artifacts_clean(self):
        assert bench_schema.lint_artifacts() == []


# ---------------------------------------------------------------------------
# regression locks for the round-8 hazard fixes
# ---------------------------------------------------------------------------


class TestHazardRegressions:
    def test_autotune_harnesses_split_their_keys(self):
        """Round-8 fix: flash/paged autotune drew q/k/v from ONE key —
        identical streams degenerating the softmax the sweep times. The
        harness files must stay AL001-clean."""
        for rel in ("paddle_tpu/ops/pallas/flash_attention.py",
                    "paddle_tpu/ops/pallas/paged_attention.py",
                    "paddle_tpu/ops/pallas/fused_mlp.py"):
            fs = astlint.lint_file(os.path.join(REPO, rel), REPO)
            assert [f for f in fs if f.rule == "AL001"] == [], rel

    def test_unified_step_jit_is_clean_and_donates(self):
        """The round-9 unified serving step: jaxpr walk + donation audit
        of the K/V page pools come back with ZERO findings (the baseline
        stays empty)."""
        from paddle_tpu.analysis.targets import analyze_serving_unified

        assert analyze_serving_unified() == []

    def test_serving_quant_jits_are_clean_and_donate(self):
        """The round-10 quantized serving step (int8-weight/int8-KV
        unified step): jaxpr walk — incl. JX001,
        so per-group scales can never widen the compute to f64 — and the
        donation audit of pools AND scale planes come back with ZERO
        findings (the baseline stays empty)."""
        from paddle_tpu.analysis.targets import analyze_serving_quant

        assert analyze_serving_quant() == []

    def test_serving_spec_step_is_clean_and_donates(self):
        """The round-12 speculative unified step (fp + int8w/int8kv):
        jaxpr walk of the verify/accept program and the JX005 donation
        audit over the pools and scale planes at their spec-shifted
        argument positions come back with ZERO findings (the baseline
        stays empty)."""
        from paddle_tpu.analysis.targets import analyze_serving_spec

        assert analyze_serving_spec() == []

    def test_serving_async_step_is_clean_and_donates(self):
        """The round-13 feedback-coupled unified step (a LIVE feedback
        lane reading prev_toks + the on-device sample-key fold): jaxpr
        walk and the JX005 donation audit at the feedback-shifted pool
        positions come back with ZERO findings — a dispatch-ahead step
        that stopped aliasing its pools would double-buffer the largest
        serving allocation exactly when two steps are in flight."""
        from paddle_tpu.analysis.targets import analyze_serving_async

        assert analyze_serving_async() == []

    def test_serving_tiered_restore_is_clean_and_donates(self):
        """The round-21 batched restore scatter (the ONE jitted landing
        a host-tier restore round or batched transfer tick issues per
        K/V/scale plane): jaxpr walk over all three plane geometries
        (5D fp pool, 5D int8 pool, 4D fp32 scale plane) and the JX005
        donation audit of the pool argument come back with ZERO
        findings (the baseline stays empty) — an undonated restore
        would copy the whole HBM pool per plane per round, exactly the
        eager per-page cost the batched path exists to retire."""
        from paddle_tpu.analysis.targets import analyze_serving_tiered

        assert analyze_serving_tiered() == []


# ---------------------------------------------------------------------------
# AL009 — thread-discipline lint (round 23)
# ---------------------------------------------------------------------------


class TestThreadLint:
    _RACY = """
        import threading

        class Engine:
            def __init__(self):
                self._lock = threading.Lock()
                self._inflight = {}

            def submit(self, rid, req):
                with self._lock:
                    self._inflight[rid] = req

            def cancel(self, rid):
                self._inflight.pop(rid)
    """

    def _tlint(self, src):
        from paddle_tpu.analysis import threadlint

        return threadlint.lint_source(textwrap.dedent(src), "fixture.py")

    def test_al009_fires_on_unlocked_mutation(self):
        fs = self._tlint(self._RACY)
        assert [f.rule for f in fs] == ["AL009"]
        assert fs[0].detail == "Engine.cancel:_inflight"

    def test_al009_silent_when_every_mutation_holds_the_lock(self):
        fs = self._tlint("""
            import threading

            class Engine:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._inflight = {}

                def submit(self, rid, req):
                    with self._lock:
                        self._inflight[rid] = req

                def cancel(self, rid):
                    with self._lock:
                        self._inflight.pop(rid)
        """)
        assert fs == []

    def test_al009_exempts_init_and_designated_drivers(self):
        """__init__ precedes sharing; dispatch/reconcile/tick-named methods
        are the single-threaded loop bodies that own their state."""
        fs = self._tlint("""
            class Engine:
                def __init__(self):
                    self._q = []

                def submit(self, item):
                    with self._lock:
                        self._q.append(item)

                def _dispatch_round(self):
                    self._q.pop()

                def _watchdog_tick(self):
                    self._q = []

                def _reconcile(self):
                    self._q.extend(())
        """)
        assert fs == []

    def test_al009_pragma_suppresses_a_site(self):
        fs = self._tlint("""
            class Engine:
                def grow(self):
                    with self._lock:
                        self._n += 1

                def reset(self):
                    self._n = 0  # tpulint: disable=AL009
        """)
        assert fs == []

    def test_al009_sees_subscripts_tuples_and_mutator_calls(self):
        fs = self._tlint("""
            class Engine:
                def locked(self):
                    with self._lock:
                        self._d = {}
                        self._a = self._b = 0

                def racy(self):
                    self._d["k"] = 1
                    self._a, self._b = 1, 2
                    self._d.update({})
        """)
        assert sorted(f.detail for f in fs) == [
            "Engine.racy:_a", "Engine.racy:_b",
            "Engine.racy:_d", "Engine.racy:_d"]

    def test_repo_threaded_packages_are_al009_clean(self):
        """The satellite fix-not-baseline contract: inference/ +
        observability/ ship with zero thread-discipline findings."""
        from paddle_tpu.analysis import threadlint

        assert threadlint.lint_package() == []


# ---------------------------------------------------------------------------
# JX007 — static HBM cost model vs the bench analytic model (round 23)
# ---------------------------------------------------------------------------


class TestCostModel:
    """Synthetic serving-shaped program: params (emb replicated + a stacked
    layer scan) and two 5D KV pools, sized so every term is hand-checkable."""

    L, H, T = 2, 8, 4

    def _toy(self, carried_pools=False):
        import jax.numpy as jnp
        from jax import lax

        from paddle_tpu.analysis.jaxpr_checks import trace_callable

        L, h, t = self.L, self.H, self.T
        emb = jnp.ones((16, h), jnp.float32)
        stack = jnp.ones((L, h, h), jnp.float32)
        k_pages = jnp.ones((L, 3, 2, 4, 4), jnp.float32)  # heads*hd == h
        v_pages = jnp.ones((L, 3, 2, 4, 4), jnp.float32)

        def step(emb, stack, k_pages, v_pages):
            def body(c, w):
                return c @ w, ()

            def carrying(c, w):
                # the unified step's form: the stacked pools ride the
                # carry beside the activation and dwarf it
                x, kp, vp = c
                return (x @ w, kp.at[0, 0, 0, 0].add(x[0, :4]), vp), ()

            if carried_pools:
                (c, k_pages, v_pages), _ = lax.scan(
                    carrying, (emb[:t], k_pages, v_pages), stack)
            else:
                c, _ = lax.scan(body, emb[:t], stack)
            return c.sum() + k_pages.sum() + v_pages.sum()

        closed = trace_callable(step, emb, stack, k_pages, v_pages)
        return closed, (k_pages, v_pages)

    def _geom(self, **kw):
        from paddle_tpu.analysis.cost_model import ServingGeometry

        base = dict(layer_weight_bytes=self.L * self.H * self.H * 4,
                    replicated_weight_bytes=16 * self.H * 4,
                    num_layers=self.L, kv_heads=2, head_dim=4,
                    kv_itemsize=4, kv_quantized=False, act_itemsize=4,
                    mp=1, batch=2, avg_ctx=8.0)
        base.update(kw)
        return ServingGeometry(**base)

    @pytest.mark.parametrize("carried_pools", [False, True])
    def test_static_report_matches_hand_count(self, carried_pools):
        from paddle_tpu.analysis import cost_model

        closed, pools = self._toy(carried_pools)
        rep = cost_model.static_hbm_report(closed, 2, pools,
                                           batch=2, avg_ctx=8.0)
        assert rep["num_layers"] == self.L and rep["hidden"] == self.H
        # wb = (layer/1 + repl)/2; kv = 2 pools x L*ctx*heads*hd*4;
        # act = 2 roundtrips x L x 17h x 4
        assert rep["weight_bytes_per_token"] == (512 + 512) // 2
        assert rep["kv_bytes_per_token"] == 1024
        assert rep["act_bytes_per_token"] == 2 * self.L * 17 * self.H * 4
        assert rep["flow_bytes_upper_bound"] > 0

    def test_jx007_silent_when_models_agree(self):
        from paddle_tpu.analysis import cost_model

        closed, pools = self._toy()
        fs = cost_model.check_hbm_model(closed, 2, pools, self._geom(),
                                        0.02, "t")
        assert fs == []

    def test_jx007_fires_on_drift_and_layer_count(self):
        from paddle_tpu.analysis import cost_model

        closed, pools = self._toy()
        # geometry claims 3 layers: scan-length mismatch AND hbm drift
        fs = cost_model.check_hbm_model(closed, 2, pools,
                                        self._geom(num_layers=3), 0.02, "t")
        details = {f.detail for f in fs}
        assert {"layer-scan-length", "hbm-drift"} <= details
        assert all(f.rule == "JX007" for f in fs)

    def test_jx007_underivable_without_a_layer_scan(self):
        import jax.numpy as jnp

        from paddle_tpu.analysis import cost_model
        from paddle_tpu.analysis.jaxpr_checks import trace_callable

        closed = trace_callable(lambda x: x * 2.0,
                                jnp.ones((4,), jnp.float32))
        fs = cost_model.check_hbm_model(closed, 0, (), self._geom(),
                                        0.02, "t")
        assert [f.detail for f in fs] == ["no-layer-scan"]


# ---------------------------------------------------------------------------
# JX008 — pallas VMEM footprints (round 23)
# ---------------------------------------------------------------------------


class TestVmem:
    def test_jx008_budget_gate_on_pallas_footprint(self):
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        from paddle_tpu.analysis import vmem
        from paddle_tpu.analysis.jaxpr_checks import trace_callable

        def kern(x_ref, o_ref):
            o_ref[...] = x_ref[...] * 2.0

        f = pl.pallas_call(
            kern, out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32))
        closed = trace_callable(f, jnp.ones((8, 128), jnp.float32))
        [fp] = vmem.pallas_footprints(closed)
        # in + out blocks (full array, 4 KiB each), double-buffered
        want = vmem.LIVE_BUFFERS * 2 * 8 * 128 * 4
        assert fp["vmem_bytes"] == want
        assert vmem.check_vmem(closed, want, "t") == []
        fs = vmem.check_vmem(closed, want - 1, "t")
        assert [f.rule for f in fs] == ["JX008"]
        assert fs[0].detail.startswith("vmem-budget:")


# ---------------------------------------------------------------------------
# JX009 — collective inventory + compiled-HLO wire audit (round 23)
# ---------------------------------------------------------------------------


class TestCollectivesAudit:
    def test_inventory_counts_with_scan_multiplier(self):
        import jax
        import jax.numpy as jnp
        from jax import lax

        from paddle_tpu.analysis import collectives_audit as ca

        def f(x):
            def body(c, _):
                return lax.psum(c, "i"), ()

            c, _ = lax.scan(body, x, None, length=3)
            return c

        closed = jax.make_jaxpr(f, axis_env=[("i", 2)])(
            jnp.ones((4,), jnp.float32))
        assert ca.collective_inventory(closed) == {"psum:float32": 3}
        assert ca.check_collectives(closed, {"psum:float32": 3}, "t") == []
        fs = ca.check_collectives(closed, {}, "t")
        assert [f.rule for f in fs] == ["JX009"]
        assert fs[0].detail == "psum:float32"

    def test_contract_misses_and_dtype_changes_both_diverge(self):
        import jax
        import jax.numpy as jnp
        from jax import lax

        from paddle_tpu.analysis import collectives_audit as ca

        closed = jax.make_jaxpr(
            lambda x: lax.psum(x, "i"), axis_env=[("i", 2)])(
            jnp.ones((4,), jnp.float32))
        # contracted-but-absent entries diverge too (a REMOVED psum is as
        # suspicious as an added one)
        fs = ca.check_collectives(
            closed, {"psum:float32": 1, "all_gather:float32": 1}, "t")
        assert [f.detail for f in fs] == ["all_gather:float32"]

    def test_hlo_contract_flags_fp_traffic_and_missing_s8(self):
        from paddle_tpu.analysis import collectives_audit as ca

        bad = [{"kind": "all-reduce", "dtype": "f32", "elems": 1 << 20}]
        fs = ca.check_hlo_collectives(bad, "t")
        assert sorted(f.detail for f in fs) == [
            "hlo-fp-all-reduce:f32", "hlo-no-s8-collective"]
        ok = [{"kind": "all-reduce", "dtype": "f32", "elems": 1},
              {"kind": "all-gather", "dtype": "s8", "elems": 1 << 20}]
        assert ca.check_hlo_collectives(ok, "t") == []

    def test_hlo_collectives_reads_the_compiled_program(self):
        import jax
        import jax.numpy as jnp
        import numpy as onp
        from jax import lax
        from jax.experimental.shard_map import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        from paddle_tpu.analysis import collectives_audit as ca

        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 (forced host) devices")
        mesh = Mesh(onp.array(jax.devices()[:2]), ("dp",))
        f = shard_map(lambda x: lax.psum(x, "dp"), mesh=mesh,
                      in_specs=P("dp"), out_specs=P())
        entries = ca.hlo_collectives(f, (jnp.ones((4, 8), jnp.float32),),
                                     mesh=mesh)
        assert any(e["kind"] == "all-reduce" and e["dtype"] == "f32"
                   and e["elems"] == 16 for e in entries), entries


# ---------------------------------------------------------------------------
# contracts table + the tpulint CLI (round 23)
# ---------------------------------------------------------------------------


class TestContractsAndCLI:
    def test_unkeyed_target_certifies_vacuously(self):
        from paddle_tpu.analysis.contracts import cost_certify

        assert cost_certify("no-such-target", None) == []

    def test_contract_keys_name_real_targets(self):
        """A typo'd contract key would certify NOTHING silently — every key
        must extend a registered flagship target name (the --target
        baseline-ownership prefix rule depends on this too)."""
        from paddle_tpu.analysis.contracts import CONTRACTS
        from paddle_tpu.analysis.targets import TARGETS

        for key in CONTRACTS:
            assert any(key == name or key.startswith(name + "-")
                       for name in TARGETS), key

    def test_perturbed_contract_exits_2(self, monkeypatch, capsys):
        """The satellite drift gate: deliberately break a committed
        expectation -> the gate exits 2 with the JX009 divergence."""
        from paddle_tpu.analysis import __main__ as cli
        from paddle_tpu.analysis import contracts

        monkeypatch.setitem(
            contracts.CONTRACTS, "serving-tiered-restore-fp",
            contracts.CostContract(collectives={"psum:float32": 99}))
        rc = cli.main(["--target", "serving-tiered", "--json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 2
        assert any(f["rule"] == "JX009"
                   and f["target"] == "serving-tiered-restore-fp"
                   for f in out["new"])

    def test_target_selector_runs_clean_and_scopes_the_trace(
            self, capsys):
        """--target runs ONLY the named flagships' trace analyses (and
        their cost certification) and the repo ships them clean."""
        from paddle_tpu.analysis import __main__ as cli

        rc = cli.main(["--target", "serving-tiered", "--json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert out["passes"] == ["trace"] and out["new"] == []

    def test_list_targets_prints_the_registry(self, capsys):
        from paddle_tpu.analysis import __main__ as cli
        from paddle_tpu.analysis.targets import TARGETS

        assert cli.main(["--list-targets"]) == 0
        assert capsys.readouterr().out.split() == list(TARGETS)

    def test_unknown_target_is_a_usage_error(self):
        from paddle_tpu.analysis import __main__ as cli

        with pytest.raises(SystemExit):
            cli.main(["--target", "no-such-flagship"])

    def test_target_forbids_write_baseline(self):
        from paddle_tpu.analysis import __main__ as cli

        with pytest.raises(SystemExit):
            cli.main(["--target", "serving-tiered", "--write-baseline"])


# ---------------------------------------------------------------------------
# baseline fingerprint robustness (round-23 satellite regression)
# ---------------------------------------------------------------------------


class TestFingerprintRobustness:
    _SRC = textwrap.dedent("""
        import jax

        def bench():
            key = jax.random.PRNGKey(0)
            q = jax.random.normal(key, (8, 8))
            k = jax.random.normal(key, (8, 8))
            return q, k
    """)

    def test_comment_shift_stays_suppressed_site_change_refires(self):
        """The fingerprint excludes line numbers and prose: adding a
        comment ABOVE a baselined site must keep it suppressed; changing
        the site itself (a different enclosing function) must re-fire."""
        fs = astlint.lint_source(self._SRC, "fixture.py")
        baselined = [f for f in fs if f.rule == "AL001"]
        assert baselined, "fixture must fire AL001 to baseline it"
        base = {f.fingerprint for f in baselined}

        shifted = "# new leading comment\n# another\n" + self._SRC
        fs2 = astlint.lint_source(shifted, "fixture.py")
        assert [f for f in fs2 if f.rule == "AL001"]  # still fires...
        new, accepted, fixed = diff_against_baseline(fs2, base)
        assert new == [] and fixed == []              # ...all suppressed
        assert {f.fingerprint for f in accepted} == base
        assert any(f.line != b.line
                   for f, b in zip(sorted(accepted, key=str),
                                   sorted(baselined, key=str)))

        moved = self._SRC.replace("def bench():", "def bench_two():")
        fs3 = astlint.lint_source(moved, "fixture.py")
        new, _accepted, fixed = diff_against_baseline(fs3, base)
        assert new and fixed == sorted(base)          # a DIFFERENT site


# ---------------------------------------------------------------------------
# the gate: the repo itself, against the checked-in baseline
# ---------------------------------------------------------------------------


class TestRepoGate:
    def test_rule_catalog_documented(self):
        from paddle_tpu.analysis import RULES
        from paddle_tpu.analysis import (astlint, bench_schema,  # noqa: F401
                                         collectives_audit, cost_model,
                                         jaxpr_checks, registry_audit,
                                         threadlint, vmem)

        for rid in ("AL001", "AL002", "AL003", "AL004", "AL005", "AL006",
                    "AL007", "AL009",
                    "JX001", "JX002", "JX003", "JX004", "JX005", "JX006",
                    "JX007", "JX008", "JX009",
                    "TR001", "RA001", "RA002", "RA003", "BL001"):
            assert rid in RULES, f"rule {rid} missing from the catalog"

    def test_acceptance_targets_are_cost_contracted(self):
        """The round-23 acceptance names the serving steps explicitly:
        they must carry a REAL hbm-drift contract (the clean-run halves
        live in the hazard-regression tests — the analyze fns now run
        cost_certify inline) and keep the kernel VMEM budget armed."""
        from paddle_tpu.analysis.contracts import CONTRACTS

        for key in ("serving-unified-step", "serving-quant-unified-step"):
            assert CONTRACTS[key].hbm_tolerance is not None, key
            assert (CONTRACTS[key].vmem_budget_bytes or 0) > 0, key

    def test_repo_is_clean_against_baseline(self):
        """The CI gate: every pass over the real tree + flagship callables;
        any finding not in analysis/baseline.json fails tier-1."""
        findings = run_all(PASSES)
        new, _accepted, _fixed = diff_against_baseline(
            findings, load_baseline())
        assert not new, (
            "non-baselined tpulint findings (fix them, or review + "
            "python -m paddle_tpu.analysis --write-baseline):\n"
            + "\n".join(f"  {f}" for f in new))
