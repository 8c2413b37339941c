"""Compile-contract auditor + JAX/async hygiene + interprocedural
dataflow (fleetflow_tpu/analysis).

Two proof obligations, mirroring the chaos-invariant canary discipline:

  1. the UNMODIFIED tree passes: the full audit over the registered
     hot-path kernels reports zero violations and zero drift against the
     pinned contract file (tests/goldens/compile_contract.json), the
     hygiene rules find nothing in solver/ or cp/, and the FJ007+
     dataflow rules find nothing in the whole package beyond the
     reviewed baseline (audit_baseline.json).

  2. every contract class has a failing world: a deliberately-broken
     kernel variant — donation dropped, host callback inserted, output
     sharding lost, static argument added — MUST fail the auditor, and
     every dataflow rule has a canary fixture (tests/fixtures/dataflow/)
     that MUST produce exactly its finding. An auditor whose canaries
     pass is not checking anything.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fleetflow_tpu.analysis.auditor import (audit_case, audit_kernels,
                                            contract_diff,
                                            default_contract_path,
                                            render_contract)
from fleetflow_tpu.analysis.baseline import (Baseline, apply_baseline,
                                             load_baseline, write_baseline)
from fleetflow_tpu.analysis.dataflow import (dataflow_lint_paths,
                                             dataflow_lint_source)
from fleetflow_tpu.analysis.hygiene import (hygiene_lint_paths,
                                            hygiene_lint_source)
from fleetflow_tpu.analysis.jitspec import extract_jit_decl
from fleetflow_tpu.lint import Severity
from fleetflow_tpu.solver.contracts import (KernelCase, KernelContract,
                                            hot_path_kernels)

PKG = os.path.dirname(os.path.abspath(
    __import__("fleetflow_tpu").__file__))


def _need_devices(n):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices, have {len(jax.devices())}")


# --------------------------------------------------------------------------
# the healthy tree: full audit == pinned contract
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def report():
    _need_devices(8)
    return audit_kernels()


class TestContractHolds:
    def test_no_intrinsic_violations(self, report):
        assert report.violations == []
        assert report.skipped == []

    def test_matches_pinned_contract(self, report):
        with open(default_contract_path(), encoding="utf-8") as f:
            pinned = json.load(f)
        assert contract_diff(report, pinned) == []

    def test_render_roundtrip(self, report):
        doc = json.loads(render_contract(report))
        assert contract_diff(report, doc) == []

    def test_every_registered_kernel_audited(self, report):
        assert set(report["kernels"]) == {
            c.name for c in hot_path_kernels()}
        for entry in report["kernels"].values():
            assert len(entry["tiers"]) >= 2   # representative tiers

    def test_merge_kernels_alias_their_planes(self, report):
        """The perf story itself: every (S, .) plane and the assignment
        of both merge kernels must be reused in place."""
        for name in ("resident.merge", "sharded.merge"):
            for tier, rec in report["kernels"][name]["tiers"].items():
                for leaf in ("prob.demand", "prob.eligible", "assignment"):
                    assert leaf in rec["aliased"], (name, tier, leaf)


# --------------------------------------------------------------------------
# canaries: one broken world per contract class
# --------------------------------------------------------------------------

def _case(fn, args, kwargs=None, arg_names=("x", "y"),
          out_shardings=None):
    return KernelCase(tier="8x4", fn=fn, args=args, kwargs=kwargs or {},
                      arg_names=arg_names, out_shardings=out_shardings)


class TestCanaries:
    def test_dropped_donation_fails(self):
        """The same update-in-place shape as the merge kernel, jitted
        WITHOUT donate_argnums: the must-alias check has to fire."""
        def merge(x, rows):
            return x.at[rows].set(0.0)

        good = jax.jit(merge, donate_argnums=(0,))
        bad = jax.jit(merge)
        contract = KernelContract(
            name="canary.merge", module="", qualname="",
            cases=lambda: [], must_alias=("x",))
        args = (jnp.ones((16, 3)), jnp.arange(4))
        rec, violations = audit_case(contract, _case(good, args,
                                                     arg_names=("x",
                                                                "rows")))
        assert violations == [] and rec["aliased"] == ["x"]
        rec, violations = audit_case(contract, _case(bad, args,
                                                     arg_names=("x",
                                                                "rows")))
        assert rec["donated"] == [] and rec["aliased"] == []
        assert any("not aliased" in v and "x" in v for v in violations)

    def test_dense_plane_fails_packed_contract(self, monkeypatch):
        """Deliberate breakage of the packed-plane layout: a resident
        staging carrying a dense bool eligibility plane (FLEET_PACKED=0)
        and a materialized zero preference plane must trip the intrinsic
        packed-plane checks — an f32/bool (S, N) plane can never silently
        reappear in a hot-path executable."""
        monkeypatch.setenv("FLEET_PACKED", "0")
        from fleetflow_tpu.lower import synthetic_problem
        from fleetflow_tpu.solver.contracts import (_MERGE_ARG_NAMES,
                                                    _rich_delta)
        from fleetflow_tpu.solver.resident import ResidentProblem

        pt = synthetic_problem(60, 12, seed=0, port_fraction=0.3,
                               volume_fraction=0.2)
        rp = ResidentProblem(pt)
        rp.adopt_host(np.zeros(pt.S, np.int32), pt.node_valid, warm=False)
        uploads, n_real, statics = rp.merge_inputs(pt, _rich_delta(pt))
        contract = KernelContract(
            name="canary.packed", module="", qualname="", cases=lambda: [])
        case = KernelCase(
            tier="dense", fn=rp._merge(),
            args=(rp.prob, rp.assignment, *uploads, n_real),
            kwargs=statics,
            arg_names=_MERGE_ARG_NAMES)
        rec, violations = audit_case(contract, case)
        assert rec["problem_dtypes"]["prob.eligible"] == "bool"
        assert any("bit-packed uint32" in v for v in violations)
        # dense staging also materializes the zero preference plane
        assert "prob.preferred" in rec["problem_dtypes"]
        assert any("preference plane" in v for v in violations)

    def test_host_callback_fails(self):
        """A smuggled pure_callback must trip the purity check."""
        def clean(x):
            return x * 2

        def dirty(x):
            host = jax.pure_callback(
                lambda v: np.asarray(v) * 2,
                jax.ShapeDtypeStruct(x.shape, x.dtype), x)
            return host + 1

        contract = KernelContract(name="canary.purity", module="",
                                  qualname="", cases=lambda: [])
        args = (jnp.ones((8,)),)
        _rec, violations = audit_case(
            contract, _case(jax.jit(clean), args, arg_names=("x",)))
        assert violations == []
        rec, violations = audit_case(
            contract, _case(jax.jit(dirty), args, arg_names=("x",)))
        assert rec["host_callbacks"]
        assert any("host-callback" in v for v in violations)

    def test_lost_output_sharding_fails(self):
        """Declared P('svc') output that actually compiles replicated
        (constraint dropped) must trip the sharding check."""
        _need_devices(4)
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("svc",))
        svc = NamedSharding(mesh, P("svc"))
        rep = NamedSharding(mesh, P())

        def keeps(x):
            return jax.lax.with_sharding_constraint(x * 2, svc)

        def loses(x):
            # an all-reduce style rewrite that silently de-shards
            return jax.lax.with_sharding_constraint(x * 2, rep)

        contract = KernelContract(name="canary.shard", module="",
                                  qualname="", cases=lambda: [])
        x = jax.device_put(jnp.arange(16.0), svc)
        decl = {"out": "P('svc')"}
        _rec, violations = audit_case(
            contract, _case(jax.jit(keeps), (x,), arg_names=("x",),
                            out_shardings=decl))
        assert violations == []
        rec, violations = audit_case(
            contract, _case(jax.jit(loses), (x,), arg_names=("x",),
                            out_shardings=decl))
        assert rec["output_shardings"] == {"out": "P()"}
        assert any("output sharding" in v for v in violations)

    def test_extra_static_arg_is_contract_drift(self, report):
        """Adding a recompile axis to a kernel's jit declaration must
        surface as drift against the pinned contract — simulated by
        pinning a contract missing the new axis."""
        with open(default_contract_path(), encoding="utf-8") as f:
            pinned = json.load(f)
        entry = pinned["kernels"]["refine.warm"]
        entry["static_args"] = [a for a in entry["static_args"]
                                if a != "steps"]
        drift = contract_diff(report, pinned)
        assert any("refine.warm" in d and "static args" in d
                   for d in drift)

    def test_new_static_problem_field_is_contract_drift(self, report):
        with open(default_contract_path(), encoding="utf-8") as f:
            pinned = json.load(f)
        pinned["problem_static_fields"].append("new_axis")
        drift = contract_diff(report, pinned)
        assert any("problem_static_fields" in d for d in drift)

    def test_unregistered_kernel_is_contract_drift(self, report):
        with open(default_contract_path(), encoding="utf-8") as f:
            pinned = json.load(f)
        pinned["kernels"]["ghost.kernel"] = {"static_args": [],
                                             "donated_params": [],
                                             "tiers": {}}
        drift = contract_diff(report, pinned)
        assert any("ghost.kernel" in d for d in drift)


# --------------------------------------------------------------------------
# jitspec: AST extraction is ground truth
# --------------------------------------------------------------------------

class TestJitSpec:
    def test_extracts_decorator_form(self):
        src = ('from functools import partial\nimport jax\n'
               '@partial(jax.jit, static_argnames=("b", "a"),\n'
               '         donate_argnums=(0,))\n'
               'def f(x, y, *, a, b):\n    return x\n')
        d = extract_jit_decl(src, "f")
        assert d.static_args == ["a", "b"]
        assert d.donated_params == ["x"]

    def test_extracts_call_form(self):
        src = ('import jax\n'
               'def maker():\n'
               '    def merge(prob, assignment, n):\n'
               '        return prob, assignment\n'
               '    return jax.jit(merge, donate_argnums=(0, 1),\n'
               '                   static_argnames=("n",))\n')
        d = extract_jit_decl(src, "maker.merge")
        assert d.static_args == ["n"]
        assert d.donated_params == ["assignment", "prob"]

    def test_missing_anchor_raises(self):
        with pytest.raises(LookupError):
            extract_jit_decl("def f():\n    pass\n", "g")
        with pytest.raises(LookupError):
            # found but not jitted: must fail loudly, not pass vacuously
            extract_jit_decl("def f():\n    pass\n", "f")

    @pytest.mark.parametrize("module,qualname,expect_static", [
        ("solver/resident.py", "_merge_fn.merge",
         ["has_conflict", "has_demand", "has_eligible", "has_price"]),
        ("solver/sharded.py", "anneal_sharded",
         ["block", "exchange_every", "mesh",
          "proposals_per_step", "return_stats", "return_sweeps",
          "steps", "trace_blocks"]),
    ])
    def test_real_anchors_resolve(self, module, qualname, expect_static):
        path = os.path.join(PKG, module)
        with open(path, encoding="utf-8") as f:
            d = extract_jit_decl(f.read(), qualname, path)
        assert d.static_args == expect_static


# --------------------------------------------------------------------------
# hygiene: FJ rules fire on broken worlds, stay silent on the tree
# --------------------------------------------------------------------------

_JIT_HEADER = ("import jax, os, time\nimport numpy as np\n"
               "from functools import partial\n"
               '@partial(jax.jit, static_argnames=("flag",))\n')


def _codes(src):
    return [d.code for d in hygiene_lint_source(src, "t.py")]


class TestHygieneRules:
    def test_fj001_item_in_jit(self):
        src = _JIT_HEADER + "def f(x, *, flag):\n    return x.item()\n"
        assert _codes(src) == ["FJ001"]

    def test_fj002_cast_on_tracer_but_not_static(self):
        src = _JIT_HEADER + ("def f(x, *, flag):\n"
                             "    a = float(x)\n"
                             "    b = float(flag)\n"   # static: allowed
                             "    return a + b\n")
        assert _codes(src) == ["FJ002"]

    def test_fj003_numpy_compute_but_not_dtypes(self):
        src = _JIT_HEADER + ("def f(x, *, flag):\n"
                             "    a = np.sum(x)\n"
                             "    dt = np.float32\n"   # dtype: allowed
                             "    return a\n")
        assert _codes(src) == ["FJ003"]

    def test_fj004_env_read(self):
        src = _JIT_HEADER + ("def f(x, *, flag):\n"
                             "    if os.environ.get('FLEET_X'):\n"
                             "        return x\n"
                             "    return x + int(os.getenv('Y') or 0)\n")
        assert _codes(src) == ["FJ004", "FJ004"]

    def test_fj005_blocking_in_async(self):
        src = ("import time\nasync def h(req):\n"
               "    time.sleep(1)\n    return req\n")
        assert _codes(src) == ["FJ005"]

    def test_fj005_from_import_sleep(self):
        """`from time import sleep` must be caught too — the dotted-name
        match alone can't see it."""
        src = ("from time import sleep\nasync def h(req):\n"
               "    sleep(1)\n    return req\n")
        assert _codes(src) == ["FJ005"]
        src = ("from subprocess import run\nasync def h(req):\n"
               "    run(['ls'])\n    return req\n")
        assert _codes(src) == ["FJ005"]

    def test_fj005_sync_helper_exempt(self):
        """A sync helper nested in the coroutine may block — whether to
        executor it is the CALL site's problem, and only a direct
        blocking call in the coroutine body is the hazard."""
        src = ("import time\nasync def h(req):\n"
               "    def helper():\n"
               "        time.sleep(1)\n"
               "    helper()\n    return req\n")
        assert _codes(src) == []

    def test_nested_roots_not_double_reported(self):
        """A jit root nested in a jit root (and an async def nested in
        an async def) must be scanned exactly once."""
        src = ("import jax\n"
               "@jax.jit\n"
               "def outer(x):\n"
               "    @jax.jit\n"
               "    def inner(y):\n"
               "        return y.item()\n"
               "    return inner(x)\n")
        assert _codes(src) == ["FJ001"]
        src = ("import requests\n"
               "async def outer(req):\n"
               "    async def inner():\n"
               "        requests.get('http://x')\n"
               "    await inner()\n")
        assert _codes(src) == ["FJ005"]

    def test_fj006_await_under_lock(self):
        src = ("async def h(self):\n"
               "    with self._lock:\n"
               "        await self.flush()\n")
        assert _codes(src) == ["FJ006"]

    def test_nested_defs_inside_jit_are_traced(self):
        src = ("import jax\nimport numpy as np\n"
               "def outer():\n"
               "    def body(x):\n"
               "        return np.square(x)\n"
               "    return jax.jit(body)\n")
        assert _codes(src) == ["FJ003"]

    def test_host_callback_subtree_exempt(self):
        src = ("import jax\nimport numpy as np\n"
               "@jax.jit\n"
               "def f(x):\n"
               "    def cb(v):\n"
               "        return np.asarray(v) * 2\n"
               "    return jax.pure_callback(\n"
               "        cb, jax.ShapeDtypeStruct(x.shape, x.dtype), x)\n")
        assert _codes(src) == []

    def test_noqa_suppresses(self):
        src = _JIT_HEADER + ("def f(x, *, flag):\n"
                             "    return x.item()  # noqa: FJ001\n")
        assert _codes(src) == []

    def test_plain_functions_not_traced(self):
        src = ("import numpy as np\nimport os\n"
               "def f(x):\n"
               "    return np.sum(x) + int(os.getenv('Y') or 0)\n")
        assert _codes(src) == []

    def test_syntax_error_returns_nothing(self):
        assert hygiene_lint_source("def f(:\n", "t.py") == []

    def test_severities_ride_lint_machinery(self):
        src = _JIT_HEADER + "def f(x, *, flag):\n    return x.item()\n"
        d = hygiene_lint_source(src, "t.py")[0]
        assert d.severity is Severity.ERROR
        assert d.file == "t.py" and d.line == 6
        assert "t.py:6:" in d.format()


class TestHygieneTreeClean:
    def test_solver_and_cp_are_clean(self):
        """The production tree holds its own bar (anything here is a real
        finding: fix it or `# noqa: FJ00x` it with a reason)."""
        diags = hygiene_lint_paths(
            [os.path.join(PKG, "solver"), os.path.join(PKG, "cp")])
        assert diags == [], "\n".join(d.format() for d in diags)


# --------------------------------------------------------------------------
# dataflow: FJ007+ interprocedural rules — every canary fails, the clean
# idioms pass, the production tree stays clean modulo the reviewed baseline
# --------------------------------------------------------------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DF_FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "fixtures", "dataflow")


def _df_fixture(name):
    with open(os.path.join(DF_FIXTURES, name), encoding="utf-8") as f:
        return dataflow_lint_source(f.read(), name)


class TestDataflowCanaries:
    """One deliberately-broken world per rule (tests/fixtures/dataflow/):
    an analyzer whose canaries pass is not checking anything. Each
    fixture documents its own hazard; here we pin rule code, anchoring
    function, and the load-bearing bits of the message."""

    def test_fj007_direct_use_after_donate(self):
        diags = _df_fixture("fj007.py")
        assert [d.code for d in diags] == ["FJ007"]
        d = diags[0]
        assert d.function == "dispatch" and d.severity is Severity.ERROR
        assert "`a`" in d.message and "donated" in d.message

    def test_fj007_pr14_device_get_view(self):
        """The PR 14 bug class end to end: factory dispatch resolution
        (self._merge() -> _merge_fn() -> jax.jit(..., donate_argnums)),
        donated-slot discovery on the class, and the retained
        device_get view flagged as dead after apply_delta()."""
        diags = _df_fixture("fj007_pr14.py")
        assert [d.code for d in diags] == ["FJ007"]
        d = diags[0]
        assert d.function == "solve"
        assert "view" in d.message
        assert "resident.assignment" in d.message

    def test_fj008_traced_bool_one_call_deep(self):
        diags = _df_fixture("fj008.py")
        assert [d.code for d in diags] == ["FJ008"]
        d = diags[0]
        assert d.function == "_decide" and d.severity is Severity.ERROR
        assert "`x`" in d.message and "step" in d.message

    def test_fj009_env_read_into_static_arg(self):
        diags = _df_fixture("fj009.py")
        assert [d.code for d in diags] == ["FJ009"]
        d = diags[0]
        # reported at the dispatch site, WARNING severity (intentional
        # per-call knobs exist — the baseline owns those)
        assert d.function == "solve" and d.severity is Severity.WARNING
        assert "`nb`" in d.message and "kernel" in d.message

    def test_fj010_deep_host_sync_under_hot_root(self):
        diags = _df_fixture("fj010.py")
        assert [d.code for d in diags] == ["FJ010"]
        d = diags[0]
        assert d.function == "_stat" and d.severity is Severity.ERROR
        assert "hot" in d.message

    def test_fj011_global_write_in_traced_code(self):
        diags = _df_fixture("fj011.py")
        assert [d.code for d in diags] == ["FJ011"]
        d = diags[0]
        assert d.function == "_bump" and d.severity is Severity.ERROR
        assert "_CALLS" in d.message and "step" in d.message

    def test_clean_idioms_pass(self):
        """The sanctioned counterparts — np.array(..., copy=True) before
        the donating call, same-statement rebinding of donated slots,
        `is None` identity checks on traced values — must NOT fire."""
        assert _df_fixture("clean.py") == []

    def test_noqa_suppresses_dataflow(self):
        src = ("import jax\n"
               "def _decide(x):\n"
               "    if x > 0:  # noqa: FJ008\n"
               "        return 1\n"
               "    return 0\n"
               "@jax.jit\n"
               "def step(x):\n"
               "    return _decide(x)\n")
        assert dataflow_lint_source(src, "t.py") == []


class TestCallGraphResolution:
    """The call-graph legs the interprocedural rules stand on, each
    exercised through an FJ008 probe: if resolution breaks, the traced
    bool one call deep goes dark."""

    @staticmethod
    def _codes(src):
        return [(d.code, d.function)
                for d in dataflow_lint_source(src, "t.py")]

    def test_method_resolution_via_local_type(self):
        src = ("import jax\n"
               "class Policy:\n"
               "    def decide(self, x):\n"
               "        if x > 0:\n"
               "            return 1\n"
               "        return 0\n"
               "@jax.jit\n"
               "def step(x):\n"
               "    p = Policy()\n"
               "    return p.decide(x)\n")
        assert self._codes(src) == [("FJ008", "Policy.decide")]

    def test_method_resolution_walks_bases(self):
        src = ("import jax\n"
               "class Base:\n"
               "    def decide(self, x):\n"
               "        if x > 0:\n"
               "            return 1\n"
               "        return 0\n"
               "class Derived(Base):\n"
               "    pass\n"
               "@jax.jit\n"
               "def step(x):\n"
               "    p = Derived()\n"
               "    return p.decide(x)\n")
        assert self._codes(src) == [("FJ008", "Base.decide")]

    def test_functools_partial_unwraps(self):
        src = ("import jax\n"
               "from functools import partial\n"
               "def _decide(x):\n"
               "    if x > 0:\n"
               "        return 1\n"
               "    return 0\n"
               "_bound = partial(_decide)\n"
               "@jax.jit\n"
               "def step(x):\n"
               "    return _bound(x)\n")
        assert self._codes(src) == [("FJ008", "_decide")]

    def test_decorator_unwraps(self):
        src = ("import functools\nimport jax\n"
               "@functools.lru_cache(maxsize=None)\n"
               "def _decide(x):\n"
               "    if x > 0:\n"
               "        return 1\n"
               "    return 0\n"
               "@jax.jit\n"
               "def step(x):\n"
               "    return _decide(x)\n")
        assert self._codes(src) == [("FJ008", "_decide")]

    def test_recursion_terminates(self):
        """Mutually recursive callees: the fixed-point summary pass and
        the sink propagation must both terminate AND still surface the
        finding (bounded passes, monotone joins)."""
        src = ("import jax\n"
               "def _even(x):\n"
               "    if x > 0:\n"
               "        return _odd(x)\n"
               "    return 1\n"
               "def _odd(x):\n"
               "    return _even(x)\n"
               "@jax.jit\n"
               "def step(x):\n"
               "    return _even(x)\n")
        assert self._codes(src) == [("FJ008", "_even")]

    def test_syntax_error_returns_nothing(self):
        assert dataflow_lint_source("def f(:\n", "t.py") == []


class TestAuditBaseline:
    """The accepted-findings ledger (analysis/baseline.py): count-capped
    suppression keyed rule+path+function, stale entries surfaced, write
    -> load roundtrip stable."""

    @staticmethod
    def _diag(code="FJ009", file="a.py", function="f"):
        from fleetflow_tpu.lint.diagnostics import Diagnostic
        return Diagnostic(code=code, severity=Severity.WARNING,
                          message="m", file=file, line=1, col=1,
                          function=function)

    def test_count_capped_suppression(self):
        """Two findings accepted in a function; a THIRD new one in the
        same function must still fail the gate."""
        b = Baseline(entries={("FJ009", "a.py", "f"): 2})
        kept, suppressed, stale = apply_baseline(
            [self._diag(), self._diag(), self._diag()], b)
        assert suppressed == 2 and len(kept) == 1 and stale == []

    def test_stale_entries_reported(self):
        b = Baseline(entries={("FJ009", "gone.py", "g"): 1})
        kept, suppressed, stale = apply_baseline([self._diag()], b)
        assert suppressed == 0 and len(kept) == 1
        assert stale == [("FJ009", "gone.py", "g")]

    def test_key_mismatch_never_suppresses(self):
        b = Baseline(entries={("FJ007", "a.py", "f"): 5,
                              ("FJ009", "a.py", "other"): 5,
                              ("FJ009", "b.py", "f"): 5})
        kept, suppressed, _ = apply_baseline([self._diag()], b)
        assert suppressed == 0 and len(kept) == 1

    def test_write_load_roundtrip(self, tmp_path):
        path = str(tmp_path / "baseline.json")
        write_baseline([self._diag(), self._diag(),
                        self._diag(function="g")], path)
        b = load_baseline(path)
        assert b.entries == {("FJ009", "a.py", "f"): 2,
                             ("FJ009", "a.py", "g"): 1}

    def test_malformed_baseline_raises(self, tmp_path):
        """A baseline that silently loaded empty would un-suppress
        everything (CI noise) or a typo'd schema would suppress nothing
        while looking reviewed — both must fail loudly."""
        p = tmp_path / "bad.json"
        p.write_text("[]")
        with pytest.raises(ValueError):
            load_baseline(str(p))
        p.write_text('{"entries": [{"path": "a.py"}]}')
        with pytest.raises(ValueError):
            load_baseline(str(p))


class TestDataflowTreeClean:
    """The production package holds the interprocedural bar."""

    @pytest.fixture(scope="class")
    def tree_diags(self):
        return dataflow_lint_paths([PKG], rel_to=REPO, package_root=PKG)

    def test_no_errors_anywhere(self, tree_diags):
        """ERROR-severity findings (use-after-donate, traced bools, deep
        host syncs, trace-time global writes) are never baselined — the
        tree must carry zero."""
        errors = [d for d in tree_diags if d.severity is Severity.ERROR]
        assert errors == [], "\n".join(d.format() for d in errors)

    def test_clean_modulo_reviewed_baseline(self, tree_diags):
        """Everything the pass finds is in the reviewed ledger
        (audit_baseline.json: the per-call env knobs FJ009 flags, which
        tests monkeypatch per-test — caching them would break that), and
        the ledger carries no stale entries. This is the same gate
        `fleet audit all --strict --baseline audit_baseline.json` (and
        CI) applies."""
        baseline = load_baseline(os.path.join(REPO,
                                              "audit_baseline.json"))
        kept, _suppressed, stale = apply_baseline(tree_diags, baseline)
        assert kept == [], "\n".join(d.format() for d in kept)
        assert stale == [], f"stale baseline entries: {stale}"
