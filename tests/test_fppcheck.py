"""fppcheck seeded-violation tests (DESIGN.md §7).

Each pass family is fed a deliberately broken input and must catch it:
an injected io_callback inside a while body, an f64 promotion, an
oversized BlockSpec, a reintroduced bare assert, a budget-exceeding
metric row.  The clean-repo integration tests then pin that the *real*
tree stays green — the same invariant CI's analysis job enforces.
"""
import pathlib
import textwrap

import numpy as np
import pytest

from repro.analysis import Finding, PassContext, Report, repo_root, run_passes
from repro.analysis.ast_passes import check_asserts, check_host_jnp_loops
from repro.analysis.hlo_passes import check_row
from repro.analysis.pallas_passes import check_contract
from repro.kernels.contract import KernelContract, TileSpec

ROOT = repo_root()


def _mini_repo(tmp_path: pathlib.Path) -> pathlib.Path:
    """A minimal repo skeleton the path-scanning passes can run over."""
    (tmp_path / "src" / "repro" / "core").mkdir(parents=True)
    (tmp_path / "DESIGN.md").write_text(
        "# design\n\n## §1 Overview\n\nbody\n\n## §2 Engine\n\nbody\n")
    (tmp_path / "README.md").write_text(
        "# readme\n\n## Repo map\n\n| path | role |\n|---|---|\n"
        "| `src/repro/core/` | core |\n\n## Next\n\nnothing\n")
    return tmp_path


# ------------------------------------------------------------------- ast


def test_bare_assert_caught_and_escape_respected(tmp_path):
    root = _mini_repo(tmp_path)
    (root / "src" / "repro" / "mod.py").write_text(textwrap.dedent("""\
        def f(x):
            assert x > 0
            assert x < 10  # fppcheck: allow-assert
            return x
    """))
    findings = check_asserts(PassContext(root=root))
    assert [f.code for f in findings] == ["bare-assert"]
    assert findings[0].severity == "error"
    assert findings[0].location == "src/repro/mod.py:2"


def test_asserts_exempt_under_tests_dir(tmp_path):
    root = _mini_repo(tmp_path)
    (root / "src" / "repro" / "tests").mkdir()
    (root / "src" / "repro" / "tests" / "t.py").write_text(
        "def f():\n    assert True\n")
    assert check_asserts(PassContext(root=root)) == []


def test_jnp_in_host_loop_caught(tmp_path):
    root = _mini_repo(tmp_path)
    (root / "src" / "repro" / "core" / "hot.py").write_text(
        textwrap.dedent("""\
            import jax.numpy as jnp

            def slow(xs):
                out = []
                for x in xs:
                    out.append(jnp.add(x, 1))        # flagged
                    out.append(jnp.int32(0))         # scalar ctor: fine
                    out.append(jnp.exp(x))  # fppcheck: allow-host-jnp
                return out

            def traced(xs):
                for _ in range(3):
                    def body(c):
                        return jnp.add(c, 1)         # nested def: fine
                return body
        """))
    findings = check_host_jnp_loops(PassContext(root=root))
    assert [f.code for f in findings] == ["jnp-in-host-loop"]
    assert findings[0].location == "src/repro/core/hot.py:6"
    assert "jnp.add" in findings[0].message


def test_jnp_in_host_loop_caught_under_serve(tmp_path):
    # the serving lanes are host-side by design (admission / delivery /
    # result cache) — a per-iteration dispatch there stalls every tenant,
    # so the lint polices serve/ with the same rules as core/
    root = _mini_repo(tmp_path)
    (root / "src" / "repro" / "serve").mkdir()
    (root / "src" / "repro" / "serve" / "lane.py").write_text(
        textwrap.dedent("""\
            import jax.numpy as jnp

            def drain(batches):
                out = []
                while batches:
                    out.append(jnp.stack(batches.pop()))   # flagged
                    out.append(jnp.asarray(0))             # ctor: fine
                return out
        """))
    findings = check_host_jnp_loops(PassContext(root=root))
    assert [f.location for f in findings] == ["src/repro/serve/lane.py:6"]
    assert "jnp.stack" in findings[0].message


# ------------------------------------------------------------------ docs


def test_dangling_design_ref_caught(tmp_path):
    root = _mini_repo(tmp_path)
    # assembled so the docs pass scanning THIS file doesn't see a citation
    dangling = "DESIGN.md " + chr(0xA7) + "9.3"
    (root / "src" / "repro" / "mod.py").write_text(
        f'"""See {dangling} for details."""\n')
    from repro.analysis.docs import run_pass
    findings = run_pass(PassContext(root=root))
    assert any(f.code == "dangling-ref" and "9.3" in f.message
               and f.severity == "error" for f in findings)


def test_stale_repo_map_entry_caught(tmp_path):
    root = _mini_repo(tmp_path)
    readme = root / "README.md"
    readme.write_text(readme.read_text().replace(
        "`src/repro/core/`", "`src/repro/gone.py`"))
    from repro.analysis.docs import run_pass
    findings = run_pass(PassContext(root=root))
    assert any("gone.py" in f.message for f in findings)


# ---------------------------------------------------------------- pallas


def _contract(**kw):
    base = dict(
        name="fake", module="repro.kernels.fake.fake", grid=(4,),
        in_tiles=(TileSpec("a", (256, 64), (64, 64)),),
        out_tiles=(TileSpec("o", (256, 64), (64, 64)),),
        wired=False)
    base.update(kw)
    return KernelContract(**base)


class _Mem:
    """Stand-in MemoryModel: tiny working set, real-sized VMEM."""
    vmem_bytes = 100 * 2 ** 20

    def working_set(self, block_size, num_queries):
        return 64 * 1024

    def covers(self, fp, block_size, num_queries):
        return fp <= self.working_set(block_size, num_queries)


def test_contract_clean_passes():
    assert check_contract(_contract(), _Mem()) == []


def test_tile_divisibility_violation_caught():
    c = _contract(in_tiles=(TileSpec("a", (100, 64), (64, 64)),))
    findings = check_contract(c, _Mem())
    assert any(f.code == "tile-divisibility" and f.severity == "error"
               for f in findings)


def test_grid_coverage_violation_caught():
    c = _contract(grid=(2,))   # 4 output blocks, only 2 programs
    findings = check_contract(c, _Mem())
    assert any(f.code == "grid-coverage" and f.severity == "error"
               for f in findings)


def test_vmem_overflow_caught():
    big = TileSpec("a", (8192, 8192), (8192, 8192))   # 256 MiB > VMEM
    c = _contract(in_tiles=(big,))
    findings = check_contract(c, _Mem())
    assert any(f.code == "vmem-overflow" and f.severity == "error"
               for f in findings)


def test_model_overflow_caught_for_wired_kernel():
    # fits VMEM but blows the planner's modeled working set
    big = TileSpec("a", (256, 256), (256, 256))       # 256 KiB > 64 KiB
    c = _contract(in_tiles=(big,),
                  out_tiles=(TileSpec("o", (256, 256), (256, 256)),),
                  grid=(1,), wired=True, block_size=64, num_queries=64)
    findings = check_contract(c, _Mem())
    assert any(f.code == "model-overflow" and f.severity == "error"
               for f in findings)


def test_wired_kernel_within_model_reports_footprint():
    c = _contract(grid=(4,), wired=True, block_size=64, num_queries=64)
    findings = check_contract(c, _Mem())
    assert [f.code for f in findings] == ["footprint"]
    assert findings[0].severity == "info"


# ----------------------------------------------------------------- jaxpr


def _program(fn, args, **kw):
    from repro.analysis.programs import Program
    return Program(key="seeded/test", backend="test", kind="test",
                   fn=fn, args=args, **kw)


def _codes(findings):
    return {f.code for f in findings}


def test_io_callback_in_while_body_caught():
    import jax
    from jax.experimental import io_callback

    from repro.analysis.jaxpr_passes import check_program

    def fn(x):
        def body(c):
            io_callback(lambda v: None, None, c)
            return c + 1
        return jax.lax.while_loop(lambda c: c < 10, body, x)

    findings = check_program(_program(fn, (np.int32(0),)))
    hits = [f for f in findings if f.code == "host-callback-in-loop"]
    assert len(hits) == 1 and hits[0].severity == "error"


def test_callback_outside_loop_is_warning_only():
    import jax
    from jax.experimental import io_callback

    from repro.analysis.jaxpr_passes import check_program

    def fn(x):
        io_callback(lambda v: None, None, x)
        return x + 1

    findings = check_program(_program(fn, (np.int32(0),)))
    assert _codes(findings) == {"host-callback"}
    assert all(f.severity == "warning" for f in findings)


def test_f64_promotion_caught():
    import jax
    import jax.numpy as jnp

    from repro.analysis.jaxpr_passes import check_program

    def fn(x):
        return x.astype(jnp.float64) * 2.0

    with jax.enable_x64(True):
        findings = check_program(
            _program(fn, (jax.ShapeDtypeStruct((8,), jnp.float32),)))
    assert "x64-promotion" in _codes(findings)
    assert any(f.severity == "error" for f in findings)


def test_weak_output_caught():
    import jax.numpy as jnp

    from repro.analysis.jaxpr_passes import check_program

    def fn(x):
        return jnp.add(1.0, 2.0)      # literal-only: weakly typed output

    findings = check_program(
        _program(fn, (np.zeros(4, np.float32),)))
    assert "weak-output" in _codes(findings)


def test_counter_dtype_contract_enforced():
    import jax.numpy as jnp

    from repro.analysis.jaxpr_passes import check_program

    def fn(x):
        return x.sum()                # float32 "counter"

    findings = check_program(_program(
        fn, (np.zeros(4, np.float32),),
        counters=lambda out: {"eq": out}))
    assert "counter-dtype" in _codes(findings)


def test_donation_aval_drift_caught():
    import jax.numpy as jnp

    from repro.analysis.jaxpr_passes import check_program

    def fn(x):
        return x[:2]                  # state comes back a different shape

    findings = check_program(_program(
        fn, (np.zeros(4, np.float32),),
        donation=lambda args, out: [("state", args[0], out)]))
    assert "donation-unsafe" in _codes(findings)


def test_clean_program_has_no_findings():
    import jax
    import jax.numpy as jnp

    from repro.analysis.jaxpr_passes import check_program

    def fn(x):
        return jax.lax.while_loop(
            lambda c: c[0] < jnp.int32(10),
            lambda c: (c[0] + jnp.int32(1), c[1] * jnp.float32(0.5)), x)

    findings = check_program(_program(
        fn, ((np.int32(0), np.float32(1.0)),),
        donation=lambda args, out: [("state", args[0], out)]))
    assert findings == []


# ------------------------------------------------------------------- hlo


_BASE = {"ops_total": 100, "while_body_total": 20, "op_copy": 3}


def test_budget_exceeded_caught():
    row = dict(_BASE, op_copy=4)      # one extra copy past the ceiling
    findings = check_row("engine/test", row, _BASE)
    assert [f.code for f in findings] == ["budget-exceeded"]
    assert findings[0].severity == "error"
    assert "op_copy: 4 > 3" in findings[0].message


def test_budget_is_a_ceiling_not_an_equality():
    row = dict(_BASE, ops_total=90)   # shrinking never fails
    findings = check_row("engine/test", row, _BASE)
    assert [f.code for f in findings] == ["within-budget"]


def test_unbudgeted_metric_warns():
    row = dict(_BASE, op_scatter=1)
    findings = check_row("engine/test", row, _BASE)
    codes = [f.code for f in findings]
    assert "unbudgeted-metric" in codes
    sev = {f.code: f.severity for f in findings}
    assert sev["unbudgeted-metric"] == "warning"


def test_committed_budgets_cover_full_matrix():
    import json
    budgets = json.loads(
        (ROOT / "src" / "repro" / "analysis" / "budgets.json").read_text())
    kinds = ("sssp", "bfs", "ppr", "cc", "kreach", "rw")
    want = {f"{b}/{k}" for b in ("engine", "streaming", "baselines")
            for k in kinds}
    want |= {f"engine-serve/{k}" for k in kinds}
    want |= {f"engine-fused/{k}" for k in kinds if k != "rw"}
    want |= {f"distributed/{k}@d{d}" for k in kinds for d in (1, 8)}
    assert want <= set(budgets)
    for key, row in budgets.items():
        assert row["ops_total"] > 0, key


# ------------------------------------------------- clean-repo integration


def test_fast_families_clean_on_real_repo():
    """ast + docs + pallas must be green on the committed tree."""
    report = run_passes(["ast.asserts", "ast.host-jnp", "docs.refs",
                         "pallas.contracts", "pallas.reachability"],
                        PassContext(root=ROOT))
    assert report.ok, report.render()


def test_report_severity_model():
    r = Report(findings=[
        Finding("p", "c", "warning", "loc", "m"),
        Finding("p", "c", "allowlisted", "loc", "m"),
        Finding("p", "c", "info", "loc", "m")], passes_run=["p"])
    assert r.ok                       # only errors fail
    r2 = Report(findings=[Finding("p", "c", "error", "loc", "m")],
                passes_run=["p"])
    assert not r2.ok
    assert r2.as_dict()["counts"]["error"] == 1
