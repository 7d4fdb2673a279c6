"""isolint: golden positive/negative fixtures per rule, VMEM arithmetic,
and the full-tree gate.

Each rule gets at least one snippet that MUST produce its finding and one
near-identical snippet that must NOT — the analyzer's precision is part of
the contract (a lint the tree can't stay clean against gets pragma'd into
noise).  The VMEM test pins the footprint arithmetic to hand-computed
numbers so a refactor of the shape evaluator can't silently change what
the budget gate measures.  The final test runs the shipped analyzer over
the real tree and requires exit 0 — the same gate CI enforces.
"""
from __future__ import annotations

import ast
import json
import pathlib
import subprocess
import sys
import textwrap

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from tools import lintlib                              # noqa: E402
from tools.isolint import (config, passes_fences, passes_hygiene,  # noqa: E402
                           passes_taint, passes_vmem)
from tools.isolint.__main__ import analyze_tree        # noqa: E402


def _parse(src: str) -> ast.Module:
    return ast.parse(textwrap.dedent(src))


def _rules(findings) -> set[str]:
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# pass 1: egress-bypass taint
# ---------------------------------------------------------------------------

def test_taint_flags_direct_index_of_pool_tensor():
    src = """
    def leak(pool, rows):
        w = pool.tensor("w")
        return w[rows]
    """
    f = passes_taint.run(_parse(src), "examples/x.py")
    assert _rules(f) == {"egress-bypass"}
    assert any("indexed" in x.message or "escapes" in x.message for x in f)


def test_taint_allows_checked_sink_and_metadata():
    src = """
    def ok(pool, rows, table, local):
        region = pool.region("w")
        n = region.n_pages            # metadata read: fine
        return checked_gather(pool, "w", rows, hwpid=1, table=table,
                              hwpid_local=local), n
    """
    assert passes_taint.run(_parse(src), "examples/x.py") == []


def test_taint_propagates_through_rebinding():
    src = """
    def leak(pool):
        t = pool.tensor("w")
        u = t
        return u + 1
    """
    f = passes_taint.run(_parse(src), "examples/x.py")
    assert any(f_.rule == "egress-bypass" and "`u`" in f_.message for f_ in f)


def test_taint_flags_pass_to_unchecked_call():
    src = """
    def leak(pool):
        t = pool.tensor("w")
        publish_somewhere(t)
    """
    f = passes_taint.run(_parse(src), "examples/x.py")
    assert _rules(f) == {"egress-bypass"}


def test_taint_skips_trusted_impl_bodies():
    src = """
    def checked_gather(pool, name, rows, **kw):
        t = pool.tensor(name)         # the read the checker guards
        return t[rows]
    """
    assert passes_taint.run(_parse(src), "src/repro/core/pool.py") == []


# ---------------------------------------------------------------------------
# pass 2: fence discipline + default-deny
# ---------------------------------------------------------------------------

def test_fence_flags_consume_after_publish():
    src = """
    def stale(fm, bus, rt):
        fm.propose(p)
        rt.check(ext, write=False)
    """
    f = passes_fences.run(_parse(src), "examples/x.py")
    assert _rules(f) == {"fence-discipline"}


def test_fence_accepts_interposed_fence():
    src = """
    def fresh(fm, bus, rt):
        fm.propose(p)
        bus.deliver_until(fm.epoch)
        rt.check(ext, write=False)
    """
    assert passes_fences.run(_parse(src), "examples/x.py") == []


def test_default_deny_requires_fault_fallthrough():
    bad = """
    def check_access(table, ext):
        return True
    """
    good = """
    def check_access(table, ext):
        if bad(ext):
            return FAULT_PERM
        return FAULT_NONE
    """
    assert _rules(passes_fences.run(_parse(bad), "src/repro/core/x.py")) \
        == {"default-deny"}
    assert passes_fences.run(_parse(good), "src/repro/core/x.py") == []


def test_default_deny_only_applies_to_src():
    src = """
    def check(x):
        return True
    """
    assert passes_fences.run(_parse(src), "benchmarks/x.py") == []


# ---------------------------------------------------------------------------
# pass 3: VMEM budget + compiled-path lints
# ---------------------------------------------------------------------------

_KERNEL_SRC = """
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 1024

def crypt(buf, npad):
    return pl.pallas_call(
        kernel,
        grid=(npad // BLOCK,),
        in_specs=[pl.BlockSpec((BLOCK,), lambda i: (i,))],
        out_specs=pl.BlockSpec((BLOCK,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((npad,), jnp.uint32),
        compiler_params=ptu(dimension_semantics=("parallel",)),
    )(buf)
"""


def test_vmem_arithmetic_pinned():
    # one (BLOCK,) u32 in + one (BLOCK,) u32 out = 2 * 1024 * 4 = 8192 B
    # per step; "parallel" grid -> Mosaic double-buffers: 16384 B gated.
    f, rows = passes_vmem.analyze_file(
        _parse(_KERNEL_SRC), "src/x.py", REPO, budget=4 << 20)
    assert f == []
    (row,) = rows
    assert row["in_bytes"] == 4096
    assert row["out_bytes"] == 4096
    assert row["per_step_bytes"] == 8192
    assert row["double_buffered"] is True
    assert row["gated_bytes"] == 16384
    assert row["within_budget"] is True


def test_vmem_budget_gate_fires():
    f, rows = passes_vmem.analyze_file(
        _parse(_KERNEL_SRC), "src/x.py", REPO, budget=10_000)
    assert _rules(f) == {"vmem-budget"}       # 16384 > 10000
    assert rows[0]["within_budget"] is False


def test_vmem_flags_missing_dimension_semantics():
    src = _KERNEL_SRC.replace(
        "        compiler_params=ptu(dimension_semantics=(\"parallel\",)),\n",
        "")
    f, rows = passes_vmem.analyze_file(
        _parse(src), "src/x.py", REPO, budget=4 << 20)
    assert _rules(f) == {"missing-dimension-semantics"}
    assert rows[0]["double_buffered"] is False
    assert rows[0]["gated_bytes"] == 8192     # no 2x without "parallel"


def test_vmem_flags_interpret_hardcoded():
    src = """
    from jax.experimental import pallas as pl

    def k(x, interpret: bool = True):
        return pl.pallas_call(f, interpret=True)(x)
    """
    f, _ = passes_vmem.analyze_file(
        _parse(src), "src/x.py", REPO, budget=4 << 20)
    assert [x.rule for x in f].count("interpret-hardcoded") == 2  # default+call


def test_vmem_worst_case_fallback_and_unresolved():
    src = """
    from jax.experimental import pallas as pl

    def k(x, np_):
        return pl.pallas_call(
            f, grid=(4,),
            in_specs=[pl.BlockSpec((np_,), lambda i: (0,))],
            compiler_params=ptu(dimension_semantics=("arbitrary",)),
        )(x)
    """
    f, rows = passes_vmem.analyze_file(
        _parse(src), "src/x.py", REPO, budget=4 << 20)
    # np_ is dynamic -> the architectural ceiling binding, not unresolved
    assert rows[0]["in_bytes"] == config.WORST_CASE_DIMS["np_"] * 4
    src2 = src.replace("np_", "mystery_dim")
    f2, rows2 = passes_vmem.analyze_file(
        _parse(src2), "src/x.py", REPO, budget=4 << 20)
    assert _rules(f2) == {"vmem-unresolved"}
    assert rows2[0]["unresolved"] == "mystery_dim"


def test_vmem_resolves_grid_spec_and_local_specs():
    """Scalar-prefetch grid specs, specs bound to local names or built by
    a local helper, and the compiler-params helper all resolve; SMEM
    blocks take no VMEM."""
    src = """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def k(x, h, n):
        rows = 8
        words = pl.BlockSpec((None, rows, 128), lambda i, j, *_: (i, j, 0))

        def shard_row(m):
            return pl.BlockSpec((None, 1, m), lambda i, j, *_: (i, 0, 0),
                                memory_space=pltpu.SMEM)

        return pl.pallas_call(
            f,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(h, 4),
                in_specs=[words] + [shard_row(n)] * 3,
                out_specs=[words]),
            out_shape=[jax.ShapeDtypeStruct((h, 32, 128), jnp.int32)],
            **compiler_params(interpret, "parallel", "arbitrary"),
        )(x)
    """
    f, rows = passes_vmem.analyze_file(
        _parse(src), "src/x.py", REPO, budget=4 << 20)
    assert f == []
    (row,) = rows
    assert row["in_bytes"] == 8 * 128 * 4 and row["out_bytes"] == 8 * 128 * 4
    assert row["double_buffered"] is True


def test_vmem_closure_captured_operand():
    bad = """
    import jax
    import jax.numpy as jnp

    def bench():
        w = jnp.zeros((10, 10))
        fn = jax.jit(lambda r: jnp.take(w, r, axis=0))
    """
    good = """
    import jax
    import jax.numpy as jnp

    def bench():
        w = jnp.zeros((10, 10))
        fn = jax.jit(lambda r, w_: jnp.take(w_, r, axis=0))
    """
    f, _ = passes_vmem.analyze_file(
        _parse(bad), "benchmarks/x.py", REPO, budget=4 << 20)
    assert _rules(f) == {"closure-captured-operand"}
    f2, _ = passes_vmem.analyze_file(
        _parse(good), "benchmarks/x.py", REPO, budget=4 << 20)
    assert f2 == []


# ---------------------------------------------------------------------------
# pass 4: silent-except hygiene
# ---------------------------------------------------------------------------

def test_silent_except_flags_unrecorded_swallow():
    src = """
    def f():
        try:
            g()
        except Exception:
            pass
    """
    assert _rules(passes_hygiene.run(_parse(src), "src/x.py")) \
        == {"silent-except"}


def test_silent_except_accepts_recorded_or_reraised():
    src = """
    def f(stats):
        try:
            g()
        except Exception as exc:
            stats.append(repr(exc))
        try:
            g()
        except Exception:
            cleanup()
            raise
        except ValueError:
            pass                      # narrow: a decision, not a hole
    """
    assert passes_hygiene.run(_parse(src), "src/x.py") == []


# ---------------------------------------------------------------------------
# pragmas, baseline, CLI
# ---------------------------------------------------------------------------

def test_pragma_suppresses_and_malformed_pragma_is_a_finding(tmp_path):
    (tmp_path / "ok.py").write_text(textwrap.dedent("""
        def f():
            try:
                g()
            # isolint: allow(silent-except) — probing an optional backend
            except Exception:
                pass
    """))
    (tmp_path / "bad.py").write_text(textwrap.dedent("""
        def f():
            try:
                g()
            # isolint: allow(silent-except)
            except Exception:
                pass
    """))
    findings, _, suppressed, errs = analyze_tree(
        tmp_path, ["ok.py", "bad.py"], budget=4 << 20)
    assert errs == []
    assert suppressed == 1
    assert {(f.rule, f.path) for f in findings} == {
        ("malformed-pragma", "bad.py"), ("silent-except", "bad.py")}


def test_baseline_ratchet(tmp_path):
    f1 = lintlib.Finding("r", "a.py", 3, "msg", key="k1")
    f2 = lintlib.Finding("r", "a.py", 9, "msg2", key="k2")
    base = tmp_path / "b.json"
    lintlib.save_baseline(base, [f1], tool="isolint")
    new, old, stale = lintlib.partition_findings(
        [f1, f2], lintlib.load_baseline(base))
    assert new == [f2] and old == [f1] and stale == []
    # f1 fixed -> its entry is stale and reported for deletion
    new, old, stale = lintlib.partition_findings(
        [f2], lintlib.load_baseline(base))
    assert stale == [("r", "a.py", "k1")]


def test_cli_full_tree_is_clean_and_covers_every_kernel(tmp_path):
    report = tmp_path / "report.json"
    proc = subprocess.run(
        [sys.executable, "-m", "tools.isolint", "src", "examples",
         "benchmarks", "--report", str(report)],
        cwd=REPO, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    data = json.loads(report.read_text())
    assert data["new"] == []
    # every pallas_call site in the tree must appear in the VMEM table,
    # resolved (no site may silently fall out of the budget gate)
    sites = set()
    for f in (REPO / "src").rglob("*.py"):
        tree = ast.parse(f.read_text())
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and isinstance(
                    node.func, ast.Attribute) and \
                    node.func.attr == "pallas_call":
                sites.add((lintlib.rel_path(f, REPO), node.lineno))
    covered = {(r["path"], r["line"]) for r in data["vmem"]}
    assert sites, "no pallas_call sites found — did the tree move?"
    assert sites <= covered, f"uncovered kernels: {sites - covered}"
    assert all("unresolved" not in r for r in data["vmem"])
    assert all(r["within_budget"] for r in data["vmem"])


def test_cli_fails_on_seeded_violation(tmp_path):
    (tmp_path / "leak.py").write_text(textwrap.dedent("""
        def leak(pool, rows):
            return pool.tensor("w")[rows]
    """))
    proc = subprocess.run(
        [sys.executable, "-m", "tools.isolint", str(tmp_path / "leak.py"),
         "--no-baseline"],
        cwd=REPO, capture_output=True, text=True)
    assert proc.returncode == 1
    assert "egress-bypass" in proc.stdout
