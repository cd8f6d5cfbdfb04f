"""reprolint self-tests: one true-positive and one true-negative per
rule ID, waiver mechanics (RL000), the construction-time hashability
backstops, and the auditor's flagged-config paths."""
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.lint import (Report, UnhashableFieldError, check_hashable_fields,
                        lint_source, rule_ids)
from repro.lint.catalog import ALL_IDS, AST_RULES, AUDIT_CHECKS

REPO = Path(__file__).resolve().parents[1]


def ids(findings, *, include_waived=False):
    return sorted(f.rule_id for f in findings
                  if include_waived or not f.waived)


def run(src, relpath="src/repro/train/somefile.py"):
    return lint_source(textwrap.dedent(src), relpath)


# ---------------------------------------------------------------------------
# catalog sanity
# ---------------------------------------------------------------------------

def test_catalog_covers_registered_rules():
    assert set(rule_ids()) <= set(r.id for r in AST_RULES)
    assert len(set(ALL_IDS)) == len(ALL_IDS)
    assert all(r.invariant and r.established
               for r in AST_RULES + AUDIT_CHECKS)


# ---------------------------------------------------------------------------
# RL000 — waiver mechanics
# ---------------------------------------------------------------------------

def test_rl000_waiver_without_reason_is_a_finding():
    fs = run("""
        import jax.numpy as jnp
        def f(x):
            # reprolint: disable=RL001
            return jnp.median(x, axis=0)
        """)
    assert "RL000" in ids(fs)
    assert "RL001" in ids(fs)  # unexcused -> still active


def test_rl000_reasoned_waiver_suppresses():
    fs = run("""
        import jax.numpy as jnp
        def f(x):
            # reprolint: disable=RL001 reference oracle for the dispatch test
            return jnp.median(x, axis=0)
        """)
    assert ids(fs) == []
    assert ids(fs, include_waived=True) == ["RL001"]


def test_rl000_stale_waiver_is_a_finding():
    fs = run("""
        # reprolint: disable=RL002 there is nothing repeated here
        x = 1
        """)
    assert ids(fs) == ["RL000"]


def test_rl000_docstring_mention_is_not_a_waiver():
    fs = run('''
        def f():
            """Docs may say `# reprolint: disable=RL001` without waiving."""
            return 0
        ''')
    assert ids(fs) == []


# ---------------------------------------------------------------------------
# RL001 — direct-aggregation-bypass
# ---------------------------------------------------------------------------

def test_rl001_true_positive_median_and_import():
    fs = run("""
        import jax.numpy as jnp
        from repro.core import aggregators
        def f(x):
            return jnp.median(x, axis=0) + aggregators.trimmed_mean(x, 0.1)
        """)
    assert ids(fs).count("RL001") == 3


def test_rl001_true_negative_estimator_layer_and_numpy():
    # inside the allowlisted estimator layer the same code is legal
    fs = lint_source(textwrap.dedent("""
        import jax.numpy as jnp
        def f(x):
            return jnp.median(x, axis=0)
        """), "src/repro/core/estimator.py")
    assert ids(fs) == []
    # host-side numpy oracles are not on the jit path
    fs = run("""
        import numpy as np
        def f(x):
            return np.median(x, axis=0)
        """)
    assert ids(fs) == []


# ---------------------------------------------------------------------------
# RL002 — kv-head-repeat
# ---------------------------------------------------------------------------

def test_rl002_true_positive_kv_repeat_in_models():
    fs = lint_source(textwrap.dedent("""
        import jax.numpy as jnp
        def mha(q, k, v):
            k = jnp.repeat(k, 4, axis=2)
            v = jnp.repeat(v, 4, axis=2)
            return q
        """), "src/repro/models/myattn.py")
    assert ids(fs) == ["RL002", "RL002"]


def test_rl002_true_negative_ssm_state_and_other_dirs():
    # mamba-style state expansion: not a K/V name
    fs = lint_source(textwrap.dedent("""
        import jax.numpy as jnp
        def ssm(B, C, nh):
            B = jnp.repeat(B, nh, axis=1)
            return B
        """), "src/repro/models/mamba2.py")
    assert ids(fs) == []
    # same call outside models//kernels/ is out of scope
    fs = lint_source(textwrap.dedent("""
        import jax.numpy as jnp
        def f(k):
            return jnp.repeat(k, 4, axis=2)
        """), "src/repro/data/loader.py")
    assert ids(fs) == []


# ---------------------------------------------------------------------------
# RL003 — trace-unsafe-python
# ---------------------------------------------------------------------------

def test_rl003_true_positive_branch_and_cast():
    fs = run("""
        import jax

        @jax.jit
        def f(x):
            if x > 0:
                return x
            return int(x)
        """)
    assert ids(fs) == ["RL003", "RL003"]


def test_rl003_jit_callsite_with_static_argnames():
    fs = run("""
        import jax

        def f(x, mode):
            if mode == "fast":   # static -> fine
                return x
            if x.shape[0] > 2:   # shape read -> fine
                return x + 1
            if x > 0:            # traced -> flagged
                return x - 1
            return x

        g = jax.jit(f, static_argnames=("mode",))
        """)
    assert ids(fs) == ["RL003"]


def test_rl003_true_negative_shape_none_and_unjitted():
    fs = run("""
        import jax

        @jax.jit
        def f(x, y):
            if y is None:
                return x
            if len(x.shape) > 2:
                return x + 1
            return x

        def g(x):
            if x > 0:   # not jitted -> out of scope
                return 1
            return int(x)
        """)
    assert ids(fs) == []


# ---------------------------------------------------------------------------
# RL004 — unhashable-static
# ---------------------------------------------------------------------------

def test_rl004_true_positive_unfrozen_and_mutable_field():
    fs = run("""
        import dataclasses
        from typing import List, NamedTuple

        @dataclasses.dataclass
        class DecodeConfig:
            m: int = 8

        class TileSpec(NamedTuple):
            dims: List[int]
        """)
    assert ids(fs) == ["RL004", "RL004"]


def test_rl004_true_negative_frozen_config_and_host_record():
    fs = run("""
        import dataclasses

        @dataclasses.dataclass(frozen=True)
        class DecodeConfig:
            m: int = 8
            name: str = "x"

        @dataclasses.dataclass
        class Request:        # host-side bookkeeping: not config-named
            prompt: str = ""
        """)
    assert ids(fs) == []


# ---------------------------------------------------------------------------
# RL005 — impure-index-map
# ---------------------------------------------------------------------------

def test_rl005_true_positive_subscript_and_call():
    fs = run("""
        from jax.experimental import pallas as pl
        def f(table):
            return pl.BlockSpec((1, 8), lambda i, j: (table[i], j))
        def g(fn):
            return pl.BlockSpec((1, 8), index_map=lambda i, j: (fn(i), j))
        """)
    assert ids(fs) == ["RL005", "RL005"]


def test_rl005_scalar_prefetch_reads():
    """A subscript of the map's own scalar-prefetch argument is the
    Pallas TPU way to pick a block by data; a call or a captured table
    inside the subscript is still flagged."""
    fs = run("""
        from jax.experimental import pallas as pl
        def f(table, fn):
            ok = pl.BlockSpec((1, 8), lambda i, j, layer: (layer[0], i, j))
            bad = pl.BlockSpec((1, 8), lambda i, j, layer: (layer[fn(i)], j))
            worse = pl.BlockSpec((1, 8), lambda i, j, layer: (table[i], j))
            return ok, bad, worse
        """)
    assert ids(fs) == ["RL005", "RL005"]


def test_rl005_true_negative_pure_arithmetic():
    fs = run("""
        from jax.experimental import pallas as pl
        H, G = 8, 2
        def f():
            return pl.BlockSpec(
                (1, 8), lambda b, i, j: ((b // H) * G + (b % H) // G, j, 0))
        """)
    assert ids(fs) == []


# ---------------------------------------------------------------------------
# RL006 — unmasked-padded-load
# ---------------------------------------------------------------------------

def test_rl006_true_positive_padded_without_mask():
    fs = run("""
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def _kern(x_ref, o_ref):
            o_ref[...] = x_ref[...] * 2.0

        def f(x, blk):
            x = jnp.pad(x, ((0, 3), (0, 0)))
            return pl.pallas_call(_kern, grid=(4,),
                                  out_shape=x)(x)
        """)
    assert ids(fs) == ["RL006"]


def test_rl006_true_negative_masked_or_unpadded():
    fs = run("""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def _kern(x_ref, o_ref, *, n):
            i = jax.lax.broadcasted_iota(jnp.int32, x_ref.shape, 0)
            o_ref[...] = jnp.where(i < n, x_ref[...], 0.0)

        def masked(x, n):
            x = jnp.pad(x, ((0, 3), (0, 0)))
            import functools
            return pl.pallas_call(functools.partial(_kern, n=n),
                                  grid=(4,), out_shape=x)(x)

        def _kern2(x_ref, o_ref):
            o_ref[...] = x_ref[...] * 2.0

        def unpadded(x):
            return pl.pallas_call(_kern2, grid=(4,), out_shape=x)(x)
        """)
    assert ids(fs) == []


def test_rl006_true_positive_partial_bound_kernel():
    """The fused-tail shape: a wrapper that pads rows, then dispatches a
    functools.partial-bound kernel with NO mask anywhere — must flag."""
    fs = run("""
        import functools
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def _tail(x_ref, o_ref, *, m):
            o_ref[...] = jnp.sum(x_ref[...], axis=0)

        def fused(x, m_pad):
            x = jnp.pad(x, ((0, m_pad - x.shape[0]), (0, 0)))
            return pl.pallas_call(functools.partial(_tail, m=x.shape[0]),
                                  grid=(4,), out_shape=x)(x)
        """)
    assert ids(fs) == ["RL006"]


def test_rl006_true_negative_mask_in_module_helper():
    """The mask may live in a same-module helper the kernel calls (the
    vrmom kernels share ``_agg_block``) — the rule follows plain-name
    calls to module-level defs before flagging."""
    fs = run("""
        import functools
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def _shared_block(x, n):
            i = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
            return jnp.where(i < n, x, 0.0)

        def _kern(x_ref, o_ref, *, n):
            o_ref[...] = _shared_block(x_ref[...], n)

        def padded(x, n):
            x = jnp.pad(x, ((0, 3), (0, 0)))
            return pl.pallas_call(functools.partial(_kern, n=n),
                                  grid=(4,), out_shape=x)(x)
        """)
    assert ids(fs) == []


# ---------------------------------------------------------------------------
# RL007 — wall-clock-outside-obs
# ---------------------------------------------------------------------------

def test_rl007_true_positive_clock_call_and_import():
    fs = run("""
        import time
        from time import perf_counter

        def f():
            t0 = time.time()
            t1 = perf_counter()
            return time.monotonic() - t0 + t1
        """)
    # the bare perf_counter() call is caught at its import site
    assert ids(fs) == ["RL007", "RL007", "RL007"]


def test_rl007_true_negative_obs_layer_and_nonclock_time():
    # the obs layer IS the allowed wall-clock site
    fs = lint_source(textwrap.dedent("""
        import time

        def now():
            return time.perf_counter()
        """), "src/repro/obs/metrics.py")
    assert ids(fs) == []
    # non-clock time functions (sleep, strftime) are fine anywhere
    fs = run("""
        import time

        def f():
            time.sleep(0.1)
            return time.strftime("%Y")
        """)
    assert ids(fs) == []


def test_rl007_scope_is_library_code_only():
    src = """
        import time

        def f():
            return time.time()
        """
    for relpath in ("benchmarks/serve.py", "examples/serve.py",
                    "scripts/metrics_dump.py", "tests/test_obs.py"):
        assert ids(lint_source(textwrap.dedent(src), relpath)) == []
    assert ids(lint_source(textwrap.dedent(src),
                           "src/repro/serve/scheduler.py")) == ["RL007"]


def test_rl007_repo_library_tree_is_clean():
    """The invariant holds on the actual tree: no direct wall-clock
    reads anywhere under src/repro/ outside obs/metrics.py."""
    from repro.lint import lint_paths

    findings = [f for f in lint_paths([str(REPO / "src" / "repro")],
                                      root=str(REPO))
                if f.rule_id == "RL007" and not f.waived]
    assert findings == [], findings


# ---------------------------------------------------------------------------
# hashability backstops (satellite 2)
# ---------------------------------------------------------------------------

def test_estimator_rejects_unhashable_field():
    from repro.core.estimator import Estimator

    with pytest.raises(UnhashableFieldError, match=r"Estimator\.K"):
        Estimator(method="median", K=[1, 2])
    hash(Estimator(method="median"))  # clean spec stays hashable


def test_robust_decode_config_rejects_unhashable_field():
    from repro.serve.robust import RobustDecodeConfig

    with pytest.raises(UnhashableFieldError, match=r"\.attack"):
        RobustDecodeConfig(m=8, estimator="median", attack=["none"])
    hash(RobustDecodeConfig(m=8, estimator="median"))


def test_arch_config_rejects_unhashable_field():
    from repro.configs.base import ArchConfig

    with pytest.raises(UnhashableFieldError, match=r"ArchConfig\.source"):
        ArchConfig(name="x", family="dense", n_layers=1, d_model=8,
                   n_heads=2, n_kv_heads=1, d_ff=16, vocab=32,
                   source=["paper"])


def test_check_hashable_fields_plain_object():
    class Box:
        def __init__(self):
            self.data = {"a": 1}

    with pytest.raises(UnhashableFieldError, match=r"Box\.data"):
        check_hashable_fields(Box())


# ---------------------------------------------------------------------------
# auditor: flagged configs (satellite 3)
# ---------------------------------------------------------------------------

def test_auditor_flags_worker_indivisible_config():
    from repro.lint.auditor import divisibility_audit

    bad = divisibility_audit("train.global_batch", batch=9, n_workers=8)
    assert bad.status == "fail"
    assert "not divisible" in bad.detail
    good = divisibility_audit("train.global_batch", batch=16, n_workers=8)
    assert good.status == "ok"


def test_auditor_flags_hash_unstable_config():
    import dataclasses

    from repro.lint.auditor import recompile_stability

    @dataclasses.dataclass(frozen=True, eq=False)  # hash by identity
    class DriftyConfig:
        m: int = 8

    bad = recompile_stability("DriftyConfig", DriftyConfig)
    assert bad.status == "fail"

    from repro.core.estimator import Estimator

    good = recompile_stability("Estimator",
                               lambda: Estimator(method="median"))
    assert good.status == "ok", good.detail


def test_auditor_flags_consensus_validity_region():
    from repro.lint.auditor import consensus_validity_audit

    bad = consensus_validity_audit("dist.consensus", n=8, f=2)
    assert bad.status == "fail"
    assert "n > 5f" in bad.detail
    boundary = consensus_validity_audit("dist.consensus", n=10, f=2)
    assert boundary.status == "fail"  # n == 5f is still invalid
    good = consensus_validity_audit("dist.consensus", n=8, f=1)
    assert good.status == "ok", good.detail
    assert good.check_id == "RL210"


def test_auditor_full_run_has_no_failures():
    """The shipped tree passes its own audit (skips allowed off-mesh)."""
    from repro.lint.auditor import run_audit

    results = run_audit()
    fails = [r for r in results if r.status == "fail"]
    assert not fails, "\n".join(r.render() for r in fails)
    # every advertised RL2xx check reported at least once
    seen = {r.check_id for r in results}
    assert {c.id for c in AUDIT_CHECKS} <= seen | {"RL201", "RL205",
                                                   "RL206"}


# ---------------------------------------------------------------------------
# CLI + shipped tree (acceptance)
# ---------------------------------------------------------------------------

def test_shipped_tree_is_lint_clean():
    from repro.lint import lint_paths

    findings = lint_paths(["src", "tests"], str(REPO))
    report = Report(findings=findings, audit=[])
    assert report.errors == [], report.render_text()
    # zero unexplained suppressions
    assert all(f.waive_reason for f in findings if f.waived)


def test_cli_exits_nonzero_on_violation(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import jax.numpy as jnp\n"
                   "def f(x):\n"
                   "    return jnp.median(x, axis=0)\n")
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "reprolint.py"),
         str(bad), "--format", "json"],
        capture_output=True, text=True)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert '"RL001"' in proc.stdout
    # warn-only downgrades to exit 0 but still reports
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "reprolint.py"),
         str(bad), "--warn-only"],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "1 warning" in proc.stdout
