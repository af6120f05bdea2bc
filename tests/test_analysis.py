"""The static schedule verifier (``repro.analysis``): axis attribution,
the lint passes on synthetic inputs, the source-level AST lint, and —
in an 8-device subprocess — the compiled-IR acceptance cells plus the
two seeded regressions the verifier must *catch* (a non-bijective ring
ppermute and a gathered operand under a ring schedule).
"""

import os
import subprocess
import sys
import textwrap

import pytest

from repro.analysis import astlint
from repro.analysis.collect import (axis_groups, effective_axes,
                                    normalize_mesh_axes, orbits)
from repro.analysis.lints import (Finding, errors, lint_footprint,
                                  lint_wire)

pytestmark = pytest.mark.static

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CONV_MESH = (("b", 2), ("h", 1), ("w", 1), ("k", 2), ("c", 2))


def run_in_subprocess(body: str, devices: int = 8):
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \\
            "--xla_force_host_platform_device_count={devices}"
        os.environ["REPRO_DIST_PALLAS"] = "0"
        os.environ["REPRO_AUTOTUNE"] = "0"
        import jax, jax.numpy as jnp
        import numpy as np
    """) + textwrap.dedent(body)
    env = dict(os.environ,
               PYTHONPATH=os.path.join(_ROOT, "src")
               + os.pathsep + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nERR:\n{proc.stderr}"
    return proc.stdout


# -------------------------------------------------------- axis attribution

def test_axis_groups_row_major():
    # conv mesh (2,1,1,2,2): device = 4*b + 2*k + c
    assert axis_groups(CONV_MESH, ("c",)) == frozenset({
        frozenset({0, 1}), frozenset({2, 3}),
        frozenset({4, 5}), frozenset({6, 7})})
    assert axis_groups(CONV_MESH, ("b",)) == frozenset({
        frozenset({0, 4}), frozenset({1, 5}),
        frozenset({2, 6}), frozenset({3, 7})})
    assert axis_groups(CONV_MESH, ("k", "c")) == frozenset({
        frozenset({0, 1, 2, 3}), frozenset({4, 5, 6, 7})})
    # extent-1 axes never change the partition
    assert axis_groups(CONV_MESH, ("k", "h")) \
        == axis_groups(CONV_MESH, ("k",))
    with pytest.raises(ValueError, match="not in mesh"):
        axis_groups(CONV_MESH, ("zz",))


def test_effective_axes_and_normalize():
    assert effective_axes(CONV_MESH, ("h", "w")) == ()
    assert effective_axes(CONV_MESH, ("c", "b")) == ("b", "c")
    assert normalize_mesh_axes({"m": 2, "n": 4}) == (("m", 2), ("n", 4))


def test_orbits():
    assert set(orbits([(0, 1), (1, 0), (2, 3), (3, 2)])) \
        == {frozenset({0, 1}), frozenset({2, 3})}
    assert orbits([(0, 1), (1, 2), (2, 3)]) == (frozenset({0, 1, 2, 3}),)


# ------------------------------------------------- lint units (synthetic)

def test_lint_wire_drift():
    assert lint_wire(100.0, 100.0) == []
    assert lint_wire(101.0, 100.0, rtol=0.02) == []
    bad = lint_wire(120.0, 100.0, rtol=0.02, what="fwd")
    assert errors(bad) and "1.2" in bad[0].message
    assert lint_wire(5.0, 0.0) and lint_wire(0.0, 0.0) == []


def test_lint_footprint_memory_band():
    ok = lint_footprint((), schedule="ring2", contraction_axes=("b", "k"),
                        live=100.0, analytic=100.0, mem_band=(0.4, 1.6))
    assert ok == []
    bad = lint_footprint((), schedule="ring2", contraction_axes=("b", "k"),
                         live=500.0, analytic=100.0, mem_band=(0.4, 1.6))
    assert errors(bad)


def test_finding_str():
    f = Finding("wire", "error", "drifted")
    assert "wire" in str(f) and "error" in str(f)


# ----------------------------------------------------------- AST lint

def test_astlint_repo_is_clean():
    findings = astlint.lint_tree(astlint.default_root())
    assert findings == [], "\n".join(str(f) for f in findings)


def test_astlint_flags_raw_collectives(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""
        import jax
        import jax.lax as jlx
        from jax import lax
        from jax.lax import psum_scatter as ps

        def f(x):
            a = jax.lax.psum(x, "i")
            b = lax.ppermute(x, "i", [(0, 1)])
            c = jlx.all_gather(x, "i")
            d = ps(x, "i")
            e = lax.pmean(x, "i")  # raw-collective-ok
            f = lax.axis_index("i")     # free, never flagged
            return a + b + c + d + e + f
    """))
    found = astlint.lint_file(str(bad))
    names = sorted(f.name for f in found)
    assert names == ["all_gather", "ppermute", "psum", "psum_scatter"]
    # the pragma'd pmean and the non-collective axis_index are exempt
    assert all("pmean" != f.name and "axis_index" != f.name
               for f in found)


def test_astlint_tree_skips_collectives_py(tmp_path):
    pkg = tmp_path / "dist"
    pkg.mkdir()
    (pkg / "collectives.py").write_text(
        "from jax import lax\ndef f(x):\n    return lax.psum(x, 'i')\n")
    (pkg / "other.py").write_text(
        "from jax import lax\ndef f(x):\n    return lax.psum(x, 'i')\n")
    found = astlint.lint_tree(str(tmp_path))
    assert len(found) == 1 and found[0].path.endswith("other.py")


# ----------------------------------------------------------- doc lint

def test_doclint_repo_is_clean():
    from repro.analysis import doclint
    findings = doclint.lint_tree(doclint.default_root())
    assert findings == [], "\n".join(str(f) for f in findings)


def test_doclint_flags_stale_references(tmp_path):
    from repro.analysis import doclint
    (tmp_path / "Makefile").write_text("test:\n\techo hi\n")
    src = tmp_path / "src" / "repro"
    src.mkdir(parents=True)
    (src / "x.py").write_text("import os\nos.environ['REPRO_REAL']\n")
    (tmp_path / "README.md").write_text(textwrap.dedent("""
        Run `make test` then `make bogus-target`.
        Set `REPRO_REAL=1` or REPRO_MISSING.
        See `src/repro/x.py` and `src/repro/gone.py`.
        Try `python -m repro.x` and `python -m repro.gone`.
        Prose about make targets is not a reference.
        ```
        make test
        ```
    """))
    found = doclint.lint_tree(str(tmp_path))
    msgs = sorted(f.message for f in found)
    assert len(msgs) == 4, msgs
    assert any("bogus-target" in m for m in msgs)
    assert any("REPRO_MISSING" in m for m in msgs)
    assert any("src/repro/gone.py" in m for m in msgs)
    assert any("repro.gone" in m for m in msgs)


# ===================================================== 8-device compiled ==

@pytest.mark.subprocess
def test_verifier_acceptance_cells_8dev():
    """The flagship 2.5D conv ring2 cell and the 3D matmul ring cell
    pass every lint (fwd + VJP) with wire ratio 1.00."""
    run_in_subprocess("""
        from repro.analysis.verify import (verify_conv_cell,
                                           verify_matmul_cell)
        cells = verify_conv_cell((2, 1, 1, 2, 2), "ring2") \\
            + verify_matmul_cell((2, 2, 2), "ring")
        for c in cells:
            assert c.ok, (c.name, [str(f) for f in c.findings])
            assert abs(c.wire_ratio - 1.0) < 0.02, (c.name, c.wire_ratio)
        print("ok")
    """)


@pytest.mark.subprocess
def test_seeded_deadlock_regression_8dev():
    """A ring hop missing its closing edge — compiles fine, hangs SPMD
    peers at runtime — must fail the deadlock lint; the total rotation
    and a plain (untagged) halo shift must pass."""
    run_in_subprocess("""
        from jax.sharding import PartitionSpec as P
        from repro.analysis.collect import extract_collectives
        from repro.analysis.lints import errors, lint_deadlock
        from jax import shard_map
        from repro.dist.collectives import (make_mesh, ppermute,
                                            record_collectives)

        mesh = make_mesh((4,), ("r",))
        axes = {"r": 4}

        def compile_perm(perm, tag):
            def body(x):
                return ppermute(x, "r", perm, tag=tag)
            fn = shard_map(body, mesh=mesh, in_specs=P("r"),
                           out_specs=P("r"))
            with record_collectives() as notes:
                low = jax.jit(fn).lower(
                    jax.ShapeDtypeStruct((8, 64), jnp.float32))
            colls = extract_collectives(low.compile().as_text(), axes)
            return colls, list(notes)

        # seeded regression: ring hop dropped the closing edge
        bad = [(i, (i + 1) % 4) for i in range(3)]
        colls, notes = compile_perm(bad, "ring_zip")
        errs = errors(lint_deadlock(colls, axes, notes))
        assert errs, "deadlock lint missed the non-bijective ring hop"
        assert any("bijection" in str(e) for e in errs), errs

        # a partial-but-bijective sub-ring starves ranks 2,3: also fails
        colls, notes = compile_perm([(0, 1), (1, 0)], "ring_reduce")
        errs = errors(lint_deadlock(colls, axes, notes))
        assert errs, "deadlock lint missed the partial sub-ring"

        # the total rotation passes
        good = [(i, (i + 1) % 4) for i in range(4)]
        colls, notes = compile_perm(good, "ring_zip")
        assert not lint_deadlock(colls, axes, notes)

        # an untagged halo-style shift is legal (no false positive)
        colls, notes = compile_perm([(0, 1), (1, 2), (2, 3)], "halo")
        assert not lint_deadlock(colls, axes, notes)
        print("ok")
    """)


@pytest.mark.subprocess
def test_seeded_footprint_regression_8dev():
    """A cell that *claims* the ring2 slab-memory schedule but compiles
    an all-gather on a contraction axis must fail the footprint lint."""
    run_in_subprocess("""
        from jax.sharding import PartitionSpec as P
        from repro.analysis.collect import extract_collectives
        from repro.analysis.lints import errors, lint_footprint
        from jax import shard_map
        from repro.dist.collectives import gather_axis, make_mesh

        mesh = make_mesh((4,), ("k",))
        axes = {"k": 4}

        def body(x):  # a gathered contraction operand
            return gather_axis(x, "k", dim=0, schedule="allgather")

        fn = shard_map(body, mesh=mesh, in_specs=P("k"),
                       out_specs=P(None), check_vma=False)
        text = jax.jit(fn).lower(
            jax.ShapeDtypeStruct((8, 64), jnp.float32)).compile().as_text()
        colls = extract_collectives(text, axes)
        assert any(c.kind == "all-gather" for c in colls)

        # declared ring2 -> the gathered operand is a broken promise
        errs = errors(lint_footprint(colls, schedule="ring2",
                                     contraction_axes=("b", "k")))
        assert errs, "footprint lint missed the gathered operand"
        assert "all-gather" in str(errs[0])

        # the same IR is fine under its true (gather) schedule
        assert not lint_footprint(colls, schedule="allgather",
                                  contraction_axes=("b", "k"))
        print("ok")
    """)


@pytest.mark.subprocess
def test_loop_ring_attribution_8dev():
    """Rings of size >= 3 compile to fori_loops: extraction must find
    the loop-body ppermute, multiply it by the trip count, and still
    attribute it to the ring axis."""
    run_in_subprocess("""
        from repro.analysis.collect import extract_collectives
        from repro.analysis.lints import errors, lint_deadlock
        from repro.dist.collectives import make_mesh
        from repro.dist.matmul import matmul_distributed

        mesh = make_mesh((1, 8, 1), ("m", "n", "c"))
        a = jax.ShapeDtypeStruct((32, 64), jnp.float32)
        b = jax.ShapeDtypeStruct((64, 16), jnp.float32)
        text = jax.jit(lambda p, q: matmul_distributed(
            p, q, mesh, schedule="ring")).lower(a, b).compile().as_text()
        colls = extract_collectives(text, dict(mesh.shape))
        perms = [c for c in colls if c.kind == "collective-permute"]
        assert perms, "no ppermute extracted from the 8-ring"
        assert all(c.axes == ("n",) for c in perms), perms
        # one hop in the loop body, 7 trips
        assert sum(c.mult for c in perms) >= 7, perms
        assert not errors(lint_deadlock(colls, dict(mesh.shape)))
        print("ok")
    """)
