"""Ring-primitive structure: odd ring sizes (3 and 5) for the
rotating-gather / scatter-reduce primitives, ``ring_zip`` ring-size
validation, ``conv/matmul_ring2_supported`` edge cases (Cannon-skew
grids must report unsupported and fall back, never mis-schedule), and
the trace-time ``record_collectives`` attribution table.
"""

import os
import subprocess
import sys
import textwrap

import pytest

from repro.dist.conv2d import (_conv_effective_schedule,
                               conv_ring2_supported)
from repro.dist.matmul import (_matmul_effective_schedule,
                               matmul_ring2_supported)

pytestmark = pytest.mark.static

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_in_subprocess(body: str, devices: int = 8):
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \\
            "--xla_force_host_platform_device_count={devices}"
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


# -------------------------------------------------- ring2 support matrix

def test_conv_ring2_supported_edge_cases():
    # trivial ring on either contraction side
    assert conv_ring2_supported((1, 1, 1, 1, 1))
    assert conv_ring2_supported((1, 4, 2, 1, 1))
    assert conv_ring2_supported((1, 1, 1, 8, 1))
    # both rings of 2 (the Cannon-free special case)
    assert conv_ring2_supported((2, 4, 4, 2, 8))
    # Cannon-skew territory: equal rings > 2 and unequal rings
    assert not conv_ring2_supported((3, 1, 1, 3, 1))
    assert not conv_ring2_supported((4, 1, 1, 4, 1))
    assert not conv_ring2_supported((2, 1, 1, 3, 1))
    assert not conv_ring2_supported((3, 2, 2, 2, 1))


def test_matmul_ring2_supported_edge_cases():
    assert matmul_ring2_supported((1, 1, 1))
    assert matmul_ring2_supported((2, 2, 8))
    assert matmul_ring2_supported((1, 5, 1))
    assert not matmul_ring2_supported((3, 3, 1))
    assert not matmul_ring2_supported((2, 4, 1))
    assert not matmul_ring2_supported((5, 2, 1))


def test_effective_schedule_falls_back_to_ring():
    # unsupported grids silently run the one-ring schedule instead —
    # the predicate and the dispatch must agree
    assert _conv_effective_schedule("ring2", (4, 1, 1, 2, 1)) == "ring"
    assert _conv_effective_schedule("ring2", (2, 1, 1, 2, 2)) == "ring2"
    assert _conv_effective_schedule("ring", (4, 1, 1, 2, 1)) == "ring"
    assert _matmul_effective_schedule("ring2", (4, 2, 1)) == "ring"
    assert _matmul_effective_schedule("ring2", (2, 2, 2)) == "ring2"
    assert _matmul_effective_schedule("allgather", (4, 2, 1)) \
        == "allgather"


# ------------------------------------------------------- odd ring sizes

@pytest.mark.subprocess
def test_ring_primitives_odd_sizes_8dev():
    """ring_all_gather / ring_reduce_scatter / ring_scatter_reduce match
    the one-shot collectives on rings of 3 and 5 (odd sizes exercise the
    fori_loop path and the (me - t) % g source arithmetic)."""
    run_in_subprocess("""
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.dist.collectives import (make_mesh, ring_all_gather,
                                            ring_reduce_scatter,
                                            ring_scatter_reduce)

        for g in (3, 5):
            mesh = make_mesh((g,), ("r",))
            x = jax.random.normal(jax.random.PRNGKey(g), (g * 2, 4))

            gathered = shard_map(
                lambda s: ring_all_gather(s, "r", dim=0),
                mesh=mesh, in_specs=P("r"), out_specs=P(None),
                check_vma=False)(x)
            np.testing.assert_allclose(np.asarray(gathered),
                                       np.asarray(x), rtol=1e-6)

            # reduce-scatter of the replicated x == g * own chunk
            scattered = shard_map(
                lambda _s: ring_reduce_scatter(x, "r", dim=0),
                mesh=mesh, in_specs=P("r"), out_specs=P("r"),
                check_vma=False)(x)
            np.testing.assert_allclose(np.asarray(scattered),
                                       g * np.asarray(x), rtol=1e-5)

            # on-the-fly producer variant: produce(r, t) = chunk r of x
            chunk = x.shape[0] // g
            def rs_body(_s):
                def produce(r, _t):
                    return jax.lax.dynamic_slice_in_dim(
                        x, r * chunk, chunk, axis=0)
                return ring_scatter_reduce("r", produce)
            tok = shard_map(rs_body, mesh=mesh, in_specs=P("r"),
                            out_specs=P("r"), check_vma=False)(x)
            np.testing.assert_allclose(np.asarray(tok),
                                       g * np.asarray(x), rtol=1e-5)
        print("ok")
    """)


@pytest.mark.subprocess
def test_ring_zip_structure_9dev():
    """ring_zip on equal odd rings (3 x 3): the reported source indices
    stay in lockstep with the rotating payloads, and each device visits
    exactly the cross-product diagonal src_a - src_b == ia - ib (mod g);
    a degenerate 1 x 3 zip streams the full cross product per device;
    non-trivial unequal sizes (2 x 3) raise ValueError at trace time."""
    run_in_subprocess("""
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.dist.collectives import make_mesh, ring_zip

        # --- 3 x 3 lockstep structure.  Shards carry their origin rank
        # so payload content can be checked against the reported src.
        mesh = make_mesh((3, 3), ("a", "b"))
        xa = jnp.arange(3.0)
        xb = jnp.arange(3.0)

        def body(sa, sb):
            def fold(acc, t, ia, ca, ib, cb):
                ind = (jax.nn.one_hot(ia, 3)[:, None]
                       * jax.nn.one_hot(ib, 3)[None, :])
                err = jnp.abs(ca[0] - ia) + jnp.abs(cb[0] - ib)
                if acc is None:
                    return ind, err
                return acc[0] + ind, acc[1] + err
            ind, err = ring_zip(sa, "a", sb, "b", fold)
            return ind[None, None], err[None, None]

        ind, err = shard_map(
            body, mesh=mesh, in_specs=(P("a"), P("b")),
            out_specs=(P("a", "b", None, None), P("a", "b")),
            check_vma=False)(xa, xb)
        ind, err = np.asarray(ind), np.asarray(err)
        assert err.max() == 0, err  # payloads match reported sources
        for ia in range(3):
            for ib in range(3):
                m = ind[ia, ib]
                assert m.sum() == 3, (ia, ib, m)
                for p in range(3):
                    for q in range(3):
                        want = (p - q) % 3 == (ia - ib) % 3
                        assert m[p, q] == want, (ia, ib, m)

        # --- 1 x 3 degenerate: the stationary operand streams against
        # the full rotating ring, so a blockwise matmul closes per device
        mesh = make_mesh((1, 3), ("a", "b"))
        xam = jnp.arange(6.0).reshape(1, 6)
        xbm = jnp.arange(12.0).reshape(6, 2)

        def body_mm(sa, sb):
            def fold(acc, t, ia, ca, ib, cb):
                cols = jax.lax.dynamic_slice_in_dim(ca, ib * 2, 2, axis=1)
                part = cols @ cb
                return part if acc is None else acc + part
            return ring_zip(sa, "a", sb, "b", fold)

        out = shard_map(body_mm, mesh=mesh,
                        in_specs=(P("a", None), P("b", None)),
                        out_specs=P(None, None), check_vma=False)(xam, xbm)
        np.testing.assert_allclose(np.asarray(out), np.asarray(xam @ xbm),
                                   rtol=1e-5)

        # --- 2 x 3 must be rejected at trace time
        mesh = make_mesh((2, 3), ("a", "b"))
        try:
            shard_map(body_mm, mesh=mesh,
                      in_specs=(P("a", None), P("b", None)),
                      out_specs=P(None, None), check_vma=False)(
                jnp.zeros((2, 6)), jnp.zeros((6, 2)))
            raise SystemExit("ring_zip accepted a 2 x 3 ring pair")
        except ValueError as e:
            assert "equal or trivial ring sizes" in str(e), e
        print("ok")
    """, devices=9)


@pytest.mark.subprocess
def test_record_collectives_notes_8dev():
    """Tracing under record_collectives yields one note per wrapper
    call with the right kind/axis/tag — the attribution table the
    verifier cross-checks against the compiled IR."""
    run_in_subprocess("""
        from repro.dist.collectives import record_collectives
        from repro.dist.conv2d import conv2d_distributed, make_conv_mesh

        mesh = make_conv_mesh((2, 1, 1, 2, 2))
        xs = jax.ShapeDtypeStruct((8, 128, 8, 8), jnp.float32)
        ws = jax.ShapeDtypeStruct((32, 128, 3, 3), jnp.float32)
        with record_collectives() as notes:
            jax.jit(lambda a, b: conv2d_distributed(
                a, b, mesh, schedule="ring2")).lower(xs, ws)
        kinds = {(n.kind, n.axes) for n in notes}
        assert ("collective-permute", ("b",)) in kinds, notes  # Ker ring
        assert ("collective-permute", ("k",)) in kinds, notes  # In ring
        assert ("all-reduce", ("c",)) in kinds, notes          # Out psum
        assert all(n.tag for n in notes), notes
        # the buffer is scoped: nothing records outside the context
        with record_collectives() as empty:
            pass
        assert empty == []
        print("ok")
    """)
