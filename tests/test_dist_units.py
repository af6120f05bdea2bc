"""Fast in-process unit tests for ``repro.dist`` (single device — the main
pytest process keeps the 1-device dry-run view, so these cover the shape
logic, boundary/zero-fill semantics, multi-hop halo assembly, the
compressor math, and the full conv/matmul code path on trivial grids.
The real 8-device exchanges live in the ``subprocess``-marked suite."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.dist.compress import (_quantize_int8, _topk_mask,
                                 compressed_psum, compressed_psum_tree)
from repro.dist.conv2d import (_pad_amounts, conv2d_distributed,
                               conv_comm_elems, make_conv_mesh)
from repro.dist.halo import halo_exchange_1d
from repro.dist.matmul import (make_matmul_mesh, matmul_comm_elems,
                               matmul_distributed)


def _mesh1(axis="x"):
    return Mesh(np.array(jax.devices()[:1]), (axis,))


def _run_sharded(f, *args, axis="x"):
    mesh = _mesh1(axis)
    specs = tuple(P(axis) for _ in args)
    return jax.shard_map(f, mesh=mesh, in_specs=specs,
                         out_specs=P(axis), check_vma=False)(*args)


# -------------------------------------------------------------------- halo

def test_halo_noop_when_lo_hi_zero():
    x = jnp.arange(12.0).reshape(4, 3)
    out = _run_sharded(
        lambda xl: halo_exchange_1d(xl, "x", spatial_dim=0, lo=0, hi=0), x)
    np.testing.assert_array_equal(out, x)


def test_halo_single_rank_is_zero_padding():
    x = jnp.arange(1.0, 5.0).reshape(4, 1)
    out = _run_sharded(
        lambda xl: halo_exchange_1d(xl, "x", spatial_dim=0, lo=2, hi=3), x)
    assert out.shape == (9, 1)
    np.testing.assert_array_equal(out[:2], 0.0)
    np.testing.assert_array_equal(out[2:6], x)
    np.testing.assert_array_equal(out[6:], 0.0)


def test_halo_shard_smaller_than_halo():
    # lo/hi wider than the 4-row shard: multi-hop path; past the global
    # boundary everything must be zero-filled
    x = jnp.arange(1.0, 5.0).reshape(4, 1)
    out = _run_sharded(
        lambda xl: halo_exchange_1d(xl, "x", spatial_dim=0, lo=6, hi=9), x)
    assert out.shape == (4 + 6 + 9, 1)
    np.testing.assert_array_equal(out[:6], 0.0)
    np.testing.assert_array_equal(out[6:10], x)
    np.testing.assert_array_equal(out[10:], 0.0)


def test_halo_rejects_negative_width():
    x = jnp.zeros((4, 1))
    with pytest.raises(ValueError):
        _run_sharded(
            lambda xl: halo_exchange_1d(xl, "x", spatial_dim=0, lo=-1, hi=0),
            x)


# ------------------------------------------------------------- pad amounts

@pytest.mark.parametrize("size,k,s", [(16, 3, 1), (17, 3, 2), (16, 4, 1),
                                      (17, 5, 3), (7, 7, 1)])
@pytest.mark.parametrize("padding", ["SAME", "VALID"])
def test_pad_amounts_match_xla(size, k, s, padding):
    lo, hi, out = _pad_amounts(size, k, s, padding)
    x = jnp.zeros((1, 1, size, size))
    w = jnp.zeros((1, 1, k, k))
    ref = lax.conv_general_dilated(
        x, w, (s, s), padding, dimension_numbers=("NCHW", "OIHW", "NCHW"))
    assert out == ref.shape[2]
    if padding == "SAME":
        assert lo + hi == max((out - 1) * s + k - size, 0)
    else:
        assert (lo, hi) == (0, 0)


# ---------------------------------------------------------------- compress

def test_int8_quantization_error_bound():
    v = jax.random.normal(jax.random.PRNGKey(0), (128,))
    dq = _quantize_int8(v)
    scale = float(jnp.max(jnp.abs(v))) / 127.0
    assert float(jnp.max(jnp.abs(v - dq))) <= scale / 2 + 1e-7


def test_topk_mask_keeps_largest():
    v = jnp.array([0.1, -5.0, 0.3, 2.0, -0.2, 1.0])
    mask = _topk_mask(v, 0.5)
    np.testing.assert_array_equal(mask, [0, 1, 0, 1, 0, 1])


def test_compressed_psum_error_feedback_converges():
    # top-k keeps 25% per step; with error feedback the accumulated applied
    # update must approach the true gradient as steps accumulate
    g = jax.random.normal(jax.random.PRNGKey(3), (1, 64))

    def f(gl, el):
        return compressed_psum(gl, "x", el, k_frac=0.25)

    mesh = _mesh1()
    fn = jax.shard_map(f, mesh=mesh, in_specs=(P("x"), P("x")),
                       out_specs=(P("x"), P("x")), check_vma=False)
    e = jnp.zeros_like(g)
    applied = jnp.zeros_like(g)
    errs = []
    for t in range(1, 9):
        out, e = fn(g, e)
        applied = applied + out
        errs.append(float(jnp.max(jnp.abs(applied / t - g))))
    # EF keeps the residual bounded, so the time-averaged error decays ~1/t
    assert errs[-1] < errs[0] / 2
    assert errs[-1] < 0.15 * float(jnp.max(jnp.abs(g)))


def test_compressed_psum_preserves_err_dtype():
    # the error-feedback state must round-trip through steps unchanged:
    # bf16 grads in -> bf16 residual out (no silent f32 upcast)
    g = jax.random.normal(jax.random.PRNGKey(5), (32,), jnp.bfloat16)

    def f(gl, el):
        return compressed_psum(gl, "x", el)

    mesh = _mesh1()
    fn = jax.shard_map(f, mesh=mesh, in_specs=(P("x"), P("x")),
                       out_specs=(P("x"), P("x")), check_vma=False)
    out, err = fn(g, jnp.zeros_like(g))
    assert err.dtype == jnp.bfloat16
    assert out.dtype == jnp.bfloat16
    out, err = fn(g, err)  # state feeds back without dtype mismatch
    assert err.dtype == jnp.bfloat16


def test_compressed_psum_tree_shapes_and_none_err():
    grads = {"a": jnp.ones((4,)), "b": {"c": jnp.full((2, 3), 2.0)}}

    def f(gl):
        red, err = compressed_psum_tree(gl, "x", None)
        return jax.tree.map(lambda r, e: r + 0 * e, red, err)

    mesh = _mesh1()
    spec = jax.tree.map(lambda _: P(), grads)
    fn = jax.shard_map(f, mesh=mesh, in_specs=(spec,), out_specs=spec,
                       check_vma=False)
    out = fn(grads)
    assert jax.tree.structure(out) == jax.tree.structure(grads)
    # int8 round-trip of a constant tensor is exact (max|v| maps to 127)
    np.testing.assert_allclose(out["a"], grads["a"], atol=1e-6)


def test_compressed_psum_tree_handles_tuple_pytrees():
    # structural tuples in the grads pytree must not be confused with the
    # (reduced, err) result pairs
    grads = (jnp.ones((3,)), {"w": (jnp.full((2,), 2.0), jnp.ones((4,)))})

    def f(gl):
        red, _ = compressed_psum_tree(gl, "x", None)
        return red

    mesh = _mesh1()
    spec = jax.tree.map(lambda _: P(), grads)
    out = jax.shard_map(f, mesh=mesh, in_specs=(spec,), out_specs=spec,
                        check_vma=False)(grads)
    assert jax.tree.structure(out) == jax.tree.structure(grads)
    np.testing.assert_allclose(out[1]["w"][0], grads[1]["w"][0], atol=1e-6)


# ------------------------------------------------- full ops, trivial grids

def test_conv2d_distributed_single_device_paths():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (2, 4, 9, 9), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 4, 3, 3), jnp.float32)
    mesh = make_conv_mesh((1, 1, 1, 1, 1))
    for stride, padding in [((1, 1), "SAME"), ((2, 2), "VALID"),
                            ((1, 1), ((0, 2), (2, 0)))]:
        ref = lax.conv_general_dilated(
            x, w, stride, padding if isinstance(padding, str)
            else tuple(padding),
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        out = conv2d_distributed(x, w, mesh, stride=stride, padding=padding)
        assert float(jnp.max(jnp.abs(out - ref))) < 1e-4, (stride, padding)


def test_matmul_distributed_single_device():
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (8, 6), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (6, 10), jnp.float32)
    mesh = make_matmul_mesh((1, 1, 1))
    out = matmul_distributed(a, b, mesh)
    assert float(jnp.max(jnp.abs(out - a @ b))) < 1e-5


def test_shape_validation_errors():
    mesh = make_conv_mesh((1, 1, 1, 1, 1))
    x = jnp.zeros((2, 4, 9, 9))
    w = jnp.zeros((4, 5, 3, 3))  # channel mismatch
    with pytest.raises(ValueError):
        conv2d_distributed(x, w, mesh)
    with pytest.raises(ValueError):
        make_conv_mesh((2, 2))  # wrong arity
    with pytest.raises(ValueError):
        make_matmul_mesh((1, 1))
    with pytest.raises(ValueError):
        conv2d_distributed(x, jnp.zeros((4, 4, 3, 3)), mesh,
                           schedule="bogus")


# -------------------------------------------------------- analytic volumes

def test_matmul_comm_elems_accounting():
    v = matmul_comm_elems(512, 256, 256, (2, 2, 2))
    assert v["gather_in"] == 512 * 256 / 8    # shard * (Pn-1)
    assert v["gather_ker"] == 256 * 256 / 8
    assert v["reduce_out"] == 2 * 256 * 128 / 2
    v2d = matmul_comm_elems(512, 256, 256, (8, 1, 1))
    assert v2d["gather_in"] == 0 and v2d["reduce_out"] == 0
    assert v2d["gather_ker"] > 0


def test_conv_comm_elems_accounting():
    # pure data parallel: only the kernel gather moves bytes
    v = conv_comm_elems((8, 32, 16, 16), (32, 32, 3, 3), (8, 1, 1, 1, 1))
    assert v["gather_in"] == 0 and v["reduce_out"] == 0 and v["halo"] == 0
    assert v["gather_ker"] == 32 * 32 * 9 / 8 * 7
    # pure contraction split: only the output all-reduce
    v = conv_comm_elems((8, 32, 16, 16), (32, 32, 3, 3), (1, 1, 1, 1, 8))
    assert v["gather_in"] == 0 and v["gather_ker"] == 0
    assert v["reduce_out"] > 0
    # spatial split pays halo
    v = conv_comm_elems((8, 32, 16, 16), (32, 32, 3, 3), (1, 2, 2, 1, 1))
    assert v["halo"] > 0
