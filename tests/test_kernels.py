"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.problem import resnet50_layers
from repro.kernels import ops as kops
from repro.kernels import tiling
from repro.kernels.conv2d import conv2d_pallas
from repro.kernels.matmul import matmul_pallas
from repro.kernels.ops import conv2d_same, math_gcd_block, matmul
from repro.kernels.ref import ref_conv2d, ref_matmul
from repro.kernels.tiling import plan_blocks


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("m,n,k", [(128, 128, 128), (256, 512, 384),
                                   (512, 128, 1024), (128, 384, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_pallas_sweep(m, n, k, dtype):
    kx, kw = jax.random.split(jax.random.PRNGKey(m + n + k))
    x = jax.random.normal(kx, (m, k), dtype)
    w = jax.random.normal(kw, (k, n), dtype)
    bm, bn, bk = (math_gcd_block(m, 128), math_gcd_block(n, 128),
                  math_gcd_block(k, 256))
    out = matmul_pallas(x, w, block_m=bm, block_n=bn, block_k=bk,
                        interpret=True)
    ref = ref_matmul(x, w)
    np.testing.assert_allclose(out.astype(np.float32),
                               ref.astype(np.float32), **_tol(dtype))


@pytest.mark.parametrize("n,c,hw,k,ks", [(2, 8, 8, 8, 3), (4, 16, 14, 32, 3),
                                         (2, 32, 7, 16, 5), (1, 8, 10, 8, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_conv2d_pallas_sweep(n, c, hw, k, ks, dtype):
    kx, kw = jax.random.split(jax.random.PRNGKey(n * c + k))
    x = jax.random.normal(kx, (n, c, hw, hw), dtype)
    w = jax.random.normal(kw, (k, c, ks, ks), dtype)
    out = conv2d_pallas(x, w, block_b=min(2, n), block_k=min(8, k),
                        block_c=min(8, c), interpret=True)
    ref = ref_conv2d(x, w)
    np.testing.assert_allclose(out.astype(np.float32),
                               ref.astype(np.float32), **_tol(dtype))


def test_conv2d_accumulates_over_c_blocks():
    """Multiple contraction slabs exercise the VMEM-scratch accumulation."""
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (2, 64, 8, 8), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 64, 3, 3), jnp.float32)
    out = conv2d_pallas(x, w, block_b=2, block_k=16, block_c=16,
                        interpret=True)
    np.testing.assert_allclose(out, ref_conv2d(x, w), rtol=1e-4, atol=1e-4)


def test_ops_wrappers_dispatch():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (4, 16, 14, 14), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 16, 3, 3), jnp.float32)
    out = conv2d_same(x, w)
    ref = conv2d_same(x, w, use_pallas=False)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)

    xm = jax.random.normal(key, (256, 256), jnp.float32)
    wm = jax.random.normal(jax.random.PRNGKey(2), (256, 512), jnp.float32)
    np.testing.assert_allclose(matmul(xm, wm), ref_matmul(xm, wm),
                               rtol=1e-4, atol=1e-4)


def test_block_plan_fits_vmem_and_aligns():
    """Paper-derived BlockSpec plans: VMEM feasibility + MXU alignment."""
    for name, p in resnet50_layers(32).items():
        plan = plan_blocks(p)
        assert plan.vmem_elems <= 16 * 1024 * 1024, name
        assert plan.block_k == p.Nk or plan.block_k % 128 == 0, name
        assert plan.block_bhw == p.Nbhw or plan.block_bhw % 128 == 0, name


def test_block_plan_traffic_decreases_with_vmem():
    p = resnet50_layers(32)["res4a_2b"]
    small = plan_blocks(p, vmem_elems=1 << 20)
    big = plan_blocks(p, vmem_elems=1 << 24)
    assert big.hbm_traffic <= small.hbm_traffic


# ------------------------------------------- chip fit: tiling + VMEM rules


@pytest.mark.parametrize("m,n,k,itemsize", [
    (4, 128256, 2048, 2), (128, 8192, 2048, 2), (401408, 64, 576, 4),
    (24, 40, 56, 4), (2304, 512, 25088, 4)])
def test_matmul_plan_tiles_and_fits_vmem(m, n, k, itemsize):
    bm, bn, bk = kops.matmul_plan(m, n, k, itemsize)
    sub = tiling.sublanes(itemsize)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    assert bm == m or bm % sub == 0
    assert bn == n or bn % 128 == 0
    assert bk == k or bk % 128 == 0
    assert tiling.matmul_vmem_bytes(bm, bn, bk, itemsize) \
        <= tiling.VMEM_BUDGET_BYTES


def test_exclusion_rules_name_the_rule():
    f32 = jnp.float32
    assert kops.conv_exclusion((32, 64, 226, 226), (64, 64, 3, 3), (1, 1),
                               "VALID", f32) == "image slab does not fit VMEM"
    assert kops.conv_exclusion((32, 128, 58, 58), (128, 128, 3, 3), (1, 1),
                               "VALID", f32) is None
    assert "taps" in kops.conv_exclusion((64, 32, 58, 58), (64, 32, 56, 56),
                                         (1, 1), "VALID", f32)
    assert kops.conv_exclusion((4, 8, 9, 9), (8, 8, 3, 3), (2, 2),
                               "VALID", f32) == "stride != 1"
    assert kops.matmul_exclusion(15, 16, 16) == "extent % 8 != 0"
    assert kops.matmul_exclusion(4, 128256, 2048, jnp.bfloat16) is None
    # a lane extent with no 128-multiple divisor must be one block, and
    # that block exceeds VMEM
    assert kops.matmul_exclusion(8, 1_000_008, 8) == \
        "no tiled block fits VMEM"


def test_dispatch_table_records_choice_and_exclusion():
    a = jnp.ones((15, 16), jnp.float32)
    b = jnp.ones((16, 16), jnp.float32)
    kops.local_matmul(a, b)
    key = kops.matmul_key(15, 16, 16, jnp.float32)
    assert key.endswith("@" + jax.devices()[0].device_kind)
    ent = kops.dispatch_table()[key]
    assert ent["impl"] == "xla"
    assert ent["excluded"] == {"pallas": "extent % 8 != 0"}
    assert ent["refused"] == {}
