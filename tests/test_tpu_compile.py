"""Compile rehearsals for the TPU v5e: the Pallas kernels of the main path,
at real widths, compiled for a described (not attached) ``v5e:2x2`` chip.

Interpret mode (the CPU path of every other kernel test) cannot show what
the chip's compiler refuses: blocks that break the (sublane, 128) tiling
or that do not fit VMEM.  These compiles can.  Each one takes a second or
two; nothing runs, so they say nothing about results or times.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and every pytest
worker imports this file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.problem import resnet50_layers
from repro.kernels import ops as kops
from repro.kernels.conv2d import conv2d_pallas
from repro.kernels.matmul import matmul_pallas
from repro.kernels.winograd import wino_gemm_pallas

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's compile is written to the persistent cache but
    # cannot be read back without the chip; keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prior)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


# llama3.2-1b at 4 decode slots: the projections of one decode step
# ([slots, d_in] @ [d_in, d_out]), the LM head last.
LLAMA_DECODE = [(4, 2048, 2048), (4, 2048, 512), (4, 2048, 8192),
                (4, 8192, 2048), (4, 2048, 128256)]


@pytest.mark.parametrize("m,k,n", LLAMA_DECODE)
def test_matmul_pallas_compiles_for_llama_decode(one_chip, m, k, n):
    blocks = kops.matmul_plan(m, n, k, 2)
    assert blocks is not None and kops.pallas_applicable_matmul(m, n, k, BF16)
    bm, bn, bk = blocks
    fn = functools.partial(matmul_pallas, block_m=bm, block_n=bn,
                           block_k=bk)
    _assert_kernel(_compile(fn, one_chip, ((m, k), BF16), ((k, n), BF16)))


def test_conv2d_pallas_compiles_at_resnet_stage(one_chip):
    """res3a_2b (28x28, 128 -> 128), the VALID form the dist schedules
    hand the kernel after halo windowing."""
    p = resnet50_layers(batch=32)["res3a_2b"]
    xs = (p.Nb, p.Nc, p.Nh + 2, p.Nw + 2)
    ws = (p.Nk, p.Nc, 3, 3)
    for dtype in (jnp.float32, BF16):
        assert kops.pallas_applicable_conv(xs, ws, (1, 1), "VALID", dtype)
        bb, bk, bc = kops.conv_plan(*xs[:2], ws[0], *xs[2:], 3, 3,
                                    jnp.dtype(dtype).itemsize, "VALID")
        fn = functools.partial(conv2d_pallas, block_b=bb, block_k=bk,
                               block_c=bc, padding="VALID")
        _assert_kernel(_compile(fn, one_chip, (xs, dtype), (ws, dtype)))


def test_wino_gemm_pallas_compiles(one_chip):
    """The Winograd tile GEMM of res3a_2b at batch 32: P = 32*14*14 tiles."""
    p, c, k = 32 * 14 * 14, 128, 128
    assert kops.pallas_applicable_matmul(p, k, c, BF16)
    bp, bk, bc = kops.matmul_plan(p, k, c, 2)
    fn = functools.partial(wino_gemm_pallas, block_p=bp, block_k=bk,
                           block_c=bc)
    _assert_kernel(_compile(fn, one_chip, ((16, p, c), BF16),
                            ((16, c, k), BF16)))


def test_rejected_plans_are_refused_by_the_compiler(one_chip):
    """The shape rules reject exactly what the chip cannot hold: the
    llama3.2-1b LM-head blocks the paper plan alone picks, (4, 64128,
    256), exhaust VMEM, and so does the direct conv's whole padded
    224x224 image slab (conv1 width, 64 channels)."""
    from jax.errors import JaxRuntimeError
    from repro.kernels import tiling

    m, k, n = 4, 2048, 128256
    assert tiling.matmul_vmem_bytes(4, 64128, 256, 2) > \
        tiling.VMEM_BUDGET_BYTES
    fn = functools.partial(matmul_pallas, block_m=4, block_n=64128,
                           block_k=256)
    with pytest.raises(JaxRuntimeError, match="vmem"):
        _compile(fn, one_chip, ((m, k), BF16), ((k, n), BF16))

    xs, ws = (32, 64, 226, 226), (64, 64, 3, 3)
    assert kops.conv_exclusion(xs, ws, (1, 1), "VALID", BF16) == \
        "image slab does not fit VMEM"
    fn = functools.partial(conv2d_pallas, block_b=1, block_k=64,
                           block_c=64, padding="VALID")
    with pytest.raises(JaxRuntimeError, match="vmem"):
        _compile(fn, one_chip, (xs, BF16), (ws, BF16))
