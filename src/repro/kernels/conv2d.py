"""Pallas TPU direct-conv kernel (stride 1, NCHW in and out).

TPU-native realization of the paper's tiled CNN (Listing 3) at the
HBM->VMEM level: grid (i, j, q) over (batch tiles, k tiles, c slabs); the
Out tile stays resident in a VMEM f32 scratch across the sequential c slabs
(the paper's "store Out once" schedule), while In/Ker tiles stream in.

Inside the kernel the operands are channels-last (the wrapper transposes
NCHW -> NHWC and OIHW -> HWIO around the call), so channels ride the
128-lane axis and the image width the sublanes.  Each output row is
``kh*kw`` MXU matmuls of shape ``(Wo, Tc) @ (Tc, Tk)`` — the shifted
input row against the (r, s) slice of the kernel — accumulated in f32.
Keeping every in-kernel access a plain row load avoids the minor-dim
transposes and reshapes the TPU compiler cannot lower cheaply.

Spatial dims stay whole inside the block (blocking h/w would need
overlapping halo reads that Pallas blocked indexing cannot express), so
the padded image slab of one block must fit VMEM: ``kernels.tiling``
``fit_conv_blocks`` decides which shapes the kernel can hold.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _conv_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_c: int, kh: int, kw: int):
    # x_ref [Tb,Hp,Wp,Tc]  w_ref [kh,kw,Tc,Tk]  o_ref/acc_ref [Tb,Ho,Wo,Tk]
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    tb, ho, wo, tk = acc_ref.shape

    def out_row(t, carry):
        b, i = t // ho, t % ho
        acc = jnp.zeros((wo, tk), jnp.float32)
        for r in range(kh):
            for s in range(kw):
                lhs = x_ref[b, i + r, pl.ds(s, wo), :]        # [Wo,Tc]
                acc += jnp.dot(lhs, w_ref[r, s],
                               preferred_element_type=jnp.float32)
        acc_ref[b, i] += acc
        return carry

    lax.fori_loop(0, tb * ho, out_row, 0)

    @pl.when(pl.program_id(2) == n_c - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def conv2d_pallas(x: jax.Array, w: jax.Array, *, block_b: int = 1,
                  block_k: int = 128, block_c: int = 128,
                  padding: str = "SAME",
                  interpret: bool = False) -> jax.Array:
    """stride-1 conv: x [N,C,H,W], w [K,C,kh,kw] -> [N,K,H',W'].

    ``padding="SAME"`` zero-pads to the input spatial extent;
    ``padding="VALID"`` runs the kernel on the raw input (H' = H - kh + 1),
    which is the form every per-step contraction of the distributed
    schedules takes after halo windowing."""
    n, c, h, wd = x.shape
    k, c2, kh, kw = w.shape
    assert c == c2
    bb, bk, bc = min(block_b, n), min(block_k, k), min(block_c, c)
    assert n % bb == 0 and k % bk == 0 and c % bc == 0, (n, k, c, bb, bk, bc)
    if padding == "SAME":
        ph, pw = (kh - 1) // 2, (kw - 1) // 2
        xp = jnp.pad(x, ((0, 0), (0, 0), (ph, kh - 1 - ph),
                         (pw, kw - 1 - pw)))
    elif padding == "VALID":
        xp = x
    else:
        raise ValueError(f"padding must be SAME or VALID, got {padding!r}")
    hp, wp = xp.shape[2], xp.shape[3]
    ho, wo = hp - kh + 1, wp - kw + 1
    n_c = c // bc
    out = pl.pallas_call(
        functools.partial(_conv_kernel, n_c=n_c, kh=kh, kw=kw),
        grid=(n // bb, k // bk, n_c),
        in_specs=[
            pl.BlockSpec((bb, hp, wp, bc), lambda i, j, q: (i, 0, 0, q)),
            pl.BlockSpec((kh, kw, bc, bk), lambda i, j, q: (0, 0, q, j)),
        ],
        out_specs=pl.BlockSpec((bb, ho, wo, bk), lambda i, j, q: (i, 0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((n, ho, wo, k), x.dtype),
        scratch_shapes=[pltpu.VMEM((bb, ho, wo, bk), jnp.float32)],
        interpret=interpret,
    )(xp.transpose(0, 2, 3, 1), w.transpose(2, 3, 1, 0))
    return out.transpose(0, 3, 1, 2)
