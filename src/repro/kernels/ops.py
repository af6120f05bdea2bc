"""Local-kernel engine: the candidate menu, the best-of selector, and the
jit'd public wrappers the distributed hot path (`repro.dist`) routes
through.

Every per-step slab contraction of the distributed schedules lands on
:func:`local_conv2d` / :func:`local_matmul`.  Instead of the former
static two-way choice (Pallas-direct when the shape tiles, XLA
otherwise), each dispatcher now consults a runtime autotuner
(``kernels.autotune``, the PyDTNN ``best_of`` idiom): per unique
``(op, shape, dtype, stride, padding)`` key it times every applicable
candidate once, memoizes the winner, and persists the plan table to
``.repro_autotune.json`` so later processes start hot.

The conv candidate menu:

* ``direct``   — ``kernels.conv2d.conv2d_pallas``, the paper's two-level
  tiled direct conv (stride 1, feature dims that tile into >= 8 blocks,
  an image slab that fits VMEM);
* ``winograd`` — ``kernels.winograd.conv2d_winograd``, F(2x2,3x3)
  transforms around a batched 16-frequency tile GEMM (3x3 stride-1, the
  CNN FLOPs hot spot; 2.25x fewer multiplies);
* ``im2col``   — ``kernels.gemm_conv.conv2d_im2col``, the patch-matrix
  GEMM (any stride, any extent; stencils up to ``MAX_STENCIL_TAPS``);
* ``xla``      — ``lax.conv_general_dilated``.

The matmul menu is ``pallas`` (tiled ``matmul_pallas`` with the memoized
paper plan) vs ``xla``; Winograd's batched tile GEMM has its own
``pallas``/``einsum`` menu.  Composite candidates recurse through the
dispatchers — im2col's GEMM *is* ``local_matmul``, so its backend is
autotuned too.

Every Pallas kernel carries a ``jax.custom_vjp`` whose backward runs the
same kernel family on transposed operands (dX of a matmul is a matmul,
dIn/dKer of a stride-1 conv are convs), so every candidate — and hence
every winner — differentiates natively; the dist ``save_gathered=True``
paths no longer force the XLA fallback.

Block shapes come from the paper-derived plan (`kernels.tiling`), fitted
to the chip's (sublane, 128) tiling and VMEM budget and memoized per shape
(`matmul_plan` / `conv_plan`).  A shape no block fits loses its Pallas
candidate before anything is compiled; :func:`matmul_exclusion` /
:func:`conv_exclusion` name the rule, and :func:`dispatch_table` lists
every key with its winner, the excluded Pallas candidate and any
candidate that failed while timed.  On the CPU the Pallas kernels execute
in interpret mode; on TPU they compile to Mosaic.
``REPRO_DIST_PALLAS=0`` removes the Pallas candidates everywhere;
``REPRO_AUTOTUNE=0`` disables the tuner and restores the static
paper-plan dispatch (see ``kernels.autotune``).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.problem import ConvProblem
from repro.kernels import autotune, tiling
from repro.kernels.conv2d import conv2d_pallas
from repro.kernels.gemm_conv import conv2d_im2col
from repro.kernels.matmul import matmul_pallas
from repro.kernels.tiling import math_gcd_block  # noqa: F401 (re-export)
from repro.kernels.winograd import (conv2d_winograd, winograd_applicable,
                                    wino_gemm_einsum, wino_gemm_pallas)

_DIST_PALLAS_ENV = "REPRO_DIST_PALLAS"
_DIMNUMS = ("NCHW", "OIHW", "NCHW")


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _pallas_enabled() -> bool:
    return os.environ.get(_DIST_PALLAS_ENV, "1") != "0"


# --------------------------------------------------------------------------
# Memoized tiling plans (the Eq. 4 solve is pure Python; one per shape)
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def matmul_plan(m: int, n: int, k: int, itemsize: int = 4):
    """Paper-planned ``(block_m, block_n, block_k)`` for an ``[m,k]@[k,n]``
    matmul of ``itemsize``-byte elements, fitted to the chip's tiling and
    VMEM budget (``tiling.fit_matmul_blocks``); ``None`` when no block
    fits."""
    return tiling.fit_matmul_blocks(m, n, k, tiling.matmul_blocks(m, n, k),
                                    itemsize)


@functools.lru_cache(maxsize=None)
def conv_plan(n: int, c: int, k: int, h: int, w: int, kh: int, kw: int,
              itemsize: int = 4, padding: str = "VALID"):
    """Paper-planned ``(block_b, block_k, block_c)`` for an NCHW/OIHW
    stride-1 conv of an ``h x w`` input, fitted to the chip's tiling and
    VMEM budget (``tiling.fit_conv_blocks``); ``None`` when even the
    narrowest legal block does not fit."""
    prob = ConvProblem.from_conv_layer(batch=n, cin=c, cout=k, h=h, w=w,
                                       kh=kh, kw=kw)
    plan = tiling.plan_blocks(prob)
    want = (max(1, plan.block_bhw // (h * w)), plan.block_k, plan.block_c)
    hp, wp = (h + kh - 1, w + kw - 1) if padding == "SAME" else (h, w)
    return tiling.fit_conv_blocks(n, c, k, hp, wp, kh, kw, want, itemsize)


# --------------------------------------------------------------------------
# custom_vjp wrappers: the Pallas kernels differentiate via the same
# kernel family on transposed operands
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _matmul_pallas_vjp(x, w, blocks):
    bm, bn, bk = blocks
    return matmul_pallas(x, w, block_m=bm, block_n=bn, block_k=bk,
                         interpret=_on_cpu())


def _matmul_pallas_fwd(x, w, blocks):
    return _matmul_pallas_vjp(x, w, blocks), (x, w)


def _matmul_pallas_bwd(blocks, res, g):
    x, w = res
    # dX = g @ W^T and dW = X^T @ g are matmuls: re-dispatch (the
    # transposed shapes get their own plan / winner)
    dx = local_matmul(g, w.T)
    dw = local_matmul(x.T, g)
    return dx.astype(x.dtype), dw.astype(w.dtype)


_matmul_pallas_vjp.defvjp(_matmul_pallas_fwd, _matmul_pallas_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _conv_pallas_vjp(x, w, blocks, padding):
    bb, bk, bc = blocks
    return conv2d_pallas(x, w, block_b=bb, block_k=bk, block_c=bc,
                         padding=padding, interpret=_on_cpu())


def _conv_pallas_fwd(x, w, blocks, padding):
    return _conv_pallas_vjp(x, w, blocks, padding), (x, w)


def _conv_pallas_bwd(blocks, padding, res, g):
    """Stride-1 conv transposes inside the family: dIn is the VALID conv
    of the edge-padded cotangent against the flipped/O-I-swapped kernel,
    dKer the N/C-transposed VALID correlation — both re-dispatched."""
    x, w = res
    kh, kw = w.shape[2], w.shape[3]
    if padding == "SAME":
        lo_h, lo_w = (kh - 1) // 2, (kw - 1) // 2
        hi_h, hi_w = kh - 1 - lo_h, kw - 1 - lo_w
        xp = jnp.pad(x, ((0, 0), (0, 0), (lo_h, hi_h), (lo_w, hi_w)))
    else:
        lo_h = lo_w = 0
        xp = x
    gp = jnp.pad(g, ((0, 0), (0, 0), (kh - 1, kh - 1), (kw - 1, kw - 1)))
    wt = lax.rev(w, (2, 3)).transpose(1, 0, 2, 3)
    dxp = local_conv2d(gp, wt, stride=(1, 1), padding="VALID")
    dx = dxp[:, :, lo_h:lo_h + x.shape[2], lo_w:lo_w + x.shape[3]] \
        if padding == "SAME" else dxp
    dw = local_conv2d(xp.transpose(1, 0, 2, 3), g.transpose(1, 0, 2, 3),
                      stride=(1, 1), padding="VALID").transpose(1, 0, 2, 3)
    return dx.astype(x.dtype), dw.astype(w.dtype)


_conv_pallas_vjp.defvjp(_conv_pallas_fwd, _conv_pallas_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _wino_gemm_pallas_vjp(v, u, blocks):
    bp, bk, bc = blocks
    return wino_gemm_pallas(v, u, block_p=bp, block_k=bk, block_c=bc,
                            interpret=_on_cpu())


def _wino_gemm_pallas_fwd(v, u, blocks):
    return _wino_gemm_pallas_vjp(v, u, blocks), (v, u)


def _wino_gemm_pallas_bwd(blocks, res, g):
    v, u = res
    dv = wino_gemm(g, u.transpose(0, 2, 1))
    du = wino_gemm(v.transpose(0, 2, 1), g)
    return dv.astype(v.dtype), du.astype(u.dtype)


_wino_gemm_pallas_vjp.defvjp(_wino_gemm_pallas_fwd, _wino_gemm_pallas_bwd)


# --------------------------------------------------------------------------
# jit'd whole-op wrappers (the static paper-plan path; bench baseline)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k"))
def matmul(x: jax.Array, w: jax.Array, *, block_m: int = 0, block_n: int = 0,
           block_k: int = 0) -> jax.Array:
    """Paper-planned tiled matmul.  Shapes must divide by the chosen blocks
    (the planner only returns divisors of MXU-aligned extents)."""
    if not (block_m and block_n and block_k):
        block_m, block_n, block_k = _matmul_blocks(x, w)
    return _matmul_pallas_vjp(x, w, (block_m, block_n, block_k))


@functools.partial(jax.jit, static_argnames=("block_b", "block_k", "block_c",
                                              "use_pallas"))
def conv2d_same(x: jax.Array, w: jax.Array, *, block_b: int = 0,
                block_k: int = 0, block_c: int = 0,
                use_pallas: bool = True) -> jax.Array:
    """stride-1 SAME conv, NCHW/OIHW, on the static paper plan
    (``use_pallas=False`` is the XLA reference/baseline path)."""
    if not use_pallas:
        return lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=_DIMNUMS,
            preferred_element_type=jnp.float32).astype(x.dtype)
    if not (block_b and block_k and block_c):
        block_b, block_k, block_c = _conv_blocks(x, w, "SAME")
    return _conv_pallas_vjp(x, w, (block_b, block_k, block_c), "SAME")


# --------------------------------------------------------------------------
# Applicability predicates
# --------------------------------------------------------------------------

# Largest stencil the direct kernel and im2col unroll (kh*kw MXU dots per
# output row; kh*kw patch slices): 7x7, the ResNet stem.  Larger
# "kernels" — the dKer correlation, whose stencil is the whole image —
# belong to XLA.
MAX_STENCIL_TAPS = 49


def _fitted(blocks, *operands):
    """The plan's blocks, or ``ValueError`` for a shape no block fits (the
    dispatchers exclude such shapes before they get here)."""
    if blocks is None:
        raise ValueError("no Pallas block fits " + " x ".join(
            f"{o.shape}{o.dtype}" for o in operands))
    return blocks


def _matmul_blocks(x, w):
    return _fitted(matmul_plan(x.shape[0], w.shape[1], x.shape[1],
                               x.dtype.itemsize), x, w)


def _conv_blocks(x, w, padding):
    n, c, h, wd = x.shape
    k, _, kh, kw = w.shape
    return _fitted(conv_plan(n, c, k, h, wd, kh, kw, x.dtype.itemsize,
                             padding), x, w)


def _wino_gemm_blocks(v, u):
    return _fitted(matmul_plan(v.shape[1], u.shape[2], v.shape[2],
                               v.dtype.itemsize), v, u)


def matmul_exclusion(m: int, n: int, k: int, dtype=jnp.float32):
    """``None`` when the Pallas matmul covers the shape, else the rule that
    excludes it: feature extents that do not tile into blocks of the VPU
    sublane width (8), rows that neither do so nor fit one block of fewer
    than 8 (a decode batch), or no (sublane, 128)-tiled block that fits
    VMEM."""
    if n % 8 or k % 8 or (m % 8 and m > 8):
        return "extent % 8 != 0"
    if matmul_plan(m, n, k, jnp.dtype(dtype).itemsize) is None:
        return "no tiled block fits VMEM"
    return None


def conv_exclusion(x_shape, w_shape, stride, padding, dtype=jnp.float32):
    """``None`` when the direct Pallas conv covers the shape, else the rule
    that excludes it."""
    n, c, h, wd = x_shape
    k, c2, kh, kw = w_shape
    if tuple(stride) != (1, 1):
        return "stride != 1"
    if padding not in ("SAME", "VALID") or c != c2:
        return "padding/channels"
    if k % 8 or c % 8:
        return "C or K % 8 != 0"
    if kh > h or kw > wd:
        return "kernel larger than image"
    if kh * kw > MAX_STENCIL_TAPS:
        return f"stencil {kh}x{kw} > {MAX_STENCIL_TAPS} taps"
    if conv_plan(n, c, k, h, wd, kh, kw, jnp.dtype(dtype).itemsize,
                 padding) is None:
        return "image slab does not fit VMEM"
    return None


def pallas_applicable_matmul(m: int, n: int, k: int,
                             dtype=jnp.float32) -> bool:
    """The Pallas matmul covers the shape (see :func:`matmul_exclusion`)."""
    return matmul_exclusion(m, n, k, dtype) is None


def pallas_applicable_conv(x_shape, w_shape, stride, padding,
                           dtype=jnp.float32) -> bool:
    """The Pallas direct conv covers stride-1 SAME/VALID with feature dims
    that tile into >= 8-wide blocks, kernels no larger than the image, and
    a block that fits VMEM (see :func:`conv_exclusion`)."""
    return conv_exclusion(x_shape, w_shape, stride, padding, dtype) is None


# --------------------------------------------------------------------------
# Autotune keys and candidate menus
# --------------------------------------------------------------------------

def _dt(dtype) -> str:
    return jnp.dtype(dtype).name


@functools.lru_cache(maxsize=None)
def device_kind() -> str:
    """Kind of the default device (``"cpu"``, ``"TPU v5 lite"``, ...).
    Every plan-table key ends in it, so a winner timed on one device kind
    is never read on another."""
    return jax.devices()[0].device_kind


def conv_key(x_shape, w_shape, dtype, stride, padding) -> str:
    n, c, h, wd = x_shape
    k, _, kh, kw = w_shape
    return (f"conv2d:{n}x{c}x{h}x{wd}:k{k}:{kh}x{kw}"
            f":s{stride[0]}x{stride[1]}:{padding}:{_dt(dtype)}"
            f"@{device_kind()}")


def matmul_key(m: int, n: int, k: int, dtype) -> str:
    return f"matmul:{m}x{k}x{n}:{_dt(dtype)}@{device_kind()}"


def wino_gemm_key(p: int, k: int, c: int, dtype) -> str:
    return f"wino_gemm:16x{p}x{c}:k{k}:{_dt(dtype)}@{device_kind()}"


def _rand(shape, dtype):
    return jax.random.normal(jax.random.PRNGKey(0), shape, dtype)


# Every local-kernel key this process dispatched (see dispatch_table).
_DISPATCH: dict = {}


def dispatch_table() -> dict:
    """``{key: {"impl", "excluded", "refused"}}`` for every local-kernel
    key dispatched in this process: the implementation chosen, the Pallas
    candidate a shape rule removed before any compile (with the rule), and
    the candidates that failed to compile or run while being timed (with
    the error)."""
    return {k: dict(v) for k, v in _DISPATCH.items()}


def _note(key: str, impl: str, excluded: dict) -> None:
    ent = autotune.plan_cache().lookup(key, allow_file=False) or {}
    _DISPATCH[key] = {"impl": impl, "excluded": dict(excluded),
                      "refused": dict(ent.get("refused", {}))}


def _pallas_gate(allow_pallas: bool, rule):
    """``None`` when the Pallas candidate may run, else why it may not."""
    if not allow_pallas:
        return "prefer_pallas=False"
    if not _pallas_enabled():
        return f"{_DIST_PALLAS_ENV}=0"
    return rule()


def _run_matmul_impl(impl: str, x, w):
    if impl == "pallas":
        return _matmul_pallas_vjp(x, w, _matmul_blocks(x, w))
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(
        jnp.result_type(x.dtype, w.dtype))


def select_matmul_impl(m: int, n: int, k: int, dtype, *,
                       allow_pallas: bool = True) -> str:
    """Winning matmul impl (``pallas`` | ``xla``) for the shape — the
    static paper plan (Pallas when the shape tiles and fits) when the
    autotuner is off, the timed best-of otherwise."""
    key = matmul_key(m, n, k, dtype)
    why = _pallas_gate(allow_pallas, lambda: matmul_exclusion(m, n, k, dtype))
    if why is not None:
        impl = "xla"
    elif not autotune.enabled():
        impl = "pallas"
    else:
        cands = [("pallas", functools.partial(_run_matmul_impl, "pallas")),
                 ("xla", functools.partial(_run_matmul_impl, "xla"))]
        impl = autotune.best_of(
            key, cands, lambda: (_rand((m, k), dtype), _rand((k, n), dtype)))
    _note(key, impl, {"pallas": why} if why else {})
    return impl


def _run_wino_gemm_impl(impl: str, v, u):
    if impl == "pallas":
        return _wino_gemm_pallas_vjp(v, u, _wino_gemm_blocks(v, u))
    return wino_gemm_einsum(v, u)


def wino_gemm(v: jax.Array, u: jax.Array) -> jax.Array:
    """Autotuned ``[16,P,C] @ [16,C,K]`` batched tile GEMM (the Winograd
    hot spot): Pallas when the shape tiles and wins, XLA einsum
    otherwise."""
    t, p, c = v.shape
    k = u.shape[2]
    key = wino_gemm_key(p, k, c, v.dtype)
    why = _pallas_gate(True, lambda: matmul_exclusion(p, k, c, v.dtype))
    if why is not None:
        impl = "einsum"
    elif not autotune.enabled():
        impl = "pallas"
    else:
        cands = [("pallas", functools.partial(_run_wino_gemm_impl, "pallas")),
                 ("einsum", functools.partial(_run_wino_gemm_impl, "einsum"))]
        impl = autotune.best_of(
            key, cands,
            lambda: (_rand(v.shape, v.dtype), _rand(u.shape, u.dtype)))
    _note(key, impl, {"pallas": why} if why else {})
    return _run_wino_gemm_impl(impl, v, u)


def _run_conv_impl(impl: str, x, w, stride, padding):
    if impl == "direct":
        return _conv_pallas_vjp(x, w, _conv_blocks(x, w, padding), padding)
    if impl == "winograd":
        return conv2d_winograd(x, w, padding=padding, gemm=wino_gemm)
    if impl == "im2col":
        return conv2d_im2col(x, w, stride=stride, padding=padding,
                             matmul=local_matmul)
    return lax.conv_general_dilated(
        x, w, stride, padding, dimension_numbers=_DIMNUMS,
        preferred_element_type=jnp.float32).astype(
            jnp.result_type(x.dtype, w.dtype))


def _direct_gate(x_shape, w_shape, stride, padding, dtype, allow_pallas):
    return _pallas_gate(allow_pallas, lambda: conv_exclusion(
        x_shape, w_shape, stride, padding, dtype))


def conv_candidates(x_shape, w_shape, stride, padding, *,
                    allow_pallas: bool = True,
                    dtype=jnp.float32) -> list:
    """Ordered applicable-candidate names for the conv shape (the static
    paper-plan choice first)."""
    direct_ok = _direct_gate(x_shape, w_shape, stride, padding, dtype,
                             allow_pallas) is None
    cands = ["direct"] if direct_ok else []
    if winograd_applicable(x_shape, w_shape, stride, padding):
        cands.append("winograd")
    if w_shape[2] * w_shape[3] <= MAX_STENCIL_TAPS:
        cands.append("im2col")
    cands.append("xla")
    if not direct_ok:  # static choice (xla) leads when direct is out
        cands.remove("xla")
        cands.insert(0, "xla")
    return cands


def select_conv_impl(x_shape, w_shape, dtype, stride, padding, *,
                     allow_pallas: bool = True) -> str:
    """Winning conv impl (``direct`` | ``winograd`` | ``im2col`` |
    ``xla``) for the shape — the static paper plan when the autotuner is
    off, the timed best-of otherwise."""
    stride = tuple(stride)
    key = conv_key(x_shape, w_shape, dtype, stride, padding)
    why = _direct_gate(x_shape, w_shape, stride, padding, dtype,
                       allow_pallas)
    cands = conv_candidates(x_shape, w_shape, stride, padding,
                            allow_pallas=allow_pallas, dtype=dtype)
    if not autotune.enabled():
        impl = cands[0]  # static paper plan: direct when it fits, else xla
    else:
        menu = [(name, functools.partial(_run_conv_impl, name,
                                         stride=stride, padding=padding))
                for name in cands]
        impl = autotune.best_of(
            key, menu, lambda: (_rand(x_shape, dtype), _rand(w_shape, dtype)))
    _note(key, impl, {"direct": why} if why else {})
    return impl


# --------------------------------------------------------------------------
# Local-contraction dispatchers: the repro.dist hot path calls these for
# every per-step slab contraction, so every distributed schedule (and
# make_grid_train_step) inherits the autotuned winners.
# --------------------------------------------------------------------------

def local_matmul(x: jax.Array, w: jax.Array, *,
                 prefer_pallas: bool = True) -> jax.Array:
    """``[m,k] @ [k,n]`` for a distributed inner step, dispatched through
    the autotuned selector (f32 accumulation on every path).  All
    candidates differentiate natively — the Pallas kernel carries a
    custom VJP running the same family on transposed operands.
    ``prefer_pallas=False`` removes the Pallas candidate."""
    m, k = x.shape
    _, n = w.shape
    impl = select_matmul_impl(m, n, k, x.dtype, allow_pallas=prefer_pallas)
    return _run_matmul_impl(impl, x, w)


def local_conv2d(x: jax.Array, w: jax.Array, *, stride=(1, 1),
                 padding: str = "VALID",
                 prefer_pallas: bool = True) -> jax.Array:
    """NCHW/OIHW conv for a distributed inner step, dispatched through
    the autotuned selector over the full candidate menu (direct Pallas /
    Winograd / im2col-GEMM / XLA).  Every candidate differentiates
    natively.  ``prefer_pallas=False`` removes the direct-Pallas
    candidate."""
    stride = tuple(stride)
    impl = select_conv_impl(x.shape, w.shape, x.dtype, stride, padding,
                            allow_pallas=prefer_pallas)
    return _run_conv_impl(impl, x, w, stride, padding)
