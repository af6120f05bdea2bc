"""Distributed 2-D convolution on the paper's 5-axis processor grid.

Grid tuple convention (everywhere in this repo): ``(Pb, Ph, Pw, Pk, Pc)``
over mesh axes ``("b", "h", "w", "k", "c")`` — batch, image height, image
width, output features, input features (contraction).

Data placement (NCHW activations, OIHW kernels):

* ``In  [N, C, H, W]``  sharded ``P("b", ("c", "k"), "h", "w")`` — the
  contraction dim is sharded over c and *sub-sharded* over k, so the only
  input collective is an all-gather over the k-axis;
* ``Ker [K, C, kh, kw]`` sharded ``P("k", ("c", "b"), None, None)`` — its
  contraction sub-shard is gathered over the b-axis (batch ranks hold
  disjoint kernel slices, the conv analogue of SUMMA's stationary-C kernel
  replication);
* ``Out [N, K, H', W']`` sharded ``P("b", "k", "h", "w")``, produced by an
  all-reduce over the c-axis.

Spatial decomposition (``Ph``/``Pw > 1``) partitions the *output* rows
evenly and reconstructs each rank's input window from the evenly sharded
input via :func:`halo_exchange_1d` plus a per-rank window slice (see
:class:`SpatialPlan`); ppermute's zero fill provides the SAME zero padding
at the global image boundary, so padding and halo share one code path and
strided / VALID convolutions shard spatially too (the stride-1 /
``lo+hi == k-1`` restriction is gone).

``schedule="ring"`` is the paper's pipelined variant: the input's C-slabs
rotate around the k-ring and each arriving slab is immediately contracted
(local conv) against the matching kernel C-slice — the ring-pipelined
c-slab reduction.  The kernel is still fully all-gathered over b up
front, so per-rank peak memory is gathered-size on that operand.

``schedule="ring2"`` pipelines *both* sides (the true two-ring schedule):
Ker's C-chunks rotate around the b-ring while In's C-slabs rotate around
the k-ring (:func:`collectives.ring_zip`), so no rank ever materializes a
gathered operand — wire volume is identical (each piece still crosses
each ring exactly once), peak live memory drops from gathered-size to
slab-size.  A naive double rotation has a per-rank phase lag
``(k_idx - b_idx) mod g`` between the two arrival streams (Cannon's
algorithm fixes this with an alignment skew that would cost an extra
wire hop per operand); instead we exploit the two schedules this repo's
grids actually use where the lag is coverable for free:

* ``Pb == 1`` or ``Pk == 1`` — one ring is trivial, the other operand
  streams chunk-at-a-time against the stationary local shard (this is
  the big win on pure-DP grids, where ``ring`` gathers ``Pb`` kernel
  copies);
* ``Pb == Pk == 2`` — the always-resident *own* input shards cover
  exactly the two pairs the lag misses, via masked dual contractions
  (each step runs two slab convs, at most one of which is masked out).

Other grids fall back to ``"ring"`` (see :func:`conv_ring2_supported`).
The backward pass streams the same way: dIn slabs are produced on the
fly and reduced around the k-ring (:func:`collectives.ring_scatter_reduce`),
dKer chunks around the b-ring, with the spatial psum applied to the
already-scattered chunk (``1/Pb`` of the one-ring psum volume).

**Differentiation.**  ``conv2d_distributed`` carries a ``jax.custom_vjp``
whose backward pass transposes the forward communication structure
(paper Sec. 4's observation that fwd, dIn and dKer share one grid):

* the c-axis all-reduce transposes to a broadcast — the output cotangent
  arrives replicated over c, no collective;
* the k-axis input gather transposes to a k-axis reduce-scatter of dIn
  (``dIn`` is the transposed-kernel distributed conv);
* the b-axis kernel gather transposes to a b-axis reduce-scatter of dKer
  (``dKer`` is the batch/spatial-contraction distributed correlation,
  all-reduced over the spatial axes);
* the halo exchange transposes to :func:`halo_accumulate_1d`.

``conv_comm_elems`` / ``conv_train_comm_elems`` give the analytic
per-device wire volumes of the forward and forward+backward schedules that
``launch.hlo_analysis`` numbers are validated against.

The local contractions run under ``jax.named_scope`` ``conv.fwd``,
``conv.dx`` and ``conv.dw``, which the compiled program keeps in each
instruction's ``op_name`` metadata: a device trace or the compiled HLO
tells the three phases apart.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from repro.dist.collectives import (SCHEDULES, gather_axis, make_mesh,
                                    ppermute, psum, ring_reduce,
                                    ring_scatter_reduce, ring_zip,
                                    scatter_axis, stream_elems)
from repro.dist.halo import halo_accumulate_1d, halo_exchange_1d
from repro.kernels import ops as kops

AXES = ("b", "h", "w", "k", "c")
_DIMNUMS = ("NCHW", "OIHW", "NCHW")

Padding = Union[str, Tuple[Tuple[int, int], Tuple[int, int]]]


def make_conv_mesh(grid) -> Mesh:
    """Mesh over ``("b", "h", "w", "k", "c")`` from ``(Pb,Ph,Pw,Pk,Pc)``."""
    if len(grid) != 5:
        raise ValueError(f"conv grid must be (Pb,Ph,Pw,Pk,Pc), got {grid}")
    return make_mesh(grid, AXES)


def _pad_amounts(size: int, k: int, s: int, pad) -> Tuple[int, int, int]:
    """(lo, hi, out_size) for one spatial dim, XLA's SAME/VALID rules."""
    if isinstance(pad, str):
        if pad.upper() == "SAME":
            out = -(-size // s)
            total = max((out - 1) * s + k - size, 0)
            return total // 2, total - total // 2, out
        if pad.upper() == "VALID":
            return 0, 0, (size - k) // s + 1
        raise ValueError(f"unknown padding {pad!r}")
    lo, hi = pad
    return lo, hi, (size + lo + hi - k) // s + 1


class SpatialPlan(NamedTuple):
    """Decomposition of one spatial dim over ``p`` ranks, general stride.

    Output rows are split evenly (``out % p == 0``); rank ``r`` evaluates
    global output rows ``[r*out/p, (r+1)*out/p)``, which read global input
    rows ``[r*(out/p)*s - lo, ...)`` — a window of ``win`` rows whose start
    drifts by ``shift = (size - out*s)/p`` rows per rank relative to the
    evenly sharded input.  The uniform halo ``(lo_x, hi_x)`` covers the
    worst-case drift for every rank; each rank then slices its ``win``-row
    window at offset ``lo_x - lo - r*shift``.  For stride-1 SAME this
    degenerates to the classic ``(lo, hi)`` halo with an identity slice.
    """

    p: int        # ranks on this axis
    size: int     # global input extent
    k: int        # kernel extent
    s: int        # stride
    lo: int       # conv padding below
    hi: int       # conv padding above
    out: int      # global output extent
    win: int      # per-rank input window rows = (out/p - 1)*s + k
    shift: int    # per-rank window drift = (size - out*s)/p
    lo_x: int     # uniform halo rows fetched from predecessors (+ zero pad)
    hi_x: int     # uniform halo rows fetched from successors (+ zero pad)

    @property
    def identity_slice(self) -> bool:
        return self.win == self.size // self.p + self.lo_x + self.hi_x \
            and self.shift == 0 and self.lo_x == self.lo

    def offset(self, axis_name: str):
        """Local window start within the halo-extended block (traced when
        the drift is rank-dependent)."""
        base = self.lo_x - self.lo
        if self.p == 1 or self.shift == 0:
            return base
        return base - lax.axis_index(axis_name) * self.shift


def _spatial_plan(size: int, k: int, s: int, pad, p: int,
                  dim: str) -> SpatialPlan:
    lo, hi, out = _pad_amounts(size, k, s, pad)
    if p <= 0 or size % p or out % p:
        raise ValueError(
            f"spatial sharding over '{dim}' needs the input extent "
            f"({size}) and output extent ({out}) divisible by P{dim}={p}")
    win = (out // p - 1) * s + k
    shift = (size - out * s) // p  # exact: p | size and p | out*s
    lo_x = lo + max(0, (p - 1) * shift)
    hi_x = max(0, win - lo - size // p + max(0, -(p - 1) * shift))
    return SpatialPlan(p=p, size=size, k=k, s=s, lo=lo, hi=hi, out=out,
                       win=win, shift=shift, lo_x=lo_x, hi_x=hi_x)


def _halo_and_window(xl, plans: Tuple[SpatialPlan, SpatialPlan]):
    """Halo-extend the local shard and slice each rank's conv window.

    Returns ``(extended_block, window, (off_h, off_w))`` — the forward
    consumes only the window; the backward also needs the extended block
    shape and the slice offsets to transpose the reconstruction."""
    plan_h, plan_w = plans
    xh = halo_exchange_1d(xl, "h", spatial_dim=2, lo=plan_h.lo_x,
                          hi=plan_h.hi_x)
    xh = halo_exchange_1d(xh, "w", spatial_dim=3, lo=plan_w.lo_x,
                          hi=plan_w.hi_x)
    off_h, off_w = plan_h.offset("h"), plan_w.offset("w")
    xwin = xh
    if not plan_h.identity_slice:
        xwin = lax.dynamic_slice_in_dim(xwin, off_h, plan_h.win, axis=2)
    if not plan_w.identity_slice:
        xwin = lax.dynamic_slice_in_dim(xwin, off_w, plan_w.win, axis=3)
    return xh, xwin, (off_h, off_w)


def _conv_fwd_ring2(xwin, wl, *, pb, pk, conv):
    """Two-ring forward: In slabs rotate the k-ring, Ker chunks the b-ring.

    Supported cases (see module docstring): a trivial ring on either side
    (pure streaming against the stationary shard) or both rings of size 2
    (own-shard covered zip)."""
    cx = xwin.shape[1]   # C / (Pc*Pk), the In c-slab width
    cw = wl.shape[1]     # C / (Pc*Pb), the Ker c-chunk width
    if pb == 1 and pk == 1:
        return conv(xwin, wl)
    if pk == 1:
        # In holds its full C/Pc columns: stream Ker chunks around the
        # b-ring, contract each against the matching In c-slice
        def chunk_conv(acc, src, wchunk):
            xs = lax.dynamic_slice_in_dim(xwin, src * cw, cw, axis=1)
            part = conv(xs, wchunk)
            return part if acc is None else acc + part

        return ring_reduce(wl, "b", chunk_conv, None)
    if pb == 1:
        # Ker holds its full C/Pc rows: stream In slabs around the k-ring
        def slab_conv(acc, src, slab):
            ws = lax.dynamic_slice_in_dim(wl, src * cx, cx, axis=1)
            part = conv(slab, ws)
            return part if acc is None else acc + part

        return ring_reduce(xwin, "k", slab_conv, None)
    # Pb == Pk == 2: zip both rings.  Aligned ranks (k_idx == b_idx) see
    # matching c-ranges arrive together every step; misaligned ranks pair
    # each arrival against their own stationary shard instead.
    kappa, beta = lax.axis_index("k"), lax.axis_index("b")
    aligned = kappa == beta

    def zip_body(acc, t, sx, cur_x, sw, cur_w):
        # accumulate the two masked contractions one at a time so their
        # out-sized scratch buffers can be reused, not live together
        w1 = jnp.where(aligned, cur_w, wl)
        m1 = jnp.logical_or(aligned, sx == beta)
        c1 = conv(cur_x, w1)
        acc = c1 * m1.astype(c1.dtype) if acc is None \
            else acc + c1 * m1.astype(c1.dtype)
        m2 = jnp.logical_and(jnp.logical_not(aligned), sw == kappa)
        c2 = conv(xwin, cur_w)
        return acc + c2 * m2.astype(c2.dtype)

    return ring_zip(xwin, "k", wl, "b", zip_body, None)


def _local_conv(xl, wl, *, sizes, stride, plans, schedule, pallas=True):
    pb, ph, pw, pk, pc = (sizes[a] for a in AXES)
    # halo (interior) / zero pad (global boundary) on the thin C sub-shard,
    # before any gather so boundary traffic is minimal
    _, xl, _ = _halo_and_window(xl, plans)
    # per-step local contraction through the Pallas/XLA kernel dispatcher
    def conv(a, b):
        with jax.named_scope("conv.fwd"):
            return kops.local_conv2d(a, b, stride=stride, padding="VALID",
                                     prefer_pallas=pallas)

    if schedule == "ring2":
        out = _conv_fwd_ring2(xl, wl, pb=pb, pk=pk, conv=conv)
        if pc > 1:
            out = psum(out, "c", tag="conv_out")
        return out
    # kernel contraction sub-shard gathered over the batch axis
    wg = gather_axis(wl, "b", dim=1, schedule=schedule) if pb > 1 else wl
    if pk == 1:
        out = conv(xl, wg)
    elif schedule == "ring":
        # ring-pipelined c-slab reduction: In's C-slabs rotate around the
        # k-ring; contract each against the matching kernel C-slice
        csub = xl.shape[1]

        def partial_conv(acc, src, slab):
            wslab = lax.dynamic_slice_in_dim(wg, src * csub, csub, axis=1)
            part = conv(slab, wslab)
            return part if acc is None else acc + part

        out = ring_reduce(xl, "k", partial_conv, None)
    else:
        xg = gather_axis(xl, "k", dim=1, schedule=schedule)
        out = conv(xg, wg)
    if pc > 1:
        out = psum(out, "c", tag="conv_out")
    return out


# --------------------------------------------------------------------------
# Backward pass: the transposed communication schedule
# --------------------------------------------------------------------------

def _dx_local(gl, wg, *, stride):
    """dIn of the local VALID conv: the transposed-kernel conv —
    ``conv(dOut dilated by the stride, flip(Ker) with O/I swapped)``.
    Stride-1 is a plain VALID conv on the edge-padded cotangent and goes
    through the kernel dispatcher; strided needs ``lhs_dilation``."""
    kh, kw = wg.shape[2], wg.shape[3]
    with jax.named_scope("conv.dx"):
        if tuple(stride) == (1, 1):
            gp = jnp.pad(gl, ((0, 0), (0, 0), (kh - 1, kh - 1),
                              (kw - 1, kw - 1)))
            wt = lax.rev(wg, (2, 3)).transpose(1, 0, 2, 3)
            return kops.local_conv2d(gp, wt, stride=(1, 1),
                                     padding="VALID")
        return lax.conv_general_dilated(
            gl, lax.rev(wg, (2, 3)), window_strides=(1, 1),
            padding=((kh - 1, kh - 1), (kw - 1, kw - 1)),
            lhs_dilation=stride, dimension_numbers=("NCHW", "IOHW", "NCHW"))


def _dw_local(xg, gl, *, stride):
    """dKer of the local VALID conv: the batch-contraction correlation —
    In slides under the stride-dilated dOut, contracting over N.
    Stride-1 is the N/C-transposed VALID conv and goes through the kernel
    dispatcher; strided needs ``rhs_dilation``."""
    with jax.named_scope("conv.dw"):
        if tuple(stride) == (1, 1):
            out = kops.local_conv2d(xg.transpose(1, 0, 2, 3),
                                    gl.transpose(1, 0, 2, 3),
                                    stride=(1, 1), padding="VALID")
            return out.transpose(1, 0, 2, 3)
        out = lax.conv_general_dilated(
            xg, gl, window_strides=(1, 1), padding="VALID",
            rhs_dilation=stride, dimension_numbers=("CNHW", "IOHW", "NCHW"))
        return out.transpose(1, 0, 2, 3)


def _conv_bwd_ring2(xwin, wl, gl, *, pb, pk, stride, psp):
    """Streaming backward of the two-ring schedule: dIn slabs are produced
    on the fly and reduced around the k-ring, dKer chunks around the
    b-ring — no gathered operand, no gathered gradient is ever
    materialized.  The Ker/In re-circulations replace the one-ring
    backward's gather replays at identical wire volume; the spatial psum
    applies to the already-scattered own chunk (``1/Pb`` of the one-ring
    volume).  Returns ``(dxwin, dwl)`` in windowed/local layout."""
    cx = xwin.shape[1]
    cw = wl.shape[1]
    ring2 = [(i, (i + 1) % 2) for i in range(2)]

    # --- dIn: per-slab transposed-kernel conv ----------------------------
    if pk == 1:
        if pb == 1:
            dxwin = _dx_local(gl, wl, stride=stride)
        else:
            # stream Ker chunks around the b-ring; each fills its c-rows
            def fill(acc, src, wchunk):
                part = _dx_local(gl, wchunk, stride=stride)
                return lax.dynamic_update_slice_in_dim(
                    acc, part.astype(acc.dtype), src * cw, axis=1)

            dxwin = ring_reduce(wl, "b", fill,
                                jnp.zeros(xwin.shape, gl.dtype))
    elif pb == 1:
        # Ker holds its full rows: produce each k-ring token's slab locally
        def produce_dx(r, t):
            ws = lax.dynamic_slice_in_dim(wl, r * cx, cx, axis=1)
            return _dx_local(gl, ws, stride=stride)

        dxwin = ring_scatter_reduce("k", produce_dx)
    else:  # Pb == Pk == 2: one b-hop re-delivers the foreign Ker chunk
        w_arr = ppermute(wl, "b", ring2, tag="ring2_redeliver")
        aligned = lax.axis_index("k") == lax.axis_index("b")

        def produce_dx(r, t):
            wsel = jnp.where(aligned, w_arr, wl) if t == 0 \
                else jnp.where(aligned, wl, w_arr)
            return _dx_local(gl, wsel, stride=stride)

        dxwin = ring_scatter_reduce("k", produce_dx)

    # --- dKer: per-chunk batch contraction -------------------------------
    if pb == 1:
        if pk == 1:
            dwl = _dw_local(xwin, gl, stride=stride)
        else:
            # stream In slabs around the k-ring; each fills its c-rows
            def fill_dw(acc, src, slab):
                part = _dw_local(slab, gl, stride=stride)
                return lax.dynamic_update_slice_in_dim(
                    acc, part.astype(acc.dtype), src * cx, axis=1)

            kh, kw = wl.shape[2], wl.shape[3]
            dwl = ring_reduce(
                xwin, "k", fill_dw,
                jnp.zeros((wl.shape[0], cw, kh, kw), gl.dtype))
    elif pk == 1:
        def produce_dw(r, t):
            xs = lax.dynamic_slice_in_dim(xwin, r * cw, cw, axis=1)
            return _dw_local(xs, gl, stride=stride)

        dwl = ring_scatter_reduce("b", produce_dw)
    else:  # Pb == Pk == 2: one k-hop re-delivers the foreign In slab
        x_arr = ppermute(xwin, "k", ring2, tag="ring2_redeliver")
        aligned = lax.axis_index("k") == lax.axis_index("b")

        def produce_dw(r, t):
            xsel = jnp.where(aligned, x_arr, xwin) if t == 0 \
                else jnp.where(aligned, xwin, x_arr)
            return _dw_local(xsel, gl, stride=stride)

        dwl = ring_scatter_reduce("b", produce_dw)
    if psp > 1:  # Ker was replicated over h/w: transpose is a psum
        dwl = psum(dwl, ("h", "w"), tag="dker_spatial")
    return dxwin, dwl


def _local_conv_bwd(xl, wl, gl, *, sizes, stride, plans, schedule):
    """One shard_map transposing the forward schedule: gl (the Out
    cotangent) arrives replicated over c (transpose of the all-reduce);
    the forward gathers are replayed (or re-streamed, for ``ring2``), dIn
    is reduce-scattered over k and halo-accumulated, dKer is all-reduced
    over the spatial axes and reduce-scattered over b."""
    pb, ph, pw, pk, pc = (sizes[a] for a in AXES)
    plan_h, plan_w = plans
    # replay the forward operand reconstruction (rematerialized, not saved)
    xh, xwin, (off_h, off_w) = _halo_and_window(xl, plans)
    if schedule == "ring2":
        dxwin, dwl = _conv_bwd_ring2(xwin, wl, gl, pb=pb, pk=pk,
                                     stride=stride, psp=ph * pw)
    else:
        wg = gather_axis(wl, "b", dim=1, schedule=schedule) if pb > 1 else wl
        xg = gather_axis(xwin, "k", dim=1, schedule=schedule) \
            if pk > 1 else xwin

        # --- dIn: transposed-kernel conv, k-gather -> k-scatter ----------
        dxg = _dx_local(gl, wg, stride=stride)
        dxwin = scatter_axis(dxg, "k", dim=1, schedule=schedule) \
            if pk > 1 else dxg

        # --- dKer: batch/spatial contraction, b-gather -> b-scatter ------
        dwg = _dw_local(xg, gl, stride=stride)
        if ph * pw > 1:  # Ker was replicated over h/w: transpose is a psum
            dwg = psum(dwg, ("h", "w"), tag="dker_spatial")
        dwl = scatter_axis(dwg, "b", dim=1, schedule=schedule) \
            if pb > 1 else dwg

    if plan_h.identity_slice and plan_w.identity_slice:
        dxe = dxwin
    else:  # transpose of the window slice: scatter back into the block
        dxe = jnp.zeros(xh.shape, dxwin.dtype)
        dxe = lax.dynamic_update_slice(
            dxe, dxwin, (0, 0,
                         off_h if not plan_h.identity_slice else 0,
                         off_w if not plan_w.identity_slice else 0))
    dxl = halo_accumulate_1d(dxe, "w", spatial_dim=3, lo=plan_w.lo_x,
                             hi=plan_w.hi_x)
    dxl = halo_accumulate_1d(dxl, "h", spatial_dim=2, lo=plan_h.lo_x,
                             hi=plan_h.hi_x)
    return dxl.astype(xl.dtype), dwl.astype(wl.dtype)


def conv_ring2_supported(grid) -> bool:
    """True when the two-ring schedule covers ``grid = (Pb,Ph,Pw,Pk,Pc)``:
    a trivial ring on either contraction side (``Pb == 1`` or ``Pk == 1``)
    or both rings of size 2.  ``conv2d_distributed(schedule="ring2")``
    falls back to ``"ring"`` on other grids (see module docstring for why
    larger double rings would need a Cannon alignment skew)."""
    pb, ph, pw, pk, pc = grid
    return pb == 1 or pk == 1 or (pb == 2 and pk == 2)


def _conv_effective_schedule(schedule: str, grid) -> str:
    if schedule == "ring2" and not conv_ring2_supported(grid):
        return "ring"
    return schedule


def _conv2d_raw(x, w, mesh, schedule, stride, plans, pallas=True):
    """The forward shard_map itself — differentiable natively, in which
    case JAX saves the gathered operands as residuals and the backward
    transposes each collective in place (zero gather-replay traffic);
    this is the ``save_gathered=True`` memory-for-wire endpoint.  The
    local contractions keep their autotuned Pallas winners: every
    candidate behind ``kops.local_conv2d`` carries a ``custom_vjp``
    (backward via the same kernel family on transposed operands)."""
    sizes = dict(mesh.shape)
    fn = shard_map(
        functools.partial(_local_conv, sizes=sizes, stride=stride,
                          plans=plans, schedule=schedule, pallas=pallas),
        mesh=mesh,
        in_specs=(P("b", ("c", "k"), "h", "w"),
                  P("k", ("c", "b"), None, None)),
        out_specs=P("b", "k", "h", "w"),
        check_vma=False)
    return fn(x, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _conv2d_vjp(x, w, mesh, schedule, stride, plans):
    return _conv2d_raw(x, w, mesh, schedule, stride, plans)


def _conv2d_fwd(x, w, mesh, schedule, stride, plans):
    return _conv2d_vjp(x, w, mesh, schedule, stride, plans), (x, w)


def _conv2d_bwd(mesh, schedule, stride, plans, res, g):
    x, w = res
    sizes = dict(mesh.shape)
    fn = shard_map(
        functools.partial(_local_conv_bwd, sizes=sizes, stride=stride,
                          plans=plans, schedule=schedule),
        mesh=mesh,
        in_specs=(P("b", ("c", "k"), "h", "w"),
                  P("k", ("c", "b"), None, None),
                  P("b", "k", "h", "w")),
        out_specs=(P("b", ("c", "k"), "h", "w"),
                   P("k", ("c", "b"), None, None)),
        check_vma=False)
    return fn(x, w, g)


_conv2d_vjp.defvjp(_conv2d_fwd, _conv2d_bwd)


def _conv_plans(x_shape, w_shape, grid, stride, padding
                ) -> Tuple[SpatialPlan, SpatialPlan]:
    N, C, H, W = x_shape
    K, C2, kh, kw = w_shape
    pb, ph, pw, pk, pc = grid
    if C != C2:
        raise ValueError(f"channel mismatch: x {x_shape} vs w {w_shape}")
    pad_spec = (padding, padding) if isinstance(padding, str) else padding
    plan_h = _spatial_plan(H, kh, stride[0], pad_spec[0], ph, "h")
    plan_w = _spatial_plan(W, kw, stride[1], pad_spec[1], pw, "w")
    for extent, div, what in [
            (N, pb, "N % Pb"), (K, pk, "K % Pk"), (C, pc * pk, "C % (Pc*Pk)"),
            (C, pc * pb, "C % (Pc*Pb)")]:
        if div <= 0 or extent % div:
            raise ValueError(f"shape not divisible by grid: {what} != 0 "
                             f"({extent} % {div})")
    return plan_h, plan_w


def conv_grid_divides(x_shape, w_shape, grid, *, stride=(1, 1),
                      padding: Padding = "SAME") -> bool:
    """True when the shapes satisfy every runtime divisibility constraint
    of :func:`conv2d_distributed` on ``grid`` (batch, feature sub-shards,
    and the spatial input *and output* extents) — the single predicate the
    synthesizer and model-level helpers share."""
    if isinstance(stride, int):
        stride = (stride, stride)
    try:
        _conv_plans(x_shape, w_shape, grid, tuple(stride), padding)
    except ValueError:
        return False
    return True


def conv2d_distributed(x, w, mesh: Mesh, *, schedule: str = "allgather",
                       stride: Union[int, Tuple[int, int]] = (1, 1),
                       padding: Padding = "SAME",
                       save_gathered: bool = False):
    """NCHW x OIHW convolution distributed over a 5-axis grid; numerically
    matches ``lax.conv_general_dilated(x, w, stride, padding)`` and is
    differentiable.

    By default the custom VJP rematerializes the forward gathers in the
    backward pass (communication-optimal memory).  ``save_gathered=True``
    instead differentiates the forward schedule natively, so the gathered
    operands are saved as residuals and the backward pays zero
    gather-replay traffic — the memory-for-wire endpoint that
    ``conv_train_comm_elems(..., save_gathered=True)`` /
    ``conv_train_mem_elems`` account for.  ``schedule="ring2"`` falls back
    to ``"ring"`` on grids :func:`conv_ring2_supported` rejects."""
    if schedule not in SCHEDULES:
        raise ValueError(f"schedule must be one of {SCHEDULES}")
    sizes = dict(mesh.shape)
    missing = [a for a in AXES if a not in sizes]
    if missing:
        raise ValueError(f"mesh lacks axes {missing}; use make_conv_mesh")
    if isinstance(stride, int):
        stride = (stride, stride)
    grid = tuple(sizes[a] for a in AXES)
    schedule = _conv_effective_schedule(schedule, grid)
    plans = _conv_plans(x.shape, w.shape, grid, stride, padding)
    if save_gathered:
        return _conv2d_raw(x, w, mesh, schedule, tuple(stride), plans)
    return _conv2d_vjp(x, w, mesh, schedule, tuple(stride), plans)


# --------------------------------------------------------------------------
# Analytic per-device communication accounting (fwd and fwd+bwd)
# --------------------------------------------------------------------------

def conv_comm_elems(x_shape, w_shape, grid, *, stride=(1, 1),
                    padding: Padding = "SAME") -> dict:
    """Analytic per-device communication (elements) of the forward
    schedule: gather In over k, gather Ker over b, all-reduce Out over c,
    plus the spatial halo — the runtime counterpart of
    ``core.grid.comm_volume``."""
    if isinstance(stride, int):
        stride = (stride, stride)
    N, C, H, W = x_shape
    K, _, kh, kw = w_shape
    pb, ph, pw, pk, pc = grid
    plan_h, plan_w = _conv_plans(x_shape, w_shape, grid, stride, padding)
    csub_in = C / (pc * pk)
    gather_in = (N / pb) * csub_in * plan_h.win * plan_w.win * (pk - 1)
    gather_ker = K / pk * (C / (pc * pb)) * kh * kw * (pb - 1)
    reduce_out = 2 * (N / pb) * (K / pk) * (plan_h.out / ph) \
        * (plan_w.out / pw) * (pc - 1) / pc
    halo = 0.0
    if ph > 1:
        halo += (plan_h.lo_x + plan_h.hi_x) * (N / pb) * csub_in * (W // pw)
    if pw > 1:
        h_ext = H // ph + plan_h.lo_x + plan_h.hi_x
        halo += (plan_w.lo_x + plan_w.hi_x) * (N / pb) * csub_in * h_ext
    return {"gather_in": gather_in, "gather_ker": gather_ker,
            "reduce_out": reduce_out, "halo": halo,
            "total": gather_in + gather_ker + reduce_out + halo}


def conv_train_comm_elems(x_shape, w_shape, grid, *, stride=(1, 1),
                          padding: Padding = "SAME",
                          schedule: str = "allgather",
                          save_gathered: bool = False) -> dict:
    """Forward + backward analytic per-device wire volume (elements).

    By default the backward shard_map replays the forward halo + both
    gathers (rematerialization), then transposes them: dIn reduce-scatters
    over k (same volume as the In gather) and halo-accumulates (same
    volume as the halo), dKer all-reduces over the spatial axes and
    reduce-scatters over b (same volume as the Ker gather).  The c-axis
    all-reduce has no backward counterpart (its transpose is a broadcast
    of the already replicated cotangent).

    ``save_gathered=True`` models the residual-saving (native) VJP: the
    replay terms vanish (the gathered operands are stored, not
    re-fetched), but the transpose of the c-axis all-reduce is no longer
    the free broadcast the custom VJP exploits — under ``check_vma=False``
    the native transpose cannot prove the cotangent replicated and psums
    it once (``psum_out_bwd``, the forward ``reduce_out`` volume again).
    ``schedule="ring2"`` (on supported grids) scatters dKer over b
    *before* the spatial psum, shrinking that term by ``1/Pb``.
    """
    if isinstance(stride, int):
        stride = (stride, stride)
    K, C, kh, kw = w_shape[0], w_shape[1], w_shape[2], w_shape[3]
    pb, ph, pw, pk, pc = grid
    schedule = _conv_effective_schedule(schedule, grid)
    fwd = conv_comm_elems(x_shape, w_shape, grid, stride=stride,
                          padding=padding)
    psp = ph * pw
    ker_rows = C / pc if schedule != "ring2" else C / (pc * pb)
    psum_ker = (2 * (K / pk) * ker_rows * kh * kw * (psp - 1) / psp
                if psp > 1 else 0.0)
    replay = 0.0 if save_gathered else 1.0
    bwd = {"halo_replay": replay * fwd["halo"],
           "gather_in_replay": replay * fwd["gather_in"],
           "gather_ker_replay": replay * fwd["gather_ker"],
           "rs_in": fwd["gather_in"],
           "rs_ker": fwd["gather_ker"],
           "psum_ker_spatial": psum_ker,
           "psum_out_bwd": fwd["reduce_out"] if save_gathered else 0.0,
           "halo_acc": fwd["halo"]}
    bwd["total"] = sum(v for k, v in bwd.items() if k != "total")
    return {"fwd": fwd, "bwd": bwd, "total": fwd["total"] + bwd["total"]}


# --------------------------------------------------------------------------
# Analytic per-device peak-live-memory accounting (fwd and fwd+bwd)
# --------------------------------------------------------------------------

def _conv_mem_parts(x_shape, w_shape, grid, stride, padding) -> dict:
    """Per-device buffer sizes (elements) every schedule's peak-live
    accounting is assembled from — one definition shared by the fwd and
    train variants so the two can never disagree on a shard size."""
    N, C, H, W = x_shape
    K, _, kh, kw = w_shape
    pb, ph, pw, pk, pc = grid
    plan_h, plan_w = _conv_plans(x_shape, w_shape, grid, stride, padding)
    cx = C / (pc * pk)
    nb = N / pb
    return {
        "xl": nb * cx * (H / ph) * (W / pw),
        "xh": nb * cx * (H / ph + plan_h.lo_x + plan_h.hi_x)
              * (W / pw + plan_w.lo_x + plan_w.hi_x),
        "xwin": nb * cx * plan_h.win * plan_w.win,
        "wl": (K / pk) * (C / (pc * pb)) * kh * kw,
        "out": nb * (K / pk) * (plan_h.out / ph) * (plan_w.out / pw),
    }


def conv_mem_elems(x_shape, w_shape, grid, *, stride=(1, 1),
                   padding: Padding = "SAME",
                   schedule: str = "allgather") -> dict:
    """Analytic per-device peak live memory (elements) of one forward pass.

    Counts every simultaneously live buffer of the schedule: the resident
    input shards, the halo-extended block and conv window, the schedule's
    gather results / stream buffers, and the output (doubled under a
    ``Pc > 1`` all-reduce for the partial-sum buffer).  This is the
    runtime counterpart of ``core.cost_model.memory_distributed`` and the
    quantity ``schedule="ring2"`` exists to shrink: the gathered-operand
    terms (``Pk`` In windows / ``Pb`` Ker chunks) become O(1) stream
    buffers.
    """
    if isinstance(stride, int):
        stride = (stride, stride)
    pb, ph, pw, pk, pc = grid
    schedule = _conv_effective_schedule(schedule, grid)
    p = _conv_mem_parts(x_shape, w_shape, grid, stride, padding)
    xwin, wl = p["xwin"], p["wl"]
    if schedule == "allgather":
        in_t = pk * xwin if pk > 1 else 0.0
        ker_t = pb * wl if pb > 1 else 0.0
    elif schedule == "ring":
        in_t = stream_elems(pk, xwin)
        ker_t = pb * wl + (wl if pb > 1 else 0.0) if pb > 1 else 0.0
    else:  # ring2: both operands stream, nothing gathered
        in_t = stream_elems(pk, xwin)
        ker_t = stream_elems(pb, wl)
    comp = {"args": p["xl"] + wl, "halo": p["xh"] + xwin,
            "in_transient": in_t, "ker_transient": ker_t,
            "out": p["out"] * (2.0 if pc > 1 else 1.0)}
    comp["peak"] = sum(comp.values())
    return comp


def conv_train_mem_elems(x_shape, w_shape, grid, *, stride=(1, 1),
                         padding: Padding = "SAME",
                         schedule: str = "allgather",
                         save_gathered: bool = False) -> dict:
    """Peak live memory (elements) of a forward + backward pass.

    The default (rematerializing) backward replays the forward
    reconstruction and additionally holds the cotangent, the gathered
    gradient buffers (``Pk`` dIn windows / ``Pb`` dKer chunks for the
    gather schedules; O(1) token buffers for ``ring2``) and the operand
    gradients.  ``save_gathered=True`` adds the saved residuals
    (gathered-size, by construction) to both phases but drops nothing
    else — memory traded for the replay wire.
    """
    if isinstance(stride, int):
        stride = (stride, stride)
    pb, ph, pw, pk, pc = grid
    schedule = _conv_effective_schedule(schedule, grid)
    fwd = conv_mem_elems(x_shape, w_shape, grid, stride=stride,
                         padding=padding, schedule=schedule)
    p = _conv_mem_parts(x_shape, w_shape, grid, stride, padding)
    xwin, wl = p["xwin"], p["wl"]
    if schedule == "ring2":
        din_t = stream_elems(pk, xwin)   # dIn token ring
        dker_t = stream_elems(pb, wl)    # dKer token ring
    else:
        din_t = pk * xwin if pk > 1 else 0.0    # materialized dxg
        dker_t = pb * wl if pb > 1 else 0.0     # materialized dwg
    resid = (pk * xwin + pb * wl) if save_gathered else 0.0
    bwd = {"args": fwd["args"], "halo": fwd["halo"], "cotangent": p["out"],
           "in_transient": 0.0 if save_gathered else fwd["in_transient"],
           "ker_transient": 0.0 if save_gathered else fwd["ker_transient"],
           # token/gathered buffers + unwindow block + dxl / + dwl
           "din": din_t + p["xh"] + p["xl"],
           "dker": dker_t + wl,
           "residuals": resid}
    bwd["peak"] = sum(v for k, v in bwd.items() if k != "peak")
    fwd_peak = fwd["peak"] + resid
    return {"fwd": fwd, "bwd": bwd,
            "peak": max(fwd_peak, bwd["peak"])}
