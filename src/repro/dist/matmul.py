"""Distributed matrix multiplication on a 3-axis processor grid
(paper Sec. 2.2: the 2D-SUMMA / 2.5D / 3D family).

Grid ``(Pm, Pn, Pc)`` over mesh axes ``("m", "n", "c")``:

* ``In  [M, C]`` sharded ``P("m", ("c", "n"))`` — rows over m, contraction
  over c then sub-sharded over n;
* ``Ker [C, N]`` sharded ``P(("c", "m"), "n")`` — contraction over c then
  sub-sharded over m, columns over n;
* ``Out [M, N]`` sharded ``P("m", "n")``, replicated over c.

Per-device communication (the paper's cost_C): all-gather In over n
(``|In|/P * (Pn-1)`` elements), all-gather Ker over m
(``|Ker|/P * (Pm-1)``), all-reduce Out over c (``2|Out|/(Pm*Pn) *
(Pc-1)/Pc``).  ``Pc = 1`` gives the 2D SUMMA algorithm, ``Pc > 1`` with
replication the 2.5D variant, and a balanced ``(Pm, Pn, Pc)`` the 3D one.

``schedule="ring"`` pipelines the contraction: Ker shards rotate around the
m-ring and each arriving chunk is contracted against the matching column
slab of the gathered In — but In is still fully all-gathered over n up
front, so per-rank peak memory is gathered-size on the large operand.

``schedule="ring2"`` pipelines *both* sides: In's c-slabs rotate around the
n-ring while Ker's c-chunks rotate around the m-ring
(:func:`collectives.ring_zip`), so no rank ever materializes a gathered
operand.  Same wire volume, slab-size peak memory.  Supported on grids
where one contraction ring is trivial (``Pm == 1`` or ``Pn == 1``, pure
streaming against the stationary shard) or both rings have size 2 (the
own-shard covered zip — see ``repro.dist.conv2d`` for the phase-lag
analysis); other grids fall back to ``"ring"``
(:func:`matmul_ring2_supported`).

Per-step local products are dispatched through
``repro.kernels.ops.local_matmul`` — the Pallas tiled kernel with the
memoized paper plan when the shape tiles, the XLA dot otherwise.

**Differentiation.**  ``matmul_distributed`` carries a ``jax.custom_vjp``
transposing the schedule: the Out cotangent arrives replicated over c
(transpose of the all-reduce), the forward gathers are replayed (or
re-streamed, for ``ring2``), and ``dIn = g @ Ker^T`` / ``dKer = In^T @ g``
are reduce-scattered over n / m respectively — each scatter moving exactly
the volume of the gather it transposes.  ``save_gathered=True``
differentiates the forward natively instead, saving the gathered operands
as residuals and paying zero gather-replay traffic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from repro.dist.collectives import (SCHEDULES, gather_axis, make_mesh,
                                    ppermute, psum, ring_reduce,
                                    ring_scatter_reduce, ring_zip,
                                    scatter_axis, stream_elems)
from repro.kernels import ops as kops

AXES = ("m", "n", "c")


def make_matmul_mesh(grid) -> Mesh:
    """Mesh over axes ``("m", "n", "c")`` from a ``(Pm, Pn, Pc)`` tuple."""
    if len(grid) != 3:
        raise ValueError(f"matmul grid must be (Pm, Pn, Pc), got {grid}")
    return make_mesh(grid, AXES)


def matmul_mesh_from_conv(mesh: Mesh) -> Mesh:
    """View a conv ``(b,h,w,k,c)`` mesh as a matmul ``(m,n,c)`` mesh:
    the composite ``b*h*w`` extent becomes m (rows), k becomes n (columns),
    c stays the contraction axis.  Device order is preserved, so the two
    meshes coexist inside one program."""
    devs = mesh.devices
    if devs.ndim != 5:
        raise ValueError(f"expected a 5-axis conv mesh, got {mesh}")
    pb, ph, pw, pk, pc = devs.shape
    return Mesh(devs.reshape(pb * ph * pw, pk, pc), AXES)


def matmul_ring2_supported(grid) -> bool:
    """True when the two-ring schedule covers ``grid = (Pm, Pn, Pc)``: a
    trivial contraction ring on either side or both rings of size 2."""
    pm, pn, pc = grid
    return pm == 1 or pn == 1 or (pm == 2 and pn == 2)


def _matmul_effective_schedule(schedule: str, grid) -> str:
    if schedule == "ring2" and not matmul_ring2_supported(grid):
        return "ring"
    return schedule


def _check_matmul_shapes(M: int, C: int, N: int, grid) -> None:
    """Raise unless the shapes satisfy the runtime sub-shard divisibility
    constraints — the single source both the runtime op and the
    :func:`matmul_grid_divides` predicate share."""
    pm, pn, pc = grid
    for extent, div, what in [(M, pm, "M % Pm"), (N, pn, "N % Pn"),
                              (C, pc * pn, "C % (Pc*Pn)"),
                              (C, pc * pm, "C % (Pc*Pm)")]:
        if div <= 0 or extent % div:
            raise ValueError(f"shape not divisible by grid: {what} != 0 "
                             f"({extent} % {div})")


def matmul_grid_divides(M: int, C: int, N: int, grid) -> bool:
    """True when the operand shapes satisfy the runtime sub-shard
    divisibility constraints of :func:`matmul_distributed`."""
    try:
        _check_matmul_shapes(M, C, N, grid)
    except ValueError:
        return False
    return True


def _matmul_fwd_ring2(xl, wl, *, pm, pn, mm):
    """Two-ring forward: In slabs rotate the n-ring, Ker chunks the m-ring
    (see ``repro.dist.conv2d`` for the schedule's coverage argument)."""
    cx = xl.shape[1]   # C / (Pc*Pn), the In c-slab width
    cw = wl.shape[0]   # C / (Pc*Pm), the Ker c-chunk width
    if pm == 1 and pn == 1:
        return mm(xl, wl)
    if pn == 1:
        # In holds its full C/Pc columns: stream Ker chunks around m
        def chunk_dot(acc, src, wchunk):
            xs = lax.dynamic_slice_in_dim(xl, src * cw, cw, axis=1)
            part = mm(xs, wchunk)
            return part if acc is None else acc + part

        return ring_reduce(wl, "m", chunk_dot, None)
    if pm == 1:
        # Ker holds its full C/Pc rows: stream In slabs around n
        def slab_dot(acc, src, slab):
            ws = lax.dynamic_slice_in_dim(wl, src * cx, cx, axis=0)
            part = mm(slab, ws)
            return part if acc is None else acc + part

        return ring_reduce(xl, "n", slab_dot, None)
    # Pm == Pn == 2: zip both rings, own shards cover the misaligned pairs
    nu, mu = lax.axis_index("n"), lax.axis_index("m")
    aligned = nu == mu

    def zip_body(acc, t, sx, cur_x, sw, cur_w):
        # accumulate the two masked products one at a time so their
        # out-sized scratch buffers can be reused, not live together
        w1 = jnp.where(aligned, cur_w, wl)
        m1 = jnp.logical_or(aligned, sx == mu)
        c1 = mm(cur_x, w1)
        acc = c1 * m1.astype(c1.dtype) if acc is None \
            else acc + c1 * m1.astype(c1.dtype)
        m2 = jnp.logical_and(jnp.logical_not(aligned), sw == nu)
        c2 = mm(xl, cur_w)
        return acc + c2 * m2.astype(c2.dtype)

    return ring_zip(xl, "n", wl, "m", zip_body, None)


def _local_matmul(xl, wl, *, pm, pn, pc, schedule, pallas=True):
    mm = functools.partial(kops.local_matmul, prefer_pallas=pallas)
    if schedule == "ring2":
        out = _matmul_fwd_ring2(xl, wl, pm=pm, pn=pn, mm=mm)
        if pc > 1:
            out = psum(out, "c", tag="matmul_out")
        return out
    # gather In's contraction sub-shard over n -> full C/Pc slab
    xg = gather_axis(xl, "n", dim=1, schedule=schedule) if pn > 1 else xl
    if pm == 1:
        out = mm(xg, wl)
    elif schedule == "ring":
        # pipelined SUMMA: rotate Ker shards around the m-ring, contract
        # each against its matching column slab of In as it arrives
        chunk = wl.shape[0]

        def partial_dot(acc, src, wchunk):
            xs = lax.dynamic_slice_in_dim(xg, src * chunk, chunk, axis=1)
            part = mm(xs, wchunk)
            return part if acc is None else acc + part

        out = ring_reduce(wl, "m", partial_dot, None)
    else:
        wg = gather_axis(wl, "m", dim=0, schedule=schedule)
        out = mm(xg, wg)
    if pc > 1:
        out = psum(out, "c", tag="matmul_out")
    return out


def _matmul_bwd_ring2(xl, wl, gl, *, pm, pn):
    """Streaming backward of the two-ring schedule: dIn slabs are produced
    on the fly and reduced around the n-ring, dKer chunks around the
    m-ring — no gathered operand or gradient is materialized."""
    cx = xl.shape[1]
    cw = wl.shape[0]
    mm = kops.local_matmul
    ring2 = [(i, (i + 1) % 2) for i in range(2)]

    # --- dIn = g @ Ker^T, slab-wise --------------------------------------
    if pn == 1:
        if pm == 1:
            dxl = mm(gl, wl.T)
        else:
            def fill_dx(acc, src, wchunk):
                part = mm(gl, wchunk.T)
                return lax.dynamic_update_slice_in_dim(
                    acc, part.astype(acc.dtype), src * cw, axis=1)

            dxl = ring_reduce(wl, "m", fill_dx,
                              jnp.zeros(xl.shape, gl.dtype))
    elif pm == 1:
        def produce_dx(r, t):
            ws = lax.dynamic_slice_in_dim(wl, r * cx, cx, axis=0)
            return mm(gl, ws.T)

        dxl = ring_scatter_reduce("n", produce_dx)
    else:  # Pm == Pn == 2: one m-hop re-delivers the foreign Ker chunk
        w_arr = ppermute(wl, "m", ring2, tag="ring2_redeliver")
        aligned = lax.axis_index("n") == lax.axis_index("m")

        def produce_dx(r, t):
            wsel = jnp.where(aligned, w_arr, wl) if t == 0 \
                else jnp.where(aligned, wl, w_arr)
            return mm(gl, wsel.T)

        dxl = ring_scatter_reduce("n", produce_dx)

    # --- dKer = In^T @ g, chunk-wise -------------------------------------
    if pm == 1:
        if pn == 1:
            dwl = mm(xl.T, gl)
        else:
            def fill_dw(acc, src, slab):
                part = mm(slab.T, gl)
                return lax.dynamic_update_slice_in_dim(
                    acc, part.astype(acc.dtype), src * cx, axis=0)

            dwl = ring_reduce(xl, "n", fill_dw,
                              jnp.zeros(wl.shape, gl.dtype))
    elif pn == 1:
        def produce_dw(r, t):
            xs = lax.dynamic_slice_in_dim(xl, r * cw, cw, axis=1)
            return mm(xs.T, gl)

        dwl = ring_scatter_reduce("m", produce_dw)
    else:  # Pm == Pn == 2: one n-hop re-delivers the foreign In slab
        x_arr = ppermute(xl, "n", ring2, tag="ring2_redeliver")
        aligned = lax.axis_index("n") == lax.axis_index("m")

        def produce_dw(r, t):
            xsel = jnp.where(aligned, x_arr, xl) if t == 0 \
                else jnp.where(aligned, xl, x_arr)
            return mm(xsel.T, gl)

        dwl = ring_scatter_reduce("m", produce_dw)
    return dxl, dwl


def _local_matmul_bwd(xl, wl, gl, *, pm, pn, pc, schedule):
    """Transposed schedule: replay the gathers (or re-stream, for ring2),
    contract against the replicated Out cotangent, reduce-scatter each
    operand gradient."""
    if schedule == "ring2":
        dxl, dwl = _matmul_bwd_ring2(xl, wl, gl, pm=pm, pn=pn)
        return dxl.astype(xl.dtype), dwl.astype(wl.dtype)
    mm = kops.local_matmul
    xg = gather_axis(xl, "n", dim=1, schedule=schedule) if pn > 1 else xl
    wg = gather_axis(wl, "m", dim=0, schedule=schedule) if pm > 1 else wl
    dxg = mm(gl, wg.T)                   # [M/pm, C/pc]
    dwg = mm(xg.T, gl)                   # [C/pc, N/pn]
    dxl = scatter_axis(dxg, "n", dim=1, schedule=schedule) \
        if pn > 1 else dxg
    dwl = scatter_axis(dwg, "m", dim=0, schedule=schedule) \
        if pm > 1 else dwg
    return dxl.astype(xl.dtype), dwl.astype(wl.dtype)


def _matmul_raw(x, w, mesh, schedule, pallas=True):
    """The forward shard_map itself — differentiable natively for the
    ``save_gathered=True`` memory-for-wire endpoint.  The local
    contractions keep their autotuned Pallas winners: every candidate
    behind ``kops.local_matmul`` carries a ``custom_vjp`` (backward via
    the same kernel family on transposed operands)."""
    sizes = dict(mesh.shape)
    pm, pn, pc = sizes["m"], sizes["n"], sizes["c"]
    fn = shard_map(
        functools.partial(_local_matmul, pm=pm, pn=pn, pc=pc,
                          schedule=schedule, pallas=pallas),
        mesh=mesh,
        in_specs=(P("m", ("c", "n")), P(("c", "m"), "n")),
        out_specs=P("m", "n"),
        check_vma=False)
    return fn(x, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _matmul_vjp(x, w, mesh, schedule):
    return _matmul_raw(x, w, mesh, schedule)


def _matmul_fwd(x, w, mesh, schedule):
    return _matmul_vjp(x, w, mesh, schedule), (x, w)


def _matmul_bwd(mesh, schedule, res, g):
    x, w = res
    sizes = dict(mesh.shape)
    pm, pn, pc = sizes["m"], sizes["n"], sizes["c"]
    fn = shard_map(
        functools.partial(_local_matmul_bwd, pm=pm, pn=pn, pc=pc,
                          schedule=schedule),
        mesh=mesh,
        in_specs=(P("m", ("c", "n")), P(("c", "m"), "n"), P("m", "n")),
        out_specs=(P("m", ("c", "n")), P(("c", "m"), "n")),
        check_vma=False)
    return fn(x, w, g)


_matmul_vjp.defvjp(_matmul_fwd, _matmul_bwd)


def matmul_distributed(x, w, mesh: Mesh, *, schedule: str = "allgather",
                       save_gathered: bool = False):
    """``x @ w`` on the 3-axis grid; result matches the serial product and
    is differentiable.  The default custom VJP rematerializes the forward
    gathers; ``save_gathered=True`` differentiates natively, saving the
    gathered operands as residuals (zero gather-replay traffic).
    ``schedule="ring2"`` falls back to ``"ring"`` on grids
    :func:`matmul_ring2_supported` rejects."""
    if schedule not in SCHEDULES:
        raise ValueError(f"schedule must be one of {SCHEDULES}")
    sizes = dict(mesh.shape)
    missing = [a for a in AXES if a not in sizes]
    if missing:
        raise ValueError(f"mesh lacks axes {missing}; use make_matmul_mesh")
    pm, pn, pc = sizes["m"], sizes["n"], sizes["c"]
    (M, C), (C2, N) = x.shape, w.shape
    if C != C2:
        raise ValueError(f"contraction mismatch: {x.shape} @ {w.shape}")
    _check_matmul_shapes(M, C, N, (pm, pn, pc))
    schedule = _matmul_effective_schedule(schedule, (pm, pn, pc))
    if save_gathered:
        return _matmul_raw(x, w, mesh, schedule)
    return _matmul_vjp(x, w, mesh, schedule)


def matmul_comm_elems(M: int, C: int, N: int, grid) -> dict:
    """Analytic per-device communication (elements) of the forward
    schedule — the Sec. 2.2 accounting that ``analyze_hlo`` wire bytes are
    checked against.  Identical for every schedule: each operand piece
    crosses its ring exactly once however it is pipelined."""
    pm, pn, pc = grid
    P_tot = pm * pn * pc
    gather_in = (M * C / P_tot) * (pn - 1)
    gather_ker = (C * N / P_tot) * (pm - 1)
    reduce_out = 2 * (M / pm) * (N / pn) * (pc - 1) / pc
    return {"gather_in": gather_in, "gather_ker": gather_ker,
            "reduce_out": reduce_out,
            "total": gather_in + gather_ker + reduce_out}


def matmul_train_comm_elems(M: int, C: int, N: int, grid, *,
                            save_gathered: bool = False) -> dict:
    """Forward + backward analytic per-device wire volume (elements): the
    backward replays both gathers and transposes each into an equal-volume
    reduce-scatter; the c-axis all-reduce transposes to a free broadcast.
    ``save_gathered=True`` drops the replay terms (the gathered operands
    are stored as residuals, not re-fetched) but pays the forward
    ``reduce_out`` volume once more: the native transpose of the c-axis
    psum cannot prove the cotangent replicated under ``check_vma=False``
    and psums it."""
    fwd = matmul_comm_elems(M, C, N, grid)
    replay = 0.0 if save_gathered else 1.0
    bwd = {"gather_in_replay": replay * fwd["gather_in"],
           "gather_ker_replay": replay * fwd["gather_ker"],
           "rs_in": fwd["gather_in"],
           "rs_ker": fwd["gather_ker"],
           "psum_out_bwd": fwd["reduce_out"] if save_gathered else 0.0}
    bwd["total"] = sum(v for k, v in bwd.items() if k != "total")
    return {"fwd": fwd, "bwd": bwd, "total": fwd["total"] + bwd["total"]}


# --------------------------------------------------------------------------
# Analytic per-device peak-live-memory accounting (fwd and fwd+bwd)
# --------------------------------------------------------------------------

def _matmul_mem_parts(M: int, C: int, N: int, grid) -> dict:
    """Per-device buffer sizes (elements) shared by the fwd and train
    peak-live accounting."""
    pm, pn, pc = grid
    return {"xl": (M / pm) * C / (pc * pn),
            "wl": (C / (pc * pm)) * (N / pn),
            "out": (M / pm) * (N / pn)}


def matmul_mem_elems(M: int, C: int, N: int, grid, *,
                     schedule: str = "allgather") -> dict:
    """Analytic per-device peak live memory (elements) of one forward
    pass: resident shards + the schedule's gather results / stream
    buffers + the output (doubled under a ``Pc > 1`` all-reduce)."""
    pm, pn, pc = grid
    schedule = _matmul_effective_schedule(schedule, grid)
    p = _matmul_mem_parts(M, C, N, grid)
    xl, wl, out = p["xl"], p["wl"], p["out"]
    if schedule == "allgather":
        in_t = pn * xl if pn > 1 else 0.0
        ker_t = pm * wl if pm > 1 else 0.0
    elif schedule == "ring":
        in_t = pn * xl + (xl if pn > 1 else 0.0) if pn > 1 else 0.0
        ker_t = stream_elems(pm, wl)
    else:  # ring2
        in_t = stream_elems(pn, xl)
        ker_t = stream_elems(pm, wl)
    comp = {"args": xl + wl, "in_transient": in_t, "ker_transient": ker_t,
            "out": out * (2.0 if pc > 1 else 1.0)}
    comp["peak"] = sum(comp.values())
    return comp


def matmul_train_mem_elems(M: int, C: int, N: int, grid, *,
                           schedule: str = "allgather",
                           save_gathered: bool = False) -> dict:
    """Peak live memory (elements) of a forward + backward pass (see
    ``conv_train_mem_elems`` for the model)."""
    pm, pn, pc = grid
    schedule = _matmul_effective_schedule(schedule, grid)
    fwd = matmul_mem_elems(M, C, N, grid, schedule=schedule)
    p = _matmul_mem_parts(M, C, N, grid)
    xl, wl, g = p["xl"], p["wl"], p["out"]
    if schedule == "ring2":
        din_t = stream_elems(pn, xl)
        dker_t = stream_elems(pm, wl)
    else:
        din_t = pn * xl if pn > 1 else 0.0
        dker_t = pm * wl if pm > 1 else 0.0
    resid = (pn * xl + pm * wl) if save_gathered else 0.0
    bwd = {"args": fwd["args"], "cotangent": g,
           "in_transient": 0.0 if save_gathered else fwd["in_transient"],
           "ker_transient": 0.0 if save_gathered else fwd["ker_transient"],
           "din": din_t + xl, "dker": dker_t + wl,
           "residuals": resid}
    bwd["peak"] = sum(v for k, v in bwd.items() if k != "peak")
    return {"fwd": fwd, "bwd": bwd,
            "peak": max(fwd["peak"] + resid, bwd["peak"])}
