"""VMEM BlockSpec sizing via the paper's Eq. 4 optimizer.

The paper's two-level model applies *twice* on TPU (DESIGN.md §3).  This is
the chip level: fast memory = VMEM, slow memory = HBM, "processors" = the
sequential grid steps (P = 1).  The optimizer picks (T_bhw, T_k) minimizing
HBM traffic; we then project onto MXU-aligned integers (multiples of 128 on
the matmul dims, or the full extent when smaller).

TPU adaptation recorded in DESIGN.md §6: the paper's T_c = 1 is
movement-optimal but starves the 128x128 systolic array, so the contraction
block is floored at min(N_c, 256) and the Eq. 4 budget reduced accordingly.

The Eq. 4 plan is what the model would like; ``fit_matmul_blocks`` and
``fit_conv_blocks`` turn it into blocks the chip's compiler accepts (the
Winograd tile GEMM is one matmul per frequency).  Two rules decide,
before anything is compiled:

* **tiling** — the last two dims of every block are a multiple of the
  (sublane, 128-lane) tile of the dtype, or the whole array extent;
* **VMEM** — the double-buffered operand and output blocks, the f32
  accumulator and the kernel body's dot temporaries, each padded to that
  tile, fit ``VMEM_BUDGET_BYTES``.

A shape with no block that passes both returns ``None``: the kernel
cannot hold it, and the dispatcher (``kernels.ops``) leaves it to
another candidate.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

from repro.core import cost_model, tile_optimizer
from repro.core.problem import ConvProblem

# Eq. 4 planning budget in elements: what the paper model may assume when
# it picks the "wanted" tiles.  The hardware fit is enforced separately,
# in bytes, by the fit_* functions below.
VMEM_ELEMS_BUDGET = 16 * 1024 * 1024

# Bytes of VMEM the modeled buffers and temporaries of one kernel may
# take.  The TPU compiler's default scoped VMEM limit is 16 MiB on v5e;
# 2 MiB are left for what the model does not count.
VMEM_BUDGET_BYTES = 14 * 1024 * 1024
LANES = 128


def _align(x: float, mult: int, hi: int) -> int:
    """Round to a multiple of ``mult``, clamped to [mult, hi] (or hi if the
    extent itself is smaller than one multiple)."""
    if hi <= mult:
        return hi
    v = int(max(mult, round(x / mult) * mult))
    return min(v, (hi // mult) * mult)


@dataclasses.dataclass(frozen=True)
class BlockPlan:
    block_bhw: int     # rows of the (bhw) x k output tile
    block_k: int       # output-feature block
    block_c: int       # contraction block (TPU floor, see module doc)
    vmem_elems: float  # modeled footprint
    hbm_traffic: float # modeled HBM<->VMEM elements moved (Eq. 4 cost)


def plan_blocks(p: ConvProblem, *, vmem_elems: int = VMEM_ELEMS_BUDGET,
                mxu: int = 128) -> BlockPlan:
    """Block sizes for the conv/matmul kernels from the paper's model."""
    block_c = min(p.Nc, 256)
    # Budget left for the (bhw, k) tiles after the contraction slabs
    # (In tile scales with block_c, Ker tile with block_c * stencil).
    # Solve Eq. 4 with P=1 on the reduced budget.
    sol = tile_optimizer.solve_closed_form(
        p, P=1, M=max(4 * mxu * mxu, vmem_elems // max(1, 2 * block_c // 128)),
        ml_correction=True)
    tbhw = _align(sol.choice.Tbhw, mxu, p.Nbhw)
    tk = _align(sol.choice.Tk, mxu, p.Nk)
    in_tile = p.sh * p.sw * tbhw * block_c
    ker_tile = p.Nr * p.Ns * tk * block_c
    out_tile = tbhw * tk
    foot = in_tile + ker_tile + out_tile
    # shrink until the true footprint fits
    while foot > vmem_elems and (tbhw > mxu or tk > mxu):
        if tbhw >= tk and tbhw > mxu:
            tbhw = max(mxu, tbhw // 2)
        elif tk > mxu:
            tk = max(mxu, tk // 2)
        else:
            break
        foot = (p.sh * p.sw * tbhw * block_c + p.Nr * p.Ns * tk * block_c
                + tbhw * tk)
    cost = cost_model.cost_simplified(p, 1, p.Nbhw, p.Nk, tbhw, tk)
    return BlockPlan(block_bhw=tbhw, block_k=tk, block_c=block_c,
                     vmem_elems=foot, hbm_traffic=cost)


def matmul_blocks(m: int, n: int, k: int, *,
                  vmem_elems: int = VMEM_ELEMS_BUDGET) -> Tuple[int, int, int]:
    plan = plan_blocks(ConvProblem.from_matmul(m, n, k), vmem_elems=vmem_elems)
    return plan.block_bhw, plan.block_k, plan.block_c


# --------------------------------------------------------------------------
# Hardware fit: (sublane, lane) tiling and the VMEM budget
# --------------------------------------------------------------------------

def sublanes(itemsize: int) -> int:
    """Rows of one (sublane, 128) VMEM tile: 8 for 32-bit, 16 for bf16."""
    return 8 * max(1, 4 // itemsize)


def _rup(x: int, m: int) -> int:
    return -(-x // m) * m


def tile_bytes(shape, itemsize: int) -> int:
    """Bytes of a VMEM buffer of ``shape``: the last two dims padded to the
    (sublane, lane) tile."""
    *lead, rows, cols = (1,) * max(0, 2 - len(shape)) + tuple(shape)
    n = _rup(rows, sublanes(itemsize)) * _rup(cols, LANES) * itemsize
    for d in lead:
        n *= d
    return n


@functools.lru_cache(maxsize=None)
def math_gcd_block(extent: int, want: int) -> int:
    """Largest divisor of ``extent`` not exceeding ``want`` — by divisor
    enumeration in O(sqrt(extent)) (the former descending scan was
    O(extent) on large prime extents), memoized."""
    want = min(want, extent)
    best = 1
    for d in range(1, math.isqrt(extent) + 1):
        if extent % d == 0:
            if d <= want:
                best = max(best, d)
            q = extent // d
            if q <= want:
                best = max(best, q)
    return best


def legal_block(extent: int, want: int, align: int) -> int:
    """Largest block ``<= want`` that divides ``extent`` and is a multiple
    of ``align``.  The whole extent when it is ``<= want`` or when no such
    divisor exists: a full-extent block is always legal, and the VMEM
    check then decides whether it can be held."""
    if extent <= want or extent % align or want < align:
        return extent
    return align * math_gcd_block(extent // align, want // align)


def _shrink(blocks, fits, legal):
    """Halve the largest block that can still shrink until
    ``fits(blocks)``; ``None`` when none can and it still does not fit."""
    blocks = list(blocks)
    while not fits(blocks):
        for i in sorted(range(len(blocks)), key=lambda i: -blocks[i]):
            smaller = legal(i, blocks[i] // 2)
            if smaller < blocks[i]:
                blocks[i] = smaller
                break
        else:
            return None
    return tuple(blocks)


def matmul_vmem_bytes(bm: int, bn: int, bk: int, itemsize: int) -> int:
    """Modeled VMEM of ``matmul_pallas``: double-buffered x/w/out blocks,
    the f32 accumulator, and the body's temporaries — the f32 dot result
    and its sum with the accumulator, plus the operand tiles as the MXU
    takes them (an f32 dot at ``highest`` precision splits each operand
    into three bf16 parts, so f32 operands count three times)."""
    operands = tile_bytes((bm, bk), itemsize) + tile_bytes((bk, bn), itemsize)
    return (2 * (operands + tile_bytes((bm, bn), itemsize))
            + 3 * tile_bytes((bm, bn), 4)
            + (3 if itemsize == 4 else 1) * operands)


def fit_matmul_blocks(m: int, n: int, k: int, want, itemsize: int, *,
                      budget: int = VMEM_BUDGET_BYTES
                      ) -> Optional[Tuple[int, int, int]]:
    """``(bm, bn, bk)`` nearest ``want`` that tiles and fits VMEM, or
    ``None``.  bm is a sublane dim, bn and bk are lane dims."""
    extents = (m, n, k)
    aligns = (sublanes(itemsize), LANES, LANES)

    def legal(i, w):
        return legal_block(extents[i], max(1, w), aligns[i])

    blocks = [legal(i, w) for i, w in enumerate(want)]
    return _shrink(blocks, lambda b: matmul_vmem_bytes(*b, itemsize)
                   <= budget, legal)


def conv_vmem_bytes(bb: int, bk: int, bc: int, hp: int, wp: int, kh: int,
                    kw: int, itemsize: int) -> int:
    """Modeled VMEM of ``conv2d_pallas`` (channels-last blocks): double-
    buffered image slab, kernel slab and output tile, the f32 accumulator,
    and one output row's dot temporaries (operands counted as in
    :func:`matmul_vmem_bytes`)."""
    ho, wo = hp - kh + 1, wp - kw + 1
    row_operands = tile_bytes((wo, bc), itemsize) + tile_bytes((bc, bk),
                                                               itemsize)
    return (2 * (bb * hp * tile_bytes((wp, bc), itemsize)
                 + kh * kw * tile_bytes((bc, bk), itemsize)
                 + bb * ho * tile_bytes((wo, bk), itemsize))
            + bb * ho * tile_bytes((wo, bk), 4)
            + 3 * tile_bytes((wo, bk), 4)
            + (3 if itemsize == 4 else 1) * row_operands)


def fit_conv_blocks(n: int, c: int, k: int, hp: int, wp: int, kh: int,
                    kw: int, want, itemsize: int, *,
                    budget: int = VMEM_BUDGET_BYTES
                    ) -> Optional[Tuple[int, int, int]]:
    """``(bb, bk, bc)`` nearest ``want`` for a stride-1 conv whose padded
    input is ``hp x wp``, or ``None`` when even one padded image of the
    narrowest legal channel block does not fit.  bk and bc are lane dims;
    bb is a leading dim (any divisor of n)."""
    extents = (n, k, c)
    aligns = (1, LANES, LANES)

    def legal(i, w):
        return legal_block(extents[i], max(1, w), aligns[i])

    blocks = [legal(i, w) for i, w in enumerate(want)]
    return _shrink(blocks, lambda b: conv_vmem_bytes(
        b[0], b[1], b[2], hp, wp, kh, kw, itemsize) <= budget, legal)

