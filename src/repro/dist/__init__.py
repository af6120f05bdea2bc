"""Distributed CNN/matmul algorithms (paper Secs. 2-3): the 2D-SUMMA /
2.5D / 3D family on explicit processor grids, plus the supporting
primitives — halo exchange, microbatch pipelining, compressed reductions.

Grid tuple conventions:

* conv:   ``(Pb, Ph, Pw, Pk, Pc)`` over mesh axes ``("b","h","w","k","c")``
* matmul: ``(Pm, Pn, Pc)``         over mesh axes ``("m","n","c")``

Schedule tradeoffs (per device; "slab"/"chunk" = one rank's contraction
sub-shard of In / Ker, g = ring size; wire volumes are identical because
each piece crosses its ring exactly once however it is pipelined):

=========== ======================= ============================ =============
schedule    wire (contraction ops)  peak operand memory          latency shape
=========== ======================= ============================ =============
allgather   slab*(g-1) + chunk*(g-1) both operands gathered       1 collective
                                     (g slabs + g chunks)         per operand
ring        same                     Ker gathered (g chunks),     g pipelined
                                     In streams (O(1) slabs)      steps
ring2       same                     nothing gathered: O(1)       g pipelined
                                     slabs + O(1) chunks          steps, 2
                                                                  contractions
                                                                  per step on
                                                                  the zip path
=========== ======================= ============================ =============

``ring2`` additionally shrinks the backward spatial psum of dKer by
``1/Pb`` (the chunk is scattered before the reduce).  It covers grids
where one contraction ring is trivial or both have size 2
(``conv_ring2_supported`` / ``matmul_ring2_supported``) and falls back to
``ring`` elsewhere — larger double rings would need a Cannon alignment
skew costing an extra wire hop per operand (see ``dist.conv2d``).

Every op is differentiable: ``conv2d_distributed``, ``matmul_distributed``,
``halo_exchange_1d`` and ``pipelined_apply`` carry custom VJPs whose
backward passes transpose the forward communication structure (gathers to
reduce-scatters, the c-axis all-reduce to a broadcast, halo exchange to
halo accumulation), so ``jax.grad`` of a model built on them runs the
paper's fwd+bwd schedule end to end (see ``dist/train.py``).  The custom
VJPs rematerialize the forward gathers (communication-optimal memory);
``save_gathered=True`` differentiates natively instead, saving the
gathered operands as residuals and paying zero gather-replay wire.
``conv_mem_elems`` / ``matmul_mem_elems`` (+ ``*_train_*`` variants) give
the analytic per-device peak-live accounting of both endpoints, alongside
the ``*_comm_elems`` wire accounting.

Per-step local contractions dispatch through ``repro.kernels.ops``
(Pallas tiled kernels with memoized paper plans where the shapes tile,
XLA otherwise; ``REPRO_DIST_PALLAS=0`` forces XLA).

**Verified invariants.**  ``repro.analysis`` (CLI:
``python -m repro.analysis.lint`` / ``make verify-dist``) statically
proves the claims above against the *compiled* post-SPMD HLO of every
op, on a fake CPU mesh with no real devices:

* **wire accounting** — IR wire bytes (ring model: all-gather
  ``V*(g-1)/g``, reduce-scatter ``shard*(g-1)``, all-reduce
  ``2V*(g-1)/g``, ppermute ``V``; loop-body collectives multiplied by
  their trip counts) equal ``*_comm_elems`` / ``*_train_comm_elems``
  within 2%, forward and VJP;
* **footprint** — ``ring``/``ring2`` compile with no all-gather on a
  contraction-ring operand, and XLA's ``memory_analysis()`` peak-live
  stays within a band of ``*_mem_elems`` / ``*_train_mem_elems``;
* **deadlock freedom** — every compiled ppermute's source-target pairs
  are attributable to one mesh-axis ring, cycles cover their whole
  device group, and ring-tagged permutes form total bijections;
* **attribution** — every collective in the IR is declared by a
  trace-time ``collectives.record_collectives()`` note and vice versa
  (the accounted wrappers in ``dist.collectives`` are the only legal
  spelling of raw collectives — enforced by an AST lint).
"""

from repro.dist.collectives import (
    SCHEDULES,
    CollectiveNote,
    gather_axis,
    make_mesh,
    record_collectives,
    ring_all_gather,
    ring_reduce,
    ring_reduce_scatter,
    ring_scatter_reduce,
    ring_zip,
    scatter_axis,
)
from repro.dist.compress import compressed_psum, compressed_psum_tree
from repro.dist.conv2d import (
    conv2d_distributed,
    conv_comm_elems,
    conv_grid_divides,
    conv_mem_elems,
    conv_ring2_supported,
    conv_train_comm_elems,
    conv_train_mem_elems,
    make_conv_mesh,
)
from repro.dist.halo import halo_accumulate_1d, halo_exchange_1d
from repro.dist.matmul import (
    make_matmul_mesh,
    matmul_comm_elems,
    matmul_distributed,
    matmul_grid_divides,
    matmul_mem_elems,
    matmul_mesh_from_conv,
    matmul_ring2_supported,
    matmul_train_comm_elems,
    matmul_train_mem_elems,
)
from repro.dist.pipeline import pipelined_apply

# dist.train sits above the model/optimizer stack (it imports models.cnn
# and train.step, which themselves import repro.dist lazily); re-export it
# lazily so importing the primitives package neither pulls in the whole
# training stack nor risks a circular import.
_TRAIN_EXPORTS = ("make_grid_train_step", "init_grid_train_state",
                  "cnn_train_comm_elems", "cnn_train_mem_elems",
                  "grid_divides_cnn")


def __getattr__(name):
    if name in _TRAIN_EXPORTS:
        from repro.dist import train as _train
        return getattr(_train, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "SCHEDULES", "CollectiveNote", "record_collectives",
    "gather_axis", "ring_all_gather",
    "ring_reduce", "ring_reduce_scatter", "ring_scatter_reduce",
    "ring_zip", "scatter_axis", "make_mesh",
    "conv2d_distributed", "make_conv_mesh", "conv_comm_elems",
    "conv_train_comm_elems", "conv_grid_divides", "conv_mem_elems",
    "conv_train_mem_elems", "conv_ring2_supported",
    "matmul_distributed", "make_matmul_mesh", "matmul_comm_elems",
    "matmul_train_comm_elems", "matmul_grid_divides", "matmul_mem_elems",
    "matmul_train_mem_elems", "matmul_ring2_supported",
    "matmul_mesh_from_conv",
    "halo_exchange_1d", "halo_accumulate_1d", "pipelined_apply",
    "compressed_psum", "compressed_psum_tree",
    "make_grid_train_step", "init_grid_train_state",
    "cnn_train_comm_elems", "cnn_train_mem_elems", "grid_divides_cnn",
]
