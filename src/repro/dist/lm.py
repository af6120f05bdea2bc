"""LM serving on the ``(Pm, Pn, Pc)`` matmul grid — the paper's
2D-SUMMA / 2.5D / 3D family routed under transformer inference.

A decoder-only transformer step is a chain of matmuls: the QKV/O
projections, the (possibly gated) MLP, and the vocabulary head.  Each one
is the degenerate 1x1 CNN of the paper, so each one runs on the explicit
``(Pm, Pn, Pc)`` grid through :func:`repro.dist.matmul.matmul_distributed`
— token rows over m, output features over n, the d_model contraction
sub-sharded over c (2.5D replication when ``Pc > 1``).

:func:`dist_projection` is the routing shim ``models/lm.py`` calls when a
``dist_mesh=`` is passed: it flattens ``[..., C] @ [C, N]`` to the 2D
matmul view, checks the runtime sub-shard divisibility constraints, and
falls back to the dense dot for shapes the grid cannot divide (tiny
router tables, indivisible feature extents) — so a model never fails to
serve because one projection does not tile.

**MoE expert contractions.**  :func:`expert_ffn_distributed` runs the
grouped expert FFN (`models/moe.py` dispatch -> per-expert gate/up/down
-> combine) with the *expert dimension on the contraction ring*: the
stacked expert weights are sharded over c (each c-rank owns ``E/Pc``
experts), the expert ff dim over n, and — because dispatch selects and
combine sums over experts — the only communication is one all-reduce of
the combined ``[g, t, d]`` output over the ``(n, c)`` plane.  The
per-expert contractions dispatch through ``kernels.ops.local_matmul``
like every other distributed inner step.

**Accounting.**  :func:`lm_serve_comm_elems` /
:func:`lm_serve_mem_elems` extend the analytic per-device accounting to
a serving step: per-token decode wire (every projection's
``matmul_comm_elems`` plus the MoE combine all-reduce) and peak live
elements including the grid-sharded KV cache.  The wire totals are
validated against compiled HLO exactly like the CNN path
(``tests/test_serve.py``); the memory totals drive
``synthesize_serve_grid`` grid selection under a KV-cache cap.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from repro.dist.collectives import SCHEDULES, psum
from repro.dist.matmul import (matmul_comm_elems, matmul_distributed,
                               matmul_grid_divides, matmul_mem_elems)
from repro.kernels import ops as kops
from repro.models.config import ModelConfig


def mesh_grid(mesh: Mesh) -> Tuple[int, int, int]:
    """The ``(Pm, Pn, Pc)`` tuple of a serving mesh."""
    sizes = dict(mesh.shape)
    missing = [a for a in ("m", "n", "c") if a not in sizes]
    if missing:
        raise ValueError(f"mesh lacks axes {missing}; use make_matmul_mesh")
    return sizes["m"], sizes["n"], sizes["c"]


# ------------------------------------------------------------ projections --

def dist_projection(x, w, mesh: Mesh, *, schedule: str = "allgather"):
    """``x[..., C] @ w[C, N]`` through ``matmul_distributed`` on ``mesh``.

    Leading dims of ``x`` are flattened into the matmul row (m) dim.
    Shapes that violate the grid's sub-shard divisibility constraints run
    the dense dot instead — the caller never has to special-case them.
    """
    C, N = w.shape
    lead = x.shape[:-1]
    M = 1
    for s in lead:
        M *= s
    if not matmul_grid_divides(M, C, N, mesh_grid(mesh)):
        return x @ w
    out = matmul_distributed(x.reshape(M, C), w, mesh, schedule=schedule)
    return out.reshape(*lead, N)


def projection_routed(M: int, C: int, N: int, grid) -> bool:
    """True when ``dist_projection`` routes this shape through the grid
    (rather than falling back to the dense dot)."""
    return matmul_grid_divides(M, C, N, grid)


# ------------------------------------------------------------- MoE expert --

def moe_ffn_grid_divides(n_experts: int, d_ff: int, grid) -> bool:
    """True when the expert FFN shards on ``grid``: experts over the
    c-ring, the expert ff dim over n."""
    pm, pn, pc = grid
    return n_experts % pc == 0 and d_ff % pn == 0


def _expert_ffn_local(xg, disp, comb, w_gate, w_up, w_down, *, act: str):
    """Per-rank body: dispatch to the local experts, contract, combine.

    ``disp``/``comb`` arrive with their expert dim sliced to this c-rank
    and the weights with their ff dim sliced to this n-rank, so dispatch
    and the nonlinearity are entirely local; the combined output is a
    partial sum over (n, c) finished by one all-reduce.
    """
    g, t, d = xg.shape
    el, cap = disp.shape[2], disp.shape[3]
    gate_fn = jax.nn.silu if act == "swiglu" else jax.nn.gelu
    # dispatch: select this rank's experts' token slots (no comm)
    xe = jnp.einsum("gtd,gtec->gecd", xg, disp.astype(xg.dtype))
    outs = []
    for e in range(el):
        xr = xe[:, e].reshape(g * cap, d)
        hup = kops.local_matmul(xr, w_up[e])
        if act in ("swiglu", "geglu"):
            hgate = kops.local_matmul(xr, w_gate[e])
            h = (gate_fn(hgate.astype(jnp.float32))
                 * hup.astype(jnp.float32)).astype(xg.dtype)
        else:
            h = jax.nn.gelu(hup.astype(jnp.float32)).astype(xg.dtype)
        outs.append(kops.local_matmul(h, w_down[e]))
    ye = jnp.stack(outs).reshape(el, g, cap, d).transpose(1, 0, 2, 3)
    # combine is linear in ye: contract the local experts/slots first,
    # then finish the partial sums over the ff (n) and expert (c) shards
    # with a single all-reduce of the small [g, t, d] output.
    out = jnp.einsum("gecd,gtec->gtd", ye.astype(jnp.float32), comb)
    return psum(out, ("n", "c"), tag="moe_combine").astype(xg.dtype)


def expert_ffn_distributed(xg, disp, comb, w_gate, w_up, w_down,
                           mesh: Mesh, *, act: str = "swiglu"):
    """Grouped expert FFN with the expert dim on the contraction ring.

    ``xg: [g, t, d]`` grouped tokens, ``disp``/``comb``: ``[g, t, E, C]``
    dispatch/combine tensors, ``w_gate``/``w_up``: ``[E, d, f]``,
    ``w_down``: ``[E, f, d]``.  Experts shard over the c axis, the expert
    ff dim over n; the m axis replicates (decode rows are latency-bound
    and tiny — they ride m in the surrounding projections, not here).
    Requires :func:`moe_ffn_grid_divides`.
    """
    pm, pn, pc = mesh_grid(mesh)
    e, f = w_gate.shape[0], w_gate.shape[2]
    if not moe_ffn_grid_divides(e, f, (pm, pn, pc)):
        raise ValueError(f"experts {e} % Pc {pc} or d_ff {f} % Pn {pn}")
    fn = shard_map(
        functools.partial(_expert_ffn_local, act=act),
        mesh=mesh,
        in_specs=(P(), P(None, None, "c", None), P(None, None, "c", None),
                  P("c", None, "n"), P("c", None, "n"), P("c", "n", None)),
        out_specs=P(),
        check_vma=False)
    return fn(xg, disp, comb, w_gate, w_up, w_down)


def moe_ffn_comm_elems(g: int, t: int, d: int, grid) -> float:
    """Per-device wire (elements) of one ``expert_ffn_distributed`` call:
    a single all-reduce of the combined ``[g, t, d]`` output over the
    ``(n, c)`` plane (ring model ``2 V (P-1)/P``)."""
    pm, pn, pc = grid
    plane = pn * pc
    if plane == 1:
        return 0.0
    return 2.0 * g * t * d * (plane - 1) / plane


# ---------------------------------------------------------- serve account --

def lm_decode_matmuls(cfg: ModelConfig, slots: int
                      ) -> List[Tuple[str, int, int, int]]:
    """The ``(name, M, C, N)`` projection shapes of one decode step
    (per layer; the vocab head is listed once as ``lm_head``)."""
    d, hd = cfg.d_model, cfg.head_dim
    shapes = [
        ("wq", slots, d, cfg.n_heads * hd),
        ("wk", slots, d, cfg.n_kv_heads * hd),
        ("wv", slots, d, cfg.n_kv_heads * hd),
        ("wo", slots, cfg.n_heads * hd, d),
    ]
    if not cfg.is_moe:
        if cfg.mlp_act in ("swiglu", "geglu"):
            shapes.append(("w_gate", slots, d, cfg.d_ff))
        shapes.append(("w_up", slots, d, cfg.d_ff))
        shapes.append(("w_down", slots, cfg.d_ff, d))
    shapes.append(("lm_head", slots, d, cfg.vocab))
    return shapes


def _moe_decode_group(cfg: ModelConfig, slots: int) -> Tuple[int, int]:
    """(g, t) token grouping `models/moe.py` uses for a decode step."""
    n_tok = slots
    gsz = min(cfg.moe_group_size, n_tok)
    while n_tok % gsz != 0:
        gsz //= 2
    return n_tok // gsz, gsz


def lm_serve_comm_elems(cfg: ModelConfig, grid, *, slots: int,
                        schedule: str = "allgather") -> Dict:
    """Analytic per-device wire volume (elements) of ONE decode token
    step across all ``slots`` — the per-token serving wire.

    Sums ``matmul_comm_elems`` over every grid-routed projection (dense
    fallbacks contribute 0, mirroring :func:`dist_projection`), plus the
    MoE combine all-reduce.  Matches the collective bytes of the
    compiled decode step's dist ops (``tests/test_serve.py``).
    """
    if schedule not in SCHEDULES:
        raise ValueError(f"schedule must be one of {SCHEDULES}")
    per_layer: Dict[str, float] = {}
    head = 0.0
    for name, M, C, N in lm_decode_matmuls(cfg, slots):
        elems = (matmul_comm_elems(M, C, N, grid)["total"]
                 if matmul_grid_divides(M, C, N, grid) else 0.0)
        if name == "lm_head":
            head = elems
        else:
            per_layer[name] = elems
    if cfg.is_moe:
        g, t = _moe_decode_group(cfg, slots)
        per_layer["moe_ffn"] = (
            moe_ffn_comm_elems(g, t, cfg.d_model, grid)
            if moe_ffn_grid_divides(cfg.n_experts, cfg.d_ff, grid) else 0.0)
    layer_total = sum(per_layer.values())
    total = cfg.n_layers * layer_total + head
    return {"per_layer": per_layer, "layer_total": layer_total,
            "lm_head": head, "total": total,
            "per_slot": total / max(slots, 1)}


def kv_cache_elems(cfg: ModelConfig, slots: int, max_seq: int) -> float:
    """Global KV cache size (elements): K and V, all layers."""
    return 2.0 * cfg.n_layers * slots * max_seq * cfg.n_kv_heads \
        * cfg.head_dim


def lm_serve_mem_elems(cfg: ModelConfig, grid, *, slots: int, max_seq: int,
                       schedule: str = "allgather") -> Dict:
    """Analytic per-device peak live memory (elements) of the serving
    engine: grid-sharded weights + the KV cache sharded over m (slots
    ride the matmul row axis) + the worst projection's transient peak.

    Weights of grid-routed projections shard ``1/P``; dense-fallback
    projections, norms, the router and the embedding table replicate.
    """
    pm, pn, pc = grid
    P_tot = pm * pn * pc
    d = cfg.d_model
    w_sharded = 0.0
    w_replicated = float(cfg.vocab * d)          # embedding table (take)
    act_peak = 0.0
    for name, M, C, N in lm_decode_matmuls(cfg, slots):
        w = float(C * N)
        mult = 1 if name == "lm_head" else cfg.n_layers
        if matmul_grid_divides(M, C, N, grid):
            w_sharded += mult * w / P_tot
            act_peak = max(act_peak,
                           matmul_mem_elems(M, C, N, grid,
                                            schedule=schedule)["peak"])
        else:
            w_replicated += mult * w
            act_peak = max(act_peak, float(M * C + C * N + M * N))
    if cfg.is_moe:
        w_exp = float(cfg.n_experts * 3 * d * cfg.d_ff)
        if moe_ffn_grid_divides(cfg.n_experts, cfg.d_ff, grid):
            w_sharded += cfg.n_layers * w_exp / (pn * pc)
        else:
            w_replicated += cfg.n_layers * w_exp
        w_replicated += cfg.n_layers * float(d * cfg.n_experts)  # router
    w_replicated += (2 * cfg.n_layers + 1) * d                   # norms
    cache = kv_cache_elems(cfg, slots, max_seq) / (pm if slots % pm == 0
                                                   else 1)
    peak = w_sharded + w_replicated + cache + act_peak
    return {"weights_sharded": w_sharded, "weights_replicated": w_replicated,
            "kv_cache": cache, "act_peak": act_peak, "peak": peak}
