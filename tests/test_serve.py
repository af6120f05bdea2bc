"""The LM serving engine (`launch/serve.py`) and its dist-grid plumbing
(`repro.dist.lm`): analytic wire/memory accounting, serve-grid
synthesis, queue/slot invariants, and the 8-device acceptance runs
(decode equivalence dist vs dense, HLO wire-ratio validation).

Fast checks run in-process on one device (the engine itself serves
dense there); the grid acceptance runs in an 8-device subprocess.  The
``bench``-marked test validates the checked-in ``BENCH_serve.json``
baseline.
"""

import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.sharding_synthesis import synthesize_serve_grid
from repro.dist.lm import (kv_cache_elems, lm_decode_matmuls,
                           lm_serve_comm_elems, lm_serve_mem_elems,
                           moe_ffn_comm_elems, moe_ffn_grid_divides,
                           projection_routed)
from repro.launch.serve import ContinuousEngine, Request, _make_requests
from repro.models import lm as lm_mod
from repro.models.api import model_fns

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_in_subprocess(body: str):
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ["REPRO_DIST_PALLAS"] = "0"
        import jax, jax.numpy as jnp
        import numpy as np
    """) + textwrap.dedent(body)
    env = dict(os.environ,
               PYTHONPATH=os.path.join(_ROOT, "src")
               + os.pathsep + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nERR:\n{proc.stderr}"
    return proc.stdout


def _smoke_cfg(arch="llama3.2-1b"):
    return dataclasses.replace(get_config(arch, smoke=True),
                               dtype="float32")


# ------------------------------------------------------ decode shape list

def test_lm_decode_matmuls_dense():
    cfg = _smoke_cfg()
    shapes = dict((n, (M, C, N))
                  for n, M, C, N in lm_decode_matmuls(cfg, 4))
    assert set(shapes) == {"wq", "wk", "wv", "wo", "w_gate", "w_up",
                           "w_down", "lm_head"}
    d, hd = cfg.d_model, cfg.head_dim
    assert shapes["wq"] == (4, d, cfg.n_heads * hd)
    assert shapes["wk"] == (4, d, cfg.n_kv_heads * hd)
    assert shapes["wo"] == (4, cfg.n_heads * hd, d)
    assert shapes["w_down"] == (4, cfg.d_ff, d)
    assert shapes["lm_head"] == (4, d, cfg.vocab)


def test_lm_decode_matmuls_moe_has_no_dense_mlp():
    cfg = _smoke_cfg("granite-moe-1b-a400m")
    names = [n for n, *_ in lm_decode_matmuls(cfg, 4)]
    assert names == ["wq", "wk", "wv", "wo", "lm_head"]


# ------------------------------------------------------- wire accounting

def test_serve_comm_accounting_structure():
    cfg = _smoke_cfg()
    v = lm_serve_comm_elems(cfg, (2, 2, 2), slots=4)
    assert v["total"] == pytest.approx(
        cfg.n_layers * v["layer_total"] + v["lm_head"])
    assert v["per_slot"] == pytest.approx(v["total"] / 4)
    assert v["layer_total"] > 0 and v["lm_head"] > 0
    assert set(v["per_layer"]) == {"wq", "wk", "wv", "wo", "w_gate",
                                   "w_up", "w_down"}
    # single device: nothing crosses a wire
    assert lm_serve_comm_elems(cfg, (1, 1, 1), slots=4)["total"] == 0.0
    with pytest.raises(ValueError, match="schedule"):
        lm_serve_comm_elems(cfg, (2, 2, 2), slots=4, schedule="bogus")


def test_serve_comm_accounting_fallback_is_zero():
    # M=2 slots cannot ride Pm=4: every projection falls back to the
    # dense dot, and the accounting mirrors that with zero wire
    cfg = _smoke_cfg()
    assert not projection_routed(2, cfg.d_model, cfg.vocab, (4, 2, 1))
    v = lm_serve_comm_elems(cfg, (4, 2, 1), slots=2)
    assert v["total"] == 0.0


def test_serve_comm_wire_schedule_invariant():
    # each operand piece crosses its ring once however it is pipelined
    cfg = _smoke_cfg()
    totals = {s: lm_serve_comm_elems(cfg, (2, 2, 2), slots=4,
                                     schedule=s)["total"]
              for s in ("allgather", "ring", "ring2")}
    assert totals["allgather"] == totals["ring"] == totals["ring2"]


def test_moe_ffn_comm_and_divisibility():
    cfg = _smoke_cfg("granite-moe-1b-a400m")
    assert moe_ffn_grid_divides(cfg.n_experts, cfg.d_ff, (1, 2, 2))
    assert not moe_ffn_grid_divides(cfg.n_experts, cfg.d_ff, (1, 1, 3))
    assert moe_ffn_comm_elems(1, 4, 64, (8, 1, 1)) == 0.0
    # one all-reduce of [g, t, d] over the (n, c) plane
    assert moe_ffn_comm_elems(1, 4, 64, (2, 2, 2)) == pytest.approx(
        2.0 * 4 * 64 * 3 / 4)
    v = lm_serve_comm_elems(cfg, (1, 2, 2), slots=4)
    assert "moe_ffn" in v["per_layer"]
    assert v["per_layer"]["moe_ffn"] > 0


# ----------------------------------------------------- memory accounting

def test_serve_mem_accounting():
    cfg = _smoke_cfg()
    v = lm_serve_mem_elems(cfg, (2, 2, 2), slots=4, max_seq=32)
    assert v["peak"] == pytest.approx(
        v["weights_sharded"] + v["weights_replicated"] + v["kv_cache"]
        + v["act_peak"])
    # slots % Pm == 0: the KV cache shards over the m (slot) axis
    assert v["kv_cache"] == pytest.approx(
        kv_cache_elems(cfg, 4, 32) / 2)
    # indivisible slot count replicates the cache
    v3 = lm_serve_mem_elems(cfg, (2, 2, 2), slots=3, max_seq=32)
    assert v3["kv_cache"] == pytest.approx(kv_cache_elems(cfg, 3, 32))
    # a bigger grid shards the routed weights further down
    v8 = lm_serve_mem_elems(cfg, (2, 2, 2), slots=8, max_seq=32)
    v1 = lm_serve_mem_elems(cfg, (1, 1, 1), slots=8, max_seq=32)
    assert v8["weights_sharded"] < v1["weights_sharded"] \
        + v1["weights_replicated"]


def test_serve_mem_accounting_moe_expert_shards():
    # an odd d_ff defeats pn=2 sharding but not pn=1: the expert stacks
    # shard over (n, c) when divisible, else replicate — on two grids
    # whose projection sharding is otherwise identical (P_tot=4)
    cfg = dataclasses.replace(_smoke_cfg("granite-moe-1b-a400m"),
                              d_ff=33)
    shard = lm_serve_mem_elems(cfg, (2, 1, 2), slots=4, max_seq=32)
    rep = lm_serve_mem_elems(cfg, (1, 2, 2), slots=4, max_seq=32)
    w_exp = cfg.n_layers * cfg.n_experts * 3 * cfg.d_model * cfg.d_ff
    assert rep["weights_replicated"] - shard["weights_replicated"] \
        == pytest.approx(w_exp)
    assert shard["weights_sharded"] - rep["weights_sharded"] \
        == pytest.approx(w_exp / 2)


# --------------------------------------------------------- grid synthesis

def test_synthesize_serve_grid_picks_routed_grid():
    cfg = _smoke_cfg()
    choice = synthesize_serve_grid(cfg, 8, slots=4, max_seq=32)
    pm, pn, pc = choice.grid
    assert pm * pn * pc == 8
    assert choice.routed > 0
    assert choice.algo in ("2D-DP", "2D-SUMMA", "2.5D", "3D")
    assert choice.comm_elems["total"] >= 0
    assert choice.mem_elems["peak"] > 0


def test_synthesize_serve_grid_mem_cap():
    cfg = _smoke_cfg()
    free = synthesize_serve_grid(cfg, 8, slots=4, max_seq=32)
    # a generous cap changes nothing
    capped = synthesize_serve_grid(cfg, 8, slots=4, max_seq=32,
                                   mem_cap_elems=free.mem_elems["peak"])
    assert capped.grid == free.grid
    # an impossible cap reports how many grids it discarded
    with pytest.raises(ValueError, match="over cap"):
        synthesize_serve_grid(cfg, 8, slots=4, max_seq=32,
                              mem_cap_elems=1.0)
    # a tight cap steers to a grid that fits, possibly at more wire
    peaks = sorted(
        lm_serve_mem_elems(cfg, g, slots=4, max_seq=32)["peak"]
        for g in [(2, 2, 2), (1, 4, 2), (4, 2, 1), (1, 8, 1)])
    tight = synthesize_serve_grid(cfg, 8, slots=4, max_seq=32,
                                  mem_cap_elems=peaks[0])
    assert tight.mem_elems["peak"] <= peaks[0]


# --------------------------------------------------------- engine: queue

def test_init_cache_per_slot_len_vector():
    cfg = _smoke_cfg()
    scalar = lm_mod.init_cache(cfg, 3, 16)
    vec = lm_mod.init_cache(cfg, 3, 16, per_slot=True)
    assert scalar["len"].shape == ()
    assert vec["len"].shape == (3,)
    assert vec["k"].shape == scalar["k"].shape
    # the family registry forwards the flag
    api_vec = model_fns(cfg).init_cache(cfg, 3, 16, per_slot=True)
    assert api_vec["len"].shape == (3,)


def _engine(cfg, slots=2, max_seq=24, **kw):
    params = model_fns(cfg).init(jax.random.PRNGKey(0), cfg)
    return ContinuousEngine(cfg, params, slots=slots, max_seq=max_seq,
                            prefill_bucket=8, **kw)


def test_engine_admission_rejects_oversized():
    # oversize retires with a structured status — it never raises out
    # of the serving loop and never blocks later admissible requests
    eng = _engine(_smoke_cfg(), max_seq=16)
    big = Request(rid=0, prompt=[1] * 10, max_new=8)
    assert eng.submit(big) is False
    assert big.status == "rejected_oversize"
    assert "exceeds max_seq" in big.error
    assert [r.rid for r in eng.retired] == [0]
    # fits exactly: admitted
    ok = Request(rid=1, prompt=[1] * 8, max_new=8)
    assert eng.submit(ok) is True
    assert ok.status == "ok"
    assert len(eng.queue) == 1


def _greedy_reference(cfg, params, reqs, max_seq):
    """Each request decoded alone on the batch-1 scalar-length cache."""
    prefill = jax.jit(lambda p, c, t: lm_mod.prefill(p, cfg, c, t))
    step = jax.jit(lambda p, c, t: lm_mod.decode_step(p, cfg, c, t))
    outs = {}
    for r in reqs:
        logits, cache = prefill(params, lm_mod.init_cache(cfg, 1, max_seq),
                                jnp.asarray([r.prompt], jnp.int32))
        out = [int(logits[0, -1].argmax())]
        while len(out) < r.max_new:
            logits, cache = step(params, cache,
                                 jnp.asarray([[out[-1]]], jnp.int32))
            out.append(int(logits[0, -1].argmax()))
        outs[r.rid] = out
    return outs


@pytest.mark.parametrize("slots,requests,prompt_len,gen", [
    (2, 5, 6, 4), (4, 9, 7, 9), (3, 7, 5, 12)],
    ids=["2slots", "4slots", "3slots-long"])
def test_engine_slot_recycling_serves_all(slots, requests, prompt_len, gen):
    # more requests than slots, of varied lengths: every request retires
    # with exactly max_new tokens each (no EOS id set), the engine drains
    # clean, and the tokens fed back on the device through retirement
    # and re-admission into the same slot are each request's own greedy
    # decode
    cfg = _smoke_cfg()
    eng = _engine(cfg, slots=slots, max_seq=32)
    reqs = _make_requests(cfg, requests=requests, prompt_len=prompt_len,
                          gen=gen, seed=1)
    res = eng.serve(reqs)
    assert res["n_requests"] == requests
    assert sorted(res["tokens"]) == list(range(requests))
    for r in reqs:
        assert len(r.out) == r.max_new, r.rid
    assert not eng.queue and all(s is None for s in eng.active)
    assert res["n_tokens"] == sum(r.max_new for r in reqs)
    assert res["tokens_per_s"] > 0
    assert res["tokens"] == _greedy_reference(cfg, eng.params, reqs, 32)


def _decode_cost(eng):
    """Calls of each jitted engine program and host reads of device
    arrays in one ``_decode_once``, run with implicit transfers
    disallowed.  Every read of a ``jax.Array`` to the host goes through
    ``ArrayImpl._value``; reads are counted there because the CPU backend
    does not enforce the device-to-host transfer guard."""
    from jax._src.array import ArrayImpl
    value = ArrayImpl._value
    calls, reads = {}, []

    def counted(name, fn):
        def call(*args):
            calls[name] = calls.get(name, 0) + 1
            return fn(*args)
        return call

    def read(arr):
        reads.append(arr.shape)
        return value.fget(arr)

    jitted = {k: v for k, v in vars(eng).items() if hasattr(v, "lower")}
    try:
        for k, v in jitted.items():
            setattr(eng, k, counted(k, v))
        ArrayImpl._value = property(read)
        with jax.transfer_guard("disallow"):
            eng._decode_once()
    finally:
        ArrayImpl._value = value
        for k, v in jitted.items():
            setattr(eng, k, v)
    return calls, reads


def test_decode_step_cost_independent_of_active_slots():
    # a decode step dispatches the same programs, once each, and reads
    # the [slots, 1] tokens to the host once, whether 1 or 3 of 4 slots
    # are occupied: nothing per slot goes to or comes from the device
    cfg = _smoke_cfg()
    eng = _engine(cfg, slots=4, max_seq=32)
    reqs = [Request(rid=i, prompt=[1 + i, 2, 3], max_new=12)
            for i in range(3)]
    eng.submit(reqs[0])
    eng._admit()
    eng._decode_once()                       # compiles outside the count
    one = _decode_cost(eng)
    for r in reqs[1:]:
        eng.submit(r)
    eng._admit()
    assert sum(r is not None for r in eng.active) == 3
    three = _decode_cost(eng)
    assert one == three
    calls, reads = one
    assert calls["_decode_fn"] == 1 and set(calls.values()) == {1}
    assert reads == [(4, 1)]
    assert [len(r.out) for r in reqs] == [4, 2, 2]


def test_idle_slot_len_stays_zero():
    # a slot whose request retired keeps decoding garbage rows; its
    # length is masked back to 0 every step so it never runs off the
    # cache, while the occupied slot advances one position a step
    cfg = _smoke_cfg()
    eng = _engine(cfg, slots=4, max_seq=32)
    short = Request(rid=0, prompt=[1, 2, 3], max_new=2)
    long = Request(rid=1, prompt=[4, 5, 6, 7], max_new=12)
    eng.submit(short)
    eng.submit(long)
    eng._admit()
    eng._decode_once()
    assert eng.active[0] is None and eng.retired == [short]
    for step in range(2, 8):
        eng._decode_once()
        np.testing.assert_array_equal(np.asarray(eng.cache["len"]),
                                      [0, 4 + step, 0, 0])


def test_engine_eos_frees_slot():
    cfg = _smoke_cfg()
    eng = _engine(cfg, slots=2, eos_id=7)
    req = Request(rid=0, prompt=[1, 2], max_new=100, out=[3])
    eng.active[0] = req
    eng._maybe_retire(0, 5)      # ordinary token: keeps the slot
    assert eng.active[0] is req
    eng._maybe_retire(0, 7)      # EOS: retires and frees
    assert eng.active[0] is None
    assert eng.retired == [req]


def test_engine_rejects_non_transformer_family():
    cfg = get_config("xlstm-350m", smoke=True)
    with pytest.raises(ValueError, match="static Engine"):
        ContinuousEngine(cfg, {}, slots=2, max_seq=16)


def test_per_slot_decode_matches_scalar():
    # with every slot at the same length, the per-slot scatter/mask
    # decode path reproduces the scalar dynamic-update-slice path
    cfg = _smoke_cfg()
    params = model_fns(cfg).init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
    cache_s = lm_mod.init_cache(cfg, 2, 16)
    _, cache_s = lm_mod.prefill(params, cfg, cache_s, toks)
    cache_v = dict(cache_s, len=jnp.full((2,), cache_s["len"]))
    nxt = jnp.array([[3], [5]], jnp.int32)
    ls, cs = lm_mod.decode_step(params, cfg, cache_s, nxt)
    lv, cv = lm_mod.decode_step(params, cfg, cache_v, nxt)
    np.testing.assert_allclose(np.asarray(ls), np.asarray(lv),
                               rtol=2e-5, atol=2e-5)
    assert (np.argmax(np.asarray(ls), -1)
            == np.argmax(np.asarray(lv), -1)).all()
    np.testing.assert_array_equal(np.asarray(cv["len"]),
                                  np.full((2,), np.asarray(cs["len"])))


# ----------------------------------------------- 8-device acceptance runs

@pytest.mark.subprocess
def test_serve_engine_dist_matches_dense_8dev():
    """Acceptance: the continuous engine on the (2,2,2) serving grid
    emits the same greedy tokens as the dense engine, through admission,
    bucketed prefill and slot recycling; grid="auto" synthesizes a full
    8-device factorization."""
    run_in_subprocess("""
        import dataclasses
        from repro.configs import get_config
        from repro.launch.serve import run
        cfg = dataclasses.replace(get_config("llama3.2-1b", smoke=True),
                                  dtype="float32")
        kw = dict(requests=5, prompt_len=10, gen=6, slots=2)
        dense = run(cfg, grid=None, **kw)
        dist = run(cfg, grid=(2, 2, 2), **kw)
        assert dense["tokens"] == dist["tokens"], (dense["tokens"],
                                                   dist["tokens"])
        assert dist["wire_bytes_per_tok"] > 0
        assert dist["n_requests"] == 5
        auto = run(cfg, grid="auto", requests=2, prompt_len=8, gen=3,
                   slots=2)
        pm, pn, pc = auto["grid"]
        assert pm * pn * pc == 8, auto["grid"]
        print("ok")
    """)


@pytest.mark.subprocess
def test_serve_moe_dist_matches_dense_8dev():
    """The MoE arch serves through expert_ffn_distributed (experts on
    the contraction ring) with dense-identical greedy tokens."""
    run_in_subprocess("""
        import dataclasses
        from repro.configs import get_config
        from repro.launch.serve import run
        cfg = dataclasses.replace(
            get_config("granite-moe-1b-a400m", smoke=True),
            dtype="float32")
        kw = dict(requests=3, prompt_len=8, gen=5, slots=2)
        dense = run(cfg, grid=None, **kw)
        dist = run(cfg, grid=(2, 2, 2), **kw)
        assert dense["tokens"] == dist["tokens"], (dense["tokens"],
                                                   dist["tokens"])
        print("ok")
    """)


@pytest.mark.subprocess
def test_serve_wire_matches_hlo_8dev():
    """The analytic serving wire matches compiled HLO — the same
    validation contract as the CNN path.  Each decode projection's
    accounting is exact (ratio 1.0) against its compiled collective
    bytes; the whole decode step's HLO carries those collectives plus
    bounded GSPMD resharding glue between the shard_map regions, so the
    analytic total is a tight lower bound on the step's wire."""
    run_in_subprocess("""
        import dataclasses
        from repro.configs import get_config
        from repro.dist.lm import dist_projection, lm_decode_matmuls
        from repro.dist.lm import lm_serve_comm_elems
        from repro.dist.matmul import make_matmul_mesh, matmul_comm_elems
        from repro.launch.hlo_analysis import analyze_hlo
        from repro.models import lm as lm_mod
        from repro.models.api import model_fns

        cfg = dataclasses.replace(get_config("llama3.2-1b", smoke=True),
                                  dtype="float32")
        slots = 4
        # (a) every decode projection shape: exact per-device collective
        # bytes, on each grid family (2.5D / wire-optimal / 2D-SUMMA)
        for grid in [(2, 2, 2), (1, 4, 2), (2, 4, 1)]:
            mesh = make_matmul_mesh(grid)
            for name, M, C, N in lm_decode_matmuls(cfg, slots):
                a = jax.ShapeDtypeStruct((M, C), jnp.float32)
                b = jax.ShapeDtypeStruct((C, N), jnp.float32)
                c = jax.jit(lambda p, q: dist_projection(
                    p, q, mesh)).lower(a, b).compile()
                wire = analyze_hlo(c.as_text())["total_wire_bytes"]
                v = matmul_comm_elems(M, C, N, grid)
                assert wire == v["total"] * 4, (grid, name, wire,
                                                v["total"] * 4)
        # (b) the full decode step: the analytic total is a lower bound
        # on the HLO wire, and the gap — GSPMD resharding glue between
        # the shard_map regions — stays under an absolute budget that is
        # small against the model (the glue moves [slots, d]-sized
        # activations, not weight shards, so it is additive, not
        # proportional: the wire-optimal pm=1 grid has the largest
        # relative but still-bounded gap)
        params = model_fns(cfg).init(jax.random.PRNGKey(0), cfg)
        cache = lm_mod.init_cache(cfg, slots, 32, per_slot=True)
        toks = jnp.zeros((slots, 1), jnp.int32)
        glue_budget = 32 * 1024
        for grid in [(2, 2, 2), (1, 4, 2), (2, 4, 1)]:
            mesh = make_matmul_mesh(grid)
            fn = lambda p, c, t: lm_mod.decode_step(p, cfg, c, t,
                                                    dist_mesh=mesh)
            c = jax.jit(fn).lower(params, cache, toks).compile()
            wire = analyze_hlo(c.as_text())["total_wire_bytes"]
            an = lm_serve_comm_elems(cfg, grid, slots=slots)["total"] * 4
            assert an <= wire <= an + glue_budget, (grid, wire, an)
        print("ok")
    """)


# -------------------------------------------------- perf-trajectory JSON

@pytest.mark.bench
def test_bench_serve_baseline_schema_and_invariants():
    """The checked-in BENCH_serve.json is the serving regression
    baseline: schema-complete, the verified (2,2,2) grid matches dense
    tokens, and the exact wire fields reproduce the analytic per-token
    accounting (latency/throughput fields are machine-dependent and
    informational)."""
    with open(os.path.join(_ROOT, "BENCH_serve.json")) as f:
        recs = json.load(f)
    assert any(r["grid"] is None for r in recs), "no dense baseline"
    for rec in recs:
        for key in ("name", "arch", "grid", "schedule", "tokens_per_s",
                    "p50_ms", "p99_ms", "wire_bytes_per_tok",
                    "wire_bytes", "peak_elems", "wall_ms",
                    "slots", "smoke", "dtype", "std_ms", "reps",
                    "predicted_ms", "tokens_match_dense"):
            assert key in rec, (rec.get("name"), key)
        assert rec["tokens_per_s"] > 0
        assert rec["reps"] >= 1 and rec["std_ms"] >= 0.0, rec["name"]
        # predicted_ms drift gates separately from wall_ms noise
        assert rec["predicted_ms"] > 0, rec["name"]
        if rec["grid"] == [2, 2, 2]:
            assert rec["tokens_match_dense"], rec["name"]
    # the exact wire field reproduces the analytic accounting (f32,
    # slots=4 — the bench_serve cell parameters)
    cfg = _smoke_cfg()
    expect = lm_serve_comm_elems(cfg, (2, 2, 2),
                                 slots=4)["per_slot"] * 4
    by = {(tuple(r["grid"]) if r["grid"] else None, r["schedule"]): r
          for r in recs}
    rec = by[((2, 2, 2), "allgather")]
    assert rec["wire_bytes_per_tok"] == pytest.approx(expect)
    # wire is schedule-invariant; memory is what ring2 trades
    r2 = by.get(((2, 2, 2), "ring2"))
    if r2 is not None:
        assert r2["wire_bytes_per_tok"] == pytest.approx(
            rec["wire_bytes_per_tok"])
    for r in recs:
        if r["grid"] is not None:
            assert r["wire_bytes_per_tok"] > 0, r["name"]
