"""LM serving on the distributed matmul grid: continuous batching over
static slots, with every projection routed through
``repro.dist.matmul.matmul_distributed`` when a serving grid is given.

Engine structure (the production shape):

  - a request **queue** with admission control: a request enters a slot
    only when one is free and ``prompt + max_new`` fits the KV budget;
  - **prefill/decode split**: an admitted prompt is right-padded to a
    prefill bucket (bounding compilation churn), prefilled as a batch of
    one, and its KV rows scattered into the shared per-slot cache;
  - batched single-token **decode** over all occupied slots against the
    per-slot cache (``cache["len"]`` is a [slots] vector — every slot
    advances independently);
  - **slot recycling**: a slot frees on EOS / ``max_new`` and the next
    queued request is admitted into it — no drain barrier.

The serving grid is a ``(Pm, Pn, Pc)`` mesh: decode rows (slots) ride m,
output features n, the d_model contraction c — the paper's 2D/2.5D/3D
matmul family under every projection
(:mod:`repro.dist.lm`).  ``core.sharding_synthesis.synthesize_serve_grid``
picks the grid under a per-device memory cap
(``mem_cap_elems=`` — weights + grid-sharded KV cache + transients).

CLI::

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke

``--smoke`` runs the whole engine twice on a fake 8-device CPU mesh —
once on the synthesized grid, once dense — and checks the greedy tokens
match.  This module imports jax lazily so ``main()`` can set
``XLA_FLAGS`` before jax loads.
"""

from __future__ import annotations

import argparse
import os
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from repro.configs import get_config

_TRANSFORMER_FAMILIES = ("dense", "moe", "vlm")


@dataclass
class Request:
    """One generation request.

    ``status`` is the structured per-request outcome: ``"ok"`` (served
    to EOS/``max_new``), ``"rejected_oversize"`` / ``"rejected_backpressure"``
    (admission refused it — ``error`` says why), or ``"deadline"``
    (``deadline_s`` elapsed since submit; any tokens produced so far
    stay in ``out``).  A bad request never raises out of the engine
    loop — it retires with its status and serving continues.
    """

    rid: int
    prompt: List[int]
    max_new: int
    out: List[int] = field(default_factory=list)
    prefill_ms: float = 0.0
    step_ms: List[float] = field(default_factory=list)
    deadline_s: Optional[float] = None
    status: str = "ok"
    error: str = ""
    t_submit: float = 0.0
    prefill_logits: Optional[object] = None


class ContinuousEngine:
    """Continuous-batching decode engine on ``slots`` static KV rows.

    ``dist_mesh`` routes every projection through the ``(Pm, Pn, Pc)``
    grid (`models/lm.py` ``dist_mesh=`` path); ``None`` serves dense —
    the two run the identical queue/prefill/decode schedule, which is
    what makes the smoke-mode token comparison meaningful.
    """

    def __init__(self, cfg, params, *, slots: int, max_seq: int,
                 dist_mesh=None, dist_schedule: str = "allgather",
                 prefill_bucket: int = 16, eos_id: Optional[int] = None,
                 max_queue: Optional[int] = None,
                 decode_watchdog_timeout_s: Optional[float] = None,
                 state_dump_path: Optional[str] = None,
                 fault_log=None, injector=None, keep_logits: bool = False):
        import jax
        import jax.numpy as jnp

        from repro.models import lm as lm_mod
        if cfg.family not in _TRANSFORMER_FAMILIES:
            raise ValueError(
                f"continuous batching covers {_TRANSFORMER_FAMILIES}; "
                f"family {cfg.family!r} serves via the static Engine")
        self._jax, self._jnp, self._lm = jax, jnp, lm_mod
        self.cfg, self.params = cfg, params
        self.slots, self.max_seq = slots, max_seq
        self.bucket = prefill_bucket
        self.eos_id = eos_id
        # degradation knobs: a bounded queue applies backpressure
        # (reject-with-status, never unbounded growth); the decode
        # watchdog snapshots engine bookkeeping when a decode wedges
        self.max_queue = max_queue
        self.decode_watchdog_timeout_s = decode_watchdog_timeout_s
        self.state_dump_path = state_dump_path
        self.fault_log = fault_log
        self.injector = injector
        self.keep_logits = keep_logits
        self.queue: deque = deque()
        self.active: List[Optional[Request]] = [None] * slots
        self.retired: List[Request] = []
        self.decode_ms: List[float] = []
        self.cache = lm_mod.init_cache(cfg, slots, max_seq, per_slot=True)
        self.next_tok = jnp.zeros((slots, 1), jnp.int32)

        def _decode(p, c, t):
            return lm_mod.decode_step(p, cfg, c, t, dist_mesh=dist_mesh,
                                      dist_schedule=dist_schedule)

        def _prefill(p, toks, last_pos):
            stage = lm_mod.init_cache(cfg, 1, max_seq)
            return lm_mod.prefill(p, cfg, stage, toks, last_pos=last_pos,
                                  dist_mesh=dist_mesh,
                                  dist_schedule=dist_schedule)

        if dist_mesh is not None:
            # pin boundary shardings: the KV cache rides the m (slot)
            # axis, everything else replicates.  Without the pin, pjit
            # re-specializes when a decode output (mesh-sharded) feeds
            # back as the next input — a ~100x one-off latency spike.
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P
            rep = NamedSharding(dist_mesh, P())
            pm = dist_mesh.shape["m"]
            kv = (NamedSharding(dist_mesh, P(None, "m", None, None, None))
                  if slots % pm == 0 else rep)
            self._cache_sh = {"k": kv, "v": kv, "len": rep}
            # params are committed replicated once; the cache is
            # conformed by device_put before each decode (see
            # _decode_once).  Pinning both jit boundaries keeps pjit on
            # ONE specialization and keeps the donation alias exact.
            self.params = jax.device_put(params, rep)
            self._decode_fn = jax.jit(_decode, donate_argnums=1,
                                      in_shardings=(rep, self._cache_sh,
                                                    rep),
                                      out_shardings=(rep, self._cache_sh))
            self._prefill_fn = jax.jit(_prefill,
                                       in_shardings=(rep, rep, rep),
                                       out_shardings=(rep, rep))
        else:
            self._cache_sh = None
            self._decode_fn = jax.jit(_decode, donate_argnums=1)
            self._prefill_fn = jax.jit(_prefill)

    # ------------------------------------------------------------- queue --

    def submit(self, req: Request) -> bool:
        """Admission control: a request that can never fit the KV
        budget, or arrives while the bounded queue is full, retires
        immediately with a structured reject status — it never raises
        out of the engine loop and never abandons queued requests.
        Returns True when the request was queued."""
        req.t_submit = time.monotonic()
        if len(req.prompt) + req.max_new > self.max_seq:
            self._reject(
                req, "rejected_oversize",
                f"prompt {len(req.prompt)} + max_new {req.max_new} "
                f"exceeds max_seq {self.max_seq}")
            return False
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            self._reject(
                req, "rejected_backpressure",
                f"queue full ({self.max_queue} waiting)")
            return False
        self.queue.append(req)
        return True

    def _reject(self, req: Request, status: str, error: str) -> None:
        req.status, req.error = status, error
        self.retired.append(req)

    def _expired(self, req: Request, now: Optional[float] = None) -> bool:
        if req.deadline_s is None:
            return False
        now = time.monotonic() if now is None else now
        return now - req.t_submit > req.deadline_s

    def _next_queued(self) -> Optional[Request]:
        """Pop the next admissible request, retiring queued requests
        whose deadline already passed (they would only waste a prefill)."""
        while self.queue:
            req = self.queue.popleft()
            if self._expired(req):
                self._reject(req, "deadline",
                             f"deadline {req.deadline_s}s elapsed "
                             f"before admission")
                continue
            return req
        return None

    def _padded_len(self, plen: int) -> int:
        b = self.bucket
        return min(((plen + b - 1) // b) * b, self.max_seq)

    def _admit(self) -> None:
        jnp = self._jnp
        for slot in range(self.slots):
            if self.active[slot] is not None:
                continue
            req = self._next_queued()
            if req is None:
                break
            plen = len(req.prompt)
            padded = self._padded_len(plen)
            toks = jnp.asarray(
                [req.prompt + [0] * (padded - plen)], jnp.int32)
            t0 = time.perf_counter()
            logits, stage = self._prefill_fn(self.params, toks, plen - 1)
            first = int(logits[0, 0].argmax())
            req.prefill_ms = (time.perf_counter() - t0) * 1e3
            if self.keep_logits:
                req.prefill_logits = logits[0, 0]
            self.cache["k"] = self.cache["k"].at[:, slot].set(
                stage["k"][:, 0])
            self.cache["v"] = self.cache["v"].at[:, slot].set(
                stage["v"][:, 0])
            self.cache["len"] = self.cache["len"].at[slot].set(plen)
            self.next_tok = self.next_tok.at[slot, 0].set(first)
            self.active[slot] = req
            req.out.append(first)
            self._maybe_retire(slot, first)

    def _maybe_retire(self, slot: int, tok: int) -> None:
        req = self.active[slot]
        if tok == self.eos_id or len(req.out) >= req.max_new:
            self.retired.append(req)
            self.active[slot] = None

    def _retire_slot(self, slot: int, status: str, error: str) -> None:
        """Retire an active slot early (deadline) — the slot frees for
        the next queued request; tokens produced so far are kept."""
        req = self.active[slot]
        req.status, req.error = status, error
        self.retired.append(req)
        self.active[slot] = None

    # ------------------------------------------------------------ decode --

    def _decode_once(self) -> None:
        jnp = self._jnp
        t0 = time.perf_counter()
        if self._cache_sh is not None:
            # conform the cache to the grid layout (KV over the m/slot
            # axis); a no-op in steady state when it is last decode's
            # output, a real reshard right after an admission scatter.
            # Without it pjit re-specializes per input sharding combo.
            self.cache = self._jax.device_put(self.cache, self._cache_sh)
        logits, self.cache = self._decode_fn(self.params, self.cache,
                                             self.next_tok)
        nxt = [int(v) for v in logits[:, 0].argmax(-1)]  # host sync
        dt = (time.perf_counter() - t0) * 1e3
        self.decode_ms.append(dt)
        now = time.monotonic()
        for slot, req in enumerate(self.active):
            if req is None:
                continue
            req.out.append(nxt[slot])
            req.step_ms.append(dt)
            self.next_tok = self.next_tok.at[slot, 0].set(nxt[slot])
            self._maybe_retire(slot, nxt[slot])
            if self.active[slot] is not None and self._expired(req, now):
                # per-request deadline: retire the timed-out slot so it
                # recycles instead of decoding for a caller that's gone
                self._retire_slot(
                    slot, "deadline",
                    f"deadline {req.deadline_s}s exceeded after "
                    f"{len(req.out)} tokens")
        # idle slots decode garbage rows; pin their length so the ring
        # write can never run off the cache end while a slot sits empty
        mask = jnp.asarray([r is not None for r in self.active])
        self.cache["len"] = jnp.where(mask, self.cache["len"], 0)

    def warmup(self, prompt_lens: List[int]) -> None:
        """Compile prefill (per bucket) and decode ahead of serving so
        measured latencies are steady-state."""
        jnp = self._jnp
        for pl in sorted({self._padded_len(p) for p in prompt_lens}):
            self._prefill_fn(self.params, jnp.zeros((1, pl), jnp.int32),
                             pl - 1)
        throwaway = self._lm.init_cache(self.cfg, self.slots,
                                        self.max_seq, per_slot=True)
        self._decode_fn(self.params, throwaway, self.next_tok)

    # ----------------------------------------------------- wedge handling --

    def engine_state(self) -> Dict:
        """Bookkeeping snapshot — what the decode watchdog checkpoints
        when a decode wedges, so a restarted engine (or an operator)
        knows exactly which requests were in flight."""
        return {
            "queued": [r.rid for r in self.queue],
            "active": [{"rid": r.rid, "n_out": len(r.out)}
                       for r in self.active if r is not None],
            "retired": [{"rid": r.rid, "status": r.status,
                         "n_out": len(r.out)} for r in self.retired],
            "decode_steps": len(self.decode_ms),
        }

    def _on_decode_wedge(self, iteration: int, elapsed: float) -> None:
        snap = dict(self.engine_state(), event="decode_wedge",
                    iteration=iteration, elapsed_s=elapsed)
        if self.state_dump_path:
            import json
            tmp = self.state_dump_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(snap, f, indent=1)
            os.replace(tmp, self.state_dump_path)

    # ------------------------------------------------------------- serve --

    def serve(self, requests: List[Request]) -> Dict:
        for r in requests:
            self.submit(r)
        wd = None
        if self.decode_watchdog_timeout_s:
            from repro.fault.watchdog import StepWatchdog
            wd = StepWatchdog(self.decode_watchdog_timeout_s,
                              on_wedge=self._on_decode_wedge,
                              log=self.fault_log)
        t0 = time.perf_counter()
        iteration = 0
        try:
            while self.queue or any(r is not None for r in self.active):
                self._admit()
                if any(r is not None for r in self.active):
                    if wd is not None:
                        wd.arm(iteration)
                    try:
                        if self.injector is not None:
                            self.injector.fire("decode", iteration)
                        self._decode_once()
                    finally:
                        if wd is not None:
                            wd.disarm()
                iteration += 1
        finally:
            if wd is not None:
                wd.close()
        wall = time.perf_counter() - t0
        return self._stats(wall)

    def _stats(self, wall_s: float) -> Dict:
        reqs = sorted(self.retired, key=lambda r: r.rid)
        n_tok = sum(len(r.out) for r in reqs)
        dms = sorted(self.decode_ms) or [0.0]

        def pct(q):
            return dms[min(int(q * len(dms)), len(dms) - 1)]

        decode_s = sum(self.decode_ms) / 1e3
        mean_ms = sum(self.decode_ms) / max(len(self.decode_ms), 1)
        std_ms = (sum((t - mean_ms) ** 2 for t in self.decode_ms)
                  / max(len(self.decode_ms), 1)) ** 0.5
        statuses = {r.rid: r.status for r in reqs}
        return {
            "tokens": {r.rid: list(r.out) for r in reqs},
            "n_requests": len(reqs),
            "n_tokens": n_tok,
            "wall_s": wall_s,
            "tokens_per_s": n_tok / max(decode_s, 1e-9),
            "p50_ms": pct(0.50),
            "p99_ms": pct(0.99),
            "mean_ms": mean_ms,
            "std_ms": std_ms,
            "reps": len(self.decode_ms),
            "statuses": statuses,
            "errors": {r.rid: r.error for r in reqs if r.error},
            "n_ok": sum(1 for s in statuses.values() if s == "ok"),
            "n_rejected": sum(1 for s in statuses.values()
                              if s.startswith("rejected")),
            "n_deadline": sum(1 for s in statuses.values()
                              if s == "deadline"),
        }


class Engine:
    """Static-slot batched engine (one prefill, then batched decode).

    Retained for the non-transformer families (encdec/ssm/hybrid) whose
    serve fns don't take a serving grid; the transformer families serve
    through :class:`ContinuousEngine`.
    """

    def __init__(self, cfg, params, *, slots: int, max_seq: int):
        import jax

        from repro.models.api import model_fns
        self._jax = jax
        self.cfg = cfg
        self.fns = model_fns(cfg)
        self.params = params
        self.slots = slots
        self.max_seq = max_seq
        self.decode = jax.jit(
            lambda p, c, t: self.fns.decode_step(p, cfg, c, t),
            donate_argnums=1)

    def run(self, prompts, gen: int):
        jax = self._jax
        import jax.numpy as jnp
        cache = self.fns.init_cache(self.cfg, prompts.shape[0],
                                    self.max_seq, enc_len=prompts.shape[1])
        t0 = time.time()
        if self.cfg.family == "encdec":
            frames = jnp.zeros((prompts.shape[0], prompts.shape[1],
                                self.cfg.d_model), jnp.float32)
            logits, cache = self.fns.prefill(self.params, self.cfg, cache,
                                             frames, prompts)
        else:
            logits, cache = self.fns.prefill(self.params, self.cfg, cache,
                                             prompts)
        t_prefill = time.time() - t0
        out = [jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)]
        t0 = time.time()
        for _ in range(gen - 1):
            logits, cache = self.decode(self.params, cache, out[-1])
            out.append(jnp.argmax(logits[:, -1:], -1).astype(jnp.int32))
        jax.block_until_ready(out[-1])
        t_decode = time.time() - t0
        return jnp.concatenate(out, 1), t_prefill, t_decode


# ------------------------------------------------------------------ run ---

def _make_requests(cfg, *, requests: int, prompt_len: int, gen: int,
                   seed: int,
                   deadline_s: Optional[float] = None) -> List[Request]:
    """Deterministic request set with varied prompt/output lengths so
    bucketed prefill and slot recycling are actually exercised."""
    import jax
    out = []
    for i in range(requests):
        plen = max(1, prompt_len - (i % 4))
        toks = jax.random.randint(jax.random.PRNGKey(seed * 1000 + i),
                                  (plen,), 0, cfg.vocab)
        out.append(Request(rid=i, prompt=[int(t) for t in toks],
                           max_new=max(1, gen - (i % 3)),
                           deadline_s=deadline_s))
    return out


def run(cfg, *, requests: int = 8, prompt_len: int = 16, gen: int = 16,
        slots: int = 4, max_seq: Optional[int] = None, grid=None,
        schedule: str = "allgather", minimize: str = "comm",
        mem_cap_elems: Optional[float] = None,
        seed: int = 0, params=None, prefill_bucket: int = 16,
        warmup: bool = False, max_queue: Optional[int] = None,
        deadline_s: Optional[float] = None,
        decode_watchdog_timeout_s: Optional[float] = None,
        state_dump_path: Optional[str] = None,
        keep_logits: bool = False) -> Dict:
    """Serve a deterministic request set; the callable engine API.

    ``grid``: a ``(Pm, Pn, Pc)`` tuple, ``"auto"`` (synthesized over all
    visible devices via ``synthesize_serve_grid``), or ``None`` (dense).
    ``max_queue`` / ``deadline_s`` / ``decode_watchdog_timeout_s`` are
    the degradation knobs (backpressure, per-request deadlines, wedge
    state dump — see ``docs/fault.md``).  ``keep_logits`` adds
    ``prefill_logits``: each request's last-prompt-position logits
    ``[vocab]`` (f32, on the device).  Returns the stats dict of
    :meth:`ContinuousEngine.serve` plus the grid/schedule and the
    analytic wire/memory accounting.
    """
    import jax

    from repro.models.api import model_fns
    max_seq = max_seq or prompt_len + gen
    fns = model_fns(cfg)
    if params is None:
        params = fns.init(jax.random.PRNGKey(seed), cfg)
    chosen = None
    if grid == "auto":
        from repro.core.sharding_synthesis import synthesize_serve_grid
        chosen = synthesize_serve_grid(cfg, jax.device_count(),
                                       slots=slots, max_seq=max_seq,
                                       schedule=schedule,
                                       minimize=minimize,
                                       mem_cap_elems=mem_cap_elems)
        grid = chosen.grid
    mesh = None
    if grid is not None:
        from repro.dist.matmul import make_matmul_mesh
        mesh = make_matmul_mesh(tuple(grid))
    engine = ContinuousEngine(
        cfg, params, slots=slots, max_seq=max_seq, dist_mesh=mesh,
        dist_schedule=schedule, prefill_bucket=prefill_bucket,
        max_queue=max_queue,
        decode_watchdog_timeout_s=decode_watchdog_timeout_s,
        state_dump_path=state_dump_path, keep_logits=keep_logits)
    reqs = _make_requests(cfg, requests=requests, prompt_len=prompt_len,
                          gen=gen, seed=seed, deadline_s=deadline_s)
    if warmup:
        engine.warmup([len(r.prompt) for r in reqs])
    res = engine.serve(reqs)
    res["arch"] = cfg.arch_id
    res["grid"] = tuple(grid) if grid is not None else None
    res["schedule"] = schedule
    if keep_logits:
        res["prefill_logits"] = {r.rid: r.prefill_logits
                                 for r in engine.retired
                                 if r.prefill_logits is not None}
    if grid is not None:
        from repro.dist.lm import lm_serve_comm_elems, lm_serve_mem_elems
        itemsize = cfg.jdtype.itemsize
        comm = lm_serve_comm_elems(cfg, tuple(grid), slots=slots,
                                   schedule=schedule)
        mem = lm_serve_mem_elems(cfg, tuple(grid), slots=slots,
                                 max_seq=max_seq, schedule=schedule)
        res["wire_bytes_per_tok"] = comm["per_slot"] * itemsize
        res["peak_mem_bytes"] = mem["peak"] * itemsize
    return res


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true",
                    help="fake 8-device mesh, f32, dist-vs-dense token "
                         "comparison")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--schedule", default="allgather",
                    choices=("allgather", "ring", "ring2"))
    ap.add_argument("--grid", default=None,
                    help='"PmxPnxPc", "auto", or omit for dense')
    ap.add_argument("--minimize", default="comm",
                    choices=("comm", "time"),
                    help="--grid auto objective: analytic wire volume "
                         "or calibrated replay time (CALIB.json)")
    ap.add_argument("--mem-cap-elems", type=float, default=None)
    args = ap.parse_args(argv)

    if args.smoke:
        # must precede the first jax import
        os.environ.setdefault("XLA_FLAGS",
                              "--xla_force_host_platform_device_count=8")
        os.environ.setdefault("REPRO_DIST_PALLAS", "0")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    if args.smoke and cfg.family in _TRANSFORMER_FAMILIES:
        # greedy token comparison needs f32 headroom, not bf16 rounding
        import dataclasses
        cfg = dataclasses.replace(cfg, dtype="float32")

    if cfg.family not in _TRANSFORMER_FAMILIES:
        import jax
        from repro.models.api import model_fns
        fns = model_fns(cfg)
        params = fns.init(jax.random.PRNGKey(0), cfg)
        engine = Engine(cfg, params, slots=args.requests,
                        max_seq=args.prompt_len + args.gen)
        prompts = jax.random.randint(jax.random.PRNGKey(1),
                                     (args.requests, args.prompt_len),
                                     0, cfg.vocab)
        toks, t_pre, t_dec = engine.run(prompts, args.gen)
        n_tok = args.requests * args.gen
        print(f"[serve] {cfg.arch_id}: prefill {t_pre*1e3:.1f}ms, decode "
              f"{t_dec*1e3:.1f}ms for {n_tok} tokens "
              f"({n_tok/max(t_dec,1e-9):.0f} tok/s), output {toks.shape}")
        return toks

    # smoke pins the 2.5D (2,2,2) grid: the dist-vs-dense greedy-token
    # comparison needs a grid whose rollout is verified tie-free; pass
    # --grid auto to exercise synthesize_serve_grid instead
    grid = args.grid or ((2, 2, 2) if args.smoke else None)
    if isinstance(grid, str) and grid != "auto":
        grid = tuple(int(x) for x in grid.split("x"))
    kw = dict(requests=args.requests, prompt_len=args.prompt_len,
              gen=args.gen, slots=args.slots, schedule=args.schedule,
              minimize=args.minimize, mem_cap_elems=args.mem_cap_elems)
    res = run(cfg, grid=grid, **kw)
    wire = res.get("wire_bytes_per_tok", 0.0)
    print(f"[serve] {cfg.arch_id} grid={res['grid']} "
          f"schedule={res['schedule']}: {res['n_tokens']} tokens from "
          f"{res['n_requests']} requests, {res['tokens_per_s']:.0f} tok/s, "
          f"p50 {res['p50_ms']:.1f}ms p99 {res['p99_ms']:.1f}ms, "
          f"wire {wire:.0f} B/tok")
    if args.smoke:
        dense = run(cfg, grid=None, **kw)
        match = dense["tokens"] == res["tokens"]
        print(f"[serve] dist grid {res['grid']} vs dense: greedy tokens "
              f"{'identical' if match else 'DIVERGED'}")
        if not match:
            raise SystemExit(1)
    return res


if __name__ == "__main__":
    main()
