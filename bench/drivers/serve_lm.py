"""Driver for LM serving cells: the program's continuous-batching engine,
``launch.serve.ContinuousEngine``, built as ``launch.serve.run(...,
grid="auto")`` builds it, under open-loop arrivals.

The engine is driven with the body of ``ContinuousEngine.serve``'s loop:
requests go in through ``submit()`` when they are due, then ``_admit()``,
then ``_decode_once()`` while any slot is busy.  Arrivals start a
pre-roll (the mix's ``preroll_s``) before the window opens, so the window
measures the engine in its steady state, not its fill from empty; the
pre-roll counts as set-up.  A token is
delivered when the engine call that produced it returns; the harness stamps
it then.  Time to first token runs from the request's due time, so a late
generator or a stalled engine shows in it.  After the window closes the
engine runs on, with no new arrivals, until every request due in the window
has its first token (at most ``DRAIN_S``); a request that never gets one
counts as failed.

Correctness: a sample drawn from the seed of the requests the engine
finished, the longest among them, is run through the plain reference
(``bench/reference/decoder.py``) over prompt and served tokens.
``token_gap`` is the widest gap by which a served token's logit lies below
the reference's best at that position.
"""

from __future__ import annotations

import dataclasses
import sys
import time

import numpy as np

import generator
import harness

DRAIN_S = 60.0
WARM_NEW = 2            # tokens each warm-up request decodes


def make_params(cfg: dict, seed: int):
    """The served weights, in the program's layout and dtype, on the
    device in one jitted call."""
    import jax
    import jax.numpy as jnp

    d, ff, L = (cfg["hidden_size"], cfg["intermediate_size"],
                cfg["num_hidden_layers"])
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    v, std = cfg["vocab_size"], cfg["initializer_range"]
    dt = jnp.dtype(cfg["dtype"])

    @jax.jit
    def make(key):
        ks = iter(jax.random.split(key, 12))
        w = lambda shape: (std * jax.random.normal(next(ks), shape,
                                                   jnp.float32)).astype(dt)
        ln = lambda shape: (0.1 * jax.random.normal(next(ks), shape,
                                                    jnp.float32)).astype(dt)
        return {
            "emb": {"tok": w((v, d)), "lm_head": w((d, v))},
            "blocks": {
                "ln1": ln((L, d)), "ln2": ln((L, d)),
                "attn": {"wq": w((L, d, nh * hd)), "wk": w((L, d, nkv * hd)),
                         "wv": w((L, d, nkv * hd)), "wo": w((L, nh * hd, d))},
                "mlp": {"w_up": w((L, d, ff)), "w_gate": w((L, d, ff)),
                        "w_down": w((L, ff, d))}},
            "ln_f": ln((d,)),
        }

    return make, generator.jax_key(seed, 0)


def program_config(cfg: dict):
    """The program's ModelConfig for this configuration, checked against
    the file's published sizes."""
    from repro.configs import get_config
    pc = get_config(cfg["program_arch"],
                    smoke=cfg.get("program_smoke", False))
    pc = dataclasses.replace(pc, norm_eps=cfg["rms_norm_eps"],
                             rope_theta=cfg["rope_theta"], dtype=cfg["dtype"])
    want = {"d_model": cfg["hidden_size"], "d_ff": cfg["intermediate_size"],
            "n_layers": cfg["num_hidden_layers"],
            "n_heads": cfg["num_attention_heads"],
            "n_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "vocab": cfg["vocab_size"],
            "mlp_act": "swiglu", "tie_embeddings": cfg["tie_word_embeddings"]}
    got = {k: getattr(pc, k) for k in want}
    if got != want:
        raise ValueError(f"program config {got} differs from {want}")
    return pc


def build_engine(cfg: dict, mix: dict, params):
    """``ContinuousEngine`` as ``launch.serve.run(grid=...)`` builds it."""
    import jax

    from repro.launch.serve import ContinuousEngine
    from repro.models.api import model_fns

    pc = program_config(cfg)
    shapes = jax.eval_shape(lambda k: model_fns(pc).init(k, pc),
                            jax.random.PRNGKey(0))
    if (jax.tree.structure(shapes) != jax.tree.structure(params)
            or jax.tree.leaves(jax.tree.map(lambda a, b: a.shape != b.shape,
                                            shapes, params)).count(True)):
        raise ValueError("the benchmark's weights do not match the "
                         "program's parameter layout")
    eng = mix["engine"]
    grid = eng["grid"]
    if grid == "auto":
        from repro.core.sharding_synthesis import synthesize_serve_grid
        grid = synthesize_serve_grid(pc, jax.device_count(),
                                     slots=eng["slots"],
                                     max_seq=eng["max_seq"]).grid
    mesh = None
    if grid is not None:
        from repro.dist.matmul import make_matmul_mesh
        mesh = make_matmul_mesh(tuple(grid))
    engine = ContinuousEngine(pc, params, slots=eng["slots"],
                              max_seq=eng["max_seq"], dist_mesh=mesh,
                              prefill_bucket=eng["prefill_bucket"])
    return engine, tuple(grid) if grid is not None else None


class Stamps:
    """Delivery times of every request's tokens, taken after each engine
    call, from the requests' ``out`` lists."""

    def __init__(self, engine):
        self.engine = engine
        self.times = {}          # rid -> [token delivery times]
        self._seen_retired = len(engine.retired)

    def take(self, now: float) -> None:
        e = self.engine
        live = [r for r in e.active if r is not None]
        live += e.retired[self._seen_retired:]
        self._seen_retired = len(e.retired)
        for req in live:
            ts = self.times.setdefault(req.rid, [])
            if len(req.out) > len(ts):
                ts.extend([now] * (len(req.out) - len(ts)))


def _warm(engine, mix: dict, vocab: int) -> None:
    """Compile everything the window runs: each prefill bucket, the
    decode step and the engine's eager per-slot updates, by serving one
    short request per bucket, twice: the second round admits into a cache
    that a decode step returned (another sharding than the initial
    cache's), as the window does."""
    from repro.launch.serve import Request
    for rnd in range(2):
        for i, b in enumerate(generator.prefill_buckets(mix)):
            engine.submit(Request(rid=-1 - i - 100 * rnd,
                                  prompt=[(7 * i + j) % vocab
                                          for j in range(b)],
                                  max_new=WARM_NEW))
        while engine.queue or any(r is not None for r in engine.active):
            engine._admit()
            if any(r is not None for r in engine.active):
                engine._decode_once()
    engine.decode_ms.clear()


def pct(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, float), q,
                               method="linear"))


class Window:
    """What ``serve_window`` saw: the window's start and close on the
    monotonic clock, when the run stopped, the submit lags, and the live
    lengths of the active slots and the engine's ``decode_ms`` indices of
    the decode steps inside the window."""

    def __init__(self, t0: float, seconds: float):
        self.t0, self.end = t0, t0 + seconds
        self.t_stop = t0
        self.lags = []
        self.steps = []
        self.decode_from = self.decode_to = 0


def serve_window(engine, schedule, seconds: float, tracer, on_open=None):
    """Open-loop serving of ``schedule``: the pre-roll's arrivals (due
    before 0), then the window of ``seconds``; ``on_open()`` is called as
    the window opens (trace and compile count), before the first engine
    call inside it.  After the window closes the engine runs on, with no
    new arrivals, until every request due in the window has its first
    token (at most ``DRAIN_S``).  Returns the stamps, the requests by id
    and the ``Window``."""
    from repro.launch.serve import Request
    stamps = Stamps(engine)
    reqs = {}
    i, n = 0, len(schedule)
    win = Window(time.monotonic() - min(0.0, schedule[0].due_s), seconds)
    opened = False
    in_window = [a for a in schedule if a.due_s >= 0]
    while True:
        now = time.monotonic()
        if not opened and now >= win.t0:
            if on_open is not None:
                on_open()
            win.decode_from = len(engine.decode_ms)
            opened = True
            now = time.monotonic()
        busy = engine.queue or any(r is not None for r in engine.active)
        if now >= win.end and i >= n:
            waiting = any(not stamps.times.get(a.rid) for a in in_window)
            if not busy or not waiting or now - win.end > DRAIN_S:
                break
        while i < n and win.t0 + schedule[i].due_s <= now:
            a = schedule[i]
            with tracer.span("bench.submit"):
                req = Request(rid=a.rid, prompt=a.prompt, max_new=a.max_new)
                reqs[a.rid] = req
                engine.submit(req)
            if a.due_s >= 0:
                win.lags.append(now - (win.t0 + a.due_s))
            i += 1
            busy = True
        if not busy:
            nxt = win.t0 + schedule[i].due_s if i < n else win.end
            if not opened:
                nxt = min(nxt, win.t0)
            if nxt > now:
                with tracer.span("bench.wait"):
                    time.sleep(nxt - now)
            continue
        with tracer.span("bench.admit"):
            engine._admit()
        stamps.take(time.monotonic())
        if any(r is not None for r in engine.active):
            live = [len(r.prompt) + len(r.out) for r in engine.active
                    if r is not None]
            with tracer.span("bench.decode"):
                engine._decode_once()
            t = time.monotonic()
            stamps.take(t)
            if opened and t <= win.end:
                win.steps.append(live)
                win.decode_to = len(engine.decode_ms)
    win.t_stop = time.monotonic()
    return stamps, reqs, win


def metrics(schedule, stamps, reqs, win):
    """End-to-end numbers of the window.  Time to first token is taken
    over every request due in the window; a request with no first token
    counts as failed, with its wait until the run stopped.  Tokens and
    gaps between tokens are those delivered inside the window, whenever
    their request arrived."""
    ttft, itl, tokens = [], [], 0
    failed = 0
    for a in schedule:
        ts = stamps.times.get(a.rid, [])
        req = reqs.get(a.rid)
        tokens += sum(1 for t in ts if win.t0 <= t <= win.end)
        itl += [b - a_ for a_, b in zip(ts, ts[1:])
                if win.t0 <= a_ and b <= win.end]
        if a.due_s < 0:
            continue
        if not ts or req is None or req.status != "ok":
            failed += 1
            ttft.append(win.t_stop - (win.t0 + a.due_s))
            continue
        ttft.append(ts[0] - (win.t0 + a.due_s))
    seconds = win.end - win.t0
    return {"ttft_p95_ms": pct(ttft, 95) * 1e3,
            "itl_p95_ms": pct(itl, 95) * 1e3 if itl else float("nan"),
            "serve_tokens_per_s": tokens / seconds}, failed


def sample(schedule, reqs, seed: int, k: int):
    """Finished requests to compare: the longest, and ``k - 1`` drawn
    from the seed."""
    done = [reqs[a.rid] for a in schedule if a.rid in reqs
            and reqs[a.rid].status == "ok"
            and len(reqs[a.rid].out) == reqs[a.rid].max_new]
    if not done:
        return []
    done.sort(key=lambda r: (len(r.prompt) + len(r.out), r.rid))
    longest = done[-1]
    rng = np.random.default_rng(generator.key_words(seed, 3))
    rest = [done[j] for j in rng.permutation(len(done) - 1)[:k - 1]]
    return [longest] + rest


def reference_gaps(cfg: dict, params, seqs, max_seq: int, quant: str = ""
                   ) -> list:
    """For each ``(prompt, served tokens)`` pair, per served token: the
    reference's best logit minus its logit of the served token; with
    ``quant`` (the control), of the token that the reference computed in
    that precision puts first instead.  Each sequence is padded to
    ``max_seq`` and its gaps taken at every position by one program of
    fixed shapes, then cut to the served positions, so that no program is
    compiled per sequence length."""
    import jax
    import jax.numpy as jnp

    import reference.decoder as ref

    @jax.jit
    def all_gaps(p, toks, served_at):
        rows = ref.logits(p, toks, cfg)
        chosen = (jnp.argmax(ref.logits(p, toks, cfg, quant), axis=-1)
                  if quant else served_at)
        return jnp.max(rows, -1) - jnp.take_along_axis(
            rows, chosen[:, None], 1)[:, 0]

    out = []
    for prompt, served in seqs:
        seq = list(prompt) + list(served[:-1])
        lo, hi = len(prompt) - 1, len(seq)
        toks = np.zeros(max_seq, np.int32)
        toks[:hi] = seq
        served_at = np.zeros(max_seq, np.int32)
        served_at[lo:hi] = served
        gap = all_gaps(params, jnp.asarray(toks), jnp.asarray(served_at))
        out.append(np.asarray(gap)[lo:hi])
    return out


def widest(gaps) -> float:
    return max(float(g.max()) for g in gaps) if gaps else float("nan")


def run(r: harness.Run) -> harness.Record:
    import jax

    cfg, mix = r.config, r.mix
    make, key = make_params(cfg, r.seed)
    params = make(key)
    opened = {}

    def on_open():
        opened["t"] = time.monotonic()
        r.tracer.start()
        opened["c0"] = harness.compiles()

    with jax.default_matmul_precision(cfg["matmul_precision"]):
        engine, grid = build_engine(cfg, mix, params)
        if r.fault is not None:
            r.fault("serve", engine)
        _warm(engine, mix, cfg["vocab_size"])
        schedule = generator.request_schedule(mix, r.seed, r.seconds,
                                              cfg["vocab_size"])
        try:
            stamps, reqs, win = serve_window(engine, schedule, r.seconds,
                                             r.tracer, on_open)
        finally:
            c1 = harness.compiles()
            t_stop = time.monotonic()
            r.tracer.stop()
    t_read = time.monotonic()
    e2e, failed = metrics(schedule, stamps, reqs, win)
    e2e["setup_s"] = opened["t"] - r.t_start
    in_window = [a for a in schedule if a.due_s >= 0]
    prefill = [reqs[a.rid].prefill_ms for a in in_window
               if a.rid in reqs and reqs[a.rid].out]
    decode = list(engine.decode_ms[win.decode_from:win.decode_to])
    mem = harness.memory_peak(r.devices)
    picked = [(q.prompt, list(q.out)) for q in
              sample(schedule, reqs, r.seed, mix["check"]["requests"])]
    del engine, stamps
    gaps = reference_gaps(cfg, params, picked, mix["engine"]["max_seq"])
    print(f"phases: set-up {e2e['setup_s']:.1f}s, drain "
          f"{t_stop - win.end:.1f}s, trace read {t_read - t_stop:.1f}s, "
          f"reference {time.monotonic() - t_read:.1f}s", file=sys.stderr)
    return harness.Record(
        end_to_end=e2e, attempted=len(in_window), failed=failed,
        checks={"token_gap": widest(gaps)}, memory_peak_bytes=mem,
        trace=r.tracer.trace, window_s=r.seconds,
        window_programs=harness.lowered_between(opened["c0"], c1),
        layer={"prefill_ms": prefill, "decode_ms": decode,
               "decode_live": win.steps, "config": cfg, "peaks": r.peaks,
               "grid": grid, "chips": len(r.devices),
               "checked_tokens": int(sum(len(g) for g in gaps)),
               "checked": picked,
               "submit_lag_ms": [x * 1e3 for x in win.lags]})


def sweep(cfg: dict, mix: dict, seed: int, seconds: float, rates):
    """The knee sweep: one engine serves the mix at each offered rate in
    turn, each with its own pre-roll and window (rates ascending: what the
    last rate left unfinished runs on into the next pre-roll).  Yields per
    rate the end-to-end numbers, the failures, the requests due in the
    window, the median decode step and the largest submit lag."""
    import statistics

    import jax

    make, key = make_params(cfg, seed)
    params = make(key)
    off = harness.Tracer(False)
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        engine, _ = build_engine(cfg, mix, params)
        _warm(engine, mix, cfg["vocab_size"])
        for k, rate in enumerate(rates):
            m = dict(mix, arrivals=dict(mix["arrivals"], rate_per_s=rate))
            sched = [dataclasses.replace(a, rid=a.rid + 1_000_000 * (k + 1))
                     for a in generator.request_schedule(
                         m, seed, seconds, cfg["vocab_size"])]
            stamps, reqs, win = serve_window(engine, sched, seconds, off)
            e2e, failed = metrics(sched, stamps, reqs, win)
            dec = engine.decode_ms[win.decode_from:win.decode_to]
            yield {"rate_per_s": rate, "metrics": e2e, "failed": failed,
                   "attempted": sum(1 for a in sched if a.due_s >= 0),
                   "decode_ms_median": statistics.median(dec) if dec
                   else None,
                   "submit_lag_ms_max": 1e3 * max(win.lags, default=0.0)}
