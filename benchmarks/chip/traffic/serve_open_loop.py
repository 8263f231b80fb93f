"""Traffic kind ``serve_open_loop``: requests arriving on the wall clock.

The mix file gives the load: ``rate_per_s`` (Poisson arrivals), prompt
and output lengths (lognormal of mean ``*_mean`` and shape ``*_sigma``,
clipped to ``*_min``..``*_max``; each output length is the request's
``max_new_tokens``), the serving shapes (``max_batch``, ``max_prompt``,
``prompt_bucket``, ``max_new_tokens``) and ``checked_tokens``, how many
served tokens the reference checks.

Every seed serves the same arrivals and lengths, in the same order
(drawn from a fixed stream), with its own token ids: the work and its
timing do not change with the seed.

Set-up serves the seeded weights through ``serve(spec, params=...)``,
warms every prompt bucket, the slot insert, the decode step and both
samplers by serving one short request per bucket, and freezes the
objects it made out of the garbage collector's way.  The window submits
each request at its scheduled time and drives
``ContinuousScheduler.step()`` itself, timestamping every step: a token
is received when the step that made it returns.  Time to first token
runs from the scheduled arrival.  After the window the server runs on,
submitting nothing more, until every request has its first token (tokens
then no longer count); the server is freed, and the plain reference
checks a seeded sample of the finished requests, the longest among them.
"""
from __future__ import annotations

import gc
import math
import sys
import time

import numpy as np

from benchmarks.chip import harness, weights

#: the stream the lengths and gaps are drawn from, whatever the run's seed
WORK_SEED = 20260101
#: how long the server may run on after the window
DRAIN_S = 60.0


# ---------------------------------------------------------------------------
# the generator
# ---------------------------------------------------------------------------


def _lognormal(rng, mean, sigma, lo, hi, n):
    """Lengths of mean ``mean`` (before clipping): the median is
    ``mean · exp(−σ²/2)``."""
    x = np.exp(np.log(mean) - sigma**2 / 2 + sigma * rng.standard_normal(n))
    return np.clip(np.round(x), lo, hi).astype(np.int64)


def schedule(mix: dict, vocab: int, seed: int, seconds: float) -> list:
    """[(arrival_s, prompt ids, output length)] of one window, in order of
    arrival.  Gaps and lengths come from ``WORK_SEED``; the seed draws
    the ids."""
    n = int(math.ceil(mix["rate_per_s"] * seconds * 1.5)) + 8
    # one stream each, so that the k-th request is the same at every rate
    gaps = np.random.default_rng([WORK_SEED, 0]).exponential(1.0 / mix["rate_per_s"], n)
    prompts = _lognormal(np.random.default_rng([WORK_SEED, 1]), mix["prompt_mean"],
                         mix["prompt_sigma"], mix["prompt_min"], mix["prompt_max"], n)
    outputs = _lognormal(np.random.default_rng([WORK_SEED, 2]), mix["output_mean"],
                         mix["output_sigma"], mix["output_min"], mix["output_max"], n)
    arrivals = np.cumsum(gaps) - gaps[0]
    ids = np.random.default_rng([seed, 2]).integers(0, vocab, int(prompts.sum()))
    out = []
    for t, p, k in zip(arrivals, np.split(ids, np.cumsum(prompts)[:-1]), outputs):
        if t >= seconds:
            break
        out.append((float(t), p.astype(np.int32), int(k)))
    return out


# ---------------------------------------------------------------------------
# the server
# ---------------------------------------------------------------------------


def open_server(cell: harness.Cell, seed: int):
    """``serve(spec, params=...)`` on the seed's weights."""
    import jax

    from repro.api import ExperimentSpec, ModelSpec, ServeSpec, TelemetrySpec, serve
    from repro.api.tasks import lm_model_config
    from repro.models import build_model

    from benchmarks.chip.program import check_config, program_params

    cfg, mix = cell.config, cell.traffic
    spec = ExperimentSpec(
        name=cell.name, seed=seed % 2**31, rounds=0, log_every=0,
        model=ModelSpec(arch=cfg["registry"], smoke=cfg.get("smoke", False),
                        kernels=cfg["kernels"]),
        telemetry=TelemetrySpec(enabled=False),
        serve=ServeSpec(
            max_batch=mix["max_batch"], max_queue=mix["max_queue"],
            max_prompt=mix["max_prompt"], prompt_bucket=mix["prompt_bucket"],
            max_new_tokens=mix["max_new_tokens"], mode="continuous",
        ),
    )
    mc = lm_model_config(spec.model)
    check_config(cfg, mc, cell.root)
    model = build_model(mc)
    like = jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
    flat = weights.generate(cfg, seed, cell.root)
    return serve(spec, params=program_params(flat, like, cfg, cell.root))


def warm(sess, mix: dict) -> None:
    """Serve one two-token request per prompt bucket: compiles (or loads)
    every executable the window uses."""
    from repro.serve import Request

    sched = sess.scheduler
    buckets = range(mix["prompt_bucket"], mix["max_prompt"] + 1, mix["prompt_bucket"])
    for i, b in enumerate(buckets):
        sched.submit(Request(rid=-(i + 1), tokens=np.ones(b, np.int32), max_new_tokens=2))
    while sched.queue or sched.active:
        sched.step()


class GcPauses:
    """The garbage collector's passes while it is installed, as
    (generation, seconds)."""

    def __init__(self):
        self.pauses, self._t = [], None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"], time.perf_counter() - self._t))
            self._t = None

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)


class Log:
    """Per-request and per-step readings of one window."""

    def __init__(self):
        self.arrival, self.prompt = {}, {}
        self.tokens = {}    # rid -> [token receive times]
        self.done = {}      # rid -> Completion
        # (end time, contexts the decode read, admitted prompt lengths,
        #  decoded?, seconds the step took)
        self.steps = []
        self.refused = 0
        self.late = 0.0     # how late the generator submitted, at most
        self.drain_s = 0.0  # how long the server ran on after the window
        self.gc = []        # the garbage collector's passes in the window

    def record(self, sched, before: dict, done: list, t: float, admitted: list,
               decoded: bool, took: float):
        contexts = []
        for s in sched.slots:
            if s is not None:
                self._got(s.rid, len(s.out) - before.get(s.rid, 0), t)
                contexts.append(s.prompt_len + len(s.out) - 1)
        for c in done:
            self._got(c.rid, len(c.tokens) - before.get(c.rid, 0), t)
            self.done[c.rid] = c
            if c.rid in before:  # it was in a slot for this step's decode
                contexts.append(c.prompt_len + len(c.tokens) - 1)
        self.steps.append((t, contexts, admitted, decoded, took))

    def _got(self, rid, n, t):
        if n > 0:
            self.tokens.setdefault(rid, []).extend([t] * n)


def drive(sess, plan: list, seconds: float, log: Log, *, t0=None,
          drain_s: float = 0.0, until_first_tokens: bool = False):
    """Submit ``plan`` on the wall clock and step the scheduler until
    ``seconds`` past ``t0`` (now if None); then, submitting nothing more,
    run on for at most ``drain_s`` from then, until the server is idle or,
    with ``until_first_tokens``, until every request submitted has its
    first token.  Returns ``t0``."""
    import jax

    from repro.serve import Request

    sched = sess.scheduler
    i = 0
    t0 = time.perf_counter() if t0 is None else t0
    drain_until = None
    while True:
        now = time.perf_counter() - t0
        closed = now >= seconds
        if closed and drain_until is None:
            drain_until = now + drain_s
        if closed and (not (sched.queue or sched.active) or now >= drain_until
                       or (until_first_tokens and log.tokens.keys() >= log.arrival.keys())):
            break
        while not closed and i < len(plan) and plan[i][0] <= now:
            t_arr, prompt, k = plan[i]
            log.arrival[i], log.prompt[i] = t_arr, prompt
            log.late = max(log.late, now - t_arr)
            try:
                with jax.profiler.TraceAnnotation("bench.submit"):
                    sched.submit(Request(rid=i, tokens=prompt, max_new_tokens=k))
            except RuntimeError:
                log.refused += 1
            i += 1
        if sched.queue or sched.active:
            before = {s.rid: len(s.out) for s in sched.slots if s is not None}
            free = sum(s is None for s in sched.slots)
            admitted = [len(r.tokens) for r, _, _ in list(sched.queue)[:free]]
            n_dec = sched.decode_steps
            t_step = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.step"):
                done = sched.step()
            t_end = time.perf_counter()
            log.record(sched, before, done, t_end - t0, admitted,
                       sched.decode_steps > n_dec, t_end - t_step)
        elif not closed and i < len(plan):
            time.sleep(max(0.0, min(plan[i][0], seconds) - (time.perf_counter() - t0)))
        elif not closed:
            time.sleep(max(0.0, seconds - now))
    return t0


def p95(values) -> float:
    """The 95th percentile, interpolated as numpy's default does; infinite
    where a missing (infinite) value enters it."""
    v = np.sort(np.asarray(values, np.float64))
    if not v.size:
        return math.inf
    k = 0.95 * (v.size - 1)
    lo, hi = v[math.floor(k)], v[math.ceil(k)]
    return float(hi) if not math.isfinite(hi) else float(lo + (hi - lo) * (k - math.floor(k)))


def summarise(log: Log, seconds: float) -> dict:
    """End-to-end numbers of the window.  A request that arrived and has
    no first token is missing: its wait counts as infinite."""
    ttft, itl, n_tok = [], [], 0
    for rid, t_arr in log.arrival.items():
        times = log.tokens.get(rid, [])
        ttft.append(times[0] - t_arr if times else math.inf)
        inside = [t for t in times if t <= seconds]
        n_tok += len(inside)
        itl.extend(np.diff(inside).tolist())
    queued = [c.queued_s for c in log.done.values()]
    return {
        "ttft_p95_ms": 1e3 * p95(ttft),
        "itl_p95_ms": 1e3 * p95(itl),
        "serve_tokens_per_s": n_tok / seconds,
        "queue_wait_p95_ms": 1e3 * p95(queued),
        "requests": len(log.arrival),
        "started": sum(1 for r in log.arrival if r in log.tokens),
        "finished": len(log.done),
    }


# ---------------------------------------------------------------------------
# the reference and the comparison
# ---------------------------------------------------------------------------


def sample(log: Log, seed: int, want_tokens: int) -> list:
    """Finished requests the reference checks: the longest, then a seeded
    draw, until ``want_tokens`` served tokens are covered."""
    done = sorted(log.done)
    if not done:
        return []
    longest = max(done, key=lambda r: len(log.prompt[r]) + len(log.done[r].tokens))
    rest = [r for r in np.random.default_rng([seed, 3]).permutation(done) if r != longest]
    picked, n = [longest], len(log.done[longest].tokens)
    for r in rest:
        if n >= want_tokens:
            break
        picked.append(int(r))
        n += len(log.done[r].tokens)
    return picked


def logit_gaps(cell: harness.Cell, seed: int, seqs: list, mode: str = "float32",
               rows: int = 2) -> list:
    """For each (prompt, served tokens): per served token, how far its
    logit in the float32 reference (the logits of the configuration's
    architecture file) lies below the reference's best.  With
    ``mode`` lower than float32, the token judged is the one that the
    reference in that precision puts first (the control).  Sequences run
    ``rows`` at a time, padded to the longest a slot can hold."""
    import jax
    import jax.numpy as jnp

    cfg = cell.config
    arch = harness.architecture(cfg, cell.root)
    w = {k: v for k, v in weights.generate(cfg, seed, cell.root).items()
         if not k.endswith("/rank")}
    T = cell.traffic["max_prompt"] + cell.traffic["max_new_tokens"]

    def logits_fn(mode_):
        return jax.jit(lambda w_, x: arch.logits(cfg, w_, x, mode_))

    ref = logits_fn("float32")
    low = logits_fn(mode) if mode != "float32" else None
    out = []
    with jax.default_matmul_precision("highest"):
        for i in range(0, len(seqs), rows):
            chunk = seqs[i:i + rows]
            x = np.zeros((rows, T), np.int32)
            for j, (p, s) in enumerate(chunk):
                seq = np.concatenate([p, s])[:T]
                x[j, :len(seq)] = seq
            lg = ref(w, jnp.asarray(x))
            lg_low = low(w, jnp.asarray(x)) if low is not None else None
            for j, (p, s) in enumerate(chunk):
                pos = np.arange(len(p) - 1, len(p) - 1 + len(s))
                got = np.asarray(lg[j, pos], np.float64)
                pick = (np.asarray(s) if lg_low is None
                        else np.asarray(jnp.argmax(lg_low[j, pos], -1)))
                out.append(got.max(-1) - got[np.arange(len(s)), pick])
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def served(cell: harness.Cell, seed: int, seconds: float, session=None):
    """Serve the seed's plan for ``seconds`` (in ``session``'s window when
    given), run on until every request has its first token, and free the
    server.  Returns (log, window length, sampled (prompt, served
    tokens))."""
    mix = cell.traffic
    sess = open_server(cell, seed)
    warm(sess, mix)
    plan = schedule(mix, cell.config["vocab_size"], seed, seconds)
    log = Log()
    # set-up's objects are never garbage: the collector skips them from
    # here on, as a server does once it is warm
    gc.collect()
    gc.freeze()
    with GcPauses() as pauses:
        if session is not None:
            with session.window():
                t0 = drive(sess, plan, seconds, log)
                # the window closes when the step running at ``seconds`` returns
                window_s = time.perf_counter() - t0
        else:
            t0 = drive(sess, plan, seconds, log)
            window_s = time.perf_counter() - t0
    log.gc = pauses.pauses
    if session is not None:
        import jax

        session.rec.extra["device"] = harness.device_block(jax, 1)
    t_drain = time.perf_counter()
    # outside the window: requests that arrived late get their first
    # token, for the tail of time to first token; tokens after the close
    # do not count
    drive(sess, [], seconds, log, t0=t0, drain_s=DRAIN_S, until_first_tokens=True)
    log.drain_s = time.perf_counter() - t_drain
    picked = sample(log, seed, int(mix["checked_tokens"]))
    seqs = [(log.prompt[r], np.asarray(log.done[r].tokens)) for r in picked]
    sess.engine.params = sess.engine.state = sess.scheduler.state = None
    del sess
    gc.unfreeze()
    gc.collect()
    return log, window_s, seqs


def worst_gap(gaps: list) -> float:
    return max((float(g.max()) for g in gaps if g.size), default=math.inf)


def run(session: harness.Session) -> None:
    rec, cell = session.rec, session.rec.cell
    log, rec.window_s, seqs = served(cell, rec.seed, session.seconds, session)
    summ = summarise(log, session.seconds)
    rec.end_to_end.update({k: summ[k] for k in ("ttft_p95_ms", "itl_p95_ms")})
    rec.attempted = summ["requests"]
    # a request fails when it gets no first token; one still decoding at
    # the close has not failed
    rec.failed = rec.attempted - summ["started"]
    rec.extra["serve_log"] = log
    print(f"window: {summ['requests']} requests, {summ['finished']} finished "
          f"(ran on {log.drain_s:.1f} s), refused {log.refused}, generator late by "
          f"at most {1e3 * log.late:.1f} ms; {summ}", file=sys.stderr)
    gc_s = [t for _, t in log.gc]
    print(f"garbage collector in the window: {len(gc_s)} passes "
          f"({sum(g == 2 for g, _ in log.gc)} of generation 2), "
          f"{1e3 * sum(gc_s):.1f} ms in all, longest {1e3 * max(gc_s, default=0.0):.1f} ms",
          file=sys.stderr)
    inside = [x for x in log.steps if x[0] <= rec.window_s]
    took = np.array([x[4] for x in inside])
    slow = sorted(inside, key=lambda x: -x[4])[:5]
    print(f"steps: {len(inside)} in the window, median {1e3 * np.median(took):.1f} ms, "
          f"slowest (end s, ms, admitted prompt lengths, active): "
          f"{[(round(x[0], 2), round(1e3 * x[4], 1), x[2], len(x[1])) for x in slow]}",
          file=sys.stderr)
    itl = np.concatenate([np.diff([t for t in ts if t <= session.seconds])
                          for ts in log.tokens.values()] or [np.zeros(0)])
    if itl.size:
        q = np.percentile(1e3 * itl, [50, 90, 93, 95, 97, 99])
        print(f"gaps between tokens: {itl.size}, ms at p50/90/93/95/97/99 "
              f"{np.round(q, 2).tolist()}, {int((itl > 0.1).sum())} over 100 ms",
              file=sys.stderr)
    t_ref = time.perf_counter()
    gaps = logit_gaps(cell, rec.seed, seqs)
    print(f"reference: {len(seqs)} requests, {sum(g.size for g in gaps)} served "
          f"tokens, {time.perf_counter() - t_ref:.1f} s", file=sys.stderr)
    rec.checks = {"logit_gap": {"value": worst_gap(gaps),
                                "limit": cell.workload["limits"]["logit_gap"]}}


def calibrate(cell: harness.Cell, seeds: list, control_seeds: set, seconds: float = 15.0):
    """Per seed: serve the plan for ``seconds`` and read ``logit_gap``
    against the float32 reference.  On the control seeds also: the
    control (the token that the reference in the precision below the
    configuration's puts first), a token altered where it is produced
    (each served token + 1), and a decode step that returns its cache
    unchanged (planted in the program, a run of its own)."""
    from repro.serve.engine import ServeEngine

    from benchmarks.chip.references.arith import LOWER

    vocab = cell.config["vocab_size"]
    for seed in seeds:
        t0 = time.perf_counter()
        log, _, seqs = served(cell, seed, seconds)
        line = {"cell": cell.name, "seed": seed, "tokens": int(sum(len(s) for _, s in seqs)),
                "program": {"logit_gap": worst_gap(logit_gaps(cell, seed, seqs))}}
        if seed in control_seeds:
            lower = LOWER[cell.config["compute_dtype"]]
            line["control"] = {"logit_gap": worst_gap(logit_gaps(cell, seed, seqs, mode=lower))}
            altered = [(p, (s + 1) % vocab) for p, s in seqs]
            line["token_altered"] = {"logit_gap": worst_gap(logit_gaps(cell, seed, altered))}
            real = ServeEngine.step
            ServeEngine.step = lambda self, state, last: (real(self, state, last)[0], state)
            try:
                _, _, stale = served(cell, seed, seconds)
            finally:
                ServeEngine.step = real
            line["state_unchanged"] = {"logit_gap": worst_gap(logit_gaps(cell, seed, stale))}
        line["seconds"] = time.perf_counter() - t0
        yield line


def sweep(cell: harness.Cell, seed: int, rates: list, seconds: float) -> list:
    """Summaries of windows at several offered rates on one server: what
    the knee of a mix is found from.  Each window drains before the next."""
    sess = open_server(cell, seed)
    warm(sess, cell.traffic)
    gc.collect()
    gc.freeze()
    out = []
    for rate in rates:
        mix = dict(cell.traffic, rate_per_s=rate)
        plan = schedule(mix, cell.config["vocab_size"], seed, seconds)
        log = Log()
        with GcPauses() as pauses:
            t0 = drive(sess, plan, seconds, log)
        queued, active = len(sess.scheduler.queue), sess.scheduler.active
        drive(sess, [], seconds, log, t0=t0, drain_s=DRAIN_S)
        summ = summarise(log, seconds)
        took = [x[4] for x in log.steps if x[0] <= seconds]
        summ.update(rate_per_s=rate, queued_at_close=queued, active_at_close=active,
                    late_ms=1e3 * log.late, refused=log.refused,
                    step_ms_median=1e3 * float(np.median(took)),
                    step_ms_max=1e3 * max(took),
                    gc_passes=len(pauses.pauses),
                    gc_ms_max=1e3 * max((t for _, t in pauses.pauses), default=0.0))
        print(summ, file=sys.stderr, flush=True)
        out.append(summ)
    return out
