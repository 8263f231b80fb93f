"""Smoke run on one TPU chip: FeDLRT training and factor-resident serving
of Qwen2-7B at its published widths, through the normal entry points.

    python chip_smoke.py

Three phases run in this one process (a chip belongs to one process at a
time), in order:

1. kernels — the compiled Pallas chain (``kernels/ops.lowrank_apply`` with
   ``use_kernels=True``) at every factor shape the train phase uses, at
   rank ``r_max`` and at the augmented ``2·r_max``: forward and custom VJP
   against the float32 jnp chain at ``highest`` matmul precision.
2. train — ``build(ExperimentSpec(...)).run()``: FeDLRT with the
   simplified correction, 3 rounds of 2 local steps on the planted Markov
   token stream at the real vocabulary.
3. serve — ``Experiment.serve()`` on the trained factors: 4 seeded
   requests with staggered arrivals, continuous and static batching.

The numbers printed on the way (compile seconds, seconds per round, peak
device memory, kernel counts) are smoke numbers, not benchmark results.
Every check raises on failure, so the run exits non-zero; the last line
of a run that passed is the JSON verdict.  Without a TPU the script exits
non-zero before any phase runs.

The phases are functions of an ``ExperimentSpec``, so a test can drive
the train and serve phases at reduced shapes on the CPU.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.api import (  # noqa: E402
    DataSpec,
    ExperimentSpec,
    FedSpec,
    ModelSpec,
    ServeSpec,
    TelemetrySpec,
    build,
    serve,
)
from repro.api.tasks import lm_model_config  # noqa: E402
from repro.core.factorization import (  # noqa: E402
    LowRankFactor,
    is_factor,
    rank_mask,
)
from repro.kernels.ops import lowrank_apply  # noqa: E402
from repro.telemetry.sinks import MemorySink  # noqa: E402
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

SEED = 0
ROUNDS = 3
LOCAL_STEPS = 2
# the compile rehearsal against a described v5e puts the 4-client round
# step at 15.05 GiB of the chip's 15.75 GiB (args 2.56 + temp 12.49)
CLIENTS = 4
PROMPT_LENS = (16, 5, 11, 3)
ARRIVALS = (0, 0, 1, 3)
# max|kernel − ref| / max|ref| per output: four bf16 ulps at the largest
# magnitude (the chain rounds its (M, R) intermediate and its outputs)
KERNEL_TOL = {"bfloat16": 2.0**-6, "float32": 1e-4}

_COMPILE_S: dict = collections.defaultdict(float)
_PHASE = ["setup"]


def _on_duration(event: str, duration: float, **_):
    # backend compile or persistent-cache load, whichever served the jit
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[_PHASE[0]] += duration


@contextlib.contextmanager
def phase(name: str):
    _PHASE[0] = name
    t0 = time.perf_counter()
    print(f"== {name}", flush=True)
    yield
    print(
        f"== {name} done: {time.perf_counter() - t0:.1f} s wall, "
        f"{_COMPILE_S[name]:.1f} s compiling",
        flush=True,
    )


def smoke_spec():
    """The full-width run: Qwen2-7B, 28 layers, registry policy, bf16."""
    data = DataSpec()  # the spec's default batch and seq
    return ExperimentSpec(
        name="chip-smoke",
        seed=SEED,
        rounds=ROUNDS,
        log_every=1,
        model=ModelSpec(arch="qwen2-7b", kernels="auto"),
        data=dataclasses.replace(
            data,
            tokens_per_client=tokens_per_client(
                ROUNDS, CLIENTS, data.batch, data.seq
            ),
        ),
        fed=FedSpec(
            method="fedlrt", correction="simplified", clients=CLIENTS,
            local_steps=LOCAL_STEPS,
        ),
        telemetry=TelemetrySpec(enabled=True, sinks="console,memory"),
        serve=ServeSpec(
            max_batch=4, max_queue=4, max_prompt=16, prompt_bucket=16,
            max_new_tokens=8,
        ),
    )


def tokens_per_client(rounds: int, clients: int, batch: int, seq: int) -> int:
    """Stream tokens per client that the run consumes: the lm task cuts
    windows of ``seq + 1`` at stride ``seq // 2``, deals them out evenly,
    and each client takes ``batch`` windows per round."""
    windows = rounds * clients * batch
    total = (windows - 1) * (seq // 2) + seq + 1
    return -(-total // clients)


def require(ok, what) -> None:
    """A check that holds under ``python -O`` too (``assert`` would not)."""
    if not ok:
        raise RuntimeError(f"chip smoke check failed: {what}")


def dispatch_resolutions(hub) -> set:
    """Every ``kernels.dispatch`` resolution the hub's memory sink saw."""
    (sink,) = [s for s in hub.sinks if isinstance(s, MemorySink)]
    got = {
        e["attrs"]["resolved"]
        for e in sink.events
        if e["name"] == "kernels.dispatch"
    }
    require(got, "no kernels.dispatch counter was emitted")
    return got


def peak_bytes():
    return jax.devices()[0].memory_stats()["peak_bytes_in_use"]


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def kernel_shapes(cfg, m: int):
    """(label, M, K, N, R) of every factorized matmul in the train phase.

    The embedding runs the chain on gathered ``U`` rows: ``x = U[tokens]``
    is (M, R), the coefficient takes ``U``'s slot and ``V`` the output.
    """
    d, pol = cfg.d_model, cfg.lowrank
    mats = {
        "q/o": (d, cfg.num_heads * cfg.hd),
        "k/v": (d, cfg.num_kv_heads * cfg.hd),
        "gate/up": (d, cfg.d_ff),
        "down": (cfg.d_ff, d),
        "lm_head": (d, cfg.vocab_size),
    }
    for label, (k, n) in mats.items():
        r = pol.r_max_for(k, n)
        for rr in (r, 2 * r):
            yield label, m, k, n, rr
    r = pol.r_max_for(cfg.vocab_size, d)
    for rr in (r, 2 * r):
        yield "embed", m, rr, d, rr


def _fwd_vjp(x, U, S, V, dy, *, use_kernels):
    y, pull = jax.vjp(lambda *a: lowrank_apply(*a, use_kernels), x, U, S, V)
    return (y, *pull(dy))


def kernel_phase(cfg, m: int):
    """Compiled kernels vs the float32 jnp chain at every train shape."""
    dt = jnp.dtype(cfg.compute_dtype)
    tol = KERNEL_TOL[dt.name]
    kern = jax.jit(functools.partial(_fwd_vjp, use_kernels=True))
    ref = jax.jit(functools.partial(_fwd_vjp, use_kernels=False))
    names = ("y", "dx", "dU", "dS", "dV")
    for i, (label, M, K, N, R) in enumerate(kernel_shapes(cfg, m)):
        ks = jax.random.split(jax.random.PRNGKey(SEED + i), 5)
        args = (
            jax.random.normal(ks[0], (M, K), dt),
            (jax.random.normal(ks[1], (K, R)) / K**0.5).astype(dt),
            jax.random.normal(ks[2], (R, R)).astype(dt),
            (jax.random.normal(ks[3], (N, R)) / N**0.5).astype(dt),
            jax.random.normal(ks[4], (M, N), dt),
        )
        compiled = kern.lower(*args).compile()
        n_custom = compiled.as_text().count("tpu_custom_call")
        require(n_custom > 0, f"{label}: no tpu_custom_call in the compiled HLO")
        got = compiled(*args)
        with jax.default_matmul_precision("highest"):
            want = ref(*(a.astype(jnp.float32) for a in args))
        errs = {
            n: float(
                jnp.max(jnp.abs(g.astype(jnp.float32) - w))
                / jnp.max(jnp.abs(w))
            )
            for n, g, w in zip(names, got, want)
        }
        print(
            f"kernel {label:8s} M={M} K={K} N={N} R={R} "
            f"tpu_custom_call={n_custom} rel_err "
            + " ".join(f"{n}={e:.2e}" for n, e in errs.items())
            + f" (tol {tol:.2e})",
            flush=True,
        )
        bad = {n: e for n, e in errs.items() if not e <= tol}
        require(not bad, f"{label} R={R}: errors above {tol:.2e}: {bad}")
        del args, got, want


def check_factors(params):
    """Ranks ≤ r_max and exactly-zero inactive columns on every factor."""
    leaves = jax.tree_util.tree_flatten_with_path(params, is_leaf=is_factor)[0]
    n = 0
    for path, f in leaves:
        if not isinstance(f, LowRankFactor):
            continue
        n += 1
        where = jax.tree_util.keystr(path)
        rank = jnp.asarray(f.rank)
        require(bool(jnp.all(rank <= f.r_max)), f"{where}: rank > {f.r_max}")
        off = 1.0 - rank_mask(rank, f.r_max)
        spill = (
            jnp.max(jnp.abs(f.U.astype(jnp.float32) * off[..., None, :]))
            + jnp.max(jnp.abs(f.V.astype(jnp.float32) * off[..., None, :]))
            + jnp.max(jnp.abs(f.S.astype(jnp.float32) * off[..., None, :]))
            + jnp.max(jnp.abs(f.S.astype(jnp.float32) * off[..., :, None]))
        )
        require(float(spill) == 0.0, f"{where}: inactive columns are nonzero")
    require(n, "no LowRankFactor in the params")
    return n


def train_phase(spec):
    """Build and run the experiment; returns it after checking the run."""
    exp = build(spec)
    print(exp.describe(), flush=True)
    hist = exp.run()
    require(len(hist) == spec.rounds, (len(hist), spec.rounds))
    for r in hist:
        print(
            f"round {r.round_idx}: loss {r.loss_before:.4f} -> "
            f"{r.loss_after:.4f}, {r.seconds:.2f} s (metrics fetched), "
            f"mean rank {np.mean([np.mean(v) for v in r.ranks.values()]):.1f}",
            flush=True,
        )
        require(np.isfinite(r.loss_before) and np.isfinite(r.loss_after), r)
    n_factors = check_factors(exp.params)
    print(f"factors checked: {n_factors} (rank <= r_max, inactive == 0)")

    d = spec.data
    step = exp.engine._step_for(spec.fed.clients, weighted=False)
    batch = {
        "tokens": jax.ShapeDtypeStruct(
            (spec.fed.clients, d.batch, d.seq + 1), jnp.int32
        )
    }
    text = step.lower(exp.params, batch, jnp.int32(0)).as_text()
    n_custom = text.count("tpu_custom_call")
    print(f"round step: tpu_custom_call={n_custom} in the lowered module")
    return exp, n_custom


def serve_phase(exp):
    """Continuous and static batching over the trained factors; returns
    the sessions after checking that both emitted the same tokens."""
    spec = exp.spec
    require(spec.serve.mode == "continuous", spec.serve.mode)
    static = spec.replace(serve=dataclasses.replace(spec.serve, mode="static"))
    vocab = lm_model_config(spec.model).vocab_size
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, vocab, size=n, dtype=np.int32) for n in PROMPT_LENS]
    sessions, outs = [], []
    for mode in ("continuous", "static"):
        # one at a time: each session installs its own telemetry hub
        sess = exp.serve() if mode == "continuous" else serve(static, params=exp.params)
        out, comps = sess.generate(prompts, arrival_steps=list(ARRIVALS))
        n_tok = sum(len(c.tokens) for c in comps)
        print(
            f"serve {mode}: {len(comps)} requests, {n_tok} tokens, admit "
            f"steps {[c.admit_step for c in comps]}",
            flush=True,
        )
        require(n_tok == len(prompts) * spec.serve.max_new_tokens, n_tok)
        sessions.append(sess)
        outs.append(out)
    for i, (a, b) in enumerate(zip(*outs)):
        require(np.array_equal(a, b), f"request {i}: continuous {a} != static {b}")
    print("serve: continuous and static tokens identical")
    return sessions


# ---------------------------------------------------------------------------


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke.py needs a TPU; JAX found platform "
            f"{dev.platform!r} ({dev.device_kind})",
            file=sys.stderr,
        )
        return 1

    cache = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    print(
        f"jax {jax.__version__}, {dev.platform} {dev.device_kind} "
        f"x{len(jax.devices())}, compile cache {cache}",
        flush=True,
    )

    spec = smoke_spec()
    cfg = lm_model_config(spec.model)
    print(
        f"model {cfg.name}: layers={cfg.num_layers} (no cut) d_model="
        f"{cfg.d_model} heads={cfg.num_heads}/{cfg.num_kv_heads} head_dim="
        f"{cfg.hd} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
        f"params={cfg.param_dtype} compute={cfg.compute_dtype} "
        f"kernels={cfg.kernels}; clients={spec.fed.clients} "
        f"batch={spec.data.batch} seq={spec.data.seq}",
        flush=True,
    )

    with phase("kernels"):
        kernel_phase(cfg, spec.data.batch * spec.data.seq)
    with phase("train"):
        exp, n_custom = train_phase(spec)
        require(n_custom > 0, "no Pallas kernel in the round step")
        got = dispatch_resolutions(exp.hub)
        require(got == {"True"}, f"kernels.dispatch resolved to {got}")
        print(f"train: kernels.dispatch resolved {sorted(got)}")
        print(f"peak_bytes_in_use after train: {peak_bytes()}")
    with phase("serve"):
        for sess in serve_phase(exp):
            got = dispatch_resolutions(sess.hub)
            require(got == {"True"}, f"serve kernels.dispatch resolved to {got}")
        print("serve: kernels.dispatch resolved ['True']")
        print(f"peak_bytes_in_use after serve: {peak_bytes()}")
    print(
        "compile seconds: "
        + ", ".join(f"{k}={v:.1f}" for k, v in _COMPILE_S.items()),
        flush=True,
    )
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
