"""Serving subsystem tests: engine, scheduler, quantization, spec path.

The heavy pins: a 2-round-trained checkpoint served factor-resident is
token-identical to the materialized dense path; rank-sliced load ≡ full
load; continuous batching ≡ the single-sequence reference (admission
order and batch composition never change a request's tokens).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (
    CheckpointSpec,
    DataSpec,
    ExperimentSpec,
    FedSpec,
    ModelSpec,
    ServeSpec,
    build,
    serve,
)
from repro.core.factorization import LowRankFactor, is_factor, materialize
from repro.serve import (
    Completion,
    ContinuousScheduler,
    QuantizedFactor,
    Request,
    ServeEngine,
    decode_matmul_flops,
    quantization_error_bound,
    quantize_params,
    rank_slice_params,
    resident_bytes,
)
from repro.serve.quantize import (
    dequantize_factor,
    materialize_params,
    quantize_factor,
)


def tiny_spec(**serve_kw) -> ExperimentSpec:
    sv = dict(max_batch=3, max_prompt=16, prompt_bucket=8, max_new_tokens=6)
    sv.update(serve_kw)
    return ExperimentSpec(
        name="serve-test",
        model=ModelSpec(kind="lm", preset="llm-tiny", smoke=True),
        serve=ServeSpec(**sv),
    )


def prompts_for(spec, n=4, seed=0):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(1, 256, size=int(rng.integers(3, spec.serve.max_prompt)))
        .astype(np.int32)
        for _ in range(n)
    ]


@pytest.fixture(scope="module")
def session():
    return serve(tiny_spec())


# ---------------------------------------------------------------------------
# engine ≡ single-sequence reference
# ---------------------------------------------------------------------------


def ref_greedy(session, prompt, n):
    """Unbatched, unpadded, unbucketed decode through the raw model."""
    model, params = session.engine.model, session.engine.params
    logits, cache = model.serve_prefill(
        params, {"tokens": jnp.asarray(prompt)[None]},
        cache_len=len(prompt) + n,
    )
    out = [int(jnp.argmax(logits, -1)[0])]
    for _ in range(n - 1):
        logits, cache = model.serve_step(
            params, cache, jnp.asarray([[out[-1]]], jnp.int32)
        )
        out.append(int(jnp.argmax(logits, -1)[0]))
    return out


def test_continuous_matches_single_sequence_reference(session):
    spec = session.spec
    prompts = prompts_for(spec)
    outs, comps = session.generate(prompts, arrival_steps=[0, 0, 1, 3])
    for out, p in zip(outs, prompts):
        assert out.tolist() == ref_greedy(session, p, 6)
    # staggered arrivals really were admitted into freed slots mid-run
    assert any(c.admit_step > 0 for c in comps)


def test_greedy_deterministic_across_runs(session):
    prompts = prompts_for(session.spec)
    outs1, _ = session.generate(prompts)
    outs2, _ = session.generate(prompts)
    for a, b in zip(outs1, outs2):
        np.testing.assert_array_equal(a, b)


def test_batching_invariance(session):
    """A request's tokens don't depend on who shares the batch."""
    prompts = prompts_for(session.spec)
    together, _ = session.generate(prompts)
    for i, p in enumerate(prompts):
        alone, _ = session.generate([p])
        np.testing.assert_array_equal(together[i], alone[0])


def test_eos_early_stop(session):
    [out], _ = session.generate([np.arange(1, 5, dtype=np.int32)])
    eos = int(out[0])
    comps = session.run([Request(
        rid=0, tokens=np.arange(1, 5, dtype=np.int32), eos_id=eos,
    )])
    assert comps[0].tokens.tolist() == [eos]  # stopped at the first token


def test_temperature_sampling_reproducible_and_batching_invariant():
    spec = tiny_spec(temperature=1.3)
    sess = serve(spec)
    prompts = prompts_for(spec, n=3, seed=1)
    outs1, _ = sess.generate(prompts)
    outs2, _ = sess.generate(prompts)
    for a, b in zip(outs1, outs2):
        np.testing.assert_array_equal(a, b)  # keyed on (seed, rid, index)
    # batching invariance under sampling: keep the rid, drop the batchmates
    comps = sess.run([Request(rid=1, tokens=prompts[1])])
    np.testing.assert_array_equal(outs1[1], comps[0].tokens)
    greedy, _ = serve(tiny_spec()).generate(prompts)
    assert any(
        o.tolist() != g.tolist() for o, g in zip(outs1, greedy)
    )  # temperature actually changes something


# ---------------------------------------------------------------------------
# train → serve round trip
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def trained_spec(tmp_path_factory):
    ckpt = str(tmp_path_factory.mktemp("serve_ckpt"))
    spec = ExperimentSpec(
        name="serve-roundtrip",
        rounds=2,
        model=ModelSpec(kind="lm", preset="llm-tiny", smoke=True),
        data=DataSpec(kind="token_stream", tokens_per_client=2048, batch=4,
                      seq=32),
        fed=FedSpec(method="fedlrt", clients=2, local_steps=2),
        checkpoint=CheckpointSpec(dir=ckpt, every=1),
        serve=ServeSpec(checkpoint=ckpt, max_batch=2, max_prompt=16,
                        prompt_bucket=8, max_new_tokens=5),
    )
    exp = build(spec)
    exp.run()
    return spec


def test_trained_checkpoint_factor_resident_equals_dense(trained_spec):
    """The acceptance pin: factor-resident decode of a trained checkpoint
    is token-identical to the materialized U S Vᵀ path, at strictly fewer
    cost-model decode FLOPs."""
    prompts = prompts_for(trained_spec, n=3, seed=2)
    factor_sess = serve(trained_spec)
    dense_sess = serve(dataclasses.replace(
        trained_spec,
        serve=dataclasses.replace(trained_spec.serve, materialize=True),
    ))
    f_outs, _ = factor_sess.generate(prompts)
    d_outs, _ = dense_sess.generate(prompts)
    for a, b in zip(f_outs, d_outs):
        np.testing.assert_array_equal(a, b)
    params = factor_sess.engine.params
    assert decode_matmul_flops(params, factor_resident=True) < \
        decode_matmul_flops(params, factor_resident=False)
    assert factor_sess.engine.decode_flops_per_token() is not None
    assert dense_sess.engine.decode_flops_per_token() is None


def test_rank_sliced_load_equals_full_load(trained_spec):
    prompts = prompts_for(trained_spec, n=3, seed=3)
    full, _ = serve(trained_spec).generate(prompts)
    sliced_sess = serve(dataclasses.replace(
        trained_spec,
        serve=dataclasses.replace(trained_spec.serve, rank_slice=True),
    ))
    sliced, _ = sliced_sess.generate(prompts)
    for a, b in zip(full, sliced):
        np.testing.assert_array_equal(a, b)


def test_experiment_serve_inprocess(trained_spec):
    """Experiment.serve() serves the live params — same tokens as the
    checkpoint round-trip (the engine checkpoints every round here)."""
    exp = build(trained_spec)
    exp.resume()
    prompts = prompts_for(trained_spec, n=2, seed=4)
    live, _ = exp.serve().generate(prompts)
    ckpt, _ = serve(trained_spec).generate(prompts)
    for a, b in zip(live, ckpt):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# quantization
# ---------------------------------------------------------------------------


def _factor(rng, n, m, w, rank):
    u = rng.standard_normal((n, w)).astype(np.float32)
    v = rng.standard_normal((m, w)).astype(np.float32)
    s = rng.standard_normal((w, w)).astype(np.float32)
    mask = (np.arange(w) < rank).astype(np.float32)
    return LowRankFactor(
        U=jnp.asarray(u * mask), S=jnp.asarray(s * mask[:, None] * mask[None]),
        V=jnp.asarray(v * mask), rank=jnp.float32(rank),
    )


def test_quantization_error_bound():
    f = _factor(np.random.default_rng(0), 48, 40, 16, 11)
    qf = quantize_factor(f)
    bound = quantization_error_bound(qf)
    back = dequantize_factor(qf)
    assert float(jnp.max(jnp.abs(back.U - f.U))) <= bound + 1e-7
    assert float(jnp.max(jnp.abs(back.V - f.V))) <= bound + 1e-7
    np.testing.assert_array_equal(back.S, f.S)  # S rides through in f32
    # per-column scales: bound is the wire formula, scale/2
    assert bound <= float(
        (jnp.max(jnp.abs(f.U)) - jnp.min(f.U)) / 255.0
    ) * 260  # sanity: same order as range/255


def test_quantized_inactive_columns_exactly_zero():
    f = _factor(np.random.default_rng(1), 32, 32, 12, 5)
    back = dequantize_factor(quantize_factor(f))
    np.testing.assert_array_equal(np.asarray(back.U[:, 5:]), 0.0)
    np.testing.assert_array_equal(np.asarray(back.V[:, 5:]), 0.0)
    # materialization therefore unaffected by the inactive block
    w_full = materialize(f)
    w_back = materialize(back)
    assert float(jnp.max(jnp.abs(w_full - w_back))) < 1.0  # finite, no junk


def test_int8_shrinks_resident_bytes_and_serves(trained_spec):
    base = serve(trained_spec)
    q_sess = serve(dataclasses.replace(
        trained_spec,
        serve=dataclasses.replace(trained_spec.serve, quantize="int8"),
    ))
    assert resident_bytes(q_sess.engine.params) < \
        resident_bytes(base.engine.params)
    assert any(
        isinstance(x, QuantizedFactor)
        for x in jax.tree.leaves(
            q_sess.engine.params,
            is_leaf=lambda x: isinstance(x, QuantizedFactor),
        )
    )
    outs, _ = q_sess.generate(prompts_for(trained_spec, n=2, seed=5))
    for o in outs:
        assert o.dtype == np.int32 and len(o) == 5


def test_bf16_mode_serves(trained_spec):
    sess = serve(dataclasses.replace(
        trained_spec,
        serve=dataclasses.replace(trained_spec.serve, quantize="bf16"),
    ))
    factors = [
        x for x in jax.tree.leaves(sess.engine.params, is_leaf=is_factor)
        if is_factor(x)
    ]
    assert factors and all(f.U.dtype == jnp.bfloat16 for f in factors)
    outs, _ = sess.generate(prompts_for(trained_spec, n=2, seed=6))
    assert all(len(o) == 5 for o in outs)


def test_rank_slice_shrinks_buffers():
    params = {"w": _factor(np.random.default_rng(2), 64, 48, 32, 9)}
    sliced = rank_slice_params(params)
    assert sliced["w"].r_max == 16  # 9 → next multiple of 8
    np.testing.assert_array_equal(
        np.asarray(materialize(sliced["w"])),
        np.asarray(materialize(params["w"])),
    )
    assert resident_bytes(sliced) < resident_bytes(params)
    # quantize composes after slicing
    q = quantize_params(sliced, "int8")
    assert q["w"].r_max == 16


# ---------------------------------------------------------------------------
# scheduler behavior
# ---------------------------------------------------------------------------


def test_queue_overflow_raises(session):
    spec = tiny_spec(max_batch=2, max_queue=2)
    sess = serve(spec)
    sched = sess.scheduler
    p = np.arange(1, 5, dtype=np.int32)
    sched.submit(Request(rid=0, tokens=p))
    sched.submit(Request(rid=1, tokens=p))
    with pytest.raises(RuntimeError, match="queue full"):
        sched.submit(Request(rid=2, tokens=p))


def test_static_mode_admits_in_waves():
    spec = tiny_spec(mode="static", max_batch=2, max_new_tokens=4)
    sess = serve(spec)
    p = np.arange(1, 6, dtype=np.int32)
    comps = sess.run([Request(rid=i, tokens=p) for i in range(4)])
    admits = sorted(c.admit_step for c in comps)
    # two waves of two; second wave waits for the first to fully drain
    assert admits[0] == admits[1] and admits[2] == admits[3]
    assert admits[2] > admits[0]


def test_continuous_backfills_freed_slots():
    spec = tiny_spec(mode="continuous", max_batch=2, max_new_tokens=8)
    sess = serve(spec)
    p = np.arange(1, 6, dtype=np.int32)
    reqs = [
        Request(rid=0, tokens=p, max_new_tokens=2),
        Request(rid=1, tokens=p, max_new_tokens=8),
        Request(rid=2, tokens=p, max_new_tokens=2),
    ]
    comps = sess.run(reqs)
    by = {c.rid: c for c in comps}
    # rid 2 entered the slot rid 0 freed, while rid 1 was still decoding
    assert by[2].admit_step > by[0].admit_step
    assert by[2].admit_step <= by[1].finish_step
    assert [len(by[i].tokens) for i in range(3)] == [2, 8, 2]


def test_completion_phases_and_stats(session):
    comps = session.run([Request(
        rid=7, tokens=np.arange(1, 8, dtype=np.int32), arrival_step=0,
    )])
    c = comps[0]
    assert isinstance(c, Completion) and c.rid == 7 and c.prompt_len == 7
    assert c.queued_s >= 0 and c.prefill_s > 0 and c.decode_s > 0
    assert c.tokens_per_s > 0
    assert c.finish_step >= c.admit_step >= c.submit_step


def test_prompt_bucketing_is_transparent(session):
    """Prompt lengths sharing a bucket and lengths in different buckets
    all agree with the unpadded reference; executables stay bounded."""
    eng = session.engine
    for length in (3, 8, 9, 16):
        p = np.arange(1, length + 1, dtype=np.int32)
        [out], _ = session.generate([p])
        assert out.tolist() == ref_greedy(session, p, 6)
    assert set(eng._prefill_fns) == {8, 16}
    assert eng.num_executables() == 4  # 2 buckets + insert + step


def test_insert_consumes_its_state_and_the_step_does_not():
    """The insert writes the slot into its donated state in place; the
    decode step leaves its input state readable (the benchmark's planted
    fault of a step returning its input state relies on that).  Each
    compiles once, and reports its compiled memory."""
    from repro.api.spec import TelemetrySpec
    from repro.telemetry import MemorySink

    spec = dataclasses.replace(
        tiny_spec(), telemetry=TelemetrySpec(enabled=True, sinks="memory"),
    )
    eng = serve(spec).engine
    compiles = []

    def listen(event, duration, fun_name="", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(fun_name)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        state = eng.new_state()
        kv = [x for x in jax.tree.leaves(state["stack"]) if x.ndim == 5]
        kv_bytes = sum(x.nbytes for x in kv)
        prompt = np.arange(1, 6, dtype=np.int32)
        for slot in (1, 2):
            _, cache = eng.prefill(prompt)
            old, state = state, eng.insert(state, cache, slot, prompt.size)
            assert all(
                x.is_deleted() for x in jax.tree.leaves(old["stack"]) if x.ndim == 5
            )
        tokens = np.full(eng.max_batch, 7, np.int32)
        first, _ = eng.step(state, tokens)
        again, _ = eng.step(state, tokens)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    np.testing.assert_array_equal(np.asarray(first), np.asarray(again))
    # at most once: an executable compiled by an earlier engine is reused
    assert compiles.count("jit(_insert_cache)") <= 1
    assert compiles.count("jit(step)") <= 1
    [sink] = [s for s in eng.hub.sinks if isinstance(s, MemorySink)]
    got = {e["name"]: e["value"] for e in sink.events if e["kind"] == "gauge"}
    assert got["serve.insert.aliased_bytes"] >= kv_bytes > 0
    assert got["serve.decode_step.aliased_bytes"] == 0


def test_prompt_too_long_rejected(session):
    with pytest.raises(ValueError, match="exceeds max_prompt"):
        session.engine.prefill(np.arange(99, dtype=np.int32))
    with pytest.raises(ValueError, match="empty prompt"):
        session.engine.prefill(np.zeros(0, dtype=np.int32))


# ---------------------------------------------------------------------------
# spec path / construction seam
# ---------------------------------------------------------------------------


def test_serve_requires_lm():
    spec = ExperimentSpec(
        model=ModelSpec(kind="mlp"),
        fed=FedSpec(clients=2),
        data=DataSpec(kind="classification"),
    )
    with pytest.raises(ValueError, match="no decode path"):
        serve(spec)


def test_serve_rejects_encdec():
    spec = ExperimentSpec(
        model=ModelSpec(kind="lm", arch="whisper-large-v3", smoke=True),
    )
    with pytest.raises(ValueError, match="enc-dec"):
        serve(spec)


def test_serve_missing_checkpoint_dir(tmp_path):
    spec = tiny_spec(checkpoint=str(tmp_path))
    with pytest.raises(FileNotFoundError, match="round_"):
        serve(spec)


def test_session_describe(session):
    text = session.describe()
    assert "continuous" in text and "spec" in text


# ---------------------------------------------------------------------------
# telemetry threading
# ---------------------------------------------------------------------------


def test_serve_telemetry_spans_and_counters():
    from repro.api.spec import TelemetrySpec
    from repro.telemetry import get_hub

    spec = dataclasses.replace(
        tiny_spec(),
        telemetry=TelemetrySpec(enabled=True, sinks="memory"),
    )
    sess = serve(spec)
    sess.generate(prompts_for(spec, n=3, seed=7), arrival_steps=[0, 1, 2])
    [sink] = [s for s in get_hub().sinks if hasattr(s, "events")]
    kinds = {(e["kind"], e["name"]) for e in sink.events}
    assert ("span", "serve.prefill") in kinds
    assert ("span", "serve.queued") in kinds
    assert ("span", "serve.decode") in kinds
    assert ("counter", "serve.tokens") in kinds
    assert ("gauge", "serve.queue_depth") in kinds
    decode_spans = [
        e for e in sink.events
        if e["kind"] == "span" and e["name"] == "serve.decode"
    ]
    assert len(decode_spans) == 3
    assert all(e["dur"] >= 0 for e in decode_spans)


def _host_spans(tmp_path, fn, prefix="serve."):
    """Run ``fn`` under the profiler; its ``prefix`` host events as
    (name, start_ns, end_ns, stats), in order of start."""
    import glob

    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                    for e in line.events if e.name.startswith(prefix)
                )
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _parent(span, spans):
    """The innermost other span that holds ``span`` whole, or None."""
    _, s, e, _ = span
    holders = [p for p in spans if p is not span and p[1] <= s and e <= p[2]]
    return min(holders, key=lambda p: p[2] - p[1], default=None)


def test_serving_spans_form_the_step_tree_in_the_profiler_trace(session, tmp_path):
    """With telemetry off (the session's spec), the step's spans reach the
    profiler's trace with their attrs, each inside its parent."""
    spec = session.spec
    prompts = prompts_for(spec, n=4, seed=3)
    session.generate(prompts, arrival_steps=[0, 0, 2, 5])  # compiles
    spans = _host_spans(
        tmp_path, lambda: session.generate(prompts, arrival_steps=[0, 0, 2, 5])
    )
    steps = [x for x in spans if x[0] == "serve.step"]
    admits = [x for x in spans if x[0] == "serve.admit"]
    assert steps and len(admits) == len(prompts)
    kids = {id(p): [] for p in steps + admits}
    for x in spans:
        parent = _parent(x, spans)
        if x[0] == "serve.step":
            assert parent is None
        else:
            assert parent is not None, x
            kids[id(parent)].append(x)
    for st in steps:
        names = sorted(k[0] for k in kids[id(st)])
        decode = [k for k in names if k != "serve.admit"]
        assert decode == ["serve.decode_step", "serve.emit", "serve.sample"], names
        [sample] = [k for k in kids[id(st)] if k[0] == "serve.sample"]
        assert sample[3] == {"rows": spec.serve.max_batch}
        assert set(st[3]) == {"step", "active", "queued"}
    for ad in admits:
        rid = ad[3]["rid"]
        n = len(prompts[rid])
        assert ad[3] == {"rid": rid, "prompt_len": n,
                         "bucket": session.engine.bucket_len(n)}
        assert _parent(ad, spans)[0] == "serve.step"
        assert [k[0] for k in kids[id(ad)]] == [
            "serve.prefill", "serve.sample", "serve.insert"
        ]
        prefill, sample, _ = kids[id(ad)]
        assert prefill[3] == {"bucket": ad[3]["bucket"]} and sample[3] == {"rows": 1}
    assert sorted(a[3]["rid"] for a in admits) == list(range(len(prompts)))
    emitted = sum(k[3]["done"] for k in spans if k[0] == "serve.emit")
    assert emitted == len(prompts)


def test_scheduler_gauges_only_at_submit_and_step_attrs_carry_the_rest():
    from repro.api.spec import TelemetrySpec
    from repro.telemetry import MemorySink

    spec = dataclasses.replace(
        tiny_spec(), telemetry=TelemetrySpec(enabled=True, sinks="memory"),
    )
    sess = serve(spec)
    sess.generate(prompts_for(spec, n=3, seed=5), arrival_steps=[0, 0, 3])
    [sink] = [s for s in sess.hub.sinks if isinstance(s, MemorySink)]
    gauges = [e["name"] for e in sink.events if e["kind"] == "gauge"]
    # besides the scheduler's, the engine's two executables report their
    # compiled memory once each, at their first call
    memory = [f"serve.{x}.{k}" for x in ("insert", "decode_step")
              for k in ("aliased_bytes", "temp_bytes")]
    assert [g for g in gauges if g not in memory] == ["serve.queue_depth"] * 3
    assert sorted(g for g in gauges if g in memory) == sorted(memory)
    steps = [e for e in sink.events if e["kind"] == "span" and e["name"] == "serve.step"]
    assert [e["attrs"]["step"] for e in steps] == list(range(len(steps)))
    assert steps[0]["attrs"]["queued"] == 2 and steps[0]["attrs"]["active"] == 0


def test_prefill_s_covers_the_wait_for_the_first_token(session, monkeypatch):
    import time

    real = type(session.engine).sample

    def slow_first(self, logits, rids, steps):
        if len(rids) == 1:
            time.sleep(0.05)
        return real(self, logits, rids, steps)

    p = np.arange(1, 8, dtype=np.int32)
    session.generate([p])  # compiles
    monkeypatch.setattr(type(session.engine), "sample", slow_first)
    [c] = session.run([Request(rid=3, tokens=p)])
    assert c.prefill_s >= 0.05
    assert 0 < c.decode_s
