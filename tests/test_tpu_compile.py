"""Compile rehearsals of the main-path Pallas kernels for a described TPU v5e.

Nothing runs: each case compiles one kernel program at Qwen2-7B's widths
(q/o, k/v, down-proj, lm-head, embedding) at rank 256 and the augmented
512, in bf16, against a ``v5e:2x2`` topology that is described, not
attached.  The TPU compiler refuses here what interpret mode cannot see:
tiles not aligned to the hardware, more fast memory than a kernel may use,
operand layouts Mosaic does not lower.

The topology is described inside a fixture (never while a module is
imported), and every test that needs it lives in this one file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.kernels.ops as ops
from repro.configs import get_config
from repro.kernels.coeff_grad import atb
from repro.kernels.flash_attention import flash_attention
from repro.kernels.lowrank_matmul import avt, xus

BF16 = jnp.bfloat16
QWEN = get_config("qwen2-7b")
D, FF, VOCAB = QWEN.d_model, QWEN.d_ff, QWEN.vocab_size
KV = QWEN.num_kv_heads * QWEN.hd
M = 512  # batch 4 × seq 128, one client's tokens per step

# (label, K, N): the factorized matmuls of a Qwen2-7B layer stack
SHAPES = [
    ("q_o", D, D),
    ("k_v", D, KV),
    ("down", FF, D),
    ("lm_head", D, VOCAB),
    ("embed", None, D),  # gathered U rows: K is the rank itself
]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache without the chip: keep the cache out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(one_chip, *shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compiled_text(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("R", [256, 512])
@pytest.mark.parametrize("K", [D, FF])
def test_xus_compiles(one_chip, K, R):
    _compiled_text(
        lambda x, U, S: xus(x, U, S),
        _sds(one_chip, M, K), _sds(one_chip, K, R),
        _sds(one_chip, R, R, dtype=jnp.float32),
    )


@pytest.mark.parametrize("R", [256, 512])
@pytest.mark.parametrize("N", [D, VOCAB])
def test_avt_compiles(one_chip, N, R):
    _compiled_text(
        lambda A, V: avt(A, V), _sds(one_chip, M, R), _sds(one_chip, N, R)
    )


@pytest.mark.parametrize("R", [256, 512])
@pytest.mark.parametrize("Ka", [D, FF, VOCAB])
def test_atb_compiles(one_chip, Ka, R):
    """The transposed-LHS contraction: dU (Ka = n_in) and dV (Ka = n_out)."""
    _compiled_text(
        lambda A, B: atb(A, B), _sds(one_chip, M, Ka), _sds(one_chip, M, R)
    )


def _factor_args(one_chip, label, K, N, R):
    if label == "embed":
        K = R
    elif label == "k_v":
        R //= 4  # r_max 64 (augmented 128) on the 512-wide k/v projections
    return (
        _sds(one_chip, M, K), _sds(one_chip, K, R),
        _sds(one_chip, R, R), _sds(one_chip, N, R),
    )


@pytest.mark.parametrize("R", [256, 512])
@pytest.mark.parametrize("label,K,N", SHAPES, ids=[s[0] for s in SHAPES])
def test_lowrank_apply_forward_compiles(one_chip, monkeypatch, label, K, N, R):
    """``kernels="auto"`` resolves to the compiled chain on a TPU."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    use = ops.use_kernels_for("auto")
    assert use is True
    text = _compiled_text(
        lambda x, U, S, V: ops.lowrank_apply(x, U, S, V, use),
        *_factor_args(one_chip, label, K, N, R),
    )
    assert text.count("tpu_custom_call") >= 2  # xus then avt


@pytest.mark.parametrize("R", [256, 512])
@pytest.mark.parametrize("label,K,N", SHAPES, ids=[s[0] for s in SHAPES])
def test_lowrank_apply_vjp_compiles(one_chip, monkeypatch, label, K, N, R):
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    use = ops.use_kernels_for("auto")

    def fwd_bwd(x, U, S, V):
        def loss(*a):
            return jnp.sum(ops.lowrank_apply(*a, use).astype(jnp.float32))

        return jax.grad(loss, argnums=(0, 1, 2, 3))(x, U, S, V)

    text = _compiled_text(fwd_bwd, *_factor_args(one_chip, label, K, N, R))
    # forward xus/avt plus the backward's xus ×4, avt and atb ×3
    assert text.count("tpu_custom_call") >= 8


def _flash(q, k, v):
    pos = jnp.arange(q.shape[1])
    return flash_attention(q, k, v, q_positions=pos, kv_positions=pos)


# (kernel, function, operands as (shape, dtype)): flash attention lowers
# for the TPU only at one (batch, head) row, its position blocks being
# (1, bq)
NAMED = [
    ("xus", xus, [((M, D), BF16), ((D, 256), BF16), ((256, 256), jnp.float32)]),
    ("avt", avt, [((M, 256), BF16), ((D, 256), BF16)]),
    ("atb", atb, [((M, D), BF16), ((M, 256), BF16)]),
    ("flash_attention", _flash, [((1, 256, 1, 128), BF16)] * 3),
]


@pytest.mark.parametrize("name,fn,operands", NAMED, ids=[k[0] for k in NAMED])
def test_kernel_name_is_in_the_compiled_hlo(one_chip, name, fn, operands):
    """Each ``pallas_call`` names its kernel, so that the compiled program
    (and a profiler trace of it) calls it by name."""
    args = [_sds(one_chip, *shape, dtype=dt) for shape, dt in operands]
    # under a lambda, so that the jitted function's own name is not the op's
    text = _compiled_text(lambda *a: fn(*a), *args)
    assert re.search(rf"%{name}(\.\d+)? = .*tpu_custom_call", text), name


@pytest.mark.parametrize("S", [2048, 4096])
def test_decode_step_writes_the_cache_in_place(one_chip, monkeypatch, S):
    """The serving engine's decode step at the chat cell's shapes (Qwen2-7B,
    32 slots of 2048 positions; and of 4096, read in two blocks a slot):
    each layer's keys and values are read by
    the decode-attention kernel where they lie in the head-major stack,
    and written in place.  The one copy of the cache left is the step's
    input into its output, the input not being donated."""
    from repro.api import ModelSpec
    from repro.api.tasks import lm_model_config
    from repro.models import build_model
    from repro.serve.quantize import dequantize_params

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    model = build_model(lm_model_config(ModelSpec(arch="qwen2_7b", kernels="auto")))
    B = 32
    place = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    params = jax.tree.map(place, jax.eval_shape(
        lambda k: model.init(k)[0], jax.random.PRNGKey(0)))
    state = jax.tree.map(place, jax.eval_shape(
        lambda: model.init_cache(None, B, S, per_slot=True)))
    step = lambda p, st, t: model.serve_step(dequantize_params(p), st, t)
    text = jax.jit(step).lower(
        params, state, _sds(one_chip, B, 1, dtype=jnp.int32)
    ).compile().as_text()
    assert re.search(r"%decode_attention(\.\d+)? = .*tpu_custom_call", text)
    NB, Hkv, hd = QWEN.num_layers, QWEN.num_kv_heads, QWEN.hd
    stack = re.escape(f"bf16[{NB},{B},{Hkv},{S},{hd}]")
    # no slice of a layer is made, in either order of its axes
    for layer in (f"{B},{Hkv},{S},{hd}]", f"{B},{S},{Hkv},{hd}]"):
        assert f"bf16[{layer}" not in text and f"bf16[1,{layer}" not in text
    made = re.findall(rf"= {stack}\{{([^}}]*)\}} ([a-z-]+)\(", text)
    # the stacks keep the head-major layout the kernel reads (a relayout
    # would be a copy of the whole stack in every layer)...
    assert made and all(layout.startswith("4,3,2,1,0:") for layout, _ in made)
    ops_ = sorted(op for _, op in made)
    # ...are made only by the in-place scatter of each layer's rows (in
    # its fusion), and copied once each: the step's input into its output
    assert ops_.count("copy") == 2  # keys and values
    assert set(ops_) <= {"parameter", "get-tuple-element", "copy", "fusion", "scatter"}
    assert ops_.count("fusion") == ops_.count("scatter") == 2


@pytest.mark.parametrize("Hkv,G,hd,S", [
    (4, 7, 128, 2048),  # Qwen2-7B: one block a slot
    (8, 8, 128, 4096),  # 64 query heads on 8 kv heads: 1024-position blocks
    (8, 4, 64, 2048),  # half-width heads
    (32, 1, 128, 1024),  # no grouping
])
def test_decode_attention_compiles(one_chip, Hkv, G, hd, S):
    from repro.kernels.decode_attention import block_len, decode_attention

    NB, B = 4, 16
    assert block_len(S, Hkv, hd, 2) > 0
    _compiled_text(
        lambda q, m, k, v, l: decode_attention(q, m, k, v, l),
        _sds(one_chip, B, Hkv * G, hd), _sds(one_chip, B, S, dtype=jnp.bool_),
        _sds(one_chip, NB, B, Hkv, S, hd), _sds(one_chip, NB, B, Hkv, S, hd),
        _sds(one_chip, dtype=jnp.int32),
    )
