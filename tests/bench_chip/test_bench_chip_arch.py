"""A configuration names its architecture, and an architecture is one
file: a toy architecture written into a copy of the benchmark is found by
name by the weight generator, the counts, the reference, the program
check and the path map, with no other file changed.  And the dense
decoder's reference logits read, to the bit, what they read when the
serving driver named that reference itself."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_chip_tiny as tiny  # noqa: E402
from benchmarks.chip import harness  # noqa: E402

#: a two-position pattern whose parameters all lie at ``pos1``: a seeded
#: scale (not a constant), a router that stays dense, a factorized MLP
TOY = '''"""Toy architecture of the benchmark's tests."""
import jax
import jax.numpy as jnp

from benchmarks.chip import counts, layout
from benchmarks.chip.references import arith


def linears(cfg):
    d, f, V, L = (cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"],
                  cfg["num_hidden_layers"])
    return [layout.factorized(cfg, "embed", V, d), layout.factorized(cfg, "lm_head", d, V),
            layout.factorized(cfg, "blocks/pos1/up", d, f, L),
            layout.factorized(cfg, "blocks/pos1/down", f, d, L)]


def _scale(key, shape):
    return jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5)


def _router(key, shape):
    return jax.random.normal(key, shape, jnp.float32) / shape[-2] ** 0.5


def leaves(cfg):
    d, E, L = cfg["hidden_size"], cfg["num_experts"], cfg["num_hidden_layers"]
    return [("final_norm", (d,), 1.0), ("blocks/pos1/scale", (L, d), _scale),
            ("blocks/pos1/router", (L, d, E), _router)]


def program_fields(cfg):
    return {"family": "toy", "block_pattern": ("mix", "mlp"),
            "d_model": cfg["hidden_size"], "num_layers": cfg["num_hidden_layers"]}


def program_path(names):
    return "/".join(names)


def _linear(w, path, x, mode):
    h = arith.einsum("...i,ir->...r", x, w[path + "/U"], mode)
    h = arith.einsum("...r,rs->...s", h, w[path + "/S"], mode)
    return arith.einsum("...s,os->...o", h, w[path + "/V"], mode)


def logits(cfg, w, inputs, mode="float32"):
    u = jnp.take(w["embed/U"], inputs, axis=0)
    x = arith.einsum("btr,rs->bts", u, w["embed/S"], mode)
    x = arith.einsum("bts,ds->btd", x, w["embed/V"], mode)
    blocks = {k[len("blocks/pos1/"):]: v for k, v in w.items() if k.startswith("blocks/pos1/")}

    def step(x, p):
        h = x * p["scale"]
        gate = jax.nn.sigmoid(arith.einsum("btd,de->bte", h, p["router"], mode))
        y = _linear(p, "down", jax.nn.silu(_linear(p, "up", h, mode)), mode)
        return x + gate.mean(-1, keepdims=True) * y, None

    x, _ = jax.lax.scan(step, x, blocks)
    return _linear(w, "lm_head", x * w["final_norm"], mode)


def decode_step_cost(cfg, contexts, itemsize=2):
    B = len(contexts)
    flops = nbytes = 0
    for lin in linears(cfg):
        f, b = (counts.embed_cost if lin.path == "embed" else counts.factor_cost)(
            lin, B, itemsize)
        flops, nbytes = flops + f, nbytes + b
    d, E, L = cfg["hidden_size"], cfg["num_experts"], cfg["num_hidden_layers"]
    return flops + L * B * 2 * d * E, nbytes + L * itemsize * d * E
'''

TOY_CONFIG = {
    "registry": "qwen2_7b", "smoke": True, "architecture": "toy_lm",
    "hidden_size": 64, "intermediate_size": 128, "num_experts": 4,
    "num_hidden_layers": 2, "vocab_size": 96,
    "lowrank": {"rank_frac": 0.25, "r_cap": 256, "min_dim": 32},
    "compute_dtype": "float32", "param_dtype": "float32", "basis_dtype": "float32",
    "kernels": "off",
}


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    root, manifest = tiny.make_tree(tmp_path_factory.mktemp("bench"))
    (root / "references" / "toy_lm.py").write_text(TOY)
    return root, manifest


def test_the_toy_is_one_added_file(toy):
    root, _ = toy
    code = {p.relative_to(root) for p in root.rglob("*.py") if "__pycache__" not in p.parts}
    real = {p.relative_to(tiny.CHIP) for p in tiny.CHIP.rglob("*.py")
            if "__pycache__" not in p.parts}
    assert code - real == {Path("references/toy_lm.py")} and real <= code
    for rel in real:
        assert (root / rel).read_bytes() == (tiny.CHIP / rel).read_bytes(), rel


def test_the_weight_generator_finds_the_toy(toy):
    from benchmarks.chip import weights

    root, _ = toy
    a = weights.generate(TOY_CONFIG, 2**31 + 3, root)
    factors = {f"{p}/{x}" for p in ("embed", "lm_head", "blocks/pos1/up", "blocks/pos1/down")
               for x in ("U", "S", "V", "rank")}
    assert set(a) == factors | {"final_norm", "blocks/pos1/scale", "blocks/pos1/router"}
    assert a["blocks/pos1/scale"].shape == (2, 64)
    assert a["blocks/pos1/router"].shape == (2, 64, 4)
    assert a["blocks/pos1/up/U"].shape == (2, 64, 16)
    scale = np.asarray(a["blocks/pos1/scale"])
    assert np.unique(scale).size > 100 and 0.5 <= scale.min() and scale.max() <= 1.5
    assert np.array_equal(scale, weights.generate(TOY_CONFIG, 2**31 + 3, root)["blocks/pos1/scale"])
    assert not np.array_equal(scale, weights.generate(TOY_CONFIG, 4, root)["blocks/pos1/scale"])
    assert np.all(np.asarray(a["final_norm"]) == 1.0)
    assert np.unique(np.asarray(a["blocks/pos1/router"])).size > 100


def test_the_counts_find_the_toy(toy):
    from benchmarks.chip import counts

    root, _ = toy
    # every rank is 16 (a quarter of 64); the router is d x E, read once
    B, d, f, V, E, L = 2, 64, 128, 96, 4, 2
    lin = lambda K, N, R: (B * 2 * (K * R + R * R + R * N), 2 * (K * R + R * R + N * R))
    want_f = lin(d, V, 16)[0] + L * (lin(d, f, 16)[0] + lin(f, d, 16)[0])
    want_b = lin(d, V, 16)[1] + L * (lin(d, f, 16)[1] + lin(f, d, 16)[1])
    want_f += B * 2 * (16 * 16 + 16 * d) + L * B * 2 * d * E
    want_b += 2 * (B * 16 + 16 * 16 + d * 16) + L * 2 * d * E
    assert counts.decode_step_cost(TOY_CONFIG, [3, 5], root=root) == (want_f, want_b)


def test_the_reference_finds_the_toy(toy):
    root, manifest = toy
    from benchmarks.chip import weights

    w = weights.generate(TOY_CONFIG, 11, root)
    x = np.arange(2 * 7, dtype=np.int32).reshape(2, 7) % 96
    lg = harness.architecture(TOY_CONFIG, root).logits(TOY_CONFIG, w, x)
    assert lg.shape == (2, 7, 96) and np.all(np.isfinite(lg))
    # and through the serving driver's comparison
    cell = harness.Cell("toy", {}, TOY_CONFIG, {"max_prompt": 8, "max_new_tokens": 4},
                        [], [], root)
    kind = harness.Bench(root, manifest).kind("serve_open_loop")
    seqs = [(np.arange(5, dtype=np.int32), np.array([1, 2, 3])),
            (np.arange(8, dtype=np.int32), np.array([4]))]
    gaps = kind.logit_gaps(cell, 11, seqs)
    assert [g.shape for g in gaps] == [(3,), (1,)] and all(np.all(g >= 0) for g in gaps)


def test_the_program_check_refuses_the_toy_against_the_registry(toy):
    from repro.api import ModelSpec
    from repro.api.tasks import lm_model_config

    from benchmarks.chip import program

    root, _ = toy
    mc = lm_model_config(ModelSpec(arch="qwen2_7b", smoke=True, kernels="off"))
    with pytest.raises(ValueError, match="family") as err:
        program.check_config(TOY_CONFIG, mc, root)
    assert "block_pattern" in str(err.value) and "d_model" in str(err.value)


def test_the_toy_maps_the_program_paths_at_pos1(toy):
    import jax
    import jax.numpy as jnp

    from benchmarks.chip import program, weights

    root, _ = toy
    flat = weights.generate(TOY_CONFIG, 5, root)
    like = {"blocks": {"pos1": {"scale": jax.ShapeDtypeStruct((2, 64), jnp.float32),
                                "router": jax.ShapeDtypeStruct((2, 64, 4), jnp.float32)}},
            "final_norm": jax.ShapeDtypeStruct((64,), jnp.float32)}
    got = program.program_params(flat, like, TOY_CONFIG, root)
    assert got["blocks"]["pos1"]["router"] is flat["blocks/pos1/router"]
    with pytest.raises(ValueError, match="blocks/pos1/scale"):
        program.program_params(flat, {"blocks": {"pos1": {"scale": jax.ShapeDtypeStruct(
            (2, 64), jnp.bfloat16)}}}, TOY_CONFIG, root)


#: ``tiny.digest`` of the tiny serve cell's gaps ({"000": gaps of the first
#: sequence, ...}) at seed 2**31 + 41, with the reference in each mode, as
#: the serving driver read them when it named the dense reference itself
TINY_GAPS_SHA256 = {
    "float32": "f91d5763f080a2c971fe6fb9ea9db00c302c95431cde22899b836a01e250cf67",
    "bfloat16": "26faf952d00f72d147c2947b19eae55847875f5f1e61c714ee529411893154e8",
}


@pytest.mark.parametrize("mode", sorted(TINY_GAPS_SHA256))
def test_the_tiny_cell_logit_gaps_are_pinned_bit_for_bit(toy, mode):
    root, manifest = toy
    bench = harness.Bench(root, manifest)
    cell = bench.cell(tiny.SERVE_CELL)
    rng = np.random.default_rng(41)
    seqs = [(rng.integers(0, 512, n).astype(np.int32), rng.integers(0, 512, k))
            for n, k in ((40, 16), (5, 3), (64, 16))]
    gaps = bench.kind("serve_open_loop").logit_gaps(cell, 2**31 + 41, seqs, mode=mode)
    assert tiny.digest({f"{i:03d}": g for i, g in enumerate(gaps)}) == TINY_GAPS_SHA256[mode]


def test_the_chat_configuration_names_an_architecture_file():
    cfg = json.loads((tiny.CHIP / "configs" / "qwen2-7b.json").read_text())
    arch = harness.architecture(cfg)
    assert Path(arch.__file__) == tiny.CHIP / "references" / "dense_lm.py"
    with pytest.raises(FileNotFoundError, match="no_such_arch"):
        harness.architecture(dict(cfg, architecture="no_such_arch"))
