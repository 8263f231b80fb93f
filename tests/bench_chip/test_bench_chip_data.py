"""The benchmark is driven by data: its manifest, files and generators."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_chip_tiny as tiny  # noqa: E402
from benchmarks.chip import harness  # noqa: E402

MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
CHIP = REPO / "benchmarks" / "chip"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_manifest_keys_and_names():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"} and len(c["why"]) <= 200
    for p in MANIFEST["paths"]:
        assert (REPO / p).is_dir() and not p.startswith("/") and ".." not in p
    assert 1 <= MANIFEST["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in MANIFEST[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names), names
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in MANIFEST["end_to_end"])


def test_every_workload_names_files_that_exist():
    bench = harness.Bench()
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    for w in MANIFEST["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        cell = bench.cell(w["name"])
        assert (cell.workload["config"], cell.workload["traffic"]) == (w["config"], w["traffic"])
        assert (REPO / configs[w["config"]]["file"]).is_file()
        assert cell.config["source"] == configs[w["config"]]["source"]
        assert cell.config["reduced"] == configs[w["config"]]["reduced"]
        assert (CHIP / "traffic" / f"{cell.traffic['kind']}.py").is_file()
        assert (CHIP / "references" / f"{cell.config['architecture']}.py").is_file()
        assert set(cell.workload["limits"]) == {"logit_gap"}
        # a mix names the public source its parameters come from
        assert cell.traffic["source"] and cell.traffic["assumed"]


def test_every_per_layer_metric_has_a_reader_and_its_cells_report_what_it_moves():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    cells = [w["name"] for w in MANIFEST["workloads"]]
    for m in MANIFEST["per_layer"]:
        assert (CHIP / "metrics" / f"{m['name']}.py").is_file(), m["name"]
        assert hasattr(harness.Bench().reader(m["name"]), "read")
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert "workloads" not in moved or cell in moved["workloads"]
    for cell in cells:
        c = harness.Bench().cell(cell)
        names = {m["name"] for m in c.end_to_end}
        assert "setup_s" in names and len(names) >= 2 and c.per_layer


def _schedule(seed, seconds=20.0):
    kind = harness.Bench().kind("serve_open_loop")
    mix = json.loads((CHIP / "traffic" / "serve.chat.json").read_text())
    return kind.schedule(mix, 152064, seed, seconds)


def test_generator_is_reproducible_from_the_seed():
    seed = 2**31 + 12345
    a, b = _schedule(seed), _schedule(seed)
    assert len(a) == len(b) > 20
    for (ta, pa, ka), (tb, pb, kb) in zip(a, b):
        assert ta == tb and ka == kb and np.array_equal(pa, pb)
    assert all(p.dtype == np.int32 and p.min() >= 0 and p.max() < 152064 for _, p, _ in a)


def test_every_seed_serves_the_same_work_with_its_own_tokens():
    a, b = _schedule(2**31 + 1), _schedule(7)
    assert [(t, len(p), k) for t, p, k in a] == [(t, len(p), k) for t, p, k in b]
    assert not np.array_equal(np.concatenate([p for _, p, _ in a]),
                              np.concatenate([p for _, p, _ in b]))
    times = [t for t, _, _ in a]
    assert times == sorted(times) and times[0] == 0.0 and times[-1] < 20.0


def test_lengths_have_the_mix_means_and_bounds():
    kind = harness.Bench().kind("serve_open_loop")
    rng = np.random.default_rng(0)
    x = kind._lognormal(rng, 214.5, 1.0, 1, 10**9, 200_000)
    assert np.mean(x) == pytest.approx(214.5, rel=0.02)
    assert np.median(x) == pytest.approx(214.5 * np.exp(-0.5), rel=0.02)
    mix = json.loads((CHIP / "traffic" / "serve.chat.json").read_text())
    plan = _schedule(3, seconds=200.0)
    prompts = [len(p) for _, p, _ in plan]
    outputs = [k for _, _, k in plan]
    assert mix["prompt_min"] <= min(prompts) and max(prompts) <= mix["max_prompt"]
    assert mix["output_min"] <= min(outputs) and max(outputs) <= mix["max_new_tokens"]


def test_generator_and_weights_do_not_need_the_program():
    code = (
        "import sys\n"
        "class Block:\n"
        "    def find_spec(self, name, *a):\n"
        "        if name == 'repro' or name.startswith('repro.'):\n"
        "            raise ImportError('the program may not be imported')\n"
        "sys.meta_path.insert(0, Block())\n"
        "from benchmarks.chip import harness, weights\n"
        "import json\n"
        "kind = harness.Bench().kind('serve_open_loop')\n"
        "mix = json.load(open('benchmarks/chip/traffic/serve.chat.json'))\n"
        "print(sum(len(p) for _, p, _ in kind.schedule(mix, 1000, 5, 10.0)))\n"
        "print(weights.seed_key(2**31 + 5))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]


def test_the_program_must_run_what_the_configuration_file_states():
    from repro.api import ModelSpec
    from repro.api.tasks import lm_model_config

    from benchmarks.chip import program

    cfg = json.loads((CHIP / "configs" / "qwen2-7b.json").read_text())
    mc = lm_model_config(ModelSpec(arch=cfg["registry"], kernels=cfg["kernels"]))
    program.check_config(cfg, mc)
    with pytest.raises(ValueError, match="num_kv_heads"):
        program.check_config(dict(cfg, num_key_value_heads=28), mc)
    with pytest.raises(ValueError, match="norm_eps"):
        program.check_config(dict(cfg, rms_norm_eps=1e-5), mc)
    looked_for = CHIP / "references" / "no_such_arch.py"
    with pytest.raises(FileNotFoundError, match=re.escape(str(looked_for))):
        program.check_config(dict(cfg, architecture="no_such_arch"), mc)


def test_weights_are_reproducible_and_orthonormal():
    import jax.numpy as jnp

    from benchmarks.chip import weights

    a = weights.generate(tiny.CONFIG, 2**31 + 7)
    b = weights.generate(tiny.CONFIG, 2**31 + 7)
    c = weights.generate(tiny.CONFIG, 8)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["embed/U"], c["embed/U"])
    U = a["blocks/mlp/down/U"]
    gram = jnp.einsum("lni,lnj->lij", U, U)
    assert np.allclose(gram, np.eye(U.shape[-1]), atol=1e-5)


#: ``tiny.digest`` of the tiny configuration's weights at seed 2**31 + 7,
#: as the generator made them
#: when it named the dense decoder's leaves itself
TINY_WEIGHTS_SHA256 = "973d09fcc2892fe5f9c9b5933598af3a6d0ce3405155c2c3abaffed5424d3613"


def test_weights_of_the_tiny_configuration_are_pinned_bit_for_bit():
    from benchmarks.chip import weights

    assert tiny.digest(weights.generate(tiny.CONFIG, 2**31 + 7)) == TINY_WEIGHTS_SHA256


def _run_cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         MANIFEST["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_the_command_exits_nonzero_without_a_tpu():
    out = _run_cli(REPO)
    assert out.returncode == 2, out.stderr[-2000:]
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_the_command_exits_nonzero_with_only_the_benchmark(tmp_path):
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"} and len(c["why"]) <= 200
    for p in MANIFEST["paths"]:
        shutil.copytree(REPO / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    out = _run_cli(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_a_new_cell_is_found_by_adding_files_only(tmp_path):
    root, manifest = tiny.make_tree(tmp_path)
    bench = harness.Bench(root, manifest)
    serve = bench.cell(tiny.SERVE_CELL)
    assert serve.config["hidden_size"] == 256
    assert serve.traffic["max_batch"] == tiny.SERVE_MIX["max_batch"]
    assert {m["name"] for m in serve.end_to_end} == {"itl_p95_ms", "setup_s"}
    assert {m["name"] for m in serve.per_layer} == {"device_idle_share.serve", "serve_decode_mfu"}
    # code files of the copy are the real ones, byte for byte
    for f in ("run.py", "harness.py", "traffic/serve_open_loop.py"):
        assert (root / f).read_bytes() == (CHIP / f).read_bytes()
    with pytest.raises(FileNotFoundError):
        bench.cell("no-such-cell")
