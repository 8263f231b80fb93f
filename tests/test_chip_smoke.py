"""``chip_smoke.py`` off the chip: its phases at reduced shapes, and its
refusal to report success without a TPU.

The script runs Qwen2-7B at published widths and only on a TPU.  Its
train and serve phases are functions of an ``ExperimentSpec``, so here
they run on the CPU at the ``reduced()`` shapes ``model.smoke`` selects,
with the kernel path through the Pallas interpreter.
"""
import dataclasses
import importlib.util
import os
import subprocess
import sys

ROOT = os.path.join(os.path.dirname(__file__), "..")
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_phases_at_reduced_shapes():
    cs = _load()
    spec = cs.smoke_spec()
    spec = spec.replace(
        model=dataclasses.replace(spec.model, smoke=True, kernels="interpret")
    )
    exp, _ = cs.train_phase(spec)
    assert len(exp.history) == cs.ROUNDS
    assert cs.dispatch_resolutions(exp.hub) == {"interpret"}
    for sess in cs.serve_phase(exp):
        assert cs.dispatch_resolutions(sess.hub) == {"interpret"}


def test_chip_smoke_tokens_cover_the_run():
    cs = _load()
    spec = cs.smoke_spec()
    d, C = spec.data, spec.fed.clients
    n = C * d.tokens_per_client
    windows = -(-(n - d.seq) // (d.seq // 2))  # the lm task's window count
    assert windows // C >= spec.rounds * d.batch


def test_chip_smoke_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, SCRIPT], capture_output=True, text=True, env=env,
        cwd=ROOT, timeout=300,
    )
    assert p.returncode != 0
    assert "'cpu'" in p.stderr
    assert '"ok"' not in p.stdout
