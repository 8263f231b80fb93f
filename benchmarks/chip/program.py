"""What the benchmark takes from the program: its model configuration,
checked against the configuration file, and its parameter pytree, into
which the benchmark's own seeded weights are placed.  What is particular
to an architecture (its fields, its paths) comes from the configuration's
architecture file (``harness.architecture``)."""
from __future__ import annotations

from pathlib import Path

from benchmarks.chip import harness

_MISSING = "<no such field>"


def _field(obj, dotted: str):
    for name in dotted.split("."):
        obj = getattr(obj, name, _MISSING)
    return obj


def check_config(cfg: dict, mc, root: Path = harness.ROOT) -> None:
    """The program runs what the configuration file states, or refuses:
    the architecture's fields, the storage and compute types, the kernel
    policy and FeDLRT's low-rank policy at full initial rank, with the
    embedding and the head factorized."""
    want = dict(harness.architecture(cfg, root).program_fields(cfg))
    want.update({"compute_dtype": cfg["compute_dtype"], "param_dtype": cfg["param_dtype"],
                 "kernels": cfg["kernels"]})
    want.update({f"lowrank.{k}": v for k, v in cfg["lowrank"].items()})
    want.update({"lowrank.init_rank_frac": 1.0, "lowrank.factorize_embed": True,
                 "lowrank.factorize_head": True})
    got = {k: _field(mc, k) for k in want}
    bad = {k: (got[k], v) for k, v in want.items() if got[k] != v}
    if bad:
        raise ValueError(f"the program's {mc.name} differs from the configuration "
                         f"file (program, file): {bad}")


def program_params(flat: dict, like, cfg: dict, root: Path = harness.ROOT):
    """``flat`` (``{benchmark path: array}``) in the program's pytree,
    checked leaf by leaf against ``like``; ``cfg``'s architecture file
    maps each program leaf to its benchmark path.  The configuration file
    states the storage types as the program runs them, so a leaf stored
    otherwise is refused, not cast."""
    import jax

    from repro.core.factorization import LowRankFactor, is_factor

    to_path = harness.architecture(cfg, root).program_path

    def one(keys, ref):
        p = to_path(tuple(getattr(k, "key", getattr(k, "name", str(k))) for k in keys))
        if is_factor(ref):
            parts = {x: flat[f"{p}/{x}"] for x in ("U", "S", "V", "rank")}
            for x, a in parts.items():
                r = getattr(ref, x)
                if a.shape != r.shape or a.dtype != r.dtype:
                    raise ValueError(f"{p}/{x}: benchmark {a.shape} {a.dtype}, "
                                     f"program {r.shape} {r.dtype}")
            return LowRankFactor(**parts)
        a = flat[p]
        if a.shape != ref.shape or a.dtype != ref.dtype:
            raise ValueError(f"{p}: benchmark {a.shape} {a.dtype}, program {ref.shape} {ref.dtype}")
        return a

    return jax.tree_util.tree_map_with_path(one, like, is_leaf=is_factor)
