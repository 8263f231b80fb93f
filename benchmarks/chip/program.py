"""What the benchmark takes from the program: its model configuration,
checked against the configuration file, and its parameter pytree, into
which the benchmark's own seeded weights are placed."""
from __future__ import annotations

from benchmarks.chip import layout


def check_config(cfg: dict, mc) -> None:
    """The program runs what the configuration file states, or refuses."""
    want = {
        "d_model": cfg["hidden_size"], "num_layers": cfg["num_hidden_layers"],
        "num_heads": cfg["num_attention_heads"],
        "num_kv_heads": cfg["num_key_value_heads"], "hd": layout.head_dim(cfg),
        "d_ff": cfg["intermediate_size"], "vocab_size": cfg["vocab_size"],
        "qkv_bias": cfg["qkv_bias"], "rope_theta": cfg["rope_theta"],
        "norm_eps": cfg["rms_norm_eps"], "tie_embeddings": cfg["tie_word_embeddings"],
        "compute_dtype": cfg["compute_dtype"], "param_dtype": cfg["param_dtype"],
        "kernels": cfg["kernels"], "family": "dense", "block_pattern": ("attn",),
        "gated_mlp": True, "qk_norm": False, "sliding_window": 0, "moe": None,
    }
    pol = cfg["lowrank"]
    got = {k: getattr(mc, k) for k in want}
    want.update({f"lowrank.{k}": v for k, v in pol.items()})
    got.update({f"lowrank.{k}": getattr(mc.lowrank, k) for k in pol})
    want.update({"lowrank.init_rank_frac": 1.0, "lowrank.factorize_embed": True,
                 "lowrank.factorize_head": True})
    got.update({k: getattr(mc.lowrank, k.split(".")[1]) for k in
                ("lowrank.init_rank_frac", "lowrank.factorize_embed", "lowrank.factorize_head")})
    bad = {k: (got[k], v) for k, v in want.items() if got[k] != v}
    if bad:
        raise ValueError(f"the program's {mc.name} differs from the configuration "
                         f"file (program, file): {bad}")


def _path(keys) -> str:
    names = [getattr(k, "key", getattr(k, "name", str(k))) for k in keys]
    return "/".join(n for n in names if n != "pos0")


def program_params(flat: dict, like):
    """``flat`` (``{layout path: array}``) in the program's pytree, checked
    leaf by leaf against ``like``.  The configuration file states the
    storage types as the program runs them, so a leaf stored otherwise is
    refused, not cast."""
    import jax

    from repro.core.factorization import LowRankFactor, is_factor

    def one(keys, ref):
        p = _path(keys)
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
