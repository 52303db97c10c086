"""Random weights from ``--seed``, made on the device in one jitted call,
in the dtype they are served in.

The tree has the layout the serving engine takes (``embed``, the
layer-stacked ``groups``, ``final_norm``, ``head``); ``reference.py``
reads the same tree.  Norm parameters follow that layout's convention:
the gain is ``1 + scale``.  Values are drawn from the benchmark's own
generator, so the reference depends on nothing the program made.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import numpy as np

#: spread of the norm gains and biases around 1 and 0: large enough that a
#: reference which dropped them would fail, small enough to stay a norm
NORM_STD = 0.1


def seed32(seed: int) -> int:
    """A 32-bit key derived from any whole number (run seeds may
    exceed 32 signed bits)."""
    return int(np.random.default_rng(seed).integers(0, 2**31 - 1))


def leaf_specs(m: dict) -> List[Tuple[Tuple[str, ...], tuple, float]]:
    """(path, shape, standard deviation) of every weight.  Dense weights
    are N(0, 1/fan_in); the embedding is N(0, 1)."""
    L, d, h, kh, hd, f, v = (m["n_layers"], m["d_model"], m["n_heads"],
                             m["n_kv_heads"], m["head_dim"], m["d_ff"],
                             m["vocab_size"])
    blk = ("groups", "b0_attn")
    specs = [
        (("embed", "table"), (v, d), 1.0),
        (blk + ("norm_mix", "scale"), (L, d), NORM_STD),
        (blk + ("norm_mix", "bias"), (L, d), NORM_STD),
        (blk + ("mix", "wq"), (L, d, h * hd), d ** -0.5),
        (blk + ("mix", "wk"), (L, d, kh * hd), d ** -0.5),
        (blk + ("mix", "wv"), (L, d, kh * hd), d ** -0.5),
        (blk + ("mix", "wo"), (L, h * hd, d), (h * hd) ** -0.5),
        (blk + ("norm_mlp", "scale"), (L, d), NORM_STD),
        (blk + ("norm_mlp", "bias"), (L, d), NORM_STD),
    ]
    if m["mlp"] == "swiglu":
        specs += [(blk + ("mlp", "wi_gate"), (L, d, f), d ** -0.5),
                  (blk + ("mlp", "wi_up"), (L, d, f), d ** -0.5)]
    else:
        specs += [(blk + ("mlp", "wi"), (L, d, f), d ** -0.5)]
    specs += [
        (blk + ("mlp", "wo"), (L, f, d), f ** -0.5),
        (("final_norm", "scale"), (d,), NORM_STD),
        (("final_norm", "bias"), (d,), NORM_STD),
        (("head", "kernel"), (d, v), d ** -0.5),
    ]
    return specs


def _nest(flat: Dict[Tuple[str, ...], object]) -> dict:
    out: dict = {}
    for path, val in flat.items():
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = val
    return out


def make_params(m: dict, seed: int):
    """The whole tree in ``m["dtype"]``, from one jitted call on the
    default device."""
    return _make(tuple(sorted(_freeze(m).items())))(seed32(seed))


def _freeze(m: dict) -> dict:
    keys = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
            "d_ff", "vocab_size", "mlp", "dtype")
    return {k: m[k] for k in keys}


@functools.lru_cache(maxsize=None)
def _make(frozen):
    import jax
    import jax.numpy as jnp

    m = dict(frozen)
    specs = leaf_specs(m)
    dtype = jnp.dtype(m["dtype"])

    def make(s):
        key = jax.random.key(s)
        flat = {}
        for i, (path, shape, std) in enumerate(specs):
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * std
            flat[path] = x.astype(dtype)
        return _nest(flat)

    return jax.jit(make)
