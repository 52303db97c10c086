"""The plain reference: a float32 forward pass of the served model in
``jax.numpy``, with no cache, no kernel and no batching, written from the
configuration file alone (it imports nothing of the program).

It reads the weight tree of ``weights.py`` (norm gain ``1 + scale``,
``x @ W`` projections, a head of its own) and runs one sequence at a time
through a scan over the layers, each layer's bf16 weights upcast to
float32 inside the scan, so that float32 copies of all weights never
exist at once.  Matmuls run at ``precision=HIGHEST`` (on a TPU a float32
matmul is otherwise rounded to bf16 passes).

``precision="fp8"`` is the control: every matmul's operands are rounded
to float8_e4m3fn (per-tensor absmax scaling for weights, per-row for
activations) before a float32 product, the step below the configured
bfloat16 that a later change might be tempted by.
"""

from __future__ import annotations

import functools
import math

import numpy as np

#: sequences are padded to a power of two of at least ``SEQ_BUCKET``
#: tokens, served positions to one of at least ``GAP_BUCKET``, so that
#: the reference compiles a few shapes, cached across runs
SEQ_BUCKET = 512
GAP_BUCKET = 256


def _mm(a, b, precision):
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    if precision == "fp8":
        a, b = _fp8(a, axis=-1), _fp8(b, axis=None)
    return jnp.matmul(a, b, precision=hi)


def _fp8(x, axis):
    """Round to float8_e4m3fn under absmax scaling (per row for
    activations, per tensor for weights) and back to float32."""
    import jax.numpy as jnp

    f8 = jnp.float8_e4m3fn
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.where(amax > 0, 448.0 / amax, 1.0)
    return (x * scale).astype(f8).astype(jnp.float32) / scale


def _norm(m, p, x):
    import jax.numpy as jnp

    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) / jnp.sqrt(var + m["norm_eps"])
    return y * (1.0 + p["scale"].astype(jnp.float32)) \
        + p["bias"].astype(jnp.float32)


def _rope(m, x, pos):
    """Rotary embedding on the first ``rotary_dim`` lanes of each head,
    halves rotated against each other (x: (S, heads, hd))."""
    import jax.numpy as jnp

    rd = m["rotary_dim"]
    inv = 1.0 / (m["rope_theta"] ** (np.arange(0, rd, 2) / rd))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xr, xp = x[..., :rd], x[..., rd:]
    x1, x2 = xr[..., : rd // 2], xr[..., rd // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([out, xp], -1)


def _layer(m, precision, x, lp, pos):
    import jax
    import jax.numpy as jnp

    lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    s = x.shape[0]
    h, kh, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    mm = functools.partial(_mm, precision=precision)
    hn = _norm(m, lp["norm_mix"], x)
    q = _rope(m, mm(hn, lp["mix"]["wq"]).reshape(s, h, hd), pos)
    k = _rope(m, mm(hn, lp["mix"]["wk"]).reshape(s, kh, hd), pos)
    v = mm(hn, lp["mix"]["wv"]).reshape(s, kh, hd)
    g = h // kh
    k = jnp.repeat(k, g, axis=1)  # query head i reads kv head i // g
    v = jnp.repeat(v, g, axis=1)
    scores = mm(q.transpose(1, 0, 2), k.transpose(1, 2, 0)) / math.sqrt(hd)
    causal = pos[None, :] <= pos[:, None]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = mm(probs, v.transpose(1, 0, 2)).transpose(1, 0, 2)
    x = x + mm(att.reshape(s, h * hd), lp["mix"]["wo"])
    hn = _norm(m, lp["norm_mlp"], x)
    if m["mlp"] == "swiglu":
        ff = jax.nn.silu(mm(hn, lp["mlp"]["wi_gate"])) \
            * mm(hn, lp["mlp"]["wi_up"])
    else:
        ff = jax.nn.gelu(mm(hn, lp["mlp"]["wi"]), approximate=True)
    return x + mm(ff, lp["mlp"]["wo"])


def _logits_at(m, precision, params, tokens, at):
    """Logits (len(at), V) at positions ``at`` of one sequence."""
    import jax
    import jax.numpy as jnp

    pos = jnp.arange(tokens.shape[0])
    x = params["embed"]["table"][tokens].astype(jnp.float32)

    def body(x, lp):
        return _layer(m, precision, x, lp, pos), None

    x, _ = jax.lax.scan(body, x, params["groups"]["b0_attn"])
    x = _norm(m, params["final_norm"], x[at])
    return _mm(x, params["head"]["kernel"].astype(jnp.float32), precision)


def _gaps(m, with_control, params, tokens, at, served):
    """Per served position: how far the served token's reference logit
    lies below the reference's best; with the control, the same for the
    token the fp8 reference puts first."""
    import jax.numpy as jnp

    ref = _logits_at(m, "f32", params, tokens, at)
    best = jnp.max(ref, axis=-1)
    gap = best - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
    if not with_control:
        return gap, gap
    low = _logits_at(m, "fp8", params, tokens, at)
    pick = jnp.argmax(low, axis=-1)
    return gap, best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]


@functools.lru_cache(maxsize=None)
def _gaps_jit(frozen, with_control):
    import jax

    return jax.jit(functools.partial(_gaps, dict(frozen), with_control))


def _bucket(n, least):
    return max(least, 1 << max(0, n - 1).bit_length())


def served_gaps(m: dict, params, prompt, generated, *, control=False):
    """Run the reference once over ``prompt + generated`` and return, for
    every generated token, the gap by which its reference logit lies
    below the reference's best at that position (0 where it is the
    reference's own choice).  With ``control`` also returns the gaps of
    the tokens the fp8 control would have chosen, else None."""
    import jax

    seq = list(prompt) + list(generated[:-1])
    n_gen = len(generated)
    s_pad = _bucket(len(seq), SEQ_BUCKET)
    g_pad = _bucket(n_gen, GAP_BUCKET)
    tokens = np.zeros((s_pad,), np.int32)
    tokens[: len(seq)] = seq
    at = np.full((g_pad,), len(prompt) - 1, np.int32)
    at[:n_gen] = np.arange(len(prompt) - 1, len(prompt) - 1 + n_gen)
    served = np.zeros((g_pad,), np.int32)
    served[:n_gen] = generated
    frozen = tuple(sorted((k, v) for k, v in m.items()
                          if not isinstance(v, (list, dict))))
    with jax.default_matmul_precision("highest"):
        gap, low = _gaps_jit(frozen, control)(params, tokens, at, served)
    gap = np.asarray(gap)[:n_gen]
    return gap, (np.asarray(low)[:n_gen] if control else None)
