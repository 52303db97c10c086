"""Compute/communication overlap primitives.

TPU XLA already overlaps collectives with independent compute via async
collective scheduling (``--xla_tpu_enable_async_collective_*``), so the
first-line mechanism is *structural*: keep producer matmuls independent of
the collective operands.  Where structure is not enough we provide explicit
shard_map building blocks:

* ``ag_matmul`` — all-gather-then-matmul with the gather decomposed into
  |axis| - 1 ``collective_permute`` steps, each overlapped with the matmul
  of the chunk that is already resident (the "collective matmul" of
  Wang et al.; what XLA's ag-matmul fusion does internally).  Used in the
  §Perf hillclimbs for the TP all-gathers of the FFN path.
* ``rs_matmul`` — matmul with reduce-scattered output, same decomposition
  in reverse.

These run under ``jax.shard_map`` with the model axis explicit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map

__all__ = ["ag_matmul", "rs_matmul", "shard_map"]


def ag_matmul(x_shard: jax.Array, w_shard: jax.Array, axis_name: str
              ) -> jax.Array:
    """Overlapped all_gather(x) @ w, inside shard_map.

    x_shard: (m/k, n) — sharded on dim 0 over ``axis_name`` (k shards);
    w_shard: (n, p/k) — weight sharded on dim 1 (column parallel).
    Returns the local (m, p/k) output, equal to all_gather(x) @ w_shard,
    but computed as k chunk-matmuls pipelined with k-1 collective_permutes
    so the ICI transfer of chunk i+1 hides under the matmul of chunk i.
    """
    k = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % k) for i in range(k)]

    chunk = x_shard
    m = x_shard.shape[0]
    out = jnp.zeros((k * m, w_shard.shape[1]), x_shard.dtype)
    # mark the accumulator as device-varying so the fori_loop carry type
    # matches after ppermute (varying-manual-axes tracking)
    out = jax.lax.pcast(out, (axis_name,), to="varying")

    def body(i, carry):
        out, chunk = carry
        # matmul the resident chunk while the permute of the next is in flight
        nxt = jax.lax.ppermute(chunk, axis_name, perm)
        src = (idx - i) % k  # whose shard we currently hold
        part = jnp.dot(chunk, w_shard, preferred_element_type=jnp.float32
                       ).astype(x_shard.dtype)
        out = jax.lax.dynamic_update_slice(out, part, (src * m, 0))
        return out, nxt

    out, chunk = jax.lax.fori_loop(0, k - 1, body, (out, chunk))
    src = (idx - (k - 1)) % k
    part = jnp.dot(chunk, w_shard, preferred_element_type=jnp.float32
                   ).astype(x_shard.dtype)
    out = jax.lax.dynamic_update_slice(out, part, (src * m, 0))
    return out


def rs_matmul(x: jax.Array, w_shard: jax.Array, axis_name: str) -> jax.Array:
    """Overlapped x @ w with reduce-scattered output, inside shard_map.

    x: (m, n/k) local activation (row-parallel input);
    w_shard: (n/k, p) local weight shard.
    Returns (m/k, p): the reduce_scatter of the full (m, p) partial sums,
    decomposed into k-1 permute+add steps overlapped with chunk matmuls.
    """
    k = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    m = x.shape[0]
    assert m % k == 0, (m, k)
    mc = m // k
    perm = [(i, (i - 1) % k) for i in range(k)]

    def chunk_mm(j):
        # compute the partial destined for shard j
        rows = jax.lax.dynamic_slice(x, (j * mc, 0), (mc, x.shape[1]))
        return jnp.dot(rows, w_shard, preferred_element_type=jnp.float32)

    acc = chunk_mm((idx + 1) % k)
    # ring: after k-1 permute+add steps every shard holds its reduced chunk
    for i in range(1, k):
        acc = jax.lax.ppermute(acc, axis_name, perm)
        acc = acc + chunk_mm((idx + 1 + i) % k)
    return acc.astype(x.dtype)
