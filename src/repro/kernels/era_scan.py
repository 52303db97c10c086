"""Pallas TPU kernel: WFE cleanup() interval scan (paper Fig. 4, Theorem 4).

The reclamation hot path is R retired blocks × (T threads × H reservations)
interval-overlap tests.  At serving scale (tens of thousands of 16-token KV
blocks retiring per scheduling tick) this is a dense, memory-bound,
embarrassingly-parallel compare-reduce: ideal VPU work.

The kernel takes the *generalized* reservation form ``[lo, hi]`` used by the
era-table layer (``core/era_table.py``): point reservations (HE/WFE eras)
pass ``lo == hi``; IBR passes its per-thread interval; EBR derives
``lo = announce - 1, hi = ∞``.  A block conflicts with slot ``s`` iff

    lo[s] != INF  ∧  lo[s] ≤ retire_era  ∧  alloc_era ≤ hi[s]

which for ``lo == hi == e`` is exactly the paper's
``alloc_era ≤ e ≤ retire_era``.

TPU mapping
-----------
* retired-block era vectors are tiled into VMEM in (BLOCK_R, 1) column tiles;
* the reservation vectors are tiled along a second grid axis in (1, BLOCK_TH)
  chunks, so T·H is no longer bounded by what fits in one VMEM block —
  serving fleets with thousands of threads × slots stream through;
* per (i, j) step: (BLOCK_R, BLOCK_TH) broadcast compare + any-reduce — a
  pure VPU elementwise/reduction pattern, no MXU.  The output tile is
  revisited across the j axis (innermost on TPU), accumulating conflicts
  with an OR: initialized at j == 0, inverted on the host side;
* eras are int32 on-device (the host-side clock is monotonically advanced;
  a 31-bit horizon outlasts any realistic serving epoch between restarts);
* ``interpret=None`` auto-selects: compiled Mosaic on real TPU backends,
  interpreter everywhere else (CPU CI).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .platform import resolve_interpret

INF_ERA32 = jnp.iinfo(jnp.int32).max
BLOCK_R = 256    # retired blocks per grid step (8×128-aligned Rb×TH tiles)
BLOCK_TH = 512   # reservation slots per grid step (128-lane multiple)


def _era_scan_kernel(alloc_ref, retire_ref, lo_ref, hi_ref, out_ref):
    j = pl.program_id(1)
    a = alloc_ref[:, 0]   # (Rb,)
    r = retire_ref[:, 0]
    lo = lo_ref[0, :]     # (THb,)
    hi = hi_ref[0, :]
    valid = lo != INF_ERA32
    conflict = ((lo[None, :] <= r[:, None])
                & (a[:, None] <= hi[None, :])
                & valid[None, :])
    c = jnp.any(conflict, axis=1).astype(jnp.int32)

    @pl.when(j == 0)
    def _init():
        out_ref[:, 0] = c

    @pl.when(j != 0)
    def _accumulate():
        out_ref[:, 0] = out_ref[:, 0] | c


@functools.partial(jax.jit, static_argnames=("interpret",))
def _era_scan_call(a, t, lo, hi, *, interpret: bool):
    rp, thp = a.shape[0], lo.shape[1]
    grid = (rp // BLOCK_R, thp // min(BLOCK_TH, thp))
    block_th = thp // grid[1]
    conflicts = pl.pallas_call(
        _era_scan_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_R, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((BLOCK_R, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, block_th), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_th), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((BLOCK_R, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, 1), jnp.int32),
        interpret=interpret,
    )(a, t, lo, hi)
    return conflicts == 0


def era_scan_interval(alloc_eras: jax.Array, retire_eras: jax.Array,
                      res_lo: jax.Array, res_hi: jax.Array, *,
                      interpret: bool | None = None) -> jax.Array:
    """(R,), (R,), (S,), (S,) int32 -> (R,) bool deletable mask."""
    r = alloc_eras.shape[0]
    th = res_lo.shape[0]
    # pad R to a BLOCK_R multiple; TH to a 128-lane multiple, and further to
    # a BLOCK_TH multiple once it spans more than one tile
    rp = max(BLOCK_R, -(-r // BLOCK_R) * BLOCK_R)
    thp = max(128, -(-th // 128) * 128)
    if thp > BLOCK_TH:
        thp = -(-thp // BLOCK_TH) * BLOCK_TH
    # padded rows use alloc = INF, retire = -1: the conflict predicate
    # (lo <= retire ∧ alloc <= hi) then needs lo < 0 or hi = INF — neither
    # is produced by the era-table layer (eras clip to [0, INF-1], and an
    # INF hi always comes with an invalid lo).  They're sliced off below
    # regardless; the padding just keeps any future reduction over the
    # padded output honest.
    a = jnp.full((rp, 1), INF_ERA32, jnp.int32).at[:r, 0].set(alloc_eras)
    t = jnp.full((rp, 1), -1, jnp.int32).at[:r, 0].set(retire_eras)
    # padded slots: lo = INF marks them invalid
    lo = jnp.full((1, thp), INF_ERA32, jnp.int32).at[0, :th].set(res_lo)
    hi = jnp.full((1, thp), INF_ERA32, jnp.int32).at[0, :th].set(res_hi)
    out = _era_scan_call(a, t, lo, hi,
                         interpret=resolve_interpret(interpret))
    return out[:r, 0]


def era_scan(alloc_eras: jax.Array, retire_eras: jax.Array,
             reservations: jax.Array, *,
             interpret: bool | None = None) -> jax.Array:
    """Point-reservation form: (R,), (R,), (T, H) -> (R,) bool mask.

    Kept as the historical entry point; a point era ``e`` is the degenerate
    interval ``[e, e]``.
    """
    res = jnp.asarray(reservations, jnp.int32).reshape(-1)
    return era_scan_interval(alloc_eras, retire_eras, res, res,
                             interpret=interpret)
