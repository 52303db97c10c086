"""Distributed era clocks: the multi-shard / multi-pod era subsystem.

A single F&A word does not exist across SMR instances.  Instead each
instance (a *shard* of the block pool, or a pod in the multi-host setting)
advances a local monotone counter, and the global era is the *maximum*
over instances, merged periodically — host-side for shards within one
process (:class:`ShardedEraDomain`), or by an all-reduce-max piggybacked on
collectives a decode/train step already runs (:func:`merged_era` /
:meth:`DistributedEraClock.device_merge`).

Safety argument (HE/WFE invariant preserved): every block lives its whole
lifecycle — ``alloc_era`` stamp, ``retire_era`` stamp, reservation scan —
against ONE instance's clock, so the single-instance proof applies shard by
shard.  The merge only ever *advances* a lagging clock to the fleet maximum
(a monotone join): a reader's published reservation can then only LAG the
true global era, and the interval check ``alloc_era <= resv <= retire_era``
errs toward keeping blocks alive — lag delays reclamation, never enables
it.  Monotonicity of max-merge means eras never regress, so
``retire_era >= alloc_era`` stays true for every block.  Boundedness: each
instance's increments are bounded by its own alloc/retire activity exactly
as in the single-instance proof, and the merge adds no increments — it only
equalizes, so the fleet-wide clock spread after a merge is zero and between
merges is bounded by one merge period's worth of local activity.

``merged_era`` is the shard_map building block; ``DistributedEraClock`` is
the host-side wrapper around one SMR instance's clock;
``ShardedEraDomain`` joins N shard clocks inside one process (the sharded
block pool's merge-on-step-boundary uses it).
"""

from __future__ import annotations

from typing import List

__all__ = ["merged_era", "DistributedEraClock", "ShardedEraDomain"]


def merged_era(local_era, axis_name: str):
    """all-reduce-max merge of per-pod era counters (inside shard_map)."""
    import jax

    return jax.lax.pmax(local_era, axis_name)


class DistributedEraClock:
    """One SMR instance's era clock with monotone max-merge.

    The local component is the instance's ordinary F&A counter (WFE/HE
    ``global_era``, EBR/IBR ``global_epoch`` — whatever ``era_clock()``
    exposes); ``merge`` folds in the freshest remote maximum and returns the
    merged value.  ``advance_to`` is monotone by construction.  Schemes
    without a clock (HP, Leak) construct a clock whose ops are no-ops.
    """

    def __init__(self, smr) -> None:
        self.smr = smr
        self._clock = smr.era_clock()
        #: merges that actually advanced the local clock (telemetry)
        self.merged_in = 0

    @property
    def local(self) -> int:
        return self._clock.load() if self._clock is not None else 0

    def merge(self, remote_max: int) -> int:
        """Fold a remote era maximum into the local clock (monotone join).

        Uses CAS so concurrent local F&A increments are never lost; bounded
        retries (the clock only moves forward, so a failed CAS means
        someone else already advanced past ``remote_max``).
        """
        if self._clock is None:
            return 0
        while True:
            cur = self._clock.load()
            if remote_max <= cur:
                return cur
            if self._clock.cas(cur, remote_max):
                self.merged_in += 1
                return remote_max

    def device_merge(self, mesh, axis: str = "pod") -> int:
        """Run the actual collective on ``mesh`` and merge the result.

        In production this rides on an existing step collective; here it is
        a standalone shard_map (the dry-run lowers it on the 2x16x16 mesh).
        """
        import jax.numpy as jnp
        import numpy as np
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        n = mesh.shape[axis]
        local = jnp.full((n,), self.local, jnp.int32)

        def f(x):
            return merged_era(x[0], axis)[None]

        merged = shard_map(f, mesh=mesh, in_specs=P(axis),
                           out_specs=P(axis))(local)
        return self.merge(int(np.max(np.asarray(merged))))


class ShardedEraDomain:
    """Monotone max-merge across N shard clocks inside one process.

    The sharded block pool gives each shard its own SMR instance; this
    domain is the join of their clocks.  ``merge_all`` reads every local
    clock, takes the maximum, and folds it into each shard — the host-side
    analogue of the all-reduce-max.  Reads and merges are racy with
    concurrent local F&A increments, which is fine: a concurrent increment
    can only make some local clock LARGER than the maximum we computed, and
    ``merge`` never moves a clock backwards, so the join stays monotone.
    """

    def __init__(self, smrs) -> None:
        self.clocks: List[DistributedEraClock] = [
            DistributedEraClock(smr) for smr in smrs
        ]
        #: completed merge rounds (telemetry / tests)
        self.merges = 0

    @property
    def locals(self) -> List[int]:
        return [c.local for c in self.clocks]

    def spread(self) -> int:
        """Current max-min divergence across shard clocks (racy gauge)."""
        vals = self.locals
        return max(vals) - min(vals) if vals else 0

    def merge_all(self) -> int:
        """One merge round: every shard clock advances to the fleet max."""
        m = max(self.locals, default=0)
        for c in self.clocks:
            c.merge(m)
        self.merges += 1
        return m

    def device_merge_all(self, mesh, axis: str = "pod") -> int:
        """Fold a cross-pod device maximum into every shard clock."""
        m = max((c.device_merge(mesh, axis) for c in self.clocks), default=0)
        for c in self.clocks:
            c.merge(m)
        self.merges += 1
        return m

    def stats(self) -> dict:
        return {
            "era_merges": self.merges,
            "era_spread": self.spread(),
            "era_max": max(self.locals, default=0),
            "merged_in": sum(c.merged_in for c in self.clocks),
        }
