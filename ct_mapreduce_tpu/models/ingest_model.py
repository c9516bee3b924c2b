"""The flagship model: config → mesh → aggregator → ingest/drain.

This is the composition root for the device pipeline — the analog of
the reference's wired-up ``LogSyncEngine`` + ``FilesystemDatabase``
stack (/root/reference/engine/engine.go:19-48), but TPU-shaped: a
:class:`TpuAggregator` on one chip, a :class:`ShardedAggregator` over
a multi-device mesh, behind one interface the ingest sinks and CLIs
consume.
"""

from __future__ import annotations

import os
from datetime import datetime, timezone
from typing import Optional

from ct_mapreduce_tpu.agg.aggregator import AggregateSnapshot, TpuAggregator
from ct_mapreduce_tpu.config import CTConfig
from ct_mapreduce_tpu.parallel.mesh import make_mesh, parse_mesh_shape


def build_aggregator(config: CTConfig, mesh=None) -> TpuAggregator:
    """Pick the device path from config: a mesh with >1 device gets the
    sharded aggregator; otherwise single-chip. ``meshShape`` empty →
    all local devices on the ``shard`` axis."""
    import jax

    now = (
        datetime.fromtimestamp(0, tz=timezone.utc)
        if config.log_expired_entries
        else None
    )
    common = dict(
        capacity=1 << config.table_bits,
        batch_size=config.batch_size,
        cn_prefixes=tuple(config.issuer_cn_filters()),
        now=now,
        grow_at=config.table_grow_at,
        max_capacity=1 << config.table_max_bits,
    )
    if mesh is None:
        spec = parse_mesh_shape(config.mesh_shape)
        n_fixed = spec.fixed_size if -1 not in spec.axis_sizes else len(jax.devices())
        if n_fixed > 1:
            mesh = make_mesh(spec)
    if mesh is not None and mesh.devices.size > 1:
        from jax.sharding import Mesh

        from ct_mapreduce_tpu.agg.sharded import AXIS, mesh_capacity
        from ct_mapreduce_tpu.agg.sharded_agg import ShardedAggregator

        # The dedup's table/batch sharding is 1-D; flatten multi-axis
        # meshes (e.g. "data:4,expert:2") over the same devices.
        if len(mesh.axis_names) != 1:
            mesh = Mesh(mesh.devices.reshape(-1), (AXIS,))
        n = mesh.devices.size
        # Round capacity UP to a power-of-two-per-shard multiple, and
        # the batch up to a multiple of the mesh size.
        cap = mesh_capacity(n, 1 << config.table_bits)
        batch = -(-common["batch_size"] // n) * n
        return ShardedAggregator(
            mesh, **{**common, "capacity": cap, "batch_size": batch}
        )
    return TpuAggregator(**common)


class IngestModel:
    """Aggregator + snapshot lifecycle, as one object."""

    def __init__(self, aggregator: TpuAggregator, state_path: str = ""):
        self.aggregator = aggregator
        self.state_path = state_path

    @classmethod
    def from_config(cls, config: CTConfig, mesh=None) -> "IngestModel":
        agg = build_aggregator(config, mesh=mesh)
        model = cls(agg, state_path=config.agg_state_path)
        if model.state_path and os.path.exists(model.state_path):
            agg.load_checkpoint(model.state_path)
        return model

    def ingest(self, entries):
        return self.aggregator.ingest(entries)

    def drain(self) -> AggregateSnapshot:
        return self.aggregator.drain()

    def save(self) -> None:
        if self.state_path:
            self.aggregator.save_checkpoint(self.state_path)

    def prepare_growth(self) -> bool:
        """A round's end, after the save: a table near its growth
        threshold gets the doubled table's programs made ready
        (`TpuAggregator.prepare_growth`); any other pays a compare."""
        return self.aggregator.prepare_growth()
