"""First-class device placement for the execution engine and gateway.

The paper's headline is that a dataflow architecture scales LSTM-AE
throughput with hardware resources; the serving-layer analogue is *data
placement* — how pool-slot state, micro-batch rows, and pipeline stages
are laid out over a device mesh.  Before this module, placement was an
ad-hoc ``data_parallel`` int buried in :class:`EngineConfig` that only
the pipelined schedule read; neither the gateway session pool nor the
micro-batcher could use more than one device.

A :class:`Placement` is the single declarative surface:

>>> pl = Placement.data(4)            # 4-way data-parallel mesh
>>> pl.mesh()                         # jax Mesh over the first 4 devices
>>> pl.row_sharding()                 # NamedSharding: leading dim over "data"
>>> pl.pad_rows(30)                   # -> 32 (per-device multiple)

It is threaded through ``EngineConfig(placement=...)`` → :class:`Engine`
(batch/masked programs jitted with ``in_shardings``/``out_shardings``) →
``AnomalyService.open_gateway(placement=...)`` → ``SessionPool`` (the
stacked ``(h, c)`` + error-sum slot block shards over the data axis, so
capacity scales to ``slots_per_device x mesh_size``) and ``MicroBatcher``
(bucket flushes score data-parallel, padded to a per-device multiple).

Design rules:

* **Declarative and hashable** — a frozen dataclass of plain fields, so
  it participates in ``EngineConfig`` equality and the schedule
  resolve-cache key (sharded and unsharded compiled programs never
  collide).  Meshes are built lazily, per-process, via a cached factory;
  importing this module touches no jax device state.
* **Single-device no-op** — ``Placement.single()`` (the default) changes
  nothing: no mesh is built, no sharding constraints are added, programs
  and values are identical to the pre-placement code paths.
* **Contiguous row blocks** — ``row_sharding`` lays the leading dim out
  in contiguous per-device blocks (device *d* of *n* holds rows
  ``[d*rows/n, (d+1)*rows/n)``), which is what makes per-device slot
  occupancy and flush fill observable host-side.

The deprecated ``EngineConfig(data_parallel=N)`` maps to
``Placement.data(N)`` with a :class:`DeprecationWarning` (see
``engine/base.py``), so every PR 1–3 call site keeps working.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


@functools.lru_cache(maxsize=None)
def _mesh_for(data_shards: int, data_axis: str) -> Mesh:
    """One cached 1-D mesh per (ways, axis name) — meshes hold device
    handles, so they are process-global state and must not be rebuilt per
    Engine (the resolve-cache leak class of bug)."""
    devices = jax.devices()
    if len(devices) < data_shards:
        raise ValueError(
            f"placement needs {data_shards} devices on the {data_axis!r} "
            f"axis, have {len(devices)}; run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={data_shards} "
            f"to emulate, or shrink the placement"
        )
    # Auto axes: the pool's slot updates (``.at[slot].set``) index arrays
    # sharded on this mesh, which Explicit axes (make_mesh's default)
    # refuse with ShardingTypeError
    return jax.make_mesh((data_shards,), (data_axis,), (AxisType.Auto,),
                         devices=devices[:data_shards])


@dataclass(frozen=True)
class Placement:
    """Declarative device placement: mesh axes + named shardings.

    ``data_shards``  ways on the data axis — pool slots, micro-batch rows
                     and batched scoring rows shard over it
    ``data_axis``    mesh axis name for the data dimension
    ``stage_axis``   mesh axis name pipeline stages use (the pipelined
                     schedule builds its own (data, stage) mesh from the
                     same axis names)
    """

    data_shards: int = 1
    data_axis: str = "data"
    stage_axis: str = "model"

    def __post_init__(self):
        if self.data_shards < 1:
            raise ValueError(f"data_shards must be >= 1, got {self.data_shards}")
        if self.data_axis == self.stage_axis:
            raise ValueError(
                f"data_axis and stage_axis must differ, both {self.data_axis!r}"
            )

    # -- constructors ------------------------------------------------------

    @classmethod
    def single(cls) -> "Placement":
        """The no-op placement: one device, no mesh, unchanged programs."""
        return cls()

    @classmethod
    def data(cls, n: int, *, data_axis: str = "data") -> "Placement":
        """N-way data-parallel placement (``data_parallel=N``'s successor)."""
        return cls(data_shards=n, data_axis=data_axis)

    @classmethod
    def from_spec(cls, spec: str) -> "Placement":
        """Parse a CLI mesh spec like ``"data=4"`` (the ``--mesh`` flag).

        Only the ``data`` axis is placeable from the CLI today; unknown
        axes fail loudly rather than being dropped.
        """
        out: dict[str, int] = {}
        for part in spec.split(","):
            part = part.strip()
            if not part:
                continue
            axis, sep, n = part.partition("=")
            axis = axis.strip()
            if not sep or axis not in ("data",):
                raise ValueError(
                    f"bad mesh spec {part!r}: expected data=N (axes "
                    f"supported: data)"
                )
            try:
                out[axis] = int(n)
            except ValueError:
                raise ValueError(f"bad mesh spec {part!r}: {n!r} is not an int")
        return cls.data(out.get("data", 1))

    # -- queries -----------------------------------------------------------

    @property
    def is_sharded(self) -> bool:
        return self.data_shards > 1

    @property
    def devices_needed(self) -> int:
        return self.data_shards

    def pad_rows(self, n: int) -> int:
        """Round ``n`` up to a per-device multiple (sharded leading dims
        must split evenly across the data axis)."""
        s = self.data_shards
        return ((max(n, 1) + s - 1) // s) * s

    def shard_of_row(self, row: int, n_rows: int) -> int:
        """Which data shard holds ``row`` of a ``row_sharding``-laid-out
        leading dim of ``n_rows`` (contiguous blocks)."""
        return row // (n_rows // self.data_shards)

    # -- mesh + shardings (lazy; never built for the single placement) ----

    def mesh(self) -> Mesh:
        """The 1-D data mesh (cached per process); raises with a clear
        message when fewer than ``data_shards`` devices exist."""
        return _mesh_for(self.data_shards, self.data_axis)

    def row_sharding(self) -> NamedSharding:
        """Leading dim over the data axis — pool-slot state, micro-batch
        rows, per-row scores."""
        return NamedSharding(self.mesh(), P(self.data_axis))

    def replicated_sharding(self) -> NamedSharding:
        """Fully replicated — model params, scalar controls."""
        return NamedSharding(self.mesh(), P())

    def describe(self) -> dict:
        """Telemetry-friendly summary (surfaced by ``gateway.stats()``)."""
        return {
            "data": self.data_shards,
            "data_axis": self.data_axis,
            "stage_axis": self.stage_axis,
        }

    def __repr__(self) -> str:
        if not self.is_sharded:
            return "Placement.single()"
        return (f"Placement.data({self.data_shards}, "
                f"data_axis={self.data_axis!r})")


__all__ = ["Placement"]
