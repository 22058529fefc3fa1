"""Slot-indexed session pool: thousands of logical streams, one program.

The paper keeps its datapath fed by batching independent work into the
same hardware pipeline; the serving-layer analogue is a fixed block of
``capacity`` stream slots — stacked per-layer (h, c) plus running error
sums — stepped by ONE compiled masked program regardless of which logical
streams are resident.  Admission/eviction only touches host-side slot
maps and zeroes the slot's state rows, so stream churn never retraces.

Under a sharded :class:`~repro.engine.placement.Placement` the slot block
itself distributes over the data mesh axis — contiguous row blocks of
``slots_per_device`` slots per device — so capacity scales to
``slots_per_device x mesh_size`` instead of what one device holds.  The
masked step is jitted with explicit in/out shardings (state in, state out
keep the row layout; params replicate), admission balances new streams
onto the least-loaded device, and per-device occupancy is gauged as
``pool.device_active`` so mesh imbalance is observable.  The single
placement is a strict no-op: programs, values and telemetry are unchanged.

Semantics contract (equivalence-tested in tests/test_gateway.py and, for
the sharded layout, tests/test_placement.py): a stream admitted to a slot
and stepped through any interleaving of pool steps observes exactly the
per-timestep running errors it would see alone through
``AnomalyService.stream_step`` — batch rows are independent through the
LSTM cell, and unmasked slots carry their state unchanged.
"""
from __future__ import annotations

import logging
from collections import deque
from typing import Callable, Hashable, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.base import Engine
from repro.gateway.telemetry import Telemetry

logger = logging.getLogger(__name__)


class PoolFullError(RuntimeError):
    """Admission rejected: every slot is occupied (the gateway's
    fixed-capacity admission contract — callers shed or retry)."""


class UnknownStreamError(KeyError):
    """A stream id that is not resident in the pool."""


class SessionPool:
    """Fixed-capacity pooled streaming over one :class:`Engine`.

    >>> pool = SessionPool(engine, capacity=32)
    >>> pool.admit("conn-7")
    >>> errors = pool.step({"conn-7": x_t})   # any subset of residents
    >>> final = pool.evict("conn-7")
    """

    def __init__(
        self,
        engine: Engine,
        capacity: int,
        telemetry: Optional[Telemetry] = None,
    ):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.engine = engine
        self.capacity = capacity
        self.features = engine.cfg.lstm_ae.input_features
        self.telemetry = telemetry or Telemetry()
        # the pool always lays its block out on the ENGINE's placement —
        # the masked-step programs and the slot state must agree on one
        # layout (re-place via Engine.with_placement, not a pool knob)
        self.placement = engine.placement
        # the state block pads up to a per-device multiple; the padding rows
        # are never admitted (logical capacity stays exactly ``capacity``)
        self._block = self.placement.pad_rows(capacity)
        self.slots_per_device = self._block // self.placement.data_shards

        self._state = engine.init_stream_state(self._block)
        self._sq_sum = jnp.zeros((self._block,), jnp.float32)
        self._steps = jnp.zeros((self._block,), jnp.int32)
        self._slot_of: dict[Hashable, int] = {}
        # per-device free stacks + active counters: admission picks the
        # least-loaded device in O(devices), pops its stack in O(1) —
        # churn-heavy serving must not walk the resident map per admit.
        # Stacks hold only logical slots (< capacity); descending order so
        # pop() yields the lowest slot id first, matching the PR-2 order
        # bit for bit on a single device.
        self._free_count = capacity
        self._free_by_dev: list[list[int]] = [
            [] for _ in range(self.placement.data_shards)
        ]
        for slot in range(capacity - 1, -1, -1):
            self._free_by_dev[slot // self.slots_per_device].append(slot)
        self._active_by_dev = [0] * self.placement.data_shards

        def _pool_step(params, x, state, mask, sq_sum, steps):
            # one fused program: masked cell step + masked error accumulate
            y_t, state = engine._masked_stream_step(params, x, state, mask)
            sq = jnp.mean(
                jnp.square(y_t.astype(jnp.float32) - x.astype(jnp.float32)),
                axis=-1,
            )
            sq_sum = sq_sum + jnp.where(mask, sq, 0.0)
            steps = steps + mask.astype(jnp.int32)
            return state, sq_sum, steps

        def _clear_slot(state, sq_sum, steps, slot):
            state = jax.tree.map(lambda leaf: leaf.at[slot].set(0.0), state)
            return state, sq_sum.at[slot].set(0.0), steps.at[slot].set(0)

        def _load_slot(state, sq_sum, steps, slot, row, sq, n):
            # inverse of _clear_slot: write one stream's saved rows back
            # into its slot (the durability restore path)
            state = jax.tree.map(
                lambda leaf, r: leaf.at[slot].set(r.astype(leaf.dtype)),
                state, row,
            )
            return state, sq_sum.at[slot].set(sq), steps.at[slot].set(n)

        use_jit = engine.engine_cfg.jit
        if use_jit and self.placement.is_sharded:
            # slot rows live distributed over the data mesh: the fused step
            # is compiled with explicit shardings (state in == state out, so
            # the block never gathers between steps) and the initial block
            # is placed shard-by-shard up front
            rows = self.placement.row_sharding()
            repl = self.placement.replicated_sharding()
            self._pool_step = jax.jit(
                _pool_step,
                in_shardings=(repl, rows, rows, rows, rows, rows),
                out_shardings=(rows, rows, rows),
            )
            self._clear_slot = jax.jit(
                _clear_slot,
                in_shardings=(rows, rows, rows, repl),
                out_shardings=(rows, rows, rows),
            )
            self._load_slot = jax.jit(
                _load_slot,
                in_shardings=(rows, rows, rows, repl, repl, repl, repl),
                out_shardings=(rows, rows, rows),
            )
            self._state = jax.device_put(self._state, rows)
            self._sq_sum = jax.device_put(self._sq_sum, rows)
            self._steps = jax.device_put(self._steps, rows)
        else:
            self._pool_step = jax.jit(_pool_step) if use_jit else _pool_step
            self._clear_slot = jax.jit(_clear_slot) if use_jit else _clear_slot
            self._load_slot = jax.jit(_load_slot) if use_jit else _load_slot

    # -- membership -------------------------------------------------------

    @property
    def active(self) -> int:
        return len(self._slot_of)

    def __contains__(self, stream_id: Hashable) -> bool:
        return stream_id in self._slot_of

    @property
    def resident(self) -> tuple:
        return tuple(self._slot_of)

    def device_of_slot(self, slot: int) -> int:
        """Which data shard holds ``slot`` (contiguous row blocks)."""
        return slot // self.slots_per_device

    def per_device_active(self) -> list:
        """Resident stream count per data shard — the mesh-imbalance view
        (a single-entry list under the single placement)."""
        return list(self._active_by_dev)

    def _pick_slot(self) -> int:
        """Pop a free slot from the least-loaded device that has one (ties
        broken by device order, deterministically), so resident streams
        spread across the mesh.  O(devices) + an O(1) stack pop; on a
        single device this is the original lowest-slot-first order bit for
        bit."""
        dev = min(
            (d for d, stack in enumerate(self._free_by_dev) if stack),
            key=lambda d: (self._active_by_dev[d], d),
        )
        self._free_count -= 1
        self._active_by_dev[dev] += 1
        return self._free_by_dev[dev].pop()

    def admit(self, stream_id: Hashable) -> int:
        """Claim a slot for ``stream_id`` (zeroed state); raises
        :class:`PoolFullError` when no slot is free."""
        if stream_id in self._slot_of:
            raise ValueError(f"stream {stream_id!r} is already resident")
        if not self._free_count:
            self.telemetry.count("pool.rejected")
            raise PoolFullError(
                f"pool at capacity ({self.capacity}); evict a stream first"
            )
        slot = self._pick_slot()
        self._slot_of[stream_id] = slot
        self._zero(slot)
        self.telemetry.count("pool.admitted")
        self._gauge_occupancy()
        return slot

    def evict(self, stream_id: Hashable) -> float:
        """Release the stream's slot; returns its final running error."""
        slot = self._require(stream_id)
        final = float(self.errors()[slot])
        del self._slot_of[stream_id]
        dev = self.device_of_slot(slot)
        self._free_by_dev[dev].append(slot)
        self._free_count += 1
        self._active_by_dev[dev] -= 1
        self.telemetry.count("pool.evicted")
        self._gauge_occupancy()
        return final

    def _gauge_occupancy(self) -> None:
        self.telemetry.gauge("pool.active", self.active)
        self.telemetry.gauge("pool.occupancy", self.active / self.capacity)
        if self.placement.is_sharded:
            self.telemetry.gauge_vec("pool.device_active", self.per_device_active())

    def reset(self, stream_id: Hashable) -> None:
        """Zero a resident stream's state and error counters in place."""
        self._zero(self._require(stream_id))

    def _require(self, stream_id: Hashable) -> int:
        try:
            return self._slot_of[stream_id]
        except KeyError:
            raise UnknownStreamError(
                f"stream {stream_id!r} is not resident (admit it first)"
            ) from None

    def _zero(self, slot: int) -> None:
        self._state, self._sq_sum, self._steps = self._clear_slot(
            self._state, self._sq_sum, self._steps, slot
        )

    # -- stepping ---------------------------------------------------------

    def step(self, inputs: Mapping[Hashable, "np.ndarray"]) -> dict:
        """Advance every stream in ``inputs`` one timestep.

        ``inputs`` maps resident stream ids to their next sample ``(F,)``;
        any subset of residents may step (the rest carry unchanged).
        Returns {stream_id: running mean error so far} for stepped streams.
        """
        if not inputs:
            return {}
        tel = self.telemetry
        with tel.span("pool.step", "pool_step_ms"):
            with tel.span("pool.assemble", "pool_assemble_ms"):
                slots = [self._require(sid) for sid in inputs]
                x = np.zeros((self._block, self.features), np.float32)
                mask = np.zeros((self._block,), bool)
                for sid, slot in zip(inputs, slots):
                    sample = np.asarray(inputs[sid], np.float32)
                    if sample.shape != (self.features,):
                        raise ValueError(
                            f"stream {sid!r}: expected sample shape "
                            f"({self.features},), got {sample.shape}"
                        )
                    x[slot] = sample
                    mask[slot] = True
            with tel.span("pool.launch", "pool_launch_ms"):
                # dispatch of the step and of the error read's three ops;
                # JAX returns without waiting for their results
                self._state, self._sq_sum, self._steps = self._pool_step(
                    self.engine._require_params(), jnp.asarray(x), self._state,
                    jnp.asarray(mask), self._sq_sum, self._steps,
                )
                errors = self.errors()
            tel.record_pool_step(len(slots), self.capacity)
            with tel.span("pool.readback", "pool_readback_ms"):
                # waits for the errors and copies them to the host
                errs = np.asarray(errors)
        return {sid: float(errs[slot]) for sid, slot in zip(inputs, slots)}

    # -- durability export / restore --------------------------------------
    #
    # Snapshots read a HOST COPY of the whole block; restores write one
    # slot's rows through a jitted setter (the mirror of ``_clear_slot``).
    # Rows travel as plain numpy in tree-leaves order so they serialize
    # through checkpoint/manager.py without carrying treedefs around.

    def slot_of(self, stream_id: Hashable) -> int:
        """Resident slot index of ``stream_id`` (UnknownStreamError if not)."""
        return self._require(stream_id)

    def export_block(self) -> tuple[list, np.ndarray, np.ndarray]:
        """Host copy of the full slot block: (state leaves in tree-leaves
        order, each ``(block, ...)``; sq_sum ``(block,)``; steps ``(block,)``).
        This is the snapshot read — it blocks only for device->host copies,
        never for host-side serialization."""
        leaves = [np.asarray(l) for l in jax.tree_util.tree_leaves(self._state)]
        return leaves, np.asarray(self._sq_sum), np.asarray(self._steps)

    def export_slot(self, stream_id: Hashable) -> tuple[list, float, int]:
        """Host copy of ONE stream's rows (state leaf rows in tree-leaves
        order, sq_sum, steps) — the park-on-disconnect path."""
        slot = self._require(stream_id)
        rows = [np.asarray(l[slot]) for l in jax.tree_util.tree_leaves(self._state)]
        return rows, float(self._sq_sum[slot]), int(self._steps[slot])

    def restore(self, stream_id: Hashable, rows, sq_sum: float,
                steps: int) -> int:
        """Admit ``stream_id`` into a free slot and load previously exported
        state rows + error counters into it.  ``rows`` is a sequence of
        per-leaf arrays in tree-leaves order (as produced by
        :meth:`export_slot` / a sliced :meth:`export_block`)."""
        treedef = jax.tree_util.tree_structure(self._state)
        expect = [l.shape[1:] for l in jax.tree_util.tree_leaves(self._state)]
        rows = [np.asarray(r) for r in rows]
        got = [r.shape for r in rows]
        if got != expect:
            raise ValueError(
                f"restore rows for {stream_id!r} do not match this pool's "
                f"state layout: got {got}, expected {expect} (arch mismatch?)"
            )
        row_tree = jax.tree_util.tree_unflatten(
            treedef, [jnp.asarray(r) for r in rows]
        )
        slot = self.admit(stream_id)
        self._state, self._sq_sum, self._steps = self._load_slot(
            self._state, self._sq_sum, self._steps, slot, row_tree,
            jnp.float32(sq_sum), jnp.int32(steps),
        )
        self.telemetry.count("pool.restored")
        return slot

    def errors(self) -> jnp.ndarray:
        """Running mean error per slot (capacity,) — lazy device array."""
        return self._sq_sum / jnp.maximum(self._steps, 1).astype(jnp.float32)

    def error_of(self, stream_id: Hashable) -> float:
        return float(self.errors()[self._require(stream_id)])

    def __repr__(self) -> str:
        pl = (f", placement={self.placement!r}"
              if self.placement.is_sharded else "")
        return (f"SessionPool(capacity={self.capacity}, active={self.active}, "
                f"schedule={self.engine.schedule.tag}{pl})")


class _Pending:
    """One frame's samples of one stream, waiting in a :class:`StepCoalescer`."""

    __slots__ = ("xs", "errors", "next", "on_done")

    def __init__(self, xs, on_done):
        self.xs = xs
        self.errors = np.zeros(len(xs), np.float32)
        self.next = 0  # index of the sample the next step takes
        self.on_done = on_done

    def finish(self, exc: Optional[BaseException] = None) -> None:
        try:
            self.on_done(self.errors[:self.next], exc)
        except Exception:
            logger.exception("step completion callback raised")


class StepCoalescer:
    """STEP samples of every stream, stepped together: one pool step per
    event-loop pass instead of one per sample.

    :meth:`submit` queues a frame's samples on its stream's FIFO and, on
    the first submit since the last flush, hands :meth:`flush` to
    ``schedule`` (the server passes ``loop.call_soon``, so every frame read
    in the same pass of the loop is queued by the time the flush runs).  A
    flush repeats until every FIFO is empty: the head sample of each
    stream that has one, ONE :meth:`SessionPool.step` over all of them,
    each running error handed back.  A step carries whatever arrived —
    nothing waits on a timer, so a lone stream steps alone as before — and
    rows are independent through the cell (the contract above), so each
    error is bit-for-bit what the stream sees stepped alone.  A frame of
    ``k`` samples takes ``k`` steps; frames of one stream step in arrival
    order.

    ``on_done(errors, exc)`` is called once per frame with the running
    errors of its samples that were stepped: all ``k`` and ``exc=None``
    once the last is known.  An engine exception fails every frame of
    that step with it (their remaining samples are dropped, the other
    streams step on), and a stream evicted while its samples wait is
    skipped, its frames failed with :class:`UnknownStreamError`.
    """

    def __init__(self, pool: SessionPool,
                 schedule: Callable[[Callable[[], None]], object]):
        self.pool = pool
        self._schedule = schedule
        self._queues: dict[Hashable, deque] = {}
        self._due = False

    @property
    def pending(self) -> int:
        """Streams with samples waiting."""
        return len(self._queues)

    def submit(self, stream_id: Hashable, xs: "np.ndarray",
               on_done: Callable) -> None:
        """Queue the ``(k, F)`` samples ``xs`` of a resident stream."""
        self._queues.setdefault(stream_id, deque()).append(_Pending(xs, on_done))
        if not self._due:
            self._due = True
            self._schedule(self.flush)

    def flush(self) -> None:
        """Step until no sample waits (nothing to do when none does)."""
        self._due = False
        while self._queues:
            heads = {}
            for sid in list(self._queues):
                if sid in self.pool:
                    heads[sid] = self._queues[sid][0]
                    continue
                gone = UnknownStreamError(
                    f"stream {sid!r} was evicted while its samples waited")
                for job in self._queues.pop(sid):
                    job.finish(gone)
            if not heads:
                return
            try:
                running = self.pool.step(
                    {sid: job.xs[job.next] for sid, job in heads.items()})
            except Exception as exc:
                # the batcher's ticket semantics: this step's frames answer
                # the error, every other sample still steps
                for sid in heads:
                    self._pop(sid).finish(exc)
                continue
            for sid, job in heads.items():
                job.errors[job.next] = running[sid]
                job.next += 1
                if job.next == len(job.xs):
                    self._pop(sid).finish()

    def _pop(self, stream_id: Hashable) -> _Pending:
        queue = self._queues[stream_id]
        job = queue.popleft()
        if not queue:
            del self._queues[stream_id]
        return job
