"""Slot-indexed session pool: thousands of logical streams, one program.

The paper keeps its datapath fed by batching independent work into the
same hardware pipeline; the serving-layer analogue is a fixed block of
``capacity`` stream slots — the model family's slot state (its
:class:`~repro.models.api.StreamModel`: per-layer (h, c) for the LSTM-AE;
Mamba states, KV caches and the last hidden state for Jamba) plus running
loss sums — stepped by ONE compiled masked program regardless of which
logical streams are resident.  The state is an opaque tree whose leaves are
rows-first; the program donates it, so the block is never held twice.
Admission/eviction only touches host-side slot maps and zeroes the slot's
state rows, so stream churn never retraces.  A slot's answer is the mean of
its per-sample losses (reconstruction MSE, or a token's surprisal).

A family whose state holds a cache (``StreamModel.cached``) gives each slot
room for ``max_seq_len`` samples; the cache length of a slot is its step
count, and a stream that has filled it is refused
(:class:`SequenceFullError`), never wrapped.

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
import threading
from collections import deque
from queue import SimpleQueue
from typing import Callable, Hashable, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.base import Engine
from repro.gateway.telemetry import Telemetry
from repro.models.api import StreamModel

logger = logging.getLogger(__name__)


class PoolFullError(RuntimeError):
    """Admission rejected: every slot is occupied (the gateway's
    fixed-capacity admission contract — callers shed or retry)."""


class UnknownStreamError(KeyError):
    """A stream id that is not resident in the pool."""


class SequenceFullError(RuntimeError):
    """A stream's cache holds ``max_seq_len`` samples: it takes no more."""


#: steps whose launch and wait times :attr:`SessionPool.device_bound` averages
TIMING_WINDOW = 8


class StepLaunch:
    """One pool step dispatched by :meth:`SessionPool.launch`: the streams
    it steps and their slots, its errors on their way to the host, and its
    timing (``launch_ms`` the dispatch, ``loop_ms`` the caller's whole
    launch, clock readings at the launch's end and once the errors are on
    the host)."""

    __slots__ = ("sids", "slots", "errors", "launch_ms", "t_launched",
                 "loop_ms", "host", "exc", "t_ready")

    def __init__(self, sids, slots, errors, launch_ms: float, t_launched: float):
        self.sids = sids
        self.slots = slots
        self.errors = errors
        self.launch_ms = launch_ms
        self.t_launched = t_launched
        self.loop_ms = 0.0
        self.host = None
        self.exc: Optional[BaseException] = None
        self.t_ready: Optional[float] = None


def pool_step_program(model: StreamModel) -> Callable:
    """The pool's step as one program of ``(params, x, state, mask,
    sq_sum, steps)``: the model's masked step, and each stepped row's loss
    added to its running sum (``_pool_step``, the name the device trace
    reads)."""
    def _pool_step(params, x, state, mask, sq_sum, steps):
        state, loss = model.step(params, x, state, mask, steps)
        sq_sum = sq_sum + jnp.where(mask, loss, 0.0)
        steps = steps + mask.astype(jnp.int32)
        return state, sq_sum, steps

    return _pool_step


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
        max_seq_len: int = 1024,
    ):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.engine = engine
        self.capacity = capacity
        model = self.model = engine.stream_model
        self.sample_shape = model.sample_shape
        self.sample_dtype = model.sample_dtype
        self.features = int(np.prod(model.sample_shape))
        self.max_seq_len = max_seq_len
        self.telemetry = telemetry or Telemetry()
        # the pool always lays its block out on the ENGINE's placement —
        # the masked-step programs and the slot state must agree on one
        # layout (re-place via Engine.with_placement, not a pool knob)
        self.placement = engine.placement
        # the state block pads up to a per-device multiple; the padding rows
        # are never admitted (logical capacity stays exactly ``capacity``)
        self._block = self.placement.pad_rows(capacity)
        self.slots_per_device = self._block // self.placement.data_shards

        self._state = model.init_state(self._block, max_seq_len)
        self._sq_sum = jnp.zeros((self._block,), jnp.float32)
        self._steps = jnp.zeros((self._block,), jnp.int32)
        # host copy of each slot's step count (its cache length), kept
        # only where a cache bounds it
        self._length = np.zeros((self._block,), np.int64) if model.cached else None
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
        # host launch and device wait of the last few steps (device_bound)
        self._launch_ms: deque = deque(maxlen=TIMING_WINDOW)
        self._wait_ms: deque = deque(maxlen=TIMING_WINDOW)

        _pool_step = pool_step_program(model)

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
                donate_argnums=(2, 4, 5),
            )
            self._clear_slot = jax.jit(
                _clear_slot,
                in_shardings=(rows, rows, rows, repl),
                out_shardings=(rows, rows, rows),
                donate_argnums=(0, 1, 2),
            )
            self._load_slot = jax.jit(
                _load_slot,
                in_shardings=(rows, rows, rows, repl, repl, repl, repl),
                out_shardings=(rows, rows, rows),
                donate_argnums=(0, 1, 2),
            )
            self._state = jax.device_put(self._state, rows)
            self._sq_sum = jax.device_put(self._sq_sum, rows)
            self._steps = jax.device_put(self._steps, rows)
        else:
            # the state block is donated: a step, a clear or a load writes
            # the block in place instead of holding a second copy
            self._pool_step = (jax.jit(_pool_step, donate_argnums=(2, 4, 5))
                               if use_jit else _pool_step)
            self._clear_slot = (jax.jit(_clear_slot, donate_argnums=(0, 1, 2))
                                if use_jit else _clear_slot)
            self._load_slot = (jax.jit(_load_slot, donate_argnums=(0, 1, 2))
                               if use_jit else _load_slot)

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
        if self._length is not None:
            self._length[slot] = 0

    def check_room(self, stream_id: Hashable) -> None:
        """Raise :class:`SequenceFullError` (counted as ``pool.kv_full``)
        when the stream's cache holds ``max_seq_len`` samples."""
        slot = self._require(stream_id)
        if self._length is not None and self._length[slot] >= self.max_seq_len:
            self.telemetry.count("pool.kv_full")
            raise SequenceFullError(
                f"stream {stream_id!r} holds max_seq_len={self.max_seq_len} "
                f"samples; evict it (or open the gateway with a larger "
                f"max_seq_len) to go on")

    def check_samples(self, samples) -> np.ndarray:
        """``samples`` as an array of the model's sample dtype, ``(k,) +
        sample_shape``; ValueError on a shape, dtype or value the model
        cannot take."""
        arr = np.asarray(samples)
        if arr.dtype != self.sample_dtype:
            raise ValueError(
                f"samples must be {self.sample_dtype.name} for "
                f"{self.engine.cfg.name}, got {arr.dtype.name}")
        if arr.ndim < 1 or arr.shape[1:] != self.sample_shape:
            raise ValueError(
                f"expected samples of shape (k,) + {self.sample_shape}, "
                f"got {arr.shape}")
        if self.model.check is not None:
            self.model.check(arr)
        return arr

    # -- stepping ---------------------------------------------------------

    def step(self, inputs: Mapping[Hashable, "np.ndarray"]) -> dict:
        """Advance every stream in ``inputs`` one timestep.

        ``inputs`` maps resident stream ids to their next sample ``(F,)``;
        any subset of residents may step (the rest carry unchanged).
        Returns {stream_id: running mean error so far} for stepped streams.
        The synchronous form of :meth:`launch` then :meth:`collect`, in one
        ``pool.step`` span.
        """
        if not inputs:
            return {}
        with self.telemetry.span("pool.step", "pool_step_ms"):
            return self._collect(self._launch(inputs, chained=False))

    def launch(self, inputs: Mapping[Hashable, "np.ndarray"],
               chained: bool = False) -> "StepLaunch":
        """Dispatch one step of the non-empty ``inputs`` without waiting for
        it: the errors start their copy to the host, :meth:`wait` blocks
        until they are there and :meth:`collect` hands them back.  Until
        then the pool's state is the step's output, so a caller that reads
        it (an export, an error) waits for the step.  ``chained`` counts
        the step as launched ahead of the previous step's answers
        (``pool.steps_chained``)."""
        tel = self.telemetry
        t0 = tel.now()
        with tel.span("pool.step"):
            handle = self._launch(inputs, chained)
        handle.loop_ms = (tel.now() - t0) * 1e3
        return handle

    def wait(self, handle: "StepLaunch") -> None:
        """Block until ``handle``'s errors are on the host, as the span
        ``pool.readback``.  Safe on any thread: it touches the handle and
        the profiler's annotation only; a failure of the step is kept for
        :meth:`collect` to raise."""
        with self.telemetry.span("pool.readback"):
            try:
                handle.host = np.asarray(handle.errors)
            except Exception as exc:
                handle.exc = exc
        handle.t_ready = self.telemetry.now()

    def collect(self, handle: "StepLaunch") -> dict:
        """{stream_id: running mean error} of a launched step, waiting for
        it here unless :meth:`wait` has.  Its ``pool_step_ms`` sample is the
        caller's own time for the step: the launch and this call."""
        tel = self.telemetry
        t0 = tel.now()
        with tel.span("pool.step"):
            try:
                return self._collect(handle)
            finally:
                tel.observe_stage("pool_step_ms",
                                  handle.loop_ms + (tel.now() - t0) * 1e3)

    @property
    def device_bound(self) -> bool:
        """Whether the device is the longer pole: over the last few steps
        the mean wait for a step's errors (from the launch's end until
        they are on the host) exceeds the mean launch.  False until a step
        has been timed."""
        return sum(self._wait_ms) > sum(self._launch_ms)

    def _launch(self, inputs, chained: bool) -> "StepLaunch":
        tel = self.telemetry
        with tel.span("pool.assemble", "pool_assemble_ms"):
            slots = [self._require(sid) for sid in inputs]
            x = np.zeros((self._block,) + self.sample_shape, self.sample_dtype)
            mask = np.zeros((self._block,), bool)
            for sid, slot in zip(inputs, slots):
                sample = np.asarray(inputs[sid], self.sample_dtype)
                if sample.shape != self.sample_shape:
                    raise ValueError(
                        f"stream {sid!r}: expected sample shape "
                        f"{self.sample_shape}, got {sample.shape}"
                    )
                x[slot] = sample
                mask[slot] = True
            if self.model.check is not None:
                self.model.check(x[mask])
            if self._length is not None:
                for sid in inputs:
                    self.check_room(sid)
        with tel.span("pool.launch", "pool_launch_ms") as launch:
            # dispatch of the step and of the error read's three ops, and
            # the start of the errors' copy to the host; JAX returns
            # without waiting for their results
            self._state, self._sq_sum, self._steps = self._pool_step(
                self.engine._require_params(), jnp.asarray(x), self._state,
                jnp.asarray(mask), self._sq_sum, self._steps,
            )
            errors = self.errors()
            errors.copy_to_host_async()
        handle = StepLaunch(list(inputs), slots, errors, launch.ms, tel.now())
        tel.record_pool_step(len(slots), self.capacity, chained)
        if self._length is not None:
            stepped = self._length[slots] + 1
            self._length[slots] = stepped
            tel.record_pool_kv(int(stepped.sum()), len(slots) * self.max_seq_len)
        return handle

    def _collect(self, handle: "StepLaunch") -> dict:
        if handle.t_ready is None:
            with self.telemetry.span("pool.readback", "pool_readback_ms") as wait:
                # waits for the errors and copies them to the host
                errs = np.asarray(handle.errors)
            wait_ms = wait.ms
        else:
            if handle.exc is not None:
                raise handle.exc
            errs = handle.host
            wait_ms = (handle.t_ready - handle.t_launched) * 1e3
            self.telemetry.observe_stage("pool_readback_ms", wait_ms)
        self._launch_ms.append(handle.launch_ms)
        self._wait_ms.append(wait_ms)
        return {sid: float(errs[slot]) for sid, slot in zip(handle.sids, handle.slots)}

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
        if self._length is not None:
            self._length[slot] = steps
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
    step takes the head sample of each stream that has one and hands each
    running error back.  A step carries whatever arrived — nothing waits
    on a timer, so a lone stream steps alone as before — and rows are
    independent through the cell (the contract above), so each error is
    bit-for-bit what the stream sees stepped alone.  A frame of ``k``
    samples takes ``k`` steps; frames of one stream step in arrival order.

    The flush steps in one of two ways, by what the pool observes
    (:attr:`SessionPool.device_bound`), with the pool's first steps
    synchronous:

    * **synchronous**, while the host launch is the longer pole: steps
      one after another until every FIFO is empty, each waited for on the
      caller's thread;
    * **chained**, while the wait for the device is: at most one step in
      flight.  The flush launches it and returns; a waiter thread blocks
      on its errors and hands its completion to ``post`` (the server
      passes ``loop.call_soon_threadsafe``).  On completion the next step
      is launched over every sample waiting (counted
      ``pool.steps_chained``), and only then are the finished step's
      frames answered, so the loop reads, decodes and answers while the
      device computes.  Each step is launched after the previous one's
      errors are on the host, so the donated state is never read by a
      pending step.  Without ``post`` every flush is synchronous.

    ``on_done(errors, exc)`` is called once per frame with the running
    errors of its samples that were stepped: all ``k`` and ``exc=None``
    once the last is known.  An engine exception fails every frame of
    that step with it (their remaining samples are dropped, the other
    streams step on), and a stream evicted while its samples wait (or
    while its step is in flight) is skipped, its frames failed with
    :class:`UnknownStreamError`.  :meth:`settle` answers everything queued
    before it returns.
    """

    def __init__(self, pool: SessionPool,
                 schedule: Callable[[Callable[[], None]], object],
                 post: Optional[Callable[..., object]] = None):
        self.pool = pool
        self._schedule = schedule
        self._post = post
        self._queues: dict[Hashable, deque] = {}
        self._due = False
        # the chained step in flight: (its StepLaunch, its head frames)
        self._inflight: Optional[tuple] = None
        self._handoff: SimpleQueue = SimpleQueue()
        self._waiter: Optional[threading.Thread] = None

    @property
    def pending(self) -> int:
        """Streams with samples waiting."""
        return len(self._queues)

    def submit(self, stream_id: Hashable, xs: "np.ndarray",
               on_done: Callable) -> None:
        """Queue the ``(k,) + sample_shape`` samples ``xs`` of a resident
        stream."""
        self._queues.setdefault(stream_id, deque()).append(_Pending(xs, on_done))
        if not self._due:
            self._due = True
            self._schedule(self.flush)

    def flush(self) -> None:
        """This pass's step: nothing while a step is in flight (its
        completion launches what waits), else one chained launch or
        synchronous steps until no sample waits."""
        self._due = False
        if self._inflight is not None:
            return
        if self._post is not None and self.pool.device_bound:
            self._finish(self._launch(chained=False))
        else:
            self._step_sync()

    def settle(self) -> None:
        """Step every waiting sample before returning: the step in flight
        is waited for and its frames answered, then the rest step
        synchronously."""
        if self._inflight is not None:
            self._finish(self._land())
        self._step_sync()

    def close(self) -> None:
        """:meth:`settle`, then stop the waiter thread (a later chained
        step starts another)."""
        self.settle()
        waiter, self._waiter = self._waiter, None
        if waiter is not None:
            self._handoff.put(None)
            waiter.join()

    def _heads(self) -> tuple[dict, list]:
        """The head frame of each waiting stream that can step, and the
        frames that cannot, with their exception: every frame of a stream
        no longer resident, the head frame of one whose cache is full."""
        heads, failed = {}, []
        cached = self.pool.model.cached
        for sid in list(self._queues):
            if sid not in self.pool:
                gone = UnknownStreamError(
                    f"stream {sid!r} was evicted while its samples waited")
                failed += [(job, gone) for job in self._queues.pop(sid)]
                continue
            if cached:
                try:
                    self.pool.check_room(sid)
                except SequenceFullError as full:
                    failed.append((self._pop(sid), full))
                    continue
            heads[sid] = self._queues[sid][0]
        return heads, failed

    def _step_sync(self) -> None:
        while self._queues:
            heads, failed = self._heads()
            self._finish(failed)
            if not heads:
                return
            try:
                running = self.pool.step(
                    {sid: job.xs[job.next] for sid, job in heads.items()})
            except Exception as exc:
                # the batcher's ticket semantics: this step's frames answer
                # the error, every other sample still steps
                self._finish([(self._pop(sid), exc) for sid in heads])
                continue
            self._finish(self._advance(heads, running))

    def _launch(self, chained: bool) -> list:
        """Launch one step over the head sample of every waiting stream and
        hand it to the waiter thread; returns the frames that failed
        instead, with their exception, to be answered by the caller."""
        failed: list = []
        while self._queues:
            heads, cannot = self._heads()
            failed += cannot
            if not heads:
                break
            try:
                handle = self.pool.launch(
                    {sid: job.xs[job.next] for sid, job in heads.items()},
                    chained)
            except Exception as exc:
                failed += [(self._pop(sid), exc) for sid in heads]
                continue
            self._inflight = (handle, heads)
            if self._waiter is None:
                self._waiter = threading.Thread(
                    target=self._await_steps, name="pool-step-waiter",
                    daemon=True)
                self._waiter.start()
            self._handoff.put(handle)
            break
        return failed

    def _await_steps(self) -> None:
        """The waiter thread: block until each handed step's errors are on
        the host, then post its completion to the owner's thread."""
        while True:
            handle = self._handoff.get()
            if handle is None:
                return
            self.pool.wait(handle)
            try:
                self._post(self._on_ready, handle)
            except RuntimeError:  # the loop has closed: nobody to answer
                logger.debug("step completion not posted", exc_info=True)

    def _on_ready(self, handle: StepLaunch) -> None:
        """A chained step's errors are on the host: launch the next step
        over what waits if the device is still the longer pole, then
        answer the finished step's frames (else answer them and step the
        rest synchronously)."""
        if self._inflight is None or self._inflight[0] is not handle:
            return  # settle() has taken this step already
        done = self._land()
        if self._queues and self.pool.device_bound:
            done += self._launch(chained=True)
        self._finish(done)
        if self._inflight is None:
            self._step_sync()

    def _land(self) -> list:
        """Collect the step in flight: the frames it completes or fails,
        with their exception, to be answered by the caller."""
        handle, heads = self._inflight
        self._inflight = None
        try:
            running = self.pool.collect(handle)
        except Exception as exc:
            return [(self._pop(sid), exc) for sid in heads]
        return self._advance(heads, running)

    def _advance(self, heads: dict, running: dict) -> list:
        """Each head frame takes its sample's running error (a stream
        evicted meanwhile is skipped); returns the frames now complete."""
        done = []
        for sid, job in heads.items():
            if sid not in self.pool:
                continue
            job.errors[job.next] = running[sid]
            job.next += 1
            if job.next == len(job.xs):
                done.append((self._pop(sid), None))
        return done

    @staticmethod
    def _finish(frames: list) -> None:
        for job, exc in frames:
            job.finish(exc)

    def _pop(self, stream_id: Hashable) -> _Pending:
        queue = self._queues[stream_id]
        job = queue.popleft()
        if not queue:
            del self._queues[stream_id]
        return job
