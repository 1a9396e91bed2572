"""Device-side input prefetcher: overlapped H2D for the train step loop.

The jitted train step is one donated XLA program (train/train_step.py), but
feeding it an inline ``jnp.asarray`` stalls that program every step on a
synchronous host→device copy — step N's compute never overlaps batch N+1's
transfer, or (under a mesh) its resharding at dispatch. Production TPU
stacks hide exactly this latency (MegaScale-style compute/transfer overlap;
tf.data-style pipelined input). This module restores it: a background
thread pulls host batches from any loader with the ``generate_batch(step)``
surface (data/memory.py, data/streaming.py, data/token_shards.py), issues
``jax.device_put`` with the explicit ``NamedSharding(mesh, batch_pspec)``
the jitted step expects — so jit never re-shards at dispatch — and keeps up
to ``depth`` batches already resident on device. The step loop's ``get()``
then returns immediately in steady state, and its ``data_wait_s`` measures
the only true input stall.

Checkpoint contract (PR 3 resume depends on it): ``state_dict()`` reflects
the position of the last batch the TRAINER consumed via ``get()`` —
batches sitting in the device queue have not been trained on and must not
advance the saved position. This is the same contract as
``StreamingDataManager.state_dict`` (streaming.py), which snapshots the
last *served* batch; stream-stateful loaders advertise it via the
``stream_stateful`` class attribute and the worker snapshots
``loader.state_dict()`` after each fetch so the consumer can expose the
consumed one. Loaders whose ``generate_batch`` is a pure function of the
step (memory/token_shards) carry no stream position — for those
``state_dict()`` delegates live so e.g. validation pointers stay current.

``depth <= 0`` selects the synchronous mode: no worker thread; each
``get()`` fetches and transfers inline. Same code path, same sharding,
same batch sequence — the parity tests pin prefetch on == off losses.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Dict, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding

from ..parallel.sharding_rules import batch_pspec


class DevicePrefetcher:
    """Wraps a host loader and serves device-resident, pre-sharded batches.

    ``get()`` returns ``(device_batch, local_tokens, waits)`` for data
    steps ``start_step``, ``start_step+1``, ... — matching the trainer's
    ``generate_batch(step - 1)`` convention.
    """

    def __init__(
        self,
        loader: Any,
        mesh: Any = None,
        depth: int = 2,
        start_step: int = 0,
        total_steps: Optional[int] = None,
        metrics: Any = None,
    ):
        self.loader = loader
        self.mesh = mesh
        self.depth = int(depth)
        self.total_steps = total_steps  # None: run until StopIteration
        # Optional obs.MetricsRegistry: input-pipeline health lands in the
        # same registry the trainer exports (counters/histograms, no dicts).
        self._m_batches = self._m_queue = self._m_data_wait = self._m_h2d = None
        if metrics is not None:
            self._m_batches = metrics.counter(
                "input_batches_total", "batches served to the step loop")
            self._m_queue = metrics.gauge(
                "input_queue_depth", "device-resident batches ready to consume")
            self._m_data_wait = metrics.histogram(
                "input_data_wait_seconds", "step-loop stall waiting for input")
            self._m_h2d = metrics.histogram(
                "input_h2d_seconds", "host-to-device transfer time per item")

        self._stateful = bool(getattr(loader, "stream_stateful", False))
        # Captured before the worker starts fetching: a checkpoint taken
        # before anything is consumed must not see worker-advanced state.
        self._initial_state = loader.state_dict() if self._stateful else None
        self._consumed_state: Optional[Dict[str, Any]] = None  # graftsync: owner=trainer-thread

        self._sharding = None
        if mesh is not None:
            self._sharding = NamedSharding(mesh, batch_pspec(mesh))

        # next trainer step to feed
        self._cursor = int(start_step) + 1  # graftsync: owner=prefetch-worker
        self._done = False  # graftsync: owner=prefetch-worker
        # Consumer-side latch: once an end/error item is consumed the worker
        # has exited, so a further queue.get() would block forever — repeat
        # the terminal outcome instead.
        self._terminal: Optional[Dict[str, Any]] = None  # graftsync: owner=trainer-thread

        self._queue: Optional[queue.Queue] = None
        self._stop_evt = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # In synchronous mode (depth=0) the H2D transfer blocks the step
        # loop, so h2d_wait_s is real wall time; with a worker thread the
        # transfer overlaps compute and any residual stall already shows
        # up in data_wait_s (items reach the queue post-transfer). Goodput
        # accounting keys off this to avoid double-booking wall time.
        self.h2d_blocks_consumer = self.depth <= 0
        if self.depth > 0:
            self._queue = queue.Queue(maxsize=self.depth)
            self._thread = threading.Thread(
                target=self._worker, daemon=True, name="device-prefetch")
            self._thread.start()

    # -- producer ------------------------------------------------------------

    def _produce_one(self) -> Dict[str, Any]:
        """Fetch the next host batch, transfer, advance the cursor.
        Returns a queue item; never raises (errors become items so they
        surface at the consumer's ``get()``, not in the thread)."""
        if self._done or (
                self.total_steps is not None and self._cursor > self.total_steps):
            self._done = True
            return {"kind": "end"}
        step = self._cursor
        snapshot = None
        t0 = time.perf_counter()
        # Annotations on this thread: in a profile a gap under the loop's
        # train.data_get is the read (prefetch.fetch) or the copy (prefetch.h2d).
        try:
            with TraceAnnotation("prefetch.fetch", step=step):
                batch = self.loader.generate_batch(step - 1)
                if self._stateful:
                    snapshot = self.loader.state_dict()
        except StopIteration:
            self._done = True
            return {"kind": "end"}
        except Exception as exc:  # producer errors (e.g. streaming RuntimeError)
            self._done = True
            return {"kind": "error", "error": exc}
        fetch_s = time.perf_counter() - t0
        # Host-side token count (non-pad targets) — off the critical path
        # here, so tok/s stays correct even though device metrics are only
        # read every logging_interval steps.
        tokens = int(batch["mask"].sum())
        t0 = time.perf_counter()
        with TraceAnnotation("prefetch.h2d", step=step):
            dev = self._transfer(batch, self._sharding)
            # Block HERE, in the worker: the consumer's get() never waits on
            # the copy.
            jax.block_until_ready(dev)
        h2d_s = time.perf_counter() - t0
        self._cursor = step + 1
        return {
            "kind": "batch",
            "batch": dev,
            "tokens": tokens,
            "snapshot": snapshot,
            "fetch_s": fetch_s,
            "h2d_s": h2d_s,
        }

    def _transfer(self, host_batch: Dict[str, np.ndarray], sharding):
        if sharding is not None and jax.process_count() > 1 and hasattr(
                jax, "make_array_from_process_local_data"):
            # Multi-host: each process holds only its local rows; assemble
            # the global sharded array from per-process shards.
            return {k: jax.make_array_from_process_local_data(sharding, v)
                    for k, v in host_batch.items()}
        if sharding is not None:
            return jax.device_put(host_batch, sharding)
        return jax.device_put(host_batch)

    def _worker(self) -> None:  # graftsync: owner=prefetch-worker
        while not self._stop_evt.is_set():
            item = self._produce_one()
            while not self._stop_evt.is_set():
                try:
                    self._queue.put(item, timeout=0.2)
                    break
                except queue.Full:
                    continue
            if item["kind"] in ("end", "error"):
                return

    # -- consumer ------------------------------------------------------------

    def get(self):  # graftsync: owner=trainer-thread
        """Next device-resident batch: ``(batch, tokens, waits)``.

        ``tokens`` is this host's non-pad target count. ``waits`` carries
        ``data_wait_s`` (time this call blocked waiting for input — the
        true stall), ``h2d_wait_s`` (host→device transfer time for the
        item: overlapped with compute when the worker thread is running, on
        the critical path in synchronous mode) and ``queue_depth`` (batches
        that were ready when this call asked; None without a worker). Raises
        StopIteration at end of stream; re-raises loader errors.
        """
        depth = self._queue.qsize() if self._queue is not None else None
        if self._terminal is not None:
            item = self._terminal
            data_wait = 0.0
        elif self._queue is None:
            item = self._produce_one()
            data_wait = item.get("fetch_s", 0.0)
        else:
            t0 = time.perf_counter()
            item = self._queue.get()
            data_wait = time.perf_counter() - t0
        if item["kind"] == "error":
            self._terminal = item
            raise item["error"]
        if item["kind"] == "end":
            self._terminal = item
            raise StopIteration("stream exhausted")
        if item["snapshot"] is not None:
            self._consumed_state = item["snapshot"]
        if self._m_batches is not None:
            self._m_batches.inc()
            self._m_data_wait.observe(data_wait)
            self._m_h2d.observe(item["h2d_s"])
            if self._queue is not None:
                self._m_queue.set(self._queue.qsize())
        return item["batch"], item["tokens"], {
            "data_wait_s": data_wait, "h2d_wait_s": item["h2d_s"], "queue_depth": depth}

    # -- loader surface ------------------------------------------------------

    def state_dict(self) -> Dict[str, Any]:
        """Loader position as CONSUMED by the trainer (see module
        docstring). Stream-stateful loaders get the snapshot taken right
        after the last consumed batch's fetch; pure-function-of-step
        loaders delegate live. Snapshots are stamped with the world they
        were taken under (``process_count``/``process_index``) so an
        elastic resume can detect and remap a mismatched world instead of
        silently double-consuming documents."""
        if self._stateful:
            state = (dict(self._consumed_state)
                     if self._consumed_state is not None
                     else dict(self._initial_state))
        else:
            state = self.loader.state_dict()
        if isinstance(state, dict):
            for key in ("process_count", "process_index"):
                stamp = getattr(self.loader, key, None)
                if stamp is not None:
                    state.setdefault(key, int(stamp))
        return state

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        self.loader.load_state_dict(state)

    def stop(self) -> None:
        """Stop the worker thread. Does NOT stop the wrapped loader — the
        trainer owns the loader's lifecycle (it may still run validation)."""
        self._stop_evt.set()
        if self._queue is not None:
            try:
                while True:
                    self._queue.get_nowait()
            except queue.Empty:
                pass
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
