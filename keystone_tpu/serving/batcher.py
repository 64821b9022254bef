"""Continuous micro-batching onto the certified pad ladder.

The headline perf mechanism of the serving runtime: concurrent
single-item requests land in a BOUNDED ingress queue (depth =
``KEYSTONE_SERVING_QUEUE_DEPTH``, the KJ019 discipline) and a single
dispatcher thread coalesces them — up to the certified envelope's
``max_batch``, within a ``KEYSTONE_SERVING_WINDOW_MS`` window — into
one stacked batch per dispatch. The batcher never pads: it hands the
executor a batch of n ≤ max_batch ≤ chunk rows, and the dispatcher's
`ServingRuntime._apply_batch` pads that to the next pow-2 rung (the
`_pad_target` arithmetic) and slices the riders back out — exactly the
ladder the KP9xx certificate was issued against and
`warmup_manifest()` pre-compiled, so a warm server only ever runs
pre-compiled programs, even for ragged coalesced counts. Throughput scales with the coalesced
batch size because the per-apply fixed cost (executor bind + program
lookup, the certificate's APPLY_FLOOR) is amortized over every rider.

Overload is shed, not buffered: a full queue rejects the request with
`ShedError`, bumps ``serving.shed_total`` and dumps the flight ring
(`tag="shed"`) so the overload interval is diagnosable after the fact.

Kill switch: ``KEYSTONE_SERVING_COALESCE=0`` bypasses the queue and
dispatcher entirely — `submit` applies the single-row batch inline on
the caller's thread, which is bit-for-bit the direct
`FittedPipeline.apply` path (same rows, same pad rung for n=1, same
program). The ≥4× bench delta is measured against exactly this mode.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, List, Optional

import numpy as np

from ..telemetry.flight import flight_snapshot
from ..telemetry.metrics import counter, gauge, histogram
from ..telemetry.spans import span
from ..workflow.env import execution_config


class ShedError(RuntimeError):
    """Raised at submit time when the bounded ingress queue is full —
    the load-shed discipline: overload is refused immediately, never
    buffered into unbounded memory or unbounded queueing delay."""


class _Pending:
    """One in-flight request: the validated ingress row, an event the
    dispatcher fires once the per-row result (or error) lands, and the
    batcher's clock at `submit` and when the dispatcher thread took the
    request off the queue (its two waits end at the dispatch)."""

    __slots__ = ("row", "done", "result", "error", "t_submit", "t_taken")

    def __init__(self, row: np.ndarray, t_submit: float):
        self.row = row
        self.done = threading.Event()
        self.result: Optional[np.ndarray] = None
        self.error: Optional[BaseException] = None
        self.t_submit = t_submit
        self.t_taken = t_submit


class MicroBatcher:
    """Coalesce concurrent single-row requests into ladder-shaped
    batches for ``apply_fn`` (which must map a stacked ``(n, ...)``
    batch to an ``(n, ...)`` result)."""

    def __init__(self, apply_fn: Callable[[np.ndarray], np.ndarray], *,
                 max_batch: int, name: str = "serving",
                 clock: Callable[[], float] = time.perf_counter):
        cfg = execution_config()
        self.apply_fn = apply_fn
        self._clock = clock  # a test hands in a fake
        self.max_batch = max(1, int(max_batch))
        self.coalesce = bool(cfg.serving_coalesce)
        self.window_s = float(cfg.serving_window_ms) / 1e3
        # bounded by construction — KJ019 forbids the unbounded form in
        # this package precisely so overload becomes a shed, not an OOM
        self.depth = int(cfg.serving_queue_depth)
        self._queue: "queue.Queue[Optional[_Pending]]" = queue.Queue(
            maxsize=self.depth)
        self._shed = counter("serving.shed_total")
        self._depth_gauge = gauge("serving.queue_depth")
        self._coalesced = histogram("serving.coalesced_batch")
        self._dispatched = counter("serving.dispatches")
        # a request's two waits, summed over requests: submit to the
        # dispatcher taking it off the queue, and from there to the call
        # of `apply_fn` on its batch (the coalescing window)
        self._rows = counter("serving.rows_dispatched")
        self._queue_wait = counter("serving.queue_wait_seconds")
        self._coalesce_wait = counter("serving.coalesce_wait_seconds")
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._stopping = False
        self._name = name

    # -- lifecycle ----------------------------------------------------

    def start(self) -> "MicroBatcher":
        if self.coalesce and self._thread is None:
            self._stopping = False
            self._thread = threading.Thread(
                target=self._run, name=f"{self._name}-batcher", daemon=True)
            self._thread.start()
        return self

    def stop(self, timeout: float = 10.0) -> None:
        thread = self._thread
        if thread is None:
            return
        self._stopping = True
        try:
            self._queue.put(None, timeout=timeout)
        except queue.Full:
            pass
        thread.join(timeout=timeout)
        self._thread = None

    # -- request path -------------------------------------------------

    def submit(self, row: np.ndarray, timeout: Optional[float] = None
               ) -> np.ndarray:
        """Block until the row's result is available; raises `ShedError`
        when the ingress queue is full and re-raises any dispatch
        error."""
        if not self.coalesce or self._thread is None:
            # kill-switch path: per-request dispatch on the caller's
            # thread — identical to direct FittedPipeline.apply
            with span("dispatch", cat="serve", layer="serve", rows=1):
                out = self.apply_fn(row[np.newaxis, ...])
            self._dispatched.inc()
            self._rows.inc()
            self._coalesced.observe(1)
            return np.asarray(out)[0]
        pending = _Pending(row, self._clock())
        try:
            self._queue.put_nowait(pending)
        except queue.Full:
            self._shed.inc()
            flight_snapshot(tag="shed")
            raise ShedError(
                f"ingress queue full (depth={self.depth}) — request shed")
        self._depth_gauge.set(self._queue.qsize())
        if not pending.done.wait(timeout):
            raise TimeoutError("request timed out awaiting dispatch")
        if pending.error is not None:
            raise pending.error
        assert pending.result is not None
        return pending.result

    # -- dispatcher ---------------------------------------------------

    def _drain_batch(self) -> List[Optional[_Pending]]:
        """Block for the first request, then coalesce followers until
        the envelope's max_batch or the window closes."""
        first = self._queue.get()
        batch: List[Optional[_Pending]] = [first]
        if first is None:
            return batch
        first.t_taken = self._clock()
        deadline = time.monotonic() + self.window_s
        while len(batch) < self.max_batch:
            remaining = deadline - time.monotonic()
            try:
                if remaining <= 0:
                    item = self._queue.get_nowait()
                else:
                    item = self._queue.get(timeout=remaining)
            except queue.Empty:
                break
            batch.append(item)
            if item is None:
                break
            item.t_taken = self._clock()
        return batch

    def _run(self) -> None:
        while True:
            batch = self._drain_batch()
            stop = batch and batch[-1] is None
            requests = [p for p in batch if p is not None]
            self._depth_gauge.set(self._queue.qsize())
            if requests:
                self._dispatch(requests)
            if stop or self._stopping:
                return

    def _dispatch(self, requests: List[_Pending]) -> None:
        stacked = np.stack([p.row for p in requests])
        self._coalesced.observe(len(requests))
        self._dispatched.inc()
        self._rows.inc(len(requests))
        now = self._clock()
        self._queue_wait.inc(sum(p.t_taken - p.t_submit for p in requests))
        self._coalesce_wait.inc(sum(now - p.t_taken for p in requests))
        try:
            with span("dispatch", cat="serve", layer="serve",
                      rows=len(requests)):
                out = np.asarray(self.apply_fn(stacked))
            if out.shape[0] < len(requests):
                raise RuntimeError(
                    f"apply returned {out.shape[0]} rows for a batch of "
                    f"{len(requests)}")
            for i, p in enumerate(requests):
                p.result = out[i]
        except BaseException as e:  # noqa: BLE001 - fanned to callers
            for p in requests:
                p.error = e
        finally:
            for p in requests:
                p.done.set()
