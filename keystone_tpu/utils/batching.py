"""Host→device batching for variable-shape item collections, with an
async double-buffered dispatch engine.

The reference amortizes JVM→native costs by processing images
per-partition (ImageLoaderUtils.scala:56-94). The TPU analog: group a
`HostDataset`'s items by shape into buckets, stack each bucket, and run
ONE vmapped XLA dispatch per (shape, chunk) instead of one dispatch per
item — on a high-latency link the per-item path costs a full round trip
per image (VERDICT r1 item 8).

The overlap engine (this PR) removes the remaining serialization: the
serial path stacks chunk k, dispatches it, and BLOCKS on a host
``np.asarray`` pull before touching chunk k+1, so host stacking, the
host→device upload, device compute, and the device→host pull all take
turns. Overlapped (`workflow.env.execution_config().overlap`, default
on):

  - a background producer thread converts/stacks chunk k+1 and
    ``device_put``s it while the device runs chunk k, feeding a queue
    bounded at ``prefetch_depth`` (peak host memory stays
    O(depth × chunk) items);
  - the main thread only *dispatches* — jax's async dispatch returns
    device futures immediately — and keeps a sliding window of
    ``prefetch_depth + 1`` in-flight results, draining the oldest with
    ``np.asarray`` only when the window is full (total residency:
    ≤ depth queued + 1 being stacked + depth + 1 dispatched, i.e.
    ≤ 2·depth + 2 chunks — still O(depth), never O(n));
  - results come back in dispatch order, are re-placed in the original
    item order, and a producer exception re-raises in the caller
    (never a hang).

Single-chunk inputs fall back to the serial path (there is nothing to
overlap). `prefetch_iterator` is the same bounded producer-thread
pattern over any generator, reused by the archive/CIFAR loaders.

Shape-stable dispatch (`ExecutionConfig.pad_chunks`, default on): every
distinct stacked leading dim is a distinct XLA program, so a bucket's
ragged tail (`bucket_size % chunk`) used to compile its own program per
residue — pure compile tax. Tails are now zero-padded up to the chunk
size (power-of-two ladder below it, `_pad_target`), the batch fn runs at
the padded width, and `_split_result` slices the phantom rows off before
anything downstream sees them, so a stage executes ONE compiled program
per bucket shape regardless of item count. Both dispatch paths share the
stack/split helpers, so the (indices, results) chunk contract — union of
indices == range(len(items)), no phantoms — holds identically serial and
overlapped.
"""

from __future__ import annotations

import queue
import threading
from time import perf_counter
from typing import Callable, Iterable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ..telemetry import counter, dispatch, fn_label, gauge, histogram, span


class _ProducerError:
    """Sentinel carrying an exception out of a producer thread."""

    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


_DONE = object()


def _bounded_put(q: "queue.Queue", item, cancel: threading.Event) -> bool:
    """Put that can be cancelled while the queue is full (a consumer that
    stopped draining must not leave the producer blocked forever).
    Blocked time is the engine's *producer stall* — recorded so traces
    show when the device outruns host staging (and vice versa via the
    consumer-wait histogram)."""
    t0 = perf_counter()
    try:
        while not cancel.is_set():
            try:
                q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False
    finally:
        histogram("prefetch.producer_stall_s").observe(perf_counter() - t0)


def prefetch_iterator(
    it: Iterable, depth: Optional[int] = None
) -> Iterator:
    """Drain ``it`` in a background thread through a queue bounded at
    ``depth`` (default: config ``prefetch_depth``), yielding items in
    order. Producer exceptions re-raise at the consumer's next pull;
    closing the generator early cancels the producer. This is the
    loaders' decode-prefetch primitive: the producer does the blocking
    I/O (tar member reads, file reads) while the consumer decodes."""
    from ..workflow.env import execution_config

    cfg = execution_config()
    if not cfg.overlap:
        yield from it
        return
    if depth is None:
        depth = cfg.prefetch_depth
    q: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
    cancel = threading.Event()
    depth_gauge = gauge("prefetch.queue_depth")
    wait_hist = histogram("prefetch.consumer_wait_s")

    def producer():
        try:
            for item in it:
                # count BEFORE the put: the gauge can momentarily read
                # one high (the item in flight to the queue) but never
                # negative, and its max stays ≤ depth + 1
                depth_gauge.add(1)
                if not _bounded_put(q, (item,), cancel):
                    depth_gauge.add(-1)
                    return
        except BaseException as e:  # re-raised at the consumer
            _bounded_put(q, _ProducerError(e), cancel)
            return
        _bounded_put(q, _DONE, cancel)

    t = threading.Thread(
        target=producer, name="keystone-prefetch", daemon=True
    )
    t.start()
    try:
        while True:
            t0 = perf_counter()
            msg = q.get()
            wait_hist.observe(perf_counter() - t0)
            if msg is _DONE:
                break
            if isinstance(msg, _ProducerError):
                raise msg.exc
            depth_gauge.add(-1)
            yield msg[0]
    finally:
        cancel.set()
        # unwind the staged-count accounting for items the consumer never
        # pulled (early close), so the depth gauge returns to baseline —
        # best-effort: a producer mid-put can land one more item after
        # this drain, and the high-water mark is unaffected either way
        while True:
            try:
                msg = q.get_nowait()
            except queue.Empty:
                break
            if msg is not _DONE and not isinstance(msg, _ProducerError):
                depth_gauge.add(-1)


# --------------------------------------------------------------------------
# Chunk planning (shared by the serial and overlapped paths)


def _pad_target(n: int, chunk: Optional[int], bucket_n: int) -> int:
    """Leading-dim a chunk of ``n`` items pads to under shape-stable
    dispatch. A ragged tail of a bucket that fills at least one whole
    chunk rounds up to the chunk size, so every chunk of that bucket
    shares ONE compiled program; a bucket smaller than the chunk rounds
    up a power-of-two ladder (1, 2, 4, ... chunk) instead, so tiny
    buckets neither pay full-chunk padding waste nor compile one
    program per distinct item count."""
    if chunk is None or n == chunk:
        return n
    if bucket_n >= chunk:
        return chunk
    return min(chunk, 1 << max(0, n - 1).bit_length())


def _plan_chunks(
    items: Sequence, chunk: Optional[int], pad: bool = False
) -> List[Tuple[List[int], int]]:
    """Bucket item indices by shape, then split each bucket into
    ``(indices, pad_to)`` chunks. Dispatch count is
    Σ_buckets ceil(bucket_size / chunk), independent of item count
    within a chunk; with ``pad`` the pad target additionally makes the
    stacked leading dim shape-stable (`_pad_target` — the bucket size
    decides tail-of-full-bucket vs tiny-bucket-ladder, which is why the
    target is computed here, where the bucket structure is still
    known)."""
    buckets: dict = {}
    for i, x in enumerate(items):
        shape = x.shape if hasattr(x, "shape") else np.asarray(x).shape
        buckets.setdefault(shape, []).append(i)
    plan: List[Tuple[List[int], int]] = []
    for idxs in buckets.values():
        step = chunk or len(idxs)
        for start in range(0, len(idxs), step):
            part = idxs[start : start + step]
            pad_to = (_pad_target(len(part), chunk, len(idxs)) if pad
                      else len(part))
            plan.append((part, pad_to))
    return plan


def _stack_chunk(
    items: Sequence, part: List[int], pad_to: Optional[int] = None
) -> np.ndarray:
    """Stack a chunk's items, zero-padding the leading axis up to
    ``pad_to`` (shape-stable dispatch: a ragged tail reuses the full
    chunk's compiled program instead of compiling its own). Zero rows
    follow the `Dataset` padding convention; `_split_result` slices them
    off before any consumer sees them, so the validity contract is
    positional — rows [0, len(part)) are real, the rest are phantoms."""
    stacked = np.stack([np.asarray(items[i], np.float32) for i in part])
    if pad_to is not None and pad_to > len(part):
        widths = [(0, pad_to - len(part))] + [(0, 0)] * (stacked.ndim - 1)
        stacked = np.pad(stacked, widths)
    return stacked


def _split_result(res, part: List[int]) -> Tuple[List[int], List]:
    with span("chunk_pull", cat="sync", layer="sync"):
        res = np.asarray(res)  # the blocking device→host pull
    counter("overlap.bytes_pulled").inc(float(res.nbytes))
    # slice padded phantom rows off HERE, in the one place both dispatch
    # paths share: the indices/results yielded downstream always cover
    # exactly the chunk's real items
    return part, [res[j] for j in range(len(part))]


def _stream_serial(items, plan, batch_fn) -> Iterator[Tuple[List[int], List]]:
    """Pre-overlap behavior: stack → dispatch → blocking pull, one chunk
    at a time."""
    for i, (part, pad_to) in enumerate(plan):
        with span("chunk_serial", cat="chunk", idx=i, rows=len(part)):
            chunk = _stack_chunk(items, part, pad_to)
            # one program per (shape, chunk) dispatch
            with dispatch(fn_label(batch_fn)):
                res = batch_fn(chunk)
            out = _split_result(res, part)
        yield out


_device_put_warned = False


def _device_put_host(stacked: np.ndarray):
    """Upload a stacked chunk from the producer thread so the transfer
    overlaps the device's work on the previous chunk. Falls back to the
    host array when no device placement is possible (e.g. an
    uninitialized backend in a pure-host test) — warning ONCE, because a
    persistently failing upload (backend misconfiguration, device OOM
    while staging) silently moves the H2D transfer back into the
    dispatch path and erases the overlap win."""
    try:
        import jax

        return jax.device_put(stacked)
    except Exception as e:
        global _device_put_warned
        if not _device_put_warned:
            _device_put_warned = True
            import logging

            logging.getLogger(__name__).warning(
                "overlap dispatcher could not device_put a staged chunk "
                "(%s: %s); falling back to host arrays — the host→device "
                "upload will no longer overlap device compute",
                type(e).__name__, e)
        return stacked


def _stream_overlapped(
    items, plan, batch_fn, depth: int
) -> Iterator[Tuple[List[int], List]]:
    """Double-buffered dispatch: `prefetch_iterator` runs the
    stack-and-upload of chunk k+1 in its producer thread while chunk k
    runs; the consumer keeps ≤ ``depth + 1`` dispatched results in
    flight and drains the oldest in dispatch order (at depth=1 that is
    classic double buffering: one result being pulled while the next is
    on the device)."""
    from collections import deque

    # Per-stream producer-side chunk count (stacking + uploading +
    # queued): incremented when staging BEGINS, decremented when the
    # consumer receives the chunk — so `resident` below is THIS stream's
    # residency, not a mix of every concurrent prefetch queue, and the
    # documented ≤ 2·depth + 2 bound holds exactly: producer side
    # ≤ depth queued + 1 in hand, consumer side ≤ depth + 1 dispatched.
    # Locked: a lost cross-thread read-modify-write would drift the
    # count (and the exported residency series) permanently.
    staged_count = [0]
    staged_lock = threading.Lock()

    def _bump_staged(d: int) -> None:
        with staged_lock:
            staged_count[0] += d

    def _stage(idx_part):
        i, (part, pad_to) = idx_part
        _bump_staged(1)
        with span("chunk_stage", cat="chunk", idx=i, rows=len(part)):
            return part, _device_put_host(_stack_chunk(items, part, pad_to))

    staged = prefetch_iterator(
        (_stage(ip) for ip in enumerate(plan)), depth,
    )
    inflight: "deque" = deque()  # (part, device result future)
    label = fn_label(batch_fn)
    inflight_gauge = gauge("overlap.inflight_results")
    resident_gauge = gauge("overlap.resident_chunks")
    dispatched = counter("overlap.chunks_dispatched")

    def _note_residency():
        inflight_gauge.set(len(inflight))
        resident_gauge.set(len(inflight) + staged_count[0])

    def _drain(idx):
        part0, res0 = inflight.popleft()
        _note_residency()
        with span("chunk_drain", cat="chunk", idx=idx, rows=len(part0)):
            return _split_result(res0, part0)  # deferred pull, in order

    try:
        drained = 0
        for part, staged_chunk in staged:
            _bump_staged(-1)  # chunk left the producer side
            # async dispatch: returns immediately, device queues the work
            with dispatch(label):  # one program per dispatched chunk
                inflight.append((part, batch_fn(staged_chunk)))
            dispatched.inc()
            _note_residency()
            if len(inflight) > depth:
                yield _drain(drained)
                drained += 1
        while inflight:
            yield _drain(drained)
            drained += 1
    finally:
        staged.close()  # early exit / batch_fn failure cancels the producer


# --------------------------------------------------------------------------
# Megafused host dispatch: one program per bucket, chunk loop in-program
#
# With shape-stable padding (PR 5) every chunk of a bucket shares ONE
# leading-dim shape, so the per-chunk dispatch loop can move INSIDE the
# program: stack the bucket's padded chunks into a (n_chunks, pad, ...)
# array and run a single jitted `lax.scan` over the chunk axis. On a
# high-RTT link that turns ceil(n/chunk) round trips into one. The
# stacked input is freshly built here and owned by nobody else, so it IS
# donated to XLA (on backends that honor donation). Ineligible cases —
# single-chunk buckets, non-traceable (host-code) batch fns, padding off
# — keep the overlapped host-staging path unchanged.

#: id(batch_fn) -> (batch_fn strong ref, jitted scan program). Strong
#: refs on purpose: an id-keyed entry must never outlive its function
#: (GC id reuse would silently run the wrong program).
_MEGAFUSED_SCANNERS: dict = {}

#: id(batch_fn) -> batch_fn for fns whose scan trace failed once (host
#: code behind a jit-like facade): permanently back on the per-chunk
#: path. The strong ref pins the id so GC reuse can never exclude an
#: unrelated (traceable) fn; membership is identity-checked.
_MEGAFUSED_REJECTED: dict = {}

#: Cap on chunks stacked into one scan program. Bounds the megafused
#: path's residency at ~2 × trips × chunk rows (stacked input + scanned
#: output) instead of a whole bucket — a 10⁵-item bucket still streams,
#: it just does so 64 chunks per dispatch instead of one.
_MEGAFUSED_MAX_TRIPS = 64


def _megafused_scanner(batch_fn):
    ent = _MEGAFUSED_SCANNERS.get(id(batch_fn))
    if ent is not None and ent[0] is batch_fn:
        return ent[1]
    import jax
    from jax import lax

    def scan_all(stack):
        return lax.scan(lambda c, xb: (c, batch_fn(xb)), (), stack)[1]

    # CPU ignores donation (and warns); only donate where XLA honors it
    donate = (0,) if jax.default_backend() != "cpu" else ()
    # identity-memoized in _MEGAFUSED_SCANNERS: one compile per batch_fn
    jitted = jax.jit(scan_all, donate_argnums=donate)  # keystone: ignore[KJ006]
    if len(_MEGAFUSED_SCANNERS) >= 512:
        # bound the cache: evict the oldest entries (a dropped scanner
        # just re-jits next time, warm from the persistent cache)
        for stale in list(_MEGAFUSED_SCANNERS)[:256]:
            _MEGAFUSED_SCANNERS.pop(stale, None)
    _MEGAFUSED_SCANNERS[id(batch_fn)] = (batch_fn, jitted)
    return jitted


def _megafusable_batch_fn(batch_fn) -> bool:
    """Only jax-jitted callables (they expose ``lower``/``trace``) are
    provably traceable under the scan; arbitrary host callables would
    need a speculative trace whose side effects we cannot undo."""
    return (hasattr(batch_fn, "lower")
            and _MEGAFUSED_REJECTED.get(id(batch_fn)) is not batch_fn)


def _megafused_groups(items, plan):
    """Group plan entries into per-bucket stack runs: ``(entries,
    stackable)`` where ``stackable`` means >= 2 chunks sharing one
    padded width (the shape-stable contract megafusion scans over).
    Bucket runs are split at ``_MEGAFUSED_MAX_TRIPS`` chunks so one
    program never stacks an unbounded bucket (the residency cap)."""
    def shape_of(i):
        x = items[i]
        return x.shape if hasattr(x, "shape") else np.asarray(x).shape

    buckets: List[List] = []
    by_shape: dict = {}
    for part, pad_to in plan:
        key = shape_of(part[0])
        if key in by_shape:
            by_shape[key].append((part, pad_to))
        else:
            by_shape[key] = [(part, pad_to)]
            buckets.append(by_shape[key])
    groups: List[Tuple[List, bool]] = []
    for entries in buckets:
        for i in range(0, len(entries), _MEGAFUSED_MAX_TRIPS):
            run = entries[i:i + _MEGAFUSED_MAX_TRIPS]
            groups.append(
                (run, len(run) > 1 and len({p for _, p in run}) == 1))
    return groups


def _fallback_stream(items, entries, batch_fn):
    """The pre-megafusion dispatch for a group of plan entries: the
    overlapped host-staging path when the engine is on, serial
    otherwise — exactly what `map_host_batched_stream` would have
    chosen without megafusion."""
    from ..workflow.env import execution_config

    cfg = execution_config()
    if cfg.overlap and len(entries) > 1:
        return _stream_overlapped(items, entries, batch_fn,
                                  cfg.prefetch_depth)
    return _stream_serial(items, entries, batch_fn)


def _stream_megafused(
    items, groups, batch_fn
) -> Iterator[Tuple[List[int], List]]:
    """One scan-bodied program per stackable chunk-run; leftover
    single-chunk runs dispatch on the ordinary path (they are already
    one program each). Yields the standard ``(indices, results)`` chunk
    contract — padded phantom rows never surface."""
    for entries, stackable in groups:
        # the rejection re-check matters mid-stream: a trace failure on
        # an earlier group must not be retried on every later one
        if not stackable or not _megafusable_batch_fn(batch_fn):
            yield from _fallback_stream(items, entries, batch_fn)
            continue
        trips = len(entries)
        rows = sum(len(part) for part, _ in entries)
        with span("chunk_megafused", cat="chunk", megafused=True,
                  scan_trips=trips, rows=rows):
            try:
                # the launch: trace refusals (host code behind a jit
                # facade), stack failures, and launch-time errors all
                # surface HERE, before anything is counted — the
                # fallback re-dispatches with nothing double-counted
                stack = np.stack([_stack_chunk(items, part, pad_to)
                                  for part, pad_to in entries])
                # the whole run is ONE launched program
                with dispatch(fn_label(batch_fn)):
                    ys = _megafused_scanner(batch_fn)(
                        _device_put_host(stack))
            except Exception:
                # permanently back to per-chunk for this fn, overlapped
                # staging included
                _MEGAFUSED_REJECTED[id(batch_fn)] = batch_fn
                yield from _fallback_stream(items, entries, batch_fn)
                continue
            # in-order drain of the single result — the sanctioned
            # pull, exactly like _split_result's. A failure HERE is a
            # genuine runtime failure of a launched program and
            # propagates, exactly as the per-chunk path's pull would.
            with span("megafused_pull", cat="sync", layer="sync"):
                res = np.asarray(ys)  # keystone: ignore[KJ005]
        counter("overlap.bytes_pulled").inc(float(res.nbytes))
        counter("megafusion.programs").inc()
        counter("megafusion.scan_trips").inc(trips)
        for c, (part, _) in enumerate(entries):
            yield part, [res[c, j] for j in range(len(part))]


#: sentinel: "use `ExecutionConfig.chunk_size`" — distinct from None,
#: which keeps its historical meaning of one chunk per shape bucket.
USE_CONFIG_CHUNK = object()


def _resolve_chunk(chunk):
    if chunk is USE_CONFIG_CHUNK:
        # the shared resolution: the unified planner's enforced chunk
        # decision when one is live, else ExecutionConfig.chunk_size —
        # the dispatcher and the KP2xx memory model read the same one
        from ..workflow.env import resolved_chunk_size

        return resolved_chunk_size()
    return chunk


def map_host_batched_stream(
    items: Sequence,
    batch_fn: Callable,
    chunk=USE_CONFIG_CHUNK,
) -> Iterator[Tuple[List[int], List]]:
    """Streaming form of `map_host_batched`: yields ``(indices, results)``
    per drained chunk, in dispatch (bucket-major) order. ``indices`` are
    positions in the original item order; the union over all chunks is
    exactly ``range(len(items))`` — with shape-stable dispatch on
    (``ExecutionConfig.pad_chunks``) a ragged tail executes at the full
    padded width, but its phantom rows never leave this module. The
    chunk size defaults to `ExecutionConfig.chunk_size`
    (``KEYSTONE_CHUNK_SIZE``); pass an int to pin it, or None for one
    chunk per shape bucket. Consumers that only need the final
    collection should use `map_host_batched`; chunk-capable pipeline
    stages consume this directly so downstream host work starts before
    the last chunk is off the device."""
    chunk = _resolve_chunk(chunk)
    from ..workflow.env import execution_config

    cfg = execution_config()
    plan = _plan_chunks(items, chunk, pad=cfg.pad_chunks)
    if (cfg.megafusion and cfg.pad_chunks and len(plan) > 1
            and _megafusable_batch_fn(batch_fn)):
        groups = _megafused_groups(items, plan)
        if any(s for _, s in groups):
            # shape-stable multi-chunk runs + a traceable batch fn: the
            # chunk loop moves in-program (one scan-bodied dispatch per
            # run, residency capped at _MEGAFUSED_MAX_TRIPS chunks).
            # Ineligible plans keep the overlapped staging path.
            return _stream_megafused(items, groups, batch_fn)
    if cfg.overlap and len(plan) > 1:
        return _stream_overlapped(items, plan, batch_fn, cfg.prefetch_depth)
    return _stream_serial(items, plan, batch_fn)


# --------------------------------------------------------------------------
# Windowed host→device prefetcher (the out-of-core spill tier's reload
# path). A host-resident source — a planner-spilled cache
# (`data.dataset.SpilledDataset`) or an on-demand sharded source
# (`data.dataset.OutOfCoreDataset`) — re-enters the device in bounded
# row WINDOWS on the same pow-2 pad ladder chunk dispatch uses, so warm
# runs compile one program per window shape and device residency stays
# O(window), never O(count). Overlapped (the default), the load+upload
# of window k+1 rides `prefetch_iterator`'s producer thread while the
# consumer computes on window k — the PR-1 double buffer, pointed at
# reload traffic. Telemetry: ``spill.bytes_in`` counts re-entered bytes,
# ``spill.reload_stall_s`` observes the consumer's blocking wait per
# window (the observed side `analysis.reconcile` joins against the
# planner's predicted reload seconds), ``spill.window_trips`` counts
# reload dispatch trips.


def _window_plan(
    count: int, window: Optional[int], pad: bool = True
) -> List[Tuple[int, int, int]]:
    """``[(lo, hi, pad_to)]`` row windows covering ``range(count)``
    exactly once, in order. The ragged final window pads on the same
    ladder as chunk dispatch (`_pad_target`): up to the window size when
    the source fills at least one whole window, up a pow-2 ladder for
    tiny sources — so a warm reload pass adds 0 cold compiles no matter
    the count."""
    window = window or count
    plan: List[Tuple[int, int, int]] = []
    lo = 0
    while lo < count:
        hi = min(count, lo + window)
        pad_to = _pad_target(hi - lo, window, count) if pad else hi - lo
        plan.append((lo, hi, pad_to))
        lo = hi
    return plan


def _pad_rows(arr: np.ndarray, pad_to: int) -> np.ndarray:
    n = arr.shape[0]
    if pad_to > n:
        widths = [(0, pad_to - n)] + [(0, 0)] * (arr.ndim - 1)
        arr = np.pad(arr, widths)
    return arr


def _stage_spill_window(load, lo: int, hi: int, pad_to: int):
    """Load rows [lo, hi) from the host source, pad each leaf up to
    ``pad_to`` on the leading axis, and upload — the producer-side work
    the overlapped path runs one window ahead. ``load`` may return one
    array or any pytree of arrays sharing the leading dim."""
    import jax

    host = load(lo, hi)
    leaves, treedef = jax.tree_util.tree_flatten(host)
    nbytes = 0.0
    staged = []
    for leaf in leaves:
        arr = np.asarray(leaf)
        nbytes += float(arr.nbytes)
        staged.append(_device_put_host(_pad_rows(arr, pad_to)))
    counter("spill.bytes_in").inc(nbytes)
    return list(range(lo, hi)), jax.tree_util.tree_unflatten(treedef, staged)


def stream_spill_windows(
    load: Callable,
    count: int,
    window=USE_CONFIG_CHUNK,
) -> Iterator[Tuple[List[int], object]]:
    """Yield ``(indices, device_window)`` over a host-resident source of
    ``count`` rows, ``window`` rows at a time (default: the resolved
    chunk size — the unified planner's window decision reaches reloads
    through the same `resolved_chunk_size` seam as chunk dispatch).

    ``load(lo, hi)`` returns host rows [lo, hi) (array or pytree).
    ``indices`` always cover exactly ``range(count)`` across the yielded
    windows, in order; the device window's leading axis is padded to the
    pow-2 ladder target, so consumers must slice their result to
    ``len(indices)`` rows (or use `map_spill_windows`, which does).
    With the overlap engine on and more than one window, staging of
    window k+1 overlaps the consumer's compute on window k."""
    from ..workflow.env import execution_config

    window = _resolve_chunk(window)
    cfg = execution_config()
    plan = _window_plan(count, window, pad=cfg.pad_chunks)
    stall = histogram("spill.reload_stall_s")
    trips = counter("spill.window_trips")

    def gen():
        for i, (lo, hi, pad_to) in enumerate(plan):
            with span("spill_window", cat="chunk", idx=i, rows=hi - lo):
                yield _stage_spill_window(load, lo, hi, pad_to)

    it = (prefetch_iterator(gen(), cfg.prefetch_depth)
          if cfg.overlap and len(plan) > 1 else gen())
    try:
        while True:
            t0 = perf_counter()
            try:
                item = next(it)
            except StopIteration:
                break
            # the consumer-side reload stall: ~the full load+upload on
            # the serial path, ~0 when the producer thread stayed ahead
            stall.observe(perf_counter() - t0)
            trips.inc()
            yield item
    finally:
        close = getattr(it, "close", None)
        if close is not None:
            close()  # early exit cancels the producer thread


def map_spill_windows(
    load: Callable,
    count: int,
    fn: Callable,
    window=USE_CONFIG_CHUNK,
) -> Iterator[Tuple[List[int], List]]:
    """Apply ``fn`` to each reloaded device window, yielding the
    standard ``(indices, results)`` chunk contract: per-row results in
    source order, phantom padded rows sliced off before anything
    downstream sees them — the PR-5 pad-exactness contract extended to
    windows."""
    for idxs, win in stream_spill_windows(load, count, window):
        with dispatch(fn_label(fn)):  # one program per reloaded window
            out = fn(win)
        yield _split_result(out, idxs)


def map_host_batched(
    items: Sequence,
    batch_fn: Callable,
    chunk=USE_CONFIG_CHUNK,
) -> List[np.ndarray]:
    """Apply a batched (leading-axis) function to variable-shape items.

    Items are bucketed by shape; each bucket is stacked and dispatched
    through ``batch_fn`` in chunks of ``chunk`` (default
    `ExecutionConfig.chunk_size`; bounds peak host+device memory).
    Results come back in the original item order. With the overlap
    engine on (the default), stacking/upload of chunk k+1, device
    compute on chunk k, and the result pull of chunk k−depth all proceed
    concurrently; the serial path (single chunk, or overlap disabled)
    computes the identical result one blocking chunk at a time. With
    ``ExecutionConfig.pad_chunks`` (default on) each bucket's ragged
    tail is zero-padded to the chunk size (power-of-two ladder below
    it), so a stage compiles one XLA program per bucket shape no matter
    the item count — ``batch_fn`` must be per-item along the leading
    axis (the documented contract), making the padded rows dead weight
    that is sliced off before results surface.
    """
    out: List = [None] * len(items)
    for part, results in map_host_batched_stream(items, batch_fn, chunk):
        for i, r in zip(part, results):
            out[i] = r
    return out
