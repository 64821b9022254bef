"""Overlapped execution engine (utils/batching.py + workflow streaming).

Contracts under test:
  - the overlapped dispatcher returns results in the original item
    order across shape buckets, identical (allclose) to the serial path
    and the per-item path;
  - a producer-thread exception propagates to the caller (no hang, no
    leaked blocked thread);
  - the bounded queue caps peak host memory at O(depth × chunk) items;
  - forced Expressions stream per-chunk results to chunk-capable
    consumers (downstream work starts before the upstream stage has
    materialized);
  - the serial fallback fires for single-chunk inputs and when the
    config flag is off.
"""

import threading
import time

import numpy as np
import pytest

from keystone_tpu.utils import batching
from keystone_tpu.workflow.env import (
    execution_config,
    overlap_override,
    set_execution_config,
)


def _mixed_shape_items(rng, n_a=9, n_b=7):
    items = [rng.uniform(size=(8, 6)).astype(np.float32) for _ in range(n_a)]
    items += [rng.uniform(size=(5, 4)).astype(np.float32) for _ in range(n_b)]
    # interleave the buckets so ordering is non-trivial
    order = rng.permutation(len(items))
    return [items[i] for i in order]


def test_overlapped_matches_serial_across_shape_buckets():
    rng = np.random.default_rng(0)
    items = _mixed_shape_items(rng)
    fn = lambda x: np.asarray(x) * 2.0 + 1.0

    with overlap_override(False):
        serial = batching.map_host_batched(items, fn, chunk=4)
    with overlap_override(True):
        overlapped = batching.map_host_batched(items, fn, chunk=4)
    assert len(serial) == len(overlapped) == len(items)
    for s, o, x in zip(serial, overlapped, items):
        np.testing.assert_allclose(o, s)
        np.testing.assert_allclose(o, x * 2.0 + 1.0, rtol=1e-6)


def test_overlapped_two_chunk_smoke():
    """Fast smoke: the overlapped path with a minimal 2-chunk input
    (the smallest input that actually exercises the producer thread)."""
    items = [np.full((3, 3), i, np.float32) for i in range(4)]
    with overlap_override(True, prefetch_depth=1):
        out = batching.map_host_batched(items, lambda x: np.asarray(x) + 1, chunk=2)
    for i, r in enumerate(out):
        np.testing.assert_allclose(r, np.full((3, 3), i + 1, np.float32))


def test_single_chunk_input_takes_serial_path(monkeypatch):
    """Nothing to overlap for one chunk: the dispatcher must not spawn a
    producer thread."""
    spawned = []
    orig = threading.Thread

    class Spy(orig):
        def __init__(self, *a, **kw):
            spawned.append(kw.get("name"))
            super().__init__(*a, **kw)

    monkeypatch.setattr(threading, "Thread", Spy)
    items = [np.ones((2, 2), np.float32) for _ in range(5)]
    with overlap_override(True):
        out = batching.map_host_batched(items, lambda x: np.asarray(x), chunk=8)
    assert len(out) == 5
    assert not any(n and n.startswith("keystone-") for n in spawned)


def test_producer_exception_propagates_without_hang():
    class Cursed:
        shape = (2, 2)

        def __array__(self, dtype=None):
            raise ValueError("corrupt item (simulated)")

    items = [np.ones((2, 2), np.float32) for _ in range(6)] + [Cursed()]
    with overlap_override(True, prefetch_depth=1):
        t0 = time.monotonic()
        with pytest.raises(ValueError, match="corrupt item"):
            batching.map_host_batched(items, lambda x: np.asarray(x), chunk=2)
        assert time.monotonic() - t0 < 30.0  # propagated, did not hang


def test_consumer_exception_cancels_producer():
    """A batch_fn failure must re-raise promptly and release the
    producer thread (bounded put is cancellable, never blocked forever)."""
    items = [np.ones((2, 2), np.float32) * i for i in range(40)]

    def fn(x):
        if float(np.asarray(x)[0, 0, 0]) >= 4.0:
            raise RuntimeError("device rejected batch (simulated)")
        return np.asarray(x)

    before = threading.active_count()
    with overlap_override(True, prefetch_depth=2):
        with pytest.raises(RuntimeError, match="rejected batch"):
            batching.map_host_batched(items, fn, chunk=2)
    deadline = time.monotonic() + 30.0
    while threading.active_count() > before and time.monotonic() < deadline:
        time.sleep(0.01)
    assert threading.active_count() <= before


def test_bounded_queue_caps_peak_host_memory():
    """With the consumer blocked, the producer may stage at most
    queue(depth) + 1 chunks — peak host memory O(depth × chunk) items,
    not O(n)."""
    depth, chunk, n_chunks = 2, 4, 12
    converted = []
    release = threading.Event()
    entered = threading.Event()

    class Tracked:
        shape = (2, 2)

        def __init__(self, i):
            self.i = i

        def __array__(self, dtype=None):
            converted.append(self.i)
            return np.full((2, 2), self.i, np.float32)

    items = [Tracked(i) for i in range(chunk * n_chunks)]

    def fn(x):
        entered.set()
        release.wait(timeout=60.0)
        return np.asarray(x)

    def consume():
        with overlap_override(True, prefetch_depth=depth):
            return batching.map_host_batched(items, fn, chunk=chunk)

    out = [None]
    t = threading.Thread(target=lambda: out.__setitem__(0, consume()))
    t.start()
    assert entered.wait(timeout=30.0)
    time.sleep(0.5)  # let the producer run as far as the queue allows
    # producer staged ≤ depth queued + 1 being stacked + ≤ (depth + 1)
    # chunks handed to the (blocked) dispatch window
    cap = (2 * depth + 2) * chunk
    staged = len(converted)
    assert staged <= cap, (staged, cap)
    assert staged < len(items)  # strictly bounded, not all-at-once
    release.set()
    t.join(timeout=60.0)
    assert not t.is_alive()
    for i, r in enumerate(out[0]):
        np.testing.assert_allclose(r, np.full((2, 2), i, np.float32))


def test_prefetch_iterator_order_exception_and_early_close():
    with overlap_override(True, prefetch_depth=2):
        assert list(batching.prefetch_iterator(iter(range(20)))) == list(range(20))

        def broken():
            yield 1
            raise OSError("short read (simulated)")

        it = batching.prefetch_iterator(broken())
        assert next(it) == 1
        with pytest.raises(OSError, match="short read"):
            list(it)

        produced = []

        def slow_gen():
            for i in range(1000):
                produced.append(i)
                yield i

        it = batching.prefetch_iterator(slow_gen(), depth=2)
        assert next(it) == 0
        it.close()  # early break must cancel the producer
        time.sleep(0.2)
        assert len(produced) < 1000

    with overlap_override(False):  # disabled: plain passthrough
        assert list(batching.prefetch_iterator(iter("abc"))) == ["a", "b", "c"]


def test_execution_config_env_and_override(monkeypatch):
    monkeypatch.setenv("KEYSTONE_OVERLAP", "0")
    monkeypatch.setenv("KEYSTONE_PREFETCH_DEPTH", "5")
    set_execution_config(None)
    try:
        cfg = execution_config()
        assert cfg.overlap is False and cfg.prefetch_depth == 5
        with overlap_override(True, prefetch_depth=3) as inner:
            assert inner.overlap is True and inner.prefetch_depth == 3
            assert execution_config().overlap is True
        assert execution_config().overlap is False
    finally:
        set_execution_config(None)


# --------------------------------------------------------------------------
# Workflow streaming: forced Expressions yield per-chunk results


def _stream_stage(tag, log, fn):
    """A chunkable per-item transformer that records when items pass."""
    from keystone_tpu.workflow.pipeline import Transformer

    def apply(x):
        log.append(tag)
        return fn(x)

    return Transformer.from_function(apply, name=tag)


def test_pipeline_streams_chunks_between_host_stages():
    """With overlap on, a chunk-capable downstream stage must start
    consuming before the upstream host-batched stage has finished every
    chunk — observable as interleaved per-item work."""
    from keystone_tpu.data.dataset import HostDataset
    from keystone_tpu.nodes.images.descriptors import LCSExtractor

    rng = np.random.default_rng(1)
    items = [rng.uniform(size=(40, 40, 3)).astype(np.float32) for _ in range(8)]
    ext = LCSExtractor(stride=8)

    log = []
    post = _stream_stage("post", log, lambda d: np.asarray(d).sum())
    pipe = ext >> post

    with overlap_override(True, prefetch_depth=1):
        import keystone_tpu.utils.batching as b

        orig = b.map_host_batched_stream

        def chunked(its, fn, chunk=256):
            for part, results in orig(its, fn, chunk=2):
                log.append(("chunk", tuple(part)))
                yield part, results

        b.map_host_batched_stream, saved = chunked, orig
        try:
            streamed = pipe(HostDataset(items)).get()
        finally:
            b.map_host_batched_stream = saved

    with overlap_override(False):
        serial = pipe(HostDataset(items)).get()

    # equality with the serial path, original order
    for s, o in zip(serial.items, streamed.items):
        np.testing.assert_allclose(np.asarray(s), np.asarray(o), rtol=1e-5)
    # interleaving: downstream "post" work appears BETWEEN chunk markers,
    # not after all of them (the stage did not materialize first)
    chunk_marks = [i for i, e in enumerate(log) if isinstance(e, tuple)]
    post_marks = [i for i, e in enumerate(log) if e == "post"]
    assert len(chunk_marks) >= 2
    assert min(post_marks) < max(chunk_marks), log


def test_pipeline_result_stream_api():
    """PipelineResult.stream() yields (indices, items) chunks whose
    union reassembles the full result; .get() afterwards is the memo."""
    from keystone_tpu.data.dataset import HostDataset
    from keystone_tpu.nodes.images.sift import SIFTExtractor

    rng = np.random.default_rng(2)
    items = [rng.uniform(size=(32, 32)).astype(np.float32) for _ in range(6)]
    ext = SIFTExtractor(step=8, num_scales=1)

    with overlap_override(True, prefetch_depth=1):
        res = ext(HostDataset(items))
        seen = {}
        n_chunks = 0
        for idxs, payload in res.stream():
            assert idxs is not None
            n_chunks += 1
            for i, item in zip(idxs, payload):
                seen[i] = item
        assert sorted(seen) == list(range(len(items)))
        full = res.get()  # memoized assembly of the same chunks
        for i, item in seen.items():
            np.testing.assert_allclose(
                np.asarray(full.items[i]), np.asarray(item))

    with overlap_override(False):
        serial = ext(HostDataset(items)).get()
    for i in range(len(items)):
        np.testing.assert_allclose(
            np.asarray(serial.items[i]), np.asarray(seen[i]), rtol=1e-5)


def test_streaming_preserves_non_host_pipelines():
    """Device-Dataset pipelines and non-chunkable stages take the
    whole-value fallback chunk — same results, same types."""
    from keystone_tpu.data.dataset import Dataset
    from keystone_tpu.workflow.pipeline import Transformer

    double = Transformer.from_function(lambda x: x * 2.0, name="double")
    X = np.arange(12, dtype=np.float32).reshape(6, 2)
    with overlap_override(True):
        out = double(Dataset(X)).get()
        assert isinstance(out, Dataset)
        np.testing.assert_allclose(np.asarray(out.array)[:6], X * 2.0)
        chunks = list(double(Dataset(X)).stream())
        assert len(chunks) == 1 and chunks[0][0] is None


def test_partial_stream_drain_never_rewinds_the_producer():
    """Breaking out of .stream() then forcing .get() must RESUME the
    producer, not re-run it: each chunk is dispatched exactly once, and
    the final value includes the chunks consumed before the break."""
    from keystone_tpu.data.dataset import HostDataset
    from keystone_tpu.workflow.pipeline import Transformer
    from keystone_tpu.utils import batching

    items = [np.full((2, 2), i, np.float32) for i in range(8)]
    dispatched = []

    class Chunky(Transformer):
        chunkable = True

        def apply(self, x):
            return np.asarray(x) + 1.0

        def apply_batch_stream(self, data):
            def fn(stacked):
                dispatched.append(np.asarray(stacked).shape[0])
                return np.asarray(stacked) + 1.0

            return batching.map_host_batched_stream(data.items, fn, chunk=2)

    with overlap_override(True, prefetch_depth=1):
        res = Chunky()(HostDataset(items))
        stream = res.stream()
        idxs0, payload0 = next(stream)  # consume ONE chunk, then abandon
        stream.close()
        full = res.get()
    assert sum(dispatched) == len(items), dispatched  # no chunk re-dispatched
    for i, r in enumerate(full.items):
        np.testing.assert_allclose(r, np.full((2, 2), i + 1, np.float32))
    # the chunk consumed before the break is the same object the final
    # assembly used (memoized prefix, not a recompute)
    for i, item in zip(idxs0, payload0):
        np.testing.assert_allclose(full.items[i], item)


def test_failed_stream_stays_failed_on_reforce():
    """A producer exception mid-stream is STICKY: forcing the same
    (executor-memoized) expression again must re-raise, never silently
    assemble the truncated prefix as the complete value."""
    from keystone_tpu.workflow.expressions import StreamingDatasetExpression

    calls = {"n": 0}

    def chunks():
        calls["n"] += 1
        yield [0, 1], ["a", "b"]
        raise ValueError("producer died (simulated)")

    expr = StreamingDatasetExpression(chunks)
    with pytest.raises(ValueError, match="producer died"):
        for _ in expr.iter_chunks():
            pass
    with pytest.raises(ValueError, match="producer died"):
        expr.get
    with pytest.raises(ValueError, match="producer died"):
        list(expr.iter_chunks())
    assert calls["n"] == 1  # the dead producer was never re-run
    assert not expr.is_forced
