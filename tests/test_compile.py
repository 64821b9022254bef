"""Compile-bounded execution correctness suite (ISSUE 5).

Covers the shape-stable chunk dispatch + AOT warmup + persistent
compilation cache + compile accounting contract:

  - padded-tail exactness at non-multiple counts (43 items, chunk 16)
    for plain host batching, fused device chains, and streamed stages —
    outputs identical to the unpadded path, no phantom rows anywhere;
  - the chunk-contract bugfix: `map_host_batched_stream`'s indices cover
    exactly ``range(len(items))`` on BOTH the serial fallback and the
    overlapped path, at ragged counts;
  - compiles-per-run: padding bounds a bucket's programs at one per
    shape (the ragged tail stops compiling its own), and a second
    identical example-pipeline run in-process performs 0 cold compiles;
  - AOT warmup: identical outputs to the cold path, no cold compile at
    force time, `ExecutionConfig.chunk_size` honored end to end.
"""

import numpy as np
import pytest

from keystone_tpu import Dataset, HostDataset, PipelineEnv, Transformer
from keystone_tpu.telemetry import counter
from keystone_tpu.utils import batching
from keystone_tpu.workflow.env import (
    config_override,
    dispatch_override,
    execution_config,
    overlap_override,
)

RAGGED_N, CHUNK = 43, 16


def _items(n=RAGGED_N, dim=6, seed=0):
    rng = np.random.default_rng(seed)
    return [np.abs(rng.normal(size=(dim,)).astype(np.float32)) + 1.0
            for _ in range(n)]


# --------------------------------------------------------------------------
# padded-tail exactness + the chunk contract


@pytest.mark.parametrize("overlap", [False, True])
def test_padded_tail_exact_plain(overlap):
    """map_host_batched at 43 items / chunk 16: the padded path's output
    equals the unpadded path's, element for element."""
    items = _items()
    fn = lambda xb: np.asarray(xb) * 3.0 - 1.0  # noqa: E731

    with overlap_override(overlap), config_override(pad_chunks=True):
        padded = batching.map_host_batched(items, fn, chunk=CHUNK)
    with overlap_override(overlap), config_override(pad_chunks=False):
        ragged = batching.map_host_batched(items, fn, chunk=CHUNK)
    assert len(padded) == len(ragged) == RAGGED_N
    for i in range(RAGGED_N):
        np.testing.assert_allclose(padded[i], items[i] * 3.0 - 1.0,
                                   rtol=1e-6)
        np.testing.assert_allclose(padded[i], ragged[i], rtol=1e-6)


@pytest.mark.parametrize("overlap", [False, True])
def test_stream_indices_cover_exactly_range_n(overlap):
    """Bugfix regression: the serial fallback and `_stream_overlapped`
    agree on the padded chunk contract — indices yielded by
    `map_host_batched_stream` are exactly range(len(items)) with no
    padded phantoms, and every payload length matches its index list."""
    items = _items()
    seen = []
    with overlap_override(overlap, prefetch_depth=1), \
            config_override(pad_chunks=True):
        for idxs, payload in batching.map_host_batched_stream(
                items, lambda xb: np.asarray(xb) * 2.0, chunk=CHUNK):
            assert idxs is not None
            assert len(idxs) == len(payload)
            # a padded chunk must never surface rows beyond its real part
            assert len(payload) <= CHUNK
            seen.extend(idxs)
    assert sorted(seen) == list(range(RAGGED_N))
    assert len(seen) == RAGGED_N  # no duplicates either


class _ChunkProducer16(Transformer):
    """Bucketed host-batch stage streaming 16-row chunks (the
    SIFT/grid-descriptor pattern) — the streamed-stage fixture."""

    chunkable = True

    def apply(self, x):
        return np.asarray(x, np.float32) * 2.0

    def apply_batch_stream(self, data):
        return batching.map_host_batched_stream(
            data.items, lambda xb: np.asarray(xb) * 2.0, chunk=CHUNK)


def test_padded_tail_exact_streamed_consumer():
    """A streaming consumer at a ragged count: chunks flow through a
    fused elementwise chain, the union of streamed indices is exactly
    range(43), and values match the fully serial unpadded reference."""
    from keystone_tpu.nodes.stats import NormalizeRows, SignedHellingerMapper
    from keystone_tpu.workflow.optimizer import DefaultOptimizer

    items = _items()
    pipe = (_ChunkProducer16().to_pipeline()
            >> NormalizeRows() >> SignedHellingerMapper())

    with overlap_override(False), config_override(pad_chunks=False):
        PipelineEnv.get().set_optimizer(DefaultOptimizer(fuse=False))
        reference = pipe(HostDataset(items)).get()
    PipelineEnv.reset()

    with overlap_override(True, prefetch_depth=1), \
            config_override(pad_chunks=True):
        res = pipe(HostDataset(items))
        seen = {}
        for idxs, payload in res.stream():
            assert idxs is not None, "stream materialized"
            for i, item in zip(idxs, payload):
                assert i not in seen, f"index {i} streamed twice"
                seen[i] = item
    PipelineEnv.reset()
    assert sorted(seen) == list(range(RAGGED_N))
    for i in range(RAGGED_N):
        np.testing.assert_allclose(
            np.asarray(reference.items[i]), np.asarray(seen[i]), rtol=1e-5)


def test_padded_tail_exact_fused_device_chain():
    """A fused device chain at count 43 (non-multiple of the 8-device
    mesh): identical to the unfused, unpadded serial path."""
    from keystone_tpu.nodes.learning import LinearMapEstimator
    from keystone_tpu.nodes.stats import NormalizeRows, StandardScaler
    from keystone_tpu.nodes.util import ClassLabelIndicatorsFromInt
    from keystone_tpu.workflow.optimizer import DefaultOptimizer

    rng = np.random.default_rng(5)
    X = np.abs(rng.normal(size=(RAGGED_N, 6))).astype(np.float32) + 1.0
    y = rng.integers(0, 3, RAGGED_N).astype(np.int32)

    def run(fuse, warm):
        PipelineEnv.reset()
        PipelineEnv.get().set_optimizer(DefaultOptimizer(fuse=fuse))
        with config_override(aot_warmup=warm):
            train = Dataset.from_numpy(X)
            labels = ClassLabelIndicatorsFromInt(3)(
                Dataset.from_numpy(y)).get()
            pipe = (NormalizeRows().to_pipeline()
                    .and_then(StandardScaler(), train)
                    .and_then(LinearMapEstimator(0.1), train, labels))
            out = pipe(train).get().numpy()
        PipelineEnv.reset()
        return out

    with overlap_override(False), dispatch_override(False):
        reference = run(fuse=False, warm=False)
    np.testing.assert_allclose(run(fuse=True, warm=False), reference,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(run(fuse=True, warm=True), reference,
                               rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------------------
# compile accounting


def test_padding_bounds_programs_compiled():
    """43 same-shape items at chunk 16: with shape-stable dispatch the
    whole stage compiles ONE program; with it off the ragged tail
    compiles its own second program."""
    import jax

    items = _items()

    def compiles_for(pad):
        fn = jax.jit(lambda xb: xb * 2.0 + 1.0)  # fresh fn: cold by
        # construction, so the delta below measures THIS stage only
        cold = counter("dispatch.programs_compiled")
        with config_override(pad_chunks=pad, compile_cache_dir=None):
            before = cold.value
            out = batching.map_host_batched(items, fn, chunk=CHUNK)
        for i in range(RAGGED_N):
            np.testing.assert_allclose(
                np.asarray(out[i]), items[i] * 2.0 + 1.0, rtol=1e-6)
        return int(cold.value - before)

    assert compiles_for(True) == 1
    assert compiles_for(False) == 2


def test_multi_chunk_bucket_tail_pads_to_full_chunk():
    """Review regression: a ragged tail of a bucket that fills whole
    chunks must pad to the CHUNK size, not its own power-of-two (40
    items at chunk 16 → parts [16, 16, 8]; the 8-tail must dispatch at
    16 or the bucket still compiles two programs)."""
    items = _items(n=40)
    shapes = []

    def fn(xb):
        shapes.append(xb.shape[0])
        return np.asarray(xb) * 2.0

    with config_override(pad_chunks=True):
        out = batching.map_host_batched(items, fn, chunk=CHUNK)
    assert set(shapes) == {CHUNK}, shapes
    assert len(shapes) == 3
    for i in range(40):
        np.testing.assert_allclose(out[i], items[i] * 2.0, rtol=1e-6)

    # a bucket SMALLER than a chunk still takes the pow-2 ladder
    shapes.clear()
    with config_override(pad_chunks=True):
        batching.map_host_batched(_items(n=5), fn, chunk=CHUNK)
    assert shapes == [8], shapes


def test_second_run_performs_zero_cold_compiles():
    """The acceptance gate: an example pipeline rebuilt and re-run in
    the same process against a fresh persistent-cache dir performs 0
    cold compiles on the second run and beats the cold wall clock, with
    identical outputs (compile_bench is the bench-tier twin)."""
    from keystone_tpu.compile_bench import measure_example_compiles

    rep = measure_example_compiles("TimitPipeline")
    assert rep["warm_programs_compiled"] == 0, rep
    assert rep["warm_beats_cold"], rep
    assert rep["apply_compiles_le_plan_programs"], rep
    assert rep["outputs_match_cold"]


def test_ragged_example_counts_stay_identical_and_warm():
    """The same gate at a NON-multiple example count (the padded-row
    machinery live in the measured run)."""
    from keystone_tpu.compile_bench import measure_example_compiles

    rep = measure_example_compiles("TimitPipeline", ragged_test=True)
    assert rep["warm_programs_compiled"] == 0, rep
    assert rep["outputs_match_cold"]


# --------------------------------------------------------------------------
# AOT warmup


def test_warmup_identical_outputs_and_no_force_time_compile():
    """`FusedBatchTransformer.warmup` from a static spec: the warmed
    apply performs zero cold compiles and produces exactly the cold
    path's values."""
    import jax

    from keystone_tpu.nodes.stats import NormalizeRows, SignedHellingerMapper
    from keystone_tpu.nodes.util.fusion import FusedBatchTransformer

    rng = np.random.default_rng(11)
    X = np.abs(rng.normal(size=(RAGGED_N, 6)).astype(np.float32)) + 1.0

    warmed = FusedBatchTransformer([NormalizeRows(), SignedHellingerMapper()])
    status = warmed.warmup(jax.ShapeDtypeStruct((6,), np.float32), RAGGED_N)
    assert status == "compiled"
    assert warmed.warmup(
        jax.ShapeDtypeStruct((6,), np.float32), RAGGED_N) == "cached"

    ds = Dataset.from_numpy(X)
    ds.mask  # its tiny utility jits are not this chain's program
    cold = counter("dispatch.programs_compiled")
    before = cold.value
    out = warmed.apply_batch(ds).numpy()
    assert cold.value == before, "warmed apply still compiled cold"

    reference = FusedBatchTransformer(
        [NormalizeRows(), SignedHellingerMapper()]).apply_batch(
        Dataset.from_numpy(X)).numpy()
    np.testing.assert_allclose(out, reference, rtol=1e-6)


def test_warmup_unwarmable_specs_are_refused():
    from keystone_tpu.nodes.stats import NormalizeRows
    from keystone_tpu.nodes.util.fusion import FusedBatchTransformer

    fused = FusedBatchTransformer([NormalizeRows()])
    assert fused.warmup(object(), 8) is None  # no shape/dtype
    import jax

    assert fused.warmup(jax.ShapeDtypeStruct((4,), np.float32), 0) is None


# --------------------------------------------------------------------------
# chunk-size config


def test_chunk_size_config_reaches_batching_and_memory_model():
    """`ExecutionConfig.chunk_size` is the one chunk number: the host
    batcher's default AND the static memory model's streaming-chunk
    assumption read it."""
    items = _items(n=12, dim=4)
    shapes = []

    def fn(xb):
        shapes.append(xb.shape)
        return xb

    with config_override(chunk_size=4, pad_chunks=False):
        assert execution_config().chunk_size == 4
        batching.map_host_batched(items, fn)  # no explicit chunk
        assert {s[0] for s in shapes} == {4}

        from keystone_tpu.analysis.memory import resolve_chunk_rows

        assert resolve_chunk_rows(None) == 4
        assert resolve_chunk_rows(64) == 64
    assert execution_config().chunk_size == 256  # override scoped


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    """`JAX_COMPILATION_CACHE_DIR` names the cache directory and
    `_sync_compile_cache` never sets one that jax already has;
    `KEYSTONE_COMPILE_CACHE=0` turns the cache off and names nothing;
    with neither, the fixed repo-local directory."""
    import jax

    from keystone_tpu.workflow import env

    outside = str(tmp_path / "outside")
    monkeypatch.delenv("KEYSTONE_COMPILE_CACHE", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert env._env_compile_cache_dir() == env._default_compile_cache_dir()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    assert env._env_compile_cache_dir() == outside
    monkeypatch.setenv("KEYSTONE_COMPILE_CACHE", "0")
    assert env._env_compile_cache_dir() is None
    # a directory is not a value of the off switch any more
    monkeypatch.setenv("KEYSTONE_COMPILE_CACHE", str(tmp_path / "named"))
    assert env._env_compile_cache_dir() == outside

    updates = []
    real_update = jax.config.update

    def recording_update(name, value):
        updates.append(name)
        real_update(name, value)

    before = jax.config.jax_compilation_cache_dir
    try:
        # jax holds the outside directory already, as it does when the
        # variable was set before the process started
        real_update("jax_compilation_cache_dir", outside)
        monkeypatch.setattr(jax.config, "update", recording_update)
        with config_override(compile_cache_dir=outside):
            assert "jax_compilation_cache_dir" not in updates
            assert jax.config.jax_enable_compilation_cache
            with config_override(compile_cache_dir=None):
                assert not jax.config.jax_enable_compilation_cache
                assert jax.config.jax_compilation_cache_dir == outside
            assert "jax_compilation_cache_dir" not in updates
            assert jax.config.jax_enable_compilation_cache
    finally:
        monkeypatch.setattr(jax.config, "update", real_update)
        real_update("jax_compilation_cache_dir", before)
        env._sync_compile_cache(execution_config())
