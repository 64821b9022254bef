"""Distributed dataset handles — the TPU-native replacement for RDDs.

Two containers:

  - `Dataset` — a pytree of arrays with a leading example axis, padded to a
    multiple of the mesh's ``data`` axis and sharded over it. This is the
    analog of an `RDD[DenseVector]`/`RDD[Image]` with one shard per chip
    (SURVEY.md §2.7 'Data parallelism'). Zero-padding is deliberate: padded
    rows contribute nothing to Gram matrices, moment sums, or one-hot label
    sums, so reductions only need the true ``count`` for normalization.

  - `HostDataset` — a plain list of host objects (variable-size images,
    strings, token lists). The NLP stack and variable-shape image loaders
    run host-side, mirroring the reference's JVM-side per-item code, and
    convert to `Dataset` at the dense boundary via ``stack()``.

`Transformer.apply_batch`'s default path maps a per-item function over a
`Dataset` via ``jit(vmap(f))`` — the analog of `RDD.map` lowering to one
fused XLA program per shard (reference Transformer.scala:46).
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..parallel import mesh as meshlib


def _pad_to(x, target: int):
    n = x.shape[0]
    if n == target:
        return x
    pad_widths = [(0, target - n)] + [(0, 0)] * (x.ndim - 1)
    if isinstance(x, np.ndarray):
        return np.pad(x, pad_widths)
    return jnp.pad(x, pad_widths)


def leaf_sharding(mesh, shape) -> NamedSharding:
    """The sharding `Dataset` placement assigns a leaf of this shape:
    2-D (n, d) leaves shard their feature axis over 'model' when the
    mesh has one (the VectorSplitter analog), everything else is
    data-sharded on the leading axis. One function, used both by
    `Dataset.__init__`'s placement and by AOT plan warmup
    (`FusedBatchTransformer.warmup`) — the compiled-ahead executable
    must be lowered with exactly the shardings the runtime will pass.

    The leading axis must divide the mesh's data-shard count. `Dataset`
    placement always pads it first, but direct callers (AOT warmup over
    analyzer specs, ad-hoc `device_put`s) can hand in ragged leading
    axes — those fall back to a fully replicated placement with a
    warning instead of letting jax raise mid-force with an opaque
    uneven-sharding error (the KP604 lint flags the same condition
    statically)."""
    shards = mesh.shape.get(meshlib.DATA_AXIS, 1)
    if shape and shards > 1 and int(shape[0]) % shards != 0:
        import warnings

        warnings.warn(
            f"leaf_sharding: leading axis {shape[0]} does not divide the "
            f"{shards}-way {meshlib.DATA_AXIS!r} mesh axis; placing the "
            "value replicated instead (pad the leading axis to a "
            "multiple of the data-shard count to shard it)",
            stacklevel=2)
        return NamedSharding(mesh, P())
    if len(shape) == 2:
        feat = meshlib.feature_sharding(mesh, shape[1])
        if feat is not None:
            return feat
    return NamedSharding(mesh, P(meshlib.DATA_AXIS))


def sync_pull(leaf) -> None:
    """THE scalar-pull sync idiom, in one place: transfer one element of
    a (device) array to host. A value that has reached the host is an
    honest fence on any backend and behind any runtime — the element
    cannot arrive before the program that computes it has finished — so
    every timing fence in the library routes through this helper.

    In a multi-process job a cross-host global array's element-0 slice is
    not addressable from every host, so np.asarray would raise; those
    leaves fall back to block_until_ready."""
    from ..telemetry import span

    if hasattr(leaf, "ndim") and hasattr(leaf, "dtype") and leaf.ndim > 0:
        with span("sync_pull", cat="sync", layer="sync"):
            if getattr(leaf, "is_fully_addressable", True):
                np.asarray(leaf[(0,) * leaf.ndim])
            else:
                jax.block_until_ready(leaf)


class Dataset:
    """Sharded device-resident dataset (leading axis = examples)."""

    is_dataset = True

    def __init__(self, data: Any, count: Optional[int] = None, mesh=None, _placed=False):
        self.mesh = mesh or meshlib.current_mesh()
        leaves = jax.tree_util.tree_leaves(data)
        if not leaves:
            raise ValueError("Dataset requires at least one array")
        n = leaves[0].shape[0]
        for leaf in leaves:
            if leaf.shape[0] != n:
                raise ValueError("all leaves must share the leading axis length")
        self.count = int(count) if count is not None else n
        shards = self.mesh.shape.get(meshlib.DATA_AXIS, 1)
        padded = -(-self.count // shards) * shards if self.count else shards
        if _placed and n == padded:
            self.data = data
        else:
            if n < self.count:
                raise ValueError("count exceeds data length")
            data = jax.tree_util.tree_map(lambda x: _pad_to(x[: self.count], padded), data)
            # On a ('data', 'model') mesh, 2-D (n, d) leaves also shard
            # their feature axis over 'model' — the library-level analog
            # of the reference's VectorSplitter feature blocking. Other
            # ranks (images, label vectors of odd widths) stay data-only
            # and replicate over the model axis (see `leaf_sharding`).
            self.data = jax.tree_util.tree_map(
                lambda x: jax.device_put(
                    x, leaf_sharding(self.mesh, x.shape)),
                data)

    # ------------------------------------------------------------- factories

    @staticmethod
    def from_numpy(x, count: Optional[int] = None, mesh=None) -> "Dataset":
        return Dataset(np.asarray(x), count=count, mesh=mesh)

    # ---------------------------------------------------------------- views

    @property
    def array(self):
        """The padded, sharded pytree (single array in the common case)."""
        return self.data

    @property
    def padded_count(self) -> int:
        return jax.tree_util.tree_leaves(self.data)[0].shape[0]

    @property
    def n_shards(self) -> int:
        return self.mesh.shape.get(meshlib.DATA_AXIS, 1)

    @property
    def per_shard_count(self) -> int:
        """Max examples per shard (≈ reference `numPerPartition`,
        WorkflowUtils.scala:12-17)."""
        return self.padded_count // self.n_shards

    @property
    def mask(self):
        """Boolean validity mask over the padded leading axis (cached:
        eager re-dispatch per access costs a device round trip). Placed
        with the same leading-axis sharding as the data so programs
        consuming (data, mask) compile against ONE deterministic input
        layout — what AOT warmup lowers against."""
        m = self.__dict__.get("_mask_cache")
        if m is None:
            # built on host: an eager jnp.arange/lt pair compiles two
            # one-op XLA programs per DISTINCT padded count — cold
            # compiles the serving certifier's 0-cold-compile warm
            # ladder claim (KP902) cannot afford; device_put is a
            # transfer, not a compile
            m = np.arange(self.padded_count) < self.count
            sh = NamedSharding(self.mesh, P(meshlib.DATA_AXIS))
            if sh.is_fully_addressable:
                # multi-host meshes keep the host mask (a host array
                # can't device_put to a cross-process sharding);
                # AOT-warmed programs just fall back to the jit path
                m = jax.device_put(m, sh)
            self.__dict__["_mask_cache"] = m
        return m

    def mask_as(self, dtype):
        """The validity mask as ``dtype``, for programs that multiply by
        it. The conversion is a launched program of its own, so it is
        counted and timed as one (`telemetry.dispatch`)."""
        from ..telemetry import dispatch

        with dispatch("mask.astype"):
            return self.mask.astype(dtype)

    def numpy(self):
        """Unpadded host copy (≈ `collect`)."""
        from ..telemetry import span

        with span("Dataset.numpy", cat="sync", layer="sync"):
            return jax.tree_util.tree_map(
                lambda x: np.asarray(x)[: self.count], self.data)

    def __len__(self) -> int:
        return self.count

    # ------------------------------------------------------------ operations

    def map(self, fn: Callable, jitted: bool = True) -> "Dataset":
        """Apply a per-item function via vmap (≈ `RDD.map`). ``fn`` must be
        traceable; use `map_batches` for whole-batch functions."""
        batched = jax.vmap(fn)
        return self.map_batches(batched, jitted=jitted)

    def map_batches(self, fn: Callable, jitted: bool = True, count: Optional[int] = None) -> "Dataset":
        """Apply a whole-batch function to the padded sharded pytree. The
        result keeps the leading axis and sharding. One call = one
        executed XLA program — THE library-wide jitted call boundary, so
        it feeds the ``dispatch.programs_executed`` budget."""
        from ..telemetry import dispatch, fn_label

        label = fn_label(fn)
        if jitted:
            fn = jax.jit(fn)
        with dispatch(label):
            out = fn(self.data)
        return Dataset(out, count=count if count is not None else self.count,
                       mesh=self.mesh, _placed=True)

    def with_data(self, data: Any, count: Optional[int] = None) -> "Dataset":
        """New Dataset sharing this one's mesh/count, for already-sharded
        results of jitted computations."""
        return Dataset(data, count=count if count is not None else self.count,
                       mesh=self.mesh, _placed=True)

    def reshard(self, spec) -> "Dataset":
        """New Dataset with every leaf moved to ``spec`` (a batch-level
        `PartitionSpec`; entries beyond a leaf's rank are trimmed) via
        `parallel.collectives.reshard` — the explicit spelling of a
        placement decision, used by the sharding planner to seed plan
        inputs from the chosen plan instead of the static default.
        Leaves already laid out as ``spec`` are returned as-is (the
        identity short-circuit), so resharding to the current placement
        builds no program and moves nothing."""
        from ..parallel.collectives import reshard_tree

        return Dataset(reshard_tree(self.data, spec, mesh=self.mesh),
                       count=self.count, mesh=self.mesh, _placed=True)

    def cache(self) -> "Dataset":
        """Device arrays are already materialized (≈ `.cache()` + action).
        NOT a timing fence — production Cacher nodes call this on every
        run, and a host round trip here would defeat async dispatch
        overlap at every cache boundary; timing paths (autocache
        profiling, calibration) must use `sync()` instead."""
        from ..telemetry import span

        with span("Dataset.cache", cat="sync", layer="sync"):
            jax.block_until_ready(self.data)
        return self

    def sync(self) -> "Dataset":
        """TRUE host sync: transfer one element per leaf (`sync_pull`).
        Honest wall-clock timing — autocache profiling, calibration —
        fences on a value that has arrived; a single-element device
        slice keeps the transfer tiny."""
        from ..telemetry import span

        with span("Dataset.sync", cat="sync", layer="sync"):
            for leaf in jax.tree_util.tree_leaves(self.data):
                sync_pull(leaf)
        return self

    def spread_take(self, m: int):
        """Host copy of ≤ m valid examples at evenly spread indices —
        one device gather + one small transfer, never a full collect."""
        m = min(self.count, m)
        if m == 0:
            return jax.tree_util.tree_map(
                lambda x: np.asarray(x[:0]), self.data
            )
        idx = jnp.asarray(
            np.linspace(0, self.count - 1, num=m, dtype=np.int64)
        )
        return jax.tree_util.tree_map(
            lambda x: np.asarray(jnp.take(x, idx, axis=0)), self.data
        )

    def sample_per_shard(self, k: int, seed: int = 0) -> "Dataset":
        """Deterministic sample of ≤ k·n_shards valid examples, resharded
        (≈ SampleCollector's per-partition samples,
        NodeOptimizationRule.scala:145-197)."""
        m = min(self.count, k * self.n_shards)
        return Dataset(self.spread_take(m), count=m, mesh=self.mesh)

    def take(self, k: int):
        k = min(k, self.count)
        return jax.tree_util.tree_map(lambda x: np.asarray(x[:k]), self.data)

    def __repr__(self) -> str:
        shapes = jax.tree_util.tree_map(lambda x: tuple(x.shape), self.data)
        return f"Dataset(count={self.count}, shapes={shapes}, shards={self.n_shards})"


class HostDataset:
    """List-backed dataset of host objects (≈ RDD of JVM objects for the
    non-dense stages: strings, token lists, variable-size images)."""

    is_dataset = True

    def __init__(self, items: Sequence[Any]):
        self.items = list(items)

    @property
    def count(self) -> int:
        return len(self.items)

    @property
    def per_shard_count(self) -> int:
        return -(-len(self.items) // max(1, len(jax.devices())))

    def map(self, fn: Callable) -> "HostDataset":
        return HostDataset([fn(x) for x in self.items])

    def cache(self) -> "HostDataset":
        return self

    def sample_per_shard(self, k: int, seed: int = 0) -> "HostDataset":
        m = min(len(self.items), k * max(1, len(jax.devices())))
        if m == 0:
            return HostDataset([])
        idx = np.linspace(0, len(self.items) - 1, num=m, dtype=np.int64)
        return HostDataset([self.items[i] for i in idx])

    def stack(self, dtype=None, mesh=None, spec=None) -> Dataset:
        """Stack fixed-shape items into a device `Dataset`. ``spec``
        overrides the static `leaf_sharding` default at this
        host→device seam with an explicit batch-level `PartitionSpec`
        (the sharding planner's chosen placement for the stacked
        value). The host array is padded and placed DIRECTLY into the
        requested layout (one `collectives.reshard` device_put from
        host) — never staged through the default placement first."""
        from ..parallel.collectives import reshard_tree

        arr = np.stack([np.asarray(x, dtype=dtype) for x in self.items])
        if spec is None:
            return Dataset(arr, mesh=mesh)
        mesh = mesh or meshlib.current_mesh()
        count = arr.shape[0]
        shards = mesh.shape.get(meshlib.DATA_AXIS, 1)
        padded = -(-count // shards) * shards if count else shards
        placed = reshard_tree(_pad_to(arr, padded), spec, mesh=mesh)
        return Dataset(placed, count=count, mesh=mesh, _placed=True)

    def numpy(self):
        return self.items

    def take(self, k: int):
        return self.items[:k]

    def __len__(self) -> int:
        return len(self.items)

    def __iter__(self):
        return iter(self.items)

    def __repr__(self) -> str:
        return f"HostDataset(count={len(self.items)})"


class SpilledDataset:
    """Host-spilled dataset: the out-of-core tier's cache payload.

    A host-placed `workflow.autocache.CacheMarker` pulls its input off
    the device into one of these — an unpadded numpy pytree plus the
    true ``count`` — freeing the HBM the device copy pinned. Consumers
    re-enter the device through `utils.batching.stream_spill_windows`:
    bounded pow-2 row windows on the pad ladder, reload of window k+1
    overlapped with compute on window k. `rehydrate()` is the sanctioned
    full re-entry for consumers that genuinely need whole-batch
    residency (it re-counts the bytes as ``spill.bytes_in``).

    Deliberately does NOT expose ``.data`` or ``.items``: the telemetry
    byte estimator (`telemetry.instrument.estimate_bytes`) unwraps those
    attributes to count device payloads, and a spilled value must count
    as ~nothing against device residency — its whole point.
    """

    is_dataset = True
    is_spilled = True

    def __init__(self, host_data: Any, count: Optional[int] = None,
                 mesh=None, name: str = ""):
        self.mesh = mesh or meshlib.current_mesh()
        self.name = name
        leaves = jax.tree_util.tree_leaves(host_data)
        if not leaves:
            raise ValueError("SpilledDataset requires at least one array")
        n = int(leaves[0].shape[0])
        self.count = int(count) if count is not None else n
        if self.count > n:
            raise ValueError("count exceeds data length")
        # trim any device-side padding at spill time: host rows are the
        # TRUE rows, so windowed reload never re-uploads phantom rows
        self._host = jax.tree_util.tree_map(
            lambda x: np.asarray(x)[: self.count], host_data)

    @staticmethod
    def spill(dataset: "Dataset", name: str = "") -> "SpilledDataset":
        """Pull a device `Dataset` to the host, counting the evicted
        bytes as ``spill.bytes_out`` — THE device→host spill seam."""
        from ..telemetry import counter

        host = dataset.numpy()
        counter("spill.bytes_out").inc(float(sum(
            a.nbytes for a in jax.tree_util.tree_leaves(host))))
        return SpilledDataset(host, count=dataset.count, mesh=dataset.mesh,
                              name=name)

    @property
    def nbytes(self) -> int:
        return int(sum(a.nbytes for a in jax.tree_util.tree_leaves(self._host)))

    def row_loader(self, lo: int, hi: int):
        """Host rows [lo, hi) — the ``load`` callback
        `utils.batching.stream_spill_windows` stages from."""
        return jax.tree_util.tree_map(lambda x: x[lo:hi], self._host)

    def window_iter(self, window=None):
        """``(indices, device_window)`` pairs with bounded residency —
        see `utils.batching.stream_spill_windows`."""
        from ..utils.batching import USE_CONFIG_CHUNK, stream_spill_windows

        return stream_spill_windows(
            self.row_loader, self.count,
            USE_CONFIG_CHUNK if window is None else window)

    def rehydrate(self) -> "Dataset":
        """Sanctioned FULL re-entry: the whole spilled value back on
        device, counted as ``spill.bytes_in``. Consumers that can take
        windows should use `window_iter` instead."""
        from ..telemetry import counter

        counter("spill.bytes_in").inc(float(self.nbytes))
        return Dataset(self._host, count=self.count, mesh=self.mesh)

    def numpy(self):
        return self._host

    def take(self, k: int):
        k = min(k, self.count)
        return jax.tree_util.tree_map(lambda x: x[:k], self._host)

    def sample_per_shard(self, k: int, seed: int = 0) -> "Dataset":
        m = min(self.count, k * max(1, len(jax.devices())))
        if m == 0:
            return Dataset(jax.tree_util.tree_map(
                lambda x: x[:0], self._host), count=0, mesh=self.mesh)
        idx = np.linspace(0, self.count - 1, num=m, dtype=np.int64)
        return Dataset(jax.tree_util.tree_map(
            lambda x: x[idx], self._host), count=m, mesh=self.mesh)

    def cache(self) -> "SpilledDataset":
        return self  # already materialized (on the host — that's the point)

    def sync(self) -> "SpilledDataset":
        return self  # host arrays: nothing in flight

    def __len__(self) -> int:
        return self.count

    def __repr__(self) -> str:
        return (f"SpilledDataset(count={self.count}, "
                f"host_bytes={self.nbytes})")


class OutOfCoreDataset:
    """On-demand sharded source for datasets ≫ HBM (the arXiv 1610.09451
    §5 out-of-core regime).

    Backed by per-shard loader callbacks — ``loaders[i]()`` returns
    shard i's host rows (array or pytree) with ``counts[i]`` rows — so
    nothing loads until a window asks for it, and device residency stays
    O(window) through `window_iter` / `utils.batching.map_spill_windows`
    instead of O(count). At most one loaded shard is kept (the window
    walk is sequential, so a shard is hot for exactly the windows that
    overlap it). `materialize()` is the sanctioned full drain for
    explicitly-unconstrained runs (the bench's reference arm); anything
    else draining one of these wholesale is what jaxlint KJ020 flags.

    Like `SpilledDataset`, deliberately exposes neither ``.data`` nor
    ``.items`` — see `telemetry.instrument.estimate_bytes`.
    """

    is_dataset = True
    is_out_of_core = True

    def __init__(self, loaders: Sequence[Callable[[], Any]],
                 counts: Sequence[int], mesh=None, name: str = "ooc"):
        if not loaders:
            raise ValueError("OutOfCoreDataset requires at least one shard")
        if len(loaders) != len(counts):
            raise ValueError("one count per shard loader required")
        self._loaders = list(loaders)
        self._counts = [int(c) for c in counts]
        if any(c <= 0 for c in self._counts):
            raise ValueError("shard counts must be positive")
        self._offsets = np.concatenate(([0], np.cumsum(self._counts)))
        self.count = int(self._offsets[-1])
        self.mesh = mesh or meshlib.current_mesh()
        self.name = name
        self._hot: Tuple[Optional[int], Any] = (None, None)

    def _shard(self, i: int):
        """Shard i's host rows, via the single-slot hot cache."""
        hot_i, hot_v = self._hot
        if hot_i != i:
            hot_v = self._loaders[i]()
            n = jax.tree_util.tree_leaves(hot_v)[0].shape[0]
            if int(n) != self._counts[i]:
                raise ValueError(
                    f"shard {i} loader returned {n} rows, declared "
                    f"{self._counts[i]}")
            self._hot = (i, hot_v)
        return hot_v

    def row_loader(self, lo: int, hi: int):
        """Host rows [lo, hi), concatenated across exactly the shards
        that overlap the range — the windowed prefetcher's ``load``
        callback. Sequential windows touch each shard once."""
        if not (0 <= lo <= hi <= self.count):
            raise IndexError(f"rows [{lo}, {hi}) out of range")
        first = int(np.searchsorted(self._offsets, lo, side="right")) - 1
        pieces = []
        i = first
        while i < len(self._loaders) and int(self._offsets[i]) < hi:
            base = int(self._offsets[i])
            shard = self._shard(i)
            a, b = max(lo - base, 0), min(hi - base, self._counts[i])
            pieces.append(jax.tree_util.tree_map(
                lambda x, a=a, b=b: x[a:b], shard))
            i += 1
        if len(pieces) == 1:
            return pieces[0]
        return jax.tree_util.tree_map(
            lambda *xs: np.concatenate(xs, axis=0), *pieces)

    @property
    def nbytes(self) -> int:
        """Total host bytes, estimated from shard 0's per-row bytes —
        the figure the planner's live-set model scales by window/count."""
        shard0 = self._shard(0)
        per_row = sum(a.nbytes / max(1, a.shape[0])
                      for a in jax.tree_util.tree_leaves(shard0))
        return int(per_row * self.count)

    def window_iter(self, window=None):
        from ..utils.batching import USE_CONFIG_CHUNK, stream_spill_windows

        return stream_spill_windows(
            self.row_loader, self.count,
            USE_CONFIG_CHUNK if window is None else window)

    def map_windowed(self, fn: Callable, window=None):
        """``(indices, results)`` chunks of ``fn`` over reloaded device
        windows — `utils.batching.map_spill_windows` over this source."""
        from ..utils.batching import USE_CONFIG_CHUNK, map_spill_windows

        return map_spill_windows(
            self.row_loader, self.count, fn,
            USE_CONFIG_CHUNK if window is None else window)

    def materialize(self) -> "Dataset":
        """Sanctioned FULL materialization (the explicitly-unconstrained
        path: reference arms, tiny sources). Counts ``spill.bytes_in``
        like any other host→device re-entry."""
        from ..telemetry import counter

        host = self.row_loader(0, self.count)
        counter("spill.bytes_in").inc(float(sum(
            a.nbytes for a in jax.tree_util.tree_leaves(host))))
        return Dataset(host, count=self.count, mesh=self.mesh)

    def spill(self, name: str = "") -> "SpilledDataset":
        """Full host materialization as a `SpilledDataset` (no device
        trip) — for handing an on-demand source to the spill-cache tier."""
        return SpilledDataset(self.row_loader(0, self.count),
                              count=self.count, mesh=self.mesh,
                              name=name or self.name)

    def numpy(self):
        return self.row_loader(0, self.count)

    def take(self, k: int):
        return self.row_loader(0, min(k, self.count))

    def sample_per_shard(self, k: int, seed: int = 0) -> "Dataset":
        m = min(self.count, k * max(1, len(jax.devices())))
        if m == 0:
            return Dataset(jax.tree_util.tree_map(
                lambda x: x[:0], self._shard(0)), count=0, mesh=self.mesh)
        idx = np.linspace(0, self.count - 1, num=m, dtype=np.int64)
        rows = [self.row_loader(int(j), int(j) + 1) for j in idx]
        stacked = jax.tree_util.tree_map(
            lambda *xs: np.concatenate(xs, axis=0), *rows)
        return Dataset(stacked, count=m, mesh=self.mesh)

    def cache(self) -> "OutOfCoreDataset":
        return self  # caching an on-demand source is a planner decision

    def __len__(self) -> int:
        return self.count

    def __repr__(self) -> str:
        return (f"OutOfCoreDataset(count={self.count}, "
                f"shards={len(self._loaders)})")


def zip_datasets(datasets: List[Any]):
    """Elementwise zip of N aligned datasets into one dataset of tuples
    (≈ `RDD.zip`; used by the gather operator,
    GatherTransformerOperator.scala:9-18)."""
    if not datasets:
        raise ValueError("zip_datasets requires at least one dataset")
    if all(isinstance(d, HostDataset) for d in datasets):
        return HostDataset([list(t) for t in zip(*(d.items for d in datasets))])
    if all(isinstance(d, Dataset) for d in datasets):
        counts = {d.count for d in datasets}
        if len(counts) != 1:
            raise ValueError(f"zip of misaligned datasets: counts {counts}")
        return Dataset(
            tuple(d.data for d in datasets),
            count=datasets[0].count,
            mesh=datasets[0].mesh,
            _placed=True,
        )
    raise TypeError("zip_datasets requires all-device or all-host datasets")
