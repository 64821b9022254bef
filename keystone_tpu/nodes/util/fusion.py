"""Stage fusion + microbatching — the TPU-native answer to per-node
dataset materialization.

A chain of device transformers executed node-by-node materializes every
intermediate in HBM (e.g. RandomPatchCifar's conv output is
n·27·27·K floats — 7 GB at n=10⁴, K=256 — before pooling shrinks it
1000×). `FusedBatchTransformer` composes the stages' batch functions into
ONE jitted program and processes each mesh shard's rows in fixed-size
microbatches via `lax.map`, so peak HBM is the chunk's intermediates
while XLA fuses elementwise stages into the conv/pool loops.

The reference has no analog — Spark streams partition iterators through
the operator chain, getting memory-boundedness for free; on TPU we
recover it with scan-over-chunks inside `shard_map`.
"""

from __future__ import annotations

import itertools
import math
import threading
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ...data.dataset import Dataset
from ...parallel import mesh as meshlib
from ...telemetry import scope_name
from ...workflow.pipeline import Transformer


def _leaf_dtype_name(p) -> str:
    """Canonical dtype name of one program-key leaf WITHOUT materializing
    it as a jax array: `jnp.asarray(p).dtype` on a host numpy leaf pays
    a device put + convert_element_type per call — milliseconds per
    warm serving dispatch across a plan's weight pytree. Canonicalizing
    the dtype directly (x64-flag aware) produces the identical key."""
    dt = getattr(p, "dtype", None)
    if dt is None:
        return jnp.asarray(p).dtype.name
    return jax.dtypes.canonicalize_dtype(dt).name


def _stage_batch_fn(stage: Transformer):
    """The stage's whole-batch device function."""
    fn = getattr(stage, "batch_fn", None)
    if fn is not None:
        return fn()
    return jax.vmap(stage.apply)


def fused_stages(stages) -> list:
    """The stages a fused chain of ``stages`` runs: each row selection
    moved ahead where it may (`Transformer.moves_ahead_of`), then each
    stage merged with the followers it absorbs (`Transformer.absorb`).
    The program's functions and the planners' casts and slices index it."""
    stages = list(stages)
    for i in range(len(stages) - 1):
        ahead = getattr(stages[i + 1], "moves_ahead_of", None)
        if ahead is not None and ahead(stages[i]):
            stages[i], stages[i + 1] = stages[i + 1], stages[i]
    out, i = [], 0
    while i < len(stages):
        absorb = getattr(stages[i], "absorb", None)
        merged = absorb(stages[i + 1:]) if absorb is not None else None
        stage, taken = merged if merged is not None else (stages[i], 0)
        out.append(stage)
        i += 1 + taken
    return out


def _mask_rows(y, mb):
    """Zero the padded rows of a per-chunk result (mb: bool (chunk,))."""
    return y * mb.reshape((-1,) + (1,) * (y.ndim - 1)).astype(y.dtype)


#: sentinel 4th element marking a fuse() whose fn already takes
#: (params, xb, mask_b) — produced by composing decompositions
#: (`FusedBatchTransformer.fuse`, `GatherConcatStage.fuse`).
MASK_AWARE = "mask-aware"


def stage_fuse(stage: Transformer):
    """Decompose a stage into (static_key, params_pytree, pure_fn) where
    ``pure_fn(params, xb, mask_b) -> yb`` (``mask_b`` is the chunk's
    valid-row mask).

    Stages implementing ``fuse()`` get cross-instance program caching:
    two pipelines with the same structure but different parameter VALUES
    share one compiled XLA program (params are traced arguments, not
    baked constants). Stages without it fall back to a closure keyed on
    object identity — correct, but compiled per instance.

    Mask discipline: a stage whose *unfused* batch path re-zeros padded
    rows (``fuse_masks_output = True`` — StandardScalerModel, the label
    indicators) keeps doing so inside the fused program, so mask-less
    whole-batch reductions downstream (`_normal_equations`, `_moments`,
    which rely on 'padded rows are zero') see exactly the values the
    node-by-node path would have produced.
    """
    f = getattr(stage, "fuse", None)
    if f is not None:
        res = f()
        if len(res) == 4 and res[3] == MASK_AWARE:
            key, params, fn = res[:3]
        else:
            key, params, fn2 = res
            fn = (lambda p, xb, mb, fn2=fn2: fn2(p, xb))
    else:
        bf = _stage_batch_fn(stage)
        key, params = ("opaque", id(stage)), ()
        fn = (lambda p, xb, mb, bf=bf: bf(xb))
    if getattr(stage, "fuse_masks_output", False):
        inner = fn
        fn = (lambda p, xb, mb, inner=inner: _mask_rows(inner(p, xb, mb), mb))
        key = (key, "masked")
    # the stage's ops carry its label in their names, in the compiled
    # program's metadata and so in a device trace. Metadata only: the
    # jaxpr, the compiled code and the program cache keys are as without
    return key, params, jax.named_scope(scope_name(stage.label))(fn)


#: a shard's input of this many bytes or more goes through the chunk loop
#: where it lies (`_overlapped_chunks`): below it a copy is cheap
COPY_FREE_BYTES = 1 << 30
#: rows of a slab: a TPU lays a large array out with its rows on the 128
#: lanes where that pads least, and cuts of whole lane tiles are cheap
SLAB_ROWS = 128


def _overlapped_chunks(chunk_fn, params, xs, ms, chunk: int):
    """The chunk loop over rows that are no whole number of chunks, or
    too many bytes to be copied: every chunk is cut from the rows where
    they lie and the last one from their end, so it overlaps the one
    before (those rows are made twice, the same) and the input is
    neither padded nor copied: a padded copy of a shard's input is the
    whole of it again, 3.8 GB where the input is 5,011 cached images.

    Where a chunk is a fraction of `SLAB_ROWS`, the rows go through in
    slabs of `SLAB_ROWS` and a slab in chunks: the v5e compiler, asked
    for eight rows at a time of an array it keeps with its rows on the
    lanes, otherwise first copies the whole array into a layout of its
    liking."""
    local_n = xs.shape[0]
    if chunk < SLAB_ROWS <= local_n and SLAB_ROWS % chunk == 0:
        inner = SLAB_ROWS // chunk

        def slab_fn(ps, xb, mb):
            yb = lax.map(
                lambda xm: chunk_fn(ps, xm[0], xm[1]),
                (xb.reshape((inner, chunk) + xb.shape[1:]),
                 mb.reshape((inner, chunk))))
            return yb.reshape((SLAB_ROWS,) + yb.shape[2:])

        return _overlapped_chunks(slab_fn, params, xs, ms, SLAB_ROWS)
    out = jax.eval_shape(lambda x, m: chunk_fn(params, x, m),
                         xs[:chunk], ms[:chunk])

    def body(i, ys):
        start = jnp.minimum(i * chunk, local_n - chunk)
        yb = chunk_fn(params, lax.dynamic_slice_in_dim(xs, start, chunk, 0),
                      lax.dynamic_slice_in_dim(ms, start, chunk, 0))
        return lax.dynamic_update_slice_in_dim(ys, yb, start, 0)

    return lax.fori_loop(
        0, -(-local_n // chunk), body,
        jnp.zeros((local_n,) + out.shape[1:], out.dtype))


# (structure key) -> jitted program. Programs take (flat_params, xs) so
# rebuilding a pipeline — the bench re-fits from scratch — never
# recompiles the featurizer.
_PROGRAM_CACHE: dict = {}

# key -> Future of an in-flight AOT warmup compile (`warmup`), so a
# force that arrives mid-warmup waits for THAT compile instead of
# racing a duplicate one. Entries are removed when the future resolves.
_WARMUP_PENDING: dict = {}
_WARMUP_LOCK = threading.Lock()


class _AotProgram:
    """A program cache entry carrying both the jit wrapper and an
    ahead-of-time compiled executable for the warmed-up input avals.
    Calls dispatch straight into the compiled executable; if the live
    arguments disagree with the warmed avals (sharding drift, an
    unexpected layout) the entry degrades permanently to the jit path —
    correct either way, and with the persistent compilation cache on the
    jit path still retrieves the warmup's executable warm instead of
    recompiling."""

    __slots__ = ("_jitted", "_compiled")

    def __init__(self, jitted, compiled):
        self._jitted = jitted
        self._compiled = compiled

    def __call__(self, flat, xs, ms):
        compiled = self._compiled
        if compiled is not None:
            try:
                return compiled(flat, xs, ms)
            except Exception:
                self._compiled = None
        return self._jitted(flat, xs, ms)


def contains_opaque(key) -> bool:
    """True when a (possibly nested — composed FusedChain keys) static
    key contains an id-keyed "opaque" entry, which must never enter the
    global program cache (see the opaque comment in `apply_batch`)."""
    if isinstance(key, tuple):
        return any(contains_opaque(k) for k in key)
    return key == "opaque"


class GatherConcatStage(Transformer):
    """N fusable branches over ONE input, concatenated along the last
    axis — a ``Pipeline.gather`` fan-out plus its `VectorCombiner`
    collapsed into a single traceable stage, so the whole
    branch-and-merge diamond compiles into one XLA program
    (NodeFusionRule's gather pass). Branch order is the gather's
    dependency order, matching `zip_datasets` + concat semantics."""

    fusable = True

    def __init__(self, branches: Sequence[Transformer]):
        self.branches = list(branches)

    @property
    def label(self) -> str:
        """Branch labels in order, a run of equal ones counted: the
        label is a scope on every op of the program, and fifty equal
        branches spelled out would be a kilobyte on each."""
        runs = [(label, len(list(run))) for label, run in
                itertools.groupby(b.label for b in self.branches)]
        return "Gather[" + " | ".join(
            label if n == 1 else f"{n} x {label}" for label, n in runs) + "]"

    def abstract_apply(self, elem):
        """The branches' output elements side by side on the last axis."""
        from ...workflow.operators import fitted_elem_fn

        outs = [fitted_elem_fn(b)(elem) for b in self.branches]
        width = sum(o.shape[-1] for o in outs)
        return jax.ShapeDtypeStruct(
            tuple(outs[0].shape[:-1]) + (width,), outs[0].dtype)

    @property
    def chunkable(self) -> bool:
        return all(getattr(b, "chunkable", False) for b in self.branches)

    @property
    def precision_tolerance(self):
        """Tolerant iff every branch declares tolerance — the collapsed
        diamond inherits the weakest member's contract."""
        tols = {getattr(b, "precision_tolerance", None)
                for b in self.branches}
        return "tolerant" if tols == {"tolerant"} else "exact"

    def apply(self, x):
        return jnp.concatenate(
            [jnp.asarray(b.apply(x)) for b in self.branches], axis=-1)

    def fuse(self):
        fused = [stage_fuse(b) for b in self.branches]
        statics = tuple(f[0] for f in fused)
        params = tuple(f[1] for f in fused)
        fns = tuple(f[2] for f in fused)

        def fn(ps, xb, mb):
            return jnp.concatenate(
                [f(p, xb, mb) for f, p in zip(fns, ps)], axis=-1)

        return (("GatherConcat",) + statics, params, fn, MASK_AWARE)


#: the most rows a microbatch takes however small a row is: what every
#: fused program ran at before the rows' bytes were looked at
MAX_MICROBATCH = 2048

# (chain structure, parameter shapes, element, budget) -> rows a microbatch
_MICROBATCH_CACHE: dict = {}


def _made_bytes(jaxpr, out: list) -> list:
    """Bytes of every value the equations of ``jaxpr`` make, in order,
    those of the programs inside it too (a Pallas kernel's values live in
    VMEM and are left out)."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            aval = v.aval
            size = getattr(aval, "size", None)
            out.append(int(size) * aval.dtype.itemsize if size is not None
                       else 0)
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _made_bytes(inner, out)
    return out


def chain_row_bytes(fns, params, elem_shape, dtype) -> int:
    """The bytes of the largest value one more row makes anywhere in the
    chain ``fns``: the chain is traced abstractly for one row and for
    two, and a value's share of a row is what it grew by (a value made
    of the parameters alone does not grow). Nothing runs and nothing is
    compiled."""
    def made(rows):
        def chain(ps, xb, mb):
            for f, p in zip(fns, ps):
                xb = f(p, xb, mb)
            return xb

        closed = jax.make_jaxpr(chain)(
            params, jax.ShapeDtypeStruct((rows,) + tuple(elem_shape), dtype),
            jax.ShapeDtypeStruct((rows,), jnp.bool_))
        return _made_bytes(closed.jaxpr, [])

    one, two = made(1), made(2)
    if len(one) != len(two):  # the trace depends on the rows: be careful
        return max(one, default=0)
    return max((b - a for a, b in zip(one, two)), default=0)


class FusedBatchTransformer(Transformer):
    """Compose device transformer stages into one microbatched program.

    stages: transformers whose batch path is a pure array→array function
    (exposed via ``batch_fn()`` or vmap of ``apply``).
    microbatch: rows processed per step per shard. None (the default)
    derives it from the bytes a row makes in this chain under the
    planner's HBM budget (`workflow.budget.microbatch_rows`), at most
    `MAX_MICROBATCH`; a number is taken as given.
    """

    #: a fused chain is itself a traceable single-dep stage, so later
    #: optimizer passes (or hand-fused example featurizers) can extend it
    fusable = True

    @property
    def precision_tolerance(self):
        """A fused chain tolerates reduced precision iff EVERY member
        does — the precision planner treats the whole program as one
        stage when it appears inside a larger graph."""
        tols = {getattr(s, "precision_tolerance", None)
                for s in self.stages}
        return "tolerant" if tols == {"tolerant"} else "exact"

    #: the sharding planner's chosen output placement (a batch-level
    #: `PartitionSpec`), set by `ShardingPlannerRule` on a tagged copy
    #: when the plan deviates from the default: `_build_program` lowers
    #: it into a `with_sharding_constraint` on the program output and
    #: the program cache keys on it, so the chosen layout is baked into
    #: the compiled executable (and never collides with the unplanned
    #: form's cache entry). None (the default) compiles exactly the
    #: PR-8 program.
    planned_out_spec = None

    #: the precision planner's chosen per-stage storage dtypes (set by
    #: `PrecisionPlannerRule` on a tagged copy): a tuple of dtype names
    #: or None, one per `fused_stages` output. `_build_program` bakes
    #: each entry into the traced chunk body as a
    #: ``convert_element_type`` cast after that stage — jaxpr-visible,
    #: AOT-warmable, and part of the program cache key, so a planned
    #: program never collides with the unplanned form's entry. The LAST
    #: entry RESTORES the unplanned trail's output dtype, which the
    #: consumers see. None (the default): no casts.
    planned_precision = None

    #: the precision planner's matmul-precision scope (e.g.
    #: ``"bfloat16"``): when set, the traced chunk body runs under
    #: `jax.default_matmul_precision`, so every dot the program
    #: contains carries the reduced precision in its jaxpr. Also part
    #: of the program cache key.
    planned_matmul_precision = None

    #: the unified planner's chain-megakernel tag (set by
    #: `UnifiedPlannerRule` on a tagged copy): ``(start, stop, family)``
    #: over the `fused_stages` list. `_build_program` swaps that stage
    #: sub-trail for ONE `pl.pallas_call` (ops/chain_kernels.py) that
    #: streams batch blocks HBM→VMEM double-buffered and applies every
    #: stage body in VMEM — the chain boundaries inside the slice never
    #: round-trip HBM. The effective tag (`_kernel_plan`, which folds in
    #: the `KEYSTONE_CHAIN_KERNELS` gate and the interpret mode) is part
    #: of the program cache key, so the kernel form never collides with
    #: the XLA form's entry and a kill-switch flip recompiles instead of
    #: reusing the wrong program. None (the default) or a stale tag
    #: compiles exactly the pre-kernel XLA program (bit-for-bit).
    planned_kernel = None

    #: the planner's predicted seconds for the kernel side of the swap
    #: (set alongside `planned_kernel`); rides the ``chain_kernel`` span
    #: so `reconcile_roofline` can join predicted vs observed.
    planned_kernel_seconds = None

    #: the KP10xx static verifier's verdict for the planned lowering
    #: (True proved, False refuted, None unverifiable) — rides the
    #: ``chain_kernel`` span so the ledger records whether the executed
    #: kernel carried a static proof.
    planned_kernel_statically_verified = None

    def __init__(self, stages: Sequence[Transformer],
                 microbatch: Optional[int] = None):
        self.stages = list(stages)
        self.microbatch = microbatch

    @property
    def label(self) -> str:
        return "Fused[" + " >> ".join(s.label for s in self.stages) + "]"

    @property
    def chunkable(self) -> bool:
        """A fused chain distributes over host chunks iff every stage
        does — so PR-1's overlap engine keeps streaming through fused
        chains instead of silently materializing at the fusion boundary
        (KP302)."""
        return all(getattr(s, "chunkable", False) for s in self.stages)

    def apply(self, x):
        for s in self.stages:
            x = s.apply(x)
        return x

    def fuse(self):
        """Compose the stages' own fuse decompositions, so a fused chain
        embedded in a LARGER chain (optimizer re-fusion, fitted fused
        chains) keeps structural program caching instead of degrading to
        an id-keyed opaque closure. Mask-aware: inner masking stages
        keep re-zeroing padded rows at their original chain position."""
        statics, flat, treedef, fns = self._decompose()
        params = jax.tree_util.tree_unflatten(treedef, flat)

        def fn(ps, xb, mb):
            for f, p in zip(fns, ps):
                xb = f(p, xb, mb)
            return xb

        return (("FusedChain",) + statics, params, fn, MASK_AWARE)

    def _decompose(self):
        """The chain's fused decomposition plus the flattened params:
        (statics, flat_params, treedef, fns). Shared by `apply_batch`
        and `warmup` so both derive the SAME program cache key."""
        fused = [stage_fuse(s) for s in fused_stages(self.stages)]
        statics = tuple(f[0] for f in fused)
        params = tuple(f[1] for f in fused)
        fns = tuple(f[2] for f in fused)
        flat, treedef = jax.tree_util.tree_flatten(params)
        return statics, flat, treedef, fns

    def _kernel_plan(self):
        """The EFFECTIVE chain-kernel tag: ``((start, stop, family),
        interpret)`` — or None when unplanned or the gate is off. Folds
        in `use_chain_kernels()` and the interpret mode so the program
        cache key changes whenever a `KEYSTONE_CHAIN_KERNELS` flip would
        change the built program."""
        if self.planned_kernel is None:
            return None
        from ...ops import chain_kernels as _ck

        if not _ck.use_chain_kernels():
            return None
        return tuple(self.planned_kernel), _ck.chain_interpret()

    def _kernel_swap(self, statics):
        """Resolve the planned kernel against THIS decomposition:
        ``(start, stop, kern_fn)`` when the tagged sub-trail lowers, else
        None (stale tag, unmatched statics, gate off) — the same
        ignore-don't-miscompile discipline as a stale precision tag."""
        kplan = self._kernel_plan()
        if kplan is None or statics is None:
            return None
        (start, stop, family), interp = kplan
        if not (0 <= start < stop <= len(statics)):
            return None
        from ...ops.chain_kernels import build_chain_fn

        fn = build_chain_fn(tuple(statics[start:stop]), family=family,
                            interpret=interp)
        if fn is None:
            return None
        return start, stop, fn

    def _chunk_rows(self, decomposition, array_shape, dtype_name,
                    local_n: int) -> int:
        """Rows a microbatch of this chain takes on ``local_n`` rows a
        shard: the number handed over if one was, else derived once a
        chain structure, element and budget from the bytes a row makes
        (`chain_row_bytes`) and remembered."""
        if self.microbatch is not None:
            return max(1, min(self.microbatch, local_n))
        from ...workflow.budget import hbm_budget_bytes, microbatch_rows

        statics, flat, treedef, fns = decomposition
        budget = hbm_budget_bytes()
        key = (statics, treedef,
               tuple((tuple(p.shape), _leaf_dtype_name(p)) for p in flat),
               tuple(array_shape[1:]), dtype_name, budget)
        cache = (self.__dict__.setdefault("_instance_microbatch", {})
                 if contains_opaque(statics) else _MICROBATCH_CACHE)
        rows = cache.get(key)
        if rows is None:
            params = jax.tree_util.tree_unflatten(
                treedef, [jax.ShapeDtypeStruct(jnp.shape(p),
                                               _leaf_dtype_name(p))
                          for p in flat])
            rows = cache[key] = microbatch_rows(
                chain_row_bytes(fns, params, array_shape[1:], dtype_name),
                budget, MAX_MICROBATCH)
        return max(1, min(rows, local_n))

    def _program_key(self, statics, flat, treedef, array_shape, dtype_name,
                     padded_count, n_shards, mesh, chunk=None):
        if chunk is None:  # asked by hand: as `_build_program` builds by hand
            chunk = min(self.microbatch or MAX_MICROBATCH,
                        padded_count // n_shards)
        return (
            statics,
            treedef,
            tuple((tuple(p.shape), _leaf_dtype_name(p)) for p in flat),
            tuple(array_shape),
            dtype_name,
            padded_count,
            n_shards,
            chunk,
            mesh,
            self.planned_out_spec,
            self.planned_precision,
            self.planned_matmul_precision,
            self._kernel_plan(),
        )

    def _program_cache(self, statics):
        """Opaque stages are keyed on object identity: caching those
        globally would pin the stage (and its captured arrays) forever
        and make the id-keyed entry unsafe after GC reuses the id. Keep
        such programs on THIS instance instead."""
        if contains_opaque(statics):
            return self.__dict__.setdefault("_instance_programs", {})
        return _PROGRAM_CACHE

    def apply_batch(self, data):
        if not isinstance(data, Dataset):
            # host/object datasets: run the stages' own batch paths
            for s in self.stages:
                data = s.apply_batch(data)
            return data

        statics, flat, treedef, fns = decomposition = self._decompose()
        chunk = self._chunk_rows(
            decomposition, data.array.shape, data.array.dtype.name,
            data.padded_count // data.n_shards)
        key = self._program_key(
            statics, flat, treedef, data.array.shape, data.array.dtype.name,
            data.padded_count, data.n_shards, data.mesh, chunk)
        cache = self._program_cache(statics)
        program = cache.get(key)
        if program is None:
            # an in-flight AOT warmup for this very program? Wait for it
            # instead of compiling the same thing twice concurrently.
            with _WARMUP_LOCK:
                pending = _WARMUP_PENDING.get(key)
            if pending is not None:
                try:
                    pending.result()
                except Exception:
                    pass  # warmup died: compile inline as if it never ran
                program = cache.get(key)
        if program is None:
            program = self._build_program(
                data.mesh, data.n_shards, data.padded_count,
                treedef, fns, statics=statics, chunk=chunk)
            cache[key] = program
        from ...telemetry import counter, dispatch, span

        self._count_gather_bytes(data)
        self._count_stage_rows(data)
        swap = self._kernel_swap(statics)
        if swap is not None:
            # the planned chain megakernel is live in this program:
            # span-visible so reconcile_roofline can join the planner's
            # predicted seconds against the observed wall span
            start, stop, _ = swap
            with span("chain_kernel", cat="node", label=self.label,
                      family=self.planned_kernel[2], stages=stop - start,
                      rows=data.count,
                      predicted_seconds=self.planned_kernel_seconds,
                      statically_verified=(
                          self.planned_kernel_statically_verified)), \
                    dispatch(self.label, rows=data.count):
                out = data.with_data(program(flat, data.array, data.mask))
            counter("pallas.chain_programs").inc()
            return out
        # the whole chain is ONE executed program
        with dispatch(self.label, rows=data.count):
            return data.with_data(program(flat, data.array, data.mask))

    def _flat_stages(self):
        for s in self.stages:
            if isinstance(s, FusedBatchTransformer):
                yield from s._flat_stages()
            else:
                yield s

    def _count_gather_bytes(self, data):
        """`gather.concat_bytes`: what the chain's gather stages write
        when they put their branches side by side. Each branch's output
        is a buffer of its own first and the combined rows are written
        from those, a chunk at a time (the v5e's compiler fuses that
        write into the chunk's slot of the loop's output; PERF.md
        section 6, PR 28). Counted from shapes, per call."""
        stages = list(self._flat_stages())
        last = max((i for i, s in enumerate(stages)
                    if isinstance(s, GatherConcatStage)), default=-1)
        if last < 0:
            return
        from ...telemetry import counter
        from ...workflow.operators import fitted_elem_fn

        elem = jax.ShapeDtypeStruct(data.array.shape[1:], data.array.dtype)
        per_row = 0
        for s in stages[:last + 1]:  # what follows the last gather is not priced
            elem = fitted_elem_fn(s)(elem)
            if isinstance(s, GatherConcatStage):
                per_row += math.prod(elem.shape) * elem.dtype.itemsize
        counter("gather.concat_bytes").inc(data.padded_count * per_row)

    def _count_stage_rows(self, data):
        """Lets every stage that counts what a dispatch works through
        (`count_rows(element, rows)`: SIFT's images and descriptors, the
        sampler's kept rows, the Fisher encoder's images) do so, from
        the shapes, once a call."""
        stages = list(self._flat_stages())
        if not any(hasattr(s, "count_rows") for s in stages):
            return
        stages = fused_stages(stages)  # as the program runs them
        last = max(i for i, s in enumerate(stages)
                   if hasattr(s, "count_rows"))
        from ...workflow.operators import fitted_elem_fn

        elem = jax.ShapeDtypeStruct(data.array.shape[1:], data.array.dtype)
        for s in stages[:last + 1]:
            if hasattr(s, "count_rows"):
                s.count_rows(elem, data.count)
            elem = fitted_elem_fn(s)(elem)

    def warmup(self, element, count: int, mesh=None) -> Optional[str]:
        """AOT-compile this chain's batch program from a static spec —
        no data touched. ``element`` is the per-item
        `jax.ShapeDtypeStruct` the analyzer propagated; ``count`` the
        dataset's example count. Lowers with the exact input avals and
        shardings `apply_batch` will pass (Dataset leaf placement + the
        row-sharded mask) and installs an `_AotProgram` under the same
        cache key, so the first force dispatches into a warm executable.
        With the persistent compilation cache armed the compile also
        lands on disk, warming every later process. Returns "cached" /
        "compiled" / None (spec not warmable — pytree elements, unknown
        shapes)."""
        if not (hasattr(element, "shape") and hasattr(element, "dtype")):
            return None
        mesh = mesh or meshlib.current_mesh()
        shards = mesh.shape.get(meshlib.DATA_AXIS, 1)
        count = int(count)
        if count <= 0:
            return None
        padded = -(-count // shards) * shards
        array_shape = (padded,) + tuple(element.shape)
        dtype = jnp.dtype(element.dtype)
        statics, flat, treedef, fns = decomposition = self._decompose()
        chunk = self._chunk_rows(decomposition, array_shape, dtype.name,
                                 padded // shards)
        key = self._program_key(
            statics, flat, treedef, array_shape, dtype.name,
            padded, shards, mesh, chunk)
        cache = self._program_cache(statics)
        if key in cache:
            return "cached"
        with _WARMUP_LOCK:
            if key in _WARMUP_PENDING:
                return "cached"
            import concurrent.futures

            fut = concurrent.futures.Future()
            _WARMUP_PENDING[key] = fut
        try:
            from ...data.dataset import leaf_sharding
            from ...telemetry import span

            with span("aot_warmup", cat="compile", layer="compile",
                      label=self.label, rows=padded):
                jitted = self._build_program(mesh, shards, padded,
                                             treedef, fns, statics=statics,
                                             chunk=chunk)
                xs_aval = jax.ShapeDtypeStruct(
                    array_shape, dtype,
                    sharding=leaf_sharding(mesh, array_shape))
                ms_aval = jax.ShapeDtypeStruct(
                    (padded,), jnp.bool_,
                    sharding=NamedSharding(mesh, P(meshlib.DATA_AXIS)))
                flat_avals = [
                    jax.ShapeDtypeStruct(jnp.shape(p),
                                         jnp.asarray(p).dtype)
                    for p in flat
                ]
                compiled = jitted.lower(
                    flat_avals, xs_aval, ms_aval).compile()
                cache[key] = _AotProgram(jitted, compiled)
            fut.set_result(key)
            return "compiled"
        except BaseException as e:
            fut.set_exception(e)
            raise
        finally:
            with _WARMUP_LOCK:
                _WARMUP_PENDING.pop(key, None)

    def _chunk_loop(self, chunk_fn, params, xs, ms):
        """The in-program loop over the stacked (n_chunks, chunk, ...)
        axis. Base form: `lax.map` (sequential chunks, bounded HBM);
        `MegafusedBatchTransformer` overrides with an explicit
        ``lax.scan`` whose carry stays empty (params are closure-
        invariant — the KJ007 discipline) and whose stacked output is
        XLA's own donated accumulation buffer."""
        return lax.map(lambda xm: chunk_fn(params, xm[0], xm[1]), (xs, ms))

    def _build_program(self, mesh, shards, padded_count, treedef, fns,
                       statics=None, chunk=None):
        local_n = padded_count // shards
        if chunk is None:  # built by hand: the number given, or the ceiling
            chunk = min(self.microbatch or MAX_MICROBATCH, local_n)
        n_chunks = -(-local_n // chunk)
        padded_local = n_chunks * chunk

        # the precision planner's chosen per-stage storage dtypes: one
        # entry per fused stage (aligned with `fns`: both index
        # `fused_stages`); a stale/misaligned tag is ignored rather than
        # mis-cast
        planned_prec = self.planned_precision
        if planned_prec is not None and len(planned_prec) != len(fns):
            planned_prec = None
        matmul_prec = self.planned_matmul_precision
        if planned_prec is not None:
            # the OBSERVED side of the precision decision's cast count:
            # each non-None entry becomes one convert_element_type in
            # the traced program, counted at build time (the ledger's
            # predicted `casts_baked` reconciles against this)
            from ...telemetry import counter as _counter

            _counter("precision.casts_baked").inc(
                sum(1 for p in planned_prec if p is not None))

        # the unified planner's chain-megakernel tag: when the tagged
        # sub-trail lowers, ONE pallas_call replaces those stage bodies
        # (a stale/unmatched tag builds exactly the XLA form, like a
        # stale precision tag)
        swap = self._kernel_swap(statics)
        kstart, kstop, kern_fn = swap if swap is not None else (-1, -1, None)

        def chunk_fn(params, xb, mb):
            i = 0
            while i < len(fns):
                if i == kstart and kern_fn is not None:
                    # the chain megakernel: every boundary inside
                    # [kstart, kstop) stays in VMEM, so the planner's
                    # intra-slice storage casts are subsumed — only the
                    # slice-end cast below still applies
                    with jax.named_scope(scope_name(
                            f"chain_kernel.{self.planned_kernel[2]}")):
                        xb = kern_fn(tuple(params[kstart:kstop]), xb, mb)
                    i = kstop - 1
                else:
                    xb = fns[i](params[i], xb, mb)
                if planned_prec is not None and planned_prec[i] is not None \
                        and jnp.issubdtype(xb.dtype, jnp.floating):
                    # the chosen boundary storage dtype, baked into the
                    # traced program (convert_element_type in the jaxpr)
                    xb = xb.astype(jnp.dtype(planned_prec[i]))
                i += 1
            return xb

        if matmul_prec is not None:
            inner_chunk = chunk_fn

            def chunk_fn(params, xb, mb):
                with jax.default_matmul_precision(matmul_prec):
                    return inner_chunk(params, xb, mb)

        def per_shard(flat_params, xs, ms):
            # xs: (local_n, ...) shard rows; ms: (local_n,) valid mask
            params = jax.tree_util.tree_unflatten(treedef, flat_params)
            if padded_local != local_n or xs.nbytes >= COPY_FREE_BYTES:
                return _overlapped_chunks(chunk_fn, params, xs, ms, chunk)
            xs = xs.reshape((n_chunks, chunk) + xs.shape[1:])
            ms = ms.reshape((n_chunks, chunk))
            # sequential chunks: bounded HBM
            ys = self._chunk_loop(chunk_fn, params, xs, ms)
            ys = ys.reshape((padded_local,) + ys.shape[2:])
            return ys[:local_n]

        if shards > 1:
            spec = P(meshlib.DATA_AXIS)
            flat_specs = [P()] * treedef.num_leaves
            fn = jax.shard_map(
                per_shard, mesh=mesh, in_specs=(flat_specs, spec, spec),
                out_specs=spec, check_vma=False,
            )
        else:
            fn = per_shard
        planned = self.planned_out_spec
        if planned is not None:
            # the sharding planner's chosen output placement, enforced
            # IN the program: the constraint is part of the traced
            # computation, so the jaxpr carries it, AOT warmup lowers
            # it, and the executable's output lands in the planned
            # layout with no separate reshard dispatch
            inner_fn = fn

            def fn(flat_params, xs, ms):
                ys = inner_fn(flat_params, xs, ms)
                return jax.lax.with_sharding_constraint(
                    ys, NamedSharding(mesh, planned))

        # every caller stores the result in a program cache keyed on the
        # chain's structure (_PROGRAM_CACHE / _instance_programs), so
        # this fresh closure compiles once per key, not once per call
        return jax.jit(fn)  # keystone: ignore[KJ006]


class MegafusedBatchTransformer(FusedBatchTransformer):
    """A whole-plan fused chain whose chunk loop is an in-program
    ``lax.scan`` — the single donated XLA program of the megafusion
    optimizer pass (workflow/fusion_rule.MegafusionRule).

    Differences from the base `FusedBatchTransformer`:

      - the per-shard microbatch loop is an explicit ``lax.scan`` over
        the padded chunk axis (shape-stable: PR 5's padding contract
        guarantees every trip sees the same chunk shape). Fit state is
        captured as scan-invariant closure params — never threaded
        through the carry, so model buffers are not doubled per trip
        (the KJ007 discipline) — and per-chunk masks ride the scanned
        axis so ``fuse_masks_output`` stages keep padded rows exact;
      - the scan's stacked output is XLA's own donated accumulation
        buffer (`ys` is written in place per trip); the carry is empty;
      - dispatches are telemetry-visible: the program span carries
        ``megafused=true`` and the scan trip count, and the
        ``megafusion.programs`` / ``megafusion.scan_trips`` counters
        feed the trace CLI's dispatch digest.
    """

    #: trace/span marker — also how tests and the memory model recognize
    #: the one-program apply path
    megafused = True

    def _n_trips(self, data) -> int:
        local_n = max(1, data.padded_count // max(1, data.n_shards))
        # a microbatch handed over needs no look at the chain
        decomposition = (None if self.microbatch is not None
                         else self._decompose())
        chunk = self._chunk_rows(decomposition, data.array.shape,
                                 data.array.dtype.name, local_n)
        return -(-local_n // chunk)

    def _program_key(self, *args, **kwargs):
        # a scan-bodied program must never collide with the base class's
        # lax.map form in the shared structural cache
        return ("megafused", super()._program_key(*args, **kwargs))

    def apply_batch(self, data):
        if not isinstance(data, Dataset):
            return super().apply_batch(data)
        from ...telemetry import counter, span

        trips = self._n_trips(data)
        with span("megafused_program", cat="node", megafused=True,
                  scan_trips=trips, rows=data.count, label=self.label):
            out = super().apply_batch(data)
        counter("megafusion.programs").inc()
        counter("megafusion.scan_trips").inc(trips)
        return out

    def _chunk_loop(self, chunk_fn, params, xs, ms):
        # params are scan-INVARIANT closure captures: model state is
        # read by every trip but never carried (carry stays empty), so
        # the scan cannot double O(model) buffers per trip; XLA writes
        # each trip's rows into the preallocated (donated) ys buffer
        def trip(carry, xm):
            xb, mb = xm
            return carry, chunk_fn(params, xb, mb)

        _, ys = lax.scan(trip, (), (xs, ms))
        return ys
