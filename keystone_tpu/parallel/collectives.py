"""The distributed communication backend, stated explicitly.

The reference's comm backend is Spark's driver-coordinated BSP: torrent
broadcast, depth-log(P) `treeReduce`/`treeAggregate` to the driver,
co-partitioned `zip`, and hash shuffles (SURVEY.md §2.7; e.g.
LBFGS.scala:97-103 gradient treeReduce, LinearMapper.scala:48 model
broadcast). On TPU the backend is XLA collectives over ICI (and DCN
between hosts), reached two ways:

  1. **GSPMD (implicit)** — most code paths: arrays carry shardings and
     `jit` inserts all-reduce/all-gather where the math requires them.
     `Xᵀ X` on a data-sharded X *is* the treeReduce of per-shard Grams.
  2. **shard_map (explicit)** — the helpers here, for algorithms whose
     per-shard step is not expressible as plain sharded math (TSQR's
     per-shard QR, per-shard sketches).

This module gives the explicit spelling of each reference collective so
solver code (and readers coming from the reference) can name them.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from . import mesh as meshlib


def _shard_map(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# jitted programs keyed on (kind, mesh, axis[, seq_op]) — rebuilding the
# closure per call would retrace/recompile every invocation, turning a
# per-iteration solver reduce into a per-iteration compile. The cache is
# a bounded LRU so pathological callers (fresh unhashable closures every
# call) can't grow it without limit.
from collections import OrderedDict

_COLLECTIVE_CACHE: OrderedDict = OrderedDict()
_COLLECTIVE_CACHE_MAX = 128


def _cached(key, build):
    fn = _COLLECTIVE_CACHE.get(key)
    if fn is None:
        fn = jax.jit(build())
        _COLLECTIVE_CACHE[key] = fn
        if len(_COLLECTIVE_CACHE) > _COLLECTIVE_CACHE_MAX:
            _COLLECTIVE_CACHE.popitem(last=False)
    else:
        _COLLECTIVE_CACHE.move_to_end(key)
    return fn


def _fn_key(fn):
    """Cache identity for a user callback: two lambdas with identical
    code, closure values, and defaults share one compiled program, so
    inline ``lambda``s in loops reuse instead of recompiling every
    iteration. Values are keyed with their types (1 vs 1.0 vs True hash
    equal but trace differently). Bound methods and anything whose
    captured state can't be hashed fall back to object identity."""
    import types

    if isinstance(fn, types.MethodType):
        return fn  # state lives on __self__; identity is the safe key
    code = getattr(fn, "__code__", None)
    if code is None:
        return fn

    def typed(v):
        return (type(v), v)

    try:
        cells = tuple(
            typed(c.cell_contents) for c in (getattr(fn, "__closure__", None) or ())
        )
        defaults = tuple(typed(v) for v in (fn.__defaults__ or ()))
        kwdefaults = tuple(
            sorted((k, typed(v)) for k, v in (fn.__kwdefaults__ or {}).items())
        )
        key = (code, cells, defaults, kwdefaults)
        hash(key)
    except (ValueError, TypeError):  # unfilled cell / unhashable value
        return fn
    return key


def tree_reduce_sum(x, mesh=None, axis: str = meshlib.DATA_AXIS):
    """≈ `rdd.treeReduce(_ + _)` of per-shard partial sums.

    ``x`` is sharded over ``axis`` on its leading dim; returns the
    replicated total (summed over the leading dim). Spark's branching
    factor / depth knobs have no analog: the ICI all-reduce schedule is
    the hardware's, and is strictly better than tree-to-driver.
    """
    mesh = mesh or meshlib.current_mesh()

    def build():
        def local(xs):
            return lax.psum(jnp.sum(xs, axis=0), axis)

        return _shard_map(local, mesh, in_specs=(P(axis),), out_specs=P())

    return _cached(("tree_reduce_sum", mesh, axis), build)(x)


def tree_aggregate(x, seq_op, mesh=None, axis: str = meshlib.DATA_AXIS):
    """≈ `treeAggregate(zero)(seqOp, combOp)` where combOp is `+`:
    ``seq_op`` maps one shard's rows to a partial aggregate, psum
    combines. (StandardScaler.scala:46's moment aggregation shape.)

    The compiled program is cached per (mesh, axis, seq_op) — pass a
    stable (module-level) ``seq_op`` in loops to reuse it."""
    mesh = mesh or meshlib.current_mesh()

    def build():
        def local(xs):
            return jax.tree_util.tree_map(lambda v: lax.psum(v, axis), seq_op(xs))

        return _shard_map(local, mesh, in_specs=(P(axis),), out_specs=P())

    return _cached(("tree_aggregate", mesh, axis, _fn_key(seq_op)), build)(x)


def broadcast(x, mesh=None):
    """≈ `sc.broadcast(model)` — replicate across the mesh. GSPMD keeps
    replicated operands resident per-chip; no torrent protocol needed."""
    return meshlib.replicate(x, mesh)


def co_sharded(a, b):
    """≈ `rddA.zip(rddB)` precondition: identically sharded leading axes.

    Spark zip requires equal partitioning; here the check is that both
    arrays carry the same NamedSharding, which makes any elementwise
    combination collective-free."""
    sa = getattr(a, "sharding", None)
    sb = getattr(b, "sharding", None)
    if sa is None or sb is None:
        return a.shape[0] == b.shape[0]
    return a.shape[0] == b.shape[0] and sa.is_equivalent_to(sb, a.ndim)


def all_gather_rows(x, mesh=None, axis: str = meshlib.DATA_AXIS):
    """≈ `rdd.collect()` onto every executor (the reference instead
    collects to the driver; on TPU gathering to all chips over ICI is
    the cheap direction). Returns the full leading axis, replicated."""
    mesh = mesh or meshlib.current_mesh()

    def build():
        def local(xs):
            return lax.all_gather(xs, axis, axis=0, tiled=True)

        return _shard_map(local, mesh, in_specs=(P(axis),), out_specs=P())

    return _cached(("all_gather_rows", mesh, axis), build)(x)


def reshard(x, spec: P, mesh=None):
    """≈ shuffle/repartition: move data to a new layout. XLA lowers the
    transfer to all-to-all/collective-permute over ICI (or DCN across
    hosts) — the analog of Shuffler.scala:16-19 without a sort key.

    Identity reshards short-circuit: when the operand already carries an
    equivalent sharding the array is returned as-is — no program is
    built or dispatched (a repartition to the current layout is free in
    Spark too; the static KP601 lint prices only *real* boundary
    moves)."""
    mesh = mesh or meshlib.current_mesh()
    target = NamedSharding(mesh, spec)
    current = getattr(x, "sharding", None)
    ndim = getattr(x, "ndim", None)
    if current is not None and ndim is not None:
        try:
            if current.is_equivalent_to(target, ndim):
                return x
        except (TypeError, ValueError):
            pass  # cross-mesh / exotic shardings: fall through and move
    return jax.device_put(x, target)


def reshard_tree(tree, spec: P, mesh=None):
    """`reshard` over a pytree: move every array leaf to ``spec``,
    trimming trailing spec entries that exceed a leaf's rank (a
    batch-level P('data', 'model') applied to a 1-D mask keeps only its
    leading entry). The host↔device seam spelling of a planner
    placement: seeding a `Dataset` from a chosen plan is one
    `reshard_tree` call, and leaves already laid out correctly move
    nothing (the identity short-circuit above)."""
    mesh = mesh or meshlib.current_mesh()
    entries = tuple(spec) if spec is not None else ()

    def one(x):
        ndim = getattr(x, "ndim", None)
        if ndim is None:
            return x
        leaf_spec = P(*entries[:ndim])
        return reshard(x, leaf_spec, mesh=mesh)

    return jax.tree_util.tree_map(one, tree)
