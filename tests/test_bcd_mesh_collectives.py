"""What the partitioner makes of the block solver on a `(4,)` `data`
mesh, read off the compiled programs (a count, not a time): every
program that sums over the rows holds an all-reduce, what it reduces is
what `solver.allreduce_bytes` counts from the shapes, and no collective
moves a slice of X. The comments in `_bcd_epoch` say "all-reduce over
the data axis" and no line of code asks for one: this fails on a solver
change that makes the partitioner gather the (n, B) slice instead (1.07
GB a chip at the benchmark's `timit_fit_4chip`)."""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from keystone_tpu.nodes.learning import block_ls

SHARDS = 4
# rows and rows a shard that no other dimension equals; two blocks of four
# 256-wide panels each
N, B, BLOCKS, K = 2560, 1024, 2, 16
D = B * BLOCKS
TILE = block_ls._gram_tile(B)
COLLECTIVE = re.compile(
    r" = (?P<result>.*?) (?P<kind>all-reduce|all-gather|reduce-scatter|"
    r"collective-permute|all-to-all)(?:-start)?\((?P<operands>.*)$")
SHAPE = re.compile(r"\w+\[([\d,]*)\]")


def _aval(mesh, shape, spec, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def _collectives(compiled):
    """[(kind, [dims of each array in the result], the HLO line)]."""
    found = []
    for line in compiled.as_text().splitlines():
        m = COLLECTIVE.search(line)
        if m:
            shapes = [[int(x) for x in dims.split(",") if x]
                      for dims in SHAPE.findall(m["result"])]
            found.append((m["kind"], shapes, line.strip()))
    return found


def _programs(mesh):
    row, rep = P("data"), P()
    W = _aval(mesh, (BLOCKS, B, K), rep)
    R = _aval(mesh, (N, K), row)
    Xc = _aval(mesh, (N, D), row)
    lam = _aval(mesh, (), rep)
    epoch = block_ls._bcd_epoch
    return {
        "prepare": lambda: block_ls._bcd_prepare.lower(
            Xc, R, _aval(mesh, (N,), row), B, BLOCKS, True),
        "forming": lambda: epoch.lower(
            W, R, Xc, lam, B, BLOCKS, keep_factors=True, gram_tile=TILE),
        "kept_factors": lambda: epoch.lower(
            W, R, Xc, lam, B, BLOCKS,
            factors=_aval(mesh, (BLOCKS, B, B), rep)),
        "one_epoch": lambda: epoch.lower(
            W, R, Xc, lam, B, BLOCKS, gram_tile=TILE),
    }


# what one shard hands to the all-reduces of one launch, from the shapes
BYTES = {
    "prepare": 4 * (D + 1 + K),  # the column sums, the row count, ym's sums
    "forming": block_ls._allreduce_bytes(B, K, TILE, forming=True),
    "kept_factors": block_ls._allreduce_bytes(B, K, None, forming=False),
    "one_epoch": block_ls._allreduce_bytes(B, K, TILE, forming=True),
}


@pytest.mark.parametrize("name", list(BYTES))
def test_the_solver_reduces_over_the_rows_and_gathers_no_slice_of_x(name):
    assert TILE == 256
    mesh = Mesh(jax.devices()[:SHARDS], ("data",))
    found = _collectives(_programs(mesh)[name]().compile())
    reduces = [shapes for kind, shapes, _ in found if kind == "all-reduce"]
    assert reduces, "nothing sums over the data axis"
    # a block step's all-reduces stand once in the scan's body
    reduced = 4 * sum(math.prod(dims) for shapes in reduces
                      for dims in shapes)
    assert reduced == BYTES[name]
    for kind, shapes, line in found:
        rows = [dims for dims in shapes if N in dims or N // SHARDS in dims]
        assert kind == "all-reduce" and not rows, (
            f"a collective moves rows of X or of the residual: {line[:300]}")


def test_the_counter_s_formula_at_the_benchmark_s_shapes():
    # sixteen panels of 256 x (4,096 - 256 i) and the (4,096, 147)
    # correlation; the one full product where a block has under two tiles
    assert block_ls._allreduce_bytes(4096, 147, 256, forming=True) == (
        35_651_584 + 2_408_448)
    assert block_ls._allreduce_bytes(4096, 147, None, forming=False) == 2_408_448
    assert block_ls._allreduce_bytes(64, 12, None, forming=True) == 4 * (
        64 * 64 + 64 * 12)
    # a last panel narrower than the tile: 256 x 600, 256 x 344, 88 x 88
    assert block_ls._allreduce_bytes(600, 1, 256, forming=True) == 4 * (
        256 * 600 + 256 * 344 + 88 * 88 + 600)
