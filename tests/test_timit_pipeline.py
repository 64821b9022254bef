"""TimitPipeline at the source's structure: a gather of cosine
random-feature branches, `VectorCombiner`, five epochs of block least
squares at lambda 0, `MaxClassifier`. The system's class scores are held
against the plain reference of the benchmark (`benchmark/reference/
timit_cosine.py`) on seeded random W, b and frames, and the same fit
with its Gram products in bfloat16 is shown to fall outside the
tolerance. Small sizes, on the CPU."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from keystone_tpu.pipelines.timit import (  # noqa: E402
    TimitConfig,
    build_pipeline,
    build_scorer,
    cosine_branches,
)

SEED = 2**31 + 28
# 2,048 rows, 32 dimensions, 3 branches of 64, 12 classes, 5 epochs, lambda 0
SIZES = {
    "input_dim": 32, "num_cosines": 3, "num_cosine_features": 64,
    "feature_dim": 192, "block_size": 64, "bcd_iters": 5, "lam": 0.0,
    "gamma": 0.2, "distribution": "gaussian", "num_classes": 12,
    "num_train": 2048, "num_test": 512, "assumed": {"signal": 0.6},
    "default_matmul_operands": "float32"}  # the CPU's default rounds nothing
# Scores lie in about [-1.5, 0]. The system and the reference compute the
# same float32 arithmetic in another order (a scan over blocks against
# Python loops, `solve(assume_a="pos")` against `cho_solve`, the branches
# fused into one program), and a block Gram's condition number multiplies
# float32's 6e-8: over ten seeds the largest difference was 1.2e-6 to
# 1.6e-6. The same fit with the operands of its Gram products rounded to
# bfloat16 (what a TPU's default matmul precision does) differed from the
# reference by 2.6e-3 to 3.5e-3 on the same seeds. The tolerance stands
# thirty times over the one and fifty times under the other.
SCORE_TOLERANCE = 5e-5


@pytest.fixture(scope="module")
def split():
    from benchmark.configs import timit_cosine as adapter
    from keystone_tpu.parallel.mesh import current_mesh

    mesh = current_mesh()  # the tests' eight virtual devices
    train, test = adapter.make_data(SIZES, SEED, mesh)
    return adapter, mesh, train, test


@pytest.fixture(scope="module")
def reference_scores(split):
    from benchmark.reference import timit_cosine as reference

    adapter, mesh, train, test = split
    return reference.scores(train, test, SIZES, SEED)


def test_the_defaults_are_the_source_s():
    c = TimitConfig()
    assert (c.num_cosines, c.num_cosine_features, c.gamma, c.distribution,
            c.num_epochs, c.lam, c.num_classes, c.synth_dim) == (
        50, 4096, 0.05555, "gaussian", 5, 0.0, 147, 440)
    assert c.n_synth > c.num_cosine_features  # a block's Gram has full rank


def test_the_pipeline_is_a_gather_of_branches_in_front_of_the_block_solver(split):
    from keystone_tpu.nodes.learning import BlockLeastSquaresEstimator
    from keystone_tpu.nodes.stats import CosineRandomFeatures
    from keystone_tpu.nodes.util import Cacher, MaxClassifier, VectorCombiner
    from keystone_tpu.workflow.operators import GatherTransformerOperator

    adapter, _, train, _ = split
    config = adapter.program_config(SIZES, SEED)
    ops = list(build_pipeline(train, config).graph.operators.values())
    kinds = [type(op) for op in ops]
    # the featurizer stands in the graph twice: over the training frames
    # in front of the solver, and over the pipeline's input
    assert kinds.count(CosineRandomFeatures) == 2 * config.num_cosines == 6
    for kind in (GatherTransformerOperator, VectorCombiner, Cacher):
        assert kinds.count(kind) == 2, kind
    for kind in (BlockLeastSquaresEstimator, MaxClassifier):
        assert kinds.count(kind) == 1, kind
    (solver,) = [op for op in ops if isinstance(op, BlockLeastSquaresEstimator)]
    assert (solver.block_size, solver.num_iter, solver.lam) == (64, 5, 0.0)
    # every branch has random parameters of its own
    Ws = [np.asarray(b.W) for b in cosine_branches(config, 32)]
    assert all(W.shape == (32, 64) for W in Ws)
    assert not np.allclose(Ws[0], Ws[1]) and not np.allclose(Ws[1], Ws[2])
    again = [np.asarray(b.W) for b in cosine_branches(config, 32)]
    np.testing.assert_array_equal(Ws[2], again[2])


def test_the_system_s_scores_are_the_reference_s(split, reference_scores):
    adapter, _, train, test = split
    scorer = build_scorer(train, adapter.program_config(SIZES, SEED))
    got = np.asarray(scorer(test.data).get().numpy())
    assert got.shape == reference_scores.shape == (512, 12)
    assert np.abs(got - reference_scores).max() < SCORE_TOLERANCE
    labels = np.asarray(test.labels.numpy())
    assert np.mean(np.argmax(got, axis=-1) == labels) > 0.9


def _bcd_with_bfloat16_products(X, Y, block, epochs):
    """`plain.block_least_squares` at lambda 0, every product of the
    solve with its operands rounded to bfloat16 and summed in float32."""
    def mm(a, b):
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)

    xm, ym = X.mean(axis=0), Y.mean(axis=0)
    X, R = X - xm, Y - ym
    starts = list(range(0, X.shape[1], block))
    Ws = [jnp.zeros((block, Y.shape[1]), jnp.float32) for _ in starts]
    for _ in range(epochs):
        for i, s in enumerate(starts):
            Xb = X[:, s:s + block]
            R = R + mm(Xb, Ws[i])
            Ws[i] = jax.scipy.linalg.cho_solve(
                jax.scipy.linalg.cho_factor(mm(Xb.T, Xb)), mm(Xb.T, R))
            R = R - mm(Xb, Ws[i])
    W = jnp.concatenate(Ws, axis=0)
    return W, ym - xm @ W


def test_bfloat16_grams_fall_outside_the_tolerance(split, reference_scores):
    from benchmark.reference import plain, timit_cosine as reference

    _, _, train, test = split
    W, b = reference._weights(SIZES, SEED)
    f32 = jnp.dtype("float32")
    X = reference._features(train.data.array[:2048], W, b, operands=f32)
    Y = plain.indicators(train.labels.array[:2048], 12)
    M, c = _bcd_with_bfloat16_products(X, Y, 64, 5)
    got = np.asarray(reference._scores(
        test.data.array[:512], W, b, M, c, operands=f32))
    assert np.abs(got - reference_scores).max() > 20 * SCORE_TOLERANCE


def test_the_reference_rounds_the_operands_the_configuration_states():
    from benchmark.reference import timit_cosine as reference

    rng = np.random.default_rng(0)
    A = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
    bf16, f32 = jnp.dtype("bfloat16"), jnp.dtype("float32")
    with jax.default_matmul_precision("highest"):
        rounded = (A.astype(bf16).astype(f32)) @ (B.astype(bf16).astype(f32))
        np.testing.assert_allclose(
            reference._product(A, B, bf16), rounded, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            reference._product(A, B, f32), A @ B, rtol=1e-6, atol=1e-6)
    assert np.abs(np.asarray(rounded - A @ B)).max() > 1e-3


def test_the_gather_s_label_counts_equal_branches():
    from keystone_tpu.nodes.stats import CosineRandomFeatures, RandomSignNode
    from keystone_tpu.nodes.util.fusion import GatherConcatStage

    cos = [CosineRandomFeatures(8, 4, seed=i) for i in range(3)]
    assert GatherConcatStage(cos).label == "Gather[3 x CosineRandomFeatures]"
    mixed = GatherConcatStage([RandomSignNode(8), cos[0], cos[1]])
    assert mixed.label == "Gather[RandomSignNode | 2 x CosineRandomFeatures]"
    out = GatherConcatStage(cos).abstract_apply(
        jax.ShapeDtypeStruct((8,), jnp.float32))
    assert out.shape == (12,) and out.dtype == jnp.float32


def _scopes(lowered):
    return lowered.as_text(debug_info=True)


def test_the_scopes_reach_the_programs_of_both_paths(split):
    """`ks.Gather[...]` and a `ks.CosineRandomFeatures` for every branch
    in the fused program; `ks.CosineRandomFeatures` and
    `ks.VectorCombiner` in the programs of the node-by-node path."""
    from keystone_tpu.nodes.stats.random_features import _cosine_rf
    from keystone_tpu.nodes.util.basic import _concat_last
    from keystone_tpu.nodes.util.fusion import (
        FusedBatchTransformer,
        GatherConcatStage,
    )

    _, mesh, train, _ = split
    branches = cosine_branches(TimitConfig(
        num_cosines=3, num_cosine_features=64, gamma=0.2), 32)
    fused = FusedBatchTransformer([GatherConcatStage(branches)])
    statics, flat, treedef, fns = fused._decompose()
    program = fused._build_program(
        mesh, train.data.n_shards, 2048, treedef, fns, statics=statics)
    text = _scopes(program.lower(flat, train.data.array, train.data.mask))
    assert "ks.Gather[3xCosineRandomFeatures]" in text
    assert text.count("ks.Gather[3xCosineRandomFeatures]/ks.CosineRandomFeatures") >= 3
    x = train.data.array
    text = _scopes(_cosine_rf.lower(x, branches[0].W, branches[0].b))
    assert "ks.CosineRandomFeatures" in text
    text = _scopes(_concat_last.lower((x, x)))
    assert "ks.VectorCombiner" in text


def test_the_node_by_node_path_counts_the_bytes_it_copies(split):
    from keystone_tpu.nodes.util import VectorCombiner
    from keystone_tpu.telemetry import counter

    _, _, train, _ = split
    branches = cosine_branches(TimitConfig(
        num_cosines=3, num_cosine_features=64, gamma=0.2), 32)
    parts = tuple(b.apply_batch(train.data).array for b in branches)
    before = counter("gather.concat_bytes").value
    out = VectorCombiner().apply_batch(train.data.with_data(parts))
    assert out.array.shape == (2048, 192)
    assert counter("gather.concat_bytes").value - before == 2048 * 192 * 4


def test_the_random_parameters_are_drawn_on_the_device_and_counted():
    from keystone_tpu.nodes.stats import CosineRandomFeatures
    from keystone_tpu.telemetry import counter

    before = counter("dispatch.programs_executed").value
    node = CosineRandomFeatures(440, 256, gamma=0.05555, seed=2**32 + 7)
    assert counter("dispatch.programs_executed").value - before == 1
    W, b = np.asarray(node.W), np.asarray(node.b)
    assert W.dtype == b.dtype == np.float32
    assert W.shape == (440, 256) and b.shape == (256,)
    assert abs(W.std() / 0.05555 - 1.0) < 0.02 and abs(W.mean()) < 1e-3
    assert b.min() >= 0.0 and b.max() < 2 * np.pi and abs(b.mean() - np.pi) < 0.4
    again = CosineRandomFeatures(440, 256, gamma=0.05555, seed=2**32 + 7)
    np.testing.assert_array_equal(W, np.asarray(again.W))
    other = CosineRandomFeatures(440, 256, gamma=0.05555, seed=8)
    assert not np.allclose(W, np.asarray(other.W))
    cauchy = CosineRandomFeatures(440, 256, gamma=0.05555,
                                  distribution="cauchy", seed=7)
    assert np.abs(np.asarray(cauchy.W)).max() > 100 * 0.05555  # heavy tails


@pytest.mark.parametrize("chips", [1, 4])
def test_every_chip_of_a_mesh_holds_the_same_random_parameters(chips):
    """One counted program a branch on any mesh; across chips W and b
    come out replicated (no program that takes them has to copy them
    from the first chip) and are the numbers one chip draws."""
    from keystone_tpu.nodes.stats import CosineRandomFeatures
    from keystone_tpu.parallel.mesh import make_mesh, use_mesh
    from keystone_tpu.telemetry import counter

    with use_mesh(make_mesh(jax.devices()[:1])):
        alone = CosineRandomFeatures(440, 256, gamma=0.05555, seed=7)
    before = counter("dispatch.programs_executed").value
    with use_mesh(make_mesh(jax.devices()[:chips])):
        node = CosineRandomFeatures(440, 256, gamma=0.05555, seed=7)
    assert counter("dispatch.programs_executed").value - before == 1
    for drawn, want in ((node.W, alone.W), (node.b, alone.b)):
        assert drawn.sharding.is_fully_replicated
        assert len(drawn.devices()) == chips
        np.testing.assert_array_equal(np.asarray(drawn), np.asarray(want))


def test_the_command_line_runs_the_pipeline_at_small_sizes(capsys):
    """The README's example; with no sizes given the parser's defaults
    are `TimitConfig`'s, the source's."""
    from keystone_tpu.__main__ import REGISTRY
    from keystone_tpu.pipelines import timit

    assert REGISTRY["pipelines.speech.TimitPipeline"] == (
        "keystone_tpu.pipelines.timit", "main")
    result = timit.main(["--num-cosines", "2", "--num-cosine-features", "256",
                         "--n-synth", "2048"])
    assert result["test_accuracy"] > 0.9
    assert "train_error=" in capsys.readouterr().out
