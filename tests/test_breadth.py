"""Tests for the breadth wave: weighted solvers, kernel methods,
classifiers, NLP stack, sparse features, MAP/augmented evaluators."""

import os

import numpy as np
import pytest

from keystone_tpu import Dataset, HostDataset
from keystone_tpu.evaluation import (
    MulticlassClassifierEvaluator,
    AugmentedExamplesEvaluator,
    MeanAveragePrecisionEvaluator,
)
from keystone_tpu.nodes.learning import (
    BlockWeightedLeastSquaresEstimator,
    GaussianKernelTransformer,
    KernelRidgeRegression,
    LinearDiscriminantAnalysis,
    LinearMapEstimator,
    LogisticRegressionEstimator,
    NaiveBayesEstimator,
    PerClassWeightedLeastSquares,
)
from keystone_tpu.nodes.nlp import (
    NGramsHashingTF,
    HashingTF,
    NaiveBitPackIndexer,
    NGramsCounts,
    NGramsFeaturizer,
    StupidBackoffEstimator,
    Tokenizer,
    WordFrequencyEncoder,
)
from keystone_tpu.nodes.util import (
    AllSparseFeatures,
    ClassLabelIndicatorsFromInt,
    CommonSparseFeatures,
)
from keystone_tpu.nodes.nlp.text import TermFrequency


# ------------------------------------------------------------- weighted LS


def test_bwls_mixture_zero_equals_unweighted():
    """mixtureWeight=0 → every class uses uniform 1/n weights → matches
    plain ridge (cross-implementation agreement,
    BlockWeightedLeastSquaresSuite.scala:115)."""
    rng = np.random.default_rng(0)
    n, d, k = 160, 12, 3
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.integers(0, k, n)
    Y = (2.0 * np.eye(k, dtype=np.float32)[y] - 1.0)
    lam = 1.0
    bw = BlockWeightedLeastSquaresEstimator(d, 12, lam, mixture_weight=0.0).fit(
        Dataset(X), Dataset(Y)
    )
    # unweighted ridge on 1/n-scaled objective: (XᵀX/n + λI) W = XᵀYc/n
    xm, ym = X.mean(0), Y.mean(0)
    Xc, Yc = X - xm, Y - ym
    Wref = np.linalg.solve(Xc.T @ Xc / n + lam * np.eye(d), Xc.T @ Yc / n)
    np.testing.assert_allclose(np.asarray(bw.W), Wref, atol=2e-2, rtol=5e-2)


def test_bwls_zero_gradient():
    """Weighted normal equations hold at the solution (the reference's
    zero-gradient check, BlockWeightedLeastSquaresSuite.scala:142-166)."""
    rng = np.random.default_rng(1)
    n, d, k = 120, 10, 3
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.integers(0, k, n)
    Y = (2.0 * np.eye(k, dtype=np.float32)[y] - 1.0)
    lam, mw = 0.5, 0.7
    model = BlockWeightedLeastSquaresEstimator(5, 25, lam, mw).fit(
        Dataset(X), Dataset(Y)
    )
    W = np.asarray(model.W)
    b = np.asarray(model.b)
    for c in range(k):
        member = (Y[:, c] > 0).astype(np.float64)
        wts = mw * member / member.sum() + (1 - mw) / n
        resid = X @ W[:, c] + b[c] - Y[:, c]
        grad = X.T @ (wts * resid) + lam * W[:, c]
        assert np.abs(grad).max() < 5e-3, f"class {c}"


def test_per_class_weighted_delegates():
    rng = np.random.default_rng(2)
    X = rng.normal(size=(64, 6)).astype(np.float32)
    y = rng.integers(0, 2, 64)
    Y = 2.0 * np.eye(2, dtype=np.float32)[y] - 1.0
    model = PerClassWeightedLeastSquares(0.1, 0.5).fit(Dataset(X), Dataset(Y))
    assert np.asarray(model.W).shape == (6, 2)


# ------------------------------------------------------------------ kernels


def test_gaussian_kernel_values():
    X = np.array([[0.0, 0.0], [1.0, 0.0]], np.float32)
    t = GaussianKernelTransformer(X, gamma=0.5)
    K = np.asarray(t.apply_batch(Dataset(X)).numpy())
    np.testing.assert_allclose(np.diag(K), 1.0, atol=1e-5)
    np.testing.assert_allclose(K[0, 1], np.exp(-0.5), atol=1e-5)


def test_krr_learns_xor():
    """XOR learnability (KernelModelSuite.scala:13-39)."""
    X = np.array([[0, 0], [0, 1], [1, 0], [1, 1]], np.float32)
    X = np.tile(X, (16, 1)) + 0.05 * np.random.default_rng(3).normal(
        size=(64, 2)
    ).astype(np.float32)
    y = (np.round(X[:, 0]) != np.round(X[:, 1])).astype(int)
    Y = 2.0 * np.eye(2, dtype=np.float32)[y] - 1.0
    model = KernelRidgeRegression(gamma=2.0, lam=0.01, block_size=16, num_epochs=4).fit(
        Dataset(X), Dataset(Y)
    )
    preds = np.argmax(model.apply_batch(Dataset(X)).numpy(), axis=1)
    assert (preds == y).mean() > 0.95


def test_krr_blocked_equals_unblocked():
    """blocked == unblocked (KernelModelSuite.scala:29-39)."""
    rng = np.random.default_rng(4)
    X = rng.normal(size=(48, 3)).astype(np.float32)
    Y = rng.normal(size=(48, 2)).astype(np.float32)
    full = KernelRidgeRegression(1.0, 0.5, block_size=48, num_epochs=8).fit(
        Dataset(X), Dataset(Y)
    )
    blocked = KernelRidgeRegression(1.0, 0.5, block_size=12, num_epochs=8).fit(
        Dataset(X), Dataset(Y)
    )
    pred_f = full.apply_batch(Dataset(X)).numpy()
    pred_b = blocked.apply_batch(Dataset(X)).numpy()
    np.testing.assert_allclose(pred_f, pred_b, atol=5e-2)


# -------------------------------------------------------------- classifiers


def test_naive_bayes_separates_counts():
    X = np.array(
        [[5, 0, 1], [4, 1, 0], [0, 5, 1], [1, 4, 0]], np.float32
    )
    y = np.array([0, 0, 1, 1], np.int32)
    model = NaiveBayesEstimator(2).fit(Dataset(X), Dataset(y))
    scores = model.apply_batch(Dataset(X)).numpy()
    assert (np.argmax(scores, axis=1) == y).all()


def test_logistic_regression_linearly_separable():
    rng = np.random.default_rng(5)
    X = np.concatenate(
        [rng.normal(-2, 0.5, (60, 2)), rng.normal(2, 0.5, (60, 2))]
    ).astype(np.float32)
    y = np.array([0] * 60 + [1] * 60, np.int32)
    model = LogisticRegressionEstimator(2, lam=1e-3, num_iters=40).fit(
        Dataset(X), Dataset(y)
    )
    preds = np.asarray(model.apply_batch(Dataset(X)).numpy())
    assert (preds == y).mean() > 0.98


def test_lda_projects_classes_apart():
    rng = np.random.default_rng(6)
    X = np.concatenate(
        [rng.normal([0, 0, 0], 1, (80, 3)), rng.normal([5, 5, 0], 1, (80, 3))]
    ).astype(np.float32)
    y = np.array([0] * 80 + [1] * 80)
    proj = LinearDiscriminantAnalysis(1).fit(Dataset(X), Dataset(y.astype(np.int32)))
    Z = proj.apply_batch(Dataset(X)).numpy().ravel()
    gap = abs(Z[:80].mean() - Z[80:].mean())
    spread = Z[:80].std() + Z[80:].std()
    assert gap > 2 * spread


# ---------------------------------------------------------------------- NLP


def test_tokenize_ngrams_counts():
    tok = Tokenizer()
    toks = tok.apply("the cat sat on the mat")
    ngrams = NGramsFeaturizer([1, 2]).apply(toks)
    assert ("the",) in ngrams and ("the", "cat") in ngrams
    counted = NGramsCounts("default").apply_batch(HostDataset([ngrams, ngrams]))
    pairs = dict(counted.items[0])
    assert pairs[("the",)] == 4  # 2 occurrences x 2 docs


def test_hashing_tf_and_term_frequency():
    v = HashingTF(16).apply(["a", "b", "a"])
    assert v.sum() == 3.0 and v.shape == (16,)
    tf = dict(TermFrequency().apply(["a", "b", "a"]))
    assert tf["a"] == 2


def test_word_frequency_encoder_rank_and_oov():
    enc = WordFrequencyEncoder().fit(
        HostDataset([["a", "b", "a", "c"], ["a", "b"]])
    )
    assert enc.apply(["a", "b", "c", "zzz"]) == [0, 1, 2, -1]


def test_bitpack_indexer_roundtrip():
    idx = NaiveBitPackIndexer()
    packed = idx.pack([3, 7, 11])
    assert idx.unpack(packed) == [3, 7, 11]
    assert idx.unpack(idx.remove_far_left_word(packed)) == [7, 11]


def test_stupid_backoff_scores():
    from collections import Counter

    counts = Counter(
        {("the", "cat"): 2, ("the", "dog"): 1, ("the",): 3, ("cat",): 2, ("dog",): 1}
    )
    model = StupidBackoffEstimator().fit(HostDataset([counts]))
    assert abs(model.score(("the", "cat")) - 2 / 3) < 1e-9
    # unseen bigram backs off to alpha * unigram freq
    assert abs(model.score(("cat", "dog")) - 0.4 * (1 / 6)) < 1e-9


def test_sparse_features_topk_and_vectorize():
    docs = [[("a", 1.0), ("b", 2.0)], [("a", 1.0), ("c", 3.0)], [("a", 1.0)]]
    vec = CommonSparseFeatures(2).fit(HostDataset(docs))
    out = vec.apply_batch(HostDataset(docs))
    assert out.dim == 2
    assert out.matrix.shape == (3, 2)
    all_vec = AllSparseFeatures().fit(HostDataset(docs))
    assert all_vec.apply_batch(HostDataset(docs)).dim == 3


# --------------------------------------------------------------- evaluators


def test_map_evaluator_perfect_and_reverse():
    scores = np.array([[0.9, 0.1], [0.8, 0.2], [0.1, 0.9]])
    actuals = [[0], [0], [1]]
    aps = MeanAveragePrecisionEvaluator(2)(scores, actuals)
    np.testing.assert_allclose(aps, [1.0, 1.0], atol=1e-9)


def test_augmented_examples_evaluator_averages():
    ids = ["a", "a", "b", "b"]
    scores = np.array([[0.6, 0.4], [0.0, 1.0], [0.9, 0.1], [0.8, 0.2]])
    actuals = [1, 1, 0, 0]
    m = AugmentedExamplesEvaluator(2)(ids, scores, actuals)
    # 'a' mean = [0.3, 0.7] -> 1 correct; 'b' -> 0 correct
    assert m.accuracy == 1.0


def test_bitpack_rejects_overflow_and_roundtrips_max():
    from keystone_tpu.nodes.nlp.indexers import MAX_WORD

    idx = NaiveBitPackIndexer()
    assert idx.unpack(idx.pack([MAX_WORD, 0]))[0] == MAX_WORD
    with pytest.raises(ValueError):
        idx.pack([MAX_WORD + 1])


def test_sparse_vectorizer_single_batch_duplicate_parity():
    from keystone_tpu.nodes.util import AllSparseFeatures

    docs = [[("a", 1.0), ("a", 2.0)]]
    vec = AllSparseFeatures().fit(HostDataset(docs))
    single = vec.apply(docs[0]).toarray().ravel()
    batch = vec.apply_batch(HostDataset(docs)).matrix.toarray().ravel()
    np.testing.assert_allclose(single, batch)
    assert single[0] == 3.0


def test_bwls_single_class():
    """Degenerate one-class problem must not NaN or diverge
    (BlockWeightedLeastSquaresSuite.scala:168)."""
    rng = np.random.default_rng(5)
    n, d = 48, 6
    X = rng.normal(size=(n, d)).astype(np.float32)
    Y = np.ones((n, 1), np.float32)  # every example positive, k=1
    m = BlockWeightedLeastSquaresEstimator(d, 4, lam=1.0, mixture_weight=0.3).fit(
        Dataset(X), Dataset(Y)
    )
    W = np.asarray(m.W)
    assert np.all(np.isfinite(W))
    assert np.linalg.norm(W) < 1e3  # bounded, not merely finite
    preds = X @ W + np.asarray(m.b)
    # every training label is +1: the ridge fit must predict positive
    assert np.all(preds > 0)


def test_bwls_nondivisible_blocksize():
    """d % block_size != 0 pads the trailing block
    (BlockWeightedLeastSquaresSuite.scala:188): result must agree with
    the single-block solve."""
    rng = np.random.default_rng(6)
    n, d, k = 160, 10, 3  # block 4 -> blocks of 4,4,2
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.integers(0, k, n)
    Y = 2.0 * np.eye(k, dtype=np.float32)[y] - 1.0
    blocked = BlockWeightedLeastSquaresEstimator(4, 20, 1.0, mixture_weight=0.2).fit(
        Dataset(X), Dataset(Y)
    )
    single = BlockWeightedLeastSquaresEstimator(d, 20, 1.0, mixture_weight=0.2).fit(
        Dataset(X), Dataset(Y)
    )
    np.testing.assert_allclose(
        np.asarray(blocked.W), np.asarray(single.W), atol=5e-2, rtol=5e-2
    )


def test_bwls_count_smaller_than_shards():
    """n < mesh shards leaves some shards all-padding (the reference's
    empty-partition case, BlockWeightedLeastSquaresSuite.scala:72)."""
    rng = np.random.default_rng(7)
    n, d, k = 5, 4, 2  # 8-device mesh -> shards with zero valid rows
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.integers(0, k, n)
    Y = 2.0 * np.eye(k, dtype=np.float32)[y] - 1.0
    m = BlockWeightedLeastSquaresEstimator(d, 2, 1.0, mixture_weight=0.0).fit(
        Dataset(X), Dataset(Y)
    )
    assert np.all(np.isfinite(np.asarray(m.W)))


def test_ngrams_hashing_tf_equivalence():
    """NGramsHashingTF ≡ NGramsFeaturizer ∘ HashingTF — the reference
    proves its rolling hash matches the composed pair
    (NGramsHashingTF.scala:25-118)."""
    tokens = "the quick brown fox jumps over the lazy dog the quick".split()
    fused = NGramsHashingTF([1, 2, 3], 64).apply(tokens)
    composed = HashingTF(64).apply(NGramsFeaturizer([1, 2, 3]).apply(tokens))
    np.testing.assert_array_equal(fused, composed)


def test_multiclass_summary_pretty_printer():
    """Mahout-style summary block (MulticlassClassifierEvaluator.scala:
    123-167): spot-check headline metrics appear."""
    preds = Dataset(np.array([0, 1, 2, 1, 0], np.int32))
    actual = Dataset(np.array([0, 1, 1, 1, 0], np.int32))
    s = MulticlassClassifierEvaluator(3).evaluate(preds, actual).summary()
    assert "Confusion matrix" in s and "accuracy" in s.lower()


def test_kernel_apply_is_single_dispatch(monkeypatch):
    # the blocked kernel apply must be ONE jitted scan, not one dispatch
    # per train block (per-block host dispatch would dominate the
    # apply)
    from keystone_tpu.nodes.learning import kernels as K

    rng = np.random.default_rng(5)
    Xtr = rng.normal(size=(50, 3)).astype(np.float32)  # pads to 4 blocks of 16
    alpha = rng.normal(size=(50, 2)).astype(np.float32)
    Xte = rng.normal(size=(20, 3)).astype(np.float32)

    calls = []
    orig = K._kernel_apply_scan

    def counting(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    monkeypatch.setattr(K, "_kernel_apply_scan", counting)
    mapper = K.KernelBlockLinearMapper(Xtr, alpha, gamma=0.7, block_size=16)
    out = np.asarray(mapper.apply_batch(Dataset(Xte)).numpy())
    assert len(calls) == 1

    # correctness vs the unblocked dense product
    D = ((Xte[:, None, :] - Xtr[None, :, :]) ** 2).sum(-1)
    expect = np.exp(-0.7 * D) @ alpha
    np.testing.assert_allclose(out, expect, atol=1e-4)


def test_block_mapper_apply_and_evaluate():
    # incremental per-block eval (BlockLinearMapper.scala:96-137): one
    # scan dispatch, last partial == full apply
    from keystone_tpu.nodes.learning.block_ls import BlockLinearMapper

    rng = np.random.default_rng(6)
    X = rng.normal(size=(30, 10)).astype(np.float32)
    W = rng.normal(size=(10, 3)).astype(np.float32)
    b = rng.normal(size=(3,)).astype(np.float32)
    mapper = BlockLinearMapper(W, b, block_size=4)  # 3 blocks (last padded)
    ds = Dataset(X)

    evals = list(mapper.apply_and_evaluate(ds, lambda d: np.asarray(d.numpy())))
    assert len(evals) == 3
    full = np.asarray(mapper.apply_batch(ds).numpy())
    np.testing.assert_allclose(evals[-1], full, atol=1e-5)
    # first partial uses only the first feature block
    np.testing.assert_allclose(evals[0], X[:, :4] @ W[:4] + b, atol=1e-5)
    assert not np.allclose(evals[0], full)


def test_apply_and_evaluate_chunked_matches_unchunked():
    # chunked scans (memory-bounded dispatch groups) must yield the same
    # partial-prediction sequence as one block per dispatch
    from keystone_tpu.nodes.learning.block_ls import BlockLinearMapper

    rng = np.random.default_rng(8)
    X = rng.normal(size=(20, 12)).astype(np.float32)
    W = rng.normal(size=(12, 2)).astype(np.float32)
    mapper = BlockLinearMapper(W, block_size=3)  # 4 blocks
    ds = Dataset(X)
    grab = lambda d: np.asarray(d.numpy())
    one = list(mapper.apply_and_evaluate(ds, grab, blocks_per_dispatch=1))
    big = list(mapper.apply_and_evaluate(ds, grab, blocks_per_dispatch=3))
    assert len(one) == len(big) == 4
    for a, b in zip(one, big):
        np.testing.assert_allclose(a, b, atol=1e-5)


# ------------------------------------------------- reference aMat/bMat fixtures
# (the exact 15x12 / 15x3 matrices the reference's BWLS suite loads —
# BlockWeightedLeastSquaresSuite.scala:63-223)


def _load_amat_bmat(a="aMat.csv", b="bMat.csv"):
    base = os.path.join(os.path.dirname(__file__), "resources")
    A = np.loadtxt(os.path.join(base, a), delimiter=",").astype(np.float32)
    B = np.loadtxt(os.path.join(base, b), delimiter=",").astype(np.float32)
    if B.ndim == 1:
        B = B[:, None]
    return A, B


def test_bwls_reference_fixture_zero_gradient():
    """The reference's exact zero-gradient configuration: aMat/bMat,
    blockSize=4, numIter=10, lambda=0.1, mixtureWeight=0.3, |grad|<1e-2
    (BlockWeightedLeastSquaresSuite.scala:142-166)."""
    A, B = _load_amat_bmat()
    n, k = B.shape
    lam, mw = 0.1, 0.3
    model = BlockWeightedLeastSquaresEstimator(4, 10, lam, mw).fit(
        Dataset(A), Dataset(B)
    )
    W = np.asarray(model.W, np.float64)
    b = np.asarray(model.b, np.float64)
    A64, B64 = A.astype(np.float64), B.astype(np.float64)
    grad_norm2 = 0.0
    for c in range(k):
        member = (B64[:, c] > 0).astype(np.float64)
        wts = mw * member / member.sum() + (1 - mw) / n
        resid = A64 @ W[:, c] + b[c] - B64[:, c]
        grad = A64.T @ (wts * resid) + lam * W[:, c]
        grad_norm2 += float(grad @ grad)
    assert np.sqrt(grad_norm2) < 1e-2


def test_bwls_reference_fixture_per_class_matches_blockweighted():
    """Per-class delegate ≈ BlockWeighted on the reference fixture
    (BlockWeightedLeastSquaresSuite.scala:115-140)."""
    A, B = _load_amat_bmat()
    lam, mw = 0.1, 0.3
    bw = BlockWeightedLeastSquaresEstimator(4, 10, lam, mw).fit(
        Dataset(A), Dataset(B)
    )
    pc = PerClassWeightedLeastSquares(lam, mw).fit(Dataset(A), Dataset(B))
    np.testing.assert_allclose(
        np.asarray(bw.W), np.asarray(pc.W), atol=5e-2, rtol=5e-2
    )


def test_bwls_reference_fixture_single_class():
    """1-class fixture satisfies its weighted normal equations
    (BlockWeightedLeastSquaresSuite.scala:168-186). With one class the
    per-example weights collapse to mw/n_c + (1-mw)/n = 1/n."""
    A, B = _load_amat_bmat("aMat-1class.csv", "bMat-1class.csv")
    n, k = B.shape
    lam, mw = 0.1, 0.3
    model = BlockWeightedLeastSquaresEstimator(4, 10, lam, mw).fit(
        Dataset(A), Dataset(B)
    )
    W = np.asarray(model.W, np.float64)
    b = np.asarray(model.b, np.float64)
    assert W.shape == (A.shape[1], k)
    A64, B64 = A.astype(np.float64), B.astype(np.float64)
    for c in range(k):
        member = (B64[:, c] > 0).astype(np.float64)
        wts = mw * member / max(member.sum(), 1.0) + (1 - mw) / n
        resid = A64 @ W[:, c] + b[c] - B64[:, c]
        grad = A64.T @ (wts * resid) + lam * W[:, c]
        assert np.abs(grad).max() < 1e-2, f"class {c}: {np.abs(grad).max()}"


def test_bwls_reference_fixture_nondivisible_blocksize():
    """nFeatures=12 not divisible by blockSize=5
    (BlockWeightedLeastSquaresSuite.scala:188-223): same solution as a
    divisible blocking."""
    A, B = _load_amat_bmat()
    lam, mw = 0.1, 0.3
    m5 = BlockWeightedLeastSquaresEstimator(5, 12, lam, mw).fit(
        Dataset(A), Dataset(B)
    )
    m4 = BlockWeightedLeastSquaresEstimator(4, 12, lam, mw).fit(
        Dataset(A), Dataset(B)
    )
    np.testing.assert_allclose(
        np.asarray(m5.W), np.asarray(m4.W), atol=5e-2, rtol=5e-2
    )


def test_lda_iris_matches_published_eigenvectors():
    """The reference's iris fixture (LinearDiscriminantAnalysisSuite.
    scala:13-38): LDA(2) on standardized iris must reproduce the
    published discriminant directions (Raschka's LDA tutorial), up to
    sign and scale — the reference normalizes to unit length."""
    path = os.path.join(os.path.dirname(__file__), "resources", "iris.data")
    rows = [l.strip() for l in open(path) if l.strip()]
    X = np.array([[float(v) for v in r.split(",")[:4]] for r in rows],
                 np.float64)
    name_to_label = {"Iris-setosa": 1, "Iris-versicolor": 2,
                     "Iris-virginica": 3}
    y = np.array([name_to_label[r.split(",")[-1]] for r in rows], np.int32)
    Xs = ((X - X.mean(0)) / X.std(0, ddof=1)).astype(np.float32)

    model = LinearDiscriminantAnalysis(2).fit(Dataset(Xs), Dataset(y))
    W = np.asarray(model.components, np.float64)
    major = np.array([-0.1498, -0.1482, 0.8511, 0.4808])
    minor = np.array([0.0095, 0.3272, -0.5748, 0.75])
    for col, want in ((W[:, 0], major), (W[:, 1], minor)):
        got = col / np.linalg.norm(col)
        err = min(np.abs(got - want).max(), np.abs(got + want).max())
        assert err < 1e-3, (got, want)


def test_stupid_backoff_reference_corpus_exact_scores():
    """The reference suite's exact corpus and score assertions
    (StupidBackoffSuite.scala:15-79): 'Winter is coming' / 'Finals are
    coming' / 'Summer is coming really soon', n-grams of orders 2-5 via
    the node chain, separate unigram counts fed to the estimator."""
    from collections import Counter

    data = ["Winter is coming", "Finals are coming",
            "Summer is coming really soon"]
    tok = Tokenizer()
    ngrams = Counter()
    unigrams = Counter()
    for s in data:
        toks = tok.apply(s)
        for ng in NGramsFeaturizer(range(2, 6)).apply(toks):
            ngrams[tuple(ng)] += 1
        for ng in NGramsFeaturizer([1]).apply(toks):
            unigrams[ng[0]] += 1

    lm = StupidBackoffEstimator(unigram_counts=dict(unigrams)).fit(
        HostDataset([ngrams])
    )
    num_tokens = sum(unigrams.values())  # 11
    assert abs(lm.score(("is", "coming")) - 2.0 / 2.0) < 1e-12
    assert abs(lm.score(("is", "coming", "really")) - 1.0 / 2.0) < 1e-12
    # backed off once AND current word unseen -> 0
    assert lm.score(("is", "unseen-coming")) == 0.0
    # backed off once, current word seen -> alpha * count/numTokens
    assert abs(
        lm.score(("is-unseen", "coming")) - lm.alpha * 3.0 / num_tokens
    ) < 1e-12


def test_packed_stupid_backoff_matches_recursive_model():
    """PackedStupidBackoffModel (sorted bit-packed arrays, iterative
    vectorized scoring, InitialBigramPartitioner-style first-two-words
    grouping) reproduces the recursive dict model's scores on every
    query class: seen trigram, backed-off bigram, double-backoff,
    OOV members, and bare unigrams. Also pins the reference suite's
    exact values and the 12-bytes/ngram memory bound."""
    from collections import Counter

    from keystone_tpu.nodes.nlp import (
        PackedStupidBackoffEstimator,
        StupidBackoffEstimator,
    )

    rng = np.random.default_rng(0)
    vocab = [f"t{i}" for i in range(300)]
    docs = [
        [vocab[j] for j in rng.zipf(1.4, size=40) % 300]
        for _ in range(200)
    ]
    packed = PackedStupidBackoffEstimator().fit(HostDataset(docs))

    ngrams = Counter()
    unigrams = Counter()
    for toks in docs:
        for o in (2, 3):
            for i in range(len(toks) - o + 1):
                ngrams[tuple(toks[i:i + o])] += 1
        for w in toks:
            unigrams[w] += 1
    ref = StupidBackoffEstimator(unigram_counts=dict(unigrams)).fit(
        HostDataset([ngrams]))

    queries = []
    for toks in docs[:40]:
        for i in range(len(toks) - 2):
            queries.append(tuple(toks[i:i + 3]))
    queries += [
        ("t1", "t2"), ("t5",), ("oov-x", "t2", "t3"),
        ("t1", "oov-x", "t3"), ("t1", "t2", "oov-x"), ("oov-x",),
    ]
    got = packed.score_batch(queries)
    want = np.array([ref.score(q) for q in queries])
    np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-12)

    # memory bound: 12 bytes per distinct 2/3-gram + unigram vector
    n_types = len(packed.keys)
    assert packed.nbytes <= 12 * n_types + 8 * len(packed.unigram) + 64

    # reference suite exact values through the packed path
    data = ["Winter is coming", "Finals are coming",
            "Summer is coming really soon"]
    pk = PackedStupidBackoffEstimator().fit(
        HostDataset([s.split() for s in data]))
    assert abs(pk.score(("is", "coming")) - 1.0) < 1e-12
    assert pk.score(("is", "unseen-coming")) == 0.0
    assert abs(pk.score(("is-unseen", "coming")) - 0.4 * 3.0 / 11) < 1e-12
