"""`KernelRidgeRegression`'s block structure, as the source has it
(KernelRidgeRegression.scala:37-275, KernelMatrix.scala:17-90): contiguous
column blocks of the kernel matrix visited in a seeded shuffled order, an
optional cache of the blocks a fit's first epoch forms, and a
Gauss-Seidel iteration that converges to the dual system's solution."""

import numpy as np
import pytest

from keystone_tpu import telemetry
from keystone_tpu.data.dataset import Dataset
from keystone_tpu.nodes.learning import kernels
from keystone_tpu.nodes.learning.kernels import (
    KernelRidgeRegression,
    block_order,
)

COUNTERS = ("solver.steps", "solver.kernel_blocks_formed",
            "solver.kernel_blocks_reused", "solver.kernel_cache_bytes")


def _problem(n=96, d=5, k=3, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(n, d)).astype(np.float32),
            rng.normal(size=(n, k)).astype(np.float32))


def _kernel(X, gamma):
    d2 = ((X[:, None, :].astype(np.float64) - X[None, :, :]) ** 2).sum(-1)
    return np.exp(-gamma * d2)


def _counted(fit):
    """(model, the solver counters' deltas over ``fit()``)."""
    before = {name: telemetry.counter(name).value for name in COUNTERS}
    model = fit()
    return model, {name.split(".")[1]: telemetry.counter(name).value - before[name]
                   for name in COUNTERS}


@pytest.mark.parametrize("cache_kernel", [True, False],
                         ids=["cached", "uncached"])
def test_the_cache_changes_what_is_formed_and_not_the_model(cache_kernel):
    """Three epochs over four blocks: with the cache every block is
    formed once and read twice, without it formed three times; alpha is
    the same to float32 rounding either way, and the model keeps the
    anchors and alpha alone."""
    X, Y = _problem()
    epochs, B, n = 3, 24, X.shape[0]
    blocks = n // B
    want = KernelRidgeRegression(
        0.3, 0.5, block_size=B, num_epochs=epochs, seed=4,
        cache_kernel=not cache_kernel).fit(Dataset(X), Dataset(Y))
    model, counts = _counted(lambda: KernelRidgeRegression(
        0.3, 0.5, block_size=B, num_epochs=epochs, seed=4,
        cache_kernel=cache_kernel).fit(Dataset(X), Dataset(Y)))
    np.testing.assert_allclose(np.asarray(model.alpha),
                               np.asarray(want.alpha), rtol=1e-5, atol=1e-6)
    assert counts["steps"] == epochs * blocks
    if cache_kernel:
        assert counts["kernel_blocks_formed"] == blocks
        assert counts["kernel_blocks_reused"] == (epochs - 1) * blocks
        assert counts["kernel_cache_bytes"] == 4 * n * n
    else:
        assert counts["kernel_blocks_formed"] == epochs * blocks
        assert counts["kernel_blocks_reused"] == 0
        assert counts["kernel_cache_bytes"] == 0
    assert set(vars(model)) == {"train_X", "alpha", "gamma", "block_size"}


def test_a_fit_of_one_epoch_keeps_no_block():
    X, Y = _problem()
    _, counts = _counted(lambda: KernelRidgeRegression(
        0.3, 0.5, block_size=24).fit(Dataset(X), Dataset(Y)))
    assert counts == {"steps": 4, "kernel_blocks_formed": 4,
                      "kernel_blocks_reused": 0, "kernel_cache_bytes": 0}


@pytest.mark.parametrize("n", [96, 90], ids=["whole_blocks", "padded_block"])
def test_gauss_seidel_converges_to_the_dual_system_s_solution(n):
    """Enough epochs over contiguous blocks in shuffled order give
    solve(K + lam I, Y): the iteration is the source's, on the system the
    source states. With 90 rows the last block of 24 holds 18 rows and 6
    of padding, whose alpha stays 0."""
    import jax

    from keystone_tpu.parallel.mesh import make_mesh, use_mesh

    X, Y = _problem(n=n)
    gamma, lam = 0.3, 0.5
    # one device: 240 launches queued without a fence deadlock XLA:CPU's
    # in-process collectives on the 8-device test mesh (ROADMAP.md M6)
    with use_mesh(make_mesh(jax.devices()[:1])):
        model = KernelRidgeRegression(
            gamma, lam, block_size=24, num_epochs=60, seed=1).fit(
                Dataset(X), Dataset(Y))
        scores = np.asarray(model.apply_batch(Dataset(X)).numpy())
    want = np.linalg.solve(_kernel(X, gamma) + lam * np.eye(n), Y)
    alpha = np.asarray(model.alpha)
    np.testing.assert_allclose(alpha[:n], want, rtol=2e-3, atol=2e-4)
    assert not alpha[n:].any()
    np.testing.assert_allclose(scores, _kernel(X, gamma) @ want,
                               rtol=2e-3, atol=2e-4)


def test_block_order_is_a_function_of_seed_and_epoch():
    for seed, epoch in [(0, 0), (0, 1), (7, 0), (7, 5)]:
        order = block_order(seed, epoch, 10)
        assert sorted(order) == list(range(10))
        np.testing.assert_array_equal(order, block_order(seed, epoch, 10))
    orders = {tuple(block_order(seed, epoch, 10))
              for seed in (0, 100) for epoch in range(3)}
    assert len(orders) == 6  # every epoch and seed shuffles anew


def test_a_fit_visits_fixed_blocks_in_each_epoch_s_order(monkeypatch):
    """The steps of a fit take a block index and nothing else from the
    host: each epoch's indices are `block_order(seed, epoch)`, so a
    block's rows are the same in every epoch (what lets a block be
    kept)."""
    X, Y = _problem()
    visited = []
    step = kernels._krr_step

    def recording(*args, **kwargs):
        block = args[6]
        assert isinstance(block, np.int32) and kwargs["block_size"] == 24
        visited.append(int(block))
        return step(*args, **kwargs)

    monkeypatch.setattr(kernels, "_krr_step", recording)
    KernelRidgeRegression(0.3, 0.5, block_size=24, num_epochs=3,
                          seed=11).fit(Dataset(X), Dataset(Y))
    assert visited == [int(b) for epoch in range(3)
                       for b in block_order(11, epoch, 4)]


def test_a_resumed_fit_forms_again_what_the_lost_process_had_kept(
        tmp_path, monkeypatch):
    """A fit that dies in its first epoch and is resumed from the
    checkpoint ends at the uninterrupted fit's alpha: the blocks the lost
    process had kept are formed again when a later epoch reaches them."""
    X, Y = _problem()
    make = lambda **kw: KernelRidgeRegression(
        0.3, 0.5, block_size=24, num_epochs=3, seed=2, **kw)
    want = make().fit(Dataset(X), Dataset(Y))

    step, calls = kernels._krr_step, []

    def dying(*args, **kwargs):
        if len(calls) == 2:
            raise RuntimeError("lost")
        calls.append(1)
        return step(*args, **kwargs)

    monkeypatch.setattr(kernels, "_krr_step", dying)
    checkpointed = dict(checkpoint_dir=str(tmp_path),
                        blocks_before_checkpoint=1)
    with pytest.raises(RuntimeError, match="lost"):
        make(**checkpointed).fit(Dataset(X), Dataset(Y))
    monkeypatch.setattr(kernels, "_krr_step", step)
    model, counts = _counted(
        lambda: make(**checkpointed).fit(Dataset(X), Dataset(Y)))
    np.testing.assert_allclose(np.asarray(model.alpha),
                               np.asarray(want.alpha), rtol=1e-5, atol=1e-6)
    # 10 of the 12 steps were left; the first epoch's last two blocks were
    # formed and kept, the two before the loss formed again in epoch 1
    assert counts["steps"] == 10
    assert counts["kernel_blocks_formed"] == 4
    assert counts["kernel_blocks_reused"] == 6


def test_the_mapper_s_products_run_at_the_precision_it_declares():
    """`KernelBlockLinearMapper` declares `exact`: the scan's products,
    the distance product and K alpha alike, are lowered at `highest`."""
    import jax
    import jax.numpy as jnp

    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    lowered = kernels._kernel_apply_scan.lower(
        f32(8, 5), f32(32, 5), f32(32, 3), 0.3, 16, 2, False)
    dots = [line for line in lowered.as_text().splitlines()
            if "dot_general" in line]
    assert len(dots) == 2
    assert all("HIGHEST" in line and "DEFAULT" not in line for line in dots)
    assert "ks.krr.apply" in lowered.as_text(debug_info=True)
