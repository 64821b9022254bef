"""`KernelRidgeRegression`'s block structure, as the source has it
(KernelRidgeRegression.scala:37-275, KernelMatrix.scala:17-90): contiguous
column blocks of the kernel matrix visited in a seeded shuffled order, an
optional cache of the blocks a fit's first epoch forms, each kept with
the Cholesky factor of its diagonal part, and a Gauss-Seidel iteration
that converges to the dual system's solution."""

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
            "solver.kernel_blocks_reused", "solver.kernel_cache_bytes",
            "solver.kernel_factors_formed", "solver.kernel_factors_reused",
            "solver.kernel_factor_bytes")


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
        assert not any(counts[name] for name in counts if "factor" in name)
    assert set(vars(model)) == {"train_X", "alpha", "gamma", "block_size"}


def test_a_fit_of_one_epoch_keeps_no_block():
    X, Y = _problem()
    _, counts = _counted(lambda: KernelRidgeRegression(
        0.3, 0.5, block_size=24).fit(Dataset(X), Dataset(Y)))
    assert counts == {"steps": 4, "kernel_blocks_formed": 4,
                      "kernel_blocks_reused": 0, "kernel_cache_bytes": 0,
                      "kernel_factors_formed": 0, "kernel_factors_reused": 0,
                      "kernel_factor_bytes": 0}


def test_a_kept_block_s_factor_is_formed_once_and_read_in_every_later_epoch():
    """Three epochs over six blocks: each block's Cholesky factor is
    formed with the block, kept beside it (B x B floats a block, counted
    apart from the blocks' own bytes) and solved on twice; when the fit
    returns nothing of the fit's cache is alive, reachable from the
    mapper or otherwise."""
    import gc

    import jax

    B, blocks, epochs = 17, 6, 3
    X, Y = _problem(n=B * blocks)
    model, counts = _counted(lambda: KernelRidgeRegression(
        0.3, 0.5, block_size=B, num_epochs=epochs, seed=4).fit(
            Dataset(X), Dataset(Y)))
    assert counts["kernel_factors_formed"] == blocks
    assert counts["kernel_factors_reused"] == (epochs - 1) * blocks
    assert counts["kernel_factor_bytes"] == blocks * B * B * 4
    assert counts["kernel_cache_bytes"] == 4 * (B * blocks) ** 2
    gc.collect()
    assert model.alpha.shape == (B * blocks, Y.shape[1])
    assert not [a.shape for a in jax.live_arrays()
                if a.ndim == 2 and a.shape[1] == B]


@pytest.mark.parametrize("lam", [0.5, 0.0], ids=["ridge", "lam_0"])
@pytest.mark.parametrize("n", [96, 90], ids=["whole_blocks", "padded_block"])
def test_solving_on_kept_factors_gives_the_alpha_of_factoring_every_step(
        n, lam):
    """A cached fit factors each K_bb + lam I once and runs `cho_solve` on
    the kept factor in the later epochs; the same fit with
    ``cache_kernel=False`` runs `solve(assume_a="pos")`, a factorization,
    in every step. `solve` is `cho_factor` and `cho_solve` on the same
    operand, so alpha agrees to float32 rounding: also where the last
    block holds 6 rows of padding (ones on their diagonal) and at
    lam = 0, where nothing but the kernel keeps the system definite."""
    X, Y = _problem(n=n)
    fit = lambda cache_kernel: KernelRidgeRegression(
        0.3, lam, block_size=24, num_epochs=4, seed=5,
        cache_kernel=cache_kernel).fit(Dataset(X), Dataset(Y))
    (cached, counts), uncached = _counted(lambda: fit(True)), fit(False)
    assert counts["kernel_factors_reused"] == 12
    alpha = np.asarray(cached.alpha)
    assert np.isfinite(alpha).all() and not alpha[n:].any()
    np.testing.assert_allclose(alpha, np.asarray(uncached.alpha),
                               rtol=2e-5, atol=2e-6)


def test_on_a_mesh_a_kept_factor_is_replicated_and_its_block_is_not(
        monkeypatch):
    """Across devices a kept (n, B) block stays sharded by rows, as the
    data is, and its (B, B) factor is on every device, as the solve is."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("one device: nothing to shard")
    X, Y = _problem()
    step, kept = kernels._krr_step, []

    def recording(*args, **kwargs):
        out = step(*args, **kwargs)
        kept.extend(out[2:])
        return out

    monkeypatch.setattr(kernels, "_krr_step", recording)
    KernelRidgeRegression(0.3, 0.5, block_size=24, num_epochs=2).fit(
        Dataset(X), Dataset(Y))
    assert len(kept) == 4
    for Kb, Ub in kept:
        assert Ub.shape == (24, 24) and Ub.sharding.is_fully_replicated
        assert not Kb.sharding.is_fully_replicated


def _primitives(jaxpr):
    """The names of every primitive of a jaxpr, nested ones included."""
    import jax

    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


@pytest.mark.parametrize("program,factorizations", [
    ("kept", 0), ("forming_and_keeping", 1), ("forming", 1)])
def test_a_step_on_a_kept_block_factors_nothing(program, factorizations):
    """The step handed a kept block and its factor holds no `cholesky`
    (two triangular solves and nothing else of the solve); a forming
    step holds exactly one, whether it hands the factor out or not."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    n, d, k, B = 48, 5, 3, 12
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    kept = (f32(n, B), f32(B, B)) if program == "kept" else None
    step = partial(kernels._krr_step, gamma=0.3, block_size=B,
                   keep_kernel=program == "forming_and_keeping")
    args = (f32(n, d), f32(n, k), f32(n), f32(n, k), f32(n, k), f32(),
            jax.ShapeDtypeStruct((), jnp.int32), kept)
    names = list(_primitives(jax.make_jaxpr(step)(*args).jaxpr))
    assert names.count("cholesky") == factorizations
    if program != "forming":  # `solve` also carries its transpose's pair
        assert names.count("triangular_solve") == 2
    assert ("exp" in names) == (program != "kept")
    outputs = jax.eval_shape(step, *args)
    if program == "forming_and_keeping":
        assert [o.shape for o in outputs[2]] == [(n, B), (B, B)]
    else:
        assert len(outputs) == 2


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


@pytest.mark.parametrize("lost_after,left", [
    # the first epoch's last two blocks are formed, factored and kept, the
    # two before the loss formed again in epoch 1
    (2, {"steps": 10, "kernel_blocks_formed": 4, "kernel_factors_formed": 4,
         "kernel_blocks_reused": 6, "kernel_factors_reused": 6,
         "kernel_factor_bytes": 2 * 24 * 24 * 4}),
    # lost on kept blocks and factors: the second epoch's last two blocks
    # are formed, factored and kept, the third epoch reads those two and
    # forms the other two, which no epoch follows to read
    (6, {"steps": 6, "kernel_blocks_formed": 4, "kernel_factors_formed": 2,
         "kernel_blocks_reused": 2, "kernel_factors_reused": 2,
         "kernel_factor_bytes": 2 * 24 * 24 * 4}),
], ids=["first_epoch", "second_epoch"])
def test_a_resumed_fit_forms_again_what_the_lost_process_had_kept(
        tmp_path, monkeypatch, lost_after, left):
    """A fit that dies and is resumed from the checkpoint ends at the
    uninterrupted fit's alpha: the blocks and factors the lost process
    had kept are formed again, a block's factor with the block, when a
    later epoch reaches them, and kept where another epoch follows."""
    X, Y = _problem()
    make = lambda **kw: KernelRidgeRegression(
        0.3, 0.5, block_size=24, num_epochs=3, seed=2, **kw)
    want = make().fit(Dataset(X), Dataset(Y))

    step, calls = kernels._krr_step, []

    def dying(*args, **kwargs):
        if len(calls) == lost_after:
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
    assert {name: counts[name] for name in left} == left


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
