"""Worker for the 2-process multihost test (spawned by
test_multihost_2proc.py). Each process owns 4 virtual CPU devices; the
pair forms one 8-device job connected via jax.distributed (Gloo over
localhost — the CPU stand-in for DCN).

Exercises the real multi-host code paths, not the single-process noop:
`global_data_mesh` (model axis within a host, data axis across hosts),
`dataset_from_process_local` (per-host loader splits → one global
Dataset), a cross-host collective, and a full distributed solver fit
checked against the host closed form (SURVEY §2.7 comm backend).
"""

import sys

proc_id = int(sys.argv[1])
port = sys.argv[2]

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 4)

jax.distributed.initialize(
    coordinator_address=f"127.0.0.1:{port}",
    num_processes=2,
    process_id=proc_id,
)

import os
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from keystone_tpu.parallel import multihost
from keystone_tpu.parallel.mesh import use_mesh

assert jax.device_count() == 8 and jax.local_device_count() == 4

mesh = multihost.global_data_mesh(model_shards=2)
assert dict(mesh.shape) == {"data": 4, "model": 2}

# --- global dataset from per-host rows + cross-host reduction ----------
rows = (
    np.arange(proc_id * 8, proc_id * 8 + 8, dtype=np.float32).reshape(8, 1)
    * np.ones((1, 4), np.float32)
)
ds = multihost.dataset_from_process_local(rows, mesh=mesh)
total = float(jax.jit(lambda x: x.sum())(ds.array))
want = float(np.arange(16, dtype=np.float32).sum() * 4)
assert abs(total - want) < 1e-3, (total, want)

# --- distributed solver fit vs host closed form ------------------------
rng = np.random.default_rng(0)  # same seed on both hosts: same problem
n_global, d, k, lam = 64, 6, 3, 1e-2
X = rng.normal(size=(n_global, d)).astype(np.float32)
W_true = rng.normal(size=(d, k)).astype(np.float32)
Y = (X @ W_true + 0.01 * rng.normal(size=(n_global, k))).astype(np.float32)

lo, hi = proc_id * (n_global // 2), (proc_id + 1) * (n_global // 2)
with use_mesh(mesh):
    Xds = multihost.dataset_from_process_local(X[lo:hi], mesh=mesh)
    Yds = multihost.dataset_from_process_local(Y[lo:hi], mesh=mesh)

    from keystone_tpu.nodes.learning import LinearMapEstimator

    model = LinearMapEstimator(lam=lam, fit_intercept=False).fit(Xds, Yds)
    W = np.asarray(model.W)

W_ref = np.linalg.solve(X.T @ X + lam * np.eye(d), X.T @ Y)
err = np.abs(W - W_ref).max() / max(np.abs(W_ref).max(), 1e-9)
assert err < 5e-3, err

# --- BCD block solver across hosts (scan + psum over the DCN link) -----
with use_mesh(mesh):
    from keystone_tpu.nodes.learning import BlockLeastSquaresEstimator

    bcd = BlockLeastSquaresEstimator(block_size=3, num_iter=25, lam=lam).fit(
        Xds, Yds
    )
    Wb = np.asarray(bcd.W)[:d]  # strip intercept row if present
err_b = np.abs(Wb - W_ref).max() / max(np.abs(W_ref).max(), 1e-9)
assert err_b < 5e-2, err_b

# --- class-weighted BCD across hosts (SURVEY §2.7 class-partition row) --
# One-hot ±1 labels with mixture_weight=0.5 so the per-class weighted
# Gram path (class counts, per-class covariance blend) really runs;
# the cross-host fit must match a single-host fit of the same global
# problem (sharding changes layout, not math).
from keystone_tpu.data.dataset import Dataset
from keystone_tpu.nodes.learning import BlockWeightedLeastSquaresEstimator
from keystone_tpu.parallel.mesh import make_mesh

cls = (np.arange(n_global) % 3)
Yc = (2.0 * np.eye(3, dtype=np.float32)[cls] - 1.0).astype(np.float32)
with use_mesh(mesh):
    Ycds = multihost.dataset_from_process_local(Yc[lo:hi], mesh=mesh)
    bwls = BlockWeightedLeastSquaresEstimator(
        d, num_iter=8, lam=lam, mixture_weight=0.5
    ).fit(Xds, Ycds)
    Ww = np.asarray(bwls.W)
with use_mesh(make_mesh(jax.local_devices()[:1])):
    bwls1 = BlockWeightedLeastSquaresEstimator(
        d, num_iter=8, lam=lam, mixture_weight=0.5
    ).fit(Dataset(X), Dataset(Yc))
    Ww1 = np.asarray(bwls1.W)
err_w = np.abs(Ww - Ww1).max() / max(np.abs(Ww1).max(), 1e-9)
assert err_w < 1e-3, f"cross-host BWLS diverged from single-host: {err_w}"

# --- distributed PCA (TSQR) across hosts -------------------------------
# The per-shard QR runs on every device of both hosts; the R-combine and
# SVD are replicated. Principal subspace must match the host-side SVD of
# the same global matrix (columns up to the sign convention, which
# _sign_convention pins).
from keystone_tpu.nodes.learning import DistributedPCAEstimator

rng_p = np.random.default_rng(2)
Xp = (rng_p.normal(size=(48, 5)) * np.array([4.0, 2.0, 1.0, 0.5, 0.1])).astype(
    np.float32
)
lo_p, hi_p = proc_id * 24, (proc_id + 1) * 24
with use_mesh(mesh):
    Xpds = multihost.dataset_from_process_local(Xp[lo_p:hi_p], mesh=mesh)
    V = np.asarray(DistributedPCAEstimator(dims=3).fit(Xpds).components)
Xc = Xp - Xp.mean(axis=0)
_, _, Vt_ref = np.linalg.svd(Xc, full_matrices=False)
V_ref = Vt_ref.T[:, :3]
# compare subspaces column-by-column up to sign
for j in range(3):
    dot = abs(float(V[:, j] @ V_ref[:, j]))
    assert dot > 0.999, f"distributed PCA col {j} off: |cos|={dot}"

# --- kernel ridge regression across hosts ------------------------------
# XOR-style task (KernelModelSuite.scala:13-39): linearly inseparable,
# so success requires the kernel path — permuted column blocks, the
# treeReduce-analog psum of K·alpha, and the distributed residual — to
# work over the cross-host data axis.
rng_k = np.random.default_rng(1)
nk = 32
Xk = rng_k.uniform(-1, 1, size=(nk, 2)).astype(np.float32)
Yk = np.where((Xk[:, 0] > 0) ^ (Xk[:, 1] > 0), 1.0, -1.0).astype(
    np.float32
).reshape(-1, 1)
lo_k, hi_k = proc_id * (nk // 2), (proc_id + 1) * (nk // 2)
with use_mesh(mesh):
    from keystone_tpu.nodes.learning import KernelRidgeRegression

    Xkds = multihost.dataset_from_process_local(Xk[lo_k:hi_k], mesh=mesh)
    Ykds = multihost.dataset_from_process_local(Yk[lo_k:hi_k], mesh=mesh)
    krr = KernelRidgeRegression(
        gamma=2.0, lam=1e-2, block_size=8, num_epochs=4
    ).fit(Xkds, Ykds)
    out = krr(Xkds).get().array
    # the global prediction array spans both hosts; reduce to a fully
    # replicated scalar on device instead of fetching non-addressable
    # shards to the host
    import jax.numpy as jnp

    acc = float(
        jax.jit(lambda p, y: (jnp.sign(p) == y).mean())(out, Ykds.array)
    )
assert acc >= 0.9, f"multihost KRR failed to learn XOR: acc={acc}"

# --- the full north-star pipeline across hosts -------------------------
# build_pipeline (PixelScaler → folded-ZCA Convolver → SymmetricRectifier
# → Pooler → StandardScaler → BCD solve → MaxClassifier) fit and applied
# with the training images dp-sharded ACROSS the two processes — the
# multihost analog of the driver's single-process dryrun_multichip.
from keystone_tpu.loaders.cifar_loader import synthetic_cifar
from keystone_tpu.pipelines.random_patch_cifar import (
    RandomPatchCifarConfig,
    build_pipeline,
)

n_img = 64  # per the global job; each process contributes half
# generate on a LOCAL 1-device mesh so the host copy below is
# addressable; same seed on both hosts -> same global data
local_mesh = make_mesh(jax.local_devices()[:1])
tr, _ = synthetic_cifar(n_img, 8, seed=5, mesh=local_mesh)
imgs = np.asarray(tr.data.numpy())
labs = np.asarray(tr.labels.numpy())
lo_i, hi_i = proc_id * (n_img // 2), (proc_id + 1) * (n_img // 2)
with use_mesh(mesh):
    from keystone_tpu.loaders.csv_loader import LabeledData

    tr_ds = LabeledData(
        data=multihost.dataset_from_process_local(imgs[lo_i:hi_i], mesh=mesh),
        labels=multihost.dataset_from_process_local(labs[lo_i:hi_i], mesh=mesh),
    )
    config = RandomPatchCifarConfig(
        num_filters=16, block_size=64, microbatch=32, sample_patches=2000
    )
    predictor = build_pipeline(tr_ds, config)
    pred_arr = predictor(tr_ds.data).get().array
    train_acc = float(
        jax.jit(lambda p, y: (p == y).mean())(pred_arr, tr_ds.labels.array)
    )
# the synthetic default task is separable: the cross-host fit must
# reach high train accuracy or the distributed pipeline is broken
assert train_acc >= 0.9, f"multihost pipeline train acc {train_acc}"

# --- dp-sharded sparse iterative L-BFGS across hosts -------------------
# rows shard over the cross-host 'data' axis; every row-space reduction
# (gradient, colsum, line-search inner products) psums over the Gloo
# link — the reference's treeReduce-to-master for sparse gradients
# (LBFGS.scala:97-103) as a true multi-process collective
import scipy.sparse as sp

from keystone_tpu.data.sparse import SparseDataset
from keystone_tpu.nodes.learning import SparseLBFGSwithL2

rng_s = np.random.default_rng(5)  # same seed both hosts: same problem
n_s, d_s, k_s = 600, 32, 2
dense_s = (rng_s.normal(size=(n_s, d_s))
           * (rng_s.random((n_s, d_s)) < 0.15)).astype(np.float32)
Ys = rng_s.normal(size=(n_s, k_s)).astype(np.float32)
with use_mesh(mesh):
    # host CSR + host labels (the sparse fit path is host-input by
    # design; a cross-host Dataset would not be host-fetchable)
    m_sp = SparseLBFGSwithL2(lam=1.0, num_iters=50, method="iterative").fit(
        SparseDataset(sp.csr_matrix(dense_s)), Ys)
xm_s, ym_s = dense_s.mean(0), Ys.mean(0)
Xc_s, Yc_s = dense_s - xm_s, Ys - ym_s
W_sp_ref = np.linalg.solve(Xc_s.T @ Xc_s + np.eye(d_s), Xc_s.T @ Yc_s)
err_sp = np.abs(np.asarray(m_sp.W) - W_sp_ref).max() / max(
    np.abs(W_sp_ref).max(), 1e-9)
assert err_sp < 5e-3, f"multihost sparse L-BFGS diverged: {err_sp}"

multihost.barrier()
print(f"[{proc_id}] MULTIHOST_OK", flush=True)
