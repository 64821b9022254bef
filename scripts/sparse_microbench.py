"""Measure candidate TPU sparse-matvec primitives head-to-head.

The iterative sparse L-BFGS spends its whole budget in two ops:
  Xv   (n rows, w slots; table lookup W[idx] then reduce over slots)
  XᵀR  (column form: table lookup R[:, cidx] then reduce over slots)
Which XLA lowering is fast on TPU is not derivable from first
principles (gather granularity, lane vs sublane axes, scatter
serialization are all compiler-dependent), so this script times each
candidate at Amazon-like shapes and prints one JSON line per cell.

Run:  python scripts/sparse_microbench.py [--n 8000000] [--d 1024]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

if os.environ.get("KEYSTONE_BACKEND") == "cpu":
    jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp


def timeit(fn, *args, reps: int = 3):
    """Warm once, then time `reps` fresh-valued executions."""
    out = fn(*args)
    jax.tree_util.tree_map(
        lambda x: np.asarray(x.ravel()[:1]).sum(), out)
    best = float("inf")
    for r in range(reps):
        bumped = [a * (1 + 1e-7 * (r + 1)) if jnp.issubdtype(a.dtype, jnp.floating)
                  else a for a in args]
        t0 = time.perf_counter()
        out = fn(*bumped)
        jax.tree_util.tree_map(
            lambda x: np.asarray(x.ravel()[:1]).sum(), out)
        best = min(best, time.perf_counter() - t0)
    return best


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--n", type=int, default=8_000_000)
    p.add_argument("--d", type=int, default=1024)
    p.add_argument("--k", type=int, default=2)
    p.add_argument("--w", type=int, default=5)
    p.add_argument("--block", type=int, default=1 << 19)
    args = p.parse_args()
    n, d, k, w, b = args.n, args.d, args.k, args.w, args.block
    n = n // b * b
    nb = n // b

    key = jax.random.PRNGKey(0)
    ki, kv, kw = jax.random.split(key, 3)
    idxT = jax.random.randint(ki, (w, n), 0, d, jnp.int32)   # slot-major
    valT = jax.random.normal(kv, (w, n), jnp.float32)
    W = jax.random.normal(kw, (k, d), jnp.float32)           # model space
    nnz = n * w
    meta = {"n": n, "d": d, "k": k, "w": w, "block": b,
            "platform": jax.devices()[0].platform}
    print(json.dumps({"meta": meta}), flush=True)

    def report(name, sec, flops=None):
        row = {"candidate": name, "ms": round(sec * 1e3, 2),
               "gbytes_min": round(nnz * (8 + 4 * k) / 1e9, 2),
               "eff_gbs": round(nnz * (8 + 4 * k) / sec / 1e9, 1)}
        print(json.dumps(row), flush=True)

    # A. lane-axis gather: take(table (k,d+1), idx, axis=1) — current impl
    @jax.jit
    def cand_a(valT, W):
        table = jnp.concatenate([W, jnp.zeros((k, 1), W.dtype)], axis=1)

        def body(i, R):
            ib = jax.lax.dynamic_slice_in_dim(idxT, i * b, b, 1)
            vb = jax.lax.dynamic_slice_in_dim(valT, i * b, b, 1)
            g = jnp.take(table, ib, axis=1)  # (k, w, b)
            rb = jnp.einsum("wb,kwb->kb", vb, g)
            return jax.lax.dynamic_update_slice(R, rb, (0, i * b))

        return jax.lax.fori_loop(0, nb, body, jnp.zeros((k, n), jnp.float32))

    report("A_lane_gather", timeit(cand_a, valT, W))

    # B. row gather of a (d+1, k) table from block-transposed indices
    @jax.jit
    def cand_b(valT, W):
        table = jnp.concatenate([W.T, jnp.zeros((1, k), W.dtype)], axis=0)

        def body(i, R):
            ib = jax.lax.dynamic_slice_in_dim(idxT, i * b, b, 1).T  # (b, w)
            vb = jax.lax.dynamic_slice_in_dim(valT, i * b, b, 1).T
            g = jnp.take(table, ib, axis=0)  # (b, w, k)
            rb = jnp.einsum("bw,bwk->bk", vb, g).T
            return jax.lax.dynamic_update_slice(R, rb, (0, i * b))

        return jax.lax.fori_loop(0, nb, body, jnp.zeros((k, n), jnp.float32))

    report("B_row_gather", timeit(cand_b, valT, W))

    # C. per-k 1-D table gather (k unrolled in python, tiny k)
    @jax.jit
    def cand_c(valT, W):
        tables = [jnp.concatenate([W[c], jnp.zeros((1,), W.dtype)])
                  for c in range(k)]

        def body(i, R):
            ib = jax.lax.dynamic_slice_in_dim(idxT, i * b, b, 1)
            vb = jax.lax.dynamic_slice_in_dim(valT, i * b, b, 1)
            rows = [jnp.sum(vb * tables[c][ib], axis=0) for c in range(k)]
            rb = jnp.stack(rows, axis=0)
            return jax.lax.dynamic_update_slice(R, rb, (0, i * b))

        return jax.lax.fori_loop(0, nb, body, jnp.zeros((k, n), jnp.float32))

    report("C_1d_gather", timeit(cand_c, valT, W))

    # D. one-hot densify on MXU: dense_b = onehot GEMM, then dense @ W.T
    #    (the embedding-as-matmul idiom; cost ~ 2·b·w·d one-hot ops +
    #    2·b·d·k MXU flops per block, bf16 one-hot pass)
    @jax.jit
    def cand_d(valT, W):
        iota = jnp.arange(d + 1, dtype=jnp.int32)

        def body(i, R):
            ib = jax.lax.dynamic_slice_in_dim(idxT, i * b, b, 1)
            vb = jax.lax.dynamic_slice_in_dim(valT, i * b, b, 1)
            # (b, d+1) dense block built by compare-accumulate
            dense = jnp.zeros((b, d + 1), jnp.float32)
            for j in range(w):
                dense = dense + jnp.where(
                    ib[j][:, None] == iota[None, :], vb[j][:, None], 0.0)
            rb = (dense[:, :d] @ W.T).T  # (k, b)
            return jax.lax.dynamic_update_slice(R, rb, (0, i * b))

        return jax.lax.fori_loop(0, nb, body, jnp.zeros((k, n), jnp.float32))

    report("D_onehot_mxu", timeit(cand_d, valT, W))

    # E. scatter-densify + MXU (the Gram-accumulate idiom)
    @jax.jit
    def cand_e(valT, W):
        rows = jnp.broadcast_to(jnp.arange(b)[None, :], (w, b))

        def body(i, R):
            ib = jax.lax.dynamic_slice_in_dim(idxT, i * b, b, 1)
            vb = jax.lax.dynamic_slice_in_dim(valT, i * b, b, 1)
            dense = (jnp.zeros((b, d + 1), jnp.float32)
                     .at[rows, ib].add(vb)[:, :d])
            rb = (dense @ W.T).T
            return jax.lax.dynamic_update_slice(R, rb, (0, i * b))

        return jax.lax.fori_loop(0, nb, body, jnp.zeros((k, n), jnp.float32))

    report("E_scatter_mxu", timeit(cand_e, valT, W))

    # F. sort-free segment-sum tmatvec probe: XᵀR via scatter into (k, d+1)
    R = jax.random.normal(jax.random.PRNGKey(9), (k, n), jnp.float32)

    @jax.jit
    def cand_f(valT, R):
        def body(i, acc):
            ib = jax.lax.dynamic_slice_in_dim(idxT, i * b, b, 1)
            vb = jax.lax.dynamic_slice_in_dim(valT, i * b, b, 1)
            Rb = jax.lax.dynamic_slice_in_dim(R, i * b, b, 1)
            contrib = vb[None, :, :] * Rb[:, None, :]
            return acc.at[:, ib.reshape(-1)].add(contrib.reshape(k, -1))

        return jax.lax.fori_loop(0, nb, body, jnp.zeros((k, d + 1), jnp.float32))

    report("F_tmat_scatter", timeit(cand_f, valT, R))

    # G. tmatvec by densify + MXU: dense_bᵀ @ R_bᵀ per block
    @jax.jit
    def cand_g(valT, R):
        rows = jnp.broadcast_to(jnp.arange(b)[None, :], (w, b))

        def body(i, acc):
            ib = jax.lax.dynamic_slice_in_dim(idxT, i * b, b, 1)
            vb = jax.lax.dynamic_slice_in_dim(valT, i * b, b, 1)
            Rb = jax.lax.dynamic_slice_in_dim(R, i * b, b, 1)  # (k, b)
            dense = (jnp.zeros((b, d + 1), jnp.float32)
                     .at[rows, ib].add(vb)[:, :d])
            return acc + Rb @ dense  # (k, d)

        return jax.lax.fori_loop(0, nb, body, jnp.zeros((k, d), jnp.float32))

    report("G_tmat_mxu", timeit(cand_g, valT, R))


if __name__ == "__main__":
    main()
