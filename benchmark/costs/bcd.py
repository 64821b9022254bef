"""Operations and bytes of one block-coordinate-descent fit, from its
shapes (`nodes/learning/block_ls.py`): n rows, d features in blocks of
B, k classes, E epochs. Per block step: the Gram Xb'Xb (2 n B^2), the
correlation Xb'R (2 n B k), two residual updates (4 n B k) and a
Cholesky solve (B^3/3 + 2 B^2 k). Every epoch forms each block's Gram
again. Bytes: each block step reads its (n, B) slice of X for the Gram,
the correlation and both residual updates once each pass is fused at
best: counted as one read of the slice and a read and write of the
(n, k) residual, plus the centring pass (read and write of X) once.

The solver runs at `highest` matmul precision (float32 by several bf16
passes), so its honest ceiling is a fraction of the bf16 peak the share
is taken of; PERF.md says so beside the number."""


def cost(sizes):
    n, k = sizes["num_train"], sizes["num_classes"]
    d = sizes["feature_dim"]
    B = min(sizes["block_size"], d)
    blocks = -(-d // B)
    epochs = sizes["bcd_iters"]
    step_flops = 2 * n * B * B + 6 * n * B * k + B**3 / 3 + 2 * B * B * k
    step_bytes = 4 * (n * B + 2 * n * k)
    return {"flops": epochs * blocks * step_flops,
            "bytes": epochs * blocks * step_bytes + 2 * 4 * n * blocks * B}
