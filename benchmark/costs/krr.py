"""Operations and bytes of one kernel ridge regression fit by
Gauss-Seidel column blocks (`nodes/learning/kernels.py`), from its
shapes, whatever implements it: n rows of d features, blocks of B
columns of the n x n Gaussian kernel matrix, k classes, E epochs.

Forming a column block is the distance product 2 n B d (the norms and
the exponential are not counted: the share is taken of the matrix
unit's peak). A block step is a Cholesky solve of K_bb + lam I
(B^3/3 + 2 B^2 k) and the update K[:, b] delta (2 n B k). With
`cache_kernel` and more than one epoch every block is formed once, else
in every epoch.

Bytes are what the algorithm needs: the features read once a block
formed; with the cache the n x n matrix written once and read once in
each later epoch, in float32; K alpha (n x k) read and written a step.
Without the cache a block lives no longer than its step and is not
counted.

The solver runs at `highest` matmul precision (float32 by several bf16
passes), so its honest ceiling is a fraction of the bf16 peak the share
is taken of; PERF.md says so beside the number."""


def cost(sizes):
    n, d, k = sizes["num_train"], sizes["feature_dim"], sizes["num_classes"]
    B = min(sizes["kernel_block"], n)
    blocks = -(-n // B)
    epochs = sizes["num_epochs"]
    cached = sizes["cache_kernel"] and epochs > 1
    formed = blocks if cached else epochs * blocks
    step_flops = B**3 / 3 + 2 * B * B * k + 2 * n * B * k
    cache_bytes = 4 * blocks * n * B * epochs if cached else 0
    return {"flops": formed * 2 * n * B * d + epochs * blocks * step_flops,
            "bytes": (4 * formed * (n * d + B * d) + cache_bytes
                      + 4 * epochs * blocks * 2 * n * k)}
