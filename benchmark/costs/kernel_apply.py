"""Operations and bytes of the kernel model's pass over one fit's
training rows (the train error, `KernelBlockLinearMapper`): m = n rows
against n anchors of d features, k classes. The Gaussian kernel block
is the distance product 2 m n d and the scores are K alpha, 2 m n k (the
norms and the exponential are not counted: the share is taken of the
matrix unit's peak). Bytes are what the work needs, whichever way the
program computes it, in float32: the rows and the anchors read once,
alpha read once, the scores written once; the m x n kernel is never
stored. The pass runs at `highest` matmul precision, so its honest
ceiling is a fraction of the bf16 peak."""


def cost(sizes):
    n, d, k = sizes["num_train"], sizes["feature_dim"], sizes["num_classes"]
    m = n
    return {"flops": 2 * m * n * d + 2 * m * n * k,
            "bytes": 4 * (m * d + n * d + n * k + m * k)}
