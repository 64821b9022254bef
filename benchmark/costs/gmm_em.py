"""Operations and bytes of the mixture's EM iterations of one fit
(`nodes/learning/gmm.py`), from the shapes, whatever implements them:
n sample rows of d dimensions, k components, I iterations. An iteration
is 8 n d k: the two products of the Mahalanobis form (x^2 . 1/var and
x . mu/var, 2 n d k each) and the two moment products of the M-step
(q'x and q'x^2, 2 n d k each); the n k exponentials and the
normalization are not counted, the share is taken of the matrix unit's
peak. Bytes: the samples read once an iteration in float32; the
posteriors live no longer than a block of rows and are not counted.

EM runs at `highest` matmul precision (float32 by several bf16 passes),
so its honest ceiling is a fraction of the bf16 peak the share is taken
of; PERF.md says so beside the number."""


def cost(sizes):
    n = sizes["num_train"] * max(
        1, min(sizes["num_gmm_samples"] // sizes["num_train"],
               sizes["descriptors_per_image"]))
    d, k, iters = sizes["pca_dims"], sizes["gmm_k"], sizes["gmm_iters"]
    return {"flops": iters * 8 * n * d * k, "bytes": iters * 4 * n * d}
