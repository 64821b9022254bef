"""Operations and bytes of one fit's cosine random features, from the
shapes: n frames of `input_dim`, d = `feature_dim` features in all the
branches together, cos(X W + b). The projection is 2 n input_dim d
operations (the bias and the cosine are not counted: the share is taken
of the matrix unit's peak). Bytes are what the work needs, whichever way
the program computes it, in float32: the frames, W and b read once and
the (n, d) features written once. A path that writes each branch to a
buffer of its own and then copies it into the combined array moves the
features three times, and its share shows it."""


def cost(sizes):
    n, p, d = sizes["num_train"], sizes["input_dim"], sizes["feature_dim"]
    return {"flops": 2 * n * p * d,
            "bytes": 4 * (n * p + p * d + d + n * d)}
