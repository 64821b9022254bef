"""Operations and bytes of the featurizer's conv + rectify + pool over
one fit's training images, from its shapes: n images of h x w x c, K
filters of p x p x c at stride 1, whichever way the program computes
them (its fused Mosaic kernel, `ops/pallas_kernels.py`, or XLA's
convolution and a windowed sum). The convolution is
2 n (h-p+1)(w-p+1) p^2 c K operations; rectifying both ways and pooling
add 3 per conv output. Bytes are what the work needs, not what one way
of doing it moves: the images read once and the pooled features written
once, in float32. (A path that writes the conv outputs to HBM and reads
them back moves a thousand times that, and its share shows it.)"""


def cost(sizes):
    n = sizes["num_train"]
    h, w, c = sizes["image_height"], sizes["image_width"], sizes["image_channels"]
    p, K = sizes["patch_size"], sizes["num_filters"]
    gy, gx = h - p + 1, w - p + 1
    conv_out = n * gy * gx * K
    return {"flops": 2 * conv_out * p * p * c + 3 * conv_out,
            "bytes": 4 * (n * h * w * c + n * sizes["feature_dim"])}
