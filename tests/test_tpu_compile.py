"""What the v5e's compiler says of the main path's kernels, asked without
the chip (the `on-chip-measurement` guide, section 2): each test lowers a
kernel at the RandomPatchCifar widths for a DESCRIBED TPU v5e and compiles
it, so a kernel that interpret mode accepts and Mosaic refuses (a block
past the scoped-VMEM limit, a slice off the tiling, a kernel XLA is asked
to partition) fails here and costs no chip time. Nothing runs: these say
nothing about results or times.

The topology is described inside a module-scoped fixture and nowhere
else: only one process may hold the TPU's library, so the call must not
happen while any worker merely imports this file. All such tests stay in
this one file, so one worker gets them all.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from keystone_tpu.workflow.env import config_override

# RandomPatchCifar at its defaults (RandomPatchCifarConfig): 32x32x3
# images, 256 filters of 6x6, pool 14 stride 13, microbatch 2048.
H = W = 32
C = 3
K = 256
PATCH = 6
POOL, STRIDE, ALPHA = 14, 13, 0.25
POS = H - PATCH + 1  # 27x27 conv positions
MICROBATCH = 2048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.asarray(topo.devices).reshape(4), ("data",))


@pytest.fixture(autouse=True)
def no_compile_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: the next run would warn
    with config_override(compile_cache_dir=None):
        yield


def _compile(fn, *avals):
    compiled = jax.jit(fn).lower(*avals).compile()
    return compiled.as_text()


def _aval(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _module_name(compiled):
    import re

    return re.match(r"HloModule (\S+?),", compiled.as_text()).group(1)


def _metric_pattern(metric):
    import json
    import os
    import re

    with open(os.path.join(
            os.path.dirname(__file__), "..", "benchmark", "layer_metrics",
            metric + ".json")) as f:
        return re.compile(json.load(f)["args"]["pattern"])


def _conv_avals(n, image_dtype, sharding, k=K):
    return (
        _aval((n, H, W, C), image_dtype, sharding),
        _aval((C * PATCH * PATCH, k), jnp.float32, sharding),
        _aval((k,), jnp.float32, sharding),
        _aval((k,), jnp.float32, sharding),
    )


@pytest.mark.parametrize("n,image_dtype,k", [
    (MICROBATCH, jnp.float32, K),
    (MICROBATCH + 3, jnp.float32, K),  # ragged: a padded tail block
    (MICROBATCH, jnp.bfloat16, K),     # the precision planner's boundary
    # the benchmark's cell (benchmark/configs/random_patch_cifar.json):
    # the documented 10,000 filters at a microbatch of 32, which runs
    # as filter tiles (16 image blocks of 2 by 10 filter blocks of 1,024)
    (32, jnp.float32, 10000),
    # `cifar_kernel_fit` (random_patch_cifar_kernel.json): the source's
    # 100 filters at the optimizer's default microbatch, one filter block
    # under a lane tile (93 image blocks of 22)
    (MICROBATCH, jnp.float32, 100),
], ids=["f32", "ragged", "bf16", "10000_filters", "100_filters"])
def test_fused_conv_compiles_at_the_cifar_geometry(
        one_chip, n, image_dtype, k):
    from keystone_tpu.ops import conv_rectify_pool_pallas

    def fn(images, g, colsum, bias):
        return conv_rectify_pool_pallas(
            images, g, colsum, bias, ALPHA, 0.0, POOL, STRIDE, True, PATCH)

    hlo = _compile(fn, *_conv_avals(n, image_dtype, one_chip, k))
    assert "tpu_custom_call" in hlo
    # no (position x filter) tensor leaves the kernel: nothing in the
    # program is as wide as one image's conv outputs
    assert f"{POS},{POS},{k}]" not in hlo and f"{POS * POS},{k}]" not in hlo
    # the benchmark's `fused_conv_ms_per_fit` finds the call by the name
    # the device trace prints for it (an op's HLO text, reduced by
    # `trace_reduce.op_name`) inside the fused program `jit_per_shard`
    from benchmark.trace_reduce import op_name

    pattern = _metric_pattern("fused_conv_ms_per_fit")
    (call,) = [line.strip() for line in hlo.splitlines()
               if "tpu_custom_call" in line and " = " in line]
    assert pattern.search("jit_per_shard/" + op_name(call)), op_name(call)
    # the patches the call takes are class-ordered, 784 rows an image
    # where row-major order had 736 (`_pool_layout`), and slices and a
    # concatenation put them so: no gather stands in front of the call
    from keystone_tpu.ops.pallas_kernels import _fused_conv_plan

    layout, (b, _, _, _) = _fused_conv_plan(H, W, C, k, POOL, STRIDE, PATCH)
    assert layout.posp == 784
    assert f"bf16[{-(-n // b) * b * 784},128]" in call, call
    assert "gather" not in hlo


@pytest.mark.parametrize("block_n", [3, None],
                         ids=["dispatcher_block", "default_block"])
def test_rectify_pool_compiles_at_the_conv_output(one_chip, block_n):
    """The standalone kernel at the block the dispatcher used to hand it
    and at its own default. A fixed default of 8 was refused here:
    RESOURCE_EXHAUSTED, 16.19M of scoped vmem against a 16.00M limit."""
    from keystone_tpu.ops import rectify_pool_pallas
    from keystone_tpu.ops.pallas_kernels import _rectify_pool_block

    assert _rectify_pool_block(POS, POS, K) == 3

    def fn(x):
        return rectify_pool_pallas(
            x, ALPHA, 0.0, POOL, STRIDE, block_n=block_n)

    hlo = _compile(fn, _aval((MICROBATCH, POS, POS, K), jnp.float32, one_chip))
    assert "tpu_custom_call" in hlo


def test_rectify_pool_vectorize_compiles(one_chip):
    from keystone_tpu.ops.chain_kernels import rectify_pool_vectorize_pallas

    def fn(x):
        return rectify_pool_vectorize_pallas(x, ALPHA, 0.0, POOL, STRIDE)

    hlo = _compile(fn, _aval((MICROBATCH, POS, POS, K), jnp.float32, one_chip))
    assert "tpu_custom_call" in hlo


def test_rbf_block_compiles(one_chip):
    """TIMIT's frame width (d=440) against one column block."""
    from keystone_tpu.ops import rbf_block_pallas

    def fn(x, yb):
        return rbf_block_pallas(x, yb, 0.01)

    hlo = _compile(fn, _aval((8192, 440), jnp.float32, one_chip),
                   _aval((2048, 440), jnp.float32, one_chip))
    assert "tpu_custom_call" in hlo


def test_elementwise_chain_compiles(one_chip):
    """`scripts/kernel_live_check.py`'s elementwise geometry: the
    LinearPixels trail PixelScaler >> GrayScaler >> ImageVectorizer on
    32x32x3, at a ragged count."""
    from keystone_tpu.nodes.images import (
        GrayScaler,
        ImageVectorizer,
        PixelScaler,
    )
    from keystone_tpu.nodes.util.fusion import fused_stages, stage_fuse
    from keystone_tpu.ops.chain_kernels import elementwise_chain_pallas

    fused = [stage_fuse(s) for s in fused_stages(
        [PixelScaler(), GrayScaler(), ImageVectorizer()])]
    statics = tuple(f[0] for f in fused)
    params = [f[1] for f in fused]

    def fn(x):
        return elementwise_chain_pallas(statics, params, x)

    hlo = _compile(fn, _aval((MICROBATCH + 3, H, W, C), jnp.float32, one_chip))
    assert "tpu_custom_call" in hlo


@pytest.fixture
def kernels_as_on_the_chip(monkeypatch):
    """The dispatchers ask `jax.default_backend()`, which is the CPU
    here, and their canary runs the kernel: steer both from the test, so
    the program that is lowered is the one the chip would build."""
    import keystone_tpu.ops.pallas_kernels as pk

    monkeypatch.setattr(pk, "use_fused_conv", lambda: True)
    monkeypatch.setattr(pk, "_fused_conv_canary_ok", lambda *a: True)


def test_fused_operator_compiles_per_shard_on_four_chips(
        mesh4, kernels_as_on_the_chip):
    """The pipeline's own path: the fused featurizer operator
    (Convolver >> SymmetricRectifier >> Pooler >> ImageVectorizer, which
    the peephole turns into the fused conv stage) through its AOT warmup
    on the described mesh, where `per_shard` runs inside `shard_map`."""
    from keystone_tpu.nodes.images.core import (
        Convolver,
        ImageVectorizer,
        Pooler,
        SymmetricRectifier,
    )
    from keystone_tpu.nodes.util.fusion import (
        _PROGRAM_CACHE,
        FusedBatchTransformer,
    )

    filters = np.zeros((K, PATCH * PATCH * C), np.float32)
    op = FusedBatchTransformer([
        Convolver(filters, H, W, C, normalize_patches=True),
        SymmetricRectifier(alpha=ALPHA),
        Pooler(STRIDE, POOL, None, "sum"),
        ImageVectorizer(),
    ], microbatch=MICROBATCH)
    before = set(_PROGRAM_CACHE)
    try:
        verdict = op.warmup(
            jax.ShapeDtypeStruct((H, W, C), jnp.float32),
            4 * MICROBATCH, mesh=mesh4)
        assert verdict == "compiled"
        (key,) = set(_PROGRAM_CACHE) - before
        hlo = _PROGRAM_CACHE[key]._compiled.as_text()
        assert "tpu_custom_call" in hlo
    finally:
        for key in set(_PROGRAM_CACHE) - before:
            del _PROGRAM_CACHE[key]


def _compiled_gather(n, rows, whole, mesh=None, shards=1):
    """The gather of four 4,096-wide `CosineRandomFeatures` branches over
    ``n`` frames of 440 dimensions as the optimizer builds it, one fused
    program, compiled with the frames placed as ``rows`` and the random
    parameters as ``whole``. Returns (compiled, branches, width)."""
    from keystone_tpu.nodes.stats import CosineRandomFeatures
    from keystone_tpu.nodes.util.fusion import (
        FusedBatchTransformer,
        GatherConcatStage,
    )

    dim, branches, width = 440, 4, 4096
    nodes = [CosineRandomFeatures(dim, 8, 0.05555, seed=i)
             for i in range(branches)]
    for node in nodes:  # shapes stand in for the random parameters
        node.W = _aval((dim, width), jnp.float32, whole)
        node.b = _aval((width,), jnp.float32, whole)
    op = FusedBatchTransformer([GatherConcatStage(nodes)])
    statics, flat, treedef, fns = op._decompose()
    program = op._build_program(mesh, shards, n, treedef, fns, statics=statics)
    compiled = program.lower(
        flat, _aval((n, dim), jnp.float32, rows),
        _aval((n,), jnp.bool_, rows)).compile()
    return compiled, branches, width


def test_the_gathered_cosine_branches_fit_the_chip_at_timit_fit_s_size(one_chip):
    """`timit_fit`'s featurizer as the optimizer builds it: the gather of
    four 4,096-wide `CosineRandomFeatures` branches over 65,536 frames
    of 440 dimensions, one fused program. Its output is the (n, 16,384)
    features, 4.29 GB; the branches' chunks are its only temporaries, so
    no second (n, d) array is held beside it, and every op carries the
    gather's scope and its branch's."""
    n = 65536
    compiled, branches, width = _compiled_gather(n, one_chip, one_chip)
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == 4 * n * branches * width
    assert memory.temp_size_in_bytes < 4 * n * branches * width // 8
    hlo = compiled.as_text()
    assert hlo.count(
        "ks.Gather[4xCosineRandomFeatures]/ks.CosineRandomFeatures") >= branches


def test_the_gathered_cosine_branches_stay_on_their_chip_at_timit_fit_4chip(mesh4):
    """`timit_fit_4chip`'s featurizer: the same gather over 262,144 frames
    on the (4,) mesh, `per_shard` inside `shard_map` with W and b
    replicated. A chip writes its own 65,536 rows of features (4.29 GB)
    and the program holds no collective."""
    n = 262144
    compiled, branches, width = _compiled_gather(
        n, NamedSharding(mesh4, P("data")), NamedSharding(mesh4, P()),
        mesh=mesh4, shards=4)
    memory = compiled.memory_analysis()  # of one chip
    assert memory.output_size_in_bytes == 4 * (n // 4) * branches * width
    assert memory.temp_size_in_bytes < 4 * (n // 4) * branches * width // 8
    assert not _collective_lines(compiled.as_text())


def _collective_lines(hlo):
    import re

    return [line.strip() for line in hlo.splitlines() if re.search(
        r" (all-reduce|all-gather|reduce-scatter|collective-permute|"
        r"all-to-all)(-start)?\(", line)]


def test_the_forming_sweep_all_reduces_its_panels_once_a_block_step(mesh4):
    """`_bcd_epoch`'s forming trace on the (4,) mesh by the v5e's own
    partitioner and all-reduce combiner (2,048 rows a chip, blocks of
    1,024 in four panels): one all-reduce a block step, of the panels and
    the correlation together, and X is never gathered. The benchmark's
    `collective_ms_per_fit` finds the op by the name the device trace
    prints for it."""
    import re

    from benchmark.trace_reduce import op_name
    from keystone_tpu.nodes.learning import block_ls

    n, B, blocks, k = 8192, 1024, 2, 16
    rows, whole = P("data"), P()
    aval = lambda shape, spec: _aval(shape, jnp.float32,
                                     NamedSharding(mesh4, spec))
    compiled = block_ls._bcd_epoch.lower(
        aval((blocks, B, k), whole), aval((n, k), rows),
        aval((n, blocks * B), rows), aval((), whole), B, blocks,
        keep_factors=True, gram_tile=block_ls._gram_tile(B)).compile()
    (reduce,) = _collective_lines(compiled.as_text())
    result = reduce.split(" all-reduce(")[0]
    dims = re.findall(r"f32\[([\d,]*)\]", result)
    reduced = 4 * sum(int(np.prod([int(x) for x in d.split(",")]))
                      for d in dims)
    assert reduced == block_ls._allreduce_bytes(B, k, 256, forming=True)
    pattern = _metric_pattern("collective_ms_per_fit")
    assert pattern.search("jit__bcd_epoch/" + op_name(reduce)), op_name(reduce)


# `cifar_kernel_fit` (benchmark/configs/random_patch_cifar_kernel.json):
# 50,000 rows of 800 features, column blocks of 5,000, 10 classes
KRR_N, KRR_D, KRR_B, KRR_K, KRR_GAMMA = 50000, 800, 5000, 10, 2e-4
CHIP_BYTES = 16 * 2**30


@pytest.mark.parametrize("cached", [False, True], ids=["forming", "cached"])
def test_the_kernel_solver_s_step_fits_the_chip_at_cifar_kernel_fit_s_size(
        one_chip, cached):
    """`_krr_step`'s two programs at the cell's shapes: the forming step
    writes its (n, B) kernel block (1 GB) and the block's (B, B) Cholesky
    factor (100 MB) as outputs and holds no second array of the block's
    size beside them; the cached step takes both as arguments, forms
    nothing and factors nothing. Both are the XLA module `jit__krr_step`,
    which `krr_ms_per_fit` and `krr_roofline` find."""
    from keystone_tpu.nodes.learning import kernels

    f32 = lambda *shape: _aval(shape, jnp.float32, one_chip)
    block_bytes, factor_bytes = 4 * KRR_N * KRR_B, 4 * KRR_B * KRR_B
    compiled = kernels._krr_step.lower(
        f32(KRR_N, KRR_D), f32(KRR_N, KRR_K), f32(KRR_N),
        f32(KRR_N, KRR_K), f32(KRR_N, KRR_K), f32(),
        _aval((), jnp.int32, one_chip),
        (f32(KRR_N, KRR_B), f32(KRR_B, KRR_B)) if cached else None,
        gamma=KRR_GAMMA, block_size=KRR_B, keep_kernel=not cached).compile()
    memory = compiled.memory_analysis()
    hlo = compiled.as_text()
    assert memory.temp_size_in_bytes < block_bytes // 2
    if cached:
        assert memory.argument_size_in_bytes >= block_bytes + factor_bytes
        assert "ks.krr.kernel" not in hlo and "exponential" not in hlo
        assert "cholesky" not in hlo
    else:
        assert memory.output_size_in_bytes >= block_bytes + factor_bytes
        assert "ks.krr.kernel" in hlo and "cholesky" in hlo
    assert "ks.krr.solve" in hlo and "ks.krr.update" in hlo
    # ten kept blocks with their factors and this program beside them
    # stay under the chip
    assert (9 * (block_bytes + factor_bytes) + memory.argument_size_in_bytes
            + memory.output_size_in_bytes + memory.temp_size_in_bytes
            < CHIP_BYTES)
    for metric in ("krr_ms_per_fit", "krr_roofline"):
        assert _metric_pattern(metric).search(_module_name(compiled))


@pytest.mark.parametrize("rows", [KRR_N, 10000], ids=["train", "test"])
def test_the_kernel_apply_fits_the_chip_at_cifar_kernel_fit_s_size(
        one_chip, rows):
    """`_kernel_apply_scan` over the cell's 50,000 anchors in ten blocks,
    for the train error's 50,000 rows and the test set's 10,000: one
    program, its body under `ks.krr.apply`, found by
    `kernel_apply_ms_per_fit` and `kernel_apply_roofline`."""
    from keystone_tpu.nodes.learning import kernels

    f32 = lambda *shape: _aval(shape, jnp.float32, one_chip)
    compiled = kernels._kernel_apply_scan.lower(
        f32(rows, KRR_D), f32(KRR_N, KRR_D), f32(KRR_N, KRR_K), KRR_GAMMA,
        KRR_B, KRR_N // KRR_B, False).compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 3 * 4 * rows * KRR_B
    assert "ks.krr.apply" in compiled.as_text()
    for metric in ("kernel_apply_ms_per_fit", "kernel_apply_roofline"):
        assert _metric_pattern(metric).search(_module_name(compiled))


VOC_N = 5011  # voc_fit's training images


def _voc_fisher_program(one_chip, topo, chunk=None):
    """`voc_fit`'s heaviest program as the optimizer builds it: SIFT, the
    PCA projection, the Fisher encoding and the three normalizations
    over the 5,011 cached grayscale images (375 x 500), one fused
    program, compiled at ``chunk`` images a microbatch, or at the one
    the rule derives. (derived chunk, compiled program)."""
    from keystone_tpu.nodes.images.fisher_vector import FisherVector
    from keystone_tpu.nodes.images.sift import SIFTExtractor
    from keystone_tpu.nodes.learning.gmm import GaussianMixtureModel
    from keystone_tpu.nodes.learning.pca import PCATransformer
    from keystone_tpu.nodes.stats import NormalizeRows, SignedHellingerMapper
    from keystone_tpu.nodes.util import MatrixVectorizer
    from keystone_tpu.nodes.util.fusion import FusedBatchTransformer
    from keystone_tpu.workflow.env import ExecutionConfig, set_execution_config

    n, h, w = VOC_N, 375, 500
    pca = PCATransformer.__new__(PCATransformer)
    pca.components = _aval((128, 80), jnp.float32, one_chip)
    gmm = GaussianMixtureModel.__new__(GaussianMixtureModel)
    gmm.means = _aval((256, 80), jnp.float32, one_chip)
    gmm.variances = _aval((256, 80), jnp.float32, one_chip)
    gmm.weights = _aval((256,), jnp.float32, one_chip)
    op = FusedBatchTransformer([
        SIFTExtractor(3, 4, 4, 0), pca, FisherVector(gmm), MatrixVectorizer(),
        NormalizeRows(), SignedHellingerMapper(), NormalizeRows()])
    decomposition = statics, flat, treedef, fns = op._decompose()
    set_execution_config(ExecutionConfig(hbm_budget_bytes=16 << 30))
    try:
        derived = op._chunk_rows(decomposition, (n, h, w), "float32", n)
    finally:
        set_execution_config(None)
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    program = op._build_program(mesh, 1, n, treedef, fns, statics=statics,
                                chunk=chunk or derived)
    return derived, program.lower(
        flat, _aval((n, h, w), jnp.float32, one_chip),
        _aval((n,), jnp.bool_, one_chip)).compile()


def test_voc_fit_s_fisher_program_fits_beside_what_a_fit_keeps(one_chip, topo):
    """`voc_fit`'s heaviest program (`_voc_fisher_program`) at the
    microbatch the rule derives (8 images: 605 MB of posteriors). Its
    output is the (5,011, 40,960) features; its temporaries stay a
    microbatch's (under 1.5 GB where a copy of the cached images in a
    layout of the compiler's liking was 3.76 GB more: the v5e keeps
    (5011, 375, 500) with the images on the lanes), so the images, the
    samples, the features and the program fit 16 GB."""
    chunk, compiled = _voc_fisher_program(one_chip, topo)
    assert chunk == 8
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes >= 4 * VOC_N * 40960
    assert memory.temp_size_in_bytes < 1.5e9
    hlo = compiled.as_text()
    assert "ks.sift" in hlo and "ks.pca.apply" in hlo and "ks.fisher" in hlo


def test_voc_fit_s_fisher_program_encodes_in_one_kernel(
        one_chip, topo, monkeypatch):
    """The same program with the gates as the chip takes them: SIFT's
    normalization and the Fisher encoding each one Mosaic call. At the
    parent's microbatch of 8 no (8, 73,866, 256) float32 array is left
    and the temporaries fall from the parent's 1,127,940,096 bytes to
    725,953,536; the encoding's input is the PCA product's output laid
    out (8, 80, 73,866), with no copy in front of the call. The rule
    then derives 16 images a microbatch (the largest value a row makes
    is SIFT's (73,866, 128) descriptors), and that program fits too."""
    import re

    from benchmark.trace_reduce import op_name
    from keystone_tpu.nodes.images import fisher_vector, sift
    from keystone_tpu.nodes.util import fusion
    from keystone_tpu.ops import pallas_kernels as pk

    # the derived microbatch is remembered by the chain's structure, not
    # by the gates: the test above may have left the parent form's
    monkeypatch.setattr(fusion, "_MICROBATCH_CACHE", {})
    monkeypatch.setattr(
        sift, "use_sift_normalize",
        lambda rows: rows >= pk.SIFT_NORMALIZE_TILE)
    monkeypatch.setattr(
        fisher_vector, "use_fisher_kernel",
        lambda nd, d, k: 0 < pk.fisher_tile(d, k) <= nd)
    chunk, compiled = _voc_fisher_program(one_chip, topo, chunk=8)
    assert chunk == 16
    hlo = compiled.as_text()
    calls = sorted(op_name(line.strip()) for line in hlo.splitlines()
                   if "tpu_custom_call" in line and " = " in line)
    assert [c.split(".")[0] for c in calls] == \
        ["ks_fisher", "ks_sift_normalize"], calls
    assert not re.search(r"f32\[8,73866,256\]", hlo)
    made = re.findall(r"= f32\[8,80,73866\]\{[^}]*\} ([\w-]+)\(", hlo)
    assert made and not {"copy", "transpose"} & set(made), made
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 1_127_940_096
    assert temp < 0.75e9
    memory = _voc_fisher_program(one_chip, topo)[1].memory_analysis()
    assert memory.temp_size_in_bytes < 1.5e9


@pytest.mark.parametrize("d,k", [(128, 128), (128, 1024)])
def test_the_fisher_kernel_compiles_at_the_widest_its_gate_takes(
        one_chip, d, k):
    """`fisher_moments_pallas` at its gate's widest widths (d = 128, k
    up to 1,024), at the tile `fisher_tile` gives them, over a masked
    last tile: the step's VMEM is what the tile rule is for."""
    from keystone_tpu.ops import pallas_kernels as pk

    tile = pk.fisher_tile(d, k)
    f32 = lambda *shape: _aval(shape, jnp.float32, one_chip)
    compiled = pk.fisher_moments_pallas.lower(
        f32(2, 3 * tile + 5, d), f32(k, d), f32(k, d), f32(k)).compile()
    assert "ks_fisher" in compiled.as_text()


def test_sift_s_full_pass_takes_its_exact_products_in_three_passes(
        one_chip, monkeypatch):
    """SIFT's full pass over a microbatch of 8 VOC images (375 x 500,
    `voc_fit`'s configuration): its 8 binning products and 2 row sums
    keep `default` on their exact constant and `highest` on the maps
    (three bf16 passes where six ran), the 8 Gaussian products keep
    `highest` on both; no more convolutions, bytes accessed or
    temporaries than the same program at `highest` everywhere."""
    import re

    from keystone_tpu.nodes.images import sift

    gray = _aval((8, 375, 500), jnp.float32, one_chip)
    extractor = sift.SIFTExtractor(3, 4, 4, 0)

    def compiled():
        return jax.jit(extractor._batch).lower(gray).compile()

    split = compiled()
    monkeypatch.setattr(sift, "_exact_in_bf16", lambda m: False)
    six = compiled()
    precisions = re.findall(r"operand_precision=\{(\w+),(\w+)\}",
                            split.as_text())
    assert sorted(precisions) == \
        10 * [("highest", "default")] + 8 * [("highest", "highest")]
    assert split.as_text().count("convolution(") \
        <= six.as_text().count("convolution(")

    def cost(c):
        costs = c.cost_analysis()
        return (costs[0] if isinstance(costs, list) else costs)[
            "bytes accessed"]

    assert cost(split) <= cost(six)
    assert split.memory_analysis().temp_size_in_bytes \
        <= six.memory_analysis().temp_size_in_bytes + 0.25e9


def test_sift_s_full_pass_normalizes_its_descriptors_in_one_kernel(
        one_chip, monkeypatch):
    """SIFT's full pass over a microbatch of 8 VOC images (375 x 500,
    `voc_fit`'s configuration) with the normalization's gate as the chip
    takes it: the four scales' raw descriptors go into one Mosaic call,
    `ks_sift_normalize`, which writes them quantized at their places
    among the scales'. No row sums (the reference's two products with
    ones), no concatenate and no other array of the descriptors' size
    but the call's output and its copy to the entry's layout are left in
    the program; the eight binning products keep their three passes."""
    import re

    from benchmark.trace_reduce import op_name
    from keystone_tpu.nodes.images import sift
    from keystone_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(
        sift, "use_sift_normalize",
        lambda rows: rows >= pk.SIFT_NORMALIZE_TILE)
    gray = _aval((8, 375, 500), jnp.float32, one_chip)
    hlo = jax.jit(sift.SIFTExtractor(3, 4, 4, 0)._batch).lower(
        gray).compile().as_text()
    (call,) = [line.strip() for line in hlo.splitlines()
               if "tpu_custom_call" in line and " = " in line]
    assert op_name(call).startswith("ks_sift_normalize"), op_name(call)
    assert re.search(r"custom-call\((%[\w.]+, ){3}%[\w.]+\)", call), call
    made = re.findall(r"= f32\[8,73866,128\]\{[^}]*\} ([\w-]+)\(", hlo)
    assert sorted(made) == ["copy", "custom-call"], made
    precisions = re.findall(r"operand_precision=\{(\w+),(\w+)\}", hlo)
    assert sorted(precisions) == \
        8 * [("highest", "default")] + 8 * [("highest", "highest")]


def test_a_kernel_s_body_is_the_same_whoever_traces_it(
        one_chip, topo, monkeypatch):
    """The persistent compile cache keys a program by its text, and a
    Mosaic call carries its kernel's serialized body, with a source
    location on every op. A process traces a kernel once and keeps that
    trace, so which stack traced it first decides the key: the executor's
    AOT warm-up thread in one process, the dispatch in the next. The
    fused SIFT program is lowered here through the plain chunk loop,
    then, its traces dropped as a new process would not have them, on a
    worker thread through the megafused scan. The `ks_sift_normalize`
    call's body is the same text both times. Lowered only: nothing is
    compiled."""
    import re
    import threading

    from keystone_tpu.nodes.images import sift
    from keystone_tpu.nodes.util.fusion import (
        FusedBatchTransformer,
        MegafusedBatchTransformer,
    )
    from keystone_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(
        sift, "use_sift_normalize",
        lambda rows: rows >= pk.SIFT_NORMALIZE_TILE)
    n, h, w = 2, 96, 128  # 3,248 descriptors an image: one tile and more
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))

    def body(chain_type):
        op = chain_type([sift.SIFTExtractor(3, 4, 4, 0)], microbatch=n)
        statics, flat, treedef, fns = op._decompose()
        program = op._build_program(mesh, 1, n, treedef, fns, statics=statics)
        text = program.lower(flat, _aval((n, h, w), jnp.float32, one_chip),
                             _aval((n,), jnp.bool_, one_chip)).as_text()
        (call,) = [line for line in text.splitlines()
                   if "tpu_custom_call" in line]
        assert "ks_sift_normalize" in call
        return re.search(r'backend_config = "([^"]*)"', call).group(1)

    mine = body(FusedBatchTransformer)
    jax.clear_caches()
    theirs = []
    worker = threading.Thread(
        target=lambda: theirs.append(body(MegafusedBatchTransformer)))
    worker.start()
    worker.join()
    assert theirs == [mine]
