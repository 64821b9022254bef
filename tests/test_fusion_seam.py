"""The seam between the fused-program layer and the operators.

`nodes/util/fusion.py` walks a chain's stages and merges those that
declare it (`Transformer.absorb`, `Transformer.moves_ahead_of`); it names
no operator, and what it offers the rest of the package is public
(`fused_stages`, `stage_fuse`). The stage lists of the five benchmark
chains are pinned: their stages, labels, program keys and microbatch.
"""

import ast
import pathlib

import numpy as np
import pytest

from keystone_tpu.workflow.env import ExecutionConfig, set_execution_config

ROOT = pathlib.Path(__file__).resolve().parent.parent
FUSION = ROOT / "keystone_tpu" / "nodes" / "util" / "fusion.py"
FUSION_MODULE = "keystone_tpu.nodes.util.fusion"


def _imports(path):
    """(absolute module, names, the name the module is bound to) for
    every import statement of ``path``."""
    rel = path.relative_to(ROOT).with_suffix("")
    package = list(rel.parts[:-1])
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name, [], alias.asname
        elif isinstance(node, ast.ImportFrom):
            base = package[:len(package) - node.level + 1] if node.level \
                else []
            module = ".".join(base + ([node.module] if node.module else []))
            names = [alias.name for alias in node.names]
            yield module, names, None
            for alias in node.names:  # `from ..util import fusion`
                yield f"{module}.{alias.name}", [], alias.asname or alias.name


def test_the_fusion_module_imports_no_operator_package():
    for module, _, _ in _imports(FUSION):
        assert not module.startswith(tuple(
            f"keystone_tpu.nodes.{p}" for p in ("images", "stats",
                                                "learning"))), module


def test_no_module_reaches_a_private_name_of_the_fusion_module():
    """Neither by import nor through the module bound to a name."""
    from keystone_tpu.nodes.util import fusion

    assert not hasattr(fusion, "_peephole")
    sources = [p for d in ("keystone_tpu", "scripts")
               for p in (ROOT / d).rglob("*.py") if p != FUSION]
    for path in sources:
        bound = set()
        for module, names, name in _imports(path):
            if module != FUSION_MODULE:
                continue
            assert not [n for n in names if n.startswith("_")], path
            if name:
                bound.add(name)
        text = path.read_text()
        assert "_peephole" not in text, path
        if not bound:
            continue
        for node in ast.walk(ast.parse(text)):
            if (isinstance(node, ast.Attribute)
                    and isinstance(node.value, ast.Name)
                    and node.value.id in bound):
                assert not node.attr.startswith("_"), (path, node.attr)


def _chain(name):
    """The five benchmark chains at the pipeline tests' tiny sizes:
    (stages, element shape)."""
    from keystone_tpu.nodes.images import (
        Convolver,
        ImageVectorizer,
        Pooler,
        SymmetricRectifier,
    )
    from keystone_tpu.nodes.images.fisher_vector import FisherVector
    from keystone_tpu.nodes.images.sift import SIFTExtractor
    from keystone_tpu.nodes.learning.gmm import GaussianMixtureModel
    from keystone_tpu.nodes.learning.pca import PCATransformer
    from keystone_tpu.nodes.stats import (
        ColumnSampler,
        CosineRandomFeatures,
        NormalizeRows,
        SignedHellingerMapper,
    )
    from keystone_tpu.nodes.util import MatrixVectorizer
    from keystone_tpu.nodes.util.fusion import GatherConcatStage

    rng = np.random.default_rng(0)
    pca = PCATransformer(rng.normal(size=(128, 8)).astype(np.float32))
    if name == "conv_rectify_pool":
        conv = Convolver(rng.normal(size=(8, 5 * 5 * 3)).astype(np.float32),
                         16, 16, 3, normalize_patches=True)
        return [conv, SymmetricRectifier(alpha=0.25),
                Pooler(4, 5, None, "sum"), ImageVectorizer()], (16, 16, 3)
    if name == "rectify_pool":
        return [SymmetricRectifier(alpha=0.25), Pooler(13, 14, pool_fn="sum"),
                ImageVectorizer()], (27, 27, 8)
    if name == "sift_pca_sampler":
        return [SIFTExtractor(3, 4, 4, 0), pca, ColumnSampler(30, 7)], (48, 64)
    if name == "fisher":
        gmm = GaussianMixtureModel(
            rng.normal(size=(4, 8)).astype(np.float32),
            np.ones((4, 8), np.float32), np.full(4, 0.25, np.float32))
        return [SIFTExtractor(3, 4, 4, 0), pca, FisherVector(gmm),
                MatrixVectorizer(), NormalizeRows(), SignedHellingerMapper(),
                NormalizeRows()], (48, 64)
    assert name == "cosine_gather"
    return [GatherConcatStage([CosineRandomFeatures(40, 64, 0.05555, seed=i)
                               for i in range(4)])], (40,)


FISHER = ["SIFTExtractor", "PCATransformer", "FisherVector",
          "MatrixVectorizer", "NormalizeRows", "SignedHellingerMapper",
          "NormalizeRows"]


@pytest.mark.parametrize("name,types,labels,keys", [
    ("conv_rectify_pool",
     ["_ConvRectifyPoolStage", "ImageVectorizer"],
     ["_ConvRectifyPoolStage", "ImageVectorizer"],
     (("ConvRectifyPool", 0.25, 0.0, 5, 4, 5, True, False),
      ("ImageVectorizer",))),
    ("rectify_pool",
     ["_RectifyPoolStage", "ImageVectorizer"],
     ["_RectifyPoolStage", "ImageVectorizer"],
     (("RectifyPool", 0.25, 0.0, 14, 13, False), ("ImageVectorizer",))),
    ("sift_pca_sampler",
     ["_SampledSIFTStage", "PCATransformer"],
     ["SIFTExtractor>>ColumnSampler", "PCATransformer"],
     (("SampledSIFT", ("SIFT", 3, 4, 4, 0), 30, 7), ("PCA",))),
    ("fisher", FISHER, FISHER,
     (("SIFT", 3, 4, 4, 0), ("PCA",), ("FisherVector",),
      ("MatrixVectorizer",), ("NormalizeRows",), ("SignedHellingerMapper",),
      ("NormalizeRows",))),
    ("cosine_gather",
     ["GatherConcatStage"],
     ["Gather[4 x CosineRandomFeatures]"],
     (("GatherConcat",) + 4 * (("CosineRandomFeatures",),),)),
])
def test_the_stage_list_of_each_benchmark_chain_is_as_it_was(
        name, types, labels, keys):
    from keystone_tpu.nodes.util.fusion import (
        FusedBatchTransformer,
        fused_stages,
        stage_fuse,
    )

    stages, elem = _chain(name)
    merged = fused_stages(stages)
    assert [type(s).__name__ for s in merged] == types
    assert [s.label for s in merged] == labels
    assert tuple(stage_fuse(s)[0] for s in merged) == keys
    op = FusedBatchTransformer(stages)
    decomposition = op._decompose()
    assert decomposition[0] == keys
    set_execution_config(ExecutionConfig(hbm_budget_bytes=16 << 30))
    try:
        assert op._chunk_rows(decomposition, (1 << 20,) + elem, "float32",
                              1 << 20) == 2048
    finally:
        set_execution_config(None)
