from .linear import (
    LinearMapEstimator,
    LinearMapper,
    LocalLeastSquaresEstimator,
    SparseLinearMapper,
)
from .block_ls import BlockLeastSquaresEstimator, BlockLinearMapper
from .lbfgs import DenseLBFGSwithL2, SparseLBFGSwithL2
from .least_squares import LeastSquaresEstimator
from .calibrate import CostWeights, calibrate_cost_weights
from .cost_model import (
    BlockSolverCostModel,
    CostModel,
    CostProfile,
    ExactSolverCostModel,
    LBFGSCostModel,
)
from .zca import ZCAWhitener, ZCAWhitenerEstimator
from .pca import (
    ApproximatePCAEstimator,
    BatchPCATransformer,
    ColumnPCAEstimator,
    DistributedPCAEstimator,
    PCAEstimator,
    PCATransformer,
)
from .kmeans import KMeansModel, KMeansPlusPlusEstimator
from .gmm import GaussianMixtureModel, GaussianMixtureModelEstimator
from .classifiers import (
    LinearDiscriminantAnalysis,
    LogisticRegressionEstimator,
    LogisticRegressionModel,
    NaiveBayesEstimator,
    NaiveBayesModel,
)
from .weighted_ls import BlockWeightedLeastSquaresEstimator, PerClassWeightedLeastSquares
from .kernels import (
    GaussianKernelGenerator,
    GaussianKernelTransformer,
    KernelBlockLinearMapper,
    KernelRidgeRegression,
)
