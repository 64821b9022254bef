"""VOC-style mean average precision (reference
evaluation/MeanAveragePrecisionEvaluator.scala:11-86): per class, rank
scores descending and compute 11-point interpolated average precision.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np


class MeanAveragePrecisionEvaluator:
    """actuals: per-example list/array of true class ids (multi-label),
    or with ``multi_hot`` an (n, classes) array of 0/1 indicators;
    scores: per-example score vector over classes. Returns per-class AP
    array (mean is mAP)."""

    def __init__(self, num_classes: int, multi_hot: bool = False):
        self.num_classes = num_classes
        self.multi_hot = multi_hot

    def evaluate(self, scores, actuals) -> np.ndarray:
        from ..data.dataset import Dataset, HostDataset
        from ..workflow.pipeline import PipelineResult

        if isinstance(scores, PipelineResult):
            scores = scores.get()
        if isinstance(scores, Dataset):
            scores = np.asarray(scores.numpy())
        elif isinstance(scores, HostDataset):
            scores = np.asarray(scores.items)
        if isinstance(actuals, PipelineResult):
            actuals = actuals.get()
        if isinstance(actuals, (Dataset, HostDataset)):
            actuals = actuals.numpy() if isinstance(actuals, Dataset) else actuals.items

        if self.multi_hot:
            indicators = np.asarray(actuals)[: len(scores)] > 0.5
        aps = np.zeros(self.num_classes)
        for c in range(self.num_classes):
            if self.multi_hot:
                y_true = indicators[:, c]
            else:
                y_true = np.array([c in set(np.atleast_1d(a).tolist())
                                   for a in actuals])
            s = scores[:, c]
            order = np.argsort(-s, kind="stable")
            tp = y_true[order]
            npos = tp.sum()
            if npos == 0:
                aps[c] = 0.0
                continue
            cum_tp = np.cumsum(tp)
            precision = cum_tp / (np.arange(len(tp)) + 1)
            recall = cum_tp / npos
            # 11-point interpolation (MeanAveragePrecisionEvaluator.scala:40-86)
            ap = 0.0
            for t in np.linspace(0, 1, 11):
                p = precision[recall >= t]
                ap += (p.max() if p.size else 0.0) / 11.0
            aps[c] = ap
        return aps

    def __call__(self, scores, actuals) -> np.ndarray:
        return self.evaluate(scores, actuals)
