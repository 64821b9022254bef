"""Multiclass evaluation (reference
evaluation/MulticlassClassifierEvaluator.scala:22-167).

The confusion matrix is a single jitted one-hot outer-product reduction
over the sharded prediction/label arrays (the reference's one-pass
`aggregate`); all derived metrics (per-class P/R/F1, micro/macro
averages, Mahout-style pretty printer) are computed on the host from the
k×k matrix.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@partial(jax.jit, static_argnames=("num_classes",))
def _confusion(preds, actuals, mask, num_classes: int):
    P = jax.nn.one_hot(preds, num_classes) * mask[:, None]
    A = jax.nn.one_hot(actuals, num_classes)
    # rows = actual, cols = predicted
    return A.T @ P


@dataclass
class MulticlassMetrics:
    confusion: np.ndarray  # (k, k), rows=actual, cols=predicted

    @property
    def num_classes(self) -> int:
        return self.confusion.shape[0]

    @property
    def total(self) -> float:
        return float(self.confusion.sum())

    @property
    def accuracy(self) -> float:
        return float(np.trace(self.confusion)) / max(self.total, 1.0)

    @property
    def error(self) -> float:
        return 1.0 - self.accuracy

    def class_precision(self, c: int) -> float:
        col = self.confusion[:, c].sum()
        return float(self.confusion[c, c] / col) if col else 0.0

    def class_recall(self, c: int) -> float:
        row = self.confusion[c, :].sum()
        return float(self.confusion[c, c] / row) if row else 0.0

    def class_f1(self, c: int) -> float:
        p, r = self.class_precision(c), self.class_recall(c)
        return 2 * p * r / (p + r) if (p + r) else 0.0

    def class_fbeta(self, c: int, beta: float) -> float:
        """F_β = (1+β²)·P·R / (β²·P + R) (the reference's
        classMetrics(c).fScore(beta), MulticlassMetrics.scala)."""
        p, r = self.class_precision(c), self.class_recall(c)
        denom = beta * beta * p + r
        return (1 + beta * beta) * p * r / denom if denom else 0.0

    def macro_fbeta(self, beta: float) -> float:
        return float(np.mean(
            [self.class_fbeta(c, beta) for c in range(self.num_classes)]))

    @property
    def macro_precision(self) -> float:
        return float(np.mean([self.class_precision(c) for c in range(self.num_classes)]))

    @property
    def macro_recall(self) -> float:
        return float(np.mean([self.class_recall(c) for c in range(self.num_classes)]))

    @property
    def macro_f1(self) -> float:
        return float(np.mean([self.class_f1(c) for c in range(self.num_classes)]))

    @property
    def micro_precision(self) -> float:
        # single-label multiclass: micro P = micro R = accuracy
        return self.accuracy

    micro_recall = micro_precision

    @property
    def micro_f1(self) -> float:
        return self.accuracy

    def summary(self, class_names=None) -> str:
        """Mahout-style pretty printer
        (MulticlassClassifierEvaluator.scala:123-167)."""
        k = self.num_classes
        names = class_names or [str(i) for i in range(k)]
        lines = [
            "=" * 48,
            "Summary",
            "-" * 48,
            f"Accuracy: {self.accuracy:.4f}",
            f"Macro Precision/Recall/F1: "
            f"{self.macro_precision:.4f}/{self.macro_recall:.4f}/{self.macro_f1:.4f}",
            "-" * 48,
            "Confusion matrix (rows=actual, cols=predicted)",
        ]
        header = "      " + " ".join(f"{n[:6]:>6}" for n in names)
        lines.append(header)
        for i in range(k):
            row = " ".join(f"{int(self.confusion[i, j]):6d}" for j in range(k))
            lines.append(f"{names[i][:6]:>6} {row}")
        lines.append("=" * 48)
        return "\n".join(lines)


class MulticlassClassifierEvaluator:
    """Evaluate int predictions vs int actuals → MulticlassMetrics."""

    def __init__(self, num_classes: int):
        self.num_classes = num_classes

    def evaluate(self, predictions, actuals) -> MulticlassMetrics:
        from ..data.dataset import Dataset, HostDataset
        from ..workflow.pipeline import PipelineResult

        if isinstance(predictions, PipelineResult):
            predictions = predictions.get()
        if isinstance(actuals, PipelineResult):
            actuals = actuals.get()
        if isinstance(predictions, Dataset) and isinstance(actuals, Dataset):
            from ..telemetry import dispatch, span

            mask = predictions.mask_as(jnp.float32)
            with dispatch("_confusion"):
                cm = _confusion(predictions.array, actuals.array, mask,
                                self.num_classes)
            # the pull that ends a fit: the host waits here for every
            # program still queued behind the predictions
            with span("confusion_pull", cat="sync", layer="sync"):
                return MulticlassMetrics(np.asarray(cm))

        def to_host(x):
            if isinstance(x, Dataset):
                return np.asarray(x.numpy()).ravel()
            if isinstance(x, HostDataset):
                return np.asarray(x.items).ravel()
            return np.asarray(x).ravel()

        p, a = to_host(predictions), to_host(actuals)
        if p.shape != a.shape:
            raise ValueError(f"predictions/actuals misaligned: {p.shape} vs {a.shape}")
        cm = np.zeros((self.num_classes, self.num_classes))
        for pi, ai in zip(p, a):
            cm[int(ai), int(pi)] += 1
        return MulticlassMetrics(cm)

    def __call__(self, predictions, actuals) -> MulticlassMetrics:
        return self.evaluate(predictions, actuals)
