"""Metrics: accuracy, EWMA smoothing, and convergence-plot rendering.

Reproduces the reference's observability surface — per-iteration test
accuracy and the EWMA accuracy plot (``/root/reference/optimization/
ssgd.py:50-66`` ``draw_acc_plot``, α=0.9) — plus step-timing helpers the
reference lacks (SURVEY.md §5: build adds steps/sec metric emission).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def binary_accuracy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Accuracy with the reference's decision rule: predict 1 iff p >= 0.5
    (``ssgd.py:110`` uses ``where(y_pred < 0.5, 0, 1)``)."""
    pred = jnp.where(logits < 0.0, 0.0, 1.0)  # sigmoid(z) < .5  <=>  z < 0
    return jnp.mean((pred == labels).astype(jnp.float32))


def guard_finite(tree, context: str):
    """Raise FloatingPointError if any floating leaf holds NaN/Inf — the
    guard the reference lacks entirely (its unstable sigmoid can NaN
    silently, SURVEY.md §5). Called on final model state by every
    trainer; the checkpointed paths additionally guard every segment."""
    import jax.numpy as jnp

    for leaf in jax.tree.leaves(tree):
        leaf = jnp.asarray(leaf)
        if (jnp.issubdtype(leaf.dtype, jnp.floating)
                and not bool(jnp.all(jnp.isfinite(leaf)))):
            raise FloatingPointError(
                f"non-finite values in {context} — check eta/"
                f"regularisation/input data (guard absent in the "
                f"reference)"
            )
    return tree


def nbytes(*trees) -> int:
    """The bytes the arrays of ``trees`` hold by their own ``nbytes``
    (all shards' together; a leaf without one counts 0): what a loader
    phase's span says it made, and what a checkpoint holds, sized
    without fetching anything."""
    return sum(int(getattr(a, "nbytes", 0))
               for a in jax.tree.leaves(trees))


def ewma(values: np.ndarray, alpha: float = 0.9) -> np.ndarray:
    """EWMA with the reference's recurrence s[t] = α·s[t-1] + (1-α)·v[t],
    s[0] = v[0] (``ssgd.py:51-59``)."""
    values = np.asarray(values, dtype=np.float64)
    out = np.empty_like(values)
    if len(values) == 0:
        return out
    out[0] = values[0]
    for i in range(1, len(values)):
        out[i] = alpha * out[i - 1] + (1 - alpha) * values[i]
    return out


def draw_acc_plot(accs, path: str, alpha: float = 0.9, title: str =
                  "Accuracy on test dataset") -> None:
    """Raw + EWMA accuracy curves, saved to ``path`` (≙ ``draw_acc_plot``)."""
    import matplotlib

    matplotlib.use("Agg")
    import matplotlib.pyplot as plt

    accs = np.asarray(accs)
    xs = np.arange(1, len(accs) + 1)
    fig, ax = plt.subplots()
    ax.plot(xs, accs, color="C0", alpha=0.3)
    ax.plot(xs, ewma(accs, alpha), color="C0")
    ax.set_title(title)
    ax.set_xlabel("Round")
    ax.set_ylabel("Accuracy")
    fig.savefig(path)
    plt.close(fig)


def display_clusters(points, assignments, path: str, k: int | None = None):
    """2-D cluster scatter plot — the reference's ``display_clusters``
    (``k-means.py:30-40``), with stable per-cluster colors instead of its
    random hex strings."""
    import matplotlib

    matplotlib.use("Agg")
    import matplotlib.pyplot as plt

    points = np.asarray(points)
    assignments = np.asarray(assignments)
    if points.shape[1] != 2:
        raise ValueError("display_clusters draws 2-D points only")
    k = k if k is not None else int(assignments.max()) + 1
    fig, ax = plt.subplots()
    for c in range(k):
        sel = assignments == c
        ax.scatter(points[sel, 0], points[sel, 1], s=12, label=f"c{c}")
    ax.legend(loc="best", fontsize=8)
    fig.savefig(path)
    plt.close(fig)


class StepTimer:
    """Wall-clock timer for XLA programs. Dispatch is async, so assign the
    program's output to ``.result`` inside the block — ``__exit__`` calls
    ``jax.block_until_ready`` on it before reading the clock::

        with StepTimer() as t:
            t.result = train_fn(...)
        print(t.elapsed)
    """

    def __init__(self):
        self._t0 = None
        self.elapsed = 0.0
        self.result = None

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if exc[0] is None and self.result is not None:
            jax.block_until_ready(self.result)
        self.elapsed = time.perf_counter() - self._t0
        return False
