"""Map-based classification (paper §3.4).

1. After training, each unit j is labelled with the class of its nearest
   training sample (Eq. 7).
2. A query sample is classified by the label of its BMU.

Macro-averaged precision/recall match the paper's Table 2 reporting.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import search as search_lib


def label_units(w: jnp.ndarray, samples: jnp.ndarray, labels: jnp.ndarray,
                chunk: int = 4096) -> jnp.ndarray:
    """Eq. (7): y_j = label of argmin_i |w_j - s_i|. Returns (N,) int32."""
    best_q = jnp.full((w.shape[0],), jnp.inf, jnp.float32)
    best_label = jnp.zeros((w.shape[0],), jnp.int32)
    for lo in range(0, samples.shape[0], chunk):
        s = samples[lo:lo + chunk]
        y = labels[lo:lo + chunk]
        # distances (N, chunk)
        w2 = jnp.sum(w * w, axis=-1, keepdims=True)
        s2 = jnp.sum(s * s, axis=-1)
        cross = jnp.matmul(w, s.T, precision=search_lib.HIGHEST)
        q2 = w2 - 2.0 * cross + s2[None, :]
        k = jnp.argmin(q2, axis=-1)
        q = jnp.take_along_axis(q2, k[:, None], axis=-1)[:, 0]
        better = q < best_q
        best_q = jnp.where(better, q, best_q)
        best_label = jnp.where(better, y[k], best_label)
    return best_label


def label_units_majority(w: jnp.ndarray, samples: jnp.ndarray,
                         labels: jnp.ndarray, num_classes: int | None = None,
                         chunk: int = 4096) -> jnp.ndarray:
    """Majority vote of the samples whose BMU is unit j; units that attract
    no samples fall back to the Eq. (7) nearest-sample label."""
    if num_classes is None:
        num_classes = int(labels.max()) + 1
    votes = jnp.zeros((w.shape[0], num_classes), jnp.float32)
    for lo in range(0, samples.shape[0], chunk):
        bmu, _ = search_lib.exact_bmu(w, samples[lo:lo + chunk])
        votes = votes.at[bmu, labels[lo:lo + chunk]].add(1.0)
    majority = jnp.argmax(votes, axis=-1).astype(jnp.int32)
    hit = votes.sum(axis=-1) > 0
    return jnp.where(hit, majority, label_units(w, samples, labels, chunk))


def predict(w: jnp.ndarray, unit_labels: jnp.ndarray, queries: jnp.ndarray,
            chunk: int = 4096) -> jnp.ndarray:
    """Label of each query's BMU. Returns (B,) int32."""
    outs = []
    for lo in range(0, queries.shape[0], chunk):
        bmu, _ = search_lib.exact_bmu(w, queries[lo:lo + chunk])
        outs.append(unit_labels[bmu])
    return jnp.concatenate(outs, axis=0)


def precision_recall(pred: jnp.ndarray, true: jnp.ndarray, num_classes: int):
    """Macro-averaged precision and recall (classes absent from both sides
    contribute 0 to precision / recall, matching sklearn zero_division=0)."""
    pred = pred.astype(jnp.int32)
    true = true.astype(jnp.int32)
    conf = jnp.zeros((num_classes, num_classes), jnp.float32).at[true, pred].add(1.0)
    tp = jnp.diag(conf)
    pred_tot = conf.sum(axis=0)
    true_tot = conf.sum(axis=1)
    prec = jnp.where(pred_tot > 0, tp / jnp.maximum(pred_tot, 1.0), 0.0)
    rec = jnp.where(true_tot > 0, tp / jnp.maximum(true_tot, 1.0), 0.0)
    present = true_tot > 0
    denom = jnp.maximum(present.sum(), 1)
    return (jnp.sum(jnp.where(present, prec, 0.0)) / denom,
            jnp.sum(jnp.where(present, rec, 0.0)) / denom)
