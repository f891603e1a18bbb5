"""Evaluation metrics over sharded models.

The reference's vendored benchmark trainers tracked task metrics
(top-1/top-5 for ImageNet, masked-LM accuracy for BERT, HR/NDCG for NCF)
inside ~12.9k LoC of official-models code; the framework itself shipped
none. Here metrics are a thin functional layer over the same contract
the rest of the stack uses: a jitted ``(params, batch) -> {name: value}``
function evaluated under the plan's parameter shardings, plus a
weighted-average aggregator for dataset-scale evaluation.

Usage::

    from autodist_tpu import metrics

    mfn = metrics.classification_metrics(model.apply, top_k=(1, 5))
    results = metrics.evaluate_dataset(step, state, loader, metrics_fn=mfn)
    # {"loss": 1.93, "top1": 0.71, "top5": 0.90, "examples": 50000}
"""
from __future__ import annotations

import threading
from typing import Any, Callable, Dict, Iterable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "accuracy",
    "top_k_accuracy",
    "perplexity",
    "classification_metrics",
    "lm_metrics",
    "ranking_metrics",
    "evaluate_dataset",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "registry",
]


# ------------------------------------------------------------- pure metrics
def accuracy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Fraction of rows whose argmax matches the integer label."""
    return jnp.mean((jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32))


def top_k_accuracy(logits: jnp.ndarray, labels: jnp.ndarray, k: int) -> jnp.ndarray:
    """Fraction of rows whose label lands in the k highest logits."""
    _, top = jax.lax.top_k(logits, k)
    hit = jnp.any(top == labels[..., None], axis=-1)
    return jnp.mean(hit.astype(jnp.float32))


def perplexity(mean_loss) -> float:
    """exp(cross-entropy) — the LM-convention view of a token loss."""
    return float(np.exp(np.asarray(mean_loss)))


# -------------------------------------------------------- metric factories
def classification_metrics(
    apply_fn: Callable[[Any, Any], Any],
    input_key: str = "images",
    label_key: str = "labels",
    top_k: Sequence[int] = (1,),
) -> Callable[[Any, Any], Dict[str, jnp.ndarray]]:
    """(params, batch) -> {top1, top5, ...} for dict image/label batches
    via the model's ``apply`` (every CNN zoo model exposes one)."""

    def metrics_fn(params, batch):
        logits = apply_fn(params, batch[input_key])
        labels = batch[label_key]
        out = {}
        for k in top_k:
            name = f"top{k}"
            out[name] = (accuracy(logits, labels) if k == 1
                         else top_k_accuracy(logits, labels, k))
        return out

    return metrics_fn


def lm_metrics(
    apply_fn: Callable[[Any, Any], Any],
    token_key: str = "tokens",
    shift: bool = True,
    pad_id: Optional[int] = None,
) -> Callable[[Any, Any], Dict[str, jnp.ndarray]]:
    """(params, batch) -> {token_accuracy} for next-token LMs: the model's
    logits at position t predict token t+1 (``shift=True``); ``pad_id``
    positions are masked out of the average."""

    def metrics_fn(params, batch):
        tokens = batch[token_key]
        logits = apply_fn(params, tokens)
        if shift:
            logits, targets = logits[:, :-1], tokens[:, 1:]
        else:
            targets = tokens
        correct = (jnp.argmax(logits, axis=-1) == targets).astype(jnp.float32)
        if pad_id is not None:
            # Masked mean PLUS its weight: a per-batch mean over valid
            # tokens must aggregate across batches weighted by the valid
            # count, not the row count (the __weight convention
            # evaluate_dataset consumes).
            mask = (targets != pad_id).astype(jnp.float32)
            n_valid = jnp.sum(mask)
            return {
                "token_accuracy": jnp.sum(correct * mask)
                / jnp.maximum(n_valid, 1.0),
                "token_accuracy__weight": n_valid,
            }
        return {"token_accuracy": jnp.mean(correct)}

    return metrics_fn


def ranking_metrics(
    score_fn: Callable[[Any, Any, Any], Any],
    user_key: str = "users",
    item_key: str = "candidates",
    k: int = 10,
) -> Callable[[Any, Any], Dict[str, jnp.ndarray]]:
    """HR@k / NDCG@k for implicit-feedback recommenders (the reference
    NCF benchmark's metrics, utils/recommendation eval layout): each row
    is one user with candidate items ``[C]`` whose POSITIVE sits in
    column 0 and the rest are sampled negatives. ``score_fn(params,
    users, items)`` scores equal-length user/item vectors (for the zoo
    NeuMF: ``lambda p, u, i: model.apply(p, {"users": u, "items": i})``).

    The positive's rank is the number of negatives scored strictly
    higher (ties resolve in the positive's favor — matching argsort-less
    hand counting); HR@k = rank < k, NDCG@k = 1/log2(rank+2) when hit.
    """

    def metrics_fn(params, batch):
        users = batch[user_key]                    # [B]
        cands = batch[item_key]                    # [B, C]
        scores = jax.vmap(
            lambda u, items: score_fn(
                # Broadcast u in ITS OWN dtype: casting user ids to the
                # candidate dtype could silently wrap when the user vocab
                # outgrows the item dtype.
                params, jnp.full(items.shape, u, u.dtype), items)
        )(users, cands)                            # [B, C]
        pos = scores[:, :1]
        rank = jnp.sum((scores[:, 1:] > pos).astype(jnp.int32), axis=1)
        hit = (rank < k).astype(jnp.float32)
        ndcg = jnp.where(rank < k,
                         1.0 / jnp.log2(rank.astype(jnp.float32) + 2.0),
                         0.0)
        return {f"hr@{k}": jnp.mean(hit), f"ndcg@{k}": jnp.mean(ndcg)}

    return metrics_fn


# ----------------------------------------------------------- aggregation
def _batch_size(batch) -> int:
    for leaf in jax.tree.leaves(batch):
        if getattr(leaf, "ndim", 0) >= 1:
            return int(leaf.shape[0])
    return 0


def _logical_params(step, state):
    """The user-shaped parameter view — the step's own definition when
    available (``DistributedTrainStep.logical_params`` handles pad-and-
    mask storage), raw params otherwise. Offload streaming is handled by
    ``step.compile_metrics`` inside the jitted program, not here."""
    if hasattr(step, "logical_params"):
        return step.logical_params(state)
    return getattr(state, "params", state)


def _compile(step, state, metrics_fn):
    """Prefer the step's jit (streams offloaded leaves + unpads storage
    inside the trace — lowering.compile_metrics); plain jit for foreign
    step objects (tests, custom engines)."""
    if hasattr(step, "compile_metrics"):
        return step.compile_metrics(metrics_fn, state), True
    return jax.jit(metrics_fn), False


def evaluate_dataset(
    step,
    state,
    batches: Iterable[Any],
    metrics_fn: Optional[Callable[[Any, Any], Dict[str, Any]]] = None,
    max_batches: Optional[int] = None,
) -> Dict[str, float]:
    """Weighted-average ``step.evaluate`` loss (+ optional task metrics)
    over an iterable of batches (a DataLoader or any batch iterator).

    Each metric's contribution is weighted by the batch's leading
    dimension, so ragged tails average correctly; a metrics_fn may
    override the weight for metric ``k`` by also returning
    ``"<k>__weight"`` (masked metrics — ``lm_metrics(pad_id=...)`` counts
    valid tokens this way). ``metrics_fn`` runs jitted against the
    LOGICAL parameter view (unpadded, HBM-resident — the same handling
    the step's own loss path applies). Returns
    ``{"loss": ..., <metrics...>, "examples": N}``.

    Multi-host: aggregation here is per-process (host-side Python). On a
    fleet either feed every process the same eval batches (replicated
    evaluation — results identical everywhere), or give each process a
    disjoint shard and combine externally: per-metric sums are
    recoverable as ``result[k] * result["examples"]`` (row-weighted
    metrics), so they add across processes.
    """
    compiled_metrics = step_jit = None
    if metrics_fn is not None:
        compiled_metrics, step_jit = _compile(step, state, metrics_fn)
    sums: Dict[str, float] = {}
    weights: Dict[str, float] = {}
    n_total = 0
    logical = None
    for i, batch in enumerate(batches):
        if max_batches is not None and i >= max_batches:
            break
        n = _batch_size(batch)
        if n == 0:
            continue
        out = step.evaluate(state, batch)
        vals = {"loss": float(out["loss"])}
        batch_weights = {}
        if compiled_metrics is not None:
            if step_jit:
                # The step's jit streams/unpads internally: raw params in.
                metric_params = getattr(state, "params", state)
            else:
                if logical is None:
                    logical = _logical_params(step, state)
                metric_params = logical
            m = {k: float(v) for k, v in
                 compiled_metrics(metric_params, batch).items()}
            batch_weights = {k[: -len("__weight")]: m.pop(k)
                             for k in list(m) if k.endswith("__weight")}
            vals.update(m)
        for k, v in vals.items():
            w = batch_weights.get(k, float(n))
            sums[k] = sums.get(k, 0.0) + v * w
            weights[k] = weights.get(k, 0.0) + w
        n_total += n
    if n_total == 0:
        return {"examples": 0}
    result = {k: (sums[k] / weights[k]) if weights[k] else 0.0 for k in sums}
    result["examples"] = n_total
    return result


# ------------------------------------------------------- operational registry
# The functions above evaluate *task* metrics (accuracy, perplexity) over a
# dataset. Serving needs *operational* metrics — latency percentiles, queue
# depth, token throughput — observed from hot host threads. This registry is
# the process-wide export surface the serve subsystem (and anything else
# host-driven, e.g. the async PS trainer) publishes through: prometheus-style
# named counters/gauges/histograms, thread-safe, renderable as text or a
# snapshot dict.


class Counter:
    """Monotonic counter (requests served, tokens generated)."""

    def __init__(self):
        self._value = 0.0
        self._lock = threading.Lock()

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        return self._value


class Gauge:
    """Point-in-time value (queue depth, active slots, tokens/sec)."""

    def __init__(self):
        self._value = 0.0

    def set(self, v: float) -> None:
        self._value = float(v)

    def add(self, n: float = 1.0) -> None:
        self._value += n

    @property
    def value(self) -> float:
        return self._value


class Histogram:
    """Latency-style distribution with exact count/sum and sampled quantiles.

    Keeps up to ``max_samples`` observations; past that, reservoir sampling
    (Vitter's algorithm R) keeps the retained set a uniform sample of the
    stream, so percentiles stay unbiased at serving volumes while memory
    stays bounded.

    The retained reservoir is maintained **sorted** (``bisect.insort`` on
    observe — an O(max_samples) memmove of doubles, microseconds at the
    4096 default) so :meth:`percentile` is an O(1) index + interpolation
    instead of a full ``np.percentile`` pass over every retained
    observation per quantile per render: ``GET /metrics`` under serve load
    renders every histogram in O(quantiles), not O(samples·log·quantiles).
    The interpolation replicates numpy's ``linear`` method bit-for-bit
    (including its t≥0.5 lerp branch), so the rendered exposition is
    byte-identical to the previous implementation — pinned by the
    existing byte-parity golden tests.
    """

    def __init__(self, max_samples: int = 4096):
        self._samples: list = []   # SORTED retained reservoir
        self._max = max_samples
        self._count = 0
        self._sum = 0.0
        self._lock = threading.Lock()
        self._rng = np.random.default_rng(0)

    def observe(self, v: float) -> None:
        self.observe_many((v,))

    def observe_many(self, values) -> None:
        """Observe each of ``values`` in order, under one lock: the count,
        sum and reservoir are those of one observation at a time. Past the
        reservoir's fill the batch's draws are taken in one call of the
        generator, which draws an array of bounds as it draws them one at
        a time (the same stream of ``j``)."""
        import bisect

        values = [float(v) for v in values]
        if not values:
            return
        with self._lock:
            samples, first = self._samples, self._count + 1
            for v in values:
                self._sum += v
            self._count += len(values)
            fill = min(len(values), self._max - len(samples))
            for v in values[:fill]:
                bisect.insort(samples, v)
            rest = values[fill:]
            if not rest:
                return
            bounds = range(first + fill, first + len(values))
            if len(rest) < 8:       # numpy's array call costs ~5 scalar draws
                js = [int(self._rng.integers(0, c)) for c in bounds]
            else:
                js = self._rng.integers(0, np.asarray(
                    bounds, dtype=np.int64)).tolist()
            for j, v in zip(js, rest):
                if j < self._max:
                    # Evicting the j-th order statistic for uniform random
                    # j evicts a uniform-random retained sample — same
                    # algorithm-R distribution as the unsorted variant.
                    del samples[j]
                    bisect.insort(samples, v)

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    def percentile(self, p: float) -> float:
        """p in [0, 100]; nan when nothing was observed. O(1): index math
        over the sorted reservoir, numpy-'linear'-exact interpolation."""
        with self._lock:
            xs = self._samples
            if not xs:
                return float("nan")
            rank = (len(xs) - 1) * (float(p) / 100.0)
            lo = int(rank)
            hi = min(lo + 1, len(xs) - 1)
            t = rank - lo
            a, b = xs[lo], xs[hi]
            # numpy's _lerp computes b - (b-a)(1-t) for t >= 0.5 (monotone
            # guard); mirror it exactly for byte parity through %.6g.
            if t >= 0.5:
                return float(b - (b - a) * (1.0 - t))
            return float(a + (b - a) * t)

    def summary(self) -> Dict[str, float]:
        return {
            "count": float(self._count),
            "sum": self._sum,
            "mean": (self._sum / self._count) if self._count else float("nan"),
            "p50": self.percentile(50.0),
            "p90": self.percentile(90.0),
            "p99": self.percentile(99.0),
        }


class MetricsRegistry:
    """Named metric table: get-or-create by name, snapshot/render for export.

    One process-wide default lives at ``metrics.registry``; components take a
    registry argument so tests can isolate (the serve selftest passes its
    own to keep its numbers clean of earlier runs).
    """

    def __init__(self):
        self._metrics: Dict[str, Any] = {}
        self._lock = threading.Lock()

    def _get(self, name: str, cls):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = cls()
            elif not isinstance(m, cls):
                raise TypeError(
                    f"metric {name!r} already registered as "
                    f"{type(m).__name__}, requested {cls.__name__}")
            return m

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def histogram(self, name: str) -> Histogram:
        return self._get(name, Histogram)

    def snapshot(self) -> Dict[str, Any]:
        """{name: value | histogram summary dict} for JSON export."""
        with self._lock:
            items = list(self._metrics.items())
        out: Dict[str, Any] = {}
        for name, m in items:
            out[name] = m.summary() if isinstance(m, Histogram) else m.value
        return out

    def render_text(self) -> str:
        """Text exposition of this registry — delegates to THE renderer
        (``autodist_tpu.obs.exporter.render_openmetrics``) so every export
        surface emits one format; kept as a convenience method (lazy
        import: obs imports metrics at module load)."""
        from autodist_tpu.obs.exporter import render_openmetrics

        return render_openmetrics(self)


#: Process-default registry (the serve subsystem's export surface).
registry = MetricsRegistry()
