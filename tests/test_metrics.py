"""Metrics layer tests: hand-computed values + dataset aggregation over a
real sharded step (c0 methodology)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import autodist_tpu as ad
from autodist_tpu import metrics
from autodist_tpu.models import get_model


def test_accuracy_hand_computed():
    logits = jnp.array([[5.0, 1.0, 0.0],
                        [0.0, 3.0, 1.0],
                        [1.0, 0.0, 2.0],
                        [9.0, 8.0, 7.0]])
    labels = jnp.array([0, 1, 0, 2])  # hits: row0, row1 -> 0.5
    assert float(metrics.accuracy(logits, labels)) == pytest.approx(0.5)
    # top-2: row2's label 0 is the 2nd highest (1.0 vs 2.0) -> hit;
    # row3's label 2 is 3rd -> miss. 3/4.
    assert float(metrics.top_k_accuracy(logits, labels, 2)) == pytest.approx(0.75)
    assert metrics.perplexity(np.log(7.0)) == pytest.approx(7.0)


def test_lm_metrics_shift_and_mask():
    # Vocab 4; logits constructed so position t predicts token t+1 exactly
    # for the first sequence and never for the second.
    tokens = jnp.array([[1, 2, 3], [1, 0, 0]])

    def apply_fn(params, toks):
        # predict next token = toks shifted for row 0; constant 3 for row 1.
        pred = jnp.where(jnp.arange(toks.shape[0])[:, None] == 0,
                         jnp.roll(toks, -1, axis=1), 3)
        return jax.nn.one_hot(pred, 4) * 10.0

    mfn = metrics.lm_metrics(apply_fn)
    out = mfn(None, {"tokens": tokens})
    # Row 0: targets [2,3] predicted [2,3] -> 2 hits; row 1: targets [0,0]
    # predicted [3,3] -> 0 hits. 2/4.
    assert float(out["token_accuracy"]) == pytest.approx(0.5)
    # pad_id=0 masks row 1's targets entirely -> 2/2.
    mfn_m = metrics.lm_metrics(apply_fn, pad_id=0)
    assert float(mfn_m(None, {"tokens": tokens})["token_accuracy"]) == (
        pytest.approx(1.0))


def test_evaluate_dataset_weighted_average_over_sharded_step():
    ad.AutoDist.reset_default()
    model = get_model("mlp", in_dim=8, hidden=(16,), num_classes=4)
    params = model.init(jax.random.PRNGKey(0))
    autodist = ad.AutoDist(strategy_builder=ad.strategy.AllReduce())
    step = autodist.build(model.loss_fn, params, model.example_batch(8))
    state = step.init(params)

    full = model.example_batch(24)
    batches = [
        {k: v[:16] for k, v in full.items()},
        {k: v[16:] for k, v in full.items()},   # ragged tail (8 rows)
    ]
    mfn = metrics.classification_metrics(model.apply, input_key="x", label_key="y", top_k=(1, 2))
    got = metrics.evaluate_dataset(step, state, batches, metrics_fn=mfn)
    assert got["examples"] == 24

    # Hand aggregation: weighted by batch size == whole-set evaluation.
    logits = model.apply(state.params, full["x"])
    want_top1 = float(metrics.accuracy(logits, full["y"]))
    want_top2 = float(metrics.top_k_accuracy(logits, full["y"], 2))
    assert got["top1"] == pytest.approx(want_top1, abs=1e-6)
    assert got["top2"] == pytest.approx(want_top2, abs=1e-6)
    # Loss: weighted mean of per-batch losses equals whole-set loss for a
    # mean-reduced objective.
    want_loss = float(model.loss_fn(state.params, full))
    assert got["loss"] == pytest.approx(want_loss, rel=1e-5)
    assert got["examples"] == 24
    ad.AutoDist.reset_default()


def test_evaluate_dataset_empty_and_max_batches():
    class FakeStep:
        def evaluate(self, state, batch):
            return {"loss": jnp.asarray(2.0)}

    assert metrics.evaluate_dataset(FakeStep(), None, []) == {"examples": 0}
    batches = [{"x": np.zeros((4, 2))}] * 5
    got = metrics.evaluate_dataset(FakeStep(), None, batches, max_batches=2)
    assert got["examples"] == 8 and got["loss"] == pytest.approx(2.0)


def test_masked_metric_weighted_by_valid_tokens():
    # Batch A: 2 valid tokens at accuracy 1.0; batch B: 8 valid tokens at
    # accuracy 0.5. Row-weighted would say 0.75; token-weighted truth is
    # (2*1 + 8*0.5) / 10 = 0.6.
    class FakeStep:
        def evaluate(self, state, batch):
            return {"loss": jnp.asarray(0.0)}

    def mfn(params, batch):
        acc = batch["acc"][0]
        n = batch["n"][0]
        return {"token_accuracy": acc, "token_accuracy__weight": n}

    batches = [
        {"acc": jnp.array([1.0, 1.0]), "n": jnp.array([2.0, 2.0])},
        {"acc": jnp.array([0.5, 0.5]), "n": jnp.array([8.0, 8.0])},
    ]
    got = metrics.evaluate_dataset(FakeStep(), None, batches, metrics_fn=mfn)
    assert got["token_accuracy"] == pytest.approx(0.6)
    assert got["loss"] == pytest.approx(0.0)


def test_batch_size_skips_scalar_leaves():
    assert metrics._batch_size({"alpha": jnp.float32(0.5),
                                "x": np.zeros((7, 3))}) == 7
    assert metrics._batch_size({"alpha": jnp.float32(0.5)}) == 0


def test_metrics_on_padded_plan_uses_logical_params():
    # Uneven-partition PS pads storage shapes; metrics_fn must see the
    # LOGICAL shapes the model defines or apply() shape-mismatches.
    ad.AutoDist.reset_default()
    model = get_model("mlp", in_dim=7, hidden=(13,), num_classes=4)
    params = model.init(jax.random.PRNGKey(0))
    autodist = ad.AutoDist(strategy_builder=ad.strategy.UnevenPartitionedPS())
    step = autodist.build(model.loss_fn, params, model.example_batch(8))
    state = step.init(params)
    full = model.example_batch(16)
    mfn = metrics.classification_metrics(model.apply, input_key="x",
                                         label_key="y", top_k=(1,))
    got = metrics.evaluate_dataset(step, state, [full], metrics_fn=mfn)
    logits = model.apply(
        metrics._logical_params(step, state), full["x"])
    assert got["top1"] == pytest.approx(
        float(metrics.accuracy(logits, full["y"])), abs=1e-6)
    ad.AutoDist.reset_default()


def test_fit_records_eval_metrics_series():
    ad.AutoDist.reset_default()
    model = get_model("mlp", in_dim=8, hidden=(16,), num_classes=4)
    params = model.init(jax.random.PRNGKey(0))
    autodist = ad.AutoDist(strategy_builder=ad.strategy.AllReduce())
    step = autodist.build(model.loss_fn, params, model.example_batch(8))
    state = step.init(params)
    mfn = metrics.classification_metrics(model.apply, input_key="x",
                                         label_key="y", top_k=(1,))
    batches = [model.example_batch(8) for _ in range(6)]
    eval_b = model.example_batch(16)
    # Plain path.
    state, hist = step.fit(state, iter(batches), eval_batch=eval_b,
                           eval_every=2, eval_metrics_fn=mfn)
    assert len(hist["eval_loss"]) == 3
    assert len(hist["eval_top1"]) == 3
    assert all(0.0 <= v <= 1.0 for v in hist["eval_top1"])
    # Windowed path records the same series shape.
    state, histw = step.fit(state, iter(batches), eval_batch=eval_b,
                            eval_every=2, window=2, eval_metrics_fn=mfn)
    assert len(histw["eval_top1"]) == 3
    ad.AutoDist.reset_default()


def test_fit_hook_strips_weights_and_renames_loss():
    ad.AutoDist.reset_default()
    model = get_model("mlp", in_dim=8, hidden=(16,), num_classes=4)
    params = model.init(jax.random.PRNGKey(0))
    autodist = ad.AutoDist(strategy_builder=ad.strategy.AllReduce())
    step = autodist.build(model.loss_fn, params, model.example_batch(8))
    state = step.init(params)

    def mfn(p, batch):
        return {"loss": jnp.float32(7.0),          # must NOT interleave
                "acc": jnp.float32(0.5),
                "acc__weight": jnp.float32(100.0)}  # must be stripped

    batches = [model.example_batch(8) for _ in range(4)]
    state, hist = step.fit(state, iter(batches),
                           eval_batch=model.example_batch(8),
                           eval_every=2, eval_metrics_fn=mfn)
    assert len(hist["eval_loss"]) == 2          # built-in series untouched
    assert hist["eval_metrics_loss"] == [7.0, 7.0]
    assert hist["eval_acc"] == [0.5, 0.5]
    assert "eval_acc__weight" not in hist
    ad.AutoDist.reset_default()


def test_ranking_metrics_hand_computed():
    # 3 users x 4 candidates (positive = column 0). Hand ranks:
    #  u0: pos 0.9 beats all   -> rank 0 -> HR@2 hit, ndcg 1/log2(2)=1.0
    #  u1: 1 negative higher   -> rank 1 -> HR@2 hit, ndcg 1/log2(3)
    #  u2: 3 negatives higher  -> rank 3 -> miss, ndcg 0
    table = jnp.array([
        [0.9, 0.1, 0.2, 0.3],
        [0.5, 0.8, 0.2, 0.1],
        [0.1, 0.5, 0.6, 0.7],
    ])

    def score_fn(params, users, items):
        return table[users[0], items]

    batch = {"users": jnp.arange(3),
             "candidates": jnp.tile(jnp.arange(4), (3, 1))}
    out = metrics.ranking_metrics(score_fn, k=2)(None, batch)
    assert float(out["hr@2"]) == pytest.approx(2 / 3)
    want_ndcg = (1.0 + 1.0 / np.log2(3.0) + 0.0) / 3.0
    assert float(out["ndcg@2"]) == pytest.approx(want_ndcg, rel=1e-6)


def test_ranking_metrics_over_real_ncf():
    ad.AutoDist.reset_default()
    model = get_model("ncf", num_users=32, num_items=64, mf_dim=8,
                      mlp_dims=(16, 8))
    params = model.init(jax.random.PRNGKey(0))
    autodist = ad.AutoDist(strategy_builder=ad.strategy.PSLoadBalancing())
    step = autodist.build(model.loss_fn, params, model.example_batch(8),
                          sparse_names=model.sparse_names)
    state = step.init(params)
    rng = np.random.default_rng(0)
    eval_batch = {
        "users": np.arange(8, dtype=np.int32),
        "items": np.zeros((8,), np.int32),       # step.evaluate needs these
        "labels": np.ones((8,), np.float32),
        "candidates": rng.integers(0, 64, (8, 10)).astype(np.int32),
    }
    mfn = metrics.ranking_metrics(
        lambda p, u, i: model.apply(p, {"users": u, "items": i}), k=5)
    got = metrics.evaluate_dataset(step, state, [eval_batch], metrics_fn=mfn)
    assert 0.0 <= got["hr@5"] <= 1.0
    assert 0.0 <= got["ndcg@5"] <= 1.0
    assert got["examples"] == 8
    ad.AutoDist.reset_default()


# ------------------------------------------------- Histogram.observe_many
def _reservoir(values, max_samples=4096):
    """Algorithm R over a sorted reservoir, one value at a time, as
    ``Histogram.observe`` kept it before ``observe_many``: the reference."""
    import bisect

    rng, samples, count, total = np.random.default_rng(0), [], 0, 0.0
    for v in values:
        v = float(v)
        count += 1
        total += v
        if len(samples) < max_samples:
            bisect.insort(samples, v)
        else:
            j = int(rng.integers(0, count))
            if j < max_samples:
                del samples[j]
                bisect.insort(samples, v)
    return samples, count, total


@pytest.mark.parametrize("n,batches", [
    (10, [3]), (4096, [1, 128, 7]), (4097, [4097]), (5000, [4000, 1000]),
    (20_000, [4, 1, 128, 33]), (20_000, [0, 20_000])])
def test_observe_many_leaves_what_observe_leaves(n, batches):
    """The same count, sum, retained reservoir and rendered text whether
    the values come one ``observe`` at a time or in ``observe_many``
    batches of any size, past 4,096 samples too (where algorithm R draws
    from the generator), and both equal to the one-at-a-time reference."""
    values = np.random.default_rng(n).exponential(0.01, n).tolist()
    one, many = metrics.MetricsRegistry(), metrics.MetricsRegistry()
    for v in values:
        one.histogram("serve_itl_s").observe(v)
    at, i = 0, 0
    while at < len(values):
        size = batches[i % len(batches)]
        many.histogram("serve_itl_s").observe_many(values[at:at + size])
        at, i = at + size, i + 1
    a, b = one.histogram("serve_itl_s"), many.histogram("serve_itl_s")
    samples, count, total = _reservoir(values)
    assert (a._samples, a.count, a.sum) == (samples, count, total)
    assert (b._samples, b.count, b.sum) == (samples, count, total)
    assert len(samples) == min(n, 4096)
    assert many.render_text() == one.render_text()
    # and the generator stands where it did: the next value lands alike
    a.observe(0.5)
    b.observe_many([0.5])
    assert b._samples == a._samples == _reservoir(values + [0.5])[0]


def test_observe_many_of_nothing_changes_nothing():
    h = metrics.Histogram()
    h.observe_many([])
    assert h.count == 0 and h.sum == 0.0 and h._samples == []
    h.observe_many(iter([2, 1.0, np.float32(3)]))
    assert h._samples == [1.0, 2.0, 3.0] and h.count == 3 and h.sum == 6.0
