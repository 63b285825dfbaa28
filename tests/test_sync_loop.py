"""The sync trainer's one chunk loop (``train/sync.py``): the resident
fit a step a dispatch, the resident fit by fused chunks and the
streaming fit run the same body, feed the same observers in the same
order, and make their records in one place; what a model sows reaches
the records through the model alone."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparktorch_tpu.models import Net
from sparktorch_tpu.obs import goodput as goodput_mod
from sparktorch_tpu.obs import health as health_mod
from sparktorch_tpu.obs.telemetry import Telemetry
from sparktorch_tpu.parallel.mesh import local_mesh
from sparktorch_tpu.train.sync import (
    train_distributed,
    train_distributed_streaming,
)
from sparktorch_tpu.utils.serde import ModelSpec

ITERS = 8
# how each fit is driven: the call, its arguments, the steps a dispatch holds
FITS = {
    "per_step": (train_distributed,
                 dict(iters=ITERS, steps_per_call=1, mini_batch=8), 1),
    "fused": (train_distributed,
              dict(iters=ITERS, steps_per_call=4, mini_batch=8), 4),
    # 64 rows in chunks of 32 over 4 shards: 2 chunks of 4 steps
    "streaming": (train_distributed_streaming,
                  dict(chunk_rows=32, steps_per_chunk=4, mini_batch=8), 4),
}


def _rows(n=64, dim=10, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, dim)).astype(np.float32)
    return x, (x[:, 0] > 0).astype(np.float32)


def _spec(module=None):
    return ModelSpec(module=module or Net(), loss="mse", optimizer="adam",
                     optimizer_params={"lr": 1e-2}, input_shape=(10,))


def _fit(mode, spec=None, **extra):
    call, kwargs, _ = FITS[mode]
    x, y = _rows()
    return call(spec or _spec(), x, labels=y, mesh=local_mesh(4), seed=0,
                **{**kwargs, **extra})


class _SpyLedger(goodput_mod.GoodputLedger):
    """Keeps what each closed span attributed: ``(bucket, count)``, and
    the step index of each step span."""

    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        self.closed, self.stamped = [], []

    def _stamp_step(self, step, count, t0, t1):
        self.stamped.append((step, count))
        super()._stamp_step(step, count, t0, t1)

    def _attribute(self, bucket, seconds, count):
        self.closed.append((bucket, count))
        super()._attribute(bucket, seconds, count)


@pytest.mark.parametrize("mode", list(FITS))
def test_the_loop_feeds_the_health_and_the_goodput_ledgers(mode):
    """Every step reaches the health ledger, in order, with the loss the
    records carry; every dispatch is one step span of the goodput
    ledger, the first (which compiles) in ``compile`` as one event, the
    others in ``step`` with the steps they hold."""
    steps = FITS[mode][2]
    tele = Telemetry(run_id=f"loop-{mode}")
    hl = health_mod.TrainHealthLedger(
        rank=0, telemetry=tele, config=health_mod.HealthConfig(fetch_lag=1))
    prev = health_mod.install(hl)
    led = _SpyLedger(telemetry=tele)
    try:
        with led.activate():
            result = _fit(mode, telemetry=tele)
    finally:
        health_mod.install(prev)
    assert len(result.metrics) == ITERS
    doc = hl.snapshot()
    assert doc["steps_ingested"] == ITERS and doc["pending_fetch"] == 0
    assert doc["series"]["steps"] == list(range(ITERS))
    assert doc["series"]["loss"] == pytest.approx(
        [r["loss"] for r in result.metrics])
    assert doc["series"]["grad_norm"] == pytest.approx(
        [r["grad_norm"] for r in result.metrics], rel=1e-6)
    assert hl.leaf_keys == ["Dense_0.bias", "Dense_0.kernel",
                            "Dense_1.bias", "Dense_1.kernel"]
    # one step span a dispatch: a dispatch that compiled (the first;
    # the second too where the state comes back placed otherwise) is
    # one ``compile`` event, as the init jit is, and every other is
    # ``steps`` steps under the index of its first
    dispatches = ITERS // steps
    own = [(b, c) for b, c in led.closed
           if b == "step" or (b, c) == ("compile", 1)]
    trained = [s for s, _c in led.stamped]
    assert set(own) == {("compile", 1), ("step", steps)}
    assert len(own) == dispatches + 1
    assert 1 <= own.count(("compile", 1)) - 1 <= 2
    assert own.count(("step", steps)) == len(trained)
    assert set(trained) <= {d * steps for d in range(1, dispatches)}
    assert trained == sorted(set(trained))
    assert led.snapshot()["n_steps"] == steps * len(trained)


# -- a model's counters stay behind the model ---------------------------------


class _Counted(nn.Module):
    """A layer that sows a counter no trainer has heard of: the rows it
    saw and the positive first features among them."""

    features: int

    @nn.compact
    def __call__(self, x):
        self.sow("moe_metrics", "rows_in", jnp.stack(
            [jnp.float32(x.shape[0]), jnp.sum(x[:, 0] > 0, dtype=jnp.float32)]))
        return nn.Dense(self.features)(x)


class CountingNet(nn.Module):
    """Two counted layers, and what their counter means in a record."""

    @nn.compact
    def __call__(self, x):
        hidden = nn.relu(_Counted(20)(x))
        return _Counted(1)(hidden)

    def train_gauges(self, row_shape):
        return {"train.toy.layers": 2}

    def train_counters(self, sown, drop_fraction):
        assert set(sown) == {"rows_in"} and drop_fraction is None
        by_layer = sown["rows_in"]  # [layers, (rows, positive)]
        assert by_layer.shape == (2, 2)
        fields = {"toy_rows": float(by_layer[0, 0]),
                  "toy_positive": float(by_layer[0, 1])}
        return (fields, {"train.toy.rows": fields["toy_rows"]},
                {"train.toy.positive": fields["toy_positive"]})


@pytest.mark.parametrize("mode", list(FITS))
def test_a_counter_the_trainer_never_heard_of_reaches_the_records(mode):
    tele, seen = Telemetry(run_id=f"toy-{mode}"), []
    # every row a step: the first layer's positives are the labels'
    result = _fit(mode, spec=_spec(CountingNet()), telemetry=tele,
                  metrics_hook=seen.append, mini_batch=None)
    x, y = _rows()
    rows_a_step = {"streaming": 32}.get(mode, 64)
    assert len(seen) == len(result.metrics) == ITERS
    for hooked, kept in zip(seen, result.metrics):
        assert kept["toy_rows"] == kept["examples"] == rows_a_step
        assert 0 < kept["toy_positive"] < rows_a_step
        assert hooked["toy_positive"] == kept["toy_positive"]
        assert "moe_drop_fraction" not in kept
    if mode != "streaming":
        assert {r["toy_positive"] for r in seen} == {float(y.sum())}
    else:  # two chunks of four steps an epoch, all the rows between them
        assert seen[0]["toy_positive"] + seen[4]["toy_positive"] == y.sum()
    assert tele.counter_value("train.toy.rows") == ITERS * rows_a_step
    assert tele.gauge_value("train.toy.positive") == seen[-1]["toy_positive"]
    assert tele.gauge_value("train.toy.layers") == 2


def test_a_model_that_sows_nothing_has_no_output_for_it():
    """The cells' program (``jit_train_epoch``) for a BERT-shaped model:
    the state, and per step the loss, the examples, the gradient norm
    and the health vector. What a model may sow is an empty subtree."""
    from sparktorch_tpu.models import SequenceClassifier, tiny_transformer
    from sparktorch_tpu.train.step import (
        StepMetrics,
        create_train_state,
        make_train_epoch,
    )
    from sparktorch_tpu.train.sync import prepare_sharded_batch
    from sparktorch_tpu.utils.data import DataBatch

    spec = ModelSpec(
        module=SequenceClassifier(tiny_transformer(
            vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
            max_len=8, n_classes=2, dtype="float32")),
        loss="cross_entropy", optimizer="adam", optimizer_params={"lr": 1e-3},
        input_shape=(8,))
    mesh, tx = local_mesh(4), spec.make_optimizer()
    state = create_train_state(spec, jax.random.key(0),
                               sample_x=jnp.zeros((1, 8)), tx=tx)
    batch = prepare_sharded_batch(
        DataBatch(x=np.zeros((16, 8), np.float32), y=np.zeros((16,), np.float32),
                  w=np.ones((16,), np.float32)), mesh)
    fn = make_train_epoch(spec.make_module().apply, spec.loss_fn(), tx, mesh,
                          4, mini_batch=2)
    lowered = fn.lower(state, batch)
    assert lowered.as_text().startswith("module @jit_train_epoch ")
    out_state, metrics = lowered.out_info
    assert isinstance(metrics, StepMetrics)
    assert (metrics.drop_fraction, metrics.sown, metrics.val_loss,
            metrics.active) == (None, {}, None, None)
    n_leaves = len(jax.tree.leaves(state.params))
    assert {k: v.shape for k, v in metrics._asdict().items()
            if k in ("loss", "examples", "grad_norm")} == {
                "loss": (4,), "examples": (4,), "grad_norm": (4,)}
    assert jax.tree.map(lambda a: a.shape, metrics.health) == (
        (4,), (4,), (4, n_leaves))
    assert len(jax.tree.leaves(lowered.out_info)) \
        == len(jax.tree.leaves(state)) + 6


# -- one loop, its observers wired in one place --------------------------------


@pytest.fixture
def observed(monkeypatch):
    """The calls the loop makes on its observers, in order."""
    from sparktorch_tpu.train import sync

    calls = []

    class Spy(sync._RunObservers):
        pass

    def logged(name):
        real = getattr(sync._RunObservers, name)

        def method(self, *args, **kwargs):
            calls.append(name)
            return real(self, *args, **kwargs)

        return method

    for name in ("before_dispatch", "step_span", "annotation", "after_chunk",
                 "record", "close"):
        setattr(Spy, name, logged(name))
    monkeypatch.setattr(sync, "_RunObservers", Spy)
    return calls


def test_streaming_and_resident_fits_make_the_same_records_the_same_way(
        observed):
    seen = {}
    for mode in FITS:
        del observed[:]
        records = []
        result = _fit(mode, metrics_hook=records.append,
                      telemetry=Telemetry(run_id=mode))
        seen[mode] = (list(observed), records, result.metrics)
    a_dispatch = ["before_dispatch", "step_span", "annotation", "after_chunk"]
    for mode, (calls, hooked, kept) in seen.items():
        steps = FITS[mode][2]
        assert calls == (a_dispatch + ["record"] * steps) * (
            ITERS // steps) + ["close"], mode
        assert [r["iter"] for r in kept] == list(range(ITERS))
        assert all(isinstance(r["grad_norm"], float) and r["grad_norm"] > 0
                   and r["val_loss"] is None for r in kept)
    assert seen["streaming"][0] == seen["fused"][0]
    for mode in ("per_step", "streaming"):
        for ours, theirs in zip(seen[mode][1:], seen["fused"][1:]):
            assert [sorted(r) for r in ours] == [sorted(r) for r in theirs]
    # the hook's first record alone names the leaves, in every fit
    assert "leaf_grad_norm_keys" in seen["streaming"][1][0]
    assert seen["streaming"][1][0]["leaf_grad_norms"].shape == (4,)


def test_each_fit_names_its_own_dispatch_span_and_checkpoint(tmp_path):
    """One body, three names for its dispatch; the three tiles around it
    are the fused resident fit's alone; a due save is a span."""
    want = {"per_step": "train/step", "fused": "train/step_chunk",
            "streaming": "train_streaming/chunk"}
    for mode, span in want.items():
        tele = Telemetry(run_id=mode)
        _fit(mode, telemetry=tele, checkpoint_dir=str(tmp_path / mode),
             checkpoint_every=4)
        spans = tele.snapshot()["spans"]
        steps = FITS[mode][2]
        assert spans[span]["count"] == ITERS // steps
        assert set(want.values()) & set(spans) == {span}
        assert any(k.startswith("train/chunk_") for k in spans) \
            == (mode == "fused")
        assert spans[span.split("/")[0] + "/checkpoint"]["count"] \
            == ITERS // steps
        assert spans["train/init"]["count"] == 1
        assert tele.counter_value("tracing.annotated_steps") == ITERS // steps


def test_a_shuffle_leaves_one_copy_of_the_resident_rows_alive():
    """``mini_batch`` shuffles the rows before round 0: the rows as
    placed are freed, not kept beside the shuffled ones (35 MB of the
    BERT cell's 5.94 GB, 134 MB of the Keye cell's, on the chip)."""
    x, y = _rows(n=64, dim=7)
    copies = []

    def hook(record):
        copies.append(sum(1 for a in jax.live_arrays()
                          if a.shape == x.shape))

    spec = ModelSpec(module=Net(in_features=7), loss="mse", optimizer="adam",
                     optimizer_params={"lr": 1e-2}, input_shape=(7,))
    train_distributed(spec, x, labels=y, mesh=local_mesh(4), iters=4,
                      steps_per_call=2, mini_batch=8, metrics_hook=hook,
                      telemetry=Telemetry(run_id="rows"))
    assert copies == [1] * 4
