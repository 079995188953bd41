"""ZeRO-2/3 sharded training (MXNET_SHARDED_UPDATE stages, ISSUE 15).

Runs on the suite's simulated 8-device CPU mesh (conftest.py forces
XLA_FLAGS=--xla_force_host_platform_device_count=8). Covers:

- stage selection: ``sharded_stage`` parsing/clamping, the stage-0
  opt-out, and the stage tag threaded through ``Module._fused_fit``;
- end-to-end equivalence through ``Module.fit_step`` at dp=4: the MLP
  is BITWISE identical across stages 0/1/2/3 over 8 SGD-momentum
  steps; the transformer LM matches to f32 round-off for stages 2/3
  (the producer-site reduce-scatter and the stage-3 remat change the
  backward program, so XLA CPU reassociates the replica sum — same
  tolerance class as docs/parallelism.md documents for ZeRO-1);
- the ZeRO-2 cotangent machinery (``zero2_grad_scatter`` is a value
  identity whose custom transpose shards gradients) and the ZeRO-3
  gather (``zero3_gather`` replicates values, its transpose keeps the
  cotangent sharded; ``zero3_remat`` stays a callable);
- the layout byte model (``stage_train_bytes``) behind the
  ``train_param_bytes``/``train_grad_bytes{stage=}`` gauges, plus the
  gauges and the step span's ``stage``/``gather_bytes`` themselves;
- ZeRO-3 checkpoints: local-write snapshot (no device re-replication)
  bitwise-equal to the synced exec values, dp=4 -> 2 -> 4 resharding
  round-trip bitwise INCLUDING momentum state, restore resumes
  identically;
- the kvstore no-updater push densify regression (stored shards must
  keep their layout when no updater is installed).
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import models, telemetry
from mxnet_tpu.initializer import Uniform
from mxnet_tpu.io import DataBatch
from mxnet_tpu.ndarray import NDArray
from mxnet_tpu.parallel import collectives as coll
from mxnet_tpu.resilience import checkpoint as ckpt

pytestmark = pytest.mark.parallel

DP = 4


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.reset()
    telemetry.disable_spans()
    yield
    telemetry.disable_spans()
    telemetry.reset()


def _mesh(n=DP):
    return Mesh(np.array(jax.devices()[:n]), ("data",))


def _mlp():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _mlp_batches(steps, batch=16, feat=8, classes=4):
    rng = np.random.RandomState(3)
    out = []
    for _ in range(steps):
        x = rng.uniform(-1, 1, (batch, feat)).astype(np.float32)
        y = rng.randint(0, classes, (batch,)).astype(np.float32)
        out.append(DataBatch(data=[mx.nd.array(x)], label=[mx.nd.array(y)]))
    return out


def _train_mlp(monkeypatch, stage, steps=8):
    monkeypatch.setenv("MXNET_SHARDED_UPDATE", str(stage))
    ctxs = [mx.Context("cpu", i) for i in range(DP)]
    mod = mx.mod.Module(_mlp(), context=ctxs)
    mx.random.seed(7)
    mod.bind(data_shapes=[("data", (16, 8))],
             label_shapes=[("softmax_label", (16,))])
    mod.init_params(Uniform(0.1))
    mod.init_optimizer(kvstore=None, optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    for b in _mlp_batches(steps):
        mod.fit_step(b)
    return mod


# --- stage selection --------------------------------------------------------

def test_sharded_stage_parsing(monkeypatch):
    mesh = _mesh()
    monkeypatch.delenv("MXNET_SHARDED_UPDATE", raising=False)
    assert coll.sharded_stage(mesh) == 1          # default stays ZeRO-1
    assert coll.sharded_stage(None) == 0          # no mesh -> no sharding
    one = Mesh(np.array(jax.devices()[:1]), ("data",))
    assert coll.sharded_stage(one) == 0           # size-1 axis never shards
    for env, want in [("0", 0), ("1", 1), ("2", 2), ("3", 3),
                      ("7", 3), ("-2", 0), ("garbage", 1)]:
        monkeypatch.setenv("MXNET_SHARDED_UPDATE", env)
        assert coll.sharded_stage(mesh) == want, env
    monkeypatch.setenv("MXNET_SHARDED_UPDATE", "3")
    assert coll.zero1_enabled(mesh)               # stages imply ZeRO-1


def test_stage_opt_out_and_fused_state_tag(monkeypatch):
    """MXNET_SHARDED_UPDATE=0 keeps the replicated path even on a dp
    mesh; stages 2/3 record themselves in the fused fit state."""
    m0 = _train_mlp(monkeypatch, 0, steps=1)
    assert m0._fused_fit["stage"] == 0 and m0._fused_fit["z1"] is False
    for stage in (2, 3):
        m = _train_mlp(monkeypatch, stage, steps=1)
        assert m._fused_fit["stage"] == stage
        assert m._fused_fit["z1"] is True
        for n, p in m._fused_fit["params"].items():
            assert p.sharding == coll.zero1_sharding(
                m._fused_fit["mesh"], p.shape), n


# --- end-to-end equivalence -------------------------------------------------

def test_mlp_stages_bitwise_identical(monkeypatch):
    """8 SGD-momentum steps at dp=4: stages 0/1/2/3 end with BITWISE
    identical weights (same math, same per-element reduction shapes on
    this program)."""
    weights = {}
    for stage in (0, 1, 2, 3):
        mod = _train_mlp(monkeypatch, stage)
        weights[stage] = {n: a.asnumpy().copy()
                          for n, a in mod.get_params()[0].items()}
    for stage in (1, 2, 3):
        for n in weights[0]:
            assert np.array_equal(weights[0][n], weights[stage][n]), \
                (stage, n)


def _train_lm(monkeypatch, stage, steps=8, batch=8, seq=8, vocab=32):
    monkeypatch.setenv("MXNET_SHARDED_UPDATE", str(stage))
    sym = models.get_symbol("transformer-lm", num_classes=vocab,
                            num_layers=1, num_heads=2, model_dim=32,
                            ffn_dim=64, num_kv_heads=2, scalar_loss=True)
    ctxs = [mx.Context("cpu", i) for i in range(DP)]
    mod = mx.mod.Module(sym, context=ctxs, label_names=("softmax_label",))
    mx.random.seed(7)
    mod.bind(data_shapes=[("data", (batch, seq))],
             label_shapes=[("softmax_label", (batch, seq))])
    mod.init_params(Uniform(0.1))
    mod.init_optimizer(kvstore=None, optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    rng = np.random.RandomState(3)
    for _ in range(steps):
        x = rng.randint(0, vocab, (batch, seq)).astype(np.float32)
        mod.fit_step(DataBatch(data=[mx.nd.array(x)],
                               label=[mx.nd.array(x)]))
    return {n: a.asnumpy().copy() for n, a in mod.get_params()[0].items()}


@pytest.mark.slow
def test_transformer_lm_stages_match(monkeypatch):
    """The ISSUE 15 acceptance workload: 8-step transformer LM at dp=4.
    Stage 1 is bitwise-equal to stage 0; stages 2/3 change the backward
    program (producer-site scatter, remat re-gather), so XLA CPU
    reassociates the replica sum — equality to f32 round-off, the
    documented ZeRO tolerance on this backend."""
    w = {s: _train_lm(monkeypatch, s) for s in (0, 1, 2, 3)}
    for n in w[0]:
        assert np.array_equal(w[0][n], w[1][n]), n
    for stage in (2, 3):
        for n in w[0]:
            np.testing.assert_allclose(w[stage][n], w[0][n], rtol=2e-5,
                                       atol=1e-6, err_msg=(stage, n))


# --- the ZeRO-2/3 primitives ------------------------------------------------

def test_zero2_grad_scatter_is_identity_with_sharded_cotangent():
    mesh = _mesh()
    rng = np.random.RandomState(0)
    tree = {"big": jnp.asarray(rng.randn(16, 8).astype(np.float32)),
            "s1": jnp.asarray(rng.randn(8).astype(np.float32)),
            "s2": jnp.asarray(rng.randn(4, 4).astype(np.float32)),
            "odd": jnp.asarray(rng.randn(7).astype(np.float32))}

    def loss(t):
        t = coll.zero2_grad_scatter(t, mesh, bucket_bytes=64)
        return sum(jnp.sum(v ** 2) for v in t.values())

    def plain(t):
        return sum(jnp.sum(v ** 2) for v in t.values())

    val, grads = jax.jit(jax.value_and_grad(loss))(tree)
    assert np.allclose(float(val), float(jax.jit(plain)(tree)))
    for n, g in grads.items():
        np.testing.assert_allclose(np.asarray(g), 2 * np.asarray(tree[n]),
                                   rtol=1e-6, err_msg=n)


def test_zero3_gather_replicates_values_and_keeps_grad_sharded():
    mesh = _mesh()
    rng = np.random.RandomState(1)
    host = {"w": rng.randn(16, 8).astype(np.float32),
            "b": rng.randn(7).astype(np.float32)}  # odd leaf: replicated
    sharded = coll.zero1_place({n: jnp.asarray(v)
                                for n, v in host.items()}, mesh)

    gathered = jax.jit(lambda t: coll.zero3_gather(t, mesh))(sharded)
    for n in host:
        assert np.array_equal(np.asarray(gathered[n]), host[n]), n
        assert gathered[n].sharding.is_fully_replicated, n

    def loss(t):
        t = coll.zero3_gather(t, mesh)
        return sum(jnp.sum(v ** 2) for v in t.values())

    grads = jax.jit(jax.grad(loss))(sharded)
    for n in host:
        np.testing.assert_allclose(np.asarray(grads[n]), 2 * host[n],
                                   rtol=1e-6, err_msg=n)
        # the custom transpose keeps the cotangent in the shard layout
        assert grads[n].sharding.spec == coll.zero1_partition_spec(
            host[n].shape, DP), n


def test_zero3_remat_wraps_callable():
    f = coll.zero3_remat(lambda x: jnp.sum(x * x))
    x = jnp.arange(8, dtype=jnp.float32)
    assert np.allclose(float(jax.jit(f)(x)), float(jnp.sum(x * x)))
    np.testing.assert_allclose(np.asarray(jax.grad(f)(x)),
                               2 * np.asarray(x), rtol=1e-6)


def test_stage_train_bytes_accounting():
    tree = {"w1": np.zeros((16, 8), np.float32),  # 512 B, shards /4
            "w2": np.zeros((16, 8), np.float32),  # 512 B, shards /4
            "b": np.zeros((7,), np.float32)}      # 28 B, stays replicated
    full, shard = 512 + 512 + 28, 128 + 128 + 28
    for stage, want_p, want_g in [
            (0, full, full),
            (1, full + shard, full),
            # transient = one bucket (>= the biggest leaf scattering alone)
            (2, full + shard, shard + 512),
            (3, shard + 512, shard + 512)]:
        p, g = coll.stage_train_bytes(tree, stage, DP, bucket_bytes=512)
        assert (p, g) == (want_p, want_g), (stage, p, g)
    # a bucket larger than the whole tree degenerates to stage-1 residency
    _, g = coll.stage_train_bytes(tree, 2, DP, bucket_bytes=1 << 20)
    assert g == full


def test_zero2_bucket_bytes_env(monkeypatch):
    monkeypatch.delenv("MXNET_ZERO2_BUCKET_MB", raising=False)
    assert coll.zero2_bucket_bytes() == 4 * 1024 * 1024
    monkeypatch.setenv("MXNET_ZERO2_BUCKET_MB", "0.0625")
    assert coll.zero2_bucket_bytes() == 64 * 1024


# --- observability ----------------------------------------------------------

def test_stage3_gauges_and_step_span(monkeypatch):
    """The byte gauges carry the stage label and the layout-implied
    values; the step's span says the stage and the bytes its on-demand
    weight gathers move."""
    telemetry.reset()
    mod = _train_mlp(monkeypatch, 3, steps=2)
    fs = mod._fused_fit
    want_p, want_g = coll.stage_train_bytes(fs["params"], 3, DP)
    assert telemetry.registry.gauge(
        "train_param_bytes", labels={"stage": "3"}).value == want_p
    assert telemetry.registry.gauge(
        "train_grad_bytes", labels={"stage": "3"}).value == want_g
    assert telemetry.registry.gauge(
        "train_opt_bytes", labels={"stage": "3"}).value == \
        coll.per_device_bytes(fs["states"])
    expo = telemetry.registry.exposition()
    assert 'train_param_bytes{stage="3"}' in expo
    steps = [ev[5] for ev in telemetry.drain_events()
             if ev[1] == "executor.train_step"]
    assert len(steps) == 2
    assert all(a["stage"] == 3 and a["gather_bytes"] > 0 for a in steps)


# --- ZeRO-3 checkpoints -----------------------------------------------------

def test_zero3_checkpoint_local_write_matches_synced_params(monkeypatch):
    """The sharded snapshot (host reads off the 1/N shards, no device
    re-replication) is bitwise-equal to the exec-sync'd values — the
    densify-bugfix regression."""
    mod = _train_mlp(monkeypatch, 3, steps=3)
    arrays, opt_meta = mod.get_checkpoint_state()
    arg_params, _ = mod.get_params()
    for n, a in arg_params.items():
        assert np.array_equal(arrays["param:%s" % n], a.asnumpy()), n
    assert any(k.startswith("opt:") for k in arrays)  # momentum travels
    assert opt_meta["num_update"] == 3


def test_zero3_checkpoint_reshard_roundtrip_bitwise(monkeypatch, tmp_path):
    """dp=4 -> 2 -> 4 resharding round-trip is bitwise on every tensor
    INCLUDING optimizer state, and a restored module resumes on the
    exact trajectory."""
    prefix = str(tmp_path / "ck")
    mod = _train_mlp(monkeypatch, 3, steps=3)
    arrays, opt_meta = mod.get_checkpoint_state()
    step = opt_meta["num_update"]
    ckpt.save_sharded(prefix, step, arrays, DP, opt_meta=opt_meta,
                      async_write=False)
    ckpt.reshard(prefix, step, 2)
    ckpt.reshard(prefix, step, DP)
    rc = ckpt.load_sharded(prefix, step, new_dp=DP)
    assert set(rc.arrays) == set(arrays)
    for n in arrays:
        assert np.array_equal(rc.arrays[n], arrays[n]), n
    assert rc.opt_meta["num_update"] == step

    # restore into a FRESH stage-3 module and replay one more batch on
    # both: identical weights afterward
    restored = _train_mlp(monkeypatch, 3, steps=1)  # differently trained
    restored.restore_checkpoint_state(rc.arrays, rc.opt_meta)
    extra = _mlp_batches(5)[-1]
    mod.fit_step(extra)
    restored.fit_step(extra)
    w_a = {n: a.asnumpy() for n, a in mod.get_params()[0].items()}
    w_b = {n: a.asnumpy() for n, a in restored.get_params()[0].items()}
    for n in w_a:
        assert np.array_equal(w_a[n], w_b[n]), n


# --- kvstore regression -----------------------------------------------------

def test_kvstore_push_no_updater_keeps_stored_sharding():
    """dist_sync without an updater: push must move the merged gradient
    TO the stored value's ZeRO layout, not densify the store (the
    aggregate-path twin of the updater-path fix)."""
    mesh = _mesh(8)
    kv = mx.kvstore.create("local")
    w = np.arange(16, dtype=np.float32)
    stored = NDArray(jax.device_put(jnp.asarray(w),
                                    coll.zero1_sharding(mesh, (16,))))
    kv.init(9, stored)
    kv._store[9] = stored  # keep the sharded buffer as the master value
    grad = NDArray(jax.device_put(jnp.ones(16, jnp.float32),
                                  NamedSharding(mesh, P())))
    kv.push(9, grad)  # no updater installed: stored value REPLACED
    assert kv._store[9]._data.sharding.spec == P("data")
    out = NDArray(jax.device_put(jnp.zeros(16, jnp.float32),
                                 NamedSharding(mesh, P())))
    kv.pull(9, out)
    assert out._data.sharding.spec == P()
    np.testing.assert_allclose(np.asarray(out._data), np.ones(16), rtol=0)
