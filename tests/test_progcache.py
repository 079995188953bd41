"""mxnet_tpu.progcache — persistent compiled-program cache.

Every hostile path must degrade to a fresh compile with outputs
bitwise-identical to a cold run: truncation, CRC corruption, version
skew, stale fingerprints, manifest damage. The cache may only ever make
startup faster, never answers different.
"""
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import predict, progcache
from mxnet_tpu.serving.bucket_cache import BucketCache

IN_DIM, HIDDEN = 4, 8


def _model(seed=0):
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data=data, num_hidden=HIDDEN, name="fc")
    sym = mx.sym.SoftmaxOutput(data=fc, name="softmax")
    rng = np.random.RandomState(seed)
    params = {"fc_weight": mx.nd.array(
                  rng.uniform(-0.1, 0.1, (HIDDEN, IN_DIM))
                  .astype(np.float32)),
              "fc_bias": mx.nd.zeros((HIDDEN,))}
    return sym, params


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    d = str(tmp_path / "progcache")
    monkeypatch.delenv("MXNET_PROGCACHE", raising=False)
    monkeypatch.setenv("MXNET_PROGCACHE_DIR", d)
    progcache.reset_stats()
    return d


def _predictor(sym, params, batch=2):
    return predict.Predictor(sym.tojson(), params,
                             {"data": (batch, IN_DIM)})


def _entry_files(d):
    return sorted(f for f in os.listdir(d) if f.endswith(".prog"))


def test_store_load_roundtrip_bitwise(cache_dir):
    sym, params = _model()
    x = np.random.RandomState(1).uniform(-1, 1, (2, IN_DIM)) \
        .astype(np.float32)
    p1 = _predictor(sym, params)
    assert p1.progcache_source == "compile"
    cold = p1.forward(data=x)[0].asnumpy()
    assert progcache.stats()["stores"] == 1
    assert _entry_files(cache_dir)

    p2 = _predictor(sym, params)
    assert p2.progcache_source == "disk"
    warm = p2.forward(data=x)[0].asnumpy()
    assert np.array_equal(cold, warm)  # bitwise, not allclose
    s = progcache.stats()
    assert s["hits"] == 1 and s["fallbacks"] == 0


def test_disabled_by_default(tmp_path, monkeypatch):
    monkeypatch.delenv("MXNET_PROGCACHE", raising=False)
    monkeypatch.delenv("MXNET_PROGCACHE_DIR", raising=False)
    assert not progcache.enabled()
    sym, params = _model()
    p = _predictor(sym, params)
    assert not hasattr(p, "_progcache_model_fp")


def test_kill_switch_wins_over_dir(cache_dir, monkeypatch):
    monkeypatch.setenv("MXNET_PROGCACHE", "0")
    assert not progcache.enabled()
    sym, params = _model()
    _predictor(sym, params)
    assert not os.path.exists(cache_dir) or not _entry_files(cache_dir)


def test_truncated_entry_falls_back_bitwise(cache_dir):
    sym, params = _model()
    x = np.random.RandomState(2).uniform(-1, 1, (2, IN_DIM)) \
        .astype(np.float32)
    cold = _predictor(sym, params).forward(data=x)[0].asnumpy()
    (entry,) = _entry_files(cache_dir)
    path = os.path.join(cache_dir, entry)
    blob = open(path, "rb").read()
    with open(path, "wb") as f:      # deliberate damage (test-only)
        f.write(blob[:len(blob) // 2])
    p = _predictor(sym, params)
    assert p.progcache_source == "compile"  # fell back
    assert np.array_equal(p.forward(data=x)[0].asnumpy(), cold)
    assert progcache.stats()["fallbacks"] == 1
    # the bad entry was dropped and replaced by the fallback's own store:
    # the damage is paid for once, not on every restart
    assert _predictor(sym, params).progcache_source == "disk"


def test_payload_crc_mismatch_falls_back_bitwise(cache_dir):
    sym, params = _model()
    x = np.random.RandomState(3).uniform(-1, 1, (2, IN_DIM)) \
        .astype(np.float32)
    cold = _predictor(sym, params).forward(data=x)[0].asnumpy()
    (entry,) = _entry_files(cache_dir)
    path = os.path.join(cache_dir, entry)
    blob = bytearray(open(path, "rb").read())
    blob[-1] ^= 0xFF                 # flip one payload byte
    with open(path, "wb") as f:
        f.write(bytes(blob))
    p = _predictor(sym, params)
    assert p.progcache_source == "compile"
    assert np.array_equal(p.forward(data=x)[0].asnumpy(), cold)
    assert progcache.stats()["fallbacks"] == 1


def test_version_skew_falls_back_bitwise(cache_dir):
    sym, params = _model()
    x = np.random.RandomState(4).uniform(-1, 1, (2, IN_DIM)) \
        .astype(np.float32)
    # store under a forged jax version: a valid, CRC-clean entry from an
    # "older" process
    real = progcache._runtime_meta()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(progcache, "_runtime_meta",
                   lambda: dict(real, jax="0.0.1"))
        cold = _predictor(sym, params).forward(data=x)[0].asnumpy()
    assert _entry_files(cache_dir)
    p = _predictor(sym, params)
    # the key embeds the runtime meta, so a skewed entry is simply never
    # addressed — a miss, then a fresh compile + store under today's key
    assert p.progcache_source == "compile"
    assert np.array_equal(p.forward(data=x)[0].asnumpy(), cold)


def test_meta_block_skew_is_a_fallback(cache_dir):
    # same KEY (computed with real meta), but the entry's embedded meta
    # claims another jaxlib — the load-time skew check must reject it
    sym, params = _model()
    p1 = _predictor(sym, params)
    (entry,) = _entry_files(cache_dir)
    path = os.path.join(cache_dir, entry)
    blob = open(path, "rb").read()
    off = len(progcache.MAGIC)
    (mlen,) = progcache._U32.unpack_from(blob, off)
    meta = json.loads(blob[off + 4:off + 4 + mlen].decode())
    meta["jaxlib"] = "0.0.1"
    payload = blob[off + 4 + mlen + 4:]
    with open(path, "wb") as f:
        f.write(progcache._pack_entry(meta, payload))
    assert progcache.load(entry[:-len(".prog")]) is None
    assert progcache.stats()["fallbacks"] == 1


def test_stale_fingerprint_after_param_change(cache_dir):
    sym, params = _model(seed=0)
    x = np.random.RandomState(5).uniform(-1, 1, (2, IN_DIM)) \
        .astype(np.float32)
    _predictor(sym, params)
    # same symbol/shapes, DIFFERENT weights: values are closure constants
    # inside the serialized executable, so this MUST miss — a hit would
    # silently serve the old model
    sym2, params2 = _model(seed=9)
    p2 = _predictor(sym2, params2)
    assert p2.progcache_source == "compile"
    with pytest.MonkeyPatch.context() as mp:  # cache-free reference
        mp.setenv("MXNET_PROGCACHE", "0")
        ref = _predictor(sym2, params2).forward(data=x)[0].asnumpy()
    assert np.array_equal(p2.forward(data=x)[0].asnumpy(), ref)
    # and a different SHAPE under the same weights misses too
    p3 = _predictor(sym, params, batch=3)
    assert p3.progcache_source == "compile"


def test_manifest_corruption_rebuilds_from_scan(cache_dir):
    sym, params = _model()
    _predictor(sym, params)
    man = os.path.join(cache_dir, progcache.MANIFEST)
    with open(man, "w") as f:
        f.write("{ not json")
    # loads still work (entries are content-addressed) and the manifest
    # heals on the next commit
    p = _predictor(sym, params)
    assert p.progcache_source == "disk"
    assert progcache.bytes_in_use() > 0
    m = json.loads(open(man, "rb").read().decode())
    assert m["entries"]


def test_manifest_crc_mismatch_rebuilds(cache_dir):
    sym, params = _model()
    _predictor(sym, params)
    man = os.path.join(cache_dir, progcache.MANIFEST)
    m = json.loads(open(man, "rb").read().decode())
    m["clock"] += 7  # tamper without recomputing the crc
    with open(man, "w") as f:
        f.write(json.dumps(m))
    p = _predictor(sym, params)
    assert p.progcache_source == "disk"


def test_lru_byte_budget_evicts_oldest(cache_dir, monkeypatch):
    sym, params = _model()
    p = _predictor(sym, params, batch=1)
    size = os.path.getsize(
        os.path.join(cache_dir, _entry_files(cache_dir)[0]))
    # room for about two entries; the third store must evict the oldest
    monkeypatch.setenv("MXNET_PROGCACHE_BYTES", str(int(size * 2.5)))
    p.reshape({"data": (2, IN_DIM)})
    p.reshape({"data": (3, IN_DIM)})
    assert progcache.stats()["evictions"] >= 1
    assert progcache.bytes_in_use() <= int(size * 2.5)
    # the evicted (oldest) program recompiles; the newest still loads
    assert p.reshape({"data": (3, IN_DIM)}).progcache_source == "disk"
    assert p.reshape({"data": (1, IN_DIM)}).progcache_source == "compile"


def test_atomic_commits_leave_no_tmp(cache_dir):
    sym, params = _model()
    _predictor(sym, params)
    assert not [f for f in os.listdir(cache_dir) if f.endswith(".tmp")]


def test_bucket_cache_stats_split_and_warm_restart(cache_dir):
    sym, params = _model()
    base = _predictor(sym, params, batch=1)
    cache = BucketCache(base, (1, 2, 4))
    cache.warm()
    s = cache.stats()
    # cold: base enrolled at 1, buckets 2 and 4 freshly compiled
    assert s["compiles"] == 2 and s["disk_hits"] == 0
    assert s["cache_hits"] == s["hits"]

    base2 = _predictor(sym, params, batch=1)   # disk load
    cache2 = BucketCache(base2, (1, 2, 4))
    cache2.warm()
    s2 = cache2.stats()
    # warm restart: ZERO fresh compiles, the whole ladder from disk
    assert s2["compiles"] == 0 and s2["disk_hits"] == 2
    x = np.random.RandomState(6).uniform(-1, 1, (2, IN_DIM)) \
        .astype(np.float32)
    assert np.array_equal(cache.get(2).forward(data=x)[0].asnumpy(),
                          cache2.get(2).forward(data=x)[0].asnumpy())


def test_ladder_persistence_roundtrip(cache_dir):
    sym, params = _model()
    base = _predictor(sym, params, batch=1)
    cache = BucketCache(base, (1, 4))
    cache.warm()                      # builds + stores bucket 4
    cache.prepare(3)                  # builds + stores bucket 3
    cache.set_ladder([3, 4])          # persists the tuned ladder
    fp = base._progcache_model_fp
    assert progcache.load_ladder(fp) == [3, 4]

    base2 = _predictor(sym, params, batch=1)
    cache2 = BucketCache(base2, (1, 4))
    assert cache2.restore_ladder() is True
    assert cache2.buckets == [3, 4]
    cache2.warm()
    assert cache2.stats()["compiles"] == 0  # 3 and 4 both disk-loaded


def test_restore_ladder_rejects_mismatched_max(cache_dir):
    sym, params = _model()
    base = _predictor(sym, params, batch=1)
    fp = progcache.model_fingerprint(
        base._symbol, base._arg_params, base._aux_params)
    progcache.save_ladder(fp, [2, 16])  # different max_batch than (1, 4)
    cache = BucketCache(base, (1, 4))
    assert cache.restore_ladder() is False
    assert cache.buckets == [1, 4]


def test_fused_train_step_cache_roundtrip(cache_dir):
    """The fused train step's warm restart is JAX's persistent compilation
    cache, not this one: with the program cache on, two runs of
    ``Module.fit_step`` neither store nor load a program here, and end on
    bitwise the same weights."""
    def fit(steps=2):
        sym, params = _model()
        mod = mx.mod.Module(sym, data_names=("data",),
                            label_names=("softmax_label",))
        mod.bind(data_shapes=[("data", (4, IN_DIM))],
                 label_shapes=[("softmax_label", (4,))])
        mod.init_params(initializer=None,
                        arg_params={n: a.copy() for n, a in params.items()})
        mod.init_optimizer(kvstore=None, optimizer="sgd",
                           optimizer_params=(("learning_rate", 0.1),))
        r = np.random.RandomState(8)
        mx.random.seed(0)
        for _ in range(steps):
            batch = mx.io.DataBatch(
                data=[mx.nd.array(r.uniform(-1, 1, (4, IN_DIM))
                                  .astype(np.float32))],
                label=[mx.nd.array(r.randint(0, HIDDEN, (4,))
                                   .astype(np.float32))])
            mod.fit_step(batch)
        assert mod.fit_step_path == "fused"
        return {n: a.asnumpy() for n, a in mod.get_params()[0].items()}

    assert progcache.enabled()
    w_cold = fit()
    w_warm = fit()
    s = progcache.stats()
    assert (s["stores"], s["hits"], s["misses"]) == (0, 0, 0), s
    for n in w_cold:
        assert np.array_equal(w_cold[n], w_warm[n]), n


def test_telemetry_counters_exported(cache_dir):
    from mxnet_tpu import telemetry
    sym, params = _model()
    _predictor(sym, params)
    _predictor(sym, params)
    exposition = telemetry.registry.exposition()
    lines = {l.split()[0] for l in exposition.splitlines()
             if l and not l.startswith("#")}
    for name in ("progcache_hits", "progcache_misses",
                 "progcache_fallbacks", "progcache_bytes"):
        assert name in lines, name


def test_bytes_by_kind_splits_and_survives_manifest_rebuild(cache_dir):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import telemetry

    def compiled(scale):
        return jax.jit(lambda x, _s=scale: x * _s).lower(
            jnp.zeros((2, 2), jnp.float32)).compile()

    assert progcache.store("kindtest_pred", compiled(2.0), kind="predictor")
    assert progcache.store("kindtest_step", compiled(3.0), kind="train_step")
    assert progcache.store("kindtest_legacy", compiled(4.0))  # no kind
    bk = progcache.bytes_by_kind()
    assert bk["predictor"] > 0 and bk["train_step"] > 0
    assert bk.get("", 0) > 0  # pre-kind entries collect under ""
    assert sum(bk.values()) == progcache.bytes_in_use()
    # per-kind gauges register lazily, only for kinds actually in use
    lines = {l.split()[0] for l in telemetry.registry.exposition()
             .splitlines() if l and not l.startswith("#")}
    assert "progcache_bytes_kind_predictor" in lines
    assert "progcache_bytes_kind_train_step" in lines
    # kill the manifest: the rebuild-from-scan must recover each entry's
    # kind from its meta header, not collapse everything into ""
    os.remove(os.path.join(cache_dir, progcache.MANIFEST))
    assert progcache.bytes_by_kind() == bk
