"""Module training-stack tests (reference tests/python/unittest/test_module.py
265 LoC + tests/python/train convergence suite, SURVEY §4.2/§4.5)."""
import os
import tempfile

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import ndarray as nd


def _blobs(n=600, d=10, k=3, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype(np.float32)
    W = rng.randn(d, k).astype(np.float32) * 2
    y = (X @ W).argmax(1).astype(np.float32)
    return X, y


def _mlp(k=3):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=32, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=k, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def test_fit_converges_and_scores():
    X, y = _blobs()
    it = mx.io.NDArrayIter(X, y, batch_size=32, shuffle=True,
                           label_name="softmax_label")
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.fit(it, num_epoch=6, optimizer="sgd",
            optimizer_params={"learning_rate": 0.2})
    it.reset()
    acc = dict(mod.score(it, mx.metric.Accuracy()))["accuracy"]
    assert acc > 0.9, acc


def test_module_predict_shapes():
    X, y = _blobs(n=70)
    it = mx.io.NDArrayIter(X, y, batch_size=32, label_name="softmax_label")
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.initializer.Xavier())
    out = mod.predict(it)
    assert out.shape == (70, 3)  # pad stripped from the tail batch


def test_save_load_checkpoint_with_optimizer_states():
    X, y = _blobs(n=128)
    it = mx.io.NDArrayIter(X, y, batch_size=32, label_name="softmax_label")
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer="adam",
            optimizer_params={"learning_rate": 0.01})
    with tempfile.TemporaryDirectory() as tmp:
        prefix = os.path.join(tmp, "chk")
        mod.save_checkpoint(prefix, 1, save_optimizer_states=True)
        assert os.path.exists(prefix + "-symbol.json")
        assert os.path.exists(prefix + "-0001.params")
        assert os.path.exists(prefix + "-0001.states")
        mod2 = mx.mod.Module.load(prefix, 1, load_optimizer_states=True,
                                  context=mx.cpu())
        it.reset()
        mod2.fit(it, num_epoch=1, optimizer="adam",
                 optimizer_params={"learning_rate": 0.01})


def test_module_multi_device_matches_single():
    """4-CPU-device data parallel must match single-device numerically
    (deterministic SGD, same init) — the multi-device-without-hardware
    strategy of SURVEY §4.3."""
    X, y = _blobs(n=256)
    k = 3

    def run(ctx):
        it = mx.io.NDArrayIter(X, y, batch_size=64, shuffle=False,
                               label_name="softmax_label")
        mod = mx.mod.Module(_mlp(k), context=ctx)
        mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
        mod.init_params(mx.initializer.Constant(0.05))
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1})
        for _ in range(2):
            it.reset()
            for batch in it:
                mod.forward_backward(batch)
                mod.update()
        return {k_: v.asnumpy() for k_, v in mod.get_params()[0].items()}

    single = run(mx.cpu())
    multi = run([mx.cpu(i) for i in range(4)])
    for name in single:
        np.testing.assert_allclose(single[name], multi[name],
                                   rtol=1e-4, atol=1e-5)


def test_bucketing_module():
    """Variable-length training via sym_gen per bucket (reference
    module/bucketing_module.py + lstm_bucketing example)."""
    vocab, k = 20, 5

    def sym_gen(seq_len):
        data = mx.sym.Variable("data")
        emb = mx.sym.Embedding(data, input_dim=vocab, output_dim=8,
                               name="emb")
        flat = mx.sym.Flatten(emb)
        fc = mx.sym.FullyConnected(flat, num_hidden=k, name="fc")
        sm = mx.sym.SoftmaxOutput(fc, name="softmax")
        return sm, ("data",), ("softmax_label",)

    mod = mx.mod.BucketingModule(sym_gen, default_bucket_key=8)
    rng = np.random.RandomState(0)
    mod.bind(data_shapes=[("data", (4, 8))],
             label_shapes=[("softmax_label", (4,))])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd")
    for seq_len in [8, 4, 8, 6]:
        data = rng.randint(0, vocab, (4, seq_len)).astype(np.float32)
        label = rng.randint(0, k, (4,)).astype(np.float32)
        batch = mx.io.DataBatch([nd.array(data)], [nd.array(label)],
                                bucket_key=seq_len,
                                provide_data=[("data", (4, seq_len))],
                                provide_label=[("softmax_label", (4,))])
        mod.forward_backward(batch)
        mod.update()
    assert len(mod._buckets) >= 3  # per-bucket executors created


def test_sequential_module():
    X, y = _blobs(n=64)
    net1 = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=16,
                                 name="fc1")
    net1 = mx.sym.Activation(net1, act_type="relu")
    net2 = mx.sym.FullyConnected(mx.sym.Variable("fc1_relu_output"),
                                 num_hidden=3, name="fc2")
    net2 = mx.sym.SoftmaxOutput(net2, name="softmax")
    seq = mx.mod.SequentialModule()
    seq.add(mx.mod.Module(net1, data_names=["data"], label_names=[]))
    seq.add(mx.mod.Module(net2, data_names=["fc1_relu_output"],
                          label_names=["softmax_label"]),
            take_labels=True, auto_wiring=True)
    it = mx.io.NDArrayIter(X, y, batch_size=16, label_name="softmax_label")
    seq.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    seq.init_params(mx.initializer.Xavier())
    seq.init_optimizer(optimizer="sgd")
    batch = next(iter(it))
    seq.forward(batch)
    out = seq.get_outputs()[0]
    assert out.shape == (16, 3)


def test_fused_fit_step_matches_unfused():
    """Module.fit with the fused one-program step must produce the same
    trained parameters as the unfused forward_backward+update path
    (MXNET_FUSED_FIT=0)."""
    import os
    import numpy as np
    import mxnet_tpu as mx

    rng = np.random.RandomState(3)
    X = rng.uniform(-1, 1, (64, 10)).astype(np.float32)
    w = rng.uniform(-1, 1, (10,)).astype(np.float32)
    y = (X @ w > 0).astype(np.float32)

    def build_and_fit():
        it = mx.io.NDArrayIter(X, y, batch_size=16, shuffle=False,
                               label_name="softmax_label")
        data = mx.sym.Variable("data")
        net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
        net = mx.sym.Activation(net, act_type="relu")
        net = mx.sym.FullyConnected(net, num_hidden=2, name="fc2")
        net = mx.sym.SoftmaxOutput(net, name="softmax")
        mod = mx.mod.Module(net, context=mx.cpu())
        mod.fit(it, num_epoch=3, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                                  "wd": 1e-4},
                initializer=mx.initializer.Xavier(rnd_type="uniform",
                                                  factor_type="avg",
                                                  magnitude=2.0))
        args, _ = mod.get_params()
        return {k: v.asnumpy() for k, v in args.items()}

    mx.random.seed(11)
    fused = build_and_fit()
    os.environ["MXNET_FUSED_FIT"] = "0"
    try:
        mx.random.seed(11)
        unfused = build_and_fit()
    finally:
        del os.environ["MXNET_FUSED_FIT"]
    assert set(fused) == set(unfused)
    for k in fused:
        np.testing.assert_allclose(fused[k], unfused[k], rtol=2e-4,
                                   atol=2e-5, err_msg=k)


def test_fused_fit_step_matches_unfused_adam():
    """Same fused-vs-unfused agreement under ADAM, whose effective lr
    changes EVERY step (bias correction folded host-side): guards the
    fused path's constant-lr fast cache against wrongly freezing a
    count-dependent effective_lr_wd."""
    import os
    import numpy as np
    import mxnet_tpu as mx

    rng = np.random.RandomState(5)
    X = rng.uniform(-1, 1, (64, 10)).astype(np.float32)
    w = rng.uniform(-1, 1, (10,)).astype(np.float32)
    y = (X @ w > 0).astype(np.float32)

    def build_and_fit():
        it = mx.io.NDArrayIter(X, y, batch_size=16, shuffle=False,
                               label_name="softmax_label")
        data = mx.sym.Variable("data")
        net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
        net = mx.sym.Activation(net, act_type="relu")
        net = mx.sym.FullyConnected(net, num_hidden=2, name="fc2")
        net = mx.sym.SoftmaxOutput(net, name="softmax")
        mod = mx.mod.Module(net, context=mx.cpu())
        mod.fit(it, num_epoch=3, optimizer="adam",
                optimizer_params={"learning_rate": 0.01},
                initializer=mx.initializer.Xavier(rnd_type="uniform",
                                                  factor_type="avg",
                                                  magnitude=2.0))
        args, _ = mod.get_params()
        return {k: v.asnumpy() for k, v in args.items()}

    mx.random.seed(13)
    fused = build_and_fit()
    os.environ["MXNET_FUSED_FIT"] = "0"
    try:
        mx.random.seed(13)
        unfused = build_and_fit()
    finally:
        del os.environ["MXNET_FUSED_FIT"]
    for k in fused:
        np.testing.assert_allclose(fused[k], unfused[k], rtol=2e-4,
                                   atol=2e-5, err_msg=k)


def test_fused_fit_lockstep_counts_materialize():
    """The fused path's deferred (lockstep) update counts must
    materialize into optimizer._index_update_count on any fused-state
    exit — resume/save/scheduler installs read them."""
    import numpy as np
    import mxnet_tpu as mx

    rng = np.random.RandomState(0)
    X = rng.uniform(-1, 1, (32, 6)).astype(np.float32)
    y = rng.randint(0, 2, (32,)).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=16, label_name="softmax_label")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2),
        name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    it.reset()
    batch = next(iter(it))
    for _ in range(5):
        mod.fit_step(batch)
    opt = mod._optimizer
    assert opt.num_update == 5
    mod._sync_fused_to_exec()  # any exit path (get_params/save/score)
    counts = set(opt._index_update_count.values())
    assert counts == {5}, counts
    # a later unfused-style step keeps counting from there
    mod.fit_step(batch)
    mod._sync_fused_to_exec()
    assert opt.num_update == 6
    assert set(opt._index_update_count.values()) == {6}

    # set_lr_mult must NOT tear down the fused state (it only changes the
    # lw fingerprint — a hyper-key invalidation would recompile seconds)
    fs_before = mod._fused_fit
    mod.fit_step(batch)
    opt.set_lr_mult({"fullyconnected0_weight": 0.5})
    mod.fit_step(batch)
    assert mod._fused_fit is fs_before, "set_lr_mult rebuilt the fused step"

    # force_rebind flushes deferred counts before discarding the state
    mod._sync_fused_to_exec()
    n_before = opt.num_update
    mod.fit_step(batch)  # one pending lockstep count
    mod.bind(data_shapes=[("data", (16, 6))],
             label_shapes=[("softmax_label", (16,))], force_rebind=True)
    assert set(opt._index_update_count.values()) == {n_before + 1}


@pytest.mark.parametrize("which, value", [("lr_mult", 0.0),
                                          ("wd_mult", 30.0)])
def test_fused_fit_sees_a_mult_entry_changed_in_place(which, value,
                                                      monkeypatch):
    """An existing ``lr_mult`` / ``wd_mult`` entry assigned in place, not
    through the setter, reaches the fused step's cached lr/wd arrays: the
    run matches the unfused path given the same assignment."""
    rng = np.random.RandomState(2)
    batch = mx.io.DataBatch(
        data=[mx.nd.array(rng.uniform(-1, 1, (16, 6)).astype(np.float32))],
        label=[mx.nd.array(rng.randint(0, 2, (16,)).astype(np.float32))])
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2,
                              name="fc"), name="softmax")

    def run(fused):
        monkeypatch.setenv("MXNET_FUSED_FIT", "1" if fused else "0")
        mx.random.seed(3)
        mod = mx.mod.Module(net, context=mx.cpu())
        mod.bind(data_shapes=[("data", (16, 6))],
                 label_shapes=[("softmax_label", (16,))])
        mod.init_params(mx.initializer.Xavier())
        mod.init_optimizer(optimizer="sgd", optimizer_params={
            "learning_rate": 0.1, "wd": 0.01})
        opt = mod._optimizer
        getattr(opt, "set_" + which)({"fc_weight": 1.0})
        for _ in range(2):
            mod.fit_step(batch)
        getattr(opt, which)["fc_weight"] = value
        for _ in range(2):
            mod.fit_step(batch)
        assert mod.fit_step_path == ("fused" if fused else "unfused")
        return {k: v.asnumpy() for k, v in mod.get_params()[0].items()}

    got, want = run(True), run(False)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=2e-4, atol=2e-5,
                                   err_msg=k)


def test_fused_fit_then_score_and_checkpoint(tmp_path):
    """After fused fit, score() and save_checkpoint must see the trained
    (threaded/donated) parameters."""
    import numpy as np
    import mxnet_tpu as mx

    rng = np.random.RandomState(5)
    X = rng.uniform(-1, 1, (128, 12)).astype(np.float32)
    w = rng.uniform(-1, 1, (12,)).astype(np.float32)
    y = (X @ w > 0).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=32, shuffle=False,
                           label_name="softmax_label")
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=2, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=6, optimizer="sgd",
            optimizer_params={"learning_rate": 0.5},
            initializer=mx.initializer.Xavier())
    acc = dict(mod.score(it, mx.metric.create("acc")))["accuracy"]
    assert acc > 0.9, acc
    prefix = str(tmp_path / "fusedck")
    mod.save_checkpoint(prefix, 1, save_optimizer_states=True)
    mod2 = mx.mod.Module.load(prefix, 1)
    mod2.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
              for_training=False)
    acc2 = dict(mod2.score(it, mx.metric.create("acc")))["accuracy"]
    np.testing.assert_allclose(acc2, acc, atol=1e-6)


def test_set_params_after_fused_fit_takes_effect():
    """set_params after fused training must win over the threaded fused
    buffers (and not be clobbered by a later sync)."""
    import numpy as np
    import mxnet_tpu as mx

    rng = np.random.RandomState(9)
    X = rng.uniform(-1, 1, (32, 6)).astype(np.float32)
    y = (rng.rand(32) > 0.5).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=16, label_name="softmax_label")
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=2, name="fc1")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=2, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            initializer=mx.initializer.Xavier())
    frozen = {"fc1_weight": mx.nd.array(np.zeros((2, 6), np.float32)),
              "fc1_bias": mx.nd.array(np.zeros((2,), np.float32))}
    mod.set_params(frozen, {})
    args, _ = mod.get_params()
    np.testing.assert_array_equal(args["fc1_weight"].asnumpy(),
                                  np.zeros((2, 6), np.float32))
    # user-held arrays survive further training (no donation of aliases)
    it.reset()
    batch = next(iter(it))
    mod.fit_step(batch)
    _ = frozen["fc1_weight"].asnumpy()  # must not raise Array deleted
    args, _ = mod.get_params()
    assert np.abs(args["fc1_weight"].asnumpy()).max() > 0  # stepped from 0


def test_reinit_optimizer_after_fused_fit():
    """init_optimizer(force_init=True) mid-training must preserve the fused
    (donated/threaded) parameter values."""
    import numpy as np
    import mxnet_tpu as mx

    rng = np.random.RandomState(10)
    X = rng.uniform(-1, 1, (32, 6)).astype(np.float32)
    y = (rng.rand(32) > 0.5).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=16, label_name="softmax_label")
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=2, name="fc1")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(kvstore=None, optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    batch = next(iter(it))
    mod.fit_step(batch)
    w_after = mod.get_params()[0]["fc1_weight"].asnumpy().copy()
    mod.init_optimizer(kvstore=None, optimizer="adam", force_init=True)
    np.testing.assert_array_equal(
        mod.get_params()[0]["fc1_weight"].asnumpy(), w_after)
    mod.fit_step(batch)  # must not raise Array deleted
    assert np.abs(mod.get_params()[0]["fc1_weight"].asnumpy()
                  - w_after).max() > 0


def test_fused_and_manual_paths_interleave():
    """fit_step -> manual forward_backward/update -> fit_step must agree
    with the all-manual sequence (no stale fused snapshot), and the
    compiled fused step must survive set_params (no per-epoch rebuild)."""
    import numpy as np
    import mxnet_tpu as mx

    rng = np.random.RandomState(12)
    X = rng.uniform(-1, 1, (16, 5)).astype(np.float32)
    y = (rng.rand(16) > 0.5).astype(np.float32)

    def build():
        mx.random.seed(21)
        it = mx.io.NDArrayIter(X, y, batch_size=16, label_name="softmax_label")
        data = mx.sym.Variable("data")
        net = mx.sym.FullyConnected(data, num_hidden=3, name="fc1")
        net = mx.sym.SoftmaxOutput(net, name="softmax")
        mod = mx.mod.Module(net, context=mx.cpu())
        mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
        mod.init_params(mx.initializer.Xavier())
        mod.init_optimizer(kvstore=None, optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1,
                                             "momentum": 0.9})
        return mod, next(iter(it))

    mod_a, batch = build()
    mod_a.fit_step(batch)
    mod_a.forward_backward(batch)
    mod_a.update()
    mod_a.fit_step(batch)
    w_mixed = mod_a.get_params()[0]["fc1_weight"].asnumpy()

    import os
    os.environ["MXNET_FUSED_FIT"] = "0"
    try:
        mod_b, batch_b = build()
        for _ in range(3):
            mod_b.forward_backward(batch_b)
            mod_b.update()
        w_manual = mod_b.get_params()[0]["fc1_weight"].asnumpy()
    finally:
        del os.environ["MXNET_FUSED_FIT"]
    np.testing.assert_allclose(w_mixed, w_manual, rtol=2e-4, atol=2e-6)

    # compiled fused state survives a set_params (epoch boundary)
    fs_before = mod_a._fused_fit
    args, auxs = mod_a.get_params()
    mod_a.set_params(args, auxs)
    mod_a.fit_step(batch)
    assert mod_a._fused_fit is fs_before


def test_fit_step_honors_hyperparam_mutation():
    """Module.fit's fused path bakes optimizer hyperparams into its compiled
    step; mutating one mid-training (momentum warmup) must rebuild the step
    so training matches the unfused path exactly. Covers both a value change
    (0.5 -> 0.9) and the state-structure transition (0.0 -> 0.9: the None
    momentum state must be re-materialized as a real buffer)."""
    import numpy as np
    import mxnet_tpu as mx

    def make_mod(momentum):
        data = mx.sym.Variable("data")
        fc = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
        out = mx.sym.LinearRegressionOutput(fc, mx.sym.Variable("label"),
                                            name="lro")
        mod = mx.mod.Module(out, data_names=("data",), label_names=("label",),
                            context=[mx.cpu()])
        mod.bind(data_shapes=[("data", (8, 6))],
                 label_shapes=[("label", (8, 4))])
        mx.random.seed(42)  # identical init across the two modules
        mod.init_params(mx.initializer.Uniform(0.1))
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.05,
                                             "momentum": momentum})
        return mod

    for mom0 in (0.5, 0.0):
        rng = np.random.RandomState(11)
        batches = [mx.io.DataBatch(
            data=[mx.nd.array(rng.randn(8, 6).astype(np.float32))],
            label=[mx.nd.array(rng.randn(8, 4).astype(np.float32))])
            for _ in range(4)]

        mod_fused = make_mod(mom0)
        # reference run: unfused path (forward_backward + update), never
        # touches fit_step, so no env gating is needed
        mod_unfused = make_mod(mom0)
        for step, batch in enumerate(batches):
            if step == 2:
                mod_fused._optimizer.momentum = 0.9
                mod_unfused._optimizer.momentum = 0.9
            mod_fused.fit_step(batch)
            # the fused path must actually be active, or this test proves
            # nothing about the compiled-step rebuild
            assert isinstance(mod_fused._fused_fit, dict), mod_fused._fused_fit
            mod_unfused.forward_backward(batch)
            mod_unfused.update()
        pf, _ = mod_fused.get_params()
        pu, _ = mod_unfused.get_params()
        for n in pf:
            np.testing.assert_allclose(
                pf[n].asnumpy(), pu[n].asnumpy(), rtol=2e-5, atol=1e-6,
                err_msg="mom0=%s %s" % (mom0, n))
