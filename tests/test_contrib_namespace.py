"""Tests for the contrib/ and utility-module parity surface:
registry.py, log.py, libinfo.py, contrib.autograd, contrib.ndarray/symbol,
contrib.tensorboard, notebook.callback (reference python/mxnet/{registry,
log,libinfo}.py, contrib/, notebook/)."""
import logging

import numpy as np
import pytest

import mxnet_tpu as mx


def test_generic_registry_register_create():
    class Base:
        def __init__(self, x=1):
            self.x = x

    register = mx.registry.get_register_func(Base, "thing")
    alias = mx.registry.get_alias_func(Base, "thing")
    create = mx.registry.get_create_func(Base, "thing")

    @register
    class Foo(Base):
        pass

    @alias("bar", "baz")
    class Bar(Base):
        pass

    assert isinstance(create("foo"), Foo)
    assert isinstance(create("bar", x=3), Bar)
    assert create("baz").x == 1
    assert isinstance(create('{"thing": "foo", "x": 7}'), Foo)
    assert create('{"thing": "foo", "x": 7}').x == 7
    assert create('["foo", {"x": 5}]').x == 5
    inst = Foo()
    assert create(inst) is inst
    with pytest.raises(mx.MXNetError):
        create("nope")


def test_registry_reregister_overrides():
    class Base2:
        pass

    register = mx.registry.get_register_func(Base2, "thing2")
    create = mx.registry.get_create_func(Base2, "thing2")

    @register
    class A(Base2):
        pass

    class B(Base2):
        pass

    register(B, "a")
    assert isinstance(create("a"), B)


def test_log_get_logger(tmp_path):
    logf = tmp_path / "out.log"
    logger = mx.log.get_logger("mxtpu_test_logger", filename=str(logf),
                               level=logging.INFO)
    logger.info("hello %d", 42)
    for h in logger.handlers:
        h.flush()
    assert "hello 42" in logf.read_text()
    # second call must not duplicate handlers
    again = mx.log.get_logger("mxtpu_test_logger")
    assert again is logger and len(logger.handlers) == 1


def test_libinfo_find_lib_path():
    from mxnet_tpu import native

    native.build()  # the .so files are build outputs, not tracked files
    paths = mx.libinfo.find_lib_path()
    assert paths and all(p.endswith(".so") for p in paths)
    assert mx.libinfo.__version__


def test_contrib_autograd_grad_and_loss():
    x = mx.nd.array(np.array([1.0, 2.0, 3.0], np.float32))

    def f(a):
        return mx.nd.sum(a * a)

    grads, loss = mx.contrib.autograd.grad_and_loss(f)(x)
    np.testing.assert_allclose(grads[0].asnumpy(), 2 * x.asnumpy(), rtol=1e-5)
    np.testing.assert_allclose(loss.asnumpy(), 14.0, rtol=1e-5)
    g_only = mx.contrib.autograd.grad(f)(x)
    np.testing.assert_allclose(g_only[0].asnumpy(), 2 * x.asnumpy(), rtol=1e-5)


def test_contrib_autograd_sections():
    assert not mx.autograd.is_training()
    with mx.contrib.autograd.train_section():
        assert mx.autograd.is_training()
        with mx.contrib.autograd.test_section():
            assert not mx.autograd.is_training()
        assert mx.autograd.is_training()
    assert not mx.autograd.is_training()


def test_contrib_op_namespaces():
    assert hasattr(mx.contrib.nd, "MultiBoxPrior")
    assert hasattr(mx.contrib.nd, "CTCLoss")
    assert hasattr(mx.contrib.sym, "fft")
    # smoke: fft through the contrib namespace
    x = mx.nd.array(np.random.RandomState(0).rand(2, 8).astype(np.float32))
    out = mx.contrib.nd.fft(x)
    assert out.shape == (2, 16)


def test_tensorboard_callback_records():
    from collections import namedtuple
    cb = mx.contrib.tensorboard.LogMetricsCallback(None)
    metric = mx.metric.create("acc")
    metric.update([mx.nd.array(np.array([0, 1], np.float32))],
                  [mx.nd.array(np.array([[0.9, 0.1], [0.2, 0.8]],
                                        np.float32))])
    Param = namedtuple("Param", ["eval_metric"])
    cb(Param(eval_metric=metric))
    assert cb.history and cb.history[0][0] == "accuracy"


def test_notebook_pandas_logger():
    from collections import namedtuple
    pl = mx.notebook.callback.PandasLogger(batch_size=4, frequent=1)
    metric = mx.metric.create("acc")
    metric.update([mx.nd.array(np.array([0, 1], np.float32))],
                  [mx.nd.array(np.array([[0.9, 0.1], [0.2, 0.8]],
                                        np.float32))])
    Param = namedtuple("Param", ["eval_metric", "epoch", "nbatch"])
    pl.train_cb(Param(eval_metric=metric, epoch=0, nbatch=1))
    pl.eval_cb(Param(eval_metric=metric, epoch=0, nbatch=1))
    pl.epoch_cb(epoch=0)
    dfs = pl.all_dataframes
    assert len(dfs["train"]) == 1 and len(dfs["eval"]) == 1
