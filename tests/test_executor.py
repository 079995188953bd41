"""Executor bind/forward/backward tests (analogue of reference
test_executor.py)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import ndarray as nd
from mxnet_tpu import symbol as sym


def test_bind_forward():
    a = sym.Variable("a")
    b = sym.Variable("b")
    c = a + b
    ctx = mx.cpu()
    a_nd = nd.array(np.random.rand(3, 4).astype(np.float32))
    b_nd = nd.array(np.random.rand(3, 4).astype(np.float32))
    exe = c.bind(ctx, {"a": a_nd, "b": b_nd})
    outs = exe.forward()
    np.testing.assert_allclose(outs[0].asnumpy(), a_nd.asnumpy() + b_nd.asnumpy(), rtol=1e-6)


def test_backward_simple():
    a = sym.Variable("a")
    b = sym.Variable("b")
    c = a * b
    a_np = np.random.rand(3, 4).astype(np.float32)
    b_np = np.random.rand(3, 4).astype(np.float32)
    a_nd, b_nd = nd.array(a_np), nd.array(b_np)
    grads = {"a": nd.zeros((3, 4)), "b": nd.zeros((3, 4))}
    exe = c.bind(mx.cpu(), {"a": a_nd, "b": b_nd}, args_grad=grads)
    exe.forward(is_train=True)
    exe.backward([nd.ones((3, 4))])
    np.testing.assert_allclose(grads["a"].asnumpy(), b_np, rtol=1e-5)
    np.testing.assert_allclose(grads["b"].asnumpy(), a_np, rtol=1e-5)


def test_grad_req_add():
    a = sym.Variable("a")
    c = a * 2.0
    a_nd = nd.array(np.ones((2, 2), np.float32))
    grads = {"a": nd.zeros((2, 2))}
    exe = c.bind(mx.cpu(), {"a": a_nd}, args_grad=grads, grad_req="add")
    for _ in range(3):
        exe.forward(is_train=True)
        exe.backward([nd.ones((2, 2))])
    np.testing.assert_allclose(grads["a"].asnumpy(), np.full((2, 2), 6.0), rtol=1e-5)


def test_simple_bind():
    data = sym.Variable("data")
    fc = sym.FullyConnected(data, num_hidden=8, name="fc")
    out = sym.SoftmaxOutput(fc, name="softmax")
    exe = out.simple_bind(mx.cpu(), data=(4, 10))
    assert exe.arg_dict["fc_weight"].shape == (8, 10)
    assert exe.arg_dict["softmax_label"].shape == (4,)
    exe.arg_dict["data"][:] = 1.0
    outs = exe.forward(is_train=False)
    assert outs[0].shape == (4, 8)
    np.testing.assert_allclose(outs[0].asnumpy().sum(axis=1), np.ones(4), rtol=1e-5)


def test_softmax_output_backward():
    data = sym.Variable("data")
    out = sym.SoftmaxOutput(data, name="softmax")
    x = np.random.rand(4, 5).astype(np.float32)
    label = np.array([0, 1, 2, 3], np.float32)
    exe = out.simple_bind(mx.cpu(), data=(4, 5))
    exe.arg_dict["data"][:] = x
    exe.arg_dict["softmax_label"][:] = label
    exe.forward(is_train=True)
    exe.backward()
    p = exe.outputs[0].asnumpy()
    expected = p.copy()
    expected[np.arange(4), label.astype(int)] -= 1.0
    np.testing.assert_allclose(exe.grad_dict["data"].asnumpy(), expected, rtol=1e-4, atol=1e-5)


def test_batchnorm_aux_update():
    data = sym.Variable("data")
    bn = sym.BatchNorm(data, name="bn", momentum=0.5)
    exe = bn.simple_bind(mx.cpu(), data=(8, 3, 4, 4))
    x = np.random.randn(8, 3, 4, 4).astype(np.float32)
    exe.arg_dict["data"][:] = x
    exe.aux_dict["bn_moving_var"][:] = 1.0
    mean_before = exe.aux_dict["bn_moving_mean"].asnumpy().copy()
    exe.forward(is_train=True)
    mean_after = exe.aux_dict["bn_moving_mean"].asnumpy()
    batch_mean = x.mean(axis=(0, 2, 3))
    np.testing.assert_allclose(mean_after, 0.5 * mean_before + 0.5 * batch_mean, rtol=1e-4)
    # eval mode: uses moving stats, does not update them
    exe.forward(is_train=False)
    np.testing.assert_allclose(exe.aux_dict["bn_moving_mean"].asnumpy(), mean_after, rtol=1e-6)


def test_dropout_train_vs_eval():
    data = sym.Variable("data")
    do = sym.Dropout(data, p=0.5, name="do")
    exe = do.simple_bind(mx.cpu(), data=(100, 100), grad_req="null")
    exe.arg_dict["data"][:] = 1.0
    out_train = exe.forward(is_train=True)[0].asnumpy()
    assert (out_train == 0).mean() > 0.3  # roughly half dropped
    out_eval = exe.forward(is_train=False)[0].asnumpy()
    np.testing.assert_array_equal(out_eval, np.ones((100, 100), np.float32))


def test_executor_reshape():
    data = sym.Variable("data")
    fc = sym.FullyConnected(data, num_hidden=4, name="fc")
    exe = fc.simple_bind(mx.cpu(), data=(8, 6))
    exe2 = exe.reshape(data=(2, 6))
    assert exe2.arg_dict["data"].shape == (2, 6)
    # params shared
    assert exe2.arg_dict["fc_weight"] is exe.arg_dict["fc_weight"]


def test_monitor_callback():
    data = sym.Variable("data")
    fc = sym.FullyConnected(data, num_hidden=4, name="fc")
    exe = fc.simple_bind(mx.cpu(), data=(2, 3), grad_req="null")
    seen = []
    exe.set_monitor_callback(lambda name, arr: seen.append(name))
    exe.forward(is_train=False)
    assert any("fc" in s for s in seen)


def test_compute_dtype_bf16_mixed_precision():
    """bf16 compute / f32 master weights (executor compute_dtype — the
    TPU-native analogue of the reference's fp16 training,
    tests/python/train/test_dtype.py): outputs and grads return float32,
    values match the fp32 executor within bf16 tolerance."""
    import numpy as np
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    data = sym.Variable("data")
    net = sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, num_hidden=2, name="fc2")
    net = sym.SoftmaxOutput(net, name="softmax")

    exe32 = net.simple_bind(mx.cpu(), data=(4, 6), softmax_label=(4,))
    exe16 = net.simple_bind(mx.cpu(), compute_dtype="bfloat16",
                            data=(4, 6), softmax_label=(4,))
    np.random.seed(42)  # Xavier draws from the GLOBAL rng: pin it, or the
    #   bf16-vs-f32 margins depend on how many draws earlier tests made
    init = mx.initializer.Xavier()
    for n, a in exe32.arg_dict.items():
        if n in ("data", "softmax_label"):
            continue
        init(mx.initializer.InitDesc(n), a)
        exe16.arg_dict[n]._data = a._data
    x = rng.uniform(-1, 1, (4, 6)).astype(np.float32)
    lab = np.array([0, 1, 0, 1], np.float32)
    for exe in (exe32, exe16):
        exe.arg_dict["data"]._data = jnp.asarray(x)
        exe.arg_dict["softmax_label"]._data = jnp.asarray(lab)
    o32 = exe32.forward_backward()
    o16 = exe16.forward_backward()
    assert o16[0].asnumpy().dtype == np.float32
    np.testing.assert_allclose(o32[0].asnumpy(), o16[0].asnumpy(), atol=2e-2)
    for n in exe32.grad_dict:
        g32, g16 = exe32.grad_dict[n].asnumpy(), exe16.grad_dict[n].asnumpy()
        assert g16.dtype == np.float32, (n, g16.dtype)
        np.testing.assert_allclose(g32, g16, atol=3e-2)
    # inference path also returns f32
    assert exe16.forward(is_train=False)[0].asnumpy().dtype == np.float32


def test_make_train_step_fused():
    """Fused whole-step path (fwd+bwd+update in ONE jitted program,
    Executor.make_train_step — bulk-exec analogue of
    graph_executor.cc:681-759): params actually learn and match the
    unfused forward_backward + manual SGD reference."""
    import numpy as np
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    data = sym.Variable("data")
    net = sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, num_hidden=2, name="fc2")
    net = sym.SoftmaxOutput(net, name="softmax")

    exe = net.simple_bind(mx.cpu(), data=(8, 4), softmax_label=(8,))
    exe_ref = net.simple_bind(mx.cpu(), data=(8, 4), softmax_label=(8,))
    init = mx.initializer.Xavier()
    for n, a in exe.arg_dict.items():
        if n in ("data", "softmax_label"):
            continue
        init(mx.initializer.InitDesc(n), a)
        # copy (not alias): the fused step DONATES param buffers
        exe_ref.arg_dict[n]._data = jnp.array(a._data, copy=True)

    x = rng.uniform(-1, 1, (8, 4)).astype(np.float32)
    lab = (rng.rand(8) > 0.5).astype(np.float32)
    lr = 0.1

    def sgd(params, grads, states):
        return ({n: params[n] - lr * grads[n] for n in params}, states)

    step = exe.make_train_step(sgd)
    pn = [n for n in exe.arg_dict if n not in ("data", "softmax_label")]
    params = {n: exe.arg_dict[n]._data for n in pn}
    feed = {"data": jnp.asarray(x), "softmax_label": jnp.asarray(lab)}
    for _ in range(3):
        outs, params, _ = step(params, None, feed)

    # reference: unfused path
    exe_ref.arg_dict["data"]._data = jnp.asarray(x)
    exe_ref.arg_dict["softmax_label"]._data = jnp.asarray(lab)
    for _ in range(3):
        exe_ref.forward_backward()
        for n in pn:
            exe_ref.arg_dict[n]._data = (
                exe_ref.arg_dict[n]._data - lr * exe_ref.grad_dict[n]._data)

    for n in pn:
        np.testing.assert_allclose(np.asarray(params[n]),
                                   exe_ref.arg_dict[n].asnumpy(),
                                   rtol=2e-4, atol=2e-5)


def test_train_step_exposes_the_function_its_program_is_jitted_from():
    """``run.step`` is the pure function behind the program: jitted and
    lowered by a caller at the arguments' avals (tools/step_ops.py does so
    for a described chip) it gives the text ``run.lower`` gives."""
    import jax
    import jax.numpy as jnp

    data = sym.Variable("data")
    net = sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = sym.BatchNorm(net, name="bn")  # an aux state through the step
    net = sym.SoftmaxOutput(net, name="softmax")
    exe = net.simple_bind(mx.cpu(), data=(4, 6), softmax_label=(4,))
    inputs = ("data", "softmax_label")
    params = {n: a._data for n, a in exe.arg_dict.items() if n not in inputs}
    feed = {n: exe.arg_dict[n]._data for n in inputs}

    def sgd(params, grads, states, lr):
        return ({n: params[n] - lr * grads[n] for n in params}, states)

    run = exe.make_train_step(sgd)
    lr = jnp.float32(0.1)
    want = run.lower(params, None, feed, lr).as_text()

    def aval(a):  # shape, dtype and placement: what run.lower lowers at
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)

    aux = {n: a._data for n, a in exe.aux_dict.items()}
    got = jax.jit(run.step).lower(
        jax.tree_util.tree_map(aval, params), None, aux, exe._next_rng(),
        feed, lr).as_text()
    assert got == want
    assert "stablehlo.dot_general" in want


def test_make_train_step_chained_matches_sequential():
    """chain=k runs k optimizer sub-steps in ONE device program
    (lax.scan bulk execution): 1 call at chain=4
    must land on the same params as 4 calls at chain=1, including the
    BatchNorm aux-state threading through the scan carry."""
    import numpy as np
    import jax.numpy as jnp

    rng = np.random.RandomState(1)
    data = sym.Variable("data")
    net = sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = sym.BatchNorm(net, name="bn")    # aux state exercises the carry
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, num_hidden=2, name="fc2")
    net = sym.SoftmaxOutput(net, name="softmax")

    x = rng.uniform(-1, 1, (8, 4)).astype(np.float32)
    lab = (rng.rand(8) > 0.5).astype(np.float32)
    lr = 0.1

    def sgd(params, grads, states):
        return ({n: params[n] - lr * grads[n] for n in params}, states)

    results = {}
    for chain, calls in ((1, 4), (4, 1)):
        exe = net.simple_bind(mx.cpu(), data=(8, 4), softmax_label=(8,))
        init = mx.initializer.Xavier()
        rs = np.random.RandomState(7)
        for n, a in exe.arg_dict.items():
            if n in ("data", "softmax_label"):
                continue
            a._data = jnp.asarray(
                rs.uniform(-0.5, 0.5, a.shape).astype(np.float32))
        step = exe.make_train_step(sgd, chain=chain)
        pn = [n for n in exe.arg_dict if n not in ("data", "softmax_label")]
        params = {n: jnp.array(exe.arg_dict[n]._data, copy=True)
                  for n in pn}
        feed = {"data": jnp.asarray(x), "softmax_label": jnp.asarray(lab)}
        for _ in range(calls):
            outs, params, _ = step(params, None, feed)
        results[chain] = (params,
                          {n: a.asnumpy() for n, a in exe.aux_dict.items()})
    for n in results[1][0]:
        np.testing.assert_allclose(
            np.asarray(results[4][0][n]), np.asarray(results[1][0][n]),
            rtol=2e-4, atol=2e-5, err_msg=n)
    for n in results[1][1]:
        np.testing.assert_allclose(results[4][1][n], results[1][1][n],
                                   rtol=2e-4, atol=2e-5, err_msg="aux " + n)
