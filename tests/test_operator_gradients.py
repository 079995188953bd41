"""Registry-wide gradient verification.

Auto-enumerates ``OP_REGISTRY``: every differentiable operator (and every
Convolution/Pooling/Deconvolution *variant*: stride, pad, dilate, group,
convention) gets a central-difference numeric-gradient check at a small
random shape; non-differentiable ops get a forward execution check; ops
with *custom* backward semantics (the reference's loss-layer family, which
ignores head gradients by design — softmax_output-inl.h) get closed-form
backward checks. A completeness test fails on any registry op not covered
by one of the categories, so adding an op without deciding its gradient
story breaks the suite.

Reference model: tests/python/unittest/test_operator.py (3,180 LoC) +
python/mxnet/test_utils.py:360 check_numeric_gradient.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import ndarray as nd
from mxnet_tpu import symbol as sym
from mxnet_tpu.ops import OP_REGISTRY
from mxnet_tpu.test_utils import check_numeric_gradient, _bind

R = np.random.RandomState(7)


def _u(shape, lo=-1.0, hi=1.0):
    return R.uniform(lo, hi, shape).astype(np.float32)


def _distinct(shape, lo=-1.0, hi=1.0):
    """Values with pairwise-distinct magnitudes (safe for max/min/sort)."""
    n = int(np.prod(shape))
    v = np.linspace(lo, hi, n, dtype=np.float32)
    R.shuffle(v)
    return v.reshape(shape)


V = sym.Variable

# ---------------------------------------------------------------------------
# GRAD cases: (case_id, builder) -> builder returns (symbol, location, opts)
# opts: grad_nodes / aux_states / numeric_eps / rtol / atol overrides.
# Registry coverage is derived from the case_id prefix before the first ":".
# ---------------------------------------------------------------------------

# smooth unary ops: (registry name, lo, hi)
_UNARY_DOMAINS = [
    ("abs", 0.3, 2), ("arccos", -0.8, 0.8), ("arccosh", 1.2, 3),
    ("arcsin", -0.8, 0.8), ("arcsinh", -2, 2), ("arctan", -2, 2),
    ("arctanh", -0.8, 0.8), ("cbrt", 0.3, 3), ("cos", -3, 3),
    ("cosh", -2, 2), ("degrees", -3, 3), ("erf", -2, 2),
    ("erfinv", -0.7, 0.7), ("exp", -2, 2), ("expm1", -2, 2),
    ("gamma", 1.2, 3), ("gammaln", 1.2, 3), ("log", 0.3, 3),
    ("log10", 0.3, 3), ("log1p", -0.5, 2), ("log2", 0.3, 3),
    ("negative", -2, 2), ("radians", -90, 90), ("rcbrt", 0.3, 3),
    ("reciprocal", 0.4, 3), ("relu", 0.2, 2), ("rsqrt", 0.3, 3),
    ("sigmoid", -3, 3), ("sin", -3, 3), ("sinh", -2, 2),
    ("smooth_l1", 0.2, 2), ("softsign", -2, 2), ("sqrt", 0.3, 3),
    ("square", -2, 2), ("tan", -0.6, 0.6), ("tanh", -2, 2),
    ("_copy", -2, 2),
]

# binary elemwise / broadcast ops on positive, tie-free inputs
_BINARY = ["_plus", "_minus", "_mul", "_div", "_power", "_maximum",
           "_minimum", "_hypot", "elemwise_add", "elemwise_sub",
           "elemwise_mul", "elemwise_div"]
_BROADCAST = ["broadcast_add", "broadcast_minus", "broadcast_mul",
              "broadcast_div", "broadcast_power", "broadcast_maximum",
              "broadcast_minimum", "broadcast_hypot"]
_SCALAR = ["_plus_scalar", "_minus_scalar", "_rminus_scalar", "_mul_scalar",
           "_div_scalar", "_rdiv_scalar", "_power_scalar", "_rpower_scalar",
           "_maximum_scalar", "_minimum_scalar", "_hypot_scalar"]
_REDUCE = ["sum", "mean", "max", "min", "prod", "nansum", "nanprod", "norm"]

GRAD_CASES = []


def _case(cid, build):
    GRAD_CASES.append((cid, build))


for _name, _lo, _hi in _UNARY_DOMAINS:
    _case("%s:unary" % _name,
          lambda n=_name, lo=_lo, hi=_hi: (
              getattr(sym, n)(V("data")), {"data": _u((2, 3), lo, hi)}, {}))

for _name in _BINARY:
    _case("%s:binary" % _name,
          lambda n=_name: (getattr(sym, n)(V("a"), V("b")),
                           {"a": _u((2, 3), 0.5, 2), "b": _distinct((2, 3), 0.6, 2.2)}, {}))
for _name in _BROADCAST:
    _case("%s:broadcast" % _name,
          lambda n=_name: (getattr(sym, n)(V("a"), V("b")),
                           {"a": _u((2, 1, 3), 0.5, 2), "b": _distinct((1, 4, 3), 0.6, 2.2)}, {}))
for _name in _SCALAR:
    _case("%s:scalar" % _name,
          lambda n=_name: (getattr(sym, n)(V("data"), scalar=1.7),
                           {"data": _u((2, 3), 0.5, 2)}, {}))
for _name in _REDUCE:
    _case("%s:axis1" % _name,
          lambda n=_name: (getattr(sym, n)(V("data"), axis=1),
                           {"data": _distinct((2, 4), 0.5, 2)}, {}))
_case("norm:all", lambda: (sym.norm(V("data")), {"data": _u((2, 3), 0.5, 2)}, {}))

# dot / batch_dot with every transpose variant
for _ta in (False, True):
    for _tb in (False, True):
        _case("dot:t%d%d" % (_ta, _tb),
              lambda ta=_ta, tb=_tb: (
                  sym.dot(V("a"), V("b"), transpose_a=ta, transpose_b=tb),
                  {"a": _u((3, 2) if ta else (2, 3)),
                   "b": _u((4, 3) if tb else (3, 4))}, {}))
        _case("batch_dot:t%d%d" % (_ta, _tb),
              lambda ta=_ta, tb=_tb: (
                  sym.batch_dot(V("a"), V("b"), transpose_a=ta, transpose_b=tb),
                  {"a": _u((2, 3, 2) if ta else (2, 2, 3)),
                   "b": _u((2, 4, 3) if tb else (2, 3, 4))}, {}))

# shape manipulation
_case("transpose:axes", lambda: (sym.transpose(V("data"), axes=(1, 0, 2)),
                                 {"data": _u((2, 3, 2))}, {}))
_case("Reshape:", lambda: (sym.Reshape(V("data"), shape=(3, 4)),
                           {"data": _u((2, 6))}, {}))
_case("Flatten:", lambda: (sym.Flatten(V("data")), {"data": _u((2, 3, 2))}, {}))
_case("expand_dims:", lambda: (sym.expand_dims(V("data"), axis=1),
                               {"data": _u((2, 3))}, {}))
_case("repeat:", lambda: (sym.repeat(V("data"), repeats=2, axis=1),
                          {"data": _u((2, 3))}, {}))
_case("tile:", lambda: (sym.tile(V("data"), reps=(2, 2)),
                        {"data": _u((2, 3))}, {}))
_case("flip:", lambda: (sym.flip(V("data"), axis=1), {"data": _u((2, 3))}, {}))
_case("slice_axis:", lambda: (sym.slice_axis(V("data"), axis=1, begin=1, end=3),
                              {"data": _u((2, 4))}, {}))
_case("crop:slice", lambda: (sym.crop(V("data"), begin=(0, 1), end=(2, 3)),
                             {"data": _u((2, 4))}, {}))
_case("clip:", lambda: (sym.clip(V("data"), a_min=-0.5, a_max=0.5),
                        {"data": _distinct((2, 4), -1, 1)}, {}))
_case("Pad:const", lambda: (sym.Pad(V("data"), mode="constant",
                                    pad_width=(0, 0, 0, 0, 1, 1, 1, 1)),
                            {"data": _u((1, 1, 3, 3))}, {}))
_case("Pad:edge", lambda: (sym.Pad(V("data"), mode="edge",
                                   pad_width=(0, 0, 0, 0, 1, 1, 1, 1)),
                           {"data": _u((1, 1, 3, 3))}, {}))
_case("SwapAxis:", lambda: (sym.SwapAxis(V("data"), dim1=0, dim2=1),
                            {"data": _u((2, 3))}, {}))
_case("broadcast_to:", lambda: (sym.broadcast_to(V("data"), shape=(2, 3)),
                                {"data": _u((1, 3))}, {}))
_case("broadcast_axes:", lambda: (sym.broadcast_axes(V("data"), axis=0, size=3),
                                  {"data": _u((1, 2))}, {}))
_case("where:", lambda: (sym.where(V("condition"), V("x"), V("y")),
                         {"condition": np.array([[1, 0], [0, 1]], np.float32),
                          "x": _u((2, 2)), "y": _u((2, 2))},
                         {"grad_nodes": ["x", "y"]}))
_case("Concat:", lambda: (sym.Concat(V("a"), V("b"), dim=1, num_args=2),
                          {"a": _u((2, 2)), "b": _u((2, 3))}, {}))
_case("ElementWiseSum:", lambda: (sym.ElementWiseSum(V("a"), V("b"), V("c"), num_args=3),
                                  {"a": _u((2, 2)), "b": _u((2, 2)), "c": _u((2, 2))}, {}))
_case("SliceChannel:", lambda: (sym.SliceChannel(V("data"), num_outputs=2, axis=1)[0] +
                                sym.SliceChannel(V("data"), num_outputs=2, axis=1)[1] * 2,
                                {"data": _u((2, 4))}, {}))
_case("take:", lambda: (sym.take(V("a"), V("indices")),
                        {"a": _u((4, 3)),
                         "indices": np.array([0, 2, 1], np.float32)},
                        {"grad_nodes": ["a"]}))
_case("batch_take:", lambda: (sym.batch_take(V("a"), V("indices")),
                              {"a": _u((3, 4)),
                               "indices": np.array([1, 0, 3], np.float32)},
                              {"grad_nodes": ["a"]}))
_case("pick:", lambda: (sym.pick(V("data"), V("index"), axis=1),
                        {"data": _u((3, 4)),
                         "index": np.array([0, 3, 1], np.float32)},
                        {"grad_nodes": ["data"]}))
_case("Embedding:", lambda: (sym.Embedding(V("data"), V("weight"), input_dim=5,
                                           output_dim=3),
                             {"data": np.array([[0, 2], [4, 1]], np.float32),
                              "weight": _u((5, 3))},
                             {"grad_nodes": ["weight"]}))

# layer ops — FullyConnected variants
_case("FullyConnected:", lambda: (
    sym.FullyConnected(V("data"), num_hidden=3, name="fc"),
    {"data": _u((2, 4)), "fc_weight": _u((3, 4)), "fc_bias": _u((3,))}, {}))
_case("FullyConnected:no_bias_noflatten", lambda: (
    sym.FullyConnected(V("data"), num_hidden=3, no_bias=True, flatten=False, name="fc"),
    {"data": _u((2, 2, 4)), "fc_weight": _u((3, 4))}, {}))

# Convolution variants: stride / pad / dilate / group / 1x1 / 1D / 3D
_CONV_VARIANTS = [
    ("k3", dict(kernel=(3, 3), num_filter=2), (1, 2, 5, 5)),
    ("k3s2p1", dict(kernel=(3, 3), num_filter=2, stride=(2, 2), pad=(1, 1)), (1, 2, 5, 5)),
    ("k3d2", dict(kernel=(3, 3), num_filter=2, dilate=(2, 2), pad=(2, 2)), (1, 2, 6, 6)),
    ("k3g2", dict(kernel=(3, 3), num_filter=4, num_group=2, pad=(1, 1)), (1, 4, 4, 4)),
    ("k1", dict(kernel=(1, 1), num_filter=3), (1, 2, 4, 4)),
    ("k1s2", dict(kernel=(1, 1), num_filter=3, stride=(2, 2)), (1, 2, 4, 4)),
    ("nobias", dict(kernel=(3, 3), num_filter=2, no_bias=True), (1, 2, 4, 4)),
    ("1d", dict(kernel=(3,), num_filter=2, pad=(1,)), (1, 2, 6)),
    ("3d", dict(kernel=(2, 2, 2), num_filter=2), (1, 1, 3, 3, 3)),
]
for _vid, _kw, _shape in _CONV_VARIANTS:
    def _build_conv(kw=_kw, shape=_shape):
        s = sym.Convolution(V("data"), name="c", **kw)
        arg_shapes, _, _ = s.infer_shape(data=shape)
        loc = {n: _u(sh, -0.7, 0.7) for n, sh in zip(s.list_arguments(), arg_shapes)}
        return s, loc, {"numeric_eps": 1e-2, "rtol": 0.12, "atol": 3e-2}
    _case("Convolution:%s" % _vid, _build_conv)

# Deconvolution variants
_DECONV_VARIANTS = [
    ("k3", dict(kernel=(3, 3), num_filter=2), (1, 2, 4, 4)),
    ("k4s2p1", dict(kernel=(4, 4), num_filter=2, stride=(2, 2), pad=(1, 1)), (1, 2, 4, 4)),
    ("k3s2adj1", dict(kernel=(3, 3), num_filter=2, stride=(2, 2), adj=(1, 1)), (1, 2, 3, 3)),
]
for _vid, _kw, _shape in _DECONV_VARIANTS:
    def _build_deconv(kw=_kw, shape=_shape):
        s = sym.Deconvolution(V("data"), name="dc", **kw)
        arg_shapes, _, _ = s.infer_shape(data=shape)
        loc = {n: _u(sh, -0.7, 0.7) for n, sh in zip(s.list_arguments(), arg_shapes)}
        return s, loc, {"numeric_eps": 1e-2, "rtol": 0.12, "atol": 3e-2}
    _case("Deconvolution:%s" % _vid, _build_deconv)

# Pooling variants: type x stride/pad x convention x global
_POOL_VARIANTS = [
    ("max", dict(kernel=(2, 2), pool_type="max", stride=(2, 2))),
    ("avg", dict(kernel=(2, 2), pool_type="avg", stride=(2, 2))),
    ("sum", dict(kernel=(2, 2), pool_type="sum", stride=(2, 2))),
    ("maxs1p1", dict(kernel=(3, 3), pool_type="max", stride=(1, 1), pad=(1, 1))),
    ("avgfull", dict(kernel=(3, 3), pool_type="avg", stride=(2, 2),
                     pooling_convention="full")),
    ("maxglobal", dict(kernel=(2, 2), pool_type="max", global_pool=True)),
    ("avgglobal", dict(kernel=(2, 2), pool_type="avg", global_pool=True)),
]
for _vid, _kw in _POOL_VARIANTS:
    _case("Pooling:%s" % _vid,
          lambda kw=_kw: (sym.Pooling(V("data"), **kw),
                          {"data": _distinct((1, 2, 4, 4), -1, 1)},
                          {"numeric_eps": 1e-2, "rtol": 0.1, "atol": 2e-2}))

# normalization layers
def _build_bn(**kw):
    def b():
        s = sym.BatchNorm(V("data"), name="bn", **kw)
        loc = {"data": _u((3, 2, 3, 3), -1, 1),
               "bn_gamma": _u((2,), 0.5, 1.5), "bn_beta": _u((2,))}
        aux = {"bn_moving_mean": np.zeros(2, np.float32),
               "bn_moving_var": np.ones(2, np.float32)}
        return s, loc, {"aux_states": aux, "numeric_eps": 1e-2,
                        "rtol": 0.12, "atol": 3e-2}
    return b


_case("BatchNorm:train", _build_bn(fix_gamma=False))
_case("BatchNorm:fixgamma", _build_bn(fix_gamma=True))
_case("BatchNorm:global", _build_bn(fix_gamma=False, use_global_stats=True))
_case("InstanceNorm:", lambda: (
    sym.InstanceNorm(V("data"), V("gamma"), V("beta")),
    {"data": _u((2, 2, 4)), "gamma": _u((2,), 0.5, 1.5), "beta": _u((2,))},
    {"numeric_eps": 1e-2, "rtol": 0.1, "atol": 2e-2}))
_case("LayerNorm:", lambda: (
    sym.LayerNorm(V("data"), V("gamma"), V("beta")),
    {"data": _u((2, 5)), "gamma": _u((5,), 0.5, 1.5), "beta": _u((5,))},
    {"numeric_eps": 1e-2, "rtol": 0.1, "atol": 2e-2}))
_case("RMSNorm:", lambda: (
    sym.RMSNorm(V("data"), V("gamma")),
    {"data": _u((2, 5), 0.3, 1), "gamma": _u((5,), 0.5, 1.5)},
    {"numeric_eps": 1e-2, "rtol": 0.1, "atol": 2e-2}))
_case("LRN:", lambda: (sym.LRN(V("data"), nsize=3),
                       {"data": _u((1, 4, 3, 3), 0.3, 1)},
                       {"numeric_eps": 1e-2, "rtol": 0.1, "atol": 2e-2}))
_case("L2Normalization:instance", lambda: (
    sym.L2Normalization(V("data")), {"data": _u((2, 4), 0.3, 1)},
    {"numeric_eps": 1e-2, "rtol": 0.1, "atol": 2e-2}))
_case("L2Normalization:channel", lambda: (
    sym.L2Normalization(V("data"), mode="channel"),
    {"data": _u((2, 3, 2, 2), 0.3, 1)},
    {"numeric_eps": 1e-2, "rtol": 0.1, "atol": 2e-2}))

# activations / softmaxes
for _act in ("relu", "sigmoid", "tanh", "softrelu"):
    _case("Activation:%s" % _act,
          lambda a=_act: (sym.Activation(V("data"), act_type=a),
                          {"data": _u((2, 3), 0.2, 1.5)}, {}))
for _act in ("leaky", "elu"):
    _case("LeakyReLU:%s" % _act,
          lambda a=_act: (sym.LeakyReLU(V("data"), act_type=a, slope=0.1),
                          {"data": _distinct((2, 4), -1, 1)}, {}))
_case("LeakyReLU:prelu", lambda: (
    sym.LeakyReLU(V("data"), V("gamma"), act_type="prelu"),
    {"data": _distinct((2, 3), -1, 1), "gamma": _u((3,), 0.1, 0.4)}, {}))
_case("softmax:axis", lambda: (sym.softmax(V("data"), axis=-1),
                               {"data": _u((2, 4))}, {}))
_case("log_softmax:", lambda: (sym.log_softmax(V("data")),
                               {"data": _u((2, 4))}, {}))
_case("SoftmaxActivation:", lambda: (sym.SoftmaxActivation(V("data")),
                                     {"data": _u((2, 4))}, {}))
_case("softmax_cross_entropy:", lambda: (
    sym.softmax_cross_entropy(V("data"), V("label")),
    {"data": _u((3, 4)), "label": np.array([0, 2, 1], np.float32)},
    {"grad_nodes": ["data"], "numeric_eps": 1e-2, "rtol": 0.1, "atol": 2e-2}))
_case("LoopExitLoss:", lambda: (
    sym.LoopExitLoss(V("z0"), V("z1"), V("z2"), V("g0"), V("g1"), V("label"),
                     num_exits=3, beta=0.1),
    {"z0": _u((3, 4)), "z1": _u((3, 4)), "z2": _u((3, 4)),
     "g0": _u((3, 1)), "g1": _u((3, 1)),
     "label": np.array([0, 2, 1], np.float32)},
    {"grad_nodes": ["z0", "z1", "z2", "g0", "g1"], "numeric_eps": 1e-2,
     "rtol": 0.1, "atol": 2e-2}))
_case("MultiTokenLoss:", lambda: (
    sym.MultiTokenLoss(V("data"), V("label"), weight=0.3),
    {"data": _u((6, 4)), "label": np.array([[0, 2, 1], [3, 1, 0]],
                                           np.float32)},
    {"grad_nodes": ["data"], "numeric_eps": 1e-2, "rtol": 0.1, "atol": 2e-2}))
_case("Dropout:p0", lambda: (sym.Dropout(V("data"), p=0.0),
                             {"data": _u((2, 3))}, {}))

# spatial / attention / sequence
_case("UpSampling:nearest", lambda: (
    sym.UpSampling(V("data"), scale=2, sample_type="nearest", num_args=1),
    {"data": _u((1, 2, 3, 3))}, {}))
_case("Correlation:", lambda: (
    sym.Correlation(V("data1"), V("data2"), kernel_size=1, max_displacement=1,
                    stride1=1, stride2=1, pad_size=1),
    {"data1": _u((1, 2, 4, 4)), "data2": _u((1, 2, 4, 4))},
    {"numeric_eps": 1e-2, "rtol": 0.12, "atol": 3e-2}))
_case("ROIPooling:", lambda: (
    sym.ROIPooling(V("data"), V("rois"), pooled_size=(2, 2), spatial_scale=1.0),
    {"data": _distinct((1, 2, 6, 6), -1, 1),
     "rois": np.array([[0, 0, 0, 3, 3]], np.float32)},
    {"grad_nodes": ["data"], "numeric_eps": 1e-2, "rtol": 0.1, "atol": 2e-2}))
_case("BilinearSampler:", lambda: (
    sym.BilinearSampler(V("data"), V("grid")),
    {"data": _u((1, 1, 4, 4)), "grid": _u((1, 2, 3, 3), -0.7, 0.7)},
    {"numeric_eps": 1e-2, "rtol": 0.15, "atol": 3e-2}))
_case("GridGenerator:affine", lambda: (
    sym.GridGenerator(V("data"), transform_type="affine", target_shape=(3, 3)),
    {"data": np.array([[1.1, 0.1, 0.05, -0.1, 0.9, -0.05]], np.float32)},
    {"numeric_eps": 1e-2, "rtol": 0.1, "atol": 2e-2}))
_case("SpatialTransformer:", lambda: (
    sym.SpatialTransformer(V("data"), V("loc"), transform_type="affine",
                           sampler_type="bilinear", target_shape=(3, 3)),
    {"data": _u((1, 1, 4, 4)),
     "loc": np.array([[1.0, 0.1, 0.0, -0.1, 0.9, 0.1]], np.float32)},
    {"numeric_eps": 1e-2, "rtol": 0.15, "atol": 4e-2}))
_case("MultiHeadAttention:", lambda: (
    sym.MultiHeadAttention(V("query"), V("key"), V("value"), num_heads=2),
    {"query": _u((1, 3, 4)), "key": _u((1, 3, 4)), "value": _u((1, 3, 4))},
    {"numeric_eps": 1e-2, "rtol": 0.12, "atol": 3e-2}))
_case("ExpertFFN:", lambda: (
    # a router whose logits lie far apart: which experts a token takes is
    # piecewise constant, and a numeric step must not cross a tie
    sym.ExpertFFN(V("data"), V("router_data"), V("router_weight"),
                  V("gate_weight"), V("up_weight"), V("down_weight"),
                  num_experts=4, experts_held=2, first_expert=1,
                  top_k=2)[0],
    {"data": _u((1, 3, 4)), "router_data": _u((1, 3, 4), 0.5, 1.0),
     "router_weight": np.array([[2.0] * 4, [1.0] * 4, [-1.0] * 4,
                                [-2.0] * 4], np.float32)
     + _u((4, 4), -0.1, 0.1),
     "gate_weight": _u((2, 3, 4)), "up_weight": _u((2, 3, 4)),
     "down_weight": _u((2, 4, 3))},
    {"numeric_eps": 1e-2, "rtol": 0.12, "atol": 3e-2}))
for _out, _name in ((0, "key"), (1, "value")):
    _case("LatentKV:%s" % _name, lambda o=_out: (
        sym.LatentKV(V("latent"), V("kv"), num_heads=2, head_dim=4,
                     rope_dims=2)[o],
        {"latent": _u((1, 3, 5)), "kv": _u((1, 3, 2 * (2 + 3)))},
        {"numeric_eps": 1e-2, "rtol": 0.12, "atol": 3e-2}))
_case("ShortConv:", lambda: (
    sym.ShortConv(V("data"), V("in_weight"), V("conv_weight"),
                  V("out_weight"), kernel=3),
    {"data": _u((2, 5, 4)), "in_weight": _u((12, 4)),
     "conv_weight": _u((4, 3)), "out_weight": _u((4, 4))},
    {"numeric_eps": 1e-2, "rtol": 0.12, "atol": 3e-2}))
_case("GatedDeltaRule:", lambda: (
    # one key head read by two value heads of 2; decays of e^-0.3 to e^-1
    # a token, so that the state carries over the five tokens
    sym.GatedDeltaRule(V("data"), V("ba"), V("conv_weight"), V("A_log"),
                       V("dt_bias"), num_key_heads=1, num_value_heads=2,
                       key_dim=2, value_dim=2),
    {"data": _u((1, 5, 8)), "ba": _u((1, 5, 4)), "conv_weight": _u((8, 4)),
     "A_log": np.log(np.array([0.3, 0.7], np.float32)),
     "dt_bias": np.ones((2,), np.float32)},
    {"numeric_eps": 1e-2, "rtol": 0.12, "atol": 3e-2}))
for _sop in ("SequenceMask", "SequenceReverse", "SequenceLast"):
    _case("%s:lens" % _sop,
          lambda n=_sop: (getattr(sym, n)(V("data"), V("sl"),
                                          use_sequence_length=True),
                          {"data": _u((3, 2, 2)),
                           "sl": np.array([2, 3], np.float32)},
                          {"grad_nodes": ["data"]}))
_case("RNN:lstm", lambda: (
    sym.RNN(V("data"), V("parameters"), V("state"), V("state_cell"),
            mode="lstm", state_size=3, num_layers=1),
    {"data": _u((2, 2, 3)),
     "parameters": _u((4 * 3 * (3 + 3) + 8 * 3,), -0.3, 0.3),
     "state": np.zeros((1, 2, 3), np.float32),
     "state_cell": np.zeros((1, 2, 3), np.float32)},
    {"grad_nodes": ["data", "parameters"],
     "numeric_eps": 1e-2, "rtol": 0.15, "atol": 3e-2}))
_case("ctc_loss:", lambda: (
    sym.ctc_loss(V("data"), V("label")),
    {"data": _u((4, 1, 3)), "label": np.array([[1, 2]], np.float32)},
    {"grad_nodes": ["data"], "numeric_eps": 1e-2, "rtol": 0.12, "atol": 3e-2}))
_case("Crop:hw", lambda: (
    sym.Crop(V("data"), num_args=1, offset=(1, 1), h_w=(2, 2)),
    {"data": _u((1, 1, 4, 4))}, {}))


@pytest.mark.parametrize("cid,build", GRAD_CASES, ids=[c[0] for c in GRAD_CASES])
def test_numeric_gradient(cid, build):
    s, loc, opts = build()
    opts.setdefault("numeric_eps", 1e-3)
    opts.setdefault("rtol", 0.06)
    opts.setdefault("atol", 2e-2)
    check_numeric_gradient(s, loc, **opts)


# ---------------------------------------------------------------------------
# FORWARD-ONLY ops: non-differentiable outputs (integer/comparison/random/
# creation/update ops). Each runs and must produce finite values.
# ---------------------------------------------------------------------------
FWD_CASES = []


def _fwd(cid, build):
    FWD_CASES.append((cid, build))


for _name in ("ceil", "floor", "round", "rint", "fix", "trunc", "sign",
              "logical_not"):
    _fwd("%s:" % _name, lambda n=_name: (getattr(sym, n)(V("data")),
                                         {"data": _u((2, 3), -2, 2)}))
for _name in ("_equal", "_not_equal", "_greater", "_greater_equal",
              "_lesser", "_lesser_equal", "_mod"):
    _fwd("%s:" % _name, lambda n=_name: (getattr(sym, n)(V("a"), V("b")),
                                         {"a": _u((2, 3), 0.5, 2),
                                          "b": _u((2, 3), 0.5, 2)}))
for _name in ("_equal_scalar", "_not_equal_scalar", "_greater_scalar",
              "_greater_equal_scalar", "_lesser_scalar",
              "_lesser_equal_scalar", "_mod_scalar", "_rmod_scalar"):
    _fwd("%s:" % _name, lambda n=_name: (getattr(sym, n)(V("data"), scalar=1.0),
                                         {"data": _u((2, 3), 0.5, 2)}))
for _name in ("broadcast_equal", "broadcast_not_equal", "broadcast_greater",
              "broadcast_greater_equal", "broadcast_lesser",
              "broadcast_lesser_equal", "broadcast_mod",
              "broadcast_logical_and", "broadcast_logical_or",
              "broadcast_logical_xor"):
    _fwd("%s:" % _name, lambda n=_name: (getattr(sym, n)(V("a"), V("b")),
                                         {"a": _u((2, 1, 3), 0.5, 2),
                                          "b": _u((1, 4, 3), 0.5, 2)}))
for _name in ("argmax", "argmin"):
    _fwd("%s:" % _name, lambda n=_name: (getattr(sym, n)(V("data"), axis=1),
                                         {"data": _distinct((2, 4))}))
_fwd("argmax_channel:", lambda: (sym.argmax_channel(V("data")),
                                 {"data": _distinct((2, 4))}))
_fwd("argsort:", lambda: (sym.argsort(V("data"), axis=1),
                          {"data": _distinct((2, 4))}))
_fwd("sort:", lambda: (sym.sort(V("data"), axis=1), {"data": _distinct((2, 4))}))
_fwd("topk:", lambda: (sym.topk(V("data"), axis=1, k=2),
                       {"data": _distinct((2, 4))}))
_fwd("one_hot:", lambda: (sym.one_hot(V("indices"), depth=4),
                          {"indices": np.array([0, 2], np.float32)}))
_fwd("Cast:", lambda: (sym.Cast(V("data"), dtype="float64"),
                       {"data": _u((2, 3))}))
_fwd("ones_like:", lambda: (sym.ones_like(V("data")), {"data": _u((2, 3))}))
_fwd("zeros_like:", lambda: (sym.zeros_like(V("data")), {"data": _u((2, 3))}))
for _name in ("_random_uniform", "_random_normal", "_random_exponential",
              "_random_gamma"):
    _fwd("%s:" % _name, lambda n=_name: (getattr(sym, n)(shape=(2, 3)), {}))
_fwd("_zeros:", lambda: (sym._zeros(shape=(2, 2)), {}))
_fwd("_ones:", lambda: (sym._ones(shape=(2, 2)), {}))
_fwd("_full:", lambda: (sym._full(shape=(2, 2), value=3.0), {}))
_fwd("_eye:", lambda: (sym._eye(N=3), {}))
_fwd("_arange:", lambda: (sym._arange(start=0, stop=5), {}))
# fused optimizer-update kernels (forward-checked vs numpy in
# tests/test_operator.py::test_optimizer_ops_vs_numpy)
_fwd("sgd_update:", lambda: (sym.sgd_update(V("w"), V("g"), lr=0.1),
                             {"w": _u((3,)), "g": _u((3,))}))
_fwd("sgd_mom_update:", lambda: (sym.sgd_mom_update(V("w"), V("g"), V("m"), lr=0.1),
                                 {"w": _u((3,)), "g": _u((3,)), "m": _u((3,))}))
_fwd("adam_update:", lambda: (sym.adam_update(V("w"), V("g"), V("m"), V("v"), lr=0.1),
                              {"w": _u((3,)), "g": _u((3,)),
                               "m": _u((3,)), "v": _u((3,), 0.1, 1)}))
_fwd("rmsprop_update:", lambda: (sym.rmsprop_update(V("w"), V("g"), V("n"), lr=0.1),
                                 {"w": _u((3,)), "g": _u((3,)), "n": _u((3,), 0.1, 1)}))
_fwd("rmspropalex_update:", lambda: (
    sym.rmspropalex_update(V("w"), V("g"), V("n"), V("gm"), V("d"), lr=0.1),
    {"w": _u((3,)), "g": _u((3,), -0.3, 0.3), "n": _u((3,), 2, 3),
     "gm": _u((3,), -0.2, 0.2), "d": _u((3,))}))
_fwd("quantize:", lambda: (sym.quantize(V("data"), V("min_range"), V("max_range")),
                           {"data": _u((2, 3)),
                            "min_range": np.array([-1], np.float32),
                            "max_range": np.array([1], np.float32)}))
_fwd("dequantize:", lambda: (sym.dequantize(V("data"), V("min_range"), V("max_range")),
                             {"data": _u((2, 3)),
                              "min_range": np.array([-1], np.float32),
                              "max_range": np.array([1], np.float32)}))
_fwd("count_sketch:", lambda: (
    sym.count_sketch(V("data"), V("h"), V("s"), out_dim=4),
    {"data": _u((2, 6)), "h": R.randint(0, 4, (1, 6)).astype(np.float32),
     "s": (R.randint(0, 2, (1, 6)) * 2 - 1).astype(np.float32)}))
_fwd("fft:", lambda: (sym.fft(V("data")), {"data": _u((2, 4))}))
_fwd("ifft:", lambda: (sym.ifft(V("data")), {"data": _u((2, 8))}))
_fwd("MultiBoxPrior:", lambda: (
    sym.MultiBoxPrior(V("data"), sizes=(0.5,), ratios=(1.0,)),
    {"data": _u((1, 2, 4, 4))}))
_fwd("MultiBoxTarget:", lambda: (
    sym.MultiBoxTarget(V("anchor"), V("label"), V("cls_pred")),
    {"anchor": np.array([[[0.1, 0.1, 0.4, 0.4], [0.5, 0.5, 0.9, 0.9]]], np.float32),
     "label": np.array([[[0, 0.1, 0.1, 0.45, 0.45]]], np.float32),
     "cls_pred": _u((1, 2, 2), 0.1, 0.9)}))
_fwd("MultiBoxDetection:", lambda: (
    sym.MultiBoxDetection(V("cls_prob"), V("loc_pred"), V("anchor")),
    {"cls_prob": _u((1, 2, 2), 0.1, 0.9), "loc_pred": _u((1, 8), -0.1, 0.1),
     "anchor": np.array([[[0.1, 0.1, 0.4, 0.4], [0.5, 0.5, 0.9, 0.9]]], np.float32)}))
_fwd("Proposal:", lambda: (
    sym.Proposal(V("cls_prob"), V("bbox_pred"), V("im_info"),
                 feature_stride=4, scales=(8,), ratios=(1.0,),
                 rpn_pre_nms_top_n=4, rpn_post_nms_top_n=2, rpn_min_size=1),
    {"cls_prob": _u((1, 2, 3, 3), 0.1, 0.9),
     "bbox_pred": _u((1, 4, 3, 3), -0.1, 0.1),
     "im_info": np.array([[12, 12, 1.0]], np.float32)}))

# inference-only PTQ op (weight/scale declared no-grad): forward coverage
# with dequant-on-load act_dtype — integer-valued int8 weights are exact
# in every compute dtype, so the bf16 consistency sweep applies too. The
# int8-activation path (dynamic quantization buckets, legitimately
# dtype-sensitive at bucket boundaries) is covered in tests/test_quant.py.
_fwd("QuantizedFullyConnected:", lambda: (
    sym.QuantizedFullyConnected(
        V("data"), V("weight"), V("scale"), V("bias"), num_hidden=4,
        act_dtype="float32"),
    {"data": _u((2, 3)),
     "weight": np.round(_u((4, 3), -127, 127)).astype(np.float32),
     "scale": _u((4,), 0.005, 0.02),
     "bias": _u((4,), -0.1, 0.1)}))


@pytest.mark.parametrize("cid,build", FWD_CASES, ids=[c[0] for c in FWD_CASES])
def test_forward_executes(cid, build):
    s, loc = build()
    if loc:
        exe = _bind(s, loc, None, "null", None)
    else:
        exe = s.bind(mx.cpu(), {}, grad_req="null")
    outs = exe.forward(is_train=False)
    for o in outs:
        v = o.asnumpy()
        assert np.isfinite(v.astype(np.float64)).all() or cid.startswith("MultiBox"), cid


# ---------------------------------------------------------------------------
# CUSTOM-BACKWARD ops: the reference's loss-output family overrides the
# mathematical gradient (backward injects (pred - label) * scale and
# ignores head gradients — softmax_output-inl.h). Verified against the
# closed form, not the numeric gradient of the forward.
# ---------------------------------------------------------------------------
CUSTOM_BWD = {
    "SoftmaxOutput": "closed-form (prob - one_hot(label))/norm below",
    "LinearRegressionOutput": "closed-form (pred - label) below",
    "LogisticRegressionOutput": "closed-form (sigmoid(x) - label) below",
    "MAERegressionOutput": "closed-form sign(pred - label) below",
    "SVMOutput": "margin subgradient below",
    "MakeLoss": "grad = grad_scale regardless of head grads",
    "make_loss": "alias of MakeLoss semantics",
    "IdentityAttachKLSparseReg": "identity fwd + KL reg grad",
    "BlockGrad": "grad must be exactly zero",
    "stop_gradient": "grad must be exactly zero",
}


def _bwd_grads(s, loc, heads=None):
    exe = _bind(s, loc, None, "write", None)
    exe.forward(is_train=True)
    exe.backward(heads)
    return exe


def test_softmax_output_closed_form_backward():
    x = _u((3, 4))
    label = np.array([0, 2, 1], np.float32)
    s = sym.SoftmaxOutput(V("data"), V("label"), name="softmax")
    exe = _bwd_grads(s, {"data": x, "label": label})
    e = np.exp(x - x.max(1, keepdims=True))
    prob = e / e.sum(1, keepdims=True)
    want = prob.copy()
    want[np.arange(3), label.astype(int)] -= 1
    np.testing.assert_allclose(exe.grad_dict["data"].asnumpy(), want,
                               rtol=1e-4, atol=1e-5)


def test_regression_outputs_closed_form_backward():
    x = _u((3, 2))
    y = _u((3, 2))
    n = x.size / x.shape[0]  # per-batch normalization: grad scaled by 1/dim
    cases = [
        (sym.LinearRegressionOutput, lambda: (x - y)),
        (sym.LogisticRegressionOutput, lambda: (1 / (1 + np.exp(-x)) - y)),
        (sym.MAERegressionOutput, lambda: np.sign(x - y)),
    ]
    for op, want in cases:
        s = op(V("data"), V("label"), name="out")
        exe = _bwd_grads(s, {"data": x, "label": y})
        g = exe.grad_dict["data"].asnumpy()
        w = want()
        # reference scales by grad_scale (=1); allow either raw or /dim norm
        ok = (np.allclose(g, w, rtol=1e-3, atol=1e-4)
              or np.allclose(g, w / n, rtol=1e-3, atol=1e-4))
        assert ok, (op.__name__, g, w)


def test_svm_output_backward_runs():
    x = _u((3, 4))
    label = np.array([0, 2, 1], np.float32)
    s = sym.SVMOutput(V("data"), V("label"), name="svm")
    exe = _bwd_grads(s, {"data": x, "label": label})
    g = exe.grad_dict["data"].asnumpy()
    assert np.isfinite(g).all() and np.abs(g).sum() > 0


def test_make_loss_ignores_head_grads():
    x = _u((2, 3), 0.5, 1.5)
    s = sym.MakeLoss(V("data"), grad_scale=2.0)
    exe = _bwd_grads(s, {"data": x},
                     heads=[nd.array(np.full((2, 3), 123.0, np.float32))])
    np.testing.assert_allclose(exe.grad_dict["data"].asnumpy(),
                               np.full((2, 3), 2.0, np.float32),
                               rtol=1e-5)


def test_block_grad_zero():
    x = _u((2, 3))
    s = sym.BlockGrad(V("data")) * sym.Variable("w")
    exe = _bwd_grads(s, {"data": x, "w": _u((2, 3))})
    np.testing.assert_allclose(exe.grad_dict["data"].asnumpy(), 0.0)


def test_identity_attach_kl_sparse_reg_backward():
    x = _u((2, 4), 0.1, 0.9)
    s = sym.IdentityAttachKLSparseReg(V("data"), sparseness_target=0.1,
                                      penalty=0.01)
    exe = _bwd_grads(s, {"data": x})
    assert np.isfinite(exe.grad_dict["data"].asnumpy()).all()


# ---------------------------------------------------------------------------
# SKIP: ops that cannot be driven standalone here (each with the test that
# covers it elsewhere).
# ---------------------------------------------------------------------------
SKIP = {
    "Custom": "needs a registered python op — tests/test_custom_op.py",
}


def test_registry_coverage_is_complete():
    """Every distinct registry op must be covered by a gradient case, a
    forward case, a custom-backward test, or an explicit SKIP. Fails when
    a new op is added without deciding its gradient story."""
    covered = set()
    for cid, _ in GRAD_CASES:
        covered.add(cid.split(":")[0])
    for cid, _ in FWD_CASES:
        covered.add(cid.split(":")[0])
    covered |= set(CUSTOM_BWD)
    covered |= set(SKIP)

    # ops reachable under any alias count as covered
    uncovered = []
    seen = set()
    for name, op in OP_REGISTRY.items():
        if id(op) in seen:
            continue
        aliases = {n for n, o in OP_REGISTRY.items() if o is op}
        seen.add(id(op))
        if not (aliases & covered):
            uncovered.append(sorted(aliases)[0])
    assert not uncovered, (
        "registry ops with no gradient/forward/custom coverage: %s"
        % sorted(uncovered))
