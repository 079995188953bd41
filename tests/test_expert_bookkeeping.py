"""The expert layer's bookkeeping (``ops/moe.py``): every quantity of one
number a row (the routing weights, the group sizes, the router's picks) is
moved by a sort or picked by an elementwise select, never by a gather or a
scatter. Checked against the gather-based formulation it replaced, kept
here as the reference, to the bit; and by counting the gathers and
scatters of the lowered forward and backward."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import moe
from mxnet_tpu.ops.registry import get_op


# --- the reference: the gather-based formulation ---------------------------------

def _take(src, index, mask):
    out = src.at[index].get(mode="promise_in_bounds")
    return jnp.where(mask, out, 0)


def route_gathered(router_data, router_weight, top_k, norm_topk, bias=None,
                   norm_eps=0.0, scale=1.0):
    """The router's picks by ``top_k``'s values and ``take_along_axis``."""
    hp = jax.lax.Precision.HIGHEST
    logits = jnp.einsum("nd,ed->ne", router_data.astype(jnp.float32),
                        router_weight.astype(jnp.float32), precision=hp)
    if bias is not None:
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(
            scores + jax.lax.stop_gradient(bias.astype(jnp.float32)), top_k)
        w = jnp.take_along_axis(scores, idx, 1)
        if norm_topk:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + norm_eps)
        return w * scale, idx
    if norm_topk:
        top, idx = jax.lax.top_k(logits, top_k)
        return jax.nn.softmax(top, axis=-1), idx
    _, idx = jax.lax.top_k(logits, top_k)
    return jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), idx, 1), idx


def sort_gathered(idx, first, held):
    """The group sizes by a search of the sorted keys, gathered."""
    local = idx.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    inv = jnp.argsort(order).astype(jnp.int32)
    ends = jnp.searchsorted(key[order], jnp.arange(held + 1, dtype=key.dtype))
    return order, inv, jnp.diff(ends).astype(jnp.int32)


@jax.custom_vjp
def row_weights_gathered(w, plan):
    """The routing weights gathered into row order, and their gradient
    gathered back through the slots."""
    rows = plan["valid"].shape[0]
    return _take(w.reshape(-1), plan["order"][:rows], plan["valid"])


row_weights_gathered.defvjp(
    lambda w, plan: (row_weights_gathered(w, plan), plan),
    lambda plan, g: (_take(g, plan["slot"], plan["ok"]), None))

GATHERED = {"route": route_gathered, "_sort_assignments": sort_gathered,
            "_row_weights": row_weights_gathered}


# --- the layer -------------------------------------------------------------------

ROUTES = {"softmax": dict(route="softmax", norm_topk=True),
          "softmax_all": dict(route="softmax", norm_topk=False),
          "sigmoid_bias": dict(route="sigmoid_bias", norm_topk=True,
                               norm_eps=1e-5, scale=1.8)}


def _layer(e, held, first, top_k, route):
    op = get_op("ExpertFFN")
    attrs = op.parse_attrs(dict(num_experts=e, experts_held=held,
                                first_expert=first, top_k=top_k,
                                **ROUTES[route]))

    def f(bias, *args):
        aux = (bias,) if route == "sigmoid_bias" else ()
        (y, counts), _ = op.impl(attrs, args, aux, None)
        return y, counts

    return f


def _inputs(e, held, tokens, d, f, seed):
    """The layer's six inputs in the cells' dtypes (bfloat16 but the
    router matrix), a cotangent of the output, and a selection bias."""
    rng = np.random.RandomState(seed)

    def r(*shape, scale=0.3, dtype=jnp.bfloat16):
        return jnp.asarray(scale * rng.randn(*shape), dtype)

    args = (r(2, tokens // 2, d, scale=1.0), r(2, tokens // 2, d, scale=1.0),
            r(e, d, dtype=jnp.float32), r(held, f, d), r(held, f, d),
            r(held, d, f))
    return args, r(2, tokens // 2, d, scale=1.0), r(e, dtype=jnp.float32)


def _grads(layer, bias, args, ct):
    """Output, the per-expert count and the six inputs' gradients."""
    def run(*args):
        (y, counts), vjp = jax.vjp(lambda *a: layer(bias, *a), *args)
        return (y, counts) + vjp((ct, jnp.zeros_like(counts)))
    return jax.jit(run)(*args)


@pytest.mark.parametrize("route", sorted(ROUTES))
@pytest.mark.parametrize("held,first", [(8, 0), (8, 4), (2, 0), (2, 6)])
def test_bookkeeping_equals_the_gathered_formulation(route, held, first,
                                                     monkeypatch):
    """Output, ``expert_tokens`` and every gradient equal to the bit to the
    gather-based reference, with more experts held than a token chooses
    and fewer (a buffer of N * held rows), the first held expert 0 and
    not; the router matrix's float32 gradient within 1e-6 (XLA may sum its
    product in another order)."""
    e, top_k, tokens, d, f = 16, 4, 48, 16, 24
    args, ct, bias = _inputs(e, held, tokens, d, f, seed=held + first)
    layer = _layer(e, held, first, top_k, route)
    got = _grads(layer, bias, args, ct)
    for name, reference in GATHERED.items():
        monkeypatch.setattr(moe, name, reference)
    want = _grads(layer, bias, args, ct)
    names = ("output", "expert_tokens", "data", "router_data",
             "router_weight", "gate_weight", "up_weight", "down_weight")
    assert 0 < float(want[1].sum()) <= tokens * min(held, top_k)
    for name, a, b in zip(names, got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(b).all() and b.any(), name
        if name == "router_weight":
            np.testing.assert_allclose(a, b, rtol=0,
                                       atol=1e-6 * np.abs(b).max())
        else:
            np.testing.assert_array_equal(a, b, err_msg=name)


# --- what the lowered layer holds ------------------------------------------------

_MOVE = re.compile(r"= (\w+)\[([\d,]*)\]\{?[^ ]* (gather|scatter)\(")


def _moves(route, held, top_k=3, e=8, tokens=32, d=16, f=24):
    """The shape of every gather's and scatter's result in the lowered
    forward and backward of one layer."""
    args, ct, bias = _inputs(e, held, tokens, d, f, seed=3)
    layer = _layer(e, held, 0, top_k, route)

    def loss(*args):
        y, _ = layer(bias, *args)
        return jnp.sum(y.astype(jnp.float32) * ct.astype(jnp.float32))

    text = jax.jit(jax.value_and_grad(loss, argnums=tuple(range(6)))).lower(
        *args).as_text(dialect="hlo")
    return [tuple(int(n) for n in dims.split(",") if n)
            for _, dims, _ in _MOVE.findall(text)]


@pytest.mark.parametrize("route", ["softmax", "sigmoid_bias"])
@pytest.mark.parametrize("held", [4, 2])
def test_no_gather_or_scatter_moves_one_number_a_row(route, held,
                                                     monkeypatch):
    """The lowered forward and backward of one layer hold the four row
    moves (``_spread`` and ``_collect``, each way: ``(rows, d)`` and
    ``(N k, d)`` results)
    and no gather or scatter whose result is one number a row (R, N k,
    (N, k) or (N, E) numbers). The reference holds such ops, so the count
    can see them."""
    e, top_k, tokens, d = 8, 3, 32, 16
    rows = tokens * min(top_k, held)

    def one_number_a_row(shape):
        size = int(np.prod(shape))
        return shape[-1:] != (d,) and size in (rows, tokens * top_k,
                                               tokens * e)

    shapes = _moves(route, held, top_k, e, tokens, d)
    assert sorted(s for s in shapes if s[-1:] == (d,)) == sorted(
        [(rows, d)] * 2 + [(tokens * top_k, d)] * 2), shapes
    assert not [s for s in shapes if one_number_a_row(s)], shapes
    for name, reference in GATHERED.items():
        monkeypatch.setattr(moe, name, reference)
    assert len([s for s in _moves(route, held, top_k, e, tokens, d)
                if one_number_a_row(s)]) >= 4
