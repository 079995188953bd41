"""Gated delta rule: the linear-attention mixer of a hybrid decoder
(Qwen3-Next's Gated DeltaNet, ``model_type`` ``qwen3_next``).

For a value head with state ``S`` (key size x value size, float32, zero at
the sequence's start), key ``k_t``, value ``v_t``, query ``q_t``, decay
``g_t`` <= 0 and write strength ``beta_t`` in (0, 1)::

    S_t = e^{g_t} S_{t-1} + k_t (beta_t (v_t - e^{g_t} S_{t-1}^T k_t))^T
    o_t = S_t^T q_t

``gated_delta_rule`` computes it in CHUNKS of ``CHUNK`` tokens, never token
by token. Within a chunk, with ``gam_i`` the sum of g over the chunk up to
token i and ``S_0`` the state before the chunk, the writes ``U`` solve the
unit lower-triangular system of the WY form::

    (I + A) U = beta v - (beta e^gam k) S_0,  A_ij = beta_i (k_i . k_j)
                                                    e^(gam_i - gam_j), j < i
    O = e^gam (q S_0) + ((q k^T) e^(gam_i - gam_j), j <= i) U
    S_C = e^gam_C S_0 + (e^(gam_C - gam) k)^T U

Every decay is the exponential of a DIFFERENCE of cumulative sums, masked
before the exponential, so nothing overflows however negative g is;
``(I + A)^-1`` is a product of powers of A (``_unit_lower_inverse``).
``chunk_forward`` and ``chunk_backward`` are that mathematics and its
gradient for one chunk of one head, written once on two-dimensional arrays:
the plain path (``_plain``: a ``lax.scan`` over chunks, autodiff's
backward) runs the first, and the repo's Pallas kernels
(``ops/pallas/gated_delta.py``) run both, a grid step a chunk. Which of
the two runs follows from what the code can observe (``delta_path``; the
layer's ``note_built`` record says it), with no option to choose it.

``GatedDeltaRule`` is the op around it: the causal depthwise convolution and
silu over q, k and v (``shortconv._taps``, the short convolution's shifts),
q and k normed to unit length a head and q scaled by 1/sqrt(key size),
``beta = sigmoid(b)`` and ``g = -exp(A_log) softplus(a + dt_bias)`` in
float32, and the rule, value head j reading key head j // (value heads /
key heads).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import defop, get_op, note_built
from .shortconv import _taps

CHUNK = 64
_F32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))     # a @ b.T
_NN = (((1,), (0,)), ((), ()))     # a @ b
_TN = (((0,), (0,)), ((), ()))     # a.T @ b


# --- one chunk of one head, on two-dimensional arrays ------------------------
# The kernels run these on the tiles they load, the plain path under vmap.

def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _grid(n):
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0),
            jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))


def _col(row, r, c):
    """(1, n) -> (n, 1), exactly: a select on the diagonal and a sum across
    the lanes."""
    return jnp.sum(jnp.where(r == c, row, 0.0), axis=1, keepdims=True)


def _row(col, r, c):
    """(n, 1) -> (1, n), exactly."""
    return jnp.sum(jnp.where(r == c, col, 0.0), axis=0, keepdims=True)


def _last(row, r, c):
    """(1, n) -> its last number, (1, 1)."""
    n = row.shape[1]
    return jnp.sum(jnp.where(c[0:1] == n - 1, row, 0.0), axis=1,
                   keepdims=True)


def _unit_lower_inverse(a, eye):
    """(I + a)^-1 for a strictly lower-triangular (n, n) ``a``: the sum of
    (-a)^i for i < n, as (I - a)(I + a^2)(I + a^4)..., two products a
    doubling (ten at n = 64), where forward substitution takes n steps."""
    m = -a
    p = eye + m
    span = 2
    while span < a.shape[0]:
        m = _dot(m, m)
        p = p + _dot(p, m)
        span *= 2
    return p


def _chunk_parts(q, k, v, gam, beta, s):
    """What the forward and the backward of a chunk both compute."""
    n = q.shape[0]
    r, c = _grid(n)
    q, k, v = q.astype(_F32), k.astype(_F32), v.astype(_F32)
    gc, bc = _col(gam, r, c), _col(beta, r, c)
    decay = jnp.exp(jnp.where(r >= c, gc - gam, -jnp.inf))
    kk = _dot(k, k, _NT)
    a = jnp.where(r > c, bc * kk * decay, 0.0)
    t = _unit_lower_inverse(a, (r == c).astype(_F32))
    eg = jnp.exp(gc)
    kb = (bc * eg) * k
    rhs = bc * v - _dot(kb, s)
    u = _dot(t, rhs)
    last = _last(gam, r, c)
    el = jnp.exp(last - gc)
    return dict(r=r, c=c, q=q, k=k, v=v, gc=gc, bc=bc, decay=decay, kk=kk,
                a=a, t=t, eg=eg, kb=kb, u=u, last=last, el=el)


def chunk_forward(q, k, v, gam, beta, s, want_o=True):
    """One chunk of one head: q, k (C, dk) and v (C, dv) in any float
    dtype; ``gam`` (1, C) the cumulative sums of g over the chunk and
    ``beta`` (1, C), float32; ``s`` (dk, dv) float32 the state before the
    chunk. -> (o (C, dv) float32, or None without ``want_o``; the state
    after the chunk)."""
    p = _chunk_parts(q, k, v, gam, beta, s)
    s_new = jnp.exp(p["last"]) * s + _dot(p["el"] * p["k"], p["u"], _TN)
    if not want_o:
        return None, s_new
    o = (p["eg"] * _dot(p["q"], s)
         + _dot(_dot(p["q"], p["k"], _NT) * p["decay"], p["u"]))
    return o, s_new


def chunk_backward(q, k, v, gam, beta, s, do, ds):
    """The gradient of ``chunk_forward`` for one chunk of one head: ``do``
    (C, dv) that of its output, ``ds`` (dk, dv) that of the state after it.
    -> (dq, dk, dv float32, dgam (1, C), dbeta (1, C), the gradient of the
    state before it)."""
    p = _chunk_parts(q, k, v, gam, beta, s)
    r, c, q, k, v = p["r"], p["c"], p["q"], p["k"], p["v"]
    bc, eg, decay, u, t = p["bc"], p["eg"], p["decay"], p["u"], p["t"]
    do = do.astype(_F32)
    e_last = jnp.exp(p["last"])
    # the state after: e^gam_C S_0 + kd^T U, kd = e^(gam_C - gam) k
    kd = p["el"] * k
    du = _dot(kd, ds)
    dkd = _dot(u, ds, _NT)
    dk = p["el"] * dkd
    rr = jnp.sum(dkd * kd, axis=1, keepdims=True)
    dgc = -rr
    dlast = (jnp.sum(rr, axis=0, keepdims=True)
             + e_last * jnp.sum(jnp.sum(ds * s, axis=1, keepdims=True),
                                axis=0, keepdims=True))
    ds0 = e_last * ds
    # the output: e^gam (q S_0) + P U, P = (q k^T) decay
    dqs = eg * do
    dq = _dot(dqs, s, _NT)
    ds0 = ds0 + _dot(q, dqs, _TN)
    dgc = dgc + jnp.sum(dqs * _dot(q, s), axis=1, keepdims=True)
    pm = _dot(q, k, _NT) * decay
    dp = _dot(do, u, _NT)
    du = du + _dot(pm, do, _TN)
    dqk = dp * decay
    m = dp * pm
    dgc = dgc + jnp.sum(m, axis=1, keepdims=True)
    dgr = -jnp.sum(m, axis=0, keepdims=True)
    dq = dq + _dot(dqk, k)
    dk = dk + _dot(dqk, q, _TN)
    # the writes: U = T R, T = (I + A)^-1, R = beta v - kb S_0
    dr = _dot(t, du, _TN)
    da = jnp.where(r > c, -_dot(dr, u, _NT), 0.0)
    dv = bc * dr
    dbc = jnp.sum(dr * v, axis=1, keepdims=True)
    dkb = -_dot(dr, s, _NT)
    ds0 = ds0 - _dot(p["kb"], dr, _TN)
    sb = jnp.sum(dkb * k, axis=1, keepdims=True)
    dk = dk + (bc * eg) * dkb
    dbc = dbc + sb * eg
    dgc = dgc + sb * bc * eg
    # A = beta_i (k_i . k_j) e^(gam_i - gam_j), j < i
    dkk = bc * da * decay
    dbc = dbc + jnp.sum(da * p["kk"] * decay, axis=1, keepdims=True)
    m2 = da * p["a"]
    dgc = dgc + jnp.sum(m2, axis=1, keepdims=True)
    dgr = dgr - jnp.sum(m2, axis=0, keepdims=True)
    dk = dk + _dot(dkk, k) + _dot(dkk, k, _TN)
    dgam = (_row(dgc, r, c) + dgr
            + jnp.where(c[0:1] == q.shape[0] - 1, dlast, 0.0))
    return dq, dk, dv, dgam, _row(dbc, r, c), ds0


# --- whole sequences ----------------------------------------------------------

def _plain(q, k, v, gam, beta, key_heads, value_heads):
    """The rule by ``chunk_forward`` under a ``lax.scan`` over chunks (a
    scan of T / CHUNK steps, every head and sequence at once); autodiff's
    backward. Shapes as ``gated_delta_rule``'s, T whole chunks."""
    b, t, _ = q.shape
    dk, dv = q.shape[-1] // key_heads, v.shape[-1] // value_heads
    nc, grp = t // CHUNK, value_heads // key_heads

    def heads(x, h, d):   # (B, T, h d) -> (nc, B, h, C, d)
        return x.reshape(b, nc, CHUNK, h, d).transpose(1, 0, 3, 2, 4)

    def rows(x):          # (B, T, H) -> (nc, B, H, 1, C)
        return x.reshape(b, nc, CHUNK, value_heads).transpose(
            1, 0, 3, 2)[:, :, :, None, :]

    qh = jnp.repeat(heads(q, key_heads, dk), grp, axis=2)
    kh = jnp.repeat(heads(k, key_heads, dk), grp, axis=2)
    step = jax.vmap(jax.vmap(chunk_forward))

    def body(s, xs):
        o, s = step(*xs, s)
        return s, o

    s0 = jnp.zeros((b, value_heads, dk, dv), _F32)
    _, o = jax.lax.scan(body, s0, (qh, kh, heads(v, value_heads, dv),
                                   rows(gam), rows(beta)))
    return o.transpose(1, 0, 3, 2, 4).reshape(b, t, value_heads * dv)


def delta_path(t, key_dim, value_dim):
    """Which implementation runs the rule over sequences of ``t`` tokens:
    the repo's kernels (``"kernel"``) on the TPU where the sequence is whole
    chunks, the heads whole lanes and no mesh partitions the program (the
    partitioner cannot split a Mosaic call); the plain path (``"plain"``)
    elsewhere."""
    from ..parallel.mesh import partition_mesh
    from .pallas import gated_delta, on_tpu
    if (on_tpu() and partition_mesh() is None
            and gated_delta.fits(t, key_dim, value_dim)):
        return "kernel"
    return "plain"


def gated_delta_rule(q, k, v, g, beta, key_heads, value_heads, path):
    """q, k (B, T, key_heads * dk), v (B, T, value_heads * dv), g and beta
    (B, T, value_heads) float32 -> o (B, T, value_heads * dv) in v's dtype,
    by the implementation ``path`` names. A length that is not whole chunks
    is padded at its end with tokens that write nothing (beta 0)."""
    t = q.shape[1]
    pad = -t % CHUNK
    if pad:
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                            for x in (q, k, v, g, beta))
    b, tp, h = g.shape
    gam = jnp.cumsum(g.reshape(b, tp // CHUNK, CHUNK, h), axis=2).reshape(
        b, tp, h)
    if path == "kernel":
        from .pallas import gated_delta
        o = gated_delta.gated_delta(q, k, v, gam, beta, key_heads,
                                    value_heads)
    else:
        o = _plain(q, k, v, gam, beta, key_heads, value_heads).astype(
            v.dtype)
    return o[:, :t]


def _unit_heads(x, heads, eps):
    """x (B, T, heads * d) float32, each head over its own length:
    x / sqrt(sum x^2 + eps)."""
    b, t, w = x.shape
    x = x.reshape(b, t, heads, w // heads)
    x = x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)
    return x.reshape(b, t, w)


@defop(
    "GatedDeltaRule",
    arg_names=("data", "ba", "conv_weight", "A_log", "dt_bias"),
    # the decay's and the write strength's parameters: g's cumulative sums
    # over a chunk are differences of float32 numbers
    master_inputs=("A_log", "dt_bias"),
    param_spec={"num_key_heads": 1, "num_value_heads": 1, "key_dim": 128,
                "value_dim": 128, "conv_kernel": 4, "eps": 1e-6},
)
def _gated_delta_op(attrs, data, ba, conv_weight, A_log, dt_bias):
    """Gated DeltaNet's core on (B, T, .): ``data`` the projection's q | k |
    v part (2 key_heads key_dim + value_heads value_dim), ``ba`` its b | a
    part (value_heads each); ``conv_weight`` (q k v channels,
    ``conv_kernel``) the causal depthwise taps, the last on the token
    itself, no bias; ``A_log`` and ``dt_bias`` (value_heads,). q, k, v =
    silu(conv(data)); q and k normed to unit length a head (``eps``), q
    over sqrt(key_dim); beta = sigmoid(b), g = -exp(A_log) softplus(a +
    dt_bias), float32. Output (B, T, value_heads value_dim): the rule's
    o, value head j reading key head j // (value_heads / key_heads)."""
    hk, hv = int(attrs["num_key_heads"]), int(attrs["num_value_heads"])
    dk, dv = int(attrs["key_dim"]), int(attrs["value_dim"])
    taps, wq = int(attrs["conv_kernel"]), hk * dk
    width = 2 * wq + hv * dv
    if (hv % hk or data.shape[-1] != width or ba.shape[-1] != 2 * hv
            or conv_weight.shape != (width, taps)
            or A_log.shape != (hv,) or dt_bias.shape != (hv,)):
        raise ValueError(
            "GatedDeltaRule: %d key heads of %d and %d value heads of %d "
            "want data of width %d, ba of %d, conv_weight (%d, %d), A_log "
            "and dt_bias (%d,); got %s, %s, %s, %s, %s"
            % (hk, dk, hv, dv, width, 2 * hv, width, taps, hv, data.shape,
               ba.shape, conv_weight.shape, A_log.shape, dt_bias.shape))
    path = delta_path(data.shape[1], dk, dv)
    # where the backward's states come from: the kernels' forward keeps
    # them; the plain path's scan leaves them to autodiff
    note_built({"op": "GatedDeltaRule", "path": path, "chunk": CHUNK,
                "key_heads": hk, "value_heads": hv,
                "state_dtype": "float32",
                "backward_states": "forward" if path == "kernel"
                else "autodiff"})
    # stored in the projection's dtype: what the backward keeps of it
    dt = data.dtype
    x = jax.nn.silu(_taps(data, conv_weight, 1)).astype(dt)
    eps = float(attrs["eps"])
    q = _unit_heads(x[..., :wq].astype(_F32), hk, eps) * (dk ** -0.5)
    k = _unit_heads(x[..., wq:2 * wq].astype(_F32), hk, eps)
    ba = ba.astype(_F32)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(A_log.astype(_F32)) * jax.nn.softplus(
        ba[..., hv:] + dt_bias.astype(_F32))
    return gated_delta_rule(q.astype(dt), k.astype(dt), x[..., 2 * wq:], g,
                            beta, hk, hv, path)


def _gated_delta_infer(attrs, shapes):
    if shapes[0] is None:
        return shapes
    hv = int(attrs["num_value_heads"])
    shapes[2] = shapes[2] or (shapes[0][-1], int(attrs["conv_kernel"]))
    shapes[3] = shapes[3] or (hv,)
    shapes[4] = shapes[4] or (hv,)
    return shapes


get_op("GatedDeltaRule").infer_params = _gated_delta_infer
