"""Sparse expert feed-forward: one chip's share of a top-k expert layer.

``ExpertFFN`` routes every token over ALL ``num_experts`` (the router keeps
its published width), keeps the ``top_k`` largest, and computes the part of
the layer's result that the ``experts_held`` experts it was given
(``first_expert`` onward) contribute. What the other experts would add is
another chip's; on one chip nothing is exchanged and nothing stands in for
the absent chips. Expert parallelism asks exactly this of a layer, so the
same op is what an ``expert`` mesh axis above 1 will shard.

Nothing is dropped. Assignments are sorted by expert and the three expert
matrices are GROUPED products over the ragged, sorted batches, never a
dense product over all held experts with a mask and never a one-hot
dispatch (``parallel/moe.py``'s Switch layer is that, with a capacity that
clips). Which implementation multiplies follows from what the code can
observe (``product_path``; the layer's ``note_built`` record says it),
with no option to choose it:

- on the TPU, where both widths are whole lanes, the buffer whole row
  tiles and the blocks fit a kernel's VMEM (a matrix that does not stand
  there twice, 2048 x 1536, in column blocks), the repo's own Pallas kernels
  (``ops/pallas/grouped_matmul.py``): ``gmm`` for the forward and, with the
  matrices read the other way, for dX; ``tgmm`` for dW; joined by a
  ``custom_vjp``. An expert's matrix stays in VMEM while its row tiles
  pass, and the matrices are read in the (E, out, in) layout the op holds
  them, so the step makes no transposed copy of them either: 1.05-1.15 ms
  a forward or dX product and 1.22-1.28 a dW product in the benchmark
  cell's step, 88% and 79% of the MXU over the rows walked (PERF.md,
  Findings, PR 34);
- elsewhere (the CPU's tests, odd widths) ``jax.lax.ragged_dot``, which the
  TPU compiler builds as a grouped-matmul kernel over 512 x 512 x 256
  tiles that fetches a matrix again for every row tile (1.87-2.16 ms the
  same products, 45-52%, beside transposed copies of the matrices); its
  transposes are autodiff's.

Shapes are static, so the sorted rows live in a buffer that holds
the worst case (every token's every choice held here), and the products
walk ALL of it whatever the routing: the last held expert's group is
stretched over the rows no assignment took, which are zero and add nothing
to any product. A step's time then does not follow its routing. That is a
choice, and it costs: with untrained weights a layer's held experts drew
0.05 to 2.97 times the expected load, and a first buffer of twice the
expectation with the worst case as an exact overflow path under a
``lax.cond`` took 240-254 ms a step by the seed (0.6 ms per 1000 held rows,
the overflow path in 4 seeds of 6) where this took 301 on every seed
(PERF.md, Findings, PR 33).

Every move between token order and sorted-row order is a row GATHER in
both directions (a permutation read forwards or backwards), because the
transpose of a gather that autodiff would write is a scatter-add, which the
TPU runs row by row (PERF.md, section 7, item 8). Every such gather takes a
ONE-dimensional index, gives a two-dimensional ``(rows, d)`` result and is
told that its indices lie inside the table (``_rows_at``); no array has
``top_k`` on its second-minor dimension. The combine fetches a token's k
rows CHOICE-major, into k slabs of ``(N, d)`` that one pass sums. With an
``(N, k)`` index the same rows landed in an ``(N, k, d)`` array whose k
rows pad a tile of sixteen sublanes, which cost a relayout (0.9 ms at the
cells' shapes) and a sum over the padded array after the gather, and
``jnp.take``'s default, which fills what lies outside the table with NaN,
a ``select`` pass over every gather's result (0.76-0.82 ms). What decides
a gather's own time is where its TABLE lies (PERF.md, Findings, PR 36): one
of N rows (42-67 MB) XLA keeps in VMEM, and the gather writes at the HBM's
bandwidth (0.39 ms for 49152 x 2560 or 65536 x 2048 rows); one of R rows
(251-268 MB) stays in HBM and is read at 33-41 ns a row (2.0-2.2 ms),
whatever the index's shape or order. Around the products the backward is
autodiff's: a ``custom_vjp`` that recomputed the forward from the layer's
inputs compiled to the same program, XLA merging the second forward with
the first.

Where the ``valid`` mask lives: in ``_row_weights``, always (a row past the
held assignments gets weight 0, which multiplies the down product's input
and every gradient that returns through it), and in the combine's ``ok``
(such a row is never summed). ``_spread`` masks its rows only where the
buffer is smaller than the assignments (``experts_held < top_k``); with
one row an assignment the two masks above already make what ``_spread``
writes there immaterial, and its ``select`` pass over ``(rows, d)`` is not
made (``_plan``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import defop, get_op, note_built

_ACTS = {"relu": jax.nn.relu, "silu": jax.nn.silu, "gelu": jax.nn.gelu}


def buffer_rows(tokens, top_k, held, experts):
    """(rows of the sorted-assignment buffer, assignments expected): the
    buffer holds the worst case, every token's every choice among the held
    experts; the expectation is under uniform routing."""
    return tokens * min(top_k, held), tokens * top_k * held / experts


# --- moves between token order and sorted-row order ---------------------------
# A plan (dict of int/bool arrays) describes one permutation both ways:
#   tok (R,), order (R,), valid (R,): row r holds assignment order[r], of
#       token tok[r]; rows past the held assignments are not valid;
#   spread_mask: valid, or None where what _spread writes into a row that
#       is not valid cannot reach any result (_plan);
#   slot (N, k), ok (N, k): assignment (t, j) sits in row slot[t, j], if ok.

def _rows_at(src, index):
    """``src[index]`` along axis 0, the gather alone. Every index a plan
    holds lies inside its table (``_plan``), and the gather is told so:
    ``jnp.take``'s default fills what lies outside with NaN, a ``select``
    pass over the whole result after the gather."""
    return src.at[index].get(mode="promise_in_bounds")


def _take(src, index, mask):
    out = _rows_at(src, index)
    if mask is None:
        return out
    return jnp.where(mask.reshape(mask.shape + (1,) * (src.ndim - 1)), out, 0)


@jax.custom_vjp
def _spread(x, plan):
    """Tokens (N, d) -> sorted rows (R, d): one gather, index (R,)."""
    return _take(x, plan["tok"], plan["spread_mask"])


@jax.custom_vjp
def _collect(rows, plan):
    """Sorted rows (R, d) -> tokens (N, d): the sum of a token's rows.

    ONE flat gather, choice-major: the index is ``slot.T`` read as
    ``(k * N,)``, so the result is ``(k * N, d)``, the layout ``_spread``'s
    gather writes, and its k slabs of ``(N, d)`` (a view: N is whole tiles)
    are summed over the MAJOR axis in float32 and cast once, the ``ok``
    mask a predicate inside that one pass. (Written as a reduction: as k
    explicit adds of ``got[j]`` XLA sliced every slab out and copied it
    into a transposed layout, 40 copies in ``smallthinker_train_8k``'s
    step.) The gather with the ``(N, k)`` index gave ``(N, k, d)``, k
    padding a tile's sublanes: a relayout and a sum over the padded array
    after it (PERF.md, Findings, PR 36)."""
    n, k = plan["slot"].shape
    got = _rows_at(rows, plan["slot"].T.reshape(-1)).reshape(k, n, -1)
    got = jnp.where(plan["ok"].T[:, :, None], got, 0)
    return jnp.sum(got, axis=0, dtype=jnp.float32).astype(rows.dtype)


@jax.custom_vjp
def _row_weights(w, plan):
    """Routing weights (N, k) -> one a sorted row (R,)."""
    return _take(w.reshape(-1), plan["order"], plan["valid"])


_spread.defvjp(lambda x, plan: (_spread(x, plan), plan),
               lambda plan, g: (_collect(g, plan), None))
_collect.defvjp(lambda rows, plan: (_collect(rows, plan), plan),
                lambda plan, g: (_spread(g, plan), None))
_row_weights.defvjp(
    lambda w, plan: (_row_weights(w, plan), plan),
    lambda plan, g: (_take(g, plan["slot"], plan["ok"]), None))


def _sort_assignments(idx, first, held):
    """idx (N, k): the experts each token chose. Returns the assignments'
    order by held expert (those of other chips' experts last), its inverse,
    and how many each held expert took."""
    local = idx.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    inv = jnp.argsort(order).astype(jnp.int32)
    ends = jnp.searchsorted(key[order], jnp.arange(held + 1, dtype=key.dtype))
    sizes = jnp.diff(ends).astype(jnp.int32)
    return order, inv, sizes


def _plan(order, inv, total, rows, top_k):
    """``spread_mask`` is None where the buffer has a row for every
    assignment (``rows == N * top_k``, a fact of the shapes): a row past
    ``total`` is then the row of an assignment to another chip's expert,
    ``_row_weights`` gives it weight 0, and that zero multiplies the down
    product's input and every gradient that returns through it, so the
    down product's result, dgate, dup, both dX products and all three dW
    are zero on such rows whatever ``_spread`` wrote there, and
    ``_collect`` and ``_row_weights``' backward read such rows only through
    slots that are not ``ok``. (It holds a real token's row through the
    last held expert's matrices: no likelier to overflow, and so to turn
    its zero weight into a NaN, than a row that holds an assignment.)
    A smaller buffer (``experts_held < top_k``) keeps the mask."""
    valid = jnp.arange(rows, dtype=jnp.int32) < total
    inv = inv.reshape(-1, top_k)
    return {"tok": order[:rows] // top_k, "order": order[:rows],
            "valid": valid,
            "spread_mask": None if rows == inv.size else valid,
            "slot": jnp.minimum(inv, rows - 1), "ok": inv < total}


def product_path(rows, d, f, dtype):
    """Which implementation the grouped products of a layer of these
    shapes take: the repo's own kernels (``"pallas"``) on the TPU where
    the widths are whole lanes, the buffer whole row tiles and the blocks
    fit a kernel's VMEM; the compiler's ``jax.lax.ragged_dot``
    (``"ragged_dot"``) elsewhere. One algorithm, chosen from what the code
    can observe, as ``flash_attention`` chooses."""
    from .pallas import grouped_matmul, on_tpu
    if on_tpu() and grouped_matmul.fits(rows, d, f, jnp.dtype(dtype).itemsize):
        return "pallas"
    return "ragged_dot"


def _ragged(x, w, sizes):
    """x (R, in) by w (E, out, in), rows grouped by ``sizes``: (R, out)."""
    return jax.lax.ragged_dot(x, jnp.swapaxes(w, 1, 2), sizes,
                              preferred_element_type=x.dtype)


def _held_part(rows, top_k, act, grouped, x, w, wg, wu, wd, order, inv,
               sizes):
    """The held experts' part of the layer through a buffer of ``rows``
    sorted rows, all of which the products (``grouped``) walk."""
    total = jnp.sum(sizes)
    plan = _plan(order, inv, total, rows, top_k)
    walked = sizes.at[-1].add(rows - total)  # the empty rows: zeros
    xs = _spread(x, plan)
    a = act(grouped(xs, wg, walked)) * grouped(xs, wu, walked)
    # a row's routing weight scales the down product's INPUT (f wide),
    # not its result (d wide): the same product, a third of the elements
    # to scale, and the backward keeps no (rows, d) result for the
    # weights' gradient
    a = (a * _row_weights(w, plan)[:, None]).astype(a.dtype)
    return _collect(grouped(a, wd, walked), plan)


ROUTES = ("softmax", "sigmoid_bias")


def route(router_data, router_weight, top_k, norm_topk, bias=None,
          norm_eps=0.0, scale=1.0):
    """Float32 router over all the experts: (weights (N, k) float32, which
    experts (N, k) int32). The choice carries no gradient, the weights
    do.

    Without ``bias`` the scores are a softmax: over the ``top_k`` largest
    logits (``norm_topk``) or over all the experts, not renormalised.
    With ``bias`` (E,) float32, state and no parameter, they are
    ``sigmoid(logits)``; the experts CHOSEN are the ``top_k`` largest of
    score + bias, their weights the scores alone (the bias moves the
    choice and no weight), over their sum + ``norm_eps`` where
    ``norm_topk``, times ``scale``."""
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


def _aux_names(attrs):
    return ("expert_bias",) if attrs.get("route") == "sigmoid_bias" else ()


@defop(
    "ExpertFFN",
    arg_names=("data", "router_data", "router_weight", "gate_weight",
               "up_weight", "down_weight"),
    aux_names=_aux_names,
    float32_aux=("expert_bias",),  # added to float32 scores to CHOOSE
    num_outputs=2,
    output_names=("output", "expert_tokens"),
    param_spec={"num_experts": 1, "experts_held": 0, "first_expert": 0,
                "top_k": 1, "norm_topk": True, "act_type": "relu",
                "route": "softmax", "norm_eps": 0.0, "scale": 1.0},
    simple=False,
)
def _expert_ffn(attrs, inputs, aux, ctx):
    """Top-k gated expert feed-forward, this chip's share.

    ``data`` (B, T, d) feeds the experts; the router reads ``router_data``
    (B, T, d), which may be another tensor (SmallThinker routes on the
    attention's input): ``softmax`` of the ``top_k`` largest of
    ``router_data @ router_weight.T`` over all ``num_experts``, in float32
    (``norm_topk`` False: the softmax over all experts, not renormalised).
    ``route`` "sigmoid_bias" scores with a sigmoid instead and takes an
    auxiliary state ``expert_bias`` (``num_experts``,) float32: the experts
    chosen are the ``top_k`` largest of score + bias, weighted by the score
    alone over the chosen scores' sum + ``norm_eps`` (``norm_topk``), times
    ``scale``. The bias is state: no gradient, no optimizer, and this op
    never writes it.
    ``gate_weight``/``up_weight`` (E_held, f, d) and ``down_weight``
    (E_held, d, f) are experts ``first_expert`` .. ``first_expert +
    experts_held - 1`` (``experts_held`` 0: all of them). Output 0: the sum
    over a token's chosen experts AMONG THOSE HELD of
    ``weight * down(act(gate x) * up x)``; the weights stay normalised over
    all ``top_k`` chosen. Output 1 (float32, no gradient): how many
    assignments each held expert took, for a load-balance metric. No token
    is dropped, whatever the routing."""
    del ctx
    (data, router_data, router_weight, gate_weight, up_weight,
     down_weight) = inputs
    if attrs["route"] not in ROUTES:
        raise ValueError("ExpertFFN: route %r (known: %s)"
                         % (attrs["route"], ", ".join(ROUTES)))
    bias = aux[0] if aux else None
    experts = int(attrs["num_experts"])
    held = int(attrs["experts_held"]) or experts
    first, top_k = int(attrs["first_expert"]), int(attrs["top_k"])
    if gate_weight.shape[0] != held or router_weight.shape[0] != experts:
        raise ValueError("ExpertFFN: %d expert matrices for experts_held=%d, "
                         "router of %d for num_experts=%d" % (
                             gate_weight.shape[0], held,
                             router_weight.shape[0], experts))
    if not 0 <= first <= experts - held or not 0 < top_k <= experts:
        raise ValueError("ExpertFFN: experts %d..%d of %d, top_k %d"
                         % (first, first + held - 1, experts, top_k))
    act = _ACTS[attrs["act_type"]]
    d = data.shape[-1]
    x = data.reshape(-1, d)
    w, idx = route(router_data.reshape(-1, d), router_weight, top_k,
                   bool(attrs["norm_topk"]), bias, float(attrs["norm_eps"]),
                   float(attrs["scale"]))
    order, inv, sizes = _sort_assignments(idx, first, held)
    rows, expected = buffer_rows(x.shape[0], top_k, held, experts)
    path = product_path(rows, d, gate_weight.shape[1], x.dtype)
    note_built({"op": "ExpertFFN", "experts_held": held, "top_k": top_k,
                "buffer_rows": rows, "expected_rows": expected,
                "route": attrs["route"], "path": path})
    grouped = _ragged
    if path == "pallas":
        from .pallas.grouped_matmul import grouped_matmul as grouped
    y = _held_part(rows, top_k, act, grouped, x, w, gate_weight, up_weight,
                   down_weight, order, inv, sizes)
    counts = jax.lax.stop_gradient(sizes.astype(jnp.float32))
    return (y.reshape(data.shape), counts), ()


def _expert_bias_infer(attrs, shapes):
    """The auxiliary state's shape: one number an expert of the router."""
    if len(shapes) > 6:
        shapes[6] = shapes[6] or (int(attrs["num_experts"]),)
    return shapes


get_op("ExpertFFN").infer_params = _expert_bias_infer
