"""Compile the Pallas kernels of the main path for a DESCRIBED v5e, at real
widths: what interpret mode cannot see (a block over the scoped-VMEM
limit, a slice off the tiling, a layout Mosaic refuses) fails here, at no
chip time. Nothing runs, so nothing here is a result or a time.

All such tests live in THIS file: the worker that gets it loads the TPU's
library and keeps it, and no other may (on-chip-measurement guide, section
2). The topology is described inside a fixture, never at import.
"""
import importlib.util
import os
import re

import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.telemetry.programs import graph_nodes, instruction_lines


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


# (rows = batch * kv heads, group, tq, tk, causal, dtype, backward kernels:
# 1 where the fused backward's accumulators fit a kernel's scoped VMEM at
# some query superblock, 2 (dq and dkv) where they do not)
_FLASH_SHAPES = {
    # the benchmark cell lm_train_4k: 2 x 2 KV heads, 12 query heads each
    "lm_train_4k": (4, 12, 4096, 4096, True, "bfloat16", 1),
    # the cell smallthinker_train_8k: 1 x 4 KV heads, 7 query heads each; a
    # global layer, and a window layer (a trailing 8th field: the window);
    # the fused backward at query superblocks of 2048 rows
    "smallthinker_8k_global": (4, 7, 8192, 8192, True, "bfloat16", 1),
    "smallthinker_8k_window": (4, 7, 8192, 8192, True, "bfloat16", 1, 4096),
    # a band through the fused backward, and through streamed superblocks
    "window_1024_of_4096": (4, 12, 4096, 4096, True, "bfloat16", 1, 1024),
    "window_4096_of_16384": (1, 2, 16384, 16384, True, "bfloat16", 2, 4096),
    # the longest resident sequence, in the widest dtype: superblocks of
    # 1024 rows
    "resident_8192_f32": (1, 2, 8192, 8192, False, "float32", 1),
    "streaming_16384": (1, 2, 16384, 16384, True, "bfloat16", 2),
    # serving prefill against a cache: tq < tk, forward without lse too
    "prefill_512_of_4096": (2, 12, 512, 4096, True, "bfloat16", 1),
    # an odd multiple of 256 falls back to 256-wide tiles
    "odd_multiple_768": (2, 1, 768, 1280, True, "bfloat16", 1),
}


# the same at head size 64, in 64-lane blocks (lfm2_train_8k: 1 x 8 KV
# heads, 4 query heads each; the fused backward, whose accumulators take a
# head of 128's VMEM, at superblocks of 2048 rows at 8192)
_FLASH_HEAD64 = {
    "lfm2_8k": (8, 4, 8192, 8192, True, "bfloat16", 1),
    "lfm2_8k_two_sequences": (16, 4, 8192, 8192, True, "bfloat16", 1),
    "fused_4096": (8, 4, 4096, 4096, True, "bfloat16", 1),
    "window_1024_of_4096": (8, 4, 4096, 4096, True, "bfloat16", 1, 1024),
    "streaming_16384": (1, 2, 16384, 16384, True, "bfloat16", 2),
    "prefill_512_of_4096": (2, 4, 512, 4096, True, "bfloat16", 1),
}


# the same at head size 256 (glm_flash_train_4k: 20 heads, one a KV head;
# the fused backward at query superblocks of 512 rows at 4096;
# qwen3next_train_8k: 16 heads over 2 KV heads at 8192, K/V streamed in
# superblocks of 4096 rows, the dq and dkv kernels)
_FLASH_HEAD256 = {
    "glm_4k": (20, 1, 4096, 4096, True, "bfloat16", 1),
    "qwen3next_8k": (2, 8, 8192, 8192, True, "bfloat16", 2),
}


@pytest.mark.parametrize("name", sorted(_FLASH_HEAD256))
def test_flash_kernels_compile_for_v5e_at_head_size_256(name, one_chip):
    _compile_flash(one_chip, 256, *_FLASH_HEAD256[name])


@pytest.mark.parametrize("name", sorted(_FLASH_SHAPES))
def test_flash_kernels_compile_for_v5e(name, one_chip):
    _compile_flash(one_chip, 128, *_FLASH_SHAPES[name])


@pytest.mark.parametrize("name", sorted(_FLASH_HEAD64))
def test_flash_kernels_compile_for_v5e_at_head_size_64(name, one_chip):
    _compile_flash(one_chip, 64, *_FLASH_HEAD64[name])


def _compile_flash(one_chip, d, rows, g, tq, tk, causal, dtype, bwd_kernels,
                   window=0):
    from mxnet_tpu.ops.pallas import flash_attention as fa

    assert fa.kernel_qualifies(tq, tk, d, causal=causal)

    def sds(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    q, kv = sds((rows, g, tq, d)), sds((rows, tk, d))
    lse = sds((rows, g, 1, tq), "float32")
    scale = d ** -0.5

    def fwd(q, k, v):
        return (fa._fa_forward(q, k, v, causal, scale, False, window=window),
                fa._fa_forward(q, k, v, causal, scale, False, with_lse=True,
                               window=window))

    def bwd(q, k, v, o, lse, do):
        return fa._fa_backward(q, k, v, o, lse, do, causal, scale, False,
                               window=window)

    text = jax.jit(fwd).lower(q, kv, kv).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    text = jax.jit(bwd).lower(q, kv, kv, q, lse, q).compile().as_text()
    # which backward `_fa_backward` builds follows from the shapes alone
    assert text.count('custom_call_target="tpu_custom_call"') == bwd_kernels


def _step_ops():
    spec = importlib.util.spec_from_file_location("step_ops", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools", "step_ops.py"))
    step_ops = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(step_ops)
    return step_ops


_SGD = {"learning_rate": 0.01, "momentum": 0.9}


@pytest.fixture(scope="module")
def dense_lm_step(one_chip):
    """The fused step of ``lm_train_4k`` at one layer, as the chip's
    compiler builds it (``tools/step_ops.py``): (its text, its device
    operations). One compile for the tests below."""
    step_ops = _step_ops()
    cfg = {"vocab_size": 49152, "hidden_size": 3072, "num_hidden_layers": 1,
           "intermediate_size": 12288, "num_attention_heads": 24,
           "num_key_value_heads": 2}
    traffic = {"batch": 2, "seq_len": 4096, "compute_dtype": "bfloat16",
               "optimizer": _SGD}
    compiled, sym = step_ops.compile_step(cfg, traffic)
    text = compiled.as_text()
    return text, step_ops.device_ops(text, graph_nodes(sym))


def test_fused_lm_step_makes_no_needless_pass_over_the_vocabulary(
        dense_lm_step):
    """Besides the logits and their gradient, written once, no operation
    writes a (rows, vocabulary) array: no one-hot, no log_softmax. No
    operation casts the embedding table, which is gathered from in its
    master dtype (the head's weight is cast inside the fusions that
    multiply by it). The two flash kernels (forward, fused backward) are
    there."""
    _, ops = dense_lm_step
    over_vocab = [o["name"] for o in ops if "[8192,49152]" in o["result"]]
    assert len(over_vocab) == 2, over_vocab
    casts = [o["name"] for o in ops if o["result"] == "bf16[49152,3072]"
             and "f32[49152,3072]" in o["operands"]]
    assert not casts, casts
    assert sum(o["kernel"] for o in ops) == 2
    groups = {o["group"] for o in ops}
    assert {"head_loss", "embedding", "feed_forward", "flash",
            "attention_rest", "rest", "update"} <= groups


def _scatters(text):
    """(result type with its layout, line) of every scatter of a compiled
    program. A scatter works in place: its result is the table it adds
    into or writes."""
    return [(m.group(1), line) for line in text.splitlines()
            for m in [re.search(r" = (\S+) scatter\(", line)] if m]


def _assert_compact_backward(text, ops, vocab, d, ids, added=None):
    """No scatter's result is the ``[vocab, d]`` table; the ids' rows are
    added into a table of ``ids + 1`` rows (``added`` wide: ``d``, or ``d``
    padded to whole pieces of 1024 numbers) that lies in VMEM (``S(1)`` in
    its layout), as does the ``int32[vocab]`` array of slots; one gather
    under the embedding's node writes ``bf16[vocab, d]`` once."""
    scatters = _scatters(text)
    assert not [r for r, _ in scatters if "[%d,%d]" % (vocab, d) in r], (
        scatters)
    (compact,) = [r for r, _ in scatters
                  if r.startswith("bf16[%d,%d]" % (ids + 1, added or d))]
    assert "S(1)" in compact, compact
    (slots,) = [r for r, _ in scatters if r.startswith("s32[%d]" % vocab)]
    assert "S(1)" in slots, slots
    written = [o for o in ops if o["group"] == "embedding"
               and o["result"] == "bf16[%d,%d]" % (vocab, d)]
    assert [o["opcode"] for o in written] == ["fusion"], written


def test_dense_lm_step_sums_the_tables_gradient_in_a_compact_table(
        dense_lm_step):
    """StarCoder2's vocabulary and width: 8192 ids into 49152 rows."""
    text, ops = dense_lm_step
    _assert_compact_backward(text, ops, 49152, 3072, 8192)


def test_a_tied_table_that_fits_vmem_keeps_the_direct_scatter_add(one_chip):
    """``lfm2_train_8k``'s ends alone (16384 ids, a table of 8192 x 2048
    tied to the head): the rule reads more ids than rows and a table of
    33.5 MB, so the sorted scatter-add stays, into a ``bf16[8192,2048]``
    that XLA holds in VMEM."""
    from mxnet_tpu import symbol as S

    vocab, d = 8192, 2048
    table = S.Variable("table_weight")
    x = S.Embedding(data=S.Variable("data"), weight=table, input_dim=vocab,
                    output_dim=d, name="embed")
    x = S.FullyConnected(data=S.Reshape(x, shape=(-1, d)), weight=table,
                         num_hidden=vocab, no_bias=True, name="pred")
    label = S.Reshape(data=S.Variable("softmax_label"), shape=(-1,))
    net = S.MakeLoss(S.softmax_cross_entropy(x, label), name="loss")
    traffic = {"batch": 2, "seq_len": 8192, "compute_dtype": "bfloat16",
               "optimizer": _SGD}
    compiled, _ = _step_ops().compile_step({}, traffic, sym=net)
    (table_,) = [r for r, _ in _scatters(compiled.as_text())]
    assert table_.startswith("bf16[8192,2048]") and "S(1)" in table_, table_


# the grouped products of one ExpertFFN layer of smallthinker_train_8k
# (49152 sorted rows, 16 held experts, 2560 x 768): (kernel, the two
# arrays' shapes, transposed)
_GROUPED = {
    "forward_gate_up": ("gmm", (49152, 2560), (16, 768, 2560), True),
    "forward_down": ("gmm", (49152, 768), (16, 2560, 768), True),
    "dx_gate_up": ("gmm", (49152, 768), (16, 768, 2560), False),
    "dx_down": ("gmm", (49152, 2560), (16, 2560, 768), False),
    "dw_gate_up": ("tgmm", (49152, 768), (49152, 2560), None),
    "dw_down": ("tgmm", (49152, 2560), (49152, 768), None),
}


# and of lfm2_train_8k (32768 sorted rows, 8 held experts, 2048 x 1536):
# a matrix of 6.3 MB does not stand in VMEM twice, so gmm takes its columns
# in two blocks and tgmm halves its output block
_GROUPED.update({
    "lfm2_forward_gate_up": ("gmm", (32768, 2048), (8, 1536, 2048), True),
    "lfm2_forward_down": ("gmm", (32768, 1536), (8, 2048, 1536), True),
    "lfm2_dx_gate_up": ("gmm", (32768, 1536), (8, 1536, 2048), False),
    "lfm2_dx_down": ("gmm", (32768, 2048), (8, 2048, 1536), False),
    "lfm2_dw_gate_up": ("tgmm", (32768, 1536), (32768, 2048), None),
    "lfm2_dw_down": ("tgmm", (32768, 2048), (32768, 1536), None),
})


# and the up product's dX with the gate product's dX as its addend
# (``ops/moe.py`` ``_gate_up``), a (rows, d) tile more in VMEM twice:
# SmallThinker's in two column blocks (its whole matrix beside the tiles
# and the addend's is over the 16 MiB), LFM2's in two as without it
_GROUPED.update({
    "dx_up_addend": ("gmm", (49152, 768), (16, 768, 2560), False),
    "lfm2_dx_up_addend": ("gmm", (32768, 1536), (8, 1536, 2048), False),
})


@pytest.mark.parametrize("name", sorted(_GROUPED))
def test_grouped_matmul_kernels_compile_for_v5e(name, one_chip, monkeypatch):
    """Each at the cell's shape, in the 16 MiB of VMEM a kernel gets
    unasked (a block over it fails HERE), one Mosaic call named for what it
    is, its tiling under the compiler's own key; a 512-row tile beside the
    whole matrix twice, which `fits` counts out, is refused. A dX with an
    addend reads it as one more operand of the same call."""
    from mxnet_tpu.ops.pallas import grouped_matmul as gm

    kernel, a, b, transposed = _GROUPED[name]

    def sds(shape, dt="bfloat16"):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    groups = b[0] if kernel == "gmm" else 16 if a[0] == 49152 else 8
    args = (sds(a), sds(b), sds((groups,), "int32"))
    if name.endswith("_addend"):
        assert gm.gmm_column_blocks(256, a[1], b[2], 2, addend=True) == 2
        args += (sds((a[0], b[2])),)

        def f(x, w, s, addend):
            return gm.gmm(x, w, s, transposed=False, addend=addend)
    elif kernel == "gmm":
        def f(x, w, s, **kw):
            return gm.gmm(x, w, s, transposed=transposed, **kw)
    else:
        f = gm.tgmm
    text = jax.jit(f).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "%expert_" + kernel in text and "ragged_dot_tiling" in text
    assert '"scoped_memory_configs":[{' not in text  # no vmem_limit_bytes
    if kernel == "gmm" and 2560 in a:
        # the count is the compiler's: with the matrix whole (no column
        # blocks) a 512-row tile is over it, and does not compile
        assert gm.gmm_vmem_bytes(512, 2560, 768, 2) > 16 * 2 ** 20
        monkeypatch.setattr(gm, "MAX_COLUMN_BLOCKS", 1)
        with pytest.raises(Exception, match="vmem"):
            jax.jit(lambda x, w, s: f(x, w, s, tm=512)).lower(
                *args).compile()


@pytest.mark.parametrize("batch", [1, 2])
def test_short_conv_kernels_compile_for_v5e(batch, one_chip):
    """``lfm2_train_8k``'s gated short convolution: (batch, 8192, 3 x 2048)
    in bfloat16, 3 taps, forward and backward, one Mosaic call each, named
    for what it is, in the VMEM a kernel gets unasked."""
    from mxnet_tpu.ops.pallas import short_conv as kernels

    def sds(shape, dt="bfloat16"):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    t, d = 8192, 2048
    assert kernels.fits(t, d, 3)
    u, k, dy = sds((batch, t, 3 * d)), sds((d, 3)), sds((batch, t, d))
    for name, f, args in (
            ("short_conv_fwd", lambda u, k: kernels._forward(u, k, False),
             (u, k)),
            ("short_conv_bwd",
             lambda u, k, dy: kernels._backward(u, k, dy, False),
             (u, k, dy))):
        text = jax.jit(f).lower(*args).compile().as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 1
        assert "%" + name in text
        assert '"scoped_memory_configs":[{' not in text


def test_gated_delta_kernels_compile_for_v5e(one_chip):
    """``qwen3next_train_8k``'s delta rule: (1, 8192, 16 x 128) q and k,
    (1, 8192, 32 x 128) v in bfloat16, the forward without a gradient (o
    alone), the forward a gradient runs (o and the state before every
    chunk, float32, in one call) and the backward's reverse walk over those
    states, one Mosaic call each, named for what it is, in the VMEM a
    kernel gets unasked; the backward sums a key head's dq and dk itself
    (no reduce after it)."""
    from mxnet_tpu.ops.pallas import gated_delta as kernels

    def sds(shape, dt="bfloat16"):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    t, hk, hv, d = 8192, 16, 32, 128
    assert kernels.fits(t, d, d) and kernels._heads(hv, hv // hk) == 4
    q, v, g = sds((1, t, hk * d)), sds((1, t, hv * d)), sds((1, t, hv),
                                                            "float32")
    st = sds((hv, t // 64, d, d), "float32")
    for name, f, args, outs in (
            ("gated_delta_fwd", lambda q, k, v, g, b: kernels._walk(
                q, k, v, g, b, hk, hv, False, False), (q, q, v, g, g),
             r"bf16\[1,8192,4096\]\{"),
            ("gated_delta_fwd", lambda q, k, v, g, b: kernels._walk(
                q, k, v, g, b, hk, hv, False, True), (q, q, v, g, g),
             r"\(bf16\[1,8192,4096\]\{[^}]*\}, f32\[32,128,128,128\]"),
            ("gated_delta_bwd", lambda q, k, v, g, b, st, do:
             kernels._backward(q, k, v, g, b, st, do, hk, hv, False),
             (q, q, v, g, g, st, v), None)):
        text = jax.jit(f).lower(*args).compile().as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 1
        assert "%" + name in text
        assert '"scoped_memory_configs":[{' not in text
        if outs:   # the call's results: o, or o and the states
            assert re.search(r"%%%s\.\d+ = %s" % (name, outs), text)
        if name == "gated_delta_bwd":
            assert not re.search(r"= bf16\[1,8192,16,2,128\]", text)


@pytest.fixture(scope="module")
def expert_layer_step(one_chip):
    """The fused step of ``smallthinker_train_8k`` at one layer, as the
    chip's compiler builds it: (its text, its device operations, its
    instructions' lines by name). One compile for the tests below."""
    import json

    step_ops = _step_ops()
    with open(os.path.join(step_ops.ROOT, "benchmark", "configs",
                           "smallthinker-21b-a3b.train.json")) as f:
        cfg = dict(json.load(f), num_hidden_layers=1)
    traffic = {"batch": 1, "seq_len": 8192, "compute_dtype": "bfloat16",
               "optimizer": _SGD}
    compiled, sym = step_ops.compile_step(cfg, traffic)
    text = compiled.as_text()
    ops = step_ops.device_ops(text, graph_nodes(sym))
    lines = {line.split(" = ")[0].strip().lstrip("%"): line
             for line in instruction_lines(text)}
    return text, ops, lines


def test_expert_layer_step_compiles_to_grouped_kernels(expert_layer_step):
    """The expert matrices' nine products (forward, dX and dW of gate, up
    and down) are the repo's own kernels (``expert_gmm`` / ``expert_tgmm``,
    each line saying its tiling under ``ragged_dot_tiling``) over the
    worst-case buffer and the matrices in the layout the op holds them,
    none is left to the compiler's ``ragged-dot``, there is no dense
    product over all sixteen held experts, and the one window-free layer's
    flash kernels are there: the forward and the fused backward, in query
    superblocks at 8192 tokens."""
    text, ops, lines = expert_layer_step
    # 8192 tokens x 6 choices = 49152 rows, the worst case (12288 expected)
    grouped = [o for o in ops if o["group"] == "expert_products"]
    assert sorted(o["name"].split(".")[0] for o in grouped) == (
        ["expert_gmm"] * 6 + ["expert_tgmm"] * 3), [o["name"] for o in grouped]
    assert all(o["kernel"] and "ragged_dot_tiling" in lines[o["name"]]
               for o in grouped)
    assert sorted(o["result"] for o in grouped) == sorted(
        ["bf16[49152,768]"] * 3 + ["bf16[49152,2560]"] * 3
        + ["bf16[16,768,2560]"] * 2 + ["bf16[16,2560,768]"])
    # every product reads (49152, ...) rows and (16, ...) matrices, and no
    # transposed copy of a matrix is made for it
    for o in grouped:
        assert [t for t in o["operands"] if t.startswith("bf16[49152,")], o
        if o["name"].startswith("expert_gmm"):
            assert [t for t in o["operands"] if t.startswith("bf16[16,")], o
    assert not [o["name"] for o in ops if o["opcode"] in ("copy", "transpose")
                and re.match(r"(?:bf16|f32)\[16,(?:768,2560|2560,768)\]",
                             o["result"])]
    assert "ragged-dot" not in text
    # no product, mask or one-hot with an axis over the held experts
    assert not re.findall(r"(?:bf16|f32)\[16,(?:24576|49152|8192),", text)
    assert sum(o["kernel"] and o["group"] == "flash" for o in ops) == 2
    assert {"expert_products", "expert_moves", "flash"} <= {
        o["group"] for o in ops}


def test_expert_layer_moves_are_flat_gathers(expert_layer_step):
    """Every move between token order and sorted-row order of that step is
    a gather with a one-dimensional index and a two-dimensional result: the
    four of a layer (``_spread`` and the combine, forward and backward) give
    ``bf16[49152,2560]``, no gather anywhere has a three-dimensional
    result, no array has the six choices on its second-minor dimension,
    the k slabs ``[6,8192,2560]`` are a view of the gather's result that
    ONE fusion a combine sums (no slab is sliced out or copied on the
    way), no ``select`` pass over ``[49152,2560]`` stands between a
    gather and the product that reads it (``_spread`` without its mask;
    the gather told its indices lie inside the table), and no ``add``
    sums the two dX products: the up product's dX kernel reads the gate
    product's as its addend, beside its rows (``_gate_up``)."""
    text, ops, _ = expert_layer_step
    gathers = re.findall(r"= (\w+)\[([\d,]*)\]\S* gather\(", text)
    assert gathers.count(("bf16", "49152,2560")) == 4, gathers
    assert not [g for g in gathers if g[1].count(",") > 1], gathers
    assert "8192,6,2560]" not in text
    routing = [o for o in ops if o["group"] == "expert_moves"]
    rows = [o for o in routing if o["result"] == "bf16[49152,2560]"]
    # the four gathers, nothing else: the two dX products are summed in
    # the kernel that writes the second
    assert sorted(o["opcode"] for o in rows) == ["fusion"] * 4, [
        (o["name"], o["opcode"]) for o in rows]
    summed = [o for o in ops if o["name"].startswith("expert_gmm")
              and "bf16[49152,768]" in o["operands"]
              and "bf16[49152,2560]" in o["operands"]]
    assert len(summed) == 1 and summed[0]["result"] == "bf16[49152,2560]", [
        (o["name"], o["operands"]) for o in summed]
    slabs = [o for o in ops if "bf16[6,8192,2560]" in o["operands"]
             or o["result"].startswith("bf16[6,8192,2560]")]
    assert [(o["opcode"], o["result"]) for o in slabs] == [
        ("fusion", "bf16[8192,2560]")] * 2, slabs
    assert not [o["name"] for o in ops if o["opcode"] in ("copy", "slice")
                and o["result"] in ("bf16[1,8192,2560]", "bf16[49152,2560]")
                and o["node"] == "layer0_experts"]


def test_expert_lm_step_sums_the_tables_gradient_in_a_compact_table(
        expert_layer_step):
    """SmallThinker's vocabulary share and width: 8192 ids into 37984
    rows, whose 2560 numbers are padded to 3072 for the adds."""
    text, ops, _ = expert_layer_step
    _assert_compact_backward(text, ops, 37984, 2560, 8192, added=3072)
