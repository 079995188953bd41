"""The expert layer's grouped products as the repo's own Pallas kernels
(``ops/pallas/grouped_matmul.py``), interpreted here: ``gmm`` both ways and
``tgmm`` against a plain loop over the groups, the visits a set of group
sizes is cut into, ``ExpertFFN`` down the kernel path against the
``ragged_dot`` path, and the VMEM count at the benchmark cell's shapes. The
kernels compile for a described v5e in ``tests/test_tpu_compile.py``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

import mxnet_tpu.ops.pallas as pallas
from mxnet_tpu.ops import moe
from mxnet_tpu.ops.pallas import grouped_matmul as gm
from mxnet_tpu.ops.registry import built_layers, get_op

ROWS, TM = 64, 16
# sizes of four groups over 64 rows in tiles of 16
SIZES = {
    "tile_aligned": [16, 16, 16, 16],
    "an_empty_group_and_a_cut_tile": [5, 0, 20, 39],
    "empty_groups_at_both_ends": [0, 10, 54, 0],
    "one_group_takes_everything": [0, 64, 0, 0],
    "three_groups_inside_one_tile": [3, 3, 3, 55],
    # the layer's ``walked``: the last group held nothing of its own and is
    # stretched over the rows no assignment took
    "last_group_stretched_from_zero": [7, 21, 4, 32],
}


def _loop_gmm(x, w, sizes, transposed):
    out, at = [], 0
    for g, n in enumerate(sizes):
        m = w[g].T if transposed else w[g]
        out.append(x[at:at + n].astype(np.float32) @ m.astype(np.float32))
        at += n
    return np.concatenate(out)


def _loop_tgmm(a, b, sizes):
    out, at = [], 0
    for n in sizes:
        out.append(a[at:at + n].astype(np.float32).T
                   @ b[at:at + n].astype(np.float32))
        at += n
    return np.stack(out)


def _rows(shape, dtype, seed):
    x = np.random.RandomState(seed).randn(*shape).astype(np.float32)
    return np.asarray(jnp.asarray(x, dtype))


@pytest.mark.parametrize("case", sorted(SIZES))
def test_visits_cover_every_row_once_and_every_group(case):
    """A static number of visits, groups and tiles running upwards (an
    output block is only ever revisited at once), every row in exactly one
    visit's range, every group visited, nothing in the visits left over."""
    sizes = SIZES[case]
    g, t, lo, hi = (np.asarray(a) for a in gm.group_visits(
        jnp.asarray(sizes, jnp.int32), ROWS, TM))
    assert len(g) == ROWS // TM + len(sizes) - 1
    assert (np.diff(g) >= 0).all() and (np.diff(t) >= 0).all()
    assert set(g) == set(range(len(sizes)))
    seen = np.zeros(ROWS, int)
    for v in range(len(g)):
        r = np.arange(t[v] * TM, (t[v] + 1) * TM)
        seen[r[(r >= lo[v]) & (r < hi[v])]] += 1
    assert (seen == 1).all()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("transposed", [True, False])
@pytest.mark.parametrize("case", sorted(SIZES))
def test_gmm_against_a_loop_over_the_groups(case, transposed, dtype):
    sizes, k, n = SIZES[case], 128, 256
    x = _rows((ROWS, k), dtype, 1)
    w = _rows((4, n, k) if transposed else (4, k, n), dtype, 2)
    with jax.default_matmul_precision("highest"):
        got = gm.gmm(jnp.asarray(x), jnp.asarray(w),
                     jnp.asarray(sizes, jnp.int32), transposed=transposed,
                     tm=TM, chunk=128, interpret=True)
    assert got.shape == (ROWS, n) and got.dtype == x.dtype
    want = _loop_gmm(x, w, sizes, transposed)
    tol = 1e-4 if dtype == "float32" else 0.01 * np.abs(want).max()
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=tol)


@pytest.mark.parametrize("transposed", [True, False])
@pytest.mark.parametrize("case", sorted(SIZES))
def test_gmm_in_column_blocks_against_a_loop(case, transposed, monkeypatch):
    """A matrix that does not stand in VMEM twice (here: a VMEM shrunk
    until this one does not): its columns in two blocks, each a walk over
    all the visits, the same result; one that does not fit in halves
    either is the caller's to take elsewhere."""
    sizes, k, n = SIZES[case], 128, 512
    monkeypatch.setattr(gm, "_TEMPORARIES", 0)
    monkeypatch.setattr(gm, "_SCOPED_VMEM", gm.gmm_vmem_bytes(
        TM, k, n // 4, 4))
    assert gm.gmm_column_blocks(TM, k, n, 4) == 0
    monkeypatch.setattr(gm, "_SCOPED_VMEM", gm.gmm_vmem_bytes(
        TM, k, n // 2, 4))
    assert gm.gmm_column_blocks(TM, k, n, 4) == 2
    x = _rows((ROWS, k), "float32", 11)
    w = _rows((4, n, k) if transposed else (4, k, n), "float32", 12)
    with jax.default_matmul_precision("highest"):
        got = gm.gmm(jnp.asarray(x), jnp.asarray(w),
                     jnp.asarray(sizes, jnp.int32), transposed=transposed,
                     tm=TM, chunk=128, interpret=True)
    np.testing.assert_allclose(got, _loop_gmm(x, w, sizes, transposed),
                               atol=1e-4)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("ka,nb", [(128, 256), (256, 128)])
@pytest.mark.parametrize("case", sorted(SIZES))
def test_tgmm_against_a_loop_over_the_groups(case, ka, nb, dtype):
    """Either side the wider (the wider is the one cut into blocks), an
    empty group's block zeros."""
    sizes = SIZES[case]
    a, b = _rows((ROWS, ka), dtype, 3), _rows((ROWS, nb), dtype, 4)
    with jax.default_matmul_precision("highest"):
        got = gm.tgmm(jnp.asarray(a), jnp.asarray(b),
                      jnp.asarray(sizes, jnp.int32), tm=TM, wide=128,
                      interpret=True)
    assert got.shape == (4, ka, nb) and got.dtype == a.dtype
    want = _loop_tgmm(a, b, sizes)
    tol = 1e-4 if dtype == "float32" else 0.01 * np.abs(want).max()
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=tol)
    for g, n in enumerate(sizes):
        if n == 0:
            assert not np.asarray(got[g], np.float32).any()


def test_tgmm_keeps_float32_where_asked():
    a, b = _rows((ROWS, 128), "bfloat16", 5), _rows((ROWS, 128), "bfloat16", 6)
    got = gm.tgmm(jnp.asarray(a), jnp.asarray(b),
                  jnp.asarray(SIZES["tile_aligned"], jnp.int32), tm=TM,
                  out_dtype="float32", interpret=True)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, _loop_tgmm(a, b, SIZES["tile_aligned"]),
                               rtol=1e-5, atol=1e-4)


def test_grouped_matmul_differentiates_into_the_two_transposes():
    sizes = jnp.asarray(SIZES["an_empty_group_and_a_cut_tile"], jnp.int32)
    x = jnp.asarray(_rows((ROWS, 128), "float32", 7))
    w = jnp.asarray(_rows((4, 256, 128), "float32", 8))
    ct = jnp.asarray(_rows((ROWS, 256), "float32", 9))

    def kernels(x, w):
        return gm.gmm(x, w, sizes, tm=TM, chunk=128, interpret=True)

    def plain(x, w):
        return jax.lax.ragged_dot(x, jnp.swapaxes(w, 1, 2), sizes)

    with jax.default_matmul_precision("highest"):
        # the custom backward, through tiles that fit these 64 rows
        dx = gm.gmm(ct, w, sizes, transposed=False, tm=TM, chunk=128,
                    interpret=True)
        dw = gm.tgmm(ct, x, sizes, tm=TM, interpret=True)
        y, vjp = jax.vjp(plain, x, w)
        want_dx, want_dw = vjp(ct)
        np.testing.assert_allclose(kernels(x, w), y, atol=1e-4)
    np.testing.assert_allclose(dx, want_dx, atol=1e-4)
    np.testing.assert_allclose(dw, want_dw, atol=1e-4)


def _expert_layer(e, held, first, top_k):
    op = get_op("ExpertFFN")
    attrs = op.parse_attrs(dict(num_experts=e, experts_held=held,
                                first_expert=first, top_k=top_k))

    def f(*args):
        (y, counts), _ = op.impl(attrs, args, (), None)
        return y, counts

    return f


def _built(layers):
    return [r["path"] for r in layers if r["op"] == "ExpertFFN"]


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4), ("bfloat16", 0.03)])
def test_expert_layer_down_the_kernel_path(dtype, tol, monkeypatch):
    """``ExpertFFN`` where its gate says "pallas" (the platform answered
    for the chip, the kernels interpreted) against the same layer through
    ``jax.lax.ragged_dot``: the output and all six gradients, and the
    layer's own record says which path each trace took."""
    e, held, first, top_k, tokens, d, f = 8, 4, 2, 2, 256, 128, 256
    rng = np.random.RandomState(11)

    def rand(*shape, scale=1.0):
        return jnp.asarray(rng.randn(*shape).astype(np.float32) * scale,
                           dtype)

    args = (rand(1, tokens, d), rand(1, tokens, d),
            rand(e, d).astype(jnp.float32), rand(held, f, d, scale=0.1),
            rand(held, f, d, scale=0.1), rand(held, d, f, scale=0.1))
    ct = rand(1, tokens, d)
    rows = moe.buffer_rows(tokens, top_k, held, e)[0]
    assert rows % gm.ROWS == 0 and gm.fits(rows, d, f, jnp.dtype(dtype).itemsize)
    layer = _expert_layer(e, held, first, top_k)

    def both(path):
        with jax.default_matmul_precision("highest"), \
                built_layers() as built:
            (y, counts), vjp = jax.vjp(layer, *args)
            grads = vjp((ct, jnp.zeros_like(counts)))
        assert _built(built.layers) == [path]
        return (y,) + tuple(grads)

    assert moe.product_path(rows, d, f, dtype) == "ragged_dot"  # the CPU
    want = both("ragged_dot")
    monkeypatch.setattr(pallas, "on_tpu", lambda: True)
    assert moe.product_path(rows, d, f, dtype) == "pallas"
    with pltpu.force_tpu_interpret_mode():
        got = both("pallas")
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        b = np.asarray(b, np.float32)
        np.testing.assert_allclose(np.asarray(a, np.float32), b,
                                   atol=tol * max(1.0, np.abs(b).max()))


@pytest.mark.parametrize("rows,d,f,why", [
    (512, 100, 256, "a width that is not whole lanes"),
    (512, 128, 200, "a width that is not whole lanes"),
    (384, 128, 256, "a buffer that is not whole row tiles"),
    (512, 8192, 4096, "matrices that do not fit a kernel's VMEM"),
])
def test_gate_leaves_other_shapes_to_the_compiler(rows, d, f, why, monkeypatch):
    monkeypatch.setattr(pallas, "on_tpu", lambda: True)
    assert moe.product_path(rows, d, f, "bfloat16") == "ragged_dot", why


def test_vmem_count_at_the_cells_shapes():
    """``smallthinker_train_8k``: 49152 rows, 2560 x 768, bfloat16. Every
    kernel's blocks fit the 16 MiB a kernel gets unasked, by the count the
    gate uses (the compile for a described v5e is the proof:
    ``test_tpu_compile.py``); a 512-row tile beside the whole (768, 2560)
    matrix twice does not, nor did it compile."""
    rows, d, f = 49152, 2560, 768
    limit, temporaries = 16 * 2 ** 20, 3 * 2 ** 20
    assert gm.fits(rows, d, f, 2)
    # the matrix twice 7.86 MB + a 256-row tile twice 2.62 + the result
    # twice 0.79, and the kernel's temporaries
    assert gm.gmm_vmem_bytes(256, d, f, 2) == 2 * (
        d * f + 256 * d + 256 * f) * 2 + temporaries == 11_272_192 + temporaries
    assert gm.gmm_vmem_bytes(256, f, d, 2) == gm.gmm_vmem_bytes(256, d, f, 2)
    assert gm.gmm_vmem_bytes(512, d, f, 2) > limit
    assert gm._tgmm_blocks(f, d) == (768, 1280)
    assert gm._tgmm_blocks(d, f) == (1280, 768)
    # row tiles twice 2.10 MB, the output block twice 3.93, its float32
    # accumulator 3.93, a's tile transposed 0.39
    assert gm.tgmm_vmem_bytes(256, f, d, 2, 2) == (
        2 * 256 * (768 + 1280) * 2 + 2 * 768 * 1280 * 2 + 768 * 1280 * 4
        + 256 * 768 * 2 + temporaries)
    assert (gm.tgmm_vmem_bytes(256, f, d, 2, 2)
            < gm.tgmm_vmem_bytes(256, d, f, 2, 2) < limit)
    assert gm.tgmm_vmem_bytes(256, f, d, 2, 2, wide=2560) > limit
    assert gm.gmm_column_blocks(256, d, f, 2) == 1
    assert gm.gmm_column_blocks(256, f, d, 2) == 1
    assert gm.tgmm_wide(256, f, d, 2, 2) == gm.TGMM_WIDE
    # float32 models: the matrices alone are 15.7 MB twice, and in halves
    # the forward's still does not fit: the compiler's kernel, as before
    assert gm.gmm_column_blocks(256, d, f, 4) == 0
    assert not gm.fits(rows, d, f, 4)


def test_vmem_count_at_the_second_cells_shapes():
    """``lfm2_train_8k``: 32768 rows, 2048 x 1536, bfloat16. An expert's
    matrix is 6.3 MB, and twice beside the tiles 18.5 MiB: ``gmm`` takes
    its columns in two blocks both ways, ``tgmm`` halves its output block
    to 640 columns (512 of this shape's), and all of it fits the 16 MiB a
    kernel gets unasked (the compile for a described v5e is the proof:
    ``test_tpu_compile.py``). That is the blocking the chip has timed
    against ``ragged_dot``; what it does not fit stays the compiler's."""
    rows, d, f = 32768, 2048, 1536
    limit = 16 * 2 ** 20
    assert gm.gmm_vmem_bytes(256, d, f, 2) > limit
    assert gm.gmm_column_blocks(256, d, f, 2) == 2      # gate, up; dX of down
    assert gm.gmm_column_blocks(256, f, d, 2) == 2      # down; dX of gate, up
    assert gm.gmm_vmem_bytes(256, d, f // 2, 2) < limit
    assert gm.tgmm_vmem_bytes(256, f, d, 2, 2) > limit
    assert gm.tgmm_wide(256, f, d, 2, 2) == gm.tgmm_wide(256, d, f, 2, 2) == 640
    assert gm._tgmm_blocks(f, d, 640) == (1536, 512)
    assert gm._tgmm_blocks(d, f, 640) == (512, 1536)
    assert gm.tgmm_vmem_bytes(256, f, d, 2, 2, wide=640) < limit
    assert gm.fits(rows, d, f, 2) and gm.fits(2 * rows, d, f, 2)
    assert gm.MAX_COLUMN_BLOCKS == 2 and gm.TGMM_NARROW == 640
    assert gm.gmm_column_blocks(256, d, 2 * f, 2) == 0   # three blocks: no
    assert not gm.fits(rows, d, 2 * f, 2)
    assert gm.gmm_column_blocks(256, 8192, 4096, 2) == 0
    assert not gm.fits(512, 8192, 4096, 2)
