"""What the families' plain references share: the key from a large seed, the
control's fp8 rounding, a norm of a difference, which leaves are kept whole.
Imports JAX and nothing of the program."""
import jax
import jax.numpy as jnp
import numpy as np

FP8 = jnp.float8_e4m3fn


def seed_key(seed):
    """A key from any whole number up to 2**62: PRNGKey alone refuses more
    than 32 signed bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def q8(x):
    """Round to fp8 (e4m3) with one scale per tensor, the gradient passed
    straight through: the control's precision, one step below bfloat16."""
    scale = jnp.max(jnp.abs(jax.lax.stop_gradient(x))) / 448.0 + 1e-30
    q = (x / scale).astype(FP8).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


@jax.jit
def diff_norm(a, b):
    return jnp.linalg.norm((a.astype(jnp.float32)
                            - b.astype(jnp.float32)).reshape(-1))


@jax.jit
def norm(a):
    return jnp.linalg.norm(a.astype(jnp.float32).reshape(-1))


def kept(a):
    """Whether a leaf is handed back whole, for the vector differences: the
    one-dimensional leaves (biases, LayerNorm scales and shifts), on the
    program's side and the reference's alike."""
    return a.ndim == 1


def kept_vectors(tree, scale=1.0):
    """The kept leaves as float64 on the host."""
    return {n: np.asarray(a, np.float64) * scale for n, a in tree.items()
            if kept(a)}


def leaf_changes(params, start_of):
    """|p - p_0| per leaf, and p - p_0 whole for the kept leaves;
    ``start_of(name)`` gives p_0 one leaf at a time."""
    norm, vec = {}, {}
    for n, a in params.items():
        start = start_of(n)
        norm[n] = float(diff_norm(a, start))
        if kept(a):
            vec[n] = np.asarray(a - start, np.float64)
    return {"change_norm": norm, "change_vec": vec}
