"""The served expert layer (incubate/moe.route_topk + expert_share over
ops/grouped_matmul): no capacity, a share of the experts held."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.incubate.moe import expert_share, route_topk
from paddle_tpu.ops.grouped_matmul import grouped_matmul

HI = jax.lax.Precision.HIGHEST


def _close(a, b, tol=1e-5):
    a, b = np.asarray(a), np.asarray(b)
    assert np.max(np.abs(a - b)) <= tol * max(1.0, np.max(np.abs(b)))


def _weights(E, D, F, seed=0, dtype=jnp.float32):
    r = np.random.default_rng(seed)
    n = lambda *s: jnp.asarray(r.standard_normal(s).astype(np.float32)
                               / np.sqrt(s[-2])).astype(dtype)
    return n(E, D, F), n(E, D, F), n(E, F, D)


def _swiglu(x, g, u, d):
    mm = lambda a, b: jnp.matmul(a, b.astype(jnp.float32), precision=HI)
    return mm(jax.nn.silu(mm(x, g)) * mm(x, u), d)


def _layer_by_loop(x, w, idx, gate, up, down, first=0):
    """Every chosen expert of a token, one at a time."""
    out = np.zeros(x.shape, np.float32)
    for t in range(x.shape[0]):
        for j in range(idx.shape[1]):
            e = int(idx[t, j]) - first
            if 0 <= e < gate.shape[0]:
                out[t] += float(w[t, j]) * np.asarray(
                    _swiglu(x[t:t + 1], gate[e], up[e], down[e]))[0]
    return out


@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("sizes", [(3, 0, 5, 1), (0, 0, 0, 0),
                                   (120, 0, 20, 9), (40, 40, 40, 40),
                                   (0, 150, 0, 0)])
def test_grouped_product_is_a_loop_over_groups(kernel, sizes):
    """160 rows: two row tiles of the kernel's 128, groups that share a
    tile and a group that spans both; the rows of no group are the
    caller's to mask and are not compared."""
    r = np.random.default_rng(1)
    M, K, N = 160, 16, 24
    lhs = jnp.asarray(r.standard_normal((M, K)).astype(np.float32))
    rhs = jnp.asarray(r.standard_normal((4, K, N)).astype(np.float32))
    out = grouped_matmul(lhs, rhs, jnp.asarray(sizes, jnp.int32),
                         kernel=kernel)
    assert out.shape == (M, N)
    want, o = np.zeros((M, N), np.float32), 0
    for g, n in enumerate(sizes):
        want[o:o + n] = np.asarray(jnp.matmul(lhs[o:o + n], rhs[g],
                                              precision=HI))
        o += n
    if o:
        _close(out[:o], want[:o])


def test_grouped_product_on_bfloat16_matrices_is_exact_in_them():
    r = np.random.default_rng(2)
    lhs = jnp.asarray(r.standard_normal((16, 32)).astype(np.float32))
    rhs = jnp.asarray(r.standard_normal((2, 32, 8))).astype(jnp.bfloat16)
    out = grouped_matmul(lhs, rhs, jnp.asarray([7, 6], jnp.int32),
                         kernel=True)[:13]
    want = jax.lax.ragged_dot(lhs, rhs.astype(jnp.float32),
                              jnp.asarray([7, 6], jnp.int32),
                              precision=HI)[:13]
    _close(out, want, 3e-5)


@pytest.mark.parametrize("kernel", [False, True])
def test_no_token_is_dropped_when_routing_piles_onto_one_expert(kernel):
    T, D, F, E, k = 24, 16, 8, 6, 2
    r = np.random.default_rng(3)
    x = jnp.asarray(r.standard_normal((T, D)).astype(np.float32))
    router = jnp.zeros((D, E)).at[:, 2].set(jnp.sign(x[0]) * 0.0)
    # every token's first choice is expert 2, its second expert 4
    logits_bias = jnp.asarray([0., 0., 9., 0., 5., 0.])
    w, idx = jax.lax.top_k(jax.nn.softmax(
        x @ router + logits_bias, axis=-1), k)
    w = w / w.sum(-1, keepdims=True)
    gate, up, down = _weights(E, D, F, seed=4)
    y, counts = expert_share(x, w, idx.astype(jnp.int32), gate, up,
                             down, 0, kernel=kernel)
    assert list(np.asarray(counts)) == [0, 0, T, 0, T, 0]
    _close(y, _layer_by_loop(x, w, idx, gate, up, down))


def test_router_normalises_over_the_chosen():
    r = np.random.default_rng(5)
    x = jnp.asarray(r.standard_normal((5, 8)).astype(np.float32))
    router = jnp.asarray(r.standard_normal((8, 12)).astype(np.float32))
    w, idx = route_topk(x, router, 3)
    s = np.asarray(jax.nn.softmax(jnp.matmul(x, router, precision=HI)))
    for t in range(5):
        top = np.argsort(-s[t])[:3]
        assert sorted(top) == sorted(np.asarray(idx[t]))
        _close(np.sort(np.asarray(w[t])),
               np.sort(s[t, top] / s[t, top].sum()))


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight chips with five experts each: their routed parts add up to
    what all forty experts give."""
    T, D, F, E, k, chips = 12, 16, 8, 40, 8, 8
    r = np.random.default_rng(6)
    x = jnp.asarray(r.standard_normal((T, D)).astype(np.float32))
    router = jnp.asarray(r.standard_normal((D, E)).astype(np.float32))
    gate, up, down = _weights(E, D, F, seed=7)
    w, idx = route_topk(x, router, k)
    whole = _layer_by_loop(x, w, idx, gate, up, down)
    held = E // chips
    total, parts = np.zeros((T, D), np.float32), 0
    for c in range(chips):
        sl = slice(c * held, (c + 1) * held)
        y, counts = expert_share(x, w, idx, gate[sl], up[sl], down[sl],
                                 c * held)
        total += np.asarray(y)
        parts += int(counts.sum())
    assert parts == T * k
    _close(total, whole)
