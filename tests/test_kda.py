"""ops/kda.py: the recurrence, the chunked form and the decode kernel
(interpreted) are one function."""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import kda

H, K, V = 4, 8, 16


def _inputs(T, seed=0, decay=1.0, beta_hi=False):
    r = np.random.default_rng(seed)
    n = lambda *s: r.standard_normal(s).astype(np.float32)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    q, k = unit(n(T, H, K)) * K ** -0.5, unit(n(T, H, K))
    g = -decay * np.log1p(np.exp(n(T, H, K)))
    beta = 2.0 / (1.0 + np.exp(-n(T, H)))
    if beta_hi:
        beta = 2.0 - 1e-3 * beta
    return tuple(jnp.asarray(a) for a in (q, k, n(T, H, V), g, beta))


def _close(a, b, tol=2e-5):
    a, b = np.asarray(a), np.asarray(b)
    assert np.max(np.abs(a - b)) <= tol * max(1.0, np.max(np.abs(b)))


@pytest.mark.parametrize("T,chunk", [(48, 16), (37, 16), (20, 64),
                                     (64, 8)])
def test_chunked_is_the_recurrence(T, chunk):
    x = _inputs(T, seed=T)
    o, S = kda.kda_recurrent(*x)
    oc, Sc = kda.kda_chunked(*x, chunk=chunk)
    _close(oc, o)
    _close(Sc, S)


def test_padded_bucket_leaves_the_state_alone():
    x = _inputs(32, seed=3)
    n = 21
    o, S = kda.kda_recurrent(*(a[:n] for a in x))
    oc, Sc = kda.kda_chunked(*x, chunk=8, valid=jnp.arange(32) < n)
    _close(oc[:n], o)
    _close(Sc, S)


@pytest.mark.parametrize("case", ["long_memory", "beta_near_2",
                                  "fast_decay"])
def test_forms_agree_at_the_edges(case):
    kw = {"long_memory": dict(decay=1e-3),
          "beta_near_2": dict(decay=1e-2, beta_hi=True),
          "fast_decay": dict(decay=40.0)}[case]
    x = _inputs(96, seed=7, **kw)
    o, S = kda.kda_recurrent(*x)
    oc, Sc = kda.kda_chunked(*x, chunk=32)
    _close(oc, o, 2e-4)
    _close(Sc, S, 2e-4)
    assert np.isfinite(np.asarray(oc)).all()


def test_chunked_carries_a_state_between_calls():
    x = _inputs(40, seed=5)
    o, S = kda.kda_recurrent(*x)
    o1, S1 = kda.kda_chunked(*(a[:24] for a in x), chunk=8)
    o2, S2 = kda.kda_chunked(*(a[24:] for a in x), chunk=8, state=S1)
    _close(jnp.concatenate([o1, o2]), o)
    _close(S2, S)


@pytest.mark.parametrize("kernel", [False, True])
def test_decode_is_one_step_of_the_recurrence(kernel):
    B, T = 3, 6
    xs = [_inputs(T, seed=10 + b) for b in range(B)]
    active = jnp.asarray([True, False, True])
    S = jnp.stack([kda.kda_recurrent(*(a[:T - 1] for a in x))[1]
                   for x in xs])
    last = [jnp.stack([x[i][T - 1] for x in xs]) for i in range(5)]
    o, S2 = kda.kda_decode(*last, S, active, kernel=kernel)
    for b, x in enumerate(xs):
        want_o, want_S = kda.kda_recurrent(*x)
        if active[b]:
            _close(o[b], want_o[-1])
            _close(S2[b], want_S)
        else:
            assert not np.asarray(o[b]).any()
            np.testing.assert_array_equal(np.asarray(S2[b]),
                                          np.asarray(S[b]))


def test_decode_kernel_with_no_active_slot_changes_nothing():
    x = _inputs(2, seed=1)
    S = jnp.ones((2, H, K, V), jnp.float32)
    o, S2 = kda.kda_decode(*(a for a in x), S, jnp.zeros(2, bool),
                           kernel=True)
    assert not np.asarray(o).any()
    np.testing.assert_array_equal(np.asarray(S2), np.asarray(S))
