"""ops/power_retention.py: the feature map, the three forms of the layer
against each other, the state's layout, and the Pallas decode kernel (in
interpret mode) against ``jax.numpy``.

Long memory is held here: gates are given directly in 0.9-0.999 over
T >= 512, so that the state really accumulates (a model's random gate
projection centres gates on 0.5, a memory a few tokens long).

Tolerances: everything is float32 with highest-precision products, so
the forms differ by rounding in another order. ``RTOL`` is relative to
the largest output: sums of ~500 terms of mixed sign read 1e-5 to 5e-5
between forms; a state rounded to bfloat16 (2^-9 a number) reads over
1e-3 and has to fail (``test_a_bfloat16_state_fails_the_tolerance``).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import power_retention as pr

RTOL = 2e-4


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / np.abs(b).max())


def draw(T, kv=2, rep=5, D=8, seed=0, gates=(0.9, 0.999)):
    rng = np.random.default_rng(seed)
    f = lambda *s: rng.normal(size=s).astype(np.float32)
    lg = np.log(rng.uniform(*gates, size=(T, kv))).astype(np.float32)
    return f(T, kv * rep, D), f(T, kv, D), f(T, kv, D), lg


@pytest.mark.parametrize("D", [2, 8, 16, 128])
def test_phi_is_the_symmetric_square(D):
    rng = np.random.default_rng(D)
    a, b = rng.normal(size=(2, 7, D)).astype(np.float32)
    pa, pb = pr.phi(a), pr.phi(b)
    assert pa.shape == (7, D * (D + 1) // 2) == (7, pr.phi_size(D))
    # exact but for float32 rounding of D(D+1)/2 terms of mixed sign,
    # which is relative to |a|^2 |b|^2 and not to a small (a . b)^2
    err = np.abs((pa * pb).sum(-1) - (a * b).sum(-1) ** 2)
    assert (err <= 1e-6 * (a * a).sum(-1) * (b * b).sum(-1)).all()


def test_phi_needs_an_even_head_size():
    with pytest.raises(ValueError, match="even"):
        pr.phi(np.ones((3, 5), np.float32))


@pytest.mark.parametrize("D", [2, 8, 16])
def test_the_device_layout_is_a_permutation(D):
    rng = np.random.default_rng(1)
    x = rng.normal(size=(3, 2, pr.phi_size(D), D)).astype(np.float32)
    y = np.asarray(pr.state_to_layout(x))
    assert y.shape == x.shape and not np.array_equal(y, x)
    assert sorted(y.ravel()) == sorted(x.ravel())
    assert np.array_equal(pr.state_from_layout(y), x)
    assert np.array_equal(pr.state_to_layout(pr.state_from_layout(x)), x)
    # row o * D + d, lane i of the layout holds pair (o, i), value d
    o, d, i = 1 % max(D // 2, 1), D - 1, 1
    if D > 2:
        assert y[0, 0, o * D + d, i] == x[0, 0, o * D + i, d]


# chunk sizes that do (40, 65) and do not (64, 7) divide T = 520
@pytest.mark.parametrize("chunk,block", [(40, 65), (64, 128), (7, 520)])
def test_recurrent_attention_and_chunked_forms_agree(chunk, block):
    q, k, v, lg = draw(520)
    y_rec, (S_rec, z_rec) = jax.jit(pr.retention_recurrent)(q, k, v, lg)
    y_att = pr.retention_attention(q, k, v, lg, block=block)
    y_chk, (S_chk, z_chk) = pr.retention_chunked(q, k, v, lg, chunk=chunk)
    S_bld, z_bld = pr.retention_state(k, v, lg, chunk=chunk)
    assert rel(y_att, y_rec) < RTOL and rel(y_chk, y_rec) < RTOL
    for S, z in ((S_chk, z_chk), (S_bld, z_bld)):
        assert rel(S, S_rec) < RTOL and rel(z, z_rec) < RTOL
    # the memory is tens of tokens long (a mean gate of 0.95 halves a
    # key's weight in 14 tokens): the last 16 tokens alone are not it
    S_late, _ = pr.retention_state(k[-16:], v[-16:], lg[-16:])
    assert rel(S_late, S_rec) > 0.1


def test_a_bfloat16_state_fails_the_tolerance():
    q, k, v, lg = draw(520, seed=3)
    y, _ = jax.jit(pr.retention_recurrent)(q, k, v, lg)
    _, (S, z) = jax.jit(pr.retention_recurrent)(
        q[:-1], k[:-1], v[:-1], lg[:-1])
    step = lambda S, z: pr.retention_recurrent(
        q[-1:], k[-1:], v[-1:], lg[-1:], state=(S, z))[0]
    assert rel(step(S, z), y[-1:]) < RTOL
    low = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    assert rel(step(low(S), low(z)), y[-1:]) > 5 * RTOL


def test_padding_leaves_the_state_alone():
    q, k, v, lg = draw(96, seed=4)
    n = 70
    valid = np.arange(96) < n
    S, z = pr.retention_state(k, v, lg, valid=valid, chunk=32)
    S_n, z_n = pr.retention_state(k[:n], v[:n], lg[:n], chunk=32)
    assert rel(S, S_n) < 1e-6 and rel(z, z_n) < 1e-6
    y, (S_c, z_c) = pr.retention_chunked(q, k, v, lg, chunk=32,
                                         valid=valid)
    y_n, _ = pr.retention_chunked(q[:n], k[:n], v[:n], lg[:n], chunk=32)
    assert rel(S_c, S_n) < 1e-6 and rel(y[:n], y_n) < 1e-6
    # rows of the A form before the prompt's end do not see the padding
    y_a = pr.retention_attention(q, k, v, lg, block=32)
    assert rel(y_a[:n], y_n) < RTOL


def test_a_state_carries_a_sequence_on():
    q, k, v, lg = draw(128, seed=5)
    y, (S, z) = pr.retention_chunked(q, k, v, lg, chunk=32)
    y1, st = pr.retention_chunked(q[:50], k[:50], v[:50], lg[:50],
                                  chunk=32)
    y2, (S2, z2) = pr.retention_chunked(q[50:], k[50:], v[50:], lg[50:],
                                        chunk=32, state=st)
    assert rel(np.concatenate([y1, y2]), y) < RTOL
    assert rel(S2, S) < RTOL and rel(z2, z) < RTOL


def _slots(B, kv, rep, D, seed):
    rng = np.random.default_rng(seed)
    f = lambda *s: rng.normal(size=s).astype(np.float32)
    P = pr.phi_size(D)
    return (f(B, kv * rep, D), f(B, kv, D), f(B, kv, D),
            np.log(rng.uniform(0.5, 0.999, (B, kv))).astype(np.float32),
            pr.state_to_layout(jnp.asarray(f(B, kv, P, D))),
            jnp.asarray(np.abs(f(B, kv, P))))


@pytest.mark.parametrize("D,active", [
    (8, [True, False, True, True]), (8, [False, False, False, True]),
    (8, [False] * 4), (16, [True] * 4), (2, [True, True, False, True])])
def test_decode_kernel_in_interpret_mode_matches_jax_numpy(D, active):
    q, k, v, lg, S, z = _slots(4, 2, 5, D, seed=D)
    active = jnp.asarray(active)
    y0, S0, z0 = pr.retention_decode(q, k, v, lg, S, z, active,
                                     kernel=False)
    y1, S1, z1 = jax.jit(lambda *a: pr.retention_decode(
        *a, kernel=True))(q, k, v, lg, S, z, active)
    on = np.asarray(active)
    if on.any():
        assert rel(y1, y0) < 1e-5 and rel(S1, S0) < 1e-6
    assert rel(z1, z0) < 1e-6
    # slots that are not active: no output, state untouched to the bit
    for y, Sn, zn in ((y0, S0, z0), (y1, S1, z1)):
        assert not np.asarray(y)[~on].any()
        assert np.array_equal(np.asarray(Sn)[~on], np.asarray(S)[~on])
        assert np.array_equal(np.asarray(zn)[~on], np.asarray(z)[~on])


@pytest.mark.parametrize("kernel", [False, True])
def test_decode_from_a_prefilled_state_is_the_next_recurrent_step(kernel):
    q, k, v, lg = draw(65, seed=6)
    y, (S_end, z_end) = jax.jit(pr.retention_recurrent)(q, k, v, lg)
    S, z = pr.retention_state(k[:-1], v[:-1], lg[:-1], chunk=16)
    # two slots: the sequence, and the same one not active
    two = lambda a: jnp.stack([jnp.asarray(a)] * 2)
    y1, S1, z1 = pr.retention_decode(
        two(q[-1]), two(k[-1]), two(v[-1]), two(lg[-1]),
        pr.state_to_layout(two(S)), two(z), jnp.asarray([True, False]),
        kernel=kernel)
    assert rel(y1[0], y[-1]) < RTOL
    assert rel(pr.state_from_layout(S1)[0], S_end) < RTOL
    assert rel(z1[0], z_end) < RTOL
    assert np.array_equal(pr.state_from_layout(S1)[1], S)
