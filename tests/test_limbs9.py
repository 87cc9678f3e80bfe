"""f32/MXU limb layer (ops/limbs9) vs exact python-int math.

Every assertion is a bit-exact differential against python integers —
this is what guards the f32-mantissa bound analysis in the module
docstring (and the PRECISION setting of the constant matmuls): any
inexact product/sum shows up as a wrong limb, never as a tolerance.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from fabric_mod_tpu.ops import limbs9 as L

P256_P = 0xFFFFFFFF00000001000000000000000000000000FFFFFFFFFFFFFFFFFFFFFFFF
P256_N = 0xFFFFFFFF00000000FFFFFFFFFFFFFFFFBCE6FAADA7179E84F3B9CAC2FC632551
R = 1 << L.RBITS

FP = L.FieldSpec.make("p256.p", P256_P)
FN = L.FieldSpec.make("p256.n", P256_N)


def rand_ints(rng, n, bound):
    return [rng.randrange(bound) for _ in range(n)]


def batch_limbs(vals):
    """python ints -> (K, n) device-layout f32 limbs."""
    return L.to_device(np.stack([L.int_to_limbs(v) for v in vals]))


def col(arr, i):
    """(K, n) -> python int value of lane i."""
    return L.limbs_to_int(np.asarray(arr)[:, i])


def test_converters_roundtrip(rng):
    for v in rand_ints(rng, 20, 1 << 256):
        assert L.limbs_to_int(L.int_to_limbs(v)) == v
    vals = rand_ints(rng, 64, 1 << 256)
    buf = np.stack([
        np.frombuffer(v.to_bytes(32, "big"), np.uint8) for v in vals])
    lb = L.be_bytes_to_limbs(buf)
    for i, v in enumerate(vals):
        assert L.limbs_to_int(lb[i].astype(np.float32)) == v


def test_mont_mul_matches_int_math(rng):
    for spec, mod in [(FP, P256_P), (FN, P256_N)]:
        a = rand_ints(rng, 32, mod)
        b = rand_ints(rng, 32, mod)
        am, bm = batch_limbs(a), batch_limbs(b)
        out = np.asarray(L.mont_mul(am, bm, spec))
        for i in range(32):
            got = L.limbs_to_int(out[:, i]) % mod
            want = (a[i] * b[i] * pow(R, -1, mod)) % mod
            assert got == want
            # lazy-bound invariant from the module docstring
            assert np.abs(out[:, i]).max() <= 273


def test_mont_sqr_matches_mul(rng):
    a = rand_ints(rng, 16, P256_P)
    am = batch_limbs(a)
    sq = np.asarray(L.canonical(L.mont_sqr(am, FP), FP))
    for i in range(16):
        want = (a[i] * a[i] * pow(R, -1, P256_P)) % P256_P
        assert L.limbs_to_int(sq[:, i]) == want


def test_mont_roundtrip_and_addsub(rng):
    a = rand_ints(rng, 16, P256_P)
    b = rand_ints(rng, 16, P256_P)
    am = L.to_mont(batch_limbs(a), FP)
    bm = L.to_mont(batch_limbs(b), FP)
    back = np.asarray(L.canonical(L.from_mont(am, FP), FP))
    for i in range(16):
        assert L.limbs_to_int(back[:, i]) == a[i]
    s = np.asarray(L.canonical(L.from_mont(L.add(am, bm), FP), FP))
    d = np.asarray(L.canonical(L.from_mont(L.sub(am, bm), FP), FP))
    for i in range(16):
        assert L.limbs_to_int(s[:, i]) == (a[i] + b[i]) % P256_P
        assert L.limbs_to_int(d[:, i]) == (a[i] - b[i]) % P256_P


def test_deep_chain_differential(rng):
    """200 rounds of sqr/add/sub/mul with an int mirror: catches any
    slow drift of the lazy bounds or a single inexact matmul pass."""
    xs = rand_ints(rng, 32, P256_P)
    a = batch_limbs(xs)
    am = L.to_mont(a, FP)
    Rinv = pow(R, -1, P256_P)
    x_dev = am
    x_int = [x * R % P256_P for x in xs]
    for _ in range(200):
        t = L.add(L.mont_sqr(x_dev, FP), L.sub(x_dev, L.mul_small(x_dev, 3)))
        x_dev = L.mont_mul(t, am, FP)
        x_int = [((xi * xi * Rinv - 2 * xi) * (xs[i] * R) * Rinv) % P256_P
                 for i, xi in enumerate(x_int)]
    assert np.abs(np.asarray(x_dev)).max() <= 273
    canon = np.asarray(L.canonical(x_dev, FP))
    for i in range(32):
        assert L.limbs_to_int(canon[:, i]) == x_int[i]


def test_canonical_and_eq_zero(rng):
    vals = [0, 1, P256_P - 1]
    vm = batch_limbs(vals)
    c = np.asarray(L.canonical(vm, FP))
    for i, v in enumerate(vals):
        assert L.limbs_to_int(c[:, i]) == v % P256_P
    multiples = batch_limbs([P256_P, 2 * P256_P])
    assert np.asarray(L.eq_zero(multiples, FP)).all()
    assert not np.asarray(L.eq_zero(batch_limbs([1]), FP)).any()
    neg = L.sub(batch_limbs([1]), batch_limbs([2]))
    c = np.asarray(L.canonical(neg, FP))
    assert L.limbs_to_int(c[:, 0]) == P256_P - 1


def test_pow_and_inverse(rng):
    a = rand_ints(rng, 8, P256_N - 1)
    a = [v + 1 for v in a]
    am = L.to_mont(batch_limbs(a), FN)
    inv = L.inv_mont(am, FN)
    got = np.asarray(L.canonical(L.from_mont(inv, FN), FN))
    for i in range(8):
        assert L.limbs_to_int(got[:, i]) == pow(a[i], -1, P256_N)


def test_bits_le(rng):
    vals = rand_ints(rng, 8, P256_N)
    c = L.canonical(batch_limbs(vals), FN)
    bits = np.asarray(L.bits_le(c))
    for i, v in enumerate(vals):
        want = [(v >> j) & 1 for j in range(256)]
        assert bits[:, i].tolist() == want


def test_mul_small(rng):
    a = rand_ints(rng, 8, P256_P)
    out = L.mul_small(batch_limbs(a), 13)
    got = np.asarray(L.canonical(out, FP))
    for i in range(8):
        assert L.limbs_to_int(got[:, i]) == (13 * a[i]) % P256_P


def _be_bytes_to_limbs_by_bits(buf):
    """The bit-by-bit reference for `be_bytes_to_limbs`: unpack every
    bit, weigh B of them a limb."""
    bits = np.unpackbits(np.asarray(buf, np.uint8)[..., ::-1], axis=-1,
                         bitorder="little")
    pad = np.zeros(bits.shape[:-1] + (L.RBITS - 256,), np.uint8)
    bits = np.concatenate([bits, pad], axis=-1).reshape(
        bits.shape[:-1] + (L.K, L.B))
    return (bits.astype(np.int32) << np.arange(L.B)).sum(-1)


@pytest.mark.parametrize("shape", [(32,), (7, 32), (2048, 32), (3, 2, 32)])
def test_be_bytes_to_limbs_matches_the_bitwise_reference(shape):
    gen = np.random.default_rng(len(shape) * 1000 + shape[0])
    for buf in (gen.integers(0, 256, shape, dtype=np.uint8),
                np.full(shape, 255, np.uint8), np.zeros(shape, np.uint8)):
        got = L.be_bytes_to_limbs(buf)
        assert got.dtype == np.int32 and got.shape == shape[:-1] + (L.K,)
        np.testing.assert_array_equal(got, _be_bytes_to_limbs_by_bits(buf))
