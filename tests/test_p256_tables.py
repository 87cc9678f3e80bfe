"""The table program: a lane whose public key has a fixed-base table
is verified from it, 129 additions and no doubling.

Three checks:
  1. the host table (`p256.key_table`) against `_ecfallback`'s point
     arithmetic: entry (i, j) = j * 16**i * Q, and no table for a key
     that is no curve point;
  2. the table program against the ladder AND the software provider,
     bit for bit, on real signatures and on crafted ones that reach
     the corners of the sum: zero windows, a zero scalar, the halves
     equal (the last addition doubles), the halves opposite (the
     identity), the r + n branch;
  3. the fused hash composes with it as with the ladder.
"""
import hashlib
import random

import numpy as np
import pytest

from fabric_mod_tpu.bccsp import _ecfallback as ec
from fabric_mod_tpu.bccsp import sw
from fabric_mod_tpu.bccsp.api import VerifyItem
from fabric_mod_tpu.bccsp.tpu import KEY_SLOTS
from fabric_mod_tpu.ops import limbs9 as limbs, p256

P, N = p256.P, p256.N
G = (p256.GX, p256.GY)
R = 1 << limbs.RBITS
LANES = 8                       # the one batch width compiled here


# --- 1. the host table -----------------------------------------------------

def _entry(tab, i, j):
    """Entry (i, j) of a key table as plain python ints (X, Y, Z)."""
    rinv = pow(R, -1, P)
    return tuple(limbs.limbs_to_int(tab[p256.N_WINDOWS - 1 - i, c, :, j])
                 * rinv % P for c in range(3))


def test_key_table_matches_reference_arithmetic(rng):
    q = ec.point_mul(rng.randrange(1, N), G)
    tab = p256.key_table(*q)
    assert tab.shape == (p256.N_WINDOWS, 3, limbs.K, p256.TABLE)
    assert tab.dtype == np.float32
    assert 0 <= tab.min() and tab.max() < limbs.BASE     # canonical limbs
    for i in range(p256.N_WINDOWS):
        assert _entry(tab, i, 0) == (0, 1, 0)            # the identity
        for j in range(1, p256.TABLE):
            x, y = ec.point_mul(j * 16 ** i, q)
            assert _entry(tab, i, j) == (x, y, 1), (i, j)


def test_g_fixed_table_holds_the_ladders_constant():
    """Position 16**0 of G's fixed-base table is `_g_table`, the
    constant the ladder selects from."""
    np.testing.assert_array_equal(
        p256._g_fixed_table()[-1], p256._g_table().transpose(0, 2, 1))


@pytest.mark.parametrize("why", ["identity_encoding", "off_curve",
                                 "x_out_of_range", "y_out_of_range"])
def test_no_table_for_a_key_that_is_no_point(why, rng):
    x, y = ec.point_mul(rng.randrange(1, N), G)
    key = {"identity_encoding": (0, 0), "off_curve": (x, y ^ 1),
           "x_out_of_range": (x + P, y), "y_out_of_range": (x, y + P)}[why]
    assert p256.key_table(*key) is None


# --- 2. the program against the ladder and the software provider ----------

def _neg(pt):
    return None if pt is None else (pt[0], (P - pt[1]) % P)


def _craft(u1, u2, d=None, point=None, r=None):
    """A (digest, r, s, Q) whose verification computes exactly
    u1*G + u2*Q: Q = d*G, or, for a chosen sum `point`, the Q that
    makes it so.  r is the sum's x mod n unless given (the identity
    has no x)."""
    if point is not None:
        q = ec.point_mul(pow(u2, -1, N),
                         ec.point_add(point, _neg(ec.point_mul(u1, G))))
    else:
        q = ec.point_mul(d, G)
        point = ec.point_add(ec.point_mul(u1, G), ec.point_mul(u2, q))
    if r is None:
        r = point[0] % N
    s = r * pow(u2, -1, N) % N
    return u1 * s % N, r, s, q


def _low_s(make):
    """The first crafted signature whose s the low-S rule admits (so
    that the provider level can be asked too): `make` draws anew."""
    while True:
        case = make()
        if 0 < case[2] <= N // 2:
            return case


def _point_with_x_at_least_n():
    """A curve point whose x lies in [n, p): r = x - n is the rare
    signature that verifies on the r + n branch only."""
    x = N
    while True:
        rhs = (x * x * x - 3 * x + p256.B) % P
        y = pow(rhs, (P + 1) // 4, P)
        if y * y % P == rhs:
            return (x, y)
        x += 1


def _cases():
    """(name, digest int, r, s, (qx, qy), expected verdict at the math
    level).  Every s but `high_s`'s is low, so the software provider
    can be asked too."""
    rng = random.Random(0x7AB1E5)
    csp = sw.SwCSP()
    out = []

    def real(name, mutate=None, ok=True):
        key = csp.key_gen("P256")
        e = int.from_bytes(hashlib.sha256(name.encode()).digest(), "big")
        r, s = sw.decode_dss_signature(csp.sign(key, e.to_bytes(32, "big")))
        xy = key.public_xy()
        case = [e, r, s, (int.from_bytes(xy[:32], "big"),
                          int.from_bytes(xy[32:], "big"))]
        if mutate:
            mutate(case)
        out.append((name, *case, ok))

    def crafted(name, make, ok=True):
        out.append((name, *_low_s(make), ok))

    def scalar():
        return rng.randrange(1, N)

    real("valid")
    real("valid_again")
    real("tampered_digest", lambda c: c.__setitem__(0, c[0] ^ 2), ok=False)
    real("high_s", lambda c: c.__setitem__(2, N - c[2]))   # valid math
    real("r_tampered", lambda c: c.__setitem__(1, c[1] ^ 1), ok=False)
    real("r_is_n", lambda c: c.__setitem__(1, N), ok=False)
    real("s_is_zero", lambda c: c.__setitem__(2, 0), ok=False)
    real("s_is_n", lambda c: c.__setitem__(2, N), ok=False)
    real("off_curve_key",
         lambda c: c.__setitem__(3, (c[3][0], c[3][1] ^ 1)), ok=False)
    real("identity_key", lambda c: c.__setitem__(3, (0, 0)), ok=False)
    real("key_out_of_range",
         lambda c: c.__setitem__(3, (c[3][0], P)), ok=False)
    sparse = [sum(rng.randrange(1, 16) << (4 * i) for i in at)
              for at in ((0, 17, 40, 63), (1, 18, 41, 62))]
    crafted("zero_windows", lambda: _craft(*sparse, d=scalar()))
    crafted("one_window_each", lambda: _craft(1 << 252, 15, d=scalar()))
    # u1 = 0 (the digest is 0): the G half adds the identity 64 times
    crafted("zero_digest", lambda: _craft(0, scalar(), d=scalar()))

    def halves(sign, **kw):
        d, u2 = scalar(), scalar()
        return _craft(sign * u2 * d % N, u2, d=d, **kw)
    # u1*G == u2*Q: the last addition meets equal operands
    crafted("halves_equal", lambda: halves(1))
    # u1*G == -(u2*Q): the sum is the identity, Z = 0
    crafted("halves_opposite", lambda: halves(-1, r=7), ok=False)
    # x of the sum is r + n: accepted on the second comparison only
    high = _point_with_x_at_least_n()
    crafted("r_plus_n", lambda: _craft(scalar(), scalar(), point=high))
    e, r, s, q = _low_s(lambda: _craft(scalar(), scalar(), point=high))
    out.append(("r_plus_n_other_digest", e ^ 1, r, s, q, False))
    return out


def _bytes32(v):
    return np.frombuffer((v % (1 << 256)).to_bytes(32, "big"), np.uint8)


@pytest.fixture(scope="module")
def cases():
    return _cases()


def _planes(chunk):
    d, r, s, qx, qy = ([] for _ in range(5))
    for _name, e, ri, si, (x, y), _ok in chunk:
        for plane, v in zip((d, r, s, qx, qy), (e, ri, si, x, y)):
            plane.append(_bytes32(v))
    pad = LANES - len(chunk)
    return tuple(np.pad(np.stack(p), ((0, pad), (0, 0)))
                 for p in (d, r, s, qx, qy))


def _by_tables(d, r, s, qx, qy):
    """The table program over planes: a table a distinct key, at
    scattered slots; a key that is no point gets slot_ok False."""
    host = p256.empty_key_tables(KEY_SLOTS)
    slot_of, slot, slot_ok = {}, [], []
    for x, y in zip(qx, qy):
        key = (int.from_bytes(bytes(x), "big"), int.from_bytes(bytes(y), "big"))
        if key not in slot_of:
            at = (7 * len(slot_of) + 3) % KEY_SLOTS
            table = p256.key_table(*key)
            if table is not None:
                host[..., at * p256.TABLE:(at + 1) * p256.TABLE] = table
            slot_of[key] = (at, table is not None)
        slot.append(slot_of[key][0])
        slot_ok.append(slot_of[key][1])
    return p256.batch_verify_tables(
        d, r, s, np.array(slot, np.int32), np.array(slot_ok, bool),
        p256.place_key_tables(host))


@pytest.mark.parametrize("part", [0, 1, 2])
def test_table_program_matches_ladder_and_software(cases, part):
    chunk = cases[part * LANES:(part + 1) * LANES]
    assert chunk, "a part with no case"
    planes = _planes(chunk)
    by_ladder = p256.batch_verify(*planes)[:len(chunk)]
    by_tables = _by_tables(*planes)[:len(chunk)]
    names = [c[0] for c in chunk]
    expect = [c[5] for c in chunk]
    assert dict(zip(names, by_tables)) == dict(zip(names, by_ladder)) \
        == dict(zip(names, expect))
    # the software provider (OpenSSL), on what it can be asked: DER
    # cannot carry a scalar past 2**256, and it refuses high-S
    csp = sw.SwCSP()
    for (name, e, r, s, (x, y), ok), got in zip(chunk, by_tables):
        if name == "high_s":
            continue
        item = VerifyItem(e.to_bytes(32, "big"),
                          sw.encode_dss_signature(r, s),
                          x.to_bytes(32, "big") + y.to_bytes(32, "big"))
        assert csp.verify_batch([item]) == [bool(got)], name


def test_every_corner_is_among_the_cases(cases):
    names = [c[0] for c in cases]
    assert len(set(names)) == len(names) and len(names) <= 3 * LANES
    assert {"tampered_digest", "high_s", "r_is_n", "s_is_zero",
            "off_curve_key", "identity_key", "zero_windows", "zero_digest",
            "halves_equal", "halves_opposite", "r_plus_n"} <= set(names)
    # the crafted signatures are what they claim
    by = {c[0]: c for c in cases}
    _, e, r, s, q, _ = by["r_plus_n"]
    w = pow(s, -1, N)
    total = ec.point_add(ec.point_mul(e * w % N, G),
                         ec.point_mul(r * w % N, q))
    assert total[0] == r + N and r < P - N
    _, e, r, s, q, _ = by["halves_opposite"]
    w = pow(s, -1, N)
    assert ec.point_add(ec.point_mul(e * w % N, G),
                        ec.point_mul(r * w % N, q)) is None
    _, e, r, s, q, _ = by["halves_equal"]
    w = pow(s, -1, N)
    assert ec.point_mul(e * w % N, G) == ec.point_mul(r * w % N, q)
    assert all(c[3] <= N // 2 or c[0] in ("high_s", "s_is_n") for c in cases)


# --- 3. the fused hash -----------------------------------------------------

def test_fused_hash_composes_with_the_table_program():
    from fabric_mod_tpu.bccsp import der
    csp = sw.SwCSP()
    key = csp.key_gen("P256")
    msgs = [b"alpha" * 9, b"beta", b"gamma" * 40, b"delta"]
    sigs = [csp.sign(key, hashlib.sha256(m).digest()) for m in msgs]
    msgs[1] += b"!"                                  # tampered message
    r, s, der_ok = der.decode_der_batch(sigs, LANES)
    assert der_ok[:len(msgs)].all()
    words, nblocks, _ = der.pack_messages(msgs, LANES, round_blocks_pow2=True)
    has_msg = np.arange(LANES) < 3                   # lane 3: pre-digested
    nblocks = np.where(has_msg, nblocks, 0).astype(np.int32)
    d = np.zeros((LANES, 32), np.uint8)
    d[3] = np.frombuffer(hashlib.sha256(msgs[3]).digest(), np.uint8)
    xy = key.public_xy()
    host = p256.empty_key_tables(KEY_SLOTS)
    host[..., 5 * p256.TABLE:6 * p256.TABLE] = p256.key_table(
        int.from_bytes(xy[:32], "big"), int.from_bytes(xy[32:], "big"))
    got = p256.batch_verify_tables(
        d, r, s, np.full(LANES, 5, np.int32), np.arange(LANES) < 4,
        p256.place_key_tables(host), msg=(words, nblocks, has_msg))
    assert list(got) == [True, False, True, True] + [False] * 4
