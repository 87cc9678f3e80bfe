"""Batched ECDSA-P256 verification on TPU.

The TPU-native replacement for the reference's per-signature software
verify (reference: bccsp/sw/ecdsa.go:41-57 ``verifyECDSA`` and the
dispatch in bccsp/sw/impl.go:247): instead of one goroutine per
signature behind a semaphore (core/committer/txvalidator/v20/
validator.go:194-239), the whole block's (digest, r, s, pubkey) tuples
become device arrays and one jitted program verifies them all.

Field arithmetic is the f32/MXU limb layer (ops/limbs9.py): radix-2^9
limbs with the limb axis FIRST — (K, batch) arrays — so element-wise
work fills all vector lanes and the schoolbook/Montgomery folds run as
constant matmuls on the MXU.

Point arithmetic uses the Renes-Costello-Batina *complete* projective
addition formulas for a=-3 short Weierstrass curves (eprint 2015/1060,
algorithms 4 and 6).  Complete formulas are the TPU-idiomatic choice:
they are branch-free — identity, doubling, and inverse cases all fall
out of the same straight-line code — so a batch never diverges and XLA
sees one fused SIMD program.

Scalar multiplication u1*G + u2*Q has two programs, and which one a
lane takes is decided by what is known of its public key, never by a
knob (bccsp/tpu.py keeps the tables and makes the choice):

* **The table program** (`verify_core_tables`): a permissioned
  ledger's signers repeat (a block of 1,500-3,000 signatures carries
  five or six distinct keys), so the provider keeps, for each key seen
  lately, the host-built fixed-base table [j * 16**i * Q] (i = 0..63,
  j = 0..15; `key_table`) in one device array of a fixed number of
  slots.  G has the same table as a program constant.  The sum is then
  64 scan steps of ONE complete addition over 2 x batch lanes (the G
  half and the Q half accumulate side by side: G's entry by a constant
  one-hot matmul, the lane's entry of its slot's table by a one-hot
  over slot x 16) and one last addition of the two halves: 65 complete
  additions deep, 129 a lane, and NO doubling.  The key's curve check
  is the table's build, once a key.
* **The Shamir ladder** (`verify_core`), for a lane whose key has no
  table (more new keys in one batch than the provider's budget, a key
  met once while every slot is taken, a mesh-sharded batch): 64 steps of 4 doublings + two table-adds, the
  16-entry G table a host constant and the 16-entry Q table built on
  the device in every call.

Montgomery multiplications a lane (each one schoolbook fold, two
constant matmuls, three carry passes), by reading the code:

    ladder   64 x (4 x 13 + 2 x 14) = 5,120, the Q table 189,
             s^-1 mod n 512, curve check and conversions ~15:  ~5,836
    tables   129 x 14 = 1,806, s^-1 mod n 512, conversions ~10: ~2,328
             (sequentially 65 x 14 + 512 + 10 = 1,432 deep)

Both share the scalar prologue (`_scalar_windows`), `point_add` and
the final comparison (`_accept`), which avoids an inversion: accept
iff X == (r + k*n)*Z (mod p) for k in {0, 1} (with r + k*n < p),
Z != 0.  Complete additions make the table sum the same group element
as the ladder's for every input, so the verdicts are bit for bit the
same.

Two ladder variants share the ladder's schedule: the original
all-projective `shamir_ladder` (complete addition, alg. 4) and the
affine-table `shamir_ladder_mixed` (complete MIXED addition, alg. 5,
with the Q table normalized by one Montgomery simultaneous inversion) —
selectable via FABRIC_MOD_TPU_MIXED_ADD, differentially tested to
produce identical verdicts.  The table program has one form.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from fabric_mod_tpu.ops import limbs9 as limbs
from fabric_mod_tpu.ops.limbs9 import (
    FieldSpec, K, add, sub, mont_mul, mont_sqr, to_mont, eq_zero,
    mul_small, canonical, bits_le, inv_mont, inv_mont_many,
    be_bytes_to_limbs, const_like, const_dot,
)

WINDOW = 4                     # Shamir ladder window width (bits)
N_WINDOWS = 256 // WINDOW
TABLE = 1 << WINDOW

# --- Curve constants (NIST P-256 / secp256r1) ------------------------------
P = 0xFFFFFFFF00000001000000000000000000000000FFFFFFFFFFFFFFFFFFFFFFFF
N = 0xFFFFFFFF00000000FFFFFFFFFFFFFFFFBCE6FAADA7179E84F3B9CAC2FC632551
B = 0x5AC635D8AA3A93E7B3EBBD55769886BC651D06B0CC53B0F63BCE3C3E27D2604B
GX = 0x6B17D1F2E12C4247F8BCE6E563A440F277037D812DEB33A0F4A13945D898C296
GY = 0x4FE342E2FE1A7F9B8EE7EB4A7C0F9E162BCE33576B315ECECBB6406837BF51F5


def _affine_add(p1, p2):
    """Host-side python-int affine addition (build-time table precompute
    only — never on the hot path)."""
    if p1 is None:
        return p2
    if p2 is None:
        return p1
    x1, y1 = p1
    x2, y2 = p2
    if x1 == x2 and (y1 + y2) % P == 0:
        return None
    if p1 == p2:
        lam = (3 * x1 * x1 - 3) * pow(2 * y1, -1, P) % P
    else:
        lam = (y2 - y1) * pow(x2 - x1, -1, P) % P
    x3 = (lam * lam - x1 - x2) % P
    y3 = (lam * (x1 - x3) - y1) % P
    return (x3, y3)


@functools.lru_cache(maxsize=None)
def _consts():
    """Field specs and Montgomery-domain curve params.

    numpy (not jnp) on purpose — may be first called under a jit trace,
    and caching jnp values there would cache tracers.
    """
    fp = FieldSpec.make("p256.p", P)
    fn = FieldSpec.make("p256.n", N)
    R = 1 << limbs.RBITS
    b_m = limbs.int_to_limbs((B * R) % P)
    gx_m = limbs.int_to_limbs((GX * R) % P)
    gy_m = limbs.int_to_limbs((GY * R) % P)
    return fp, fn, b_m, gx_m, gy_m


@functools.lru_cache(maxsize=None)
def _g_table():
    """(3, TABLE, K) numpy constants: projective Montgomery-domain
    multiples [inf, G, 2G, ..., 15G] of the fixed base point, shared by
    every batch lane of the windowed ladder (the base point is a curve
    constant — unlike the per-signature Q table built on device)."""
    R = 1 << limbs.RBITS
    one_m = limbs.int_to_limbs(R % P)
    zero = np.zeros(K, np.float32)
    xs, ys, zs = [zero], [one_m.copy()], [np.zeros(K, np.float32)]
    acc = None
    for _ in range(1, TABLE):
        acc = _affine_add(acc, (GX, GY))
        xs.append(limbs.int_to_limbs(acc[0] * R % P))
        ys.append(limbs.int_to_limbs(acc[1] * R % P))
        zs.append(one_m.copy())
    return np.stack([np.stack(xs), np.stack(ys), np.stack(zs)])


# --- Fixed-base tables (host, python ints; once a key) ---------------------

def _jac_double(X, Y, Z):
    """Jacobian doubling, a = -3 (never the identity here)."""
    yy = Y * Y % P
    s = 4 * X * yy % P
    zz = Z * Z % P
    m = 3 * (X - zz) * (X + zz) % P
    X3 = (m * m - 2 * s) % P
    return X3, (m * (s - X3) - 8 * yy * yy) % P, 2 * Y * Z % P


def _jac_add_affine(X1, Y1, Z1, x2, y2):
    """Jacobian + affine, for operands that are neither equal nor
    opposite (every pair `key_table` adds is k*B + B with k = 2, 4,
    .., 14 and B of prime order)."""
    zz = Z1 * Z1 % P
    h = (x2 * zz - X1) % P
    r = (y2 * zz * Z1 - Y1) % P
    hh = h * h % P
    hhh = hh * h % P
    v = X1 * hh % P
    X3 = (r * r - hhh - 2 * v) % P
    return X3, (r * (v - X3) - Y1 * hhh) % P, Z1 * h % P


def _to_affine(points):
    """Jacobian points -> affine with ONE modular inversion
    (Montgomery's trick); no point may be the identity."""
    prefix, acc = [], 1
    for _, _, z in points:
        prefix.append(acc)
        acc = acc * z % P
    inv = pow(acc, -1, P)
    out = [None] * len(points)
    for i in range(len(points) - 1, -1, -1):
        x, y, z = points[i]
        zi = inv * prefix[i] % P
        inv = inv * z % P
        zi2 = zi * zi % P
        out[i] = (x * zi2 % P, y * zi2 * zi % P)
    return out


def key_table(x: int, y: int):
    """The fixed-base table of the affine point Q = (x, y):
    (N_WINDOWS, 3, K, TABLE) f32, entry [t, :, :, j] the projective
    Montgomery-domain point j * 16**(N_WINDOWS - 1 - t) * Q (position
    axis MSB first, as `_scalar_windows` orders its windows), entry
    j = 0 the identity (0 : 1 : 0) as `_g_table` encodes it, Z = 1
    elsewhere, limbs canonical.  With it u * Q is 64 additions and no
    doubling.

    None where Q is not a point of the curve (a coordinate out of
    range, off the curve, the (0, 0) encoding): what `on_curve` and
    the (0, 0) test decide in the ladder is decided here, once a key.
    Every scalar j * 16**i is under the (prime) group order, so no
    entry is the identity and no sum meets an exceptional case.
    ~16 ms of host a key (python ints, one batched inversion a pass).
    """
    if not (0 <= x < P and 0 <= y < P
            and (y * y - (x * x * x - 3 * x + B)) % P == 0):
        return None
    bases = [(x, y, 1)]
    for _ in range(N_WINDOWS - 1):
        pt = bases[-1]
        for _ in range(WINDOW):
            pt = _jac_double(*pt)
        bases.append(pt)
    rows = []
    for bx, by in _to_affine(bases):                 # 16**i * Q
        row = [None, (bx, by, 1)]
        for j in range(2, TABLE):
            row.append(_jac_double(*row[j // 2]) if j % 2 == 0
                       else _jac_add_affine(*row[j - 1], bx, by))
        rows.extend(row[1:])
    R = 1 << limbs.RBITS
    raw = b"".join((v * R % P).to_bytes(32, "big")
                   for pt in _to_affine(rows) for v in pt)
    xy = be_bytes_to_limbs(np.frombuffer(raw, np.uint8).reshape(
        N_WINDOWS, TABLE - 1, 2, 32))                # (NW, 15, 2, K)
    one_m = limbs.int_to_limbs(R % P)
    tab = np.zeros((N_WINDOWS, 3, K, TABLE), np.float32)
    tab[:, 1, :, 0] = one_m
    tab[:, :2, :, 1:] = xy.transpose(0, 2, 3, 1)
    tab[:, 2, :, 1:] = one_m[:, None]
    return np.ascontiguousarray(tab[::-1])


def empty_key_tables(slots: int) -> np.ndarray:
    """The host array `slots` key tables live in, side by side on the
    last axis: slot k is [..., k * TABLE:(k + 1) * TABLE]."""
    return np.zeros((N_WINDOWS, 3, K, slots * TABLE), np.float32)


def place_key_tables(host: np.ndarray):
    """A device array of the host tables as they stand.  The transfer
    gets a copy of its own: the caller goes on writing slots into
    `host`, and a buffer a transfer may still be reading is never
    written (bccsp/tpu.marshal_items' note)."""
    return jnp.asarray(host.copy())


@functools.lru_cache(maxsize=None)
def _g_fixed_table():
    """`key_table` of the base point: a constant of the table program."""
    return key_table(GX, GY)


# --- Complete projective point addition (RCB alg. 4/6, a = -3) -------------

def point_add(p1, p2, fp: FieldSpec, b_m: jnp.ndarray):
    """Complete addition of projective points (X:Y:Z), Montgomery domain.

    Valid for ALL inputs on the (prime-order) curve, including P == Q,
    P == -Q, and either operand at infinity (0:1:0).  Arrays are
    (K, ...batch); `b_m` must already be rank-matched (const_like).
    12 muls + 2 muls-by-b; every add/sub re-normalises limbs so lazy
    value bounds stay far inside limbs9's 2**260 domain.
    """
    X1, Y1, Z1 = p1
    X2, Y2, Z2 = p2
    t0 = mont_mul(X1, X2, fp)
    t1 = mont_mul(Y1, Y2, fp)
    t2 = mont_mul(Z1, Z2, fp)
    t3 = add(X1, Y1)
    t4 = add(X2, Y2)
    t3 = mont_mul(t3, t4, fp)
    t4 = add(t0, t1)
    t3 = sub(t3, t4)
    t4 = add(Y1, Z1)
    X3 = add(Y2, Z2)
    t4 = mont_mul(t4, X3, fp)
    X3 = add(t1, t2)
    t4 = sub(t4, X3)
    X3 = add(X1, Z1)
    Y3 = add(X2, Z2)
    X3 = mont_mul(X3, Y3, fp)
    Y3 = add(t0, t2)
    Y3 = sub(X3, Y3)
    Z3 = mont_mul(b_m, t2, fp)
    X3 = sub(Y3, Z3)
    Z3 = add(X3, X3)
    X3 = add(X3, Z3)
    Z3 = sub(t1, X3)
    X3 = add(t1, X3)
    Y3 = mont_mul(b_m, Y3, fp)
    t1 = add(t2, t2)
    t2 = add(t1, t2)
    Y3 = sub(Y3, t2)
    Y3 = sub(Y3, t0)
    t1 = add(Y3, Y3)
    Y3 = add(t1, Y3)
    t1 = add(t0, t0)
    t0 = add(t1, t0)
    t0 = sub(t0, t2)
    t1 = mont_mul(t4, Y3, fp)
    t2 = mont_mul(t0, Y3, fp)
    Y3 = mont_mul(X3, Z3, fp)
    Y3 = add(Y3, t2)
    X3 = mont_mul(t3, X3, fp)
    X3 = sub(X3, t1)
    Z3 = mont_mul(t4, Z3, fp)
    t1 = mont_mul(t3, t0, fp)
    Z3 = add(Z3, t1)
    return (X3, Y3, Z3)


def point_add_mixed(p1, p2, fp: FieldSpec, b_m: jnp.ndarray):
    """Complete MIXED addition (RCB alg. 5, a = -3): p1 projective,
    p2 AFFINE (Z2 = 1 implicit), Montgomery domain.

    Algorithm 4 with Z2 = 1 substituted: t2 degenerates to Z1 and the
    three rank-1 cross products collapse (t4 = Y2*Z1 + Y1, the X-plane
    twin = X2*Z1 + X1), dropping the Z1*Z2 multiply — 11 muls + 2
    muls-by-b vs the full add's 12 + 2, and table entries need no Z
    plane at all (2/3 of the one-hot select bandwidth).  Complete for
    every projective p1 ON THE CURVE including infinity and p1 == ±p2;
    p2 cannot encode infinity — callers select around zero windows
    (see shamir_ladder_mixed).
    """
    X1, Y1, Z1 = p1
    X2, Y2 = p2
    t0 = mont_mul(X1, X2, fp)
    t1 = mont_mul(Y1, Y2, fp)
    t3 = add(X2, Y2)
    t4 = add(X1, Y1)
    t3 = mont_mul(t3, t4, fp)
    t4 = add(t0, t1)
    t3 = sub(t3, t4)
    t4 = mont_mul(Y2, Z1, fp)
    t4 = add(t4, Y1)
    Y3 = mont_mul(X2, Z1, fp)
    Y3 = add(Y3, X1)
    Z3 = mont_mul(b_m, Z1, fp)
    X3 = sub(Y3, Z3)
    Z3 = add(X3, X3)
    X3 = add(X3, Z3)
    Z3 = sub(t1, X3)
    X3 = add(t1, X3)
    Y3 = mont_mul(b_m, Y3, fp)
    t1 = add(Z1, Z1)
    t2 = add(t1, Z1)
    Y3 = sub(Y3, t2)
    Y3 = sub(Y3, t0)
    t1 = add(Y3, Y3)
    Y3 = add(t1, Y3)
    t1 = add(t0, t0)
    t0 = add(t1, t0)
    t0 = sub(t0, t2)
    t1 = mont_mul(t4, Y3, fp)
    t2 = mont_mul(t0, Y3, fp)
    Y3 = mont_mul(X3, Z3, fp)
    Y3 = add(Y3, t2)
    X3 = mont_mul(t3, X3, fp)
    X3 = sub(X3, t1)
    Z3 = mont_mul(t4, Z3, fp)
    t1 = mont_mul(t3, t0, fp)
    Z3 = add(Z3, t1)
    return (X3, Y3, Z3)


def point_double(p, fp: FieldSpec, b_m: jnp.ndarray):
    """Complete projective doubling (RCB alg. 6, a = -3), Montgomery
    domain.  Valid for ALL curve points including infinity.  3 squarings
    + 8 muls + 2 muls-by-b — ~20% cheaper than doubling through the
    generic complete addition."""
    X, Y, Z = p
    t0 = mont_sqr(X, fp)
    t1 = mont_sqr(Y, fp)
    t2 = mont_sqr(Z, fp)
    t3 = mont_mul(X, Y, fp)
    t3 = add(t3, t3)
    Z3 = mont_mul(X, Z, fp)
    Z3 = add(Z3, Z3)
    Y3 = mont_mul(b_m, t2, fp)
    Y3 = sub(Y3, Z3)
    X3 = add(Y3, Y3)
    Y3 = add(X3, Y3)
    X3 = sub(t1, Y3)
    Y3 = add(t1, Y3)
    Y3 = mont_mul(X3, Y3, fp)
    X3 = mont_mul(X3, t3, fp)
    t3 = add(t2, t2)
    t2 = add(t2, t3)
    Z3 = mont_mul(b_m, Z3, fp)
    Z3 = sub(Z3, t2)
    Z3 = sub(Z3, t0)
    t3 = add(Z3, Z3)
    Z3 = add(Z3, t3)
    t3 = add(t0, t0)
    t0 = add(t3, t0)
    t0 = sub(t0, t2)
    t0 = mont_mul(t0, Z3, fp)
    Y3 = add(Y3, t0)
    t0 = mont_mul(Y, Z, fp)
    t0 = add(t0, t0)
    Z3 = mont_mul(t0, Z3, fp)
    X3 = sub(X3, Z3)
    Z3 = mont_mul(t0, t1, fp)
    Z3 = add(Z3, Z3)
    Z3 = add(Z3, Z3)
    return (X3, Y3, Z3)


def infinity(shape_suffix) -> tuple:
    """The projective identity (0 : 1 : 0), (K, *shape_suffix) arrays."""
    fp, _, _, _, _ = _consts()
    zero = jnp.zeros((K,) + tuple(shape_suffix), jnp.float32)
    one = jnp.broadcast_to(
        jnp.asarray(fp.one_mont).reshape((K,) + (1,) * len(shape_suffix)),
        (K,) + tuple(shape_suffix)).astype(jnp.float32)
    return (zero, one, zero)


def on_curve(xm: jnp.ndarray, ym: jnp.ndarray) -> jnp.ndarray:
    """y^2 == x^3 - 3x + b (mod p) for Montgomery-domain affine coords."""
    fp, _, b_m, _, _ = _consts()
    y2 = mont_sqr(ym, fp)
    x2 = mont_sqr(xm, fp)
    x3 = mont_mul(x2, xm, fp)
    rhs = add(sub(x3, mul_small(xm, 3)), const_like(b_m, xm))
    return eq_zero(sub(y2, rhs), fp)


# --- The jitted verify core ------------------------------------------------

def build_q_table(q1, inf_pt, fp: FieldSpec, b_m):
    """[inf, Q, 2Q, ..., 15Q] as a list of projective points — the
    per-lane window table schedule (7 doublings + 7 additions),
    shared by the XLA ladder and the Pallas kernel so the two can
    never diverge."""
    qtab = [inf_pt, q1]
    for i in range(2, TABLE):
        if i % 2 == 0:
            qtab.append(point_double(qtab[i // 2], fp, b_m))
        else:
            qtab.append(point_add(qtab[i - 1], q1, fp, b_m))
    return qtab


def shamir_ladder(u1_w: jnp.ndarray, u2_w: jnp.ndarray,
                  qx_m: jnp.ndarray, qy_m: jnp.ndarray):
    """The windowed Shamir ladder: u1*G + u2*Q from MSB-first window
    values (N_WINDOWS, batch) and the Montgomery-domain affine key.
    Returns the projective (X, Y, Z).  This is the dominant cost of a
    verify; ops/p256_pallas.py provides a VMEM-fused drop-in."""
    fp, _fn, b_m_np, _, _ = _consts()
    batch = qx_m.shape[1:]
    b_m = const_like(b_m_np, qx_m)

    qtab = build_q_table((qx_m, qy_m, infinity(batch)[1]),
                         infinity(batch), fp, b_m)
    q_table = tuple(
        jnp.stack([pt[c] for pt in qtab], axis=0)    # (TABLE, K, batch)
        for c in range(3))
    g_tab_np = _g_table()                            # (3, TABLE, K)

    # MSB -> LSB: per step WINDOW doublings, one add from each table
    # (complete addition absorbs the zero-window infinity entries
    # branch-free).
    sel_seq = jnp.stack([u1_w, u2_w], axis=1)        # (NW, 2, batch)

    def step(acc, w2):
        # WINDOW doublings as a fori_loop: the traced scan body holds
        # ONE doubling instead of WINDOW unrolled copies — measurably
        # faster XLA compiles with identical math.
        acc = jax.lax.fori_loop(
            0, WINDOW, lambda _i, a: point_double(a, fp, b_m), acc)
        # Q-table select: one-hot reduce over the per-lane tables (VPU).
        oh_q = jax.nn.one_hot(w2[1], TABLE, dtype=jnp.float32, axis=0)
        acc = point_add(acc, tuple(
            jnp.sum(oh_q[:, None] * q_table[c], axis=0)
            for c in range(3)), fp, b_m)
        # G-table select: constant table -> one-hot matmul (MXU).
        # const_dot, NOT a bare tensordot: table limbs reach 511 and
        # would be rounded by the TPU's default bf16 matmul precision.
        oh_g = jax.nn.one_hot(w2[0], TABLE, dtype=jnp.float32, axis=0)
        acc = point_add(acc, tuple(
            const_dot(g_tab_np[c].T, oh_g)
            for c in range(3)), fp, b_m)
        return acc, None

    acc, _ = jax.lax.scan(step, infinity(batch), sel_seq)
    return acc


@functools.lru_cache(maxsize=None)
def _g_table_affine():
    """(2, TABLE-1, K) numpy constants: AFFINE Montgomery-domain
    multiples [G, 2G, ..., 15G] — no Z plane, no infinity entry (the
    zero window is handled by the mixed ladder's keep-select)."""
    R = 1 << limbs.RBITS
    xs, ys = [], []
    acc = None
    for _ in range(1, TABLE):
        acc = _affine_add(acc, (GX, GY))
        xs.append(limbs.int_to_limbs(acc[0] * R % P))
        ys.append(limbs.int_to_limbs(acc[1] * R % P))
    return np.stack([np.stack(xs), np.stack(ys)])


def build_q_table_affine(qx_m, qy_m, fp: FieldSpec, b_m):
    """[Q, 2Q, ..., 15Q] as AFFINE Montgomery-domain (x, y) pairs.

    Built through the shared projective schedule (build_q_table) and
    normalized with ONE batched Montgomery simultaneous inversion
    (limbs9.inv_mont_many) — 1 Fermat inversion + 3(TABLE-2) muls for
    the whole table instead of one inversion per entry.  All 128
    table-adds of the ladder then take the cheaper mixed formula and
    the one-hot selects move two planes instead of three.

    Lanes whose key is invalid (off-curve / (0,0)) can hit Z = 0 in
    the schedule; the simultaneous inversion then zeroes that LANE's
    whole table — harmless, those lanes are masked by key_ok.
    """
    batch = qx_m.shape[1:]
    inf_pt = infinity(batch)
    qtab = build_q_table((qx_m, qy_m, inf_pt[1]), inf_pt, fp, b_m)[1:]
    zinv = inv_mont_many([pt[2] for pt in qtab], fp)
    ax = [mont_mul(pt[0], zi, fp) for pt, zi in zip(qtab, zinv)]
    ay = [mont_mul(pt[1], zi, fp) for pt, zi in zip(qtab, zinv)]
    return ax, ay


def shamir_ladder_mixed(u1_w: jnp.ndarray, u2_w: jnp.ndarray,
                        qx_m: jnp.ndarray, qy_m: jnp.ndarray):
    """The windowed Shamir ladder over AFFINE tables + complete mixed
    additions — same contract as `shamir_ladder` (identical verdicts;
    the projective representative differs by a Z scale).

    Both window tables are affine (G: host constant; Q: device-built
    then normalized by one simultaneous inversion), so every table-add
    is RCB algorithm 5 and the one-hot selects move x/y only.  Affine
    tables cannot encode the infinity entry a zero window used to
    select; instead the add runs unconditionally against whatever the
    all-zero one-hot produces and a keep-select drops it — branch-free
    (the same reason the complete formulas are used at all).

    Selected by FABRIC_MOD_TPU_MIXED_ADD=1 (bccsp buckets route
    through `verify_core_mixed`); dark by default until on-chip
    measurement confirms it, like the Pallas ladder before it.
    """
    fp, _fn, b_m_np, _, _ = _consts()
    batch = qx_m.shape[1:]
    b_m = const_like(b_m_np, qx_m)

    ax, ay = build_q_table_affine(qx_m, qy_m, fp, b_m)
    q_tab = (jnp.stack(ax, axis=0), jnp.stack(ay, axis=0))
    g_aff = _g_table_affine()                        # (2, TABLE-1, K)
    sel_seq = jnp.stack([u1_w, u2_w], axis=1)        # (NW, 2, batch)

    def add_selected(acc, w, p2):
        """Mixed-add the selected affine point; keep acc on w == 0
        (the affine table has no infinity row — the one-hot is all
        zero there and the formula output is discarded)."""
        added = point_add_mixed(acc, p2, fp, b_m)
        keep = (w == 0)[None]
        return tuple(jnp.where(keep, a, n) for a, n in zip(acc, added))

    def step(acc, w2):
        acc = jax.lax.fori_loop(
            0, WINDOW, lambda _i, a: point_double(a, fp, b_m), acc)
        # Q-table select: one-hot reduce over the per-lane AFFINE
        # table (w-1 indexed; w == 0 yields a zero one-hot).
        oh_q = jax.nn.one_hot(w2[1] - 1, TABLE - 1, dtype=jnp.float32,
                              axis=0)
        acc = add_selected(acc, w2[1], tuple(
            jnp.sum(oh_q[:, None] * q_tab[c], axis=0) for c in range(2)))
        # G-table select: constant table -> one-hot matmul (MXU,
        # precision-pinned — table limbs reach 511).
        oh_g = jax.nn.one_hot(w2[0] - 1, TABLE - 1, dtype=jnp.float32,
                              axis=0)
        acc = add_selected(acc, w2[0], tuple(
            const_dot(g_aff[c].T, oh_g) for c in range(2)))
        return acc, None

    acc, _ = jax.lax.scan(step, infinity(batch), sel_seq)
    return acc


def inv_mont_p_chain(a_mont: jnp.ndarray, spec=None) -> jnp.ndarray:
    """Fermat inversion mod p via a fixed addition chain — 255
    squarings (in fori_loop runs) + 13 multiplies, no data-dependent
    control flow and, unlike the generic `limbs9.inv_mont`, no
    lax.scan over a captured (256,) exponent-bit constant — which is
    what makes it usable INSIDE a Pallas kernel (Mosaic cannot
    materialize captured array constants; kernel window-0 table
    normalization runs this).

    The chain is specific to P-256's p (the exponent p-2 decomposes
    into 2^32-1 word runs plus a (2^30-1)·4+1 tail); `spec`, if given,
    must be the p field.  Verified against `inv_mont` in
    tests/test_p256_mixed.py.
    """
    fp = _consts()[0]
    if spec is not None and spec.modulus != P:
        raise ValueError("inv_mont_p_chain is specific to the P-256 p field")

    def sqr_n(x, n):
        return jax.lax.fori_loop(
            0, n, lambda _i, v: mont_sqr(v, fp), x)

    a = a_mont
    x2 = mont_mul(mont_sqr(a, fp), a, fp)            # a^(2^2 - 1)
    x4 = mont_mul(sqr_n(x2, 2), x2, fp)              # a^(2^4 - 1)
    x8 = mont_mul(sqr_n(x4, 4), x4, fp)              # a^(2^8 - 1)
    x16 = mont_mul(sqr_n(x8, 8), x8, fp)             # a^(2^16 - 1)
    x24 = mont_mul(sqr_n(x16, 8), x8, fp)            # a^(2^24 - 1)
    x28 = mont_mul(sqr_n(x24, 4), x4, fp)            # a^(2^28 - 1)
    x30 = mont_mul(sqr_n(x28, 2), x2, fp)            # a^(2^30 - 1)
    x32 = mont_mul(sqr_n(x30, 2), x2, fp)            # a^(2^32 - 1)
    # p - 2 as big-endian 32-bit words: FFFFFFFF 00000001 00000000
    # 00000000 00000000 FFFFFFFF FFFFFFFF FFFFFFFD
    acc = mont_mul(sqr_n(x32, 32), a, fp)            # FFFFFFFF 00000001
    acc = sqr_n(acc, 96)                             # three zero words
    acc = mont_mul(sqr_n(acc, 32), x32, fp)          # FFFFFFFF
    acc = mont_mul(sqr_n(acc, 32), x32, fp)          # FFFFFFFF
    acc = mont_mul(sqr_n(acc, 30), x30, fp)          # FFFFFFFD ...
    acc = mont_mul(sqr_n(acc, 2), a, fp)             # ... = (2^30-1)*4+1
    return acc


def digest_words_to_limbs(dw: jnp.ndarray) -> jnp.ndarray:
    """(..., 8) uint32 big-endian SHA-256 digest words -> (K, ...) f32
    limbs of the digest-as-256-bit-integer — the DEVICE-side half of
    the fused hash->verify path (host twin: `limbs9.be_bytes_to_limbs`
    over `sha256.digest_to_bytes`; differentially tested equal).
    Pure shifts/masks + one tiny constant fold, shape-static."""
    w = jnp.moveaxis(dw.astype(jnp.uint32), -1, 0)   # (8, ...batch)
    j = np.arange(256)
    # global bit j (LSB-first) lives in word 7 - j//32, bit j%32
    rows = w[7 - j // 32]                            # (256, ...batch)
    shifts = jnp.asarray(j % 32, jnp.uint32).reshape(
        (256,) + (1,) * (w.ndim - 1))
    bits = ((rows >> shifts) & jnp.uint32(1)).astype(jnp.float32)
    pad = jnp.zeros((limbs.RBITS - 256,) + bits.shape[1:], jnp.float32)
    bits = jnp.concatenate([bits, pad], axis=0)
    bits = bits.reshape((K, limbs.B) + bits.shape[1:])
    wts = jnp.asarray((1 << np.arange(limbs.B)).astype(np.float32))
    # precision-pinned like every limb fold: weights are powers of two
    # (bf16-exact), but the pin keeps this path out of the "bare
    # matmul rounds limbs" bug class limbs9.const_dot exists to stop
    return jnp.tensordot(wts, bits, axes=(0, 1),
                         precision=limbs.PRECISION)  # (K, ...batch)


def _scalar_windows(e, r, s):
    """The scalar prologue both programs share: u1 = e/s and u2 = r/s
    (mod n) from (K, batch) canonical limbs, as WINDOW-bit window
    values, MSB window first: two (N_WINDOWS, batch) int32 arrays."""
    _fp, fn, _b_m_np, _, _ = _consts()
    batch = e.shape[1:]

    # Scalars mod n: w = s^-1, u1 = e*w, u2 = r*w.  mont_mul of a *plain*
    # value by a Montgomery-domain one yields a plain product directly.
    s_mn = to_mont(s, fn)
    w_mn = inv_mont(s_mn, fn)
    u1 = canonical(mont_mul(e, w_mn, fn), fn)       # (K, batch) int32
    u2 = canonical(mont_mul(r, w_mn, fn), fn)

    wexp = jnp.asarray(1 << np.arange(WINDOW), jnp.int32)

    def windows_msb_first(u):
        bits = bits_le(u)                            # (256, batch)
        w = jnp.tensordot(
            wexp, bits.reshape((N_WINDOWS, WINDOW) + batch), axes=(0, 1))
        return w[::-1]                               # (N_WINDOWS, batch)

    return windows_msb_first(u1), windows_msb_first(u2)


def _accept(acc, r, rn_lt_p, key_ok):
    """The final comparison both programs share: accept iff the key is
    good, Z != 0 and X == r'*Z for r' in {r, r+n} (r' < p)."""
    fp, fn, _b_m_np, _, _ = _consts()
    X, Z = acc[0], acc[2]
    not_inf = ~eq_zero(Z, fp)
    r_m = to_mont(r, fp)
    ok_r = eq_zero(sub(X, mont_mul(r_m, Z, fp)), fp)
    rn = add(r, const_like(fn.p, r))
    rn_m = to_mont(rn, fp)
    ok_rn = eq_zero(sub(X, mont_mul(rn_m, Z, fp)), fp) & rn_lt_p
    return key_ok & not_inf & (ok_r | ok_rn)


def _verify_core_impl(e, r, s, qx, qy, rn_lt_p,
                      ladder=shamir_ladder) -> jnp.ndarray:
    """Batched ECDSA-P256 verify on raw limb arrays, for keys never
    seen before (the ladder).

    Args:
      e, r, s: (K, batch) f32 canonical limbs — digest (as 256-bit int),
        and signature scalars already range-checked to [1, n-1] on host.
      qx, qy: (K, batch) f32 canonical limbs of the affine public key,
        host-checked to be < p.
      rn_lt_p: (batch,) bool — whether r + n < p (host-precomputed).
    Returns:
      (batch,) bool — signature valid AND key on curve.
    """
    fp = _consts()[0]

    # Key checks: on curve, not the identity encoding (0, 0).
    qx_m = to_mont(qx, fp)
    qy_m = to_mont(qy, fp)
    key_ok = on_curve(qx_m, qy_m)
    key_ok &= ~(eq_zero(qx, fp) & eq_zero(qy, fp))

    u1_w, u2_w = _scalar_windows(e, r, s)
    acc = ladder(u1_w, u2_w, qx_m, qy_m)
    return _accept(acc, r, rn_lt_p, key_ok)


def table_sum(u1_w: jnp.ndarray, u2_w: jnp.ndarray, slot: jnp.ndarray,
              tables: jnp.ndarray):
    """u1*G + u2*Q from fixed-base tables: no doubling.

    `u1_w`, `u2_w`: (N_WINDOWS, batch) window values, MSB first;
    `slot`: (batch,) int32, which of `tables`' slots holds the lane's
    key; `tables`: (N_WINDOWS, 3, K, slots * TABLE), the provider's
    `key_table`s side by side (`empty_key_tables`).  Each of the 64
    scan steps selects G's entry of that position (a constant one-hot
    matmul) and the lane's entry of its slot's table (a one-hot over
    slot x TABLE; precision pinned, table limbs reach 511) and adds
    both into their accumulators in ONE complete addition over
    2 x batch lanes; a last addition joins the halves.  Complete
    additions absorb identity entries (zero windows), equal and
    opposite operands, so the result is the ladder's group element for
    every input.  A lane whose slot holds no table sums garbage; the
    caller masks it.
    """
    fp, _fn, b_m_np, _, _ = _consts()
    width = tables.shape[-1]
    b_m = const_like(b_m_np, u1_w)                   # its rank only
    lanes = u1_w.shape[1]

    def step(acc, xs):
        w_g, w_q, g_t, q_t = xs
        oh_g = jax.nn.one_hot(w_g, TABLE, dtype=jnp.float32, axis=0)
        oh_q = jax.nn.one_hot(w_q, width, dtype=jnp.float32, axis=0)
        picked = jnp.concatenate(
            [jnp.tensordot(g_t, oh_g, axes=(-1, 0),
                           precision=limbs.PRECISION),
             jnp.tensordot(q_t, oh_q, axes=(-1, 0),
                           precision=limbs.PRECISION)],
            axis=-1)                                 # (3, K, 2 * batch)
        return point_add(acc, tuple(picked), fp, b_m), None

    acc, _ = jax.lax.scan(
        step, infinity((2 * lanes,)),
        (u1_w, slot[None] * TABLE + u2_w,
         limbs.const_jnp(_g_fixed_table()), tables))
    return point_add(tuple(c[:, :lanes] for c in acc),
                     tuple(c[:, lanes:] for c in acc), fp, b_m)


def _verify_core_tables_impl(e, r, s, rn_lt_p, slot, slot_ok,
                             tables) -> jnp.ndarray:
    """Batched ECDSA-P256 verify for lanes whose public key has a
    fixed-base table (`key_table`) in `tables`.

    Args:
      e, r, s, rn_lt_p: as `_verify_core_impl`.
      slot: (batch,) int32 — the slot of `tables` holding the lane's key.
      slot_ok: (batch,) bool — False where the key is no curve point
        (its slot holds no table): the lane is False.
      tables: (N_WINDOWS, 3, K, slots * TABLE) f32.
    Returns:
      (batch,) bool — bit for bit `_verify_core_impl`'s verdicts.
    """
    u1_w, u2_w = _scalar_windows(e, r, s)
    acc = table_sum(u1_w, u2_w, slot, tables)
    return _accept(acc, r, rn_lt_p, slot_ok)


verify_core = jax.jit(_verify_core_impl)
verify_core_mixed = jax.jit(
    functools.partial(_verify_core_impl, ladder=shamir_ladder_mixed))
verify_core_tables = jax.jit(_verify_core_tables_impl)


def _device_digest(words, nblocks, has_msg, e) -> jnp.ndarray:
    """The fused hash->verify prologue: e = SHA-256(m) computed ON
    DEVICE in the same program as the ECDSA verify — one dispatch, no
    host digest loop (the host half of the old path hashed per message
    in msp/identities.digest_for).

    Args:
      words: (batch, max_blocks, 16) uint32 — FIPS 180-4 pre-padded
        message words (bccsp/der.pack_messages).
      nblocks: (batch,) int32 — real block count per lane; 0 for
        pre-digested lanes (the compression state freezes at H0 and
        the lane's digest comes from `e` instead).
      has_msg: (batch,) bool — which lanes carry a raw message.  Mixed
        batches are first-class: a bucket can hold raw-message items
        and pre-digested items and still be ONE device program.
      e: (K, batch) f32 — host-side digest limbs for the pre-digested
        lanes (ignored where has_msg).
    """
    from fabric_mod_tpu.ops import sha256
    dw = sha256.sha256_blocks(words, nblocks)        # (batch, 8) u32
    e_dev = digest_words_to_limbs(dw)                # (K, batch) f32
    return jnp.where(has_msg[None], e_dev, e)


def _verify_core_fused_impl(words, nblocks, has_msg, e, r, s, qx, qy,
                            rn_lt_p, ladder=shamir_ladder) -> jnp.ndarray:
    """`_verify_core_impl` behind `_device_digest`."""
    e = _device_digest(words, nblocks, has_msg, e)
    return _verify_core_impl(e, r, s, qx, qy, rn_lt_p, ladder=ladder)


def _verify_core_tables_fused_impl(words, nblocks, has_msg, e, r, s,
                                   rn_lt_p, slot, slot_ok,
                                   tables) -> jnp.ndarray:
    """`_verify_core_tables_impl` behind `_device_digest`: the table
    program composes with the fused hash as the ladder does."""
    e = _device_digest(words, nblocks, has_msg, e)
    return _verify_core_tables_impl(e, r, s, rn_lt_p, slot, slot_ok,
                                    tables)


verify_core_fused = jax.jit(_verify_core_fused_impl)
verify_core_fused_mixed = jax.jit(
    functools.partial(_verify_core_fused_impl, ladder=shamir_ladder_mixed))
verify_core_tables_fused = jax.jit(_verify_core_tables_fused_impl)


# --- Host wrapper ----------------------------------------------------------

_N_BYTES = N.to_bytes(32, "big")
_P_BYTES = P.to_bytes(32, "big")
_P_MINUS_N_BYTES = (P - N).to_bytes(32, "big")


def _lt_bytes(a: np.ndarray, b_: bytes) -> np.ndarray:
    """Lexicographic a < b over (..., 32) big-endian byte arrays."""
    bb = np.frombuffer(b_, np.uint8)
    diff = a.astype(np.int16) - bb.astype(np.int16)
    nz = diff != 0
    first = np.argmax(nz, axis=-1)
    any_nz = nz.any(axis=-1)
    firstval = np.take_along_axis(diff, first[..., None], axis=-1)[..., 0]
    return np.where(any_nz, firstval < 0, False)


def _host_limbs(b: np.ndarray) -> np.ndarray:
    """(batch, 32) bytes -> (K, batch) f32 host array (device layout)."""
    return np.moveaxis(be_bytes_to_limbs(b), -1, 0).astype(np.float32)


def marshal_scalars(digests: np.ndarray, r_bytes: np.ndarray,
                    s_bytes: np.ndarray):
    """The key-less half of the host prologue: ((e, r, s limbs,
    rn_lt_p), range_ok) — the scalars' range checks and byte->limb
    marshalling, which both programs take."""
    digests = np.asarray(digests, np.uint8)
    r_bytes = np.asarray(r_bytes, np.uint8)
    s_bytes = np.asarray(s_bytes, np.uint8)
    range_ok = (r_bytes.any(axis=-1) & s_bytes.any(axis=-1)
                & _lt_bytes(r_bytes, _N_BYTES) & _lt_bytes(s_bytes, _N_BYTES))
    rn_lt_p = _lt_bytes(r_bytes, _P_MINUS_N_BYTES)
    return (_host_limbs(digests), _host_limbs(r_bytes),
            _host_limbs(s_bytes), rn_lt_p), range_ok


def marshal_inputs(digests: np.ndarray, r_bytes: np.ndarray,
                   s_bytes: np.ndarray, qx_bytes: np.ndarray,
                   qy_bytes: np.ndarray):
    """Host prologue shared by batch_verify and the driver entry
    points: range checks + byte->limb marshalling.

    Returns (core_args, range_ok): `core_args` is the positional tuple
    for verify_core ((K, batch) f32 limb arrays + rn_lt_p flags),
    `range_ok` the host-side scalar-range verdict to AND into the
    device mask.
    """
    qx_bytes = np.asarray(qx_bytes, np.uint8)
    qy_bytes = np.asarray(qy_bytes, np.uint8)
    (e, r, s, rn_lt_p), range_ok = marshal_scalars(
        digests, r_bytes, s_bytes)
    range_ok = (range_ok & _lt_bytes(qx_bytes, _P_BYTES)
                & _lt_bytes(qy_bytes, _P_BYTES))
    core_args = (e, r, s, _host_limbs(qx_bytes), _host_limbs(qy_bytes),
                 rn_lt_p)
    return core_args, range_ok


def _put(x: np.ndarray, sharding):
    """Host array -> device.  With a sharding, each device receives
    its slice straight from the host (no stop on device 0 first)."""
    if sharding is None:
        return jnp.asarray(x)
    return jax.device_put(x, sharding)


def _verdicts(ok, range_ok: np.ndarray, lazy: bool):
    """A dispatched program's mask ANDed with the host's range
    verdict: the array, or with `lazy` a resolver that fetches it when
    called (the program has been dispatched, not awaited)."""
    if lazy:
        return lambda: np.asarray(ok) & range_ok
    return np.asarray(ok) & range_ok


def place_core_args(core_args, mesh=None):
    """`marshal_inputs`' core_args as device arrays, placed where the
    verify program wants them: default device without a mesh; with
    one, the batch axis split over `dp` (parallel.verify_shardings)."""
    shardings = (None,) * 6
    if mesh is not None:
        from fabric_mod_tpu.parallel import verify_shardings
        limb_s, flag_s = verify_shardings(mesh)
        shardings = (limb_s,) * 5 + (flag_s,)
    return tuple(_put(a, s) for a, s in zip(core_args, shardings))


def batch_verify(digests: np.ndarray, r_bytes: np.ndarray,
                 s_bytes: np.ndarray, qx_bytes: np.ndarray,
                 qy_bytes: np.ndarray, mesh=None, lazy: bool = False):
    """Verify a batch of ECDSA-P256 signatures over 32-byte digests.

    All args are (batch, 32) uint8 big-endian.  Returns (batch,) bool —
    or, with `lazy=True`, a zero-arg resolver: the device program has
    been DISPATCHED (jax dispatch is asynchronous) but not awaited, so
    the caller can overlap host work for the next batch against this
    one's device execution and call the resolver when the verdicts are
    needed (the commit pipeline's double buffer, SURVEY §2.9 row 2).

    `mesh` (optional jax.sharding.Mesh, see parallel/mesh.py) shards
    the trailing batch axis of the limb arrays across the `dp` axis, so
    GSPMD partitions the same jitted program across chips — multi-chip
    is a data-placement decision, not a different code path.  The batch
    must then divide the mesh size (every bucket in bccsp/tpu.py does).
    """
    core_args, range_ok = marshal_inputs(
        digests, r_bytes, s_bytes, qx_bytes, qy_bytes)
    core = _select_core(digests.shape[0], mesh)
    ok = core(*place_core_args(core_args, mesh))
    return _verdicts(ok, range_ok, lazy)


def batch_verify_raw(words: np.ndarray, nblocks: np.ndarray,
                     has_msg: np.ndarray, digests: np.ndarray,
                     r_bytes: np.ndarray, s_bytes: np.ndarray,
                     qx_bytes: np.ndarray, qy_bytes: np.ndarray,
                     mesh=None, lazy: bool = False):
    """`batch_verify` with the digest computed ON DEVICE for raw-
    message lanes: one jitted program runs SHA-256 over the pre-padded
    message words AND the ECDSA verify (verify_core_fused) — the last
    host round-trip of the commit path (the per-message hashlib loop)
    gone.  Lanes with has_msg=False fall back to the `digests` plane,
    so mixed buckets stay one program.

    `words` is (batch, max_blocks, 16) uint32 from
    bccsp/der.pack_messages; the other args match `batch_verify`.
    Honors the same FABRIC_MOD_TPU_MIXED_ADD / FABRIC_MOD_TPU_PALLAS
    composition, and the same mesh sharding (message words shard on
    their LEADING batch axis — parallel.fused_verify_shardings).
    """
    core_args, range_ok = marshal_inputs(
        digests, r_bytes, s_bytes, qx_bytes, qy_bytes)

    flag_s = words_s = None
    if mesh is not None:
        from fabric_mod_tpu.parallel import (fused_verify_shardings,
                                             verify_shardings)
        _, flag_s = verify_shardings(mesh)
        words_s, _ = fused_verify_shardings(mesh)

    core = _select_core(digests.shape[0], mesh, fused=True)
    ok = core(_put(np.asarray(words, np.uint32), words_s),
              _put(np.asarray(nblocks, np.int32), flag_s),
              _put(np.asarray(has_msg, bool), flag_s),
              *place_core_args(core_args, mesh))
    return _verdicts(ok, range_ok, lazy)


def batch_verify_tables(digests: np.ndarray, r_bytes: np.ndarray,
                        s_bytes: np.ndarray, slot: np.ndarray,
                        slot_ok: np.ndarray, tables, msg=None,
                        lazy: bool = False):
    """`batch_verify` for lanes whose public keys have fixed-base
    tables: (batch, 32) uint8 digests and scalars, `slot` (batch,)
    which slot of `tables` (a device array over `empty_key_tables`'
    layout) holds the lane's `key_table`, `slot_ok` (batch,) False
    where the key is no curve point.  `msg`, if given, is
    `batch_verify_raw`'s (words, nblocks, has_msg): those lanes' digests
    are computed on the device in the same program.  One device (the
    provider keeps the ladder for a mesh)."""
    core_args, range_ok = marshal_scalars(digests, r_bytes, s_bytes)
    args = core_args + (np.asarray(slot, np.int32),
                        np.asarray(slot_ok, bool))
    if msg is None:
        core = verify_core_tables
    else:
        core = verify_core_tables_fused
        words, nblocks, has_msg = msg
        args = (np.asarray(words, np.uint32), np.asarray(nblocks, np.int32),
                np.asarray(has_msg, bool)) + args
    ok = core(*map(jnp.asarray, args), tables)
    return _verdicts(ok, range_ok, lazy)


def _select_core(batch: int, mesh, fused: bool = False):
    """The env-knob composition matrix (PALLAS x MIXED_ADD x fused
    hash), one place: Pallas when enabled and tileable (single-device
    only — GSPMD cannot partition a pallas_call, so the mesh path
    stays on the XLA core), mixed ladder when enabled — the Pallas
    kernel now IMPLEMENTS the mixed schedule rather than being routed
    around it (the PR-1 follow-up ROADMAP.md named)."""
    mixed = _use_mixed()
    if _use_pallas():
        if mesh is None and batch % 8 == 0:
            # odd direct-caller batches (not divisible by 8 — bccsp
            # buckets always are) stay on the XLA core below: a lane
            # width under 8 would make the grid pathological
            tile = next(t for t in (128, 64, 32, 16, 8)
                        if batch % t == 0)
            return _pallas_core(tile, mixed, fused)
        _say_once("FABRIC_MOD_TPU_PALLAS is set, but "
                  + ("a mesh-sharded batch" if mesh is not None
                     else "a batch that is not a multiple of 8")
                  + " runs the XLA ladder, not the Pallas kernel")
    if fused:
        return verify_core_fused_mixed if mixed else verify_core_fused
    return verify_core_mixed if mixed else verify_core


def _use_mixed() -> bool:
    """FABRIC_MOD_TPU_MIXED_ADD=1 swaps the affine-table mixed-
    addition ladder into the verify pipeline (shamir_ladder_mixed) —
    dark-launched pending on-chip measurement, selectable per-run by
    bench.py --mixed-add.  COMPOSES with FABRIC_MOD_TPU_PALLAS: with
    both set, the VMEM-fused Pallas kernel runs the mixed-addition
    schedule (ops/p256_pallas.pallas_ladder_mixed) — no longer routed
    around it."""
    from fabric_mod_tpu.utils import knobs
    return knobs.get_bool("FABRIC_MOD_TPU_MIXED_ADD")


def _use_pallas() -> bool:
    """FABRIC_MOD_TPU_PALLAS=1 swaps the VMEM-fused Pallas ladder into
    the verify pipeline (ops/p256_pallas.py) — dark-launched until
    on-chip measurement confirms it over the XLA ladder.  No-op on the
    CPU backend (compiled pallas_call is TPU-only; the interpreter is
    for tests)."""
    from fabric_mod_tpu.utils import knobs
    if not knobs.get_bool("FABRIC_MOD_TPU_PALLAS"):
        return False
    if jax.default_backend() == "cpu":
        _say_once("FABRIC_MOD_TPU_PALLAS is set, but the CPU backend "
                  "cannot run a compiled pallas_call: the XLA ladder "
                  "runs instead")
        return False
    return True


@functools.lru_cache(maxsize=None)
def _say_once(msg: str) -> None:
    """A caller asked for one kernel and gets another: say so, once
    per distinct reason (the selection runs on every dispatch)."""
    from fabric_mod_tpu.observability.logging import get_logger
    get_logger("ops.p256").warning(msg)


@functools.lru_cache(maxsize=None)
def _pallas_core(tile: int, mixed: bool = False, fused: bool = False):
    """Jitted Pallas verify core for one (tile, ladder-variant,
    hash-fusion) combination — lru-cached so each compiles once."""
    from fabric_mod_tpu.ops import p256_pallas
    ladder = functools.partial(
        p256_pallas.pallas_ladder_mixed if mixed
        else p256_pallas.pallas_ladder, tile=tile)
    impl = _verify_core_fused_impl if fused else _verify_core_impl
    return jax.jit(functools.partial(impl, ladder=ladder))
