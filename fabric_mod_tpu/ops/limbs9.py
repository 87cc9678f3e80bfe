"""f32 radix-2^9 modular arithmetic for TPU — the MXU limb layer.

Second-generation limb layer (first: ops/limbs.py, int32 radix-2^11).
Two structural changes move the hot work from the VPU's weakest paths
onto the MXU and fully-occupied vector lanes:

1. **f32 limbs, radix B=2^9, K=30.**  All products and column sums stay
   exact in the 24-bit f32 mantissa (bounds below), so the schoolbook
   column fold and both Montgomery constant-operand products become
   *float matmuls* — which XLA puts on the MXU systolic array.  The
   int32 matmuls of the previous layer had no MXU lowering and ran as
   vector-unit emulation.
2. **Limb axis FIRST.**  Arrays are (K, ...batch): the minor-most axis
   is the batch, so every element-wise op (carries, adds) fills all 128
   vector lanes.  The previous (batch, K=25) layout wasted 80% of every
   vreg on lane padding, and limb shifts were lane-relayouts; here a
   limb shift is a whole-register sublane move.

Value-bound analysis (do not change K/B casually):

* ``carried`` uses *rounded* carries: hi = floor(x/B + 1/2), so limbs
  land in [-B/2, B/2] = [-256, 256]; the second pass adds a carry-in
  of at most ~17, giving the working invariant |limb| <= 273.
* products |a_i*b_j| <= 273^2 < 2^16.2; a column sums <= K such terms
  plus the slightly larger top-limb terms: < 2^21.3 — exact in f32.
* Montgomery with R = 2^270 (K*B = 270): for inputs |v| < 2^260,
  |T|/R < 2^251 and |m*p|/R < 2^256.2, so outputs are < 2^256.3 —
  the chain is self-stabilizing with ~10 bits of headroom for the
  add/sub chains between multiplies (point formulas sum at most a few
  terms, staying far below 2^260).
* canonicalization lifts by 32p (> any |v| above) and still fits the
  30-limb capacity 2^270 — the extra headroom relative to the old
  R = 2^275 design is why K is 30 and not 29.

Matmul exactness: operands are integer-valued f32 well inside the
mantissa, and accumulation happens in f32 on values bounded < 2^22, so
a full-precision float32 dot is exact.  ``PRECISION`` pins
jax.lax.Precision.HIGHEST (6-pass bf16 emulation on TPU — exact for
f32 operands); see test_limbs9.py for the differential that guards it.

Replaces the software per-signature math of the reference
(bccsp/sw/ecdsa.go:41-57) with a batch axis (SURVEY.md §2.9): the
batch is the trailing axes, no vmap needed anywhere.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

K = 30            # number of limbs
B = 9             # bits per limb
BASE = 1 << B     # 512
MASK = BASE - 1
RBITS = K * B     # 270
HALF = BASE // 2  # rounding offset

# Exact f32 dot emulation on TPU (6-pass bf16). The operands here are
# integers < 2^17 and sums < 2^22, so HIGHEST is bit-exact.
#
# The cheaper 3-pass emulation (Precision.HIGH) is exact ONLY for the
# 0/1 fold matrices — it exists for an on-chip A/B, and it can make
# verify verdicts silently WRONG if it leaks into production (on the
# chip it turns every valid lane False: PERF.md section 6, PR 26).  No
# environment variable selects it: `set_precision_mode("high")` is
# called by the benchmark's control (benchmarks/seeds.py --control) and
# bench.py's --precision, and by nothing else.
import sys as _sys

PRECISION = jax.lax.Precision.HIGHEST


def set_precision_mode(mode: str) -> str:
    """Select the limb matmul precision ("highest" | "high").

    BENCH-ONLY.  Returns the previous mode.  Must be called before the
    first verify/pairing trace in the process — jitted programs bake
    the precision at trace time and are NOT retraced.  Selecting
    "high" emits a prominent warning: verdicts are only trustworthy
    after the differential suite passes at that precision.
    """
    global PRECISION
    prev = "high" if PRECISION == jax.lax.Precision.HIGH else "highest"
    mode = (mode or "highest").lower()
    if mode not in ("high", "highest"):
        raise ValueError(f"unknown precision mode {mode!r}")
    PRECISION = (jax.lax.Precision.HIGH if mode == "high"
                 else jax.lax.Precision.HIGHEST)
    if mode == "high":
        print("=" * 70 + "\nWARNING: fabric_mod_tpu limb matmuls set to "
              "Precision.HIGH (3-pass bf16\nemulation).  This is exact "
              "ONLY for the 0/1 fold matrices; signature and\npairing "
              "verdicts are NOT guaranteed until the differential suite "
              "passes\nat this precision.  Bench A/B use only — never "
              "production.\n" + "=" * 70, file=_sys.stderr, flush=True)
    return prev


from fabric_mod_tpu.utils import knobs as _knobs

_F = jnp.float32


# ---------------------------------------------------------------------------
# Host-side converters (numpy; trailing limb axis for numpy-friendliness —
# device code moves limbs to axis 0 via `to_device` below)
# ---------------------------------------------------------------------------

def int_to_limbs(x: int) -> np.ndarray:
    """Non-negative python int (< 2**RBITS) -> (K,) float32 limbs."""
    assert 0 <= x < (1 << RBITS)
    out = np.zeros(K, np.float32)
    for i in range(K):
        out[i] = x & MASK
        x >>= B
    return out


def limbs_to_int(a) -> int:
    """Exact value of a (possibly lazy, signed) limb vector -> int.

    Accepts the device's (K,) arrays (f32 or int32)."""
    a = np.asarray(a)
    assert a.ndim == 1 and a.shape[0] == K
    return sum(int(v) << (B * i) for i, v in enumerate(a.tolist()))


# Limb i holds bits [B*i, B*i + B) of the little-endian value: they
# start in byte _LIMB_BYTE[i] at bit _LIMB_SHIFT[i] (<= 7) and end in
# the byte after it.
_LIMB_BYTE = (B * np.arange(K)) // 8
_LIMB_SHIFT = ((B * np.arange(K)) % 8).astype(np.uint16)


def be_bytes_to_limbs(buf: np.ndarray) -> np.ndarray:
    """(..., 32) uint8 big-endian -> (..., K) int32 limbs (host-side).

    Two byte gathers, a shift and a mask a limb: this runs for every
    scalar plane of every verify batch, on the thread that dispatches."""
    buf = np.asarray(buf, np.uint8)
    assert buf.shape[-1] == 32
    le = np.zeros(buf.shape[:-1] + (_LIMB_BYTE[-1] + 2,), np.uint16)
    le[..., :32] = buf[..., ::-1]
    pair = le[..., _LIMB_BYTE] | (le[..., _LIMB_BYTE + 1] << 8)
    return ((pair >> _LIMB_SHIFT) & MASK).astype(np.int32)


def to_device(host_limbs: np.ndarray) -> jnp.ndarray:
    """(..., K) host limbs -> (K, ...) f32 device layout."""
    return jnp.asarray(np.moveaxis(np.asarray(host_limbs), -1, 0), _F)


# ---------------------------------------------------------------------------
# Field specification (per modulus)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FieldSpec:
    """Montgomery constants for one odd modulus (R = 2^270).

    numpy on purpose: the spec may first materialize inside a jit trace
    and numpy constants are trace-neutral (jnp values there would cache
    tracers)."""
    name: str
    modulus: int
    p: np.ndarray          # (K,) f32 canonical limbs of p
    one: np.ndarray        # (K,) f32 limbs of 1
    one_mont: np.ndarray   # (K,) f32 R mod p
    r2: np.ndarray         # (K,) f32 R^2 mod p
    np_mat: np.ndarray     # (K, K) f32: m = np_mat @ t_low  (x*N' mod R)
    p_mat: np.ndarray      # (2K-1, K) f32: full columns of m*p
    kp32: np.ndarray       # (6, K) int32 canonical limbs of 32p..p
    lift32: np.ndarray     # (K,) int32 canonical limbs of 32p

    @staticmethod
    @functools.lru_cache(maxsize=None)
    def make(name: str, modulus: int) -> "FieldSpec":
        R = 1 << RBITS
        nprime = (-pow(modulus, -1, R)) % R
        p_l = int_to_limbs(modulus)
        np_l = int_to_limbs(nprime)
        np_mat = np.zeros((K, K), np.float32)      # m_c = sum_j np_{c-j} t_j
        p_mat = np.zeros((2 * K - 1, K), np.float32)  # out_c = sum_j p_{c-j} m_j
        for c in range(K):
            for j in range(c + 1):
                np_mat[c, j] = np_l[c - j]
        for c in range(2 * K - 1):
            for j in range(K):
                if 0 <= c - j < K:
                    p_mat[c, j] = p_l[c - j]
        kps = [int_to_limbs((32 >> i) * modulus).astype(np.int32)
               for i in range(6)]
        return FieldSpec(
            name=name, modulus=modulus, p=p_l,
            one=int_to_limbs(1),
            one_mont=int_to_limbs(R % modulus),
            r2=int_to_limbs((R * R) % modulus),
            np_mat=np_mat, p_mat=p_mat,
            kp32=np.stack(kps), lift32=kps[0],
        )


# ---------------------------------------------------------------------------
# Carries (f32 arithmetic; no bitwise ops exist for floats)
# ---------------------------------------------------------------------------

def _split(x: jnp.ndarray):
    """Rounded carry split: x = hi*BASE + lo with lo in [-HALF, HALF]."""
    hi = jnp.floor(x * (1.0 / BASE) + 0.5)
    return hi, x - hi * BASE


def _shift_up(hi: jnp.ndarray) -> jnp.ndarray:
    """Move carry rows up one limb along axis 0 (drop the top row)."""
    pad = [(1, 0, 0)] + [(0, 0, 0)] * (hi.ndim - 1)
    return jax.lax.pad(hi[:-1], jnp.float32(0), pad)


def carried(x: jnp.ndarray) -> jnp.ndarray:
    """Two rounded carry passes preserving the exact value.

    The TOP limb is never split (splitting would drop value); for the
    operation-driven value bounds in the module docstring it stays
    small.  Output invariant: |limb| <= 273 for all but the top limb,
    top limb <= value/2^(B*(L-1)) + 273."""
    for _ in range(2):
        hi, lo = _split(x)
        hi = hi.at[-1].set(0.0)
        lo = lo.at[-1].set(x[-1])
        x = lo + _shift_up(hi)
    return x


def carry_mod_r(x: jnp.ndarray) -> jnp.ndarray:
    """Two rounded passes over exactly K limbs, dropping overflow (mod R)."""
    for _ in range(2):
        hi, lo = _split(x)
        x = lo + _shift_up(hi)
    return x


# ---------------------------------------------------------------------------
# Schoolbook + Montgomery (the MXU path)
# ---------------------------------------------------------------------------

# Trace-time constant source override: Pallas kernels may not capture
# array constants, so while a kernel body is being traced this hook
# maps the module's numpy constant singletons (by IDENTITY) to values
# read from kernel input refs.  THREAD-LOCAL: a concurrent trace of
# the ordinary XLA path on another thread must never observe a Pallas
# kernel's in-flight hook (leaked tracers otherwise).
import threading as _threading

_TRACE_TLS = _threading.local()


def set_const_lookup(fn) -> None:
    """Install/clear (None) this thread's constant-source hook."""
    _TRACE_TLS.const_lookup = fn


def get_const_lookup():
    return getattr(_TRACE_TLS, "const_lookup", None)


def const_jnp(arr: np.ndarray) -> jnp.ndarray:
    hook = get_const_lookup()
    if hook is not None:
        got = hook(arr)
        if got is not None:
            return got
    return jnp.asarray(arr)


def const_dot(mat: np.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """(rows, cols) constant  @  (cols, ...batch) -> (rows, ...batch).

    ALWAYS use this (never a bare jnp.matmul/tensordot) for any product
    involving limb values: it pins PRECISION so the TPU does not round
    f32 operands to bf16 (integers > 256 are not bf16-exact)."""
    return jnp.tensordot(const_jnp(mat), x, axes=(1, 0),
                         precision=PRECISION)


# Anti-diagonal fold: flattened outer index (i*K+j) -> column i+j.
_COLSUM = np.zeros((2 * K - 1, K * K), np.float32)
for _i in range(K):
    for _j in range(K):
        _COLSUM[_i + _j, _i * K + _j] = 1.0

# Symmetric fold for squaring: upper-triangle products (i <= j), laid
# out as K concatenated slices [a_i*a_i, a_i*a_{i+1}, ..., a_i*a_{K-1}];
# cross terms carry weight 2.  K(K+1)/2 = 465 multiplies instead of 900.
_COLSUM_SQR = np.zeros((2 * K - 1, K * (K + 1) // 2), np.float32)
_idx = 0
for _i in range(K):
    for _j in range(_i, K):
        _COLSUM_SQR[_i + _j, _idx] = 1.0 if _i == _j else 2.0
        _idx += 1


def sb_mul_cols(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Schoolbook product columns: (K, ...) x (K, ...) -> (2K-1, ...).

    The outer product is element-wise VPU work (broadcast along leading
    axes — no lane shuffles); the anti-diagonal fold is ONE constant
    (2K-1, K^2) matmul on the MXU.  Column sums < 2^21.3 (docstring
    bounds) — exact in f32."""
    outer = a[:, None] * b[None, :]                      # (K, K, ...)
    return const_dot(_COLSUM, outer.reshape((K * K,) + outer.shape[2:]))


# When True (per-thread), the sequential low-carry unrolls to
# straight-line code with STATIC row indices — required inside Pallas
# kernels (Mosaic's dynamic sublane indexing is the risk) and a
# compile-time/runtime trade elsewhere.
def set_unroll_low_carry(flag: bool) -> None:
    _TRACE_TLS.unroll_low_carry = flag


# env default lets bench variants A/B this without code changes
_UNROLL_DEFAULT = _knobs.get_bool("FABRIC_MOD_TPU_UNROLL_LOW_CARRY")


def get_unroll_low_carry() -> bool:
    return getattr(_TRACE_TLS, "unroll_low_carry", _UNROLL_DEFAULT)


def _exact_low_carry(s: jnp.ndarray) -> jnp.ndarray:
    """Exact carry out of the low K limbs of s (value ≡ 0 mod R).

    Sequential by nature; fori_loop so the body compiles once (or
    unrolled under set_unroll_low_carry, see above)."""
    if get_unroll_low_carry():
        c = jnp.zeros(s.shape[1:], _F)
        for i in range(K):
            c = jnp.floor((s[i] + c) * (1.0 / BASE))
        return c

    def body(i, c):
        row = jax.lax.dynamic_index_in_dim(s, i, axis=0, keepdims=False)
        return jnp.floor((row + c) * (1.0 / BASE))
    return jax.lax.fori_loop(0, K, body,
                             jnp.zeros(s.shape[1:], _F))


def _mont_reduce(t: jnp.ndarray, spec: FieldSpec) -> jnp.ndarray:
    """Montgomery reduction of carried columns t -> t*R^-1 mod p.

    Both constant-operand products (x*N' mod R, m*p) are MXU matmuls."""
    m = carry_mod_r(const_dot(spec.np_mat, t[:K]))
    s = t + const_dot(spec.p_mat, m)             # low K limbs ≡ 0 mod R
    c = _exact_low_carry(s)
    hi = s[K:]                              # (K-1, ...)
    hi = jnp.concatenate(
        [hi[:1] + c[None], hi[1:],
         jnp.zeros((1,) + hi.shape[1:], _F)], axis=0)   # (K, ...)
    return carried(hi)


def _align2(a: jnp.ndarray, b: jnp.ndarray):
    """Rank-align two leading-limb-axis operands: a bare (K,) constant
    against a (K, batch...) value reshapes to (K, 1, ...) — numpy's
    trailing-axis broadcasting would otherwise reject (or worse,
    misalign) the pair.  No-op when ranks agree."""
    an = getattr(a, "ndim", 0)
    bn = getattr(b, "ndim", 0)
    if an < bn:
        a = jnp.reshape(a, a.shape + (1,) * (bn - an))
    elif bn < an:
        b = jnp.reshape(b, b.shape + (1,) * (an - bn))
    return a, b


def mont_mul(a: jnp.ndarray, b: jnp.ndarray, spec: FieldSpec) -> jnp.ndarray:
    """Montgomery product a*b*R^-1 mod p (lazy limbs in and out)."""
    a, b = _align2(a, b)
    return _mont_reduce(carried(sb_mul_cols(a, b)), spec)


def sb_sqr_cols(a: jnp.ndarray) -> jnp.ndarray:
    """Schoolbook square columns via the upper triangle: (K, ...) ->
    (2K-1, ...).  465 multiplies instead of 900 (a_i*a_j == a_j*a_i);
    the doubling of cross terms lives in the constant fold matrix, so
    column bounds only double for cross terms: < 2*K*273^2 < 2^22.2 —
    still exact in f32."""
    tri = jnp.concatenate([a[i:i + 1] * a[i:] for i in range(K)], axis=0)
    return const_dot(_COLSUM_SQR, tri)


def mont_sqr(a: jnp.ndarray, spec: FieldSpec) -> jnp.ndarray:
    """Montgomery square via the symmetric schoolbook (~half the MACs)."""
    return _mont_reduce(carried(sb_sqr_cols(a)), spec)


def add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    a, b = _align2(a, b)
    return carried(a + b)


def sub(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    a, b = _align2(a, b)
    return carried(a - b)


def mul_small(a: jnp.ndarray, k: int) -> jnp.ndarray:
    """Multiply by a small non-negative python int (k < 2**6)."""
    return carried(a * jnp.float32(k))


def const_like(c: np.ndarray, a: jnp.ndarray) -> jnp.ndarray:
    """(K,) constant -> (K, 1, ..., 1) matching a's rank.

    With the limb axis FIRST, numpy-style trailing-axis broadcasting
    would mis-align a bare (K,) against (K, batch...) — every constant
    must be lifted explicitly."""
    return const_jnp(c).reshape((K,) + (1,) * (a.ndim - 1))


def to_mont(a: jnp.ndarray, spec: FieldSpec) -> jnp.ndarray:
    return mont_mul(a, const_like(spec.r2, a), spec)


def from_mont(a: jnp.ndarray, spec: FieldSpec) -> jnp.ndarray:
    return mont_mul(a, const_like(spec.one, a), spec)


# ---------------------------------------------------------------------------
# Canonicalization & comparisons (int32 tail — low volume, exact bit ops)
# ---------------------------------------------------------------------------

def _full_carry_nonneg_i32(x: jnp.ndarray) -> jnp.ndarray:
    """Full sequential masked carry; value must be in [0, R)."""
    c = jnp.zeros(x.shape[1:], jnp.int32)
    outs = []
    for i in range(K):
        t = x[i] + c
        outs.append(jnp.bitwise_and(t, MASK))
        c = jnp.right_shift(t, B)
    return jnp.stack(outs, axis=0)


def _geq_sub_i32(v: jnp.ndarray, kp: jnp.ndarray) -> jnp.ndarray:
    """If canonical v >= canonical kp: v - kp, else v."""
    d = v - kp.reshape((K,) + (1,) * (v.ndim - 1))
    borrow = jnp.zeros(d.shape[1:], jnp.int32)
    outs = []
    for i in range(K):
        t = d[i] + borrow
        outs.append(jnp.bitwise_and(t, MASK))
        borrow = jnp.right_shift(t, B)      # 0 or -1
    ok = (borrow >= 0)[None]
    return jnp.where(ok, jnp.stack(outs, axis=0), v)


def canonical(a: jnp.ndarray, spec: FieldSpec) -> jnp.ndarray:
    """Lazy f32 limbs (|value| < 2^260) -> canonical int32 limbs in [0, p).

    Lifts by 32p (sign removal), carries sequentially in int32 (limbs
    are small ints — the cast is exact), then six conditional
    subtractions of 32p..p."""
    x = a.astype(jnp.int32) + jnp.asarray(spec.lift32).reshape(
        (K,) + (1,) * (a.ndim - 1))
    v = _full_carry_nonneg_i32(x)
    for i in range(6):
        v = _geq_sub_i32(v, jnp.asarray(spec.kp32[i]))
    return v


def eq_zero(a: jnp.ndarray, spec: FieldSpec) -> jnp.ndarray:
    """Is lazy value ≡ 0 (mod p)?  (K, ...) -> (...) bool."""
    return jnp.all(canonical(a, spec) == 0, axis=0)


def eq_canonical(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(a == b, axis=0)


def bits_le(canon_i32: jnp.ndarray, nbits: int = 256) -> jnp.ndarray:
    """Canonical int32 limbs (K, ...) -> (nbits, ...) bits, LSB first."""
    limb_idx = np.arange(nbits) // B
    bit_idx = np.arange(nbits) % B
    rows = canon_i32[limb_idx]                       # (nbits, ...)
    shifts = jnp.asarray(bit_idx, jnp.int32).reshape(
        (nbits,) + (1,) * (canon_i32.ndim - 1))
    return jnp.right_shift(rows, shifts) & 1


# ---------------------------------------------------------------------------
# Exponentiation
# ---------------------------------------------------------------------------

def pow_static(a_mont: jnp.ndarray, exponent: int, spec: FieldSpec) -> jnp.ndarray:
    """a^exponent in the Montgomery domain, static python-int exponent."""
    nbits = max(exponent.bit_length(), 1)
    bits = jnp.asarray(
        np.array([(exponent >> (nbits - 1 - i)) & 1 for i in range(nbits)],
                 np.bool_))
    acc0 = jnp.broadcast_to(
        jnp.asarray(spec.one_mont).reshape((K,) + (1,) * (a_mont.ndim - 1)),
        a_mont.shape).astype(_F)

    def body(acc, bit):
        acc = mont_sqr(acc, spec)
        withmul = mont_mul(acc, a_mont, spec)
        return jnp.where(bit, withmul, acc), None

    acc, _ = jax.lax.scan(body, acc0, bits)
    return acc


def inv_mont(a_mont: jnp.ndarray, spec: FieldSpec) -> jnp.ndarray:
    """Modular inverse in the Montgomery domain (Fermat; p prime)."""
    return pow_static(a_mont, spec.modulus - 2, spec)


def inv_mont_many(vals, spec: FieldSpec, inv=None) -> list:
    """Montgomery's simultaneous-inversion trick: invert m Montgomery-
    domain values with ONE Fermat inversion plus 3(m-1) multiplies.

    `vals` is a python list of (K, ...batch) arrays (a static table,
    e.g. the per-lane Q window table's Z coordinates); returns their
    inverses in order.  All products/inverses are element-wise along
    the batch axes, so lanes never mix.  A zero value poisons every
    inverse OF ITS LANE (0^(p-2) = 0 propagates through the prefix
    products) — callers rely on such lanes being masked out anyway
    (an on-curve point of a prime-order curve never has Z = 0 in the
    window table; only invalid keys do, and key_ok masks those).

    `inv` overrides the single Fermat inversion (default `inv_mont`,
    the generic square-and-multiply scan).  Pallas kernels pass a
    scan-free addition chain (ops/p256.inv_mont_p_chain): a lax.scan
    over a captured (256,) constant bit array is exactly the kind of
    trace Mosaic rejects.
    """
    inv = inv or inv_mont
    m = len(vals)
    if m == 0:
        return []
    if m == 1:
        return [inv(vals[0], spec)]
    prefix = [vals[0]]
    for v in vals[1:]:
        prefix.append(mont_mul(prefix[-1], v, spec))
    running = inv(prefix[-1], spec)          # (v_0 * ... * v_{m-1})^-1
    out = [None] * m
    for i in range(m - 1, 0, -1):
        out[i] = mont_mul(running, prefix[i - 1], spec)
        running = mont_mul(running, vals[i], spec)
    out[0] = running
    return out
