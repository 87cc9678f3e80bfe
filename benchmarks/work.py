"""The yardstick for the verify kernel: operations and bytes one
ECDSA P-256 verification needs, and the chip's peaks.

Derived from the textbook algorithm alone (Hankerson, Menezes,
Vanstone, "Guide to Elliptic Curve Cryptography", alg. 3.48 and
tables 3.3/3.4); nothing is imported from the program, so whatever
implements the kernel later (XLA ladder, Pallas, a fused program) is
held to the same count.  Pad lanes are no work.

One verification of (digest, r, s, Q):

* w = s^-1 mod n by Fermat: 255 squarings + ~128 multiplications
  = 383 modular multiplications; u1 = e*w, u2 = r*w: 2 more.
* R = u1*G + u2*Q by Shamir's trick over 256 bits in Jacobian
  coordinates: 256 doublings, and an addition wherever either scalar
  has a set bit, 3/4 of the positions: 192 mixed additions.
  Doubling (a = -3): 4 multiplications + 4 squarings = 8.
  Mixed addition: 8 multiplications + 3 squarings = 11.
* accept iff x(R) = r: compare r*Z^2 with X, 1 squaring + 1
  multiplication, no second inversion.

One multiplication modulo a 256-bit prime on 32-bit limbs, schoolbook:
8 x 8 = 64 limb multiplies and 64 limb additions for the product, and
as many again for a Montgomery reduction: 256 operations.
"""

LIMBS = 8                                       # 256 bits / 32
OPS_PER_MODMUL = 2 * (2 * LIMBS * LIMBS)        # product + reduction
MODMULS_INVERSION = 255 + 128
MODMULS_SCALARS = 2
MODMULS_DOUBLE_SCALAR = 256 * 8 + 192 * 11
MODMULS_ACCEPT = 2
MODMULS_PER_VERIFY = (MODMULS_INVERSION + MODMULS_SCALARS
                      + MODMULS_DOUBLE_SCALAR + MODMULS_ACCEPT)   # 4,547
OPS_PER_VERIFY = MODMULS_PER_VERIFY * OPS_PER_MODMUL              # 1,164,032

# in: digest, r, s, Qx, Qy of 32 bytes each; out: one verdict byte
BYTES_PER_VERIFY = 5 * 32 + 1

# Published peaks per chip, keyed by `device_kind`.  Source: Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s (bf16), 819 GB/s HBM.
# The ladder's operations are integer limb operations; they are held
# against the chip's one published arithmetic peak.
PEAKS = {
    "TPU v5 lite": {"ops_per_s": 197e12, "bytes_per_s": 819e9},
}


def least_seconds(n_verifies: int, device_kind: str) -> dict:
    """The least time one chip could take for `n_verifies` real
    signatures, and which bound binds."""
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add "
            f"them to benchmarks/work.py with their source")
    peak = PEAKS[device_kind]
    by_ops = n_verifies * OPS_PER_VERIFY / peak["ops_per_s"]
    by_bytes = n_verifies * BYTES_PER_VERIFY / peak["bytes_per_s"]
    return {"seconds": max(by_ops, by_bytes),
            "binds": "operations" if by_ops >= by_bytes else "bytes"}
