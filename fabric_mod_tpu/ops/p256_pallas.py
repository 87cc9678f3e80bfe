"""Pallas-fused Shamir ladder: the whole scalar-mult loop in VMEM.

The XLA version of the ladder (ops/p256.shamir_ladder) materializes
every intermediate limb array to HBM between fusions — measured to be
the throughput ceiling at the XLA level (PERF.md "Older notes": on the
retired int32 kernel long element-wise chains ran at ~0.07 Tops/s
because they were HBM-materialization-bound).  This kernel keeps the
accumulator, the per-lane Q window table, and every Montgomery
intermediate in VMEM for the full 64-window ladder.

STATUS (tests/test_chip_compile.py): the TPU compiler does not accept
this kernel yet — after the window-selection BlockSpec repair the
lowering stops at `scatter` (limbs9.carried's `.at[-1].set`).  It is
off the default path and has never executed on a chip.

Structure (designed around the Mosaic failure modes catalogued in
round 3 — no giant concats, no scratch-slice accumulation, no
unrolled vreg lists, no dynamic sublane indexing):

* grid = (batch_tiles, N_WINDOWS); TPU grids execute sequentially
  with the LAST axis minor, so for one batch tile the 64 window steps
  run in order sharing VMEM scratch (the standard accumulator
  pattern).
* window selections arrive pre-tiled via BlockSpec index maps — the
  kernel never indexes by a loop variable;
* the Q window table (16 points, built once per tile at window 0)
  lives in three (TABLE*K, T) f32 scratch buffers; selects are
  one-hot multiply-reduces;
* the G table is a host constant folded in with a precision-pinned
  dot;
* all field math is ops/limbs9 — inside the kernel the sequential
  low-carry unrolls to static row indices
  (limbs9.set_unroll_low_carry, thread-local).

TWO ladder schedules share the kernel skeleton, selected by the same
env knobs as the XLA cores (the PALLAS x MIXED_ADD composition
matrix, ops/p256._select_core):

* `pallas_ladder` — the original all-projective schedule, numerically
  IDENTICAL to p256.shamir_ladder (same formulas, same order).
* `pallas_ladder_mixed` — the affine-table mixed-addition schedule
  (p256.shamir_ladder_mixed ported into VMEM, the PR-1 follow-up
  ROADMAP.md named): at window 0 the per-lane Q table is built through
  the shared projective schedule and normalized AFFINE by one
  Montgomery simultaneous inversion (limbs9.inv_mont_many with the
  scan-free p256.inv_mont_p_chain — Mosaic cannot materialize the
  generic inversion's captured exponent-bit constant), dropping the
  Z plane: VMEM scratch shrinks from three (TABLE*K, tile) table
  buffers to two ((TABLE-1)*K, tile), every window select moves one
  fewer plane, and all 128 table-adds take the cheaper complete MIXED
  formula (RCB alg. 5, 11+2 muls vs 12+2).  Zero windows keep-select
  around the add exactly like the XLA mixed ladder.

Both are differentially tested in interpret mode; flip on in
production with FABRIC_MOD_TPU_PALLAS=1 (+ FABRIC_MOD_TPU_MIXED_ADD=1
for the mixed schedule) once on-chip measurement confirms the win —
`bench.py --metric diffverify` reports the on-chip mixed-vs-projective
A/B alongside the verdict differential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from fabric_mod_tpu.ops import limbs9 as limbs
from fabric_mod_tpu.ops import p256
from fabric_mod_tpu.ops.limbs9 import K
from fabric_mod_tpu.ops.p256 import (
    N_WINDOWS, TABLE, _consts, _g_table, point_add, point_double)

_F = jnp.float32


def _one_hot(sel: jnp.ndarray, t: int, rows: int = TABLE) -> jnp.ndarray:
    """(T,) int32 -> (rows, T) f32 one-hot via 2D iota (Mosaic needs
    >= 2D iotas; jax.nn.one_hot can emit 1D).  Out-of-range selects
    (e.g. the mixed ladder's sel-1 == -1 for zero windows) yield an
    all-zero column — exactly the keep-select contract."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (rows, t), 0)
    return (iota == sel[None, :]).astype(_F)


def _ladder_kernel(sel1_ref, sel2_ref, qx_ref, qy_ref,
                   colsum_ref, colsum_sqr_ref, npmat_ref, pmat_ref,
                   onemont_ref, bm_ref, gtab_ref,
                   xo_ref, yo_ref, zo_ref,
                   qtx_ref, qty_ref, qtz_ref,
                   accx_ref, accy_ref, accz_ref):
    import jax.experimental.pallas as pl

    fp, _fn, _b_m_np, _gx, _gy = _consts()
    t = qx_ref.shape[1]
    nw = pl.program_id(1)

    # Pallas kernels may not capture array constants; the limb layer's
    # fold matrices arrive as inputs and are routed into limbs9's
    # mont ops via the identity-keyed, THREAD-LOCAL constant hook
    # (limbs9.set_const_lookup, trace-time).
    const_map = {
        id(limbs._COLSUM): colsum_ref[...],
        id(limbs._COLSUM_SQR): colsum_sqr_ref[...],
        id(fp.np_mat): npmat_ref[...],
        id(fp.p_mat): pmat_ref[...],
    }
    old_hook = limbs.get_const_lookup()
    limbs.set_const_lookup(lambda arr: const_map.get(id(arr)))
    try:
        b_m = bm_ref[...]                            # (K, 1)
        one_m = jnp.broadcast_to(onemont_ref[...], (K, t))
        zero = jnp.zeros((K, t), _F)

        @pl.when(nw == 0)
        def _init():
            # per-lane window table, shared schedule with the XLA
            # ladder (p256.build_q_table)
            q1 = (qx_ref[...], qy_ref[...], one_m)
            qtab = p256.build_q_table(q1, (zero, one_m, zero), fp,
                                      b_m)
            qtx_ref[...] = jnp.concatenate([pt[0] for pt in qtab],
                                           axis=0)
            qty_ref[...] = jnp.concatenate([pt[1] for pt in qtab],
                                           axis=0)
            qtz_ref[...] = jnp.concatenate([pt[2] for pt in qtab],
                                           axis=0)
            accx_ref[...] = zero
            accy_ref[...] = one_m
            accz_ref[...] = zero

        acc = (accx_ref[...], accy_ref[...], accz_ref[...])
        # WINDOW doublings (unrolled: 4 copies trace once per kernel,
        # not per window — the window loop is the grid)
        for _ in range(p256.WINDOW):
            acc = point_double(acc, fp, b_m)
        # Q-table select: one-hot reduce over the VMEM-resident table
        oh_q = _one_hot(sel2_ref[0], t)[:, None]     # (TABLE, 1, T)
        qsel = tuple(
            jnp.sum(oh_q * ref[...].reshape(TABLE, K, t), axis=0)
            for ref in (qtx_ref, qty_ref, qtz_ref))
        acc = point_add(acc, qsel, fp, b_m)
        # G-table select (precision-pinned: limbs reach 511)
        oh_g = _one_hot(sel1_ref[0], t)
        gt = gtab_ref[...]                           # (3*K, TABLE)
        gsel = tuple(
            jax.lax.dot_general(gt[c * K:(c + 1) * K], oh_g,
                                (((1,), (0,)), ((), ())),
                                precision=limbs.PRECISION)
            for c in range(3))
        acc = point_add(acc, gsel, fp, b_m)

        accx_ref[...], accy_ref[...], accz_ref[...] = acc

        @pl.when(nw == N_WINDOWS - 1)
        def _finish():
            xo_ref[...] = accx_ref[...]
            yo_ref[...] = accy_ref[...]
            zo_ref[...] = accz_ref[...]
    finally:
        limbs.set_const_lookup(old_hook)


def _ladder_kernel_mixed(sel1_ref, sel2_ref, qx_ref, qy_ref,
                         colsum_ref, colsum_sqr_ref, npmat_ref, pmat_ref,
                         onemont_ref, bm_ref, gtab_ref,
                         xo_ref, yo_ref, zo_ref,
                         qtx_ref, qty_ref,
                         accx_ref, accy_ref, accz_ref):
    """The affine-table mixed-addition schedule in VMEM: the Q table
    is normalized affine at window 0 (one simultaneous inversion) and
    held in TWO ((TABLE-1)*K, tile) scratch planes — no Z plane, no
    infinity row; zero windows keep-select around the add, exactly
    like p256.shamir_ladder_mixed (identical formulas, same order)."""
    import jax.experimental.pallas as pl

    fp, _fn, _b_m_np, _gx, _gy = _consts()
    t = qx_ref.shape[1]
    nw = pl.program_id(1)

    const_map = {
        id(limbs._COLSUM): colsum_ref[...],
        id(limbs._COLSUM_SQR): colsum_sqr_ref[...],
        id(fp.np_mat): npmat_ref[...],
        id(fp.p_mat): pmat_ref[...],
    }
    old_hook = limbs.get_const_lookup()
    limbs.set_const_lookup(lambda arr: const_map.get(id(arr)))
    try:
        b_m = bm_ref[...]                            # (K, 1)
        one_m = jnp.broadcast_to(onemont_ref[...], (K, t))
        zero = jnp.zeros((K, t), _F)

        @pl.when(nw == 0)
        def _init():
            # shared projective schedule (p256.build_q_table), then
            # ONE Montgomery simultaneous inversion drops the Z plane
            # (the scan-free chain: Mosaic cannot materialize the
            # generic inversion's captured bit-array constant)
            q1 = (qx_ref[...], qy_ref[...], one_m)
            qtab = p256.build_q_table(q1, (zero, one_m, zero), fp,
                                      b_m)[1:]
            zinv = limbs.inv_mont_many([pt[2] for pt in qtab], fp,
                                       inv=p256.inv_mont_p_chain)
            qtx_ref[...] = jnp.concatenate(
                [limbs.mont_mul(pt[0], zi, fp)
                 for pt, zi in zip(qtab, zinv)], axis=0)
            qty_ref[...] = jnp.concatenate(
                [limbs.mont_mul(pt[1], zi, fp)
                 for pt, zi in zip(qtab, zinv)], axis=0)
            accx_ref[...] = zero
            accy_ref[...] = one_m
            accz_ref[...] = zero

        def add_selected(acc, sel, p2):
            """Complete mixed add of the selected affine point; keep
            acc on sel == 0 (the affine table has no infinity row —
            the one-hot was all zero there)."""
            added = p256.point_add_mixed(acc, p2, fp, b_m)
            keep = (sel == 0)[None]
            return tuple(jnp.where(keep, a, n)
                         for a, n in zip(acc, added))

        acc = (accx_ref[...], accy_ref[...], accz_ref[...])
        for _ in range(p256.WINDOW):
            acc = point_double(acc, fp, b_m)
        # Q-table select: one-hot reduce over TWO VMEM planes (w-1
        # indexed; w == 0 yields an all-zero one-hot column)
        sel2 = sel2_ref[0]
        oh_q = _one_hot(sel2 - 1, t, rows=TABLE - 1)[:, None]
        acc = add_selected(acc, sel2, tuple(
            jnp.sum(oh_q * ref[...].reshape(TABLE - 1, K, t), axis=0)
            for ref in (qtx_ref, qty_ref)))
        # G-table select: affine constant table, precision-pinned MXU
        # one-hot matmul (limbs reach 511)
        sel1 = sel1_ref[0]
        oh_g = _one_hot(sel1 - 1, t, rows=TABLE - 1)
        gt = gtab_ref[...]                           # (2K, TABLE-1)
        acc = add_selected(acc, sel1, tuple(
            jax.lax.dot_general(gt[c * K:(c + 1) * K], oh_g,
                                (((1,), (0,)), ((), ())),
                                precision=limbs.PRECISION)
            for c in range(2)))

        accx_ref[...], accy_ref[...], accz_ref[...] = acc

        @pl.when(nw == N_WINDOWS - 1)
        def _finish():
            xo_ref[...] = accx_ref[...]
            yo_ref[...] = accy_ref[...]
            zo_ref[...] = accz_ref[...]
    finally:
        limbs.set_const_lookup(old_hook)


@functools.partial(jax.jit,
                   static_argnames=("tile", "interpret", "mixed"))
def _ladder_call(u1_w, u2_w, qx_m, qy_m, tile: int = 128,
                 interpret: bool = False, mixed: bool = False):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch = qx_m.shape[1]
    if batch % tile != 0:
        # explicit raise, not assert: under python -O a stripped
        # assert would silently drop the remainder lanes and return
        # uninitialized output rows for them
        raise ValueError(f"batch {batch} not divisible by tile {tile}")
    grid = (batch // tile, N_WINDOWS)
    # selections ride as (N_WINDOWS, 1, batch) with the window axis
    # squeezed: the TPU lowering wants a block's last two dims
    # divisible by (8, 128) or equal to the array's, and (1, tile) over
    # (N_WINDOWS, batch) is neither.  The kernels still see (1, tile).
    sel_spec = pl.BlockSpec((None, 1, tile), lambda i, nw: (nw, 0, i))
    limb_spec = pl.BlockSpec((K, tile), lambda i, nw: (0, i))

    def full(shape):
        return pl.BlockSpec(shape, lambda i, nw: (0, 0))

    fp, _fn, b_m_np, _gx, _gy = _consts()
    if mixed:
        g_aff = p256._g_table_affine()               # (2, TABLE-1, K)
        g_flat = np.concatenate([g_aff[c].T for c in range(2)],
                                axis=0).astype(np.float32)
        kernel = _ladder_kernel_mixed
        scratch = [
            pltpu.VMEM(((TABLE - 1) * K, tile), _F),  # q table x (affine)
            pltpu.VMEM(((TABLE - 1) * K, tile), _F),  # q table y (affine)
        ]
    else:
        g_tab = _g_table()                           # (3, TABLE, K)
        g_flat = np.concatenate([g_tab[c].T for c in range(3)],
                                axis=0).astype(np.float32)  # (3K, TABLE)
        kernel = _ladder_kernel
        scratch = [
            pltpu.VMEM((TABLE * K, tile), _F),       # q table x
            pltpu.VMEM((TABLE * K, tile), _F),       # q table y
            pltpu.VMEM((TABLE * K, tile), _F),       # q table z
        ]
    consts = (
        limbs._COLSUM, limbs._COLSUM_SQR,
        fp.np_mat, fp.p_mat,
        fp.one_mont.reshape(K, 1).astype(np.float32),
        np.asarray(b_m_np, np.float32).reshape(K, 1),
        g_flat,
    )

    old = limbs.get_unroll_low_carry()
    limbs.set_unroll_low_carry(True)       # static indices in-kernel
    try:
        out_shape = [jax.ShapeDtypeStruct((K, batch), _F)] * 3
        x, y, z = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[sel_spec, sel_spec, limb_spec, limb_spec]
                     + [full(c.shape) for c in consts],
            out_specs=[limb_spec] * 3,
            out_shape=out_shape,
            scratch_shapes=scratch + [
                pltpu.VMEM((K, tile), _F),           # acc x
                pltpu.VMEM((K, tile), _F),           # acc y
                pltpu.VMEM((K, tile), _F),           # acc z
            ],
            interpret=interpret,
        )(u1_w.astype(jnp.int32).reshape(N_WINDOWS, 1, batch),
          u2_w.astype(jnp.int32).reshape(N_WINDOWS, 1, batch),
          qx_m, qy_m, *(jnp.asarray(c) for c in consts))
    finally:
        limbs.set_unroll_low_carry(old)
    return x, y, z


def pallas_ladder(u1_w, u2_w, qx_m, qy_m, tile: int = 128,
                  interpret: bool = False):
    """Drop-in for p256.shamir_ladder (same signature + semantics)."""
    return _ladder_call(u1_w, u2_w, qx_m, qy_m, tile=tile,
                        interpret=interpret)


def pallas_ladder_mixed(u1_w, u2_w, qx_m, qy_m, tile: int = 128,
                        interpret: bool = False):
    """Drop-in for p256.shamir_ladder_mixed: identical formulas in the
    same order, so canonical outputs match the XLA mixed ladder bit
    for bit (and verdicts match the projective ladder — the
    representatives differ by a Z scale)."""
    return _ladder_call(u1_w, u2_w, qx_m, qy_m, tile=tile,
                        interpret=interpret, mixed=True)


def verify_core_pallas(e, r, s, qx, qy, rn_lt_p, tile: int = 128,
                       interpret: bool = False, mixed: bool = False):
    """p256._verify_core_impl with the VMEM-fused ladder (jit this
    per deployment; ops/p256._select_core wires it under
    FABRIC_MOD_TPU_PALLAS, with `mixed` from FABRIC_MOD_TPU_MIXED_ADD)."""
    ladder = functools.partial(
        pallas_ladder_mixed if mixed else pallas_ladder,
        tile=tile, interpret=interpret)
    return p256._verify_core_impl(e, r, s, qx, qy, rn_lt_p,
                                  ladder=ladder)
