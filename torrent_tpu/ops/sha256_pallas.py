"""Hand-tiled Pallas TPU SHA-256 kernel — the v2 fast path.

Identical structure to ``ops/sha1_pallas.py`` (see that module for the
layout rationale): pieces tiled ``tile_sub × 128`` per program, input
pre-swizzled to ``[1, nblk, 16, sub, 128]`` slabs, one pallas_call per
tile row (bounded swizzle temporaries), grid ``(1, nblk/unroll)`` with
the chain axis "arbitrary" and the running 8-word state living in the
revisited output block. Only the compression differs: 64 rounds of
FIPS 180-4 SHA-256 with a 16-entry rolling schedule window.

BEP 52 workloads hit this kernel with two shapes: 16 KiB leaf blocks
(nblk=9 with padding block) and 64-byte merkle pair messages (nblk=2) —
both short chains, so ``unroll`` folds to the chain length and every
piece is one grid step. Like the SHA1 kernel it accepts ``uint8`` or
host-order ``uint32`` input (u32 avoids the 4×-widened bitcast fusion).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torrent_tpu.ops.sha1_pallas import (
    TILE_LANE,
    TILE_SUB as _SHA1_TILE_SUB,
    UNROLL as _SHA1_UNROLL,
    _check_tiling,
    _swizzle_tile,
)
from torrent_tpu.ops.sha256_jax import _IV256, _K256, _round, _schedule_step
from torrent_tpu.utils.env import env_bool, env_int

# SHA-256's sweet spot need not match SHA-1's (different rounds/registers
# per block and the leaf plane's 16 KiB rows vs 256 KiB pieces) — own
# knobs, defaulting to the SHA-1 tuning until tools/tune_sha256 says
# otherwise on the real chip.
TILE_SUB = env_int("TORRENT_TPU_SHA256_TILE_SUB", _SHA1_TILE_SUB)
UNROLL = env_int("TORRENT_TPU_SHA256_UNROLL", _SHA1_UNROLL)
# Straight-line 64-round body (the SHA-1 kernel's shape) instead of the
# fori_loop-over-groups one. OFF by default: the unrolled graph hangs
# the XLA *CPU* compiler's algebraic simplifier (measured: >300 s, the
# documented circular-rewrite trap), so it cannot run — or be validated
# — in interpret mode; Mosaic compiles through a different pipeline
# where straight-line code is exactly what the SHA-1 kernel already
# ships. tools/tune_sha256 A/B-tests it on the real chip (golden-checked
# there); interpret mode always falls back to the loop body.
FULL_UNROLL = env_bool("TORRENT_TPU_SHA256_FULL_UNROLL")
# 2-way round-chain interleave — same roofline knob as the SHA-1
# kernel's (see ops/sha1_pallas.py _one_block_x2 / BASELINE.md): split
# the tile's sublanes in half, alternate the halves' rounds in program
# order. OFF by default; tools/tune_sha256 A/Bs it on-chip. Composes
# with FULL_UNROLL (straight-line alternation) and with the loop body
# (interpret-safe alternation inside the group fori_loop).
INTERLEAVE2 = env_bool("TORRENT_TPU_SHA256_INTERLEAVE2")
_check_tiling(TILE_SUB, UNROLL)  # bad env knobs fail at import, not mid-bench
if INTERLEAVE2 and (TILE_SUB < 16 or (TILE_SUB // 2) % 8):
    raise ValueError(
        "TORRENT_TPU_SHA256_INTERLEAVE2 needs TILE_SUB >= 16 with "
        f"8-sublane halves, got {TILE_SUB}"
    )

# Sub-tile launch granule: the smallest legal tile is 8 sublanes × 128
# lanes, so any launch stages a multiple of 1024 rows. Row-bucketed
# padding (below) rounds a live batch up to this granule instead of the
# configured TILE_SUB tile (default 32 → 4096 rows) — a 300-row partial
# flush pads to 1024 sentinel rows, not 4096.
SUB_TILE_ROWS = 8 * TILE_LANE


def pad_rows_for(n_rows: int) -> int:
    """Rows a pallas launch of ``n_rows`` live pieces actually stages:
    the nearest ``SUB_TILE_ROWS`` multiple at or above the batch (the
    sentinel rows carry ``nblocks=0`` and their chains never run)."""
    if n_rows <= 0:
        return SUB_TILE_ROWS
    return -(-n_rows // SUB_TILE_ROWS) * SUB_TILE_ROWS


def tile_sub_for_rows(padded_rows: int, cap: int | None = None) -> int:
    """Largest legal ``tile_sub`` that tiles ``padded_rows`` exactly.

    ``padded_rows`` must be a ``SUB_TILE_ROWS`` multiple (see
    :func:`pad_rows_for`). The cap defaults to the env-tuned TILE_SUB:
    full-target launches keep the sweep's fastest tiling, sub-tile
    launches drop to whatever multiple-of-8 sublane count divides the
    bucketed row count (8 for 1024 rows, 16 for 2048, 24 for 3072, …).
    """
    cap = TILE_SUB if cap is None else cap
    subs = padded_rows // TILE_LANE
    if padded_rows % SUB_TILE_ROWS:
        raise ValueError(f"padded_rows={padded_rows} is not a {SUB_TILE_ROWS} multiple")
    best = 8
    for cand in range(8, min(cap, 64) + 1, 8):
        if subs % cand == 0:
            best = cand
    return best


def _one_block256(state, w, kc_ref):
    """One 64-round SHA-256 compression on vreg-shaped u32 tensors.

    16-round prologue + ``fori_loop`` over the three schedule groups
    (static window indices within a group; the 48 tail K constants come
    from ``kc_ref`` in SMEM, row-indexed by the loop variable) — the same
    shape as the jax backend's ``_compress256``, and for the same reason:
    a fully unrolled 64-round graph trips XLA's algebraic-simplifier
    circular-rewrite loop in interpret mode.
    """
    vars8 = state
    for t in range(16):
        vars8 = _round(vars8, w[t], np.uint32(_K256[t]))

    def group(g, carry):
        vars8, w = carry
        w = list(w)
        for i in range(16):
            wt = _schedule_step(w, i)
            w[i] = wt
            vars8 = _round(vars8, wt, kc_ref[g, i])
        return (vars8, tuple(w))

    new, _ = jax.lax.fori_loop(0, 3, group, (vars8, tuple(w)))
    return tuple(s + n for s, n in zip(state, new))


def _one_block256_unrolled(state, w):
    """Straight-line 64-round compression with immediate K constants —
    no loop-carried 24-vreg tuple, no SMEM K loads, full cross-round
    scheduling freedom for Mosaic. NEVER reached under interpret (see
    FULL_UNROLL above)."""
    vars8 = state
    for t in range(64):
        if t < 16:
            wt = w[t]
        else:
            wt = _schedule_step(w, t % 16)
            w[t % 16] = wt
        vars8 = _round(vars8, wt, np.uint32(_K256[t]))
    return tuple(s + n for s, n in zip(state, vars8))


def _one_block256_x2(state_a, wa, state_b, wb, kc_ref):
    """Loop-body compression over TWO independent half-tiles, rounds
    alternated in program order (interpret-safe: same fori_loop-over-
    groups shape as _one_block256, carrying both halves)."""
    va, vb = state_a, state_b
    for t in range(16):
        va = _round(va, wa[t], np.uint32(_K256[t]))
        vb = _round(vb, wb[t], np.uint32(_K256[t]))

    def group(g, carry):
        va, wa, vb, wb = carry
        wa, wb = list(wa), list(wb)
        for i in range(16):
            wta = _schedule_step(wa, i)
            wa[i] = wta
            va = _round(va, wta, kc_ref[g, i])
            wtb = _schedule_step(wb, i)
            wb[i] = wtb
            vb = _round(vb, wtb, kc_ref[g, i])
        return (va, tuple(wa), vb, tuple(wb))

    va, _, vb, _ = jax.lax.fori_loop(
        0, 3, group, (va, tuple(wa), vb, tuple(wb))
    )
    return (
        tuple(s + n for s, n in zip(state_a, va)),
        tuple(s + n for s, n in zip(state_b, vb)),
    )


def _one_block256_x2_unrolled(state_a, wa, state_b, wb):
    """Straight-line alternation of two half-tiles' 64-round chains —
    FULL_UNROLL's scheduling freedom plus explicit cross-chain
    independence. NEVER reached under interpret (same XLA-CPU
    simplifier trap as _one_block256_unrolled)."""
    va, vb = state_a, state_b
    for t in range(64):
        if t < 16:
            wta, wtb = wa[t], wb[t]
        else:
            wta = _schedule_step(wa, t % 16)
            wa[t % 16] = wta
            wtb = _schedule_step(wb, t % 16)
            wb[t % 16] = wtb
        va = _round(va, wta, np.uint32(_K256[t]))
        vb = _round(vb, wtb, np.uint32(_K256[t]))
    return (
        tuple(s + n for s, n in zip(state_a, va)),
        tuple(s + n for s, n in zip(state_b, vb)),
    )


def _sha256_kernel(
    words_ref,
    nblocks_ref,
    kc_ref,
    state_ref,
    *,
    unroll: int,
    tile_sub: int,
    full: bool,
    interleave2: bool = False,
):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        for i, v in enumerate(_IV256):
            state_ref[0, i] = jnp.full((tile_sub, TILE_LANE), v, dtype=jnp.uint32)

    nblocks = nblocks_ref[0]
    half = tile_sub // 2

    def body(j, state):
        w = [words_ref[0, j, t] for t in range(16)]
        if interleave2:
            sa = tuple(s[:half] for s in state)
            sb = tuple(s[half:] for s in state)
            wa = [x[:half] for x in w]
            wb = [x[half:] for x in w]
            if full:
                na, nb = _one_block256_x2_unrolled(sa, wa, sb, wb)
            else:
                na, nb = _one_block256_x2(sa, wa, sb, wb, kc_ref)
            new = tuple(
                jnp.concatenate([x, y], axis=0) for x, y in zip(na, nb)
            )
        elif full:
            new = _one_block256_unrolled(state, w)
        else:
            new = _one_block256(state, w, kc_ref)
        keep = k * unroll + j < nblocks
        return tuple(jnp.where(keep, n, o) for n, o in zip(new, state))

    state = tuple(state_ref[0, i] for i in range(8))
    if unroll == 1:
        state = body(0, state)
    else:
        state = jax.lax.fori_loop(0, unroll, body, state)
    for i in range(8):
        state_ref[0, i] = state[i]


@functools.partial(
    jax.jit,
    static_argnames=("interpret", "tile_sub", "unroll", "full_unroll", "interleave2"),
)
def _sha256_pallas_aligned(
    data, nblocks, interpret, tile_sub, unroll, full_unroll, interleave2=False
):
    tile = tile_sub * TILE_LANE
    b = data.shape[0]
    if data.dtype == jnp.uint32:
        data32 = data
    else:
        data32 = jax.lax.bitcast_convert_type(
            data.reshape(b, data.shape[1] // 4, 4), jnp.uint32
        )
    nblk = data32.shape[1] // 16
    unroll = min(unroll, nblk)
    nblk_pad = ((nblk + unroll - 1) // unroll) * unroll
    if nblk_pad != nblk:
        data32 = jnp.pad(data32, ((0, 0), (0, (nblk_pad - nblk) * 16)))
        nblk = nblk_pad
    nb = nblocks.astype(jnp.int32).reshape(b // tile, tile_sub, TILE_LANE)
    kc = jnp.asarray(np.array(_K256[16:], dtype=np.uint32).reshape(3, 16))

    call = pl.pallas_call(
        functools.partial(
            _sha256_kernel,
            unroll=unroll,
            tile_sub=tile_sub,
            # interpret lowers through XLA CPU, whose simplifier hangs on
            # the straight-line body — the loop body is mandatory there
            full=bool(full_unroll) and not interpret,
            interleave2=interleave2,
        ),
        grid=(1, nblk // unroll),
        in_specs=[
            pl.BlockSpec(
                (1, unroll, 16, tile_sub, TILE_LANE),
                lambda i, k: (i, k, 0, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, tile_sub, TILE_LANE), lambda i, k: (i, 0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec((3, 16), lambda i, k: (0, 0), memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec(
            (1, 8, tile_sub, TILE_LANE), lambda i, k: (i, 0, 0, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((1, 8, tile_sub, TILE_LANE), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )

    states = []
    for r0 in range(0, b, tile):
        words = _swizzle_tile(data32[r0 : r0 + tile], nblk, tile_sub)
        states.append(call(words, nb[r0 // tile : r0 // tile + 1], kc))
    state = jnp.concatenate(states, axis=0) if len(states) > 1 else states[0]
    return jnp.transpose(state, (0, 2, 3, 1)).reshape(b, 8)


def sha256_pieces_pallas(
    data: jax.Array,
    nblocks: jax.Array,
    interpret: bool | None = None,
    tile_sub: int | None = None,
    unroll: int | None = None,
    full_unroll: bool | None = None,
    interleave2: bool | None = None,
) -> jax.Array:
    """Batched SHA-256 via Pallas; pads the batch to a tile multiple.

    ``interleave2`` (env ``TORRENT_TPU_SHA256_INTERLEAVE2``, default
    off) alternates two half-tiles' round chains — see the SHA-1
    kernel's variant; composes with ``full_unroll``."""
    from torrent_tpu.ops.sha1_pallas import _auto_interpret

    if interpret is None:
        interpret = _auto_interpret()
    ts = TILE_SUB if tile_sub is None else tile_sub
    un = UNROLL if unroll is None else unroll
    fu = FULL_UNROLL if full_unroll is None else full_unroll
    il2 = INTERLEAVE2 if interleave2 is None else interleave2
    _check_tiling(ts, un)
    if il2 and (ts < 16 or (ts // 2) % 8):
        raise ValueError(
            f"interleave2 needs tile_sub >= 16 with 8-sublane halves, got {ts}"
        )
    tile = ts * TILE_LANE
    b = data.shape[0]
    bp = ((b + tile - 1) // tile) * tile
    if bp != b:
        data = jnp.pad(data, ((0, bp - b), (0, 0)))
        nblocks = jnp.pad(nblocks, (0, bp - b))
    out = _sha256_pallas_aligned(data, nblocks, interpret, ts, un, fu, il2)
    return out[:b]
