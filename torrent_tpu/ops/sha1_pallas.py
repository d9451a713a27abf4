"""Hand-tiled Pallas TPU SHA1 kernel — the fast path of the hash plane.

Same contract as ops/sha1_jax.py (``(data[B, ...], nblocks[B]) →
u32[B, 5]``), but laid out for the VPU explicitly:

- Pieces are tiled ``tile_sub × 128`` per program — every schedule word
  ``w[t]``, every state variable, and every round temp is ``tile_sub/8``
  int32 vector registers (8 sublanes × 128 lanes each). Larger tile_sub
  interleaves more independent SHA1 chains per vector op, hiding the
  chain's serial dependency latency. The default of 32 (with UNROLL 16)
  was chosen on a retired setup, not measured on this one;
  tools/tune_sha1.py sweeps it.
- Input is pre-swizzled (one fused XLA pass) to
  ``[nblk, 16, tile_sub, 128]`` per tile row so each grid step's DMA is
  one contiguous slab from HBM. The batch is processed **one tile row at
  a time** inside the jit: the swizzle's transpose materializes
  temporaries proportional to the slab, and per-tile slabs keep them
  bounded (a whole-batch swizzle at 4096 × 1 MiB pieces is 4.3 GiB of
  input and >8 GiB of temporaries — an instant HBM OOM).
- Accepts ``uint8[B, padded]`` or ``uint32[B, padded//4]`` (host order)
  input. The u32 form is the fast path: a u8→u32 bitcast lowers through
  a 4×-widened convert fusion on TPU, while u32 input needs only the
  in-place byteswap. Callers can reinterpret their staging buffer with
  ``ndarray.view(np.uint32)`` for free.
- Grid is ``(1, nblk)`` with the block axis innermost ("arbitrary"
  semantics): the 5-word running state lives in the revisited output
  block in VMEM across the whole chain — initialized at ``k == 0``,
  written back to HBM once per tile.
- Ragged batches: per-lane ``k < nblocks`` masks freeze a piece's state
  once its (shorter) chain ends — same semantics as the scan mask in
  sha1_jax.py, no dynamic shapes.

The 80 rounds are Python-unrolled with a 16-register rolling schedule
window: ~21 live vreg values, well inside the register file; no VMEM
traffic inside the round loop at all.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torrent_tpu.ops.sha1_jax import _IV, _K, _bswap32, _rotl
from torrent_tpu.utils.env import env_bool, env_int

TILE_LANE = 128
# Default pieces-per-program sublane rows; see the sweep table above.
TILE_SUB = env_int("TORRENT_TPU_SHA1_TILE_SUB", 32)
# SHA1 blocks chained per grid step. Each block is only ~640 vector ops
# per (8, 128) vreg — far less than the fixed per-step cost (DMA issue,
# revisited-block bookkeeping), so one-block steps are overhead-bound.
# The kernel runs UNROLL blocks per step via an in-kernel fori_loop (NOT
# Python unrolling — 640 rounds in one basic block sends the backend
# compiler superlinear).
UNROLL = env_int("TORRENT_TPU_SHA1_UNROLL", 16)
# 2-way round-chain interleave (BASELINE.md roofline's named knob):
# OFF by default — only an on-device A/B (tools/tune_sha1.py) should
# ever turn it on, exactly like the sha256 FULL_UNROLL variant.
INTERLEAVE2 = env_bool("TORRENT_TPU_SHA1_INTERLEAVE2")


def _check_tiling(tile_sub: int, unroll: int) -> None:
    if tile_sub % 8 or tile_sub > 64:
        raise ValueError(
            f"tile_sub={tile_sub}: must be a multiple of 8 (the int32 vreg "
            "sublane count) and <= 64 (VMEM block budget)"
        )
    if unroll > 128:
        raise ValueError(
            f"unroll={unroll}: > 128 blows the per-step VMEM block "
            "(unroll*16 words per lane) with no amortization left to gain"
        )


_check_tiling(TILE_SUB, UNROLL)
TILE = TILE_SUB * TILE_LANE  # default tile (rows per program instance)


def _round_t(t, a, b, c, d, e, w):
    """Round ``t`` of the SHA1 compression on one state tuple; ``w`` is
    the 16-entry rolling schedule window (mutated in place)."""
    if t < 16:
        wt = w[t]
    else:
        wt = _rotl(w[(t - 3) % 16] ^ w[(t - 8) % 16] ^ w[(t - 14) % 16] ^ w[t % 16], 1)
        w[t % 16] = wt
    if t < 20:
        # ch(b,c,d) = (b&c)|(~b&d), 4 ops naively; the mux form needs 3
        f = d ^ (b & (c ^ d))
        kc = _K[0]
    elif t < 40:
        f = b ^ c ^ d
        kc = _K[1]
    elif t < 60:
        # maj(b,c,d) = (b&c)|(b&d)|(c&d), 5 ops naively; 4 via the
        # b^c factoring (identical truth table)
        f = (b & c) | (d & (b ^ c))
        kc = _K[2]
    else:
        f = b ^ c ^ d
        kc = _K[3]
    tmp = _rotl(a, 5) + f + e + np.uint32(kc) + wt
    return tmp, a, _rotl(b, 30), c, d


def _one_block(state, w):
    """One 80-round SHA1 compression. state: 5-tuple of u32 vregs; w: 16 words.

    The 80-word schedule is a 16-entry rolling window so only 16 vectors
    are live at a time. Returns the chained (not yet masked) new state.
    """
    r = state
    for t in range(80):
        r = _round_t(t, *r, w)
    return tuple(s + x for s, x in zip(state, r))


def _one_block_x2(state_a, wa, state_b, wb):
    """One compression over TWO independent half-tiles with their round
    chains interleaved in program order (the roofline's named knob,
    BASELINE.md): each round's rotl→add critical path is ~5 dependent
    op-levels deep, so alternating rounds of two independent chains
    hands the backend a ready instruction from the other chain while one
    chain's adds are in flight. Whether Mosaic's scheduler benefits
    beyond what tile_sub-level vreg independence already gives is
    EMPIRICAL — this variant is opt-in and A/B'd on-chip by
    tools/tune_sha1.py, never a default."""
    ra, rb = state_a, state_b
    for t in range(80):
        ra = _round_t(t, *ra, wa)
        rb = _round_t(t, *rb, wb)
    return (
        tuple(s + x for s, x in zip(state_a, ra)),
        tuple(s + x for s, x in zip(state_b, rb)),
    )


def _sha1_kernel(
    words_ref,
    nblocks_ref,
    state_ref,
    *,
    unroll: int,
    tile_sub: int,
    interleave2: bool = False,
):
    """``unroll`` chained SHA1 block steps for one ``tile_sub*128``-piece tile.

    words_ref:   u32[1, unroll, 16, tile_sub, 128] — this step's schedule words
    nblocks_ref: i32[1, tile_sub, 128]             — per-piece chain lengths
    state_ref:   u32[1, 5, tile_sub, 128]          — running digest state
                 (revisited across the k grid axis; read once, written once)
    ``interleave2``: split the tile's sublanes in half and advance the
    two halves' round chains alternately (see _one_block_x2).
    """
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        for i, v in enumerate(_IV):
            state_ref[0, i] = jnp.full((tile_sub, TILE_LANE), v, dtype=jnp.uint32)

    nblocks = nblocks_ref[0]
    half = tile_sub // 2

    def body(j, state):
        # Dynamic index on a leading (untiled) VMEM axis — one contiguous slab.
        w = [words_ref[0, j, t] for t in range(16)]
        if interleave2:
            sa = tuple(s[:half] for s in state)
            sb = tuple(s[half:] for s in state)
            na, nb = _one_block_x2(sa, [x[:half] for x in w], sb, [x[half:] for x in w])
            new = tuple(
                jnp.concatenate([x, y], axis=0) for x, y in zip(na, nb)
            )
        else:
            new = _one_block(state, w)
        keep = k * unroll + j < nblocks
        return tuple(jnp.where(keep, n, o) for n, o in zip(new, state))

    state = tuple(state_ref[0, i] for i in range(5))
    if unroll == 1:
        state = body(0, state)
    else:
        state = jax.lax.fori_loop(0, unroll, body, state)
    for i in range(5):
        state_ref[0, i] = state[i]


def _swizzle_tile(tile_words_u32: jax.Array, nblk: int, tile_sub: int) -> jax.Array:
    """Host-order u32[tile, nblk*16] → u32[1, nblk, 16, tile_sub, 128],
    big-endian schedule words, one contiguous slab per chain step."""
    words = _bswap32(tile_words_u32).reshape(1, tile_sub, TILE_LANE, nblk, 16)
    return jnp.transpose(words, (0, 3, 4, 1, 2))


@functools.partial(
    jax.jit, static_argnames=("interpret", "tile_sub", "unroll", "interleave2")
)
def _sha1_pallas_aligned(data, nblocks, interpret, tile_sub, unroll, interleave2=False):
    """Tile-aligned batch → digest words. ``data`` is u8[B, padded] or
    (fast path) u32[B, padded//4]; B must be a ``tile_sub*128`` multiple.

    The batch is processed one tile row per pallas_call so swizzle
    temporaries stay proportional to a single tile, not the batch.
    """
    tile = tile_sub * TILE_LANE
    b = data.shape[0]
    if data.dtype == jnp.uint32:
        data32 = data
    else:
        # compat path: u8 rows are bitcast in 4-byte quads (the widening
        # lowering makes this the slow/memory-hungry form on TPU)
        data32 = jax.lax.bitcast_convert_type(
            data.reshape(b, data.shape[1] // 4, 4), jnp.uint32
        )
    nblk = data32.shape[1] // 16
    # Short chains (authoring tests, tiny pieces) keep unroll = chain
    # length so no work or trace time is wasted; long chains use the full
    # amortization factor. Static per input shape — no recompiles.
    unroll = min(unroll, nblk)
    # Round the chain up to an unroll multiple with zero blocks; they sit
    # beyond every row's nblocks so the masked updates skip them.
    nblk_pad = ((nblk + unroll - 1) // unroll) * unroll
    if nblk_pad != nblk:
        data32 = jnp.pad(data32, ((0, 0), (0, (nblk_pad - nblk) * 16)))
        nblk = nblk_pad
    nb = nblocks.astype(jnp.int32).reshape(b // tile, tile_sub, TILE_LANE)

    call = pl.pallas_call(
        functools.partial(
            _sha1_kernel,
            unroll=unroll,
            tile_sub=tile_sub,
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
        ],
        out_specs=pl.BlockSpec(
            (1, 5, tile_sub, TILE_LANE), lambda i, k: (i, 0, 0, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((1, 5, tile_sub, TILE_LANE), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )

    states = []
    for r0 in range(0, b, tile):
        words = _swizzle_tile(data32[r0 : r0 + tile], nblk, tile_sub)
        states.append(call(words, nb[r0 // tile : r0 // tile + 1]))
    state = jnp.concatenate(states, axis=0) if len(states) > 1 else states[0]
    # [R, 5, tile_sub, 128] → [B, 5]
    return jnp.transpose(state, (0, 2, 3, 1)).reshape(b, 5)


def _auto_interpret() -> bool:
    """Run the real Mosaic kernel on the TPU platform, interpret elsewhere."""
    return jax.devices()[0].platform != "tpu"


def sha1_pieces_pallas(
    data: jax.Array,
    nblocks: jax.Array,
    interpret: bool | None = None,
    tile_sub: int | None = None,
    unroll: int | None = None,
    interleave2: bool | None = None,
) -> jax.Array:
    """Batched SHA1 via the Pallas kernel; pads the batch to a tile multiple.

    ``data`` is ``uint8[B, padded]`` or host-order ``uint32[B, padded//4]``
    (fast path — see module docstring). Rows added by padding get
    ``nblocks=0`` (their chain never runs) and are sliced off the result.
    ``tile_sub``/``unroll`` default to the env-tunable module constants;
    ``interleave2`` (env ``TORRENT_TPU_SHA1_INTERLEAVE2``, default off)
    selects the 2-way round-chain interleave variant — opt-in until an
    on-device A/B says it wins (tools/tune_sha1.py).
    """
    if interpret is None:
        interpret = _auto_interpret()
    ts = TILE_SUB if tile_sub is None else tile_sub
    un = UNROLL if unroll is None else unroll
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
    out = _sha1_pallas_aligned(data, nblocks, interpret, ts, un, il2)
    return out[:b]
