"""Batched SHA-256 in pure JAX — the v2 (BEP 52) side of the hash plane.

BitTorrent v2 replaces SHA1 piece hashes with SHA-256 merkle trees over
16 KiB leaf blocks (BEP 52; the reference predates v2 entirely — this is
beyond-parity surface). The shapes are even friendlier to the TPU than
v1's: leaves are uniform 16 KiB messages (8-block chains), and the merkle
reduction above them is batched SHA-256 over 64-byte pair messages — both
pure batch problems.

Same contract family as ``ops/sha1_jax.py``:
``(data_u8[B, padded], nblocks[B]) → u32[B, 8]``; padding/packing is the
identical FIPS 180-4 64-byte-block scheme, so ``ops/padding.py`` is
shared verbatim between the two hash planes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from torrent_tpu.ops.sha1_jax import _bswap32

# FIPS 180-4 §5.3.3 / §4.2.2 constants.
_IV256 = (
    0x6A09E667, 0xBB67AE85, 0x3C6EF372, 0xA54FF53A,
    0x510E527F, 0x9B05688C, 0x1F83D9AB, 0x5BE0CD19,
)
_K256 = (
    0x428A2F98, 0x71374491, 0xB5C0FBCF, 0xE9B5DBA5, 0x3956C25B, 0x59F111F1,
    0x923F82A4, 0xAB1C5ED5, 0xD807AA98, 0x12835B01, 0x243185BE, 0x550C7DC3,
    0x72BE5D74, 0x80DEB1FE, 0x9BDC06A7, 0xC19BF174, 0xE49B69C1, 0xEFBE4786,
    0x0FC19DC6, 0x240CA1CC, 0x2DE92C6F, 0x4A7484AA, 0x5CB0A9DC, 0x76F988DA,
    0x983E5152, 0xA831C66D, 0xB00327C8, 0xBF597FC7, 0xC6E00BF3, 0xD5A79147,
    0x06CA6351, 0x14292967, 0x27B70A85, 0x2E1B2138, 0x4D2C6DFC, 0x53380D13,
    0x650A7354, 0x766A0ABB, 0x81C2C92E, 0x92722C85, 0xA2BFE8A1, 0xA81A664B,
    0xC24B8B70, 0xC76C51A3, 0xD192E819, 0xD6990624, 0xF40E3585, 0x106AA070,
    0x19A4C116, 0x1E376C08, 0x2748774C, 0x34B0BCB5, 0x391C0CB3, 0x4ED8AA4A,
    0x5B9CCA4F, 0x682E6FF3, 0x748F82EE, 0x78A5636F, 0x84C87814, 0x8CC70208,
    0x90BEFFFA, 0xA4506CEB, 0xBEF9A3F7, 0xC67178F2,
)


def _rotr(x: jax.Array, n: int) -> jax.Array:
    return (x >> np.uint32(n)) | (x << np.uint32(32 - n))


def _round(vars8, wt, kc):
    """One SHA-256 round on the 8 working variables."""
    a, b, c, d, e, f, g, h = vars8
    big_s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
    ch = g ^ (e & (f ^ g))  # mux form: 3 ops vs 4
    temp1 = h + big_s1 + ch + kc + wt
    big_s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
    maj = (a & b) | (c & (a ^ b))  # identical truth table, 4 ops vs 5
    return (temp1 + big_s0 + maj, a, b, c, d + temp1, e, f, g)


def _schedule_step(w, i):
    """Next schedule word for round ``16g + i`` (g ≥ 1): window indices are
    static functions of the in-group position ``i``."""
    w15 = w[(i + 1) % 16]
    w2 = w[(i + 14) % 16]
    s0 = _rotr(w15, 7) ^ _rotr(w15, 18) ^ (w15 >> np.uint32(3))
    s1 = _rotr(w2, 17) ^ _rotr(w2, 19) ^ (w2 >> np.uint32(10))
    return w[i] + s0 + w[(i + 9) % 16] + s1


def _compress256(state, w16):
    """One SHA-256 compression: state 8-tuple, w16 list of 16 u32 tensors.

    Structured as a 16-round prologue (schedule = message words) plus a
    ``lax.scan`` over the remaining three 16-round groups — within a
    group every rolling-window index is static. A fully unrolled 64-round
    graph both sends XLA's compile superlinear inside an outer block scan
    AND trips an algebraic-simplifier circular-rewrite loop on the CPU
    backend (observed: "stuck in a circular simplification loop"); the
    scan form compiles in seconds everywhere. The Pallas kernel
    (ops/sha256_pallas.py) keeps its full unroll — Mosaic has no such
    pathology and the VPU wants the straight-line rounds.
    """
    vars8 = state
    for t in range(16):
        vars8 = _round(vars8, w16[t], np.uint32(_K256[t]))

    k_groups = jnp.asarray(np.array(_K256[16:], dtype=np.uint32).reshape(3, 16))

    def group(carry, k16):
        vars8, w = carry
        w = list(w)
        for i in range(16):
            wt = _schedule_step(w, i)
            w[i] = wt
            vars8 = _round(vars8, wt, k16[i])
        return (vars8, tuple(w)), None

    (new, _), _ = jax.lax.scan(group, (vars8, tuple(w16)), k_groups)
    return tuple(s + n for s, n in zip(state, new))


def bytes_to_schedule256(data: jax.Array) -> jax.Array:
    """``uint8[B, padded]`` or host-order ``uint32[B, padded // 4]`` →
    ``uint32[nblk, 16, B]`` big-endian schedule.

    Identical packing to SHA1 (both are big-endian 64-byte-block Merkle-
    Damgård); kept separate for call-site clarity. ``u32`` rows (the leaf
    road's upload, a free ``ndarray.view`` on the host) skip the 4-byte
    quads and their bitcast, which lower through a 4×-widened convert on
    TPU; either way the words are byte-swapped to big-endian here.
    """
    if data.dtype == jnp.uint32:
        words = data
    else:
        quads = data.reshape(data.shape[0], data.shape[1] // 4, 4)
        words = jax.lax.bitcast_convert_type(quads, jnp.uint32)
    b, nwords = words.shape
    words = _bswap32(words).reshape(b, nwords // 16, 16)
    return jnp.transpose(words, (1, 2, 0))


def sha256_chain(schedule: jax.Array, nblocks: jax.Array) -> jax.Array:
    """Masked block chain → ``uint32[B, 8]`` digest words."""
    nblk, _, b = schedule.shape
    init = tuple(jnp.full((b,), v, dtype=jnp.uint32) for v in _IV256)

    def step(carry, block):
        state, t = carry
        new = _compress256(state, [block[i] for i in range(16)])
        keep = t < nblocks
        state = tuple(jnp.where(keep, n, o) for n, o in zip(new, state))
        return (state, t + 1), None

    (final, _), _ = jax.lax.scan(step, (init, jnp.int32(0)), schedule)
    return jnp.stack(final, axis=1)


@functools.partial(jax.jit, static_argnames=())
def sha256_pieces_jax(data: jax.Array, nblocks: jax.Array) -> jax.Array:
    """Batched SHA-256: ``uint8[B, padded]`` or host-order
    ``uint32[B, padded // 4]``, ``int32[B]`` → ``uint32[B, 8]``."""
    return sha256_chain(bytes_to_schedule256(data), nblocks)


def make_sha256_fn(backend: str = "jax"):
    """Jittable ``(data_u8[B, padded], nblocks[B]) -> u32[B, 8]`` factory."""
    if backend == "jax":
        return sha256_pieces_jax
    if backend == "pallas":
        try:
            from torrent_tpu.ops.sha256_pallas import sha256_pieces_pallas

            return sha256_pieces_pallas
        except ImportError as e:  # pragma: no cover - env without pallas
            raise RuntimeError(f"pallas backend unavailable: {e}") from e
    raise ValueError(f"unknown sha256 backend {backend!r}")
