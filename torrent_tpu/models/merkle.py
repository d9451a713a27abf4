"""Batched merkle trees over SHA-256 — the BEP 52 (BitTorrent v2) plane.

v2 hashes files as merkle trees with 16 KiB leaf blocks: leaves are
SHA-256 of each block, interior nodes are SHA-256 of the 64-byte
concatenation of their children, a file's ``pieces root`` is the tree
root, and for files larger than one piece the per-piece subtree roots
are published as the ``piece layers`` (BEP 52 "file tree" / "piece
layers"). The reference predates v2 — this subsystem is beyond-parity.

TPU mapping: digests never leave word form. Leaves come out of the
SHA-256 plane as ``u32[N, 8]`` big-endian words; each merkle level is
one batched compression of the 16-word pair concatenation plus a
constant padding block (message length is always exactly 64 bytes), so
a whole level is ``sha256_pairs: u32[M, 16] → u32[M/2, 8]`` — no byte
swizzling anywhere above the leaves.
"""

from __future__ import annotations

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from torrent_tpu.ops.sha256_jax import _IV256, _compress256

# The XLA module names of the merkle reduce (the fused chain on an
# accelerator, one pair level a dispatch on the CPU backend): apart from
# the leaf steps' (``models.v2.LEAF_STEP_MODULE_NAMES``), so that a
# reader of the leaf step's device time never counts the fold.
MERKLE_MODULE_NAMES = frozenset({"jit__merkle_reduce_fused", "jit_sha256_pairs"})


@jax.jit
def sha256_pairs(words: jax.Array) -> jax.Array:
    """One merkle level: ``u32[M, 16]`` child-pair words → ``u32[M, 8]``.

    The 64-byte message is exactly one block; the second (padding) block
    is the constant ``0x80 || zeros || bitlen=512``.
    """
    m = words.shape[0]
    state = tuple(jnp.full((m,), v, dtype=jnp.uint32) for v in _IV256)
    state = _compress256(state, [words[:, i] for i in range(16)])
    pad = (
        [jnp.full((m,), 0x80000000, dtype=jnp.uint32)]
        + [jnp.zeros((m,), dtype=jnp.uint32)] * 14
        + [jnp.full((m,), 512, dtype=jnp.uint32)]
    )
    state = _compress256(state, pad)
    return jnp.stack(state, axis=1)


@functools.partial(jax.jit, static_argnames=("levels",))
def _merkle_reduce_fused(words: jax.Array, levels: int) -> jax.Array:
    """``u32[B, 2**levels, 8]`` → roots ``u32[B, 8]``: EVERY pair level
    in one dispatch. The per-level host wrapper (``merkle_level``) pays
    a device round-trip per level — log2(L) dispatches and transfers per
    reduction. Here intermediates never leave the device."""
    for _ in range(levels):
        b, m, _ = words.shape
        pairs = words.reshape(b * (m // 2), 16)
        # nested jit traces inline: still ONE dispatch for all levels
        words = sha256_pairs(pairs).reshape(b, m // 2, 8)
    return words[:, 0, :]


def merkle_level(words: np.ndarray) -> np.ndarray:
    """Host wrapper: ``u32[..., M, 8]`` → ``u32[..., M/2, 8]``.

    Leading batch axes are flattened into the pair batch so one call
    reduces a whole level of MANY trees at once.
    """
    *lead, m, _ = words.shape
    if m % 2:
        raise ValueError("merkle level must have an even node count")
    pairs = np.ascontiguousarray(words).reshape(-1, 16)
    out = np.asarray(sha256_pairs(jnp.asarray(pairs)))
    return out.reshape(*lead, m // 2, 8)


def merkle_root(words: np.ndarray) -> np.ndarray:
    """``u32[..., L, 8]`` (L a power of two) → root ``u32[..., 8]``.

    Backend-keyed: on an accelerator all levels fuse into ONE dispatch
    (instead of log2(L) host hops per reduction); on the CPU backend the
    per-level loop wins instead, because dispatch is free there and the
    fused program's levels×-larger XLA graph makes compile time dominate
    real work (measured 2× on the v2 suite)."""
    *lead, l, _ = words.shape
    if l & (l - 1):
        raise ValueError("leaf count must be a power of two")
    if l == 1:
        return np.asarray(words)[..., 0, :]
    if jax.default_backend() == "cpu":
        out = words
        while out.shape[-2] > 1:
            out = merkle_level(out)
        return out[..., 0, :]
    flat = np.ascontiguousarray(words).reshape(-1, l, 8)
    out = np.asarray(_merkle_reduce_fused(jnp.asarray(flat), l.bit_length() - 1))
    return out.reshape(*lead, 8)


@functools.lru_cache(maxsize=None)
def zero_chain(levels: int) -> tuple[bytes, ...]:
    """``zero_chain(k)[i]`` = root digest of a full zero-leaf subtree of
    height ``i`` (index 0 = the 32-byte zero leaf itself), up to height
    ``levels``. Host-side hashlib — computed once per geometry."""
    out = [b"\x00" * 32]
    for _ in range(levels):
        out.append(hashlib.sha256(out[-1] + out[-1]).digest())
    return tuple(out)


def digests_to_words32(digests) -> np.ndarray:
    """32-byte SHA-256 digests → ``u32[N, 8]`` big-endian words."""
    from torrent_tpu.ops.padding import digests_to_words

    return digests_to_words(digests, words=8)


# width follows the array; the shared converter handles both planes
from torrent_tpu.ops.padding import words_to_digests as words32_to_digests  # noqa: E402


def pad_leaves(leaf_words: np.ndarray, target: int) -> np.ndarray:
    """Pad ``u32[n, 8]`` leaf words with zero-hash leaves up to ``target``."""
    n = leaf_words.shape[0]
    if n == target:
        return leaf_words
    padded = np.zeros((target, 8), dtype=np.uint32)
    padded[:n] = leaf_words
    return padded


def piece_roots_from_leaves(leaf_words: np.ndarray, leaves_per_piece: int) -> np.ndarray:
    """Leaf words ``u32[n_leaves, 8]`` → per-piece roots ``u32[n_pieces, 8]``.

    The final piece's missing leaves are zero-hash-padded (BEP 52). All
    pieces reduce together: one device call per tree level.
    """
    if leaves_per_piece & (leaves_per_piece - 1):
        raise ValueError("leaves_per_piece must be a power of two")
    n = leaf_words.shape[0]
    n_pieces = -(-n // leaves_per_piece)
    grid = np.zeros((n_pieces, leaves_per_piece, 8), dtype=np.uint32)
    grid.reshape(-1, 8)[:n] = leaf_words
    return merkle_root(grid)


def file_root_from_piece_roots(piece_root_words: np.ndarray, leaves_per_piece: int) -> bytes:
    """Piece roots → the file's ``pieces root`` digest.

    The piece-root layer is padded to the next power of two with the root
    of an all-zero piece subtree (NOT the zero leaf — BEP 52's "remaining
    leaf hashes ... set to zero" composes upward through the full-height
    zero subtree).
    """
    n = piece_root_words.shape[0]
    target = 1 << max(0, (n - 1).bit_length())
    if target != n:
        height = leaves_per_piece.bit_length() - 1
        zero_root = zero_chain(height)[height]
        pad = np.tile(digests_to_words32([zero_root]), (target - n, 1))
        piece_root_words = np.concatenate([piece_root_words, pad], axis=0)
    return words32_to_digests(merkle_root(piece_root_words)[None, :])[0]


def small_file_root(leaf_words: np.ndarray) -> bytes:
    """Root for a file no larger than one piece: leaves zero-padded to the
    next power of two of the file's own block count."""
    n = leaf_words.shape[0]
    target = max(1, 1 << max(0, (n - 1).bit_length()))
    return words32_to_digests(merkle_root(pad_leaves(leaf_words, target))[None, :])[0]


def piece_root_cpu(data: bytes, pad_leaves: int) -> bytes:
    """Merkle root of one piece's data: SHA-256 16 KiB leaf hashes padded
    with ZERO digests (BEP 52 "remaining leaf hashes ... set to zero" —
    the pad is the zero VALUE, not the hash of zero bytes) up to
    ``pad_leaves`` (a power of two), pairs folded to the root.

    ``pad_leaves`` is blocks-per-piece for pieces of multi-piece files,
    or the file's own next-power-of-two block count for single-piece
    files — the per-piece expected digest in session/v2.py either way.
    Host-side hashlib: one piece is at most 64 leaves (1 MiB pieces), so
    the batched device planes only pay off across MANY pieces (see
    piece_roots_from_leaves / parallel/verify.py).
    """
    from torrent_tpu.codec.metainfo_v2 import BLOCK

    if pad_leaves < 1 or pad_leaves & (pad_leaves - 1):
        raise ValueError("pad_leaves must be a power of two >= 1")
    leaves = [
        hashlib.sha256(data[i : i + BLOCK]).digest()
        for i in range(0, len(data), BLOCK)
    ] or [hashlib.sha256(b"").digest()]
    if len(leaves) > pad_leaves:
        raise ValueError(f"piece has {len(leaves)} leaves > pad target {pad_leaves}")
    leaves += [b"\x00" * 32] * (pad_leaves - len(leaves))
    while len(leaves) > 1:
        leaves = [
            hashlib.sha256(leaves[i] + leaves[i + 1]).digest()
            for i in range(0, len(leaves), 2)
        ]
    return leaves[0]
