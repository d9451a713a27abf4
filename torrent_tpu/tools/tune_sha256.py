"""On-device Pallas SHA-256 knob sweep (tile_sub x unroll) — leaf plane.

The v2 (BEP 52) hash plane hashes 16 KiB leaf blocks, a much shorter
chain (256 compression blocks) than the SHA-1 plane's 256 KiB pieces —
its best tiling need not match. Same measurement discipline as
tools/tune_sha1:

- data generated ON device (TPU PRNG); only golden rows reach the host
- every timed dispatch distinct (``rand ^ salt``, fresh salt each time)
- completion forced by fetching an on-device reduction of the LAST
  dispatch
- u32 fast-path input, the form the leaf plane uploads

Apply the winner via ``TORRENT_TPU_SHA256_TILE_SUB`` /
``TORRENT_TPU_SHA256_UNROLL`` (ops/sha256_pallas.py reads them at
import).

Usage::

    python -m torrent_tpu.tools.tune_sha256 [--block-kb 16] [--batch 32768]
        [--grid 8x16,16x16,32x8,32x16,32x32] [--iters 8]

Prints one ranked JSON line per config plus a ``best`` summary line.
"""

from __future__ import annotations

import argparse
import functools
import hashlib
import json
import sys
import time

import numpy as np

from torrent_tpu.utils.device import enable_compile_cache


def _parse_grid(spec: str) -> list[tuple[int, int]]:
    out = []
    for part in spec.split(","):
        ts, un = part.lower().split("x")
        out.append((int(ts), int(un)))
    return out


def _pad_tail(mlen: int) -> np.ndarray:
    """The 64-byte SHA-2 padding block for a message of exactly ``mlen``
    bytes (mlen % 64 == 0, so the pad is a standalone final block —
    identical framing to SHA-1: 0x80, zeros, 64-bit big-endian bitlen)."""
    assert mlen % 64 == 0
    tail = np.zeros(64, dtype=np.uint8)
    tail[0] = 0x80
    tail[-8:] = np.frombuffer((mlen * 8).to_bytes(8, "big"), dtype=np.uint8)
    return tail


def run_sweep(
    block_kb: int,
    batch: int,
    grid: list[tuple[int, int]],
    iters: int,
    interpret: bool = False,
):
    import jax

    if interpret:
        jax.config.update("jax_platforms", "cpu")
    enable_compile_cache()  # persist sweep compiles across processes
    import jax.numpy as jnp

    from torrent_tpu.ops import sha256_pallas as sp
    from torrent_tpu.ops.padding import num_blocks_for, padded_len_for

    mlen = block_kb * 1024
    padded = padded_len_for(mlen)
    nblk = int(num_blocks_for(mlen))
    tail = np.zeros(padded - mlen, dtype=np.uint8)
    tail[:64] = _pad_tail(mlen)[: min(64, padded - mlen)]

    key = jax.random.key(20260730)

    @functools.partial(jax.jit, static_argnames="rows")
    def _gen(k, rows):
        return jax.random.bits(k, (rows, mlen // 4), jnp.uint32)

    rows_per = max(1, min(batch, (256 << 20) // mlen))
    parts = []
    for i, start in enumerate(range(0, batch, rows_per)):
        parts.append(_gen(jax.random.fold_in(key, i), min(rows_per, batch - start)))
    rand = jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]
    del parts
    rand_rows = {
        i: np.asarray(rand[i]).view(np.uint8).tobytes() for i in (0, batch - 1)
    }
    golden = {i: hashlib.sha256(rand_rows[i]).digest() for i in rand_rows}
    tail_dev = jax.device_put(tail.view(np.uint32))
    nblocks = jnp.full((batch,), nblk, dtype=jnp.int32)

    results = []
    # the straight-line 64-round body (full_unroll) can only compile on
    # real Mosaic — interpret mode would hang the XLA CPU simplifier —
    # and has no off-chip validation, so it is swept as an EXTRA
    # candidate with golden mismatches recorded, never fatal
    # (full_unroll, interleave2) combos: straight-line only on Mosaic;
    # the loop-form interleave IS interpret-safe, so smoke covers it
    variants = (
        [(False, False), (False, True)]
        if interpret
        else [(False, False), (True, False), (False, True), (True, True)]
    )
    for tile_sub, unroll in grid:
        if batch % (tile_sub * 128):
            print(
                f"# skip {tile_sub}x{unroll}: batch {batch} not a multiple of "
                f"tile {tile_sub * 128}",
                file=sys.stderr,
            )
            continue
      # fall through to the per-variant loop below

        for full, il2 in variants:
            if il2 and (tile_sub < 16 or (tile_sub // 2) % 8):
                continue  # halves must be whole vregs

            @jax.jit
            def hash_salted(
                r, t, nb, salt, _ts=tile_sub, _un=unroll, _fu=full, _il2=il2
            ):
                data = jnp.concatenate(
                    [r ^ salt, jnp.broadcast_to(t, (batch, t.shape[0]))], axis=1
                )
                return sp.sha256_pieces_pallas(
                    data, nb, interpret=interpret, tile_sub=_ts, unroll=_un,
                    full_unroll=_fu, interleave2=_il2,
                )

            reduce_sum = jax.jit(lambda s: jnp.sum(s, dtype=jnp.uint32))
            tag = {
                "tile_sub": tile_sub,
                "unroll": unroll,
                "full_unroll": full,
                "interleave2": il2,
            }

            try:
                t0 = time.perf_counter()
                state0 = hash_salted(rand, tail_dev, nblocks, jnp.uint32(0))
                got = np.asarray(state0[np.array([0, batch - 1])])
                compile_s = time.perf_counter() - t0
            except Exception as e:  # Mosaic can reject a tiling outright
                print(json.dumps({**tag, "error": repr(e)[:200]}))
                continue
            bad = False
            for row, idx in ((0, 0), (1, batch - 1)):
                want = np.frombuffer(golden[idx], dtype=">u4").astype(np.uint32)
                if not np.array_equal(got[row], want):
                    if full or il2:
                        # an experimental on-chip body (straight-line or
                        # interleaved — both invisible to CPU-interpret
                        # smoke) failed its golden: record and move on —
                        # never poison the sweep
                        print(json.dumps({**tag, "error": "golden mismatch"}))
                        bad = True
                        break
                    raise SystemExit(
                        f"golden mismatch at {tile_sub}x{unroll} row {idx}: "
                        f"{got[row]} != {want}"
                    )
            if bad:
                continue
            _ = int(reduce_sum(state0))  # warm the completion-forcing reduction

            t0 = time.perf_counter()
            outs = [
                hash_salted(rand, tail_dev, nblocks, jnp.uint32(s))
                for s in range(1, iters + 1)
            ]
            _ = int(reduce_sum(outs[-1]))
            secs = time.perf_counter() - t0
            bps = iters * batch / secs
            line = {
                **tag,
                "blocks_per_sec": round(bps, 1),
                "gib_per_sec": round(bps * mlen / 2**30, 2),
                "compile_s": round(compile_s, 1),
            }
            results.append(line)
            print(json.dumps(line), flush=True)

    if results:
        best = max(results, key=lambda r: r["blocks_per_sec"])
        # the winner as ready-to-export env knobs: the scheduler's pallas
        # plane and models/v2's leaf fn read these at import, so a
        # script can `export $(jq ...)` the sweep result straight into
        # the run that uses them
        env = {
            "TORRENT_TPU_SHA256_TILE_SUB": best["tile_sub"],
            "TORRENT_TPU_SHA256_UNROLL": best["unroll"],
            "TORRENT_TPU_SHA256_FULL_UNROLL": int(best["full_unroll"]),
            "TORRENT_TPU_SHA256_INTERLEAVE2": int(best["interleave2"]),
        }
        print(json.dumps({"best": best, "env": env, "block_kb": block_kb, "batch": batch}))
    return results


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--block-kb", type=int, default=16)
    ap.add_argument("--batch", type=int, default=32768)
    ap.add_argument("--grid", default="8x16,16x16,32x8,32x16,32x32")
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument(
        "--interpret",
        action="store_true",
        help="interpret-mode kernel (CPU smoke test of the sweep itself)",
    )
    args = ap.parse_args()
    run_sweep(
        args.block_kb, args.batch, _parse_grid(args.grid), args.iters, args.interpret
    )


if __name__ == "__main__":
    main()
