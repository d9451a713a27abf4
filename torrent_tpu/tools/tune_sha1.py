"""On-device Pallas SHA1 knob sweep (tile_sub x unroll).

Ranks kernel tilings by sustained hash-plane throughput on the real
chip. The method was chosen on a retired setup, not re-derived on this
one; it stays because it measures the kernel and not the host path:

- **Data lives on device.** The input batch is generated with the TPU
  PRNG; only two rows ever come back to the host (for the hashlib
  golden check), so the sweep times the kernel, not the upload.
- **Every timed dispatch is distinct.** The kernel input is
  ``rand ^ salt`` with a fresh salt per dispatch.
- **Completion is forced by fetching an on-device reduction** of the
  final dispatch's digests (the device executes in-order, so the last
  result landing implies the whole queue ran). The reduction executable
  is warmed before the timed loop.
- **The u32 fast path is what's measured** — host-order u32 input, the
  same form the verifier uploads (a u8 batch would add the 4x-widened
  bitcast fusion the production path exists to avoid).

Tilings are passed straight to ``sha1_pieces_pallas`` (they are call
parameters, not module state); the digest of the salt=0 warmup is
checked bit-exact against hashlib before any timing is trusted.

Usage::

    python -m torrent_tpu.tools.tune_sha1 [--piece-kb 256] [--batch 4096]
        [--grid 8x16,16x16,32x8,32x16] [--iters 8]

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


def _parse_grid(spec: str) -> list[tuple[int, int, bool]]:
    """``32x16`` → (32, 16, False); a trailing ``i`` (``32x16i``)
    selects the 2-way round-chain interleave variant (sha1_pallas
    ``interleave2`` — the BASELINE.md roofline knob, off by default in
    production until this sweep says it wins)."""
    out = []
    for part in spec.split(","):
        ts, un = part.lower().split("x")
        il2 = un.endswith("i")
        out.append((int(ts), int(un.rstrip("i")), il2))
    return out


def _pad_tail(plen: int) -> np.ndarray:
    """The 64-byte SHA1 padding block for a message of exactly ``plen``
    bytes (plen % 64 == 0, so the pad is a standalone final block)."""
    assert plen % 64 == 0
    tail = np.zeros(64, dtype=np.uint8)
    tail[0] = 0x80
    tail[-8:] = np.frombuffer((plen * 8).to_bytes(8, "big"), dtype=np.uint8)
    return tail


def run_sweep(
    piece_kb: int,
    batch: int,
    grid: list[tuple[int, int]],
    iters: int,
    interpret: bool = False,
):
    import jax

    if interpret:
        jax.config.update("jax_platforms", "cpu")  # smoke-test mode
    # a sweep compiles every grid config — persist the compiles so a
    # re-sweep skips straight to execution
    enable_compile_cache()
    import jax.numpy as jnp

    from torrent_tpu.ops import sha1_pallas as sp
    from torrent_tpu.ops.padding import num_blocks_for, padded_len_for

    plen = piece_kb * 1024
    padded = padded_len_for(plen)
    nblk = int(num_blocks_for(plen))  # true chain length; ghost tail is masked
    tail = np.zeros(padded - plen, dtype=np.uint8)
    tail[: 64] = _pad_tail(plen)[: min(64, padded - plen)]

    # One device-resident random payload (host-order u32 — the verifier's
    # fast path), shared by every config. Golden rows 0 and batch-1 come
    # back to the host exactly once. Generated in chunks: threefry's
    # temporaries are ~4x the output.
    key = jax.random.key(20260730)

    @functools.partial(jax.jit, static_argnames="rows")
    def _gen(k, rows):
        return jax.random.bits(k, (rows, plen // 4), jnp.uint32)

    rows_per = max(1, min(batch, (256 << 20) // plen))
    parts = []
    for i, start in enumerate(range(0, batch, rows_per)):
        parts.append(_gen(jax.random.fold_in(key, i), min(rows_per, batch - start)))
    rand = jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]
    del parts
    rand_rows = {
        i: np.asarray(rand[i]).view(np.uint8).tobytes() for i in (0, batch - 1)
    }
    golden = {i: hashlib.sha1(rand_rows[i]).digest() for i in rand_rows}
    tail_dev = jax.device_put(tail.view(np.uint32))
    nblocks = jnp.full((batch,), nblk, dtype=jnp.int32)

    results = []
    for tile_sub, unroll, il2 in grid:
        name = f"{tile_sub}x{unroll}{'i' if il2 else ''}"
        if batch % (tile_sub * 128):
            print(
                f"# skip {name}: batch {batch} not a multiple of "
                f"tile {tile_sub * 128}",
                file=sys.stderr,
            )
            continue

        @jax.jit
        def hash_salted(r, t, nb, salt, _ts=tile_sub, _un=unroll, _il2=il2):
            data = jnp.concatenate(
                [r ^ salt, jnp.broadcast_to(t, (batch, t.shape[0]))], axis=1
            )
            return sp.sha1_pieces_pallas(
                data,
                nb,
                interpret=interpret,
                tile_sub=_ts,
                unroll=_un,
                interleave2=_il2,
            )

        reduce_sum = jax.jit(lambda s: jnp.sum(s, dtype=jnp.uint32))

        try:
            t0 = time.perf_counter()
            state0 = hash_salted(rand, tail_dev, nblocks, jnp.uint32(0))
            got = np.asarray(state0[np.array([0, batch - 1])])
            compile_s = time.perf_counter() - t0
        except Exception as e:  # Mosaic can reject a tiling outright
            print(
                json.dumps(
                    {
                        "tile_sub": tile_sub,
                        "unroll": unroll,
                        "interleave2": il2,
                        "error": repr(e)[:200],
                    }
                )
            )
            continue
        for row, idx in ((0, 0), (1, batch - 1)):
            want = np.frombuffer(golden[idx], dtype=">u4").astype(np.uint32)
            if not np.array_equal(got[row], want):
                raise SystemExit(
                    f"golden mismatch at {name} row {idx}: "
                    f"{got[row]} != {want}"
                )
        _ = int(reduce_sum(state0))  # warm the completion-forcing reduction

        t0 = time.perf_counter()
        outs = [
            hash_salted(rand, tail_dev, nblocks, jnp.uint32(s))
            for s in range(1, iters + 1)
        ]
        _ = int(reduce_sum(outs[-1]))
        secs = time.perf_counter() - t0
        pps = iters * batch / secs
        line = {
            "tile_sub": tile_sub,
            "unroll": unroll,
            "interleave2": il2,
            "pieces_per_sec": round(pps, 1),
            "gib_per_sec": round(pps * plen / 2**30, 2),
            "compile_s": round(compile_s, 1),
        }
        results.append(line)
        print(json.dumps(line), flush=True)

    if results:
        best = max(results, key=lambda r: r["pieces_per_sec"])
        print(json.dumps({"best": best, "piece_kb": piece_kb, "batch": batch}))
    return results


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--piece-kb", type=int, default=256)
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--grid", default="8x16,16x16,32x8,32x16,32x16i,16x16i")
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument(
        "--interpret",
        action="store_true",
        help="interpret-mode kernel (CPU smoke test of the sweep itself)",
    )
    args = ap.parse_args()
    run_sweep(
        args.piece_kb, args.batch, _parse_grid(args.grid), args.iters, args.interpret
    )


if __name__ == "__main__":
    main()
