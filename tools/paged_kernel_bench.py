"""Microbenchmark of the paged decode attention kernel on the chip (a
builder's tool, not part of the benchmark): the kernel alone at the
serving geometries (the dense cells' pool of keys and values, the Kimi
cell's pool of latent rows and the Pangu cell's under two query rows
of 128 heads, the ZAYA cell's narrow pool, the Trinity cell's pool
under a growing table and under a ring read through a window), each
under its slot mixes, against the gather formulation, as seconds a
layer and as a share of the live bytes' time at the memory roofline,
at the chunk, the issue group and the extent the kernel's rules give
and at fixed ones beside them, the copies a call issues and the bytes
one of them moves on every row.  A one-head geometry's table is what
the paged cache hands out (serve/kvcache.py): aligned extents of
consecutive blocks, here each slot's row one run, so that any extent
can be tried; the row `kernel_scattered` reads a shuffled table a block
a copy, as every call did before PR 41.
`python tools/paged_kernel_bench.py [geometry ...]` prints one JSON
line a measurement; fails off the TPU."""
import functools
import json
import math
import sys
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from singa_tpu.ops import paged_attention as pa  # noqa: E402

HBM_BYTES_S = 819e9
MXU_FLOP_S = 197e12


class Geometry(NamedTuple):
    """One caller's arguments: a pool of (slots x table + 1, sides x
    hkv, bl, d) bf16 (`sides` 2: a block's keys and then its values; 1:
    latent rows whose leading columns are the value), the calls a
    decode step makes, and what the caller passes beside the arrays."""
    slots: int
    heads: int
    hkv: int
    d: int
    bl: int
    table: int
    layers: int
    sides: int
    scale: float
    value_dim: int
    window: int = 0         # > 0: `table` is the ring's width
    rows: int = 1           # query rows a slot (`heads` a row)


GEOMETRIES = {
    # mistral7b-serve-l16: kAttention
    "dense": Geometry(32, 32, 8, 128, 16, 80, 16, 2, 1 / math.sqrt(128), 128),
    # kimilinear-serve-l17-ep8: kMLA, rank 512 + rope 64 stored as 640
    "latent": Geometry(96, 32, 1, 640, 16, 128, 4, 1,
                       1 / math.sqrt(192), 512),
    # openpangu-ultra-moe-serve-l5-ep32: kMLA at 128 heads, a verify
    # step's two rows a slot, five layers and the module's one
    "latent128x2": Geometry(64, 128, 1, 640, 16, 448, 6, 1,
                            1 / math.sqrt(192), 512, rows=2),
    # the same pool under one query row: T = a + b x rows
    "latent128": Geometry(64, 128, 1, 640, 16, 448, 6, 1,
                          1 / math.sqrt(192), 512),
    # zaya1-8b-serve-l16: kCCA
    "cca": Geometry(64, 8, 2, 128, 16, 256, 16, 2, 1 / math.sqrt(128), 128),
    # trinity-mini-serve-l16-ep8: kAttention, 4 full layers under the
    # growing table, 12 windowed under a ring of 129 blocks a slot
    "table": Geometry(64, 32, 4, 128, 16, 512, 4, 2, 1 / math.sqrt(128), 128),
    "ring": Geometry(64, 32, 4, 128, 16, 129, 12, 2, 1 / math.sqrt(128), 128,
                     window=2048),
}


def mixes(name, g, rng):
    full = np.full(g.slots, g.table * g.bl - 1, np.int32)
    if name in ("table", "ring"):
        # the Trinity cell's full house: 1k of prompt and 0-6k generated
        agent = 300 + rng.exponential(2300, g.slots)
        return {"agent": np.minimum(agent, 8000).astype(np.int32),
                "full": np.full(g.slots, 8191, np.int32)}
    if name in ("latent128x2", "latent128"):
        # the Pangu cell's full house: 64 slots of up to 7,168 rows, a
        # mean of ~2,050 (the ledger's ~132 k live rows a call, PR 39);
        # the same draw scaled to the 110,698 rows PR 39's builder read
        # the kernel alone at
        think = np.minimum(300 + rng.exponential(1850, g.slots), 7168)
        return {"think": think.astype(np.int32),
                "probe39": (think * (110698 / think.sum())).astype(np.int32),
                "full": full - (g.rows - 1)}
    if name == "latent":
        # the Kimi cell's full house: cb_live_block_share 0.22
        return {"assist": rng.integers(150, 750, g.slots).astype(np.int32),
                "full": full}
    if name == "cca":
        # the ZAYA cell's full house: rows of 300 to 4,000 tokens, mean
        # ~1,100, live_block_share.zaya 0.26
        reason = 300 + rng.exponential(800, g.slots)
        return {"reason": np.minimum(reason, 4000).astype(np.int32),
                "full": full}
    chat = np.zeros(g.slots, np.int32)
    chat[:16] = rng.integers(50, 400, 16)
    code = rng.integers(300, 1100, g.slots).astype(np.int32)
    code[-2:] = 0
    return {"chat": chat, "code": code, "full": full}


def timed(fn, args, layers, reps=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps / layers


def chain(attn, layers):
    """`layers` calls in one program, each fed the one before."""
    def run(q, pool, tables, ntoks):
        def body(_, x):
            out = attn(x, pool, tables, ntoks).astype(x.dtype)
            return jnp.pad(out, ((0, 0), (0, 0),
                                 (0, x.shape[-1] - out.shape[-1])))
        return jax.lax.fori_loop(0, layers, body, q)
    return jax.jit(run)


def bench(name, g):
    rng = np.random.default_rng(0)
    nb = g.slots * g.table + 1
    dt = jnp.bfloat16
    q = jnp.asarray(
        rng.standard_normal((g.slots, g.rows * g.heads, g.d)), dt)
    pool = jnp.asarray(
        rng.standard_normal((nb, g.sides * g.hkv, g.bl, g.d)), dt)
    copy_bytes = pool[0].size * pool.dtype.itemsize
    scattered = jnp.asarray(rng.permutation(np.arange(1, nb))
                            .reshape(g.slots, g.table).astype(np.int32))
    tables = scattered
    how = {"scale": g.scale}
    if g.sides == 1:
        how["value_dim"] = g.value_dim
        # a slot's row one run of the pool's blocks, begun at 1 + k E
        # for every E that divides the table
        tables = jnp.arange(1, nb, dtype=jnp.int32).reshape(g.slots, g.table)
    if g.rows > 1:
        how["rows"] = g.rows
    if g.window:
        how["window"] = g.window
    gather = functools.partial(pa.paged_attention_reference, **how)
    # float32 pools (chip_smoke.py's serve leg serves them): parity only
    f32 = [q.astype(jnp.float32), pool.astype(jnp.float32)]
    last = list(mixes(name, g, np.random.default_rng(1)).values())[0]
    err = jnp.max(jnp.abs(
        pa.paged_decode_attention(*f32, tables, jnp.asarray(last), **how)
        - gather(*f32, tables, jnp.asarray(last))))
    print(json.dumps({"geometry": name, "what": "kernel_f32",
                      "max_err_vs_gather": float(err)}), flush=True)
    for mix, ntoks in mixes(name, g, rng).items():
        nt = jnp.asarray(ntoks)
        first = np.maximum(ntoks - g.window + 1, 0) // g.bl if g.window else 0
        # the walk goes to a slot's last query row
        horizon = ntoks + g.rows - 1
        live = int(np.sum(horizon // g.bl - first + 1))
        need = live * copy_bytes / HBM_BYTES_S
        # scores over a row's D columns and values of `value_dim`, for
        # every query head of every row, at each live block's positions
        work = (live * g.bl * g.rows * g.heads * 2 * (g.d + g.value_dim)
                / MXU_FLOP_S)
        ref = gather(q, pool, tables, nt)
        ruled = pa.chunk_positions(pool.shape, pool.dtype,
                                   how.get("value_dim"))
        extent = 1 if g.window else pa.extent_blocks(
            pool.shape, pool.dtype, how.get("value_dim"), g.table)
        fixed = functools.partial(pa.singa_paged_decode, interpret=False,
                                  **how)
        at = functools.partial(fixed, chunk=ruled)
        rows = {"gather": (gather, None, None, 1),
                "kernel": (functools.partial(pa.paged_decode_attention,
                                             **how), ruled, pa._ISSUE_GROUP,
                           extent)}
        for pos in (128, 256, 512, 1024):
            e = math.gcd(extent, pos // g.bl)     # divides the chunk
            rows[f"kernel_{pos}"] = (
                functools.partial(fixed, chunk=pos, extent=e), pos,
                pa._ISSUE_GROUP, e)
        for group in (4, 8, 16):
            rows[f"kernel_group_{group}"] = (functools.partial(
                at, group=group, extent=extent), ruled, group, extent)
        if g.sides == 1 and g.hkv == 1 and not g.window:
            for e in (1, 2, 4, 8, 16, 32):
                rows[f"kernel_extent_{e}"] = (functools.partial(
                    at, extent=e), ruled, pa._ISSUE_GROUP, e)
            rows["kernel_scattered"] = (at, ruled, pa._ISSUE_GROUP, 1)
        for label, (fn, chunk, group, e) in rows.items():
            table = scattered if label == "kernel_scattered" else tables
            want = ref if table is tables else gather(q, pool, table, nt)
            one = jax.jit(fn)(q, pool, table, nt)
            err = float(jnp.max(jnp.abs(one.astype(jnp.float32)
                                        - want.astype(jnp.float32))))
            sec = timed(chain(fn, g.layers), (q, pool, table, nt),
                        g.layers)
            copies = (live if e == 1
                      else int(np.sum(horizon // (g.bl * e) + 1)))
            print(json.dumps({
                "geometry": name, "mix": mix, "what": label,
                "live_blocks": live,
                "live_block_share": live / (g.slots * g.table),
                "chunk_positions": chunk, "issue_group": group,
                "extent_blocks": e,
                "copy_bytes": copy_bytes * e,
                "copies_a_call": copies,
                "us_a_layer": sec * 1e6,
                f"ms_a_step_{g.layers}_layers": sec * g.layers * 1e3,
                "roofline_share": need / sec,
                "operations_share": work / sec,
                "max_err_vs_gather": err}), flush=True)


def main():
    if jax.default_backend() != "tpu":
        sys.exit("paged_kernel_bench: JAX's default backend is not a TPU")
    for name in sys.argv[1:] or GEOMETRIES:
        bench(name, GEOMETRIES[name])


if __name__ == "__main__":
    main()
