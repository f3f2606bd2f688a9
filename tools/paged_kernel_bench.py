"""Microbenchmark of the paged decode attention kernel on the chip (a
builder's tool, not part of the benchmark): the kernel alone at the
serving cell's geometry under three slot mixes, against the gather
formulation, as seconds a layer and as a share of the live bytes'
time at the memory roofline.  `python tools/paged_kernel_bench.py`
prints one JSON line a measurement; fails off the TPU."""
import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from singa_tpu.ops import paged_attention as pa  # noqa: E402

S, H, HKV, D, BL, T = 32, 32, 8, 128, 16, 80
LAYERS = 16
HBM_BYTES_S = 819e9


def mixes(rng):
    chat = np.zeros(S, np.int32)
    chat[:16] = rng.integers(50, 400, 16)
    code = rng.integers(300, 1100, S).astype(np.int32)
    code[-2:] = 0
    return {"chat": chat, "code": code,
            "full": np.full(S, T * BL - 1, np.int32)}


def timed(fn, args, reps=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps / LAYERS


def chain(attn):
    """LAYERS calls in one program, each fed the one before."""
    def run(q, kp, vp, tables, ntoks):
        def body(_, x):
            return attn(x, kp, vp, tables, ntoks).astype(x.dtype)
        return jax.lax.fori_loop(0, LAYERS, body, q)
    return jax.jit(run)


def main():
    if jax.default_backend() != "tpu":
        sys.exit("paged_kernel_bench: JAX's default backend is not a TPU")
    rng = np.random.default_rng(0)
    nb = S * T + 1
    dt = jnp.bfloat16
    q = jnp.asarray(rng.standard_normal((S, H, D)), dt)
    kp = jnp.asarray(rng.standard_normal((nb, HKV, BL, D)), dt)
    vp = jnp.asarray(rng.standard_normal((nb, HKV, BL, D)), dt)
    tables = jnp.asarray(rng.permutation(np.arange(1, nb)).reshape(S, T)
                         .astype(np.int32))
    # float32 pools (chip_smoke.py's serve leg serves them): parity only
    f32 = [a.astype(jnp.float32) for a in (q, kp, vp)]
    nt = jnp.asarray(mixes(np.random.default_rng(1))["code"])
    err = jnp.max(jnp.abs(pa.paged_decode_attention(*f32, tables, nt)
                          - pa.paged_attention_reference(*f32, tables, nt)))
    print(json.dumps({"mix": "code", "what": "kernel_f32",
                      "max_err_vs_gather": float(err)}), flush=True)
    for name, ntoks in mixes(rng).items():
        nt = jnp.asarray(ntoks)
        live = int(np.sum(ntoks // BL + 1))
        need = live * 2 * HKV * BL * D * 2 / HBM_BYTES_S
        ref = pa.paged_attention_reference(q, kp, vp, tables, nt)
        rows = {"gather": pa.paged_attention_reference}
        for pos in (128, 256, 512):
            rows[f"kernel_{pos}"] = functools.partial(
                pa.singa_paged_decode, interpret=False, chunk=pos)
        for label, fn in rows.items():
            one = jax.jit(fn)(q, kp, vp, tables, nt)
            err = float(jnp.max(jnp.abs(one.astype(jnp.float32)
                                        - ref.astype(jnp.float32))))
            sec = timed(chain(fn), (q, kp, vp, tables, nt))
            print(json.dumps({
                "mix": name, "what": label, "live_blocks": live,
                "live_block_share": live / (S * T),
                "us_a_layer": sec * 1e6, "ms_a_step_16_layers":
                sec * LAYERS * 1e3, "roofline_share": need / sec,
                "max_err_vs_gather": err}), flush=True)


if __name__ == "__main__":
    main()
