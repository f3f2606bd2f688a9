"""Plain reference of the Trinity block stack (`model_type: afmoe`,
huggingface.co/arcee-ai/Trinity-Mini): sandwich-normed blocks (an
RMSNorm before AND after each sublayer) whose mixer is gated, QK-normed
grouped-query attention, windowed with RoPE in three layers of four and
full with no positional encoding in the fourth; leading dense SwiGLU
layers, then sparse experts (sigmoid router with a selection bias, top-k
renormalised and scaled, one shared expert); an embedding scaled by
sqrt(hidden_size), a final RMSNorm and an untied head.

Written from the equations of ISSUE 35 in plain `jax.numpy`, float32,
under `jax.default_matmul_precision("highest")`.  No kernels, no cache,
no batching trick: a full forward pass over whole sequences.  It
imports nothing from `singa_tpu` and is handed no array the program
made: weights come from `get_leaf(name)`, backed by
`benchmark.trinity_weights.leaf` (the seed's own values).

With h the stream, N_x an RMSNorm (learned scale) and layer i from 0:

    h = E[token] * sqrt(hidden_size)                      (mup_enabled)
    h = h + N_post_attn(Attn_i(N_in(h)))
    h = h + N_post_mlp(Mlp_i(N_pre_mlp(h)))
    logits = N_f(h) Wout

Attn_i(x): q = x Wq (H heads of D), k = x Wk, v = x Wv (Hkv heads), g =
x Wg (H D wide); q and k each through an RMSNorm over a head's D dims
(one learned D-vector for q, one for k, shared by the heads).  If
layer_types[i] is "sliding_attention": RoPE on q and k over all D dims
(dim j paired with dim j + D / 2, rope_theta, no scaling) and query t
sees keys t - W + 1 .. t, W = sliding_window.  If "full_attention": no
positional encoding at all, and query t sees keys 0 .. t.  Scores /
sqrt(D), softmax, each of the H / Hkv query heads of a group on its
group's K/V head.  Output (sigmoid(g) * o) Wo.

Mlp_i, i < num_dense_layers: SwiGLU of width intermediate_size.  Else: s
= sigmoid(x Wr) over all routed experts; the k with the largest s + b
chosen (b moves the choice only; one group, so no group limit); weights
s_chosen / (sum of the chosen s + 1e-20) x route_scale; SwiGLU experts,
plus one shared SwiGLU expert on every token.

Departures, each only to fit the chip's memory or the chip's share:
 - the share of a stated deployment: of the routed experts only
   `num_experts` from `first_held_expert` are held and computed; what the
   others would add is left out (`moe(..., first=)` with other stacked
   weights gives another share, or all of them, for the test that adds
   the shares up); the vocabulary is the slice `vocab_size`;
 - weights are asked for one layer at a time and dropped; held experts
   run one at a time over all tokens, masked by who chose them; attention
   runs `QUERY_BLOCK` queries at a time against all keys; the layers and
   the head run one row of the batch at a time.

`round_to` is a control of "How `correct` is decided": "fp8" / "bf16"
round both operands of every matmul (projections, experts, attention,
head); "no_window" leaves the precision alone and takes the window out
(the windowed layers see every key, RoPE kept): what a served model
that ignored its window would compute.  With `round_to=None` this is
the reference.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

GetLeaf = Callable[[str], jax.Array]
HIGHEST = "highest"
QUERY_BLOCK = 1024      # queries whose scores exist at a time

ATTENTION_LEAVES = ("wq", "wk", "wv", "wg", "q_norm", "k_norm", "wo")
DENSE_LEAVES = ("w_gate", "w_up", "w_down")
MOE_LEAVES = ("router", "router_bias", "w_gate", "w_up", "w_down",
              "shared_gate", "shared_up", "shared_down")
NORM_LEAVES = ("mix_norm", "mix_post_norm", "ffn_norm", "ffn_post_norm")


# -- the controls -------------------------------------------------------------

def _fp8(x):
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = amax / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _bf16(x):
    # not a pair of casts: XLA drops those as excess precision it may keep
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


_SAME = lambda x: x                                          # noqa: E731
ROUNDINGS = {None: _SAME, "fp8": _fp8, "bf16": _bf16, "no_window": _SAME}


# -- which layer is what ------------------------------------------------------

def layer_kinds(cfg: Dict) -> List[Tuple[str, str]]:
    """(mixer, ffn) of published layers 0..num_hidden_layers - 1: the
    mixer "sliding" or "full" by `layer_types`, the ffn "dense" for the
    leading `num_dense_layers`, "moe" after."""
    names = {"sliding_attention": "sliding", "full_attention": "full"}
    return [(names[cfg["layer_types"][i]],
             "dense" if i < cfg["num_dense_layers"] else "moe")
            for i in range(cfg["num_hidden_layers"])]


# -- the layers ---------------------------------------------------------------

def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, theta: float):
    """x (B, S, heads, D): every head turned by its position, dim j
    paired with dim j + D / 2."""
    s, half = x.shape[1], x.shape[-1] // 2
    inv = theta ** (-np.arange(half, dtype=np.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv    # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, w, cfg, mixer: str, round_to=None):
    r = ROUNDINGS[round_to]
    h, hk, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    g = h // hk
    b, s, _ = x.shape
    xr = r(x)
    eps = cfg["rms_norm_eps"]
    q = rms_norm((xr @ r(w["wq"])).reshape(b, s, h, d), w["q_norm"], eps)
    k = rms_norm((xr @ r(w["wk"])).reshape(b, s, hk, d), w["k_norm"], eps)
    v = (xr @ r(w["wv"])).reshape(b, s, hk, d)
    gate = jax.nn.sigmoid(xr @ r(w["wg"]))
    window = 0
    if mixer == "sliding":
        q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
        if round_to != "no_window":
            window = cfg["sliding_window"]
    kr, vr = r(k), r(v)
    kpos = jnp.arange(s)

    def queries(args):              # QUERY_BLOCK queries against all keys
        qb, qpos = args             # (B, Q, hk, g, d), (Q,)
        seen = kpos[None, :] <= qpos[:, None]
        if window:
            seen = seen & (kpos[None, :] > qpos[:, None] - window)
        sc = jnp.einsum("bqhgd,bkhd->bhgqk", r(qb), kr) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", r(p), vr)

    qb = min(QUERY_BLOCK, s)
    n = -(-s // qb)
    pad = n * qb - s                # padded queries stand at the last position
    qp = jnp.pad(q.reshape(b, s, hk, g, d),
                 ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    o = jax.lax.map(queries, (
        jnp.moveaxis(qp.reshape(b, n, qb, hk, g, d), 1, 0),
        jnp.minimum(jnp.arange(n * qb), s - 1).reshape(n, qb)))
    o = jnp.moveaxis(o, 0, 1).reshape(b, n * qb, h * d)[:, :s]
    return r(gate * o) @ r(w["wo"])


def swiglu(x, gate, up, down, r):
    return r(jax.nn.silu(x @ r(gate)) * (x @ r(up))) @ r(down)


def route(x, w, cfg, r):
    """Chosen experts (T, k) and their weights (T, k), over ALL routed
    experts."""
    s = jax.nn.sigmoid(x @ r(w["router"]))
    _, idx = jax.lax.top_k(s + w["router_bias"], cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["route_norm"]:
        chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return idx, chosen * cfg["route_scale"]


def moe(x, w, cfg, round_to=None, first: Optional[int] = None,
        shared: bool = True):
    """The experts w["w_gate"] etc. hold are routed experts `first` ..
    first + X - 1; what the others would add is left out."""
    r = ROUNDINGS[round_to]
    first = cfg["first_held_expert"] if first is None else first
    b, s, e = x.shape
    xr = r(x.reshape(b * s, e))
    idx, weight = route(xr, w, cfg, r)

    def expert(y, args):                                     # one at a time
        j, gate, up, down = args
        mine = jnp.sum(jnp.where(idx == first + j, weight, 0.0), axis=-1)
        return y + mine[:, None] * swiglu(xr, gate, up, down, r), None

    y, _ = jax.lax.scan(expert, jnp.zeros((b * s, e), jnp.float32),
                        (jnp.arange(w["w_gate"].shape[0]), w["w_gate"],
                         w["w_up"], w["w_down"]))
    if shared and cfg["num_shared_experts"]:
        y = y + swiglu(xr, w["shared_gate"], w["shared_up"],
                       w["shared_down"], r)
    return y.reshape(b, s, e)


def block(x, w, kind, cfg, round_to=None):
    mixer, ffn = kind
    r = ROUNDINGS[round_to]
    eps = cfg["rms_norm_eps"]
    a = attention(rms_norm(x, w["mix_norm"], eps), w["mix"], cfg, mixer,
                  round_to)
    x = x + rms_norm(a, w["mix_post_norm"], eps)
    y = rms_norm(x, w["ffn_norm"], eps)
    if ffn == "dense":
        f = w["ffn"]
        m = swiglu(r(y), f["w_gate"], f["w_up"], f["w_down"], r)
    else:
        m = moe(y, w["ffn"], cfg, round_to)
    return x + rms_norm(m, w["ffn_post_norm"], eps)


def layer_weights(get_leaf: GetLeaf, i: int, kind) -> Dict:
    f32 = lambda n: get_leaf(f"L{i}.{n}").astype(jnp.float32)  # noqa: E731
    ffn = "ffn" if kind[1] == "dense" else "moe"
    out = {n: f32(n) for n in NORM_LEAVES}
    out["mix"] = {n: f32(f"attention.{n}") for n in ATTENTION_LEAVES}
    out["ffn"] = {n: f32(f"{ffn}.{n}")
                  for n in (DENSE_LEAVES if ffn == "ffn" else MOE_LEAVES)}
    return out


# -- serving: teacher-forced logits -------------------------------------------

class _Frozen(dict):
    def __hash__(self):
        return hash(repr(sorted(self.items())))


def _static(cfg: Dict) -> "_Frozen":
    """The sizes a traced function needs, hashable."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "sliding_window", "rope_theta", "rms_norm_eps",
            "num_experts_per_tok", "route_norm", "route_scale",
            "first_held_expert", "num_shared_experts")
    return _Frozen({k: cfg[k] for k in keys})


@partial(jax.jit, static_argnums=(2, 3, 4))
def _block(x, w, kind, cfg, round_to):
    with jax.default_matmul_precision(HIGHEST):
        return block(x, w, kind, cfg, round_to)


def embed_scale(cfg: Dict) -> float:
    """muP's multiplier on the embedding's rows."""
    return math.sqrt(cfg["hidden_size"]) if cfg["mup_enabled"] else 1.0


def hidden_states(tokens, get_leaf: GetLeaf, cfg: Dict, round_to=None,
                  rows: int = 1):
    """Final-norm hidden states (B, S, E) of `tokens` (B, S), `rows`
    rows of the batch at a time under each layer's weights."""
    scfg = _static(cfg)
    x = jnp.take(get_leaf("embed").astype(jnp.float32), tokens,
                 axis=0) * embed_scale(cfg)
    for i, kind in enumerate(layer_kinds(cfg)):
        w = layer_weights(get_leaf, i, kind)
        x = jnp.concatenate([_block(x[j:j + rows], w, kind, scfg, round_to)
                             for j in range(0, x.shape[0], rows)])
        del w
    return rms_norm(x, get_leaf("final_norm").astype(jnp.float32),
                    cfg["rms_norm_eps"])


@partial(jax.jit, static_argnums=(3,))
def _gap_rows(hid, head, nxt, round_to):
    """For rows of hidden states (N, E): the reference's best logit
    minus its logit of `nxt` (N,), and the argmax token."""
    r = ROUNDINGS[round_to]
    with jax.default_matmul_precision(HIGHEST):
        logits = r(hid) @ r(head)
    best = jnp.max(logits, axis=-1)
    mine = jnp.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]
    return best - mine, jnp.argmax(logits, axis=-1).astype(jnp.int32)


def logits(tokens, get_leaf: GetLeaf, cfg: Dict, round_to=None):
    """(B, S, V) float32 logits: for the CPU tests at small sizes."""
    hid = hidden_states(jnp.asarray(tokens, jnp.int32), get_leaf, cfg,
                        round_to)
    with jax.default_matmul_precision(HIGHEST):
        return hid @ get_leaf("head").astype(jnp.float32)


def served_gaps(tokens: np.ndarray, nxt: np.ndarray, get_leaf: GetLeaf,
                cfg: Dict, control: Optional[str] = None):
    """`tokens` (B, S): each row a prompt followed by the tokens served
    for it (padded on the right; causality keeps padding out of every
    earlier position).  `nxt` (B, S): the token served after each
    position (any value where none was).

    Returns gap (B, S): by how much the reference's logit of the served
    next token lies below the reference's best, at every position.
    With `control`, also returns the same gap for the token that the
    control's forward pass puts first at each position."""
    tokens = jnp.asarray(tokens, jnp.int32)
    b, s = tokens.shape
    head = get_leaf("head").astype(jnp.float32)
    hid = hidden_states(tokens, get_leaf, cfg)
    gap = np.stack([np.asarray(_gap_rows(hid[i], head,
                                         jnp.asarray(nxt[i], jnp.int32),
                                         None)[0]) for i in range(b)])
    if control is None:
        return gap
    hid_c = hidden_states(tokens, get_leaf, cfg, round_to=control)
    ctl = np.empty((b, s), np.float32)
    for i in range(b):
        _, first = _gap_rows(hid_c[i], head, jnp.zeros((s,), jnp.int32),
                             control)
        ctl[i] = np.asarray(_gap_rows(hid[i], head, first, None)[0])
    return gap, ctl
