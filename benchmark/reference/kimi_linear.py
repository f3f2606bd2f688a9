"""Plain reference of the Kimi-Linear block stack (`model_type:
kimi_linear`, huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct):
pre-norm RMSNorm blocks whose mixer is Kimi Delta Attention (KDA) or
NoPE multi-head latent attention (MLA) by the config's layer lists, a
leading dense SwiGLU layer, then sparse experts (sigmoid router with a
selection bias, top-k renormalised and scaled, one shared expert), a
final RMSNorm and an untied head.

Written from the equations of ISSUE 27 in plain `jax.numpy`, float32,
under `jax.default_matmul_precision("highest")`.  No kernels, no cache,
no chunking: the KDA recurrence is a scan over single tokens.  It
imports nothing from `singa_tpu` and is handed no array the program
made: weights come from `get_leaf(name)`, backed by
`benchmark.kimi_weights.leaf` (the seed's own values).

Block l = 1..L:  x += mixer_l(RMSNorm(x));  x += ffn_l(RMSNorm(x)).

KDA (H heads, d_k = d_v = D): q~, k~, v~ = x Wq, x Wk, x Wv; each through
a causal depthwise convolution over time (kernel K, y_t = sum_j w[:, j]
x_{t-K+1+j}, zeros before the sequence) and SiLU; per head q = q~ /
sqrt(|q~|^2 + 1e-6) / sqrt(D), k likewise without the 1/sqrt(D), v = v~;
beta = sigmoid(x Wbeta); g = -exp(A_log_h) softplus(Wfb (Wfa x) +
dt_bias) per head and key channel; S' = diag(exp g_t) S_{t-1}; u =
beta_t (v_t - S'^T k_t); S_t = S' + k_t u^T; o_t = S_t^T q_t; output
RMSNorm_D(o) * sigmoid(Wgb (Wga x)) per head, then Wo.

MLA (H heads): q = x Wq -> H x (nope + rope dims); x Wkva -> rank + rope
dims, c = RMSNorm(first rank), k_pe = the rest (shared by the heads, not
rotated: `mla_use_nope`); [k_nope | v] = c Wkvb per head; scores (q_nope
. k_nope + q_pe . k_pe) / sqrt(nope + rope), causal softmax, Wo.

MoE: s = sigmoid(x Wr) over all routed experts; the k with the largest
s + b chosen; weights s_i / (sum of the chosen s) x routed_scaling_factor;
SwiGLU experts, plus one shared SwiGLU expert on every token.

Departures, each only to fit the chip's memory or the chip's share:
 - the share of a stated deployment: of the routed experts only
   `num_experts` from `first_held_expert` are held and computed; what the
   others would add is left out (`moe(..., first=)` with other stacked
   weights gives another share, or all of them, for the test that adds
   the shares up); the vocabulary is the
   slice `vocab_size`;
 - weights are asked for one layer at a time and dropped; held experts
   run one at a time over all tokens, masked by who chose them; MLA runs
   one head at a time; the head runs one row of the batch at a time;
 - the l2 norm's 1e-6 sits inside the square root, as in the public
   implementation's kernel.

`round_to` is the control of "How `correct` is decided": "fp8" / "bf16"
round both operands of every matmul (projections, experts, attention,
head; the recurrence's own sums stay float32); "state_bf16" rounds the
KDA state to bfloat16 after every token and leaves everything else
alone.  With `round_to=None` this is the reference.
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

KDA_LEAVES = ("wq", "wk", "wv", "conv_q", "conv_k", "conv_v", "w_beta",
              "w_fa", "w_fb", "a_log", "dt_bias", "w_ga", "w_gb", "o_norm",
              "wo")
MLA_LEAVES = ("wq", "w_kva", "kv_norm", "w_kvb", "wo")
DENSE_LEAVES = ("w_gate", "w_up", "w_down")
MOE_LEAVES = ("router", "router_bias", "w_gate", "w_up", "w_down",
              "shared_gate", "shared_up", "shared_down")


# -- the lower-precision controls --------------------------------------------

def _fp8(x):
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = amax / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _bf16(x):
    # not a pair of casts: XLA drops those as excess precision it may keep
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


_SAME = lambda x: x                                          # noqa: E731
MATMUL_ROUNDINGS = {None: _SAME, "fp8": _fp8, "bf16": _bf16,
                    "state_bf16": _SAME}
STATE_ROUNDINGS = {None: _SAME, "fp8": _SAME, "bf16": _SAME,
                   "state_bf16": _bf16}


# -- which layer is what ------------------------------------------------------

def layer_kinds(cfg: Dict) -> List[Tuple[str, str]]:
    """(mixer, ffn) of published layers 1..num_hidden_layers."""
    lin = cfg["linear_attn_config"]
    out = []
    for l in range(1, cfg["num_hidden_layers"] + 1):
        if l in lin["full_attn_layers"]:
            mixer = "mla"
        elif l in lin["kda_layers"]:
            mixer = "kda"
        else:
            raise ValueError(f"layer {l} is in neither list")
        out.append((mixer, "dense" if l <= cfg["first_k_dense_replace"]
                    else "moe"))
    return out


# -- the layers ---------------------------------------------------------------

def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def short_conv(x, w):
    """x (B, S, C), w (C, K): causal depthwise conv, then SiLU."""
    k = w.shape[1]
    s = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, j:j + s] * w[:, j] for j in range(k)))


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


def kda(x, w, cfg, round_to=None):
    r, rs = MATMUL_ROUNDINGS[round_to], STATE_ROUNDINGS[round_to]
    lin = cfg["linear_attn_config"]
    h, d = lin["num_heads"], lin["head_dim"]
    b, s, _ = x.shape
    xr = r(x)
    heads = lambda a: a.reshape(b, s, h, d)                  # noqa: E731
    q = heads(short_conv(xr @ r(w["wq"]), w["conv_q"]))
    k = heads(short_conv(xr @ r(w["wk"]), w["conv_k"]))
    v = heads(short_conv(xr @ r(w["wv"]), w["conv_v"]))
    q, k = l2norm(q) * d ** -0.5, l2norm(k)
    beta = jax.nn.sigmoid(xr @ r(w["w_beta"]))               # (B, S, H)
    f = r(xr @ r(w["w_fa"])) @ r(w["w_fb"]) + w["dt_bias"]
    g = -jnp.exp(w["a_log"])[:, None] * heads(jax.nn.softplus(f))

    def step(state, xs):                     # state (B, H, Dk, Dv)
        qt, kt, vt, gt, bt = xs
        state = state * jnp.exp(gt)[..., None]
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", state, kt))
        state = rs(state + kt[..., None] * u[..., None, :])
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt)

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((b, h, d, d), jnp.float32), xs)
    o = jnp.moveaxis(o, 0, 1)                                # (B, S, H, D)
    gate = jax.nn.sigmoid(r(xr @ r(w["w_ga"])) @ r(w["w_gb"]))
    o = rms_norm(o, w["o_norm"], cfg["rms_norm_eps"]) * heads(gate)
    return r(o.reshape(b, s, h * d)) @ r(w["wo"])


def mla(x, w, cfg, round_to=None):
    r = MATMUL_ROUNDINGS[round_to]
    h = cfg["num_attention_heads"]
    nope, rope, vd, rank = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                            cfg["v_head_dim"], cfg["kv_lora_rank"])
    b, s, _ = x.shape
    xr = r(x)
    q = (xr @ r(w["wq"])).reshape(b, s, h, nope + rope)
    kva = xr @ r(w["w_kva"])
    c = rms_norm(kva[..., :rank], w["kv_norm"], cfg["rms_norm_eps"])
    k_pe = kva[..., rank:]                                   # (B, S, rope)
    kvb = jnp.moveaxis(r(w["w_kvb"]).reshape(rank, h, nope + vd), 1, 0)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def head(args):                                          # a head a time
        qh, wh = args                        # (B, S, nope + rope), (rank, .)
        kv = r(c) @ wh                                       # (B, S, .)
        sc = (jnp.einsum("bqd,bkd->bqk", r(qh[..., :nope]),
                         r(kv[..., :nope]))
              + jnp.einsum("bqd,bkd->bqk", r(qh[..., nope:]), r(k_pe)))
        sc = jnp.where(causal[None], sc / math.sqrt(nope + rope), -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bqk,bkd->bqd", r(p), r(kv[..., nope:]))

    o = jax.lax.map(head, (jnp.moveaxis(q, 2, 0), kvb))      # (H, B, S, vd)
    o = jnp.moveaxis(o, 0, 2).reshape(b, s, h * vd)
    return r(o) @ r(w["wo"])


def swiglu(x, gate, up, down, r):
    return r(jax.nn.silu(x @ r(gate)) * (x @ r(up))) @ r(down)


def route(x, w, cfg, r):
    """Chosen experts (T, k) and their weights (T, k), over ALL routed
    experts."""
    s = jax.nn.sigmoid(x @ r(w["router"]))
    _, idx = jax.lax.top_k(s + w["router_bias"], cfg["num_experts_per_token"])
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["moe_renormalize"]:
        chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return idx, chosen * cfg["routed_scaling_factor"]


def moe(x, w, cfg, round_to=None, first: Optional[int] = None,
        shared: bool = True):
    """The experts w["w_gate"] etc. hold are routed experts `first` ..
    first + X - 1; what the others would add is left out."""
    r = MATMUL_ROUNDINGS[round_to]
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
    r = MATMUL_ROUNDINGS[round_to]
    eps = cfg["rms_norm_eps"]
    y = rms_norm(x, w["mix_norm"], eps)
    x = x + (kda if mixer == "kda" else mla)(y, w["mix"], cfg, round_to)
    y = rms_norm(x, w["ffn_norm"], eps)
    if ffn == "dense":
        f = w["ffn"]
        return x + swiglu(r(y), f["w_gate"], f["w_up"], f["w_down"], r)
    return x + moe(y, w["ffn"], cfg, round_to)


def layer_weights(get_leaf: GetLeaf, i: int, kind) -> Dict:
    f32 = lambda n: get_leaf(f"L{i}.{n}").astype(jnp.float32)  # noqa: E731
    mixer, ffn = kind
    names = KDA_LEAVES if mixer == "kda" else MLA_LEAVES
    fnames = DENSE_LEAVES if ffn == "dense" else MOE_LEAVES
    return {"mix_norm": f32("mix_norm"), "ffn_norm": f32("ffn_norm"),
            "mix": {n: f32(f"{mixer}.{n}") for n in names},
            "ffn": {n: f32(f"{'ffn' if ffn == 'dense' else 'moe'}.{n}")
                    for n in fnames}}


# -- serving: teacher-forced logits -------------------------------------------

class _Frozen(dict):
    def __hash__(self):
        return hash(repr(sorted(self.items())))


def _static(cfg: Dict) -> "_Frozen":
    """The sizes a traced function needs, hashable."""
    keys = ("linear_attn_config", "num_attention_heads", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "rms_norm_eps",
            "num_experts_per_token", "moe_renormalize",
            "routed_scaling_factor", "first_held_expert",
            "num_shared_experts")
    out = {k: cfg[k] for k in keys}
    out["linear_attn_config"] = _Frozen(
        {k: tuple(v) if isinstance(v, list) else v
         for k, v in cfg["linear_attn_config"].items()})
    return _Frozen(out)


@partial(jax.jit, static_argnums=(2, 3, 4))
def _block(x, w, kind, cfg, round_to):
    with jax.default_matmul_precision(HIGHEST):
        return block(x, w, kind, cfg, round_to)


def hidden_states(tokens, get_leaf: GetLeaf, cfg: Dict, round_to=None,
                  rows: int = 3):
    """Final-norm hidden states (B, S, E) of `tokens` (B, S), `rows`
    rows of the batch at a time under each layer's weights."""
    scfg = _static(cfg)
    x = jnp.take(get_leaf("embed").astype(jnp.float32), tokens, axis=0)
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
    r = MATMUL_ROUNDINGS[round_to]
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
    earlier position, in the recurrence as in the attention).  `nxt`
    (B, S): the token served after each position (any value where none
    was).

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
