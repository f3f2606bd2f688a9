"""Plain reference of the Solar-Open2 block stack (`model_type:
solar_open2`, huggingface.co/upstage/Solar-Open2-250B): pre-norm RMSNorm
blocks whose mixer is, in the layers `gqa_layers` lists (0, 4, 8, ...),
gated grouped-query softmax attention with NO positional encoding and,
in the three layers after each, Kimi Delta Attention with negative
eigenvalues allowed; sparse experts behind every mixer (sigmoid router
with a selection bias, top-k renormalised and scaled, one shared
expert; no dense layer: `first_k_dense_replace` 0); a final RMSNorm and
an untied head.

Written from the equations of ISSUE 43 in plain `jax.numpy`, float32,
under `jax.default_matmul_precision("highest")`.  No kernels, no cache,
no chunks: the KDA recurrence is a scan over single tokens from a zero
state, attention is a dense softmax over every earlier position.  It
imports nothing from `singa_tpu` and is handed no array the program
made: weights come from `get_leaf(name)`, backed by
`benchmark.solar_weights.leaf` (the seed's own values).

Block l = 0..L-1:  x += mixer_l(RMSNorm(x));  x += moe_l(RMSNorm(x)).

GQA (H heads, Hkv key/value heads, D dims): q = x Wq, k = x Wk, v = x Wv,
g = x Wg (H D wide); no rotation, no QK norm; scores / sqrt(D), causal
softmax, each of the H / Hkv query heads of a group on its group's K/V
head; output (sigmoid(g) * o) Wo.

KDA (H heads, d_k = d_v = D): q~, k~, v~ = x Wq, x Wk, x Wv; each through
a causal depthwise convolution over time (kernel K, y_t = sum_j w[:, j]
x_{t-K+1+j}, zeros before the sequence) and SiLU; per head q = q~ /
sqrt(|q~|^2 + 1e-6) / sqrt(D), k likewise without the 1/sqrt(D), v = v~;
beta = 2 sigmoid(x Wbeta) (`kda_allow_neg_eigval`: the transition
I - beta k k^T has eigenvalues in [-1, 1]); g = -exp(A_log_h)
softplus(Wfb (Wfa x) + dt_bias) per head and key channel; S' =
diag(exp g_t) S_{t-1}; u = beta_t (v_t - S'^T k_t); S_t = S' + k_t u^T;
o_t = S_t^T q_t; output RMSNorm_D(o) * sigmoid(Wgb (Wga x)) per head,
then Wo.

MoE: s = sigmoid(x Wr) over all routed experts; the k with the largest
s + b chosen; weights s_i / (sum of the chosen s) (`norm_topk_prob`) x
routed_scaling_factor; SwiGLU experts, plus one shared SwiGLU expert on
every token.

Departures, each only to fit the chip's memory or the chip's share:
 - the share of a stated deployment: of the routed experts only
   `n_routed_experts` from `first_held_expert` are held and computed;
   what the others would add is left out (`moe(..., first=)` with other
   stacked weights gives another share, or all of them, for the test
   that adds the shares up); the vocabulary is the slice `vocab_size`;
 - the reference runs IN BLOCKS so that a sequence of 33,792 positions
   at the published widths fits the chip beside nothing else: weights
   are asked for one layer at a time and dropped; one sequence at a
   time, cut to whole `LENGTH_STEP`s behind its last token; KDA runs
   `KDA_HEADS` heads at a time, attention `QUERY_BLOCK` queries at a
   time against all keys, held experts one at a time over all tokens,
   masked by who chose them; the head runs on the compared rows only.
   None of these changes a sum's operands.

`round_to` is a control of "How `correct` is decided": "fp8" / "bf16"
round both operands of every matmul (projections, experts, attention,
head; the recurrence's own sums stay float32); "pos_eig" leaves the
precision alone and does not double beta (a served model that ignored
`kda_allow_neg_eigval`).  `served_gaps` has one more, "cold_chunk": the
forward pass starts at the last chunk boundary before the prompt's end
(state, tails and every earlier key dropped there: what a chunked
prefill that carried nothing over would compute).  With
`round_to=None` this is the reference.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

GetLeaf = Callable[[str], jax.Array]
HIGHEST = "highest"
QUERY_BLOCK = 256       # queries whose scores exist at a time
KDA_HEADS = 16          # KDA heads whose q, k, v, g exist at a time
LENGTH_STEP = 4096      # a served sequence is cut to whole steps

ATTENTION_LEAVES = ("wq", "wk", "wv", "wg", "wo")
KDA_LEAVES = ("wq", "wk", "wv", "conv_q", "conv_k", "conv_v", "w_beta",
              "w_fa", "w_fb", "a_log", "dt_bias", "w_ga", "w_gb", "o_norm",
              "wo")
MOE_LEAVES = ("router", "router_bias", "w_gate", "w_up", "w_down",
              "shared_gate", "shared_up", "shared_down")


# -- the controls -------------------------------------------------------------

def _fp8(x):
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = amax / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _bf16(x):
    # not a pair of casts: XLA drops those as excess precision it may keep
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


_SAME = lambda x: x                                          # noqa: E731
ROUNDINGS = {None: _SAME, "fp8": _fp8, "bf16": _bf16, "pos_eig": _SAME}


# -- which layer is what ------------------------------------------------------

def layer_kinds(cfg: Dict) -> List[str]:
    """The mixer of published layers 0..num_hidden_layers - 1: "gqa" in
    the layers `gqa_layers` lists, "kda" in the others.  Every layer's
    ffn is sparse (`first_k_dense_replace` 0)."""
    if cfg["first_k_dense_replace"]:
        raise ValueError("a leading dense layer is not this model's")
    return ["gqa" if i in cfg["gqa_layers"] else "kda"
            for i in range(cfg["num_hidden_layers"])]


# -- the layers ---------------------------------------------------------------

def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def attention(x, w, cfg, round_to=None):
    r = ROUNDINGS[round_to]
    h, hk, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    g = h // hk
    b, s, _ = x.shape
    xr = r(x)
    q = (xr @ r(w["wq"])).reshape(b, s, hk, g, d)
    kr = r((xr @ r(w["wk"])).reshape(b, s, hk, d))
    vr = r((xr @ r(w["wv"])).reshape(b, s, hk, d))
    gate = jax.nn.sigmoid(xr @ r(w["wg"]))
    kpos = jnp.arange(s)

    def queries(args):              # QUERY_BLOCK queries against all keys
        qb, qpos = args             # (B, Q, hk, g, d), (Q,)
        seen = kpos[None, :] <= qpos[:, None]
        sc = jnp.einsum("bqhgd,bkhd->bhgqk", r(qb), kr) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", r(p), vr)

    qb = min(QUERY_BLOCK, s)
    n = -(-s // qb)
    pad = n * qb - s                # padded queries stand at the last position
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    o = jax.lax.map(queries, (
        jnp.moveaxis(qp.reshape(b, n, qb, hk, g, d), 1, 0),
        jnp.minimum(jnp.arange(n * qb), s - 1).reshape(n, qb)))
    o = jnp.moveaxis(o, 0, 1).reshape(b, n * qb, h * d)[:, :s]
    return r(gate * o) @ r(w["wo"])


def short_conv(x, w):
    """x (B, S, C), w (C, K): causal depthwise conv, then SiLU."""
    k = w.shape[1]
    s = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, j:j + s] * w[:, j] for j in range(k)))


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


def kda(x, w, cfg, round_to=None):
    r = ROUNDINGS[round_to]
    lin = cfg["linear_attn_config"]
    h, d = lin["num_heads"], lin["head_dim"]
    doubled = 1.0 if round_to == "pos_eig" else 2.0
    if not cfg["kda_allow_neg_eigval"]:
        doubled = 1.0
    b, s, _ = x.shape
    xr = r(x)
    n = min(KDA_HEADS, h)
    assert h % n == 0, (h, n)
    beta = doubled * jax.nn.sigmoid(xr @ r(w["w_beta"]))     # (B, S, H)
    low = r(xr @ r(w["w_fa"]))                               # (B, S, D)

    def heads(args):                # `n` heads from projection to o
        wq, wk, wv, cq, ck, cv, wfb, dt, a_log, bt = args
        part = lambda a: a.reshape(b, s, n, d)               # noqa: E731
        q = part(short_conv(xr @ r(wq), cq))
        k = part(short_conv(xr @ r(wk), ck))
        v = part(short_conv(xr @ r(wv), cv))
        q, k = l2norm(q) * d ** -0.5, l2norm(k)
        gg = -jnp.exp(a_log)[:, None] * part(jax.nn.softplus(
            low @ r(wfb) + dt))

        def step(state, xs):                 # state (B, n, Dk, Dv)
            qt, kt, vt, gt, bb = xs
            state = state * jnp.exp(gt)[..., None]
            u = bb[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", state, kt))
            state = state + kt[..., None] * u[..., None, :]
            return state, jnp.einsum("bhkv,bhk->bhv", state, qt)

        xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, gg, bt))
        _, o = jax.lax.scan(step, jnp.zeros((b, n, d, d), jnp.float32), xs)
        return jnp.moveaxis(o, 0, 1)                         # (B, S, n, D)

    cols = lambda a: jnp.moveaxis(                           # noqa: E731
        a.reshape(a.shape[0], h // n, n * d), 1, 0)
    rows = lambda a: a.reshape(h // n, n * d, -1)            # noqa: E731
    o = jax.lax.map(heads, (
        cols(w["wq"]), cols(w["wk"]), cols(w["wv"]), rows(w["conv_q"]),
        rows(w["conv_k"]), rows(w["conv_v"]), cols(w["w_fb"]),
        w["dt_bias"].reshape(h // n, n * d), w["a_log"].reshape(h // n, n),
        jnp.moveaxis(beta.reshape(b, s, h // n, n), 2, 0)))
    o = jnp.moveaxis(o, 0, 2).reshape(b, s, h, d)
    gate = jax.nn.sigmoid(r(xr @ r(w["w_ga"])) @ r(w["w_gb"]))
    o = rms_norm(o, w["o_norm"], cfg["rms_norm_eps"]) * gate.reshape(
        b, s, h, d)
    return r(o.reshape(b, s, h * d)) @ r(w["wo"])


def swiglu(x, gate, up, down, r):
    return r(jax.nn.silu(x @ r(gate)) * (x @ r(up))) @ r(down)


def route(x, w, cfg, r):
    """Chosen experts (T, k) and their weights (T, k), over ALL routed
    experts."""
    s = jax.nn.sigmoid(x @ r(w["router"]))
    _, idx = jax.lax.top_k(s + w["router_bias"], cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return idx, chosen * cfg["routed_scaling_factor"]


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
    if shared and cfg["n_shared_experts"]:
        y = y + swiglu(xr, w["shared_gate"], w["shared_up"],
                       w["shared_down"], r)
    return y.reshape(b, s, e)


def block(x, w, mixer, cfg, round_to=None):
    eps = cfg["rms_norm_eps"]
    y = rms_norm(x, w["mix_norm"], eps)
    x = x + (kda if mixer == "kda" else attention)(y, w["mix"], cfg,
                                                   round_to)
    return x + moe(rms_norm(x, w["ffn_norm"], eps), w["ffn"], cfg, round_to)


def layer_weights(get_leaf: GetLeaf, i: int, mixer: str) -> Dict:
    f32 = lambda n: get_leaf(f"L{i}.{n}").astype(jnp.float32)  # noqa: E731
    kind, names = (("kda", KDA_LEAVES) if mixer == "kda"
                   else ("attention", ATTENTION_LEAVES))
    return {"mix_norm": f32("mix_norm"), "ffn_norm": f32("ffn_norm"),
            "mix": {n: f32(f"{kind}.{n}") for n in names},
            "ffn": {n: f32(f"moe.{n}") for n in MOE_LEAVES}}


# -- serving: teacher-forced logits -------------------------------------------

class _Frozen(dict):
    def __hash__(self):
        return hash(repr(sorted(self.items())))


def _static(cfg: Dict) -> "_Frozen":
    """The sizes a traced function needs, hashable."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor", "first_held_expert",
            "n_shared_experts", "kda_allow_neg_eigval")
    out = {k: cfg[k] for k in keys}
    out["linear_attn_config"] = _Frozen(cfg["linear_attn_config"])
    return _Frozen(out)


@partial(jax.jit, static_argnums=(2, 3, 4))
def _block(x, w, mixer, cfg, round_to):
    with jax.default_matmul_precision(HIGHEST):
        return block(x, w, mixer, cfg, round_to)


def hidden_states(tokens, get_leaf: GetLeaf, cfg: Dict, round_to=None):
    """Final-norm hidden states (B, S, E) of `tokens` (B, S), one row of
    the batch at a time under each layer's weights."""
    scfg = _static(cfg)
    x = jnp.take(get_leaf("embed").astype(jnp.float32), tokens, axis=0)
    for i, mixer in enumerate(layer_kinds(cfg)):
        w = layer_weights(get_leaf, i, mixer)
        x = jnp.concatenate([_block(x[j:j + 1], w, mixer, scfg, round_to)
                             for j in range(x.shape[0])])
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


def _rounded_up(n: int, step: int) -> int:
    return -(-n // step) * step


def served_gaps(seqs: Sequence[np.ndarray], plens: Sequence[int],
                get_leaf: GetLeaf, cfg: Dict, control: Optional[str] = None,
                chunk: int = 0, length_step: int = LENGTH_STEP):
    """`seqs`: each a prompt (its first `plens[i]` tokens) followed by
    the tokens served for it.  For every request: gap, by how much the
    reference's logit of each served token lies below the reference's
    best at the position before it (len(seq) - plen values, the first
    the token behind the prompt's last).  With `control`, a second list:
    the same gap for the token that the control's forward pass puts
    first at each of those positions ("cold_chunk": the pass over the
    sequence from the last multiple of `chunk` under the prompt's end).

    One sequence at a time, right-padded to whole `length_step`s:
    causality keeps the padding out of every compared position, in the
    recurrence as in the attention."""
    head = get_leaf("head").astype(jnp.float32)

    def hidden(seq, round_to):
        width = _rounded_up(len(seq), length_step)
        toks = np.zeros((1, width), np.int32)
        toks[0, :len(seq)] = seq
        return hidden_states(jnp.asarray(toks), get_leaf, cfg, round_to)[0]

    gaps, ctls = [], []
    for seq, plen in zip(seqs, plens):
        seq = np.asarray(seq, np.int32)
        at = np.arange(plen - 1, len(seq) - 1)   # positions that were served
        nxt = jnp.asarray(seq[at + 1])
        hid = hidden(seq, None)[at]
        gaps.append(np.asarray(_gap_rows(hid, head, nxt, None)[0]))
        if control is None:
            continue
        if control == "cold_chunk":
            cut = (plen - 1) // chunk * chunk
            hid_c, rounding = hidden(seq[cut:], None)[at - cut], None
        else:
            hid_c, rounding = hidden(seq, control)[at], control
        _, first = _gap_rows(hid_c, head, nxt, rounding)
        ctls.append(np.asarray(_gap_rows(hid, head, first, None)[0]))
    return gaps if control is None else (gaps, ctls)
