"""Plain reference of the ZAYA1 block stack (`model_type: zaya`,
huggingface.co/Zyphra/ZAYA1-8B): every layer one compressed
convolutional attention (CCA) sublayer and one expert sublayer under an
MLP router whose state runs from layer to layer, both behind a residual
whose two terms carry learned scales and biases; a final RMSNorm and a
head tied to the embedding.

Written from the equations of ISSUE 33 in plain `jax.numpy`, float32,
under `jax.default_matmul_precision("highest")`.  No kernels, no cache,
no batching trick: a full forward pass over whole sequences.  It
imports nothing from `singa_tpu` and is handed no array the program
made: weights come from `get_leaf(name)`, backed by
`benchmark.zaya_weights.leaf` (the seed's own values).

Block l (E hidden, H query heads and Hkv key/value heads of D, G = H /
Hkv, R the router's width, N experts of width F):

    x    <- (a1 x + b1) + (c1 CCA(RMSNorm(x)) + d1)
    x, r <- (a2 x + b2) + (c2 MoE(RMSNorm(x), r_prev) + d2)

CCA, token t:  q~ = x Wq (H D wide), k~ = x Wk (Hkv D); c = [q~ ; k~];
c' = a causal depthwise convolution of c over time (K0 taps, a bias);
c'' = per head a causal convolution whose every tap is a full D x D
matrix (K1 taps, a bias); m_q^(h) = (q~^(h) + k~^(g(h))) / 2 and m_k^(g)
its mean over the group's heads; q = c''[:H] + m_q, k = c''[H:] + m_k;
q^ = sqrt(D) q / sqrt(|q|^2 + 1e-6), k^ likewise times tau_g; RoPE on
the first `partial_rotary_factor` of each head's dims, paired half
against half; v_t = [x_t Wv1 ; x_{t-1} Wv2] (the second half of the
value heads is the token before's, zeros before the first token);
causal softmax(q^ . k^ / sqrt(D)) v with G query heads a key head; Wo.

Expert sublayer l: r = x Wd + bd, plus gamma_l * r_{l-1} from the second
layer on; p = softmax(W3 gelu(W2 gelu(W1 RMSNorm(r) + b1) + b2)), exact
GELU; e* = argmax(p + bal); out = p[e*] SwiGLU_{e*}(x); r goes on to
layer l + 1.

Departures from the published description, each noted:
 - `described_as` in the catalog says "MoD"; the published config's
   router is `num_experts` wide with no further (skip) output and no key
   for one, so none is built: every token goes through its one expert;
 - config.json has no key for the convolutions' biases, tau, the
   balancing bias, gamma, the residual's scales and biases, where the
   router reads (here: the sublayer's normed input), GELU's form, which
   dims RoPE turns or the 1e-6: they are as above and listed under
   `assumed` in the configuration's file;
 - only to fit the chip's memory: weights are asked for one layer at a
   time and dropped; the experts run one at a time over all tokens,
   masked by who chose them; the head runs one row of the batch at a
   time; `moe(..., first=)` with other stacked weights gives a share of
   the experts, for the test that adds the shares up.

`round_to` is the control of "How `correct` is decided": "fp8" / "bf16"
round both operands of every matmul (projections, the per-head mix, the
router's MLP, experts, attention, head) by `lax.reduce_precision` or a
scaled e4m3 cast.  With `round_to=None` this is the reference.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

GetLeaf = Callable[[str], jax.Array]
HIGHEST = "highest"
POSITIONS = 1024        # of one sequence whose logits exist at a time

CCA_LEAVES = ("wq", "wk", "wv1", "wv2", "conv0", "bias0", "conv1", "bias1",
              "tau", "wo")
MOE_LEAVES = ("router_down", "router_down_bias", "router_norm", "router_w1",
              "router_b1", "router_w2", "router_b2", "router_w3",
              "router_bias", "w_gate", "w_up", "w_down")
RESIDUAL_LEAVES = ("a", "b", "c", "d")


# -- the lower-precision controls --------------------------------------------

def _fp8(x):
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = amax / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _bf16(x):
    # not a pair of casts: XLA drops those as excess precision it may keep
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


ROUNDINGS = {None: lambda x: x, "fp8": _fp8, "bf16": _bf16}


# -- the layers ---------------------------------------------------------------

def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def shifted(x, by: int):
    """Row t of the result is row t - by of x (B, S, ...), zeros before
    the sequence's start."""
    if by == 0:
        return x
    pad = [(0, 0), (by, 0)] + [(0, 0)] * (x.ndim - 2)
    return jnp.pad(x, pad)[:, :x.shape[1]]


def unit(x):
    d = x.shape[-1]
    return x * math.sqrt(d) / jnp.sqrt(
        jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


def rope(x, rot: int, theta: float):
    """x (B, S, heads, D): the first `rot` dims of each head turned by
    the position, dim i paired with dim i + rot / 2."""
    s, half = x.shape[1], rot // 2
    inv = theta ** (-np.arange(half, dtype=np.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv    # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], -1)


def cca(x, w, cfg, round_to=None):
    r = ROUNDINGS[round_to]
    h, hk, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    g = h // hk
    b, s, _ = x.shape
    xr = r(x)
    qt, kt = xr @ r(w["wq"]), xr @ r(w["wk"])
    c = jnp.concatenate([qt, kt], -1)                        # (B, S, C)
    k0, k1 = w["conv0"].shape[1], w["conv1"].shape[1]
    c1 = sum(shifted(c, k0 - 1 - j) * w["conv0"][:, j]
             for j in range(k0)) + w["bias0"]
    c1 = c1.reshape(b, s, h + hk, d)
    c2 = sum(jnp.einsum("bshd,hde->bshe", r(shifted(c1, k1 - 1 - j)),
                        r(w["conv1"][:, j])) for j in range(k1)) + w["bias1"]
    qh = qt.reshape(b, s, hk, g, d)
    m_q = (qh + kt.reshape(b, s, hk, 1, d)) / 2
    m_k = jnp.mean(m_q, axis=3)
    q = unit(c2[:, :, :h] + m_q.reshape(b, s, h, d))
    k = unit(c2[:, :, h:] + m_k) * w["tau"][:, None]
    rot = int(d * cfg["partial_rotary_factor"]) // 2 * 2
    theta = cfg["rope_parameters"]["hybrid"]["rope_theta"]
    q, k = rope(q, rot, theta), rope(k, rot, theta)
    v = jnp.concatenate([xr @ r(w["wv1"]), shifted(xr @ r(w["wv2"]), 1)],
                        -1).reshape(b, s, hk, d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.einsum("bqhgd,bkhd->bhgqk", r(q.reshape(b, s, hk, g, d)), r(k))
    sc = jnp.where(causal, sc / math.sqrt(d), -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", r(p), r(v))
    return r(o.reshape(b, s, h * d)) @ r(w["wo"])


def router(x, state, w, cfg, r):
    """x (T, E) the normed input, state (T, R) the layer before's or
    None.  Returns (probabilities (T, N), this layer's state (T, R))."""
    s = x @ r(w["router_down"]) + w["router_down_bias"]
    if state is not None:
        s = s + w["gamma"] * state
    gelu = partial(jax.nn.gelu, approximate=False)
    hid = rms_norm(s, w["router_norm"], cfg["rms_norm_eps"])
    hid = gelu(r(hid) @ r(w["router_w1"]) + w["router_b1"])
    hid = gelu(r(hid) @ r(w["router_w2"]) + w["router_b2"])
    return jax.nn.softmax(r(hid) @ r(w["router_w3"]), axis=-1), s


def swiglu(x, gate, up, down, r):
    return r(jax.nn.silu(x @ r(gate)) * (x @ r(up))) @ r(down)


def moe(x, state, w, cfg, round_to=None, first: int = 0):
    """The experts w["w_gate"] etc. hold are routed experts `first` ..
    first + X - 1; what the others would add is left out.  Returns
    (out (B, S, E), the router's state (B, S, R))."""
    r = ROUNDINGS[round_to]
    b, s, e = x.shape
    xr = r(x.reshape(b * s, e))
    p, state = router(xr, None if state is None
                      else state.reshape(b * s, -1), w, cfg, r)
    _, idx = jax.lax.top_k(p + w["router_bias"], cfg["num_experts_per_tok"])
    weight = jnp.take_along_axis(p, idx, axis=-1)

    def expert(y, args):                                     # one at a time
        j, gate, up, down = args
        mine = jnp.sum(jnp.where(idx == first + j, weight, 0.0), axis=-1)
        return y + mine[:, None] * swiglu(xr, gate, up, down, r), None

    y, _ = jax.lax.scan(expert, jnp.zeros((b * s, e), jnp.float32),
                        (jnp.arange(w["w_gate"].shape[0]), w["w_gate"],
                         w["w_up"], w["w_down"]))
    return y.reshape(b, s, e), state.reshape(b, s, -1)


def residual(x, y, w):
    return (w["a"] * x + w["b"]) + (w["c"] * y + w["d"])


def block(x, state, w, cfg, round_to=None):
    """One layer: (x, the router state handed in or None) -> (x, the
    router state handed on)."""
    eps = cfg["rms_norm_eps"]
    x = residual(x, cca(rms_norm(x, w["mix_norm"], eps), w["cca"], cfg,
                        round_to), w["res_a"])
    y, state = moe(rms_norm(x, w["ffn_norm"], eps), state, w["moe"], cfg,
                   round_to)
    return residual(x, y, w["res_b"]), state


def layer_weights(get_leaf: GetLeaf, i: int) -> Dict:
    f32 = lambda n: get_leaf(f"L{i}.{n}").astype(jnp.float32)  # noqa: E731
    moe_names = MOE_LEAVES + (("gamma",) if i else ())
    return {"mix_norm": f32("mix_norm"), "ffn_norm": f32("ffn_norm"),
            "cca": {n: f32(f"cca.{n}") for n in CCA_LEAVES},
            "moe": {n: f32(f"zaya_moe.{n}") for n in moe_names},
            "res_a": {n: f32(f"res_a.{n}") for n in RESIDUAL_LEAVES},
            "res_b": {n: f32(f"res_b.{n}") for n in RESIDUAL_LEAVES}}


# -- serving: teacher-forced logits -------------------------------------------

class _Frozen(dict):
    def __hash__(self):
        return hash(repr(sorted(self.items())))


def _static(cfg: Dict) -> "_Frozen":
    """The sizes a traced function needs, hashable."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "partial_rotary_factor", "rms_norm_eps", "num_experts_per_tok")
    out = {k: cfg[k] for k in keys}
    out["rope_parameters"] = _Frozen(hybrid=_Frozen(
        rope_theta=cfg["rope_parameters"]["hybrid"]["rope_theta"]))
    return _Frozen(out)


@partial(jax.jit, static_argnums=(3, 4))
def _block(x, state, w, cfg, round_to):
    with jax.default_matmul_precision(HIGHEST):
        return block(x, state, w, cfg, round_to)


def hidden_states(tokens, get_leaf: GetLeaf, cfg: Dict, round_to=None,
                  rows: int = 2):
    """Final-norm hidden states (B, S, E) of `tokens` (B, S), `rows`
    rows of the batch at a time under each layer's weights."""
    scfg = _static(cfg)
    x = jnp.take(get_leaf("embed").astype(jnp.float32), tokens, axis=0)
    state = None
    for i in range(cfg["num_hidden_layers"]):
        w = layer_weights(get_leaf, i)
        outs = [_block(x[j:j + rows],
                       None if state is None else state[j:j + rows],
                       w, scfg, round_to)
                for j in range(0, x.shape[0], rows)]
        x = jnp.concatenate([o[0] for o in outs])
        state = jnp.concatenate([o[1] for o in outs])
        del w, outs
    return rms_norm(x, get_leaf("final_norm").astype(jnp.float32),
                    cfg["rms_norm_eps"])


@partial(jax.jit, static_argnums=(3,))
def _gap_rows(hid, embed, nxt, round_to):
    """For rows of hidden states (N, E): the reference's best logit
    minus its logit of `nxt` (N,), and the argmax token.  The head is
    the embedding, (V, E)."""
    r = ROUNDINGS[round_to]
    with jax.default_matmul_precision(HIGHEST):
        logits = jnp.einsum("ne,ve->nv", r(hid), r(embed))
    best = jnp.max(logits, axis=-1)
    mine = jnp.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]
    return best - mine, jnp.argmax(logits, axis=-1).astype(jnp.int32)


def logits(tokens, get_leaf: GetLeaf, cfg: Dict, round_to=None):
    """(B, S, V) float32 logits: for the CPU tests at small sizes."""
    hid = hidden_states(jnp.asarray(tokens, jnp.int32), get_leaf, cfg,
                        round_to)
    with jax.default_matmul_precision(HIGHEST):
        return jnp.einsum("bse,ve->bsv", hid,
                          get_leaf("embed").astype(jnp.float32))


def served_gaps(tokens: np.ndarray, nxt: np.ndarray, get_leaf: GetLeaf,
                cfg: Dict, control: Optional[str] = None):
    """`tokens` (B, S): each row a prompt followed by the tokens served
    for it (padded on the right; causality keeps padding out of every
    earlier position, in the convolutions as in the attention).  `nxt`
    (B, S): the token served after each position (any value where none
    was).

    Returns gap (B, S): by how much the reference's logit of the served
    next token lies below the reference's best, at every position.
    With `control`, also returns the same gap for the token that the
    control's forward pass puts first at each position."""
    tokens = jnp.asarray(tokens, jnp.int32)
    b, s = tokens.shape
    embed = get_leaf("embed").astype(jnp.float32)
    hid = hidden_states(tokens, get_leaf, cfg)
    nxt = jnp.asarray(nxt, jnp.int32)
    # a row's logits at this vocabulary are gigabytes: POSITIONS at a time
    cuts = [(i, j) for i in range(b) for j in range(0, s, POSITIONS)]
    at = lambda a, i, j: a[i, j:j + POSITIONS]               # noqa: E731
    gap = np.concatenate([np.asarray(_gap_rows(
        at(hid, i, j), embed, at(nxt, i, j), None)[0])
        for i, j in cuts]).reshape(b, s)
    if control is None:
        return gap
    hid_c = hidden_states(tokens, get_leaf, cfg, round_to=control)
    ctl = []
    for i, j in cuts:
        _, first = _gap_rows(at(hid_c, i, j), embed, at(nxt, i, j), control)
        ctl.append(np.asarray(_gap_rows(at(hid, i, j), embed, first,
                                        None)[0]))
    return gap, np.concatenate(ctl).reshape(b, s)
