"""Plain reference of the openPangu-Ultra-MoE block stack (`model_type:
pangu_ultra_moe`,
huggingface.co/FreedomIntelligence/openPangu-Ultra-MoE-718B): sandwich-
norm RMSNorm blocks whose mixer is multi-head latent attention (MLA)
with a low-rank query and rotated rope dimensions, leading dense SwiGLU
layers, then sparse experts (sigmoid router, top-k renormalised and
scaled, one shared expert), a final RMSNorm, an untied head, and one
multi-token prediction (MTP) module under the same embedding and head.

Written from the equations of ISSUE 39 in plain `jax.numpy`, float32,
under `jax.default_matmul_precision("highest")`.  No kernels, no cache:
every position attends the whole sequence under a causal mask.  It
imports nothing from `singa_tpu` and is handed no array the program
made: weights come from `get_leaf(name)`, backed by
`benchmark.pangu_weights.leaf` (the seed's own values).

Block l:  x += N2(mla(N1(x)));  x += N4(ffn_l(N3(x))), all RMSNorm.

MLA (H heads): cq = RMSNorm(x Wqa) (q_lora_rank); q = cq Wqb -> H x
(nope + rope dims); x Wkva -> rank + rope dims, c = RMSNorm(first
rank), k_pe = the rest, ONE row shared by the heads; q_pe of every head
and k_pe rotated by RoPE(rope_theta) at the token's position (dim j
paired with dim j + rope/2, no scaling); [k_nope | v] = c Wkvb per
head; scores (q_nope . k_nope + q_pe . k_pe) / sqrt(nope + rope), causal
softmax, Wo.  No bias.

MoE: s = sigmoid(x Wr) over all `router_width` experts; the k largest
chosen (no selection bias, no groups); weights s_i / (sum of the chosen
s) x routed_scaling_factor; SwiGLU experts, plus one shared SwiGLU
expert on every token.

MTP module, for position i with the main stack's output h_i (AFTER its
final norm) and the next token t_{i+1}: z_i = [RMSNorm_e(Emb(t_{i+1})) ;
RMSNorm_h(h_i)] W_eh; one block of the expert kind over z (its own
causal attention over positions 0..i); its own final RMSNorm; the main
model's head: the distribution of t_{i+2}.

Departures, each only to fit the chip's memory or the chip's share:
 - the share of a stated deployment: of the routed experts only
   `n_routed_experts` from `first_held_expert` are held and computed;
   what the others would add is left out (`moe(..., first=)` with other
   stacked weights gives another share, or all of them, for the test
   that adds the shares up); the vocabulary is the slice `vocab_size`;
 - depth: `num_hidden_layers` layers of which `first_k_dense_replace`
   are dense; the module whole;
 - weights are asked for one layer at a time and dropped; held experts
   run one at a time over all tokens, masked by who chose them; MLA runs
   one head at a time; rows of the batch run `rows` at a time, the head
   one row at a time.

Controls of "How `correct` is decided": `round_to` "fp8" rounds both
operands of every matmul (projections, experts, attention, head);
`rotate=False` leaves the rotation out (positions then reach the scores
not at all).  With neither this is the reference.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Callable, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

GetLeaf = Callable[[str], jax.Array]
HIGHEST = "highest"

MLA_LEAVES = ("wq_a", "q_norm", "wq", "w_kva", "kv_norm", "w_kvb", "wo")
DENSE_LEAVES = ("w_gate", "w_up", "w_down")
MOE_LEAVES = ("router", "w_gate", "w_up", "w_down", "shared_gate",
              "shared_up", "shared_down")
NORMS = ("mix_norm", "mix_post_norm", "ffn_norm", "ffn_post_norm")
CONTROLS = {None: (None, True), "fp8": ("fp8", True),
            "no_rope": (None, False)}      # name -> (round_to, rotate)


def _fp8(x):
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = amax / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


ROUNDINGS = {None: lambda x: x, "fp8": _fp8}


def layer_kinds(cfg: Dict) -> List[str]:
    """The ffn of each main layer."""
    dense = cfg["first_k_dense_replace"]
    return ["dense"] * dense + ["moe"] * (cfg["num_hidden_layers"] - dense)


def module_kinds(cfg: Dict) -> List[str]:
    """The ffn of each MTP module's block (leaf `L<num_hidden_layers +
    j>`): the expert kind."""
    return ["moe"] * cfg["num_nextn_predict_layers"]


# -- the layers ---------------------------------------------------------------

def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, theta: float):
    """x (B, S, ..., D) at positions 0..S-1: dim j turns with dim
    j + D/2 by position x theta^(-j / (D/2))."""
    s, half = x.shape[1], x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs  # (S, half)
    ang = ang.reshape((1, s) + (1,) * (x.ndim - 3) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def mla(x, w, cfg, round_to=None, rotate=True):
    r = ROUNDINGS[round_to]
    h = cfg["num_attention_heads"]
    nope, rpe, vd, rank = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                           cfg["v_head_dim"], cfg["kv_lora_rank"])
    eps = cfg["rms_norm_eps"]
    b, s, _ = x.shape
    xr = r(x)
    cq = rms_norm(xr @ r(w["wq_a"]), w["q_norm"], eps)
    q = (r(cq) @ r(w["wq"])).reshape(b, s, h, nope + rpe)
    kva = xr @ r(w["w_kva"])
    c = rms_norm(kva[..., :rank], w["kv_norm"], eps)
    q_nope, q_pe, k_pe = q[..., :nope], q[..., nope:], kva[..., rank:]
    if rotate:
        q_pe, k_pe = rope(q_pe, cfg["rope_theta"]), rope(k_pe,
                                                         cfg["rope_theta"])
    kvb = jnp.moveaxis(r(w["w_kvb"]).reshape(rank, h, nope + vd), 1, 0)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def head(args):                                          # a head a time
        qn, qp, wh = args              # (B, S, nope), (B, S, rope), (rank, .)
        kv = r(c) @ wh                                       # (B, S, .)
        sc = (jnp.einsum("bqd,bkd->bqk", r(qn), r(kv[..., :nope]))
              + jnp.einsum("bqd,bkd->bqk", r(qp), r(k_pe)))
        sc = jnp.where(causal[None], sc / math.sqrt(nope + rpe), -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bqk,bkd->bqd", r(p), r(kv[..., nope:]))

    o = jax.lax.map(head, (jnp.moveaxis(q_nope, 2, 0),
                           jnp.moveaxis(q_pe, 2, 0), kvb))   # (H, B, S, vd)
    o = jnp.moveaxis(o, 0, 2).reshape(b, s, h * vd)
    return r(o) @ r(w["wo"])


def swiglu(x, gate, up, down, r):
    return r(jax.nn.silu(x @ r(gate)) * (x @ r(up))) @ r(down)


def route(x, w, cfg, r):
    """Chosen experts (T, k) and their weights (T, k), over ALL routed
    experts."""
    s = jax.nn.sigmoid(x @ r(w["router"]))
    chosen, idx = jax.lax.top_k(s, cfg["num_experts_per_tok"])
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


def block(x, w, ffn, cfg, round_to=None, rotate=True):
    r = ROUNDINGS[round_to]
    eps = cfg["rms_norm_eps"]
    y = mla(rms_norm(x, w["mix_norm"], eps), w["mix"], cfg, round_to, rotate)
    x = x + rms_norm(y, w["mix_post_norm"], eps)
    y = rms_norm(x, w["ffn_norm"], eps)
    if ffn == "dense":
        f = w["ffn"]
        y = swiglu(r(y), f["w_gate"], f["w_up"], f["w_down"], r)
    else:
        y = moe(y, w["ffn"], cfg, round_to)
    return x + rms_norm(y, w["ffn_post_norm"], eps)


def layer_weights(get_leaf: GetLeaf, i: int, ffn: str) -> Dict:
    f32 = lambda n: get_leaf(f"L{i}.{n}").astype(jnp.float32)  # noqa: E731
    fnames = DENSE_LEAVES if ffn == "dense" else MOE_LEAVES
    out = {n: f32(n) for n in NORMS}
    out["mix"] = {n: f32(f"mla.{n}") for n in MLA_LEAVES}
    out["ffn"] = {n: f32(f"{'ffn' if ffn == 'dense' else 'moe'}.{n}")
                  for n in fnames}
    return out


def module_entry(hidden, e_next, w, cfg, round_to=None):
    """z = [RMSNorm_e(e_next) ; RMSNorm_h(hidden)] W_eh."""
    r = ROUNDINGS[round_to]
    eps = cfg["rms_norm_eps"]
    z = jnp.concatenate([rms_norm(e_next, w["e_norm"], eps),
                         rms_norm(hidden, w["h_norm"], eps)], -1)
    return r(z) @ r(w["w_eh"])


# -- whole passes -------------------------------------------------------------

class _Frozen(dict):
    def __hash__(self):
        return hash(repr(sorted(self.items())))


def _static(cfg: Dict) -> "_Frozen":
    """The sizes a traced function needs, hashable."""
    keys = ("num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "rms_norm_eps", "rope_theta", "num_experts_per_tok",
            "norm_topk_prob", "routed_scaling_factor", "first_held_expert",
            "n_shared_experts")
    return _Frozen({k: cfg[k] for k in keys})


@partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _block(x, w, ffn, cfg, round_to, rotate):
    with jax.default_matmul_precision(HIGHEST):
        return block(x, w, ffn, cfg, round_to, rotate)


@partial(jax.jit, static_argnums=(3, 4))
def _entry(hidden, e_next, w, cfg, round_to):
    with jax.default_matmul_precision(HIGHEST):
        return module_entry(hidden, e_next, w, cfg, round_to)


def _stack(x, get_leaf, cfg, first: int, kinds, round_to, rotate, rows):
    scfg = _static(cfg)
    for i, ffn in enumerate(kinds, start=first):
        w = layer_weights(get_leaf, i, ffn)
        x = jnp.concatenate([_block(x[j:j + rows], w, ffn, scfg, round_to,
                                    rotate)
                             for j in range(0, x.shape[0], rows)])
        del w
    return x


def hidden_states(tokens, get_leaf: GetLeaf, cfg: Dict, round_to=None,
                  rotate=True, rows: int = 1):
    """The main stack's final-norm hidden states (B, S, E) of `tokens`
    (B, S), `rows` rows of the batch at a time under each layer's
    weights."""
    x = jnp.take(get_leaf("embed").astype(jnp.float32), tokens, axis=0)
    x = _stack(x, get_leaf, cfg, 0, layer_kinds(cfg), round_to, rotate, rows)
    return rms_norm(x, get_leaf("final_norm").astype(jnp.float32),
                    cfg["rms_norm_eps"])


def module_states(hidden, next_tokens, get_leaf: GetLeaf, cfg: Dict,
                  round_to=None, rotate=True, rows: int = 1):
    """The MTP module's final-norm hidden states (B, S, E): `hidden` the
    main stack's (`hidden_states`), `next_tokens` (B, S) the token after
    each position."""
    f32 = lambda n: get_leaf(n).astype(jnp.float32)          # noqa: E731
    w = {n: f32(f"mtp.{n}") for n in ("e_norm", "h_norm", "w_eh")}
    e_next = jnp.take(f32("embed"), next_tokens, axis=0)
    x = jnp.concatenate([_entry(hidden[j:j + rows], e_next[j:j + rows], w,
                                _static(cfg), round_to)
                         for j in range(0, hidden.shape[0], rows)])
    del w, e_next
    x = _stack(x, get_leaf, cfg, cfg["num_hidden_layers"], module_kinds(cfg),
               round_to, rotate, rows)
    return rms_norm(x, f32("mtp.final_norm"), cfg["rms_norm_eps"])


def logits(tokens, get_leaf: GetLeaf, cfg: Dict, round_to=None, rotate=True):
    """(B, S, V) float32 logits of the main model, and the module's
    ((B, S, V): position i's are for token i + 2, the tokens shifted by
    one as its input, zeros' embedding... the last position sees token
    0 as the one after it): for the CPU tests at small sizes."""
    tokens = jnp.asarray(tokens, jnp.int32)
    hid = hidden_states(tokens, get_leaf, cfg, round_to, rotate)
    nxt = jnp.concatenate([tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], 1)
    mod = module_states(hid, nxt, get_leaf, cfg, round_to, rotate)
    with jax.default_matmul_precision(HIGHEST):
        head = get_leaf("head").astype(jnp.float32)
        return hid @ head, mod @ head


class Served(NamedTuple):
    """What `served_logprobs` reads at every position, (B, S) each."""
    logp: np.ndarray       # the main model's log-probability of `nxt`
    logq: np.ndarray       # the module's of `drafts`
    entropy: np.ndarray    # of the main model's distribution p there
    spread: np.ndarray     # the variance of log p(x) under x ~ p there
    accept: np.ndarray     # sum_x min(p_{i+1}(x), q_i(x)): the chance that
    #                        the draft made from row i is accepted


@partial(jax.jit, static_argnums=(5, 6))
def _served_rows(hid, mod, head, nxt, drafts, temperature, round_to):
    """One sequence's rows of `Served`, from its hidden states (S, E)
    of the main stack and of the module (temperature 0: as 1).  The
    draft made from row i stands for token i + 2, which the main model
    draws from row i + 1: the lossless rule accepts it with probability
    sum_x min(p_{i+1}(x), q_i(x)) (0 at the last row)."""
    r = ROUNDINGS[round_to]
    t = temperature or 1.0
    with jax.default_matmul_precision(HIGHEST):
        logp = jax.nn.log_softmax(r(hid) @ r(head) / t, axis=-1)
        logq = jax.nn.log_softmax(r(mod) @ r(head) / t, axis=-1)
    at = lambda lg, ids: jnp.take_along_axis(                # noqa: E731
        lg, ids[:, None], axis=-1)[:, 0]
    p = jnp.exp(logp)
    entropy = -(p * logp).sum(-1)
    spread = (p * logp * logp).sum(-1) - entropy * entropy
    accept = jnp.minimum(p[1:], jnp.exp(logq[:-1])).sum(-1)
    return (at(logp, nxt), at(logq, drafts), entropy, spread,
            jnp.concatenate([accept, jnp.zeros((1,), accept.dtype)]))


def served_logprobs(tokens: np.ndarray, nxt: np.ndarray, drafts: np.ndarray,
                    get_leaf: GetLeaf, cfg: Dict, temperature: float,
                    control: Optional[str] = None, rows: int = 1) -> Served:
    """`tokens` (B, S): each row a prompt followed by the tokens served
    for it (padded on the right; causality keeps padding out of every
    earlier position).  `nxt` (B, S): the token served after each
    position; `drafts` (B, S): the token the program's module drafted
    from each position's row, for the position two on (any value where
    there was none).

    Returns `Served` at the temperature the tokens were drawn at: the
    two log-probabilities the program reports, and what SAMPLING from
    the main model and the lossless rule have to give on average (the
    entropy, the chance of acceptance).  With `control` ("fp8",
    "no_rope") those of the control's forward pass instead."""
    round_to, rotate = CONTROLS[control]
    tokens = jnp.asarray(tokens, jnp.int32)
    head = get_leaf("head").astype(jnp.float32)
    hid = hidden_states(tokens, get_leaf, cfg, round_to, rotate, rows)
    mod = module_states(hid, jnp.asarray(nxt, jnp.int32), get_leaf, cfg,
                        round_to, rotate, rows)
    each = [_served_rows(hid[i], mod[i], head, jnp.asarray(nxt[i], jnp.int32),
                         jnp.asarray(drafts[i], jnp.int32),
                         float(temperature), round_to)
            for i in range(hid.shape[0])]
    return Served(*(np.stack([np.asarray(e[k]) for e in each])
                    for k in range(len(Served._fields))))


@partial(jax.jit, static_argnums=())
def _gap_rows(hid, head, nxt):
    with jax.default_matmul_precision(HIGHEST):
        z = hid @ head
    mine = jnp.take_along_axis(z, nxt[:, None], axis=-1)[:, 0]
    return jnp.max(z, axis=-1) - mine


def served_gaps(tokens: np.ndarray, nxt: np.ndarray, get_leaf: GetLeaf,
                cfg: Dict, rows: int = 1):
    """For GREEDY serving (temperature 0), as the other configurations'
    references have it: by how much the reference's logit of the served
    next token lies below the reference's best, (B, S)."""
    head = get_leaf("head").astype(jnp.float32)
    hid = hidden_states(jnp.asarray(tokens, jnp.int32), get_leaf, cfg,
                        rows=rows)
    return np.stack([np.asarray(_gap_rows(hid[i], head,
                                          jnp.asarray(nxt[i], jnp.int32)))
                     for i in range(hid.shape[0])])
