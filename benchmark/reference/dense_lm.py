"""Plain reference of the dense decoder-only LM the cells run
(Mistral-7B-v0.3's block): pre-norm RMSNorm, grouped-query attention
with rotary embeddings (rotate-half layout, theta from the config),
causal softmax, SwiGLU, untied output head; its mean next-token
cross-entropy, gradients, and three steps of Adam.

Written from the published description in plain `jax.numpy`, float32,
under `jax.default_matmul_precision("highest")` (on a TPU a float32
matmul otherwise runs in bf16 passes).  No kernels, no cache, no
batching tricks.  It imports nothing from `singa_tpu` and is handed no
array the program made: weights come from `get_leaf(name)`, which the
caller backs with `benchmark.weights.leaf` (the seed's own values).

Departures from a textbook forward pass, each only to fit the chip's
memory beside nothing else: weights are asked for one layer at a time
and dropped; attention runs one key/value group at a time; the head
runs over blocks of rows; in training each block is rematerialised.

`round_to` is the control of "How `correct` is decided": it rounds both
operands of every matmul to a lower precision (float8 e4m3 with one
scale per tensor) and leaves everything else alone.  With
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
LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "ffn_norm", "w_gate",
                "w_up", "w_down")


# -- the lower-precision control --------------------------------------------

def _fp8(x):
    """Round to float8 e4m3 with one scale per tensor (the largest
    magnitude maps to the format's largest value, 448)."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = amax / 448.0
    return _straight_through(
        x, (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s)


def _bf16(x):
    return _straight_through(x, x.astype(jnp.bfloat16).astype(jnp.float32))


def _straight_through(x, rounded):
    """The rounded value forward, the identity backward (a cast's own
    derivative would round the tangents too, and in fp8 flush them)."""
    return x + jax.lax.stop_gradient(rounded - x)


ROUNDINGS = {None: lambda x: x, "fp8": _fp8, "bf16": _bf16}


# -- the block ---------------------------------------------------------------

def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, positions, theta):
    """x (..., S, D): rotate-half rotary embedding at `positions` (S,)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d // 2, dtype=jnp.float32) / (d // 2))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, w, cfg, r):
    """Causal grouped-query self-attention over x (B, S, E)."""
    b, s, _ = x.shape
    h, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    g = h // kv
    pos = jnp.arange(s)
    xr = r(x)
    q = (xr @ r(w["wq"])).reshape(b, s, kv, g, d)
    k = (xr @ r(w["wk"])).reshape(b, s, kv, d)
    v = (xr @ r(w["wv"])).reshape(b, s, kv, d)
    causal = pos[None, :] <= pos[:, None]                    # (S, S)
    outs = []
    for j in range(kv):                       # one key/value group a time
        qj = rope(q[:, :, j].transpose(0, 2, 1, 3), pos, cfg["rope_theta"])
        kj = rope(k[:, :, j], pos, cfg["rope_theta"])        # (B, S, D)
        sc = jnp.einsum("bgqd,bkd->bgqk", r(qj), r(kj)) / math.sqrt(d)
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("bgqk,bkd->bgqd", r(p), r(v[:, :, j])))
    o = jnp.stack(outs, axis=1)                              # (B,kv,g,S,D)
    o = o.transpose(0, 3, 1, 2, 4).reshape(b, s, h * d)
    return r(o) @ r(w["wo"])


def block(x, w, cfg, round_to=None):
    r = ROUNDINGS[round_to]
    eps = cfg["rms_norm_eps"]
    x = x + attention(rms_norm(x, w["attn_norm"], eps), w, cfg, r)
    y = r(rms_norm(x, w["ffn_norm"], eps))
    hidden = jax.nn.silu(y @ r(w["w_gate"])) * (y @ r(w["w_up"]))
    return x + r(hidden) @ r(w["w_down"])


def layer_weights(get_leaf: GetLeaf, i: int) -> Dict[str, jax.Array]:
    return {n: get_leaf(f"L{i}.{n}").astype(jnp.float32)
            for n in LAYER_LEAVES}


# -- serving: teacher-forced logits -----------------------------------------

def hidden_states(tokens, get_leaf: GetLeaf, cfg: Dict, round_to=None):
    """Final-norm hidden states (B, S, E) of `tokens` (B, S)."""
    blk = jax.jit(partial(block, cfg=_static(cfg), round_to=round_to))
    with jax.default_matmul_precision(HIGHEST):
        x = jnp.take(get_leaf("embed").astype(jnp.float32), tokens, axis=0)
        for i in range(cfg["num_hidden_layers"]):
            x = blk(x, layer_weights(get_leaf, i))
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


def served_gaps(tokens: np.ndarray, nxt: np.ndarray, get_leaf: GetLeaf,
                cfg: Dict, control: Optional[str] = None):
    """`tokens` (B, S): each row a prompt followed by the tokens served
    for it (padded on the right; the causal mask keeps padding out of
    every earlier position).  `nxt` (B, S): the token served after each
    position (any value where none was).

    Returns gap (B, S): by how much the reference's logit of the served
    next token lies below the reference's best, at every position.
    With `control`, also returns the same gap for the token that the
    lower-precision forward pass puts first at each position."""
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


# -- training: loss, gradients, three steps of Adam ---------------------------

def _static(cfg: Dict):
    """The sizes a traced function needs, hashable."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rope_theta", "rms_norm_eps", "num_hidden_layers")
    return _Frozen({k: cfg[k] for k in keys})


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def _head_loss(hid, head, labels, r, row_block=2048):
    """Sum of next-token cross-entropies, the head over blocks of rows."""
    n, e = hid.shape
    nb = max(n // row_block, 1)

    @jax.checkpoint
    def one(h, l):
        logits = r(h) @ r(head)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(logits, l[:, None], -1)[:, 0])

    parts = [one(hid[i * (n // nb):(i + 1) * (n // nb)],
                 labels[i * (n // nb):(i + 1) * (n // nb)])
             for i in range(nb)]
    return sum(parts)


def loss_fn(params, inputs, targets, cfg, round_to=None):
    """Mean cross-entropy of `targets` (B, S) given `inputs` (B, S), one
    row at a time (the mean over the batch is the mean over rows)."""
    r = ROUNDINGS[round_to]
    blk = jax.checkpoint(partial(block, cfg=cfg, round_to=round_to))
    b, s = inputs.shape
    total = 0.0
    for j in range(b):
        x = jnp.take(params["embed"], inputs[j:j + 1], axis=0)
        for i in range(cfg["num_hidden_layers"]):
            x = blk(x, {n: params[f"L{i}.{n}"] for n in LAYER_LEAVES})
        x = rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
        total = total + _head_loss(x[0], params["head"], targets[j], r)
    return total / (b * s)


@partial(jax.jit, static_argnums=(3, 4))
def _loss_and_grad(params, inputs, targets, cfg, round_to):
    with jax.default_matmul_precision(HIGHEST):
        return jax.value_and_grad(loss_fn)(params, inputs, targets, cfg,
                                           round_to)


@partial(jax.jit, static_argnums=(5,), donate_argnums=(0, 1, 2))
def _adam(params, m, v, g, step, opt):
    lr, b1, b2, eps = opt
    t = step.astype(jnp.float32) + 1.0

    def one(p, m_, v_, g_):
        m_ = b1 * m_ + (1 - b1) * g_
        v_ = b2 * v_ + (1 - b2) * jnp.square(g_)
        p = p - lr * (m_ / (1 - b1 ** t)) / (
            jnp.sqrt(v_ / (1 - b2 ** t)) + eps)
        return p, m_, v_

    out = {k: one(params[k], m[k], v[k], g[k]) for k in params}
    return ({k: o[0] for k, o in out.items()},
            {k: o[1] for k, o in out.items()},
            {k: o[2] for k, o in out.items()})


@jax.jit
def _norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(x))) for k, x in tree.items()}


@jax.jit
def _delta_norm(p, p0):
    return jnp.sqrt(jnp.sum(jnp.square(p - p0)))


def sample(x, count: int = 65536):
    """`count` entries of `x` at fixed, evenly strided flat positions (all
    of a smaller array): a sketch small enough to keep when the tensor
    itself has to go."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    if n <= count:
        return flat
    return flat[(jnp.arange(count) * (n // count)) + (n // count) // 2]


def train_steps(batches: Sequence[Dict[str, np.ndarray]], names: List[str],
                get_leaf: GetLeaf, cfg: Dict, opt: Dict,
                round_to: Optional[str] = None) -> Dict:
    """Follow `len(batches)` steps of Adam from the seed's weights.

    `batches[i]` = {"input": (B, S), "target": (B, S)}.  Returns each step's loss, and per leaf the norm of
    Adam's first moment and of the parameters' change after the last
    step, and a strided sample of the first moment's entries."""
    scfg = _static(cfg)
    hp = (float(opt["learning_rate"]), float(opt["beta1"]),
          float(opt["beta2"]), float(opt["epsilon"]))
    params = {n: get_leaf(n).astype(jnp.float32) for n in names}
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses = []
    for step, batch in enumerate(batches):
        loss, g = _loss_and_grad(params,
                                 jnp.asarray(batch["input"], jnp.int32),
                                 jnp.asarray(batch["target"], jnp.int32),
                                 scfg, round_to)
        losses.append(float(loss))
        params, m, v = _adam(params, m, v, g, jnp.int32(step), hp)
        del g
    out = {"loss": losses,
           "m_norm": {k: float(x) for k, x in _norms(m).items()},
           "m_sample": {k: np.asarray(sample(x)) for k, x in m.items()},
           "delta_norm": {n: float(_delta_norm(
               params[n], get_leaf(n).astype(jnp.float32)))
               for n in names}}
    del params, m, v
    return out
