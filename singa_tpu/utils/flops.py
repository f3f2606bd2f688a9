"""FLOPs accounting and MFU (model FLOPs utilization).

The reference reports only wall-clock phase percentages (TimerInfo,
worker.h:91-114).  On TPU the north-star metric is MFU — achieved
model FLOPs/s over the chip's peak (BASELINE.md: AlexNet/CIFAR-10 at
>=50% MFU) — so this module adds two FLOPs sources:

  * `compiled_flops(jitted, *args)` — XLA's own cost analysis of the
    compiled program (exact for what actually runs, includes fusion).
  * `net_forward_flops(net)` — analytic MXU-op count (2·MACs) walked
    over the net's conv/linear layers; the test oracle for the above
    and a device-independent estimate.

MFU convention: model FLOPs (matmul/conv only, 2·MACs; backward
counted as 2x forward, so train step = 3x forward) divided by
(step_time · peak_flops).  Peak table is bf16 MXU peak per chip.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

# bf16 MXU peak FLOP/s per jax Device, keyed by the `device_kind`
# string JAX reports.  The strings are the ones the installed JAX
# itself matches on (jax/_src/pallas/mosaic/tpu_info.py); "TPU v5 lite"
# is what the v5e this repo runs on reports.  Peaks are Google Cloud's
# published per-chip numbers (v4+ expose one device per chip).
PEAK_FLOPS: Dict[str, float] = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12, "TPU v5e": 197e12,
    "TPU v5": 459e12, "TPU v5p": 459e12,
    "TPU v6 lite": 918e12, "TPU v6e": 918e12,
}


def peak_flops(device=None) -> Optional[float]:
    """Per-chip bf16 peak for `device` (default: jax.devices()[0]).
    None on the CPU platform, where no utilization is defined.  An
    accelerator whose kind is not in the table raises: a utilization
    computed against a guessed peak, or silently dropped as None, is
    worse than no number."""
    if device is None:
        import jax
        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "")
    if kind in PEAK_FLOPS:
        return PEAK_FLOPS[kind]
    if getattr(device, "platform", "") == "cpu":
        return None
    raise ValueError(
        f"no peak FLOP/s on record for device kind {kind!r}; add it to "
        f"singa_tpu.utils.flops.PEAK_FLOPS with its source")


def device_info() -> Dict[str, Any]:
    """What a run executes on, as JAX reports it, and the installation:
    the record every entry point logs or prints beside its results."""
    import jax
    import jaxlib
    from importlib.metadata import PackageNotFoundError, version
    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:
        libtpu = "none"
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu}


def cost_metrics(compiled) -> Dict[str, float]:
    """Harvest XLA's cost analysis from an ALREADY-COMPILED executable
    (`jit(...).lower(...).compile()` result).  Never lowers or
    compiles anything — reading the cost model off a cached executable
    is free, which is what lets CostWatch run against every warm
    program without perturbing the compile counters it also watches.

    Returns {} when the backend reports nothing; otherwise a dict with
    whatever of `flops` / `bytes accessed` / `utilization` keys the
    cost model provides."""
    try:
        ca = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 — diagnostics, never a failure
        return {}
    if not isinstance(ca, dict):
        return {}
    return {k: float(v) for k, v in ca.items()
            if isinstance(v, (int, float))}


def compiled_flops(jitted, *args, **kwargs) -> Optional[float]:
    """FLOPs of the compiled XLA program for `jitted(*args)`.

    `jitted` is either a jax.jit-wrapped callable (lowered and
    compiled here, at compile cost) or an already-compiled executable
    from `jit(...).lower(...).compile()` — the latter is preferred
    when one is at hand: harvesting from the cached object never
    triggers a duplicate compile.  Returns None when the backend's
    cost model does not report flops.
    """
    if hasattr(jitted, "cost_analysis"):   # Compiled (or Lowered):
        compiled = jitted                  # reuse, don't recompile
    else:
        compiled = jitted.lower(*args, **kwargs).compile()
    flops = cost_metrics(compiled).get("flops")
    return float(flops) if flops and flops > 0 else None


def mfu(model_flops: float, step_seconds: float,
        device=None) -> Optional[float]:
    """model_flops per step / (step_seconds · peak). None when peak
    unknown."""
    peak = peak_flops(device)
    if not peak or step_seconds <= 0:
        return None
    return model_flops / (step_seconds * peak)


# -- analytic per-layer counts (forward, 2·MACs convention) ----------------

def _conv_flops(layer) -> int:
    n, h, w, c_out = layer.out_shape  # NHWC
    return 2 * n * c_out * h * w * layer.kernel ** 2 * layer.channels


def _linear_flops(layer) -> int:
    n, out = layer.out_shape
    vdim, hdim = layer.param_specs[0].shape  # weight (vdim, hdim)
    return 2 * n * vdim * hdim


def _attention_flops(layer) -> int:
    b, s, e = layer.out_shape
    hd = layer.heads * layer.head_dim
    kvd = layer.kv_heads * layer.head_dim
    proj = 2 * b * s * e * (hd + 2 * kvd + hd)        # wq wk wv wo
    scores = 4 * b * layer.heads * s * s * layer.head_dim   # qk + pv
    if layer.causal:
        # standard causal-half accounting convention (only ~half the
        # score matrix is live).  NOTE this is a convention, not a
        # kernel fact: the dense fallback computes the full S^2 and
        # flash diagonal blocks are full tiles, so MFU comparability
        # across paths is approximate.
        scores //= 2
    return proj + scores


def _ffn_flops(layer) -> int:
    b, s, e = layer.out_shape
    f = layer.param_specs[0].shape[1]                 # w1 (E, F)
    mats = 3 if getattr(layer, "gated", False) else 2
    return 2 * b * s * e * f * mats


def _moe_flops(layer) -> int:
    b, s, e = layer.out_shape
    f = layer.param_specs[1].shape[2]                 # w1 (n_exp, E, F)
    router = 2 * b * s * e * layer.n_exp
    # each token runs k experts' (E→F→E) MLP (capacity overflow drops
    # are data-dependent; count the routed budget)
    return router + 2 * b * s * layer.k * 2 * e * f


def _lm_head_flops(layer) -> int:
    if layer.cfg.type == "kLMHeadLoss":
        b, s, e, v = layer.flops_shape
    else:
        b, s, v = layer.out_shape
        e = layer.param_specs[0].shape[0]       # w (E, V), tied or not
    return 2 * b * s * e * v


def layer_forward_flops(layer) -> int:
    """Matmul/conv FLOPs of one layer's forward; 0 for non-MXU layers
    (elementwise/pool/LRN/norm are bandwidth-, not FLOP-, dominated)."""
    t = layer.cfg.type
    if t == "kConvolution":
        return _conv_flops(layer)
    if t == "kInnerProduct":
        return _linear_flops(layer)
    if t == "kAttention":
        return _attention_flops(layer)
    if t == "kFeedForward":
        return _ffn_flops(layer)
    if t == "kMoE":
        return _moe_flops(layer)
    if t in ("kLMHead", "kLMHeadLoss"):
        return _lm_head_flops(layer)
    return 0


def net_forward_flops(net) -> int:
    """Analytic forward model-FLOPs of a built NeuralNet."""
    return sum(layer_forward_flops(net.layers[name]) for name in net.topo)


def net_train_flops(net) -> int:
    """Train-step model FLOPs: backward re-does each matmul twice
    (d-input + d-weight), so 3x forward — the standard convention.

    NOTE the convention counts 3x for the FIRST trainable layer too,
    whose input gradient XLA never computes (its input is data).  On
    the AlexNet bench stack that is conv1's dgrad, ~2% of total train
    FLOPs — i.e. the convention-free MFU is ~0.51 when the reported
    one is ~0.52.  Kept because every published MFU number (PaLM-style
    6ND etc.) uses the same uniform-3x convention and comparability
    matters more than the 2%."""
    return 3 * net_forward_flops(net)
