"""The layer zoo: reference layer types as pure JAX compute.

Each reference Layer subclass (base_layer.h:38-563, layer.h:28-291) maps
to a registry entry here with three duties:

  setup(src_shapes)   — shape inference + param spec declaration
                        (reference Layer::Setup)
  apply(params, srcs, ctx) — forward compute (reference ComputeFeature);
                        the backward (ComputeGradient) comes from jax.grad.

The whole net therefore compiles to one XLA program per phase instead of
a hand-scheduled per-layer interpreter loop.

Layer `type` strings are the reference's registry keys
(neuralnet.cc:13-44): kConvolution, kPooling, kLRN, kInnerProduct,
kReLU, kTanh, kSigmoid, kDropout, kSoftmaxLoss, kMnistImage, kRGBImage,
kLabel, kShardData, kLMDBData, kConcate, kSlice, kSplit, kBridgeSrc,
kBridgeDst — plus TPU-native modern types (kEmbed, kAttention, kRMSNorm,
kMoE, kRBM) registered by their model families.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import ops
from ..config.schema import LayerConfig, ParamConfig


class LayerError(ValueError):
    pass


@dataclass
class ParamSpec:
    name: str           # global key: "<layer>/<param-name>"
    shape: Tuple[int, ...]
    fan_in: int
    cfg: ParamConfig
    # sharding hint: ParamProto.partition_dim (-1 = replicate)
    partition_dim: int = -1
    # mesh axis the partition_dim shards over; None = the TP axis
    # ("model"). MoE expert-stacked params use "expert".
    mesh_axis: Optional[str] = None


@dataclass
class Context:
    """Per-call state threaded through Layer.apply."""
    batch: Dict[str, Any]
    train: bool
    rng: Optional[jax.Array] = None
    layer_index: int = 0
    mesh: Any = None            # jax.sharding.Mesh for SP/EP-aware layers
    compute_dtype: Any = None   # e.g. jnp.bfloat16 under ModelProto.precision
    step: Any = None            # traced global step (cadence-aware layers,
    #                             e.g. MnistProto.elastic_freq)

    def layer_rng(self) -> jax.Array:
        if self.rng is None:
            raise LayerError("layer needs an rng but none was provided")
        return jax.random.fold_in(self.rng, self.layer_index)


LAYER_REGISTRY: Dict[str, type] = {}


def register_layer(type_name: str):
    def deco(cls):
        LAYER_REGISTRY[type_name] = cls
        cls.type_name = type_name
        return cls
    return deco


class Layer:
    """Base layer. Subclasses fill out_shape and param_specs in setup()."""

    is_data = False     # True → reads from ctx.batch, has no srcs
    is_loss = False     # True → apply returns a metrics dict incl. "loss"

    def __init__(self, cfg: LayerConfig):
        self.cfg = cfg
        self.name = cfg.name
        self.out_shape: Any = None
        self.param_specs: List[ParamSpec] = []

    def setup(self, src_shapes: List[Any]) -> None:
        raise NotImplementedError

    def apply(self, params: Dict[str, jnp.ndarray], srcs: List[Any],
              ctx: Context) -> Any:
        raise NotImplementedError

    # -- helpers ----------------------------------------------------------
    def _param_cfg(self, i: int, default_name: str) -> ParamConfig:
        if i < len(self.cfg.param):
            return self.cfg.param[i]
        return ParamConfig(name=default_name)

    def _declare(self, i: int, default_name: str, shape, fan_in: int,
                 partition_dim: int = -1) -> str:
        pcfg = self._param_cfg(i, default_name)
        pname = pcfg.name or default_name
        key = f"{self.name}/{pname}"
        if pcfg.partition_dim != -1:
            partition_dim = pcfg.partition_dim
        self.param_specs.append(
            ParamSpec(key, tuple(shape), fan_in, pcfg, partition_dim))
        return key


# ---------------------------------------------------------------------------
# data / parser layers


@register_layer("kShardData")
class ShardDataLayer(Layer):
    """Input layer (layer.cc:646-673): emits the raw record batch provided
    by the host input pipeline via ctx.batch[self.name]."""

    is_data = True

    def setup(self, src_shapes, sample_shapes: Optional[Dict] = None):
        bs = self.cfg.data_param.batchsize if self.cfg.data_param else 0
        self.batchsize = bs
        self.sample_shapes = sample_shapes or {}
        self.out_shape = {k: (bs,) + tuple(v)
                          for k, v in self.sample_shapes.items()}

    def apply(self, params, srcs, ctx):
        try:
            return ctx.batch[self.name]
        except KeyError:
            raise LayerError(
                f"batch missing entry for data layer {self.name!r}; "
                f"have {list(ctx.batch)}")


@register_layer("kLMDBData")
class LMDBDataLayer(ShardDataLayer):
    """LMDB-backed data layer (layer.cc:237-328). Device-side it is
    identical to ShardData: the host pipeline supplies the batch."""


@register_layer("kMnistImage")
class MnistImageLayer(Layer):
    """Parser (layer.cc:380-473): uint8 pixels → (x/norm_a - norm_b),
    output (B, s, s).  The reference does this per-pixel on the host; here
    it runs inside the jitted step (zero CPU in the inner loop).

    The elastic-distortion surface the reference declares but left
    commented out (MnistProto kernel/sigma/alpha/beta/gamma,
    model.proto:211-225) is implemented on-device (ops/augment.py) and
    applied in the training phase when any strength is nonzero.
    `elastic_freq` gates it to every freq-th step (the field the
    reference reads in Setup, layer.cc:462, for exactly that cadence);
    `resize` rescales samples to (resize, resize) (layer.cc:466-467
    reshapes the output blob to that size)."""

    def setup(self, src_shapes):
        p = self.cfg.mnist_param
        self.norm_a = p.norm_a if p else 1.0
        self.norm_b = p.norm_b if p else 0.0
        self.distort = dict(
            kernel=p.kernel, sigma=p.sigma, alpha=p.alpha,
            beta=p.beta, gamma=p.gamma) if p else {}
        self.distort_on = bool(p and (
            (p.alpha > 0 and p.kernel > 0) or p.beta > 0 or p.gamma > 0))
        self.elastic_freq = p.elastic_freq if p else 0
        self.resize = p.resize if p else 0
        pix = tuple(src_shapes[0]["pixel"])
        if self.resize:
            pix = pix[:1] + (self.resize, self.resize) + pix[3:]
        self.out_shape = pix

    def apply(self, params, srcs, ctx):
        x = srcs[0]["pixel"].astype(jnp.float32)
        if self.resize and x.shape[1:3] != (self.resize, self.resize):
            x = jax.image.resize(
                x, (x.shape[0], self.resize, self.resize) + x.shape[3:],
                method="bilinear")
        if self.distort_on and ctx.train:
            from ..ops.augment import elastic_deform
            rng = ctx.layer_rng()
            if self.elastic_freq > 1 and ctx.step is not None:
                # distort only every elastic_freq-th step (layer.cc:462);
                # lax.cond skips the displacement-field work entirely on
                # off steps (jnp.where would compute-and-discard it)
                on = (jnp.asarray(ctx.step) % self.elastic_freq) == 0
                x = jax.lax.cond(
                    on,
                    lambda t: elastic_deform(t, rng, **self.distort),
                    lambda t: t, x)
            else:
                x = elastic_deform(x, rng, **self.distort)
        x = x / self.norm_a - self.norm_b
        if ctx.compute_dtype is not None:
            x = x.astype(ctx.compute_dtype)
        return x


@register_layer("kRGBImage")
class RGBImageLayer(Layer):
    """Parser (layer.cc:571-643): mean-subtract, random crop + mirror in
    training / center crop in eval, scale.

    Host batches arrive channels-first ((B, 3, H, W), the Record pixel
    layout); the parser transposes once to NHWC — the layout the whole
    vision stack runs in on TPU (channels on the 128-lane axis; see
    ops/conv.py).  Output (B, crop, crop, 3)."""

    def setup(self, src_shapes):
        p = self.cfg.rgbimage_param
        self.scale = p.scale if p else 1.0
        self.cropsize = p.cropsize if p else 0
        self.mirror = bool(p.mirror) if p else False
        self.mean = (self._load_mean(p.meanfile)
                     if p and p.meanfile else None)
        b, c, h, w = src_shapes[0]["pixel"]  # (B, C, H, W) host layout
        if self.cropsize:
            h = w = self.cropsize
        self.out_shape = (b, h, w, c)

    @staticmethod
    def _load_mean(path: str):
        """Per-pixel mean record (the mean.binaryproto role,
        layer.cc:579-583: ReadProtoFromBinaryFile + mean subtract).
        Written by tools/loader.py compute_mean; fails loudly when the
        configured file is missing or malformed."""
        import numpy as _np

        from ..data.records import Record
        try:
            with open(path, "rb") as f:
                rec = Record.decode(f.read())
            arr = _np.asarray(rec.image.data, _np.float32).reshape(
                tuple(rec.image.shape))
        except FileNotFoundError:
            raise LayerError(
                f"rgbimage_param.meanfile {path!r} does not exist — "
                f"build it with singa_tpu.tools.loader compute_mean")
        except Exception as e:
            raise LayerError(
                f"rgbimage_param.meanfile {path!r} is not a mean "
                f"record: {e}")
        return arr

    def apply(self, params, srcs, ctx):
        x = srcs[0]["pixel"].astype(jnp.float32)
        # batch-supplied mean (pipeline) wins over the configured file
        mean = srcs[0].get("mean")
        if mean is None and self.mean is not None:
            mean = jnp.asarray(self.mean)
        if mean is not None:
            x = x - mean
        x = x.transpose(0, 2, 3, 1)  # → NHWC
        b, h, w, c = x.shape
        cs = self.cropsize
        # Per-IMAGE augmentation randomness, as the reference draws it
        # inside its per-record parse loop (layer.cc:587-616:
        # hoff=rand()%(shape-cropsize) and do_mirror=mirror_&&rand()%2
        # for every record).  Batch-correlated crops/flips are
        # measurably weaker regularization.  Two deliberate deviations
        # from the reference's literal code: (a) it re-rolls the mirror
        # coin outside the `training` guard (layer.cc:613), mirroring
        # at test time — here mirror is train-only; (b) at test time
        # with a cropsize it memcpys the full record into the smaller
        # cropped blob (layer.cc:596-602) — here eval takes the
        # conventional center crop.
        rng = (ctx.layer_rng()
               if ctx.train and (self.mirror or
                                 (cs and (h > cs or w > cs)))
               else None)
        if cs and (h > cs or w > cs):
            if ctx.train:
                r1, r2, rng = jax.random.split(rng, 3)
                oh = jax.random.randint(r1, (b,), 0, max(h - cs, 1))
                ow = jax.random.randint(r2, (b,), 0, max(w - cs, 1))
                x = jax.vmap(
                    lambda img, i, j: jax.lax.dynamic_slice(
                        img, (i, j, 0), (cs, cs, c)))(x, oh, ow)
            else:
                oh, ow = (h - cs) // 2, (w - cs) // 2
                x = x[:, oh:oh + cs, ow:ow + cs]
        if self.mirror and ctx.train:
            flip = jax.random.bernoulli(rng, shape=(b,))
            x = jnp.where(flip[:, None, None, None], x[:, :, ::-1], x)
        x = x * self.scale
        if ctx.compute_dtype is not None:
            x = x.astype(ctx.compute_dtype)
        return x


@register_layer("kLabel")
class LabelLayer(Layer):
    """Parser (layer.cc:416-432): int labels, shape (B,)."""

    def setup(self, src_shapes):
        self.out_shape = tuple(src_shapes[0]["label"])

    def apply(self, params, srcs, ctx):
        return srcs[0]["label"]


# ---------------------------------------------------------------------------
# neuron layers


def _nhwc_shape(shape):
    """Vision activations run NHWC on TPU.  Reference conv/pool accept
    3-D (B,H,W) inputs as single-channel (layer.cc:31-36) → (B,H,W,1)."""
    if len(shape) == 3:
        return (shape[0], shape[1], shape[2], 1)
    return tuple(shape)


def _as_nhwc(x):
    if x.ndim == 3:
        return x.reshape(x.shape[0], x.shape[1], x.shape[2], 1)
    return x


@register_layer("kConvolution")
class ConvolutionLayer(Layer):
    """layer.cc:26-123. Weight kept in the reference layout
    (num_filters, C*k*k); compute is one lax.conv_general_dilated."""

    def setup(self, src_shapes):
        p = self.cfg.convolution_param
        if p is None or not p.kernel:
            raise LayerError(f"{self.name}: convolution_param.kernel required")
        b, h, w, c = _nhwc_shape(src_shapes[0])
        self.channels, self.height, self.width = c, h, w
        self.kernel, self.stride, self.pad = p.kernel, p.stride, p.pad
        self.num_filters = p.num_filters
        self.bias_term = p.bias_term
        ch = ops.conv_out_size(h, p.kernel, p.stride, p.pad)
        cw = ops.conv_out_size(w, p.kernel, p.stride, p.pad)
        self.out_shape = (b, ch, cw, p.num_filters)
        col_height = c * p.kernel * p.kernel
        self.w_key = self._declare(0, "weight", (p.num_filters, col_height),
                                   fan_in=col_height, partition_dim=0)
        if self.bias_term:
            self.b_key = self._declare(1, "bias", (p.num_filters,), fan_in=0,
                                       partition_dim=0)

    def apply(self, params, srcs, ctx):
        x = _as_nhwc(srcs[0])
        bias = params[self.b_key] if self.bias_term else None
        return ops.conv2d(x, params[self.w_key], bias, kernel=self.kernel,
                          stride=self.stride, pad=self.pad,
                          channels=self.channels, layout="NHWC")


@register_layer("kPooling")
class PoolingLayer(Layer):
    def setup(self, src_shapes):
        p = self.cfg.pooling_param
        if p is None or not p.kernel:
            raise LayerError(f"{self.name}: pooling_param.kernel required")
        if p.pool not in ("MAX", "AVE"):
            raise LayerError(f"{self.name}: bad pool method {p.pool!r}")
        b, h, w, c = _nhwc_shape(src_shapes[0])
        self.kernel, self.stride, self.mode = p.kernel, p.stride, p.pool
        self.out_shape = (b, ops.pooled_size(h, p.kernel, p.stride),
                          ops.pooled_size(w, p.kernel, p.stride), c)

    def apply(self, params, srcs, ctx):
        x = _as_nhwc(srcs[0])
        if self.mode == "MAX":
            return ops.max_pool2d(x, self.kernel, self.stride, layout="NHWC")
        return ops.avg_pool2d(x, self.kernel, self.stride, layout="NHWC")


@register_layer("kLRN")
class LRNLayer(Layer):
    """`fuse_from`: set by NeuralNet when this LRN's source is a plain
    ReLU — apply() then receives the *pre-relu* tensor and runs the
    fused relu+lrn custom_vjp (ops/lrn.py), never materializing the
    relu output on the train path (any other consumers of the relu
    still get it from the ReLU layer; XLA dead-code-eliminates it when
    unused)."""

    fuse_from: str = ""

    def setup(self, src_shapes):
        p = self.cfg.lrn_param
        self.local_size = p.local_size if p else 5
        if self.local_size % 2 != 1:
            raise LayerError(f"{self.name}: LRN local_size must be odd")
        self.alpha = p.alpha if p else 1.0
        self.beta = p.beta if p else 0.75
        self.knorm = p.knorm if p else 1.0
        self.out_shape = tuple(src_shapes[0])

    def apply(self, params, srcs, ctx):
        return ops.relu_lrn(srcs[0], self.local_size, self.alpha, self.beta,
                            self.knorm, relu=bool(self.fuse_from),
                            layout="NHWC")


@register_layer("kInnerProduct")
class InnerProductLayer(Layer):
    """layer.cc:162-213: flatten to (B, vdim), weight (vdim, hdim).
    NOTE the reference passes fan_in = vdim*hdim to Param::Setup
    (layer.cc:174) — reproduced for init parity.  vdim element order
    follows the NHWC runtime layout (H, W, C) rather than the
    reference's (C, H, W); weight shape and numerics are unaffected."""

    def setup(self, src_shapes):
        p = self.cfg.inner_product_param
        if p is None or not p.num_output:
            raise LayerError(f"{self.name}: inner_product_param.num_output "
                             "required")
        s = tuple(src_shapes[0])
        b = s[0]
        vdim = int(math.prod(s[1:]))
        hdim = p.num_output
        self.bias_term = p.bias_term
        self.out_shape = (b, hdim)
        self.w_key = self._declare(0, "weight", (vdim, hdim),
                                   fan_in=vdim * hdim, partition_dim=1)
        if self.bias_term:
            self.b_key = self._declare(1, "bias", (hdim,), fan_in=0,
                                       partition_dim=0)

    def apply(self, params, srcs, ctx):
        bias = params[self.b_key] if self.bias_term else None
        return ops.linear(srcs[0], params[self.w_key], bias)


@register_layer("kReLU")
class ReLULayer(Layer):
    def setup(self, src_shapes):
        self.slope = (self.cfg.relu_param.negative_slope
                      if self.cfg.relu_param else 0.0)
        self.out_shape = tuple(src_shapes[0])

    def apply(self, params, srcs, ctx):
        return ops.relu(srcs[0], self.slope)


@register_layer("kTanh")
class TanhLayer(Layer):
    """Reference kTanh is the *scaled* tanh stanh (layer.cc:688-701) with
    hard-coded constants; TanhProto outer/inner_scale override them."""

    def setup(self, src_shapes):
        p = self.cfg.tanh_param
        if p is not None:
            self.outer, self.inner = p.outer_scale, p.inner_scale
        else:
            self.outer, self.inner = ops.activations.STANH_OUTER, \
                ops.activations.STANH_INNER
        self.out_shape = tuple(src_shapes[0])

    def apply(self, params, srcs, ctx):
        return ops.stanh(srcs[0], self.outer, self.inner)


@register_layer("kSigmoid")
class SigmoidLayer(Layer):
    def setup(self, src_shapes):
        self.out_shape = tuple(src_shapes[0])

    def apply(self, params, srcs, ctx):
        return ops.sigmoid(srcs[0])


@register_layer("kDropout")
class DropoutLayer(Layer):
    def setup(self, src_shapes):
        self.rate = (self.cfg.dropout_param.dropout_ratio
                     if self.cfg.dropout_param else 0.5)
        self.out_shape = tuple(src_shapes[0])

    def apply(self, params, srcs, ctx):
        if not ctx.train:
            return srcs[0]
        return ops.dropout(srcs[0], self.rate, ctx.layer_rng(), train=True)


# ---------------------------------------------------------------------------
# loss layers


@register_layer("kSoftmaxLoss")
class SoftmaxLossLayer(Layer):
    """layer.cc:702-765: fused softmax + NLL + top-k precision.
    srcs = [logits, label]."""

    is_loss = True

    def setup(self, src_shapes):
        p = self.cfg.softmaxloss_param
        self.topk = p.topk if p else 1
        self.scale = p.scale if p else 1.0
        self.out_shape = (2,)   # metric blob layout [loss, precision]

    def apply(self, params, srcs, ctx):
        logits, labels = srcs
        if labels.ndim > 1:
            # sequence labels (B, S): flatten to (B*S, V) token-level NLL
            logits = logits.reshape(-1, logits.shape[-1])
            labels = labels.reshape(-1)
        loss, prec = ops.softmax_loss_metrics(
            logits.astype(jnp.float32), labels, self.topk, self.scale)
        return {"loss": loss, "precision": prec}


# ---------------------------------------------------------------------------
# connector layers (partition infrastructure, base_layer.h:264-330 +
# base_layer.cc:39-194). Under GSPMD these are mostly identities or plain
# jnp ops — data movement is compiled in from sharding annotations.


@register_layer("kConcate")
class ConcateLayer(Layer):
    def setup(self, src_shapes):
        dim = (self.cfg.concate_param.concate_dimension
               if self.cfg.concate_param else 0)
        self.dim = dim
        shape = list(src_shapes[0])
        shape[dim] = sum(s[dim] for s in src_shapes)
        self.out_shape = tuple(shape)

    def apply(self, params, srcs, ctx):
        return jnp.concatenate(srcs, axis=self.dim)


@register_layer("kSlice")
class SliceLayer(Layer):
    """Scatter along slice_dimension into slice_num views; consumer i
    reads view i (base_layer.cc:114-173). Output is the tuple of views."""

    def setup(self, src_shapes):
        p = self.cfg.slice_param
        self.dim = p.slice_dimension if p else 0
        self.num = p.slice_num if p else 1
        s = list(src_shapes[0])
        base, rem = divmod(s[self.dim], self.num)
        shapes = []
        for i in range(self.num):
            # reference gives the remainder to the last partition
            # (neuralnet.cc:160-162 semantics)
            sz = base + (rem if i == self.num - 1 else 0)
            t = list(s)
            t[self.dim] = sz
            shapes.append(tuple(t))
        self.out_shape = tuple(shapes)

    def apply(self, params, srcs, ctx):
        x = srcs[0]
        base = x.shape[self.dim] // self.num
        outs = []
        start = 0
        for i in range(self.num):
            sz = (x.shape[self.dim] - start if i == self.num - 1 else base)
            idx = [slice(None)] * x.ndim
            idx[self.dim] = slice(start, start + sz)
            outs.append(x[tuple(idx)])
            start += sz
        return tuple(outs)


@register_layer("kSplit")
class SplitLayer(Layer):
    """Replicate to multiple consumers (base_layer.h:316-330) — a pure
    identity under functional semantics."""

    def setup(self, src_shapes):
        self.out_shape = tuple(src_shapes[0])

    def apply(self, params, srcs, ctx):
        return srcs[0]


@register_layer("kBridgeSrc")
class BridgeSrcLayer(Layer):
    """Cross-location activation sender (base_layer.h:264-312). Under
    GSPMD the transfer is a compiled collective; the layer is an identity
    marker kept for config parity."""

    def setup(self, src_shapes):
        self.out_shape = tuple(src_shapes[0])

    def apply(self, params, srcs, ctx):
        return srcs[0]


@register_layer("kBridgeDst")
class BridgeDstLayer(BridgeSrcLayer):
    pass


def create_layer(cfg: LayerConfig) -> Layer:
    if cfg.type not in LAYER_REGISTRY:
        # the sequence family registers on import and is kept lazy
        # (it pulls in Pallas); load it on first unknown type.  kRBM
        # registers the same way from its model family.
        from . import hybrid_layers, seq_layers  # noqa: F401
        from ..models.rbm import register_rbm_layer
        register_rbm_layer()
    if cfg.type not in LAYER_REGISTRY:
        raise LayerError(f"unknown layer type {cfg.type!r} "
                         f"(registered: {sorted(LAYER_REGISTRY)})")
    return LAYER_REGISTRY[cfg.type](cfg)
