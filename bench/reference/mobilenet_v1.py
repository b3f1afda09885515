"""Plain NumPy reference of MobileNet v1 (arXiv:1704.04861), float and int8.

It imports nothing of the program under test. It states the network from a
configuration file's sizes, makes its weights from a seed and its
quantisation parameters (once per configuration), and runs one image at a
time:

- float: every op in float64, from float32 weights and inputs;
- int8: the TFLite-micro affine tier: asymmetric int8 activations,
  symmetric per-tensor int8 weights, int32 accumulation of
  ``(x - zero_point) * w``, requantisation by a float32 multiplier
  ``s_x * s_w / s_y`` with round-half-to-even, then saturation.

The network is the program's graph at the published widths: a 3x3/2 stem,
13 depthwise-separable blocks, global mean, a fully connected layer and a
softmax, with TF ``SAME`` padding. Like the program's graph it has no batch
norm and no ReLU6 between layers (both fold into weights or are left out
alike; PERF.md notes the departure from the paper).

``control`` switches to the precision one step below the configuration's:
bfloat16 operands for float32, int4 weights for int8.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

#: (stride, output channels at alpha 1) of the 13 separable blocks.
BLOCKS = ((1, 64), (2, 128), (1, 128), (2, 256), (1, 256), (2, 512),
          (1, 512), (1, 512), (1, 512), (1, 512), (1, 512), (2, 1024),
          (1, 1024))

#: Pixels of int8 inputs are real values q / 128 (zero point 0).
INPUT_SCALE = 1.0 / 128.0


@dataclasses.dataclass(frozen=True)
class Layer:
    """One op: ``kind`` is conv2d / depthwise_conv2d / mean /
    fully_connected / softmax; shapes are per image (H, W, C) or (C,)."""
    name: str
    kind: str
    in_shape: Tuple[int, ...]
    out_shape: Tuple[int, ...]
    kernel: int = 1
    stride: int = 1

    @property
    def weight_shape(self) -> Optional[Tuple[int, ...]]:
        ic, oc = self.in_shape[-1], self.out_shape[-1]
        if self.kind == "conv2d":
            return (self.kernel, self.kernel, ic, oc)
        if self.kind == "depthwise_conv2d":
            return (self.kernel, self.kernel, ic, 1)
        if self.kind == "fully_connected":
            return (ic, oc)
        return None


def layers(cfg: dict) -> List[Layer]:
    """The op list of MobileNet v1 at the configuration's width multiplier
    ``alpha``, input ``resolution`` and ``classes``."""
    alpha, res = float(cfg["alpha"]), int(cfg["resolution"])
    ch = lambda c: max(8, int(c * alpha))
    out: List[Layer] = []
    shape = (res, res, 3)

    def conv(name, kind, k, s, oc):
        nonlocal shape
        h, w, c = shape
        o = (-(-h // s), -(-w // s), c if kind == "depthwise_conv2d" else oc)
        out.append(Layer(name, kind, shape, o, k, s))
        shape = o

    conv("conv1", "conv2d", 3, 2, ch(32))
    for i, (s, c) in enumerate(BLOCKS):
        conv(f"dw{i + 1}", "depthwise_conv2d", 3, s, None)
        conv(f"pw{i + 1}", "conv2d", 1, 1, ch(c))
    c = shape[-1]
    n = int(cfg["classes"])
    out.append(Layer("gap", "mean", shape, (c,)))
    out.append(Layer("logits", "fully_connected", (c,), (n,)))
    out.append(Layer("prob", "softmax", (n,), (n,)))
    return out


def make_weights(cfg: dict, seed: int) -> Dict[str, np.ndarray]:
    """float32 weights per weighted layer, He-scaled by fan-in so that
    activations stay of order one through the network."""
    rng = np.random.default_rng([seed, 1])
    return {ly.name: (rng.standard_normal(ly.weight_shape)
                      / np.sqrt(_fan_in(ly))).astype(np.float32)
            for ly in layers(cfg) if ly.weight_shape is not None}


# ---------------------------------------------------------------------------
# Float forward
# ---------------------------------------------------------------------------


def _same_pad(n: int, k: int, s: int) -> Tuple[int, int]:
    """TF SAME padding (before, after) of one spatial axis."""
    total = max(0, (-(-n // s) - 1) * s + k - n)
    return total // 2, total - total // 2


def _padded(x: np.ndarray, ly: Layer, value=0) -> np.ndarray:
    ph = _same_pad(x.shape[0], ly.kernel, ly.stride)
    pw = _same_pad(x.shape[1], ly.kernel, ly.stride)
    return np.pad(x, (ph, pw, (0, 0)), constant_values=value)


def _taps(xp: np.ndarray, ly: Layer):
    """(fy, fx, view) for each filter tap: the input pixels that tap reads
    for every output pixel, shape (oh, ow, C)."""
    oh, ow = ly.out_shape[:2]
    s = ly.stride
    for fy in range(ly.kernel):
        for fx in range(ly.kernel):
            yield fy, fx, xp[fy:fy + s * (oh - 1) + 1:s,
                             fx:fx + s * (ow - 1) + 1:s]


def _conv_acc(x: np.ndarray, w: np.ndarray, ly: Layer,
              pad_value=0.0) -> np.ndarray:
    """Sum of products of a conv/depthwise op in float64 (exact for integer
    operands): x (H, W, C), w the layer's filter."""
    xp = _padded(x.astype(np.float64), ly, pad_value)
    w = w.astype(np.float64)
    oh, ow, oc = ly.out_shape
    if ly.kind == "depthwise_conv2d":
        acc = np.zeros((oh, ow, oc))
        for fy, fx, v in _taps(xp, ly):
            acc += v * w[fy, fx, :, 0]
        return acc
    cols = np.concatenate([v for _, _, v in _taps(xp, ly)], axis=-1)
    return (cols.reshape(oh * ow, -1)
            @ w.reshape(-1, oc)).reshape(oh, ow, oc)


def forward_float(cfg: dict, weights: Dict[str, np.ndarray], image,
                  operand=None) -> Dict[str, np.ndarray]:
    """Every layer's output (keyed by layer name, plus ``"input"``) for one
    image, in float64. ``operand`` rounds the operands of each product
    (activations and weights) before use: the bfloat16 control."""
    rnd = operand or (lambda a: a)
    x = np.asarray(image, np.float64)
    vals = {"input": x}
    for ly in layers(cfg):
        if ly.kind in ("conv2d", "depthwise_conv2d"):
            x = _conv_acc(rnd(x), rnd(weights[ly.name]), ly)
        elif ly.kind == "mean":
            x = x.mean(axis=(0, 1))
        elif ly.kind == "fully_connected":
            x = rnd(x) @ rnd(weights[ly.name]).astype(np.float64)
        else:
            e = np.exp(x - x.max())
            x = e / e.sum()
        vals[ly.name] = x
    return vals


def bf16(a: np.ndarray) -> np.ndarray:
    """Round to bfloat16 and back: the operand precision of the float
    control."""
    import ml_dtypes
    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16
                                            ).astype(np.float64)


# ---------------------------------------------------------------------------
# int8 tier
# ---------------------------------------------------------------------------

f32 = np.float32


@dataclasses.dataclass
class Quant:
    """Activation (scale, zero point) per layer output (and ``"input"``),
    weight scale and int8 weights per weighted layer."""
    act: Dict[str, Tuple[float, int]]
    wscale: Dict[str, float]
    wq: Dict[str, np.ndarray]


#: The seed of the calibration set: weights and images that fix every
#: activation range of a configuration once. The program bakes scales and
#: zero points into its kernels, so ranges that moved with the run's seed
#: would make each seed a different program to compile.
CALIB_SEED = 0
CALIB_IMAGES = 8
#: Weights are clipped at this many standard deviations of their He
#: initialisation: the symmetric weight scale is a function of the layer's
#: shape alone, for the same reason.
WEIGHT_CLIP_SIGMAS = 4.0


def _fan_in(ly: Layer) -> int:
    ws = ly.weight_shape
    return ws[0] * ws[1] if ly.kind == "depthwise_conv2d" \
        else int(np.prod(ws[:-1]))


def activation_ranges(cfg: dict) -> Dict[str, Tuple[float, int]]:
    """Post-training calibration, once per configuration: each layer's
    observed float range over the calibration set, widened to hold 0,
    mapped onto [-128, 127]. Input pixels are int8 at scale 1/128."""
    weights = make_weights(cfg, CALIB_SEED)
    rng = np.random.default_rng([CALIB_SEED, 3])
    res = int(cfg["resolution"])
    lo: Dict[str, float] = {}
    hi: Dict[str, float] = {}
    for _ in range(CALIB_IMAGES):
        img = rng.integers(-128, 128, (res, res, 3)) * INPUT_SCALE
        for k, v in forward_float(cfg, weights, img).items():
            lo[k] = min(lo.get(k, 0.0), float(v.min()))
            hi[k] = max(hi.get(k, 0.0), float(v.max()))
    act = {"input": (INPUT_SCALE, 0)}
    for ly in layers(cfg):
        scale = (hi[ly.name] - lo[ly.name]) / 255.0 or 1.0
        zp = int(np.clip(round(-128.0 - lo[ly.name] / scale), -128, 127))
        act[ly.name] = (scale, zp)
    return act


def quantise_weights(cfg: dict, weights: Dict[str, np.ndarray],
                     act: Dict[str, Tuple[float, int]],
                     weight_bits: int = 8) -> Quant:
    """Symmetric per-tensor weights at a scale fixed by the layer's shape
    (``WEIGHT_CLIP_SIGMAS`` He deviations over the largest level).
    ``weight_bits=4`` gives the int4 weights of the control."""
    qmax = 2 ** (weight_bits - 1) - 1
    wscale, wq = {}, {}
    for ly in layers(cfg):
        if ly.name not in weights:
            continue
        s = WEIGHT_CLIP_SIGMAS / np.sqrt(_fan_in(ly)) / qmax
        wscale[ly.name] = float(s)
        wq[ly.name] = np.clip(np.round(weights[ly.name] / f32(s)),
                              -qmax, qmax).astype(np.int8)
    return Quant(act, wscale, wq)


def _requant(acc: np.ndarray, mult, zp: int) -> np.ndarray:
    q = np.round(np.asarray(acc).astype(f32) * f32(mult)) + zp
    return np.clip(q, -128, 127).astype(np.int8)


def forward_int8(cfg: dict, quant: Quant, image) -> np.ndarray:
    """int8 output of one image (int8 pixels)."""
    x = np.asarray(image, np.int8)
    src = "input"
    for ly in layers(cfg):
        s_x, zp_x = quant.act[src]
        s_y, zp_y = quant.act[ly.name]
        if ly.kind in ("conv2d", "depthwise_conv2d", "fully_connected"):
            mult = f32(f32(f32(s_x) * f32(quant.wscale[ly.name])) / f32(s_y))
            xc = x.astype(np.int64) - zp_x
            w = quant.wq[ly.name]
            if ly.kind == "fully_connected":
                acc = xc.astype(np.float64) @ w.astype(np.float64)
            else:
                acc = _conv_acc(xc, w, ly)
            x = _requant(np.rint(acc).astype(np.int32), mult, zp_y)
        elif ly.kind == "mean":
            cnt = x.shape[0] * x.shape[1]
            acc = x.astype(np.int32).sum(axis=(0, 1))
            val = acc.astype(f32) / f32(cnt) - zp_x
            x = _requant(val, f32(f32(s_x) / f32(s_y)), zp_y)
        else:
            v = (x.astype(f32) - f32(zp_x)) * f32(s_x)
            e = np.exp(v - v.max())
            y = (e / e.sum()).astype(f32)
            q = np.round(y / f32(s_y)) + zp_y
            x = np.clip(q, -128, 127).astype(np.int8)
        src = ly.name
    return x


# ---------------------------------------------------------------------------
# What the harness calls
# ---------------------------------------------------------------------------


def calibrate(cfg: dict):
    """What is fixed once per configuration: the int8 activation ranges
    (``None`` for float). The harness times it apart from its set-up: it is
    the reference's work, the same for every seed."""
    return activation_ranges(cfg) if cfg["dtype"] == "int8" else None


def make_params(cfg: dict, seed: int, calib, control: bool = False):
    """``(weights, quant)``: float32 weights from ``seed`` and, for int8
    configurations, their quantisation at the activation ranges ``calib``
    (from :func:`calibrate`) and fixed weight scales (int4 weights when
    ``control``)."""
    weights = make_weights(cfg, seed)
    if calib is None:
        return weights, None
    return weights, quantise_weights(cfg, weights, calib,
                                     weight_bits=4 if control else 8)


def predict(cfg: dict, weights, quant: Optional[Quant], image,
            control: bool = False) -> np.ndarray:
    """The network's output for one image: int8 class scores of the int8
    tier, float64 probabilities otherwise (bfloat16 operands when
    ``control``; an int8 control comes in through int4 ``quant``)."""
    if quant is not None:
        return forward_int8(cfg, quant, image)
    return forward_float(cfg, weights, image,
                         bf16 if control else None)["prob"]
