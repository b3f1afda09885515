"""Plain NumPy reference of Inception-ResNet-v2 (arXiv:1602.07261), float.

It imports nothing of the program under test. It states the network as
Keras Applications builds ``InceptionResNetV2`` (299 px, 1000 classes),
under the program's op names, makes its weights from a seed and runs one
image at a time in float64 from float32 weights and inputs:

- the stem: three 3x3 convs, a 3x3/2 max pool, a 1x1 and a 3x3 conv and
  another max pool (``stem_*``), then the Inception-A block ``m5b_*``
  (a 5x5 branch and a same-padded 3x3 average pool);
- ``repeats[0]`` Inception-ResNet-A blocks at 35x35x320 (``m35_<i>_*``),
  reduction A (``ra_*``), ``repeats[1]`` Inception-ResNet-B blocks at
  17x17x1088 with 1x7 and 7x1 convs (``m17_<i>_*``), reduction B
  (``rb_*``), ``repeats[2]`` Inception-ResNet-C blocks at 8x8x2080 with
  1x3 and 3x1 convs (``m8_<i>_*``), a 1x1 conv to 1536 channels
  (``conv_final``);
- global mean (``gap``), a fully connected layer (``logits``) and a
  softmax (``prob``).

Each residual block adds ``scale * up(concat(branches))`` to its input:
``up`` is a 1x1 conv with no activation, so the published scale (0.17,
0.1, 0.2, and 1.0 for the last C block) is folded into its weights, which
is exact. Convs and pools follow TF ``SAME`` / ``VALID`` padding; an
average pool divides by the count of taps that fall inside the input, as
Keras does. Like the program's graph the network has no batch norm (it
folds into the weights), no bias and no ReLU between layers;
``PERF.md`` notes the departures.

``control`` rounds the operands of every product (conv and fully
connected inputs and weights) to bfloat16: the precision one step below
float32.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

#: Published block counts: Inception-ResNet-A, -B, -C.
REPEATS = (10, 20, 10)
#: Published residual scales of the A, B and C blocks; the last C block
#: adds its branch unscaled.
SCALES = (0.17, 0.1, 0.2)


@dataclasses.dataclass(frozen=True)
class Layer:
    """One op. ``kind`` is conv2d / pool / concat / add / mean /
    fully_connected / softmax; ``inputs`` names the layers it reads
    (``"input"`` for the image); shapes are per image (H, W, C) or (C,).
    ``kernel`` and ``stride`` are (rows, cols); ``scale`` multiplies a
    residual up-projection's weights."""
    name: str
    kind: str
    inputs: Tuple[str, ...]
    in_shapes: Tuple[Tuple[int, ...], ...]
    out_shape: Tuple[int, ...]
    kernel: Tuple[int, int] = (1, 1)
    stride: int = 1
    padding: str = "same"
    mode: str = ""
    scale: float = 1.0

    @property
    def in_shape(self) -> Tuple[int, ...]:
        return self.in_shapes[0]

    @property
    def weight_shape(self) -> Optional[Tuple[int, ...]]:
        ic, oc = self.in_shape[-1], self.out_shape[-1]
        if self.kind == "conv2d":
            return (self.kernel[0], self.kernel[1], ic, oc)
        if self.kind == "fully_connected":
            return (ic, oc)
        return None


def _out_dim(n: int, k: int, s: int, padding: str) -> int:
    return -(-n // s) if padding == "same" else (n - k) // s + 1


def layers(cfg: dict) -> List[Layer]:
    """The op list at the configuration's ``resolution`` and ``classes``;
    an optional ``repeats`` key gives the block counts (the published
    ``REPEATS`` where it is absent)."""
    res, n_cls = int(cfg["resolution"]), int(cfg["classes"])
    repeats = tuple(cfg.get("repeats", REPEATS))
    out: List[Layer] = []
    shapes: Dict[str, Tuple[int, ...]] = {"input": (res, res, 3)}

    def add(name, kind, inputs, shape, **kw):
        out.append(Layer(name, kind, tuple(inputs),
                         tuple(shapes[i] for i in inputs), shape, **kw))
        shapes[name] = shape
        return name

    def conv(x, oc, k, s=1, padding="same", name="", scale=1.0):
        kh, kw = (k, k) if isinstance(k, int) else k
        h, w, _ = shapes[x]
        shape = (_out_dim(h, kh, s, padding), _out_dim(w, kw, s, padding),
                 oc)
        return add(name, "conv2d", [x], shape, kernel=(kh, kw), stride=s,
                   padding=padding, scale=scale)

    def pool(x, k, s, padding, mode, name):
        h, w, c = shapes[x]
        shape = (_out_dim(h, k, s, padding), _out_dim(w, k, s, padding), c)
        return add(name, "pool", [x], shape, kernel=(k, k), stride=s,
                   padding=padding, mode=mode)

    def concat(xs, name):
        h, w, _ = shapes[xs[0]]
        return add(name, "concat", xs,
                   (h, w, sum(shapes[x][-1] for x in xs)))

    def residual(x, branches, i, tag, scale):
        cat = concat(branches, f"{tag}_{i}_cat")
        up = conv(cat, shapes[x][-1], 1, name=f"{tag}_{i}_up", scale=scale)
        return add(f"{tag}_{i}_add", "add", [x, up], shapes[x])

    x = conv("input", 32, 3, 2, "valid", "stem_c1")
    x = conv(x, 32, 3, 1, "valid", "stem_c2")
    x = conv(x, 64, 3, 1, "same", "stem_c3")
    x = pool(x, 3, 2, "valid", "max", "stem_p1")
    x = conv(x, 80, 1, name="stem_c4")
    x = conv(x, 192, 3, 1, "valid", "stem_c5")
    x = pool(x, 3, 2, "valid", "max", "stem_p2")
    b1 = conv(x, 96, 1, name="m5b_b1")
    b2 = conv(conv(x, 48, 1, name="m5b_b2a"), 64, 5, name="m5b_b2b")
    b3 = conv(conv(conv(x, 64, 1, name="m5b_b3a"), 96, 3, name="m5b_b3b"),
              96, 3, name="m5b_b3c")
    b4 = conv(pool(x, 3, 1, "same", "avg", "m5b_p"), 64, 1, name="m5b_b4")
    x = concat([b1, b2, b3, b4], "m5b_cat")
    for i in range(repeats[0]):
        t = f"m35_{i}"
        b1 = conv(x, 32, 1, name=f"{t}_b1")
        b2 = conv(conv(x, 32, 1, name=f"{t}_b2a"), 32, 3, name=f"{t}_b2b")
        b3 = conv(conv(conv(x, 32, 1, name=f"{t}_b3a"), 48, 3,
                       name=f"{t}_b3b"), 64, 3, name=f"{t}_b3c")
        x = residual(x, [b1, b2, b3], i, "m35", SCALES[0])
    r1 = conv(x, 384, 3, 2, "valid", "ra_1")
    r2 = conv(conv(conv(x, 256, 1, name="ra_2a"), 256, 3, name="ra_2b"),
              384, 3, 2, "valid", "ra_2c")
    r3 = pool(x, 3, 2, "valid", "max", "ra_p")
    x = concat([r1, r2, r3], "ra_cat")
    for i in range(repeats[1]):
        t = f"m17_{i}"
        b1 = conv(x, 192, 1, name=f"{t}_b1")
        b2 = conv(x, 128, 1, name=f"{t}_b2a")
        b2 = conv(b2, 160, (1, 7), name=f"{t}_b2b")
        b2 = conv(b2, 192, (7, 1), name=f"{t}_b2c")
        x = residual(x, [b1, b2], i, "m17", SCALES[1])
    r1 = conv(conv(x, 256, 1, name="rb_1a"), 384, 3, 2, "valid", "rb_1b")
    r2 = conv(conv(x, 256, 1, name="rb_2a"), 288, 3, 2, "valid", "rb_2b")
    r3 = conv(conv(conv(x, 256, 1, name="rb_3a"), 288, 3, name="rb_3b"),
              320, 3, 2, "valid", "rb_3c")
    r4 = pool(x, 3, 2, "valid", "max", "rb_p")
    x = concat([r1, r2, r3, r4], "rb_cat")
    for i in range(repeats[2]):
        t = f"m8_{i}"
        b1 = conv(x, 192, 1, name=f"{t}_b1")
        b2 = conv(x, 192, 1, name=f"{t}_b2a")
        b2 = conv(b2, 224, (1, 3), name=f"{t}_b2b")
        b2 = conv(b2, 256, (3, 1), name=f"{t}_b2c")
        last = i == repeats[2] - 1
        x = residual(x, [b1, b2], i, "m8", 1.0 if last else SCALES[2])
    x = conv(x, 1536, 1, name="conv_final")
    x = add("gap", "mean", [x], (shapes[x][-1],))
    x = add("logits", "fully_connected", [x], (n_cls,))
    add("prob", "softmax", [x], (n_cls,))
    return out


def make_weights(cfg: dict, seed: int) -> Dict[str, np.ndarray]:
    """float32 weights per weighted layer: normal with deviation
    1/sqrt(fan-in), which keeps the activations of the ~170 linear layers
    of order one, times the layer's residual scale."""
    rng = np.random.default_rng([seed, 1])
    out = {}
    for ly in layers(cfg):
        ws = ly.weight_shape
        if ws is None:
            continue
        std = ly.scale / np.sqrt(float(np.prod(ws[:-1])))
        out[ly.name] = (rng.standard_normal(ws) * std).astype(np.float32)
    return out


# ---------------------------------------------------------------------------
# Float forward
# ---------------------------------------------------------------------------


def _pads(n: int, k: int, s: int, padding: str) -> Tuple[int, int]:
    """(before, after) padding of one spatial axis (TF SAME / VALID)."""
    if padding == "valid":
        return 0, 0
    total = max(0, (-(-n // s) - 1) * s + k - n)
    return total // 2, total - total // 2


def _windows(x: np.ndarray, ly: Layer, value: float):
    """(view per tap, the padded input): each view is the (oh, ow, C)
    input pixels one kernel tap reads for every output pixel."""
    (kh, kw), s = ly.kernel, ly.stride
    oh, ow = ly.out_shape[:2]
    xp = np.pad(x, (_pads(x.shape[0], kh, s, ly.padding),
                    _pads(x.shape[1], kw, s, ly.padding), (0, 0)),
                constant_values=value)
    return [xp[fy:fy + s * (oh - 1) + 1:s, fx:fx + s * (ow - 1) + 1:s]
            for fy in range(kh) for fx in range(kw)]


def _conv(x: np.ndarray, w: np.ndarray, ly: Layer) -> np.ndarray:
    oh, ow, oc = ly.out_shape
    cols = np.concatenate(_windows(x, ly, 0.0), axis=-1)
    return (cols.reshape(oh * ow, -1) @ w.reshape(-1, oc)).reshape(
        oh, ow, oc)


def _pool(x: np.ndarray, ly: Layer) -> np.ndarray:
    if ly.mode == "max":
        return np.max(_windows(x, ly, -np.inf), axis=0)
    taps = _windows(x, ly, 0.0)
    ones = np.ones(x.shape[:2] + (1,))
    count = sum(_windows(ones, ly, 0.0))
    return sum(taps) / count


def forward_float(cfg: dict, weights: Dict[str, np.ndarray], image,
                  operand=None) -> Dict[str, np.ndarray]:
    """Every layer's output (keyed by layer name, plus ``"input"``) for one
    image, in float64. ``operand`` rounds the operands of each product
    (activations and weights) before use: the bfloat16 control."""
    rnd = operand or (lambda a: a)
    vals = {"input": np.asarray(image, np.float64)}
    for ly in layers(cfg):
        xs = [vals[i] for i in ly.inputs]
        if ly.kind == "conv2d":
            y = _conv(rnd(xs[0]), rnd(weights[ly.name]).astype(np.float64),
                      ly)
        elif ly.kind == "pool":
            y = _pool(xs[0], ly)
        elif ly.kind == "concat":
            y = np.concatenate(xs, axis=-1)
        elif ly.kind == "add":
            y = xs[0] + xs[1]
        elif ly.kind == "mean":
            y = xs[0].mean(axis=(0, 1))
        elif ly.kind == "fully_connected":
            y = rnd(xs[0]) @ rnd(weights[ly.name]).astype(np.float64)
        else:
            e = np.exp(xs[0] - xs[0].max())
            y = e / e.sum()
        vals[ly.name] = y
    return vals


def bf16(a: np.ndarray) -> np.ndarray:
    """Round to bfloat16 and back: the operand precision of the control."""
    import ml_dtypes
    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16
                                            ).astype(np.float64)


# ---------------------------------------------------------------------------
# What the harness calls
# ---------------------------------------------------------------------------


def layer_work(layer: Layer, dtype_bytes: int, batch: int = 1):
    """(operations, bytes) of a pool, concat or add for one call of
    ``batch`` images: a pool does one operation per window element of each
    output, an add one per output element, a concat none; bytes are the
    inputs and the output. ``None`` for the kinds ``bench/opcount.py``
    counts itself."""
    if layer.kind not in ("pool", "concat", "add"):
        return None
    n_out = int(np.prod(layer.out_shape))
    n_in = sum(int(np.prod(s)) for s in layer.in_shapes)
    ops = {"pool": n_out * layer.kernel[0] * layer.kernel[1],
           "add": n_out, "concat": 0}[layer.kind]
    return ops * batch, (n_in + n_out) * batch * dtype_bytes


def calibrate(cfg: dict):
    """Nothing is fixed per configuration: the network is float."""
    return None


def make_params(cfg: dict, seed: int, calib, control: bool = False):
    """``(weights, None)``: float32 weights from ``seed``."""
    return make_weights(cfg, seed), None


def predict(cfg: dict, weights, quant, image,
            control: bool = False) -> np.ndarray:
    """Class probabilities of one image in float64 (bfloat16 operands when
    ``control``)."""
    return forward_float(cfg, weights, image,
                         bf16 if control else None)["prob"]
