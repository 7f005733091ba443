"""Plain float32 VGG-16 (Simonyan & Zisserman 2015, arXiv:1409.1556,
Table 1 column D).

Thirteen 3×3 convs, each followed by ReLU, with a 2×2 stride-2 max-pool
after the last conv of each of the five blocks; the 7×7×512 map flattened
in NHWC order and three fully connected layers, FC-4096, FC-4096 and
FC-n_classes, with ReLU after the first two.  Sizes come from the
configuration file.  Departures from the paper, which the configuration
file states:

  * no batch norm (VGG-16 has none) and no dropout (training only);
  * the fully connected weights are drawn as the program draws its
    dense-evaluation convs, He-normal of shape (7, 7, C, F), (1, 1, F, F)
    and (1, 1, F, n_classes), and reshaped to matrices here; their log
    codes have one scale per output column;
  * a map other than 7×7 (an image below 224 px) is first average-pooled
    to 7×7 with torchvision's adaptive bins.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common as C

MAP = 7  # side of the map the classifier reads (224 px / 2**5)


def _width(c: int, cfg: dict) -> int:
    return max(8, int(c * cfg["width_mult"]))


def init(key, cfg: dict) -> dict:
    """Float weights in the program's draw order: the 13 convs, then FC6,
    FC7 and FC8."""
    plan = cfg["convs"]
    keys = jax.random.split(key, len(plan) + 3)
    convs, c = [], cfg["in_channels"]
    for i, (cout, _) in enumerate(plan):
        cout = _width(cout, cfg)
        convs.append(C.conv_init(keys[i], 3, c, cout))
        c = cout
    f6, f7 = (_width(f, cfg) for f in cfg["classifier"])
    k6, k7, k8 = keys[len(plan):]
    fcs = [C.conv_init(k6, MAP, c, f6), C.conv_init(k7, 1, f6, f7),
           C.conv_init(k8, 1, f7, cfg["n_classes"])]
    return {"convs": convs, "fcs": fcs}


def adaptive_avgpool(x, out: int):
    """[B, H, W, C] → [B, out, out, C]: output pixel (i, j) is the mean of
    rows ⌊iH/out⌋ … ⌈(i+1)H/out⌉ − 1 and the same columns of W."""
    H, W = x.shape[1], x.shape[2]
    rows = [(i * H // out, -(-(i + 1) * H // out)) for i in range(out)]
    cols = [(j * W // out, -(-(j + 1) * W // out)) for j in range(out)]
    return jnp.stack([jnp.stack([jnp.mean(x[:, a:b, c:d], axis=(1, 2))
                                 for c, d in cols], axis=1)
                      for a, b in rows], axis=1)


def apply(params: dict, x, cfg: dict, act=None):
    for p, (_, pool) in zip(params["convs"], cfg["convs"]):
        x = jax.nn.relu(C.conv(p, x, act=act))
        if pool:
            x = C.maxpool(x, 2, 2)
    if x.shape[1:3] != (MAP, MAP):
        x = adaptive_avgpool(x, MAP)
    x = x.reshape(x.shape[0], -1)
    for i, p in enumerate(params["fcs"]):
        w = p["wq"].reshape(-1, p["wq"].shape[-1])
        x = C.dense({"w": w, "b": p["b"]}, x, act=act)
        if i < 2:
            x = jax.nn.relu(x)
    return x
