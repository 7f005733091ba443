"""Plain float32 MobileNet v1 (Howard et al. 2017, arXiv:1704.04861,
Table 1).

A 3×3 stride-2 stem, then 13 depthwise-separable pairs: a 3×3 depthwise
conv (one group per channel) and a 1×1 pointwise conv, each followed by
ReLU; global average pool and a dense head.  No batch norm: a served model
folds it into the conv bias.  Sizes come from the configuration file.
"""

from __future__ import annotations

import jax

from . import common as C


def _width(c: int, cfg: dict) -> int:
    return max(8, int(c * cfg["width_mult"]))


def init(key, cfg: dict) -> dict:
    """Float weights in the program's draw order: the stem, then dw and pw
    of each pair, then the head."""
    pairs = cfg["pairs"]
    keys = jax.random.split(key, 2 + 2 * len(pairs))
    c = _width(cfg["stem"]["channels"], cfg)
    params = {"stem": C.conv_init(keys[0], 3, cfg["in_channels"], c),
              "pairs": []}
    for i, (cout, _) in enumerate(pairs):
        cout = _width(cout, cfg)
        params["pairs"].append({
            "dw": C.conv_init(keys[1 + 2 * i], 3, c, c, groups=c),
            "pw": C.conv_init(keys[2 + 2 * i], 1, c, cout)})
        c = cout
    params["head"] = C.dense_init(keys[-1], c, cfg["n_classes"])
    return params


def apply(params: dict, x, cfg: dict, act=None):
    x = jax.nn.relu(C.conv(params["stem"], x, stride=cfg["stem"]["stride"],
                           act=act))
    for pair, (_, stride) in zip(params["pairs"], cfg["pairs"]):
        x = jax.nn.relu(C.conv(pair["dw"], x, stride=stride,
                               groups=x.shape[-1], act=act))
        x = jax.nn.relu(C.conv(pair["pw"], x, act=act))
    return C.dense(params["head"], C.global_avgpool(x), act=act)
