"""Plain float32 ResNet-34 (He et al. 2016, arXiv:1512.03385, Table 1).

Basic blocks of two 3×3 convs; a 1×1 projection where the width or the
stride changes; ReLU after the first conv and after the residual add;
global average pool and a dense head.  No batch norm: a served model folds
it into the conv bias.  Departures the configuration file states: the stem
conv is ``stem.kernel`` wide (5 here, 7 published) and its pool is
``stem.pool_kernel`` wide with stride ``stem.pool_stride`` (2 and 2 here,
3 and 2 published).  Sizes come from the configuration file.
"""

from __future__ import annotations

import jax

from . import common as C


def _width(c: int, cfg: dict) -> int:
    return max(8, int(c * cfg["width_mult"]))


def init(key, cfg: dict) -> dict:
    """Float weights in the program's draw order: the stem, then per block
    c1, c2 and (where needed) proj, then the head."""
    stages = cfg["stages"]
    blocks = sum(n for _, n, _ in stages)
    keys = iter(jax.random.split(key, 2 + 3 * blocks))
    c0 = _width(cfg["stem"]["channels"], cfg)
    params = {"stem": C.conv_init(next(keys), cfg["stem"]["kernel"],
                                  cfg["in_channels"], c0),
              "stages": []}
    cin = c0
    for cout, nblocks, first_stride in stages:
        cout = _width(cout, cfg)
        stage = []
        for b in range(nblocks):
            stride = first_stride if b == 0 else 1
            blk = {"c1": C.conv_init(next(keys), 3, cin, cout),
                   "c2": C.conv_init(next(keys), 3, cout, cout)}
            if stride != 1 or cin != cout:
                blk["proj"] = C.conv_init(next(keys), 1, cin, cout)
            stage.append(blk)
            cin = cout
        params["stages"].append(stage)
    params["head"] = C.dense_init(next(keys), cin, cfg["n_classes"])
    return params


def apply(params: dict, x, cfg: dict, act=None):
    st = cfg["stem"]
    x = jax.nn.relu(C.conv(params["stem"], x, stride=st["stride"], act=act))
    x = C.maxpool(x, st["pool_kernel"], st["pool_stride"])
    for stage, (_, _, first_stride) in zip(params["stages"], cfg["stages"]):
        for b, blk in enumerate(stage):
            stride = first_stride if b == 0 else 1
            y = jax.nn.relu(C.conv(blk["c1"], x, stride=stride, act=act))
            y = C.conv(blk["c2"], y, act=act)
            sc = (C.conv(blk["proj"], x, stride=stride, act=act)
                  if "proj" in blk else x)
            x = jax.nn.relu(y + sc)
    return C.dense(params["head"], C.global_avgpool(x), act=act)
