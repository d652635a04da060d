"""Small networks for CPU tests, each a configuration in the schema of
``configs/*.json`` and the system's network for it at the shapes it really
runs: a ResNet, built the way the system's zoo builds ``resnet(depth)``,
and a fire module with a concat join, whose configuration and plain
reference a test drops into a copy of the benchmark as new files."""
from __future__ import annotations

import copy
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def config(block: str = "basic", limit: float = 1e-4) -> dict:
    cfg = json.loads((BENCH / "configs" / "resnet50-valid.json").read_text())
    cfg.update(name=f"tiny-{block}", image=48, stem_channels=8, widths=[8, 16],
               blocks=[1, 2], block=block,
               expansion=4 if block == "bottleneck" else 1,
               max_rel_err_limit=limit)
    return cfg


def spec(cfg: dict):
    """The system's CNNSpec for ``cfg``, declaring the executed sizes."""
    from repro.models.cnn_zoo import _Builder
    b = _Builder(cfg["name"])
    im = cfg["image"]
    prev = b.conv(cfg["stem_channels"], cfg["in_channels"], im,
                  cfg["stem_stride"], cfg["stem_kernel"])
    im = (im - cfg["stem_kernel"]) // cfg["stem_stride"] + 1
    c_in = cfg["stem_channels"]
    for stage, (width, n) in enumerate(zip(cfg["widths"], cfg["blocks"])):
        out_c = width * cfg["expansion"]
        for blk in range(n):
            s = 2 if stage > 0 and blk == 0 else 1
            if cfg["block"] == "bottleneck":
                x = b.conv(width, c_in, im, 1, 1, prev=prev)
                x = b.conv(width, width, im, s, 3, prev=x)
                h = (im - 3) // s + 1
                tail = b.conv(out_c, width, h, 1, 1, prev=x)
            else:
                x = b.conv(width, c_in, im, s, 3, prev=prev)
                h = (im - 3) // s + 1
                tail = b.conv(width, width, h, 1, 3, prev=x)
                h -= 2
            if s != 1 or c_in != out_c:
                sc = b.conv(out_c, c_in, im, s, 1, prev=prev, tag="down")
                h = min(h, (im - 1) // s + 1)
            else:
                sc = prev
            prev = b.join("add", out_c, h, [tail, sc])
            im, c_in = h, out_c
    return b.build()


def cell(cfg: dict, mix: str, **over) -> dict:
    """A cell of ``cfg`` under the named traffic mix, shortened for a test."""
    from bench import registry
    m = copy.deepcopy(registry.load_json("traffic", mix))
    m.update(images=8, warmup_s=0.2, **over)
    m["server"]["max_batch"] = min(m["server"]["max_batch"], 4)
    if m["kind"] == "closed":
        m["outstanding"] = min(m["outstanding"], 8)
    return {"name": f"{cfg['name']}.{mix}", "chips": 1, "config_data": cfg,
            "traffic_data": m, "end_to_end": [], "per_layer": []}


# A SqueezeNet fire module (arXiv:1602.07360) on a 3x3 stem: a 1x1 squeeze,
# 1x1 and 3x3 expand branches joined by concat, then a residual add of the
# stem; valid convolutions, each join centre-cropped to its smallest input.
FIRE_REFERENCE = '''"""A fire module with a residual add, as ``tiny.fire_spec`` builds it."""


def convs(cfg):
    stem, sq, e = cfg["stem_channels"], cfg["squeeze"], cfg["expand"]
    return [(stem, cfg["in_channels"], 3, 1), (sq, stem, 1, 1),
            (e, sq, 1, 1), (e, sq, 3, 1)]


def _crop(xs):
    h = min(x.shape[-2] for x in xs)
    w = min(x.shape[-1] for x in xs)
    return [x[..., (x.shape[-2] - h) // 2:(x.shape[-2] - h) // 2 + h,
              (x.shape[-1] - w) // 2:(x.shape[-1] - w) // 2 + w] for x in xs]


def run(cfg, weights, x, conv):
    import jax.numpy as jnp
    stem, squeeze, e1, e3 = weights
    y = conv(x, stem, 1)
    s = conv(y, squeeze, 1)
    cat = jnp.concatenate(_crop([conv(s, e1, 1), conv(s, e3, 1)]), axis=1)
    a, b = _crop([cat, y])
    return a + b
'''


def fire_config(limit: float = 2e-6) -> dict:
    """``stem_channels`` = 2 x ``expand``, so that the concat adds to the
    stem. The limit lies between the served plan's readings on the CPU,
    2.5e-07 to 3.4e-07 over four seeds, and the control's (three bfloat16
    passes), 6.9e-06 to 9.5e-06: a net this shallow rounds less than
    resnet50-valid, whose 8e-6 would pass the control on some seeds."""
    return {"name": "fire-tiny", "reference": "fire", "image": 20,
            "in_channels": 3, "stem_channels": 16, "squeeze": 8, "expand": 8,
            "dtype": "float32", "matmul_precision": "highest",
            "max_rel_err_limit": limit}


def fire_spec(cfg: dict, swap: bool = False):
    """The system's CNNSpec of ``FIRE_REFERENCE``; ``swap`` joins the two
    expand branches in the other order, a network that differs from the
    reference in wiring alone."""
    from repro.models.cnn_zoo import _Builder
    b = _Builder(cfg["name"])
    im, e = cfg["image"], cfg["expand"]
    stem = b.conv(cfg["stem_channels"], cfg["in_channels"], im, 1, 3)
    im -= 2
    sq = b.conv(cfg["squeeze"], cfg["stem_channels"], im, 1, 1, prev=stem)
    e1 = b.conv(e, cfg["squeeze"], im, 1, 1, prev=sq, tag="exp1")
    e3 = b.conv(e, cfg["squeeze"], im, 1, 3, prev=sq, tag="exp3")
    cat = b.join("concat", 2 * e, im - 2, [e3, e1] if swap else [e1, e3])
    b.join("add", 2 * e, im - 2, [cat, stem])
    return b.build()
