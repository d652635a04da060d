"""A small ResNet for CPU tests: its configuration, in the schema of
``configs/*.json``, and the system's network for it, built the way the
system's zoo builds ``resnet(depth)`` but at the shapes it really runs."""
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
