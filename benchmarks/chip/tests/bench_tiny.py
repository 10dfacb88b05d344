"""A cell at a size a CPU test run can hold: the chat cell's configuration
and traffic with every size cut (2 layers of width 64, 4 slots of 192
tokens), served through the jnp kernel oracles."""
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (os.path.join(ROOT, "src"), HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import spec  # noqa: E402

# Limit on the mean served-token gap (in standard deviations of the
# reference logits) at this size, on the CPU with the jnp kernel oracles,
# 2-second windows: sound runs read 0.0059-0.0219 over 12 seeds, the
# control (FP4 activations) 0.13-0.31, a token altered in eight 0.23-0.40,
# a step that leaves the page pool unchanged 2.2-2.3.
TINY_LIMIT = 0.06


def tiny_cell(name="olmo-1b.chat-poisson", rate=6.0):
    c = spec.cell(name)
    cfg = dict(c.config)
    cfg["arch"] = dict(cfg["arch"], n_layers=2, d_model=64, n_heads=4,
                       n_kv_heads=4, d_ff=256, vocab_size=256)
    if "max_position" in cfg["arch"]:
        cfg["arch"]["max_position"] = 192
    cfg["serve"] = dict(cfg["serve"], slots=4, max_seq=192, page_size=16,
                        pool_pages=48, prefill_token_budget=64)
    cfg["knee_rps"] = {"chat": rate}
    cfg["check"] = dict(cfg["check"], limits={"mean_gap": TINY_LIMIT})
    tr = dict(c.traffic)
    if tr["loop"] == "open":
        tr["prompt"] = dict(tr["prompt"], median=24, min=8, max=60)
        tr["output"] = dict(tr["output"], median=12, min=4, max=40)
    else:
        tr["prompt"] = dict(tr["prompt"], min=40, max=100)
    tr["preroll_s"] = 0.5
    return spec.Cell(name=c.name, chips=1, config=cfg, traffic=tr,
                     end_to_end=c.end_to_end, per_layer=c.per_layer)
