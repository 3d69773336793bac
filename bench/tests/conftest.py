"""CPU sizes of the configurations that ``tinycells.SMOKE`` does not list,
registered before any test module here builds a cell from ``tinycells``."""
import tinycells

# Swin-B's SMOKE architecture (32x32 frames, window 4): stage 0's 8x8 token
# map shifts on its odd block, stage 1's single 4x4 window does not
tinycells.SMOKE.setdefault("swin_b", {"window": 4, "depths": [2, 2], "embed_dim": 32,
                                      "heads": [2, 4]})
