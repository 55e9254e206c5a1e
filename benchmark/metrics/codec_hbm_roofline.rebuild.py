"""The codec kernel's share of the HBM roofline over the rebuild
window: least time / device busy time, least time = (input + output
bytes of the window's reconstructs) / peak HBM bandwidth
(roofline.py)."""
from benchmark.roofline import hbm_roofline_pct


def read(run):
    return hbm_roofline_pct(run, "rebuild")
