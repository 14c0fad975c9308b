"""Shared arithmetic of the per-layer readers in `metrics/`."""
from __future__ import annotations

from chipbench.workcount import roofline_share_pct

DECODE_KERNEL = "_decode_sel_core"


def decode_roofline(r):
    """Share of the HBM-bandwidth roofline reached by the decode kernel:
    the compressed and decoded bytes of the blocks really decoded in the
    window, over the peak, over the device time of every
    `_decode_sel_core` executable in the trace."""
    if r.reduction is None:
        return None
    return roofline_share_pct(r.work_bytes,
                              r.reduction.module_seconds(DECODE_KERNEL),
                              r.peak_bytes_per_s)


def device_idle_share(r):
    """Percent of the traced window in which no operation ran on the
    device (busy is the union of the operation intervals)."""
    if r.reduction is None:
        return None
    return 100.0 * r.reduction.idle_share
