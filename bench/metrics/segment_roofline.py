"""Device: the fused segment program's share of its roofline, in
percent: the bytes its semantics needs (``harness.roofline``) at the
chip's peak HBM bandwidth, over the device time of the ``jit_seg``
programs in the traced window.  The segment does no floating-point work
worth a compute bound, so bandwidth bounds it.  Source: the profiler's
device trace, with the launches' tuple counts from the
``fused.segment`` spans."""

from harness.roofline import segment_bytes
from harness.spans import inside, named


def read(b):
    dev_s = b.get("segment_device_s")
    if not dev_s:
        return None
    cfg = b["config"]
    count_scheme = cfg["grouping"]["scheme"]
    total = 0
    for s in named(inside(b["spans"], b["window"]), "fused.segment"):
        args = s[3] or {}
        scheme = args.get("scheme")
        lanes = (cfg["workers"] if scheme == count_scheme
                 else cfg["merge_workers"]) + 1
        total += segment_bytes(scheme, args["hi"] - args["lo"], lanes,
                               windowed=scheme == count_scheme)
    if not total:
        return None
    return total / b["peaks"]["hbm_bytes_per_s"] / dev_s * 100.0
