"""The ELL round kernel's share of its roofline: the least time its
traced invocations could take, moving only their compulsory bytes
(``bench/kernels.py``) at the chip's published HBM bandwidth
(``bench/peaks.json``), over their measured device time.  Bound by
bandwidth; invocations cut by the edge of the traced period are left out."""
from bench import kernels, trace


def read(run):
    lo, hi = run.traced_ns
    fab = run.cfg["fabric"]
    least = kernels.ell_round_bytes(fab["switches"], fab["network_ports"]) \
        / run.peaks["hbm_bytes_per_s"] * 1e9
    count, spent = 0, 0.0
    for i in sorted(run.trace.devices)[:run.wl["chips"]]:
        for o in run.trace.devices[i]:
            if kernels.is_ell_round(o.name) and lo <= o.start and o.end <= hi:
                count += 1
                spent += o.end - o.start
    if not count or spent <= 0:
        return None
    return 100.0 * count * least / spent
