"""Share of the device's busy time, in the traced part of the timed
calls, spent in the ELL round kernel (``bench/kernels.py``), over the
chips the cell uses."""
from bench import kernels, trace


def read(run):
    window = trace.clip(run.traced_calls, [run.traced_ns])
    busy = kernel = 0.0
    for i in sorted(run.trace.devices)[:run.wl["chips"]]:
        ops = run.trace.devices[i]
        busy += trace.length(trace.clip([(o.start, o.end) for o in ops],
                                        window))
        kernel += trace.length(trace.clip(
            [(o.start, o.end) for o in ops if kernels.is_ell_round(o.name)],
            window))
    if busy <= 0 or kernel <= 0:
        return None
    return 100.0 * kernel / busy
