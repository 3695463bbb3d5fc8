"""The sketch kernels' share of their roofline: the least time the chip
needs for the window's sketch passes, the larger of operations over peak
FLOP/s and least bytes over HBM bandwidth (``bench/work/<family>.py``,
``bench/peaks.json``), over the kernels' device time in the trace."""


def _least(ctx):
    flop_s = byte_s = 0.0
    pk = ctx.peaks
    for c in ctx.window.counters.get("calls", []):
        w = ctx.work(c["family"])
        k = c["calls"] * c["batch"]
        flop_s += k * w.flops(c["n"], c["d"], c["m_max"]) / pk["flops_per_s"]
        byte_s += (k * w.min_bytes(c["n"], c["d"], c["m_max"])
                   / pk["hbm_bytes_per_s"])
    return flop_s, byte_s


def _kernel_s(ctx):
    t = ctx.trace
    if t is None:
        return 0.0
    fams = {c["family"] for c in ctx.window.counters.get("calls", [])}
    return sum(t.family_s.get(f, 0.0) for f in fams)


def read(ctx):
    kernel = _kernel_s(ctx)
    if kernel <= 0:
        return None
    return 100.0 * max(_least(ctx)) / kernel


def describe(ctx):
    flop_s, byte_s = _least(ctx)
    bound = "memory" if byte_s >= flop_s else "compute"
    return (f"bound by {bound}: least {max(flop_s, byte_s) * 1e3:.4f} ms "
            f"(compute {flop_s * 1e3:.4f} ms, memory {byte_s * 1e3:.4f} ms) "
            f"against {_kernel_s(ctx) * 1e3:.4f} ms of kernel time")
