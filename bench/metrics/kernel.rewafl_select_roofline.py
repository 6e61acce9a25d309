"""kernel.rewafl_select_roofline: the least time the selection needs,
over the device time of the Pallas kernel under `round.selection`.

Work per call, from shapes: seven (S,) float32 leaves read (statistical
utility, latency, energy, residual energy, reserve, availability, the
exploration draw) and K indices and K live flags written; about a dozen
FLOPs per device for Eqn (2) and its comparison. It is bound by bytes.
"""

FLOPS_PER_DEVICE = 12


def work(S: int, K: int):
    return FLOPS_PER_DEVICE * S, 7 * 4 * S + 2 * 4 * K


def read(ctx):
    if ctx.trace is None or ctx.rounds == 0:
        return None
    from bench.peaks import roofline_seconds
    from bench.trace import kernel_s
    t = kernel_s(ctx.trace, "round.selection")
    if not t:
        return None
    flops, nbytes = work(ctx.S, ctx.K)
    least = ctx.rounds * roofline_seconds(flops, nbytes, ctx.device_kind)
    return 100.0 * least / t
