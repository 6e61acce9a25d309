"""kernel.fedavg_roofline: the least time the FedAvg reduction needs,
over the device time of the Pallas kernels under `round.aggregation` and
of the copies that stage their operands into on-chip memory: XLA moves
the cohort's stack there before the kernel, so the HBM reads counted
below are made by those copies.

Work per round, from shapes, for each parameter leaf of P values: the
(K, P) float32 stack of the cohort's models and the K weights read, the
P-value aggregate written, and a multiply and an add per stacked value.
It is bound by bytes.
"""


def work(K: int, P: int):
    return 2 * K * P, 4 * (K * P + K + P)


def read(ctx):
    if ctx.trace is None or ctx.rounds == 0:
        return None
    from bench.peaks import roofline_seconds
    from bench.trace import staged_kernel_s
    t = staged_kernel_s(ctx.trace, "round.aggregation", ctx.hlo)
    if not t:
        return None
    least = ctx.rounds * sum(roofline_seconds(*work(ctx.K, p),
                                              ctx.device_kind)
                             for p in ctx.leaf_sizes)
    return 100.0 * least / t
