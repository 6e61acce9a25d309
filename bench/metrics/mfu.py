"""mfu: useful model FLOPs of the traced window over its wall time and
the chips' peak. Useful work, counted from shapes: the probe forward
(S × probe samples a round), local SGD forward and backward for live
iterations only (Σ over the selected devices of H_k × batch), the
post-training probe of each selected device, and the accuracy eval
forward at each chunk boundary. Masked iterations past H_k do not count.
"""


def read(ctx):
    if ctx.trace is None:
        return None
    h = ctx.history
    n_sel = h["selected"].sum(axis=1)
    h_sum = float((h["mean_H_selected"] * n_sel).sum())
    fwd, train = ctx.forward_flops, ctx.train_flops
    flops = (ctx.rounds * ctx.S * ctx.probe * fwd
             + h_sum * ctx.batch * train
             + float(n_sel.sum()) * ctx.probe * fwd
             + ctx.evals * ctx.n_test * fwd)
    peak = ctx.peaks["flops_per_s"] * ctx.n_chips
    return 100.0 * flops / (ctx.trace.window_s * peak)
