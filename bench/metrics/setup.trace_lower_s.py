"""setup.trace_lower_s: seconds of set-up spent tracing jitted functions
to jaxprs and lowering them to MLIR modules: the union of the
`jax.trace` and `jax.lower` spans (an inner jit traced inside an outer
one counts once), clipped to set-up (`bench/setup_spans.py`)."""


def read(ctx):
    from bench import setup_spans
    v = setup_spans.view()
    return None if v is None else setup_spans.names_s(
        v, ("jax.trace", "jax.lower"))
