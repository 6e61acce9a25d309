"""setup.compile_load_s: seconds of set-up spent in XLA compiles or
persistent-cache loads: the union of the `jax.compile` spans, clipped
to set-up (`bench/setup_spans.py`)."""


def read(ctx):
    from bench import setup_spans
    v = setup_spans.view()
    return None if v is None else setup_spans.names_s(v, ("jax.compile",))
