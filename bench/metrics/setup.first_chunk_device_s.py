"""setup.first_chunk_device_s: the first chunk's `chunk.device` span,
from its dispatch returning to its outputs being ready, clipped to
set-up (`bench/setup_spans.py`)."""


def read(ctx):
    from bench import setup_spans
    v = setup_spans.view()
    if v is None:
        return None
    first = [s for s in v.spans
             if s.name == "chunk.device" and s.args.get("index") == 0]
    return first[0].end - first[0].start if first else None
