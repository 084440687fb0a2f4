"""One part of what the fullest chip held while the window ran, in GB:
``heap_bytes`` (arrays: weights, optimizer state, page pool) or
``stack_bytes`` (the most any program was lent for its temporaries).  Read
at the window's end, before the comparison with the reference
(harness/device.py ``MemoryProbe``)."""


def read(ctx, *, part: str):
    if ctx.peaks is None or not ctx.memory.get(part):
        return None
    return ctx.memory[part] / 1e9
