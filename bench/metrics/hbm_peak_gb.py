"""Peak device memory held after the window, of the fullest device, in
GB: ``memory_stats()`` ``peak_bytes_in_use`` (buffers) plus
``peak_bytes_reserved`` (the runtime's reservation for the programs'
temporaries)."""


def read(ctx):
    return ctx.memory_peak_bytes / 1e9 if ctx.memory_peak_bytes else None
