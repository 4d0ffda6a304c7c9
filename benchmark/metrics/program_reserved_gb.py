"""Device memory the runtime reserved for compiled programs' temporaries at
its peak (``memory_stats()["peak_bytes_reserved"]``), which the TPU runtime
counts apart from live buffers (``peak_hbm_gb``) and which is what a row count
too large fails on."""


def read(ctx):
    b = ctx["device"].get("memory_reserved_peak_bytes")
    return b / 1e9 if b else None
