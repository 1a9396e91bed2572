"""What the device's allocator holds at run time, beside the compile's
``step_hbm_gib``: the largest ``hbm.reserved`` (``bytes_reserved`` of
``memory_stats()``, the largest over the local devices) on the window's
``step_window`` events. Where the runtime reports no ``bytes_reserved`` it is
the largest ``hbm.peak`` (``peak_bytes_in_use``: live buffers only)."""


def read(sources):
    stats = [e["hbm"] for e in sources.get("step_window_events") or [] if e.get("hbm")]
    for key in ("reserved", "peak"):
        levels = [h[key] for h in stats if key in h]
        if levels:
            return max(levels) / 2 ** 30
    return None
