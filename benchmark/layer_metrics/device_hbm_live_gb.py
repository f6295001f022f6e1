"""``memory_stats()["peak_bytes_in_use"]`` on the fullest chip: live
arrays only (parameters, optimizer state, batches in flight). On this
runtime it leaves a running program's scratch out; see
``device_hbm_reserved_gb``."""


def read(ctx):
    live = ctx["memory"]["live_peak_bytes"]
    return live / 1e9 if live else None
