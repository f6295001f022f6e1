"""``memory_stats()["peak_bytes_reserved"]`` on the fullest chip: the
largest scratch a program reserved while it ran (the step's
temporaries). Live arrays are counted apart in ``device_hbm_live_gb``;
the two together are the result line's ``memory_peak_bytes``."""


def read(ctx):
    reserved = ctx["memory"]["reserved_peak_bytes"]
    return reserved / 1e9 if reserved else None
