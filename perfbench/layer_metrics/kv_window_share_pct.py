"""Share of the slot pool's bytes that are window layers' rings:
``/info`` ``kv_pool_bytes_by_kind`` at the window's close, ``window``
over ``window + full``."""


def read(ctx):
    kinds = ctx.collected["info_close"].get("kv_pool_bytes_by_kind")
    if not kinds or not sum(kinds.values()):
        return None
    return 100.0 * kinds.get("window", 0) / sum(kinds.values())
