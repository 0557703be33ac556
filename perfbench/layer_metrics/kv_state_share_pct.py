"""Share of the slot pool's bytes that are recurrent state without a
position axis: ``/info`` ``kv_pool_bytes_by_kind`` at the window's
close, ``state`` over all kinds.  These bytes are read and written
WHOLE every decode step, whatever the positions; the rest is read as
far as it is written.  Nothing to read from a program that does not
know the kind."""


def read(ctx):
    kinds = ctx.collected["info_close"].get("kv_pool_bytes_by_kind")
    if not kinds or "state" not in kinds or not sum(kinds.values()):
        return None
    return 100.0 * kinds["state"] / sum(kinds.values())
