"""Share of the traced span in which a collective runs on a device
and no other operation of that device does (averaged over the chips)."""


def read(ctx):
    if ctx.reduced["devices"] < 2:
        return None
    return 100.0 * ctx.reduced["collective_exposed_s"] \
        / ctx.reduced["window_s"]
