"""Share of the window's token-expert pairs that fell on the experts
held here: growth of ``/info`` ``moe_pairs_held_total`` over growth of
``moe_pairs_routed_total`` from the window's open to its close.  12.5
under even routing over 256 experts with 32 held."""


def read(ctx):
    a, b = ctx.collected["info_open"], ctx.collected["info_close"]
    names = ("moe_pairs_held_total", "moe_pairs_routed_total")
    if any(n not in rec for n in names for rec in (a, b)):
        return None
    held, routed = (b[n] - a[n] for n in names)
    if routed <= 0:
        return None
    return 100.0 * held / routed
