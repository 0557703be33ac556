"""Share of the full-length KV planes' rows that the attention was
handed, decode steps and prefill chunks of the window: growth of
`/info` `kv_plane_rows_read_total` over growth of
`kv_plane_rows_held_total` from the window's open to its close (the
program reads a plane as far as its furthest live position, in static
widths; 100 is every plane read whole).  A program without the counters
reports nothing."""


def read(ctx):
    a, b = ctx.collected["info_open"], ctx.collected["info_close"]
    names = ("kv_plane_rows_read_total", "kv_plane_rows_held_total")
    if any(n not in rec for n in names for rec in (a, b)):
        return None
    rows_read, rows_held = (b[n] - a[n] for n in names)
    if rows_held <= 0:
        return None
    return 100.0 * rows_read / rows_held
