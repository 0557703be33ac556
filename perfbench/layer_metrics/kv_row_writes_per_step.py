"""Row writes a decode step issued into the KV pool's position-keyed
leaves: growth of `/info` `kv_row_writes_total` over growth of
`decode_steps_total` from the window's open to its close.  A write is
one update of a leaf at a position; under the pool's vmap the chip runs
each as a loop of one dependent write a slot, so this count times the
slots is the serial chain a step pays.  A stack whose layers write
their own rows reads its layers x leaves; a carried stack that writes
after the layer loop reads its leaves (2 for K and V).  The writes are
counted when a dispatch is launched and the steps when it is committed:
with one dispatch in flight at either edge the quotient is off by at
most a dispatch's steps over the window's.  A program without the
counter reports nothing."""


def read(ctx):
    a, b = ctx.collected["info_open"], ctx.collected["info_close"]
    names = ("kv_row_writes_total", "decode_steps_total")
    if any(n not in rec for n in names for rec in (a, b)):
        return None
    writes, steps = (b[n] - a[n] for n in names)
    if steps <= 0:
        return None
    return writes / steps
