"""The ``traffic_ref_state`` driver: ``drivers/traffic_ref.py``'s run as
it stands, by import, for a configuration without a window.

``traffic_ref`` asks that a compared request pass the configuration's
``sliding_window`` — for the model it was written for, the position
past which ring and plane differ.  A state-space model's catalog row
holds ``"sliding_window": null`` (kept in the file, letter for letter),
and what a compared request has to pass here is the PREFILL CHUNK: a
prompt longer than one piece is the case in which the state and the
convolution's tail are carried from piece to piece.  So the run is
handed the configuration with the chunk (``--prefill-chunk`` of its
``serve`` arguments) in that key's place, and nothing else differs:
the hands, the logits asked for again, the reference child, both
tolerances.
"""

from __future__ import annotations

from run import load_module

traffic_ref = load_module("drivers", "traffic_ref")


def prefill_chunk(args: list) -> int:
    return int(args[args.index("--prefill-chunk") + 1])


def run(ctx) -> dict:
    serve = ctx.config["serve"]
    ctx.config = dict(ctx.config, sliding_window=prefill_chunk(
        serve["rehearse_args"] if ctx.rehearse else serve["args"]))
    return traffic_ref.run(ctx)
