"""Mean of `/info` `slot_occupancy`, polled once a second in the
window."""

import stats


def read(ctx):
    values = [info["slot_occupancy"] for _, info in ctx.collected["polls"]
              if "slot_occupancy" in info]
    mean = stats.mean(values)
    return None if mean is None else 100.0 * mean
