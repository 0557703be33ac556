"""The ``traffic_ref_state_long`` driver: ``drivers/traffic_ref_state.py``'s
run as it stands, by import, for a mix whose every reply is longer than
the replay's cap, served by a model whose errors against the reference
are heavy-tailed and whose state runs over long replies.  Three
differences:

- After the window ``drivers/traffic.py`` replays on the idle server
  one request of each output budget up to ``REPLAY_MAX_NEW_TOKENS``
  (128), greedy and sampled, and asks that at least two came back token
  for token.  A reasoning mix (replies of 512 to 2 048 tokens) has no
  budget that short, so it would replay nothing and fail.  Here the cap
  is the mix's shortest budget: the replay takes the greedy and the
  sampled request of that budget.
- The first request the reference compares (the window's longest
  prompt) is asked for the mix's ``reference.long_new_tokens`` tokens,
  not ``new_tokens``: hundreds of decode rows of one request, so that
  a state that drifts over a long reply is held to the float32
  recurrence too.  A run whose long request came back shorter fails.
- One more limit of the configuration's ``correct`` block,
  ``logits_rel_err_floor_max``: the SMALLEST of the compared requests'
  median relative errors.  Where top-k routing sits before a recurrent
  state, a choice flipped on rounding at one position moves every later
  row of that request, so a request's median is no precision test; the
  error floor that every request without a flip reads is one.  A
  lower precision lifts the floor of every request; a flip lifts one.

Nothing else differs: the hands, the other requests' logits, the
reference child, the other two tolerances.
"""

from __future__ import annotations

from run import load_module

state = load_module("drivers", "traffic_ref_state")
_served_logits = state.traffic_ref.served_logits


def long_first(long_new: int, seen: dict):
    """``traffic_ref.served_logits`` with the first picked request
    asked for ``long_new`` tokens; the rows it brought back go into
    ``seen``."""
    async def served_logits(port, picked, new):
        out = await _served_logits(port, picked[:1], long_new)
        for _, status, resp in out:
            seen["rows"] = len(resp.get("new_tokens", [[]])[0]) \
                if status == 200 else 0
        return out + await _served_logits(port, picked[1:], new)
    return served_logits


def run(ctx) -> dict:
    mix = dict(ctx.mix, **(ctx.mix.get("rehearse", {}) if ctx.rehearse
                           else {}))
    traffic = state.traffic_ref.traffic
    traffic.REPLAY_MAX_NEW_TOKENS = max(
        traffic.REPLAY_MAX_NEW_TOKENS,
        min(int(b) for b in mix["max_new_tokens"]))
    long_new = int(mix["reference"].get("long_new_tokens", 0))
    seen = {}
    state.traffic_ref.served_logits = long_first(long_new, seen) \
        if long_new else _served_logits
    result = state.run(ctx)
    ref = result.get("collected", {}).get("reference") \
        if result.get("ok") else None
    if ref and long_new:
        ctx.checks.add(seen.get("rows") == long_new,
                       f"the long request brought {seen.get('rows')} "
                       f"rows of logits, not {long_new}")
    if ref and ref["request_medians"] and not ctx.rehearse:
        limit = ctx.config["correct"]["logits_rel_err_floor_max"]
        floor = min(ref["request_medians"])
        ctx.checks.add(floor <= limit,
                       f"the smallest request median of the logits' "
                       f"relative error {floor:.5f} over {limit}")
    return result
