"""The one general traffic generator: a mix file's parameters and a seed
in, requests out; and the asyncio client that offers them.

Steadiness comes from a fixed amount of work drawn from the seed: a mix
names a DECK of ``deck`` requests whose prompt lengths, output budgets
and sampling modes each follow the mix's weights exactly (largest
remainder).  Every ``--seed`` gets the same sizes, paired and ordered
its own way, and its own token ids.  The deck is dealt again and again,
paired and shuffled anew each time.

The client is one thread: an asyncio loop with a connection per request
(the server speaks HTTP/1.0 and closes after each response).
"""

from __future__ import annotations

import asyncio
import json
import random
import time
from typing import Dict, Iterator, List, Optional, Tuple


def _counts(weights: Dict[str, float], n: int) -> List[int]:
    """``n`` values, each key of ``weights`` as often as its weight
    says, by largest remainder (ties to the earlier key)."""
    total = sum(weights.values())
    keys = list(weights)
    exact = [weights[k] * n / total for k in keys]
    counts = [int(x) for x in exact]
    order = sorted(range(len(keys)), key=lambda i: (counts[i] - exact[i], i))
    for i in order[:n - sum(counts)]:
        counts[i] += 1
    return [int(k) for k, c in zip(keys, counts) for _ in range(c)]


def deck(mix: dict) -> Tuple[List[int], List[int], List[int]]:
    """The mix's fixed sizes, the same for every seed: ``deck`` prompt
    lengths, as many output budgets, as many sampling modes (1 sampled,
    0 greedy)."""
    n = int(mix["deck"])
    share = float(mix["sampled_share"])
    return (_counts(mix["prompt_tokens"], n),
            _counts(mix["max_new_tokens"], n),
            _counts({"1": share, "0": 1.0 - share}, n))


def shapes(mix: dict) -> List[dict]:
    """The warm-up set: every prompt length in each sampling mode, each
    with the mix's ``warmup_max_new_tokens``.  (The engine compiles by
    prompt length, by whether any resident samples, and by decode window
    8, 4, 2, 1.  Alone on the server, a budget of 16 walks through all
    four: one token at admission, then 15 = 8 + 4 + 2 + 1.  The output
    budget itself compiles nothing.)"""
    share = float(mix["sampled_share"])
    modes = [False, True] if 0 < share < 1 else [share >= 1]
    return [{"prompt_tokens": int(p), "sampled": sampled,
             "max_new_tokens": int(mix["warmup_max_new_tokens"])}
            for sampled in modes for p in sorted(mix["prompt_tokens"],
                                                 key=int)]


def make_request(shape: dict, mix: dict, vocab: int, rng: random.Random,
                 timings: bool) -> dict:
    """One ``POST /generate`` body of this shape, ids from ``rng``.  No
    ``eos_id``: every request runs its whole budget."""
    req = {"prompt": rng.choices(range(vocab), k=shape["prompt_tokens"]),
           "max_new_tokens": shape["max_new_tokens"]}
    if shape["sampled"]:
        req["temperature"] = float(mix["temperature"])
        req["seed"] = rng.randrange(2 ** 31)
    if timings:
        req["timings"] = True
    return req


def requests(mix: dict, seed: int, vocab: int,
             timings: bool = False) -> Iterator[dict]:
    """The seed's endless request stream: the deck, paired and shuffled
    anew per deal."""
    rng = random.Random(seed)
    prompts, budgets, modes = deck(mix)
    while True:
        for column in (prompts, budgets, modes):
            rng.shuffle(column)
        for p, m, s in zip(prompts, budgets, modes):
            yield make_request({"prompt_tokens": p, "max_new_tokens": m,
                                "sampled": bool(s)}, mix, vocab, rng,
                               timings)


# ---------------------------------------------------------------------------
# the client
# ---------------------------------------------------------------------------


async def call(host: str, port: int, method: str, path: str,
               body: Optional[bytes] = None) -> Tuple[int, bytes]:
    """One HTTP exchange on a connection of its own: ``(status, body)``;
    ``(0, b"")`` if the connection failed."""
    try:
        reader, writer = await asyncio.open_connection(host, port)
    except OSError:
        return 0, b""
    try:
        head = (f"{method} {path} HTTP/1.0\r\nHost: {host}\r\n"
                f"Content-Type: application/json\r\n"
                f"Content-Length: {len(body or b'')}\r\n\r\n")
        writer.write(head.encode() + (body or b""))
        await writer.drain()
        raw = await reader.read()
    except OSError:
        return 0, b""
    finally:
        writer.close()
    head, _, payload = raw.partition(b"\r\n\r\n")
    try:
        return int(head.split(None, 2)[1]), payload
    except (IndexError, ValueError):
        return 0, b""


async def call_json(host, port, method, path, body=None):
    status, payload = await call(
        host, port, method, path,
        None if body is None else json.dumps(body).encode())
    try:
        return status, json.loads(payload) if payload else {}
    except ValueError:
        return status, {}


class Record(dict):
    """One request as the client saw it: ``index``, ``request``,
    ``due``, ``sent``, ``done`` (host clock, seconds), ``status``,
    ``response``."""


def start_closed_loop(host: str, port: int, stream: Iterator[dict],
                      clients: int, records: List[Record],
                      stop: asyncio.Event) -> list:
    """``clients`` callers, each sending its next request the moment its
    last reply is whole (a request is due when its caller is free).
    Returns the callers' tasks; once ``stop`` is set a caller takes its
    reply and sends nothing more."""
    bodies = ((req, json.dumps(req).encode()) for req in stream)

    async def caller():
        while not stop.is_set():
            due = time.time()
            req, body = next(bodies)
            rec = Record(index=len(records), request=req, due=due)
            records.append(rec)
            rec["sent"] = time.time()
            status, payload = await call(host, port, "POST", "/generate",
                                         body)
            rec["done"] = time.time()
            rec["status"] = status
            try:
                rec["response"] = json.loads(payload) if payload else {}
            except ValueError:
                rec["response"] = {}
            if status == 0:             # nobody listens: do not spin
                await asyncio.sleep(0.05)

    return [asyncio.ensure_future(caller()) for _ in range(clients)]


async def send_all(host: str, port: int, reqs: List[dict]) -> List[Record]:
    """Send ``reqs`` at once, wait for all: the warm-up and the replay."""
    records: List[Record] = []

    async def one(req):
        rec = Record(index=len(records), request=req, due=time.time())
        records.append(rec)
        rec["sent"] = rec["due"]
        status, resp = await call_json(host, port, "POST", "/generate",
                                       req)
        rec.update(done=time.time(), status=status, response=resp)

    await asyncio.gather(*(one(r) for r in reqs))
    return records


def well_formed(rec: Record, vocab: int) -> bool:
    """Answered 200 with exactly ``max_new_tokens`` ids in [0, vocab)."""
    if rec.get("status") != 200:
        return False
    rows = rec.get("response", {}).get("new_tokens")
    if not isinstance(rows, list) or len(rows) != 1:
        return False
    new = rows[0]
    return (len(new) == rec["request"]["max_new_tokens"]
            and all(isinstance(t, int) and 0 <= t < vocab for t in new))


def tokens_in_window(rec: Record, t0: float, t1: float) -> float:
    """A whole reply's output tokens, spread evenly from when it was
    sent to when it was whole, that fall inside ``[t0, t1)``.  (The
    server does not stream: the client sees no token before the last.
    Counting a reply where it lands would move a window's rate by a
    whole reply at each edge.)"""
    new = rec["request"]["max_new_tokens"]
    span = rec["done"] - rec["sent"]
    inside = min(rec["done"], t1) - max(rec["sent"], t0)
    if inside <= 0:
        return 0.0
    return new * inside / span if span > 0 else float(new)
