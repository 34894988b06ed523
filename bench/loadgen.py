"""The one load generator: a closed loop over a fixed window.

A traffic mix is a data file, ``bench/traffic/<mix>.json``::

    {"loop": "closed", "clients": 1, "ops": {"commit": 1.0}, "warm_seconds": 3}

``clients`` loops (callers that wait, as a training job waits on its save)
each issue their next request when the last one returned, until the window's
end; the window closes when the last request issued in it returns, so a rate
over it counts whole requests over all their time.
"""

from __future__ import annotations

import asyncio
import dataclasses
import math
from typing import Awaitable, Callable, List, Optional, Tuple

OPS = ("commit",)
LOOPS = ("closed",)


@dataclasses.dataclass
class Request:
    """One request of a window and what became of it.  Times are seconds
    from the window's start."""

    op: str
    at: float                    # when it was issued
    done: float = math.nan
    vid: Optional[int] = None
    index: Optional[int] = None  # the harness's version index
    nbytes: int = 0
    error: Optional[str] = None


Issue = Callable[[Request], Awaitable[None]]


def check_mix(traffic: dict) -> None:
    """Refuse a mix this generator cannot send."""
    if traffic.get("loop") not in LOOPS:
        raise ValueError(f"unknown loop {traffic.get('loop')!r}; known: {LOOPS}")
    unknown = set(traffic["ops"]) - set(OPS)
    if unknown:
        raise ValueError(f"unknown ops {sorted(unknown)}; known: {OPS}")


async def run_closed(traffic: dict, seconds: float,
                     issue: Issue) -> Tuple[List[Request], float]:
    """``clients`` loops until ``seconds`` have passed; returns (requests,
    window length), the window closing at the last return."""
    loop = asyncio.get_running_loop()
    t0 = loop.time()
    done: List[Request] = []

    async def client() -> None:
        while loop.time() - t0 < seconds:
            req = Request(op="commit", at=loop.time() - t0)
            done.append(req)
            try:
                await issue(req)
            except Exception as exc:  # a failed request is counted, not fatal
                req.error = f"{type(exc).__name__}: {exc}"
            req.done = loop.time() - t0

    await asyncio.gather(*(client() for _ in range(int(traffic["clients"]))))
    return done, max([seconds] + [r.done for r in done])
