"""Traffic kind ``closed_loop``: a few callers, each waiting for its reply.

``clients`` connections; each sends its next request when the last one
has returned, so a slower system is offered less. Every request has
``queries_per_request`` queries. Requests cycle through a pool of
``payload_pool`` distinct payloads drawn from the seed (encoded before
the clock starts); every ``debug_every``-th payload asks for neighbours
and distances too, which the output check reads.
"""

from typing import Any, Dict


def plan(params: Dict[str, Any], seed: int, seconds: float
         ) -> Dict[str, Any]:
    pool = int(params["payload_pool"])
    every = int(params.get("debug_every", 0))
    return {
        "mode": "closed",
        "clients": int(params["clients"]),
        "sizes": [int(params["queries_per_request"])] * pool,
        "debug": [bool(every) and i % every == every - 1
                  for i in range(pool)],
        "due_s": None,
    }
