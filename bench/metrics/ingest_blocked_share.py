"""Serve front end: share of the clients' time spent blocked on a full
write queue, in percent.

The growth of the engine's ``blocked_s`` over the window, over the
window's seconds times the client threads that submit.
"""


def read(r):
    c = r.counters
    if "blocked_s" not in c:
        return None
    return 100.0 * c["blocked_s"] / (c["client_threads"] * c["window_s"])
