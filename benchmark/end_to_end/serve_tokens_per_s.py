"""Output tokens a second, counted at the client: token frames read
inside the window, over all streams that did not fail, over the
window's wall time. (Not the tokens of requests that completed inside
the window: a closed loop's long answers straddle both edges, and
counting whole requests moves the figure by one answer's length, some
percent, with the instant the window happens to close.)"""

from benchmark import clientstats
from benchmark.harness import log


def read(c):
    n = clientstats.window_tokens(c)
    log(f"serve_tokens_per_s: {n} token frames inside the window")
    return n / c["window_s"]
