"""What the serving readers share: samples taken from the client's
records of a window. A record is one request as its caller saw it:
``t_send``, ``t_tokens`` (the instant each token frame was read),
``t_end``, and ``failed``. Records of requests that failed inside the
window are kept apart (``failed_records``): a failed request counts as
missing, so it adds an infinite sample to every tail."""

from __future__ import annotations

import math


def in_window(c, t):
    return c["t_open"] <= t <= c["t_close"]


def window_tokens(c) -> int:
    """Token frames read inside the window, over all streams."""
    return sum(1 for r in c["records"] for t in r["t_tokens"]
               if in_window(c, t))


def ttft_ms(c) -> list:
    """Send -> first token frame, for first tokens inside the window;
    a request that failed inside the window is an infinite sample."""
    out = [(r["t_tokens"][0] - r["t_send"]) * 1e3 for r in c["records"]
           if r["t_tokens"] and in_window(c, r["t_tokens"][0])]
    return out + [math.inf] * len(c["failed_records"])


def gaps_ms(c) -> list:
    """Gaps between consecutive token frames of one stream, the later
    frame inside the window, all streams pooled."""
    out = []
    for r in c["records"]:
        ts = r["t_tokens"]
        out += [(b - a) * 1e3 for a, b in zip(ts, ts[1:])
                if in_window(c, b)]
    return out + [math.inf] * len(c["failed_records"])


def finite(x):
    return None if x is None or math.isinf(x) or math.isnan(x) else x
