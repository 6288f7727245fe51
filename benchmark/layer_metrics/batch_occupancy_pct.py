"""Mean share of the engine's lanes that were decoding: for every
stream the part of the window between its first token frame and its
end, summed, over the window and ``max_batch``. Taken at the client:
the engine's own ``step_log`` is not reachable through the handle."""


def read(c):
    live = 0.0
    for r in c["records"]:
        if r["t_tokens"]:
            a = max(r["t_tokens"][0], c["t_open"])
            b = min(r["t_end"], c["t_close"])
            live += max(0.0, b - a)
    return 100.0 * live / c["window_s"] / c["max_batch"]
