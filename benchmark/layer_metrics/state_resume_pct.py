"""Share of the prompt tokens that the prefix index matched in the
window which a parked state let the engine SKIP: the window's growth of
``engine_stats()["state_resumed_tokens"]`` over that of resumed plus
``state_recomputed_tokens`` (matched in the paged pools, but with no
state snapshot at or below them to start from, so computed again).
100 where every shared prefix is taken up through its snapshot; a cell
that loses its snapshots (evicted, never taken) falls towards 0 and
prefills every prompt whole."""


def read(c):
    stats = c.get("engine_stats")
    if not stats or "state_resumed_tokens" not in stats[1]:
        return None
    grew = lambda key: stats[1][key] - stats[0].get(key, 0)
    resumed, again = grew("state_resumed_tokens"), \
        grew("state_recomputed_tokens")
    return 100.0 * resumed / (resumed + again) if resumed + again else None
