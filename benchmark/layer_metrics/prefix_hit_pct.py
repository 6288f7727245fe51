"""Prompt tokens served from the prefix cache over prompt tokens looked
up, between the window's edges: ``engine_stats()["prefix"]``."""


def read(c):
    a, b = (s.get("prefix") for s in c["engine_stats"])
    if not a or not b:
        return None
    looked = b["lookup_tokens"] - a["lookup_tokens"]
    if looked <= 0:
        return None
    return 100.0 * (b["hit_tokens"] - a["hit_tokens"]) / looked
