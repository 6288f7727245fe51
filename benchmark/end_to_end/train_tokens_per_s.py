"""Tokens consumed between the two fences of the window over its wall
time, per chip. A row counts its whole sequence length."""


def read(c):
    return c["tokens"] / c["window_s"] / c["chips"]
