"""Queries answered and not failed, in requests that completed inside
the window, over the window's length. A failed or 429 item is not
counted (it counts in `failed`)."""


def read(run, params):
    return run.answered / run.seconds
