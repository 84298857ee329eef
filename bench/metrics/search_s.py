"""Seconds in the recipe search during set-up, compression of every
candidate included (host clock around ``policy.search``, ended by
blocking on the picked instance's arrays)."""


def read(ctx):
    return ctx.timers.get("search_s")
