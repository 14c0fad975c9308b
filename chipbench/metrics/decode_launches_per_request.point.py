"""Decode launches of the block cache's miss path over the window
(`cache_info()["decode_launches"]`), per request due in the window."""


def read(r):
    return r.cache_delta("decode_launches") / r.requests if r.requests else None
