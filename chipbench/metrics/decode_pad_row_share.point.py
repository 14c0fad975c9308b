"""Percent of the rows the decode launches materialized over the window
that repeat a block of their launch (the pow2 padding of miss batches and
depth groups), from the decoder's counters in `cache_info()`
(`decoder_rows`, `decoder_pad_rows`); None for a program without them."""


def read(r):
    if "decoder_rows" not in r.cache_after:
        return None
    rows = r.cache_delta("decoder_rows")
    return 100.0 * r.cache_delta("decoder_pad_rows") / rows if rows else None
