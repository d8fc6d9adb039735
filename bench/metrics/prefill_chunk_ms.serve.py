"""Model step, prefill (``ModelApi.prefill_chunk`` as the engine compiled
it): mean device time of the programs that consumed a full chunk, in ms."""


def read(r):
    return r.full_chunk_ms()
