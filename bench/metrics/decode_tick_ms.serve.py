"""Model step, decode (the engine's donated decode tick): mean device time
of its program executions, in ms."""


def read(r):
    pairs = r.matched("generate")
    if pairs is None:
        return None
    return 1e3 * sum(s for _, _, s in pairs) / len(pairs)
